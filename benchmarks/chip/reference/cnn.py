"""Plain reference of the paper's Section 4.2 CNN: its weights from a key,
and its loss.  Imports nothing of the program.

conv 3x3 (32) -> relu -> 2x2 max pool -> conv 3x3 (32) -> relu -> 2x2 max
pool -> dense 64 -> relu -> dense 32 -> relu -> dense 10 -> softmax cross
entropy.  'SAME' padding, NHWC images.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def init_params(key, config: dict, dtype=jnp.float32):
    """He-normal weights, zero biases, in the layout the program takes."""
    ch, k = config["conv_channels"], config["conv_kernel"]
    h, w, c_in = config["image_shape"]
    f1, f2, f3 = config["fc_widths"]
    flat = (h // 4) * (w // 4) * ch
    shapes = {
        "conv1_w": ((k, k, c_in, ch), k * k * c_in), "conv1_b": ((ch,), 0),
        "conv2_w": ((k, k, ch, ch), k * k * ch), "conv2_b": ((ch,), 0),
        "fc1_w": ((flat, f1), flat), "fc1_b": ((f1,), 0),
        "fc2_w": ((f1, f2), f1), "fc2_b": ((f2,), 0),
        "fc3_w": ((f2, f3), f2), "fc3_b": ((f3,), 0),
    }
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(sorted(shapes.items())):
        if fan_in == 0:
            out[name] = jnp.zeros(shape, dtype)
        else:
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (z * np.sqrt(2.0 / fan_in)).astype(dtype)
    return out


def _relu(y):
    # the usual subgradient, 0 at 0: jnp.maximum(y, 0) would pass half of
    # the gradient at 0, where blank image regions and zero biases put many
    # activations exactly
    return jnp.where(y > 0, y, 0)


def _conv_relu_pool(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    y = _relu(y)
    n, hh, ww, c = y.shape
    return y.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))


def loss(params, batch, config: dict):
    """Mean softmax cross entropy of one batch ``{"x", "y"}``."""
    dt = params["fc1_w"].dtype
    x = batch["x"].astype(dt)
    x = _conv_relu_pool(x, params["conv1_w"], params["conv1_b"])
    x = _conv_relu_pool(x, params["conv2_w"], params["conv2_b"])
    x = x.reshape(x.shape[0], -1)
    x = _relu(x @ params["fc1_w"] + params["fc1_b"])
    x = _relu(x @ params["fc2_w"] + params["fc2_b"])
    logits = x @ params["fc3_w"] + params["fc3_b"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=1))


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward and backward FLOPs one image requires (2 per multiply-add):
    each convolution and dense layer once forward, once for its weights'
    gradient and once for its input's gradient, which the first
    convolution does not need.  Biases, ReLU and pooling are not counted."""
    ch, k = config["conv_channels"], config["conv_kernel"]
    h, w, c_in = config["image_shape"]
    f1, f2, f3 = config["fc_widths"]
    conv1 = 2 * h * w * ch * k * k * c_in
    conv2 = 2 * (h // 2) * (w // 2) * ch * k * k * ch
    dense = 2 * ((h // 4) * (w // 4) * ch * f1 + f1 * f2 + f2 * f3)
    forward = conv1 + conv2 + dense
    return float(3 * forward - conv1)
