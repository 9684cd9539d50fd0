"""Plain reference of the decoder the ``transformer`` configurations name:
its weights from a key, and its next-token loss.  Imports nothing of the
program.

Per layer: x += attention(rms_norm(x)); x += swiglu_mlp(rms_norm(x)).
Attention is causal multi-head with rotary position embedding (rotate-half
form, frequencies theta^(-i / (head_dim / 2))) over every dimension of a
head and one S x S softmax per head; the output head is the input embedding
(tied), the loss the mean cross entropy of each next token, in float32.
These follow the program's ArchConfig, which departs from the published
model as the configuration's ``departures`` list.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(config: dict) -> dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    return {"d": d, "h": h, "kv": config["num_key_value_heads"],
            "hd": d // h, "f": config["intermediate_size"],
            "v": config["vocab_size"], "layers": config["num_hidden_layers"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["layer_norm_eps"])}


def init_params(key, config: dict, dtype=jnp.float32):
    """Weights in the layout the program takes: ``embed``, ``final_norm``
    and ``stack/b0/...`` with a leading layer axis."""
    m = dims(config)
    d, h, kv, hd, f, n = m["d"], m["h"], m["kv"], m["hd"], m["f"], m["layers"]
    dense = {
        ("mixer", "wq"): (n, d, h, hd), ("mixer", "wk"): (n, d, kv, hd),
        ("mixer", "wv"): (n, d, kv, hd), ("mixer", "wo"): (n, h, hd, d),
        ("mlp", "w_gate"): (n, d, f), ("mlp", "w_up"): (n, d, f),
        ("mlp", "w_down"): (n, f, d),
    }
    block = {"mixer": {}, "mlp": {},
             "norm1": jnp.ones((n, d), dtype), "norm2": jnp.ones((n, d), dtype)}
    for i, ((grp, name), shape) in enumerate(sorted(dense.items())):
        fan_in = math.prod(shape[1:-1])
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        block[grp][name] = (z / math.sqrt(fan_in)).astype(dtype)
    embed = jax.random.normal(jax.random.fold_in(key, len(dense)), (m["v"], d),
                              jnp.float32)
    return {"embed": (0.02 * embed).astype(dtype),
            "final_norm": jnp.ones((d,), dtype),
            "stack": {"b0": block}}


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, theta):
    """x: (B, S, H, hd)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _layer(x, p, m):
    bsz, s, _ = x.shape
    h = _rms_norm(x, p["norm1"], m["eps"])
    q = _rotary(jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wq"]), m["theta"])
    k = _rotary(jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wk"]), m["theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wv"])
    rep = m["h"] // m["kv"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(m["hd"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    att = jnp.einsum("bhst,bthk->bshk", probs, v)
    x = x + jnp.einsum("bshk,hkd->bsd", att, p["mixer"]["wo"])
    h = _rms_norm(x, p["norm2"], m["eps"])
    gate = jnp.einsum("bsd,df->bsf", h, p["mlp"]["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, p["mlp"]["w_up"])
    act = gate * jax.nn.sigmoid(gate) * up
    return x + jnp.einsum("bsf,fd->bsd", act, p["mlp"]["w_down"])


def loss(params, batch, config: dict):
    """Mean next-token cross entropy of ``batch["tokens"]`` (B, S)."""
    m = dims(config)
    tokens = batch["tokens"]
    x = params["embed"][tokens]
    layer = jax.checkpoint(lambda x, p: _layer(x, p, m))
    for i in range(m["layers"]):
        x = layer(x, jax.tree_util.tree_map(lambda a: a[i],
                                            params["stack"]["b0"]))
    x = _rms_norm(x, params["final_norm"], m["eps"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]).astype(jnp.float32)
    logits = logits[:, :-1]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward and backward FLOPs one sequence of the traffic's length
    requires (2 per multiply-add, backward twice forward): every projection,
    the MLP and the tied output head at each position, and the two S x S
    products of attention as computed (in full, causal mask applied after).
    Recomputation under remat is not counted."""
    m = dims(config)
    s = int(traffic["data"]["seq_len"])
    d, hd = m["d"], m["hd"]
    per_layer = d * hd * (2 * m["h"] + 2 * m["kv"]) + 3 * d * m["f"]
    per_token = 2 * (m["layers"] * per_layer + d * m["v"])
    attention = m["layers"] * 2 * 2 * s * m["h"] * hd
    return float(3 * s * (per_token + attention))
