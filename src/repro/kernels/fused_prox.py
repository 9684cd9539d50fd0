"""Pallas TPU kernel: fused local-update + L1 proximal step.

This is the paper's hot inner loop (Algorithm 1 Lines 9-10).  At production
scale the federated state tensors are billions of elements and the naive
implementation issues four separate HBM-bound elementwise passes
(grad+c add, axpy, abs/compare, sign*max).  Fusing them into one kernel reads
each of (z_hat, grads, c) exactly once from HBM and writes (z_hat', z') once:
a 2.3x traffic reduction on the dominant memory term of the update.

TPU mapping: the arrays are reshaped to (rows, 128) lanes; each grid step
processes a (BLOCK_ROWS, 128) tile resident in VMEM (3 in + 2 out tiles =
~640 KB at fp32, comfortably inside the ~16 MB VMEM budget, leaving room for
double buffering).  eta/thresh are runtime scalars (thresh depends on the
local-step index t) and ride in SMEM via scalar prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256  # (256, 128) tile: 128 KB fp32 per operand


def _kernel(scalars_ref, z_hat_ref, grads_ref, c_ref, z_hat_out_ref, z_out_ref):
    eta = scalars_ref[0]
    thresh = scalars_ref[1]
    zh = z_hat_ref[...]
    g = grads_ref[...]
    c = c_ref[...]
    dtype = zh.dtype
    zh32 = zh.astype(jnp.float32)
    upd = zh32 - eta * (g.astype(jnp.float32) + c.astype(jnp.float32))
    z_hat_out_ref[...] = upd.astype(dtype)
    mag = jnp.maximum(jnp.abs(upd) - thresh, 0.0)
    z_out_ref[...] = (jnp.sign(upd) * mag).astype(dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def fused_local_update_2d(z_hat, grads, c, eta, thresh, *, interpret=False,
                          block_rows=BLOCK_ROWS):
    """Core call on (R, 128) arrays with R % block_rows == 0."""
    rows = z_hat.shape[0]
    assert z_hat.shape[1] == LANES and rows % block_rows == 0, z_hat.shape
    scalars = jnp.stack([jnp.asarray(eta, jnp.float32),
                         jnp.asarray(thresh, jnp.float32)])
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct(z_hat.shape, z_hat.dtype),
        jax.ShapeDtypeStruct(z_hat.shape, z_hat.dtype),
    ]
    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[scalar_spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=out_shape,
        interpret=interpret,
        name="fused_prox_update",
    )(scalars, z_hat, grads, c)
