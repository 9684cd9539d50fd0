"""Pallas TPU kernels over the flat parameter plane.

Every kernel here operates on the ``(clients, d_pad)`` layout of
:mod:`repro.core.plane` -- one contiguous lane-padded buffer per client --
so the communication and aggregation hot paths run as single tiled passes
instead of one small op per pytree leaf:

  * :func:`kth_magnitude_3d` -- the search half of **global** top-k
    sparsification: each client's k-th largest magnitude, found exactly by
    bisecting on its float32 bit pattern with the client's whole row
    resident in VMEM (no sort, no indices).
  * :func:`threshold_select_3d` -- the select+scatter half: given the
    per-client k-th magnitude, zero everything below it in one fused pass.
    Reads x once, writes the sparsified plane once.
  * :func:`quantize_3d` -- fused stochastic uniform quantization
    (scale, level, stochastic round, dequantize in one pass).  Uniform
    draws are an input, so the kernel is deterministic given them and
    validates bit-for-bit in interpret mode against
    :func:`repro.kernels.ref.plane_quantize`.
  * :func:`weighted_commit_3d` -- the staleness-weighted buffered commit:
    ``sum_i w_i * buf_i`` over the client axis of a ``(clients, d_pad)``
    report buffer in one pass (the reduction
    :mod:`repro.sched.aggregator`'s commit step performs per leaf today).

TPU mapping: planes are reshaped to ``(clients, rows, 128)`` lanes; each
grid step processes one client's ``(block_rows, 128)`` tile resident in
VMEM (the commit kernel processes all clients of one tile column, since it
reduces over them, so its block height shrinks as the client count grows:
see :func:`commit_block_rows`; the k-th magnitude kernel holds one
client's whole row, so its block is never ragged and no padding row
enters a count).  ``rows`` need not divide by the block height: the grid
is ``cdiv(rows, block_rows)`` and the last block is ragged (its
out-of-range rows are never written).  Per-client scalars (ranks,
thresholds, quantization scales, commit weights) ride in SMEM.  Public
entry points with automatic interpret-mode selection live in
:mod:`repro.kernels.ops`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_prox import BLOCK_ROWS, LANES

#: VMEM bytes one commit input block may take; Pallas double-buffers it,
#: so the kernel's footprint stays well inside v5e's 16 MiB scoped default
COMMIT_VMEM_BYTES = 4 << 20
#: VMEM bytes of one client's row that :func:`kth_magnitude_3d` holds
#: whole: double-buffered, with the loop's temporaries beside it, it stays
#: inside the same 16 MiB default
KTH_VMEM_BYTES = 2 << 20
#: float32 bits below the sign: ``bits & ABS_BITS`` is ``|x|`` as int32
ABS_BITS = 0x7FFFFFFF


def kth_fits(d_pad: int, dtype) -> bool:
    """Whether :func:`kth_magnitude_3d` takes a client's plane row of
    ``d_pad`` elements: float32, whole in VMEM."""
    return jnp.dtype(dtype) == jnp.float32 and d_pad * 4 <= KTH_VMEM_BYTES


def _kth_kernel(k_ref, x_ref, out_ref):
    i = pl.program_id(0)  # client
    k = k_ref[i]

    def step(j, t):
        # the largest t with count(|x| bits >= t) >= k, one bit at a time;
        # for non-negative float32 the int32 order is the value order, and
        # NaN bits rank above inf, as lax.top_k ranks them
        cand = t | jax.lax.shift_left(jnp.int32(1),
                                      (30 - j).astype(jnp.int32))
        bits = jax.lax.bitcast_convert_type(x_ref[0], jnp.int32) & ABS_BITS
        n_ge = jnp.sum(bits >= cand, dtype=jnp.int32)
        return jnp.where(n_ge >= k, cand, t)

    out_ref[i] = jax.lax.fori_loop(0, 31, step, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def kth_magnitude_3d(x, k, *, interpret=False):
    """Core call on ``x``: (n, R, 128) float32; ``k``: (n,) int32 per-client
    ranks (1 <= k <= R * 128) -> (n,) float32 k-th largest ``|x|`` per
    client, bitwise ``lax.top_k(|x|, k)[0][:, -1]``.  One grid step per
    client, whose block is its whole row."""
    n, rows, lanes = x.shape
    assert lanes == LANES and x.dtype == jnp.float32, (x.shape, x.dtype)
    bits = pl.pallas_call(
        _kth_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
        name="topk-kth",
    )(k.astype(jnp.int32), x)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _threshold_kernel(thresh_ref, x_ref, out_ref):
    i = pl.program_id(0)  # client
    t = thresh_ref[i]
    x = x_ref[...]
    out_ref[...] = jnp.where(jnp.abs(x) >= t.astype(x.dtype), x,
                             jnp.zeros((), x.dtype))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def threshold_select_3d(x, thresh, *, interpret=False,
                        block_rows=BLOCK_ROWS):
    """Core call on ``x``: (n, R, 128); ``thresh``: (n,) f32 per-client
    magnitude thresholds."""
    n, rows, lanes = x.shape
    assert lanes == LANES, x.shape
    block_rows = min(block_rows, rows)
    grid = (n, pl.cdiv(rows, block_rows))
    spec = pl.BlockSpec((1, block_rows, LANES), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        _threshold_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="threshold_select",
    )(thresh.astype(jnp.float32), x)


def _quantize_kernel(scale_ref, x_ref, u_ref, out_ref, *, levels):
    i = pl.program_id(0)
    s = scale_ref[i]
    s = jnp.where(s == 0, jnp.float32(1.0), s)
    x = x_ref[...]
    dtype = x.dtype
    y = x.astype(jnp.float32) / s * levels
    lo = jnp.floor(y)
    q = lo + (u_ref[...].astype(jnp.float32) < (y - lo)).astype(jnp.float32)
    out_ref[...] = (q / levels * s).astype(dtype)


@functools.partial(jax.jit, static_argnames=("levels", "interpret",
                                             "block_rows"))
def quantize_3d(x, u, scale, levels: int, *, interpret=False,
                block_rows=BLOCK_ROWS):
    """Core call on ``x``/``u``: (n, R, 128); ``scale``: (n,) per-client max
    magnitudes; ``levels``: static quantization level count."""
    n, rows, lanes = x.shape
    assert lanes == LANES, x.shape
    block_rows = min(block_rows, rows)
    grid = (n, pl.cdiv(rows, block_rows))
    spec = pl.BlockSpec((1, block_rows, LANES), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        functools.partial(_quantize_kernel, levels=levels),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="quantize_plane",
    )(scale.astype(jnp.float32), x, u)


def _commit_kernel(w_ref, buf_ref, out_ref, *, n_clients):
    acc = jnp.zeros(buf_ref.shape[1:], jnp.float32)
    # n_clients is static: the loop unrolls, each step one VPU axpy from the
    # VMEM-resident tile column (per-client weights live in SMEM)
    for i in range(n_clients):
        acc = acc + w_ref[i] * buf_ref[i].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def commit_block_rows(n_clients: int, rows: int, itemsize: int,
                      block_rows: int) -> int:
    """Block height of :func:`weighted_commit_3d` for ``n_clients``.

    One block holds the tile column of every client, so its height is
    what keeps ``n_clients * height * 128 * itemsize`` within
    :data:`COMMIT_VMEM_BYTES`: a multiple of 8 (the sublane tiling), capped
    by ``block_rows`` (callers' explicit tiling) and by ``rows``.
    """
    fit = max(8, COMMIT_VMEM_BYTES // (n_clients * LANES * itemsize) // 8 * 8)
    return min(block_rows, fit, rows)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def weighted_commit_3d(buf, w, *, interpret=False, block_rows=None):
    """Core call on ``buf``: (n, R, 128), ``w``: (n,) -> (R, 128) weighted
    sum over clients (one tile column of all clients resident per step).
    ``block_rows`` caps the height :func:`commit_block_rows` picks."""
    n, rows, lanes = buf.shape
    assert lanes == LANES, buf.shape
    block_rows = commit_block_rows(
        n, rows, buf.dtype.itemsize,
        BLOCK_ROWS if block_rows is None else block_rows)
    grid = (pl.cdiv(rows, block_rows),)
    in_spec = pl.BlockSpec((n, block_rows, LANES), lambda j: (0, j, 0))
    out_spec = pl.BlockSpec((block_rows, LANES), lambda j: (j, 0))
    return pl.pallas_call(
        functools.partial(_commit_kernel, n_clients=n),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), buf.dtype),
        interpret=interpret,
        name="weighted_commit",
    )(w.astype(jnp.float32), buf)
