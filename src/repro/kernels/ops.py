"""jit'd public wrappers around the Pallas kernels.

These adapt parameter pytrees / flat planes / GQA head layouts to the
kernels' tiled layouts.  ``interpret=None`` compiles the kernel on a TPU
backend and interprets it anywhere else, so the same call sites work on
the CPU (tests) and the TPU (production); a run that must not fall back
to the CPU checks the platform itself (``chip_smoke.py``).

Since the flat-plane refactor the pytree entry points flatten the whole
tree onto ONE contiguous lane-padded buffer (:mod:`repro.core.plane`) and
make a single kernel call over it, instead of padding and launching per
leaf: the kernels see one tiled layout, and tiny leaves (biases, norms)
stop costing a full tile each.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import plane as pln
from repro.kernels import fused_prox, plane_ops, flash_attention as fa

LANES = fused_prox.LANES


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _as_tiles(flat_plane):
    """(\\*batch, d_pad) plane -> (\\*batch, rows, LANES) tiles."""
    d_pad = flat_plane.shape[-1]
    assert d_pad % LANES == 0, d_pad
    return flat_plane.reshape(flat_plane.shape[:-1] + (d_pad // LANES, LANES))


def fused_local_update(z_hat, grads, c, eta, thresh, *, interpret=None,
                       block_rows=fused_prox.BLOCK_ROWS):
    """Fused Algorithm-1 local update + L1 prox over a whole pytree.

    Flattens (z_hat, grads, c) onto one contiguous plane (padded once to
    the kernel tiling) and makes a single fused kernel call -- the
    historical per-leaf pad/launch loop is gone.  Mixed-dtype trees cannot
    share a plane and take a per-leaf fallback.  Returns
    (z_hat_next, z_next) with the same structure/shapes/dtypes.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    try:
        spec = pln.SegmentSpec.from_tree(z_hat, tile=block_rows * LANES)
    except ValueError:  # mixed dtypes: no shared plane
        return _fused_local_update_per_leaf(z_hat, grads, c, eta, thresh,
                                            interpret=interpret,
                                            block_rows=block_rows)
    dt = spec.dtype
    zf = pln.flatten(spec, z_hat).reshape(-1, LANES)
    gf = pln.flatten(spec, jax.tree_util.tree_map(
        lambda g: jnp.asarray(g).astype(dt), grads)).reshape(-1, LANES)
    cf = pln.flatten(spec, jax.tree_util.tree_map(
        lambda ci: jnp.asarray(ci).astype(dt), c)).reshape(-1, LANES)
    zh2, z2 = fused_prox.fused_local_update_2d(
        zf, gf, cf, eta, thresh, interpret=interpret, block_rows=block_rows)
    return (pln.unflatten(spec, zh2.reshape(-1)),
            pln.unflatten(spec, z2.reshape(-1)))


def _fused_local_update_per_leaf(z_hat, grads, c, eta, thresh, *, interpret,
                                 block_rows):
    leaves_zh, treedef = jax.tree_util.tree_flatten(z_hat)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_c = treedef.flatten_up_to(c)
    out_zh, out_z = [], []
    for zh, g, ci in zip(leaves_zh, leaves_g, leaves_c):
        spec = pln.SegmentSpec.from_tree(zh, tile=block_rows * LANES)
        flat = pln.flatten(spec, zh).reshape(-1, LANES)
        gflat = pln.flatten(spec, g.astype(zh.dtype)).reshape(-1, LANES)
        cflat = pln.flatten(spec, ci.astype(zh.dtype)).reshape(-1, LANES)
        zh2, z2 = fused_prox.fused_local_update_2d(
            flat, gflat, cflat, eta, thresh,
            interpret=interpret, block_rows=block_rows)
        out_zh.append(pln.unflatten(spec, zh2.reshape(-1)))
        out_z.append(pln.unflatten(spec, z2.reshape(-1)))
    return (jax.tree_util.tree_unflatten(treedef, out_zh),
            jax.tree_util.tree_unflatten(treedef, out_z))


def fused_local_update_step(reg, eta, t, z_hat, grads, c):
    """Drop-in for repro.core.algorithm.local_update_step when reg is L1."""
    from repro.core.prox import L1

    assert isinstance(reg, L1), "fused kernel path requires the L1 regularizer"
    thresh = (t + 1) * eta * reg.lam
    return fused_local_update(z_hat, grads, c, eta, thresh)


# ---------------------------------------------------------------------------
# flat-plane communication / aggregation kernels
# ---------------------------------------------------------------------------


def plane_kth_magnitude(flat_plane, k, *, interpret=None):
    """Per-client k-th largest magnitude of a float32 (clients, d_pad)
    plane, bitwise ``lax.top_k(|x|, k)[0][:, -1]``, found by bisection on
    the bit patterns with each client's row in VMEM (rows must pass
    :func:`plane_ops.kth_fits`).  ``k``: an int or (clients,) ranks."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), flat_plane.shape[:1])
    return plane_ops.kth_magnitude_3d(_as_tiles(flat_plane), k,
                                      interpret=interpret)


def plane_threshold_select(flat_plane, thresh, *, interpret=None,
                           block_rows=fused_prox.BLOCK_ROWS):
    """Global top-k select on a (clients, d_pad) plane: keep coordinates
    whose magnitude reaches the per-client ``thresh``, zero the rest (one
    fused pass; the k-th values come from :func:`plane_kth_magnitude` on
    the chip, from one ``lax.top_k`` elsewhere).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    out = plane_ops.threshold_select_3d(_as_tiles(flat_plane), thresh,
                                        interpret=interpret,
                                        block_rows=block_rows)
    return out.reshape(flat_plane.shape)


def plane_quantize(flat_plane, u, scale, levels: int, *, interpret=None,
                   block_rows=fused_prox.BLOCK_ROWS):
    """Fused stochastic uniform quantization on a (clients, d_pad) plane
    given uniform draws ``u`` and per-client ``scale`` magnitudes."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    out = plane_ops.quantize_3d(_as_tiles(flat_plane), _as_tiles(u), scale,
                                levels, interpret=interpret,
                                block_rows=block_rows)
    return out.reshape(flat_plane.shape)


def plane_weighted_commit(buf, w, *, interpret=None, block_rows=None):
    """Staleness-weighted commit reduction ``sum_i w_i * buf_i`` over the
    client axis of a (clients, d_pad) report-buffer plane, in one pass."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    out = plane_ops.weighted_commit_3d(_as_tiles(buf), w,
                                       interpret=interpret,
                                       block_rows=block_rows)
    return out.reshape(buf.shape[-1:])


def gqa_flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                        interpret=None, bq=None, bk=None):
    """Flash attention for (B, S, H, D) activations with K kv heads.

    Repeats kv heads to match q heads (GQA), transposes to the kernel's
    (B, H, S, D) layout, and picks block sizes that divide S.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, s, h, d = q.shape
    kh = k.shape[2]
    rep = h // kh
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = bq or min(fa.DEFAULT_BQ, s)
    bk = bk or min(fa.DEFAULT_BK, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    out = fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                             softcap=softcap, bq=bq, bk=bk,
                             interpret=interpret)
    return out.transpose(0, 2, 1, 3)
