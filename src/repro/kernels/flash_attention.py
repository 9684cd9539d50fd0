"""Pallas TPU kernel: causal flash attention with online softmax.

This is the compute/memory hot spot of the 32k-prefill shape: the jnp
reference materializes the (S, S) logits in fp32 (32k x 32k x 4 B = 4 GB per
head), which is the dominant term of the prefill memory roofline.  The flash
kernel streams KV blocks through VMEM and keeps only a (BQ, BK) tile plus the
running (m, l, acc) statistics -- O(S) memory instead of O(S^2), and MXU-
aligned (BQ, BK, D multiples of 128) matmuls.

Supports causal masking, sliding windows (gemma2/mistral local layers) and
tanh logit softcapping (gemma2, grok).  GQA is handled by the ops wrapper.

Grid: (B, H, S // BQ); each program owns one query block and loops over the
kv blocks its mask admits.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, causal, window, cap, scale):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (BQ, D)
    s_total = k_ref.shape[2]
    n_kv = s_total // bk

    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk)].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0, pl.ds(j * bk, bk)].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (BQ, BK)
        if cap is not None:
            logits = cap * jnp.tanh(logits / cap)
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    if causal:
        # only kv blocks at or before this q block are touched
        n_iter = jnp.minimum((qi + 1) * bq // bk + (1 if bq % bk else 0), n_kv)
        n_iter = jnp.maximum(n_iter, 1)
    else:
        n_iter = n_kv
    acc0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_iter, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=False):
    """q,k,v: (B, H, S, D) with S % bq == 0 == S % bk.  Returns (B, H, S, D)."""
    b, h, s, d = q.shape
    assert k.shape == v.shape == (b, h, s, d), (q.shape, k.shape)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    scale = 1.0 / math.sqrt(d)
    kern = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                             window=window, cap=softcap, scale=scale)
    grid = (b, h, s // bq)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
