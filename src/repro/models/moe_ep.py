"""Expert-parallel MoE dispatch with EXPLICIT all-to-all (shard_map).

The GSPMD-partitioned scatter/gather dispatch in ``repro.models.layers.moe``
is correct but lets the compiler pick the communication pattern, and the
deepseek-v3 roofline showed it falling into replicate-then-repartition
("involuntary full rematerialization") -- the dominant collective term of
that pair (EXPERIMENTS.md section Perf 3).  This module implements the
communication schedule a MoE system actually wants, by hand:

  tokens sharded over the mesh axis, experts sharded over the same axis;
  each shard routes its local tokens, packs per-destination-shard send
  buffers, ``lax.all_to_all``s activations to the experts' owners, computes
  the local experts, and all-to-alls the results back.  Total traffic per
  token: 2 x d (one round trip), the textbook expert-parallel schedule --
  no full-activation replication possible by construction.

Inside shard_map all scatters are SHARD-LOCAL, so GSPMD never sees them.

``moe_expert_parallel_sharded`` is the op; tests/test_moe_ep.py checks it
against the dense reference on 8 forced-host devices.  Constraints:
E % n_shards == 0 and T % n_shards == 0 (the production mesh satisfies both
for deepseek: 256 experts / 16, tokens / 16).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import MoECfg, gelu_mul, swiglu


def _local_moe_shard(x_loc, router, w_gate, w_up, w_down, *, cfg: MoECfg,
                     act: str, axis: str, n_shards: int, token_axes):
    """Body run per shard under shard_map.

    x_loc: (T_loc, d) local tokens; router: (d, E) replicated;
    w_*: (E_loc, ...) local expert weights.
    """
    T_loc, d = x_loc.shape
    E = router.shape[-1]
    E_loc = E // n_shards
    K = cfg.top_k
    # per-(source, expert) capacity: expected T_loc*K/E, padded
    C = max(int(T_loc * K / E * cfg.capacity_factor), 1)

    logits = jnp.einsum("td,de->te", x_loc, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)  # (T_loc, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    flat_e = expert_idx.reshape(-1)  # (T_loc*K,)
    dest = flat_e // E_loc  # destination shard
    e_local = flat_e % E_loc  # expert index on that shard

    # slot within the per-(dest, expert) send buffer: buffers are organized
    # by EXPERT so the receiver can run direct batched expert matmuls
    onehot_e = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    slot = jnp.sum(jnp.cumsum(onehot_e, axis=0) * onehot_e, -1) - 1
    keep = slot < C
    slot_c = jnp.where(keep, slot, 0)

    tok = jnp.repeat(jnp.arange(T_loc), K)
    send_x = jnp.zeros((n_shards, E_loc, C, d), x_loc.dtype)
    send_x = send_x.at[dest, e_local, slot_c].add(
        jnp.where(keep[:, None], x_loc[tok], 0).astype(x_loc.dtype))

    # ---- the explicit all-to-all round trip -------------------------------
    recv = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=True)
    # recv: (n_src, E_loc, C, d) -> (E_loc, n_src*C, d)
    xe = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * C, d)

    # ---- local expert compute: direct batched matmuls ---------------------
    actfn = swiglu if act == "swiglu" else gelu_mul
    h = actfn(jnp.einsum("esd,edf->esf", xe, w_gate),
              jnp.einsum("esd,edf->esf", xe, w_up))
    out_e = jnp.einsum("esf,efd->esd", h, w_down)

    # ---- return trip ------------------------------------------------------
    out_back = out_e.reshape(E_loc, n_shards, C, d).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(out_back, axis, 0, 0, tiled=True)

    # combine at source
    gathered = back[dest, e_local, slot_c]
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = gate_vals.reshape(-1)[:, None].astype(gathered.dtype)
    out = jnp.zeros((T_loc, d), gathered.dtype).at[tok].add(gathered * w)

    frac = jnp.mean(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32).sum(1),
                    axis=0) / K
    imp = jnp.mean(probs, axis=0)
    # aux needs the global mean over every axis that shards tokens
    frac = jax.lax.pmean(frac, token_axes)
    imp = jax.lax.pmean(imp, token_axes)
    aux = E * jnp.sum(frac * imp)
    return out.astype(x_loc.dtype), aux


def moe_expert_parallel(p, cfg: MoECfg, x, mesh, *, act: str = "swiglu",
                        axis: str = "model", token_axes=None):
    """x: (T, d) tokens sharded over ``token_axes`` (default: just ``axis``;
    pass ("data", "model") to also batch-parallelize over 'data'); expert
    weights in ``p`` sharded over their leading expert dim on ``axis``;
    router replicated.  The all-to-all runs within each ``axis`` group.
    Returns (out (T, d), aux scalar).  Shared experts (deepseek) are NOT
    handled here -- callers add them as a dense MLP outside."""
    n_shards = mesh.shape[axis]
    E = cfg.num_experts
    assert E % n_shards == 0, (E, n_shards)
    token_axes = (axis,) if token_axes is None else tuple(token_axes)
    body = functools.partial(_local_moe_shard, cfg=cfg, act=act, axis=axis,
                             n_shards=n_shards, token_axes=token_axes)
    tok_spec = P(token_axes if len(token_axes) > 1 else token_axes[0], None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
