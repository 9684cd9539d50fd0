"""Pytree arithmetic helpers.

The federated algorithms in :mod:`repro.core` operate on arbitrary model
parameter pytrees (dicts of arrays, stacked scan layers, ...).  These helpers
provide the small vector-space algebra those algorithms need, written once so
every algorithm treats pytrees uniformly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Params = object  # any pytree of arrays


def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(a, s):
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_axpy(alpha, x, y):
    """alpha * x + y."""
    return jax.tree_util.tree_map(lambda u, v: alpha * u + v, x, y)


def tree_lincomb(coeffs, trees):
    """sum_i coeffs[i] * trees[i]."""
    out = tree_scale(trees[0], coeffs[0])
    for c, t in zip(coeffs[1:], trees[1:]):
        out = tree_axpy(c, t, out)
    return out


def tree_zeros_like(a):
    return jax.tree_util.tree_map(jnp.zeros_like, a)


def tree_dot(a, b):
    leaves = jax.tree_util.tree_map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)), a, b
    )
    return jax.tree_util.tree_reduce(jnp.add, leaves, jnp.float32(0.0))


def tree_sqnorm(a):
    return tree_dot(a, a)


def tree_norm(a):
    return jnp.sqrt(tree_sqnorm(a))


def tree_l1(a):
    leaves = jax.tree_util.tree_map(
        lambda x: jnp.sum(jnp.abs(x.astype(jnp.float32))), a
    )
    return jax.tree_util.tree_reduce(jnp.add, leaves, jnp.float32(0.0))


def tree_size(a):
    leaves = jax.tree_util.tree_leaves(a)
    return sum(int(x.size) for x in leaves)


def tree_mean_over_axis0(a):
    """Average a stacked-client pytree over the leading (client) axis."""
    return jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), a)


def tree_broadcast_axis0(a, n: int):
    """Replicate a pytree along a new leading (client) axis of size ``n``."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), a
    )


def tree_index_axis0(a, i):
    return jax.tree_util.tree_map(lambda x: x[i], a)


def tree_stack_axis0(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_isfinite(a) -> jax.Array:
    leaves = jax.tree_util.tree_map(lambda x: jnp.all(jnp.isfinite(x)), a)
    return jax.tree_util.tree_reduce(jnp.logical_and, leaves, jnp.bool_(True))


def tree_cast(a, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, a
    )


def tree_copy_shared(a, b):
    """``a`` with a fresh copy of every leaf that is also a leaf of ``b``
    (the same array object), so that donating ``b``'s buffers leaves ``a``
    intact, and the other way round."""
    shared = {id(leaf) for leaf in jax.tree_util.tree_leaves(b)}
    return jax.tree_util.tree_map(
        lambda leaf: leaf.copy() if id(leaf) in shared else leaf, a)
