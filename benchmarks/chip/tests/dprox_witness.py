"""The plain reference of Algorithm 1 as it stood before its state moved to
the host: the whole round one jitted function on the device, the clients
one after another under ``lax.map``.  Kept for the tests as the witness
that the host formulation (``reference/dprox.py``) computes the same
readings."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _prox(tree, t, lam):
    return jax.tree_util.tree_map(
        lambda x: jnp.sign(x) * jnp.maximum(jnp.abs(x) - t * lam, 0), tree)


def leaf_norms(tree) -> list:
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32)))) for l in jax.tree_util.tree_leaves(t)])
    return [float(v) for v in jax.device_get(fn(tree))]


def diff_norms(a, b) -> list:
    fn = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])
    return [float(v) for v in jax.device_get(fn(a, b))]


def make_round(loss_fn, training: dict, n_clients: int, *, uplink=None,
               half_batch: bool = False):
    """``round(x_bar, c, e, batches) -> (x_bar', c', e', loss, mean_grad)``.

    ``half_batch`` leaves out the second half of every batch: a fault the
    comparison has to catch, planted here in place of the program.
    """
    lam, eta, eta_g, tau = (training["lam"], training["eta"],
                            training["eta_g"], training["tau"])
    vg = jax.value_and_grad(loss_fn)

    def client(p, c_i, b_i):
        def step(carry, t):
            z_hat, z, gsum, lsum = carry
            bt = jax.tree_util.tree_map(lambda x: x[t], b_i)
            if half_batch:
                bt = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], bt)
            l, g = vg(z, bt)
            z_hat = jax.tree_util.tree_map(
                lambda zh, gg, cc: zh - eta * (gg + cc), z_hat, g, c_i)
            z = _prox(z_hat, (t + 1) * eta, lam)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
            return (z_hat, z, gsum, lsum + l.astype(jnp.float32)), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
        (z_hat, _, gsum, lsum), _ = jax.lax.scan(
            step, (p, p, zeros, jnp.float32(0)), jnp.arange(tau))
        msg = jax.tree_util.tree_map(jnp.subtract, z_hat, p)
        return msg, jax.tree_util.tree_map(lambda g: g / tau, gsum), lsum

    def compress(msgs, e):
        leaves, treedef = jax.tree_util.tree_flatten(msgs)
        sizes = [int(np.prod(l.shape[1:])) for l in leaves]
        flat = jnp.concatenate([l.reshape(n_clients, -1) for l in leaves], 1)
        d = flat.shape[1]
        k = max(1, min(d, int(round(uplink["ratio"] * d))))
        target = e + flat
        kth = jax.lax.top_k(jnp.abs(target), k)[0][:, -1]
        sent = jnp.where(jnp.abs(target) >= kth[:, None], target, 0)
        e = target - sent if uplink.get("error_feedback", True) else e
        out, off = [], 0
        for l, s in zip(leaves, sizes):
            out.append(sent[:, off: off + s].reshape(l.shape))
            off += s
        return jax.tree_util.tree_unflatten(treedef, out), e

    def round_fn(x_bar, c, e, batches):
        p = _prox(x_bar, eta * eta_g * tau, lam)
        msgs, avg_g, lsums = jax.lax.map(lambda a: client(p, *a),
                                         (c, batches))
        if uplink is not None:
            msgs, e = compress(msgs, e)
        mean = jax.tree_util.tree_map(lambda m: jnp.mean(m, 0), msgs)
        x_next = jax.tree_util.tree_map(lambda pp, mm: pp + eta_g * mm, p, mean)
        scale = 1.0 / (eta_g * eta * tau)
        c_next = jax.tree_util.tree_map(
            lambda pp, xn, ag: scale * (pp - xn)[None] - ag, p, x_next, avg_g)
        mean_grad = jax.tree_util.tree_map(lambda g: jnp.mean(g, 0), avg_g)
        return x_next, c_next, e, jnp.mean(lsums) / tau, mean_grad

    donate = () if jax.default_backend() == "cpu" else (0, 1, 2)
    return jax.jit(round_fn, donate_argnums=donate)


def run(loss_fn, params0, training: dict, n_clients: int, rounds: list,
        chunk_rounds: int, *, uplink=None, dtype=jnp.float32,
        half_batch=False, nudge=False) -> dict:
    """Three steps of ``chunk_rounds`` rounds each over the recorded
    ``rounds`` (host batch pytrees, leaves ``(n_clients, tau, b, ...)``),
    from ``params0`` cast to ``dtype`` (``nudge``: each weight moved by
    one unit in the last place).  Returns what the comparison reads:
    every round's loss, the per-leaf norms of the corrections after step 1,
    of the change of ``x_bar`` after step 3, and of the first round's mean
    gradient."""
    round_fn = make_round(loss_fn, training, n_clients, uplink=uplink,
                          half_batch=half_batch)
    x0 = jax.tree_util.tree_map(lambda x: jnp.array(x, dtype=dtype, copy=True),
                                params0)
    x_bar = jax.tree_util.tree_map(
        lambda x: (jnp.nextafter(x, jnp.inf) if nudge
                   else jnp.array(x, copy=True)), x0)
    c = jax.tree_util.tree_map(
        lambda x: jnp.zeros((n_clients,) + x.shape, dtype), x0)
    d = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(x0))
    e = jnp.zeros((n_clients, d if uplink is not None else 0), dtype)

    def cast(b):
        return {k: (v.astype(dtype) if np.issubdtype(v.dtype, np.floating)
                    else v) for k, v in b.items()}

    out = {"losses": []}
    for r, b in enumerate(rounds[: 3 * chunk_rounds]):
        x_bar, c, e, loss, mean_grad = round_fn(x_bar, c, e, cast(b))
        out["losses"].append(float(loss))
        if r == 0:
            out["grad_norms"] = leaf_norms(mean_grad)
        if r == chunk_rounds - 1:
            out["c_norms"] = leaf_norms(c)
    out["change_norms"] = diff_norms(x_bar, x0)
    return out
