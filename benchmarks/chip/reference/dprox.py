"""Plain reference of Algorithm 1 (Zhang, Hu & Johansson 2025), client by
client, with its state on the host.  Imports nothing of the program.

One round, from the server state ``x_bar`` and the clients' corrections
``c_i``:

  p         = prox_{eta eta_g tau}(x_bar)
  client i: z_hat_0 = z_0 = p;  for t < tau:
              g_t     = grad f_i(z_t; batch_t)
              z_hat   = z_hat - eta (g_t + c_i)
              z       = prox_{(t+1) eta}(z_hat)
            sends m_i = z_hat_tau - p, keeps a_i = mean_t g_t
  uplink:   global top-k with error feedback, when the cell asks for it:
            u_i = e_i + m_i; the k = round(ratio d) largest |u_i| pass;
            e_i = u_i - sent_i
  server:   x_bar' = p + eta_g mean_i m_i
            c_i'   = (p - x_bar') / (eta_g eta tau) - a_i

with prox the soft threshold of lam ||.||_1.

Where the state lives: ``x_bar``, each ``c_i`` and ``e_i``, and a round's
``p``, ``z_hat``, gradient sum and messages are NumPy arrays in host
memory.  Only the current client's ``z`` and that step's batch go to the
device, for one jitted ``value_and_grad`` of the loss (``device_grad``);
its gradient comes back.  So the device holds one model copy, one
gradient and one step's activations, whatever the model's size.  The
elementwise steps run on the host, in the order above, over blocks of each
leaf spread across threads; each leaf of the next ``z`` goes to the device
as soon as it is made, while the next gradient leaf comes back.  In a
``dtype`` other than float32 (the control) each step computes in float32
and rounds to ``dtype`` where it stores state.  The host rounds as XLA's
fused loops do on the TPU: each multiply and add apart, and a division by
a constant as a multiplication by its float32 reciprocal.
"""
from __future__ import annotations

import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

#: elements of one host block: few enough calls that the threads seldom
#: wait for each other on the interpreter, a scratch small enough to cache
BLOCK = 1 << 20
F32 = np.dtype(np.float32)


def leaf_norms(tree) -> list:
    """L2 norm of each leaf of a device pytree, in ``tree_leaves`` order,
    summed in float32 on the device (the program's readings)."""
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32)))) for l in jax.tree_util.tree_leaves(t)])
    return [float(v) for v in jax.device_get(fn(tree))]


def diff_norms(a, b) -> list:
    fn = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])
    return [float(v) for v in jax.device_get(fn(a, b))]


def device_grad(loss_fn):
    """The one program the reference runs on the device:
    ``(params, batch) -> (loss, gradient)``."""
    return jax.jit(jax.value_and_grad(loss_fn))


class Host:
    """Elementwise work on host arrays, block by block over a thread pool.

    ``map(fn, *arrays)`` calls ``fn(*blocks)`` on aligned blocks of the
    flattened arrays (``None`` passes through) and returns the results in
    block order.  The arrays have to be C-contiguous: a block of any other
    is a copy, and what ``fn`` wrote there would be lost.  ``f32(x, i)`` is block ``x`` as float32: the block
    itself when it is float32, else a copy in the thread's scratch ``i``.
    """

    def __init__(self):
        self.threads = len(os.sched_getaffinity(0))
        self.pool = None
        self.local = threading.local()

    def __enter__(self):
        if self.threads > 1:
            self.pool = ThreadPoolExecutor(self.threads)
        return self

    def __exit__(self, *exc):
        if self.pool is not None:
            self.pool.shutdown(wait=True)
            self.pool = None

    def map(self, fn, *arrays) -> list:
        for a in arrays:
            if a is not None and not a.flags.c_contiguous:
                raise ValueError(f"a host array of shape {a.shape} and "
                                 f"strides {a.strides} is not C-contiguous")
        flat = [None if a is None else a.reshape(-1) for a in arrays]
        size = flat[0].size

        def block(s):
            return fn(*(None if f is None else f[s: s + BLOCK] for f in flat))

        starts = range(0, size, BLOCK)
        if self.pool is None or size <= BLOCK:
            return [block(s) for s in starts]
        return list(self.pool.map(block, starts))

    def scratch(self, i: int, n: int):
        bufs = getattr(self.local, "bufs", None)
        if bufs is None:
            bufs = self.local.bufs = {}
        if i not in bufs:
            bufs[i] = np.empty(BLOCK, F32)
        return bufs[i][:n]

    def f32(self, x, i: int):
        if x.dtype == F32:
            return x
        out = self.scratch(i, x.size)
        np.copyto(out, x, casting="unsafe")
        return out

    def target(self, x, i: int):
        """Where to compute a block that is stored into ``x``."""
        return x if x.dtype == F32 else self.scratch(i, x.size)

    @staticmethod
    def store(x, y) -> None:
        if y is not x:
            np.copyto(x, y, casting="unsafe")


def _soft(x, thr: float, out, tmp) -> None:
    """out = sign(x) max(|x| - thr, 0), in float32 blocks."""
    np.abs(x, out=tmp)
    np.subtract(tmp, np.float32(thr), out=tmp)
    np.maximum(tmp, 0, out=tmp)
    np.sign(x, out=out)
    np.multiply(out, tmp, out=out)


def _rounded(x, dtype):
    """A float32 block rounded to ``dtype`` in place."""
    if dtype != F32:
        np.copyto(x, x.astype(dtype), casting="unsafe")
    return x


def _prox(h: Host, x, thr: float, out) -> None:
    def fn(xb, ob):
        o = h.target(ob, 1)
        _soft(h.f32(xb, 0), thr, o, h.scratch(2, xb.size))
        h.store(ob, o)

    h.map(fn, x, out)


def _local_step(h: Host, z_hat, g, c_i, gsum, z, eta: float,
                thr: float) -> None:
    """z_hat -= eta (g + c_i); gsum += g; z = prox(z_hat) (``z`` None: the
    client's last step, whose z nothing reads)."""

    def fn(zh, gb, cb, gs, zb):
        g32, t = h.f32(gb, 0), h.scratch(1, zh.size)
        np.add(g32, h.f32(cb, 2), out=t)
        np.multiply(t, np.float32(eta), out=t)
        zh32 = h.f32(zh, 3)
        np.subtract(zh32, t, out=zh32)
        h.store(zh, zh32)
        gs32 = h.f32(gs, 4)
        np.add(gs32, g32, out=gs32)
        h.store(gs, gs32)
        if zb is not None:
            o = h.target(zb, 5)
            _soft(h.f32(zh, 3), thr, o, t)
            h.store(zb, o)

    h.map(fn, z_hat, g, c_i, gsum, z)


def _finish_client(h: Host, z_hat, p, gsum, c_i, tau: int) -> None:
    """The message z_hat - p into ``z_hat``, the mean gradient gsum / tau
    into ``c_i`` (the round reads c_i no more)."""

    def fn(zh, pb, gs, cb):
        zh32 = h.f32(zh, 0)
        np.subtract(zh32, h.f32(pb, 1), out=zh32)
        h.store(zh, zh32)
        o = h.target(cb, 2)
        np.multiply(h.f32(gs, 3), np.float32(1 / tau), out=o)
        h.store(cb, o)

    h.map(fn, z_hat, p, gsum, c_i)


def _accumulate(h: Host, acc, x) -> None:
    """acc += x, with ``acc`` float32."""
    h.map(lambda a, xb: np.add(a, h.f32(xb, 0), out=a), acc, x)


def _server(h: Host, x_bar, p, msum, c, n: int, eta_g: float, scale: float,
            dtype) -> None:
    """x_bar = p + eta_g mean_i m_i; c_i = scale (p - x_bar) - a_i, with
    ``c`` holding the a_i and ``msum`` the sum of the messages."""

    def fn(xo, pb, mb, *cs):
        p32, xn = h.f32(pb, 0), h.scratch(1, pb.size)
        np.multiply(mb, np.float32(1 / n), out=xn)
        _rounded(xn, dtype)
        np.multiply(xn, np.float32(eta_g), out=xn)
        np.add(p32, xn, out=xn)
        _rounded(xn, dtype)
        h.store(xo, xn)
        d = h.scratch(2, pb.size)
        np.subtract(p32, xn, out=d)
        np.multiply(d, np.float32(scale), out=d)
        for cb in cs:
            c32 = h.f32(cb, 3)
            np.subtract(d, c32, out=c32)
            h.store(cb, c32)

    h.map(fn, x_bar, p, msum, *[c[i] for i in range(n)])


def _sq_norm(h: Host, a, b=None) -> float:
    """||a - b||^2 (``b`` None: ||a||^2) of float32 values, summed in
    float64."""

    def fn(ab, bb):
        x = ab.astype(np.float64)
        if bb is not None:
            x = (ab.astype(F32) - bb.astype(F32)).astype(np.float64)
        return float(np.dot(x, x))

    return math.fsum(h.map(fn, a, b))


def _topk(h: Host, msg: list, e_i, ratio: float, ef: bool) -> list:
    """The message leaves that pass client i's global top-k, and its error
    feedback ``e_i`` updated in place."""
    flat = np.concatenate([m.reshape(-1).astype(F32) for m in msg])
    d = flat.size
    k = max(1, min(d, int(round(ratio * d))))
    target = (e_i.astype(F32) + flat).astype(e_i.dtype).astype(F32)
    mag = np.abs(target)
    kth = np.partition(mag, d - k)[d - k]
    sent = np.where(mag >= kth, target, 0).astype(e_i.dtype)
    if ef:
        np.copyto(e_i, target - sent.astype(F32), casting="unsafe")
    out, off = [], 0
    for m in msg:
        out.append(sent[off: off + m.size].reshape(m.shape))
        off += m.size
    return out


def _fetch(leaves) -> None:
    """Starts each device leaf's copy to the host."""
    for x in leaves:
        x.copy_to_host_async()


def _batch(b: dict, i: int, t: int, dtype, half: bool) -> dict:
    out = {}
    for k, v in b.items():
        x = np.asarray(v)[i, t]
        if np.issubdtype(x.dtype, np.floating):
            x = x.astype(dtype)
        out[k] = x[: x.shape[0] // 2] if half else x
    return out


def run(loss_fn, params0, training: dict, n_clients: int, rounds: list,
        chunk_rounds: int, *, uplink=None, dtype=jnp.float32,
        half_batch=False, nudge=False) -> dict:
    """Three steps of ``chunk_rounds`` rounds each over the recorded
    ``rounds`` (host batch pytrees, leaves ``(n_clients, tau, b, ...)``),
    from ``params0`` (host or device arrays) cast to ``dtype``
    (``nudge``: each weight moved by one unit in the last place).
    ``half_batch`` leaves out the second half of every batch: a fault the
    comparison has to catch, planted here in place of the program.

    Returns what the comparison reads: every round's loss, the per-leaf
    norms of the corrections after step 1, of the change of ``x_bar``
    after step 3, and of the first round's mean gradient."""
    lam, eta, eta_g, tau = (training["lam"], training["eta"],
                            training["eta_g"], int(training["tau"]))
    dtype, n = np.dtype(dtype), n_clients
    leaves, treedef = jax.tree_util.tree_flatten(params0)
    # C-contiguous: an array read back from the TPU can keep the device's
    # order of axes in its strides
    x0 = [np.ascontiguousarray(l, dtype) for l in leaves]
    del leaves
    x_bar = [np.nextafter(x, np.inf).astype(dtype) if nudge else x.copy()
             for x in x0]
    c = [np.zeros((n,) + x.shape, dtype) for x in x0]
    e = (np.zeros((n, sum(x.size for x in x0)), dtype)
         if uplink is not None else None)
    # z is made in one buffer: its copy to the device has ended when the
    # gradient at it has come back, before the buffer is written again
    p, z_hat, gsum, zbuf = ([np.empty_like(x) for x in x0] for _ in range(4))
    msum = [np.empty(x.shape, F32) for x in x0]
    vg = device_grad(loss_fn)
    out = {"losses": []}
    with Host() as h:
        for r, b in enumerate(rounds[: 3 * chunk_rounds]):
            for xb, pb in zip(x_bar, p):
                _prox(h, xb, eta * eta_g * tau * lam, pb)
            for m in msum:
                m.fill(0)
            lsums = []
            for i in range(n):
                c_i = [cl[i] for cl in c]
                for zh, gs, pb in zip(z_hat, gsum, p):
                    np.copyto(zh, pb)
                    gs.fill(0)
                z_dev = [jax.device_put(pb) for pb in p]
                lsum = np.float32(0)
                for t in range(tau):
                    loss, g_dev = vg(jax.tree_util.tree_unflatten(treedef,
                                                                  z_dev),
                                     _batch(b, i, t, dtype, half_batch))
                    del z_dev
                    g_dev = jax.tree_util.tree_leaves(g_dev)
                    _fetch(g_dev)
                    last = t == tau - 1
                    thr = float(np.float32(t + 1) * np.float32(eta)
                                * np.float32(lam))
                    z_dev = []
                    for j in range(len(x0)):
                        _local_step(h, z_hat[j],
                                    np.ascontiguousarray(g_dev[j]),
                                    c_i[j], gsum[j],
                                    None if last else zbuf[j], eta, thr)
                        g_dev[j] = None
                        if not last:
                            z_dev.append(jax.device_put(zbuf[j]))
                    lsum = np.float32(lsum + np.float32(jax.device_get(loss)))
                lsums.append(lsum)
                for j in range(len(x0)):
                    _finish_client(h, z_hat[j], p[j], gsum[j], c_i[j], tau)
                msg = z_hat
                if uplink is not None:
                    msg = _topk(h, z_hat, e[i], uplink["ratio"],
                                uplink.get("error_feedback", True))
                for m, mb in zip(msum, msg):
                    _accumulate(h, m, mb)
            if r == 0:  # the clients' mean gradient, which c holds now
                out["grad_norms"] = []
                for cl in c:
                    acc = np.zeros(cl.shape[1:], F32)
                    for i in range(n):
                        _accumulate(h, acc, cl[i])
                    mean = _rounded(acc * np.float32(1 / n), dtype)
                    out["grad_norms"].append(math.sqrt(_sq_norm(h, mean)))
            for j in range(len(x0)):
                _server(h, x_bar[j], p[j], msum[j], c[j], n, eta_g,
                        1.0 / (eta_g * eta * tau), dtype)
            out["losses"].append(float(
                np.sum(np.asarray(lsums, F32), dtype=F32) * np.float32(1 / n)
                * np.float32(1 / tau)))
            if r == chunk_rounds - 1:
                out["c_norms"] = [math.sqrt(_sq_norm(h, cl)) for cl in c]
        out["change_norms"] = [math.sqrt(_sq_norm(h, a, b))
                               for a, b in zip(x_bar, x0)]
    return out
