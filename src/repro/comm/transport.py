"""Uplink compressors behind one ``Transport`` interface.

A *transport* decides what actually crosses the network when a client sends
its per-round uplink message (the pytree produced by an algorithm's
``make_local_fn``; every leaf carries a leading client axis).  Messages are
*innovations* -- deltas relative to the broadcast reference -- so zeroing or
coarsening their coordinates degrades gracefully instead of truncating the
model itself.  The round math never sees the transport: the engine
compresses the message between the local-compute half and the
server-aggregate half of a round whenever the UplinkComm stage is active
(``EngineConfig(transport=...)``; it composes with the placement and
asynchrony stages).

Implemented transports:

  * :class:`Dense`    -- identity (the paper's full d-dim vector per round);
  * :class:`TopK`     -- magnitude top-k sparsification per client (a biased
    *contraction*:  ||C(x) - x||^2 <= (1 - k/d) ||x||^2);
  * :class:`RandK`    -- uniform random-k sparsification with the d/k
    rescaling that makes it *unbiased*:  E[C(x)] = x;
  * :class:`Quantize` -- per-client stochastic uniform quantization to
    ``2^bits - 1`` levels (unbiased given the per-leaf scale).

All compressing transports carry **error-feedback** state (Qiu et al.,
Compressed Proximal Federated Learning; Seide et al. 2014): the residual
``e`` of what compression dropped is added back before the next compression,

    m_hat_t = C(e_t + m_t),    e_{t+1} = e_t + m_t - m_hat_t,

so the telescoping identity  sum_t m_hat_t = sum_t m_t - e_T  holds exactly
and the long-run average uplink is undistorted.  ``tests/test_comm.py`` pins
these contracts.

Compression **granularity** (the flat-plane refactor): historically every
transport compressed per client and per message leaf (leaves flattened to
``(n_clients, d_leaf)``), which is statistically weaker -- top-k selects k
coordinates *per leaf* instead of the k globally largest -- and pays
per-leaf byte overhead (one index set / one quantizer scale per leaf).  The
paper's object is the single d-dimensional vector, so the sparsifying /
quantizing transports now take ``granularity="leaf" | "global"``:

  * ``"leaf"`` (default) -- the historical per-leaf semantics, bitwise
    unchanged (existing parity tests pin it);
  * ``"global"`` -- the client's whole message is flattened onto one
    contiguous plane (:mod:`repro.core.plane`) and compressed as a single
    d-vector: top-k selects the k globally largest magnitudes, rand-k draws
    one index set, quantization uses ONE scale per client, and
    ``uplink_bytes`` accounts index/scale overhead once instead of per
    leaf.  On TPU the select/quantize passes run as fused Pallas kernels
    over the plane (:mod:`repro.kernels.plane_ops`).

Every transport also exposes the plane-side surface the engine's flat
carry uses (``EngineConfig(plane=True)``): ``apply_plane`` /
``compress_plane`` operate directly on ``(n_clients, d_pad)`` buffers with
a *flat* error-feedback state, via :class:`PlaneTransport`.  For
leaf-granularity transports the plane path routes through cheap
pytree views, so it is bitwise the per-leaf path.

``uplink_bytes`` reports the per-client wire cost of one message -- values
plus indices for sparsifiers, packed levels plus scale(s) for the quantizer
-- which benchmarks/comm_table.py uses instead of hand-maintained
constants.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import plane as pln
from repro.utils import tree as tu

Message = Any  # pytree whose leaves have a leading client axis

GRANULARITIES = ("leaf", "global")


def _k_of(ratio: float, d: int) -> int:
    """Coordinates kept per client for one flattened leaf of size d."""
    return max(1, min(d, int(round(ratio * d))))


def _leaf_elements(leaf) -> int:
    """Elements per client: the leaf's size without its client axis."""
    shape = tuple(leaf.shape)
    n = 1
    for s in shape[1:]:
        n *= s
    return n


def message_elements_per_client(msg_template) -> int:
    """Uplink coordinates per client per round (sums over message leaves)."""
    return sum(_leaf_elements(l) for l in jax.tree_util.tree_leaves(msg_template))


def _global_dims(msg_template) -> tuple[int, int]:
    """(total d per client, itemsize) of a message compressed globally.

    Global granularity compresses one contiguous plane, so the message must
    be single-dtype (the same constraint :class:`repro.core.plane.SegmentSpec`
    enforces); raises otherwise.
    """
    leaves = jax.tree_util.tree_leaves(msg_template)
    dtypes = {jnp.dtype(l.dtype) for l in leaves}
    if len(dtypes) != 1:
        raise ValueError(
            "granularity='global' compresses one contiguous plane and "
            f"needs a single-dtype message; got {sorted(d.name for d in dtypes)}")
    return (sum(_leaf_elements(l) for l in leaves),
            dtypes.pop().itemsize)


def _check_granularity(granularity: str) -> None:
    if granularity not in GRANULARITIES:
        raise ValueError(f"granularity must be one of {GRANULARITIES}, got "
                         f"{granularity!r}")


class Transport:
    """Interface: ``init_state`` -> per-run compressor state (error-feedback
    residuals, or an empty pytree), ``compress`` -> (what the server receives,
    next compressor state).  ``key`` is a jax PRNG key; deterministic
    transports ignore it (``stochastic = False`` lets the engine skip the
    per-round key split, which is measurable on µs-scale rounds)."""

    name: str = "base"
    error_feedback: bool = False
    stochastic: bool = False
    # natural wire re-encoding of this transport's output
    # (see repro.comm.wire.pack_plane): "dense" | "sparse" | "palette"
    wire_encoding: str = "dense"

    def init_state(self, msg_template):
        if not self.error_feedback:
            return ()
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros(tuple(l.shape), l.dtype), msg_template)

    granularity: str = "leaf"

    def compress(self, comm_state, msg: Message, key) -> tuple[Message, Any]:
        target = tu.tree_add(comm_state, msg) if self.error_feedback else msg
        msg_hat = self.apply(target, key)
        new_state = (tu.tree_sub(target, msg_hat)
                     if self.error_feedback else ())
        return msg_hat, new_state

    def apply(self, msg: Message, key) -> Message:
        if self.granularity == "global":
            spec = pln.SegmentSpec.from_tree(msg, batch_dims=1)
            return pln.unflatten(
                spec, self.apply_flat(pln.flatten(spec, msg), key, spec))
        return self.apply_leaf(msg, key)

    def apply_leaf(self, msg: Message, key) -> Message:
        """The historical per-(client, leaf) compression."""
        raise NotImplementedError

    def apply_flat(self, flat, key, spec: "pln.SegmentSpec"):
        """Global compression of the (n_clients, d_pad) plane (valid region
        ``spec.d``; the zero padding must stay zero)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no global-granularity form")

    # -- the flat-plane surface (EngineConfig(plane=True)) -----------------

    def apply_plane(self, flat, key, spec: "pln.SegmentSpec"):
        """``apply`` on a (n_clients, d_pad) plane.  Global granularity
        runs directly on the plane (one fused pass); leaf granularity
        routes through pytree views, so it is bitwise the per-leaf path."""
        if self.granularity == "global":
            return self.apply_flat(flat, key, spec)
        return pln.flatten(spec, self.apply_leaf(pln.unflatten(spec, flat),
                                                 key))

    def compress_plane(self, comm_state, flat, key,
                       spec: "pln.SegmentSpec"):
        """``compress`` with a flat (n_clients, d_pad) error-feedback
        buffer -- ONE residual for the whole message instead of one per
        leaf.  Elementwise-identical (bitwise) to :meth:`compress` on the
        pytree view."""
        target = comm_state + flat if self.error_feedback else flat
        hat = self.apply_plane(target, key, spec)
        new_state = (target - hat) if self.error_feedback else comm_state
        return hat, new_state

    def select_clients(self, mask, new_state, old_state):
        """The generalized partial-participation guard: advance the
        compressor state only for the clients in ``mask``.

        Error feedback must not advance for a client that did not actually
        transmit this round (partial participation, async non-refresh,
        cohort non-membership) -- otherwise the telescoping identity
        ``sum m_hat = sum m - e_T`` breaks.  Rows are keyed by position on
        the client axis, so the same guard works whether that axis indexes
        global client ids (dense engine) or cohort slots backed by the
        global-id-keyed population store (:mod:`repro.sched.cohort`
        scatters the rows home under their global ids at chunk
        boundaries).  State-free transports pass through untouched."""
        if not self.error_feedback:
            return new_state
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new_state, old_state)

    def uplink_bytes(self, msg_template) -> int:
        """Bytes on the wire per client per round for this message."""
        raise NotImplementedError


@dataclass(frozen=True)
class Dense(Transport):
    """Identity transport: the full message is sent (ratio 1.0)."""

    name: str = "dense"
    error_feedback: bool = False

    def apply(self, msg, key):
        return msg

    def apply_plane(self, flat, key, spec):
        return flat

    def uplink_bytes(self, msg_template):
        return sum(_leaf_elements(l) * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree_util.tree_leaves(msg_template))


@dataclass(frozen=True)
class TopK(Transport):
    """Keep the ``ratio`` fraction of largest-magnitude coordinates per
    client -- per leaf (``granularity="leaf"``, the historical default) or
    over the client's whole flattened message (``granularity="global"``,
    the paper's d-vector semantics: the k *globally* largest coordinates
    survive, and the index bytes are accounted once).  Biased but a
    contraction; error feedback recovers the dropped mass over rounds.
    ``ratio=1.0`` is exactly the identity in both granularities."""

    ratio: float = 0.1
    error_feedback: bool = True
    granularity: str = "leaf"
    name: str = "topk"
    wire_encoding: str = "sparse"

    def __post_init__(self):
        _check_granularity(self.granularity)

    def apply_leaf(self, msg, key):
        def one(x):
            flat = x.reshape(x.shape[0], -1)
            d = flat.shape[1]
            k = _k_of(self.ratio, d)
            if k >= d:
                return x
            mag = jnp.abs(flat)
            kth = jax.lax.top_k(mag, k)[0][:, -1:]
            return jnp.where(mag >= kth, flat, 0).reshape(x.shape)

        return jax.tree_util.tree_map(one, msg)

    def apply_flat(self, flat, key, spec):
        k = _k_of(self.ratio, spec.d)
        if k >= spec.d:
            return flat
        from repro.kernels import ops as kops
        from repro.kernels import plane_ops

        on_tpu = kops._on_tpu()
        mag = jnp.abs(flat)
        # the k-th magnitude over the padded plane equals the k-th over the
        # valid region (padding is zero and k <= d), so no masking is needed
        # and selected padding zeros stay zero
        if on_tpu and plane_ops.kth_fits(flat.shape[1], flat.dtype):
            # bisection on the bit patterns, each row in VMEM: no sort
            kth = kops.plane_kth_magnitude(flat, k)
        else:
            kth = jax.lax.top_k(mag, k)[0][:, -1]
        if on_tpu:
            # fused select+scatter pass over the tiled plane
            return kops.plane_threshold_select(flat, kth)
        return jnp.where(mag >= kth[:, None], flat, 0)

    def uplink_bytes(self, msg_template):
        if self.granularity == "global":
            d, itemsize = _global_dims(msg_template)
            return _k_of(self.ratio, d) * (itemsize + 4)  # value + int32 idx
        total = 0
        for l in jax.tree_util.tree_leaves(msg_template):
            d = _leaf_elements(l)
            k = _k_of(self.ratio, d)
            total += k * (jnp.dtype(l.dtype).itemsize + 4)  # value + int32 idx
        return total


@dataclass(frozen=True)
class RandK(Transport):
    """Keep ``ratio * d`` uniformly random coordinates per client per leaf,
    rescaled by d/k so the compressor is unbiased: E_key[C(x)] = x."""

    ratio: float = 0.1
    error_feedback: bool = True
    rescale: bool = True
    granularity: str = "leaf"
    name: str = "randk"
    stochastic: bool = True
    wire_encoding: str = "sparse"

    def __post_init__(self):
        _check_granularity(self.granularity)

    def apply_leaf(self, msg, key):
        leaves, treedef = jax.tree_util.tree_flatten(msg)
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [self._one(x, k) for x, k in zip(leaves, keys)])

    def apply_flat(self, flat, key, spec):
        n = flat.shape[0]
        k = _k_of(self.ratio, spec.d)
        if k >= spec.d:
            return flat

        def row_mask(ki):
            # indices drawn over the VALID region only: padding stays zero
            idx = jax.random.permutation(ki, spec.d)[:k]
            return jnp.zeros((spec.d_pad,), flat.dtype).at[idx].set(1)

        mask = jax.vmap(row_mask)(jax.random.split(key, n))
        scale = jnp.asarray(spec.d / k if self.rescale else 1.0, flat.dtype)
        return flat * mask * scale

    def _one(self, x, key):
        flat = x.reshape(x.shape[0], -1)
        n, d = flat.shape
        k = _k_of(self.ratio, d)
        if k >= d:
            return x

        def row_mask(ki):
            idx = jax.random.permutation(ki, d)[:k]
            return jnp.zeros((d,), flat.dtype).at[idx].set(1)

        mask = jax.vmap(row_mask)(jax.random.split(key, n))
        scale = jnp.asarray(d / k if self.rescale else 1.0, flat.dtype)
        return (flat * mask * scale).reshape(x.shape)

    def uplink_bytes(self, msg_template):
        if self.granularity == "global":
            d, itemsize = _global_dims(msg_template)
            # indices are derivable from a shared seed: values only
            return _k_of(self.ratio, d) * itemsize
        total = 0
        for l in jax.tree_util.tree_leaves(msg_template):
            d = _leaf_elements(l)
            k = _k_of(self.ratio, d)
            # indices are derivable from a shared seed: values only
            total += k * jnp.dtype(l.dtype).itemsize
        return total


@dataclass(frozen=True)
class Quantize(Transport):
    """Per-client stochastic uniform quantization to ``2^bits - 1`` levels,
    scaled by the per-(client, leaf) max magnitude.  Unbiased given the scale
    (the stochastic rounding satisfies E[q] = x)."""

    bits: int = 8
    error_feedback: bool = True
    granularity: str = "leaf"
    name: str = "quantize"
    stochastic: bool = True
    wire_encoding: str = "palette"

    def __post_init__(self):
        _check_granularity(self.granularity)

    def apply_leaf(self, msg, key):
        leaves, treedef = jax.tree_util.tree_flatten(msg)
        keys = jax.random.split(key, len(leaves))
        levels = (1 << self.bits) - 1

        def one(x, k):
            flat = x.reshape(x.shape[0], -1)
            s = jnp.max(jnp.abs(flat), axis=1, keepdims=True)
            s = jnp.where(s == 0, jnp.ones_like(s), s)
            y = flat / s * levels
            lo = jnp.floor(y)
            u = jax.random.uniform(k, flat.shape, dtype=flat.dtype)
            q = lo + (u < (y - lo)).astype(flat.dtype)
            return (q / levels * s).reshape(x.shape)

        return jax.tree_util.tree_unflatten(
            treedef, [one(x, k) for x, k in zip(leaves, keys)])

    def apply_flat(self, flat, key, spec):
        levels = (1 << self.bits) - 1
        # ONE scale per client (vs one per leaf): the padding zeros never
        # win the max, and quantize(0) == 0 keeps the padded tail zero
        s = jnp.max(jnp.abs(flat), axis=1)
        u = jax.random.uniform(key, flat.shape, dtype=flat.dtype)
        from repro.kernels import ops as kops

        if kops._on_tpu():
            return kops.plane_quantize(flat, u, s, levels)
        from repro.kernels import ref

        return ref.plane_quantize(flat, u, s, levels)

    def uplink_bytes(self, msg_template):
        if self.granularity == "global":
            d, itemsize = _global_dims(msg_template)
            # packed signed levels for the whole d-vector + ONE fp scale
            return -(-d * (self.bits + 1) // 8) + itemsize
        total = 0
        for l in jax.tree_util.tree_leaves(msg_template):
            d = _leaf_elements(l)
            # signed levels in [-levels, +levels]: bits for the magnitude
            # plus a sign bit per coordinate, plus the per-leaf fp scale
            total += -(-d * (self.bits + 1) // 8) + jnp.dtype(l.dtype).itemsize
        return total


@dataclass(frozen=True)
class DownlinkCompressor:
    """Server-side compression of the broadcast (downlink) innovation.

    Transports above compress the *uplink*; the broadcast of the updated
    server state back to the clients is the other half of every round's
    wire bytes, and for 1-uplink/1-downlink algorithms it is exactly half
    the total.  This wrapper applies any :class:`Transport` to the
    *server-state innovation* -- the delta between the server's new state
    and the shadow state ``seen`` the clients currently hold:

        m_r      = x_{r+1} - seen_r          (innovation vs the shadow)
        seen_{r+1} = x_{r+1} - (m_r - C(m_r))

    The shadow IS the error-feedback state: because ``seen`` accumulates
    only what was actually broadcast, the next innovation automatically
    contains every coordinate earlier rounds dropped (``x - seen`` is the
    standing residual), giving the same telescoping guarantee as the
    uplink's explicit residual stream -- the long-run broadcast is
    undistorted.  ``seen`` is written in the subtractive form above so that
    at ratio 1.0 (``C = id``) the shadow equals the true state *bitwise*
    and the trajectory is unchanged (pinned in tests/test_comm.py).

    The engine's compressed backend threads ``{"seen": ...}`` through its
    scan carry and hands the clients ``seen`` in place of the true server
    fields (``EngineConfig(downlink=...)``); the server state itself stays
    authoritative.  Leaves are lifted to a leading axis of one ("one
    sender"), so the same per-client transport kernels serve the
    single-server broadcast; ``downlink_bytes`` is the per-receiver wire
    cost of one broadcast.  A ``granularity="global"`` transport compresses
    the broadcast innovation as one flat d-vector (global top-k over the
    whole server state, one quantizer scale for the broadcast).
    """

    transport: Transport
    name: str = "downlink"

    def _lift(self, tree):
        return jax.tree_util.tree_map(lambda l: l[None], tree)

    def init_state(self, server_fields):
        """``server_fields``: pytree of the broadcast server state (e.g. the
        'server'-role fields of an algorithm's state)."""
        return {"seen": self._lift(
            jax.tree_util.tree_map(jnp.asarray, server_fields))}

    def broadcast(self, dl_state, server_fields, key):
        """Compress ``server_fields - seen``; returns (what the clients now
        hold, next downlink state)."""
        new = self._lift(server_fields)
        innov = tu.tree_sub(new, dl_state["seen"])
        innov_hat = self.transport.apply(innov, key)
        # seen = seen + innov_hat, written as new - (dropped mass) so the
        # identity transport reproduces the true state bitwise
        seen = tu.tree_sub(new, tu.tree_sub(innov, innov_hat))
        visible = jax.tree_util.tree_map(lambda l: l[0], seen)
        return visible, {"seen": seen}

    def downlink_bytes(self, server_template) -> int:
        """Bytes on the wire per receiver for one broadcast."""
        spec = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((1,) + tuple(l.shape), l.dtype),
            server_template)
        return self.transport.uplink_bytes(spec)


def broadcast_elements(server_template) -> int:
    """Coordinates per receiver of one broadcast pytree -- how benchmarks
    account the downlink from the real server state instead of declared
    vector counts (the dense byte count is
    ``DownlinkCompressor(Dense()).downlink_bytes``)."""
    total = 0
    for l in jax.tree_util.tree_leaves(server_template):
        n = 1
        for s in tuple(l.shape):
            n *= int(s)
        total += n
    return total


@dataclass(frozen=True)
class PlaneTransport:
    """Adapter running any :class:`Transport` on ``(n_clients, d_pad)``
    planes with a *flat* error-feedback buffer.

    This is what the engine's flat-carry mode (``EngineConfig(plane=True)``)
    threads through its scan: messages stay one contiguous buffer end to
    end, the EF residual is ONE ``(n_clients, d_pad)`` array instead of a
    pytree of per-leaf residuals, and global-granularity transports never
    materialize the pytree view at all.  ``compress`` is elementwise- (and
    for leaf granularity bitwise-) identical to the wrapped transport's
    pytree ``compress``.
    """

    inner: Transport
    spec: pln.SegmentSpec

    @property
    def name(self) -> str:
        return self.inner.name

    @property
    def error_feedback(self) -> bool:
        return self.inner.error_feedback

    @property
    def stochastic(self) -> bool:
        return self.inner.stochastic

    @property
    def wire_encoding(self) -> str:
        return self.inner.wire_encoding

    @property
    def scheduled(self) -> bool:
        """True when the wrapped transport follows a staleness-adaptive
        :class:`repro.comm.schedule.RatioSchedule` (its ``compress`` takes
        the per-client age signal)."""
        return getattr(self.inner, "scheduled", False)

    def init_state(self, flat_template):
        if not self.inner.error_feedback:
            return ()
        return jnp.zeros(tuple(flat_template.shape), flat_template.dtype)

    def compress(self, comm_state, flat, key, ages=None):
        if ages is not None:
            return self.inner.compress_plane(comm_state, flat, key,
                                             self.spec, ages=ages)
        return self.inner.compress_plane(comm_state, flat, key, self.spec)

    def scheduled_bytes(self, msg_template, ages):
        """Per-client realized bytes under the wrapped transport's ratio
        schedule; the plane spec stands in for the pytree template (see
        :meth:`repro.comm.schedule.ScheduledTopK.scheduled_bytes_flat`)."""
        return self.inner.scheduled_bytes_flat(self.spec, ages)

    def select_clients(self, mask, new_state, old_state):
        """Per-client-row EF advance guard on the flat residual (see
        :meth:`Transport.select_clients`)."""
        if not self.inner.error_feedback:
            return new_state
        return jnp.where(mask[:, None], new_state, old_state)

    def uplink_bytes(self, msg_template) -> int:
        return self.inner.uplink_bytes(msg_template)


_TRANSPORTS = {"dense": Dense, "topk": TopK, "randk": RandK,
               "quantize": Quantize}


def get_transport(name: str, **kwargs) -> Transport:
    """Build a transport by name ('dense', 'topk', 'randk', 'quantize')."""
    try:
        cls = _TRANSPORTS[name]
    except KeyError:
        raise ValueError(
            f"unknown transport {name!r}; available: {sorted(_TRANSPORTS)}")
    return cls(**kwargs)


def uplink_message_spec(algorithm, grad_fn, state_template, batch_template):
    """ShapeDtypeStruct pytree of an algorithm's uplink message.

    Uses ``jax.eval_shape`` over the algorithm's local half, so no FLOPs are
    spent: this is how benchmarks account bytes/round from the actual message
    instead of hand-maintained per-algorithm constants.
    """
    local_fn = algorithm.make_local_fn(grad_fn)
    return jax.eval_shape(lambda s, b: local_fn(s, b)[0],
                          state_template, batch_template)
