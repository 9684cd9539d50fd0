"""The flat parameter plane (repro.core.plane) and everything built on it.

Pins the contracts of the flat-plane refactor:

  * ``flatten``/``unflatten`` round-trip bitwise for arbitrary
    shapes/dtypes/batch axes (property tests via tests/_hypo.py), padding
    is zero-filled and tile-aligned, and mixed-dtype trees fail loudly;
  * the plane-backed engine (``EngineConfig(plane=True)``) is BITWISE the
    per-leaf engine for every stage combination -- split-inline (dense
    uplink), placed, compressed, async, queued, downlink -- including
    non-identity leaf-granularity compressors (the plane path routes them
    through views);
  * ``granularity="global"`` compresses the whole d-vector: ratio 1.0 is
    the identity, global top-k beats per-leaf top-k at equal k on messages
    whose energy concentrates in one leaf, index/scale bytes are accounted
    once, and error feedback still telescopes;
  * the new plane Pallas kernels (threshold-select, quantize, weighted
    commit) match their repro.kernels.ref oracles in interpret mode, and
    the plane-flattened ``ops.fused_local_update`` is bitwise its per-leaf
    fallback;
  * the queue-aware two-stream clock: ``upload=None`` preserves the
    single-stream draws bitwise, ``upload=0.0`` preserves the trajectory,
    and a positive upload stream serializes uploads FIFO under the
    multi-slot queue.
"""
import warnings

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or fixed-grid fallback

from repro.comm import (Dense, PlaneTransport, Quantize, RandK, TopK,
                        uplink_message_spec)
from repro.core import algorithm as A
from repro.core import plane as pln
from repro.core.prox import L1
from repro.data.synthetic import logistic_heterogeneous
from repro.exec import ArraySupplier, EngineConfig, RoundEngine
from repro.fed.simulator import DProxAlgorithm
from repro.kernels import ops, ref
from repro.models import logreg
from repro.sched import (DeterministicClock, LogNormalClock, Staleness,
                         StragglerClock, clock_is_stochastic)


# ---------------------------------------------------------------------------
# SegmentSpec + flatten/unflatten
# ---------------------------------------------------------------------------


def test_lanes_matches_kernel_package():
    from repro.kernels import fused_prox

    assert pln.LANES == fused_prox.LANES


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 4000),
       m=st.integers(1, 7), batch=st.integers(1, 5))
@settings(max_examples=15, deadline=None)
def test_flatten_roundtrip_bitwise(seed, n, m, batch):
    rng = np.random.default_rng(seed)
    tree = {
        "w": jnp.asarray(rng.normal(size=(batch, n)), jnp.float64),
        "b": jnp.asarray(rng.normal(size=(batch,)), jnp.float64),
        "k": jnp.asarray(rng.normal(size=(batch, m, 3)), jnp.float64),
    }
    spec = pln.SegmentSpec.from_tree(tree, batch_dims=1)
    flat = pln.flatten(spec, tree)
    assert flat.shape == (batch, spec.d_pad)
    assert spec.d == n + 1 + 3 * m
    assert spec.d_pad % pln.LANES == 0 and spec.d_pad >= spec.d
    # the padded tail is zero
    if spec.pad:
        np.testing.assert_array_equal(np.asarray(flat[:, spec.d:]), 0.0)
    back = pln.unflatten(spec, flat)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k]),
                                      np.asarray(back[k]))


@given(n=st.integers(1, 600), tile=st.sampled_from([1, 128, 1024, 32768]))
@settings(max_examples=10, deadline=None)
def test_spec_tile_alignment(n, tile):
    tree = {"w": jax.ShapeDtypeStruct((n,), jnp.float32)}
    spec = pln.SegmentSpec.from_tree(tree, tile=tile)
    assert spec.d == n
    assert spec.d_pad % tile == 0
    assert spec.d_pad - spec.d < tile


def test_spec_rejects_mixed_dtypes_and_bad_batch():
    with pytest.raises(ValueError, match="one dtype"):
        pln.SegmentSpec.from_tree({"a": jnp.zeros(3, jnp.float32),
                                   "b": jnp.zeros(3, jnp.float64)})
    with pytest.raises(ValueError, match="batch"):
        pln.SegmentSpec.from_tree({"a": jnp.zeros((2, 3), jnp.float32),
                                   "b": jnp.zeros((4, 3), jnp.float32)},
                                  batch_dims=1)
    with pytest.raises(ValueError, match="empty"):
        pln.SegmentSpec.from_tree({})


def test_param_plane_is_a_pytree():
    tree = {"w": jnp.arange(6, dtype=jnp.float32),
            "b": jnp.ones((), jnp.float32)}
    p = pln.ParamPlane.from_tree(tree)
    assert p.spec.d == 7
    # tree_map sees ONE contiguous leaf
    leaves = jax.tree_util.tree_leaves(p)
    assert len(leaves) == 1 and leaves[0].shape == (p.spec.d_pad,)
    doubled = jax.tree_util.tree_map(lambda x: 2 * x, p)
    np.testing.assert_array_equal(np.asarray(doubled.tree["w"]),
                                  2 * np.arange(6, dtype=np.float32))
    # jit-static spec: the plane crosses a jit boundary intact
    out = jax.jit(lambda q: q.with_data(q.data + 1))(p)
    np.testing.assert_array_equal(np.asarray(out.tree["b"]), 2.0)


# ---------------------------------------------------------------------------
# plane-backed engine == per-leaf engine, bitwise, per stage combination
# ---------------------------------------------------------------------------


def _problem(n=6, m=30, d=10, seed=0, lam=0.01):
    data = logistic_heterogeneous(
        n_clients=n, m_per_client=m, d=d, alpha=5, beta=5, seed=seed)
    s = np.linalg.norm(data.features.reshape(-1, d), axis=1).max()
    data.features = (data.features / s).astype(np.float64)
    data.labels = data.labels.astype(np.float64)
    reg = L1(lam=lam)
    grad_fn = logreg.make_grad_fn()
    params0 = {"w": jnp.zeros(d, jnp.float64), "b": jnp.zeros((), jnp.float64)}
    return data, reg, grad_fn, params0


def _dprox(reg, tau=3, eta=0.05, eta_g=2.0):
    return DProxAlgorithm(reg, A.DProxConfig(tau=tau, eta=eta, eta_g=eta_g))


def _run(cfg, data, reg, grad_fn, params0, rounds=8, sup_seed=3):
    alg = _dprox(reg)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=sup_seed)
    eng = RoundEngine(alg, grad_fn, data.n_clients, cfg)
    state = eng.init(params0)
    state, metrics = eng.run(state, sup, rounds, seed=0)
    return eng, state, metrics


def _assert_states_equal(a, b, exact=True):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-12, atol=1e-15)


# combos marked exact=False involve the stochastic quantizer, whose
# dequantize (q / levels * s) feeds the EF subtraction: XLA contracts that
# multiply-subtract into an FMA differently across the two carry layouts,
# an ulp-level reassociation the bitwise pin cannot survive.  Everything
# the acceptance contract names (Dense / ratio-1.0 / top-k / rand-k select
# paths) is FMA-free and pinned EXACTLY.
STAGE_COMBOS = {
    # "inline" split form: dense uplink, no compression
    "split_inline": (dict(chunk_rounds=3, transport=Dense()), True),
    "compressed_topk": (dict(chunk_rounds=3, transport=TopK(ratio=0.5)),
                        True),
    "compressed_randk": (dict(chunk_rounds=2, transport=RandK(ratio=0.5)),
                         True),
    "compressed_quantize": (dict(chunk_rounds=2, transport=Quantize(bits=8)),
                            False),
    "async": (dict(chunk_rounds=2,
                   clock=StragglerClock(slowdown=4.0, jitter=0.0),
                   buffer_size=3, staleness=Staleness("poly", correct=True)),
              True),
    "queued": (dict(chunk_rounds=2,
                    clock=StragglerClock(slowdown=4.0, jitter=0.0),
                    buffer_size=3, queue_depth=2, transport=TopK(ratio=0.5),
                    staleness=Staleness("poly", correct=True)), True),
    "downlink": (dict(chunk_rounds=2, transport=TopK(ratio=0.5),
                      downlink=TopK(ratio=0.5)), True),
    "async_downlink": (dict(chunk_rounds=2, transport=TopK(ratio=0.5),
                            downlink=Dense(),
                            clock=StragglerClock(slowdown=4.0, jitter=0.0),
                            buffer_size=3), True),
}


@pytest.mark.parametrize("combo", sorted(STAGE_COMBOS))
def test_plane_engine_matches_per_leaf_bitwise(combo):
    data, reg, grad_fn, params0 = _problem(seed=1)
    kw, exact = STAGE_COMBOS[combo]
    _, s_leaf, m_leaf = _run(EngineConfig(**kw), data, reg, grad_fn, params0)
    eng, s_pl, m_pl = _run(EngineConfig(plane=True, **kw), data, reg,
                           grad_fn, params0)
    assert eng._plane_spec is not None and eng._plane_spec.d == 11
    _assert_states_equal(s_leaf, s_pl, exact=exact)
    if exact:
        np.testing.assert_array_equal(m_leaf["train_loss"],
                                      m_pl["train_loss"])
    else:
        np.testing.assert_allclose(m_leaf["train_loss"], m_pl["train_loss"],
                                   rtol=1e-12)
    if "vtime" in m_leaf:
        np.testing.assert_array_equal(m_leaf["vtime"], m_pl["vtime"])


def test_plane_engine_matches_per_leaf_placed():
    """Placement on top: flat carries get the 1-axis client placement."""
    from jax.sharding import AxisType

    data, reg, grad_fn, params0 = _problem(seed=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    kw = dict(chunk_rounds=2, mesh=mesh,
              param_specs={"w": ("mlp",), "b": ()},
              transport=TopK(ratio=0.5),
              clock=StragglerClock(slowdown=4.0, jitter=0.0), buffer_size=3)
    _, s_leaf, m_leaf = _run(EngineConfig(**kw), data, reg, grad_fn, params0)
    _, s_pl, m_pl = _run(EngineConfig(plane=True, **kw), data, reg, grad_fn,
                         params0)
    _assert_states_equal(s_leaf, s_pl)
    np.testing.assert_array_equal(m_leaf["train_loss"], m_pl["train_loss"])


def test_plane_carry_is_flat():
    """The carry actually holds planes, not pytrees: one (n, d_pad) comm
    residual and (depth, n, d_pad) queued report buffers."""
    data, reg, grad_fn, params0 = _problem(seed=3)
    eng, _, _ = _run(
        EngineConfig(plane=True, chunk_rounds=2, transport=TopK(ratio=0.5),
                     clock=StragglerClock(slowdown=4.0), buffer_size=3,
                     queue_depth=2, staleness=Staleness("poly", correct=True)),
        data, reg, grad_fn, params0)
    d_pad = eng._plane_spec.d_pad
    assert d_pad % pln.LANES == 0
    assert eng._comm_state.shape == (6, d_pad)
    assert eng._sched_state.pending_msg.shape == (2, 6, d_pad)
    assert eng._sched_state.resid.shape == (6, d_pad)
    # wire accounting is layout-independent
    assert eng.uplink_bytes_per_client_round == 6 * (8 + 4)


def test_plane_rejects_protocol_and_eager():
    with pytest.raises(ValueError, match="protocol"):
        EngineConfig(plane=True, protocol=True).validate()
    with pytest.raises(ValueError, match="jit"):
        EngineConfig(plane=True, jit=False).validate()


def test_plane_step_matches_run_chunking():
    """plane mode composes with step()/chunk invariance."""
    data, reg, grad_fn, params0 = _problem(seed=4)
    states = []
    for ch in (1, 4):
        _, s, _ = _run(EngineConfig(plane=True, chunk_rounds=ch,
                                    transport=TopK(ratio=0.5)),
                       data, reg, grad_fn, params0, rounds=8)
        states.append(s)
    _assert_states_equal(states[0], states[1])


# ---------------------------------------------------------------------------
# global granularity
# ---------------------------------------------------------------------------


def test_global_topk_ratio_one_is_identity():
    data, reg, grad_fn, params0 = _problem(seed=5)
    kw = dict(chunk_rounds=3)
    _, s_d, m_d = _run(EngineConfig(transport=Dense(), **kw), data, reg,
                       grad_fn, params0)
    for plane in (False, True):
        _, s_g, m_g = _run(
            EngineConfig(transport=TopK(ratio=1.0, granularity="global"),
                         plane=plane, **kw), data, reg, grad_fn, params0)
        _assert_states_equal(s_d, s_g)
        np.testing.assert_array_equal(m_d["train_loss"], m_g["train_loss"])


def test_global_topk_selects_globally():
    """Per-leaf top-k keeps k coordinates in EVERY leaf; global top-k
    spends the whole budget where the energy is."""
    key = jax.random.PRNGKey(0)
    msg = {"big": jnp.asarray([[10.0, 9.0, 8.0, 7.0]]),
           "small": jnp.asarray([[0.1, 0.2]])}
    leaf = TopK(ratio=0.5).apply(msg, key)
    glob = TopK(ratio=0.5, granularity="global").apply(msg, key)
    # leaf: 2 of 4 kept in "big", 1 of 2 kept in "small"
    assert int((np.asarray(leaf["big"]) != 0).sum()) == 2
    assert int((np.asarray(leaf["small"]) != 0).sum()) == 1
    # global: k = round(0.5 * 6) = 3, all spent on "big"
    assert int((np.asarray(glob["big"]) != 0).sum()) == 3
    assert int((np.asarray(glob["small"]) != 0).sum()) == 0


def test_global_topk_recovers_more_energy():
    """At equal k-budget, global selection retains at least the per-leaf
    energy (strictly more on energy-concentrated messages)."""
    rng = np.random.default_rng(0)
    msg = {"a": jnp.asarray(rng.normal(size=(4, 50)) * 10),
           "b": jnp.asarray(rng.normal(size=(4, 50)) * 0.01)}
    key = jax.random.PRNGKey(1)
    leaf = TopK(ratio=0.3).apply(msg, key)
    glob = TopK(ratio=0.3, granularity="global").apply(msg, key)

    def energy(m):
        return sum(float(jnp.sum(v ** 2)) for v in m.values())

    assert energy(glob) > energy(leaf)


def test_global_uplink_bytes_accounted_once():
    spec = {"w": jax.ShapeDtypeStruct((4, 100), jnp.float32),
            "b": jax.ShapeDtypeStruct((4, 50), jnp.float32),
            "c": jax.ShapeDtypeStruct((4, 6), jnp.float32)}
    d = 156
    # top-k: one index stream for the global k
    k_g = max(1, round(0.1 * d))
    assert (TopK(ratio=0.1, granularity="global").uplink_bytes(spec)
            == k_g * (4 + 4))
    # per-leaf pays ceil-ed k per leaf
    assert (TopK(ratio=0.1).uplink_bytes(spec)
            == (10 + 5 + 1) * (4 + 4))
    # quantize: ONE scale instead of one per leaf (and one contiguous bit
    # packing instead of per-leaf round-up)
    q_leaf = Quantize(bits=8).uplink_bytes(spec)
    q_glob = Quantize(bits=8, granularity="global").uplink_bytes(spec)
    assert q_leaf - q_glob >= 2 * 4  # at least the two saved fp32 scales
    with pytest.raises(ValueError, match="granularity"):
        TopK(granularity="warp")
    with pytest.raises(ValueError, match="single-dtype"):
        TopK(granularity="global").uplink_bytes(
            {"a": jax.ShapeDtypeStruct((4, 3), jnp.float32),
             "b": jax.ShapeDtypeStruct((4, 3), jnp.float64)})


def test_global_error_feedback_telescopes():
    """sum of transmitted == sum of produced - final residual, globally."""
    rng = np.random.default_rng(2)
    tr = TopK(ratio=0.3, granularity="global")
    msgs = [{"a": jnp.asarray(rng.normal(size=(3, 20))),
             "b": jnp.asarray(rng.normal(size=(3, 5)))} for _ in range(6)]
    cs = tr.init_state(msgs[0])
    key = jax.random.PRNGKey(0)
    sent_sum = jax.tree_util.tree_map(jnp.zeros_like, msgs[0])
    for m in msgs:
        hat, cs = tr.compress(cs, m, key)
        sent_sum = jax.tree_util.tree_map(jnp.add, sent_sum, hat)
    produced = jax.tree_util.tree_map(
        lambda *xs: sum(xs), *msgs)
    for k in sent_sum:
        np.testing.assert_allclose(
            np.asarray(sent_sum[k]),
            np.asarray(produced[k]) - np.asarray(cs[k]),
            atol=1e-12)


def test_global_quantize_and_randk_train():
    data, reg, grad_fn, params0 = _problem(seed=6)
    for tr in (Quantize(bits=6, granularity="global"),
               RandK(ratio=0.5, granularity="global")):
        for plane in (False, True):
            eng, s, m = _run(EngineConfig(transport=tr, plane=plane,
                                          chunk_rounds=2),
                             data, reg, grad_fn, params0, rounds=10)
            assert np.isfinite(m["train_loss"]).all()
        # plane and pytree layouts draw identically -> same trajectory
        # (up to the FMA-contraction ulps noted at STAGE_COMBOS)
        _, s_t, m_t = _run(EngineConfig(transport=tr, chunk_rounds=2),
                           data, reg, grad_fn, params0, rounds=10)
        _, s_p, m_p = _run(EngineConfig(transport=tr, plane=True,
                                        chunk_rounds=2),
                           data, reg, grad_fn, params0, rounds=10)
        _assert_states_equal(s_t, s_p, exact=False)


def test_plane_transport_compress_matches_pytree_compress():
    rng = np.random.default_rng(3)
    msg = {"w": jnp.asarray(rng.normal(size=(4, 10))),
           "b": jnp.asarray(rng.normal(size=(4,)))}
    spec = pln.SegmentSpec.from_tree(msg, batch_dims=1)
    for tr in (TopK(ratio=0.5), TopK(ratio=0.4, granularity="global"),
               Quantize(bits=8), Dense()):
        pt = PlaneTransport(tr, spec)
        key = jax.random.PRNGKey(0)
        cs_t = tr.init_state(msg)
        cs_f = pt.init_state(
            jax.ShapeDtypeStruct((4, spec.d_pad), spec.dtype))
        flat = pln.flatten(spec, msg)
        hat_t, cs_t = tr.compress(cs_t, msg, key)
        hat_f, cs_f = pt.compress(cs_f, flat, key)
        _assert_states_equal(hat_t, pln.unflatten(spec, hat_f))
        if tr.error_feedback:
            _assert_states_equal(cs_t, pln.unflatten(spec, cs_f))
            # the EF plane's padded tail stays zero (donation-safe algebra)
            np.testing.assert_array_equal(
                np.asarray(cs_f[:, spec.d:]), 0.0)


# ---------------------------------------------------------------------------
# plane Pallas kernels vs the jnp oracles (interpret mode)
# ---------------------------------------------------------------------------


# (3, 640) has 5 tile rows: two 2-row blocks and a ragged last one
@pytest.mark.parametrize("shape", [(3, 128), (5, 512), (2, 1024), (3, 640)])
def test_threshold_select_kernel_matches_ref(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    th = jnp.asarray(np.abs(rng.normal(size=shape[0])), jnp.float32)
    got = ops.plane_threshold_select(x, th, interpret=True, block_rows=2)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.plane_threshold_select(x, th)))


# 640 lanes are 5 tile rows: block_rows=2 leaves a ragged last block
@pytest.mark.parametrize("block_rows", [1, 2])
@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_kernel_matches_ref(bits, block_rows):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 640)), jnp.float32)
    u = jnp.asarray(rng.uniform(size=(4, 640)), jnp.float32)
    s = jnp.max(jnp.abs(x), axis=1)
    levels = (1 << bits) - 1
    got = ops.plane_quantize(x, u, s, levels, interpret=True,
                             block_rows=block_rows)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.plane_quantize(x, u, s, levels)),
        atol=1e-6)
    # zero rows quantize to zero (scale guard)
    z = jnp.zeros((2, 256), jnp.float32)
    got = ops.plane_quantize(z, u[:2, :256], jnp.zeros(2), levels,
                             interpret=True, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


# (6, 640) has 5 tile rows: two 2-row blocks and a ragged last one
@pytest.mark.parametrize("shape", [(6, 512), (6, 640)])
def test_weighted_commit_kernel_matches_ref(shape):
    rng = np.random.default_rng(2)
    buf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    w = jnp.asarray(rng.uniform(size=shape[0]), jnp.float32)
    got = ops.plane_weighted_commit(buf, w, interpret=True, block_rows=2)
    # the kernel accumulates sequentially in fp32; jnp.sum may reduce in a
    # different order -- 1-ulp tolerance
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.plane_weighted_commit(buf, w)),
        rtol=1e-5, atol=1e-6)


def test_weighted_commit_block_shrinks_with_clients(monkeypatch):
    """Many clients against a small VMEM budget: the commit block drops to
    8 rows, and 20 tile rows leave a ragged last block."""
    from repro.kernels import plane_ops

    n, rows = 40, 20
    monkeypatch.setattr(plane_ops, "COMMIT_VMEM_BYTES", n * 128 * 4 * 8)
    assert plane_ops.commit_block_rows(n, rows, 4, plane_ops.BLOCK_ROWS) == 8
    rng = np.random.default_rng(3)
    buf = jnp.asarray(rng.normal(size=(n, rows * 128)), jnp.float32)
    w = jnp.asarray(rng.uniform(size=n), jnp.float32)
    got = ops.plane_weighted_commit(buf, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.plane_weighted_commit(buf, w)),
        rtol=1e-5, atol=1e-6)


def test_fused_local_update_plane_matches_per_leaf():
    """The plane-flattened fused update == the per-leaf fallback bitwise
    (same kernel arithmetic, one launch instead of N)."""
    rng = np.random.default_rng(3)
    tree = {"w": jnp.asarray(rng.normal(size=(900,)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(3, 7)), jnp.float32)}
    g = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), tree)
    c = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), tree)
    got = ops.fused_local_update(tree, g, c, 0.05, 0.01, interpret=True,
                                 block_rows=8)
    exp = ops._fused_local_update_per_leaf(tree, g, c, 0.05, 0.01,
                                           interpret=True, block_rows=8)
    for a, b in zip(got, exp):
        _assert_states_equal(a, b)
    # mixed-dtype trees take the per-leaf fallback instead of failing
    mixed = {"w": jnp.zeros((40,), jnp.float32),
             "b": jnp.zeros((2,), jnp.bfloat16)}
    zh, z = ops.fused_local_update(mixed, mixed, mixed, 0.05, 0.01,
                                   interpret=True, block_rows=8)
    assert zh["b"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# queue-aware two-stream clocks
# ---------------------------------------------------------------------------


def test_upload_none_preserves_single_stream_draws_bitwise():
    key = jax.random.PRNGKey(7)
    for clock in (LogNormalClock(sigma=0.7), StragglerClock(),
                  DeterministicClock(duration=2.0)):
        comp, upl = clock.split_durations(key, jnp.int32(0), 8)
        np.testing.assert_array_equal(
            np.asarray(comp), np.asarray(clock.durations(key, jnp.int32(0), 8)))
        np.testing.assert_array_equal(np.asarray(upl), 0.0)


def test_upload_zero_trajectory_bitwise():
    data, reg, grad_fn, params0 = _problem(seed=7)
    base = dict(chunk_rounds=2, buffer_size=3, queue_depth=2,
                staleness=Staleness("poly", correct=True))
    _, s0, m0 = _run(EngineConfig(clock=StragglerClock(jitter=0.0), **base),
                     data, reg, grad_fn, params0)
    _, s1, m1 = _run(
        EngineConfig(clock=StragglerClock(jitter=0.0, upload=0.0), **base),
        data, reg, grad_fn, params0)
    _assert_states_equal(s0, s1)
    np.testing.assert_array_equal(m0["vtime"], m1["vtime"])


def test_deterministic_upload_keeps_compute_draws():
    """A constant upload stream must not perturb the compute draws (no key
    split for a keyless consumer)."""
    key = jax.random.PRNGKey(3)
    plain = LogNormalClock(sigma=0.5)
    with_up = LogNormalClock(sigma=0.5, upload=2.5)
    c0, _ = plain.split_durations(key, jnp.int32(0), 6)
    c1, u1 = with_up.split_durations(key, jnp.int32(0), 6)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(u1), 2.5)
    # a stochastic upload clock splits (and is flagged stochastic)
    both = LogNormalClock(sigma=0.5, upload=LogNormalClock(sigma=0.1))
    c2, u2 = both.split_durations(key, jnp.int32(0), 6)
    assert not np.array_equal(np.asarray(c0), np.asarray(c2))
    assert clock_is_stochastic(both)
    assert clock_is_stochastic(
        DeterministicClock(upload=LogNormalClock()))
    assert not clock_is_stochastic(DeterministicClock(upload=1.0))


def test_upload_serializes_fifo_under_queue():
    """Fast compute + slow upload: a queued client's arrivals are spaced by
    the upload time (upload-bandwidth-limited), not the compute time."""
    data, reg, grad_fn, params0 = _problem(seed=8)
    eng, state, m = _run(
        EngineConfig(chunk_rounds=2,
                     clock=DeterministicClock(duration=0.1, upload=5.0),
                     buffer_size=3, queue_depth=3),
        data, reg, grad_fn, params0, rounds=10)
    assert np.isfinite(m["train_loss"]).all()
    # in-flight uploads of one client are spaced >= the upload time
    dt = np.asarray(eng._sched_state.deliver_time)
    filled = np.asarray(eng._sched_state.slot_filled)
    for cidx in range(data.n_clients):
        times = np.sort(dt[filled[:, cidx], cidx])
        if len(times) > 1:
            assert (np.diff(times) >= 5.0 - 1e-5).all()
    # and virtual time reflects uploads, not the 0.1 compute
    assert m["vtime"][-1] >= 5.0


def test_duck_typed_clock_still_runs():
    """Clocks that implement only ``durations`` (no ClockModel subclass, no
    upload/stochastic/split_durations surface) keep working: the aggregator
    falls back to the single-stream zero-upload form."""

    class DuckClock:
        name = "duck"

        def durations(self, key, round_idx, n_clients):
            return jnp.full((n_clients,), 2.0, jnp.float32)

    assert clock_is_stochastic(DuckClock())  # assumed stochastic
    data, reg, grad_fn, params0 = _problem(seed=10)
    eng, state, m = _run(
        EngineConfig(chunk_rounds=2, clock=DuckClock(), buffer_size=3),
        data, reg, grad_fn, params0, rounds=6)
    assert np.isfinite(m["train_loss"]).all()
    # half-buffer commits arrive in waves of the fixed 2.0 duration
    np.testing.assert_allclose(np.asarray(m["vtime"]),
                               [2.0, 2.0, 4.0, 4.0, 6.0, 6.0])


def test_upload_increases_vtime_one_slot():
    data, reg, grad_fn, params0 = _problem(seed=9)
    base = dict(chunk_rounds=2, buffer_size=6)
    _, _, m0 = _run(EngineConfig(clock=DeterministicClock(duration=1.0),
                                 **base), data, reg, grad_fn, params0,
                    rounds=6)
    _, _, m1 = _run(
        EngineConfig(clock=DeterministicClock(duration=1.0, upload=2.0),
                     **base), data, reg, grad_fn, params0, rounds=6)
    np.testing.assert_allclose(np.asarray(m1["vtime"]),
                               3.0 * np.asarray(m0["vtime"]))
