"""Composable engine stages (repro.exec.stages) -- the stage-stack refactor.

Pins the contracts the backend-enum retirement is built around:

  * every single-stage configuration is BITWISE its legacy ``backend=``
    counterpart (Placement == sharded, UplinkComm == compressed,
    DownlinkComm == compressed+downlink, Asynchrony == async);
  * the ``backend=`` alias emits a DeprecationWarning and maps onto the
    right stage combination; stage-field configs emit no warning;
  * compositions the enum made impossible now run and degrade to the bare
    engine at their identity points (async + ratio-1.0 transport under a
    zero-delay clock == inline; downlink under async at ratio 1.0 == dense
    async; all three stages at once on the CPU mesh);
  * the multi-slot report queue: depth 1 reproduces the one-slot
    ``AsyncState`` trajectory, deeper queues let clients race ahead of
    delivery (upload-FIFO), and queued runs still train;
  * prefetch donation: suppliers declare staged chunks donatable, the
    engine trajectory is unchanged.
"""
import warnings

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import Dense, DownlinkCompressor, RandK, TopK
from repro.core import algorithm as A
from repro.core.prox import L1
from repro.data.synthetic import logistic_heterogeneous
from repro.exec import ArraySupplier, EngineConfig, RoundEngine
from repro.fed.simulator import DProxAlgorithm
from repro.models import logreg
from repro.sched import (DeterministicClock, QueueState, Staleness,
                         StragglerClock, init_queue_state)
from repro.utils import tree as tu


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


def _legacy(**kw):
    """An EngineConfig built through the deprecated backend= alias (the
    DeprecationWarning fires lazily at resolve time -- _run suppresses it
    around engine construction)."""
    return EngineConfig(**kw)


def _run(alg, grad_fn, n_clients, cfg, params0, sup, rounds):
    with warnings.catch_warnings():
        if cfg.backend is not None:  # the deprecated alias under test
            warnings.simplefilter("ignore", DeprecationWarning)
        eng = RoundEngine(alg, grad_fn, n_clients, cfg)
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
                                       rtol=1e-12, atol=1e-14)


# ---------------------------------------------------------------------------
# (a) single-stage == legacy backend, bitwise
# ---------------------------------------------------------------------------


def test_placement_stage_matches_legacy_sharded_bitwise():
    from jax.sharding import AxisType

    data, reg, grad_fn, params0 = _problem(seed=1)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    pspecs = {"w": ("mlp",), "b": ()}
    alg = _dprox(reg)
    _, s_new, m_new = _run(
        alg, grad_fn, data.n_clients,
        EngineConfig(chunk_rounds=3, mesh=mesh, param_specs=pspecs),
        params0, sup, 6)
    _, s_old, m_old = _run(
        alg, grad_fn, data.n_clients,
        _legacy(backend="sharded", chunk_rounds=3, mesh=mesh,
                param_specs=pspecs), params0, sup, 6)
    _assert_states_equal(s_new, s_old)
    np.testing.assert_array_equal(m_new["train_loss"], m_old["train_loss"])


def test_uplink_stage_matches_legacy_compressed_bitwise():
    data, reg, grad_fn, params0 = _problem(seed=2)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=3)
    alg = _dprox(reg)
    tr = TopK(ratio=0.5)
    _, s_new, m_new = _run(alg, grad_fn, data.n_clients,
                           EngineConfig(chunk_rounds=3, transport=tr),
                           params0, sup, 6)
    _, s_old, m_old = _run(alg, grad_fn, data.n_clients,
                           _legacy(backend="compressed", chunk_rounds=3,
                                   transport=tr), params0, sup, 6)
    _assert_states_equal(s_new, s_old)
    np.testing.assert_array_equal(m_new["train_loss"], m_old["train_loss"])


def test_downlink_stage_matches_legacy_compressed_downlink_bitwise():
    data, reg, grad_fn, params0 = _problem(seed=3)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=4)
    alg = _dprox(reg)
    _, s_new, _ = _run(alg, grad_fn, data.n_clients,
                       EngineConfig(chunk_rounds=2, downlink=TopK(ratio=0.5)),
                       params0, sup, 6)
    _, s_old, _ = _run(alg, grad_fn, data.n_clients,
                       _legacy(backend="compressed", chunk_rounds=2,
                               downlink=TopK(ratio=0.5)), params0, sup, 6)
    _assert_states_equal(s_new, s_old)


def test_asynchrony_stage_matches_legacy_async_bitwise():
    data, reg, grad_fn, params0 = _problem(seed=4)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=5)
    alg = _dprox(reg)
    kw = dict(chunk_rounds=2, clock=StragglerClock(slowdown=4.0, jitter=0.0),
              buffer_size=3, staleness=Staleness("poly", correct=True))
    _, s_new, m_new = _run(alg, grad_fn, data.n_clients, EngineConfig(**kw),
                           params0, sup, 8)
    _, s_old, m_old = _run(alg, grad_fn, data.n_clients,
                           _legacy(backend="async", **kw), params0, sup, 8)
    _assert_states_equal(s_new, s_old)
    np.testing.assert_array_equal(m_new["vtime"], m_old["vtime"])
    np.testing.assert_array_equal(m_new["staleness_mean"],
                                  m_old["staleness_mean"])


# ---------------------------------------------------------------------------
# (d) the backend= alias: DeprecationWarning + correct mapping
# ---------------------------------------------------------------------------


def test_backend_alias_emits_deprecation_and_maps():
    with pytest.warns(DeprecationWarning, match="deprecated"):
        stack = EngineConfig(backend="compressed").resolve()
    assert stack.uplink is not None and stack.asynchrony is None
    with pytest.warns(DeprecationWarning):
        stack = EngineConfig(backend="async").resolve()
    assert stack.asynchrony is not None and stack.uplink is not None
    with pytest.warns(DeprecationWarning):
        stack = EngineConfig(backend="inline").resolve()
    assert stack.names() == ()
    with pytest.warns(DeprecationWarning):
        stack = EngineConfig(backend="protocol").resolve()
    assert stack.protocol and not stack.split
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with pytest.warns(DeprecationWarning):
        stack = EngineConfig(backend="sharded", mesh=mesh,
                             param_specs={"w": ("mlp",)}).resolve()
    assert stack.placement is not None
    # unknown names still fail loudly, before any mapping
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="warp").validate()


def test_stage_fields_emit_no_deprecation_warning():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        EngineConfig(transport=TopK(ratio=0.5)).validate()
        EngineConfig(clock="straggler", buffer_size=2,
                     downlink=Dense()).validate()
        EngineConfig(protocol=True).validate()


def test_stage_names_reflect_composition():
    stack = EngineConfig(transport=Dense(), clock="straggler",
                         downlink=Dense()).resolve()
    assert stack.names() == ("uplink", "downlink", "asynchrony")
    assert EngineConfig().resolve().names() == ()
    assert EngineConfig(protocol=True).resolve().names() == ("protocol",)


# ---------------------------------------------------------------------------
# (b) compositions degrade to the bare engine at their identity points
# ---------------------------------------------------------------------------


def test_async_plus_ratio_one_uplink_zero_delay_is_inline_bitwise():
    """The composition the enum forbade: Asynchrony + UplinkComm at their
    identity points IS the synchronous uncompressed engine."""
    data, reg, grad_fn, params0 = _problem(seed=5)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=6)
    alg = _dprox(reg)
    _, s_in, m_in = _run(alg, grad_fn, data.n_clients,
                         EngineConfig(chunk_rounds=3), params0, sup, 7)
    _, s_c, m_c = _run(alg, grad_fn, data.n_clients,
                       EngineConfig(chunk_rounds=3, transport=TopK(ratio=1.0),
                                    clock=DeterministicClock()),
                       params0, sup, 7)
    _assert_states_equal(s_in, s_c)
    np.testing.assert_array_equal(m_in["train_loss"], m_c["train_loss"])


def test_async_downlink_ratio_one_matches_dense_async():
    """DownlinkComm threads its shadow through the async carry; at ratio
    1.0 the shadow is bitwise the server state, so the composition matches
    the downlink-free async run (ROADMAP: downlink compression under
    async)."""
    data, reg, grad_fn, params0 = _problem(seed=6)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=7)
    alg = _dprox(reg)
    clock = DeterministicClock(per_client=(1.0, 2.0, 3.0, 1.0, 2.0, 3.0))
    base = dict(chunk_rounds=2, clock=clock, buffer_size=4,
                staleness=Staleness("poly", correct=True))
    _, s_d, m_d = _run(alg, grad_fn, data.n_clients, EngineConfig(**base),
                       params0, sup, 8)
    for dl in (Dense(), TopK(ratio=1.0), DownlinkCompressor(Dense())):
        _, s_c, m_c = _run(alg, grad_fn, data.n_clients,
                           EngineConfig(downlink=dl, **base), params0, sup, 8)
        _assert_states_equal(s_d, s_c)
        np.testing.assert_array_equal(m_d["train_loss"], m_c["train_loss"])
        np.testing.assert_array_equal(m_d["vtime"], m_c["vtime"])


def test_async_downlink_compressed_trains_and_reports_bytes():
    data, reg, grad_fn, params0 = _problem(seed=7)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=8)
    alg = _dprox(reg)
    eng, state, m = _run(
        alg, grad_fn, data.n_clients,
        EngineConfig(chunk_rounds=4, transport=TopK(ratio=0.5),
                     downlink=TopK(ratio=0.5),
                     clock=StragglerClock(slowdown=4.0), buffer_size=3,
                     staleness=Staleness("poly", correct=True)),
        params0, sup, 24)
    losses = m["train_loss"]
    assert len(losses) == 24 and np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    assert bool(tu.tree_isfinite(state.x_bar))
    assert max(m["staleness_max"]) > 0  # stale reports DID flow
    # both wire directions accounted: x_bar (11 f64) at top-k half
    assert eng.uplink_bytes_per_client_round == 6 * (8 + 4)
    assert eng.downlink_bytes_per_client_round == 6 * (8 + 4)


def test_async_downlink_invariant_to_chunking():
    data, reg, grad_fn, params0 = _problem(seed=8)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=9)
    alg = _dprox(reg)
    states = []
    for ch in (1, 4):
        _, s, _ = _run(alg, grad_fn, data.n_clients,
                       EngineConfig(chunk_rounds=ch, downlink=TopK(ratio=0.5),
                                    clock=StragglerClock(slowdown=4.0),
                                    buffer_size=3, transport=RandK(ratio=0.5)),
                       params0, sup, 6)
        states.append(s)
    _assert_states_equal(states[0], states[1])


# ---------------------------------------------------------------------------
# all three stages at once (the acceptance composition)
# ---------------------------------------------------------------------------


def test_full_stack_identity_points_match_inline():
    """Placement + UplinkComm + Asynchrony all active at their identity
    points reproduces the bare inline trajectory on the CPU mesh."""
    from jax.sharding import AxisType

    data, reg, grad_fn, params0 = _problem(seed=9)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=10)
    alg = _dprox(reg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    _, s_in, m_in = _run(alg, grad_fn, data.n_clients,
                         EngineConfig(chunk_rounds=2), params0, sup, 6)
    _, s_f, m_f = _run(
        alg, grad_fn, data.n_clients,
        EngineConfig(chunk_rounds=2, mesh=mesh,
                     param_specs={"w": ("mlp",), "b": ()},
                     transport=TopK(ratio=1.0), clock=DeterministicClock()),
        params0, sup, 6)
    _assert_states_equal(s_in, s_f, exact=False)
    np.testing.assert_allclose(m_in["train_loss"], m_f["train_loss"],
                               rtol=1e-6)


def test_full_stack_compressed_async_sharded_end_to_end():
    """mesh + transport + downlink + clock + queue, all non-trivial, in one
    compiled scan -- the composition the backend enum made impossible."""
    from jax.sharding import AxisType

    data, reg, grad_fn, params0 = _problem(seed=10)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=11)
    alg = _dprox(reg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    eng, state, m = _run(
        alg, grad_fn, data.n_clients,
        EngineConfig(chunk_rounds=3, mesh=mesh,
                     param_specs={"w": ("mlp",), "b": ()},
                     transport=TopK(ratio=0.5), downlink=TopK(ratio=0.5),
                     clock=StragglerClock(slowdown=4.0), buffer_size=3,
                     staleness=Staleness("poly", correct=True),
                     queue_depth=2),
        params0, sup, 18)
    assert eng.stack.names() == ("placement", "uplink", "downlink",
                                 "asynchrony")
    losses = m["train_loss"]
    assert len(losses) == 18 and np.isfinite(losses).all()
    assert bool(tu.tree_isfinite(state.x_bar))
    assert (np.diff(m["vtime"]) >= 0).all()


def test_full_stack_multi_device_subprocess():
    """The 4-device host-platform mesh runs the full stack and matches the
    unplaced composition (placement never changes the math)."""
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 4
from repro.comm import TopK
from repro.core.algorithm import DProxConfig
from repro.core.prox import L1
from repro.data.synthetic import logistic_heterogeneous
from repro.exec import ArraySupplier, EngineConfig, RoundEngine
from repro.fed.simulator import DProxAlgorithm
from jax.sharding import AxisType
from repro.models import logreg
from repro.sched import Staleness, StragglerClock

data = logistic_heterogeneous(n_clients=8, m_per_client=30, d=10,
                              alpha=5, beta=5, seed=0)
data.features = data.features.astype(np.float64)
data.labels = data.labels.astype(np.float64)
reg = L1(lam=0.01)
grad_fn = logreg.make_grad_fn()
params0 = {"w": jnp.zeros(10, jnp.float64), "b": jnp.zeros((), jnp.float64)}
alg = DProxAlgorithm(reg, DProxConfig(tau=3, eta=0.02, eta_g=2.0))
sup = ArraySupplier.from_dataset(data, 3, 8, seed=1)
kw = dict(chunk_rounds=2, transport=TopK(ratio=0.5),
          clock=StragglerClock(slowdown=4.0, jitter=0.0), buffer_size=4,
          staleness=Staleness("poly", correct=True), queue_depth=2)

bare = RoundEngine(alg, grad_fn, 8, EngineConfig(**kw))
s_b, _ = bare.run(bare.init(params0), sup, 6, seed=0)

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
placed = RoundEngine(alg, grad_fn, 8, EngineConfig(
    mesh=mesh, param_specs={"w": ("mlp",), "b": ()}, plan="A", **kw))
s_p, _ = placed.run(placed.init(params0), sup, 6, seed=0)

diff = float(np.abs(np.asarray(s_b.x_bar["w"]) -
                    np.asarray(s_p.x_bar["w"])).max())
print("maxdiff", diff)
assert diff < 1e-12, diff
# the in-flight queue was placed on the mesh (client axis -> data)
sched = placed._sched_state
assert sched.slot_filled.shape == (2, 8)
print("STAGES_SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "STAGES_SHARDED_OK" in out.stdout


# ---------------------------------------------------------------------------
# (c) the multi-slot report queue
# ---------------------------------------------------------------------------


def test_queue_state_shapes():
    msg = {"v": jax.ShapeDtypeStruct((6, 4), jnp.float32)}
    aux = {"round": jax.ShapeDtypeStruct((6,), jnp.int32)}
    qs = init_queue_state(msg, aux, 6, queue_depth=3, clock_seed=0,
                          with_resid=True)
    assert isinstance(qs, QueueState)
    assert qs.pending_msg["v"].shape == (3, 6, 4)
    assert qs.slot_filled.shape == (3, 6) and not bool(qs.slot_filled.any())
    assert np.isinf(np.asarray(qs.deliver_time)).all()
    assert qs.resid["v"].shape == (6, 4)
    with pytest.raises(ValueError, match="queue_depth"):
        init_queue_state(msg, aux, 6, queue_depth=0, clock_seed=0)
    with pytest.raises(ValueError, match="client axis"):
        init_queue_state({"v": jax.ShapeDtypeStruct((4,), jnp.float32)},
                         aux, 6, queue_depth=2, clock_seed=0)


def test_queue_depth_one_matches_one_slot_buffer():
    """Depth 1 is the queue-form of the one-slot AsyncState semantics: a
    slot frees exactly when the previous report delivered."""
    data, reg, grad_fn, params0 = _problem(seed=11)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=12)
    alg = _dprox(reg)
    base = dict(chunk_rounds=2,
                clock=DeterministicClock(per_client=(1.0, 3.5, 1.5, 2.5,
                                                     0.5, 3.0)),
                buffer_size=3, staleness=Staleness("poly", correct=True))
    eng1, s1, m1 = _run(alg, grad_fn, data.n_clients, EngineConfig(**base),
                        params0, sup, 10)
    engq, sq, mq = _run(alg, grad_fn, data.n_clients,
                        EngineConfig(queue_depth=1, **base), params0, sup, 10)
    _assert_states_equal(s1, sq, exact=False)
    np.testing.assert_array_equal(m1["vtime"], mq["vtime"])
    np.testing.assert_array_equal(m1["staleness_mean"], mq["staleness_mean"])
    np.testing.assert_array_equal(
        np.asarray(eng1._sched_state.last_synced),
        np.asarray(engq._sched_state.last_synced))


def test_queue_depth_lets_clients_race_ahead():
    """With a deeper queue a slow client keeps computing while its uploads
    drain FIFO: more than one report in flight at once (the one-slot buffer
    caps this at 1 by construction)."""
    data, reg, grad_fn, params0 = _problem(seed=12)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=13)
    alg = _dprox(reg)
    eng, state, m = _run(
        alg, grad_fn, data.n_clients,
        EngineConfig(chunk_rounds=4,
                     clock=DeterministicClock(per_client=(8.0, 1.0, 1.0,
                                                          1.0, 1.0, 1.0)),
                     buffer_size=3, staleness=Staleness("poly", correct=True),
                     queue_depth=3),
        params0, sup, 16)
    inflight = np.asarray(eng._sched_state.slot_filled).sum(axis=0)
    assert inflight.max() > 1  # someone raced ahead of delivery
    assert np.isfinite(m["train_loss"]).all()
    assert (np.diff(m["vtime"]) >= 0).all()
    # FIFO: in-flight deliver times per client are distinct and ordered
    dt = np.asarray(eng._sched_state.deliver_time)
    filled = np.asarray(eng._sched_state.slot_filled)
    for c in range(data.n_clients):
        times = np.sort(dt[filled[:, c], c])
        assert (np.diff(times) >= 0).all()


def test_queue_trains_and_is_chunk_invariant():
    data, reg, grad_fn, params0 = _problem(seed=13)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=14)
    alg = _dprox(reg)
    states = []
    for ch in (1, 4):
        _, s, m = _run(alg, grad_fn, data.n_clients,
                       EngineConfig(chunk_rounds=ch,
                                    clock=StragglerClock(slowdown=4.0),
                                    buffer_size=3, queue_depth=2,
                                    transport=TopK(ratio=0.5),
                                    staleness=Staleness("poly",
                                                        correct=True)),
                       params0, sup, 12)
        assert np.isfinite(m["train_loss"]).all()
        states.append(s)
    _assert_states_equal(states[0], states[1])


# ---------------------------------------------------------------------------
# prefetch donation
# ---------------------------------------------------------------------------


def test_prefetch_chunks_declared_donatable():
    # donation only pays on accelerators (BENCH_exec measured the donate
    # variant at 0.87x of plain prefetch on CPU), so the declaration is
    # gated on the backend: donatable iff prefetch + minibatch + accelerator
    data, _, _, _ = _problem(seed=14)
    on_accel = jax.default_backend() != "cpu"
    assert (ArraySupplier.from_dataset(data, 3, 4, prefetch=True)
            .donate_chunks == on_accel)
    assert not ArraySupplier.from_dataset(data, 3, 4).donate_chunks
    # full-batch mode serves broadcast VIEWS of the cache: never donatable
    assert not ArraySupplier.from_dataset(data, 3, None,
                                          prefetch=True).donate_chunks


def test_prefetch_donation_trajectory_identical():
    data, reg, grad_fn, params0 = _problem(seed=15)
    alg = _dprox(reg)
    on_accel = jax.default_backend() != "cpu"
    states = []
    for prefetch in (False, True):
        sup = ArraySupplier.from_dataset(data, 3, 8, seed=9,
                                         prefetch=prefetch)
        eng = RoundEngine(alg, grad_fn, data.n_clients,
                          EngineConfig(chunk_rounds=4))
        state = eng.init(params0)
        state, _ = eng.run(state, sup, 10, seed=0)
        assert eng._donate_batches == (prefetch and on_accel)
        states.append(state)
    _assert_states_equal(states[0], states[1])
