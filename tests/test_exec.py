"""Round-execution engine (repro.exec) correctness.

Pins the engine's core contract: backends and chunking change HOW rounds
execute, never WHAT they compute.

  * chunked (lax.scan over rounds) == round-at-a-time, same trajectory;
  * inline == sharded (mesh-placed) == protocol (literal per-client message
    passing), on the synthetic heterogeneous logreg problem;
  * partial participation: a full mask reproduces the dense path exactly;
    subsampled clients keep non-participants' state frozen;
  * every baseline FedAlgorithm runs through the engine unchanged.
"""
import os
import subprocess
import sys

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import HAVE_HYPOTHESIS  # noqa: F401  (imports must not require it)
from repro.core import algorithm as A
from repro.core.baselines import (FastFedDA, FedAvg, FedDA, FedMid, FedProx,
                                  Scaffold)  # noqa: F401 (parametrized)
from repro.core.prox import L1
from repro.data.synthetic import logistic_heterogeneous, make_round_batches
from repro.exec import EngineConfig, RoundEngine, sample_active_masks
from repro.fed.simulator import DProxAlgorithm, run
from repro.models import logreg
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


def _supplier(data, tau, batch):
    """Deterministic per-round batches: immune to rng interleaving across
    chunk boundaries / participation mask draws."""

    def supplier(r, rng):
        return make_round_batches(data, tau, batch,
                                  np.random.default_rng(10_000 + r))

    return supplier


def _run_engine(engine, params0, supplier, rounds):
    state = engine.init(params0)
    state, metrics = engine.run(state, supplier, rounds, seed=0)
    return state, metrics


@pytest.mark.parametrize("alg", ["dprox", "fedavg"])
def test_donating_run_leaves_caller_params_intact(monkeypatch, alg):
    """The algorithms keep ``params0`` itself as their first server state;
    on an accelerator the compiled call donates the state.  ``init`` must
    hand the engine copies, so the caller can start a second run (another
    algorithm, another config) from the same ``params0``, bitwise."""
    from repro.exec import engine as engine_mod

    data, reg, grad_fn, params0 = _problem()
    algorithm = (_dprox(reg) if alg == "dprox"
                 else FedAvg(tau=3, eta=0.05))
    sup = _supplier(data, 3, 8)
    finals = []
    for donating in (True, True, False):
        monkeypatch.setattr(engine_mod, "donates", lambda: donating)
        eng = RoundEngine(algorithm, grad_fn, data.n_clients,
                          EngineConfig(chunk_rounds=2))
        state, _ = _run_engine(eng, params0, sup, 4)
        finals.append(b"".join(
            np.asarray(leaf).tobytes()
            for leaf in jax.tree_util.tree_leaves(eng.global_params(state))))
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(params0))
    assert finals[0] == finals[1] == finals[2]


# ---------------------------------------------------------------------------
# chunked == unchunked
# ---------------------------------------------------------------------------


def test_chunked_matches_round_at_a_time():
    data, reg, grad_fn, params0 = _problem()
    supplier = _supplier(data, 3, 8)
    alg = _dprox(reg)
    rounds = 11  # not a multiple of the chunk: exercises the remainder chunk
    s1, m1 = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(chunk_rounds=1)), params0, supplier, rounds)
    s4, m4 = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(chunk_rounds=4)), params0, supplier, rounds)
    np.testing.assert_allclose(np.asarray(s1.x_bar["w"]),
                               np.asarray(s4.x_bar["w"]), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(s1.c["w"]),
                               np.asarray(s4.c["w"]), rtol=1e-10, atol=1e-12)
    assert len(m1["train_loss"]) == len(m4["train_loss"]) == rounds
    np.testing.assert_allclose(m1["train_loss"], m4["train_loss"], rtol=1e-6)


def test_simulator_history_invariant_to_chunking():
    data, reg, grad_fn, params0 = _problem(seed=3)
    supplier = _supplier(data, 3, 8)
    alg = _dprox(reg)
    hists = [
        run(alg, params0, grad_fn, supplier, data.n_clients, 10,
            eval_every=4, chunk_rounds=ch)
        for ch in (1, 8)
    ]
    assert hists[0].rounds == hists[1].rounds
    np.testing.assert_allclose(hists[0].loss, hists[1].loss, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(hists[0].extra["final_params"]["w"]),
        np.asarray(hists[1].extra["final_params"]["w"]), rtol=1e-12)


# ---------------------------------------------------------------------------
# backend parity
# ---------------------------------------------------------------------------


def test_protocol_backend_matches_inline():
    data, reg, grad_fn, params0 = _problem(seed=1)
    supplier = _supplier(data, 4, 8)
    alg = _dprox(reg, tau=4)
    s_in, _ = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(backend="inline", chunk_rounds=2)),
        params0, supplier, 4)
    s_pr, _ = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(backend="protocol")),
        params0, supplier, 4)
    np.testing.assert_allclose(np.asarray(s_in.x_bar["w"]),
                               np.asarray(s_pr.x_bar["w"]),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(s_in.c["w"]),
                               np.asarray(s_pr.c["w"]),
                               rtol=1e-10, atol=1e-12)


def test_sharded_backend_matches_inline_single_device():
    from jax.sharding import AxisType

    data, reg, grad_fn, params0 = _problem(seed=2)
    supplier = _supplier(data, 3, 8)
    alg = _dprox(reg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    pspecs = {"w": ("mlp",), "b": ()}
    s_in, _ = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(backend="inline", chunk_rounds=3)),
        params0, supplier, 6)
    s_sh, m_sh = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(backend="sharded", chunk_rounds=3,
                                 mesh=mesh, param_specs=pspecs, plan="A")),
        params0, supplier, 6)
    np.testing.assert_allclose(np.asarray(s_in.x_bar["w"]),
                               np.asarray(s_sh.x_bar["w"]), rtol=1e-12)
    assert len(m_sh["train_loss"]) == 6


@pytest.mark.parametrize("alg_factory", [
    lambda reg: _dprox(reg),
    lambda reg: FedAvg(tau=3, eta=0.05),
    lambda reg: FedMid(reg, tau=3, eta=0.05),
    lambda reg: FedDA(reg, tau=3, eta=0.05, eta_g=2.0),
    lambda reg: FastFedDA(reg, tau=3, eta0=0.05),
    lambda reg: Scaffold(reg, tau=3, eta=0.05),
    lambda reg: FedProx(reg, tau=3, eta=0.05),
], ids=["dprox", "fedavg", "fedmid", "fedda", "fast_fedda", "scaffold",
        "fedprox"])
def test_all_algorithms_sharded_match_inline(alg_factory):
    """state_roles + fed_state_shardings_from_roles place EVERY algorithm's
    federated state, not just DProxState -- trajectory parity for all
    seven."""
    from jax.sharding import AxisType

    data, reg, grad_fn, params0 = _problem(seed=9)
    supplier = _supplier(data, 3, 8)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    pspecs = {"w": ("mlp",), "b": ()}
    alg = alg_factory(reg)
    e_in = RoundEngine(alg, grad_fn, data.n_clients,
                       EngineConfig(backend="inline", chunk_rounds=3))
    s_in, _ = _run_engine(e_in, params0, supplier, 6)
    e_sh = RoundEngine(alg, grad_fn, data.n_clients,
                       EngineConfig(backend="sharded", chunk_rounds=3,
                                    mesh=mesh, param_specs=pspecs, plan="A"))
    s_sh, m_sh = _run_engine(e_sh, params0, supplier, 6)
    for a, b in zip(jax.tree_util.tree_leaves(e_in.global_params(s_in)),
                    jax.tree_util.tree_leaves(e_sh.global_params(s_sh))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-12, atol=1e-14)
    assert len(m_sh["train_loss"]) == 6


SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 4
from repro.core.algorithm import DProxConfig
from repro.core.prox import L1
from repro.data.synthetic import logistic_heterogeneous, make_round_batches
from repro.exec import EngineConfig, RoundEngine
from repro.fed.simulator import DProxAlgorithm
from jax.sharding import AxisType
from repro.models import logreg

data = logistic_heterogeneous(n_clients=8, m_per_client=30, d=10,
                              alpha=5, beta=5, seed=0)
data.features = data.features.astype(np.float64)
data.labels = data.labels.astype(np.float64)
reg = L1(lam=0.01)
grad_fn = logreg.make_grad_fn()
params0 = {"w": jnp.zeros(10, jnp.float64), "b": jnp.zeros((), jnp.float64)}
alg = DProxAlgorithm(reg, DProxConfig(tau=3, eta=0.02, eta_g=2.0))
sup = lambda r, rng: make_round_batches(data, 3, 8,
                                        np.random.default_rng(10_000 + r))

inline = RoundEngine(alg, grad_fn, 8, EngineConfig(chunk_rounds=2))
s_in, _ = inline.run(inline.init(params0), sup, 6, seed=0)

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
sharded = RoundEngine(alg, grad_fn, 8, EngineConfig(
    backend="sharded", chunk_rounds=2, mesh=mesh,
    param_specs={"w": ("mlp",), "b": ()}, plan="A"))
s_sh, _ = sharded.run(sharded.init(params0), sup, 6, seed=0)

diff = float(np.abs(np.asarray(s_in.x_bar["w"]) -
                    np.asarray(s_sh.x_bar["w"])).max())
print("maxdiff", diff)
assert diff < 1e-12, diff
print("EXEC_SHARDED_OK")
"""


def test_sharded_backend_matches_inline_multi_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "EXEC_SHARDED_OK" in out.stdout


# ---------------------------------------------------------------------------
# partial participation
# ---------------------------------------------------------------------------


def test_full_participation_mask_equals_dense_path():
    data, reg, grad_fn, params0 = _problem(seed=4)
    supplier = _supplier(data, 3, 8)
    alg = _dprox(reg)
    s_dense, _ = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(chunk_rounds=2)), params0, supplier, 6)
    s_full, _ = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients,
                    EngineConfig(chunk_rounds=2, participation=1.0)),
        params0, supplier, 6)
    np.testing.assert_allclose(np.asarray(s_dense.x_bar["w"]),
                               np.asarray(s_full.x_bar["w"]),
                               rtol=1e-12, atol=1e-14)


def test_partial_participation_freezes_inactive_clients():
    data, reg, grad_fn, params0 = _problem(seed=5)
    alg = _dprox(reg)
    engine = RoundEngine(alg, grad_fn, data.n_clients,
                         EngineConfig(participation=0.5))
    state = engine.init(params0)
    rng = np.random.default_rng(0)
    # warm up so corrections are non-zero, then apply an explicit mask
    state, _ = engine.run(state, _supplier(data, 3, 8), 3, rng=rng)
    c_before = np.asarray(state.c["w"])
    active = np.zeros(data.n_clients, bool)
    active[:2] = True
    batches = make_round_batches(data, 3, 8, rng)
    state, _ = engine.step(state, batches, active=active)
    c_after = np.asarray(state.c["w"])
    np.testing.assert_array_equal(c_before[2:], c_after[2:])  # frozen
    assert np.abs(c_before[:2] - c_after[:2]).max() > 0  # participants moved


def test_partial_participation_trains():
    data, reg, grad_fn, params0 = _problem(seed=6)
    supplier = _supplier(data, 3, 8)
    alg = _dprox(reg, eta=0.05, eta_g=2.0)
    engine = RoundEngine(alg, grad_fn, data.n_clients,
                         EngineConfig(chunk_rounds=5, participation=0.5))
    state, metrics = _run_engine(engine, params0, supplier, 30)
    losses = metrics["train_loss"]
    assert len(losses) == 30
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    assert bool(tu.tree_isfinite(state.x_bar))


def test_participation_trajectory_invariant_to_chunking():
    """Mask draws interleave with batch draws per ROUND, so an rng-consuming
    supplier sees the same rng stream whatever the chunk size (regression:
    per-chunk mask sampling made the trajectory depend on chunk_rounds)."""
    data, reg, grad_fn, params0 = _problem(seed=8)
    alg = _dprox(reg)

    def rng_supplier(r, rng):  # consumes the SHARED rng, unlike _supplier
        return make_round_batches(data, 3, 8, rng)

    states = []
    for ch in (1, 4):
        engine = RoundEngine(alg, grad_fn, data.n_clients,
                             EngineConfig(chunk_rounds=ch, participation=0.5))
        state = engine.init(params0)
        state, _ = engine.run(state, rng_supplier, 6,
                              rng=np.random.default_rng(42))
        states.append(state)
    np.testing.assert_allclose(np.asarray(states[0].x_bar["w"]),
                               np.asarray(states[1].x_bar["w"]),
                               rtol=1e-12, atol=1e-14)


def test_sample_active_masks_shape_and_count():
    rng = np.random.default_rng(0)
    masks = sample_active_masks(10, 7, 0.3, rng)
    assert masks.shape == (7, 10) and masks.dtype == bool
    assert (masks.sum(axis=1) == 3).all()
    # at least one client participating even for tiny fractions
    assert (sample_active_masks(10, 5, 0.01, rng).sum(axis=1) == 1).all()


# ---------------------------------------------------------------------------
# baselines + config validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg_factory", [
    lambda reg: FedAvg(tau=3, eta=0.05),
    lambda reg: FedMid(reg, tau=3, eta=0.05),
    lambda reg: FedDA(reg, tau=3, eta=0.05, eta_g=2.0),
    lambda reg: FastFedDA(reg, tau=3, eta0=0.05),
    lambda reg: Scaffold(reg, tau=3, eta=0.05),
    lambda reg: FedProx(reg, tau=3, eta=0.05),
], ids=["fedavg", "fedmid", "fedda", "fast_fedda", "scaffold", "fedprox"])
def test_baselines_run_through_engine_chunked(alg_factory):
    data, reg, grad_fn, params0 = _problem(seed=7)
    supplier = _supplier(data, 3, 8)
    alg = alg_factory(reg)
    engine = RoundEngine(alg, grad_fn, data.n_clients,
                         EngineConfig(chunk_rounds=3))
    state, metrics = _run_engine(engine, params0, supplier, 6)
    assert len(metrics["train_loss"]) == 6
    assert np.isfinite(metrics["train_loss"]).all()
    assert bool(tu.tree_isfinite(engine.global_params(state)))


def test_engine_config_validation():
    data, reg, grad_fn, params0 = _problem()
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="warp").validate()
    with pytest.raises(ValueError, match="participation"):
        EngineConfig(participation=1.5).validate()
    with pytest.raises(ValueError, match="mesh"):
        EngineConfig(backend="sharded").validate()
    # unknown plans rejected up front, not deep inside sharding setup
    with pytest.raises(ValueError, match="plan"):
        EngineConfig(plan="C").validate()
    # missing param_specs gets an actionable message naming the fix
    with pytest.raises(ValueError, match="param_specs.*logical-axis"):
        EngineConfig(backend="sharded", mesh=object()).validate()
    with pytest.raises(ValueError, match="partial participation"):
        EngineConfig(backend="protocol", participation=0.5).validate()
    # baselines have no active-mask support -> constructing the engine fails
    with pytest.raises(ValueError, match="partial participation"):
        RoundEngine(FedAvg(tau=2, eta=0.1), grad_fn, data.n_clients,
                    EngineConfig(participation=0.5))
    # and no protocol form either
    with pytest.raises(ValueError, match="protocol"):
        RoundEngine(FedAvg(tau=2, eta=0.1), grad_fn, data.n_clients,
                    EngineConfig(backend="protocol"))


# ---------------------------------------------------------------------------
# chunk-aware batch suppliers
# ---------------------------------------------------------------------------


def test_array_supplier_chunk_matches_per_round():
    """The vectorized chunk gather produces exactly the per-round batches."""
    from repro.exec import ArraySupplier

    data, _, _, _ = _problem(seed=10)
    sup = ArraySupplier.from_dataset(data, tau=3, batch_size=5, seed=4)
    chunk = sup.sample_chunk(7, 4, None)
    for i in range(4):
        one = sup.sample_round(7 + i, None)
        for k in one:
            np.testing.assert_array_equal(np.asarray(chunk[k][i]),
                                          np.asarray(one[k]))
    assert chunk["a"].shape == (4, data.n_clients, 3, 5, 10)
    assert chunk["y"].shape == (4, data.n_clients, 3, 5)


def test_array_supplier_full_batch_mode():
    from repro.exec import ArraySupplier

    data, _, _, _ = _problem(seed=10)
    sup = ArraySupplier.from_dataset(data, tau=2, batch_size=None)
    one = sup.sample_round(0, None)
    assert one["a"].shape == (data.n_clients, 2, 30, 10)
    np.testing.assert_array_equal(np.asarray(one["a"][:, 0]), data.features)
    chunk = sup.sample_chunk(0, 3, None)
    assert chunk["a"].shape == (3, data.n_clients, 2, 30, 10)


def test_array_supplier_device_cache_matches_host():
    from repro.exec import ArraySupplier

    data, _, _, _ = _problem(seed=11)
    host = ArraySupplier.from_dataset(data, 3, 4, seed=6)
    dev = ArraySupplier.from_dataset(data, 3, 4, seed=6, device_cache=True)
    ch_h, ch_d = host.sample_chunk(2, 3, None), dev.sample_chunk(2, 3, None)
    assert isinstance(ch_d["a"], jax.Array)
    for k in ch_h:
        np.testing.assert_array_equal(np.asarray(ch_h[k]),
                                      np.asarray(ch_d[k]))


@pytest.mark.parametrize("device_cache", [False, True],
                         ids=["host", "device"])
def test_array_supplier_prefetch_matches_sync(device_cache):
    """Double-buffered chunk supply returns the same batches as the
    synchronous path, including across the remainder-chunk fallback and
    out-of-order requests (which discard the primed future)."""
    from repro.exec import ArraySupplier

    data, _, _, _ = _problem(seed=13)
    sync = ArraySupplier.from_dataset(data, 3, 4, seed=8,
                                      device_cache=device_cache)
    pre = ArraySupplier.from_dataset(data, 3, 4, seed=8,
                                     device_cache=device_cache, prefetch=True)
    # sequential chunks (primed), a remainder chunk, then a jump backwards
    for start, n in [(0, 4), (4, 4), (8, 2), (3, 4)]:
        a, b = sync.sample_chunk(start, n, None), pre.sample_chunk(start, n,
                                                                   None)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_prefetch_engine_trajectory_identical():
    from repro.exec import ArraySupplier

    data, reg, grad_fn, params0 = _problem(seed=14)
    alg = _dprox(reg)
    states = []
    for prefetch in (False, True):
        sup = ArraySupplier.from_dataset(data, 3, 8, seed=9,
                                         prefetch=prefetch)
        states.append(_run_engine(
            RoundEngine(alg, grad_fn, data.n_clients,
                        EngineConfig(chunk_rounds=4)), params0, sup, 10)[0])
    np.testing.assert_array_equal(np.asarray(states[0].x_bar["w"]),
                                  np.asarray(states[1].x_bar["w"]))


@pytest.mark.parametrize("device_cache", [False, True],
                         ids=["host", "device"])
def test_engine_trajectory_same_via_chunk_supplier(device_cache):
    """The engine's vectorized chunk path (sample_chunk, no host re-stack)
    computes the same trajectory as per-round supply of the same batches,
    for any chunk_rounds."""
    from repro.exec import ArraySupplier

    data, reg, grad_fn, params0 = _problem(seed=12)
    alg = _dprox(reg)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=7,
                                     device_cache=device_cache)
    # per-round path: wrap sample_round in a plain callable (the engine then
    # stacks on the host, the historical behavior)
    s_ref, m_ref = _run_engine(
        RoundEngine(alg, grad_fn, data.n_clients, EngineConfig(chunk_rounds=4)),
        params0, lambda r, rng: sup.sample_round(r, rng), 10)
    for ch in (1, 4):
        s_sup, m_sup = _run_engine(
            RoundEngine(alg, grad_fn, data.n_clients,
                        EngineConfig(chunk_rounds=ch)), params0, sup, 10)
        np.testing.assert_allclose(np.asarray(s_ref.x_bar["w"]),
                                   np.asarray(s_sup.x_bar["w"]),
                                   rtol=1e-12, atol=1e-14)
        np.testing.assert_allclose(m_ref["train_loss"], m_sup["train_loss"],
                                   rtol=1e-6)


# -- the engine's host spans and the round's layer scopes ---------------------

def _spans(bundle) -> list:
    """``[(name, t0, t1, args)]`` of a tracer's wire bundle."""
    import json

    names, args = bundle["names"], json.loads(bundle["args_json"])
    return [(names[int(i)], float(a), float(b), ar) for i, a, b, ar in
            zip(bundle["name_ix"], bundle["t0"], bundle["t1"], args)]


@pytest.mark.parametrize("stacked", [False, True], ids=["per_round", "chunk"])
def test_chunk_span_holds_the_engine_host_work(stacked):
    """Every supplier call, the host stack, the dispatch, the host sync and
    the per-round metrics callback of a chunk lie inside its exec/chunk."""
    from repro.exec import ArraySupplier
    from repro.obs import trace as obs_trace

    data, reg, grad_fn, params0 = _problem(seed=3)
    sup = ArraySupplier.from_dataset(data, 3, 8, seed=7)
    engine = RoundEngine(_dprox(reg), grad_fn, data.n_clients,
                         EngineConfig(chunk_rounds=2))
    cb_times = []
    tracer = obs_trace.install("test")
    try:
        engine.run(engine.init(params0),
                   sup if stacked else (lambda r, rng: sup.sample_round(r)),
                   4, seed=0,
                   metrics_cb=lambda r, m: cb_times.append(obs_trace.now()))
    finally:
        obs_trace.uninstall()
    spans = _spans(tracer.export_wire())
    chunks = [(a, b) for n, a, b, _ in spans if n == "exec/chunk"]
    assert len(chunks) == 2
    inner = ["exec/supply", "exec/dispatch", "exec/host_sync"]
    if not stacked:
        inner.append("exec/stack")
    for name in inner:
        found = [(a, b) for n, a, b, _ in spans if n == name]
        assert len(found) == 2, name
        for (a, b), (c0, c1) in zip(found, chunks):
            assert c0 <= a <= b <= c1, name
    assert len(cb_times) == 4
    for i, t in enumerate(cb_times):
        c0, c1 = chunks[i // 2]
        assert c0 <= t <= c1
    builds = [ar for n, _, _, ar in spans if n == "exec/build"]
    assert builds == [{"reason": "first"}]


def test_build_span_names_why_it_rebuilt():
    """exec/build says why the compiled call was (re)built: the first call,
    or a sink change that alters the compiled chunk's outputs."""
    from repro.comm import Dense
    from repro.obs import trace as obs_trace

    data, reg, grad_fn, params0 = _problem(seed=4)
    engine = RoundEngine(_dprox(reg), grad_fn, data.n_clients,
                         EngineConfig(chunk_rounds=2, transport=Dense()))
    supplier = _supplier(data, 3, 8)
    tracer = obs_trace.install("test")
    try:
        state = engine.init(params0)
        state, _ = engine.run(state, supplier, 2, seed=0)
        engine.set_uplink_sink(lambda r, msgs, st: None)
        engine.run(state, supplier, 2, seed=0, start_round=2)
    finally:
        obs_trace.uninstall()
    builds = [ar for n, _, _, ar in _spans(tracer.export_wire())
              if n == "exec/build"]
    assert builds == [{"reason": "first"}, {"reason": "sink"}]


#: the four layers' scopes, and how they may nest (the gradient inside the
#: client half)
LAYERS = {("fl.local",): "local", ("fl.local", "fl.grad"): "grad",
          ("fl.uplink",): "uplink", ("fl.server",): "server"}
#: what the chunk's scan body does outside the round, by the tail of its
#: op_name: the round's slice of the chunk's batches, the stacking of its
#: metrics, the loop counter, and broadcasts of constants XLA names by the
#: round's call
PLUMBING = ("dynamic_slice", "dynamic_update_slice", "add", "closed_call")


def _layer_of_instructions(hlo_text: str) -> dict:
    """``{layer or 'plumbing: <tail>': count}`` over the instructions of the
    chunk's scan body (op_name under ``jit(...)/while/body/``), by the set
    of ``fl.*`` scope segments of their op_name.  Parameters and constants
    run nothing and are left out."""
    import re
    from collections import Counter

    out = Counter()
    for line in hlo_text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%[\w.\-]+ = .*? ([\w\-]+)\(.*'
                     r'op_name="(jit\(\w+\)/while/body/[^"]*)"', line)
        if not m or m.group(1) in ("parameter", "constant"):
            continue
        tokens = tuple(dict.fromkeys(re.findall(r"\bfl\.\w+", m.group(2))))
        if tokens:
            out[LAYERS.get(tokens, f"mixed: {tokens}")] += 1
        else:
            out[f"plumbing: {m.group(2).rsplit('/', 1)[-1]}"] += 1
    return out


@pytest.mark.parametrize("uplink", [False, True], ids=["fused", "plane_topk"])
def test_round_layers_carry_their_scopes(uplink):
    """Every instruction of a DProx CNN round carries the scope of exactly
    one layer (the gradient nested in the client half), on the fused path
    and on the plane + global top-k path; what carries none is the chunk
    scan's own plumbing."""
    from repro.comm import TopK
    from repro.exec.engine import _stack_batches
    from repro.models import cnn

    alg = DProxAlgorithm(L1(lam=1e-4), A.DProxConfig(tau=2, eta=0.005,
                                                     eta_g=1.5))
    kw = (dict(plane=True, transport=TopK(ratio=0.1, granularity="global"))
          if uplink else {})
    engine = RoundEngine(alg, cnn.make_grad_fn(), 2,
                         EngineConfig(chunk_rounds=2, **kw))
    rng = np.random.default_rng(0)

    def supplier(r, rng):
        return {"x": rng.standard_normal((2, 2, 2, 28, 28, 1), np.float32),
                "y": rng.integers(0, 10, (2, 2, 2)).astype(np.int32)}

    state = engine.init(cnn.init_params(jax.random.PRNGKey(0)))
    state, _ = engine.run(state, supplier, 2, rng=rng)
    carry = (state, engine._extras) if uplink else state
    batches = _stack_batches([supplier(0, rng), supplier(1, rng)])
    text = engine._chunked_call.lower(carry, batches, None).compile().as_text()
    layers = _layer_of_instructions(text)
    assert not [k for k in layers if k.startswith("mixed")], layers
    assert {k for k in layers if k.startswith("plumbing")} <= {
        f"plumbing: {t}" for t in PLUMBING}, layers
    assert {"local", "grad", "server"} <= set(layers), layers
    assert ("uplink" in layers) == uplink, layers
