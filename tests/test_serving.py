"""Serving-plane tests: snapshot atomicity, delta publication, decode
parity, and the engine's snapshot sink.

The load-bearing pins:

  * a reader never observes a torn or version-inconsistent snapshot while
    a writer publishes concurrently (the atomic-swap contract);
  * a delta-fed replica reconstructs every published plane **bitwise**
    (XOR bit-pattern deltas; ``-0.0`` and NaN payloads included), across
    dense/sparse/palette frame encodings and keyframe cadences, and a
    late joiner locks on at the next keyframe;
  * the scan decode (``ServingEngine.generate``) produces bitwise the
    greedy tokens of the per-token loop (``generate_loop``), and
    continuous batching (``serve``) produces bitwise the sequential
    per-request trajectories;
  * ``RoundEngine.set_snapshot_sink`` publishes each committed chunk's
    state without perturbing the trajectory, composes with the async
    stage and the uplink sink, and refuses the protocol form.

MLA (deepseek) is excluded from batched-decode parity: XLA CPU gemm
blocking makes its einsum shapes batch-size-sensitive at the ~1e-6 level
even on the seed path, so row independence does not hold bitwise there.
"""
import os
import sys
import threading
import traceback

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from repro.comm import wire
from repro.serving import (DeltaPublisher, DeltaReplica, Request,
                           ServingEngine, ServingSnapshot, SnapshotGap,
                           SnapshotStore, apply_delta, tree_digest,
                           xor_delta)


def _tree_bytes(tree) -> bytes:
    return b"".join(np.asarray(leaf).tobytes()
                    for leaf in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# snapshot store
# ---------------------------------------------------------------------------


class TestSnapshotStore:
    def test_publish_versions_and_double_buffer(self):
        store = SnapshotStore()
        assert store.latest() is None and store.version == 0
        s1 = store.publish({"w": np.ones(3)}, round=4)
        s2 = store.publish({"w": np.full(3, 2.0)}, round=8)
        assert (s1.version, s2.version) == (1, 2)
        assert store.latest() is s2 and store.previous() is s1
        assert store.latest().round == 8

    def test_atomic_swap_under_writer_thread(self):
        """Readers racing a publisher must only ever see internally
        consistent snapshots (both leaves carry the same version stamp)
        with monotonically nondecreasing versions."""
        store = SnapshotStore()
        n_versions = 300
        stop = threading.Event()
        errs = []

        def read():
            last = 0
            while not stop.is_set():
                snap = store.latest()
                if snap is None:
                    continue
                a, b = snap.value["a"], snap.value["b"]
                if not (a[0] == b[0] == float(snap.version)):
                    errs.append(f"torn read at v{snap.version}: "
                                f"{a[0]} vs {b[0]}")
                    return
                if snap.version < last:
                    errs.append(f"version went backwards: {snap.version} "
                                f"< {last}")
                    return
                last = snap.version

        readers = [threading.Thread(target=read, daemon=True)
                   for _ in range(4)]
        for t in readers:
            t.start()
        for v in range(1, n_versions + 1):
            val = float(v)
            store.publish({"a": np.full(8, val), "b": np.full(8, val)},
                          round=v)
        stop.set()
        for t in readers:
            t.join(10)
        assert not errs, errs
        assert store.version == n_versions

    def test_wait_for_and_timeout(self):
        store = SnapshotStore()
        assert store.wait_for(1, timeout=0.05) is None

        def late_publish():
            store.publish({"w": np.zeros(1)})

        threading.Timer(0.05, late_publish).start()
        snap = store.wait_for(1, timeout=5.0)
        assert snap is not None and snap.version == 1

    def test_subscribe_fires_per_publish(self):
        store = SnapshotStore()
        seen = []
        store.subscribe(lambda s: seen.append(s.version))
        store.publish({"w": np.zeros(1)})
        store.publish({"w": np.ones(1)})
        assert seen == [1, 2]


# ---------------------------------------------------------------------------
# xor deltas
# ---------------------------------------------------------------------------


class TestXorDelta:
    def test_bitwise_involution_with_weird_floats(self):
        """-0.0 and NaN payloads must survive: XOR operates on bit
        patterns, so reconstruction is exact where float arithmetic is
        not."""
        nan_payload = np.array([np.float64("nan")])
        shadow = {"w": np.array([1.0, -0.0, np.inf, 0.1]),
                  "b": np.float32([3.5, -2.25])}
        new = {"w": np.array([1.0, 0.0, nan_payload[0], 0.30000000000000004]),
               "b": np.float32([3.5, -2.25])}
        delta = xor_delta(new, shadow)
        rec = apply_delta(shadow, delta)
        assert _tree_bytes(rec) == _tree_bytes(new)
        # unchanged coordinates XOR to exactly zero bits (the sparsity
        # pack_plane's sparse encoding exploits)
        assert delta["b"].view(np.uint32).sum() == 0
        assert delta["w"].view(np.uint64)[0] == 0

    def test_mismatched_leaves_raise(self):
        with pytest.raises(ValueError):
            xor_delta({"w": np.zeros(3)}, {"w": np.zeros(4)})
        with pytest.raises(ValueError):
            xor_delta({"w": np.zeros(3, np.float32)},
                      {"w": np.zeros(3, np.float64)})


# ---------------------------------------------------------------------------
# delta publication / replica reconstruction
# ---------------------------------------------------------------------------


def _plane_stream(n_versions: int, seed: int = 0):
    """Training-like commits: a few coordinates move per version."""
    rng = np.random.default_rng(seed)
    plane = {"w": rng.standard_normal(64), "b": rng.standard_normal(4)}
    for v in range(1, n_versions + 1):
        plane = {k: a.copy() for k, a in plane.items()}
        ix = rng.choice(64, size=3, replace=False)
        plane["w"][ix] += rng.standard_normal(3)
        if v % 2:
            plane["b"][v % 4] = -plane["b"][v % 4]
        yield v, plane


class TestDeltaReplica:
    @pytest.mark.parametrize("encoding", ["dense", "sparse", "palette"])
    def test_bitwise_reconstruction(self, encoding):
        pub = DeltaPublisher(keyframe_every=3, encoding=encoding)
        rep = DeltaReplica()
        kinds = []
        for v, plane in _plane_stream(7):
            frame = pub.encode(ServingSnapshot(version=v, round=v,
                                               value=plane))
            kinds.append(frame["kind"])
            out = rep.apply(frame)
            assert out is not None
            assert _tree_bytes(out.value) == _tree_bytes(plane)
        # first frame is a keyframe, then every version divisible by 3
        assert kinds == ["key", "delta", "key", "delta", "delta", "key",
                         "delta"]
        assert rep.applied == 7 and rep.skipped == 0

    def test_late_join_locks_on_at_keyframe(self):
        pub = DeltaPublisher(keyframe_every=3)
        frames = [pub.encode(ServingSnapshot(version=v, round=v, value=p))
                  for v, p in _plane_stream(6)]
        rep = DeltaReplica()
        # join mid-stream: deltas before the first keyframe are skipped
        assert rep.apply(frames[1]) is None       # v2 delta, no base
        assert rep.apply(frames[2]) is not None   # v3 keyframe: locked on
        assert rep.apply(frames[3]) is not None   # v4 delta applies
        assert rep.skipped == 1 and rep.applied == 2

    def test_gap_raises(self):
        pub = DeltaPublisher(keyframe_every=100)
        frames = [pub.encode(ServingSnapshot(version=v, round=v, value=p))
                  for v, p in _plane_stream(3)]
        rep = DeltaReplica()
        rep.apply(frames[0])
        with pytest.raises(SnapshotGap):
            rep.apply(frames[2])  # base v2, replica holds v1

    def test_digest_mismatch_raises(self):
        pub = DeltaPublisher()
        (v, plane), = list(_plane_stream(1))
        frame = pub.encode(ServingSnapshot(version=v, round=v, value=plane))
        frame["digest"] ^= 1
        with pytest.raises(wire.WireError):
            DeltaReplica().apply(frame)

    def test_wire_roundtrip_and_republish(self):
        """Frames survive the actual wire codec, and a replica-side store
        republishes every reconstructed plane."""
        store = SnapshotStore()
        pub = DeltaPublisher(keyframe_every=4, encoding="sparse")
        rep = DeltaReplica(store=store)
        last = None
        for v, plane in _plane_stream(5):
            buf = wire.encode_frame(
                wire.T_SNAP,
                pub.encode(ServingSnapshot(version=v, round=v, value=plane)))
            ftype, frame, _ = wire.decode_frame(buf)
            assert ftype == wire.T_SNAP
            rep.apply(frame)
            last = plane
        assert store.version == 5
        assert _tree_bytes(store.latest().value) == _tree_bytes(last)


# ---------------------------------------------------------------------------
# engine snapshot sink
# ---------------------------------------------------------------------------


def _logreg_engine(config=None, n=6, seed=0, alg=None):
    from repro.core import algorithm as A
    from repro.core.prox import L1
    from repro.data.synthetic import logistic_heterogeneous
    from repro.exec import EngineConfig, RoundEngine
    from repro.fed.simulator import DProxAlgorithm
    from repro.models import logreg

    d = 10
    data = logistic_heterogeneous(n_clients=n, m_per_client=30, d=d,
                                  alpha=5, beta=5, seed=seed)
    s = np.linalg.norm(data.features.reshape(-1, d), axis=1).max()
    data.features = (data.features / s).astype(np.float64)
    data.labels = data.labels.astype(np.float64)
    alg = alg or DProxAlgorithm(L1(lam=0.01),
                                A.DProxConfig(tau=3, eta=0.05, eta_g=2.0))
    eng = RoundEngine(alg, logreg.make_grad_fn(), data.n_clients,
                      config or EngineConfig(chunk_rounds=4))
    params0 = {"w": jnp.zeros(d, jnp.float64),
               "b": jnp.zeros((), jnp.float64)}

    def supplier(r, rng):
        from repro.data.synthetic import make_round_batches

        return make_round_batches(data, 3, 8,
                                  np.random.default_rng(10_000 + r))

    return eng, params0, supplier


class TestEngineSnapshotSink:
    def test_publishes_per_chunk_bitwise_unperturbed(self):
        from repro.exec import EngineConfig

        store = SnapshotStore()
        rounds_seen = []
        store.subscribe(lambda s: rounds_seen.append((s.version, s.round)))
        eng, params0, sup = _logreg_engine()
        eng.set_snapshot_sink(store.engine_sink(select=lambda st: st.x_bar))
        state = eng.init(params0)
        state, _ = eng.run(state, sup, 11, seed=0)
        # chunk_rounds=4, 11 rounds -> chunks end at rounds 4, 8, 11
        assert rounds_seen == [(1, 4), (2, 8), (3, 11)]
        assert _tree_bytes(store.latest().value) == _tree_bytes(state.x_bar)

        eng2, params0, sup = _logreg_engine()
        st2 = eng2.init(params0)
        st2, _ = eng2.run(st2, sup, 11, seed=0)
        assert _tree_bytes(st2.x_bar) == _tree_bytes(state.x_bar)

    def test_snapshot_survives_carry_donation(self, monkeypatch):
        """FedAvg's global model IS its carry (``state.x``).  On an
        accelerator the next chunk's call donates that carry, so the sink
        must publish a copy: snapshot v1, read after chunk 2, is intact and
        bitwise the one a non-donating run publishes."""
        from repro.core.baselines import FedAvg
        from repro.exec import EngineConfig
        from repro.exec import engine as engine_mod

        def first_snapshot(donating: bool):
            monkeypatch.setattr(engine_mod, "donates", lambda: donating)
            store = SnapshotStore()
            eng, params0, sup = _logreg_engine(
                EngineConfig(chunk_rounds=2), alg=FedAvg(tau=3, eta=0.05))
            eng.set_snapshot_sink(store.engine_sink(select=eng.global_params))
            state0 = eng.init(params0)
            eng.run(state0, sup, 4, seed=0)  # two chunks
            # the donating build really donated: the initial carry is gone
            assert all(leaf.is_deleted() == donating
                       for leaf in jax.tree_util.tree_leaves(state0))
            v1 = store.previous()
            assert (v1.version, v1.round, store.version) == (1, 2, 2)
            return _tree_bytes(v1.value)

        assert first_snapshot(True) == first_snapshot(False)

    def test_protocol_blocked(self):
        from repro.exec import EngineConfig

        eng, _, _ = _logreg_engine(EngineConfig(protocol=True))
        with pytest.raises(ValueError, match="protocol"):
            eng.set_snapshot_sink(SnapshotStore().engine_sink())

    def test_composes_with_async_and_uplink_sink(self):
        from repro.comm import Dense
        from repro.exec import EngineConfig

        store = SnapshotStore()
        eng, params0, sup = _logreg_engine(
            EngineConfig(chunk_rounds=4, clock="deterministic",
                         buffer_size=3))
        eng.set_snapshot_sink(store.engine_sink(select=lambda s: s.x_bar))
        state = eng.init(params0)
        eng.run(state, sup, 8, seed=0)
        assert store.version == 2

        # uplink sink + snapshot sink on the same split engine
        taps = []
        store2 = SnapshotStore()
        eng2, params0, sup = _logreg_engine(
            EngineConfig(chunk_rounds=4, transport=Dense()))
        eng2.set_uplink_sink(lambda r, msgs, st: taps.append(r))
        eng2.set_snapshot_sink(store2.engine_sink(select=lambda s: s.x_bar))
        st = eng2.init(params0)
        eng2.run(st, sup, 8, seed=0)
        assert taps == [0, 4] and store2.version == 2

    def test_sink_blockers_kinds(self):
        from repro.exec.stages import Asynchrony, StageStack, sink_blockers

        sync = StageStack()
        assert sink_blockers(sync, participation=False, jit=True,
                             kind="snapshot") == ()
        assert sink_blockers(StageStack(protocol=True), participation=False,
                             jit=True, kind="snapshot") == ("protocol",)
        asy = StageStack(asynchrony=Asynchrony())
        assert sink_blockers(asy, participation=False, jit=True,
                             kind="snapshot") == ()
        assert "asynchrony" in sink_blockers(asy, participation=False,
                                             jit=True, kind="uplink")
        with pytest.raises(ValueError):
            sink_blockers(sync, participation=False, jit=True, kind="nope")


# ---------------------------------------------------------------------------
# decode parity: loop == scan == continuous batching
# ---------------------------------------------------------------------------


def _smoke_lm(arch: str):
    from repro.configs import registry
    from repro.models import transformer as T

    cfg = registry.get_smoke(arch).with_overrides(param_dtype=jnp.float32)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, b=2, s=12):
    return (np.arange(b * s, dtype=np.int32).reshape(b, s) * 7) % cfg.vocab


class TestDecodeParity:
    def test_loop_scan_greedy_bitwise_stablelm(self):
        cfg, params = _smoke_lm("stablelm_1_6b")
        eng = ServingEngine(cfg, params, max_len=48)
        p = _prompts(cfg)
        r_loop = eng.generate_loop(p, max_new_tokens=8)
        r_scan = eng.generate(p, max_new_tokens=8)
        np.testing.assert_array_equal(r_loop.tokens, r_scan.tokens)
        np.testing.assert_array_equal(r_loop.logprobs, r_scan.logprobs)

    @pytest.mark.parametrize("arch", ["gemma2_9b", "mamba2_130m"])
    def test_loop_scan_tokens_bitwise(self, arch):
        """Greedy tokens pin bitwise across cache layouts (ring-buffer
        sliding window, SSM state); logprobs may differ at float-fusion
        noise (gemma2's logit softcap fuses differently inside the scan)."""
        cfg, params = _smoke_lm(arch)
        eng = ServingEngine(cfg, params, max_len=48)
        p = _prompts(cfg)
        r_loop = eng.generate_loop(p, max_new_tokens=6)
        r_scan = eng.generate(p, max_new_tokens=6)
        np.testing.assert_array_equal(r_loop.tokens, r_scan.tokens)
        np.testing.assert_allclose(r_loop.logprobs, r_scan.logprobs,
                                   rtol=0, atol=1e-5)

    def test_loop_scan_sampled_bitwise_stablelm(self):
        """temperature > 0: the scan mirrors the loop's key stream
        (split-then-sample per step), so sampled trajectories pin too."""
        cfg, params = _smoke_lm("stablelm_1_6b")
        eng = ServingEngine(cfg, params, max_len=48)
        p = _prompts(cfg)
        r_loop = eng.generate_loop(p, max_new_tokens=8, temperature=0.8,
                                   seed=3)
        r_scan = eng.generate(p, max_new_tokens=8, temperature=0.8, seed=3)
        np.testing.assert_array_equal(r_loop.tokens, r_scan.tokens)

    def test_continuous_batching_matches_sequential(self):
        """Batched-with-admission trajectories == sequential per-request
        greedy decode, mixed prompt/output lengths, fewer slots than
        requests."""
        cfg, params = _smoke_lm("stablelm_1_6b")
        eng = ServingEngine(cfg, params, max_len=64)
        reqs = [Request(id=i,
                        prompt=_prompts(cfg, b=1, s=6 + 3 * (i % 3))[0],
                        max_new_tokens=(5, 9, 7, 5, 12)[i])
                for i in range(5)]
        results = eng.serve(reqs, slots=2, segment=3)
        assert [r.id for r in results] == [0, 1, 2, 3, 4]
        for r in results:
            seq = eng.generate(reqs[r.id].prompt[None, :],
                               max_new_tokens=reqs[r.id].max_new_tokens)
            np.testing.assert_array_equal(r.tokens, seq.tokens[0])
        assert eng.metrics.counter("serve/requests").value == 5
        assert eng.metrics.counter("serve/tokens").value >= 38

    def test_hot_swap_between_segments(self):
        """A plane published mid-serve is adopted at a segment boundary:
        later admissions record the newer snapshot version."""
        from repro.models import transformer as T

        cfg, params = _smoke_lm("stablelm_1_6b")
        store = SnapshotStore()
        store.publish(params, round=0)
        eng = ServingEngine(cfg, params=None, snapshots=store, max_len=64)
        assert eng.refresh() is params and eng.snapshot_version == 1

        bumped = jax.tree_util.tree_map(lambda a: a * 1.01, params)
        store.publish(bumped, round=1)
        r = eng.generate(_prompts(cfg), max_new_tokens=4)
        assert eng.snapshot_version == 2
        assert r.tokens.shape == (2, 4)
        # served tokens come from the NEW plane
        eng2 = ServingEngine(cfg, bumped, max_len=64)
        np.testing.assert_array_equal(
            r.tokens, eng2.generate(_prompts(cfg), max_new_tokens=4).tokens)


# ---------------------------------------------------------------------------
# replica over the real runtime (threaded: same sockets as subprocesses)
# ---------------------------------------------------------------------------


def test_runtime_replica_bitwise_threaded():
    from repro.fed.runtime import (RuntimeArgs, run_replica, run_server,
                                   run_worker)

    a = RuntimeArgs(clients=8, m=16, dim=24, tau=2, rounds=8, chunk=2,
                    workers=1, replicas=1, keyframe_every=2,
                    mode="blocking", timeout=60.0)
    box, errs = {}, []
    ready = threading.Event()

    def srv():
        try:
            box["server"] = run_server(
                a, ready_cb=lambda p: (box.update(port=p), ready.set()))
        except BaseException:
            errs.append(traceback.format_exc())
            ready.set()

    st = threading.Thread(target=srv, daemon=True)
    st.start()
    assert ready.wait(30), "server never bound"
    assert "port" in box, f"server failed: {errs}"
    a.port = box["port"]

    def repl():
        try:
            box["replica"] = run_replica(a, rank=0)
        except BaseException:
            errs.append(traceback.format_exc())

    rt = threading.Thread(target=repl, daemon=True)
    rt.start()
    box["worker"] = run_worker(a, rank=0)
    rt.join(60)
    st.join(60)
    assert not errs, f"runtime thread failed: {errs}"
    rep = box["replica"]
    assert rep["ok"], "replica reconstruction not bitwise"
    assert rep["applied"] >= 1 and rep["keyframes"] >= 1
    assert rep["version"] == box["server"]["version"]
