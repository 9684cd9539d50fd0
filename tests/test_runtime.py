"""Multi-process runtime tests: the bitwise parity pin and the protocol.

The load-bearing acceptance pin: a server process + worker exchanging real
frames over a localhost socket produces a server trajectory BITWISE
identical to the single-process engine, for the dense transport and for a
ratio-1.0 top-k (whose compressed output equals its input exactly), in
both blocking and overlapped modes, per-leaf and plane layouts.

The server runs on a background thread in-process (same socket machinery
as the subprocess path -- ``run_pair`` drives the true 2-process form, and
the CI bench-smoke job runs ``--role pair --check-parity`` as separate OS
processes); the slow marker keeps the full subprocess variant out of the
fast CI leg.
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

from repro.fed.runtime import (RuntimeArgs, _fields_bitwise, run_local,
                               run_pair, run_server, run_worker,
                               shard_bounds)


def _base_args(**kw) -> RuntimeArgs:
    defaults = dict(clients=8, m=16, dim=24, tau=2, rounds=8, chunk=4,
                    workers=1, mode="blocking", timeout=60.0)
    defaults.update(kw)
    return RuntimeArgs(**defaults)


def _run_threaded(a: RuntimeArgs):
    """Server on a thread + ranks on threads (rank 0 inline): same sockets
    and frames as the subprocess form, with in-test error propagation."""
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

    wthreads = []
    for rank in range(1, a.workers):
        def wrk(r=rank):
            try:
                box[f"worker{r}"] = run_worker(a, rank=r)
            except BaseException:
                errs.append(traceback.format_exc())

        t = threading.Thread(target=wrk, daemon=True)
        t.start()
        wthreads.append(t)
    box["worker0"] = run_worker(a, rank=0)
    for t in wthreads:
        t.join(60)
    st.join(60)
    assert not errs, f"runtime thread failed: {errs}"
    return box


class TestShardBounds:
    def test_even(self):
        assert shard_bounds(8, 2) == [(0, 4), (4, 8)]

    def test_remainder_spread(self):
        assert shard_bounds(10, 3) == [(0, 4), (4, 7), (7, 10)]

    def test_single(self):
        assert shard_bounds(5, 1) == [(0, 5)]


@pytest.mark.parametrize("mode", ["blocking", "overlapped"])
@pytest.mark.parametrize("transport,kw", [
    ("dense", {}),
    ("topk", {"ratio": 1.0}),
])
def test_two_process_bitwise_parity(mode, transport, kw):
    """THE acceptance pin: server state == single-process engine, bit for
    bit, dense and ratio-1.0 transports, both send modes."""
    a = _base_args(mode=mode, transport=transport, **kw)
    box = _run_threaded(a)
    local = run_local(_base_args(mode=mode, transport=transport, **kw))
    assert _fields_bitwise(local["fields"], box["server"]["fields"])
    # and the replay (zero client aux) tracked the worker's own commit
    assert box["server"]["max_replay_drift"] == 0.0


def test_plane_layout_parity():
    """Plane mode: the uplink crosses as ONE flat buffer per chunk and the
    pin still holds."""
    a = _base_args(plane=True, mode="overlapped")
    box = _run_threaded(a)
    local = run_local(_base_args(plane=True, mode="overlapped"))
    assert _fields_bitwise(local["fields"], box["server"]["fields"])


def test_compressed_transport_parity_and_byte_savings():
    """Top-k at low ratio: the worker's server fields (its own committed
    trajectory) install verbatim -- still bitwise vs local -- and the
    sparse wire frames are measurably smaller than dense ones."""
    dense = _run_threaded(_base_args(mode="blocking"))
    topk = _run_threaded(_base_args(mode="blocking", transport="topk",
                                    ratio=0.1))
    local = run_local(_base_args(transport="topk", ratio=0.1))
    assert _fields_bitwise(local["fields"], topk["server"]["fields"])
    assert (topk["worker0"]["bytes_sent"]
            < 0.7 * dense["worker0"]["bytes_sent"])


def test_quantize_palette_parity():
    a = _base_args(transport="quantize", bits=4, mode="overlapped")
    box = _run_threaded(a)
    local = run_local(_base_args(transport="quantize", bits=4,
                                 mode="overlapped"))
    assert _fields_bitwise(local["fields"], box["server"]["fields"])


def test_arrival_ledger_records_real_arrivals():
    a = _base_args(rounds=8, chunk=2)  # 4 chunks -> 4 arrivals
    box = _run_threaded(a)
    led = box["server"]["ledger"]
    assert led["arrivals"] == 4
    assert led["workers"] == 1
    assert led["bytes"] == box["worker0"]["bytes_sent"]
    assert box["server"]["version"] == 4
    # blocking mode ACKs each chunk before the next computes: age 0
    assert led["max_age"] == 0
    assert np.asarray(box["server"]["age_histogram"]).sum() == 4


def test_two_workers_fedbuff_converges():
    """N=2 is the chunk-FedBuff semantics (documented as non-bitwise):
    both shards commit, every arrival lands in the ledger, and the mixed
    server fields stay finite and move from init."""
    a = _base_args(workers=2, rounds=8, chunk=4, mode="overlapped")
    box = _run_threaded(a)
    res = box["server"]
    assert res["ledger"]["workers"] == 2
    assert res["version"] == 4  # 2 workers x 2 chunks
    w = np.asarray(res["fields"]["x_bar"]["w"])
    assert np.all(np.isfinite(w)) and np.abs(w).max() > 0


def test_overlapped_matches_blocking_bitwise():
    """The overlap pipeline changes WHEN bytes move, never WHAT commits."""
    b = _run_threaded(_base_args(mode="blocking"))
    o = _run_threaded(_base_args(mode="overlapped"))
    assert _fields_bitwise(b["server"]["fields"], o["server"]["fields"])


def test_worker_report_accounting():
    a = _base_args(mode="blocking")
    box = _run_threaded(a)
    rep = box["worker0"]
    assert rep["chunks"] == 2
    assert rep["bytes_sent"] > 0
    assert rep["send_wait_s"] >= 0.0
    assert rep["rounds"] == a.rounds
    assert "train_loss" in rep["metrics"]


def test_overlapped_sender_survives_carry_donation(monkeypatch):
    """On an accelerator the engine donates its carry to the next chunk's
    call while the sender thread still ships the last one: the sender
    works from its own copy of the committed fields, so the pin holds."""
    from repro.exec import engine as engine_mod

    monkeypatch.setattr(engine_mod, "donates", lambda: True)
    box = _run_threaded(_base_args(mode="overlapped", rounds=16, chunk=2))
    local = run_local(_base_args(mode="overlapped", rounds=16, chunk=2))
    assert _fields_bitwise(local["fields"], box["server"]["fields"])


def test_spawned_server_and_replicas_stay_off_the_accelerator(monkeypatch):
    """Only workers may initialise the accelerator: the server and the
    replicas do host work, so their processes are pinned to the CPU."""
    from repro.fed import runtime

    envs = {}

    def fake_popen(argv, env):
        envs[argv[argv.index("--role") + 1]] = env
        return None

    monkeypatch.setattr(runtime.subprocess, "Popen", fake_popen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    a = _base_args()
    for role in ("server", "replica", "worker"):
        runtime._spawn(a, role, rank=1)
    assert envs["server"]["JAX_PLATFORMS"] == "cpu"
    assert envs["replica"]["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in envs["worker"]


@pytest.mark.parametrize("why", ["more_workers_than_chips",
                                 "backend_started"])
def test_pair_refuses_tpu_workers_it_cannot_place(monkeypatch, why):
    """On a TPU host each worker process needs a chip of its own, and this
    process must be confined to chip 0 before it starts a backend: a run
    that cannot have both fails before any process starts."""
    from repro.fed import runtime

    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setattr(runtime, "_tpu_chips", lambda: 2)
    monkeypatch.setattr(runtime, "_backend_started", lambda: True)
    monkeypatch.setattr(runtime, "_spawn", lambda *a, **k: pytest.fail(
        "spawned a process"))
    workers, err = {"more_workers_than_chips": (3, ValueError),
                    "backend_started": (2, RuntimeError)}[why]
    with pytest.raises(err, match="chip"):
        run_pair(_base_args(workers=workers))


def test_pair_gives_each_tpu_worker_its_own_chip(monkeypatch):
    """Worker ``w`` is confined to chip ``w`` (this process, rank 0, to
    chip 0), each as a one-chip slice with a port of its own; the server
    gets no chip."""
    from repro.fed import runtime

    for key in runtime._chip_env(0, 0):
        monkeypatch.setenv(key, "")  # restored when the test ends
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    monkeypatch.setattr(runtime, "_tpu_chips", lambda: 4)
    monkeypatch.setattr(runtime, "_backend_started", lambda: False)
    spawned = {}

    class _Done:
        @staticmethod
        def wait(timeout=None):
            return 0

        poll = wait

    def fake_spawn(a, role, rank=0, chip_env=None):
        spawned[role, rank] = chip_env
        return _Done()

    monkeypatch.setattr(runtime, "_spawn", fake_spawn)
    monkeypatch.setattr(runtime, "run_worker", lambda a, rank: dict(
        os.environ))
    env0 = run_pair(_base_args(workers=3))
    envs = [env0, spawned["worker", 1], spawned["worker", 2]]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 3
    assert spawned["server", 0] is None


@pytest.mark.slow
def test_true_subprocess_pair_parity():
    """The real thing: one server OS process + one worker OS process
    (rank 0 in this process), bitwise vs single-process."""
    a = _base_args(mode="overlapped")
    rep = run_pair(a)
    local = run_local(_base_args(mode="overlapped"))
    assert _fields_bitwise(local["fields"], rep["server_result"]["fields"])
    assert rep["server_result"]["max_replay_drift"] == 0.0


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
