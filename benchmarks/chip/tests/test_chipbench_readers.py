"""Each per-layer reader on a small hand-made trace whose numbers are
worked out by hand, and on traces recorded on a TPU v5e and committed
trimmed (``data/``), with the numbers they must give written beside them."""
from __future__ import annotations

import pathlib

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import trace as tr
from chipbench.cell import Context
from chipbench.registry import Registry

MS = 1e6  # ns
DATA = pathlib.Path(__file__).resolve().parent / "data"


def _hand_trace():
    # window 0..10 ms; chip 0 busy 1-3 (fusion), 4-5 (sort), 5-6 (select
    # kernel), 8-9 (all-reduce); chip 1 busy 0-2 (all-reduce), 2-4 (fusion)
    chip0 = [["fusion.1", 1 * MS, 2 * MS], ["sort.3", 4 * MS, 1 * MS],
             ["threshold_select_3d.2", 5 * MS, 1 * MS],
             ["all-reduce.7", 8 * MS, 1 * MS]]
    chip1 = [["all-reduce.7", 0, 2 * MS], ["fusion.1", 2 * MS, 2 * MS]]
    host = [["bench/window", 0, 10 * MS], ["bench/chunk", 0, 5 * MS],
            ["bench/supply", 0.5 * MS, 1 * MS],
            ["bench/chunk", 5 * MS, 5 * MS],
            ["bench/supply", 6 * MS, 1.5 * MS],
            ["bench/supply", 12 * MS, 1 * MS]]  # after the window
    return tr.Trace([
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": chip0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops",
                                             "events": chip1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ])


def _ctx(trace, rounds=4, chips=2, **kw):
    args = dict(chips=chips,
                peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                setup_s=1.0, window_s=0.01, chunk_s=[0.004, 0.006],
                rounds=rounds, samples=1000, flops_per_sample=1e9,
                compiles_in_window=0,
                memory={"peak_bytes": 4e9, "limit_bytes": 16e9},
                trace=trace)
    args.update(kw)
    return Context(**args)


def _read(name, ctx):
    return Registry().metric_reader(name)(ctx)


def test_hand_trace():
    ctx = _ctx(_hand_trace())
    # chip 0 busy 5 of 10 ms, chip 1 busy 4 of 10: idle (50 + 60) / 2
    assert _read("device_idle_share", ctx) == pytest.approx(55.0)
    # supply spans inside the window: 1 + 1.5 ms over 4 rounds
    assert _read("supply_ms_per_round", ctx) == pytest.approx(0.625)
    # the sort on chip 0: 1 ms over 4 rounds
    assert _read("uplink_topk_ms_per_round", ctx) == pytest.approx(0.25)
    # the select kernel on chip 0: 1 ms over 4 rounds
    assert _read("uplink_select_ms_per_round", ctx) == pytest.approx(0.25)
    # 1e9 FLOP x 1000 samples / 0.01 s over 2 chips x 197 TFLOP/s
    assert _read("train_mfu", ctx) == pytest.approx(100 * 1e14 / 3.94e14)
    assert _read("peak_hbm_share", ctx) == pytest.approx(25.0)
    assert _read("compiles_in_window", ctx) == 0.0
    assert _read("samples_per_s", ctx) == pytest.approx(1e5)
    assert _read("chunk_ms_p95", ctx) == pytest.approx(5.9)
    assert _read("setup_s", ctx) == 1.0


def test_breakdown_names_gaps_by_host_span():
    bd = tr.breakdown(_hand_trace())
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(4e-3)]
    gaps = sorted((round(s * 1e3, 6), name) for name, s in bd["idle_gaps"])
    # the loop that encloses the others is left out of the op list
    assert "while.1" not in [n for n, _ in tr.breakdown(tr.Trace([
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["while.1", 0, 9 * MS], ["fusion.1", 1 * MS, 2 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench/window", 0, 10 * MS]]}]}]))["device_ops"]]
    # chip 0 idles 0-1 (a supply span open at its middle), 3-4 (chunk),
    # 6-8 (a supply span at its middle, 7 ms) and 9-10 (chunk)
    assert gaps == [(1.0, "bench/chunk"), (1.0, "bench/chunk"),
                    (1.0, "bench/supply"), (2.0, "bench/supply")]


def test_readers_find_nothing_without_a_trace():
    ctx = _ctx(None)
    for name in ("device_idle_share", "supply_ms_per_round",
                 "uplink_topk_ms_per_round", "uplink_select_ms_per_round"):
        assert _read(name, ctx) is None


def test_trim_and_roundtrip(tmp_path):
    t = tr.trim(_hand_trace(), 0.005)
    assert t.window() == (0, 5 * MS)
    path = tmp_path / "t.json"
    tr.dump(t, str(path))
    back = tr.load(str(path))
    assert back.window() == (0, 5 * MS)
    assert [e[0] for e in back.device_ops()[0]] == ["fusion.1", "sort.3"]


#: first 20 ms (CNN) or 0.8 s (LM) of a traced window on one TPU v5e
#: (``record_trace.py``), and what the readers read there with one
#: round in the context: device idle %, supply ms, top-k ms, select ms
RECORDED = {
    "trace_cnn_dense": (52.23592, 4.29373, None, None, "select_and_scatter.23"),
    "trace_cnn_topk": (65.808515, 4.937722, 1.851518, 0.005285, "sort.11"),
    "trace_lm_dprox": (0.84149125, 6.54457, None, None, "fusion.578"),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace(name):
    idle, supply, topk, select, top_op = RECORDED[name]
    trace = tr.load(str(DATA / f"{name}.json"))
    assert list(trace.device_ops()) == [0]
    ctx = _ctx(trace, rounds=1, chips=1)
    assert _read("device_idle_share", ctx) == pytest.approx(idle, rel=1e-9)
    assert _read("supply_ms_per_round", ctx) == pytest.approx(supply,
                                                              rel=1e-9)
    if topk is not None:
        assert _read("uplink_topk_ms_per_round", ctx) == pytest.approx(
            topk, rel=1e-9)
        assert _read("uplink_select_ms_per_round", ctx) == pytest.approx(
            select, rel=1e-9)
    else:
        assert _read("uplink_select_ms_per_round", ctx) is None
    bd = tr.breakdown(trace)
    assert bd["device_ops"][0][0] == top_op
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
