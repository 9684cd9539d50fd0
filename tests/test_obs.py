"""Observability tests: the disabled-tracer bitwise pin, the Chrome
trace-event schema, the cross-process merge, and metrics properties.

The load-bearing pin: instrumentation sites live in hot paths permanently,
so the DISABLED path (NULL_TRACER, the default) must be a true no-op --
a run with a tracer installed must produce bitwise-identical numerics to
one without.  The merge tests pin what the CI smoke job's validator
checks on a real 2-process trace: schema-valid events and proper span
nesting per (pid, tid) track after clock-offset alignment.
"""
import gc
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from _hypo import given, st  # hypothesis, or fixed-grid fallback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", True)

from repro.fed.runtime import RuntimeArgs, _fields_bitwise, run_local
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace


def _args(**kw) -> RuntimeArgs:
    defaults = dict(clients=4, m=8, dim=12, tau=2, rounds=4, chunk=2,
                    timeout=60.0)
    defaults.update(kw)
    return RuntimeArgs(**defaults)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with tracing disabled."""
    obs_trace.uninstall()
    yield
    obs_trace.uninstall()


class TestTracerBitwise:
    def test_traced_run_is_bitwise_identical(self):
        """THE pin: installing a tracer must not perturb numerics -- the
        span sites only read the clock, never touch values."""
        base = run_local(_args())
        tracer = obs_trace.install("test")
        try:
            traced = run_local(_args())
        finally:
            obs_trace.uninstall()
        assert tracer.n_spans > 0  # the engine sites actually recorded
        assert _fields_bitwise(base["fields"], traced["fields"])

    def test_null_span_is_shared_noop(self):
        # disabled-path cost model: no allocation per call site
        assert obs_trace.span("a") is obs_trace.span("b")
        obs_trace.span("a").set(nbytes=1)  # no-op, no error

    def test_timed_measures_without_tracer(self):
        with obs_trace.timed("x", "t") as tm:
            pass
        assert tm.seconds >= 0.0
        assert isinstance(obs_trace.get(), obs_trace.NullTracer)


def _host_events(logdir) -> dict:
    """``{name: [(line name, stats dict)]}`` of the host plane of the one
    xplane under ``logdir``."""
    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (line.name, dict(e.stats)))
    return out


class TestProfilerBridge:
    """While a ``jax.profiler`` session records, the tracer's spans also
    land in the profiler's host plane, on the device trace's clock."""

    def test_spans_reach_the_xplane(self, tmp_path):
        f = jax.jit(lambda x: x * 2)
        x = jax.numpy.ones(4)
        f(x).block_until_ready()
        ring = obs_trace.Tracer("ring")
        with jax.profiler.trace(str(tmp_path)):
            with obs_trace.span("exec/null_span", "exec", start_round=3):
                f(x).block_until_ready()
            with ring.span("exec/ring_span", "exec"):
                f(x).block_until_ready()
            with obs_trace.timed("exec/timed_span", "exec"):
                pass
        ev = _host_events(tmp_path)
        for name in ("exec/null_span", "exec/ring_span", "exec/timed_span"):
            assert [line for line, _ in ev[name]] == ["python"], name
        assert ev["exec/null_span"][0][1] == {"start_round": 3}
        assert ring.n_spans == 1  # the ring records as before

    def test_null_span_shared_while_profiler_off(self):
        assert obs_trace.recording() is None
        assert obs_trace.span("a") is obs_trace._NULL_SPAN
        with obs_trace.timed("t") as tm:
            pass
        assert tm._prof is None

    def test_set_on_both_paths(self, tmp_path):
        ring = obs_trace.Tracer("ring")
        with jax.profiler.trace(str(tmp_path)):
            with obs_trace.span("wire/null_set", "wire") as sp:
                sp.set(nbytes=5)
            with ring.span("wire/ring_set", "wire") as sp:
                sp.set(nbytes=7)
        ev = _host_events(tmp_path)
        assert ev["wire/null_set"][0][1] == {"nbytes": 5}
        assert ev["wire/ring_set"][0][1] == {"nbytes": 7}
        assert json.loads(ring.export_wire()["args_json"]) == [{"nbytes": 7}]
        obs_trace.span("wire/off").set(nbytes=1)  # profiler off: no-op
        with ring.span("wire/off_ring") as sp:
            sp.set(nbytes=2)
        assert json.loads(ring.export_wire()["args_json"])[-1] == {
            "nbytes": 2}

    def test_gc_pauses_are_spans(self, tmp_path):
        obs_trace.watch_gc()
        obs_trace.watch_gc()
        assert gc.callbacks.count(obs_trace._on_gc) == 1
        gc.collect()  # profiler off: nothing opened
        assert obs_trace._GC_SPAN is None
        with jax.profiler.trace(str(tmp_path)):
            gc.collect()
        stats = [s for _, s in _host_events(tmp_path)["host/gc"]]
        assert any(s["generation"] == 2 and "collected" in s for s in stats)

    def test_wire_module_imports_without_jax(self):
        """The wire codec's module and the tracer it imports load in a
        process in which jax cannot be imported."""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        code = (
            "import importlib.util, sys\n"
            "class NoJax:\n"
            "    def find_spec(self, name, path=None, target=None):\n"
            "        if name.split('.')[0] in ('jax', 'jaxlib'):\n"
            "            raise ImportError('jax is not importable here')\n"
            "sys.meta_path.insert(0, NoJax())\n"
            f"path = {os.path.join(src, 'repro', 'comm', 'wire.py')!r}\n"
            "spec = importlib.util.spec_from_file_location('wire', path)\n"
            "wire = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(wire)\n"
            "from repro.obs import trace\n"
            "with trace.span('wire/encode') as sp:\n"
            "    sp.set(nbytes=1)\n"
            "assert trace.recording() is None\n"
            "assert 'jax' not in sys.modules\n")
        env = dict(os.environ, PYTHONPATH=src)
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=60)


class TestChromeExport:
    def test_export_schema_valid(self):
        tr = obs_trace.Tracer("p0", capacity=64)
        with tr.span("outer", "cat", k=1):
            with tr.span("inner", "cat") as sp:
                sp.set(nbytes=7)
        doc = obs_trace.to_chrome([tr.export_wire()])
        assert obs_trace.validate_chrome(doc) == []
        doc2 = json.loads(json.dumps(doc))  # JSON round trip stays valid
        assert obs_trace.validate_chrome(doc2) == []
        evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in evs} == {"outer", "inner"}
        assert min(e["ts"] for e in evs) == 0.0  # rebased to zero
        inner = next(e for e in evs if e["name"] == "inner")
        assert inner["args"] == {"nbytes": 7}

    def test_ring_wrap_drops_oldest(self):
        tr = obs_trace.Tracer("p0", capacity=4)
        for i in range(10):
            tr.instant(f"s{i}")
        assert tr.n_spans == 4
        assert tr.dropped == 6
        b = tr.export_wire()
        names = [b["names"][ix] for ix in b["name_ix"]]
        assert names == ["s6", "s7", "s8", "s9"]  # oldest-first survivors
        assert list(np.argsort(b["t0"])) == [0, 1, 2, 3]

    def test_merge_applies_offset_and_nests(self):
        srv = obs_trace.Tracer("server", capacity=16)
        wrk = obs_trace.Tracer("worker0", capacity=16)
        wrk.pid = srv.pid + 1  # two tracers in one test process
        wrk.offset = 123.456
        with srv.span("server/commit", "server"):
            pass
        with wrk.span("exec/chunk", "exec", start_round=0):
            with wrk.span("exec/host_sync", "exec"):
                pass
        doc = obs_trace.to_chrome([srv.export_wire(), wrk.export_wire()])
        assert obs_trace.validate_chrome(doc) == []
        evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len({e["pid"] for e in evs}) == 2
        # the worker ran at the same real time but its offset pushes it
        # ~123.456s later on the merged (server) timebase
        chunk = next(e for e in evs if e["name"] == "exec/chunk")
        commit = next(e for e in evs if e["name"] == "server/commit")
        assert chunk["ts"] - commit["ts"] > 123e6
        procs = [e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert sorted(procs) == ["server", "worker0"]

    def test_merge_dedupes_shared_process(self):
        # the in-process threaded runtime ships ONE shared tracer from
        # both ends; same-pid bundles must not double-count
        tr = obs_trace.Tracer("shared", capacity=8)
        tr.instant("a")
        b = tr.export_wire()
        assert len(obs_trace.merge_wire([b, b, None])) == 1

    def test_validator_rejects_partial_overlap(self):
        doc = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 0},
            {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 0},
        ]}
        assert obs_trace.validate_chrome(doc)

    def test_validator_accepts_disjoint_and_nested(self):
        doc = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 0},
            {"name": "b", "ph": "X", "ts": 2, "dur": 4, "pid": 1, "tid": 0},
            {"name": "c", "ph": "X", "ts": 20, "dur": 5, "pid": 1, "tid": 0},
            # same window, other track: never compared
            {"name": "d", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 1},
        ]}
        assert obs_trace.validate_chrome(doc) == []

    @given(off=st.floats(-100.0, 100.0), lat=st.floats(0.0, 0.5))
    def test_clock_offset_recovers_true_offset(self, off, lat):
        """Symmetric-latency exchange: the midpoint estimate is exact."""
        t_send = 10.0
        peer_now = (t_send + lat) + off  # peer clock = local + off
        t_recv = t_send + 2.0 * lat
        est = obs_trace.clock_offset(t_send, t_recv, peer_now)
        assert est == pytest.approx(off, abs=1e-9)


class TestOverlapReport:
    def _doc(self, events):
        return {"traceEvents": events}

    def test_hidden_fraction_from_synthetic_spans(self):
        s = 1e6  # seconds -> µs
        doc = self._doc([
            {"name": "exec/chunk", "ph": "X", "ts": 0, "dur": 1 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 0, "rounds": 2}},
            {"name": "exec/chunk", "ph": "X", "ts": 1 * s, "dur": 1 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 2, "rounds": 2}},
            # chunk 0's ship rides entirely behind chunk 1's compute
            {"name": "uplink/ship", "ph": "X", "ts": 1 * s, "dur": 1 * s,
             "pid": 1, "tid": 1, "args": {"start_round": 0, "nbytes": 100}},
            # chunk 1's ship is fully exposed after the last compute
            {"name": "uplink/ship", "ph": "X", "ts": 2 * s, "dur": 1 * s,
             "pid": 1, "tid": 1, "args": {"start_round": 2, "nbytes": 100}},
        ])
        rep = obs_report.overlap_report(doc)
        t = rep["totals"]
        assert t["chunks"] == 2
        assert t["compute_s"] == pytest.approx(2.0)
        assert t["wire_s"] == pytest.approx(2.0)
        assert t["wall_s"] == pytest.approx(3.0)
        assert t["hidden_fraction"] == pytest.approx(0.5)
        # steady drops the pid's first chunk: one chunk, ship exposed
        assert rep["steady"]["chunks"] == 1
        assert rep["steady"]["hidden_fraction"] == pytest.approx(0.0)

    def test_inline_wait_subtracted_once(self):
        """Blocking mode: uplink/wait wraps the inline ship on the SAME
        thread -- union, not sum, or compute goes negative."""
        s = 1e6
        doc = self._doc([
            {"name": "exec/chunk", "ph": "X", "ts": 0, "dur": 2 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 0, "rounds": 2}},
            {"name": "uplink/wait", "ph": "X", "ts": 1 * s, "dur": 1 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 0}},
            {"name": "uplink/ship", "ph": "X", "ts": 1 * s, "dur": 0.9 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 0, "nbytes": 10}},
        ])
        rep = obs_report.overlap_report(doc)
        assert rep["chunks"][0]["compute_s"] == pytest.approx(1.0)

    def test_compute_ref_charges_dilation_to_wire(self):
        s = 1e6
        doc = self._doc([
            {"name": "exec/chunk", "ph": "X", "ts": 0, "dur": 1 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 0, "rounds": 2}},
            # steady chunk dilated to 1.2s by sender contention
            {"name": "exec/chunk", "ph": "X", "ts": 1 * s, "dur": 1.2 * s,
             "pid": 1, "tid": 0, "args": {"start_round": 2, "rounds": 2}},
            {"name": "uplink/ship", "ph": "X", "ts": 1 * s, "dur": 1.2 * s,
             "pid": 1, "tid": 1, "args": {"start_round": 2, "nbytes": 10}},
        ])
        rep = obs_report.overlap_report(doc, compute_ref_s=1.0)
        st_ = rep["steady"]
        # trace-only view: wire fully hidden (wall == dilated compute)
        assert st_["hidden_fraction"] == pytest.approx(1.0)
        # reference view: the 0.2s dilation is exposed wire time
        assert st_["hidden_fraction_ref"] == pytest.approx(1.0 - 0.2 / 1.2)


class TestMetrics:
    @given(v=st.floats(0.0, 1e6), n=st.integers(1, 5))
    def test_counter_accumulates(self, v, n):
        c = obs_metrics.Counter("c")
        for _ in range(n):
            c.add(v)
        assert c.value == pytest.approx(n * v)

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            obs_metrics.Counter("c").add(-1.0)

    @given(v=st.floats(-5.0, 50.0))
    def test_integer_buckets_clip(self, v):
        """The AGE_HIST_BUCKETS idiom: bucket = clip(int(v), 0, n-1)."""
        h = obs_metrics.Histogram("h", buckets=8)
        h.observe(v)
        expect = min(max(int(v), 0), 7)
        assert h.counts[expect] == 1
        assert int(h.counts.sum()) == 1 == h.n
        assert h.mean == pytest.approx(v)

    @given(n=st.integers(1, 64))
    def test_observe_array_counts_every_value(self, n):
        h = obs_metrics.Histogram("h", buckets=4)
        h.observe(np.arange(n) % 9 - 1.0)
        assert int(h.counts.sum()) == n == h.n

    def test_edges_histogram(self):
        h = obs_metrics.Histogram("h", edges=[1.0, 2.0, 4.0])
        h.observe([0.5, 1.5, 3.0, 100.0])
        assert h.counts.tolist() == [1, 1, 1, 1]

    def test_exactly_one_geometry(self):
        with pytest.raises(ValueError):
            obs_metrics.Histogram("h")
        with pytest.raises(ValueError):
            obs_metrics.Histogram("h", buckets=4, edges=[1.0])

    def test_merge_counts_folds_ledger_histogram(self):
        """sched's arrival-age buckets fold in unchanged -- the geometries
        are pinned equal."""
        from repro.sched.aggregator import AGE_HIST_BUCKETS

        assert obs_metrics.AGE_BUCKETS == AGE_HIST_BUCKETS
        h = obs_metrics.Histogram("age", buckets=AGE_HIST_BUCKETS)
        ext = np.zeros(AGE_HIST_BUCKETS, np.int64)
        ext[2] = 3
        h.merge_counts(ext)
        assert h.n == 3 and h.counts[2] == 3 and h.sum == pytest.approx(6.0)
        with pytest.raises(ValueError):
            h.merge_counts(np.zeros(3, np.int64))

    def test_registry_type_mismatch(self):
        r = obs_metrics.MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_registry_get_or_create(self):
        r = obs_metrics.MetricsRegistry()
        assert r.counter("x") is r.counter("x")
        assert r.histogram("h").buckets == obs_metrics.AGE_BUCKETS

    def test_jsonl_sink_roundtrip(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        r = obs_metrics.MetricsRegistry()
        r.counter("uplink/bytes").add(42.0)
        r.gauge("round_throughput").set(3.5)
        r.histogram("arrival/age").observe([0, 1, 1, 99])
        with obs_metrics.JsonlSink(path) as sink:
            sink.write("commit", worker=0, nbytes=42)
            sink.write_snapshot(r, rounds=8)
        recs = [json.loads(line) for line in open(path)]
        assert [rec["event"] for rec in recs] == ["commit", "snapshot"]
        assert all(rec["schema"] == obs_metrics.SCHEMA for rec in recs)
        snap = recs[1]["metrics"]
        assert snap["counters"]["uplink/bytes"] == 42.0
        assert snap["gauges"]["round_throughput"] == 3.5
        h = snap["histograms"]["arrival/age"]
        assert h["n"] == 4 and h["counts"][1] == 2 and h["counts"][7] == 1


class TestRuntimeTraceEndToEnd:
    def test_threaded_pair_writes_merged_trace(self, tmp_path):
        """The in-process pair (same sockets/frames as the subprocess
        form) exports one schema-valid merged trace + metrics JSONL."""
        import threading

        from repro.fed.runtime import run_server, run_worker

        trace_path = str(tmp_path / "t.json")
        jsonl_path = str(tmp_path / "m.jsonl")
        a = _args(mode="overlapped", trace=trace_path,
                  metrics_jsonl=jsonl_path)
        box = {}
        ready = threading.Event()
        t = threading.Thread(
            target=lambda: box.update(server=run_server(
                a, ready_cb=lambda p: (box.update(port=p), ready.set()))),
            daemon=True)
        t.start()
        assert ready.wait(30)
        a.port = box["port"]
        run_worker(a, rank=0)
        t.join(60)
        assert box["server"]["trace_path"] == trace_path
        doc = json.load(open(trace_path))
        assert obs_trace.validate_chrome(doc) == []
        names = {e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        # the one-timebase pin: engine, wire, and server spans coexist
        assert {"exec/chunk", "uplink/ship", "server/commit"} <= names
        snap = box["server"]["metrics"]
        assert snap["counters"]["uplink/bytes"] > 0
        assert snap["counters"]["commits"] == 2  # 4 rounds / chunk 2
        lines = [json.loads(line) for line in open(jsonl_path)]
        assert [rec["event"] for rec in lines].count("commit") == 2
        assert lines[-1]["event"] == "snapshot"
