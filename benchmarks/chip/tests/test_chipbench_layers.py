"""The layer reduction (``chipbench/layers.py``) on a small hand-made trace
whose numbers are worked out by hand, on a profiler trace recorded here
on the CPU, and on traces recorded on a TPU v5e and committed trimmed
(``data/layers_*.json``)."""
from __future__ import annotations

import glob
import pathlib
import re

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench import layers
from chipbench import trace as tr

MS = 1e6  # ns
DATA = pathlib.Path(__file__).resolve().parent / "data"


def _hand_trace() -> layers.LayerTrace:
    # window 0..10 ms, two rounds.  Chip 0 runs a gradient fusion 1-3, the
    # client half's update 3-3.5, the top-k sort 4-5 and select 5-6 (the
    # uplink), the server's fusion 6-6.5 and an unscoped copy 8-9; chip 1
    # runs the gradient fusion alone, 0-3.
    chip0 = [["fusion.1", 1 * MS, 2 * MS], ["fusion.2", 3 * MS, 0.5 * MS],
             ["sort.3", 4 * MS, 1 * MS],
             ["threshold_select.4", 5 * MS, 1 * MS],
             ["fusion.5", 6 * MS, 0.5 * MS], ["copy.6", 8 * MS, 1 * MS]]
    chip1 = [["fusion.1", 0, 3 * MS]]
    bench = [["bench/window", 0, 10 * MS], ["bench/chunk", 0, 5 * MS],
             ["bench/supply", 0.2 * MS, 0.6 * MS],
             ["bench/chunk", 5 * MS, 5 * MS],
             ["bench/supply", 5.2 * MS, 1.1 * MS]]
    # the engine's spans of the two chunks, and a garbage-collector pause
    main = [["exec/chunk", 0.05 * MS, 4.9 * MS],
            ["exec/supply", 0.1 * MS, 0.8 * MS],
            ["exec/stack", 3.5 * MS, 0.2 * MS],
            ["exec/dispatch", 3.7 * MS, 0.2 * MS],
            ["exec/host_sync", 3.9 * MS, 1.0 * MS],
            ["exec/chunk", 5.05 * MS, 4.9 * MS],
            ["exec/supply", 5.1 * MS, 1.3 * MS],
            ["exec/stack", 6.4 * MS, 0.2 * MS],
            ["exec/dispatch", 6.6 * MS, 0.2 * MS],
            ["host/gc", 6.8 * MS, 1.1 * MS],
            ["exec/host_sync", 8.0 * MS, 1.9 * MS],
            ["exec/stack", 12 * MS, 1 * MS]]  # after the window
    other = [["supplier/stage", 0, 10 * MS]]  # another thread: never read
    scopes = {0: {"fusion.1": "fl.grad", "fusion.2": "fl.local",
                  "sort.3": "fl.uplink", "threshold_select.4": "fl.uplink",
                  "fusion.5": "fl.server",
                  "copy.6": "jit(chunk_fn)/while/body/dynamic_slice"},
              1: {"fusion.1": "fl.grad"}}
    return layers.LayerTrace(
        [{"name": "/device:TPU:0",
          "lines": [{"name": "XLA Ops", "events": chip0}]},
         {"name": "/device:TPU:1",
          "lines": [{"name": "XLA Ops", "events": chip1}]},
         {"name": "/host:CPU", "lines": [{"name": "python3",
                                          "events": bench}]}],
        {"python3": main, "supplier-prefetch": other}, scopes)


def test_layers_by_innermost_scope():
    t = _hand_trace()
    # chip 0: grad 2, update 0.5, uplink 1 + 1, server 0.5, unscoped 1 ms
    assert layers.layer_ns(t)[0] == {
        "grad": 2 * MS, "local_update": 0.5 * MS, "uplink": 2 * MS,
        "server": 0.5 * MS, "unscoped": 1 * MS}
    # over two rounds, on the chip that spends the most on the layer:
    # chip 1's 3 ms of gradient
    assert layers.layer_ms_per_round(t, "grad", 2) == pytest.approx(1.5)
    assert layers.layer_ms_per_round(t, "local_update", 2) == pytest.approx(
        0.25)
    assert layers.layer_ms_per_round(t, "uplink", 2) == pytest.approx(1.0)
    assert layers.layer_ms_per_round(t, "server", 2) == pytest.approx(0.25)
    assert layers.unscoped_ops(t) == [
        ["copy.6", "jit(chunk_fn)/while/body/dynamic_slice",
         pytest.approx(1e-3)]]


def test_loops_are_not_counted_twice():
    t = _hand_trace()
    t.planes[0]["lines"][0]["events"].append(["while.7", 0.5 * MS, 9 * MS])
    t.scopes[0]["while.7"] = "fl.local"
    assert layers.layer_ns(t)[0]["local_update"] == 0.5 * MS


def test_dispatch_ms_per_round():
    # exec/stack 0.2 + 0.2 and exec/dispatch 0.2 + 0.2 ms in the window
    assert layers.dispatch_ms_per_round(_hand_trace(), 2) == pytest.approx(
        0.4)


def test_idle_time_by_innermost_span():
    # chip 0 idles 0-1, 3.5-4, 6.5-8 and 9-10 ms: 4 ms, split by the span
    # open on the window's thread at each instant (worked out by hand)
    got = {k: v / MS for k, v in layers.idle_by_span(_hand_trace()).items()}
    want = {"bench/chunk": 0.1, "exec/chunk": 0.3, "exec/supply": 0.2,
            "bench/supply": 0.6, "exec/stack": 0.3, "exec/dispatch": 0.4,
            "exec/host_sync": 1.0, "host/gc": 1.1}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(4.0)


def test_host_activity_prefers_program_span_to_chunk():
    t = _hand_trace()
    assert tr.host_activity(t.spans(), 3.75 * MS) == "bench/chunk"
    assert layers.host_activity(t, 3.75 * MS) == "exec/dispatch"
    assert layers.host_activity(t, 0.5 * MS) == "bench/supply"
    assert layers.host_activity(t, 20 * MS) == "outside any harness span"
    bd = layers.breakdown(t)
    # the same gaps as trace.breakdown, named by the finer spans
    assert sorted(g[1] for g in bd["idle_gaps"]) == sorted(
        g[1] for g in tr.breakdown(t)["idle_gaps"])
    names = sorted((round(s * 1e3, 6), n) for n, s in bd["idle_gaps"])
    assert names == [(0.5, "exec/dispatch"), (1.0, "bench/supply"),
                     (1.0, "exec/host_sync"), (1.5, "host/gc")]


def test_innermost_covers_the_window():
    pieces = layers.innermost([("a", 1, 8), ("b", 2, 2), ("c", 5, 1)], 0, 10)
    assert pieces == [(0, 1, layers.OUTSIDE), (1, 2, "a"), (2, 4, "b"),
                      (4, 5, "a"), (5, 6, "c"), (6, 9, "a"),
                      (9, 10, layers.OUTSIDE)]


def test_scope_of_an_op_name():
    assert layers.scope_of("jit(chunk_fn)/while/body/closed_call/fl.local/"
                           "while/body/closed_call/fl.grad/vmap(jvp())/"
                           "conv") == "fl.grad"
    assert layers.scope_of("jit(chunk_fn)/while/body/dynamic_slice") == (
        "jit(chunk_fn)/while/body/dynamic_slice")


def _pb(number: int, value) -> bytes:
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        n %= 2 ** 64
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        out.append(n)
        return bytes(out)

    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def _scoped_module(first: str, second: str):
    """A serialized HLO module of ``sin`` under scope ``first`` then a
    product under ``second``, and ``{scope: the name of an op of it}``."""
    import jax
    from jax._src.lib import _jax

    def f(x):
        with jax.named_scope(first):
            y = jax.numpy.sin(x)
        with jax.named_scope(second):
            return y * 3.0

    hlo = jax.jit(f).lower(jax.numpy.ones(8)).compiler_ir("hlo")
    opts = _jax.HloPrintOptions()
    opts.print_metadata = True
    text = hlo.as_hlo_module().to_string(opts)
    names = {}
    for scope in (first, second):
        m = re.search(r"%(\S+) = [^\n]*op_name=\"[^\"]*" + scope, text)
        names[scope] = m.group(1)
    return hlo.as_serialized_hlo_module_proto(), names


def test_op_scopes_from_the_programs_hlo():
    """Each XLA op takes its scope from the HLO module of its program (by
    ``program_id``); an op name two programs share keeps the scope of the
    one with more device time; an op of a program the trace holds no
    module of takes the scope its name has in the modules, where they
    agree."""
    module, names = _scoped_module("fl.grad", "fl.server")
    swapped, again = _scoped_module("fl.server", "fl.grad")
    assert again == {"fl.grad": names["fl.server"],
                     "fl.server": names["fl.grad"]}  # the same op names
    pid, other = 2 ** 64 - 5, 17  # a program id above 2^63 reads as int64
    meta = _pb(2, "/host:metadata") + _pb(5, _pb(1, 7) + _pb(2, _pb(1, 7)
                                                        + _pb(2, "Hlo Proto")))
    for p, mod in ((pid, module), (other, swapped)):
        meta += _pb(4, _pb(1, p) + _pb(2, _pb(1, p) + _pb(2, "jit_f")
                                       + _pb(5, _pb(1, 7)
                                             + _pb(6, _pb(1, mod)))))

    def op(mid, name, program):
        stats = _pb(5, _pb(1, 3) + _pb(3, program)) if program else b""
        return _pb(4, _pb(1, mid) + _pb(2, _pb(1, mid)
                                        + _pb(2, f"%{name} = f32[8] x()")
                                        + stats))

    grad, server = names["fl.grad"], names["fl.server"]
    dev = (_pb(2, "/device:TPU:0")
           + _pb(5, _pb(1, 3) + _pb(2, _pb(1, 3) + _pb(2, "program_id")))
           + op(1, grad, pid) + op(2, server, pid) + op(3, grad, other)
           + op(4, "copy-start.1", 0) + op(5, server, 99))
    events = b"".join(_pb(4, _pb(1, mid) + _pb(3, ps)) for mid, ps in
                      [(1, 2_000_000), (2, 500_000), (3, 1_000_000),
                       (4, 1_000), (5, 700_000)])
    dev += _pb(3, _pb(2, "XLA Ops") + events)
    scopes, conflict = layers.op_scopes(_pb(1, meta) + _pb(1, dev))
    # ``grad`` is fl.grad in its program (2 us) and fl.server in the other
    # (1 us); ``server`` of program 99 is ambiguous, and outweighs the
    # program's own 0.5 us of it
    assert scopes == {0: {grad: "fl.grad", server: "", "copy-start.1": ""}}
    assert conflict == pytest.approx(1000.0 + 500.0)


def test_readers_find_nothing_without_layers():
    plain = tr.Trace(_hand_trace().planes)
    for trace in (None, plain):
        assert layers.layer_ms_per_round(trace, "grad", 2) is None
        assert layers.dispatch_ms_per_round(trace, 2) is None
    bare = _hand_trace()
    bare.scopes = {}
    assert layers.layer_ms_per_round(bare, "grad", 2) is None
    assert layers.layer_ms_per_round(_hand_trace(), "grad", 0) is None


def test_trim_dump_and_load(tmp_path):
    t = layers.trim(_hand_trace(), 0.005)
    assert t.window() == (0, 5 * MS)
    assert [e[0] for e in t.program_spans(line="python3")] == [
        "exec/chunk", "exec/supply", "exec/stack", "exec/dispatch",
        "exec/host_sync"]
    assert sorted(t.scopes[0]) == ["fusion.1", "fusion.2", "sort.3"]
    path = tmp_path / "t.json"
    layers.dump(t, str(path))
    back = layers.load(str(path))
    assert back.program == t.program and back.scopes == t.scopes
    assert layers.layer_ns(back) == layers.layer_ns(t)
    # the harness's own loader reads the same file
    assert tr.load(str(path)).window() == (0, 5 * MS)


def test_program_spans_from_a_cpu_profile(tmp_path):
    """The reduction keeps, per host line, the spans ``repro.obs.trace``
    writes into a recording profiler session, beside the harness's."""
    import jax

    from repro.obs import trace as obs_trace

    f = jax.jit(lambda x: x + 1)
    x = jax.numpy.ones(3)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench/window"):
            with obs_trace.span("exec/chunk", "exec"):
                with obs_trace.span("exec/dispatch", "exec"):
                    f(x).block_until_ready()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    t = layers.from_xplane(path)
    line = t.window_line()
    assert [e[0] for e in t.program_spans(line=line)] == ["exec/chunk",
                                                          "exec/dispatch"]
    assert [e[0] for e in t.thread_spans()] == ["exec/chunk",
                                                "exec/dispatch"]
    assert t.spans("bench/window")  # the harness's reduction is kept


#: the first 70 ms (``cnn_topk``) or 1 s (``lm_dprox``) of a traced window
#: on one TPU v5e (``layer_trace.py --out``), and what is read there with
#: one round in the context: the four layers' device ms, the dispatch
#: path's host ms, the idle ns by span (the largest three), and the
#: harness's readers (device idle %, supply ms) on the same file
RECORDED = {
    "layers_cnn_topk": {
        "layers": (30.009566, 1.111493, 15.038178, 0.232031),
        "dispatch": 5.988655,
        "idle": {"bench/supply": 8681728.0, "exec/host_sync": 6562237.0,
                 "exec/stack": 4826245.0},
        "harness": (31.540601428571424, 8.681728),
        "longest_gap": "exec/stack",
    },
    "layers_lm_dprox": {
        "layers": (814.170939, 108.284075, None, 26.022662),
        "dispatch": 1.14163,
        "idle": {"supplier/stage": 13153889.0,
                 "exec/host_sync": 1768248.0, "exec/dispatch": 335785.0},
        "harness": (1.5559482, 13.20525),
        "longest_gap": "exec/host_sync",
    },
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_layer_trace(name):
    from chipbench.cell import Context
    from chipbench.registry import Registry

    want = RECORDED[name]
    t = layers.load(str(DATA / f"{name}.json"))
    for layer, ms in zip(("grad", "local_update", "uplink", "server"),
                         want["layers"]):
        got = layers.layer_ms_per_round(t, layer, 1)
        assert got == (None if ms is None else pytest.approx(ms, rel=1e-9))
    assert layers.dispatch_ms_per_round(t, 1) == pytest.approx(
        want["dispatch"], rel=1e-9)
    idle = layers.idle_by_span(t)
    top = dict(sorted(idle.items(), key=lambda kv: -kv[1])[:3])
    assert top == pytest.approx(want["idle"], rel=1e-9)
    assert layers.breakdown(t)["idle_gaps"][0][0] == want["longest_gap"]
    # the harness's own reduction of the same file reads as before
    ctx = Context(chips=1, peaks={}, setup_s=1.0, window_s=1.0, chunk_s=[],
                  rounds=1, samples=1, flops_per_sample=1.0,
                  compiles_in_window=0,
                  trace=tr.load(str(DATA / f"{name}.json")))
    reg = Registry()
    assert (reg.metric_reader("device_idle_share")(ctx),
            reg.metric_reader("supply_ms_per_round")(ctx)) == pytest.approx(
        want["harness"], rel=1e-9)


def _reader_context(trace):
    from chipbench.cell import Context

    return Context(chips=1, peaks={"bf16_flops": 197e12,
                                   "hbm_bytes_per_s": 819e9},
                   setup_s=1.0, window_s=0.5, chunk_s=[0.25, 0.25], rounds=1,
                   samples=10, flops_per_sample=1e9, compiles_in_window=0,
                   memory={"peak_bytes": 4e9, "limit_bytes": 16e9},
                   trace=trace)


@pytest.mark.parametrize("name", sorted(
    p.stem for p in DATA.glob("*.json")))
def test_every_reader_reads_a_layer_trace_as_the_plain_one(name):
    """The harness keeps the traced window as a ``LayerTrace``: each
    per-layer reader of ``BENCHMARK.json`` reads the same number from it
    as from the plain reduction of the same recorded file."""
    from chipbench.registry import Registry

    reg = Registry()
    plain = tr.load(str(DATA / f"{name}.json"))
    layered = layers.load(str(DATA / f"{name}.json"))
    assert isinstance(layered, tr.Trace)
    found = 0
    for spec in reg.benchmark["per_layer"]:
        read = reg.metric_reader(spec["name"])
        want = read(_reader_context(plain))
        assert read(_reader_context(layered)) == want, spec["name"]
        found += want is not None
    assert found >= 3  # the trace's readers found something to read


def test_the_harness_keeps_the_layer_scopes(tmp_path):
    """``run_cell``'s reduction of a traced window is a ``LayerTrace``
    with the program's spans and the device ops' scopes (a CPU profile)."""
    import jax

    from chipbench import cell
    from repro.obs import trace as obs_trace

    f = jax.jit(lambda x: x * 2)
    x = jax.numpy.ones(3)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench/window"):
            with obs_trace.span("exec/chunk", "exec"):
                f(x).block_until_ready()
    t = cell._read_trace(str(tmp_path))
    assert isinstance(t, layers.LayerTrace)
    assert t.spans("bench/window")
    assert [e[0] for e in t.program_spans()] == ["exec/chunk"]
    assert not tmp_path.exists()  # the profiler's files are removed
    empty = tmp_path.parent / "no_trace"
    empty.mkdir()
    assert isinstance(cell._read_trace(str(empty)), layers.LayerTrace)
