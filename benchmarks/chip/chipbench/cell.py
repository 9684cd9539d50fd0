"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Set-up builds the engine and its state once and drives it, through the
window's own call (``RoundEngine.run`` over one whole chunk) and feed,
through its first three steps (chunks): they compile the chunk and give
the readings the comparison needs.  The window then calls the same engine
on the same state, chunk after chunk, until ``--seconds`` have passed.
Once it has closed, the device's peak memory is read, the program's state
is freed, and the plain reference replays those three steps.

The harness holds no device copy of the initial weights beside the
program's state: it makes them from the seed for ``engine.init`` and drops
them, makes them again (the same jitted call, so bitwise the same) to
read the program's change after step 3, and once more, into host memory,
for the reference.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from functools import partial

import numpy as np

from chipbench import check, layers, traffic as traffic_mod
from chipbench import trace as trace_mod

STEPS = 3  # steps the reference replays


class GateError(RuntimeError):
    """The machine cannot run this cell (no TPU, too few chips)."""


class CompileCounter:
    """XLA compilations and their seconds, from JAX's monitoring events
    (as ``chip_smoke.py``'s ``CompileClock``)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def device_gate(chips: int, require_tpu: bool = True) -> list:
    """The first ``chips`` devices; refuses anything but a TPU (unless a
    test lifts that) and fewer devices than the cell asks for."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise GateError(f"JAX's first device is on platform "
                        f"{devs[0].platform!r}, not 'tpu': the chip "
                        "benchmark runs on a TPU and never on another "
                        "platform in its place")
    if len(devs) < chips:
        raise GateError(f"the cell needs {chips} chips, JAX sees "
                        f"{len(devs)} {devs[0].platform} devices")
    return devs[:chips]


def seed_key(seed: int):
    """A PRNG key from a seed of any size (PRNGKey keeps 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def weights(init_fn, seed: int):
    """The initial weights of a run, on the device: ``init_fn`` (one jitted
    call) of the seed's key."""
    return init_fn(seed_key(seed))


@dataclass
class Context:
    """What a metric reader may read (``metrics/<name>.py``)."""

    chips: int
    peaks: dict
    setup_s: float
    window_s: float
    chunk_s: list
    rounds: int
    samples: int
    flops_per_sample: float
    compiles_in_window: int
    memory: dict = field(default_factory=dict)  # peak_bytes, limit_bytes
    trace: object = None  # trace.Trace of the window, in a traced run


class Feed:
    """The harness's side of the supplier: times every call into it as a
    ``bench/supply`` span in a traced run, and keeps a host copy of the
    batches of the first ``keep`` rounds for the reference."""

    def __init__(self, keep: int, annotate):
        self.keep, self.annotate, self.rounds = keep, annotate, []

    def record(self, per_round):
        if len(self.rounds) < self.keep:
            self.rounds.extend(per_round)

    def host_callable(self, data: traffic_mod.ClientData):
        def supplier(r, rng):
            with self.annotate("bench/supply"):
                b = data.sample_round(rng)
            self.record([b])
            return b

        return supplier

    def wrap(self, inner):
        """A chunk-aware supplier over ``inner`` (the engine sees the same
        vectorized ``sample_chunk`` path)."""
        from repro.exec import BatchSupplier

        feed = self

        class Spanned(BatchSupplier):
            donate_chunks = inner.donate_chunks

            def sample_round(self, round_idx, rng=None, **kw):
                with feed.annotate("bench/supply"):
                    return inner.sample_round(round_idx, rng, **kw)

            def sample_chunk(self, start_round, n_rounds, rng=None, **kw):
                with feed.annotate("bench/supply"):
                    chunk = inner.sample_chunk(start_round, n_rounds, rng,
                                               **kw)
                if len(feed.rounds) < feed.keep:
                    host = {k: np.asarray(v) for k, v in chunk.items()}
                    feed.record([{k: v[i] for k, v in host.items()}
                                 for i in range(n_rounds)])
                return chunk

        return Spanned()


def build_engine(config: dict, traffic: dict, grad_fn):
    from repro.comm import TopK
    from repro.core.algorithm import DProxConfig
    from repro.core.prox import L1
    from repro.exec import EngineConfig, RoundEngine
    from repro.fed.simulator import DProxAlgorithm

    tr, eng = config["training"], traffic["engine"]
    alg = DProxAlgorithm(L1(lam=tr["lam"]), DProxConfig(
        tau=tr["tau"], eta=tr["eta"], eta_g=tr["eta_g"]))
    kw = {"chunk_rounds": int(eng["chunk_rounds"]), "plane": bool(eng["plane"])}
    up = eng.get("uplink")
    if up is not None:
        if up["kind"] != "topk":
            raise ValueError(f"uplink kind {up['kind']!r} is not known")
        kw["transport"] = TopK(ratio=up["ratio"],
                               granularity=up["granularity"],
                               error_feedback=up.get("error_feedback", True))
    return RoundEngine(alg, grad_fn, int(traffic["clients"]),
                       EngineConfig(**kw))


def _finite(tree) -> bool:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: jnp.all(jnp.stack([jnp.all(jnp.isfinite(l))
                                              for l in jax.tree_util.tree_leaves(t)])))
    return bool(fn(tree))


def run_cell(reg, name: str, seed: int, seconds: float, traced: bool,
             t_start: float, *, require_tpu: bool = True, log=None,
             variants=None, trace_sink=None) -> dict:
    """One run of workload ``name``; returns the result line's object.

    ``variants`` (``{name: keyword arguments of reference dprox.run}``)
    also puts each variant of the reference in the program's place and
    adds its numbers under ``result["variants"]``: how the limits are
    read against the control and the planted faults.  ``trace_sink``, in
    a traced run, receives the reduced trace of the window."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    phases = {"entry": time.perf_counter()}
    import jax

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    # every program goes into the cache, so that only a cell's first run
    # in a checkout compiles (JAX skips those that compile under a second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    work = reg.workload(name)
    config, traffic = reg.config(work["config"]), reg.traffic(work["traffic"])
    chips = int(work["chips"])
    phases["jax"] = time.perf_counter()
    devices = device_gate(chips, require_tpu)
    phases["devices"] = time.perf_counter()
    kind = devices[0].device_kind
    peaks = reg.peaks(kind) if require_tpu else {}
    counter = CompileCounter()
    annotate = (jax.profiler.TraceAnnotation if traced
                else lambda _name: contextlib.nullcontext())
    precision = config["precision"]["matmul_precision"]
    ref_mod = reg.reference(config["family"])
    dprox = reg.reference("dprox")
    tr, eng = config["training"], traffic["engine"]
    chunk, n_clients = int(eng["chunk_rounds"]), int(traffic["clients"])

    with jax.default_matmul_precision(precision):
        # -- set-up: data, weights, engine, the first three steps ----------
        data = traffic_mod.make(traffic, config, seed)
        phases["data"] = time.perf_counter()
        init_fn = jax.jit(lambda k: ref_mod.init_params(k, config))
        params0 = weights(init_fn, seed)
        jax.block_until_ready(params0)
        phases["weights"] = time.perf_counter()
        grad_fn = reg.program(config["family"]).grad_fn(config)
        engine = build_engine(config, traffic, grad_fn)
        state = engine.init(params0)  # shares no buffer with params0
        del params0
        feed = Feed(STEPS * chunk, annotate)
        if traffic["supplier"] == "host_per_round":
            supplier = feed.host_callable(data)
        elif traffic["supplier"] == "device_cache":
            from repro.exec import ArraySupplier

            supplier = feed.wrap(ArraySupplier(
                data.arrays, data.tau, data.batch, seed=seed,
                device_cache=True))
        else:
            raise ValueError(f"supplier {traffic['supplier']!r} is not known")
        jax.block_until_ready(state)
        phases["engine"] = time.perf_counter()
        rng = traffic_mod.rng_for(seed, 2)
        prog = {"losses": []}
        r = 0
        for step in range(STEPS):
            state, m = engine.run(state, supplier, chunk, rng=rng,
                                  start_round=r)
            r += chunk
            prog["losses"].extend(float(x) for x in m["train_loss"])
            if step == 0:
                prog["c_norms"] = dprox.leaf_norms(state.c)
            phases[f"step{step + 1}"] = time.perf_counter()
        x0 = weights(init_fn, seed)
        prog["change_norms"] = dprox.diff_norms(state.x_bar, x0)
        del x0
        marks = list(phases.items())
        log("set-up phases (s): " + ", ".join(
            f"{k} {t1 - t0:.3f}" for (_k, t0), (k, t1)
            in zip([("start", t_start)] + marks, marks)))
        log(f"set-up: {STEPS} steps of {chunk} rounds, loss "
            f"{prog['losses'][0]:.4f} -> {prog['losses'][-1]:.4f}, "
            f"{counter.count} compiles ({counter.seconds:.1f}s)")

        # -- the measured window ---------------------------------------------
        trace_dir = tempfile.mkdtemp(prefix="chipbench-") if traced else None
        if traced:
            jax.profiler.start_trace(trace_dir)
        compiles0 = counter.count
        chunk_s, losses = [], []
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t_start
        with annotate("bench/window"):
            while True:
                t0 = time.perf_counter()
                with annotate("bench/chunk"):
                    state, m = engine.run(state, supplier, chunk, rng=rng,
                                          start_round=r)
                t1 = time.perf_counter()
                chunk_s.append(t1 - t0)
                losses.extend(m["train_loss"])
                r += chunk
                if t1 - t_w0 >= seconds:
                    break
        window_s = t1 - t_w0
        compiles = counter.count - compiles0
        trace = None
        if traced:
            jax.profiler.stop_trace()
            trace = _read_trace(trace_dir)
            if trace_sink is not None:
                trace_sink(trace)
        rounds = len(chunk_s) * chunk
        failed = int(np.count_nonzero(~np.isfinite(np.asarray(losses))))
        if not _finite(state.x_bar):
            failed = max(failed, chunk)
        memory = _memory(devices)
        log(f"window: {rounds} rounds in {len(chunk_s)} chunks, "
            f"{window_s:.3f}s; {compiles} compiles; {failed} failed; device "
            f"peak {memory.get('in_use_bytes')} bytes in use + "
            f"{memory.get('reserved_bytes')} reserved")

        # -- the reference, once the program's state is freed ----------------
        del state, engine, supplier
        gc.collect()
        t_ref = time.perf_counter()
        # in host memory, C-contiguous (read back from the TPU, an array
        # can keep the device's order of axes in its strides)
        params0 = jax.tree_util.tree_map(np.ascontiguousarray,
                                         weights(init_fn, seed))
        if traffic["supplier"] == "device_cache":
            # the program's supplier drew these rounds: the reference takes
            # the harness's own draw, and every row served is checked
            ref_rounds = [traffic_mod.array_round(data.arrays, data.tau,
                                                  data.batch, seed, i)
                          for i in range(STEPS * chunk)]
        else:  # the harness's own sampler drew what the engine was fed
            ref_rounds = feed.rounds
        ref = dprox.run(partial(ref_mod.loss, config=config), params0, tr,
                        n_clients, ref_rounds, chunk,
                        uplink=eng.get("uplink"))
        nums = check.numbers(prog, ref)
        if traffic["supplier"] == "device_cache":
            nums["feed_rows_wrong"] = check.rows_wrong(feed.rounds,
                                                       ref_rounds)
        found, readings = {}, {"program": prog, "reference": ref}
        for vname, kw in (variants or {}).items():
            alt = dprox.run(partial(ref_mod.loss, config=config), params0,
                            tr, n_clients, ref_rounds, chunk,
                            uplink=eng.get("uplink"), **kw)
            found[vname] = check.numbers(alt, ref)
            readings[vname] = alt
        correct, checks = check.judge(nums, reg.limits(name))
        peak = _memory(devices).get("peak_bytes")
        log(f"reference: {time.perf_counter() - t_ref:.1f}s; "
            f"{nums['leaves_left_out']} leaves left out; device peak "
            f"{peak} bytes with the window's")
        log("readings: " + json.dumps({"program": prog, "reference": ref}))

    ctx = Context(
        chips=chips, peaks=peaks, setup_s=setup_s, window_s=window_s,
        chunk_s=chunk_s, rounds=rounds,
        samples=rounds * n_clients * int(tr["tau"]) * int(tr["batch"]),
        flops_per_sample=ref_mod.flops_per_sample(config, traffic),
        compiles_in_window=compiles, memory=memory, trace=trace)
    wanted = reg.per_layer(name) if traced else reg.end_to_end(name)
    metrics = {}
    for spec in wanted:
        value = reg.metric_reader(spec["name"])(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory.get("peak_bytes")}
    result = {"correct": bool(correct and failed == 0), "attempted": rounds,
              "failed": failed, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"], device["window_s"] = _busy(trace)
        bd = layers.breakdown(trace)
        if bd is not None:
            result["breakdown"] = bd
    if variants:
        result["variants"], result["readings"] = found, readings
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def _read_trace(trace_dir: str):
    """The window's trace reduced with the program's spans and the device
    ops' layer scopes (``layers.LayerTrace``, a ``trace.Trace``)."""
    import glob
    import shutil

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    try:
        return (layers.from_xplane(paths[0]) if paths
                else layers.LayerTrace())
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _busy(trace) -> tuple:
    """(device busy seconds averaged over the chips, traced window s)."""
    win, ops = trace.window(), trace.device_ops()
    if win is None:
        return 0.0, 0.0
    lo, hi = win
    busy = [trace_mod.busy_ns(ev, lo, hi) for ev in ops.values()] or [0.0]
    return float(np.mean(busy)) * 1e-9, (hi - lo) * 1e-9


def _memory(devices) -> dict:
    """Peak device memory of the fullest chip, and that chip's limit.  On
    a TPU the buffers in use and the scratch that loaded programs reserve
    are counted apart (``peak_bytes_in_use``, ``peak_bytes_reserved``);
    the peak is their sum."""
    best = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        in_use = int(stats["peak_bytes_in_use"])
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if in_use + reserved >= best.get("peak_bytes", -1):
            best = {"peak_bytes": in_use + reserved,
                    "limit_bytes": int(stats.get("bytes_limit", 0)),
                    "in_use_bytes": in_use, "reserved_bytes": reserved}
    return best
