"""End-to-end federated LM training driver (deliverable (b)).

Trains any registry architecture (reduced "smoke" scale by default; the full
configs are exercised via the dry-run) with Algorithm 1 over heterogeneous
per-client token streams, with checkpointing and optional mesh sharding.

Execution goes through the unified round engine (:mod:`repro.exec`), whose
stages compose freely -- every flag below stacks with every other:
``--chunk N`` fuses N rounds per compiled call (one host sync per chunk),
``--participation f`` subsamples a fraction of clients each round,
``--transport {dense,topk,randk,quantize}`` (+ ``--compress-ratio``)
compresses the uplink, ``--downlink ...`` compresses the broadcast,
``--clock {deterministic,lognormal,straggler}`` / ``--buffer-size K`` /
``--staleness {uniform,poly}`` + ``--staleness-correct`` /
``--queue-depth Q`` activate simulated asynchrony (``--async`` alone picks
the straggler clock), ``--edges E`` aggregates commits through a
client->edge->root tree, ``--population P`` / ``--cohort C`` keep only a
C-wide working set of per-client state resident (the rest lives in a host
population store, checkpointed as a ``.store.npz`` sidecar of ``--ckpt``),
and batches come from a chunk-aware :class:`repro.exec.ArraySupplier` over
the token streams (``--device-cache`` keeps them device-resident,
``--prefetch`` overlaps the next chunk's batch assembly with the current
compiled call and donates the staged chunks).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_1_6b \
        --scale smoke --rounds 50 --tau 4 --clients 4 --ckpt out/ck.npz

    # ~100M-parameter run (paper-scale driver; slow on CPU, sized for TPU):
    PYTHONPATH=src python -m repro.launch.train --arch stablelm_1_6b \
        --scale 100m --rounds 200

Baselines are selectable with --algorithm {dprox,fedda,fedmid,fedavg,scaffold}
so the paper's comparisons run at LM scale too.

``--processes N`` switches to REAL multi-process federation
(:mod:`repro.fed.runtime`): N worker processes + a server process exchange
uplink frames over a localhost socket (overlapped with compute by default),
instead of simulating all clients in one process.  The runtime has its own
flag set (shared with ``python -m repro.fed.runtime``) -- the single-process
LM flags above do not apply in this mode:

    PYTHONPATH=src python -m repro.launch.train --processes 2 \
        --clients 16 --rounds 32 --transport topk --ratio 0.1 --plane
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils.compile_cache import use_compile_cache

from repro.checkpoint import ckpt
from repro.configs import registry
from repro.core.algorithm import DProxConfig
from repro.core.baselines import FedAvg, FedDA, FedMid, Scaffold
from repro.core.prox import L1
from repro.data.synthetic import token_stream_heterogeneous
from repro.exec import (ArraySupplier, EngineConfig, RoundEngine,
                        rounds_to_boundary)
from repro.fed.simulator import DProxAlgorithm
from repro.models import transformer as T
from repro.models.layers import AttnCfg


def scale_config(cfg, scale: str):
    if scale == "smoke":
        return cfg
    if scale == "100m":
        # ~100M-parameter member of the same family
        return cfg.with_overrides(
            name=cfg.name + "-100m", n_layers=8, d_model=768,
            d_ff=2048, vocab=32768,
            attn=None if cfg.attn is None else AttnCfg(
                kind=cfg.attn.kind, num_heads=12, num_kv_heads=max(
                    12 // max(cfg.attn.num_heads // cfg.attn.num_kv_heads, 1), 1),
                head_dim=64, rope_theta=cfg.attn.rope_theta,
                logit_softcap=cfg.attn.logit_softcap, causal=cfg.attn.causal),
            remat=False)
    raise ValueError(scale)


def model_config(arch: str, scale: str):
    """The float32 :class:`ArchConfig` that ``main`` trains for
    ``--arch``/``--scale``."""
    base = (registry.get_smoke(arch) if scale == "smoke"
            else registry.get(arch))
    return scale_config(base, scale).with_overrides(param_dtype=jnp.float32)


def make_algorithm(name, reg, tau, eta, eta_g):
    if name == "dprox":
        return DProxAlgorithm(reg, DProxConfig(tau=tau, eta=eta, eta_g=eta_g))
    if name == "fedda":
        return FedDA(reg, tau, eta, eta_g)
    if name == "fedmid":
        return FedMid(reg, tau, eta, eta_g)
    if name == "fedavg":
        return FedAvg(tau, eta, eta_g)
    if name == "scaffold":
        return Scaffold(reg, tau, eta, eta_g)
    raise ValueError(name)


def main_multiprocess(argv):
    """``--processes N``: the real multi-process runtime entry point.

    The parent runs worker rank 0 inline (so its report and exceptions
    surface directly); the server and workers 1..N-1 are re-exec'd
    subprocesses (see :func:`repro.fed.runtime.run_pair`).
    """
    from repro.fed import runtime

    ap = argparse.ArgumentParser(
        description="multi-process federated training "
                    "(repro.fed.runtime flags)")
    ap.add_argument("--processes", type=int, required=True,
                    help="number of worker processes (+1 server process)")
    ap.add_argument("--check-parity", action="store_true",
                    help="(1 worker) also run single-process and assert "
                         "the server trajectory matches bitwise")
    runtime.add_runtime_args(ap)
    ns = ap.parse_args(argv)
    if ns.processes < 1:
        ap.error("--processes must be >= 1")
    ns.workers = ns.processes
    run_argv = (["--role", "pair"]
                + (["--check-parity"] if ns.check_parity else [])
                + runtime._to_argv(runtime._from_ns(ns)))
    return runtime.main(run_argv)


def main(argv=None):
    """Train per ``argv``; returns ``(state, snapshots)``: the final
    algorithm state and the :class:`repro.serving.SnapshotStore` the run
    published into (``None`` without ``--publish-snapshots``).  With
    ``--processes`` it returns the runtime's exit code instead."""
    argv = sys.argv[1:] if argv is None else list(argv)
    use_compile_cache()
    if any(s == "--processes" or s.startswith("--processes=")
           for s in argv):
        return main_multiprocess(argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_1_6b")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "100m"])
    ap.add_argument("--algorithm", default="dprox")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4, help="per-client batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eta", type=float, default=2e-2)
    ap.add_argument("--eta-g", type=float, default=2.0)
    ap.add_argument("--lam", type=float, default=1e-6, help="L1 strength")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--chunk", type=int, default=4,
                    help="rounds fused per compiled engine call")
    ap.add_argument("--participation", type=float, default=None,
                    help="fraction of clients active per round (dprox only)")
    ap.add_argument("--transport", default=None,
                    choices=["dense", "topk", "randk", "quantize"],
                    help="compress uplinks through this repro.comm transport")
    ap.add_argument("--compress-ratio", type=float, default=0.1,
                    help="kept-coordinate fraction for topk/randk")
    ap.add_argument("--ratio-schedule", default="constant",
                    choices=["constant", "linear", "bucketed"],
                    help="staleness-adaptive per-commit ratio schedule for "
                         "--transport topk (repro.comm.schedule): stale "
                         "clients uplink at harder ratios under the async "
                         "stage's age ledger; constant is bitwise the "
                         "fixed-ratio transport")
    ap.add_argument("--autotune", type=int, default=None, metavar="BUDGET",
                    help="search engine knobs (chunk/transport/ratio/"
                         "granularity/plane + async buffer/queue/staleness/"
                         "schedule) with repro.tune before training and "
                         "adopt the winner; measures the synthetic proxy "
                         "workload, so only engine-level knobs transfer.  "
                         "Reuses this host's persisted tuning record when "
                         "one matches (zero measured trials)")
    ap.add_argument("--downlink", default=None,
                    choices=["dense", "topk", "randk", "quantize"],
                    help="compress the broadcast direction too "
                         "(DownlinkComm stage; shares --compress-ratio)")
    ap.add_argument("--granularity", default="leaf",
                    choices=["leaf", "global"],
                    help="compress per pytree leaf (historical) or the "
                         "whole flat d-vector (global top-k/one quantizer "
                         "scale; index bytes accounted once)")
    ap.add_argument("--plane", action="store_true",
                    help="thread the stage carries as flat parameter "
                         "planes (repro.core.plane): one contiguous "
                         "(clients, d_pad) buffer instead of per-leaf "
                         "pytrees")
    ap.add_argument("--device-cache", action="store_true",
                    help="keep token streams device-resident (batches are "
                         "gathered on device, no host stack)")
    ap.add_argument("--prefetch", action="store_true",
                    help="double-buffer chunk supply: stage the next "
                         "chunk's batches while the current chunk computes")
    ap.add_argument("--async", dest="run_async", action="store_true",
                    help="simulated asynchrony with the default straggler "
                         "clock (any async flag below also activates the "
                         "stage; they all compose with --transport/"
                         "--downlink)")
    ap.add_argument("--clock", default=None,
                    choices=["deterministic", "lognormal", "straggler"],
                    help="async: virtual-time clock model "
                         "(default: straggler)")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="async: reports the server waits for per commit "
                         "(default: all clients)")
    ap.add_argument("--staleness", default=None,
                    choices=["uniform", "poly"],
                    help="async: stale-report weighting (default: uniform)")
    ap.add_argument("--staleness-correct", action="store_true",
                    help="async: retain downweighted stale mass in a "
                         "server-side error-feedback residual")
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="async: per-client in-flight report queue depth "
                         "(clients race ahead of delivery; default: the "
                         "one-slot buffer)")
    ap.add_argument("--upload", type=float, default=None,
                    help="async: constant per-report upload time, split "
                         "from the clock's compute stream (uploads "
                         "serialize FIFO under --queue-depth; default: "
                         "single-stream clock)")
    ap.add_argument("--edges", type=int, default=None,
                    help="async: aggregate commits through a client->edge"
                         "->root tree with this many edge servers (must "
                         "divide --clients; default: flat aggregation)")
    ap.add_argument("--population", type=int, default=None,
                    help="cohort: total simulated client population "
                         "(default: --clients); with --cohort the engine "
                         "keeps only a cohort-width working set resident "
                         "and swaps per-client state against a host "
                         "population store at chunk boundaries")
    ap.add_argument("--cohort", type=int, default=None,
                    help="cohort: resident working-set width (default: the "
                         "full population; cohort == population reproduces "
                         "the dense engine bitwise)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record engine/supplier spans and write a Chrome "
                         "trace-event JSON here (open in Perfetto); with "
                         "--processes N the merged multi-process trace "
                         "lands at this path instead")
    ap.add_argument("--metrics-jsonl", default=None, metavar="OUT.jsonl",
                    help="append one JSONL line per round plus a final "
                         "metrics-registry snapshot")
    ap.add_argument("--publish-snapshots", action="store_true",
                    help="publish the committed global model into a "
                         "repro.serving.SnapshotStore after every chunk "
                         "(the live-serving plane: a ServingEngine in the "
                         "same process hot-swaps between decode segments)")
    args = ap.parse_args(argv)

    tracer = obs_trace.install("train") if args.trace else None
    mreg = obs_metrics.MetricsRegistry()
    sink = (obs_metrics.JsonlSink(args.metrics_jsonl)
            if args.metrics_jsonl else None)

    cfg = model_config(args.arch, args.scale)
    params, _ = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    n_params = T.count_params(params)
    print(f"arch={cfg.name} params={n_params:,} clients={args.clients} "
          f"tau={args.tau} alg={args.algorithm}")

    # heterogeneous per-client bigram corpora (data/synthetic.py)
    streams = token_stream_heterogeneous(
        args.clients, args.seq, n_seqs_per_client=64,
        vocab=min(cfg.vocab, 512), seed=args.seed)

    reg = L1(lam=args.lam)
    alg = make_algorithm(args.algorithm, reg, args.tau, args.eta, args.eta_g)
    grad_fn = T.make_grad_fn(cfg)
    # any async flag activates the asynchrony stage; --async alone picks
    # the straggler clock (stages compose, so no either/or validation)
    run_async = (args.run_async or args.clock is not None
                 or args.buffer_size is not None
                 or args.staleness is not None or args.staleness_correct
                 or args.queue_depth is not None or args.upload is not None
                 or args.edges is not None)
    if args.autotune:
        from repro.tune import TrialPoint, Workload, tune

        record = tune(Workload(clock="straggler" if run_async else "none"),
                      budget=args.autotune, log=print)
        point = TrialPoint.from_dict(record["best"]["point"])
        print(f"autotune: adopting {point.describe()} "
              f"({record['measured_trials']} measured trials"
              f"{', cached' if record.get('cached') else ''})")
        args.chunk = point.chunk_rounds
        args.plane = point.plane
        args.transport = (None if point.transport == "dense"
                          else point.transport)
        args.compress_ratio = point.ratio
        args.granularity = point.granularity
        args.ratio_schedule = point.schedule
        if run_async:
            args.buffer_size = max(1, int(round(point.buffer_frac
                                                * args.clients)))
            args.queue_depth = point.queue_depth or None
            args.staleness = point.staleness
    transport = downlink = None
    if args.transport is not None or args.downlink is not None:
        from repro.comm import as_schedule, get_transport

        def build(name, uplink=False):
            # the schedule is an uplink policy (it reads the async age
            # ledger); the broadcast direction has no age signal
            if uplink and name == "topk" and args.ratio_schedule != \
                    "constant":
                return get_transport(
                    "topk_sched",
                    schedule=as_schedule(args.ratio_schedule,
                                         args.compress_ratio),
                    granularity=args.granularity)
            kw = ({"ratio": args.compress_ratio}
                  if name in ("topk", "randk") else {})
            if name != "dense":
                kw["granularity"] = args.granularity
            return get_transport(name, **kw)

        transport = (build(args.transport, uplink=True)
                     if args.transport else None)
        downlink = build(args.downlink) if args.downlink else None
    clock = staleness = None
    if run_async:
        from repro.sched import Staleness, get_clock

        clock_kw = ({"upload": args.upload}
                    if args.upload is not None else {})
        clock = get_clock(args.clock or "straggler", **clock_kw)
        staleness = Staleness(args.staleness or "uniform",
                              correct=args.staleness_correct)
    population = args.population if args.population is not None \
        else args.clients
    engine = RoundEngine(
        alg, grad_fn, population,
        EngineConfig(chunk_rounds=args.chunk,
                     participation=args.participation, transport=transport,
                     downlink=downlink, clock=clock,
                     buffer_size=args.buffer_size, staleness=staleness,
                     queue_depth=args.queue_depth, plane=args.plane,
                     edges=args.edges, population=args.population,
                     cohort=args.cohort))
    snapshots = None
    if args.publish_snapshots:
        from repro.serving import SnapshotStore

        snapshots = SnapshotStore()
        engine.set_snapshot_sink(
            snapshots.engine_sink(select=engine.global_params))
    state = engine.init(params)
    rng = np.random.default_rng(args.seed)

    # chunk-aware supplier over the token streams: the whole chunk is
    # gathered in one vectorized call (on device with --device-cache)
    sample_batches = ArraySupplier(
        {"tokens": streams.astype(np.int32)}, args.tau, args.batch,
        seed=args.seed, device_cache=args.device_cache,
        prefetch=args.prefetch)
    if population != args.clients:
        # simulated population >> data streams: global client g trains on
        # stream g mod --clients, so batch assembly only ever touches the
        # resident cohort's rows (never population-width)
        inner = sample_batches

        def sample_batches(r, rng, *, client_ids=None):
            ids = (np.arange(population) if client_ids is None
                   else np.asarray(client_ids))
            return inner.sample_round(r, rng,
                                      client_ids=ids % args.clients)

    t0 = obs_trace.now()
    last_loss = float("nan")

    def log_cb(ri, info):
        # fires per chunk (not per block), so logs stream every --chunk rounds
        if sink is not None:
            sink.write("round", round=int(ri),
                       **{k: float(v) for k, v in info.items()
                          if np.ndim(v) == 0})
        if ri % args.log_every == 0 or ri == args.rounds - 1:
            print(f"round {ri:5d}  loss {info.get('train_loss', np.nan):.4f}  "
                  f"({(obs_trace.now()-t0)/(ri+1):.2f}s/round)", flush=True)

    # checkpoint cadence only matters when checkpointing is on
    ckpt_every = (args.ckpt_every if args.ckpt and args.ckpt_every > 0
                  else args.rounds)
    r = 0
    while r < args.rounds:
        # align engine segments to the checkpoint cadence
        k = rounds_to_boundary(r, ckpt_every, args.rounds)
        state, metrics = engine.run(state, sample_batches, k,
                                    rng=rng, start_round=r,
                                    metrics_cb=log_cb)
        losses = metrics.get("train_loss", [])
        if losses:
            last_loss = losses[-1]
        r += k
        if args.ckpt and (r % ckpt_every == 0 or r == args.rounds):
            ckpt.save(state, args.ckpt,
                      metadata={"round": r, "arch": cfg.name,
                                "algorithm": args.algorithm})
            if engine.population_store is not None:
                # run() flushed the resident cohort at the segment end, so
                # the store rows are current; the sidecar checkpoint keeps
                # the swapped-out per-client state restorable too
                engine.population_store.save(
                    args.ckpt + ".store.npz", metadata={"round": r})
    final = engine.global_params(state)
    if args.ckpt:
        print(f"checkpoint -> {args.ckpt}"
              + (f" (+ {args.ckpt}.store.npz)"
                 if engine.population_store is not None else ""))
    from repro.core.metrics import sparsity

    print(f"done: final loss {last_loss:.4f}, "
          f"global-model sparsity {float(sparsity(final)):.3f}")
    if snapshots is not None:
        snap = snapshots.latest()
        print(f"snapshots: {snapshots.version} published, latest "
              f"v{snap.version} (round {snap.round}, "
              f"{snap.age():.2f}s old)")
    if engine.population_store is not None:
        st_ = engine.population_store
        print(f"cohort: {engine.n_clients}/{population} clients resident, "
              f"store {st_.touched} touched rows "
              f"({st_.nbytes / 1e6:.2f} MB host)")
    if run_async and metrics.get("vtime"):
        sm = metrics.get("staleness_mean", [0.0])
        depth = f" queue={engine.queue_depth}" if engine.queue_depth else ""
        print(f"async: clock={clock.name} buffer={engine.buffer_size}/"
              f"{args.clients}{depth}, "
              f"virtual time {metrics['vtime'][-1]:.1f}, "
              f"mean report age (last segment) {np.mean(sm):.2f} rounds")
    if engine.uplink_bytes_per_client_round is not None:
        dense = n_params * 4
        print(f"uplink: {engine.uplink_bytes_per_client_round/1e6:.2f} "
              f"MB/client/round ({engine.transport.name}; dense would be "
              f"{dense/1e6:.2f} MB)")
    if engine.downlink_bytes_per_client_round is not None:
        print(f"downlink: {engine.downlink_bytes_per_client_round/1e6:.2f} "
              f"MB/client/round ({engine.downlink.transport.name})")
    wall = obs_trace.now() - t0
    if sink is not None:
        mreg.gauge("round_throughput").set(args.rounds / max(wall, 1e-9))
        mreg.counter("rounds").add(args.rounds)
        if engine.uplink_bytes_per_client_round is not None:
            mreg.counter("uplink/bytes").add(
                engine.uplink_bytes_per_client_round * args.clients
                * args.rounds)
        sink.write_snapshot(mreg, rounds=int(args.rounds),
                            final_loss=float(last_loss))
        sink.close()
        print(f"metrics -> {args.metrics_jsonl}")
    if tracer is not None:
        obs_trace.write_chrome(obs_trace.to_chrome([tracer.export_wire()]),
                               args.trace)
        obs_trace.uninstall()
        print(f"trace -> {args.trace} ({tracer.n_spans} spans; open in "
              "Perfetto)")
    return state, snapshots


if __name__ == "__main__":
    main()
