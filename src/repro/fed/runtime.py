"""Multi-process federated runtime: real bytes on the wire, overlapped.

Everything before this module *simulated* distribution: `fed/distributed.py`
shards the engine across local devices, and ``uplink_bytes`` is accounting.
Here the client half and the server half are separate OS processes and the
uplink message actually crosses a socket, framed by :mod:`repro.comm.wire`.

Topology
--------
One **server** process and N **worker** processes over TCP (localhost or
not).  Each worker owns a contiguous shard of the client population and
runs the full :class:`repro.exec.RoundEngine` over its shard -- the same
compiled scan as single-process execution, bit for bit.  Per engine chunk
the worker ships one CHUNK frame:

  * the chunk's compressed uplink messages (the transport's actual output,
    re-encoded sparse/palette per ``Transport.wire_encoding`` so top-k and
    quantize frames carry their *compressed* byte count);
  * the worker's committed server-role fields after the chunk (one
    d-vector for DProx -- the paper's per-round communication object);
  * the server commit version the worker last synced against.

The server records every arrival in a real-time
:class:`repro.sched.ArrivalLedger` (the wall-clock analogue of the virtual
staleness ledger), ACKs, then commits:

  * ``N == 1``: the worker owns the trajectory; the server installs the
    committed fields verbatim -- the server state is **bitwise** the
    single-process trajectory -- and *replays* the server half over the
    received messages (with zeroed client-resident aux, which the
    server-role update provably never reads) as a drift check;
  * ``N > 1``: chunk-granular FedBuff -- the committed innovation of worker
    w against its base version is mixed in with weight
    ``(n_w / n_total) * staleness.weight(age)``.  Shard trajectories are
    only exact against single-process execution for ``N == 1`` (worker
    shards see shard-local server state within a chunk); N > 1 is the
    hierarchical semantics, not a bitwise claim.

Overlap
-------
``mode="blocking"`` fetches, serializes and sends inside the engine's
uplink sink -- the wire cost lands on the critical path, which is what
``benchmarks/wire_bench.py`` measures as the blocking baseline.
``mode="overlapped"`` applies the staging-thread idiom of
``ArraySupplier(prefetch=True)`` to the uplink: the sink drops the chunk's
still-device-resident arrays into a depth-1 queue and returns; a sender
thread fetches/serializes/sends chunk k while the compiled scan computes
chunk k+1 (host fetch, ``tobytes``, and ``sendall`` all release the GIL).
The depth-1 queue IS the double buffer: producing chunk k+2 blocks until
chunk k's bytes are on the wire, so at most two chunks of uplink exist at
once and backpressure is immediate.

``--throttle-bw`` paces the sender to a target bandwidth (bytes stay real,
timing is padded): wire_bench uses it to sweep the comm/compute ratio
around the roofline-predicted crossover on a loopback that would otherwise
be too fast to resolve.

Entry points: :func:`run_server` / :func:`run_worker` /
:func:`run_local` / :func:`run_pair`, and the CLI (``python -m
repro.fed.runtime --role pair --workers 1 ...``; ``launch/train.py
--processes=N`` re-execs itself through the same machinery).
"""
from __future__ import annotations

import argparse
import os
import queue
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from repro.comm import wire
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils.compile_cache import use_compile_cache

__all__ = ["RuntimeArgs", "run_local", "run_server", "run_worker",
           "run_replica", "run_pair", "shard_bounds", "add_runtime_args"]


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclass
class RuntimeArgs:
    """Everything both sides need to build identical problem + engine.

    The server and each worker construct the SAME algorithm/data/params
    from these fields (deterministic in the seeds), so only messages --
    never the problem -- cross the wire.
    """

    # problem (the paper's sparse logistic regression, Section 4.1)
    clients: int = 16
    m: int = 64
    dim: int = 256
    alpha: float = 50.0
    beta: float = 50.0
    data_seed: int = 0
    lam: float = 1e-3
    x64: bool = True
    # algorithm
    tau: int = 4
    eta: float = 0.05
    eta_g: float = 2.0
    # engine / comm
    transport: str = "dense"
    ratio: float = 0.1
    # per-commit ratio schedule for topk (repro.comm.schedule); "constant"
    # is bitwise the fixed-ratio transport.  The adaptive kinds only bite
    # under the async stage's age ledger -- the runtime's engines are
    # synchronous, so they run at the base ratio, but the flag keeps the
    # wire path exercising the scheduled encoder
    schedule: str = "constant"
    bits: int = 8
    plane: bool = False
    chunk: int = 4
    rounds: int = 16
    batch_size: Optional[int] = None
    # runtime
    host: str = "127.0.0.1"
    port: int = 0
    workers: int = 1
    mode: str = "overlapped"  # blocking | overlapped
    encoding: str = "auto"    # auto | dense | sparse | palette
    throttle_bw: Optional[float] = None  # bytes/s pacing on the sender
    replay: bool = True       # server-side drift check (N == 1)
    # serving replicas: read-only processes fed every committed server
    # plane as T_SNAP frames (XOR-bit deltas against a per-connection
    # shadow, dense keyframe every keyframe_every versions); each replica
    # proves bitwise reconstruction against the server's final fields
    replicas: int = 0
    keyframe_every: int = 8
    timeout: float = 120.0
    # observability (repro.obs): a trace path enables span recording in
    # EVERY process; workers ship their buffers in the BYE frame and the
    # server writes ONE merged Chrome trace-event JSON there.  The
    # metrics path makes the server append one JSONL line per commit plus
    # a final registry snapshot.
    trace: Optional[str] = None
    metrics_jsonl: Optional[str] = None


def shard_bounds(n_total: int, n_workers: int) -> list:
    """Contiguous client shard ``[lo, hi)`` per worker, remainder spread
    over the first shards."""
    base, rem = divmod(n_total, n_workers)
    out, lo = [], 0
    for w in range(n_workers):
        hi = lo + base + (1 if w < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def _problem(a: RuntimeArgs):
    """(algorithm, grad_fn, data arrays, params0) -- deterministic in
    ``a``, built identically by every process."""
    import jax

    if a.x64:
        jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.core.algorithm import DProxConfig
    from repro.core.prox import L1
    from repro.data.synthetic import logistic_heterogeneous
    from repro.fed.simulator import DProxAlgorithm
    from repro.models import logreg

    data = logistic_heterogeneous(n_clients=a.clients, m_per_client=a.m,
                                  d=a.dim, alpha=a.alpha, beta=a.beta,
                                  seed=a.data_seed)
    scale = np.linalg.norm(data.features.reshape(-1, a.dim), axis=1).max()
    dt = np.float64 if a.x64 else np.float32
    data.features = (data.features / scale).astype(dt)
    data.labels = data.labels.astype(dt)
    alg = DProxAlgorithm(L1(lam=a.lam),
                         DProxConfig(tau=a.tau, eta=a.eta, eta_g=a.eta_g))
    params0 = {"w": jnp.zeros(a.dim, dt), "b": jnp.zeros((), dt)}
    return alg, logreg.make_grad_fn(), data, params0


def _transport(a: RuntimeArgs):
    from repro.comm import as_schedule, get_transport

    if a.transport == "topk" and a.schedule != "constant":
        return get_transport("topk_sched",
                             schedule=as_schedule(a.schedule, a.ratio))
    kw = {}
    if a.transport in ("topk", "randk"):
        kw["ratio"] = a.ratio
    elif a.transport == "quantize":
        kw["bits"] = a.bits
    return get_transport(a.transport, **kw)


def _engine(a: RuntimeArgs, n_clients: int):
    from repro.exec import EngineConfig, RoundEngine

    alg, grad_fn, data, params0 = _problem(a)
    eng = RoundEngine(alg, grad_fn, n_clients,
                      EngineConfig(chunk_rounds=a.chunk,
                                   transport=_transport(a), plane=a.plane))
    return eng, alg, grad_fn, data, params0


def _supplier(a: RuntimeArgs, data, lo: int, hi: int):
    from repro.exec.suppliers import ArraySupplier

    return ArraySupplier(
        {"a": data.features[lo:hi], "y": data.labels[lo:hi]},
        tau=a.tau, batch_size=a.batch_size, seed=a.data_seed)


def _server_fields(algorithm, state) -> dict:
    """Server-role state fields as host pytrees (field -> np-leafed tree:
    a field like DProx's ``x_bar`` is itself a params pytree)."""
    import jax

    from repro.exec.engine import server_state_fields

    return jax.tree_util.tree_map(
        np.asarray, server_state_fields(algorithm, state))


# ---------------------------------------------------------------------------
# single-process reference
# ---------------------------------------------------------------------------


def run_local(a: RuntimeArgs, sink=None) -> dict:
    """The single-process trajectory every multi-process claim is pinned
    against.  ``sink``, if given, is installed as the engine's uplink tap
    (wire_bench uses a serialize-and-drop sink to isolate codec cost)."""
    eng, alg, grad_fn, data, params0 = _engine(a, a.clients)
    sup = _supplier(a, data, 0, a.clients)
    if sink is not None:
        eng.set_uplink_sink(sink)
    state = eng.init(params0)
    t0 = time.perf_counter()
    state, metrics = eng.run(state, sup, a.rounds, seed=0)
    wall = time.perf_counter() - t0
    return {"fields": _server_fields(alg, state), "metrics": metrics,
            "wall_s": wall, "rounds": a.rounds}


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------


class _UplinkSender:
    """The uplink half of the overlap pipeline (see module docstring).

    ``sink`` is what gets registered via ``RoundEngine.set_uplink_sink``;
    blocking mode does the fetch/serialize/send/ACK inline, overlapped mode
    hands the device-resident chunk to the sender thread through a depth-1
    queue (the double buffer) and returns to the compute loop.
    """

    def __init__(self, sock, rank: int, algorithm, plane_spec, encoding: str,
                 mode: str, chunk: int, throttle_bw: Optional[float] = None):
        self.sock = sock
        self.rank = rank
        self.algorithm = algorithm
        self.plane_spec = plane_spec  # SegmentSpec in plane mode, else None
        self.encoding = encoding
        self.mode = mode
        self.chunk = chunk
        self.throttle_bw = throttle_bw
        self.base_version = 0
        # the sender's numbers live in a metrics registry (one schema,
        # snapshot-able); report() preserves the historical result keys
        self.metrics = obs_metrics.MetricsRegistry()
        self._m_bytes = self.metrics.counter("uplink/bytes")
        self._m_chunks = self.metrics.counter("uplink/chunks")
        # time the COMPUTE thread spent blocked handing off / sending
        self._m_wait = self.metrics.counter("uplink/send_wait_s")
        # time the wire path itself took (fetch + pack + send + ACK)
        self._m_busy = self.metrics.counter("uplink/sender_busy_s")
        self._err: Optional[BaseException] = None
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        if mode == "overlapped":
            self._q = queue.Queue(maxsize=1)
            self._thread = threading.Thread(target=self._drain, daemon=True)
            self._thread.start()
        elif mode != "blocking":
            raise ValueError(f"unknown runtime mode {mode!r}")

    # -- the engine-facing callback --------------------------------------

    def sink(self, start_round: int, msgs, state) -> None:
        from repro.exec import engine as engine_mod
        from repro.utils.tree import tree_copy_shared

        if self._err is not None:
            raise RuntimeError("uplink sender died") from self._err
        committed = engine_mod.server_state_fields(self.algorithm, state)
        if engine_mod.donates():
            # copied here, on the compute thread and outside the wait span:
            # the engine donates ``state`` to the next chunk's call, which
            # deletes its buffers before the sender thread reads them
            committed = tree_copy_shared(committed, state)
        with obs_trace.timed("uplink/wait", "uplink",
                             start_round=int(start_round)) as tm:
            if self._q is None:
                self._ship(start_round, msgs, committed)
            else:
                self._q.put((start_round, msgs, committed))
        self._m_wait.add(tm.seconds)

    # -- internals --------------------------------------------------------

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                self._ship(*item)
            except BaseException as e:  # surfaced on the compute thread
                self._err = e
                return
            finally:
                self._q.task_done()

    def _ship(self, start_round: int, msgs, committed) -> None:
        import jax

        t0 = time.perf_counter()
        with obs_trace.span("uplink/ship", "uplink",
                            start_round=int(start_round)) as sp:
            # host fetch happens HERE (on the sender thread when
            # overlapped): np.asarray blocks until the chunk's computation
            # delivers, then everything below is plain host bytes
            with obs_trace.span("uplink/fetch_pack", "uplink"):
                if self.plane_spec is not None:
                    flat = np.asarray(msgs)  # (c, n, d_pad)
                    c = flat.shape[0]
                    packed = wire.pack_plane(flat, self.encoding)
                else:
                    host = jax.tree_util.tree_map(np.asarray, msgs)
                    c = jax.tree_util.tree_leaves(host)[0].shape[0]
                    packed = wire.pack_message(host, self.encoding)
            frame = {
                "worker": self.rank,
                "start_round": int(start_round),
                "rounds": int(c),
                "base_version": int(self.base_version),
                "msgs": packed,
                "committed": jax.tree_util.tree_map(np.asarray, committed),
            }
            nb = wire.send_frame(self.sock, wire.T_CHUNK, frame)
            sp.set(nbytes=nb, rounds=int(c))
            if self.throttle_bw:
                time.sleep(max(0.0, nb / self.throttle_bw
                               - (time.perf_counter() - t0)))
            ftype, ack = wire.recv_frame(self.sock)
            if ftype != wire.T_ACK:
                raise wire.WireError(f"expected ACK, got frame type {ftype}")
        self.base_version = ack["version"]
        self._m_bytes.add(nb)
        self._m_chunks.add(1)
        self._m_busy.add(time.perf_counter() - t0)

    def finish(self) -> None:
        """Flush the queue and surface any sender-thread failure."""
        if self._q is not None:
            self._q.put(None)
            self._thread.join()
        if self._err is not None:
            raise RuntimeError("uplink sender died") from self._err

    # historical attribute surface, now registry-backed
    @property
    def bytes_sent(self) -> int:
        return int(self._m_bytes.value)

    @property
    def chunks(self) -> int:
        return int(self._m_chunks.value)

    @property
    def send_wait_s(self) -> float:
        return self._m_wait.value

    @property
    def sender_busy_s(self) -> float:
        return self._m_busy.value

    def report(self) -> dict:
        return {"mode": self.mode, "encoding": self.encoding,
                "chunks": self.chunks, "bytes_sent": self.bytes_sent,
                "send_wait_s": self.send_wait_s,
                "sender_busy_s": self.sender_busy_s}


def _connect(a: RuntimeArgs) -> socket.socket:
    deadline = time.monotonic() + a.timeout
    while True:
        try:
            sock = socket.create_connection((a.host, a.port), timeout=5.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.settimeout(a.timeout)
            return sock
        except OSError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.05)


def run_worker(a: RuntimeArgs, rank: int) -> dict:
    """One worker process: build the shard engine, stream chunks, return
    the worker report + the server's final result frame."""
    import jax

    eng, alg, grad_fn, data, params0 = None, None, None, None, None
    lo, hi = shard_bounds(a.clients, a.workers)[rank]
    eng, alg, grad_fn, data, params0 = _engine(a, hi - lo)
    sup = _supplier(a, data, lo, hi)
    state = eng.init(params0)

    # the wire shape, computed before the first chunk (eval_shape only)
    one_round = sup.sample_round(0, np.random.default_rng(0))
    local_fn = alg.make_local_fn(grad_fn)
    msg_spec, aux_spec = jax.eval_shape(local_fn, state, one_round)
    plane_spec = None
    if a.plane:
        from repro.core.plane import SegmentSpec

        plane_spec = SegmentSpec.from_tree(msg_spec, batch_dims=1)
    encoding = a.encoding
    if encoding == "auto":
        encoding = _transport(a).wire_encoding

    # install() is idempotent: in the in-process threaded topology the
    # server may already own the tracer, in which case this worker shares
    # it (one bundle; the merge dedupes by pid) and must NOT uninstall it
    owns_tracer = a.trace and not isinstance(obs_trace.get(),
                                             obs_trace.Tracer)
    tracer = obs_trace.install(f"worker{rank}") if a.trace else None
    sock = _connect(a)
    try:
        # the HELLO/ACK round trip doubles as the clock-offset estimate:
        # the server stamps its own monotonic clock into the ACK, and
        # (assuming symmetric latency) that stamp corresponds to the
        # midpoint of our send/recv window -- every shipped span lands on
        # the server timebase within half a round trip
        t_send = obs_trace.now()
        wire.send_frame(sock, wire.T_HELLO, {
            "worker": rank, "lo": lo, "hi": hi, "n_total": a.clients,
            "rounds": a.rounds, "chunk": a.chunk, "mode": a.mode,
            "encoding": encoding, "plane": a.plane,
            "spec": wire.spec_to_wire(plane_spec) if a.plane else None,
            "aux_spec": aux_spec,
        })
        ftype, hello_ack = wire.recv_frame(sock)
        t_recv = obs_trace.now()
        if ftype != wire.T_ACK:
            raise wire.WireError(f"expected HELLO ACK, got type {ftype}")
        if tracer is not None and "srv_now" in hello_ack:
            tracer.offset = obs_trace.clock_offset(
                t_send, t_recv, hello_ack["srv_now"])

        sender = _UplinkSender(sock, rank, alg, plane_spec, encoding,
                               a.mode, a.chunk, a.throttle_bw)
        eng.set_uplink_sink(sender.sink)
        t0 = time.perf_counter()
        state, metrics = eng.run(state, sup, a.rounds, seed=0)
        sender.finish()
        wall = time.perf_counter() - t0

        wire.send_frame(sock, wire.T_BYE, {
            "worker": rank, "report": sender.report(),
            "trace": tracer.export_wire() if tracer is not None else None})
        ftype, result = wire.recv_frame(sock)
        if ftype != wire.T_RESULT:
            raise wire.WireError(f"expected RESULT, got type {ftype}")
    finally:
        sock.close()
        if tracer is not None and owns_tracer:
            obs_trace.uninstall()
    rep = sender.report()
    rep.update({"worker": rank, "lo": lo, "hi": hi, "wall_s": wall,
                "rounds": a.rounds, "metrics": metrics,
                "fields": _server_fields(alg, state),
                "server_result": result})
    return rep


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


class _ServerState:
    """Authoritative server-role fields + per-version snapshots + ledger."""

    def __init__(self, algorithm, a: RuntimeArgs):
        from repro.sched import ArrivalLedger, Staleness

        import jax

        _, _, _, params0 = _problem(a)  # jax config side effect included
        state0 = algorithm.init(params0, a.clients)
        self.algorithm = algorithm
        self.args = a
        self.fields = _server_fields(algorithm, state0)
        self.ledger = ArrivalLedger()
        self.staleness = Staleness()
        self.snapshots = {0: dict(self.fields)}
        self.rounds_done = 0
        self.max_drift = 0.0
        self.lock = threading.Lock()
        # the serving plane: every commit publishes its fields snapshot
        # (store versions track ledger versions one-to-one); replica
        # connections block on wait_for and stream deltas off it
        from repro.serving import SnapshotStore

        self.store = SnapshotStore()
        self.workers_left = a.workers
        self.finished = threading.Event()
        self._replay_step = None
        self._replay_state = state0 if (a.replay and a.workers == 1) else None
        # the unified metrics surface: commit-path counters/histograms land
        # here, one JSONL line per commit when a sink is attached
        from repro.sched.aggregator import AGE_HIST_BUCKETS

        self.metrics = obs_metrics.MetricsRegistry()
        self.sink = (obs_metrics.JsonlSink(a.metrics_jsonl)
                     if a.metrics_jsonl else None)
        self._m_bytes = self.metrics.counter("uplink/bytes")
        self._m_commits = self.metrics.counter("commits")
        self._m_age = self.metrics.histogram("arrival/age",
                                             buckets=AGE_HIST_BUCKETS)
        self._m_weight = self.metrics.gauge("commit/weight")
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    # -- replay (the aux-independence check, N == 1) ----------------------

    def _replay(self, msgs_tree, spec, aux_spec, rounds: int) -> None:
        """Re-run the server half over the received messages with ZEROED
        client-resident aux.  The server-role update (DProx Lines 14-15)
        depends only on (state, message) -- aux feeds the client-side
        correction -- so replayed x_bar tracks the worker's committed
        x_bar; the gap is pure XLA fusion noise and is reported as
        ``max_drift``."""
        import jax
        import jax.numpy as jnp

        from repro.core import plane as pln

        if self._replay_step is None:
            server_fn = self.algorithm.make_server_fn()
            zero_aux = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), aux_spec)
            self._replay_step = jax.jit(
                lambda st, m: server_fn(st, m, zero_aux)[0])
        st = self._replay_state
        for r in range(rounds):
            if spec is not None:
                msg = pln.unflatten(spec, jnp.asarray(msgs_tree[r]))
            else:
                msg = jax.tree_util.tree_map(lambda l: jnp.asarray(l[r]),
                                             msgs_tree)
            st = self._replay_step(st, msg)
        self._replay_state = st

    def drift_vs(self, committed: dict) -> float:
        import jax

        replayed = _server_fields(self.algorithm, self._replay_state)
        diffs = jax.tree_util.tree_map(
            lambda r, c: float(np.max(np.abs(r - c))) if np.size(c) else 0.0,
            replayed, committed)
        return max(jax.tree_util.tree_leaves(diffs), default=0.0)

    # -- commit -----------------------------------------------------------

    def commit(self, frame: dict, nbytes: int, spec, aux_spec) -> dict:
        """Apply one CHUNK frame; returns the ACK payload.  Caller holds
        no lock -- this takes it."""
        with self.lock, obs_trace.span(
                "server/commit", "server", worker=frame["worker"],
                start_round=frame["start_round"], nbytes=nbytes):
            arrival = self.ledger.record(
                frame["worker"], frame["start_round"], frame["rounds"],
                nbytes, frame["base_version"])
            committed = frame["committed"]
            n_w = self._shard_width(frame["worker"])
            w = 1.0
            if self.args.workers == 1:
                # single trajectory owner: install verbatim (bitwise)
                if self._replay_state is not None:
                    with obs_trace.span("server/replay", "server",
                                        rounds=frame["rounds"]):
                        self._replay(frame["msgs"], spec, aux_spec,
                                     frame["rounds"])
                    self.max_drift = max(self.max_drift,
                                         self.drift_vs(committed))
                self.fields = dict(committed)
            else:
                # chunk-granular FedBuff: mix the worker's innovation
                # against its base snapshot, staleness-weighted
                import jax

                base = self.snapshots.get(frame["base_version"],
                                          self.fields)
                w = ((n_w / self.args.clients)
                     * float(self.ledger.weights_for([arrival],
                                                     self.staleness)[0]))
                self.fields = jax.tree_util.tree_map(
                    lambda cur, com, b: cur + w * (com - b),
                    self.fields, committed, base)
            version = self.ledger.bump()
            self.snapshots[version] = dict(self.fields)
            self.rounds_done = max(self.rounds_done,
                                   frame["start_round"] + frame["rounds"])
            self.store.publish(self.snapshots[version],
                               round=self.rounds_done)
            t = obs_trace.now()
            if self._t_first is None:
                self._t_first = t
            self._t_last = t
            self._m_bytes.add(nbytes)
            self._m_commits.add(1)
            self._m_age.observe(arrival.age)
            self._m_weight.set(w)
            if self.sink is not None:
                self.sink.write("commit", worker=frame["worker"],
                                version=version, start_round=frame[
                                    "start_round"],
                                rounds=frame["rounds"], nbytes=nbytes,
                                age=arrival.age, weight=w)
            return {"version": version, "age": arrival.age,
                    "t": arrival.t}

    def _shard_width(self, rank: int) -> int:
        lo, hi = shard_bounds(self.args.clients, self.args.workers)[rank]
        return hi - lo

    def result(self) -> dict:
        with self.lock:
            if self._t_first is not None and self._t_last > self._t_first:
                self.metrics.gauge("round_throughput").set(
                    self.rounds_done / (self._t_last - self._t_first))
            return {"fields": self.fields, "version": self.ledger.version,
                    "rounds_done": self.rounds_done,
                    "max_replay_drift": self.max_drift,
                    "ledger": self.ledger.summary(),
                    "age_histogram": self.ledger.age_histogram(),
                    "metrics": self.metrics.snapshot()}


def _serve_conn(conn, srv: _ServerState, reports: dict,
                traces: Optional[dict] = None) -> None:
    """One worker OR replica connection, dispatched on its HELLO.  Runs on
    its own thread; the commit path serializes on the server-state lock."""
    spec = None
    aux_spec = None
    try:
        ftype, hello = wire.recv_frame(conn)
        if ftype != wire.T_HELLO:
            raise wire.WireError(f"expected HELLO, got type {ftype}")
        if hello.get("replica") is not None:
            _serve_replica(conn, srv, hello, reports)
            return
        if hello["spec"] is not None:
            spec = wire.spec_from_wire(hello["spec"])
        aux_spec = hello["aux_spec"]
        # srv_now is the worker's clock-offset reference (see run_worker)
        wire.send_frame(conn, wire.T_ACK, {"version": srv.ledger.version,
                                           "srv_now": obs_trace.now()})
        while True:
            with obs_trace.span("wire/recv", "wire") as sp:
                buf = _recv_raw_frame(conn)
                sp.set(nbytes=len(buf))
            with obs_trace.span("wire/decode", "wire", nbytes=len(buf)):
                ftype, tree, _ = wire.decode_frame(buf)
            if ftype == wire.T_BYE:
                reports[tree["worker"]] = tree.get("report", {})
                if traces is not None and tree.get("trace") is not None:
                    traces[tree["worker"]] = tree["trace"]
                with srv.lock:
                    srv.workers_left -= 1
                    if srv.workers_left <= 0:
                        srv.finished.set()
                break
            if ftype != wire.T_CHUNK:
                raise wire.WireError(f"unexpected frame type {ftype}")
            if spec is None and tree["msgs"].get("skeleton") is None:
                pass
            msgs = (wire.unpack_plane(tree["msgs"]) if spec is not None
                    else wire.unpack_message(tree["msgs"]))
            frame = dict(tree)
            frame["msgs"] = msgs
            ack = srv.commit(frame, len(buf), spec, aux_spec)
            wire.send_frame(conn, wire.T_ACK, ack)
        wire.send_frame(conn, wire.T_RESULT, srv.result())
    finally:
        conn.close()


def _serve_replica(conn, srv: _ServerState, hello: dict,
                   reports: dict) -> None:
    """One replica connection: stream every committed serving snapshot as
    a T_SNAP frame (delta against this connection's shadow, keyframe per
    the cadence), then the final RESULT the replica proves itself against.

    A late joiner is fine: the first frame any publisher emits is a dense
    keyframe, and a delta's base is whatever was last shipped on THIS
    connection -- versions skipped while encoding lag behind commits are
    bridged by a single delta, never a gap.
    """
    from repro.serving import DeltaPublisher

    a = srv.args
    enc = a.encoding if a.encoding in wire.PLANE_ENCODINGS else "sparse"
    pub = DeltaPublisher(keyframe_every=a.keyframe_every, encoding=enc)
    rank = hello["replica"]
    wire.send_frame(conn, wire.T_ACK, {"version": srv.ledger.version,
                                       "srv_now": obs_trace.now()})
    sent = 0
    nbytes = 0
    next_v = 1
    while True:
        snap = srv.store.wait_for(next_v, timeout=0.05)
        if snap is None:
            if srv.finished.is_set() and srv.store.version < next_v:
                break
            continue
        frame = pub.encode(snap)
        with obs_trace.span("serve/snap_send", "serve",
                            version=snap.version, kind=frame["kind"]) as sp:
            nb = wire.send_frame(conn, wire.T_SNAP, frame)
            sp.set(nbytes=nb)
        nbytes += nb
        sent += 1
        next_v = snap.version + 1
    reports[f"replica{rank}"] = {"frames": sent, "bytes_sent": nbytes,
                                 "last_version": next_v - 1}
    wire.send_frame(conn, wire.T_RESULT, srv.result())


def run_replica(a: RuntimeArgs, rank: int = 0) -> dict:
    """One replica process: subscribe to the server's snapshot feed, apply
    every T_SNAP frame (keyframe or XOR delta, digest-checked), and verify
    the final reconstructed plane bitwise against the server's RESULT."""
    from repro.serving import DeltaReplica

    sock = _connect(a)
    rep = DeltaReplica()
    nbytes = 0
    keyframes = 0
    try:
        wire.send_frame(sock, wire.T_HELLO,
                        {"replica": rank, "n_total": a.clients})
        ftype, _ack = wire.recv_frame(sock)
        if ftype != wire.T_ACK:
            raise wire.WireError(f"expected HELLO ACK, got type {ftype}")
        while True:
            buf = _recv_raw_frame(sock)
            ftype, tree, _ = wire.decode_frame(buf)
            if ftype == wire.T_RESULT:
                result = tree
                break
            if ftype != wire.T_SNAP:
                raise wire.WireError(f"unexpected frame type {ftype}")
            nbytes += len(buf)
            keyframes += int(tree["kind"] == "key")
            rep.apply(tree)
    finally:
        sock.close()
    ok = rep.plane is not None and _fields_bitwise(rep.plane,
                                                   result["fields"])
    return {"replica": rank, "ok": ok, "applied": rep.applied,
            "skipped": rep.skipped, "version": rep.version,
            "keyframes": keyframes, "bytes_received": nbytes,
            "server_result": result}


def _recv_raw_frame(sock) -> bytes:
    """Receive one frame's raw bytes (header + payload) so the server can
    account exact wire bytes before decoding."""
    hdr = wire._recv_exact(sock, wire.HEADER_BYTES)
    import struct

    length = struct.unpack(">Q", hdr[-8:])[0]
    if length > wire.MAX_PAYLOAD:
        raise wire.WireError(f"frame claims {length} payload bytes")
    return hdr + wire._recv_exact(sock, length)


def run_server(a: RuntimeArgs, *, ready_cb=None) -> dict:
    """The server process: accept ``a.workers + a.replicas`` connections
    (each dispatched on its HELLO), drive workers to BYE and replicas to
    the end of the snapshot stream, return the final result (also what
    each worker and replica receives)."""
    owns_tracer = a.trace and not isinstance(obs_trace.get(),
                                             obs_trace.Tracer)
    tracer = obs_trace.install("server") if a.trace else None
    alg, _, _, _ = _problem(a)
    srv = _ServerState(alg, a)
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind((a.host, a.port))
    lsock.listen(a.workers + a.replicas)
    lsock.settimeout(a.timeout)
    port = lsock.getsockname()[1]
    if ready_cb is not None:
        ready_cb(port)
    reports: dict = {}
    traces: dict = {}
    threads = []
    try:
        for _ in range(a.workers + a.replicas):
            conn, _addr = lsock.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn.settimeout(a.timeout)
            t = threading.Thread(target=_serve_conn,
                                 args=(conn, srv, reports, traces),
                                 daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(a.timeout)
            if t.is_alive():
                raise TimeoutError("worker connection did not complete")
    finally:
        lsock.close()
    out = srv.result()
    out["worker_reports"] = reports
    out["port"] = port
    if srv.sink is not None:
        srv.sink.write_snapshot(srv.metrics, rounds_done=srv.rounds_done)
        srv.sink.close()
    if tracer is not None:
        # the merge: server spans (offset 0 -- the reference clock) + every
        # worker's shipped bundle, already offset onto this timebase.  The
        # server bundle goes first so merge_wire's pid dedupe keeps the
        # complete in-process bundle when a threaded worker shares it.
        doc = obs_trace.to_chrome([tracer.export_wire()]
                                  + [traces[w] for w in sorted(traces)])
        obs_trace.write_chrome(doc, a.trace)
        out["trace_path"] = a.trace
        if owns_tracer:
            obs_trace.uninstall()
    return out


# ---------------------------------------------------------------------------
# pair launcher (server subprocess + workers; rank 0 inline)
# ---------------------------------------------------------------------------


def _free_port(host: str) -> int:
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _tpu_chips() -> int:
    """TPU chips on this host's PCI bus, counted without starting a JAX
    backend (0 where JAX is held to other platforms)."""
    from jax._src import hardware_utils

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _backend_started() -> bool:
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _chip_env(chip: int, port: int) -> dict:
    """Environment that confines a process to TPU chip ``chip`` as a
    one-chip slice of its own: libtpu then lets several processes of one
    host hold a chip each, without ``ALLOW_MULTIPLE_LIBTPU_LOAD``."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}"}


def _worker_chip_envs(a: RuntimeArgs) -> Optional[list]:
    """One :func:`_chip_env` per worker rank on a TPU host that runs more
    than one worker, else None; refuses more workers than chips."""
    if a.workers <= 1:
        return None
    chips = _tpu_chips()
    if chips == 0:
        return None
    if a.workers > chips:
        raise ValueError(f"--workers {a.workers} needs one TPU chip per "
                         f"worker process, and this host has {chips}")
    return [_chip_env(w, _free_port("127.0.0.1")) for w in range(a.workers)]


def _spawn(a: RuntimeArgs, role: str, rank: int = 0,
           chip_env: Optional[dict] = None) -> subprocess.Popen:
    argv = [sys.executable, "-m", "repro.fed.runtime",
            "--role", role, "--rank", str(rank)] + _to_argv(a)
    env = dict(os.environ)
    env.update(chip_env or {})
    # the directory holding the ``repro`` package (``src/``)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if "PYTHONPATH" in env else []))
    if role != "worker":
        # the server and the replicas do host work only: keep them off the
        # accelerator, which belongs to the worker processes
        env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(argv, env=env)


def run_pair(a: RuntimeArgs) -> dict:
    """Server subprocess + ``a.workers`` workers (rank 0 runs in this
    process so its report and exceptions surface directly).

    libtpu lets one process of a host load it (``/tmp/libtpu_lockfile``)
    unless each process is confined to chips of its own.  On a TPU host
    with several workers, worker ``w`` gets chip ``w`` (:func:`_chip_env`),
    this process chip 0 for good, before it first starts a backend; more
    workers than chips are refused."""
    envs = _worker_chip_envs(a)
    if envs is not None and os.environ.get("TPU_VISIBLE_CHIPS") != "0":
        if _backend_started():
            raise RuntimeError(
                f"--workers {a.workers} on the TPU: this process has started "
                "JAX on every chip already; launch the workers before "
                "anything runs on the device")
        os.environ.update(envs[0])
    if a.port == 0:
        a.port = _free_port(a.host)
    procs = [_spawn(a, "server")]
    try:
        procs += [_spawn(a, "worker", rank=w,
                         chip_env=None if envs is None else envs[w])
                  for w in range(1, a.workers)]
        procs += [_spawn(a, "replica", rank=r) for r in range(a.replicas)]
        rep = run_worker(a, rank=0)
        for p in procs:
            rc = p.wait(timeout=a.timeout)
            if rc != 0:
                raise RuntimeError(f"runtime subprocess exited with {rc}")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return rep


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def add_runtime_args(ap: argparse.ArgumentParser) -> None:
    """The runtime's own flags (shared with ``launch/train.py
    --processes``)."""
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--eta-g", type=float, default=2.0)
    ap.add_argument("--lam", type=float, default=1e-3)
    ap.add_argument("--transport", default="dense",
                    choices=["dense", "topk", "randk", "quantize"])
    ap.add_argument("--ratio", type=float, default=0.1)
    ap.add_argument("--schedule", default="constant",
                    choices=["constant", "linear", "bucketed"],
                    help="per-commit topk ratio schedule "
                         "(repro.comm.schedule; constant == fixed ratio)")
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--plane", action="store_true")
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--mode", default="overlapped",
                    choices=["blocking", "overlapped"])
    ap.add_argument("--encoding", default="auto",
                    choices=["auto"] + list(wire.PLANE_ENCODINGS))
    ap.add_argument("--throttle-bw", type=float, default=None,
                    help="pace the sender to this bandwidth (bytes/s)")
    ap.add_argument("--no-replay", action="store_true",
                    help="skip the server-side replay drift check")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serving replicas fed delta-compressed snapshot "
                    "frames (each verifies bitwise reconstruction)")
    ap.add_argument("--keyframe-every", type=int, default=8,
                    help="dense keyframe cadence on the replica feed")
    ap.add_argument("--x32", action="store_true",
                    help="run in float32 (default float64)")
    ap.add_argument("--timeout", type=float, default=120.0)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record spans in every process and write ONE "
                    "merged Chrome trace-event JSON here (open in "
                    "Perfetto)")
    ap.add_argument("--metrics-jsonl", default=None, metavar="OUT.jsonl",
                    help="server appends one JSONL line per commit plus a "
                    "final metrics snapshot")


def _from_ns(ns: argparse.Namespace) -> RuntimeArgs:
    return RuntimeArgs(
        clients=ns.clients, m=ns.m, dim=ns.dim, tau=ns.tau, eta=ns.eta,
        eta_g=ns.eta_g, lam=ns.lam, x64=not ns.x32, transport=ns.transport,
        ratio=ns.ratio, schedule=ns.schedule, bits=ns.bits,
        plane=ns.plane, chunk=ns.chunk,
        rounds=ns.rounds, batch_size=ns.batch_size, host=ns.host,
        port=ns.port, workers=ns.workers, mode=ns.mode,
        encoding=ns.encoding, throttle_bw=ns.throttle_bw,
        replay=not ns.no_replay, replicas=ns.replicas,
        keyframe_every=ns.keyframe_every, timeout=ns.timeout,
        trace=ns.trace, metrics_jsonl=ns.metrics_jsonl)


def _to_argv(a: RuntimeArgs) -> list:
    argv = ["--clients", str(a.clients), "--m", str(a.m),
            "--dim", str(a.dim), "--tau", str(a.tau), "--eta", str(a.eta),
            "--eta-g", str(a.eta_g), "--lam", str(a.lam),
            "--transport", a.transport, "--ratio", str(a.ratio),
            "--schedule", a.schedule,
            "--bits", str(a.bits), "--chunk", str(a.chunk),
            "--rounds", str(a.rounds), "--host", a.host,
            "--port", str(a.port), "--workers", str(a.workers),
            "--mode", a.mode, "--encoding", a.encoding,
            "--replicas", str(a.replicas),
            "--keyframe-every", str(a.keyframe_every),
            "--timeout", str(a.timeout)]
    if a.batch_size is not None:
        argv += ["--batch-size", str(a.batch_size)]
    if a.throttle_bw is not None:
        argv += ["--throttle-bw", str(a.throttle_bw)]
    if a.trace is not None:
        argv += ["--trace", a.trace]
    if a.metrics_jsonl is not None:
        argv += ["--metrics-jsonl", a.metrics_jsonl]
    if a.plane:
        argv.append("--plane")
    if not a.replay:
        argv.append("--no-replay")
    if not a.x64:
        argv.append("--x32")
    return argv


def _fields_bitwise(x: dict, y: dict) -> bool:
    import jax

    xl, xd = jax.tree_util.tree_flatten(x)
    yl, yd = jax.tree_util.tree_flatten(y)
    return xd == yd and all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(xl, yl))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-process federated runtime (see module docstring)")
    ap.add_argument("--role", default="pair",
                    choices=["local", "server", "worker", "replica",
                             "pair"])
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--check-parity", action="store_true",
                    help="(pair, workers=1) also run single-process and "
                    "assert the server trajectory matches bitwise")
    add_runtime_args(ap)
    ns = ap.parse_args(argv)
    a = _from_ns(ns)
    use_compile_cache()

    if ns.role == "local":
        res = run_local(a)
        print(f"local: rounds={a.rounds} wall={res['wall_s']:.3f}s "
              f"loss={res['metrics']['train_loss'][-1]:.6f}")
        return 0
    if ns.role == "server":
        res = run_server(a)
        print(f"server: version={res['version']} "
              f"rounds={res['rounds_done']} "
              f"drift={res['max_replay_drift']:.3e} "
              f"ledger={res['ledger']}")
        return 0
    if ns.role == "worker":
        rep = run_worker(a, rank=ns.rank)
        print(f"worker[{ns.rank}]: wall={rep['wall_s']:.3f}s "
              f"sent={rep['bytes_sent']}B wait={rep['send_wait_s']:.3f}s")
        return 0
    if ns.role == "replica":
        rep = run_replica(a, rank=ns.rank)
        print(f"replica[{ns.rank}]: applied={rep['applied']} "
              f"keyframes={rep['keyframes']} recv={rep['bytes_received']}B "
              f"v{rep['version']} "
              f"reconstruction={'BITWISE' if rep['ok'] else 'MISMATCH'}")
        return 0 if rep["ok"] else 1
    # pair
    rep = run_pair(a)
    res = rep["server_result"]
    print(f"pair: workers={a.workers} mode={a.mode} rounds={a.rounds} "
          f"wall={rep['wall_s']:.3f}s sent={rep['bytes_sent']}B "
          f"wait={rep['send_wait_s']:.3f}s "
          f"drift={res['max_replay_drift']:.3e}")
    if a.trace:
        print(f"trace: {a.trace} (merged Chrome trace-event JSON)")
    if a.metrics_jsonl:
        print(f"metrics: {a.metrics_jsonl}")
    if ns.check_parity:
        if a.workers != 1:
            print("parity check needs --workers 1", file=sys.stderr)
            return 2
        local = run_local(a)
        ok = _fields_bitwise(local["fields"], res["fields"])
        print(f"parity: {'BITWISE' if ok else 'MISMATCH'}")
        if not ok:
            import jax

            diffs = jax.tree_util.tree_map(
                lambda a, b: float(np.max(np.abs(a - b))),
                local["fields"], res["fields"])
            print(f"  max|diff| per field: {diffs}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
