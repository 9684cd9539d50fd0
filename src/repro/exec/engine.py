"""The round-execution engine (see package docstring for the overview).

Execution model
---------------

``RoundEngine`` wraps any :class:`repro.core.baselines.FedAlgorithm`.  The
algorithm contributes the *math* of one round (the local-compute /
server-aggregate halves, or the fused ``make_round_fn``); the engine
contributes the *execution* as a stack of orthogonal **stages**
(:mod:`repro.exec.stages`), each of which wraps the round function and
contributes its slice of the ``lax.scan`` carry:

  * **Placement** (``EngineConfig(mesh=...)``) -- installs the mesh
    shardings of :mod:`repro.launch.sharding` on state, batches AND the
    other stages' carry slices (plan A/B), for any algorithm that declares
    ``state_roles`` (all seven in the repo do).  The compressor
    error-feedback residuals and the in-flight report buffer are
    client-axis pytrees, so the client placement rules place them too;
  * **UplinkComm** (``transport=``) -- splits each round into the
    algorithm's local/server halves and pushes the uplink message pytree
    through a :mod:`repro.comm` transport, threading the compressor's
    error-feedback state and PRNG key through the scan carry;
  * **DownlinkComm** (``downlink=``) -- a
    :class:`repro.comm.DownlinkCompressor` on the broadcast direction:
    clients compute against the compressed ``seen`` shadow state, whose
    error feedback is the standing ``x - seen`` residual;
  * **Asynchrony** (``clock=`` / ``buffer_size=`` / ``staleness=`` /
    ``queue_depth=``) -- simulated heterogeneous client speeds
    (:mod:`repro.sched`): a virtual-time clock schedules report arrivals,
    the server commits once ``buffer_size`` reports arrive
    (FedBuff-style), stale reports are staleness-weighted (optionally with
    an error-feedback residual that defers rather than drops the
    downweighted mass), and the in-flight report buffer -- one slot per
    client, or a ``queue_depth``-deep per-client queue that lets clients
    race ahead of delivery -- rides in the scan carry.

Stages are **orthogonal**: any subset composes (mesh-placed async rounds
with compressed uplinks and downlinks run in one compiled scan).  Setting a
stage's field activates it; ``backend=`` is kept as a deprecated alias that
maps onto the equivalent stage combination (``"sharded"`` -> Placement,
``"compressed"`` -> UplinkComm, ``"async"`` -> Asynchrony, ``"inline"`` ->
the empty stack, ``"protocol"`` -> the non-composable literal per-client
message-passing mode kept for equivalence testing).

On top of the stage stack the engine owns:

  * **flat carries** -- ``EngineConfig(plane=True)`` threads every
    message-shaped carry slice as one contiguous lane-padded
    ``(n_clients, d_pad)`` plane (:mod:`repro.core.plane`): the paper's
    one-d-vector-per-round object, bitwise-pinned against the per-leaf
    layout in tests/test_plane.py;
  * **chunking** -- ``chunk_rounds`` rounds are fused into one compiled call
    via ``lax.scan`` over pre-sampled batches; metrics come back as
    ``(chunk,)`` device arrays fetched with a single ``device_get``;
  * **batch supply** -- chunk-aware suppliers (:mod:`repro.exec.suppliers`)
    hand the engine a whole chunk of batches in one vectorized call; plain
    ``supplier(round_idx, rng)`` callables keep working;
  * **donation** -- the (potentially n_clients x d sized) carry is donated
    into the compiled call on accelerator backends; staged prefetch chunks
    (``ArraySupplier(prefetch=True)``) are additionally donated as batch
    inputs so double-buffering does not double peak batch memory;
  * **participation** -- optional client subsampling via an ``active``
    mask threaded into round functions that accept one.

Stages never change the math: every single-stage configuration is pinned
bitwise against its legacy ``backend=`` counterpart in
tests/test_stages.py, chunked == unchunked in tests/test_exec.py,
uplink compression at ratio 1.0 == the bare engine in tests/test_comm.py,
and asynchrony under a zero-delay clock + full buffer == the bare engine
bitwise in tests/test_sched.py.
"""
from __future__ import annotations

import inspect
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.baselines import FedAlgorithm
from repro.obs import trace as _trace
from repro.exec.stages import (Asynchrony, Cohort, DownlinkComm, Placement,
                               StageStack, UplinkComm, sink_blockers)
from repro.exec.suppliers import BatchSupplier, as_supplier
from repro.utils import tree as tu

Batch = Any

BACKENDS = ("inline", "sharded", "protocol", "compressed", "async")
PLANS = ("A", "A_dp", "B")


def server_state_fields(algorithm, state) -> dict:
    """The 'server'-role fields of an algorithm's state: the broadcast
    pytree a :class:`repro.comm.DownlinkCompressor` operates on, and the
    wire shape benchmarks account downlink bytes from."""
    roles = algorithm.state_roles()
    return {k: getattr(state, k) for k, r in roles.items() if r == "server"}


@dataclass(frozen=True)
class EngineConfig:
    """Execution options -- orthogonal to the algorithm being run.

    Stages activate independently by setting their fields; any subset
    composes (see the module docstring).

    chunk_rounds   : rounds fused per compiled call (lax.scan).  1 reproduces
                     the historical round-at-a-time loops exactly.
    jit            : disable to run the round function eagerly (debugging);
                     forces chunk_rounds=1 and composes with no stages.
    donate_state   : donate the carry into the compiled call.  Applies on
                     accelerator backends only (:func:`donates`); a sink
                     that keeps a carry array past the next chunk must
                     copy it (``SnapshotStore.engine_sink`` does).
    participation  : if set, the fraction of clients active each round
                     (uniform sampling without replacement, >= 1 client).
                     Requires a round function with an ``active`` argument;
                     does not compose with Asynchrony (buffered aggregation
                     subsumes it -- set buffer_size < n_clients).
    plane          : thread the communication-shaped stages' carries as
                     FLAT PARAMETER PLANES (:mod:`repro.core.plane`): the
                     uplink message flows between the local/server halves
                     as one contiguous lane-padded ``(n_clients, d_pad)``
                     buffer, the compressor error feedback is ONE flat
                     residual array, and the async report buffers/queues
                     hold ``(clients, d_pad)`` / ``(depth, clients,
                     d_pad)`` planes instead of nested pytrees.  Bitwise
                     identical to the per-leaf layout for every stage
                     combination (pinned in tests/test_plane.py); False
                     (the PR-4 per-leaf layout) remains the default until
                     the flat layout is validated on a real accelerator
                     (see ROADMAP).  A no-op without communication-shaped
                     stages; requires a single-dtype uplink message.

    Placement stage (active when ``mesh`` is set):
    mesh/param_specs/plan : the device mesh, the logical-axis spec tree of
                     the parameters, and the federated placement plan
                     ("A", "A_dp" or "B").

    UplinkComm stage (active when ``transport`` is set, or implicitly under
    any other communication-shaped stage, defaulting to Dense):
    transport      : the uplink compressor (:mod:`repro.comm`).
    comm_seed      : seed of the compressor's PRNG key stream (rand-k /
                     stochastic quantization draws).

    DownlinkComm stage (active when ``downlink`` is set):
    downlink       : a :class:`repro.comm.DownlinkCompressor` (or a plain
                     Transport, which gets wrapped) compressing the
                     broadcast server-state innovation with its own
                     error-feedback stream.

    Asynchrony stage (active when any of its fields is set):
    clock          : a :mod:`repro.sched` ClockModel (or its registry
                     name), the virtual-time per-client round durations.
                     Defaults to the zero-delay DeterministicClock.
    buffer_size    : reports the server waits for before committing an
                     update (FedBuff's K).  Defaults to n_clients.
    staleness      : a :class:`repro.sched.Staleness` policy (or a
                     weighting name: "uniform", "poly") controlling
                     stale-report downweighting and the optional
                     error-feedback correction.
    queue_depth    : if set, the depth of the per-client in-flight report
                     queue (clients race ahead of delivery, uploads
                     serialize FIFO); ``None`` keeps the historical
                     one-slot buffer; ``1`` is its queue-form equivalent.
    clock_seed     : seed of the clock model's PRNG key stream.
    edges          : if set, the client->edge->root aggregation tree of the
                     buffered commit: arrival selection and the commit
                     normalization reduce per-edge first, so the root only
                     touches ``edges * buffer_size`` candidates instead of
                     the full client axis.  Must divide the working client
                     width (the cohort width under cohort-resident state);
                     ``None``/1 is the flat selection, bitwise the
                     historical path.

    Cohort stage (active when ``population`` or ``cohort`` is set; see
    :mod:`repro.sched.cohort`):
    population     : total simulated clients.  The engine's ``n_clients``
                     argument IS the population under cohort-resident
                     state, so when both are given they must agree; the
                     per-client state lives in a host-resident, lazily
                     materialized population store, and only ``cohort``
                     rows are device-resident at a time.
    cohort         : the participating working-set width per scan chunk
                     (every per-client carry -- algorithm client fields,
                     error-feedback residuals, report buffers -- becomes
                     ``(cohort, ...)`` inside the compiled scan, gathered/
                     scattered against the store at chunk boundaries).
                     Defaults to the population; ``cohort == population``
                     reproduces the dense engine bitwise (pinned in
                     tests/test_cohort.py).
    cohort_seed    : seed of the per-chunk cohort id draws.

    protocol       : the literal per-client message-passing form of
                     Algorithm 1 (equivalence testing); composes with no
                     stages.

    backend        : DEPRECATED alias for the stage combinations above
                     ("inline", "sharded", "protocol", "compressed",
                     "async"); emits a DeprecationWarning and maps onto
                     the equivalent stage fields.
    """

    backend: Optional[str] = None
    chunk_rounds: int = 1
    jit: bool = True
    donate_state: bool = True
    participation: Optional[float] = None
    plane: bool = False
    mesh: Any = None
    param_specs: Any = None
    plan: str = "A"
    transport: Any = None
    comm_seed: int = 0
    downlink: Any = None
    clock: Any = None
    buffer_size: Optional[int] = None
    staleness: Any = None
    queue_depth: Optional[int] = None
    clock_seed: int = 0
    edges: Optional[int] = None
    population: Optional[int] = None
    cohort: Optional[int] = None
    cohort_seed: int = 0
    protocol: bool = False

    def resolve(self) -> StageStack:
        """Validate and map this config onto its :class:`StageStack`."""
        if self.backend is not None:
            if self.backend not in BACKENDS:
                raise ValueError(f"backend must be one of {BACKENDS}, got "
                                 f"{self.backend!r}")
            warnings.warn(
                "EngineConfig(backend=...) is deprecated: stages compose "
                "freely now -- activate them directly via mesh= (Placement), "
                "transport= (UplinkComm), downlink= (DownlinkComm) and "
                "clock=/buffer_size=/staleness=/queue_depth= (Asynchrony), "
                f"or protocol=True; backend={self.backend!r} maps onto the "
                "equivalent stage combination", DeprecationWarning,
                stacklevel=3)
        if self.chunk_rounds < 1:
            raise ValueError(f"chunk_rounds must be >= 1, got "
                             f"{self.chunk_rounds}")
        if self.plan not in PLANS:
            raise ValueError(f"plan must be one of {PLANS}, got "
                             f"{self.plan!r}")
        if self.participation is not None and not (0.0 < self.participation
                                                   <= 1.0):
            raise ValueError(f"participation must be in (0, 1], got "
                             f"{self.participation}")

        async_on = (self.backend == "async" or self.clock is not None
                    or self.buffer_size is not None
                    or self.staleness is not None
                    or self.queue_depth is not None
                    or self.edges is not None)
        cohort_on = self.population is not None or self.cohort is not None
        downlink_on = self.downlink is not None
        uplink_on = (self.transport is not None
                     or self.backend == "compressed"
                     or async_on or downlink_on)
        placement_on = self.mesh is not None or self.backend == "sharded"

        if self.plane and not self.jit:
            raise ValueError("plane mode threads flat carries through the "
                             "compiled scan and requires jit")
        if cohort_on:
            if not self.jit:
                raise ValueError(
                    "cohort-resident state gathers/scatters the compiled "
                    "scan's carry slices at chunk boundaries and requires "
                    "jit")
            if self.protocol or self.backend == "protocol":
                raise ValueError(
                    "cohort-resident state does not apply to the protocol "
                    "mode (literal per-client message passing has no "
                    "fixed-width working set)")
            if self.participation is not None:
                raise ValueError(
                    "cohort-resident state subsumes participation: the "
                    "sampled cohort IS the participating subset (set "
                    "cohort < population instead of a participation "
                    "fraction)")
            if self.mesh is not None or self.backend == "sharded":
                raise ValueError(
                    "cohort-resident state does not yet compose with the "
                    "placement stage (mapping the edge level onto the mesh "
                    "axis lands with the accelerator validation batch); "
                    "drop mesh= or run the dense engine")
            if self.population is not None and self.population < 1:
                raise ValueError(f"population must be >= 1, got "
                                 f"{self.population}")
            if self.cohort is not None and self.cohort < 1:
                raise ValueError(f"cohort must be >= 1, got {self.cohort}")
            if (self.population is not None and self.cohort is not None
                    and self.cohort > self.population):
                raise ValueError(
                    f"cohort={self.cohort} exceeds population="
                    f"{self.population}; the cohort is the participating "
                    "subset of the population")
        if self.edges is not None and self.edges < 1:
            raise ValueError(f"edges must be >= 1, got {self.edges}")
        if self.protocol or self.backend == "protocol":
            if self.participation is not None:
                raise ValueError("the protocol mode does not support "
                                 "partial participation")
            if self.plane:
                raise ValueError("plane mode does not apply to the protocol "
                                 "mode (literal per-client message passing)")
            if placement_on or uplink_on:
                raise ValueError(
                    "the protocol mode (literal per-client message passing) "
                    "composes with no stages; drop the "
                    "mesh/transport/downlink/clock options or run them on "
                    "the staged engine")
            return StageStack(protocol=True)

        if self.backend == "sharded" and self.mesh is None:
            raise ValueError("sharded backend requires a mesh")
        if placement_on:
            if self.param_specs is None:
                raise ValueError(
                    "the placement stage requires param_specs: the "
                    "logical-axis spec tree of the parameters, matching the "
                    "params pytree leaf for leaf (e.g. {'w': ('mlp',), "
                    "'b': ()}; model init returns it, see "
                    "repro.models.transformer.init_model)")
            if not self.jit:
                raise ValueError("the placement stage requires jit (the "
                                 "eager path performs no mesh placement)")
        if uplink_on and not self.jit:
            raise ValueError(
                "communication/asynchrony stages require jit (the "
                "compressor/scheduler state threads through the compiled "
                "scan carry)")
        if self.transport is not None and not hasattr(self.transport,
                                                      "compress"):
            raise ValueError(
                f"transport must implement the repro.comm.Transport "
                f"interface, got {type(self.transport).__name__}")
        if async_on and self.participation is not None:
            raise ValueError(
                "the asynchrony stage does not compose with participation: "
                "client subsampling is implicit in buffered aggregation "
                "(set buffer_size < n_clients instead)")
        if self.buffer_size is not None and self.buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got "
                             f"{self.buffer_size}")
        if self.queue_depth is not None and self.queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, got "
                             f"{self.queue_depth}")

        return StageStack(
            placement=(Placement(self.mesh, self.param_specs, self.plan)
                       if placement_on else None),
            uplink=(UplinkComm(self.transport, self.comm_seed)
                    if uplink_on else None),
            downlink=(DownlinkComm.coerce(self.downlink)
                      if downlink_on else None),
            asynchrony=(Asynchrony(self.clock, self.buffer_size,
                                   self.staleness, self.queue_depth,
                                   self.clock_seed, edges=self.edges)
                        if async_on else None),
            cohort=(Cohort(self.population, self.cohort, self.cohort_seed)
                    if cohort_on else None),
        )

    def validate(self, n_clients: Optional[int] = None) -> None:
        """Validate the config; with ``n_clients`` (the engine's client
        count -- the population under cohort-resident state) also check the
        width-dependent geometry: cohort vs population, buffer_size and
        edges vs the working client width.  These are exactly the checks
        the engine itself performs at construction, surfaced early."""
        self.resolve()
        if n_clients is None:
            return
        working = n_clients
        if self.population is not None or self.cohort is not None:
            from repro.sched.cohort import CohortSpec

            if self.population is not None and self.population != n_clients:
                raise ValueError(
                    f"EngineConfig(population={self.population}) disagrees "
                    f"with n_clients={n_clients}; the engine's client count "
                    "IS the population under cohort-resident state")
            working = self.cohort if self.cohort is not None else n_clients
            CohortSpec(n_clients, working, self.cohort_seed).validate()
        if (self.buffer_size is not None or self.edges is not None
                or self.clock is not None or self.staleness is not None
                or self.queue_depth is not None or self.backend == "async"):
            from repro.sched.aggregator import _validate_buffer

            _validate_buffer(
                self.buffer_size if self.buffer_size is not None
                else working,
                working,
                self.edges if self.edges is not None else 1)


def donates() -> bool:
    """Whether the engine donates its buffers into the compiled call: on
    accelerator backends, where the carry is what bounds device memory.
    The CPU path keeps every earlier carry alive."""
    return jax.default_backend() != "cpu"


def rounds_to_boundary(r: int, every: int, total: int) -> int:
    """Rounds from ``r`` to the next multiple of ``every``, capped at
    ``total`` -- the segment length drivers hand to :meth:`RoundEngine.run`
    between periodic eval/checkpoint points."""
    return min(total, (r // every + 1) * every) - r


def sample_active_masks(
    n_clients: int, n_rounds: int, participation: float,
    rng: np.random.Generator,
) -> np.ndarray:
    """(n_rounds, n_clients) bool masks: uniform subsampling w/o replacement."""
    m = max(1, int(round(participation * n_clients)))
    masks = np.zeros((n_rounds, n_clients), bool)
    for r in range(n_rounds):
        masks[r, rng.choice(n_clients, size=m, replace=False)] = True
    return masks


def _stack_batches(per_round: list) -> Batch:
    """Stack per-round batch pytrees along a new leading axis.

    Device-resident (jax) leaves stay on device -- no host round-trip; host
    (numpy/scalar) leaves stack on host and transfer once at the jit call.
    Chunk-aware suppliers bypass this entirely (they produce the stacked
    chunk directly, see :mod:`repro.exec.suppliers`).
    """

    def lead1(x):
        return x[None] if isinstance(x, jax.Array) else np.asarray(x)[None]

    if len(per_round) == 1:  # view, not copy -- the chunk-of-1 hot path
        return jax.tree_util.tree_map(lead1, per_round[0])

    def stack(*xs):
        if any(isinstance(x, jax.Array) for x in xs):
            return jnp.stack([jnp.asarray(x) for x in xs])
        return np.stack([np.asarray(x) for x in xs])

    return jax.tree_util.tree_map(stack, *per_round)


class RoundEngine:
    """Runs federated rounds for one (algorithm, grad_fn, n_clients) triple.

    The compiled artifacts are cached on the engine, so build it once per
    training run and reuse it across ``run``/``step`` calls.
    """

    def __init__(
        self,
        algorithm: FedAlgorithm,
        grad_fn,
        n_clients: int,
        config: EngineConfig = EngineConfig(),
    ):
        stack = config.resolve()
        self.algorithm = algorithm
        self.grad_fn = grad_fn
        self.n_clients = n_clients
        self.population = n_clients
        self.config = config
        self.stack = stack
        self.transport = None
        self.downlink = None
        self._cohort = None
        self._cohort_round = 0
        if stack.cohort is not None:
            from repro.sched.cohort import ResidentCohort

            if (stack.cohort.population is not None
                    and stack.cohort.population != n_clients):
                raise ValueError(
                    f"EngineConfig(population={stack.cohort.population}) "
                    f"disagrees with the engine's n_clients={n_clients}; "
                    "the engine's client count IS the population under "
                    "cohort-resident state (pass the same value, or drop "
                    "the population field)")
            self._cohort = ResidentCohort(stack.cohort.spec(n_clients))
            # every stage below sees the WORKING width: carries, buffers
            # and round halves are cohort-wide inside the compiled scan
            self.n_clients = self._cohort.spec.cohort
        # per-client wire bytes of one uplink message / one broadcast;
        # filled in lazily by the communication stages once the message
        # shape is known
        self.uplink_bytes_per_client_round: Optional[int] = None
        self.downlink_bytes_per_client_round: Optional[int] = None

        if stack.protocol:
            if not hasattr(algorithm, "make_protocol_round_fn"):
                raise ValueError(
                    f"algorithm {algorithm.name!r} has no protocol form "
                    "(make_protocol_round_fn); use the staged engine")
            self._round_fn = algorithm.make_protocol_round_fn(grad_fn)
            self._accepts_active = False
        elif stack.split:
            try:
                self._local_fn = algorithm.make_local_fn(grad_fn)
                self._server_fn = algorithm.make_server_fn()
            except NotImplementedError as e:
                raise ValueError(
                    f"algorithm {algorithm.name!r} has no local/server split "
                    "(make_local_fn/make_server_fn); run it without "
                    "communication/asynchrony stages") from e
            self._round_fn = None
            self._accepts_active = (
                "active" in inspect.signature(self._server_fn).parameters
            )
            self.transport = stack.uplink.resolve_transport()
            if stack.downlink is not None:
                self.downlink = stack.downlink.compressor
            if stack.asynchrony is not None:
                self._setup_async()
            # the effective round halves + transport the compiled scan uses:
            # identical to the algorithm's halves, or (plane mode) wrapped
            # so the uplink message flows as one flat (n_clients, d_pad)
            # buffer between them.  Plane wrapping needs the message shape,
            # so it is installed by _init_extras.
            self._local_eff = self._local_fn
            self._server_eff = self._server_fn
            self._transport_eff = self.transport
        else:
            self._round_fn = algorithm.make_round_fn(grad_fn)
            self._accepts_active = (
                "active" in inspect.signature(self._round_fn).parameters
            )
        if config.participation is not None and not self._accepts_active:
            raise ValueError(
                f"algorithm {algorithm.name!r} does not support partial "
                "participation (round_fn has no 'active' argument)")

        self._use_active = config.participation is not None
        self._plane = bool(config.plane) and stack.split
        self._plane_spec = None  # SegmentSpec of the uplink message plane
        self._chunked_call = None  # compiled lazily (needs a state template)
        self._state_shardings = None
        self._extras = None  # dict of stage carry slices, built lazily
        self._donate_batches = False  # staged prefetch chunks (see run())
        self._uplink_sink = None  # per-chunk uplink hand-off (runtime)
        self._uplink_tap = None  # device-resident msgs of the last chunk
        self._snapshot_sink = None  # per-chunk committed-state publication

    def _setup_async(self) -> None:
        """Resolve and validate clock/staleness/buffer/queue.  The async
        step itself is built lazily (_build_async_round): plane mode wraps
        the round halves around the message shape, which is only known once
        a batch template exists."""
        asyn = self.stack.asynchrony
        clock = asyn.resolve_clock()
        staleness = asyn.resolve_staleness()
        from repro.sched.aggregator import _validate_buffer

        buffer_size = (asyn.buffer_size if asyn.buffer_size is not None
                       else self.n_clients)
        self.edges = asyn.edges if asyn.edges is not None else 1
        # n_clients here is the WORKING width (the cohort width under
        # cohort-resident state): the buffer and the edge tree partition
        # the participating clients, not the population
        _validate_buffer(buffer_size, self.n_clients, self.edges)
        self.clock, self.staleness, self.buffer_size = (clock, staleness,
                                                        buffer_size)
        self.queue_depth = asyn.queue_depth
        self._async_round = None

    def _build_async_round(self) -> None:
        from repro.sched import make_async_round

        server_fields_fn = None
        if self.downlink is not None:
            server_fields_fn = (
                lambda st: server_state_fields(self.algorithm, st))
        self._async_round = make_async_round(
            self._local_eff, self._server_eff, self._transport_eff,
            self.clock, self.buffer_size, self.n_clients, self.staleness,
            accepts_active=self._accepts_active,
            queue_depth=self.queue_depth, downlink=self.downlink,
            server_fields_fn=server_fields_fn, edges=self.edges)

    # -- carry slices (read-only views of the stage state) ----------------

    @property
    def _comm_state(self):
        return None if self._extras is None else self._extras.get("comm")

    @property
    def _comm_key(self):
        return None if self._extras is None else self._extras.get("key")

    @property
    def _sched_state(self):
        return None if self._extras is None else self._extras.get("sched")

    @property
    def _dl_state(self):
        return None if self._extras is None else self._extras.get("dl")

    # -- state ------------------------------------------------------------

    def init(self, params0):
        """Algorithm state, placed on the stack's devices.  It shares no
        buffer with ``params0``: the algorithms keep ``params0`` itself as
        their first server state, and the compiled call donates the state,
        which would delete the caller's parameters."""
        state = tu.tree_copy_shared(
            self.algorithm.init(params0, self.n_clients), params0)
        if self.stack.placement is not None:
            state = jax.device_put(state, self.state_shardings(state))
        return state

    def set_state_shardings(self, shardings) -> None:
        """Install precomputed state shardings (placement stage)."""
        self._state_shardings = shardings

    def state_shardings(self, state):
        """Mesh shardings for the federated state (placement stage).

        Every algorithm declares the placement of its state fields via
        :meth:`FedAlgorithm.state_roles`; the rule tables of
        :mod:`repro.launch.sharding` turn that into NamedShardings.
        """
        if self._state_shardings is None:
            self._state_shardings = self.stack.placement.state_shardings(
                self.algorithm, state)
        return self._state_shardings

    # -- compiled chunk ---------------------------------------------------

    def _make_chunk_fn(self):
        """The function the engine compiles: scan ``body`` over the chunk.

        Stage carries ride in a dict alongside the algorithm state --
        ``comm`` (uplink error feedback) + ``key`` (comm PRNG stream),
        ``dl`` (downlink shadow), ``sched`` (report buffer/queue) -- so the
        carry structure is literally the stage composition.
        """
        with_active = self._use_active
        if self.stack.asynchrony is not None:
            async_round = self._async_round
            has_dl = self.downlink is not None

            def chunk_fn(carry, batches, active):
                def body(c, b):
                    st, ex = c
                    if has_dl:
                        st, sc, cs, key, dls, info = async_round(
                            st, ex["sched"], ex["comm"], ex["key"], b,
                            ex["dl"])
                        return (st, {"sched": sc, "comm": cs, "key": key,
                                     "dl": dls}), info
                    st, sc, cs, key, info = async_round(
                        st, ex["sched"], ex["comm"], ex["key"], b)
                    return (st, {"sched": sc, "comm": cs,
                                 "key": key}), info

                return jax.lax.scan(body, carry, batches)

            return chunk_fn

        if self.stack.split:
            local_fn, server_fn = self._local_eff, self._server_eff
            transport, downlink = self._transport_eff, self.downlink
            algorithm = self.algorithm
            tap = self._uplink_sink is not None
            # deterministic compressors ignore their key: skip the
            # per-round threefry split (measurable on µs-scale rounds)
            needs_key = getattr(transport, "stochastic", True) or (
                downlink is not None
                and getattr(downlink.transport, "stochastic", True))

            def body_keys(key):
                if not needs_key:
                    return key, key, key
                if downlink is not None:
                    return jax.random.split(key, 3)
                key, sub = jax.random.split(key)
                return key, sub, sub

            def chunk_fn(carry, batches, active):
                def body(c, xs):
                    st, ex = c
                    cs, key = ex["comm"], ex["key"]
                    if downlink is not None:
                        dls = ex["dl"]
                        key, sub, sub_dl = body_keys(key)
                        # clients compute against the compressed broadcast
                        # (what they actually hold); the server state stays
                        # authoritative
                        st_v = st._replace(**jax.tree_util.tree_map(
                            lambda l: l[0], dls["seen"]))
                    else:
                        key, sub, _ = body_keys(key)
                        st_v = st
                    b, a = xs if with_active else (xs, None)
                    msg, aux = local_fn(st_v, b)
                    msg_hat, cs_new = transport.compress(cs, msg, sub)
                    if with_active:
                        # inactive clients transmit nothing, so their
                        # error-feedback residuals must not advance -- else
                        # the telescoping identity (sent = produced - e_T)
                        # breaks per skipped round
                        cs = transport.select_clients(a, cs_new, cs)
                        st, info = server_fn(st_v, msg_hat, aux, active=a)
                    else:
                        cs = cs_new
                        st, info = server_fn(st_v, msg_hat, aux)
                    ex2 = {"comm": cs, "key": key}
                    if downlink is not None:
                        _, dls = downlink.broadcast(
                            dls, server_state_fields(algorithm, st), sub_dl)
                        ex2["dl"] = dls
                    # tapped: the scan also stacks the compressed uplink
                    # messages so run() can hand the chunk's wire payload
                    # to the sink without recomputing anything
                    return (st, ex2), ((info, msg_hat) if tap else info)

                xs = (batches, active) if with_active else batches
                return jax.lax.scan(body, carry, xs)

            return chunk_fn

        round_fn = self._round_fn

        def chunk_fn(state, batches, active):
            def body(st, xs):
                if with_active:
                    b, a = xs
                    st, info = round_fn(st, b, active=a)
                else:
                    st, info = round_fn(st, xs)
                return st, info

            xs = (batches, active) if with_active else batches
            return jax.lax.scan(body, state, xs)

        return chunk_fn

    def _build_chunked_call(self, state):
        cfg = self.config
        stack = self.stack
        chunk_fn = self._make_chunk_fn()
        accel = cfg.jit and donates()
        donate = cfg.donate_state and accel
        donate_argnums = (0,) if donate else ()
        if self._donate_batches and accel:
            # staged prefetch chunks are engine-owned, freshly created
            # buffers: donating them lets XLA reuse them in-call, so
            # double-buffered supply does not double peak batch memory
            donate_argnums = donate_argnums + (1,)

        if stack.placement is not None:
            pl = stack.placement
            state_sh = self.state_shardings(state)
            if stack.split:
                extras_sh = pl.carry_shardings(self._extras, self.n_clients)
                out_sh = ((state_sh, extras_sh), None)
            else:
                out_sh = (state_sh, None)
            jitted = jax.jit(chunk_fn, out_shardings=out_sh,
                             donate_argnums=donate_argnums)

            def call(carry, batches, active):
                batches = jax.device_put(batches,
                                         pl.batch_shardings(batches))
                return jitted(carry, batches, active)

            return call
        # only reached with jit enabled (resolve() rejects staged+eager,
        # and the eager path never builds a chunked call)
        return jax.jit(chunk_fn, donate_argnums=donate_argnums)

    def _init_extras(self, state, batches_stacked) -> dict:
        """Build the stage carry slices from the uplink message shape
        (eval_shape only, no FLOPs) -- compressor error-feedback state +
        key, downlink shadow, and the async report buffer/queue.

        In plane mode (``EngineConfig(plane=True)``) this is also where the
        stack pivots onto the flat layout: the message's
        :class:`repro.core.plane.SegmentSpec` is built once, the round
        halves are wrapped so the message crosses them as one contiguous
        ``(n_clients, d_pad)`` buffer, and every message-shaped carry slice
        (error feedback, report buffers/queues, staleness residuals)
        becomes a plane instead of a nested pytree.
        """
        ex: dict = {}
        one_round = jax.tree_util.tree_map(lambda x: x[0], batches_stacked)
        msg_spec, aux_spec = jax.eval_shape(self._local_fn, state, one_round)
        buf_spec = msg_spec  # what the carry slices are shaped like
        if self._plane:
            buf_spec = self._install_plane(msg_spec)
        ex["comm"] = self._transport_eff.init_state(buf_spec)
        ex["key"] = jax.random.PRNGKey(self.config.comm_seed)
        # wire bytes are a property of the MESSAGE, not the carry layout:
        # always accounted from the pytree spec (granularity-aware)
        self.uplink_bytes_per_client_round = (
            self.transport.uplink_bytes(msg_spec))
        if self.downlink is not None:
            fields = server_state_fields(self.algorithm, state)
            ex["dl"] = self.downlink.init_state(fields)
            self.downlink_bytes_per_client_round = (
                self.downlink.downlink_bytes(fields))
        if self.stack.asynchrony is not None:
            from repro.sched import init_async_state, init_queue_state

            if "round" not in aux_spec:
                raise ValueError(
                    f"algorithm {self.algorithm.name!r} emits no "
                    "report-round tag (aux['round']); the asynchrony stage "
                    "needs it to age buffered reports")
            start = int(state.round) if hasattr(state, "round") else 0
            if self.queue_depth is not None:
                ex["sched"] = init_queue_state(
                    buf_spec, aux_spec, self.n_clients, self.queue_depth,
                    self.config.clock_seed, start_round=start,
                    with_resid=self.staleness.correct)
            else:
                ex["sched"] = init_async_state(
                    buf_spec, aux_spec, self.n_clients,
                    self.config.clock_seed, start_round=start,
                    with_resid=(self.staleness.correct
                                and self.buffer_size < self.n_clients))
        if self.stack.asynchrony is not None and self._async_round is None:
            self._build_async_round()
        return ex

    def _install_plane(self, msg_spec):
        """Build the message plane spec and wrap the round halves +
        transport onto the flat layout.  Returns the flat carry template
        (a bare ``(n_clients, d_pad)`` ShapeDtypeStruct)."""
        from repro.comm import PlaneTransport
        from repro.core import plane as pln

        spec = pln.SegmentSpec.from_tree(msg_spec, batch_dims=1)
        self._plane_spec = spec
        local_fn, server_fn = self._local_fn, self._server_fn

        def local_eff(state, batches):
            msg, aux = local_fn(state, batches)
            return pln.flatten(spec, msg), aux

        if self._accepts_active:
            def server_eff(state, flat, aux, active=None):
                return server_fn(state, pln.unflatten(spec, flat), aux,
                                 active=active)
        else:
            def server_eff(state, flat, aux):
                return server_fn(state, pln.unflatten(spec, flat), aux)

        self._local_eff = local_eff
        self._server_eff = server_eff
        self._transport_eff = PlaneTransport(self.transport, spec)
        return jax.ShapeDtypeStruct((self.n_clients, spec.d_pad), spec.dtype)

    def set_uplink_sink(self, sink) -> None:
        """Register a per-chunk uplink hand-off: after each compiled chunk,
        ``sink(start_round, msgs, state)`` receives the chunk's compressed
        uplink messages (``msgs`` stacked ``(chunk, n_clients, ...)`` per
        leaf -- one ``(chunk, n_clients, d_pad)`` buffer in plane mode) and
        the committed post-chunk state, all still DEVICE-RESIDENT.

        This is the engine half of the overlap pipeline in
        :mod:`repro.fed.runtime`: the sink fires right after the chunk is
        *dispatched* and before the engine's own per-chunk host sync, so a
        background sender can fetch + serialize chunk k's bytes while the
        scan for chunk k+1 computes.  The sink must not mutate its
        arguments; whether it blocks is its own business (the runtime's
        blocking mode does, its overlapped mode hands off to a sender
        thread).

        The tap rides the jit'd split path only: stages that re-route the
        uplink off the scan's straight line (asynchrony's report buffers,
        cohort residency, partial participation, placement) and the eager /
        fused-``round_fn`` paths raise.  ``state`` is the carry the next
        chunk's call donates on an accelerator: a sink that reads it later
        copies what it needs first.  Pass ``None`` to remove the sink.
        """
        if sink is not None:
            if not self.stack.split:
                raise ValueError(
                    "uplink sink needs the split (local/server) engine "
                    "path; a fused or protocol round_fn never materializes "
                    "the uplink message")
            blockers = sink_blockers(self.stack,
                                     participation=self._use_active,
                                     jit=self.config.jit, kind="uplink")
            if blockers:
                raise ValueError(
                    "uplink sink is unsupported with stage(s): "
                    f"{', '.join(blockers)}; the per-chunk hand-off taps "
                    "the plain compiled scan")
        if (sink is None) != (self._uplink_sink is None):
            self._chunked_call = None  # tap output is baked into the jit
        self._uplink_sink = sink
        self._uplink_tap = None

    def _fire_uplink_sink(self, start_round: int, state) -> None:
        if self._uplink_sink is None:
            return
        tap, self._uplink_tap = self._uplink_tap, None
        if tap is not None:
            self._uplink_sink(start_round, tap, state)

    def set_snapshot_sink(self, sink) -> None:
        """Register a per-chunk serving-snapshot publication hook: after
        each committed chunk, ``sink(end_round, state)`` receives the round
        index just completed and the committed post-chunk state, still
        DEVICE-RESIDENT (fired before the engine's per-chunk host sync
        where the execution path allows, so publication overlaps the
        infos fetch).  ``repro.serving.SnapshotStore.engine_sink`` builds
        the standard sink: publish an atomically-swapped, versioned plane
        inference reads pick up between decode segments.

        Unlike the uplink sink -- which must tap message traffic inside
        the compiled scan -- this only reads state the engine holds at
        every chunk boundary, so it composes with every stage combination
        (async, cohort, participation, placement, eager) except the
        protocol form (see :func:`repro.exec.stages.sink_blockers`).  The
        sink must not mutate ``state``, and must copy any array of it that
        it keeps: on an accelerator the next chunk's call donates the
        carry, which deletes its buffers.  Pass ``None`` to remove.
        """
        if sink is not None:
            blockers = sink_blockers(self.stack,
                                     participation=self._use_active,
                                     jit=self.config.jit, kind="snapshot")
            if blockers:
                raise ValueError(
                    "snapshot sink is unsupported with stage(s): "
                    f"{', '.join(blockers)}; the protocol form bypasses "
                    "the engine's chunk structure")
        self._snapshot_sink = sink

    def _fire_snapshot_sink(self, end_round: int, state) -> None:
        if self._snapshot_sink is None:
            return
        with _trace.span("exec/snapshot_publish", "exec",
                         end_round=int(end_round)):
            self._snapshot_sink(end_round, state)

    def _set_donate_batches(self, donate: bool) -> None:
        """Flip batch donation, invalidating the compiled call when the
        flag is actually baked into it (accelerator + jit)."""
        if donate == self._donate_batches:
            return
        if self.config.jit and donates():
            self._chunked_call = None
        self._donate_batches = donate

    def _invoke_stacked(self, state, batches, active):
        """Run one chunk of already-stacked batches through the compiled
        call; returns (state, device-resident infos)."""
        if self.stack.split and self._extras is None:
            self._extras = self._init_extras(state, batches)
            if self.stack.placement is not None:
                self._extras = jax.device_put(
                    self._extras,
                    self.stack.placement.carry_shardings(self._extras,
                                                         self.n_clients))
        if self._chunked_call is None:
            # NB the jit wrapper builds here but XLA compiles lazily: the
            # first exec/dispatch span carries trace + compile time
            with _trace.span("exec/build", "exec"):
                self._chunked_call = self._build_chunked_call(state)
        if self.stack.split:
            with _trace.span("exec/dispatch", "exec"):
                (state, ex), ys = self._chunked_call((state, self._extras),
                                                     batches, active)
            self._extras = ex
            if self._uplink_sink is not None:
                infos, self._uplink_tap = ys
            else:
                infos = ys
            return state, infos
        with _trace.span("exec/dispatch", "exec"):
            return self._chunked_call(state, batches, active)

    def _invoke_chunk(self, state, per_round_batches, active):
        """Run ``len(per_round_batches)`` rounds in one compiled call."""
        if self.stack.protocol or not self.config.jit:
            stacked: dict[str, list] = {}
            for i, b in enumerate(per_round_batches):
                if self._use_active:
                    state, info = self._round_fn(
                        state, b, active=jnp.asarray(active[i]))
                else:
                    state, info = self._round_fn(state, b)
                for k, v in info.items():
                    stacked.setdefault(k, []).append(v)
            return state, {k: np.asarray(v) for k, v in stacked.items()}
        batches = _stack_batches(per_round_batches)
        act = jnp.asarray(active) if self._use_active else None
        state, infos = self._invoke_stacked(state, batches, act)
        with _trace.span("exec/host_sync", "exec"):
            return state, jax.device_get(infos)  # the chunk's ONE host sync

    # -- cohort residency (stack.cohort; see repro.sched.cohort) ----------

    @property
    def population_store(self):
        """The host-resident population store (``None`` without the cohort
        stage).  Current as of the last chunk boundary / :meth:`run`
        return; call :meth:`flush_cohort` first after ``step`` loops."""
        return None if self._cohort is None else self._cohort.store

    @property
    def cohort_ids(self):
        """Global client ids of the resident working set (``None`` without
        the cohort stage).  Before the first chunk this is the cohort the
        NEXT :meth:`step` will materialize (sampling is deterministic in
        the round index), so a ``step`` caller can gather its cohort-width
        batches before ever stepping."""
        if self._cohort is None:
            return None
        if self._cohort.current_ids is None:
            return self._cohort.spec.sample(self._cohort_round)
        return self._cohort.current_ids

    def _cohort_entries(self, state) -> dict:
        """``name -> (tree, client_axes)`` of every per-client carry slice
        the resident cohort swaps: the algorithm's client-role state
        fields, the uplink error-feedback state, and the per-client fields
        of the async report buffer/queue.  (The downlink shadow is
        single-sender server state; PRNG keys and scalar ledgers are
        global -- none of them carry a client axis.)"""
        try:
            roles = self.algorithm.state_roles()
        except NotImplementedError as e:
            raise ValueError(
                f"algorithm {self.algorithm.name!r} declares no state "
                "roles; cohort-resident state needs state_roles() to know "
                "which fields carry the client axis") from e
        entries: dict = {}
        client = {f: getattr(state, f)
                  for f, r in roles.items() if r == "client"}
        if client:
            entries["alg"] = (client, {f: 0 for f in client})
        if self._extras is not None:
            comm = self._extras.get("comm")
            if comm is not None and jax.tree_util.tree_leaves(comm):
                entries["comm"] = (comm, 0)
            sched = self._extras.get("sched")
            if sched is not None:
                from repro.sched.cohort import sched_client_axes

                axes = sched_client_axes(sched)
                fields = {f: getattr(sched, f)
                          for f, a in axes.items() if a is not None}
                entries["sched"] = (fields,
                                    {f: axes[f] for f in fields})
        return entries

    def _cohort_swap(self, state, chunk_start: int):
        """Advance the resident cohort to the chunk starting at global
        round ``chunk_start``: scatter the current working set home under
        its global ids, gather the newly sampled cohort's rows.  The first
        call registers the store entries from the initial working set
        (federated per-client init is client-uniform, so the init rows ARE
        the store's default rows and nothing needs gathering)."""
        rc = self._cohort
        ids = rc.sample(chunk_start)
        entries = self._cohort_entries(state)
        if rc.current_ids is None:
            for name, (tree, axes) in entries.items():
                rc.register(name, tree, axes)
            rc.current_ids = ids
            return state
        with _trace.span("exec/cohort_scatter", "exec"):
            for name, (tree, _axes) in entries.items():
                rc.scatter(name, rc.current_ids, tree)
        rc.current_ids = ids
        with _trace.span("exec/cohort_gather", "exec"):
            gathered = {name: rc.gather(name, ids) for name in entries}
        if "alg" in gathered:
            state = state._replace(**gathered["alg"])
        if "comm" in gathered:
            self._extras["comm"] = gathered["comm"]
        if "sched" in gathered:
            self._extras["sched"] = self._extras["sched"]._replace(
                **gathered["sched"])
        return state

    def flush_cohort(self, state) -> None:
        """Scatter the resident working set home to the population store.
        :meth:`run` does this before returning; call it manually after a
        ``step``-driven loop before reading or checkpointing the store."""
        rc = self._cohort
        if rc is None or rc.current_ids is None:
            return
        with _trace.span("exec/cohort_flush", "exec"):
            for name, (tree, _axes) in self._cohort_entries(state).items():
                rc.scatter(name, rc.current_ids, tree)

    def _run_cohort_chunk(self, state, supplier, r0: int, c: int, rng,
                          use_stacked: bool):
        """One chunk under cohort residency: sample the cohort's global
        ids, draw THEIR batches, swap the working set at the boundary, run
        the compiled chunk."""
        from repro.exec.suppliers import supports_client_ids

        rc = self._cohort
        ids = rc.sample(r0)
        kw = {}
        if not rc.spec.is_full:
            # the full cohort keeps the suppliers' historical call shape
            # (bitwise the dense engine); a strict sub-cohort needs the
            # supplier to draw batches for specific global ids
            if not supports_client_ids(supplier):
                raise ValueError(
                    f"supplier {type(supplier).__name__} does not accept "
                    "client_ids: a strict sub-cohort (cohort < population) "
                    "needs per-id batch draws -- accept a client_ids "
                    "keyword (an int64 array of global ids) in "
                    "sample_round/sample_chunk, or use "
                    "repro.exec.ArraySupplier")
            kw["client_ids"] = ids
        if use_stacked:
            batches = supplier.sample_chunk(r0, c, rng, **kw)
        else:
            batches = _stack_batches([
                supplier.sample_round(r0 + i, rng, **kw) for i in range(c)])
        if self.stack.split and self._extras is None:
            # the stage carries must exist before the first swap registers
            # them (their init rows are the store's default rows)
            self._extras = self._init_extras(state, batches)
        state = self._cohort_swap(state, r0)
        state, infos = self._invoke_stacked(state, batches, None)
        with _trace.span("exec/host_sync", "exec"):
            return state, jax.device_get(infos)  # the chunk's ONE host sync

    # -- public API -------------------------------------------------------

    def run(
        self,
        state,
        batch_supplier,
        rounds: int,
        *,
        rng: Optional[np.random.Generator] = None,
        seed: int = 0,
        start_round: int = 0,
        metrics_cb: Optional[Callable[[int, dict], None]] = None,
    ):
        """Run ``rounds`` rounds from ``state``; returns (state, metrics).

        ``batch_supplier`` is either a plain callable ``(round_idx, rng) ->
        batch`` or a :class:`repro.exec.suppliers.BatchSupplier`; batches are
        pytrees with leading dims ``(n_clients, tau, ...)``.  Chunk-aware
        suppliers feed whole chunks through ``sample_chunk`` (vectorized, no
        host re-stack); the engine falls back to per-round sampling under
        partial participation, where mask draws must interleave with batch
        draws.  Suppliers that stage engine-owned chunks
        (``donate_chunks``, e.g. ``ArraySupplier(prefetch=True)``) get
        their chunks donated into the compiled call on accelerator
        backends.  ``metrics`` maps metric name -> list with one float per
        executed round.  ``metrics_cb(round_idx, round_metrics)``, if given,
        fires per round (from per-chunk host fetches).
        """
        if rng is None:
            rng = np.random.default_rng(seed)
        supplier = as_supplier(batch_supplier)
        # batch donation is baked into the jit, so a supplier switch that
        # flips it (e.g. a prefetch supplier followed by one serving cache
        # VIEWS) must recompile -- donating a view would invalidate the
        # supplier's cache.  A supplier only declares donate_chunks when
        # every chunk it serves is a fresh, engine-owned buffer.
        self._set_donate_batches(
            bool(getattr(supplier, "donate_chunks", False))
            and not self._use_active)
        # the vectorized chunk path cannot interleave rng-consuming batch and
        # mask draws per round, so participation keeps the per-round path
        use_stacked = (
            type(supplier).sample_chunk is not BatchSupplier.sample_chunk
            and not self._use_active and self.config.jit
            and not self.stack.protocol)
        metrics: dict[str, list] = {}
        chunk = self.config.chunk_rounds if self.config.jit else 1
        done = 0
        while done < rounds:
            c = min(chunk, rounds - done)
            chunk_span = _trace.span("exec/chunk", "exec",
                                     start_round=start_round + done, rounds=c)
            with chunk_span:
                if self._cohort is not None:
                    state, infos = self._run_cohort_chunk(
                        state, supplier, start_round + done, c, rng,
                        use_stacked)
                    self._fire_snapshot_sink(start_round + done + c, state)
                elif use_stacked:
                    batches = supplier.sample_chunk(start_round + done, c,
                                                    rng)
                    state, infos = self._invoke_stacked(state, batches, None)
                    # hand the chunk's uplink to the sink BEFORE the host
                    # sync: an overlapping sender starts fetching chunk k's
                    # bytes while this thread blocks on (and dispatches) k+1
                    self._fire_uplink_sink(start_round + done, state)
                    # snapshot publication is device-resident too: readers
                    # pick up the swapped plane while this thread syncs
                    self._fire_snapshot_sink(start_round + done + c, state)
                    with _trace.span("exec/host_sync", "exec"):
                        infos = jax.device_get(infos)  # ONE host sync
                else:
                    # interleave batch and mask draws per round (not per
                    # chunk) so an rng-consuming supplier sees a
                    # chunk-size-invariant rng stream: the trajectory must
                    # not depend on chunk_rounds
                    per_round, masks = [], []
                    for i in range(c):
                        per_round.append(supplier.sample_round(
                            start_round + done + i, rng))
                        if self._use_active:
                            masks.append(sample_active_masks(
                                self.n_clients, 1,
                                self.config.participation, rng)[0])
                    active = np.stack(masks) if self._use_active else None
                    state, infos = self._invoke_chunk(state, per_round,
                                                      active)
                    self._fire_uplink_sink(start_round + done, state)
                    self._fire_snapshot_sink(start_round + done + c, state)
            per_round_infos = [{} for _ in range(c)]
            for k, v in infos.items():
                arr = np.asarray(v)
                for i in range(c):
                    x = arr[i]
                    per_round_infos[i][k] = float(x) if np.ndim(x) == 0 else x
                    metrics.setdefault(k, []).append(per_round_infos[i][k])
            if metrics_cb is not None:
                for i in range(c):
                    metrics_cb(start_round + done + i, per_round_infos[i])
            done += c
        if self._cohort is not None:
            self._cohort_round = start_round + rounds
            self.flush_cohort(state)
        return state, metrics

    def step(self, state, batches, active=None):
        """One round (the historical ``round_fn(state, batches)`` surface).

        Runs through the same compiled chunk path with chunk length 1, so a
        ``step`` trajectory is the chunked trajectory.
        """
        if active is not None and not self._accepts_active:
            raise ValueError("this algorithm's round_fn takes no active mask")
        if (active is not None and not self._use_active
                and self.config.jit and not self.stack.protocol):
            raise ValueError(
                "engine compiled without participation support; set "
                "EngineConfig.participation to thread active masks")
        if self.stack.protocol or not self.config.jit:
            if active is not None:
                return self._round_fn(state, batches, active=active)
            return self._round_fn(state, batches)
        if self._use_active and active is None:
            raise ValueError("engine configured with participation; pass the "
                             "active mask explicitly to step()")
        # step() batches are caller-owned (and chunk-of-1 stacking creates
        # VIEWS of them): never donate, even after a donating run()
        self._set_donate_batches(False)
        per_chunk = _stack_batches([batches])
        act = None
        if self._use_active:
            act = jnp.asarray(np.asarray(active)[None])
        if self._cohort is not None:
            # step() runs against the CURRENT resident cohort (batches are
            # caller-supplied, so the engine cannot resample ids for them;
            # use run() for per-chunk cohort resampling).  The first call
            # samples + registers the working set.
            if self.stack.split and self._extras is None:
                self._extras = self._init_extras(state, per_chunk)
            if self._cohort.current_ids is None:
                state = self._cohort_swap(state, self._cohort_round)
        state, infos = self._invoke_stacked(state, per_chunk, act)
        return state, {k: v[0] for k, v in infos.items()}

    def global_params(self, state):
        return self.algorithm.global_params(state)
