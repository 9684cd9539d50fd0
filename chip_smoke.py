"""Smoke run of the federated round engine on a TPU.

    python chip_smoke.py              # one chip: CNN phase + LM train->serve
    python chip_smoke.py --chips 4    # four chips: client-axis placement only

Runs in one process and fails (non-zero exit, no result line) on the first
error, and at once when JAX's first device is not a TPU: it never falls
back to the CPU.  Phases on one chip:

  * the paper's Section 4.2 CNN (d = 112,394) at full width: 10 label-skewed
    clients, tau = 5, batch 10, float32, 16 rounds in chunks of 8 through
    ``repro.fed.simulator.run``.  Checks that the loss is finite and falls;
    that each round of the dense engine agrees with the literal per-client
    protocol of Algorithm 1 (``EngineConfig(protocol=True)``) from the same
    state within ``TOL_PROTOCOL``; and that a flat-plane run with a global
    top-k uplink trains, with the compiled threshold-select kernel it runs
    equal, bit for bit, to the ``jnp.where`` select on the same plane.  The
    quantize and commit kernels run on that plane (whose last block is
    ragged) against ``repro.kernels.ref``.
  * the LM entry points: ``repro.launch.train.main`` on the stablelm smoke
    preset with ``--publish-snapshots``, then ``ServingEngine.serve``
    answering requests from the latest snapshot.

With ``--chips 4`` it runs only the CNN with 8 clients placed over a
4-device mesh (``EngineConfig(mesh=...)``), each round against the same
round unplaced on one device, and checks from ``.sharding`` that the
client-axis carries span all 4 devices.

Agreement is measured on the displacement of the server state over what is
compared (one round, or the whole run): ``|a - b| / |b - x_0|`` in the L2
norm, so the error is relative to what training moved, not to the
weights' size.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.utils.compile_cache import CACHE_DIR, use_compile_cache  # noqa: E402

SEED, N_CLIENTS, TAU, BATCH = 0, 10, 5, 10
ROUNDS, CHUNK = 16, 8
ETA, ETA_G, LAM = 0.005, 1.0, 1e-4  # benchmarks/fig4_cnn.py (paper Fig. 4)
#: one round of the engine vs the per-client protocol from the same state,
#: both at float32 "highest" matmul precision (the CPU measures ~3e-5)
TOL_PROTOCOL = 1e-3
#: the same with the engine at the default matmul precision, whose f32
#: matmuls and convolutions take one bf16 pass on the TPU: a bound that
#: catches wrong arithmetic (errors of order 1), not bf16 rounding, which
#: measures 7.2e-2 on a v5e
TOL_DEFAULT_PRECISION = 0.25
#: one round placed over 4 devices vs on one device, from the same state
#: ("highest" precision)
TOL_PLACEMENT = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds XLA spent compiling, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def device_gate(count: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(f"JAX's first device is on platform "
                           f"{d0.platform!r}, not 'tpu'; this smoke run "
                           "needs a TPU and never runs on the CPU instead")
    if len(devs) < count:
        raise SmokeFailure(f"--chips {count} needs {count} devices, JAX "
                           f"sees {len(devs)}")
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs)}
    print(f"device: {dev}", flush=True)
    return dev


# ---------------------------------------------------------------------------
# the paper's CNN
# ---------------------------------------------------------------------------


def cnn_problem(n_clients: int):
    """(params0, grad_fn, supplier, d) for the Section 4.2 CNN over
    ``n_clients`` label-skewed clients."""
    from repro.data.mnist_like import (generate, heterogeneous_split,
                                       sample_round_batches)
    from repro.models import cnn

    tx, ty, sx, sy = generate(n_train=200 * n_clients, n_test=200, seed=SEED)
    data = heterogeneous_split(tx, ty, sx, sy, n_clients=n_clients,
                               seed=SEED)
    p0 = cnn.init_params(jax.random.PRNGKey(SEED))
    d = sum(int(np.size(l)) for l in jax.tree_util.tree_leaves(p0))

    def supplier(r, rng):
        return sample_round_batches(data, TAU, BATCH, rng)

    return p0, jax.jit(cnn.make_grad_fn()), supplier, d


def rel_displacement_error(a, b, x0) -> float:
    """``|a - b| / |b - x0|`` over the whole parameter tree (L2)."""
    def flat(t):
        return np.concatenate([np.asarray(l, np.float64).ravel()
                               for l in jax.tree_util.tree_leaves(t)])

    a, b, x0 = flat(a), flat(b), flat(x0)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b - x0))


def cnn_phase(clock: CompileClock) -> None:
    from repro.comm import TopK
    from repro.core import plane as pln
    from repro.core.algorithm import DProxConfig
    from repro.core.prox import L1
    from repro.exec import EngineConfig, RoundEngine
    from repro.fed.simulator import DProxAlgorithm, run
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    p0, grad_fn, supplier, d = cnn_problem(N_CLIENTS)
    check(d == 112_394, f"CNN has d = {d}, the paper's is 112,394")
    alg = DProxAlgorithm(L1(lam=LAM), DProxConfig(tau=TAU, eta=ETA,
                                                  eta_g=ETA_G))

    def train(config: EngineConfig, label: str):
        t0, c0 = time.perf_counter(), clock.seconds
        engine = RoundEngine(alg, grad_fn, N_CLIENTS, config)
        hist = run(alg, p0, grad_fn, supplier, N_CLIENTS, ROUNDS,
                   engine=engine, seed=SEED, eval_every=ROUNDS)
        loss = np.asarray(hist.loss, np.float64)
        print(f"cnn/{label}: {time.perf_counter() - t0:.1f}s wall, "
              f"{clock.seconds - c0:.1f}s compiling; loss "
              f"{' '.join(f'{x:.4f}' for x in loss)}", flush=True)
        return hist.extra["final_params"], loss

    # (a) the dense engine at the default matmul precision: loss falls
    _, loss = train(EngineConfig(chunk_rounds=CHUNK), "dense")
    check(loss.shape == (ROUNDS,) and bool(np.all(np.isfinite(loss))),
          f"CNN loss is not {ROUNDS} finite values: {loss}")
    check(loss[-4:].mean() < loss[0],
          f"CNN loss did not fall: first {loss[0]}, last four "
          f"{loss[-4:]}")

    # (b) round by round from the same state: the engine against the
    # literal per-client protocol (rounding is not left to compound over
    # rounds, which training amplifies)
    worst = {"highest": 0.0, "default": 0.0}
    proto = RoundEngine(alg, grad_fn, N_CLIENTS, EngineConfig(protocol=True))
    high = RoundEngine(alg, grad_fn, N_CLIENTS, EngineConfig())
    dflt = RoundEngine(alg, grad_fn, N_CLIENTS, EngineConfig())
    state = high.init(p0)
    rng = np.random.default_rng(SEED)
    t0, c0 = time.perf_counter(), clock.seconds
    for r in range(ROUNDS):
        batches = supplier(r, rng)
        x = jax.device_get(state.x_bar)
        with jax.default_matmul_precision("highest"):
            want, _ = proto.step(state, batches)
        got_dflt, _ = dflt.step(jax.tree_util.tree_map(jnp.copy, state),
                                batches)
        with jax.default_matmul_precision("highest"):
            state, _ = high.step(state, batches)  # donates the old state
        for name, got in (("highest", state), ("default", got_dflt)):
            worst[name] = max(worst[name], rel_displacement_error(
                got.x_bar, want.x_bar, x))
    print(f"cnn: engine vs protocol per round over {ROUNDS} rounds: worst "
          f"rel err {worst['highest']:.3e} at 'highest' precision "
          f"(tolerance {TOL_PROTOCOL:g}), {worst['default']:.3e} at the "
          f"default precision (tolerance {TOL_DEFAULT_PRECISION:g}); "
          f"{time.perf_counter() - t0:.1f}s wall, "
          f"{clock.seconds - c0:.1f}s compiling", flush=True)
    check(worst["highest"] <= TOL_PROTOCOL,
          f"engine vs protocol rel err {worst['highest']:.3e}")
    check(worst["default"] <= TOL_DEFAULT_PRECISION,
          f"default-precision engine vs protocol rel err "
          f"{worst['default']:.3e}")

    # (c) flat plane + global top-k uplink: the select runs as a kernel
    topk = TopK(ratio=0.1, granularity="global")
    _, loss_k = train(EngineConfig(chunk_rounds=CHUNK, plane=True,
                                   transport=topk), "plane_topk")
    check(bool(np.all(np.isfinite(loss_k))) and loss_k[-4:].mean()
          < loss_k[0], f"plane/top-k CNN loss did not fall: {loss_k}")
    spec = pln.SegmentSpec.from_tree(
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((N_CLIENTS,) + x.shape, x.dtype),
            p0), batch_dims=1)
    hlo = jax.jit(lambda f: topk.apply_flat(f, None, spec)).lower(
        jax.ShapeDtypeStruct((N_CLIENTS, spec.d_pad), jnp.float32)
    ).compile().as_text()
    check("tpu_custom_call" in hlo,
          "global top-k did not lower to the threshold-select kernel")
    flat = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                             (N_CLIENTS, spec.d_pad), jnp.float32)
    flat = flat.at[:, spec.d:].set(0.0)
    k = max(1, round(topk.ratio * spec.d))
    kth = jax.jit(lambda f: jax.lax.top_k(jnp.abs(f), k)[0][:, -1])(flat)
    got = np.asarray(jax.jit(kops.plane_threshold_select)(flat, kth))
    want = np.asarray(jax.jit(
        lambda f, t: jnp.where(jnp.abs(f) >= t[:, None], f, 0))(flat, kth))
    print(f"cnn: threshold-select kernel vs jnp.where on a "
          f"({N_CLIENTS}, {spec.d_pad}) plane: {np.count_nonzero(got)} "
          f"kept (k = {k}), bitwise {got.tobytes() == want.tobytes()}",
          flush=True)
    check(got.tobytes() == want.tobytes(),
          "threshold-select kernel differs from jnp.where")
    # the k-th magnitude the kernel finds by bisection, against the sort's,
    # on the same plane and on one whose first row holds a NaN
    top_kth = jax.jit(lambda f: jax.lax.top_k(jnp.abs(f), k)[0][:, -1])
    for label, plane in (("CNN plane", flat),
                         ("NaN row", flat.at[0, 7].set(jnp.nan))):
        got_k, want_k = (np.asarray(jax.jit(f)(plane)).view(np.int32)
                         for f in (lambda f: kops.plane_kth_magnitude(f, k),
                                   top_kth))
        same = got_k.tobytes() == want_k.tobytes()
        print(f"cnn: k-th magnitude kernel vs lax.top_k on the {label}: "
              f"bitwise {same}", flush=True)
        check(same, f"k-th magnitude kernel differs from lax.top_k on the "
              f"{label}")

    # (d) the quantize and commit kernels on the same plane, whose tile rows
    # leave a ragged last block, against repro.kernels.ref
    levels = 255
    u = jax.random.uniform(jax.random.PRNGKey(SEED + 2), flat.shape)
    scale = jnp.max(jnp.abs(flat), axis=1)
    got_q, want_q = (np.asarray(jax.jit(f, static_argnums=3)(
        flat, u, scale, levels)) for f in (kops.plane_quantize,
                                            kref.plane_quantize))
    # an ulp of y = x / s * levels moves floor(y) where y sits on an
    # integer: such a coordinate differs by one level, any other by rounding
    err = np.abs(got_q - want_q)
    step = np.asarray(scale, np.float64)[:, None] / levels
    flips = int(np.count_nonzero(err > 1e-6))
    print(f"cnn: quantize kernel vs ref ({levels} levels): {flips} of "
          f"{err.size} coordinates a level apart, max error "
          f"{float(np.max(err / step)):.4f} levels", flush=True)
    check(bool(np.all(err <= step + 1e-6)) and flips <= err.size * 1e-4,
          f"quantize kernel differs from ref: {flips} coordinates beyond "
          f"1e-6, max {float(np.max(err / step)):.3f} levels")
    w = jax.random.uniform(jax.random.PRNGKey(SEED + 3), (N_CLIENTS,))
    got_c, want_c = (np.asarray(jax.jit(f)(flat, w)) for f in (
        kops.plane_weighted_commit, kref.plane_weighted_commit))
    terms = np.asarray(flat, np.float64) * np.asarray(w, np.float64)[:, None]
    # the float32 summation bound: n * eps * sum_i |w_i x_i| per coordinate
    bound = N_CLIENTS * 2.0 ** -23 * np.abs(terms).sum(axis=0)
    exact = terms.sum(axis=0)
    ratio = {name: float(np.max(np.abs(v - exact) / np.maximum(bound, 1e-30)))
             for name, v in (("kernel", got_c), ("ref", want_c))}
    print(f"cnn: commit kernel vs float64 sum: max error {ratio['kernel']:.3f}"
          f" of the float32 summation bound (ref: {ratio['ref']:.3f}); "
          f"kernel vs ref max |diff| {float(np.max(np.abs(got_c - want_c))):.3e}",
          flush=True)
    check(ratio["kernel"] <= 1.0, "commit kernel error exceeds the float32 "
          f"summation bound ({ratio['kernel']:.3f} of it)")


# ---------------------------------------------------------------------------
# LM: train -> publish -> serve
# ---------------------------------------------------------------------------


def lm_phase(clock: CompileClock) -> None:
    from repro.launch import train
    from repro.serving import Request, ServingEngine

    t0, c0 = time.perf_counter(), clock.seconds
    rounds, chunk = 4, 2
    # FedAvg's global model is its carry: the published snapshots must
    # survive the next chunk's donation of that carry
    state, snapshots = train.main([
        "--arch", "stablelm_1_6b", "--scale", "smoke", "--algorithm",
        "fedavg", "--rounds", str(rounds), "--chunk", str(chunk),
        "--clients", "2", "--tau", "2", "--batch", "2", "--seq", "64",
        "--log-every", "1", "--seed", str(SEED), "--publish-snapshots"])
    check(snapshots is not None and snapshots.version == rounds // chunk,
          f"expected {rounds // chunk} snapshots, store holds "
          f"{None if snapshots is None else snapshots.version}")
    for snap in (snapshots.previous(), snapshots.latest()):
        leaves = jax.tree_util.tree_leaves(snap.value)
        check(all(bool(jnp.all(jnp.isfinite(l))) for l in leaves),
              f"snapshot v{snap.version} is not finite")
    cfg = train.model_config("stablelm_1_6b", "smoke")
    server = ServingEngine(cfg, None, max_len=96, snapshots=snapshots)
    rng = np.random.default_rng(SEED)
    n_new = 8
    requests = [Request(i, rng.integers(0, cfg.vocab, size=16 + 8 * i,
                                        dtype=np.int32), n_new)
                for i in range(3)]
    results = server.serve(requests, slots=2, segment=4)
    check(len(results) == len(requests), f"{len(results)} results for "
          f"{len(requests)} requests")
    for r in results:
        check(r.tokens.shape == (n_new,) and r.logprobs.shape == (n_new,),
              f"request {r.id}: shapes {r.tokens.shape} {r.logprobs.shape}")
        check(bool(np.all((r.tokens >= 0) & (r.tokens < cfg.vocab))),
              f"request {r.id}: token out of vocabulary")
        check(bool(np.all(np.isfinite(r.logprobs) & (r.logprobs <= 0))),
              f"request {r.id}: bad logprobs {r.logprobs}")
        check(r.snapshot_version == snapshots.version,
              f"request {r.id} served v{r.snapshot_version}, latest is "
              f"v{snapshots.version}")
    print(f"lm: trained {rounds} rounds, {snapshots.version} snapshots, "
          f"served {len(results)} requests x {n_new} tokens from "
          f"v{snapshots.version}; {time.perf_counter() - t0:.1f}s wall, "
          f"{clock.seconds - c0:.1f}s compiling", flush=True)


# ---------------------------------------------------------------------------
# --chips 4: client-axis placement over a mesh
# ---------------------------------------------------------------------------


def placement_phase(clock: CompileClock, n_devices: int) -> None:
    from jax.sharding import AxisType

    from repro.comm import TopK
    from repro.core.algorithm import DProxConfig
    from repro.core.prox import L1
    from repro.exec import EngineConfig, RoundEngine
    from repro.fed.simulator import DProxAlgorithm

    n_clients = 8
    p0, grad_fn, supplier, _ = cnn_problem(n_clients)
    alg = DProxAlgorithm(L1(lam=LAM), DProxConfig(tau=TAU, eta=ETA,
                                                  eta_g=ETA_G))
    mesh = jax.make_mesh((n_devices, 1), ("data", "model"),
                         devices=jax.devices()[:n_devices],
                         axis_types=(AxisType.Auto,) * 2)
    specs = jax.tree_util.tree_map(lambda x: ("none",) * x.ndim, p0)
    # top-k at ratio 1.0 is exactly the identity, so the agreement below
    # does not hinge on a discontinuous selection, and it still keeps an
    # error-feedback residual per client: a client-axis carry slice that
    # launch.sharding.carry_slice_shardings places
    transport = TopK(ratio=1.0)
    ref = RoundEngine(alg, grad_fn, n_clients,
                      EngineConfig(transport=transport))
    placed = RoundEngine(alg, grad_fn, n_clients,
                         EngineConfig(mesh=mesh, param_specs=specs, plan="A",
                                      transport=transport))
    state = ref.init(p0)
    shardings = placed.state_shardings(state)
    rng = np.random.default_rng(SEED)
    worst, losses = 0.0, []
    t0, c0 = time.perf_counter(), clock.seconds
    # round by round from the same state, so rounding differences (the
    # client mean becomes a cross-device reduction) do not compound
    with jax.default_matmul_precision("highest"):
        for r in range(ROUNDS):
            batches = supplier(r, rng)
            host = jax.device_get(state)
            got, _ = placed.step(jax.device_put(host, shardings), batches)
            state, info = ref.step(state, batches)
            losses.append(float(info["train_loss"]))
            worst = max(worst, rel_displacement_error(got.x_bar, state.x_bar,
                                                      host.x_bar))
    print(f"placement: {ROUNDS} rounds, {time.perf_counter() - t0:.1f}s "
          f"wall, {clock.seconds - c0:.1f}s compiling; loss "
          f"{' '.join(f'{x:.4f}' for x in losses)}", flush=True)
    check(bool(np.all(np.isfinite(losses))) and np.mean(losses[-4:])
          < losses[0], f"placed CNN loss did not fall: {losses}")

    carries = {"c": got.c, "comm": placed._comm_state}
    for name, tree in carries.items():
        for leaf in jax.tree_util.tree_leaves(tree):
            devs = {s.device for s in leaf.addressable_shards}
            rows = {s.data.shape[0] for s in leaf.addressable_shards}
            check(len(devs) == n_devices and rows == {n_clients // n_devices},
                  f"carry {name} {leaf.shape} spans {len(devs)} devices "
                  f"with client rows {rows} per shard ({leaf.sharding})")
        print(f"placement: carry {name!r} client axis over {n_devices} "
              f"devices, {n_clients // n_devices} clients each "
              f"({jax.tree_util.tree_leaves(tree)[0].sharding.spec})",
              flush=True)
    print(f"placement: {n_devices}-device vs one-device per round: worst "
          f"rel err {worst:.3e} (tolerance {TOL_PLACEMENT:g})", flush=True)
    check(worst <= TOL_PLACEMENT, f"placed vs one-device rel err {worst:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-axis placement phase")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = device_gate(args.chips)
    clock = CompileClock()
    if args.chips == 4:
        check(dev["count"] == 4, f"--chips 4 needs exactly 4 devices, JAX "
              f"sees {dev['count']}")
        placement_phase(clock, 4)
    else:
        cnn_phase(clock)
        lm_phase(clock)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile: {clock.seconds:.1f}s in XLA; persistent cache {cache} "
          f"holds {n_cached} entries", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
