"""Multi-pod dry-run: prove the distribution config is coherent, and derive
loop-corrected roofline costs.

For every (architecture x input shape x mesh) combination this driver

  1. builds the step function (federated train round / prefill / one-token
     decode) and its in/out shardings from the logical-axis rules,
  2. ``jax.jit(step, in_shardings=..., out_shardings=..., donate_argnums=...)
     .lower(**ShapeDtypeStructs)``,
  3. ``.compile()`` -- any sharding mismatch, non-divisible dim or unsupported
     collective fails HERE, which is the point,
  4. records memory_analysis / the collective schedule of the REAL compile,
  5. derives loop-corrected FLOPs/bytes/collective-bytes via PROBE compiles.

Why probes: XLA's ``cost_analysis()`` counts a ``while``-loop body ONCE,
not times its trip count, so a scanned-layers model under-reports compute.
We therefore compile small UNROLLED variants (n_periods P in {1,2}, local
steps tau in {1,2}) whose costs are exact, fit the exactly-linear model

    cost(P, tau) = A0 + A1*P + tau*(B + C*P)        (train)
    cost(P)      = A  + C*P                          (prefill/decode)

and evaluate it at the real (P, tau).  The real compile still validates
sharding/memory; the probes are themselves dry-run compiles on the same mesh.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
"""
# The forced device count MUST precede any other import that touches jax:
import os  # noqa: E402

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.configs.base import SHAPES, shape_supported  # noqa: E402
from repro.core.algorithm import DProxConfig, DProxState, make_round_fn  # noqa: E402
from repro.core.prox import L1  # noqa: E402
from repro.launch import sharding as shd  # noqa: E402
from repro.launch import specs as sp  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.roofline import analysis as roof  # noqa: E402

DEFAULT_TAU = 4


def clients_for(plan: str, multi_pod: bool) -> int:
    if plan == "A":
        return 32 if multi_pod else 16
    return 2 if multi_pod else 1


def abstract_model(cfg):
    cap = {}

    def f(key):
        p, s = T.init_model(key, cfg)
        cap["s"] = s
        return p

    ps = jax.eval_shape(f, jax.random.PRNGKey(0))
    return ps, cap["s"]


def abstract_cache(cfg, batch, max_len):
    cap = {}

    def f():
        c, s = T.init_cache(cfg, batch, max_len)
        cap["s"] = s
        return c

    cs = jax.eval_shape(f)
    return cs, cap["s"]


def probe_cfg(cfg, n_periods: int):
    """Same arch, reduced to ``n_periods`` scanned periods, scans unrolled."""
    n_layers = (len(cfg.prefix_blocks) + len(cfg.suffix_blocks)
                + len(cfg.block_pattern) * n_periods)
    return cfg.with_overrides(n_layers=n_layers, scan_unroll=True)


def _microbatched_grad_fn(cfg, n_micro: int):
    """Gradient accumulation over n_micro chunks of the local batch -- the
    production memory-control knob for the large plan-B archs."""
    base = T.make_grad_fn(cfg)
    if n_micro <= 1:
        return base

    def fn(params, batch):
        def split(x):
            b = x.shape[0]
            assert b % n_micro == 0, (b, n_micro)
            return x.reshape((n_micro, b // n_micro) + x.shape[1:])

        mb = jax.tree_util.tree_map(split, batch)

        def body(carry, mbatch):
            loss_sum, gsum = carry
            loss, g = base(params, mbatch)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
            return (loss_sum + loss, gsum), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, gsum), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), mb)
        grads = jax.tree_util.tree_map(
            lambda g: (g / n_micro).astype(jnp.float32), gsum)
        return loss / n_micro, grads

    return fn


# ---------------------------------------------------------------------------
# step builders: (fn, arg_structs, in_shardings, out_shardings, donate)
# ---------------------------------------------------------------------------


def build_train(cfg, shape, mesh, multi_pod, tau=DEFAULT_TAU, micro=None,
                unroll_round=False, inner_dp=False, embed_fix=False):
    """embed_fix: shard the embedding table as (vocab replicated, embed over
    'model') instead of (vocab over 'model', embed over 'data').  The default
    vocab-sharded table forces GSPMD into 'involuntary full rematerialization'
    (replicate-then-repartition) on every token-embedding gather; replicating
    the vocab axis makes the gather local.  See the deepseek hillclimb."""
    n_clients = clients_for(cfg.fed_plan, multi_pod)
    b_local = shape.global_batch // n_clients
    if micro is None:
        micro = 8 if cfg.fed_plan == "B" else 1
        while b_local % micro:
            micro //= 2
    params_s, specs = abstract_model(cfg)
    fcfg = DProxConfig(tau=tau, eta=1e-3, eta_g=max(1.5, (n_clients / 8) ** 0.5))
    reg = L1(lam=1e-5)
    grad_fn = _microbatched_grad_fn(cfg, micro)
    round_fn = make_round_fn(fcfg, reg, grad_fn, unroll=unroll_round)

    state_s = DProxState(
        x_bar=params_s,
        c=jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((n_clients,) + x.shape, x.dtype),
            params_s),
        round=jax.ShapeDtypeStruct((), jnp.int32),
    )
    batches_s = sp.train_batches(cfg, shape, n_clients, tau, abstract=True)

    state_sh = shd.fed_state_shardings(mesh, params_s, specs, cfg.fed_plan,
                                       n_clients)
    if embed_fix:
        from jax.sharding import NamedSharding, PartitionSpec

        emb = NamedSharding(mesh, PartitionSpec(None, "model"))
        cemb = NamedSharding(mesh, PartitionSpec(None, None, "model"))
        xb = dict(state_sh.x_bar)
        xb["embed"] = emb
        cc = dict(state_sh.c)
        cc["embed"] = cemb
        state_sh = DProxState(x_bar=xb, c=cc, round=state_sh.round)
    batch_plan = "A_dp" if (inner_dp and cfg.fed_plan == "A") else cfg.fed_plan
    batch_sh = shd.batch_shardings(mesh, batches_s, batch_plan)
    out_sh = (state_sh, None)
    return round_fn, (state_s, batches_s), (state_sh, batch_sh), out_sh, (0,)


def build_prefill(cfg, shape, mesh, multi_pod, last_only=False,
                  replicate_embed=False):
    """last_only: emit only the final-position logits (what a real serving
    engine samples from) instead of the full (B, S, V) tensor.
    replicate_embed: hold the embedding table replicated.  The default
    (vocab x 'model', d x 'data') sharding makes the token gather output
    unshardable along batch, so GSPMD replicates ALL downstream activations
    across the mesh (16x collective + compute waste) -- the gemma2 prefill
    hillclimb measured this; see EXPERIMENTS.md section Perf."""
    params_s, specs = abstract_model(cfg)
    batch_s = sp.prefill_batch(cfg, shape, abstract=True)
    param_sh = shd.tree_shardings(params_s, specs, shd.serving_param_rules(),
                                  mesh)
    if replicate_embed:
        param_sh = dict(param_sh)
        param_sh["embed"] = NamedSharding(mesh, PartitionSpec())
    rrules = shd.request_rules()

    def one(x):
        axes = ("batch",) + ("seq",) * (x.ndim - 1)
        return NamedSharding(mesh, shd.spec_for(x.shape, axes, rrules, mesh))

    batch_sh = jax.tree_util.tree_map(one, batch_s)

    def fn(params, batch):
        return T.prefill(params, cfg, batch, max_len=shape.seq_len,
                         last_only=last_only)

    return fn, (params_s, batch_s), (param_sh, batch_sh), None, ()


def build_decode(cfg, shape, mesh, multi_pod):
    lcfg = cfg.long_context_variant() if shape.name == "long_500k" else cfg
    params_s, specs = abstract_model(lcfg)
    caches_s, cache_specs = abstract_cache(lcfg, shape.global_batch,
                                           shape.seq_len)
    param_sh = shd.tree_shardings(params_s, specs, shd.serving_param_rules(),
                                  mesh)
    cache_sh = shd.tree_shardings(caches_s, cache_specs, shd.cache_rules(),
                                  mesh)
    tok_s = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_sh = NamedSharding(
        mesh, shd.spec_for(tok_s.shape, ("batch", "none"), shd.request_rules(),
                           mesh))
    len_s = jax.ShapeDtypeStruct((), jnp.int32)
    len_sh = NamedSharding(mesh, PartitionSpec())

    def fn(params, caches, token, cache_len):
        return T.decode_step(params, lcfg, caches, token, cache_len)

    return (fn, (params_s, caches_s, tok_s, len_s),
            (param_sh, cache_sh, tok_sh, len_sh), (None, cache_sh), (1,))


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


def _compile(builder, cfg, shape, mesh, multi_pod, **kw):
    fn, args, in_sh, out_sh, donate = builder(cfg, shape, mesh, multi_pod, **kw)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    return lowered.compile()


def _costs(compiled):
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    coll = sum(c[3] for c in roof.parse_collectives(txt))
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), coll)


def probe_costs(arch_cfg, shape, mesh, multi_pod, tau, builders):
    """Loop-corrected (flops, bytes, coll_bytes) per device at the REAL
    (n_periods, tau) via the linear probe model described in the module doc."""
    kind = shape.kind
    P_real = arch_cfg.n_periods
    builder = builders[kind]
    # Probes use P in {2,3}: the P=1 compile can take structurally different
    # XLA sharding decisions (observed: a one-off embed all-gather) that break
    # the linear fit; P>=2 compiles are mutually consistent.
    if kind == "train":
        f = {}
        for (P, t) in [(2, 1), (3, 1), (2, 2), (3, 2)]:
            c = _compile(builder, probe_cfg(arch_cfg, P), shape, mesh,
                         multi_pod, tau=t, micro=1, unroll_round=True)
            f[(P, t)] = _costs(c)

        def fit(i):
            f21, f31, f22, f32 = (f[(2, 1)][i], f[(3, 1)][i], f[(2, 2)][i],
                                  f[(3, 2)][i])
            C = (f32 - f22) - (f31 - f21)       # per-period-per-step
            A1 = (f31 - f21) - C                # per-period fixed
            B = (f22 - f21) - 2 * C             # per-step fixed
            A0 = f21 - 2 * A1 - (B + 2 * C)     # round fixed
            return A0 + A1 * P_real + tau * (B + C * P_real)

        return tuple(max(fit(i), 0.0) for i in range(3))
    else:
        f2 = _costs(_compile(builder, probe_cfg(arch_cfg, 2), shape, mesh,
                             multi_pod))
        f3 = _costs(_compile(builder, probe_cfg(arch_cfg, 3), shape, mesh,
                             multi_pod))

        def fit(i):
            C = f3[i] - f2[i]
            A = f2[i] - 2 * C
            return A + C * P_real

        return tuple(max(fit(i), 0.0) for i in range(3))


def run_one(arch: str, shape_name: str, mesh_name: str, tau: int = DEFAULT_TAU,
            outdir: str = "experiments/dryrun", builders=None, note: str = "",
            cfg_override=None, probes: bool = True):
    """Lower + compile one combination; returns (status, report_or_reason)."""
    shape = SHAPES[shape_name]
    cfg = cfg_override if cfg_override is not None else registry.get(arch)
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return "skip", why
    builders = builders or BUILDERS
    multi_pod = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)

    kw = {"tau": tau} if shape.kind == "train" else {}
    with obs_trace.timed("dryrun/compile", "dryrun", arch=arch,
                         shape=shape_name, mesh=mesh_name) as tm_compile:
        compiled = _compile(builders[shape.kind], cfg, shape, mesh,
                            multi_pod, **kw)
    t_compile = tm_compile.seconds

    # loop-corrected costs from unrolled probes
    with obs_trace.timed("dryrun/probes", "dryrun", arch=arch,
                         shape=shape_name) as tm_probe:
        if probes:
            flops, byts, coll = probe_costs(cfg, shape, mesh, multi_pod, tau,
                                            builders)
        else:
            flops, byts, coll = _costs(compiled)
    t_probe = tm_probe.seconds

    lcfg = cfg.long_context_variant() if shape.name == "long_500k" else cfg
    params_s, _ = abstract_model(lcfg)
    mf = roof.model_flops_for(cfg, shape, params_s,
                              tau=tau if shape.kind == "train" else 1)
    rep = roof.analyze(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_chips=mesh.devices.size, step_kind=shape.kind, model_flops=mf,
        note=note)
    # overwrite the loop-distorted costs with the probe-corrected ones
    rep = dataclasses.replace(
        rep,
        flops_per_dev=flops, bytes_per_dev=byts, coll_bytes_per_dev=coll,
        compute_s=flops / roof.PEAK_FLOPS, memory_s=byts / roof.HBM_BW,
        collective_s=coll / roof.LINK_BW,
        useful_ratio=(mf / (flops * mesh.devices.size)) if flops else 0.0,
    )
    rep = dataclasses.replace(
        rep,
        dominant=max([("compute", rep.compute_s), ("memory", rep.memory_s),
                      ("collective", rep.collective_s)], key=lambda kv: kv[1])[0])

    rep_d = json.loads(rep.to_json())
    rep_d["timing"] = {"compile": t_compile, "probes": t_probe}
    rep_d["memory_analysis_raw"] = str(compiled.memory_analysis())
    path = pathlib.Path(outdir)
    path.mkdir(parents=True, exist_ok=True)
    suffix = f"_{note}" if note else ""
    (path / f"{arch}_{shape_name}_{mesh_name}{suffix}.json").write_text(
        json.dumps(rep_d, indent=1))
    return "ok", rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--tau", type=int, default=DEFAULT_TAU)
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip cost probes (compile-validation only)")
    args = ap.parse_args()

    archs = registry.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{arch} x {shape} x {mesh_name}"
                try:
                    status, out = run_one(arch, shape, mesh_name, tau=args.tau,
                                          outdir=args.outdir,
                                          probes=not args.no_probes)
                except Exception:
                    n_fail += 1
                    print(f"FAIL {tag}\n{traceback.format_exc()}", flush=True)
                    continue
                if status == "skip":
                    n_skip += 1
                    print(f"SKIP {tag}: {out}", flush=True)
                else:
                    n_ok += 1
                    print(f"OK   {out.summary()}", flush=True)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
