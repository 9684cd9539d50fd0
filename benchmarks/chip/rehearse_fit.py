"""Compiles a cell's chunk for a described TPU v5e, without the chip, and
prints the compiler's memory analysis: does the chunk fit one chip?

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse_fit.py --workload lm_dprox
    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse_fit.py --workload lm_dprox \
        --reference --layers 10 --clients 1 --batch 2

``--reference`` also compiles the plain reference's one program, one
client's local step (``reference/dprox.py: device_grad``), and prints its
live bytes beside the chunk's.  ``--layers``, ``--clients`` and
``--batch`` resize the cell for the rehearsal alone.  ``--run`` compiles
nothing for a described chip: on the machine's own first device it runs
the reference's three steps at that size, from weights and token rounds
made from ``--seed``, and prints their seconds and the device's peak
memory (a rehearsal of a size before it becomes a cell).

A rehearsal script, not a test: it describes the v5e topology, which loads
the TPU's library in this process.
"""
from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                                "src"))
sys.path.insert(0, BENCH_DIR)

FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
          "alias_size_in_bytes", "temp_size_in_bytes",
          "generated_code_size_in_bytes")


def sized(reg, args) -> tuple:
    """The cell's configuration and traffic, resized as the flags ask."""
    work = reg.workload(args.workload)
    config, traffic = reg.config(work["config"]), reg.traffic(work["traffic"])
    if work["chips"] != 1:
        raise SystemExit("rehearse_fit compiles one-chip cells")
    if traffic["data"]["kind"] != "token_streams":
        raise SystemExit("rehearse_fit knows the token feed's batch shape")
    if args.layers is not None:
        config["num_hidden_layers"] = args.layers
    if args.batch is not None:
        config["training"] = dict(config["training"], batch=args.batch)
    if args.clients is not None:
        traffic["clients"] = args.clients
    return config, traffic


def memory(compiled) -> dict:
    mem = compiled.memory_analysis()
    out = {f: int(getattr(mem, f)) for f in FIELDS}
    out["live_bytes"] = (out["argument_size_in_bytes"]
                         + out["output_size_in_bytes"]
                         - out["alias_size_in_bytes"]
                         + out["temp_size_in_bytes"])
    return out


def run_reference(reg, config, traffic, seed: int) -> dict:
    """The reference's three steps on this machine's first device."""
    from functools import partial

    import jax
    import numpy as np

    from chipbench import traffic as traffic_mod
    from chipbench.cell import weights

    jax.config.update("jax_enable_compilation_cache", False)
    ref_mod, dprox = reg.reference(config["family"]), reg.reference("dprox")
    dev = jax.devices()[0]
    tr, chunk = config["training"], int(traffic["engine"]["chunk_rounds"])
    with jax.default_matmul_precision(
            config["precision"]["matmul_precision"]):
        data = traffic_mod.make(traffic, config, seed)
        rounds = [traffic_mod.array_round(data.arrays, data.tau, data.batch,
                                          seed, i) for i in range(3 * chunk)]
        init_fn = jax.jit(lambda k: ref_mod.init_params(k, config))
        params0 = jax.tree_util.tree_map(np.ascontiguousarray,
                                         weights(init_fn, seed))
        t0 = time.perf_counter()
        out = dprox.run(partial(ref_mod.loss, config=config), params0, tr,
                        int(traffic["clients"]), rounds, chunk,
                        uplink=traffic["engine"].get("uplink"))
        seconds = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    return {"seconds": seconds, "device": dev.device_kind,
            "device_peak_bytes": stats.get("peak_bytes_in_use"),
            "device_peak_reserved_bytes": stats.get("peak_bytes_reserved"),
            "bytes_limit": stats.get("bytes_limit"),
            "host_peak_rss_bytes": resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024,
            "losses": out["losses"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="lm_dprox")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--reference", action="store_true",
                    help="also compile the reference's local step")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--clients", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--run", action="store_true",
                    help="run the reference's three steps on this machine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.cell import build_engine
    from chipbench.registry import Registry

    reg = Registry()
    config, traffic = sized(reg, args)
    ref_mod = reg.reference(config["family"])
    params = jax.eval_shape(lambda k: ref_mod.init_params(k, config),
                            jax.random.PRNGKey(0))
    size = {"params": int(sum(np.prod(x.shape)
                              for x in jax.tree_util.tree_leaves(params))),
            "model_bytes": int(sum(np.prod(x.shape) * x.dtype.itemsize
                                   for x in jax.tree_util.tree_leaves(
                                       params)))}
    if args.run:
        print(json.dumps(dict(size, **run_reference(reg, config, traffic,
                                                    args.seed))))
        return 0

    from functools import partial

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    tr, eng = config["training"], traffic["engine"]
    n, chunk = int(traffic["clients"]), int(eng["chunk_rounds"])
    grad_fn = reg.program(config["family"]).grad_fn(config)
    engine = build_engine(config, traffic, grad_fn)
    state = jax.eval_shape(lambda p: engine.algorithm.init(p, n), params)
    seq = traffic["data"]["seq_len"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (chunk, n, tr["tau"], tr["batch"], seq), jnp.int32)}

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    chunk_fn = engine._make_chunk_fn()
    with jax.default_matmul_precision(config["precision"]["matmul_precision"]):
        compiled = jax.jit(chunk_fn, donate_argnums=(0,)).lower(
            place(state), place(batch), None).compile()
        out = memory(compiled)
        if args.reference:
            step = {"tokens": jax.ShapeDtypeStruct((tr["batch"], seq),
                                                   jnp.int32)}
            vg = reg.reference("dprox").device_grad(
                partial(ref_mod.loss, config=config))
            out["reference_step"] = memory(
                vg.lower(place(params), place(step)).compile())
    out.update(size)
    out["device"] = topo.devices[0].device_kind
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
