"""Compiles a cell's chunk for a described TPU v5e, without the chip, and
prints the compiler's memory analysis: does the chunk fit one chip?

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse_fit.py --workload lm_dprox

A rehearsal script, not a test: it describes the v5e topology, which loads
the TPU's library in this process.
"""
from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                                "src"))
sys.path.insert(0, BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="lm_dprox")
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.cell import build_engine
    from chipbench.registry import Registry

    jax.config.update("jax_enable_compilation_cache", False)
    reg = Registry()
    work = reg.workload(args.workload)
    config, traffic = reg.config(work["config"]), reg.traffic(work["traffic"])
    if work["chips"] != 1:
        raise SystemExit("rehearse_fit compiles one-chip cells")
    tr, eng = config["training"], traffic["engine"]
    n, chunk = int(traffic["clients"]), int(eng["chunk_rounds"])
    ref_mod = reg.reference(config["family"])
    grad_fn = reg.program(config["family"]).grad_fn(config)
    params = jax.eval_shape(lambda k: ref_mod.init_params(k, config),
                            jax.random.PRNGKey(0))
    engine = build_engine(config, traffic, grad_fn)
    state = jax.eval_shape(lambda p: engine.algorithm.init(p, n), params)
    if traffic["data"]["kind"] != "token_streams":
        raise SystemExit("rehearse_fit knows the token feed's batch shape")
    batch = {"tokens": jax.ShapeDtypeStruct(
        (chunk, n, tr["tau"], tr["batch"], traffic["data"]["seq_len"]),
        jnp.int32)}

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])
    place = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    chunk_fn = engine._make_chunk_fn()
    with jax.default_matmul_precision(config["precision"]["matmul_precision"]):
        compiled = jax.jit(chunk_fn, donate_argnums=(0,)).lower(
            place(state), place(batch), None).compile()
    mem = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {f: int(getattr(mem, f)) for f in fields}
    live = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
            - out["alias_size_in_bytes"] + out["temp_size_in_bytes"])
    out["live_bytes"] = live
    out["params"] = int(sum(np.prod(x.shape)
                            for x in jax.tree_util.tree_leaves(params)))
    out["device"] = topo.devices[0].device_kind
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
