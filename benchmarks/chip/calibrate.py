"""Readings that the limits of a cell's comparison are set from (not a
benchmark run: it measures nothing).

    python benchmarks/chip/calibrate.py --workload cnn_dense \
        --seeds 11 12 13 --out out/calibrate_cnn_dense.jsonl

For each seed: set-up and the first three steps of the program, as a run
makes them, against the plain reference (the lower readings); and in the
program's place the reference in bfloat16 (the control) and the
reference with half of every batch left out (a planted fault); and the
reference from weights moved by one unit in the last place (how far
rounding, and the run's amplification of it, move the numbers).  One
JSON line per seed, then the largest program reading and the smallest
control and fault readings of each number.  ``--cpu`` lets the run take
JAX's CPU (a witness: float32 at full precision, no bf16 passes).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                                "src"))
sys.path.insert(0, BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from chipbench import check
    from chipbench.cell import run_cell
    from chipbench.check import NUMBERS
    from chipbench.registry import Registry

    reg = Registry()
    variants = {"control_bf16": {"dtype": jnp.bfloat16},
                "fault_half_batch": {"half_batch": True},
                "reference_nudged": {"nudge": True}}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows = []
    with open(args.out, "w") as f:
        for seed in args.seeds:
            t0 = time.perf_counter()
            res = run_cell(reg, args.workload, seed, 0.0, False, t0,
                           variants=variants, require_tpu=not args.cpu)
            rd = res["readings"]
            row = {"seed": seed,
                   "program": check.numbers(rd["program"], rd["reference"]),
                   "variants": res["variants"], "failed": res["failed"],
                   "checks": res["checks"],
                   "readings": res["readings"],
                   "seconds": time.perf_counter() - t0}
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps({k: v for k, v in row.items()
                              if k != "readings"}), flush=True)
    summary = {"workload": args.workload, "seeds": args.seeds,
               "lower": {n: max(r["program"][n] for r in rows)
                         for n in NUMBERS}}
    for v in variants:
        summary[v] = {n: min(r["variants"][v][n] for r in rows)
                      for n in NUMBERS}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
