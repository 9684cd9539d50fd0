"""Records one traced run of a cell and keeps the first part of its window
as the reduced JSON trace the tests read (not a benchmark run).

    python benchmarks/chip/record_trace.py --workload cnn_topk --seed 5 \
        --seconds 3 --keep 0.05 --out out/trace_cnn_topk.json

Prints the run's result line, and the readers' numbers on the kept part.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=float, required=True,
                    help="seconds of the window to keep")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from chipbench import trace as tr
    from chipbench.cell import run_cell
    from chipbench.registry import Registry

    kept = {}

    def sink(trace):
        kept["full"] = trace
        kept["trim"] = tr.trim(trace, args.keep)

    res = run_cell(Registry(), args.workload, args.seed, args.seconds, True,
                   T_START, trace_sink=sink)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    tr.dump(kept["trim"], args.out)
    full = kept["full"]
    names = {}
    for chip, events in full.device_ops().items():
        for name, _, dur in events:
            names[name] = names.get(name, 0.0) + dur
    print(json.dumps({"planes": [[p["name"], [l["name"] for l in p["lines"]]]
                                 for p in full.planes],
                      "top_ops": sorted(names.items(),
                                        key=lambda kv: -kv[1])[:40]}))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
