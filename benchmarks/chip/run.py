"""Chip benchmark of the federated round engine: one run of one cell.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``).
The run makes the clients' data and the weights from ``--seed``, builds
the engine, drives it through three steps (which compile, or load from
JAX's persistent cache, every program the window uses), measures for
``--seconds`` seconds, compares those three steps with the plain reference
(``reference/``), and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones, each read by
``metrics/<name>.py``), ``device`` and, last, ``checks``: each number
compared with its limit, which are also the last lines of standard error.

Exits non-zero, with no result line, when JAX's first device is not a TPU
or it sees fewer chips than the cell asks for.
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
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench.cell import GateError, run_cell
    from chipbench.registry import Registry

    try:
        result = run_cell(Registry(), args.workload, args.seed % 2 ** 64,
                          args.seconds, bool(args.trace), T_START)
    except GateError as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
