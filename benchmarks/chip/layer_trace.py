"""One traced run of a cell, read by layer (not a benchmark run): the
device's time by the round's layer scopes, the engine's host spans, and
the device's idle time by the host span open in it.

    python benchmarks/chip/layer_trace.py --workload cnn_topk --seed 5 \
        --seconds 10 [--keep 0.05 --out out/layers_cnn_topk.json] \
        [--xplane out/cnn_topk.xplane.pb.gz]

Prints the run's result line, then one JSON line of the layer readings
(``chipbench/layers.py``).  ``--out`` keeps the first ``--keep`` seconds
of the window as a reduced trace with the program's spans and the ops'
scopes; ``--xplane`` keeps the profiler's whole file, gzipped.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                                "src"))
sys.path.insert(0, BENCH_DIR)


def readings(lt, rounds: int) -> dict:
    """The layer readings of a ``LayerTrace`` whose window holds
    ``rounds`` rounds."""
    from chipbench import layers
    from chipbench import trace as tr

    out = {name: layers.layer_ms_per_round(lt, name, rounds)
           for name in layers.LAYERS.values()}
    out["dispatch"] = layers.dispatch_ms_per_round(lt, rounds)
    ops = lt.device_ops()
    if not ops or lt.window() is None:
        return out
    lo, hi = lt.window()
    chip = min(ops)
    busy = tr.busy_ns(ops[chip], lo, hi)
    acc = layers.layer_ns(lt).get(chip, {})
    out["unscoped"] = acc.get("unscoped", 0.0) * 1e-6 / rounds
    out["busy_ms_per_round"] = busy * 1e-6 / rounds
    out["layer_share_of_busy"] = (sum(v for k, v in acc.items()
                                      if k != "unscoped") / busy
                                  if busy else None)
    idle = layers.idle_by_span(lt, chip)
    total = sum(idle.values())
    out["idle_ms_per_round"] = total * 1e-6 / rounds
    out["idle_by_span"] = {k: [v * 1e-6 / rounds, v / total]
                           for k, v in sorted(idle.items(),
                                              key=lambda kv: -kv[1])}
    named = lt.scopes.get(chip, {})
    out["ops_with_op_name"] = [sum(1 for v in named.values() if v),
                               len(named)]
    out["unscoped_ops"] = layers.unscoped_ops(lt)
    out["conflict_ms"] = lt.conflict_ns * 1e-6
    out["breakdown"] = layers.breakdown(lt)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=float, default=0.05,
                    help="seconds of the window that --out keeps")
    ap.add_argument("--out", help="reduced trace of the kept seconds")
    ap.add_argument("--xplane", help="the profiler's file, gzipped")
    args = ap.parse_args(argv)

    from chipbench import layers
    from chipbench import trace as tr
    from chipbench.cell import run_cell
    from chipbench.registry import Registry

    kept = {}
    reduce = tr.from_xplane

    def capture(path):
        # the profiler's whole file, before the harness removes it
        os.makedirs(os.path.dirname(os.path.abspath(args.xplane)),
                    exist_ok=True)
        with open(path, "rb") as src, gzip.open(args.xplane, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return reduce(path)

    if args.xplane:
        tr.from_xplane = capture
    try:
        res = run_cell(Registry(), args.workload, args.seed % 2 ** 64,
                       args.seconds, True, T_START,
                       trace_sink=lambda t: kept.update(layers=t))
    finally:
        tr.from_xplane = reduce
    lt = kept["layers"]
    print(json.dumps(res), flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "rounds": res["attempted"],
                      "layers": readings(lt, res["attempted"])}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        layers.dump(layers.trim(lt, args.keep), args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
