"""Roofline analysis from compiled XLA artifacts (no real hardware needed).

Per (arch x shape x mesh) we derive three per-step time lower bounds:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device / HBM_bandwidth_per_chip
    collective term = collective_bytes_per_device / ICI_link_bandwidth

``compiled.cost_analysis()`` on the forced-host backend reports PER-DEVICE
post-partitioning flops and bytes (verified empirically -- see
tests/test_roofline.py), so no division by chip count is needed.

collective_bytes is NOT in cost_analysis: we parse the SPMD-partitioned module
(``compiled.as_text()``) and sum estimated per-device bytes moved for every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute,
using ring-algorithm estimates:

    all-gather        ~ result_bytes * (g-1)/g
    all-reduce        ~ 2 * shard_bytes * (g-1)/g
    reduce-scatter    ~ input_bytes * (g-1)/g  (= result_bytes * (g-1))
    all-to-all        ~ result_bytes * (g-1)/g
    collective-permute~ result_bytes

where g is the replica-group size parsed from the op's replica_groups.

Hardware model (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Optional

PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = 50e9  # bytes/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]
    return 2  # conservative default


def parse_collectives(hlo_text: str):
    """[(op, result_bytes, group_size, est_moved_bytes_per_device)]."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        rb = _shape_bytes(shape_str)
        g = _group_size(line)
        ring = (g - 1) / g if g > 1 else 0.0
        if op == "all-reduce":
            moved = 2 * rb * ring
        elif op == "all-gather":
            moved = rb * ring
        elif op == "reduce-scatter":
            moved = rb * (g - 1)
        elif op == "all-to-all":
            moved = rb * ring
        else:  # collective-permute
            moved = rb
        out.append((op, rb, g, moved))
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    step_kind: str
    n_chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float  # 6*N_active*D (or 2*N*D for inference) GLOBAL
    useful_ratio: float  # model_flops / (flops_per_dev * n_chips)
    memory_per_dev_gb: dict
    collective_breakdown: dict
    n_collectives: int
    note: str = ""

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    def summary(self) -> str:
        return (
            f"{self.arch:>20s} {self.shape:>12s} {self.mesh:>6s} | "
            f"comp {self.compute_s*1e3:9.3f}ms  mem {self.memory_s*1e3:9.3f}ms  "
            f"coll {self.collective_s*1e3:9.3f}ms -> {self.dominant:10s} | "
            f"useful {self.useful_ratio:6.1%} | "
            f"temp {self.memory_per_dev_gb.get('temp', 0):6.2f}GB/dev"
        )


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, n_chips: int,
            step_kind: str, model_flops: float, note: str = "") -> RooflineReport:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    colls = parse_collectives(txt)
    coll_bytes = sum(c[3] for c in colls)
    breakdown: dict[str, float] = {}
    for op, rb, g, moved in colls:
        breakdown[op] = breakdown.get(op, 0.0) + moved

    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll_bytes / LINK_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", collective_s)],
        key=lambda kv: kv[1],
    )[0]

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "args": ma.argument_size_in_bytes / 1e9,
            "out": ma.output_size_in_bytes / 1e9,
            "temp": ma.temp_size_in_bytes / 1e9,
            "alias": ma.alias_size_in_bytes / 1e9,
        }
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    total_hlo_flops = flops * n_chips
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, step_kind=step_kind,
        n_chips=n_chips, flops_per_dev=flops, bytes_per_dev=byts,
        coll_bytes_per_dev=coll_bytes, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant, model_flops=model_flops,
        useful_ratio=(model_flops / total_hlo_flops) if total_hlo_flops else 0.0,
        memory_per_dev_gb=mem,
        collective_breakdown=breakdown,
        n_collectives=len(colls),
        note=note,
    )


# ---------------------------------------------------------------------------
# analytic model FLOPs (the "useful work" yardstick)
# ---------------------------------------------------------------------------


def analytic_param_counts(cfg, params_struct) -> tuple[int, int]:
    """(total_params, active_params): active discounts inactive MoE experts."""
    import jax

    total = sum(int(l.size) for l in jax.tree_util.tree_leaves(params_struct))
    if cfg.moe is None:
        return total, total
    E, K, F = cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_ff_expert
    per_expert = 3 * cfg.d_model * F
    n_moe_layers = sum(
        1 for _ in range(cfg.n_periods)
    ) * len(cfg.block_pattern) if cfg.mlp_kind == "moe" else 0
    inactive = n_moe_layers * (E - K) * per_expert
    return total, total - inactive


def model_flops_for(cfg, shape, params_struct, tau: int = 1) -> float:
    """6*N_active*D for training (D = tokens incl. tau local steps);
    2*N_active*D for prefill; 2*N_active*B for one decode step."""
    total, active = analytic_param_counts(cfg, params_struct)
    # exclude the embedding table lookup (gather, ~0 matmul flops); the tied
    # unembed matmul IS counted via the table, which slightly overcounts for
    # tied models -- documented approximation.
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len * tau
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * shape.global_batch  # decode: one token per request


# ---------------------------------------------------------------------------
# wire term: the uplink bandwidth bound for the multi-process runtime
# ---------------------------------------------------------------------------
#
# The collective term above models ICI traffic *inside* one XLA program.
# The multi-process runtime (repro.fed.runtime) adds a fourth time term the
# compiler never sees: the worker->server uplink crossing a real socket.
# Per engine chunk the worker ships `rounds_per_chunk` server messages
# (plus its committed server fields), and either
#
#     blocking:    t_chunk ~ compute_s + wire_s        (send on compute thread)
#     overlapped:  t_chunk ~ max(compute_s, wire_s)    (send on sender thread)
#
# so overlap hides min(compute_s, wire_s) of the wire time.  The interesting
# design point is the comm/compute *crossover*: the compression ratio r* at
# which wire_s == compute_s.  Below r* the overlapped runtime is compute
# bound (the wire is free); above it the wire is the roofline no matter how
# the send is scheduled.  benchmarks/wire_bench.py checks this prediction
# against measured localhost runs (throttled to a known bandwidth).

WIRE_BW = 1e9        # bytes/s -- ~10GbE payload rate; override per deployment
WIRE_LATENCY = 50e-6  # seconds per frame (syscall + ACK round-trip floor)

# sparse wire encoding ships (index, value) pairs per surviving entry
# (repro.comm.wire pack_plane), so r of the entries cost r*(1 + idx/val)
# of the dense bytes -- clamped at 1.0 by the codec's dense fallback.
SPARSE_INDEX_OVERHEAD = 1.0  # idx_itemsize / val_itemsize (i64 idx, f64 vals)


@dataclasses.dataclass
class WireModel:
    """Analytic time model for one uplink frame over the runtime socket."""

    bw: float = WIRE_BW
    latency_s: float = WIRE_LATENCY

    def seconds(self, nbytes: float) -> float:
        return self.latency_s + float(nbytes) / self.bw


def uplink_nbytes(dense_nbytes: float, ratio: float, *,
                  encoding: str = "sparse",
                  index_overhead: float = SPARSE_INDEX_OVERHEAD) -> float:
    """Predicted payload bytes for one message at compression ``ratio``.

    ``dense_nbytes`` is the raw message size (n_clients * d * itemsize for
    a plane chunk row).  ``sparse`` models top-k/rand-k (index+value pairs,
    dense fallback clamp); ``palette`` models the quantizer (codes shrink
    with ratio = bits/bitwidth, plus the per-row table which we fold into
    the clamp); ``dense`` ignores ratio.
    """
    if encoding == "dense":
        return float(dense_nbytes)
    if encoding == "sparse":
        return float(dense_nbytes) * min(1.0, ratio * (1.0 + index_overhead))
    if encoding == "palette":
        return float(dense_nbytes) * min(1.0, ratio)
    raise ValueError(f"unknown wire encoding {encoding!r}")


def chunk_times(compute_s: float, wire_s: float) -> dict:
    """Per-chunk wall-time predictions for the three runtime modes, plus
    the fraction of the blocking-mode send overhead that overlap hides."""
    blocking = compute_s + wire_s
    overlapped = max(compute_s, wire_s)
    hidden = ((blocking - overlapped) / wire_s) if wire_s > 0 else 1.0
    return {"single": compute_s, "blocking": blocking,
            "overlapped": overlapped, "hidden_fraction": hidden}


def crossover_ratio(compute_s: float, dense_nbytes: float,
                    model: Optional[WireModel] = None, *,
                    encoding: str = "sparse",
                    index_overhead: float = SPARSE_INDEX_OVERHEAD) -> float:
    """The compression ratio r* where uplink wire time equals compute time.

    For r < r* the overlapped runtime is compute bound; for r > r* it is
    wire bound.  Returns +inf when even the dense message transfers faster
    than the chunk computes (the wire never becomes the roofline).
    """
    model = model or WireModel()
    budget_bytes = (compute_s - model.latency_s) * model.bw
    if budget_bytes <= 0:
        return 0.0
    if budget_bytes >= uplink_nbytes(dense_nbytes, 1.0, encoding=encoding,
                                     index_overhead=index_overhead):
        return float("inf")
    if encoding == "sparse":
        return (budget_bytes / dense_nbytes) / (1.0 + index_overhead)
    if encoding == "palette":
        return budget_bytes / dense_nbytes
    return float("inf")  # dense: ratio has no effect; handled by clamp above
