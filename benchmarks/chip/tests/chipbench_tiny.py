"""A copy of the benchmark with tiny cells, for runs on the CPU.

``make(tmp)`` copies the benchmark's files under ``tmp`` and adds, as new
files and ``BENCHMARK.json`` entries only, tiny configurations, traffic
mixes and limits: the way a later change adds a cell.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parents[1]
for p in (str(BENCH_DIR), str(REPO_DIR / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_LM = {
    "name": "tiny_lm", "source": "test", "family": "transformer",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "hidden_act": "silu", "rope_theta": 10000,
    "layer_norm_eps": 1e-5, "reduced": [],
    "training": {"algorithm": "dprox", "regularizer": "l1", "lam": 1e-6,
                 "eta": 0.02, "eta_g": 2.0, "tau": 2, "batch": 2,
                 "param_dtype": "float32"},
    "precision": {"param_dtype": "float32", "matmul_precision": "default"},
}
TRAFFIC = {
    "tiny_images": {"clients": 4, "supplier": "host_per_round",
                    "data": {"kind": "mnist_like", "images_per_client": 20},
                    "engine": {"chunk_rounds": 2, "plane": False,
                               "uplink": None}},
    "tiny_topk": {"clients": 4, "supplier": "host_per_round",
                  "data": {"kind": "mnist_like", "images_per_client": 20},
                  "engine": {"chunk_rounds": 2, "plane": True,
                             "uplink": {"kind": "topk", "ratio": 0.1,
                                        "granularity": "global"}}},
    "tiny_tokens": {"clients": 2, "supplier": "device_cache",
                    "data": {"kind": "token_streams", "seq_len": 16,
                             "seqs_per_client": 8, "bigram_vocab": 32,
                             "skew": 4.0},
                    "engine": {"chunk_rounds": 2, "plane": False,
                               "uplink": None}},
}
WORKLOADS = {
    "tiny_cnn": ("tiny_cnn", "tiny_images", 1),
    "tiny_cnn_topk": ("tiny_cnn", "tiny_topk", 1),
    "tiny_lm": ("tiny_lm", "tiny_tokens", 1),
}
#: float32 on the CPU, where the program and the reference differ by the
#: order of their sums alone: the loss by ~1e-6, the corrections (a
#: difference of gradients, times 1 / (eta eta_g tau)) and the change by up
#: to ~4e-4.  The control in bfloat16 and the planted faults read 2e-2 and
#: more on each number.
TINY_LIMITS = {"first_loss_gap": 1e-3, "loss_gap": 1e-3, "grad_gap": 1e-2,
               "change_gap": 1e-2}


def make(tmp) -> tuple:
    """(bench dir, BENCHMARK.json path) of a copy with the tiny cells."""
    tmp = pathlib.Path(tmp)
    bench = tmp / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cnn = json.loads((bench / "configs" / "cnn_fig4.json").read_text())
    cnn.update(name="tiny_cnn")
    cnn["training"] = dict(cnn["training"], tau=2, batch=2)
    (bench / "configs" / "tiny_cnn.json").write_text(json.dumps(cnn))
    (bench / "configs" / "tiny_lm.json").write_text(json.dumps(TINY_LM))
    for name, t in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(t, name=name)))
    spec = json.loads((REPO_DIR / "BENCHMARK.json").read_text())
    for name, (config, traffic, chips) in WORKLOADS.items():
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": chips,
                                  "why": "test"})
        limits = dict(TINY_LIMITS)
        if TRAFFIC[traffic]["supplier"] == "device_cache":
            limits["feed_rows_wrong"] = 0
        (bench / "limits" / f"{name}.json").write_text(json.dumps(
            {"numbers": {k: {"limit": v} for k, v in limits.items()}}))
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return bench, path
