"""The benchmark finds every piece of a cell by its name, and a cell added
as new files is picked up without editing a file that is there."""
from __future__ import annotations

import json

import pytest

import chipbench_tiny
from chipbench.registry import BENCH_DIR, REPO_DIR, Registry

SPEC = json.loads((REPO_DIR / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_benchmark_json_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_pieces_exist(name):
    reg = Registry()
    work = reg.workload(name)
    config = reg.config(work["config"])
    assert config["name"] == work["config"]
    traffic = reg.traffic(work["traffic"])
    assert traffic["name"] == work["traffic"]
    limits = reg.limits(name)
    from chipbench.check import EXACT, NUMBERS

    assert limits["numbers"] and set(limits["numbers"]) <= set(NUMBERS
                                                                + EXACT)
    for n, spec in limits["numbers"].items():
        if n in EXACT:
            assert spec["limit"] == 0 < float(spec["upper"])
        else:
            assert 0 < float(spec["lower"]) < float(spec["limit"]) < float(
                spec["upper"])
    assert hasattr(reg.reference(config["family"]), "loss")
    assert hasattr(reg.program(config["family"]), "grad_fn")
    for spec in reg.end_to_end(name) + reg.per_layer(name):
        assert callable(reg.metric_reader(spec["name"]))
    listed = {c["name"]: c for c in SPEC["configs"]}[work["config"]]
    assert listed["file"] == f"benchmarks/chip/configs/{work['config']}.json"
    assert listed["reduced"] == config["reduced"]


def test_every_config_file_is_a_listed_config():
    listed = {c["name"] for c in SPEC["configs"]}
    files = {p.stem for p in (BENCH_DIR / "configs").glob("*.json")}
    assert files == listed


def test_new_cell_as_new_files_is_found(tmp_path):
    bench, bj = chipbench_tiny.make(tmp_path)
    reg = Registry(bench, bj)
    for name, (config, traffic, _chips) in chipbench_tiny.WORKLOADS.items():
        work = reg.workload(name)
        assert reg.config(work["config"])["name"] == config
        assert reg.traffic(work["traffic"])["name"] == traffic
        assert reg.limits(name)["numbers"]["loss_gap"]["limit"] > 0
    # the files that were there are unchanged
    for sub in ("configs", "traffic", "metrics", "reference", "programs",
                "limits", "chipbench"):
        for p in (BENCH_DIR / sub).iterdir():
            if p.is_file():
                assert (bench / sub / p.name).read_bytes() == p.read_bytes()


def test_new_metric_as_new_file_is_found(tmp_path):
    bench, bj = chipbench_tiny.make(tmp_path)
    (bench / "metrics" / "rounds_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    spec = json.loads(bj.read_text())
    spec["per_layer"].append({"name": "rounds_in_window", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine host path",
                              "moves": "samples_per_s",
                              "workloads": ["tiny_cnn"]})
    bj.write_text(json.dumps(spec))
    reg = Registry(bench, bj)
    assert [m["name"] for m in reg.per_layer("tiny_cnn")][-1] == \
        "rounds_in_window"
    assert "rounds_in_window" not in [m["name"] for m in
                                      reg.per_layer("tiny_lm")]

    class Ctx:
        rounds = 7

    assert reg.metric_reader("rounds_in_window")(Ctx()) == 7.0


def test_unknown_device_kind_is_refused():
    reg = Registry()
    assert reg.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        reg.peaks("TPU v9 imaginary")


def test_missing_piece_is_an_error(tmp_path):
    bench, bj = chipbench_tiny.make(tmp_path)
    (bench / "limits" / "tiny_cnn.json").unlink()
    with pytest.raises(FileNotFoundError):
        Registry(bench, bj).limits("tiny_cnn")
    with pytest.raises(KeyError):
        Registry(bench, bj).workload("no_such_cell")
