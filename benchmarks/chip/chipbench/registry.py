"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in files of its own under the benchmark's directory, so a
later change adds a cell by adding files and entries, never by editing one:

  configs/<config>.json      sizes, source, cuts, precision, deployment
  traffic/<mix>.json         the parameters the generator in traffic.py reads
  metrics/<metric>.py        ``read(ctx) -> float | None`` for one metric
  limits/<workload>.json     the limits of the correctness comparison
  reference/<family>.py      the plain float32 model the comparison runs
  programs/<family>.py       how the program under test builds that model
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parents[1]


class Registry:
    """The benchmark's files under ``bench_dir`` and the ``BENCHMARK.json``
    that names its cells."""

    def __init__(self, bench_dir=BENCH_DIR, benchmark_json=None):
        self.dir = pathlib.Path(bench_dir)
        path = (pathlib.Path(benchmark_json) if benchmark_json is not None
                else self.dir.parents[1] / "BENCHMARK.json")
        self.benchmark = json.loads(path.read_text())
        self._modules: dict = {}

    def workload(self, name: str) -> dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.benchmark['workloads']]}")

    def _json(self, sub: str, name: str) -> dict:
        path = self.dir / sub / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"{path} is missing")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"peaks.json ({sorted(table)}); add its published "
                           "peaks there rather than assume another chip's")
        return table[device_kind]

    def module(self, sub: str, name: str):
        """The module ``<sub>/<name>.py``, loaded once by its path."""
        key = (sub, name)
        if key not in self._modules:
            path = self.dir / sub / f"{name}.py"
            if not path.is_file():
                raise FileNotFoundError(f"{path} is missing")
            mod_name = f"chipbench_{sub}_{name}".replace(".", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def reference(self, family: str):
        return self.module("reference", family)

    def program(self, family: str):
        return self.module("programs", family)

    def metric_reader(self, name: str):
        return self.module("metrics", name).read

    def end_to_end(self, workload: str) -> list:
        return [m for m in self.benchmark["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        return [m for m in self.benchmark["per_layer"]
                if workload in m.get("workloads", [workload])]
