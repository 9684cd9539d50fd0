"""``run_cell``'s hold on the initial weights, at a tiny size on the CPU: no
device copy of them lives through the measured window, the weights made
again from the seed are bitwise the first, and the result line reads as it
did with the all-device reference."""
from __future__ import annotations

import gc
import time
import weakref

import jax
import numpy as np
import pytest

import chipbench_tiny
import dprox_witness
from chipbench import cell
from chipbench.registry import Registry

SEED = 2 ** 34 + 3


@pytest.fixture()
def reg(tmp_path, monkeypatch):
    import repro.utils.compile_cache as cc

    monkeypatch.setattr(cc, "use_compile_cache", lambda: None)
    bench, bj = chipbench_tiny.make(tmp_path)
    return Registry(bench, bj)


def _run(reg, workload):
    return cell.run_cell(reg, workload, SEED, 0.2, False, time.perf_counter(),
                         require_tpu=False, log=lambda _m: None)


@pytest.mark.parametrize("workload", ["tiny_cnn", "tiny_lm"])
def test_no_device_copy_of_the_weights_through_the_window(reg, monkeypatch,
                                                          workload):
    from repro.exec import RoundEngine

    made = []  # each time the weights are made: weak refs, host copies
    real_weights = cell.weights

    def weights(init_fn, seed):
        w = real_weights(init_fn, seed)
        leaves = jax.tree_util.tree_leaves(w)
        made.append(([weakref.ref(x) for x in leaves],
                     [np.array(x) for x in leaves]))
        return w

    calls, alive = [0], []
    real_run = RoundEngine.run

    def run(self, *a, **kw):
        calls[0] += 1
        if calls[0] > cell.STEPS:  # a chunk of the measured window
            gc.collect()
            alive.append(sum(r() is not None for refs, _ in made
                             for r in refs))
        return real_run(self, *a, **kw)

    monkeypatch.setattr(cell, "weights", weights)
    monkeypatch.setattr(RoundEngine, "run", run)
    res = _run(reg, workload)
    assert res["correct"], res["checks"]
    assert alive and max(alive) == 0
    # for engine.init, for the change after step 3, for the reference
    assert len(made) == 3
    first = made[0][1]
    for _, again in made[1:]:
        for a, b in zip(first, again):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("workload", ["tiny_cnn_topk", "tiny_lm"])
def test_result_line_reads_as_with_the_device_reference(reg, monkeypatch,
                                                        workload):
    new = _run(reg, workload)
    monkeypatch.setattr(reg.reference("dprox"), "run", dprox_witness.run)
    old = _run(reg, workload)
    assert list(new) == list(old) == ["correct", "attempted", "failed",
                                      "metrics", "device", "checks"]
    assert new["correct"] and old["correct"]
    assert new["failed"] == old["failed"] == 0
    assert list(new["metrics"]) == list(old["metrics"])
    assert new["device"] == old["device"]
    assert list(new["checks"]) == list(old["checks"])
    for name, c in new["checks"].items():
        assert c["limit"] == old["checks"][name]["limit"]
        assert c["value"] == pytest.approx(old["checks"][name]["value"],
                                           rel=1e-2, abs=1e-4), name
