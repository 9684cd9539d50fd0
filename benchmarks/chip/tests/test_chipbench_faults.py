"""Whole runs of the harness at a tiny size on the CPU, with the timed path
broken underneath: ``correct`` has to come out false for each fault a
training cell can have."""
from __future__ import annotations

import time

import jax
import pytest

import chipbench_tiny
from chipbench.cell import run_cell
from chipbench.registry import Registry

SEED = 2 ** 32 + 5


@pytest.fixture()
def reg(tmp_path, monkeypatch):
    import repro.utils.compile_cache as cc

    monkeypatch.setattr(cc, "use_compile_cache", lambda: None)
    bench, bj = chipbench_tiny.make(tmp_path)
    return Registry(bench, bj)


def _run(reg, workload):
    return run_cell(reg, workload, SEED, 0.2, False, time.perf_counter(),
                    require_tpu=False, log=lambda _m: None)


def test_state_returned_unchanged(reg, monkeypatch):
    from repro.exec import RoundEngine

    real = RoundEngine.run

    def unchanged(self, state, *a, **kw):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), state)
        _, metrics = real(self, state, *a, **kw)
        return keep, metrics

    monkeypatch.setattr(RoundEngine, "run", unchanged)
    res = _run(reg, "tiny_cnn")
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", ["tiny_cnn", "tiny_lm"])
def test_half_of_the_batch_left_out(reg, monkeypatch, workload):
    if workload == "tiny_cnn":
        from repro.models import cnn as mod

        real = mod.loss_fn
        monkeypatch.setattr(mod, "loss_fn", lambda p, b: real(
            p, jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], b)))
    else:
        from repro.models import transformer as mod

        real = mod.loss_fn
        monkeypatch.setattr(mod, "loss_fn", lambda p, cfg, b: real(
            p, cfg, jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2],
                                           b)))
    res = _run(reg, workload)
    assert not res["correct"], res["checks"]


def _served(how):
    """``ArraySupplier.sample_chunk`` broken one way where it serves."""
    import jax.numpy as jnp
    from repro.exec import ArraySupplier

    real_chunk, real_gather = ArraySupplier.sample_chunk, ArraySupplier._gather

    def gather(self, idx, client_ids=None):
        ids = {"other_client": list(range(self.n_clients))[::-1],
               "one_stream": [0] * self.n_clients}[how]
        return real_gather(self, idx, ids)

    def stale(self, start_round, n_rounds, rng=None, **kw):
        return real_chunk(self, 0, n_rounds, rng, **kw)

    def token(self, *a, **kw):
        chunk = real_chunk(self, *a, **kw)
        t = chunk["tokens"]
        return dict(chunk, tokens=t.at[0, 0, 0, 0, 0].set(
            (t[0, 0, 0, 0, 0] + 1) % 256).astype(t.dtype))

    if how in ("other_client", "one_stream"):
        return "_gather", gather
    return "sample_chunk", {"stale_round": stale, "token_altered": token}[how]


@pytest.mark.parametrize("how", ["other_client", "one_stream", "stale_round",
                                 "token_altered"])
def test_supplier_serves_the_wrong_rows(reg, monkeypatch, how):
    from repro.exec import ArraySupplier

    monkeypatch.setattr(ArraySupplier, *_served(how))
    res = _run(reg, "tiny_lm")
    assert not res["correct"]
    wrong = res["checks"]["feed_rows_wrong"]["value"]
    # a token altered in each of the three chunks the reference replays
    assert wrong == 3 if how == "token_altered" else wrong > 3


def test_sound_lm_run_serves_the_harness_draw(reg):
    res = _run(reg, "tiny_lm")
    assert res["correct"], res["checks"]
    assert res["checks"]["feed_rows_wrong"] == {"value": 0, "limit": 0.0}
