"""The plain reference against the program at a tiny size on the CPU: the
models' losses and gradients, and whole runs of the harness (dense, top-k
and LM cells) in which the reference and RoundEngine agree, while the
reference in bfloat16 (the control) and with half of each batch left out
fail the comparison."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import check
from chipbench.cell import run_cell, seed_key
from chipbench.registry import Registry

SEED = 2 ** 33 + 29  # wider than 32 bits, as a run's seed may be


@pytest.fixture()
def reg(tmp_path, monkeypatch):
    import repro.utils.compile_cache as cc

    monkeypatch.setattr(cc, "use_compile_cache", lambda: None)
    bench, bj = chipbench_tiny.make(tmp_path)
    return Registry(bench, bj)


def _program_params(family, config):
    if family == "cnn":
        from repro.models import cnn

        return cnn.init_params(jax.random.PRNGKey(0))
    from repro.models import transformer as T

    arch = Registry().program("transformer").arch_config(config)
    return T.init_model(jax.random.PRNGKey(0), arch)[0]


@pytest.mark.parametrize("config_name", ["tiny_cnn", "tiny_lm"])
def test_model_loss_and_gradient_match_the_program(reg, config_name):
    config = reg.config(config_name)
    family = config["family"]
    ref = reg.reference(family)
    params = ref.init_params(seed_key(SEED), config)
    # the reference makes the weights in the program's layout
    prog_shapes = jax.tree_util.tree_map(
        lambda x: (x.shape, x.dtype), _program_params(family, config))
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype),
                                  params) == prog_shapes
    rng = np.random.default_rng(0)
    if family == "cnn":
        batch = {"x": rng.uniform(size=(4, 28, 28, 1)).astype(np.float32),
                 "y": rng.integers(0, 10, size=4).astype(np.int32)}
    else:
        batch = {"tokens": rng.integers(0, config["vocab_size"],
                                        size=(2, 16)).astype(np.int32)}
    want_l, want_g = jax.value_and_grad(
        lambda p: ref.loss(p, batch, config))(params)
    with jax.default_matmul_precision("highest"):
        got_l, got_g = reg.program(family).grad_fn(config)(params, batch)
        want_l, want_g = jax.value_and_grad(
            lambda p: ref.loss(p, batch, config))(params)
    # float32 on the CPU: the two differ by the order of their sums alone
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale


def test_cnn_gradient_where_activations_are_exactly_zero(reg):
    # blank image regions and zero biases put many ReLU inputs exactly at
    # 0: the reference has to take the program's subgradient there (0)
    from chipbench import traffic

    config = reg.config("tiny_cnn")
    ref = reg.reference("cnn")
    params = ref.init_params(seed_key(SEED), config)
    x, y = traffic.mnist_like(20, np.random.default_rng(3))
    assert np.count_nonzero(x == 0) > x.size // 4
    batch = {"x": x, "y": y}
    got_l, got_g = reg.program("cnn").grad_fn(config)(params, batch)
    want_l, want_g = jax.value_and_grad(
        lambda p: ref.loss(p, batch, config))(params)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * scale


@pytest.mark.parametrize("workload", ["tiny_cnn", "tiny_cnn_topk", "tiny_lm"])
def test_run_agrees_and_the_control_fails(reg, workload):
    res = run_cell(reg, workload, SEED, 0.2, False, time.perf_counter(),
                   require_tpu=False, log=lambda _m: None,
                   variants={"control": {"dtype": jnp.bfloat16},
                             "half": {"half_batch": True}})
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    # the reference in the program's place serves no batch of its own
    limits = {"numbers": {k: v for k, v in reg.limits(workload)["numbers"]
                          .items() if k in check.NUMBERS}}
    for variant in ("control", "half"):
        ok, checks = check.judge(res["variants"][variant], limits)
        assert not ok, (variant, checks)
    assert res["metrics"]["samples_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
