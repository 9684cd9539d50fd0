"""The FLOP and byte counters against counts made by hand."""
from __future__ import annotations

import json

import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
from chipbench.registry import Registry


def test_cnn_flops_per_image():
    reg = Registry()
    config = reg.config("cnn_fig4")
    # forward: conv1 28*28*32*9*2, conv2 14*14*32*288*2, dense
    # (1568*64 + 64*32 + 32*10)*2
    conv1, conv2 = 451_584, 3_612_672
    dense = 200_704 + 4_096 + 640
    forward = conv1 + conv2 + dense
    assert forward == 4_269_696
    want = 3 * forward - conv1
    got = reg.reference("cnn").flops_per_sample(config, {})
    assert got == want == 12_357_504


def test_lm_flops_per_sequence():
    reg = Registry()
    config = reg.config("stablelm2_1_6b_l2")
    traffic = reg.traffic("tokens_2c")
    s, d, f, v, layers = 1024, 2048, 5632, 12544, 2
    # per token forward: q, k, v, o (4 d^2 at 32 x 64 = d), gate, up, down,
    # the tied head, and QK^T and PV over all 1024 positions
    per_token = 2 * (layers * (4 * d * d + 3 * d * f) + d * v)
    attention = layers * 4 * s * d
    want = 3 * s * (per_token + attention)
    got = reg.reference("transformer").flops_per_sample(config, traffic)
    assert got == want
    assert got == pytest.approx(8.41e11, rel=1e-3)


def test_param_count_of_the_lm_cut():
    reg = Registry()
    config = reg.config("stablelm2_1_6b_l2")
    import jax

    params = jax.eval_shape(
        lambda k: reg.reference("transformer").init_params(k, config),
        jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    d, f = 2048, 5632
    assert n == 2 * (4 * d * d + 3 * d * f + 2 * d) + 12544 * d + d


def test_cnn_config_is_the_papers_size():
    reg = Registry()
    config = reg.config("cnn_fig4")
    import jax

    params = jax.eval_shape(
        lambda k: reg.reference("cnn").init_params(k, config),
        jax.random.PRNGKey(0))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(params)) == \
        config["num_params"] == 112_394


def test_lm_config_keeps_the_published_widths():
    config = json.loads((Registry().dir / "configs" /
                         "stablelm2_1_6b_l2.json").read_text())
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["intermediate_size"]) == \
        (2048, 32, 32, 5632)
    assert config["hidden_size"] // config["num_attention_heads"] == 64
    assert sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
