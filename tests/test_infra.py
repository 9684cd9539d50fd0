"""Infrastructure tests: checkpointing, serving engine, data pipelines,
roofline collective parser, sharding rule resolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or fixed-grid fallback

# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import ckpt

    tree = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "nested": {"b": jnp.ones((2,), jnp.bfloat16),
                   "c": jnp.asarray(3, jnp.int32)},
    }
    p = tmp_path / "ck.npz"
    ckpt.save(tree, p, metadata={"round": 7})
    out = ckpt.restore(p, like=jax.tree_util.tree_map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    assert ckpt.metadata(p)["round"] == 7


def test_checkpoint_shape_mismatch_raises(tmp_path):
    from repro.checkpoint import ckpt

    p = tmp_path / "ck.npz"
    ckpt.save({"a": jnp.ones((3,))}, p)
    with pytest.raises(ValueError):
        ckpt.restore(p, like={"a": jnp.ones((4,))})


def test_checkpoint_fed_state_roundtrip(tmp_path):
    from repro.checkpoint import ckpt
    from repro.core.algorithm import init_state
    from repro.models import logreg

    state = init_state(logreg.init_params(6), 3)
    p = tmp_path / "state.npz"
    ckpt.save(state, p, metadata={"arch": "logreg"})
    out = ckpt.restore(p, like=state)
    assert out.round.shape == state.round.shape
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(state)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


def test_serving_engine_greedy_deterministic():
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving.engine import ServingEngine

    cfg = registry.get_smoke("stablelm_1_6b").with_overrides(
        param_dtype=jnp.float32)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=48)
    prompts = np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % cfg.vocab
    r1 = eng.generate(prompts, max_new_tokens=6)
    r2 = eng.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)
    assert r1.tokens.shape == (2, 6)
    assert np.all(r1.logprobs <= 0)


def test_serving_engine_rejects_encoder():
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.serving.engine import ServingEngine

    cfg = registry.get_smoke("hubert_xlarge")
    with pytest.raises(ValueError):
        ServingEngine(cfg, params=None)


# ---------------------------------------------------------------------------
# data pipelines
# ---------------------------------------------------------------------------


def test_logreg_generator_heterogeneity_scales_with_alpha_beta():
    from repro.data.synthetic import heterogeneity_index, logistic_heterogeneous

    lo = logistic_heterogeneous(n_clients=10, m_per_client=80, d=8,
                                alpha=0.01, beta=0.01, seed=1)
    hi = logistic_heterogeneous(n_clients=10, m_per_client=80, d=8,
                                alpha=50, beta=50, seed=1)
    assert heterogeneity_index(hi) > heterogeneity_index(lo)


def test_round_batches_shapes_and_determinism():
    from repro.data.synthetic import logistic_heterogeneous, make_round_batches

    data = logistic_heterogeneous(n_clients=4, m_per_client=30, d=6)
    b1 = make_round_batches(data, tau=3, batch_size=5,
                            rng=np.random.default_rng(7))
    b2 = make_round_batches(data, tau=3, batch_size=5,
                            rng=np.random.default_rng(7))
    assert b1["a"].shape == (4, 3, 5, 6)
    np.testing.assert_array_equal(b1["a"], b2["a"])
    full = make_round_batches(data, tau=2, batch_size=None,
                              rng=np.random.default_rng(0))
    assert full["a"].shape == (4, 2, 30, 6)


def test_token_streams_are_client_specific():
    from repro.data.synthetic import token_stream_heterogeneous

    s = token_stream_heterogeneous(3, 64, 4, vocab=64, seed=0)
    assert s.shape == (3, 4, 64)
    # bigram statistics should differ across clients
    def bigram_hist(x):
        h = np.zeros((64, 64))
        for seq in x.reshape(-1, 64):
            for a, b in zip(seq[:-1], seq[1:]):
                h[a, b] += 1
        return h / h.sum()

    h0, h1 = bigram_hist(s[0]), bigram_hist(s[1])
    assert np.abs(h0 - h1).sum() > 0.5


# ---------------------------------------------------------------------------
# roofline parser + sharding rules
# ---------------------------------------------------------------------------


def test_collective_parser_shapes_and_groups():
    from repro.roofline.analysis import parse_collectives

    hlo = """
  %ag = bf16[1024,128]{1,0} all-gather(%x), replica_groups=[32,16]<=[512], dimensions={0}
  %ar = f32[256]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = (f32[64]{0}, f32[64]{0}) reduce-scatter(%a, %b), replica_groups=[64,8]<=[512]
  %cp = u8[16]{0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    out = parse_collectives(hlo)
    ops = [o[0] for o in out]
    assert ops == ["all-gather", "all-reduce", "reduce-scatter",
                   "collective-permute"]
    ag = out[0]
    assert ag[1] == 1024 * 128 * 2 and ag[2] == 16
    ar = out[1]
    assert ar[1] == 256 * 4 and ar[2] == 4
    rs = out[2]
    assert rs[1] == 2 * 64 * 4 and rs[2] == 8
    assert out[3][3] == 16  # permute moves its payload once


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_spec_for_never_overassigns(seed):
    """Property: resolved specs always divide dims and never reuse a mesh
    axis within one tensor."""
    from repro.launch.sharding import _COMMON_PARAMS, spec_for

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}

    rng = np.random.default_rng(seed)
    axes_pool = list(_COMMON_PARAMS)
    ndim = rng.integers(1, 4)
    axes = tuple(rng.choice(axes_pool) for _ in range(ndim))
    shape = tuple(int(rng.choice([1, 8, 16, 64, 100352, 131072, 7, 24]))
                  for _ in range(ndim))
    spec = spec_for(shape, axes, _COMMON_PARAMS, FakeMesh())
    used = []
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        sz = 1
        for nm in names:
            assert nm not in used
            used.append(nm)
            sz *= FakeMesh.shape[nm]
        assert dim % sz == 0


def test_compile_cache_keys_on_named_scopes(monkeypatch, tmp_path):
    """The entry points' compile cache keys each program on its metadata
    too: two builds that differ only by a named scope (the layer a
    profiler trace reports) do not share a cached executable."""
    from jax._src import cache_key, compiler

    from repro.utils.compile_cache import use_compile_cache

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2.0

        return jax.jit(f).lower(jnp.ones(4)).compiler_ir()

    def key():
        devices = np.array(jax.devices()[:1])
        options = compiler.get_compile_options(1, 1)
        return [cache_key.get(scoped(n), devices, options,
                              jax.devices()[0].client) for n in ("a", "b")]

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        a, b = key()
        assert a == b
        use_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        a, b = key()
        assert a != b
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          was)
