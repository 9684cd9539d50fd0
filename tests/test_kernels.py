"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles.

All kernels run in interpret=True mode on CPU (the kernel body executes in
Python with real semantics); on TPU the same call sites compile.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or fixed-grid fallback

from repro.kernels import ops, ref
from repro.kernels.fused_prox import fused_local_update_2d

# ---------------------------------------------------------------------------
# fused prox update
# ---------------------------------------------------------------------------

SHAPES = [(256, 128), (512, 128), (2048, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_fused_prox_2d_matches_ref(shape, dtype):
    rng = np.random.default_rng(0)
    zh = jnp.asarray(rng.normal(size=shape), dtype)
    g = jnp.asarray(rng.normal(size=shape), dtype)
    c = jnp.asarray(rng.normal(size=shape), dtype)
    eta, thresh = 0.37, 0.21
    got_zh, got_z = fused_local_update_2d(zh, g, c, eta, thresh,
                                          interpret=True, block_rows=256)
    exp_zh, exp_z = ref.fused_local_update(zh, g, c, eta, thresh)
    # kernel accumulates in fp32 then rounds once; the bf16 ref rounds every
    # op, so allow 1-ulp relative slack for bf16
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    rtol = 0 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got_zh, np.float32),
                               np.asarray(exp_zh, np.float32), atol=tol, rtol=rtol)
    np.testing.assert_allclose(np.asarray(got_z, np.float32),
                               np.asarray(exp_z, np.float32), atol=tol, rtol=rtol)


@given(seed=st.integers(0, 2**31 - 1),
       n=st.integers(1, 5000),
       eta=st.floats(1e-4, 2.0),
       lam=st.floats(0.0, 1.0))
@settings(max_examples=15, deadline=None)
def test_fused_prox_pytree_arbitrary_sizes(seed, n, eta, lam):
    """The ops wrapper pads/reshapes arbitrary pytrees correctly."""
    rng = np.random.default_rng(seed)
    tree = {
        "w": jnp.asarray(rng.normal(size=(n,)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(3, 7)), jnp.float32),
    }
    g = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), tree)
    c = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), tree)
    got_zh, got_z = ops.fused_local_update(tree, g, c, eta, lam,
                                           interpret=True, block_rows=8)
    exp_zh, exp_z = jax.tree_util.tree_map(
        lambda a, b, d: ref.fused_local_update(a, b, d, eta, lam)[0],
        tree, g, c), jax.tree_util.tree_map(
        lambda a, b, d: ref.fused_local_update(a, b, d, eta, lam)[1],
        tree, g, c)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got_zh[k]), np.asarray(exp_zh[k]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_z[k]), np.asarray(exp_z[k]),
                                   atol=1e-6)


def test_fused_step_in_round_fn_matches_plain():
    """Algorithm 1 round with the fused kernel == plain jnp round."""
    from repro.core.algorithm import DProxConfig, init_state, make_round_fn
    from repro.core.prox import L1
    from repro.models import logreg
    from repro.data.synthetic import logistic_heterogeneous, make_round_batches
    from repro.utils import tree as tu

    data = logistic_heterogeneous(n_clients=4, m_per_client=20, d=12, seed=0)
    data.features = (data.features / 50).astype(np.float32)
    reg = L1(lam=0.01)
    grad_fn = logreg.make_grad_fn()
    params0 = logreg.init_params(12)
    cfg = DProxConfig(tau=3, eta=0.05, eta_g=2.0)
    rf_plain = make_round_fn(cfg, reg, grad_fn)
    rf_fused = make_round_fn(cfg, reg, grad_fn, use_fused_kernel=True)
    s1 = init_state(params0, 4)
    s2 = init_state(params0, 4)
    rng = np.random.default_rng(0)
    for _ in range(2):
        batches = make_round_batches(data, cfg.tau, 8, rng)
        s1, _ = rf_plain(s1, batches)
        s2, _ = rf_fused(s2, batches)
    diff = float(tu.tree_norm(tu.tree_sub(s1.x_bar, s2.x_bar)))
    assert diff < 1e-5, f"fused round diverged from reference: {diff}"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,d,bq,bk", [(128, 64, 64, 64), (256, 128, 128, 128),
                                       (512, 64, 128, 64)])
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_flash_attention_causal_matches_ref(s, d, bq, bk, dtype):
    rng = np.random.default_rng(1)
    shape = (2, 3, s, d)
    q = jnp.asarray(rng.normal(size=shape) * 0.5, dtype)
    k = jnp.asarray(rng.normal(size=shape) * 0.5, dtype)
    v = jnp.asarray(rng.normal(size=shape) * 0.5, dtype)
    from repro.kernels.flash_attention import flash_attention

    got = flash_attention(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), atol=tol)


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_flash_attention_window_softcap(window, softcap):
    rng = np.random.default_rng(2)
    shape = (1, 2, 256, 64)
    q = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
    from repro.kernels.flash_attention import flash_attention

    got = flash_attention(q, k, v, causal=True, window=window,
                          softcap=softcap, bq=64, bk=64, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True, window=window,
                              softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=3e-5)


def test_gqa_wrapper_matches_model_attention():
    """ops.gqa_flash_attention == the model's _sdpa path (GQA, causal)."""
    from repro.models import layers as L

    rng = np.random.default_rng(3)
    b, s, h, kh, d = 2, 128, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kh, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kh, d)) * 0.3, jnp.float32)
    got = ops.gqa_flash_attention(q, k, v, causal=True, interpret=True)
    mask = L.causal_mask(s, s)[None, None]
    exp = L._sdpa(q, k, v, mask, 1.0 / (d ** 0.5))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=3e-5)


# ---------------------------------------------------------------------------
# kernel names: what a profiler trace and its readers find them by
# ---------------------------------------------------------------------------


def _kernel_calls():
    from repro.kernels import flash_attention, fused_prox, plane_ops

    x, t = jnp.ones((2, 8, 128)), jnp.ones((2,))
    tile = jnp.ones((256, 128))
    qkv = jnp.ones((1, 1, 128, 64))
    return {
        "threshold_select": (plane_ops.threshold_select_3d, (x, t), {}),
        "topk-kth": (plane_ops.kth_magnitude_3d,
                     (x.astype(jnp.float32), jnp.ones((2,), jnp.int32)), {}),
        "quantize_plane": (plane_ops.quantize_3d, (x, x, t, 4), {}),
        "weighted_commit": (plane_ops.weighted_commit_3d, (x, t), {}),
        "fused_prox_update": (fused_prox.fused_local_update_2d,
                              (tile, tile, tile, 0.1, 0.01), {}),
        "flash_attention": (flash_attention.flash_attention, (qkv,) * 3,
                            {"bq": 64, "bk": 64}),
    }


@pytest.mark.parametrize("name", ["threshold_select", "topk-kth",
                                  "quantize_plane", "weighted_commit",
                                  "fused_prox_update", "flash_attention"])
def test_pallas_call_carries_its_name(name):
    """Each ``pallas_call`` is named: lowered in interpret mode, its body
    sits under a scope of that name."""
    fn, args, kw = _kernel_calls()[name]
    text = fn.lower(*args, interpret=True, **kw).as_text(debug_info=True)
    assert f"/{name}/" in text
