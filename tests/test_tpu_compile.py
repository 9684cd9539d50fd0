"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel (or a whole DProx
round) at real widths for one chip of a described ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what interpret mode
accepts -- blocks not aligned to the (8, 128) tiling, more VMEM than a
kernel may use.  ``tpu_custom_call`` in the compiled text shows the kernel
was compiled, not inlined as jnp.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU library, and where it cannot be described the
tests skip.  The persistent compilation cache is off around the compiles
(a compile for a described device cannot be read back without one), and
so is x64, which the chip path does not use.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import fused_prox, plane_ops

#: the CNN of the paper's Section 4.2 (d = 112,394) on the engine's plane:
#: padded to 128 lanes, 879 rows (the kernels' grids are ragged there)
CNN_ROWS = 879
#: the same d padded to whole fused-prox tiles (ops.fused_local_update)
CNN_FUSED_ROWS = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the process's JAX config as the chip path
    runs it: float32 (other test modules turn x64 on at import, and the
    kernels do not lower under it) and no persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_fused_local_update_compiles(one_chip):
    x = _shape(one_chip, (CNN_FUSED_ROWS, fused_prox.LANES))
    txt = _compiled_text(
        lambda zh, g, c: fused_prox.fused_local_update_2d(zh, g, c, 0.005,
                                                          1e-6),
        x, x, x)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n_clients", [10, 64])
def test_threshold_select_compiles(one_chip, n_clients):
    txt = _compiled_text(
        plane_ops.threshold_select_3d,
        _shape(one_chip, (n_clients, CNN_ROWS, plane_ops.LANES)),
        _shape(one_chip, (n_clients,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n_clients", [10, 64])
def test_quantize_compiles(one_chip, n_clients):
    x = _shape(one_chip, (n_clients, CNN_ROWS, plane_ops.LANES))
    txt = _compiled_text(
        lambda x, u, s: plane_ops.quantize_3d(x, u, s, 255),
        x, x, _shape(one_chip, (n_clients,)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("n_clients", [10, 64, 256])
def test_weighted_commit_compiles(one_chip, n_clients):
    """One block holds every client's tile column, so its height shrinks
    with the client count (64 clients at the full 256-row block ran out of
    VMEM)."""
    txt = _compiled_text(
        plane_ops.weighted_commit_3d,
        _shape(one_chip, (n_clients, CNN_ROWS, plane_ops.LANES)),
        _shape(one_chip, (n_clients,)))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles(one_chip):
    """32 heads x 64 dims at sequence 2048 in bf16: stablelm-1.6b's heads."""
    x = _shape(one_chip, (1, 32, 2048, 64), jnp.bfloat16)
    txt = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True), x, x, x)
    assert "tpu_custom_call" in txt


def test_cnn_dprox_round_with_fused_kernel_compiles(one_chip, monkeypatch):
    """The paper's CNN round (10 clients, tau = 5, batch 10) with the fused
    local-update kernel inside the client vmap and the local scan."""
    from repro.core.algorithm import DProxConfig, init_state, make_round_fn
    from repro.core.prox import L1
    from repro.kernels import ops as kops
    from repro.models import cnn

    # the backend here is the CPU, which would pick interpret mode
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    n, tau, b = 10, 5, 10
    params = jax.eval_shape(cnn.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: init_state(p, n), params)
    state, params = jax.tree_util.tree_map(
        lambda s: _shape(one_chip, s.shape, s.dtype), (state, params))
    batches = {"x": _shape(one_chip, (n, tau, b, 28, 28, 1)),
               "y": _shape(one_chip, (n, tau, b), jnp.int32)}
    round_fn = make_round_fn(DProxConfig(tau=tau, eta=0.005, eta_g=1.0),
                             L1(lam=1e-4), cnn.make_grad_fn(),
                             use_fused_kernel=True)
    assert "tpu_custom_call" in _compiled_text(round_fn, state, batches)
