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


# -- the host formulation against the all-device one ---------------------

VARIANTS = {"plain": {}, "control_bf16": {"dtype": jnp.bfloat16},
            "half_batch": {"half_batch": True}, "nudge": {"nudge": True}}


def _inputs(reg, workload, seed=SEED):
    """(loss, host weights, training, clients, rounds, chunk, uplink) of a
    tiny cell's reference, the rounds drawn as a run draws them."""
    from functools import partial

    from chipbench import traffic

    work = reg.workload(workload)
    config, mix = reg.config(work["config"]), reg.traffic(work["traffic"])
    ref = reg.reference(config["family"])
    data = traffic.make(mix, config, seed)
    chunk = int(mix["engine"]["chunk_rounds"])
    if mix["supplier"] == "device_cache":
        rounds = [traffic.array_round(data.arrays, data.tau, data.batch,
                                      seed, i) for i in range(3 * chunk)]
    else:
        rng = traffic.rng_for(seed, 2)
        rounds = [data.sample_round(rng) for _ in range(3 * chunk)]
    params = jax.device_get(ref.init_params(seed_key(seed), config))
    return (partial(ref.loss, config=config), params, config["training"],
            int(mix["clients"]), rounds, chunk, mix["engine"].get("uplink"))


def _gap(got, want) -> float:
    """The worst leaf's gap, over the larger of its and the median leaf's
    norm (as ``check.numbers`` measures)."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)
                        / np.maximum(want, np.median(want))))


#: float32 on the CPU: XLA's fused loops there round a multiply and an add
#: once (the TPU's, and the host's, twice), so z_hat differs by an ulp;
#: the corrections amplify that by |p| / (eta tau |g|).  Under the top-k an
#: ulp can move an entry across the k-th magnitude, and error feedback
#: carries it on: there the run agrees to 1e-3 in the loss and 1e-2 in
#: the norms, and ``test_topk_selects_as_lax_top_k`` holds the selection
#: itself to exact equality.
ROUNDOFF = {"loss": 1e-5, "norms": 1e-4}
ROUNDOFF_TOPK = {"loss": 1e-3, "norms": 1e-2}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("workload", ["tiny_cnn", "tiny_cnn_topk",
                                      "tiny_lm"])
def test_host_reference_reads_as_the_device_formulation(reg, workload,
                                                        variant):
    import dprox_witness

    dprox = reg.reference("dprox")
    loss, params, training, n, rounds, chunk, uplink = _inputs(reg, workload)
    kw = VARIANTS[variant]
    got = dprox.run(loss, params, training, n, rounds, chunk, uplink=uplink,
                    **kw)
    want = dprox_witness.run(loss, params, training, n, rounds, chunk,
                             uplink=uplink, **kw)
    assert len(got["losses"]) == len(want["losses"]) == 3 * chunk
    for key in ("grad_norms", "c_norms", "change_norms"):
        assert len(got[key]) == len(want[key])
    if variant == "control_bf16":
        # bfloat16 rounds each store to 8 bits, and under the top-k the
        # ties and near-ties at the k-th magnitude then pass or not by the
        # rounding: the control has to read as the device's control did,
        # against the float32 reference of its own formulation
        np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-2)
        base = dprox.run(loss, params, training, n, rounds, chunk,
                         uplink=uplink)
        base_w = dprox_witness.run(loss, params, training, n, rounds, chunk,
                                   uplink=uplink)
        ours, theirs = check.numbers(got, base), check.numbers(want, base_w)
        limits = chipbench_tiny.TINY_LIMITS
        for name in check.NUMBERS:
            assert theirs[name] / 10 <= ours[name] <= 10 * theirs[name], (
                name, ours[name], theirs[name])
            # it fails the numbers that the device's control failed
            assert (ours[name] > limits[name]) >= (
                theirs[name] > limits[name]), (name, ours, theirs)
        return
    tol = ROUNDOFF_TOPK if uplink is not None else ROUNDOFF
    np.testing.assert_allclose(got["losses"], want["losses"],
                               rtol=tol["loss"])
    for key in ("grad_norms", "c_norms", "change_norms"):
        assert _gap(got[key], want[key]) <= tol["norms"], (
            key, got[key], want[key])


@pytest.mark.parametrize("ef", [True, False])
@pytest.mark.parametrize("case", ["random", "ties", "zeros"])
def test_topk_selects_as_lax_top_k(reg, case, ef):
    # one client's global top-k with error feedback, against the device
    # formulation's lax.top_k on the same flat vector: bitwise
    dprox = reg.reference("dprox")
    rng = np.random.default_rng(7)
    shapes = [(3, 5), (7,), (4, 2, 3)]
    msg = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    e_i = rng.standard_normal(sum(m.size for m in msg)).astype(np.float32)
    if case == "ties":
        msg = [np.round(m * 2) / 2 for m in msg]
        e_i = np.round(e_i * 2) / 2
    elif case == "zeros":
        msg[1][:] = 0
        e_i[:20] = 0
    flat = np.concatenate([m.reshape(-1) for m in msg])
    target = e_i + flat
    k = int(round(0.3 * flat.size))
    kth = jax.lax.top_k(jnp.abs(target), k)[0][-1]
    want = np.asarray(jnp.where(jnp.abs(target) >= kth, target, 0))
    e_want = target - want if ef else e_i.copy()
    with dprox.Host() as h:
        sent = dprox._topk(h, msg, e_i, 0.3, ef)
    got = np.concatenate([m.reshape(-1) for m in sent])
    assert [m.shape for m in sent] == shapes
    assert got.tobytes() == want.tobytes()
    assert e_i.tobytes() == e_want.astype(np.float32).tobytes()


def test_device_holds_one_model_copy_outside_the_gradient_call(
        reg, monkeypatch):
    # every host step of a round sees at most one model's bytes in the
    # device arrays that the reference made (the gradient leaves still to
    # fetch and the next z's leaves already sent, by weak reference: the
    # CPU runtime may hold a finished call's inputs a while longer), and
    # Algorithm 1's state in host arrays
    import weakref

    dprox = reg.reference("dprox")
    loss, params, training, n, rounds, chunk, uplink = _inputs(
        reg, "tiny_cnn_topk")
    model = sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
        params))
    made = []

    def keep(tree):
        made.extend(weakref.ref(x) for x in jax.tree_util.tree_leaves(tree))
        return tree

    real_put, real_grad = jax.device_put, dprox.device_grad
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **kw: keep(real_put(*a, **kw)))
    monkeypatch.setattr(dprox, "device_grad", lambda f: (
        lambda *a: keep(real_grad(f)(*a))))
    seen = {"step": [], "client": [], "server": []}

    def spy(name, real):
        def wrapped(h, *args, **kw):
            seen[name].append(sum(r().nbytes for r in made
                                  if r() is not None))
            for a in args:
                assert not isinstance(a, jax.Array), type(a)
            return real(h, *args, **kw)
        return wrapped

    for name, fn in (("step", "_local_step"), ("client", "_finish_client"),
                     ("server", "_server")):
        monkeypatch.setattr(dprox, fn, spy(name, getattr(dprox, fn)))
    dprox.run(loss, params, training, n, rounds, chunk, uplink=uplink)
    steps = 3 * chunk * n * int(training["tau"])
    assert len(seen["step"]) == steps * len(jax.tree_util.tree_leaves(
        params))
    # the step's loss, a scalar, may still be on the device
    assert max(seen["step"]) <= model + 16
    assert max(seen["step"]) >= model // 2  # the probe sees the device
    assert max(seen["client"] + seen["server"]) <= 16


def test_weights_in_any_memory_order_read_alike(reg):
    # an array read back from the TPU can keep the device's order of axes
    # in its strides: the reference reads the same from such weights as
    # from C-ordered ones, and its host blocks refuse a strided array
    dprox = reg.reference("dprox")
    loss, params, training, n, rounds, chunk, uplink = _inputs(reg,
                                                               "tiny_lm")
    strided = jax.tree_util.tree_map(
        lambda x: np.moveaxis(np.ascontiguousarray(np.moveaxis(x, 0, -1)),
                              -1, 0) if x.ndim > 1 else x, params)
    assert not all(x.flags.c_contiguous
                   for x in jax.tree_util.tree_leaves(strided))
    want = dprox.run(loss, params, training, n, rounds, chunk)
    got = dprox.run(loss, strided, training, n, rounds, chunk)
    assert got == want
    with dprox.Host() as h, pytest.raises(ValueError, match="C-contiguous"):
        x = np.zeros((4, 6), np.float32).T
        dprox._prox(h, x, 0.1, x)
