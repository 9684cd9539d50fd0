"""Algorithm 1 of Zhang, Hu & Johansson (2025):

    "Non-convex composite federated learning with heterogeneous data"

The algorithm solves   min_x  F(x) = (1/n) sum_i f_i(x) + g(x)   with

  * decoupled proximal evaluation / communication: each client keeps a
    *pre-proximal* model ``z_hat`` and a *post-proximal* model ``z``; only the
    pre-proximal model is communicated, so server averaging commutes with the
    (linear) gradient accumulation and the average gradient reaches the server
    undistorted;
  * ``tau`` local steps per communication round (one d-dim uplink vector per
    round per client);
  * a client-drift correction term ``c_i`` reconstructed locally from the
    broadcast pre-proximal global model -- no extra control-variate traffic
    (contrast Scaffold / Mime);
  * the (t+1)*eta proximal schedule during local updates (Section 2.2 item 4)
    which makes local iterates track centralized proximal GD.

Two equivalent implementations are provided:

  * :func:`make_round_fn` -- the compact form (Eq. 2): all clients stacked on
    a leading axis, local steps under ``lax.scan``, clients under ``vmap``.
    This is the production path: the client axis is sharded over the mesh
    'data'/'pod' axis and the server reduction lowers to a single all-reduce
    (the paper's one-vector-per-round communication pattern).
  * :func:`client_local_round` / :func:`server_update` /
    :func:`client_correction_update` -- the literal per-client protocol of
    Algorithm 1, used by the launcher's client/server message-passing driver
    and by the equivalence tests (tests/test_algorithm.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.prox import Regularizer
from repro.utils import tree as tu

Params = Any
Batch = Any
# grad_fn(params, batch) -> (loss, grads)
GradFn = Callable[[Params, Batch], tuple[jax.Array, Params]]


@dataclass(frozen=True)
class DProxConfig:
    """Hyper-parameters of Algorithm 1.

    Theorems 3.5/3.6 require  eta_tilde = eta*eta_g*tau <= 1/(10 L)  and
    eta_g >= max(1.5, sqrt(n/8)).  ``validate`` checks the latter; the former
    needs the (problem-dependent) smoothness constant L.
    """

    tau: int
    eta: float
    eta_g: float
    # "linear": the paper's (t+1)*eta prox parameter (Section 2.2 item 4);
    # "fixed": ablation using eta_tilde at every local step.
    prox_schedule: str = "linear"

    @property
    def eta_tilde(self) -> float:
        return self.eta * self.eta_g * self.tau

    def validate(self, n_clients: int) -> None:
        if self.tau < 1:
            raise ValueError(f"tau must be >= 1, got {self.tau}")
        lo = max(1.5, (n_clients / 8.0) ** 0.5)
        if self.eta_g < lo:
            import warnings

            warnings.warn(
                f"eta_g={self.eta_g} < max(1.5, sqrt(n/8))={lo:.3f}: outside "
                "the step-size regime of Theorems 3.5/3.6 (may still work "
                "empirically, as in the paper's hand-tuned experiments)."
            )


class DProxState(NamedTuple):
    """Server + per-client persistent state.

    ``x_bar`` is the *pre-proximal* global model (what the server broadcasts);
    the deployable global model is ``P_eta_tilde(x_bar)``.  ``c`` stacks the
    per-client correction terms on a leading client axis.
    """

    x_bar: Params
    c: Params  # leading axis n_clients
    round: jax.Array  # scalar int32


def init_state(params0: Params, n_clients: int) -> DProxState:
    """x_bar^1 = params0,  c_i^1 = 0 (Line 1 of Algorithm 1)."""
    return DProxState(
        x_bar=params0,
        c=tu.tree_broadcast_axis0(tu.tree_zeros_like(params0), n_clients),
        round=jnp.zeros((), jnp.int32),
    )


def global_params(reg: Regularizer, cfg: DProxConfig, state: DProxState) -> Params:
    """The post-proximal global model P_eta_tilde(x_bar) -- Algorithm 1 output."""
    return reg.prox(state.x_bar, cfg.eta_tilde)


def local_update_step(
    reg: Regularizer,
    eta: float,
    t: jax.Array,
    z_hat: Params,
    grads: Params,
    c: Params,
):
    """One local update (Lines 9-10): the paper's hot inner loop.

    z_hat_{t+1} = z_hat_t - eta * (grad + c)
    z_{t+1}     = P_{(t+1) eta}(z_hat_{t+1})

    A fused Pallas TPU kernel for the L1 case lives in
    ``repro.kernels.fused_prox`` (see ``ops.fused_local_update``); this is the
    pure-jnp reference path used on CPU and for non-L1 regularizers.
    """
    z_hat_next = jax.tree_util.tree_map(
        lambda zh, g, ci: zh - eta * (g.astype(zh.dtype) + ci), z_hat, grads, c
    )
    z_next = reg.prox(z_hat_next, (t + 1) * eta)
    return z_hat_next, z_next


def make_local_fn(
    cfg: DProxConfig,
    reg: Regularizer,
    grad_fn: GradFn,
    *,
    use_fused_kernel: bool = False,
    unroll: bool = False,
):
    """Client half of the compact-form round (Lines 5-12, clients stacked).

    Returns ``local_fn(state, batches) -> (msg, aux)`` where ``msg`` is the
    uplink message pytree -- the per-client *innovation*
    ``z_hat_tau - P(x_bar)`` (leading client axis), i.e. the accumulated
    local update relative to the broadcast reference both ends already know.
    This is the ONLY tensor that crosses the network and hence the only
    thing a :mod:`repro.comm` transport may compress; innovation encoding is
    what makes sparsifying/quantizing it meaningful (compressing the raw
    iterate would zero model coordinates).  ``aux`` holds client-resident
    values that never leave the client (the retained average gradient for
    the correction rebuild, per-client loss metrics) plus the per-client
    report-round tag ``aux["round"]`` -- the round this report was computed
    at, which the async engine backend reads to age buffered stale reports
    (:mod:`repro.sched`); the synchronous server half ignores it.
    """
    step_impl = local_update_step
    if use_fused_kernel:
        from repro.kernels import ops as kops

        step_impl = kops.fused_local_update_step

    def local_fn(state: DProxState, batches: Batch):
        # numpy batch leaves must become jnp before traced-index selection
        batches = jax.tree_util.tree_map(jnp.asarray, batches)
        n_clients = jax.tree_util.tree_leaves(batches)[0].shape[0]
        p = reg.prox(state.x_bar, cfg.eta_tilde)  # P_eta_tilde(x_bar^r), Line 5
        z_hat0 = tu.tree_broadcast_axis0(p, n_clients)
        z0 = z_hat0
        gsum0 = tu.tree_zeros_like(z_hat0)

        def per_client_grad(z_i, batch_i):
            return grad_fn(z_i, batch_i)

        def body(carry, t):
            z_hat, z, gsum, loss_sum = carry
            batch_t = jax.tree_util.tree_map(lambda x: x[:, t], batches)
            losses, grads = jax.vmap(per_client_grad)(z, batch_t)  # (n,)
            # keep the federated state arithmetic in the params dtype (the
            # microbatched grad path accumulates in fp32)
            grads = jax.tree_util.tree_map(
                lambda g, zh: g.astype(zh.dtype), grads, z_hat)
            if use_fused_kernel:
                z_hat_next, z_next = jax.vmap(
                    lambda zh, g, ci: step_impl(reg, cfg.eta, t, zh, g, ci)
                )(z_hat, grads, state.c)
            else:
                z_hat_next = jax.tree_util.tree_map(
                    lambda zh, g, ci: zh - cfg.eta * (g + ci),
                    z_hat,
                    grads,
                    state.c,
                )
                prox_param = ((t + 1) * cfg.eta if cfg.prox_schedule == "linear"
                              else cfg.eta_tilde)
                z_next = reg.prox(z_hat_next, prox_param)
            return (
                z_hat_next,
                z_next,
                tu.tree_add(gsum, grads),
                loss_sum + losses.astype(jnp.float32),
            ), None

        (z_hat_tau, _, gsum, loss_sum), _ = jax.lax.scan(
            body,
            (z_hat0, z0, gsum0, jnp.zeros((n_clients,), jnp.float32)),
            jnp.arange(cfg.tau),
            unroll=True if unroll else 1,
        )
        msg = jax.tree_util.tree_map(
            lambda zh, pp: zh - pp[None], z_hat_tau, p)
        aux = {
            "avg_grad": tu.tree_scale(gsum, 1.0 / cfg.tau),  # (n, ...)
            "loss_sum": loss_sum,  # (n,) per-client tau-summed mean loss
            "round": jnp.broadcast_to(state.round, (n_clients,)),
        }
        return msg, aux

    return local_fn


def make_server_fn(cfg: DProxConfig, reg: Regularizer):
    """Server half (Lines 14-15) plus the local correction rebuild (Line 18).

    ``server_fn(state, msg, aux, active=None) -> (state, metrics)``.  ``msg``
    is whatever arrived on the uplink (possibly transport-compressed
    innovations ``z_hat_tau - P(x_bar)``); the downlink is the new ``x_bar``
    carried in the returned state.  The correction update uses only
    broadcast values and the client-resident ``aux`` -- it stays exact under
    uplink compression.
    """

    def server_fn(state: DProxState, msg, aux, active=None):
        """``active``: optional (n_clients,) bool mask -- PARTIAL CLIENT
        PARTICIPATION (beyond-paper extension; see DESIGN.md section 8).
        Participating clients run the round with their (possibly stale)
        correction terms, the server averages over participants only, and
        non-participants keep their state.  The exact mean-zero correction
        invariant holds only in expectation under uniform sampling; the
        benchmark/test quantify the induced residual."""
        delta = msg  # per-client innovations z_hat_tau - P(x_bar)
        p = reg.prox(state.x_bar, cfg.eta_tilde)

        # --- Server (Lines 14-15): the ONLY communication of the round.
        # mean over the client axis == all-reduce of one d-dim vector/client;
        # x_bar update in innovation form:  x_bar+ = P + eta_g mean_i delta_i
        # == P + eta_g (mean_i z_hat_i - P), Line 14.
        if active is None:
            mean_delta = tu.tree_mean_over_axis0(delta)
        else:
            w = active.astype(jnp.float32)
            denom = jnp.maximum(jnp.sum(w), 1.0)

            def _wmean(z):
                wb = w.reshape((-1,) + (1,) * (z.ndim - 1)).astype(z.dtype)
                return jnp.sum(z * wb, axis=0) / denom.astype(z.dtype)

            mean_delta = jax.tree_util.tree_map(_wmean, delta)
        x_bar_next = jax.tree_util.tree_map(
            lambda pp, md: pp + cfg.eta_g * md, p, mean_delta
        )

        # --- Client correction update (Line 18), reconstructed locally from
        # the broadcast x_bar^{r+1}; no extra communication.
        scale = 1.0 / (cfg.eta_g * cfg.eta * cfg.tau)
        c_next = jax.tree_util.tree_map(
            lambda pp, xn, ag: scale * (pp - xn)[None] - ag,
            p,
            x_bar_next,
            aux["avg_grad"],
        )
        if active is not None:
            # non-participants keep their stale correction terms
            c_next = jax.tree_util.tree_map(
                lambda new, old: jnp.where(
                    active.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                c_next, state.c)

        metrics = {
            "train_loss": jnp.mean(aux["loss_sum"]) / cfg.tau,
            # drift is shift-invariant: spread of the innovations == spread
            # of the raw iterates around their mean
            "drift": tu.tree_norm(
                jax.tree_util.tree_map(
                    lambda dl, md: dl - md[None], delta, mean_delta
                )
            ),
        }
        new_state = DProxState(
            x_bar=x_bar_next, c=c_next, round=state.round + 1
        )
        return new_state, metrics

    return server_fn


def make_round_fn(
    cfg: DProxConfig,
    reg: Regularizer,
    grad_fn: GradFn,
    *,
    use_fused_kernel: bool = False,
    unroll: bool = False,
):
    """Build the compact-form round function (Eq. 2).

    Returns ``round_fn(state, batches) -> (state, metrics)`` where ``batches``
    is a pytree whose leaves have leading dims ``(n_clients, tau, ...)``.

    Since the comm refactor this is literally the composition of
    :func:`make_local_fn` and :func:`make_server_fn` with a dense (identity)
    uplink -- the round's communication is the ``msg`` pytree flowing between
    the two halves.  The function stays jit/pjit friendly: the client axis
    can be sharded over the mesh and the only cross-client collective is the
    mean over ``z_hat_tau`` (plus loss metrics), matching the paper's single
    d-dimensional uplink/downlink per round.
    """
    local_fn = make_local_fn(cfg, reg, grad_fn,
                             use_fused_kernel=use_fused_kernel, unroll=unroll)
    server_fn = make_server_fn(cfg, reg)

    def round_fn(state: DProxState, batches: Batch, active=None):
        msg, aux = local_fn(state, batches)
        return server_fn(state, msg, aux, active=active)

    return round_fn


# ---------------------------------------------------------------------------
# Literal per-client protocol (Algorithm 1 as message passing).  Used by the
# launcher's client/server driver and the equivalence tests.
# ---------------------------------------------------------------------------


def client_local_round(
    cfg: DProxConfig,
    reg: Regularizer,
    grad_fn: GradFn,
    x_bar: Params,
    c_i: Params,
    batches_i: Batch,
):
    """Lines 5-12 for a single client.

    ``batches_i`` leaves have leading dim ``tau``.  Returns the uplink message
    ``z_hat_tau`` (the ONLY thing sent to the server) and the locally retained
    average stochastic gradient used later in the correction update.
    """
    p = reg.prox(x_bar, cfg.eta_tilde)
    z_hat, z = p, p
    gsum = tu.tree_zeros_like(p)
    for t in range(cfg.tau):
        batch_t = jax.tree_util.tree_map(lambda x: x[t], batches_i)
        _, grads = grad_fn(z, batch_t)
        z_hat, z = local_update_step(reg, cfg.eta, jnp.int32(t), z_hat, grads, c_i)
        gsum = tu.tree_add(gsum, grads)
    avg_grad_i = tu.tree_scale(gsum, 1.0 / cfg.tau)
    return z_hat, avg_grad_i


def server_update(
    cfg: DProxConfig, reg: Regularizer, x_bar: Params, z_hat_msgs: list[Params]
) -> Params:
    """Line 14: x_bar^{r+1} = P(x_bar) + eta_g (mean_i z_hat_i - P(x_bar))."""
    p = reg.prox(x_bar, cfg.eta_tilde)
    mean_z_hat = tu.tree_scale(
        jax.tree_util.tree_map(lambda *xs: sum(xs), *z_hat_msgs),
        1.0 / len(z_hat_msgs),
    )
    return jax.tree_util.tree_map(
        lambda pp, mz: pp + cfg.eta_g * (mz - pp), p, mean_z_hat
    )


def client_correction_update(
    cfg: DProxConfig,
    reg: Regularizer,
    x_bar_prev: Params,
    x_bar_next: Params,
    avg_grad_i: Params,
) -> Params:
    """Line 18: rebuild c_i^{r+1} from the broadcast pre-proximal model."""
    p = reg.prox(x_bar_prev, cfg.eta_tilde)
    scale = 1.0 / (cfg.eta_g * cfg.eta * cfg.tau)
    return jax.tree_util.tree_map(
        lambda pp, xn, ag: scale * (pp - xn) - ag, p, x_bar_next, avg_grad_i
    )


def run_per_client_round(
    cfg: DProxConfig,
    reg: Regularizer,
    grad_fn: GradFn,
    state: DProxState,
    batches: Batch,
) -> DProxState:
    """One full round via the literal protocol (Python loop over clients)."""
    n_clients = jax.tree_util.tree_leaves(batches)[0].shape[0]
    msgs, avg_grads = [], []
    for i in range(n_clients):
        batches_i = jax.tree_util.tree_map(lambda x: x[i], batches)
        c_i = tu.tree_index_axis0(state.c, i)
        z_hat_i, ag_i = client_local_round(cfg, reg, grad_fn, state.x_bar, c_i, batches_i)
        msgs.append(z_hat_i)
        avg_grads.append(ag_i)
    x_bar_next = server_update(cfg, reg, state.x_bar, msgs)
    cs = [
        client_correction_update(cfg, reg, state.x_bar, x_bar_next, ag)
        for ag in avg_grads
    ]
    return DProxState(
        x_bar=x_bar_next,
        c=tu.tree_stack_axis0(cs),
        round=state.round + 1,
    )
