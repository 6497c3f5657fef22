"""ADMMConsensusTrainer — the paper's technique as an LM training feature.

Global-variable-consensus ADMM (Eqs. 5-7) applied to neural-network
training: every data-parallel "worker" (the paper's Lambda function; here a
column of the mesh, or a whole pod) keeps its own parameter copy ``x^w`` and
scaled dual ``u^w``, runs ``K_w`` local optimizer steps on the augmented
Lagrangian

    L_w(x) = loss(x; batch_w) + rho/2 * ||x - (z - u^w)||^2,

and the consensus step averages ``omega = x + u`` across workers — ONE
all-reduce per ADMM round instead of one gradient all-reduce per step.
That communication pattern is exactly why the algorithm was viable over
Lambda's slow star links, and why it is attractive across pod-level DCN
links (DESIGN.md §4, §6).

Implementation notes:
 * worker states are *stacked* on a leading axis W mapped onto the mesh's
   data axes (``worker_axes``) — the consensus ``jnp.mean`` over that axis
   lowers to the ICI/DCN all-reduce that replaces the paper's ZMQ master
   tree.  For archs whose full per-worker state exceeds one worker's HBM
   (mixtral-8x7b, llama-3.2-vision-90b at W=16), ``worker_axes=("pod",)``
   makes each *pod* one worker and FSDP-shards the worker state inside the
   pod — the paper's "worker" maps to a resource bundle, not a chip.
 * the local solver is Adam on the augmented loss (the paper's FISTA is the
   convex special case — see repro.problems.logreg for the faithful form).
   Moments persist across rounds (local-SGD practice; noted in DESIGN.md).
 * the z-update applies the prox of the regularizer h: "l1" gives
   sparsity-inducing consensus (the paper's workload), "l2sq" weight-decay
   -like shrinkage, "none" plain averaging (local-SGD/FedAvg as a special
   case of rho -> inf alternation).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import prox as prox_mod
from repro.core.admm import new_penalty, AdmmOptions
from repro.models import model as model_mod
from repro.optim import optimizers as opt_mod

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    n_workers: int = 16
    local_steps: int = 4                  # K_w
    rho0: float = 0.01
    prox: str = "none"                    # "l1" | "l2sq" | "none"
    lam: float = 1e-4                     # regularizer weight for h
    # penalty adaptation (Boyd §3.4.1)
    adapt_rho: bool = True
    mu: float = 10.0
    tau: float = 2.0
    rho_min: float = 1e-4
    rho_max: float = 1e2
    optimizer: opt_mod.AdamWConfig = opt_mod.AdamWConfig(weight_decay=0.0)


class ConsensusState(NamedTuple):
    x: Pytree          # stacked (W, ...) worker primal copies
    u: Pytree          # stacked (W, ...) scaled duals (f32)
    z: Pytree          # global consensus params (unstacked)
    opt: Pytree        # stacked Adam state over x
    rho: jnp.ndarray
    r_norm: jnp.ndarray
    s_norm: jnp.ndarray
    round: jnp.ndarray


def _stack(tree: Pytree, w: int) -> Pytree:
    return jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (w,) + t.shape), tree)


def _zeros_f32(tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(lambda t: jnp.zeros(t.shape, jnp.float32), tree)


def init_state(key, cfg: ModelConfig, ccfg: ConsensusConfig) -> ConsensusState:
    z = model_mod.init_params(key, cfg)
    x = _stack(z, ccfg.n_workers)
    u = _zeros_f32(x)
    opt = opt_mod.adamw_init(x)
    return ConsensusState(
        x=x, u=u, z=z, opt=opt,
        rho=jnp.float32(ccfg.rho0),
        r_norm=jnp.float32(jnp.inf), s_norm=jnp.float32(jnp.inf),
        round=jnp.int32(0))


def _tree_sq_dist(a: Pytree, b: Pytree, *, axis0: bool) -> jnp.ndarray:
    """sum over all leaves/workers of ||a - b||^2 (b broadcast on axis 0)."""
    tot = jnp.float32(0.0)
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        d = la.astype(jnp.float32) - (lb.astype(jnp.float32)[None] if axis0 else
                                      lb.astype(jnp.float32))
        tot = tot + jnp.sum(d * d)
    return tot


def _prox_tree(kind: str, lam: float, tree: Pytree, t) -> Pytree:
    prox_fn = prox_mod.PROX_REGISTRY[kind][0]
    return jax.tree_util.tree_map(
        lambda v: prox_fn(v.astype(jnp.float32), t, lam).astype(v.dtype), tree)


def make_round_step(cfg: ModelConfig, ccfg: ConsensusConfig,
                    loss_fn: Optional[Callable] = None) -> Callable:
    """Build the jittable ADMM round: (state, batch) -> (state, metrics).

    ``batch`` leaves carry a leading worker axis (W, B_w, ...).  One call =
    one ADMM round = Algorithm 2 for all workers (vmapped) + Algorithm 1's
    master reduce and z-update.
    """
    if loss_fn is None:
        loss_fn = lambda p, b: model_mod.loss_fn(p, cfg, b)[0]

    def per_worker_loss(xw, bw):
        return loss_fn(xw, bw)

    def round_step(state: ConsensusState, batch: Pytree
                   ) -> Tuple[ConsensusState, dict]:
        W = ccfg.n_workers
        rho = state.rho

        # ---- Algorithm 2: dual ascent + local solve ----------------------
        # r_k = x_k - z_k ; u_{k+1} = u_k + r_k ; q = ||r_k||^2 (summed)
        q_sum = _tree_sq_dist(state.x, state.z, axis0=True)
        u_new = jax.tree_util.tree_map(
            lambda u, x, z: u + (x.astype(jnp.float32) - z.astype(jnp.float32)[None]),
            state.u, state.x, state.z)
        # center = z - u_{k+1}  (stacked)
        center = jax.tree_util.tree_map(
            lambda z, u: z.astype(jnp.float32)[None] - u, state.z, u_new)

        def aug_grad(xs, bs):
            """Per-worker grads of the augmented Lagrangian (vmapped)."""
            def one(xw, bw, cw):
                loss, g = jax.value_and_grad(per_worker_loss)(xw, bw)
                g = jax.tree_util.tree_map(
                    lambda gi, xi, ci: gi.astype(jnp.float32)
                    + rho * (xi.astype(jnp.float32) - ci),
                    g, xw, cw)
                return loss, g
            return jax.vmap(one)(xs, bs, center)

        def local_step(carry, _):
            xs, opt = carry
            loss, g = aug_grad(xs, batch)
            xs, opt, om = opt_mod.adamw_update(ccfg.optimizer, xs, g, opt)
            return (xs, opt), loss.mean()

        (x_new, opt_new), losses = jax.lax.scan(
            local_step, (state.x, state.opt), None, length=ccfg.local_steps)

        # ---- Algorithm 1: master reduce + z-update ------------------------
        # omega_bar = mean_w (x + u)   — THE consensus all-reduce
        omega_bar = jax.tree_util.tree_map(
            lambda x, u: jnp.mean(x.astype(jnp.float32) + u, axis=0),
            x_new, u_new)
        z_new = _prox_tree(ccfg.prox, ccfg.lam, omega_bar, 1.0 / (W * rho))
        z_new = jax.tree_util.tree_map(
            lambda zn, zo: zn.astype(zo.dtype), z_new, state.z)

        r_norm = jnp.sqrt(q_sum)
        s_norm = rho * jnp.sqrt(
            _tree_sq_dist(z_new, state.z, axis0=False) * W)
        if ccfg.adapt_rho:
            opts = AdmmOptions(mu=ccfg.mu, tau_inc=ccfg.tau, tau_dec=ccfg.tau)
            rho_new = jnp.clip(new_penalty(rho, r_norm, s_norm, opts),
                               ccfg.rho_min, ccfg.rho_max)
            # rescale scaled duals with the penalty (Boyd §3.4.1)
            u_new = jax.tree_util.tree_map(
                lambda u: u * (rho / rho_new), u_new)
        else:
            rho_new = rho

        new_state = ConsensusState(
            x=x_new, u=u_new, z=z_new, opt=opt_new, rho=rho_new,
            r_norm=r_norm, s_norm=s_norm, round=state.round + 1)
        metrics = {"loss": losses[-1], "r_norm": r_norm, "s_norm": s_norm,
                   "rho": rho_new}
        return new_state, metrics

    return round_step


# ---------------------------------------------------------------------------
# Conventional data-parallel step (the baseline the paper compares against:
# one gradient all-reduce per step)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SgdTrainConfig:
    optimizer: opt_mod.AdamWConfig = opt_mod.AdamWConfig()


def make_sgd_step(cfg: ModelConfig, tcfg: SgdTrainConfig = SgdTrainConfig()
                  ) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Batch is sharded over the data axes; GSPMD emits the per-step gradient
    all-reduce.  ZeRO-1 comes from the moment shardings (launch layer).
    """
    def step(params, opt_state, batch):
        def loss_of(p):
            return model_mod.loss_fn(p, cfg, batch)[0]
        loss, grads = jax.value_and_grad(loss_of)(params)
        params, opt_state, om = opt_mod.adamw_update(
            tcfg.optimizer, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **om}

    return step
