"""Global-variable-consensus ADMM (Eqs. 5-7 / Algorithms 1-2 of the paper):
its options and the penalty rule.

The rounds themselves run in ``repro.runtime.scheduler``: the worker
bodies through the workload's ``solve`` / ``solve_all``, the master's
z-update, residuals and dual rescale in ``Scheduler._master_z_update``.

Notation: the paper's Algorithm 1 accumulates omega = mean_w(x^w + u^w) and
q = sum_w ||x^w - z||^2; the z-update is the prox of h at omega with penalty
W*rho (Boyd §7.1 consensus form).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core.fista import FistaOptions


@dataclasses.dataclass(frozen=True)
class AdmmOptions:
    rho0: float = 1.0
    max_iters: int = 100          # K
    eps_primal: float = 2e-2      # eps_r
    eps_dual: float = 2e-2        # eps_s
    # penalty adaptation (Boyd §3.4.1, the paper's rule)
    mu: float = 10.0
    tau_inc: float = 2.0
    tau_dec: float = 2.0
    fista: FistaOptions = FistaOptions()


def new_penalty(rho, r_norm, s_norm, opts: AdmmOptions):
    """Boyd §3.4.1 residual-balancing rule (the paper's new_penalty).

    NOTE for callers: when rho changes, every worker's SCALED dual must be
    rescaled, u <- u * (rho_old / rho_new) (Boyd §3.4.1) — u = y/rho, and
    it is y, not u, that is the persistent dual.  Skipping the rescale
    destabilizes ADMM exactly at the first penalty adaptation (observed on
    the paper's full-scale instance: clean convergence to k=38, then
    oscillation)."""
    grow = r_norm > opts.mu * s_norm
    shrink = s_norm > opts.mu * r_norm
    return jnp.where(grow, rho * opts.tau_inc,
                     jnp.where(shrink, rho / opts.tau_dec, rho))
