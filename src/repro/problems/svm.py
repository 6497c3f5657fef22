"""Sparse linear SVM via the smoothed (Huberized) hinge loss.

Global problem over the Koh-Kim-Boyd sparse shards (the SAME deterministic
generator as the paper's logreg workload — ±1 labels, density-sparse
rows — so the two workloads are directly comparable on identical data):

    min_x  sum_n  l_gamma(b_n <a_n, x>)  +  lam1 ||x||_1

with the quadratically-smoothed hinge (Rennie & Srebro '05)

    l_gamma(m) = 0                      m >= 1
               = (1 - m)^2 / (2 gamma)  1 - gamma < m < 1
               = 1 - m - gamma/2        m <= 1 - gamma

Smoothing keeps the worker subproblem FISTA-solvable (the plain hinge is
non-smooth and the repo's local solver needs gradients); gamma -> 0
recovers the hinge.  The l1 master prox makes it a *sparse* SVM — the
same h as logreg/lasso but a piecewise-quadratic margin loss, which
exercises a different curvature profile in the subsolver (flat regions
stall plain gradient steps; FISTA's momentum + backtracking handle it).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.logreg_paper import scaled
from repro.problems import base


class SVMProblem(base.SparseRowsProblem):
    """See module docstring.  h(z) = lam1 ||z||_1 at the master."""

    def __init__(self, n_samples: int = 1536, n_features: int = 96, *,
                 density: float = 0.05, lam1: float = 0.05,
                 smoothing: float = 0.5, seed: int = 0, fista=None,
                 fixed_inner=None, dtype="float32"):
        super().__init__(scaled(n_samples, n_features, density=density,
                                lam1=float(lam1), seed=seed),
                         fista=fista, fixed_inner=fixed_inner, dtype=dtype)
        self.smoothing = float(smoothing)

    def _loss_value_and_grad(self, shard, mask=None):
        # a padded row (vals=0, b=0) sits at margin 0 inside the hinge's
        # linear branch: the mask zeroes its value term, and its gradient
        # scatter is already exactly 0 (vals=0)
        idx, vals, b = shard
        gamma = self.smoothing
        d = self.n_features

        def vg(x):
            m = b * jnp.sum(vals * x[idx], axis=-1)          # margins (N,)
            one = jnp.asarray(1.0, x.dtype)
            val = jnp.where(
                m >= one, 0.0,
                jnp.where(m <= one - gamma,
                          one - m - gamma / 2,
                          (one - m) ** 2 / (2 * gamma)))
            dldm = jnp.where(
                m >= one, 0.0,
                jnp.where(m <= one - gamma, -one, -(one - m) / gamma))
            coef = base.masked(mask, dldm) * b               # (N,)
            contrib = (coef[:, None] * vals).reshape(-1)
            grad = jnp.zeros((d,), x.dtype).at[idx.reshape(-1)].add(contrib)
            return jnp.sum(base.masked(mask, val)), grad
        return vg

    # -- fused-kernel path (SchedulerConfig(kernel="pallas")) ---------------
    def _masked_kernel_loss_value_and_grad(self, shard, mask):
        from repro.kernels import ops
        A, b = shard
        gamma = self.smoothing

        def vg(x):
            return ops.fused_svm_vjp(A, b, x, gamma=gamma, mask=mask)
        return vg


base.register("svm", SVMProblem)
