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
from repro.core import prox
from repro.data.logreg import worker_shard_sparse
from repro.problems import base


class SVMProblem(base.FistaShardProblem):
    """See module docstring.  h(z) = lam1 ||z||_1 at the master."""

    def __init__(self, n_samples: int = 1536, n_features: int = 96, *,
                 density: float = 0.05, lam1: float = 0.05,
                 smoothing: float = 0.5, seed: int = 0, fista=None,
                 fixed_inner=None, dtype="float32"):
        super().__init__(n_samples, n_features, seed=seed, fista=fista,
                         fixed_inner=fixed_inner, dtype=dtype)
        self.lam1 = float(lam1)
        self.smoothing = float(smoothing)
        # reuse the KKB generator's config record as its addressing scheme
        self._data_cfg = scaled(n_samples, n_features, density=density,
                                lam1=lam1, seed=seed)

    def _gen_shard(self, wid: int, n_workers: int):
        idx, vals, b = worker_shard_sparse(self._data_cfg, wid, n_workers)
        return idx, vals.astype(self.dtype), b.astype(self.dtype)

    def _loss_value_and_grad(self, shard):
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
            coef = dldm * b                                  # (N,)
            contrib = (coef[:, None] * vals).reshape(-1)
            grad = jnp.zeros((d,), x.dtype).at[idx.reshape(-1)].add(contrib)
            return jnp.sum(val), grad
        return vg

    def _masked_loss_value_and_grad(self, shard, mask):
        # batched-engine twin: padded rows (vals=0, b=0) sit at margin 0
        # inside the hinge's linear branch — the mask zeroes their value
        # term; their gradient scatter is already exactly 0 (vals=0)
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
            coef = mask * dldm * b                           # (N,)
            contrib = (coef[:, None] * vals).reshape(-1)
            grad = jnp.zeros((d,), x.dtype).at[idx.reshape(-1)].add(contrib)
            return jnp.sum(mask * val), grad
        return vg

    # -- fused-kernel path (SchedulerConfig(kernel="pallas")) ---------------
    _kernel_batch_cache = None

    def kernel_batch_shards(self, n_workers: int):
        """Dense twin of ``batch_shards`` for the Pallas margin kernel
        (same staging as logreg: sparse gather-format rows scattered to
        dense MXU tiles once per fleet size, cached per W)."""
        if self._kernel_batch_cache is None:
            self._kernel_batch_cache = {}
        if n_workers not in self._kernel_batch_cache:
            self._kernel_batch_cache[n_workers] = base.margin_kernel_batch(
                *self.batch_shards(n_workers), self.n_features)
        return self._kernel_batch_cache[n_workers]

    def _masked_kernel_loss_value_and_grad(self, shard, mask):
        from repro.kernels import ops
        A, b = shard
        gamma = self.smoothing

        def vg(x):
            return ops.fused_svm_vjp(A, b, x, gamma=gamma, mask=mask)
        return vg

    def prox_h(self, v, t):
        return prox.prox_l1(v, t, self.lam1)

    @property
    def h_l1_lam(self):
        return self.lam1

    def h_value(self, z) -> float:
        return self.lam1 * float(jnp.sum(jnp.abs(z)))


base.register("svm", SVMProblem)
