"""Distributed Lasso: least squares + l1, the closed-form-friendly workload.

Global problem over the row-partitioned data (A, b):

    min_x  sum_w 0.5 ||A_w x - b_w||^2  +  lam1 ||x||_1

Each worker's augmented subproblem is an unconstrained QUADRATIC, so the
Algorithm-2 body has a direct solve:

    x = (A_w^T A_w + rho I)^{-1} (A_w^T b_w + rho (z - u))

The worker factors its d x d Gram matrix ONCE (eigendecomposition, cached
per (wid, W)) and every subsequent round — under any rho the adaptive
penalty picks — is two O(d^2) matvecs.  ``inner_iters`` is therefore 1:
the timing model sees a direct solver, a deliberately different
prox/solve structure from the FISTA workloads (``direct=False`` falls
back to the shared FISTA path for comparison).

Data (pure function of (seed, global row index), like every workload):
rows a_i ~ N(0, I_d); a shared ``density``-sparse ground truth x_true
(values ~ N(0,1) on a uniform index subset); b_i = <a_i, x_true> + noise.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prox
from repro.data.logreg import shard_rows
from repro.problems import base


@jax.jit
def _lasso_direct_all(evals, evecs, Atb, z, us, rho):
    """Every worker's closed-form solve from its cached eigendecomposition:
    x_w = V_w (V_w^T rhs_w) / (lam_w + rho), one device call."""
    rhs = Atb + rho * (z[None, :] - us)                   # (W, d)
    proj = jnp.einsum("wdk,wd->wk", evecs, rhs)           # V^T rhs
    return jnp.einsum("wdk,wk->wd", evecs, proj / (evals + rho))


class LassoProblem(base.FistaShardProblem):
    """See module docstring.  h(z) = lam1 ||z||_1 at the master."""

    def __init__(self, n_samples: int = 1536, n_features: int = 96, *,
                 density: float = 0.1, noise: float = 0.02,
                 lam1: float = 0.1, seed: int = 0, direct: bool = True,
                 fista=None, fixed_inner=None, dtype="float32"):
        super().__init__(n_samples, n_features, seed=seed, fista=fista,
                         fixed_inner=fixed_inner, dtype=dtype)
        self.density = float(density)
        self.noise = float(noise)
        self.lam1 = float(lam1)
        self.direct = bool(direct)
        self._factor_cache: Dict[Tuple[int, int], Tuple] = {}

    def x_true(self) -> jnp.ndarray:
        """The shared sparse ground truth (off-row PRNG stream)."""
        k_idx, k_val = jax.random.split(self._aux_key(0))
        d = self.n_features
        nnz = max(1, round(self.density * d))
        u = jax.random.uniform(k_idx, (d,), dtype=jnp.float32)
        _, idx = jax.lax.top_k(u, nnz)       # uniform nnz-subset, no repl.
        vals = jax.random.normal(k_val, (nnz,), jnp.float32)
        return jnp.zeros((d,), jnp.float32).at[idx].set(vals)

    def _gen_shard(self, wid: int, n_workers: int):
        lo, hi = shard_rows(self.total_samples, n_workers, wid)
        d = self.n_features

        def row(key):
            ka, kn = jax.random.split(key)
            a = jax.random.normal(ka, (d,), jnp.float32)
            eps = jax.random.normal(kn, (), jnp.float32)
            return a, eps

        A, eps = jax.vmap(row)(self._row_keys(lo, hi))
        b = A @ self.x_true() + self.noise * eps
        return A.astype(self.dtype), b.astype(self.dtype)

    def _loss_value_and_grad(self, shard, mask=None):
        # zero-padded rows already have r = 0; the mask keeps the
        # contract explicit (and exact for any padding convention)
        A, b = shard

        def vg(x):
            r = base.masked(mask, A @ x - b)
            return 0.5 * jnp.vdot(r, r), A.T @ r
        return vg

    def _factor(self, wid: int, n_workers: int):
        key = (wid, n_workers)
        if key not in self._factor_cache:
            A, b = self._shard(wid, n_workers)
            evals, evecs = jnp.linalg.eigh(A.T @ A)
            self._factor_cache[key] = (evals, evecs, A.T @ b)
        return self._factor_cache[key]

    def solve(self, wid, n_workers, x0, z, u, rho):
        if not self.direct:
            return super().solve(wid, n_workers, x0, z, u, rho)
        evals, evecs, Atb = self._factor(wid, n_workers)
        rho = jnp.asarray(rho, self.dtype)
        rhs = Atb + rho * (z - u)
        x_new = evecs @ ((evecs.T @ rhs) / (evals + rho))
        return x_new.astype(self.dtype), 1

    # -- batched engine: all W Gram factors stacked, one call per round ----
    def _batched_factor(self, n_workers: int):
        key = ("batch", n_workers)
        if key not in self._factor_cache:
            (A, b), _ = self.batch_shards(n_workers)   # pad rows are 0
            evals, evecs = jnp.linalg.eigh(
                jnp.einsum("wnd,wne->wde", A, A))      # batched eigh
            Atb = jnp.einsum("wnd,wn->wd", A, b)
            self._factor_cache[key] = (evals, evecs, Atb)
        return self._factor_cache[key]

    def solve_all(self, xs, us, z, rho, kernel: str = "xla"):
        # the direct path has no streaming loss to fuse — it is two dense
        # matvecs against a cached factorization — so kernel="pallas"
        # leaves the worker side untouched (the scheduler's fused
        # z-update still applies); direct=False routes the kwarg to the
        # shared FISTA engine
        if not self.direct:
            return super().solve_all(xs, us, z, rho, kernel=kernel)
        n_workers = int(xs.shape[0])
        evals, evecs, Atb = self._batched_factor(n_workers)
        x_new = _lasso_direct_all(evals, evecs, Atb, z, us,
                                  jnp.asarray(rho, self.dtype))
        return x_new.astype(self.dtype), np.ones(n_workers, np.int64)

    def prox_h(self, v, t):
        return prox.prox_l1(v, t, self.lam1)

    @property
    def h_l1_lam(self):
        return self.lam1

    def h_value(self, z) -> float:
        return self.lam1 * float(jnp.sum(jnp.abs(z)))


base.register("lasso", LassoProblem)
