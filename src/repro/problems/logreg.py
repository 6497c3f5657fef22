"""The paper's workload: l1-logistic regression on sparse Koh-Kim-Boyd
shards (Section III).  The default path is byte-identical to the
pre-registry code (``tests/test_api.py`` pins the literal residual/cost
trace).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.problems import base


class LogRegProblem(base.SparseRowsProblem):
    """l1-logistic regression on sparse Koh-Kim-Boyd shards (Section III):

        f_w(x) = sum_n log(1 + exp(-b_n <a_n, x>)),   h(z) = lam1 ||z||_1

    with margins by gather and the gradient by scatter-add over the
    gather-format (idx, vals, b) rows.  A zero-padded row has vals=0, so
    its gradient scatter is exactly 0; the mask zeroes its log(2) value
    term.  ``dtype`` float64 reproduces the paper's absolute tolerances;
    whether float32 reaches them at the paper's size is not measured."""

    def _loss_value_and_grad(self, shard, mask=None):
        idx, vals, b = shard
        d = self.n_features

        def vg(x):
            ax = jnp.sum(vals * x[idx], axis=-1)              # (N,)
            margins = -b * ax
            f = jnp.sum(base.masked(
                mask, jnp.logaddexp(jnp.zeros((), x.dtype), margins)))
            coef = base.masked(mask, -b * jax.nn.sigmoid(margins))  # (N,)
            contrib = (coef[:, None] * vals).reshape(-1)
            grad = jnp.zeros((d,), x.dtype).at[idx.reshape(-1)].add(contrib)
            return f, grad
        return vg

    # -- fused-kernel path (SchedulerConfig(kernel="pallas")) ---------------
    def _masked_kernel_loss_value_and_grad(self, shard, mask):
        from repro.kernels import ops
        A, b = shard

        def vg(x):
            return ops.fused_logistic_vjp(A, b, x, mask=mask)
        return vg


@base.register("logreg")
def make_logreg(n_samples: int = 2048, n_features: int = 128,
                density: float = 0.05, lam1: float = 0.3, seed: int = 0,
                fista=None, fixed_inner: Optional[int] = None,
                dtype="float32") -> LogRegProblem:
    """Factory for the registry.  The defaults are the repo's canonical
    reduced instance (the one ``tests/test_api.py`` anchors byte-for-byte
    against the pre-registry scheduler) — pass the paper's full sizes
    (n_samples=600_000, n_features=10_000, density=0.001, lam1=1.0) for
    the real thing.  ``fista`` accepts a kwargs dict so ExperimentSpecs
    stay JSON-declarative; its default matches the anchored instance
    (min_iters=1, eps_grad=1e-3) — pass ``fista={}`` for plain
    FistaOptions()."""
    from repro.configs.logreg_paper import scaled
    if fista is None:
        fista = dict(min_iters=1, eps_grad=1e-3)
    cfg = scaled(n_samples, n_features, density=density, lam1=lam1,
                 seed=seed)
    return LogRegProblem(cfg, fista=base.as_fista_options(fista),
                         fixed_inner=fixed_inner, dtype=jnp.dtype(dtype))
