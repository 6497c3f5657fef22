"""The paper's workload: l1-logistic regression on sparse Koh-Kim-Boyd
shards (Section III), moved verbatim from ``runtime/scheduler.py`` —
the default path is byte-identical to the pre-registry code
(``tests/test_api.py`` pins the literal residual/cost trace).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fista import FistaOptions
from repro.problems import base


class LogRegProblem(base.BatchedShardProblem):
    """l1-logistic regression on sparse Koh-Kim-Boyd shards (Section III).

    The loop path below is verbatim pre-registry code; the batched path
    (``solve_all``, via ``base.BatchedShardProblem``) stacks the sparse
    (idx, vals, b) shards and runs every worker's FISTA in one vmapped
    call — ``_masked_loss_value_and_grad`` is the masked twin of
    ``data.logreg.sparse_logistic_value_and_grad`` (zero-padded rows have
    vals=0 so their gradient scatter is exactly 0; the mask zeroes their
    log(2) value contribution)."""

    def __init__(self, logreg_cfg, *, fista: FistaOptions = FistaOptions(),
                 fixed_inner: Optional[int] = None, dtype=jnp.float32):
        from repro.configs.logreg_paper import LogRegConfig  # noqa
        from repro.data import logreg as data_mod
        self.cfg = logreg_cfg
        self.fista = fista
        self.fixed_inner = fixed_inner
        self.dtype = dtype            # f64 reproduces the paper's absolute
                                      # tolerances; f32 hits a precision
                                      # floor near r ~ 1e-1 (EXPERIMENTS.md)
        self.n_features = logreg_cfg.n_features
        self._data = data_mod
        self._shard_cache: Dict[Tuple[int, int], Tuple] = {}
        self._solver_cache: Dict[Tuple[int, int], Callable] = {}

    def n_samples(self, wid: int, n_workers: int) -> int:
        lo, hi = self._data.shard_rows(self.cfg.n_samples, n_workers, wid)
        return hi - lo

    def _shard(self, wid: int, W: int):
        key = (wid, W)
        if key not in self._shard_cache:
            idx, vals, b = self._load_or_gen(wid, W)
            self._shard_cache[key] = (idx, vals.astype(self.dtype),
                                      b.astype(self.dtype))
        return self._shard_cache[key]

    def _load_or_gen(self, wid: int, W: int):
        """Disk-cache the generated shards (generation of the full paper
        instance costs ~3 min; reruns should not pay it again)."""
        import os
        import numpy as np
        c = self.cfg
        cache_dir = os.environ.get("REPRO_DATA_CACHE", "")
        if not cache_dir:
            return self._data.worker_shard_sparse(c, wid, W)
        os.makedirs(cache_dir, exist_ok=True)
        tag = (f"logreg_n{c.n_samples}_d{c.n_features}_p{c.density}"
               f"_s{c.seed}_w{wid}of{W}.npz")
        path = os.path.join(cache_dir, tag)
        if os.path.exists(path):
            with np.load(path) as z:
                return (jnp.asarray(z["idx"]), jnp.asarray(z["vals"]),
                        jnp.asarray(z["b"]))
        idx, vals, b = self._data.worker_shard_sparse(c, wid, W)
        np.savez(path, idx=np.asarray(idx), vals=np.asarray(vals),
                 b=np.asarray(b))
        return idx, vals, b

    def _solver(self, shard_shape: Tuple[int, int]) -> Callable:
        """One jitted FISTA per shard shape (rho etc. are traced args, so
        the adaptive penalty does NOT retrace)."""
        if shard_shape not in self._solver_cache:
            d = self.cfg.n_features
            fista_opts = self.fista
            fixed = self.fixed_inner

            @jax.jit
            def run(idx, vals, b, x0, z, u, rho):
                vg = self._data.sparse_logistic_value_and_grad(
                    idx, vals, b, d)
                x_new, k, _, _ = base.solve_augmented(
                    vg, x0, z - u, rho, fixed, fista_opts)
                return x_new, k

            self._solver_cache[shard_shape] = run
        return self._solver_cache[shard_shape]

    def solve(self, wid, n_workers, x0, z, u, rho):
        idx, vals, b = self._shard(wid, n_workers)
        run = self._solver(idx.shape)
        x_new, k = run(idx, vals, b, x0, z, u,
                       jnp.asarray(rho, self.dtype))
        return x_new, int(k)

    def _masked_loss_value_and_grad(self, shard, mask):
        idx, vals, b = shard
        d = self.cfg.n_features

        def vg(x):
            ax = jnp.sum(vals * x[idx], axis=-1)              # (N,)
            margins = -b * ax
            f = jnp.sum(mask * jnp.logaddexp(jnp.zeros((), x.dtype),
                                             margins))
            coef = mask * (-b * jax.nn.sigmoid(margins))      # (N,)
            contrib = (coef[:, None] * vals).reshape(-1)
            grad = jnp.zeros((d,), x.dtype).at[idx.reshape(-1)].add(contrib)
            return f, grad
        return vg

    # -- fused-kernel path (SchedulerConfig(kernel="pallas")) ---------------
    _kernel_batch_cache: Optional[Dict[int, Tuple]] = None

    def kernel_batch_shards(self, n_workers: int):
        """Dense twin of ``batch_shards``: the Pallas margin kernel
        streams dense MXU row tiles, so the sparse gather-format shards
        are scattered into the kernel's padded layout once per fleet size
        (``base.margin_kernel_batch``; cached, ``rescale()`` to a new W
        re-densifies from the stacked batch)."""
        if self._kernel_batch_cache is None:
            self._kernel_batch_cache = {}
        if n_workers not in self._kernel_batch_cache:
            self._kernel_batch_cache[n_workers] = base.margin_kernel_batch(
                *self.batch_shards(n_workers), self.cfg.n_features)
        return self._kernel_batch_cache[n_workers]

    def _masked_kernel_loss_value_and_grad(self, shard, mask):
        from repro.kernels import ops
        A, b = shard

        def vg(x):
            return ops.fused_logistic_vjp(A, b, x, mask=mask)
        return vg

    def prox_h(self, v, t):
        from repro.core import prox
        return prox.prox_l1(v, t, self.cfg.lam1)

    @property
    def h_l1_lam(self):
        """prox_h above is soft-thresholding at lam1*t — exposing lam1 lets
        the scheduler fuse the z-update (kernel="pallas")."""
        return self.cfg.lam1

    def objective(self, x, n_workers: int) -> float:
        """Full phi(x) for convergence reporting."""
        total = self.cfg.lam1 * float(jnp.sum(jnp.abs(x)))
        for w in range(n_workers):
            idx, vals, b = self._shard(w, n_workers)
            vg = self._data.sparse_logistic_value_and_grad(
                idx, vals, b, self.cfg.n_features)
            f, _ = vg(x)
            total += float(f)
        return total

    # -- conformance contract (tests/test_problems.py) ----------------------
    def h_value(self, z) -> float:
        return self.cfg.lam1 * float(jnp.sum(jnp.abs(z)))

    def local_value(self, wid: int, n_workers: int, x) -> float:
        idx, vals, b = self._shard(wid, n_workers)
        vg = self._data.sparse_logistic_value_and_grad(
            idx, vals, b, self.cfg.n_features)
        f, _ = vg(x)
        return float(f)


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
