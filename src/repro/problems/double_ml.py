"""Double Machine Learning (partially linear model) as a phase-structured
workload: K-fold cross-fitted nuisance regressions fan OUT, a tiny
sequential combine stage computes the debiased treatment effect.

The statistical model (Chernozhukov et al.'s partially linear regression,
the concrete serverless instance of *Distributed Double Machine Learning
with a Serverless Architecture*, PAPERS.md):

    Y = theta0 * D + g0(X) + eps        (outcome)
    D = m0(X) + v                       (treatment, confounded through X)

Naively regressing Y on D is biased by the confounding (m0 and g0 share
support here by construction).  DML removes it by cross-fitting: split
the n rows into K folds; for each fold k fit BOTH nuisances on the
complement (lasso regressions of Y on X and of D on X), predict them
out-of-fold, and solve the partialling-out score on the residuals:

    theta_hat = sum_i d~_i y~_i / sum_i d~_i^2,
    y~_i = Y_i - X_i beta_y^(fold i),   d~_i = D_i - X_i beta_d^(fold i)

That is 2K independent medium-size solves (the fan-out phase) feeding
one 1-dimensional least squares (the combine phase) — exactly the
per-phase-varying parallelism the cluster's DAG jobs model.

One registered factory, two roles:

* ``role="nuisance"`` (default) — lasso-style regression of ``target``
  ("y" or "d") on X over the COMPLEMENT of ``fold``.  A full
  ``FistaShardProblem``: wire messages are d-vectors, batched engine and
  fused l1 z-update supported.  Conformance-tested like every workload.
* ``role="combine"`` — the 1-dim residual least squares.  Implements
  ``consume_stage_results``: the cluster hands it the nuisance stages'
  ``StageResult``s at dispatch and it reads each fitted beta plus its
  (target, fold) coordinates from the stage's own spec.  Without inputs
  (standalone run) the betas stay zero and it computes the NAIVE biased
  estimate — useful as the bias baseline.

Every instance regenerates identical data from (seed, global row index)
— the row keys and the coefficient draws are keyed off the FULL n, not
the instance's own row subset, so all 2K+1 stage problems see one
consistent dataset with zero data motion between stages.

``double_ml_dag(...)`` builds the ready-to-submit ``DagSpec``.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prox
from repro.data.logreg import shard_rows
from repro.problems import base

ROLES = ("nuisance", "combine")
TARGETS = ("y", "d")


class DoubleMLProblem(base.FistaShardProblem):
    """See module docstring.  ``n_samples``/``n_features`` describe the
    FULL dataset (n rows, p covariates) for both roles; the wire
    dimension is p for nuisance stages and 1 for the combine stage."""

    def __init__(self, n_samples: int = 1024, n_features: int = 24, *,
                 role: str = "nuisance", target: str = "y", fold: int = 0,
                 n_folds: int = 4, theta: float = 1.5,
                 density: float = 0.25, confound: float = 0.6,
                 noise_d: float = 1.0, noise_y: float = 0.5,
                 lam1: float = 0.02, seed: int = 0, fista=None,
                 fixed_inner=None, dtype="float32"):
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        if target not in TARGETS:
            raise ValueError(f"target must be one of {TARGETS}, "
                             f"got {target!r}")
        if n_folds < 2:
            raise ValueError("n_folds must be >= 2 (cross-fitting)")
        if not 0 <= fold < n_folds:
            raise ValueError(f"fold must be in [0, {n_folds}), got {fold}")
        self.full_n = int(n_samples)
        self.p = int(n_features)
        self.role = role
        self.target = target
        self.fold = int(fold)
        self.n_folds = int(n_folds)
        self.theta = float(theta)
        self.density = float(density)
        self.confound = float(confound)
        self.noise_d = float(noise_d)
        self.noise_y = float(noise_y)
        self.lam1 = float(lam1)
        if role == "nuisance":
            # fold of row i is i % K; train on the complement of `fold`
            rows = np.array([i for i in range(self.full_n)
                             if i % self.n_folds != self.fold], np.int64)
            wire_d = self.p
        else:
            rows = np.arange(self.full_n, dtype=np.int64)
            wire_d = 1
        super().__init__(len(rows), wire_d, seed=seed, fista=fista,
                         fixed_inner=fixed_inner, dtype=dtype)
        self._rows = rows
        # out-of-fold nuisance coefficients, filled by
        # consume_stage_results (combine role); zeros = naive estimate
        self._beta = {t: np.zeros((self.n_folds, self.p), np.float64)
                      for t in TARGETS}
        self._coef_cache = None

    # -- the shared data model (pure function of seed + global row) --------

    def _dml_aux_key(self, tag: int):
        """Off-row draws keyed past the FULL n (NOT total_samples, which
        is role-dependent) so every stage instance agrees."""
        return jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                  self.full_n + tag)

    def _sparse_vec(self, key) -> jnp.ndarray:
        k_idx, k_val = jax.random.split(key)
        nnz = max(1, round(self.density * self.p))
        u = jax.random.uniform(k_idx, (self.p,), dtype=jnp.float32)
        _, idx = jax.lax.top_k(u, nnz)
        vals = jax.random.normal(k_val, (nnz,), jnp.float32)
        return jnp.zeros((self.p,), jnp.float32).at[idx].set(vals)

    def coefs(self):
        """(g0, m0): outcome and treatment coefficients.  m0 mixes g0's
        direction with an independent one, so D and g0(X) correlate —
        the confounding that biases the naive regression."""
        if self._coef_cache is None:
            g = self._sparse_vec(self._dml_aux_key(1))
            h = self._sparse_vec(self._dml_aux_key(2))
            m = self.confound * g + self.confound * h
            self._coef_cache = (g, m)
        return self._coef_cache

    def _gen_rows(self, idx: np.ndarray):
        """(X, D, Y) for the given GLOBAL row indices."""
        g, m = self.coefs()
        base_key = jax.random.PRNGKey(self.seed)
        keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(
            jnp.asarray(idx))

        def row(key):
            kx, kd, ky = jax.random.split(key, 3)
            x = jax.random.normal(kx, (self.p,), jnp.float32)
            v = jax.random.normal(kd, (), jnp.float32)
            e = jax.random.normal(ky, (), jnp.float32)
            return x, v, e

        X, V, E = jax.vmap(row)(keys)
        D = X @ m + self.noise_d * V
        Y = self.theta * D + X @ g + self.noise_y * E
        return X, D, Y

    # -- stage handoff (combine role) ---------------------------------------

    def consume_stage_results(self, inputs: Dict[str, object]):
        """Receive the nuisance stages' ``StageResult``s (cluster calls
        this at combine dispatch).  Each input's (target, fold) is read
        from its own spec's problem_kwargs — names don't matter."""
        if self.role != "combine":
            raise RuntimeError("only the combine role consumes stage "
                               "results")
        for name, sr in inputs.items():
            kw = dict(sr.result.spec.problem_kwargs)
            if kw.get("role", "nuisance") != "nuisance":
                continue
            target = kw.get("target", "y")
            fold = int(kw.get("fold", 0))
            if not 0 <= fold < self.n_folds:
                raise ValueError(f"stage {name!r}: fold {fold} out of "
                                 f"range for n_folds={self.n_folds}")
            beta = np.asarray(sr.z, np.float64)
            if beta.shape != (self.p,):
                raise ValueError(f"stage {name!r}: nuisance solution has "
                                 f"shape {beta.shape}, expected "
                                 f"({self.p},)")
            self._beta[target][fold] = beta
        # residuals changed: drop every cached shard/factor
        self._shard_cache.clear()
        self._batch_cache = None
        self._batched_solver_cache = None

    # -- shards -------------------------------------------------------------

    def _gen_shard(self, wid: int, n_workers: int):
        lo, hi = shard_rows(self.total_samples, n_workers, wid)
        idx = self._rows[lo:hi]
        X, D, Y = self._gen_rows(idx)
        if self.role == "nuisance":
            t = Y if self.target == "y" else D
            return X.astype(self.dtype), t.astype(self.dtype)
        folds = idx % self.n_folds
        by = jnp.asarray(self._beta["y"], jnp.float32)[folds]   # (m, p)
        bd = jnp.asarray(self._beta["d"], jnp.float32)[folds]
        y_t = Y - jnp.sum(X * by, axis=1)
        d_t = D - jnp.sum(X * bd, axis=1)
        return d_t.astype(self.dtype), y_t.astype(self.dtype)

    # -- losses -------------------------------------------------------------

    def _loss_value_and_grad(self, shard, mask=None):
        if self.role == "nuisance":
            A, b = shard

            def vg(x):
                r = base.masked(mask, A @ x - b)
                return 0.5 * jnp.vdot(r, r), A.T @ r
            return vg
        d_t, y_t = shard

        def vg(th):
            r = base.masked(mask, d_t * th[0] - y_t)
            return 0.5 * jnp.vdot(r, r), jnp.array([jnp.vdot(d_t, r)])
        return vg

    # -- master regularizer -------------------------------------------------

    def prox_h(self, v, t):
        if self.role == "nuisance":
            return prox.prox_l1(v, t, self.lam1)
        return v                         # h = 0 for the scalar theta

    @property
    def h_l1_lam(self) -> Optional[float]:
        return self.lam1 if self.role == "nuisance" else None

    def h_value(self, z) -> float:
        if self.role == "nuisance":
            return self.lam1 * float(jnp.sum(jnp.abs(z)))
        return 0.0

    # -- reporting helpers --------------------------------------------------

    def closed_form_theta(self) -> float:
        """The exact partialling-out estimate under the CURRENT betas
        (zeros until consume_stage_results): sum d~ y~ / sum d~^2 over
        all n rows.  What the combine stage's ADMM converges to."""
        if self.role != "combine":
            raise RuntimeError("combine role only")
        num = den = 0.0
        for w in range(4):               # stream in 4 chunks
            d_t, y_t = self._gen_shard(w, 4)
            num += float(jnp.vdot(d_t, y_t))
            den += float(jnp.vdot(d_t, d_t))
        return num / den


def double_ml_dag(*, n_samples: int = 1024, n_features: int = 24,
                  n_folds: int = 4, theta: float = 1.5,
                  density: float = 0.25, confound: float = 0.6,
                  noise_d: float = 1.0, noise_y: float = 0.5,
                  lam1: float = 0.02, seed: int = 0,
                  nuisance_workers: int = 2, combine_workers: int = 1,
                  nuisance_rounds: int = 5, combine_rounds: int = 4,
                  pool_seed: int = 0, warm_provider: bool = False,
                  label: str = "double_ml"):
    """Build the ready-to-submit ``DagSpec``: 2K nuisance stages (both
    targets x K folds, ``nuisance_workers`` each) fanning into one
    ``combine`` stage.  Submit with ``api.submit_dag``; the estimate is
    ``run.stage_results["combine"].z[0]`` after ``run_all()``.

    ``warm_provider=True`` backs every stage's pool with the keep-alive
    provider so a cluster with ``share_provider=True`` can warm-start
    later stages on the fan-out's retired sandboxes."""
    from repro.api import ExperimentSpec                 # lazy: no cycle
    from repro.runtime.cluster import DagSpec, StageSpec
    from repro.runtime.pool import PoolConfig, ProviderConfig
    from repro.runtime.scheduler import SchedulerConfig

    common = dict(n_samples=n_samples, n_features=n_features,
                  n_folds=n_folds, theta=theta, density=density,
                  confound=confound, noise_d=noise_d, noise_y=noise_y,
                  lam1=lam1, seed=seed)

    def pool():
        if warm_provider:
            return PoolConfig(seed=pool_seed,
                              provider=ProviderConfig(enabled=True))
        return PoolConfig(seed=pool_seed)

    stages = []
    for k in range(n_folds):
        for tgt in TARGETS:
            stages.append(StageSpec(
                name=f"nuis_{tgt}{k}",
                spec=ExperimentSpec(
                    problem="double_ml",
                    problem_kwargs={**common, "role": "nuisance",
                                    "target": tgt, "fold": k},
                    scheduler=SchedulerConfig(
                        n_workers=nuisance_workers, replication=1,
                        pool=pool()),
                    max_rounds=nuisance_rounds,
                    label=f"{label}/nuis_{tgt}{k}")))
    stages.append(StageSpec(
        name="combine",
        spec=ExperimentSpec(
            problem="double_ml",
            problem_kwargs={**common, "role": "combine"},
            scheduler=SchedulerConfig(
                n_workers=combine_workers, replication=1,
                pool=pool()),
            max_rounds=combine_rounds,
            label=f"{label}/combine"),
        after=tuple(s.name for s in stages)))
    return DagSpec(stages=tuple(stages), label=label)


base.register("double_ml", DoubleMLProblem)
