"""Synthetic l1-logistic-regression data, per Koh-Kim-Boyd (JMLR'07) /
Section III of the paper.

The paper's workers "fetch a batch of data samples ... or generate the
problem data from its closed-form formulation" — the scheduler never holds
data.  We keep that property: ``worker_shard(cfg, w, W)`` is a *pure
function of (seed, worker id)*, so any respawned or re-scaled worker can
deterministically regenerate exactly its shard (this is what makes elastic
rescale data-motion-free, DESIGN.md §2).

Generation (per sample n):
  * label b_n = ±1 with probability 1/2,
  * k = round(p*d) non-zero feature indices, uniform without replacement,
  * values ~ N(nu_n, 1) with nu_n ~ U[0,1] for b=+1, U[-1,0] for b=-1.

The matrix is returned *dense* (TPU adaptation: MXU is a dense systolic
array; see DESIGN.md §7) with rows zero except at the selected indices.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.logreg_paper import LogRegConfig


def shard_rows(n_samples: int, n_workers: int, w: int) -> Tuple[int, int]:
    """Row range [lo, hi) for worker w under near-even split."""
    base, rem = divmod(n_samples, n_workers)
    lo = w * base + min(w, rem)
    hi = lo + base + (1 if w < rem else 0)
    return lo, hi


def _gen_row_sparse(key, d: int, k: int):
    """One sample in sparse form: (idx (k,) i32, vals (k,) f32, b ±1 f32).

    All draws are pinned to f32 so the data stream is bit-identical whether
    or not the process enables x64 (the f64 solver path consumes the SAME
    dataset the f32 path does)."""
    kb, knu, kidx, kval = jax.random.split(key, 4)
    b = jnp.where(jax.random.bernoulli(kb, 0.5),
                  jnp.float32(1.0), jnp.float32(-1.0))
    nu = jax.random.uniform(knu, dtype=jnp.float32) * b   # U[0,1] or U[-1,0]
    # k distinct indices: top-k of iid uniforms is a uniform k-subset
    # without replacement
    u = jax.random.uniform(kidx, (d,), dtype=jnp.float32)
    _, idx = jax.lax.top_k(u, k)                          # (k,)
    vals = nu + jax.random.normal(kval, (k,), dtype=jnp.float32)
    return idx.astype(jnp.int32), vals.astype(jnp.float32), b


def _gen_row(key, d: int, k: int):
    """One sample: (a (d,) f32 dense with k nonzeros, b ±1 f32)."""
    idx, vals, b = _gen_row_sparse(key, d, k)
    a = jnp.zeros((d,), jnp.float32).at[idx].set(vals)
    return a, b


def _row_keys(cfg: LogRegConfig, lo: int, hi: int):
    base = jax.random.PRNGKey(cfg.seed)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(lo, hi))


def worker_shard(cfg: LogRegConfig, w: int, n_workers: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministically generate worker w's rows (dense A).

    Sample identity is tied to the *global row index* (the per-row fold_in
    below), not to the worker count — so re-sharding from W to W' workers
    partitions exactly the same global dataset.
    """
    lo, hi = shard_rows(cfg.n_samples, n_workers, w)
    d = cfg.n_features
    k = max(1, round(cfg.density * d))
    A, b = jax.vmap(lambda kk: _gen_row(kk, d, k))(_row_keys(cfg, lo, hi))
    return A, b


def worker_shard_sparse(cfg: LogRegConfig, w: int, n_workers: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Worker w's rows in sparse (idx, vals, b) form — the same samples as
    ``worker_shard`` (shared per-row keys), at k/d of the memory.  This is
    what lets the FULL paper instance (N=600 000, d=10 000, p=0.001) run on
    one host: 600k x 10 nonzeros ≈ 48 MB vs 24 GB dense."""
    lo, hi = shard_rows(cfg.n_samples, n_workers, w)
    d = cfg.n_features
    k = max(1, round(cfg.density * d))
    idx, vals, b = jax.vmap(lambda kk: _gen_row_sparse(kk, d, k))(
        _row_keys(cfg, lo, hi))
    return idx, vals, b
