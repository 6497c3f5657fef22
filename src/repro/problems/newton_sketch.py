"""OverSketched Newton: the second-order workload family (PAPERS.md,
Gupta et al. 2019).

Where the four first-order workloads send a FISTA shard solve to each
worker, ``newton_sketch`` sends a **Hessian sketch block**: task w
computes its blocks of the over-provisioned blocked sketch
(``core/sketch.py``) of the weighted data matrix ``A' = D(z)^{1/2} A``
plus its per-block gradient shard, and ships the coded combination
``m_w = Σ_k B[w,k]·[g_k | vec((S_k A')ᵀ(S_k A'))]`` — one flat vector of
``d + d²`` floats.  The master decodes the EXACT full-sketch Gram and
full gradient from any ``n_tasks - redundancy`` responses and takes a
globalized Newton step (sketched-Hessian solve + Armijo backtracking on
the true l2-regularized logistic objective).  Sketch redundancy replaces
FRS physical replication as the straggler defense: under the
``replicated`` barrier every worker does useful work and the decoded
Hessian is deterministic (subset-independent); under ``drop_slowest``
the uncoded ignore-extra-blocks estimate is used instead.

The objective is  f(z) = Σ_i log(1 + exp(-b_i·aᵢᵀz)) + (lam2/2)·‖z‖² —
the SAME data rows as the ``logreg`` workload (shared per-row PRNG keys
in ``data/logreg.py``), so the ``logreg_l2`` ADMM twin registered below
solves literally the same problem for the head-to-head benchmark
(``benchmarks/bench_newton.py``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketch import BlockSketch
from repro.data import logreg as data_mod
from repro.data.logreg import shard_rows
from repro.problems import base
from repro.problems.logreg import LogRegProblem


class NewtonSketchProblem:
    """Second-order worker problem: per-round task = coded Hessian-sketch
    block.  ``second_order = True`` routes the scheduler through
    ``run_round_newton`` (round messages up, Newton step at the master)
    instead of the ADMM x/z/u machinery."""

    second_order = True

    def __init__(self, logreg_cfg, *, lam2: float = 1e-3,
                 sketch: str = "count", sketch_dim: Optional[int] = None,
                 redundancy: int = 1, coded: bool = True,
                 scheme: str = "auto", line_search_max: int = 20,
                 dtype=jnp.float32):
        self.cfg = logreg_cfg
        self.lam2 = float(lam2)
        self.sketch = sketch
        self.sketch_dim = int(sketch_dim if sketch_dim is not None
                              else 8 * logreg_cfg.n_features)
        self.redundancy = int(redundancy)
        self.coded = bool(coded)
        self.scheme = scheme
        self.ls_max = int(line_search_max)
        self.dtype = dtype
        self.n_features = logreg_cfg.n_features
        self._Ab: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
        self._Ab64: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._plans: Dict[int, BlockSketch] = {}
        self._round_fns: Dict[int, callable] = {}
        self._round_cache: Optional[Tuple] = None    # (key, msgs, iters)

    # -- data ---------------------------------------------------------------
    def _data(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The full dense (A, b) — one generation, same global samples as
        the sparse ``logreg`` shards (shared per-row keys)."""
        if self._Ab is None:
            A, b = data_mod.worker_shard(self.cfg, 0, 1)
            self._Ab = (jnp.asarray(A, self.dtype),
                        jnp.asarray(b, self.dtype))
        return self._Ab

    def _plan(self, n_workers: int) -> BlockSketch:
        if n_workers not in self._plans:
            self._plans[n_workers] = BlockSketch(
                self.cfg.n_samples, n_workers, sketch_dim=self.sketch_dim,
                redundancy=min(self.redundancy, n_workers - 1),
                method=self.sketch, coded=self.coded, scheme=self.scheme,
                seed=self.cfg.seed + 1)
        return self._plans[n_workers]

    # -- scheduler contract -------------------------------------------------
    @property
    def message_floats(self) -> int:
        """Uplink floats per task: gradient shard (d) + vec Gram (d²)."""
        return self.n_features + self.n_features ** 2

    def n_samples(self, wid: int, n_workers: int) -> int:
        """Rows streamed per sketch pass (every block touches the full
        matrix — count-sketch/SRHT mix all rows); block multiplicity is
        modeled in the returned inner-iteration count instead."""
        return self.cfg.n_samples

    def task_iters(self, n_workers: int) -> int:
        """Deterministic per-task cost in row-pass equivalents: r = s+1
        sketch passes when coded (1 uncoded), each pass one stream over
        the N rows plus the block Gram (≈ block_rows·d row-equivalents)."""
        plan = self._plan(n_workers)
        per_block = 1.0 + plan.block_rows * self.n_features / max(
            self.cfg.n_samples, 1)
        return max(1, int(round(plan.blocks_per_task() * per_block)))

    # -- worker rounds ------------------------------------------------------
    def _row_blocks(self, n_workers: int) -> np.ndarray:
        """Gradient-shard partition: row i belongs to block k iff i is in
        ``shard_rows(N, W, k)`` — the same near-even split the first-order
        workloads use, here protected by the same code as the Gram."""
        N = self.cfg.n_samples
        out = np.zeros(N, np.int32)
        for k in range(n_workers):
            lo, hi = shard_rows(N, n_workers, k)
            out[lo:hi] = k
        return out

    def _round_fn(self, n_workers: int):
        """One jitted fused round per fleet size: margins → per-block
        sketches → Grams → gradient shards → coded messages, all in a
        single device call (this IS the stacked-block batched path; the
        loop engine replays per-task slices of the same computation)."""
        if n_workers not in self._round_fns:
            plan = self._plan(n_workers)
            row_block = jnp.asarray(self._row_blocks(n_workers))
            d = self.n_features
            Bmat = (jnp.asarray(plan.B, self.dtype)
                    if plan.B is not None else None)

            @jax.jit
            def go(A, b, z):
                margins = -b * (A @ z)
                sig = jax.nn.sigmoid(margins)
                coef = -b * sig                       # ∇ loss = Aᵀ coef
                w = sig * (1.0 - sig)                 # Hessian weights
                Aw = jnp.sqrt(w)[:, None] * A         # D(z)^{1/2} A
                SA = plan.apply_all(Aw)               # (W, block_rows, d)
                grams = jnp.einsum("wbd,wbe->wde", SA, SA)
                grads = jnp.zeros((n_workers, d), A.dtype) \
                    .at[row_block].add(coef[:, None] * A)
                V = jnp.concatenate(
                    [grads, grams.reshape(n_workers, -1)], axis=1)
                if Bmat is not None:
                    V = Bmat @ V
                return V
            self._round_fns[n_workers] = go
        return self._round_fns[n_workers]

    def round_messages_all(self, z, n_workers: int
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched engine hook: all W task messages in one fused call.
        Returns (messages (W, d+d²), iters (W,))."""
        A, b = self._data()
        msgs = np.asarray(self._round_fn(n_workers)(
            A, b, jnp.asarray(z, self.dtype)))
        iters = np.full(n_workers, self.task_iters(n_workers), np.int64)
        return msgs, iters

    def round_message(self, wid: int, n_workers: int, z
                      ) -> Tuple[np.ndarray, int]:
        """Loop engine hook: task ``wid``'s message.  The fused round is
        computed once per (z, W) and sliced per task (cache keyed on the
        round inputs), so loop and batched engines emit identical
        messages by construction."""
        key = (n_workers, hash(np.asarray(z).tobytes()))
        if self._round_cache is None or self._round_cache[0] != key:
            self._round_cache = (key, *self.round_messages_all(z, n_workers))
        _, msgs, iters = self._round_cache
        return msgs[wid], int(iters[wid])

    # -- master step --------------------------------------------------------
    def _data64(self) -> Tuple[np.ndarray, np.ndarray]:
        """f64 view of the data for the master-side line search: the
        Armijo test compares objective values whose differences shrink
        below f32 epsilon near convergence (f ~ N·log 2, decrements ~
        ‖g‖²/λ), so the master evaluates f in double precision."""
        if self._Ab64 is None:
            A, b = self._data()
            self._Ab64 = (np.asarray(A, np.float64),
                          np.asarray(b, np.float64))
        return self._Ab64

    def _objective64(self, z64: np.ndarray) -> float:
        A, b = self._data64()
        margins = -b * (A @ z64)
        return float(np.logaddexp(0.0, margins).sum()
                     + 0.5 * self.lam2 * (z64 @ z64))

    def master_step(self, z, messages: np.ndarray, responders: np.ndarray,
                    n_workers: int) -> Tuple[np.ndarray, float, float]:
        """Decode → sketched-Hessian solve → Armijo line search.

        Returns (z_new, r_norm, s_norm) with r_norm = ‖∇f(z)‖₂ (the
        convergence residual) and s_norm = ‖α·p‖₂ (the step size)."""
        d = self.n_features
        plan = self._plan(n_workers)
        z64 = np.asarray(z, np.float64)
        total, n_used = plan.decode_sum(np.asarray(responders),
                                        np.asarray(messages))
        total = np.asarray(total, np.float64)
        g_loss = total[:d]
        if not plan.coded:
            # ignore-extra-blocks: rescale the partial gradient by the
            # share of data rows actually covered by the arrived shards
            N = self.cfg.n_samples
            rows = sum(shard_rows(N, n_workers, int(k))[1]
                       - shard_rows(N, n_workers, int(k))[0]
                       for k in responders)
            g_loss = g_loss * (N / max(rows, 1))
        grad = g_loss + self.lam2 * z64
        H = (total[d:].reshape(d, d) / n_used
             + self.lam2 * np.eye(d))
        try:
            p = -np.linalg.solve(H, grad)
        except np.linalg.LinAlgError:
            p = -grad
        if float(grad @ p) >= 0.0:                 # globalization guard
            p = -grad
        f0 = self._objective64(z64)
        gTp = float(grad @ p)
        alpha, best_alpha, best_f = 1.0, 1.0, np.inf
        for _ in range(self.ls_max):
            f_try = self._objective64(z64 + alpha * p)
            if f_try <= f0 + 1e-4 * alpha * gTp:   # Armijo
                best_alpha, best_f = alpha, f_try
                break
            if f_try < best_f:
                best_alpha, best_f = alpha, f_try
            alpha *= 0.5
        z_new = z64 + best_alpha * p
        return (z_new, float(np.linalg.norm(grad)),
                float(np.linalg.norm(best_alpha * p)))

    # -- reporting / conformance helpers ------------------------------------
    def full_grad(self, z) -> np.ndarray:
        """Exact ∇f(z) — the benchmark's rounds-to-target metric."""
        A, b = self._data()
        A64 = np.asarray(A, np.float64)
        b64 = np.asarray(b, np.float64)
        margins = -b64 * (A64 @ np.asarray(z, np.float64))
        coef = -b64 / (1.0 + np.exp(-margins))
        return A64.T @ coef + self.lam2 * np.asarray(z, np.float64)

    def objective(self, x, n_workers: int = 1) -> float:
        return self._objective64(np.asarray(x, np.float64))

    def h_value(self, z) -> float:
        return 0.5 * self.lam2 * float(jnp.vdot(z, z))

    def prox_h(self, v, t):
        """Protocol stub — the Newton path never runs the ADMM z-update."""
        return v

    def solve(self, wid, n_workers, x0, z, u, rho):
        raise NotImplementedError(
            "newton_sketch is a second-order workload: workers compute "
            "Hessian-sketch blocks (round_message), not FISTA shard solves")


@base.register("newton_sketch")
def make_newton_sketch(n_samples: int = 2048, n_features: int = 128,
                       density: float = 0.05, lam2: float = 1e-3,
                       seed: int = 0, sketch: str = "count",
                       sketch_dim: Optional[int] = None,
                       redundancy: int = 1, coded: bool = True,
                       scheme: str = "auto", line_search_max: int = 20,
                       dtype="float32") -> NewtonSketchProblem:
    """Registry factory.  Defaults mirror the canonical reduced logreg
    instance so ``newton_sketch`` and ``logreg``/``logreg_l2`` share the
    same data rows; ``sketch_dim`` defaults to 8·d (the fixed
    sketch acts as an inexact Newton preconditioner, so its distortion
    sets the linear convergence rate — 8·d lands near 0.4/round on the
    canonical instance)."""
    from repro.configs.logreg_paper import scaled
    cfg = scaled(n_samples, n_features, density=density, lam1=0.0,
                 seed=seed)
    return NewtonSketchProblem(cfg, lam2=lam2, sketch=sketch,
                               sketch_dim=sketch_dim,
                               redundancy=redundancy, coded=coded,
                               scheme=scheme,
                               line_search_max=line_search_max,
                               dtype=jnp.dtype(dtype))


class LogRegL2Problem(LogRegProblem):
    """l2-regularized logistic regression — the ADMM twin of
    ``newton_sketch`` (identical data rows and objective) for the
    head-to-head rounds/$-to-target benchmark.  Only the regularizer
    changes vs ``logreg``:  h(z) = (lam2/2)‖z‖²,  prox_h(v, t) =
    v / (1 + t·lam2)."""

    h_l1_lam = None        # shadows the parent property: no l1 fusion

    def __init__(self, logreg_cfg, *, lam2: float = 1e-3, **kw):
        super().__init__(logreg_cfg, **kw)
        self.lam2 = float(lam2)

    def prox_h(self, v, t):
        return v / (1.0 + t * self.lam2)

    def h_value(self, z) -> float:
        return 0.5 * self.lam2 * float(jnp.vdot(z, z))


@base.register("logreg_l2")
def make_logreg_l2(n_samples: int = 2048, n_features: int = 128,
                   density: float = 0.05, lam2: float = 1e-3,
                   seed: int = 0, fista=None,
                   fixed_inner: Optional[int] = None,
                   dtype="float32") -> LogRegL2Problem:
    """Same canonical instance as ``logreg``/``newton_sketch`` (lam1=0;
    the l2 term lives in prox_h)."""
    from repro.configs.logreg_paper import scaled
    if fista is None:
        fista = dict(min_iters=1, eps_grad=1e-3)
    cfg = scaled(n_samples, n_features, density=density, lam1=0.0,
                 seed=seed)
    return LogRegL2Problem(cfg, lam2=lam2,
                           fista=base.as_fista_options(fista),
                           fixed_inner=fixed_inner, dtype=jnp.dtype(dtype))
