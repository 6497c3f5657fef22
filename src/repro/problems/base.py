"""The workload layer: the ``WorkerProblem`` contract, a name→factory
registry, and shared scaffolding for shard-partitioned FISTA workloads.

The scheduler (``repro.runtime.scheduler``) is workload-agnostic: it
drives *any* object satisfying ``WorkerProblem`` through the four barrier
modes, both fan-in paths, compression, elasticity, and billing.  This
module is where that genericity becomes usable: a new estimation workload
is a ~100-line plugin —

    from repro import problems

    @problems.register("my_workload")
    class MyProblem(problems.FistaShardProblem):
        def _gen_shard(self, wid, n_workers): ...
        def _loss_value_and_grad(self, shard): ...
        def prox_h(self, v, t): ...
        def h_value(self, z): ...

    repro.api.run(ExperimentSpec(problem="my_workload", ...))

Contract (what the scheduler calls):
  * ``n_features`` — flat decision-vector length on the wire (matrix
    variables are flattened; see problems/softmax.py),
  * ``n_samples(wid, W)`` — shard size, used by the timing model,
  * ``solve(wid, W, x0, z, u, rho)`` — the Algorithm-2 worker body:
    ``argmin_x f_w(x) + rho/2 ||x - (z - u)||^2`` warm-started at x0,
    returning ``(x_new, real_inner_iteration_count)``,
  * ``prox_h(v, t)`` — the master's prox of the global regularizer h.

Batched-engine contract (optional; ``SchedulerConfig(engine="batched")``):
  * ``solve_all(xs, us, z, rho, kernel="xla")`` — all W worker bodies in
    ONE jitted, vmapped device call; provided by the
    ``BatchedShardProblem`` mixin for any workload that implements
    ``_masked_loss_value_and_grad``.  ``kernel="pallas"`` routes the
    masked loss through the fused Pallas wrappers (``repro.kernels.ops``)
    via the optional ``_masked_kernel_loss_value_and_grad`` /
    ``kernel_batch_shards`` hooks (``SchedulerConfig(kernel="pallas")``
    selects it; the default falls back to the jnp path).

Conformance contract (what ``tests/test_problems.py`` additionally checks
for every REGISTERED workload):
  * shards partition the dataset: Σ_w n_samples(w, W) == n_samples(0, 1),
  * ``solve`` decreases the augmented objective (via ``local_value``),
  * ``prox_h`` is the true prox of ``h_value`` (variational check),
  * a 4-worker end-to-end ``repro.api.run`` converges.

Registered factories therefore also provide ``local_value(wid, W, x)``
(the smooth local term f_w), ``h_value(z)`` (the master's regularizer),
and ``objective(x, W)`` (full φ = Σ f_w + h, for reporting).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Protocol, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fista as fista_mod
from repro.core.fista import FistaOptions
from repro.data.logreg import shard_rows


class WorkerProblem(Protocol):
    """The per-worker subproblem: the scheduler is workload-agnostic."""

    n_features: int

    def n_samples(self, wid: int, n_workers: int) -> int: ...

    def solve(self, wid: int, n_workers: int, x0: jnp.ndarray,
              z: jnp.ndarray, u: jnp.ndarray, rho: float
              ) -> Tuple[jnp.ndarray, int]:
        """argmin_x f_w(x) + rho/2 ||x - (z - u)||^2 from x0.
        Returns (x_new, real inner-iteration count)."""
        ...

    def prox_h(self, v: jnp.ndarray, t: float) -> jnp.ndarray: ...


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ProblemFactory = Callable[..., WorkerProblem]
_REGISTRY: Dict[str, ProblemFactory] = {}


def register(name: str, factory: Optional[ProblemFactory] = None):
    """Register a workload factory under ``name``.

    Usable directly (``register("lasso", LassoProblem)``) or as a
    decorator (``@register("lasso")``).  Factories take keyword arguments
    only — keep them JSON-representable so an ``ExperimentSpec`` stays
    declarative (e.g. ``fista=dict(min_iters=1)``, ``dtype="float32"``).
    """
    def _do(f: ProblemFactory) -> ProblemFactory:
        if name in _REGISTRY:
            raise ValueError(f"problem {name!r} is already registered")
        _REGISTRY[name] = f
        return f
    return _do(factory) if factory is not None else _do


def unregister(name: str) -> None:
    """Remove a registered factory (plugin teardown / tests)."""
    _REGISTRY.pop(name, None)


def make(name: str, **kwargs) -> WorkerProblem:
    """Instantiate the workload registered under ``name``."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown problem {name!r}; registered: "
                       f"{available()}") from None
    return factory(**kwargs)


def available() -> list:
    """Sorted names of every registered workload."""
    return sorted(_REGISTRY)


def as_fista_options(fista: Union[None, dict, FistaOptions]) -> FistaOptions:
    """Accept a FistaOptions, a JSON-friendly kwargs dict, or None."""
    if fista is None:
        return FistaOptions()
    if isinstance(fista, dict):
        return FistaOptions(**fista)
    return fista


def solve_augmented(vg: Callable, x0, center, rho, fixed: Optional[int],
                    fista_opts: FistaOptions):
    """The Algorithm-2 worker body shared by both execution engines:
    minimize  f(x) + rho/2 ||x - center||^2  from x0 via FISTA (adaptive,
    or ``fista_fixed`` when ``fixed`` is set).  Jit-traceable; returns
    (x_new, inner-iteration count)."""
    def aug(x):
        f, g = vg(x)
        dx = x - center
        return f + 0.5 * rho * jnp.vdot(dx, dx), g + rho * dx

    if fixed is not None:
        x_new, info = fista_mod.fista_fixed(aug, x0, fixed, fista_opts)
    else:
        x_new, info = fista_mod.fista(aug, x0, fista_opts)
    return x_new, info.k


def densify_sparse_rows(idx, vals, d: int) -> np.ndarray:
    """Gather-format sparse rows (idx (N, k) int, vals (N, k)) -> dense
    (N, d) rows, duplicate indices summed — exactly the matrix whose row
    dot-products the sparse path computes as ``sum(vals * x[idx])``.
    Used to stage shards for the Pallas kernels, whose MXU tiles are
    dense (see kernels/logistic_vjp.py's TPU-adaptation note)."""
    idx = np.asarray(idx)
    vals = np.asarray(vals)
    n, k = idx.shape
    a = np.zeros((n, d), vals.dtype)
    np.add.at(a, (np.repeat(np.arange(n), k), idx.reshape(-1)),
              vals.reshape(-1))
    return a


def margin_kernel_batch(batch, mask, d: int):
    """Stacked gather-format shards ((idx, vals, b) (W, N, ...), mask
    (W, N)) -> the dense batch the margin kernels stream: ((A, b), mask)
    with A (W, Np, Dp) already in ``ops.margin_layout(N, d)`` and b, mask
    zero on the padded rows.  Staged once per fleet size: a shard the
    kernel wrapper had to pad would be copied on every FISTA iteration
    (at the paper's width, three copies of the whole batch in HBM)."""
    from repro.kernels import ops
    idx, vals, b = batch
    idx, vals = np.asarray(idx), np.asarray(vals)
    W, n, _ = idx.shape
    n_p, d_p, _ = ops.margin_layout(n, d)
    dense = np.zeros((W, n_p, d_p), vals.dtype)
    for w in range(W):
        dense[w, :n, :d] = densify_sparse_rows(idx[w], vals[w], d)

    def pad_rows(a):
        return jnp.pad(a, ((0, 0), (0, n_p - n)))

    return (jnp.asarray(dense), pad_rows(b)), pad_rows(mask)


# ---------------------------------------------------------------------------
# Batched execution: all W subproblems in one XLA call
# ---------------------------------------------------------------------------


class BatchedShardProblem:
    """The batched execution engine's problem-side contract, as a mixin.

    The loop engine costs W device dispatches per round (one jitted
    ``solve`` per worker); past W≈256 the dispatch overhead — not the
    math — dominates simulator wall-clock.  This mixin stacks all W
    per-worker shards into leading-axis arrays ONCE per fleet size and
    exposes

        solve_all(xs, us, z, rho) -> (xs_new (W, d), inner_iters (W,))

    as a single ``jax.vmap``-ed, jitted call (``SchedulerConfig(
    engine="batched")`` selects it).  Shards of unequal length — W not
    dividing the sample count — are zero-padded to the longest shard and
    a per-row {0,1} mask rides along, so every lane has one static shape.

    Host classes provide ``_shard(wid, W)`` (a pytree whose leaves are
    all row-leading), ``fista``/``fixed_inner``/``dtype``, and implement

        _masked_loss_value_and_grad(shard, mask) -> vg(x) -> (f, grad)

    the masked twin of the loop path's loss: padded rows must contribute
    EXACTLY zero to both value and gradient (multiplying real rows by a
    1.0 mask is float-exact, so the two engines agree to vmap-reduction
    tolerance — allclose, not bitwise).  Per-lane FISTA keeps its own
    data-dependent iteration count: ``lax.while_loop`` under ``vmap``
    masks finished lanes, so a lane's trajectory and its reported
    ``inner_iters`` match the unbatched solve.

    Batches are cached per fleet size W, which is what makes elastic
    ``rescale()`` compose for free: a new W is a cache miss that
    re-stacks from the (also cached) per-(wid, W) shards.
    """

    _batch_cache: Optional[Dict[int, Tuple]] = None
    _batched_solver_cache: Optional[Dict[Tuple, Callable]] = None
    # lam for h(z) = lam * ||z||_1 when the master regularizer is l1 —
    # lets the scheduler fuse the z-update / dual-residual / sparsity
    # telemetry into ONE pass (kernels/soft_threshold) under
    # SchedulerConfig(kernel="pallas").  None = not (known to be) l1.
    h_l1_lam: Optional[float] = None

    # -- host hooks ---------------------------------------------------------
    def _masked_loss_value_and_grad(self, shard, mask) -> Callable:
        """vg(x) -> (f, grad) with padded rows contributing exactly 0."""
        raise NotImplementedError

    def _masked_kernel_loss_value_and_grad(self, shard, mask) -> Callable:
        """Fused-kernel twin of ``_masked_loss_value_and_grad``: vg built
        on ``repro.kernels.ops`` so each FISTA iteration streams the
        shard through ONE fused Pallas pass (value+grad together) instead
        of XLA's separate forward/backward matvecs.  The default falls
        back to the jnp path, so ``kernel="pallas"`` is safe on any
        batched workload; built-ins override it (logreg/svm/softmax)."""
        return self._masked_loss_value_and_grad(shard, mask)

    def kernel_batch_shards(self, n_workers: int) -> Tuple:
        """The stacked batch the KERNEL solver consumes — same contract
        as ``batch_shards``.  Workloads whose native shard layout is not
        kernel-friendly override this (logreg/svm densify their sparse
        gather-format shards here, cached per W)."""
        return self.batch_shards(n_workers)

    def supports_batched(self) -> bool:
        """True when this workload implements the batched path (either
        the masked-loss hook or a full ``solve_all`` override)."""
        cls = type(self)
        return (cls.solve_all is not BatchedShardProblem.solve_all
                or cls._masked_loss_value_and_grad
                is not BatchedShardProblem._masked_loss_value_and_grad)

    def supports_kernel(self) -> bool:
        """True when ``solve_all(..., kernel="pallas")`` is accepted.
        Any batched workload qualifies (the kernel hook defaults to the
        jnp fallback); the scheduler checks this before passing the
        kwarg so third-party ``solve_all`` overrides with the pre-kernel
        signature keep working."""
        return self.supports_batched()

    # -- stacking -----------------------------------------------------------
    def batch_shards(self, n_workers: int) -> Tuple:
        """(stacked shard pytree with leading axis W, row mask (W, Nmax)).

        Cached per W; every leaf of ``_shard`` is assumed row-leading
        (true for all built-ins), zero-padded to the longest shard."""
        if self._batch_cache is None:
            self._batch_cache = {}
        if n_workers not in self._batch_cache:
            shards = [self._shard(w, n_workers) for w in range(n_workers)]
            rows = [int(jax.tree_util.tree_leaves(s)[0].shape[0])
                    for s in shards]
            nmax = max(rows)

            def pad(leaf, n):
                a = np.asarray(leaf)
                if n == nmax:
                    return a
                widths = [(0, nmax - n)] + [(0, 0)] * (a.ndim - 1)
                return np.pad(a, widths)

            padded = [jax.tree_util.tree_map(lambda l, n=n: pad(l, n), s)
                      for s, n in zip(shards, rows)]
            stacked = jax.tree_util.tree_map(
                lambda *leaves: jnp.asarray(np.stack(leaves)), *padded)
            mask = np.zeros((n_workers, nmax), np.float64)
            for w, n in enumerate(rows):
                mask[w, :n] = 1.0
            self._batch_cache[n_workers] = (
                stacked, jnp.asarray(mask, self.dtype))
        return self._batch_cache[n_workers]

    # -- the one-call solver ------------------------------------------------
    def _batched_solver(self, shape_key: Tuple,
                        kernel: str = "xla") -> Callable:
        if self._batched_solver_cache is None:
            self._batched_solver_cache = {}
        cache_key = (shape_key, kernel)
        if cache_key not in self._batched_solver_cache:
            fista_opts = self.fista
            fixed = self.fixed_inner
            hook = (self._masked_kernel_loss_value_and_grad
                    if kernel == "pallas"
                    else self._masked_loss_value_and_grad)

            @jax.jit
            def run_all(batch, mask, xs, z, us, rho):
                def one(shard, m, x0, u):
                    vg = hook(shard, m)
                    return solve_augmented(vg, x0, z - u, rho, fixed,
                                           fista_opts)

                return jax.vmap(one, in_axes=(0, 0, 0, 0))(
                    batch, mask, xs, us)

            self._batched_solver_cache[cache_key] = run_all
        return self._batched_solver_cache[cache_key]

    def solve_all(self, xs: jnp.ndarray, us: jnp.ndarray, z: jnp.ndarray,
                  rho: float, kernel: str = "xla"
                  ) -> Tuple[jnp.ndarray, np.ndarray]:
        """All W Algorithm-2 bodies in one device call: returns
        (x_new (W, d), per-worker real inner-iteration counts (W,)).
        ``kernel="pallas"`` routes each lane's loss+grad through the
        fused kernel wrappers (vmap lifts them onto one Pallas grid)."""
        n_workers = int(xs.shape[0])
        batch, mask = (self.kernel_batch_shards(n_workers)
                       if kernel == "pallas"
                       else self.batch_shards(n_workers))
        shape_key = tuple(l.shape for l in jax.tree_util.tree_leaves(batch))
        run_all = self._batched_solver(shape_key, kernel)
        xs_new, ks = run_all(batch, mask, xs, z, us,
                             jnp.asarray(rho, self.dtype))
        return xs_new, np.asarray(ks)


# ---------------------------------------------------------------------------
# Shared scaffolding for shard-partitioned smooth-loss workloads
# ---------------------------------------------------------------------------


class FistaShardProblem(BatchedShardProblem):
    """Scaffolding shared by the built-in workloads: a deterministic
    per-(wid, W) shard cache and one jitted FISTA solver per shard shape
    over ``f_w + the augmented quadratic`` (rho etc. are traced arguments,
    so the adaptive penalty does not retrace).

    Subclasses implement ``_gen_shard`` (a pure function of
    (seed, wid, W) — that is what makes respawn/rescale data-motion-free),
    ``_loss_value_and_grad`` (jit-safe closure over a shard), ``prox_h``
    and ``h_value``.  Everything else — solve, caching, conformance
    helpers — is inherited.
    """

    def __init__(self, n_samples: int, n_features: int, *, seed: int = 0,
                 fista=None, fixed_inner: Optional[int] = None,
                 dtype="float32"):
        self.total_samples = int(n_samples)
        self.n_features = int(n_features)
        self.seed = int(seed)
        self.fista = as_fista_options(fista)
        self.fixed_inner = fixed_inner
        self.dtype = jnp.dtype(dtype)
        self._shard_cache: Dict[Tuple[int, int], Tuple] = {}
        self._solver_cache: Dict[Tuple, Callable] = {}

    # -- subclass hooks -----------------------------------------------------
    def _gen_shard(self, wid: int, n_workers: int):
        """Worker ``wid``'s data, a pure function of (seed, wid, W)."""
        raise NotImplementedError

    def _loss_value_and_grad(self, shard) -> Callable:
        """vg(x) -> (f_w(x), grad f_w(x)); must be jit-traceable."""
        raise NotImplementedError

    def prox_h(self, v: jnp.ndarray, t: float) -> jnp.ndarray:
        raise NotImplementedError

    def h_value(self, z: jnp.ndarray) -> float:
        """The master's regularizer h(z) (conformance contract)."""
        raise NotImplementedError

    # -- shared machinery ---------------------------------------------------
    def _row_keys(self, lo: int, hi: int):
        """Per-GLOBAL-row PRNG keys: sample identity is tied to the global
        row index, so re-sharding W -> W' partitions the same dataset."""
        base = jax.random.PRNGKey(self.seed)
        return jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(lo, hi))

    def _aux_key(self, tag: int):
        """Keys for shard-independent draws (ground truth, class means):
        offset past every row index so they never collide with a sample."""
        base = jax.random.PRNGKey(self.seed)
        return jax.random.fold_in(base, self.total_samples + tag)

    def n_samples(self, wid: int, n_workers: int) -> int:
        lo, hi = shard_rows(self.total_samples, n_workers, wid)
        return hi - lo

    def _shard(self, wid: int, n_workers: int):
        key = (wid, n_workers)
        if key not in self._shard_cache:
            self._shard_cache[key] = self._gen_shard(wid, n_workers)
        return self._shard_cache[key]

    def _solver(self, shape_key: Tuple) -> Callable:
        if shape_key not in self._solver_cache:
            fista_opts = self.fista
            fixed = self.fixed_inner

            @jax.jit
            def run(shard, x0, z, u, rho):
                vg = self._loss_value_and_grad(shard)
                return solve_augmented(vg, x0, z - u, rho, fixed,
                                       fista_opts)

            self._solver_cache[shape_key] = run
        return self._solver_cache[shape_key]

    def solve(self, wid, n_workers, x0, z, u, rho):
        shard = self._shard(wid, n_workers)
        shapes = tuple(a.shape for a in jax.tree_util.tree_leaves(shard))
        run = self._solver(shapes)
        x_new, k = run(shard, x0, z, u, jnp.asarray(rho, self.dtype))
        return x_new, int(k)

    # -- conformance / reporting --------------------------------------------
    def local_value(self, wid: int, n_workers: int, x) -> float:
        """The smooth local term f_w(x) (conformance contract)."""
        vg = self._loss_value_and_grad(self._shard(wid, n_workers))
        f, _ = vg(x)
        return float(f)

    def objective(self, x, n_workers: int) -> float:
        """Full phi(x) = sum_w f_w(x) + h(x) for convergence reporting."""
        total = float(self.h_value(x))
        for w in range(n_workers):
            total += self.local_value(w, n_workers, x)
        return total
