"""FISTA with backtracking (Beck & Teboulle '09) — the paper's local solver.

Solves ``min_x F(x)`` for a smooth F given by a ``value_and_grad`` callable
(for the ADMM worker subproblem, F is the local loss plus the augmented
quadratic; the non-smooth h lives at the master, so the prox step degenerates
to a gradient step).  Fully jittable: the outer iteration is a
``lax.while_loop``, the backtracking line search a bounded inner loop.

Termination follows Section III of the paper:
  * run at least ``min_iters`` (K_w) iterations,
  * stop when ||grad|| <= eps_g  OR  (F_{k-1} - F_k)/F_{k-1} <= eps_f,
  * hard cap at ``max_iters``.
``FistaState.k_tol`` is the first iteration at which that tolerance held
(``k`` if it never did), so ``k - k_tol`` counts the iterations that only
the K_w floor asked for.

``fista_lanes`` runs W such solves in one program (the batched engine's
worker phase).  A lane's iterates are those of ``fista``; the lanes share
the line search's passes of F, and a pass evaluates only lanes that are
still searching.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class FistaOptions:
    min_iters: int = 1            # K_w in the paper
    max_iters: int = 500
    eps_grad: float = 1e-2        # eps_g
    eps_fval: float = 1e-12       # eps_f (relative improvement)
    l0: float = 1.0               # initial Lipschitz estimate
    eta: float = 2.0              # backtracking multiplier
    max_backtracks: int = 30


class FistaState(NamedTuple):
    x: jnp.ndarray                # current iterate
    y: jnp.ndarray                # extrapolated point
    t: jnp.ndarray                # momentum scalar
    lip: jnp.ndarray              # current Lipschitz estimate
    f_x: jnp.ndarray              # F(x)
    g_norm: jnp.ndarray           # ||grad F(y)|| of last step
    rel_impr: jnp.ndarray         # last relative improvement
    k: jnp.ndarray                # iteration counter
    n_ls: jnp.ndarray             # line-search trials over all iterations
    k_tol: jnp.ndarray            # first k at which the tolerance held, else k


def _trial(vg: Callable, y, g_y, gsq, f_y, lip, j, opts: FistaOptions):
    """Trial ``j`` of the line search, at L = ``lip``: evaluate F at
    y - g/L and test F(y - g/L) <= F(y) - ||g||^2/(2L).  Returns (the next
    L, the trials counted, whether the search is over, F there).  Trial
    ``max_backtracks`` is not counted and grows nothing: it evaluates the
    L a search that ran out ends on."""
    x_try = y - g_y / lip
    f_try, _ = vg(x_try)
    trial = j < opts.max_backtracks
    ok = f_try <= f_y - 0.5 * gsq / lip + 1e-12 * jnp.abs(f_y)
    grow = jnp.logical_and(trial, ~ok)
    lip_next = jnp.where(grow, lip * opts.eta, lip)
    return (lip_next, j + trial.astype(j.dtype), ~grow, f_try)


def _backtrack(vg: Callable, y, f_y, g_y, lip, opts: FistaOptions):
    """Find L (by eta-doubling) with F(y - g/L) <= F(y) - ||g||^2/(2L).

    Every L the search ends on is evaluated: the accepted trial, or, when
    all ``max_backtracks`` trials fail, one more pass at the last doubled L
    that grows nothing and is not counted.  Returns (L, F(y - g/L), the
    number of trials made, each one pass of F)."""
    gsq = jnp.vdot(g_y, g_y).real

    def body(carry):
        lip, j, _, _ = carry
        return _trial(vg, y, g_y, gsq, f_y, lip, j, opts)

    lip, j, _, f_new = jax.lax.while_loop(
        lambda carry: ~carry[2], body,
        (lip, jnp.int32(0), jnp.asarray(False), f_y))
    return lip, f_new, j


def _lane_trials(vg: Callable, opts: FistaOptions) -> Callable:
    """``_trial`` on a stack of lanes: (inputs, L, j) -> the lanes' next
    (L, j, search over, F), ``inputs`` being (data, y, g, ||g||^2, F(y))
    stacked by lane and lane ``w``'s F ``vg(data_w, .)``."""
    def one(inp, lip, j):
        d, y, g_y, gsq, f_y = inp
        return _trial(functools.partial(vg, d), y, g_y, gsq, f_y, lip, j,
                      opts)
    return jax.vmap(one)


def _compact_pass(trials: Callable, inputs, search, failing, width: int):
    """A line-search pass over ``width`` lanes: the first ``width`` of the
    ``failing`` lanes are taken out of ``inputs`` and the search, make
    their next trial, and are written back.  A slot no failing lane fills
    evaluates the last lane again (the index is clamped) and writes
    nothing."""
    n_lanes = failing.shape[0]
    lanes = jnp.nonzero(failing, size=width, fill_value=n_lanes)[0]

    def pick(a):
        # a lane at a time: one gather of whole lanes of the Pallas path's
        # dense tiles compiles for a v5e into slices across all W lanes,
        # ~2.4 GB of temporaries at W=64
        return jax.lax.map(
            lambda i: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            lanes)

    new = trials(jax.tree_util.tree_map(pick, inputs), pick(search[0]),
                 pick(search[1]))
    return tuple(a.at[lanes].set(b, mode="drop")
                 for a, b in zip(search, new))


def _pass_width(n_lanes: int) -> int:
    """The width of a compacted line-search pass over ``n_lanes`` lanes:
    an eighth of them, where a pass costs the least a lane on a v5e at
    W=64 (its time follows its lanes, so 8 passes of 8 cost less than one
    of 64), and two at least, since XLA compiles a batch of one lane as an
    unbatched program whose sums may round otherwise."""
    return min(n_lanes, max(2, n_lanes // 8))


def _backtrack_lanes(vg: Callable, data, y, f_y, g_y, lip, live,
                     opts: FistaOptions):
    """``_backtrack`` on W lanes at once, lane ``w``'s F being
    ``vg(data_w, .)``, ``data_w`` the w-th slice of ``data`` along its
    leading axis.

    A ``live`` lane searches until a trial passes or its search runs out.
    Each pass takes ``_pass_width(W)`` of the searching lanes, compacted,
    until none is left.  A lane's trials are those ``_backtrack`` makes,
    at the same points, by the same expression.  Returns (L, F(y - g/L),
    trials) per lane and the lane evaluations the passes made, every slot
    of every pass counted."""
    n_lanes = y.shape[0]
    width = _pass_width(n_lanes)
    gsq = jax.vmap(lambda g: jnp.vdot(g, g).real)(g_y)
    inputs = (data, y, g_y, gsq, f_y)
    trials = _lane_trials(vg, opts)

    def failing(search):
        return jnp.logical_and(live, ~search[2])

    def compact(carry):
        search, slots = carry
        return (_compact_pass(trials, inputs, search, failing(search), width),
                slots + width)

    carry = (lip, jnp.zeros(lip.shape, jnp.int32),
             jnp.zeros(lip.shape, bool), f_y), jnp.int32(0)
    (lip, j, _, f_new), slots = jax.lax.while_loop(
        lambda c: jnp.any(failing(c[0])), compact, carry)
    return lip, f_new, j, slots


def _init(value_and_grad: Callable, x0, opts: FistaOptions) -> FistaState:
    f0, _ = value_and_grad(x0)
    ft = f0.dtype
    return FistaState(
        x=x0, y=x0, t=jnp.asarray(1.0, ft), lip=jnp.asarray(opts.l0, ft),
        f_x=f0, g_norm=jnp.asarray(jnp.inf, ft),
        rel_impr=jnp.asarray(jnp.inf, ft), k=jnp.int32(0),
        n_ls=jnp.int32(0), k_tol=jnp.int32(0))


def _tol_met(st: FistaState, opts: FistaOptions):
    """The paper's tolerance test at ``st``: ||grad|| <= eps_g or a
    relative decrease <= eps_f (false before the first iteration)."""
    return ~jnp.logical_and(st.g_norm > opts.eps_grad,
                            st.rel_impr > opts.eps_fval)


def _running(st: FistaState, opts: FistaOptions):
    """The stopping rule: true while another iteration is due."""
    not_min = st.k < opts.min_iters
    under_max = st.k < opts.max_iters
    return jnp.logical_and(under_max,
                           jnp.logical_or(not_min, ~_tol_met(st, opts)))


def _advance(st: FistaState, g_y, lip, f_new, n_try,
             opts: FistaOptions) -> FistaState:
    """The iteration after its line search ended on L = ``lip`` with
    F(y - g/L) = ``f_new``."""
    x_new = st.y - g_y / lip
    # monotone safeguard (MFISTA-lite): never accept an increase over x_k
    worse = f_new > st.f_x
    x_new = jnp.where(worse, st.x, x_new)
    f_new = jnp.where(worse, st.f_x, f_new)
    t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * st.t * st.t))
    y_new = x_new + ((st.t - 1.0) / t_new) * (x_new - st.x)
    rel = (st.f_x - f_new) / jnp.maximum(jnp.abs(st.f_x), 1e-30)
    # k_tol stops counting at the first iterate that met the tolerance
    held = jnp.logical_or(st.k_tol < st.k, _tol_met(st, opts))
    return FistaState(
        x=x_new, y=y_new, t=t_new, lip=lip, f_x=f_new,
        g_norm=jnp.linalg.norm(g_y), rel_impr=rel, k=st.k + 1,
        n_ls=st.n_ls + n_try, k_tol=jnp.where(held, st.k_tol, st.k + 1))


def _step(value_and_grad: Callable, st: FistaState,
          opts: FistaOptions) -> FistaState:
    """One FISTA iteration; F(x_new) is the line search's last evaluation."""
    f_y, g_y = value_and_grad(st.y)
    lip, f_new, n_try = _backtrack(value_and_grad, st.y, f_y, g_y, st.lip,
                                   opts)
    return _advance(st, g_y, lip, f_new, n_try, opts)


def fista(
    value_and_grad: Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]],
    x0: jnp.ndarray,
    opts: FistaOptions = FistaOptions(),
) -> Tuple[jnp.ndarray, FistaState]:
    """Minimise F from ``value_and_grad``; returns (x*, final state)."""
    final = jax.lax.while_loop(
        lambda st: _running(st, opts),
        lambda st: _step(value_and_grad, st, opts),
        _init(value_and_grad, x0, opts))
    return final.x, final


def fista_fixed(value_and_grad, x0, n_iters: int, opts: FistaOptions = FistaOptions()):
    """Fixed-iteration-count FISTA (scan) — used when a static trip count is
    needed (e.g. inside vmapped workers during the dry-run)."""
    final, _ = jax.lax.scan(
        lambda st, _: (_step(value_and_grad, st, opts), None),
        _init(value_and_grad, x0, opts), None, length=n_iters)
    return final.x, final


def _lanes_where(live, new, old):
    """Lane by lane, ``new`` where ``live``, else ``old``."""
    def pick(a, b):
        return jnp.where(live.reshape(live.shape + (1,) * (a.ndim - 1)), a, b)
    return jax.tree_util.tree_map(pick, new, old)


def fista_lanes(value_and_grad: Callable, data, x0: jnp.ndarray,
                opts: FistaOptions = FistaOptions(),
                n_iters: Optional[int] = None):
    """W independent solves in one program: lane ``w`` minimises
    ``value_and_grad(data_w, .)`` from ``x0[w]``, ``data_w`` the w-th slice
    of ``data`` along its leading axis.  Lane by lane the iterates, counts
    and trials are those of ``fista`` (of ``fista_fixed`` for ``n_iters``
    iterations where it is given); only the line search's passes are
    shared, each over lanes still searching (``_backtrack_lanes``).  Returns (x (W, d), the lanes' final state,
    the lane evaluations the line search made)."""
    n_lanes = x0.shape[0]
    lane_vg = jax.vmap(value_and_grad)
    running = jax.vmap(functools.partial(_running, opts=opts))
    advance = jax.vmap(functools.partial(_advance, opts=opts))

    def step(st, live, slots):
        f_y, g_y = lane_vg(data, st.y)
        lip, f_new, n_try, used = _backtrack_lanes(
            value_and_grad, data, st.y, f_y, g_y, st.lip, live, opts)
        return advance(st, g_y, lip, f_new, n_try), slots + used

    init = (jax.vmap(lambda d, x: _init(
        functools.partial(value_and_grad, d), x, opts))(data, x0),
        jnp.int32(0))
    if n_iters is None:
        def body(carry):
            st, slots = carry
            live = running(st)
            new, slots = step(st, live, slots)
            return _lanes_where(live, new, st), slots

        final, slots = jax.lax.while_loop(
            lambda carry: jnp.any(running(carry[0])), body, init)
    else:
        every = jnp.ones((n_lanes,), bool)
        (final, slots), _ = jax.lax.scan(
            lambda carry, _: (step(carry[0], every, carry[1]), None),
            init, None, length=n_iters)
    return final.x, final, slots
