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
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Tuple

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


def _backtrack(vg: Callable, y, f_y, g_y, lip, opts: FistaOptions):
    """Find L (by eta-doubling) with F(y - g/L) <= F(y) - ||g||^2/(2L).

    Every L the search ends on is evaluated: the accepted trial, or, when
    all ``max_backtracks`` trials fail, one more pass at the last doubled L
    that grows nothing and is not counted.  Returns (L, y - g/L, F there,
    the number of trials made, each one pass of F)."""
    gsq = jnp.vdot(g_y, g_y).real

    def cond(carry):
        _, _, done, _ = carry
        return ~done

    def body(carry):
        lip, j, _, _ = carry
        x_try = y - g_y / lip
        f_try, _ = vg(x_try)
        trial = j < opts.max_backtracks
        ok = f_try <= f_y - 0.5 * gsq / lip + 1e-12 * jnp.abs(f_y)
        grow = jnp.logical_and(trial, ~ok)
        lip_next = jnp.where(grow, lip * opts.eta, lip)
        return (lip_next, j + trial.astype(j.dtype), ~grow, f_try)

    lip, j, _, f_new = jax.lax.while_loop(
        cond, body, (lip, jnp.int32(0), jnp.asarray(False), f_y))
    # the last trial's point, by the same expression: carrying it out of
    # the loop instead costs a (W, d) carry that kept y out of the TPU's
    # VMEM, slowing the next iteration's gather of y
    return lip, y - g_y / lip, f_new, j


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


def _step(value_and_grad: Callable, st: FistaState,
          opts: FistaOptions) -> FistaState:
    """One FISTA iteration; F(x_new) is the line search's last evaluation."""
    f_y, g_y = value_and_grad(st.y)
    lip, x_new, f_new, n_try = _backtrack(value_and_grad, st.y, f_y, g_y,
                                          st.lip, opts)
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


def fista(
    value_and_grad: Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]],
    x0: jnp.ndarray,
    opts: FistaOptions = FistaOptions(),
) -> Tuple[jnp.ndarray, FistaState]:
    """Minimise F from ``value_and_grad``; returns (x*, final state)."""
    def cond(st: FistaState):
        not_min = st.k < opts.min_iters
        under_max = st.k < opts.max_iters
        return jnp.logical_and(under_max,
                               jnp.logical_or(not_min, ~_tol_met(st, opts)))

    final = jax.lax.while_loop(
        cond, lambda st: _step(value_and_grad, st, opts),
        _init(value_and_grad, x0, opts))
    return final.x, final


def fista_fixed(value_and_grad, x0, n_iters: int, opts: FistaOptions = FistaOptions()):
    """Fixed-iteration-count FISTA (scan) — used when a static trip count is
    needed (e.g. inside vmapped workers during the dry-run)."""
    final, _ = jax.lax.scan(
        lambda st, _: (_step(value_and_grad, st, opts), None),
        _init(value_and_grad, x0, opts), None, length=n_iters)
    return final.x, final
