"""Plain reference of the benchmark's job, and the check that decides
``correct``.

The job is global-variable consensus ADMM (Boyd et al. 2011, Sec. 7.1) on
l1-regularized logistic regression, as arXiv:1901.03161 runs it: W workers
each hold a contiguous block of rows and minimize their logistic loss plus
the augmented term rho/2 ||x - (z - u)||^2 with backtracking FISTA; the
master averages x + u, soft-thresholds it, and balances the penalty by the
residuals.  This module implements that from the description alone: it
imports nothing of the program and takes nothing the program made.  Its
data comes from the seed by the Koh-Kim-Boyd generator the configuration
names (one PRNG key per global row, folded from the seed's key).

The check judges each compared round by what it left, given the state it
started from (``check_round``): the workers' x against the reference's
FISTA replayed from the same inputs for the same number of iterations,
their iteration counts against those at which the paper's stopping rule
stops that replay, and z, u and the residuals against the reference's
master step on those x.  The workers' solves run on the device; the master's step runs on the
host in float64.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from work import nnz_per_row, shard_rows

CHECKS = ("x_gap", "iter_gap", "z_gap", "u_gap", "r_gap", "s_gap")


class State(NamedTuple):
    """What a round leaves: the workers' x and u (W, d), z (d,), the
    round's residuals, the penalty the next round uses, and each worker's
    FISTA iterations in the round."""
    x: np.ndarray
    u: np.ndarray
    z: np.ndarray
    r_norm: float
    s_norm: float
    rho: float
    iters: Optional[np.ndarray] = None


# -- data ---------------------------------------------------------------------


def generate_rows(base, lo: int, hi: int, d: int, k: int):
    """Rows ``[lo, hi)`` from the seed's key ``base``: label +-1 with
    probability 1/2, ``k`` distinct feature indices uniform without
    replacement, values N(nu, 1) with nu ~ U[0, 1] times the label.
    Each operation is dispatched on its own, not fused under ``jit``, so
    that the values are those of the generator as the source states it,
    rounded the same way operation by operation."""
    def row(key):
        kb, knu, kidx, kval = jax.random.split(key, 4)
        b = jnp.where(jax.random.bernoulli(kb, 0.5), jnp.float32(1.0),
                      jnp.float32(-1.0))
        nu = jax.random.uniform(knu, dtype=jnp.float32) * b
        _, idx = jax.lax.top_k(jax.random.uniform(kidx, (d,),
                                                  dtype=jnp.float32), k)
        vals = nu + jax.random.normal(kval, (k,), dtype=jnp.float32)
        return idx.astype(jnp.int32), vals.astype(jnp.float32), b

    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(jnp.arange(lo, hi))
    return jax.vmap(row)(keys)


def generate(config: dict, n_workers: int, seed: int,
             lanes: Optional[Sequence[int]] = None):
    """The shards of ``lanes`` (default all workers), grouped by row count
    so that a group is one shape: {rows: (workers, (idx, vals, b))}."""
    n, d = config["n_samples"], config["n_features"]
    k = nnz_per_row(config["density"], d)
    base = jax.random.PRNGKey(seed)
    groups: Dict[int, List] = {}
    for w in (range(n_workers) if lanes is None else lanes):
        lo, hi = shard_rows(n, n_workers, w)
        groups.setdefault(hi - lo, []).append(
            (w, generate_rows(base, lo, hi, d, k)))
    return {rows: ([w for w, _ in part],
                   tuple(jnp.stack(a) for a in zip(*(s for _, s in part))))
            for rows, part in groups.items()}


# -- the worker's solve -------------------------------------------------------


def _loss(idx, vals, b, x, operand):
    """Logistic loss of one shard and its gradient; the data and the
    iterate enter the products in ``operand`` precision."""
    def q(a):
        return a.astype(operand).astype(jnp.float32)

    a = q(vals)
    ax = jnp.sum(a * q(x)[idx], axis=1)
    m = -b * ax
    f = jnp.sum(jnp.logaddexp(0.0, m))
    c = -b * jax.nn.sigmoid(m)
    g = jax.ops.segment_sum((q(c)[:, None] * a).ravel(), idx.ravel(),
                            num_segments=x.shape[0])
    return f, g


class Replay(NamedTuple):
    """One worker's solve by the reference: its x after the iterations
    asked for, its x where the paper's stopping rule stops, and the
    number of iterations the rule makes."""
    x: np.ndarray
    x_rule: np.ndarray
    iters: int


def _fista(idx, vals, b, x0, center, rho, iters, *, opts, operand):
    """Backtracking FISTA on  loss(x) + rho/2 ||x - center||^2  from x0,
    stopped by the paper's rule: at least ``min_iters`` iterations, then
    once ||grad|| <= eps_grad or the relative decrease is at most
    eps_fval, at most ``max_iters``.  A step that would raise the
    objective is not taken.

    Where ``iters`` >= 0 asks for more iterations than the rule makes, it
    runs on to ``iters`` (at most ``max_iters``).  Returns (x after
    ``iters`` iterations, or the rule's x where ``iters`` < 0; x where the
    rule stops; the rule's iterations)."""
    def fg(x):
        f, g = _loss(idx, vals, b, x, operand)
        dx = x - center
        return f + 0.5 * rho * jnp.dot(dx, dx), g + rho * dx

    def fval(x):
        return fg(x)[0]

    def cond(s):
        k, on = s[5], s[6]
        return jnp.logical_and(k < opts["max_iters"],
                               jnp.logical_or(on, k < iters))

    def body(s):
        x, y, t, lip, fx, k, on, k_rule, x_rule, x_at = s
        fy, gy = fg(y)
        gsq = jnp.dot(gy, gy)

        def bt_cond(c):
            lip, j, ok = c
            return jnp.logical_and(~ok, j < opts["max_backtracks"])

        def bt_body(c):
            lip, j, _ = c
            ok = fval(y - gy / lip) <= fy - 0.5 * gsq / lip + 1e-12 * abs(fy)
            return jnp.where(ok, lip, lip * opts["eta"]), j + 1, ok

        lip, _, _ = jax.lax.while_loop(bt_cond, bt_body,
                                       (lip, 0, jnp.asarray(False)))
        xn = y - gy / lip
        fn = fval(xn)
        worse = fn > fx
        xn = jnp.where(worse, x, xn)
        fn = jnp.where(worse, fx, fn)
        tn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        yn = xn + ((t - 1.0) / tn) * (xn - x)
        rel = (fx - fn) / jnp.maximum(jnp.abs(fx), 1e-30)
        kn = k + 1
        go = jnp.logical_and(jnp.linalg.norm(gy) > opts["eps_grad"],
                             rel > opts["eps_fval"])
        on_next = jnp.logical_and(
            on, jnp.logical_and(kn < opts["max_iters"],
                                jnp.logical_or(kn < opts["min_iters"], go)))
        return (xn, yn, tn, lip, fn, kn, on_next,
                jnp.where(on, kn, k_rule), jnp.where(on, xn, x_rule),
                jnp.where(kn <= iters, xn, x_at))

    f32 = jnp.float32
    i32 = jnp.int32
    s0 = (x0, x0, f32(1.0), f32(opts["l0"]), fval(x0), i32(0),
          jnp.asarray(opts["max_iters"] > 0), i32(0), x0, x0)
    out = jax.lax.while_loop(cond, body, s0)
    k_rule, x_rule, x_at = out[7:]
    return jnp.where(iters < 0, x_rule, x_at), x_rule, k_rule


@functools.partial(jax.jit, static_argnames=("opts", "operand"))
def _solve_group(idx, vals, b, x0, center, rho, iters, *, opts, operand):
    solve = functools.partial(_fista, opts=dict(opts), operand=operand)
    return jax.vmap(solve, in_axes=(0, 0, 0, 0, 0, None, 0))(
        idx, vals, b, x0, center, rho, iters)


def solve(config: dict, data, prev: "State", iters=None,
          operand=jnp.float32) -> Dict[int, Replay]:
    """The workers of ``data`` solve the round that starts from ``prev``
    by the stopping rule, and, where ``iters`` is given, also for
    ``iters[w]`` iterations each."""
    opts = tuple(sorted(config["fista"].items()))
    _, _, center = worker_inputs(prev, np, np.float32)
    out = {}
    for ws, (idx, vals, b) in data.values():
        its = (np.full(len(ws), -1) if iters is None
               else np.asarray(iters)[ws])
        x, x_rule, k = _solve_group(
            idx, vals, b, jnp.asarray(prev.x[ws]), jnp.asarray(center[ws]),
            jnp.float32(prev.rho), jnp.asarray(its, jnp.int32), opts=opts,
            operand=operand)
        x, x_rule, k = np.asarray(x), np.asarray(x_rule), np.asarray(k)
        for j, w in enumerate(ws):
            out[w] = Replay(x=x[j], x_rule=x_rule[j], iters=int(k[j]))
    return out


# -- the master ---------------------------------------------------------------


def initial_state(config: dict, n_workers: int) -> State:
    d = config["n_features"]
    zeros = np.zeros((n_workers, d), np.float32)
    return State(x=zeros, u=zeros, z=np.zeros(d, np.float32),
                 r_norm=float("nan"), s_norm=float("nan"),
                 rho=float(config["admm"]["rho0"]))


def worker_inputs(prev: State, xp=np, dtype=np.float64):
    """What a round hands its workers: the primal residual x - z, the
    dual after it, u + (x - z), and the centre z - u of their augmented
    term."""
    x, u, z = (xp.asarray(a, dtype) for a in (prev.x, prev.u, prev.z))
    r = x - z[None, :]
    u_new = u + r
    return r, u_new, z[None, :] - u_new


def master_step(config: dict, prev: State, x_new, xp=np,
                dtype=np.float64) -> State:
    """The master's half of a round on the workers' new x: the average of
    x + u soft-thresholded at lambda1 / (W rho), S(a; t) = max(0, 1 -
    t/|a|) a; the primal residual sqrt(sum_w ||x_w - z||^2) of the x the
    round started from; the dual residual rho sqrt(W) ||z_new - z||; the
    penalty balanced by the two (Boyd et al. 2011, Sec. 3.4.1); and the
    scaled dual rescaled to it."""
    admm = config["admm"]
    W = x_new.shape[0]
    rho = prev.rho
    r, u_new, _ = worker_inputs(prev, xp, dtype)
    omega = xp.mean(xp.asarray(x_new, dtype) + u_new, axis=0)
    thr = dtype(config["lam1"] / (W * rho))
    mag = xp.abs(omega)
    z = xp.asarray(xp.where(mag > thr,
                            (1 - thr / xp.where(mag > 0, mag, 1)) * omega, 0),
                   dtype)
    r_norm = float(xp.sqrt(xp.sum(r * r)))
    dz = z - xp.asarray(prev.z, dtype)
    s_norm = float(rho * xp.sqrt(xp.sum(dz * dz))) * float(np.sqrt(W))
    rho_new = rho
    if r_norm > admm["mu"] * s_norm:
        rho_new = rho * admm["tau_inc"]
    elif s_norm > admm["mu"] * r_norm:
        rho_new = rho / admm["tau_dec"]
    u = u_new * dtype(rho / rho_new)
    return State(x=np.asarray(x_new, np.float32), u=np.asarray(u, np.float32),
                 z=np.asarray(z, np.float32), r_norm=r_norm, s_norm=s_norm,
                 rho=rho_new)


# -- the rounds ---------------------------------------------------------------


def rounds(config: dict, n_workers: int, data, n_rounds: int,
           precision: str = "float32") -> List[State]:
    """The first ``n_rounds`` rounds from x = u = z = 0, by the stopping
    rule.  ``precision="float32"`` is the reference: the workers' loss in
    float32, the master in float64.  ``"bfloat16"`` is its control: the
    loss's operands and the master's arithmetic in bfloat16."""
    operand = jnp.dtype(precision)
    xp, dt = (np, np.float64) if precision == "float32" else (jnp,
                                                               operand.type)
    state = initial_state(config, n_workers)
    out = []
    for _ in range(n_rounds):
        solved = solve(config, data, state, operand=operand)
        x = np.stack([solved[w].x_rule for w in range(n_workers)])
        iters = np.array([solved[w].iters for w in range(n_workers)])
        state = master_step(config, state, x, xp, dt)._replace(iters=iters)
        out.append(state)
    return out


# -- the check ----------------------------------------------------------------


def _rel(gap: float, scale: float) -> float:
    if scale > 0:
        return gap / scale
    return 0.0 if gap == 0 else float("inf")


def lane_gaps(a: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Each lane's ||a_w - ref_w||, against the larger of that lane's
    reference norm and the median lane's."""
    a = np.asarray(a, np.float64)
    ref = np.asarray(ref, np.float64)
    norms = np.linalg.norm(ref, axis=1)
    scale = np.maximum(norms, np.median(norms))
    gaps = np.linalg.norm(a - ref, axis=1)
    return np.array([_rel(g, s) for g, s in zip(gaps, scale)])


def check_round(config: dict, data, prev: State, cur: State
                ) -> Dict[str, float]:
    """Judge one round by what it left, ``cur``, given the state it
    started from, ``prev``.

    * ``x_gap``: the worst, over the workers of ``data`` (a sample, or
      all), of each one's gap to the reference's FISTA from the same x,
      z, u and rho for as many iterations as it reported (``lane_gaps``).
    * ``iter_gap``: how far those workers' iteration counts lie from the
      counts by which the paper's stopping rule stops the same replay:
      sum |k - k_rule| / sum k_rule.
    * ``z_gap``: ||z - z_ref|| / ||z_ref||, z_ref the master's step on
      all the workers' x (``master_step``, float64).
    * ``u_gap``: the worst worker's gap to the reference's rescaled dual.
    * ``r_gap``, ``s_gap``: relative gaps of the round's residuals.
    """
    ref = master_step(config, prev, cur.x)
    replay = solve(config, data, prev, cur.iters)
    ws = sorted(replay)
    z_ref = np.asarray(ref.z, np.float64)
    k_rule = np.array([replay[w].iters for w in ws])
    return {
        "x_gap": float(np.max(lane_gaps(
            np.asarray(cur.x)[ws], np.stack([replay[w].x for w in ws])))),
        "iter_gap": _rel(float(np.sum(np.abs(np.asarray(cur.iters)[ws]
                                             - k_rule))),
                         float(np.sum(k_rule))),
        "z_gap": _rel(float(np.linalg.norm(np.asarray(cur.z, np.float64)
                                           - z_ref)),
                      float(np.linalg.norm(z_ref))),
        "u_gap": float(np.max(lane_gaps(cur.u, ref.u))),
        "r_gap": _rel(abs(cur.r_norm - ref.r_norm), ref.r_norm),
        "s_gap": _rel(abs(cur.s_norm - ref.s_norm), ref.s_norm),
    }


def compare(config: dict, data, pairs: Sequence) -> Dict[str, float]:
    """The worst of each number over the compared rounds; ``pairs`` holds
    (state before, state after) of each."""
    out = dict.fromkeys(CHECKS, 0.0)
    for prev, cur in pairs:
        for k, v in check_round(config, data, prev, cur).items():
            out[k] = max(out[k], v) if np.isfinite(v) else float("inf")
    return out


def check_lanes(n_workers: int, n_lanes: int, seed: int) -> List[int]:
    """The workers whose solves are replayed: ``n_lanes`` drawn from the
    seed (all of them where ``n_lanes`` >= W)."""
    if n_lanes >= n_workers:
        return list(range(n_workers))
    rng = np.random.default_rng(seed)
    return sorted(int(w) for w in rng.choice(n_workers, n_lanes,
                                             replace=False))
