"""Smoke run of the main path on one TPU chip.

    python chip_smoke.py

Two phases, both through ``repro.api.build`` and an ``ExperimentSpec``:

* A — the default path at full size: the paper's l1-logistic instance
  (N=600,000, d=10,000, p=0.001, lambda1=1, f32, the paper's FISTA and
  ADMM options) on W=64 workers, sync, batched engine, ``kernel="xla"``
  over the sparse gather shards, for 3 ADMM rounds.  Fails unless the
  residuals are finite and r_norm falls.
* B — the Pallas kernel path: the same instance family at full width with
  N cut to 96,000 (the kernels stream dense rows, and the dense batch of
  the full instance does not fit one chip), ``kernel="pallas"`` against
  ``kernel="xla"`` with ``fixed_inner=5`` for 3 rounds.  Fails unless the
  two r_norm traces agree to rtol 1e-3, the fused z-update ran, the Pallas
  kernels ran (``ops._mode() == "pallas"``) and the lowered batched solve
  holds a ``tpu_custom_call``.

It refuses to start, with a non-zero exit, where JAX finds no TPU or where
``REPRO_PALLAS`` asks for anything but compiled kernels.  The last line of
standard output is one JSON object naming the device; it is printed only
when every phase passed.  Data is generated from the config's seed.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api, compile_cache  # noqa: E402
from repro.configs.logreg_paper import CONFIG, LogRegConfig, scaled  # noqa: E402
from repro.core.admm import AdmmOptions  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.runtime import SchedulerConfig  # noqa: E402

N_WORKERS = 64
ROUNDS = 3
KERNEL_ROWS = 96_000          # phase B's N: 1,500 rows per lane at W=64
FIXED_INNER = 5
RTOL = 1e-3


class SmokeFailure(RuntimeError):
    """A phase produced a result that is not right."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def refuse_unless_tpu() -> None:
    """Exit non-zero unless the Pallas kernels compile for a TPU here."""
    mode = os.environ.get("REPRO_PALLAS", "")
    if mode not in ("", "pallas"):
        raise SystemExit(f"chip_smoke: REPRO_PALLAS={mode!r}; the chip run "
                         f"takes only compiled kernels (unset or 'pallas')")
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (backend={backend!r})")


def paper_spec(cfg: LogRegConfig, n_workers: int, rounds: int, *,
               kernel: str = "xla", fixed_inner=None) -> api.ExperimentSpec:
    """The l1-logistic instance ``cfg`` with the paper's FISTA and ADMM
    options, sync, on the batched engine."""
    return api.ExperimentSpec(
        problem="logreg",
        problem_kwargs=dict(
            n_samples=cfg.n_samples, n_features=cfg.n_features,
            density=cfg.density, lam1=cfg.lam1, seed=cfg.seed,
            fista=dict(min_iters=cfg.fista_min_iters,
                       max_iters=cfg.fista_max_iters,
                       eps_grad=cfg.eps_grad, eps_fval=cfg.eps_fval),
            fixed_inner=fixed_inner),
        scheduler=SchedulerConfig(
            n_workers=n_workers, mode="sync", engine="batched",
            kernel=kernel,
            admm=AdmmOptions(rho0=cfg.rho0, max_iters=cfg.max_admm_iters,
                             eps_primal=cfg.eps_primal,
                             eps_dual=cfg.eps_dual)),
        max_rounds=rounds)


def peak_bytes():
    """Peak device bytes of this process so far (None where the backend
    does not report it)."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def drive(spec: api.ExperimentSpec, problem=None) -> dict:
    """Build the spec's scheduler and step it ``spec.max_rounds`` rounds.

    The shards are staged first and timed apart; the first round pays the
    compilation.  Every time ends in ``block_until_ready``."""
    t0 = time.perf_counter()
    problem, sched = api.build(spec, problem=problem)
    W = spec.scheduler.n_workers
    staged = (problem.kernel_batch_shards(W)
              if spec.scheduler.kernel == "pallas"
              else problem.batch_shards(W))
    jax.block_until_ready(staged)
    data_s = time.perf_counter() - t0
    rows, round_s = [], []
    for _ in range(spec.max_rounds):
        t = time.perf_counter()
        m, done = sched.step()
        jax.block_until_ready((sched.x, sched.u, sched.z))
        round_s.append(time.perf_counter() - t)
        rows.append(dict(k=m.k, r_norm=m.r_norm, s_norm=m.s_norm, rho=m.rho,
                         inner_max=int(m.inner_iters.max()),
                         inner_mean=float(m.inner_iters.mean()),
                         z_nnz=m.z_nnz, wall_s=round_s[-1]))
        if done:
            break
    return dict(problem=problem, sched=sched, rounds=rows, data_s=data_s,
                first_round_s=round_s[0], setup_s=data_s + round_s[0],
                steady_s_per_round=(float(np.mean(round_s[1:]))
                                    if len(round_s) > 1 else None))


def _print_rounds(tag: str, res: dict) -> None:
    for row in res["rounds"]:
        print(f"{tag} round {row['k']}: r_norm={row['r_norm']!r} "
              f"s_norm={row['s_norm']!r} rho={row['rho']!r} "
              f"inner_iters max={row['inner_max']} "
              f"mean={row['inner_mean']!r} z_nnz={row['z_nnz']} "
              f"wall_s={row['wall_s']!r}", flush=True)
    print(f"{tag} set-up s={res['setup_s']!r} (data {res['data_s']!r} + "
          f"first round {res['first_round_s']!r}); steady s/round="
          f"{res['steady_s_per_round']!r}", flush=True)


def check_residuals(r_norms, s_norms) -> None:
    """Finite residuals, and r_norm falling after round 1 (whose r_norm is
    that of the starting point x = z = 0, zero by construction)."""
    _require(bool(np.all(np.isfinite(r_norms))
                  and np.all(np.isfinite(s_norms))),
             f"non-finite residuals r={r_norms} s={s_norms}")
    tail = np.asarray(r_norms[1:], np.float64)
    _require(len(tail) >= 2 and bool(np.all(np.diff(tail) < 0)),
             f"r_norm does not fall: {r_norms}")


def check_agreement(r_xla, r_pallas, rtol: float = RTOL) -> float:
    """Largest relative gap between two r_norm traces; fails past rtol."""
    x = np.asarray(r_xla, np.float64)
    p = np.asarray(r_pallas, np.float64)
    _require(x.shape == p.shape and x.size > 0,
             f"trace lengths differ: {x.size} vs {p.size}")
    gap = float(np.max(np.abs(p - x)
                       / np.maximum(np.abs(x), np.finfo(np.float32).tiny)))
    _require(gap <= rtol, f"pallas vs xla r_norm gap {gap!r} > rtol {rtol}: "
                          f"xla={x.tolist()} pallas={p.tolist()}")
    return gap


def phase_a(cfg: LogRegConfig, n_workers: int, rounds: int) -> dict:
    """The default path: sparse gather shards, ``kernel="xla"``."""
    print(f"phase A: logreg N={cfg.n_samples} d={cfg.n_features} "
          f"p={cfg.density} lambda1={cfg.lam1} W={n_workers} sync batched "
          f"kernel=xla, {rounds} rounds", flush=True)
    res = drive(paper_spec(cfg, n_workers, rounds))
    res["peak_bytes"] = peak_bytes()
    _print_rounds("phase A", res)
    print(f"phase A peak_bytes_in_use={res['peak_bytes']}", flush=True)
    check_residuals([r["r_norm"] for r in res["rounds"]],
                    [r["s_norm"] for r in res["rounds"]])
    return res


def phase_b(cfg: LogRegConfig, n_workers: int, rounds: int,
            fixed_inner: int) -> dict:
    """The Pallas kernel path against the xla path on one instance."""
    n_p, d_p, _ = ops.margin_layout(-(-cfg.n_samples // n_workers),
                                    cfg.n_features)
    print(f"phase B: logreg N={cfg.n_samples} d={cfg.n_features} "
          f"p={cfg.density} W={n_workers} fixed_inner={fixed_inner}, "
          f"{rounds} rounds, kernel=pallas vs kernel=xla; N cut from "
          f"{CONFIG.n_samples}: the kernels stream dense rows, and the "
          f"dense batch of the full instance is "
          f"{CONFIG.n_samples * CONFIG.n_features * 4} bytes against one "
          f"v5e's 16 GB; this cut's padded dense batch is "
          f"{n_workers * n_p * d_p * 4} bytes", flush=True)
    xla = drive(paper_spec(cfg, n_workers, rounds, fixed_inner=fixed_inner))
    _print_rounds("phase B xla", xla)
    pallas = drive(paper_spec(cfg, n_workers, rounds, kernel="pallas",
                              fixed_inner=fixed_inner),
                   problem=xla["problem"])
    _print_rounds("phase B pallas", pallas)

    problem, sched = pallas["problem"], pallas["sched"]
    batch, mask = problem.kernel_batch_shards(n_workers)
    shape_key = tuple(l.shape for l in jax.tree_util.tree_leaves(batch))
    lowered = problem._batched_solver(shape_key, "pallas").lower(
        batch, mask, sched.x, sched.z, sched.u,
        jnp.asarray(sched.rho, problem.dtype))
    res = dict(
        xla=xla, pallas=pallas, mode=ops._mode(),
        custom_call="tpu_custom_call" in lowered.as_text(),
        gap=check_agreement([r["r_norm"] for r in xla["rounds"]],
                            [r["r_norm"] for r in pallas["rounds"]]),
        peak_bytes=peak_bytes())
    print(f"phase B pallas vs xla max r_norm rel gap={res['gap']!r} "
          f"(rtol {RTOL}); ops mode={res['mode']}; tpu_custom_call in "
          f"lowered solve={res['custom_call']}; peak_bytes_in_use="
          f"{res['peak_bytes']}", flush=True)
    _require(all(r["z_nnz"] >= 0 for r in pallas["rounds"]),
             "the fused z-update did not run (z_nnz < 0)")
    return res


def main() -> None:
    refuse_unless_tpu()
    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}", flush=True)

    phase_a(CONFIG, N_WORKERS, ROUNDS)
    b = phase_b(scaled(KERNEL_ROWS, CONFIG.n_features), N_WORKERS, ROUNDS,
                FIXED_INNER)
    _require(b["mode"] == "pallas", f"kernel mode {b['mode']!r}, not pallas")
    _require(b["custom_call"],
             "no tpu_custom_call in the lowered batched solve")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
