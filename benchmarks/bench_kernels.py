"""Kernel-engine benchmark: the batched scheduler at fleet scale (W in
{64, 256, 1024}) with kernel="xla" vs kernel="pallas" (the fused
wrappers run their deterministic jnp oracle on CPU — same padded
layout/masking as the TPU kernels), per-cell round time + residual.  The
residuals are deterministic simulator metrics and are pinned by
benchmarks/check_regression.py under "engine_compare".  What the kernels
cost on the chip is measured by ``benchmarks/chip`` (``solve_roofline``).
"""
import time

from benchmarks.common import emit


def engine_compare(ws=(64, 256, 1024), rounds=3) -> dict:
    """Batched engine, kernel="xla" vs kernel="pallas", per fleet size:
    wall time per simulated round and the round-``rounds`` residual.
    fixed_inner pins the FISTA work so both kernels do identical math;
    the residual pair must agree to 1e-3 (allclose, not bitwise — the
    kernel path computes on densified, padded shards)."""
    from repro import problems
    from repro.api import ExperimentSpec, build
    from repro.core.admm import AdmmOptions
    from repro.runtime import PoolConfig, SchedulerConfig

    pkw = dict(n_samples=2 * max(ws), n_features=128, density=0.05,
               lam1=0.05, fista=dict(min_iters=1), fixed_inner=5)
    prob = problems.make("logreg", **pkw)
    out = {}
    print(f"  engine-compare logreg d=128 n={pkw['n_samples']} "
          f"rounds={rounds} (batched engine, xla vs pallas wrappers)")
    print(f"  {'W':>5s}  {'xla s/round':>11s}  {'pallas s/round':>14s}  "
          f"{'r_norm xla':>10s}  {'r_norm pallas':>13s}")
    for W in ws:
        cell = {}
        for kernel in ("xla", "pallas"):
            spec = ExperimentSpec(
                problem="logreg", problem_kwargs=pkw,
                scheduler=SchedulerConfig(
                    n_workers=W, engine="batched", kernel=kernel,
                    admm=AdmmOptions(max_iters=rounds + 1),
                    pool=PoolConfig(seed=0)))
            _, sched = build(spec, problem=prob)
            sched.run_round()                  # warmup: jit + staging
            t0 = time.perf_counter()
            for _ in range(rounds):
                sched.run_round()
            cell[kernel] = {
                "round_s": (time.perf_counter() - t0) / rounds,
                "r_norm": float(sched.history[-1].r_norm)}
        rx, rp = cell["xla"]["r_norm"], cell["pallas"]["r_norm"]
        cell["r_rel_diff"] = abs(rx - rp) / max(abs(rx), 1e-12)
        assert cell["r_rel_diff"] <= 1e-3, \
            f"kernel divergence at W={W}: {cell}"
        out[W] = cell
        print(f"  {W:5d}  {cell['xla']['round_s']:11.4f}  "
              f"{cell['pallas']['round_s']:14.4f}  {rx:10.4f}  {rp:13.4f}")
    return out


def main():
    emit("bench_kernels", {"engine_compare": engine_compare()})


if __name__ == "__main__":
    main()
