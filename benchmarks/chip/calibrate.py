"""Readings that the limits of a cell's correctness check are set from.

    python3 benchmarks/chip/calibrate.py --workload logreg-paper.w64 \\
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 1

For each of ``--seeds`` it makes a benchmark run of the cell (the harness's
own ``run``, with a short window) and records the compared numbers: the
program's readings.  For each of ``--control-seeds`` it puts the
reference, computed in bfloat16 (``reference.rounds(precision=
"bfloat16")``), in the program's place for the compared rounds and judges
its rounds by the same check: the control's readings.  Everything runs in
one process on the chip the cell asks for.  One JSON line per seed goes to
standard output, and a last line gives, for each number, the largest
program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def control_numbers(cell, seed: int) -> dict:
    """The control in the program's place: the first compared rounds in
    bfloat16, judged by the cell's check."""
    import reference
    W = cell.traffic["n_workers"]
    data = reference.generate(cell.config, W, seed)
    states = reference.rounds(cell.config, W, data, cell.compare_rounds,
                              precision="bfloat16")
    pairs = zip([reference.initial_state(cell.config, W)] + states[:-1],
                states)
    lanes = reference.check_lanes(W, cell.check_lanes, seed)
    sample = reference.generate(cell.config, W, seed, lanes)
    return reference.compare(cell.config, sample, list(pairs))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=seeds, default=[])
    p.add_argument("--control-seeds", type=seeds, default=[])
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    os.environ.pop("REPRO_DATA_CACHE", None)
    import harness
    from repro import compile_cache
    import jax

    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    program, control = [], []
    for seed in args.seeds:
        res = harness.run(cell, seed, args.seconds, False,
                          t_start=time.perf_counter())
        nums = {k: c["value"] for k, c in res["checks"].items()}
        program.append(nums)
        print(json.dumps({"side": "program", "seed": seed, **nums,
                          "correct": res["correct"],
                          "rounds": res["window_rounds"],
                          "check_s": res["check_s"]}), flush=True)
    for seed in args.control_seeds:
        t = time.perf_counter()
        nums = control_numbers(cell, seed)
        control.append(nums)
        print(json.dumps({"side": "control", "seed": seed, **nums,
                          "seconds": time.perf_counter() - t}), flush=True)
    summary = {}
    for k in cell.limits:
        summary[k] = {
            "lower": max((n[k] for n in program), default=None),
            "upper": min((n[k] for n in control), default=None),
            "limit": cell.limits[k]}
    print(json.dumps({"workload": cell.name, "readings": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
