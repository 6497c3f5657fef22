"""Chip benchmark of one cell: wall seconds per ADMM round and set-up.

    python3 benchmarks/chip/bench.py --workload logreg-paper.w64 \\
        --seed 1234 --seconds 10 --trace 0

Runs on the machine it is started on, which has to hold a TPU with as many
chips as the cell asks for; anywhere else it exits non-zero and prints no
result.  With ``--trace 0`` the result line carries the cell's end-to-end
metrics (``round_s``, ``setup_s``); with ``--trace 1`` the window, cut
to its first 10 seconds, is profiled and the line carries the per-layer
metrics, the device's busy time and a breakdown of where the window
went.  Every run checks the rounds it drove against the plain reference
(``reference.py``) and prints each compared number beside its limit,
last on standard error and last in the result line, which is the last
line of standard output.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[2]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # before JAX is imported: the cache lives in the checkout, and no
    # shards written by an earlier run are read back
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(REPO / ".jax_cache")
    os.environ.pop("REPRO_DATA_CACHE", None)
    import harness
    from repro import compile_cache
    import jax

    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
