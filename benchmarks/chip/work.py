"""Bytes and operations of the worker phase, computed from shapes.

One FISTA iteration of worker ``w`` evaluates the logistic loss and its
gradient over the worker's shard at least once.  Counted in the shard's
sparse form, whatever path implements it, that pass has to

* read each nonzero's column index (int32) and value (f32),
* read each row's label (f32),
* read the iterate x (d f32) and write the gradient (d f32);

and it does two multiply-adds per nonzero: one for the margin
``<a_n, x>`` and one for the gradient term ``c_n * a_nj``.  The
transcendentals per row and the extra line-search evaluations are left
out, so the count is a lower bound.  Padded rows and lanes that have
stopped do no useful work and are not counted.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Sequence, Tuple

INDEX_BYTES = 4
VALUE_BYTES = 4


class Work(NamedTuple):
    bytes: float
    flops: float


def nnz_per_row(density: float, n_features: int) -> int:
    """Nonzeros in each generated row: ``round(p * d)``, at least one."""
    return max(1, round(density * n_features))


def shard_rows(n_samples: int, n_workers: int, w: int) -> Tuple[int, int]:
    """Row range ``[lo, hi)`` of worker ``w`` under the near-even split."""
    base, rem = divmod(n_samples, n_workers)
    lo = w * base + min(w, rem)
    return lo, lo + base + (1 if w < rem else 0)


def lane_pass(rows: int, k: int, d: int) -> Work:
    """One loss+gradient pass over a shard of ``rows`` rows with ``k``
    nonzeros each, against an iterate of width ``d``."""
    nnz = rows * k
    return Work(bytes=nnz * (INDEX_BYTES + VALUE_BYTES) + rows * VALUE_BYTES
                + 2 * d * VALUE_BYTES,
                flops=4 * nnz)


def fleet_passes(config: dict, n_workers: int) -> Sequence[Work]:
    """``lane_pass`` of every worker of a fleet of ``n_workers``."""
    n, d = config["n_samples"], config["n_features"]
    k = nnz_per_row(config["density"], d)
    out = []
    for w in range(n_workers):
        lo, hi = shard_rows(n, n_workers, w)
        out.append(lane_pass(hi - lo, k, d))
    return out


def least_seconds(work: Work, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take for ``work``: the larger of
    bytes over peak HBM bandwidth and operations over peak rate, and
    which of the two decides (``"bytes"`` or ``"flops"``)."""
    t_bytes = work.bytes / peak["hbm_bytes_per_s"]
    t_flops = work.flops / peak["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def solve_least_seconds(config: dict, n_workers: int,
                        iters_per_round: Iterable[Sequence[int]],
                        peak: dict) -> Tuple[float, str]:
    """Least time of every useful pass of a run: each round's per-lane
    FISTA iteration counts times that lane's one-pass least time, summed.
    Returns (seconds, the bound that decides the most lane-passes)."""
    lanes = [least_seconds(wk, peak) for wk in fleet_passes(config,
                                                             n_workers)]
    total, by = 0.0, {"bytes": 0.0, "flops": 0.0}
    for iters in iters_per_round:
        for (t, bound), it in zip(lanes, iters):
            total += t * int(it)
            by[bound] += t * int(it)
    return total, max(by, key=by.get)
