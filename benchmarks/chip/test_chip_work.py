"""The benchmark's byte and operation counts against hand counts."""
import json
from pathlib import Path

import pytest

import work

HERE = Path(__file__).resolve().parent
V5E = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]


# rcv1.binary's training split at the paper's data model, 74 nonzeros a
# row: a shape measured on the chip but not a cell (PERF.md, section 7)
RCV1_SHAPE = dict(n_samples=20_242, n_features=47_236, density=74 / 47_236)


def config(name):
    if name == "rcv1-shape":
        return RCV1_SHAPE
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, k, mb", [
    # 600,000 rows x 10 nonzeros x 8 B + 600,000 labels x 4 B
    # + 64 lanes x 10,000 x 4 B read (x) and written (gradient)
    ("logreg-paper", 10, 48.0 + 2.4 + 2.56 + 2.56),
    # 20,242 x 74 x 8 B + 20,242 x 4 B + 2 x 64 x 47,236 x 4 B
    ("rcv1-shape", 74, (20_242 * 74 * 8 + 20_242 * 4
                              + 2 * 64 * 47_236 * 4) / 1e6),
])
def test_fleet_pass_bytes_match_hand_count(name, k, mb):
    cfg = config(name)
    assert work.nnz_per_row(cfg["density"], cfg["n_features"]) == k
    passes = work.fleet_passes(cfg, 64)
    assert len(passes) == 64
    assert sum(p.bytes for p in passes) / 1e6 == pytest.approx(mb, rel=1e-12)
    assert sum(p.flops for p in passes) == 4 * cfg["n_samples"] * k


def test_hand_counts_in_megabytes():
    total = {n: sum(p.bytes for p in work.fleet_passes(config(n), 64))
             for n in ("logreg-paper", "rcv1-shape")}
    assert round(total["logreg-paper"] / 1e6, 1) == 55.5
    assert round(total["rcv1-shape"] / 1e6, 1) == 36.2


def test_lane_pass_and_shard_rows():
    assert work.lane_pass(rows=3, k=2, d=5) == work.Work(
        bytes=3 * 2 * 8 + 3 * 4 + 2 * 5 * 4, flops=4 * 6)
    rows = [work.shard_rows(20_242, 64, w) for w in range(64)]
    assert rows[0] == (0, 317) and rows[-1][1] == 20_242
    assert {hi - lo for lo, hi in rows} == {316, 317}


def test_bytes_decide_on_v5e():
    t, bound = work.least_seconds(work.Work(bytes=819e9, flops=1.0), V5E)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(work.Work(bytes=1.0, flops=197e12), V5E)
    assert bound == "flops" and t == pytest.approx(1.0)


def test_solve_least_seconds_sums_lane_iterations():
    cfg = dict(n_samples=8, n_features=4, density=0.5)
    one = work.least_seconds(work.lane_pass(2, 2, 4), V5E)[0]
    t, bound = work.solve_least_seconds(cfg, 4, [[1, 2, 0, 3], [1, 1, 1, 1]],
                                        V5E)
    assert bound == "bytes"
    assert t == pytest.approx(one * 10)
