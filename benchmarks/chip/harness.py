"""One run of a benchmark cell: set-up, the measured window, and the check
of what the window produced against the plain reference.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic, ``configs/<config>.json`` holds the
problem and its solver options, ``traffic/<traffic>.json`` the fleet (W,
barrier mode, engine and the scheduler options a user sets), and
``workloads/<cell>.json`` the limits of the correctness check.  A per-layer
metric is read by ``metrics/<metric>.py``.

The program is driven only through its public entry points:
``repro.api.build`` (the path users take), the problem's
``batch_shards`` / ``kernel_batch_shards`` staging, and
``Scheduler.step``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCHMARK = REPO / "BENCHMARK.json"

sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

import reference  # noqa: E402
import trace_reduce  # noqa: E402

WINDOW_SPAN = "bench.window"
# a traced run profiles this much of the window at most: a trace of a
# whole long window is too large to read back inside a run's time
TRACE_SECONDS = 10.0
STEP_SPAN = "Scheduler.step"
WAIT_SPAN = "block_until_ready"


class Refused(SystemExit):
    """The run cannot be made here; exits non-zero with no result."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: Dict[str, float]
    compare_rounds: int
    check_lanes: int
    chips: int = 1
    end_to_end: tuple = ()
    per_layer: tuple = ()


def _load(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: Path = HERE,
              benchmark: Path = BENCHMARK) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    bench = _load(benchmark)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise Refused(f"no workload {name!r} in {benchmark.name}; "
                      f"known: {sorted(entries)}")
    entry = entries[name]
    own = _load(root / "workloads" / f"{name}.json")
    return Cell(
        name=name,
        config=_load(root / "configs" / f"{entry['config']}.json"),
        traffic=_load(root / "traffic" / f"{entry['traffic']}.json"),
        limits=dict(own["limits"]),
        compare_rounds=int(own["compare_rounds"]),
        check_lanes=int(own["check_lanes"]),
        chips=int(entry["chips"]),
        end_to_end=tuple(bench["end_to_end"]),
        per_layer=tuple(bench["per_layer"]))


def check_device(chips: int) -> None:
    """Refuse to start unless JAX runs on a TPU with at least ``chips``
    devices and the Pallas kernels are compiled for it."""
    mode = os.environ.get("REPRO_PALLAS", "")
    if mode not in ("", "pallas"):
        raise Refused(f"bench: REPRO_PALLAS={mode!r}; a chip run takes only "
                      f"compiled kernels (unset or 'pallas')")
    backend = jax.default_backend()
    if backend != "tpu":
        raise Refused(f"bench: JAX found no TPU (backend={backend!r})")
    if jax.device_count() < chips:
        raise Refused(f"bench: the cell asks for {chips} chips, JAX found "
                      f"{jax.device_count()}")


def experiment_spec(cell: Cell, seed: int):
    """The cell's job as a user declares it."""
    from repro import api
    from repro.core.admm import AdmmOptions
    from repro.runtime import SchedulerConfig
    c, t = cell.config, cell.traffic
    return api.ExperimentSpec(
        problem=c["problem"],
        problem_kwargs=dict(
            n_samples=c["n_samples"], n_features=c["n_features"],
            density=c["density"], lam1=c["lam1"], seed=int(seed),
            fista=dict(c["fista"]), dtype=c["dtype"]),
        scheduler=SchedulerConfig(
            n_workers=t["n_workers"], mode=t["mode"], engine=t["engine"],
            admm=AdmmOptions(**c["admm"]), **t["scheduler"]),
        max_rounds=c["admm"]["max_iters"])


def stage(spec, problem=None):
    """Build the job through ``api.build`` and stage its shards."""
    from repro import api
    problem, sched = api.build(spec, problem=problem)
    W = spec.scheduler.n_workers
    staged = (problem.kernel_batch_shards(W)
              if spec.scheduler.kernel == "pallas"
              else problem.batch_shards(W))
    jax.block_until_ready(staged)
    return problem, sched


def step(sched):
    """One ADMM round, ended on the device."""
    with jax.profiler.TraceAnnotation(STEP_SPAN):
        m, done = sched.step()
    with jax.profiler.TraceAnnotation(WAIT_SPAN):
        jax.block_until_ready((sched.x, sched.u, sched.z))
    return m, done


class CompileCounter:
    """Counts programs traced while it is open (none should be, inside
    the window)."""

    def __init__(self):
        self.count = 0
        self.open = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if self.open and event == "/jax/core/compile/jaxpr_trace_duration":
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._hear)


def snapshot(sched, m) -> reference.State:
    """The state a round left, as the program holds it (device arrays)."""
    return reference.State(x=sched.x, u=sched.u, z=sched.z,
                           r_norm=float(m.r_norm), s_norm=float(m.s_norm),
                           rho=float(m.rho),
                           iters=np.asarray(m.inner_iters))


def to_host(state: reference.State) -> reference.State:
    return state._replace(x=np.asarray(state.x), u=np.asarray(state.u),
                          z=np.asarray(state.z))


class Compared:
    """Which rounds the check judges: the first job's first
    ``first_rounds`` rounds (round 1 in set-up, the next in the window)
    and the last round of the window, each with the state it started
    from."""

    def __init__(self, initial: reference.State, first_rounds: int):
        self.initial = initial
        self.first_rounds = first_rounds
        self.first: List = []
        self.last = None
        self._prev = initial

    def add(self, state: reference.State, restarted: bool, k: int) -> None:
        prev = self.initial if k == 1 else self._prev
        pair = (prev, state)
        if not restarted and k <= self.first_rounds:
            self.first.append(pair)
        self.last = pair
        self._prev = state

    def pairs(self) -> List:
        out = list(self.first)
        if self.last is not None and all(self.last[1] is not p[1]
                                         for p in out):
            out.append(self.last)
        return [(to_host(a), to_host(b)) for a, b in out]


@dataclasses.dataclass
class Window:
    rounds: list
    seconds: float
    restarts: int
    compiles: int
    walls: list = dataclasses.field(default_factory=list)


def run_window(spec, problem, sched, seconds: float,
               on_round: Callable, counter: Optional[CompileCounter] = None
               ) -> Window:
    """Rounds until ``seconds`` have passed and the round in flight has
    ended.  A job that stops (its stopping target met, or its
    ``max_rounds`` spent) starts again on the data already staged."""
    from repro import api
    max_rounds = spec.max_rounds
    rounds: List = []
    walls: List[float] = []
    restarts = 0
    done = False
    if counter is not None:
        counter.open = True
    t0 = t = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            if done or sched.k >= max_rounds:
                problem, sched = api.build(spec, problem=problem)
                restarts += 1
            m, done = step(sched)
            now = time.perf_counter()
            walls.append(now - t)
            t = now
            rounds.append(m)
            on_round(sched, m, restarts)
            if now - t0 >= seconds:
                break
    elapsed = time.perf_counter() - t0
    if counter is not None:
        counter.open = False
    return Window(rounds=rounds, seconds=elapsed, restarts=restarts,
                  compiles=counter.count if counter is not None else 0,
                  walls=walls)


@dataclasses.dataclass
class RunRecord:
    """What the metric readers see of a run."""
    cell: Cell
    window: Window
    stage_s: float
    trace: Optional[trace_reduce.Summary]
    peak: Optional[dict]


def load_reader(name: str, root: Path = HERE):
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str, root: Path = HERE) -> dict:
    table = _load(root / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where JAX reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True) -> dict:
    """One run of ``cell``: returns the result object to print."""
    if require_chip:
        check_device(cell.chips)
    counter = CompileCounter()
    spec = experiment_spec(cell, seed)

    t = time.perf_counter()
    problem, sched = stage(spec)
    stage_s = time.perf_counter() - t
    compared = Compared(reference.initial_state(cell.config,
                                                cell.traffic["n_workers"]),
                        cell.compare_rounds)
    m, done = step(sched)
    compared.add(snapshot(sched, m), restarted=False, k=m.k)
    first = sched
    setup_s = time.perf_counter() - t_start

    def on_round(s, m, restarts):
        compared.add(snapshot(s, m), restarted=restarts > 0, k=m.k)

    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        win = run_window(spec, problem, sched,
                         min(seconds, TRACE_SECONDS) if trace else seconds,
                         on_round, counter)
    finally:
        counter.close()
        if trace:
            jax.profiler.stop_trace()
    summary = None
    if trace:
        try:
            summary = trace_reduce.summarize(
                trace_reduce.find_xplane(tracedir), WINDOW_SPAN)
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)

    # a window too short to hold the compared rounds: finish them untimed
    done = done or win.restarts > 0
    while not done and first.k < cell.compare_rounds:
        m, done = step(first)
        compared.first.append((compared.first[-1][1], snapshot(first, m)))
    peak_bytes = memory_peak_bytes()

    dev = jax.devices()[0]
    peaks = device_peaks(dev.device_kind) if require_chip else None
    record = RunRecord(cell=cell, window=win, stage_s=stage_s,
                       trace=summary, peak=peaks)

    pairs = compared.pairs()
    del problem, sched, first, compared, spec
    gc.collect()
    t_check = time.perf_counter()
    W = cell.traffic["n_workers"]
    data = reference.generate(cell.config, W, seed, reference.check_lanes(
        W, cell.check_lanes, seed))
    numbers = reference.compare(cell.config, data, pairs)
    del data
    check_s = time.perf_counter() - t_check
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    metrics = {}
    if trace:
        for metric in cell.per_layer:
            value = load_reader(metric["name"])(record)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    else:
        own = {"round_s": win.seconds / len(win.rounds),
               "setup_s": setup_s}
        for metric in cell.end_to_end:
            metrics[metric["name"]] = {"value": own[metric["name"]],
                                       "unit": metric["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": peak_bytes}
    result = {
        "correct": bool(correct),
        "attempted": len(win.rounds),
        "failed": sum(1 for r in win.rounds
                      if not (np.isfinite(r.r_norm)
                              and np.isfinite(r.s_norm))),
        "metrics": metrics,
        "device": device,
        "window_rounds": len(win.rounds),
        "window_restarts": win.restarts,
        "window_compiles": win.compiles,
        "stage_s": stage_s,
        "round_walls": win.walls,
        "round_iters": [int(max(r.inner_iters)) for r in win.rounds],
        "check_s": check_s,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result
