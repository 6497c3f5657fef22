"""The unified experiment API: declare a run, get a priced result.

Every benchmark, example, and test used to hand-roll the same loop —
construct a problem, construct a ``Scheduler``, call ``solve``, walk
``history``, pretty-print, dump JSON.  This module is that loop, once:

    from repro.api import ExperimentSpec, run
    from repro.runtime import SchedulerConfig

    result = run(ExperimentSpec(
        problem="lasso",                          # any registered workload
        problem_kwargs=dict(n_samples=4096, n_features=256),
        scheduler=SchedulerConfig(n_workers=8, mode="drop_slowest"),
    ))
    result.trace[-1]["r_norm"], result.cost_usd, result.to_json()

``ExperimentSpec`` is declarative — a problem NAME plus JSON-friendly
kwargs, and the nested scheduler/pool/billing/autoscale dataclasses the
runtime already speaks — so a spec round-trips through ``to_dict`` and
an experiment is reproducible from its own artifact.  ``RunResult``
carries the per-round residual/cost trace, the dollar breakdown, and
live handles (``problem``, ``scheduler``) for callers that need more
than the summary (pool statistics, elastic ``rescale`` demos, ...).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro import problems
from repro.runtime.cluster import (Cluster, ClusterConfig, ClusterResult,
                                   DagRun, DagSpec, StageResult, StageSpec)
from repro.runtime import spans
from repro.runtime.scheduler import (RoundMetrics, Scheduler,
                                     SchedulerConfig)


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """A complete, declarative description of one run.

    ``problem`` names a registered workload (``repro.problems``);
    ``problem_kwargs`` are its factory kwargs (keep them
    JSON-representable — dicts for FistaOptions, strings for dtypes).
    ``scheduler`` nests everything the runtime knows: barrier mode,
    execution engine (``engine="batched"`` for one-XLA-call rounds at
    large W — allclose to the default loop engine, see
    tests/test_engine.py), fan-in path, compression, pool/provider,
    billing, autoscale.
    ``max_rounds`` caps the run (defaults to ``scheduler.admm.max_iters``).
    """
    problem: str = "logreg"
    problem_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    scheduler: SchedulerConfig = SchedulerConfig()
    max_rounds: Optional[int] = None
    label: str = ""

    def to_dict(self) -> dict:
        return {
            "problem": self.problem,
            "problem_kwargs": dict(self.problem_kwargs),
            "scheduler": dataclasses.asdict(self.scheduler),
            "max_rounds": self.max_rounds,
            "label": self.label,
        }


def _trace_row(m: RoundMetrics) -> Dict[str, float]:
    return {
        "k": m.k, "sim_time": m.sim_time, "r_norm": m.r_norm,
        "s_norm": m.s_norm, "rho": m.rho, "cost_usd": m.cost_usd,
        "n_workers": m.n_workers, "n_respawns": m.n_respawns,
        "round_wall_s": m.round_wall_s, "t_fanin_wait": m.t_fanin_wait,
        "t_comp_mean": float(m.t_comp.mean()),
        "t_comp_std": float(m.t_comp.std()),
        "t_idle_mean": float(m.t_idle.mean()),
        "t_idle_std": float(m.t_idle.std()),
        "inner_mean": float(m.inner_iters.mean()),
        "z_nnz": m.z_nnz,
    }


@dataclasses.dataclass
class RunResult:
    """What a run produced: solution, trace, dollars, live handles."""
    spec: ExperimentSpec
    problem: Any                      # the WorkerProblem instance
    scheduler: Scheduler              # live handle (pool stats, rescale...)
    z: np.ndarray                     # consensus solution
    trace: List[Dict[str, float]]     # one row per round (see _trace_row)
    converged: bool                   # hit the ADMM eps pair
    rounds: int
    sim_time_s: float
    cost_usd: float
    cost_breakdown: Dict[str, float]  # BillingMeter.summary()
    n_respawns: int
    w_start: int
    w_final: int
    wall_s: float                     # real wall-clock of solve()

    @property
    def history(self) -> List[RoundMetrics]:
        """The scheduler's full per-round metrics (per-worker arrays)."""
        return self.scheduler.history

    def final(self) -> RoundMetrics:
        return self.scheduler.history[-1]

    def to_dict(self) -> dict:
        """JSON-safe summary (the live handles and the full z stay out;
        the spec inside is enough to reproduce the run)."""
        za = np.asarray(self.z)
        return {
            "spec": self.spec.to_dict(),
            "label": self.spec.label,
            "problem": self.spec.problem,
            "converged": self.converged,
            "rounds": self.rounds,
            "sim_time_s": self.sim_time_s,
            "cost_usd": self.cost_usd,
            "cost_breakdown": dict(self.cost_breakdown),
            "n_respawns": self.n_respawns,
            "w_start": self.w_start,
            "w_final": self.w_final,
            "z_norm": float(np.linalg.norm(za)),
            "z_nnz": int(np.sum(np.abs(za) > 1e-6)),
            "wall_s": self.wall_s,
            "trace": self.trace,
        }

    def to_json(self, indent: int = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, default=float)


def build(spec: ExperimentSpec, *, problem=None):
    """Instantiate (problem, Scheduler) from a spec without running it —
    the escape hatch for drivers that need mid-run control (manual
    ``rescale``, checkpoint surgery).  Pass ``problem`` to reuse an
    existing instance (its shard/solver caches) across runs."""
    with spans.span("build"):
        if problem is None:
            problem = problems.make(spec.problem,
                                    **dict(spec.problem_kwargs))
        return problem, Scheduler(problem, spec.scheduler)


def result_from_scheduler(spec: ExperimentSpec, problem, sched: Scheduler,
                          *, wall_s: float = 0.0) -> RunResult:
    """Package a driven scheduler's state as a ``RunResult`` — shared by
    ``run()`` and the multi-tenant cluster (which steps schedulers one
    round at a time instead of calling ``solve``)."""
    last = sched.history[-1]
    eps = spec.scheduler.admm
    return RunResult(
        spec=spec, problem=problem, scheduler=sched,
        z=np.asarray(sched.z),
        trace=[_trace_row(m) for m in sched.history],
        converged=bool(last.r_norm <= eps.eps_primal
                       and last.s_norm <= eps.eps_dual),
        rounds=len(sched.history),
        sim_time_s=float(last.sim_time),
        cost_usd=float(sched.meter.total_usd()),
        cost_breakdown=sched.meter.summary(),
        n_respawns=sched.n_respawns,
        w_start=spec.scheduler.n_workers,
        w_final=sched.cfg.n_workers,
        wall_s=wall_s)


def run(spec: ExperimentSpec, *, problem=None,
        on_round: Optional[Callable[[RoundMetrics], None]] = None
        ) -> RunResult:
    """Run a spec end to end.  ``on_round`` fires per round in ALL four
    barrier modes (async included).  ``problem`` optionally reuses a
    built instance so sweeps don't regenerate shards or re-jit."""
    prob, sched = build(spec, problem=problem)
    t0 = time.time()
    sched.solve(max_rounds=spec.max_rounds, on_round=on_round)
    return result_from_scheduler(spec, prob, sched,
                                 wall_s=time.time() - t0)


# ---------------------------------------------------------------------------
# Multi-tenant surface: many specs, one shared warm pool
# ---------------------------------------------------------------------------

_default_cluster: Optional[Cluster] = None


def submit(spec: ExperimentSpec, *, tenant: str = "default",
           priority: int = 0, deadline_s: Optional[float] = None,
           at: float = 0.0, problem=None,
           cluster: Optional[Cluster] = None):
    """Queue a spec on a cluster (the module-default one unless given)
    instead of running it solo: many submitted jobs then share ONE warm
    sandbox pool, interleaved round-by-round by ``run_all()``.

        submit(spec_a, tenant="alice")
        submit(spec_b, tenant="bob", priority=2)
        results = run_all()          # ClusterResult: jobs + ClusterReport

    Returns the ``Job`` handle (state ``queued``, or ``rejected`` with a
    reason — admission control).  See ``repro.runtime.cluster`` for the
    scheduling policies and the report's contents."""
    global _default_cluster
    if cluster is None:
        if _default_cluster is None:
            _default_cluster = Cluster()
        cluster = _default_cluster
    return cluster.submit(spec, tenant=tenant, priority=priority,
                          deadline_s=deadline_s, at=at, problem=problem)


def run_all(cluster: Optional[Cluster] = None, on_job_done=None):
    """Drive every job submitted to the cluster (module-default unless
    given) to completion; returns the ``ClusterResult``.  The default
    cluster is reset afterwards, so the next ``submit()`` starts a
    fresh batch."""
    global _default_cluster
    if cluster is None:
        cluster = _default_cluster
        _default_cluster = None
        if cluster is None:
            raise RuntimeError("nothing submitted: call api.submit() "
                               "first or pass a Cluster")
    return cluster.run_all(on_job_done=on_job_done)


def submit_dag(dag: DagSpec, *, tenant: str = "default", priority: int = 0,
               deadline_s: Optional[float] = None, at: float = 0.0,
               problems: Optional[Dict[str, Any]] = None,
               cluster: Optional[Cluster] = None) -> DagRun:
    """Queue a phase-structured job — a ``DagSpec`` of named stages with
    per-stage parallelism — on a cluster (module-default unless given).
    Root stages queue at ``at``; a downstream stage is *held* until its
    last predecessor completes, then dispatches with its own
    ``worker_demand`` and receives the predecessors' ``StageResult``s
    (``problem.consume_stage_results({name: StageResult})``) if its
    problem implements the hook.

        dag = DagSpec(stages=(
            StageSpec("fit_a", spec_a),
            StageSpec("fit_b", spec_b),
            StageSpec("combine", spec_c, after=("fit_a", "fit_b")),
        ))
        h = submit_dag(dag, tenant="alice")
        run_all()
        h.stage_results["combine"].z        # the final stage's solution

    ``ClusterConfig(reservation=...)`` picks what admission reserves:
    ``"phase"`` (default) holds capacity per RUNNING stage only;
    ``"peak"`` gang-reserves the DAG's peak level demand for its whole
    life.  Returns the ``DagRun`` handle (stage results, per-stage cost
    rollup, DAG latency)."""
    global _default_cluster
    if cluster is None:
        if _default_cluster is None:
            _default_cluster = Cluster()
        cluster = _default_cluster
    return cluster.submit_dag(dag, tenant=tenant, priority=priority,
                              deadline_s=deadline_s, at=at,
                              problems=problems)


def demand(spec: ExperimentSpec) -> Dict[str, float]:
    """The multi-resource demand a spec presents to a cluster — the
    ``(workers, mem_gb, egress_mbps)`` vector DRF admission and
    class-aware placement reason about (``runtime.placement``).  Useful
    for sizing ``ClusterConfig(mem_capacity_gb=..., egress_capacity_mbps
    =...)`` before submitting:

        api.demand(spec)   # {'workers': 8.0, 'mem_gb': 24.0, ...}
    """
    from repro.runtime.placement import spec_resource_vector
    return spec_resource_vector(spec).to_dict()


def submit_at(spec: ExperimentSpec, at: float, **kw):
    """``submit`` with the arrival instant as a positional: the natural
    verb for trace-driven load, where every submission carries its
    timestamp.  ``submit_at(spec, 12.5, tenant="alice")`` queues the job
    to ARRIVE at t=12.5 on the cluster clock — it stays invisible to
    admission until the simulation reaches that instant."""
    return submit(spec, at=at, **kw)


def replay(workload, *, cluster: Optional[Cluster] = None,
           on_job_done=None, progress_every: int = 0):
    """Replay a ``runtime.loadgen.TraceWorkload`` against a cluster:
    submit every trace job at its timestamped arrival (tenant and
    deadline from the trace, problem instances shared per template so
    shard/jit caches amortize across the whole trace), then drive the
    event loop to completion.

        wl = loadgen.generate(loadgen.LoadSpec(model="azure", jobs=10_000))
        result = api.replay(wl, cluster=Cluster(ClusterConfig(...)))
        result.report.deadline_attainment, result.report.p99_latency_s

    ``progress_every`` > 0 prints a one-line progress marker every that
    many completions (a 10k-job replay is minutes of simulation).
    Returns the ``ClusterResult``."""
    if cluster is None:
        cluster = Cluster()
    problems_by_template = workload.problem_instances()
    for tj in workload.jobs:
        cluster.submit(workload.experiment_spec(tj), tenant=tj.tenant,
                       deadline_s=tj.deadline_s, at=tj.submit_at,
                       problem=problems_by_template[tj.template])
    n_done = [0]

    def _hook(job):
        n_done[0] += 1
        if progress_every and n_done[0] % progress_every == 0:
            print(f"  [replay] {n_done[0]}/{len(workload.jobs)} jobs done "
                  f"(sim t={job.finished_at:.0f}s)", flush=True)
        if on_job_done:
            on_job_done(job)

    return cluster.run_all(on_job_done=_hook)
