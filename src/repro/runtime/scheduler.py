"""Algorithm 1 (the paper's scheduler), event-driven, with the extensions
the paper leaves as future work.

The scheduler orchestrates a ``LambdaPool`` of simulated serverless workers
running REAL consensus-ADMM math on real shards.  Per round it
reproduces the paper's measurement set (idle / compute / delay per worker,
cold starts, responsiveness) and supports:

  * ``sync``         — full barrier (the paper's setting);
  * ``drop_slowest`` — K-of-W partial barrier: the slowest fraction's fresh
                       updates are not waited for; their LAST ω stays in the
                       master's running table, so the average remains over
                       all W workers (a stale-cache partial barrier — the
                       dual-consistent version of "discard the stragglers",
                       which the paper warns biases generic optimization);
  * ``replicated``   — FRS-style worker replication (repro.core.coding):
                       r workers per shard group, first responder wins;
                       tolerates r-1 stragglers/failures with EXACT math;
  * ``async_``       — bounded-staleness async ADMM (Zhang & Kwok '14 /
                       Chang et al. '16): the master updates z every S
                       arrivals; a worker whose z is older than
                       ``staleness_bound`` versions blocks until rebroadcast.

Orthogonal to the barrier mode, the worker-solve EXECUTION ENGINE is
switchable (``engine="loop"`` — one jitted solve per worker per round,
byte-identical to the historical path — or ``engine="batched"`` — all W
shards stacked and solved in ONE vmapped XLA call via
``problems.BatchedShardProblem.solve_all``; the per-worker
timing/straggler/cost model is then applied to the batched outputs, so
the simulation is allclose to the loop engine at a fraction of the
dispatch cost: the path that makes W=1024+ sweeps affordable).

Also orthogonal to the barrier mode, the fan-in path is switchable
(``fanin="flat"`` — the paper's single router, Fig 5's cliff — or
``fanin="tree"`` — hierarchical k-ary aggregation, repro.runtime.reduce)
and ω-messages can be compressed (``compress="topk"|"qsgd"``,
repro.optim.compression): compressed bytes shrink the comm clock AND the
master averages the lossy decoded ω, so the convergence impact is
measured, not assumed.

Elasticity: workers hitting their Lambda lifetime (or killed by failure
injection) are respawned — cold, or WARM when the pool's provider model
is enabled (``PoolConfig(provider=...)``: the dead invocation's sandbox
sits in a keep-alive pool); the replacement regenerates its shard
deterministically (data is a pure function of (seed, shard)); the
algorithm state a replacement needs — (z, rho, k) and its OWN (x, u) —
is exactly what ``repro.checkpoint`` persists, so mid-run worker
replacement and full restarts share one mechanism.  A billing meter
(``runtime.billing``) prices every spawn/round/byte, and
``SchedulerConfig(autoscale=...)`` lets a closed-loop controller
(``runtime.autoscale``) call ``rescale()`` mid-run — elastic resizes in
both directions, with retired sandboxes feeding the warm pool.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core import admm
from repro.core.admm import AdmmOptions
from repro.optim.compression import OmegaCodec, message_bytes
from repro.problems.base import WorkerProblem
from repro.runtime.autoscale import AutoscaleConfig, Autoscaler
from repro.runtime.billing import BillingConfig, BillingMeter
from repro.runtime.pool import LambdaPool, PoolConfig
from repro.runtime.reduce import TreeConfig, fanin_drain
from repro.runtime import spans


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    n_workers: int = 16
    mode: str = "sync"            # sync | drop_slowest | replicated | async_
    # execution engine for the round's worker solves:
    #   "loop"    — one jitted solve per worker per round (the historical
    #               path, byte-identical to pre-engine code);
    #   "batched" — stack all W shards and run ONE vmapped, jitted
    #               solve_all per round (problems.BatchedShardProblem);
    #               numerically allclose to "loop", not bitwise, and
    #               ~W/dispatch-cost faster in simulator wall-clock.
    # async_ paces itself per-arrival (a batching window of 1), so the
    # engine setting only changes the synchronous-family round path.
    engine: str = "loop"
    # numeric kernel backend inside the round:
    #   "xla"    — the default; byte-identical to the pre-kernel code path;
    #   "pallas" — route the hot math through the fused Pallas kernels
    #              (repro.kernels.ops): with engine="batched" every lane's
    #              FISTA loss+grad streams through ONE fused margin-kernel
    #              launch per iteration (vmap lifts the batch onto the
    #              Pallas grid), and the master's z-update / dual-residual
    #              / sparsity telemetry fuse into one soft-threshold pass
    #              (l1-prox f32 workloads; others keep the jnp z-update).
    #              On CPU the wrappers honor REPRO_PALLAS (interpret/ref) —
    #              numerically allclose to "xla", not bitwise.
    kernel: str = "xla"
    drop_frac: float = 0.1        # drop_slowest: fraction not waited for
    replication: int = 2          # replicated: r
    async_batch: int = 4          # async_: S arrivals per z-update
    staleness_bound: int = 4      # async_: max z-version lag
    admm: AdmmOptions = AdmmOptions()
    pool: PoolConfig = PoolConfig()
    # fan-in: "flat" = the paper's single router (master_drain, the Fig 5
    # cliff); "tree" = hierarchical k-ary aggregation (runtime.reduce)
    fanin: str = "flat"
    tree: TreeConfig = TreeConfig()
    # ω-message compression (repro.optim.compression.OmegaCodec): shrinks
    # the modelled wire bytes AND lossy-codes the ω the master averages,
    # so the convergence cost is measured by the real ADMM math
    compress: str = "none"        # none | topk | qsgd
    topk_frac: float = 0.05       # topk: fraction of d kept per message
    qsgd_bits: int = 4            # qsgd: bits per coordinate
    # decision-vector size for the WIRE/cost model only; defaults to the
    # problem's n_features.  Benchmarks that solve reduced instances but
    # model paper-scale timing set this to the paper's d (10 000) so
    # message sizes match the compute model's scale.
    wire_d: Optional[int] = None
    respawn_before_deadline_s: float = 30.0
    # timing: use the round-median inner-iteration count per worker.  At
    # paper scale (N_w ~ 1e4 iid rows) per-round FISTA counts concentrate;
    # reduced benchmark instances replicate that concentration this way.
    iter_smoothing: bool = False
    checkpoint_every: int = 0     # rounds; 0 = off
    checkpoint_dir: Optional[str] = None
    # dollar meter (runtime.billing): every run yields a cost next to its
    # sim time; constants are the AWS-style defaults in BillingConfig
    billing: BillingConfig = BillingConfig()
    # closed-loop elasticity (runtime.autoscale): when the policy is not
    # "off", solve() lets the controller call rescale() mid-run.  Applies
    # to the synchronous-family modes (async_ paces itself per-arrival)
    autoscale: AutoscaleConfig = AutoscaleConfig()


class RoundMetrics(NamedTuple):
    k: int
    sim_time: float              # sim clock at end of round
    r_norm: float
    s_norm: float
    rho: float
    t_comp: np.ndarray           # (W,) per-worker compute time
    t_comm: np.ndarray           # (W,)
    t_idle: np.ndarray           # (W,) comm + scheduler processing
    inner_iters: np.ndarray      # (W,)
    n_respawns: int
    slowest10: np.ndarray        # (W,) bool — in the slowest 10% this round
    # provider-era fields (defaulted so older call sites keep working)
    round_wall_s: float = 0.0    # this round's wall time (rescale-safe)
    t_fanin_wait: float = 0.0    # master drain past the last omega arrival
    cost_usd: float = 0.0        # cumulative run cost (runtime.billing)
    n_workers: int = 0           # fleet size this round (autoscale varies it)
    # kernel-era field: nnz(z) after the round's soft-threshold, reported
    # for free by the fused z-update (kernel="pallas" on l1 workloads);
    # -1 when the jnp z-update ran (it does not compute sparsity)
    z_nnz: int = -1
    # seconds per host span of the round (runtime.spans), filled until
    # Scheduler.step returns; None when the round ran outside step()
    span_s: Optional[Dict[str, float]] = None
    # (W,) line-search trials per worker over its FISTA iterations; None
    # where no solve recorded them (the loop engine, direct solvers)
    ls_trials: Optional[np.ndarray] = None
    # (W,) the iteration at which each worker's FISTA tolerance first held
    # (its count where it never did); None where ls_trials is
    tol_iters: Optional[np.ndarray] = None
    # lane evaluations the batched solve's line search made for the W
    # workers, every slot of every pass (a fill slot included); None where
    # ls_trials is
    ls_slots: Optional[int] = None


class Scheduler:
    """``pool`` injects a pre-built LambdaPool (the multi-tenant cluster
    hands every job a pool backed by ONE shared provider); ``start_time``
    starts this run's event clock at a later instant (the cluster admits
    jobs mid-timeline).  Defaults reproduce the historical single-
    experiment path byte-for-byte."""

    def __init__(self, problem: WorkerProblem, cfg: SchedulerConfig, *,
                 pool: Optional[LambdaPool] = None,
                 start_time: float = 0.0):
        self.problem = problem
        self.cfg = cfg
        self.pool = pool if pool is not None else LambdaPool(cfg.pool)
        self.start_time = start_time
        W, d = cfg.n_workers, problem.n_features
        dt = getattr(problem, "dtype", jnp.float32)
        # second-order problems (problem.second_order = True, e.g.
        # newton_sketch) route rounds through run_round_newton: workers
        # send coded Hessian-sketch blocks, the master takes a Newton
        # step, and the ADMM x/u/omega machinery below sits unused.
        self._second_order = bool(getattr(problem, "second_order", False))
        if self._second_order and cfg.mode == "async_":
            raise ValueError(
                "async_ mode is not supported for second-order problems "
                "(the Newton step needs a consistent decoded Hessian)")
        if self._second_order and cfg.compress != "none":
            raise ValueError(
                "compression is not supported for second-order problems "
                "(lossy sketch blocks break the exact-decode guarantee)")
        # replicated mode: W physical slots host W/r LOGICAL workers; the r
        # replicas of a logical worker solve the SAME shard (deterministic
        # FISTA -> identical results), so first-responder-wins is exact
        # under any r-1 stragglers/failures (repro.core.coding semantics).
        # Second-order replicated mode keeps W logical workers: sketch
        # redundancy replaces physical replication (the master decodes the
        # exact Hessian from the first W-(r-1) responses; every worker
        # does useful work).
        self.repl = (cfg.replication
                     if cfg.mode == "replicated" and not self._second_order
                     else 1)
        if (self._second_order and cfg.mode == "replicated"
                and getattr(problem, "redundancy", 0) < cfg.replication - 1):
            raise ValueError(
                f"replicated mode with replication={cfg.replication} needs "
                f"problem redundancy >= {cfg.replication - 1} spare sketch "
                f"blocks (got {getattr(problem, 'redundancy', 0)})")
        if (self._second_order and cfg.mode == "drop_slowest"
                and int(cfg.drop_frac * W) > getattr(problem,
                                                     "redundancy", 0)):
            raise ValueError(
                f"drop_slowest would drop {int(cfg.drop_frac * W)} blocks "
                f"but the sketch plan only over-provisions "
                f"{getattr(problem, 'redundancy', 0)} — raise the "
                f"problem's redundancy or lower drop_frac")
        if W % self.repl:
            raise ValueError("replicated mode needs r | W")
        self.n_logical = W // self.repl
        WL = self.n_logical
        self.x = jnp.zeros((WL, d), dt)
        self.u = jnp.zeros((WL, d), dt)
        self.z = jnp.zeros((d,), dt)
        self.z_prev = jnp.zeros((d,), dt)
        self.omega_table = jnp.zeros((WL, d), dt)          # last ω per slot
        self.q_table = np.zeros((WL,), np.float64)
        self.rho = cfg.admm.rho0
        self.k = 0
        self.sim_time = 0.0
        self.history: List[RoundMetrics] = []
        self.n_respawns = 0

        if cfg.fanin not in ("flat", "tree"):
            raise ValueError(f"fanin must be 'flat' or 'tree', "
                             f"got {cfg.fanin!r}")
        if cfg.engine not in ("loop", "batched"):
            raise ValueError(f"engine must be 'loop' or 'batched', "
                             f"got {cfg.engine!r}")
        self._engine_batched = cfg.engine == "batched"
        if self._engine_batched and self._second_order:
            if not callable(getattr(problem, "round_messages_all", None)):
                raise ValueError(
                    f"engine='batched' needs the second-order problem to "
                    f"implement round_messages_all (the stacked-block "
                    f"path); {type(problem).__name__} does not")
        elif self._engine_batched and not (
                callable(getattr(problem, "solve_all", None))
                and getattr(problem, "supports_batched", lambda: True)()):
            raise ValueError(
                f"engine='batched' needs the problem to implement the "
                f"batched contract (solve_all / _loss_value_and_grad — see "
                f"repro.problems.BatchedShardProblem); "
                f"{type(problem).__name__} does not")
        if cfg.kernel not in ("xla", "pallas"):
            raise ValueError(f"kernel must be 'xla' or 'pallas', "
                             f"got {cfg.kernel!r}")
        self._kernel_pallas = cfg.kernel == "pallas"
        if self._kernel_pallas and self._second_order:
            raise ValueError(
                "kernel='pallas' fuses the FISTA loss/grad and z-update; "
                "second-order problems have neither — use kernel='xla'")
        if (self._kernel_pallas and self._engine_batched
                and not getattr(problem, "supports_kernel", lambda: False)()):
            raise ValueError(
                f"kernel='pallas' with engine='batched' needs the problem "
                f"to accept solve_all(..., kernel=...) (see "
                f"repro.problems.BatchedShardProblem.supports_kernel); "
                f"{type(problem).__name__} does not")
        self._z_nnz = -1
        # message size: the paper sends (q, ω) — d+1 f32 dense; the codec
        # shrinks it (and lossy-codes the ω the master sees) when
        # compression is on
        self.codec = OmegaCodec(cfg.compress, d, topk_frac=cfg.topk_frac,
                                qsgd_bits=cfg.qsgd_bits)
        self.wire_d = cfg.wire_d or d
        if self._second_order:
            # uplink = the coded block message [g_k | vec(Gram_k)] plus
            # the q slot every message carries (d+d²+1 f32 dense)
            self.msg_bytes = 4 * (int(problem.message_floats) + 1)
        else:
            self.msg_bytes = message_bytes(cfg.compress, self.wire_d,
                                           topk_frac=cfg.topk_frac,
                                           qsgd_bits=cfg.qsgd_bits)
        self.meter = BillingMeter(cfg.billing)
        self._billed_spawns = 0
        self.autoscaler: Optional[Autoscaler] = None
        self.pool.spawn_bulk(list(range(W)), at=start_time)
        self.sim_time = max(w.ready_at for w in self.pool.workers.values())
        self.cold_starts = {w.wid: w.cold_start_s
                            for w in self.pool.workers.values()}
        self._bill_spawns()
        # the early workers idle (billed) until the whole fleet is up,
        # and the coordinator runs from the job's admission instant
        for w in self.pool.workers.values():
            self.meter.record_duration(self.sim_time - w.ready_at)
        self.meter.record_master(self.sim_time - start_time)

    # -- billing --------------------------------------------------------
    def _bill_spawns(self):
        """Meter invocation starts (and, optionally, their init time)."""
        log = self.pool.spawn_log
        new = log[self._billed_spawns:]
        if new:
            self.meter.record_requests(len(new))
            if self.cfg.billing.bill_cold_init:
                self.meter.record_duration(sum(s for s, _ in new))
            self._billed_spawns = len(log)

    def _logical(self, wid: int) -> int:
        return wid // self.repl

    # ------------------------------------------------------------------
    def _maybe_respawn(self, wid: int) -> float:
        """Returns extra delay if slot wid had to be respawned this round."""
        w = self.pool.workers[wid]
        lifetime_hit = (self.sim_time > w.deadline
                        - self.cfg.respawn_before_deadline_s)
        # short-circuit preserved: the failure roll is only drawn when the
        # lifetime check passes (seed-equivalence anchor)
        failed = not lifetime_hit and self.pool.roll_failure()
        if not (lifetime_hit or failed):
            return 0.0
        if failed:
            # a CRASHED invocation's sandbox is torn down by the provider,
            # not kept warm — only clean lifetime exits reach the pool
            self.pool.crash(wid)
        self.pool.spawn_bulk([wid], at=self.sim_time)
        self.n_respawns += 1
        # the replacement regenerates its shard and reloads (z, rho, x, u):
        # x,u live in self.x/self.u (checkpointed state), so nothing is lost
        return self.pool.workers[wid].cold_start_s

    def _worker_pass(self, wid: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                              float, int, float]:
        """One Algorithm-2 body for physical slot wid: returns (omega, q,
        t_comp, inner_iters, extra_delay).  In replicated mode the r slots
        of a group solve the same LOGICAL subproblem (same shard, same
        x/u -> identical deterministic result)."""
        lw = self._logical(wid)
        WL = self.n_logical
        extra = self._maybe_respawn(wid)
        if lw not in self._round_results:
            r = self.x[lw] - self.z
            u_new = self.u[lw] + r
            q = float(jnp.vdot(r, r))
            x_new, iters = self.problem.solve(
                lw, WL, self.x[lw], self.z, u_new, self.rho)
            # the master's (possibly lossy) view of ω = x + u: replicas of
            # a logical worker share one codec slot, so first-responder-
            # wins stays exact under compression
            omega = self.codec.encode(lw, x_new + u_new)
            self._round_results[lw] = (omega, q, iters, x_new, u_new)
        omega, q, iters, _, _ = self._round_results[lw]
        return omega, q, iters, extra

    def _commit_xu(self, lw: int):
        _, _, _, x_new, u_new = self._round_results[lw]
        self.x = self.x.at[lw].set(x_new)
        self.u = self.u.at[lw].set(u_new)

    def _all_worker_passes(self) -> Tuple[np.ndarray, np.ndarray,
                                          jnp.ndarray, np.ndarray]:
        """The batched engine's worker phase: every Algorithm-2 body in
        ONE device call (``problem.solve_all``), plus vectorized q/ω.

        The respawn checks run first, in wid order, so the pool RNG
        consumes the exact draw sequence the loop engine does.  Returns
        (q (WL,), inner_iters (WL,), encoded ω (WL, d), extras (W,));
        the committed (x, u) batch is stashed on ``self._batched_xu``
        for the round's commit step."""
        W = self.cfg.n_workers
        WL = self.n_logical
        extras = np.zeros(W)
        with spans.span("round.respawn"):
            for wid in range(W):
                extras[wid] = self._maybe_respawn(wid)
        with spans.span("round.solve"):
            r = self.x - self.z[None, :]
            u_new = self.u + r
            q = jnp.einsum("wd,wd->w", r, r)
            with spans.span("round.q.wait"):
                q = np.asarray(q, np.float64)
            # the kernel kwarg is only passed on the pallas path, so
            # third-party solve_all overrides with the pre-kernel
            # signature keep working under the default config
            if self._kernel_pallas:
                xs_new, iters = self.problem.solve_all(
                    self.x, u_new, self.z, self.rho, kernel="pallas")
            else:
                xs_new, iters = self.problem.solve_all(self.x, u_new,
                                                       self.z, self.rho)
            omegas = xs_new + u_new
            if self.codec.method != "none":
                # the codec is stateful per logical slot (delta error
                # feedback), so compression keeps a per-slot encode loop
                # — the solve batching still amortizes the W dispatches
                omegas = jnp.stack([self.codec.encode(lw, omegas[lw])
                                    for lw in range(WL)])
        self._batched_xu = (xs_new, u_new)
        return q, np.asarray(iters, np.int64), omegas, extras

    def _master_z_update(self, omega_bar: jnp.ndarray, q_sum: float,
                         n_eff: int, adapt_rho: bool = True):
        r_norm = float(np.sqrt(q_sum))
        # dual residual: Boyd's consensus form s = rho*sqrt(W)*||dz|| (the
        # stacked-problem dual residual).  The paper's Algorithm 1 prints
        # s = rho*||dz||; we keep Boyd's normalization — it balances the
        # rho-adaptation correctly (the paper-literal form overshoots rho
        # and stalls the dual residual; EXPERIMENTS.md §Paper).
        lam = getattr(self.problem, "h_l1_lam", None)
        if (self._kernel_pallas and lam is not None
                and omega_bar.dtype == jnp.float32):
            # fused path: z = S(ω̄; lam/(W·rho)), ||dz||² and nnz(z) in one
            # pass (kernels/soft_threshold).  prox_l1(v, t, lam) IS
            # soft_threshold(v, lam·t), so this is the same update; f64
            # paper runs keep the jnp path (the kernel is f32).
            from repro.kernels import ops
            thr = float(lam) / (n_eff * self.rho)
            z_new, ssq, nnz = ops.fused_z_update(omega_bar, self.z, thr)
            with spans.span("round.master.wait"):
                ssq, nnz = float(ssq), int(nnz)
            s_norm = float(self.rho * np.sqrt(ssq) * np.sqrt(n_eff))
            self._z_nnz = nnz
        else:
            z_new = self.problem.prox_h(omega_bar, 1.0 / (n_eff * self.rho))
            s_norm = (self.rho * jnp.linalg.norm(z_new - self.z)
                      * np.sqrt(n_eff))
            with spans.span("round.master.wait"):
                s_norm = float(s_norm)
            self._z_nnz = -1
        self.z_prev, self.z = self.z, z_new
        rho_old = self.rho
        if adapt_rho:
            rho = admm.new_penalty(jnp.float32(self.rho), r_norm, s_norm,
                                   self.cfg.admm)
            with spans.span("round.rho.wait"):
                self.rho = float(rho)
        if self.rho != rho_old:
            # broadcast of the new penalty: workers rescale their scaled
            # duals u = y/rho (Boyd §3.4.1; see core.admm.new_penalty)
            self.u = self.u * (rho_old / self.rho)
        return r_norm, s_norm

    # ------------------------------------------------------------------
    def run_round(self) -> RoundMetrics:
        """One synchronous-family round (sync / drop_slowest / replicated)."""
        cfg = self.cfg
        W = cfg.n_workers
        t_comp = np.zeros(W)
        t_comm = np.zeros(W)
        inner = np.zeros(W, np.int64)
        round_start = self.sim_time
        self._round_results: Dict[int, Tuple] = {}
        codec_snap = self.codec.snapshot()

        batched = self._engine_batched
        fresh: Dict[int, Tuple[jnp.ndarray, float]] = {}
        extras = np.zeros(W)
        rec = spans.current()
        ls_trials = tol_iters = ls_slots = None
        if batched:
            q_all, iters_all, omegas, extras = self._all_worker_passes()
            lanes = np.arange(W) // self.repl
            inner[:] = iters_all[lanes]
            counters = {} if rec is None else rec.counters
            ls_trials, tol_iters = (
                None if counters.get(name) is None
                else np.asarray(counters[name], np.int64)[lanes]
                for name in ("ls_trials", "tol_iters"))
            # the r replicas of a logical worker each make its solve's
            # evaluations, as each carries its trials
            if counters.get("ls_slots") is not None:
                ls_slots = int(counters["ls_slots"]) * self.repl
        else:
            with spans.span("round.solve"):
                for wid in range(W):
                    omega, q, it, extra = self._worker_pass(wid)
                    inner[wid] = it
                    extras[wid] = extra
                    fresh[wid] = (omega, q)

        with spans.span("round.timing"):
            timing_iters = inner.copy()
            if cfg.iter_smoothing:
                timing_iters[:] = max(int(np.median(inner)), 1)
            arrivals = []
            # z is broadcast DENSE (only the ω uplink is compressed)
            rx = self.pool.comm_time(4 * self.wire_d)
            tx = self.pool.comm_time(self.msg_bytes)
            for wid in range(W):
                lw = self._logical(wid)
                tc = self.pool.compute_time(
                    self.pool.workers[wid], int(timing_iters[wid]),
                    self.problem.n_samples(lw, self.n_logical))
                t_comp[wid] = tc
                t_comm[wid] = rx + tx                  # rx z + tx ω
                arrivals.append((round_start + extras[wid] + rx + tc + tx,
                                 wid))

            # -- which messages does the master wait for? -------------------
            if cfg.mode == "drop_slowest":
                n_wait = W - int(cfg.drop_frac * W)
                waited = sorted(arrivals)[:n_wait]
            elif cfg.mode == "replicated":
                # first responder per FRS group (replicas are exact copies)
                waited, seen = [], set()
                for t, wid in sorted(arrivals):
                    g = self._logical(wid)
                    if g not in seen:
                        seen.add(g)
                        waited.append((t, wid))
            else:
                waited = sorted(arrivals)

        # update the running ω table (stale-cache semantics: unwaited slots
        # keep their previous ω, so the mean stays over all workers); local
        # x/u always advance — the paper's workers keep computing even when
        # the master does not wait for them.  Undelivered messages must
        # not advance the codec's shared view either (their content rides
        # in a later delta instead of being smuggled in for free).
        with spans.span("round.commit"):
            waited_lws = {self._logical(wid) for _, wid in waited}
            self.codec.rollback_except(codec_snap, waited_lws)
            if batched:
                # vectorized table update + wholesale commit: one scatter
                # for the waited slots instead of W per-row device ops
                # (the unwaited slots keep their stale ω, as in the loop
                # path)
                idx = np.fromiter(sorted(waited_lws), np.int64)
                jidx = jnp.asarray(idx)
                self.omega_table = self.omega_table.at[jidx].set(
                    omegas[jidx])
                self.q_table[idx] = q_all[idx]
                self.x, self.u = self._batched_xu
            else:
                for _, wid in waited:
                    om, q = fresh[wid]
                    lw = self._logical(wid)
                    self.omega_table = self.omega_table.at[lw].set(om)
                    self.q_table[lw] = q
                for lw in self._round_results:
                    self._commit_xu(lw)

        # -- scheduler fan-in timing (Fig 5 cliff vs the tree fix) ----------
        with spans.span("round.fanin"):
            master_done = fanin_drain(waited, cfg.fanin, self.pool,
                                      cfg.tree, self.msg_bytes, W)

        with spans.span("round.master"):
            omega_bar = jnp.mean(self.omega_table, axis=0)
            q_sum = float(self.q_table.sum())
            r_norm, s_norm = self._master_z_update(omega_bar, q_sum,
                                                   self.n_logical)

        with spans.span("round.bill"):
            bcast = self.pool.comm_time(4 * self.wire_d)
            self.sim_time = master_done + bcast
            round_wall = self.sim_time - round_start
            t_idle = round_wall - t_comp
            self.k += 1

            # the bill: every worker holds its memory for the whole round
            # (idle time at the barrier is billed time — the serverless
            # cost story), every omega uplink + z downlink crosses the
            # boundary, and the coordinator runs throughout.  Mid-round
            # respawn init spans (extras) are carved out of the respawned
            # workers' billed time — init billing is _bill_spawns' job,
            # gated on bill_cold_init — while the OTHER workers' barrier
            # wait on those respawns stays billed.
            self._bill_spawns()
            self.meter.record_duration(round_wall * W - float(extras.sum()))
            self.meter.record_master(round_wall)
            self.meter.record_bytes(W * (self.msg_bytes + 4 * self.wire_d))

            thresh = np.quantile([t for t, _ in arrivals], 0.9)
            m = RoundMetrics(
                k=self.k, sim_time=self.sim_time, r_norm=r_norm,
                s_norm=s_norm, rho=self.rho, t_comp=t_comp, t_comm=t_comm,
                t_idle=t_idle, inner_iters=inner,
                n_respawns=self.n_respawns,
                slowest10=np.array([t >= thresh for t, _ in arrivals]),
                round_wall_s=round_wall,
                t_fanin_wait=master_done - max(t for t, _ in waited),
                cost_usd=self.meter.total_usd(), n_workers=W,
                z_nnz=self._z_nnz,
                span_s=None if rec is None else rec.span_s,
                ls_trials=ls_trials, tol_iters=tol_iters,
                ls_slots=ls_slots)
            self.history.append(m)
        return m

    # ------------------------------------------------------------------
    def run_round_newton(self) -> RoundMetrics:
        """One second-order round (``problem.second_order = True``):
        coded Hessian-sketch block messages up, a globalized Newton step
        at the master (see ``problems/newton_sketch.py``; the block
        algebra is ``core/sketch.py``).

        Reuses the sync-family timing / barrier / fan-in / billing
        machinery verbatim; the barrier modes map onto sketch semantics:

        * ``sync`` — wait for all W block messages;
        * ``drop_slowest`` — ignore-extra-blocks: proceed with the
          fastest ``W - drop_frac·W`` blocks (the over-provisioned
          sketch keeps >= sketch_dim rows as long as the problem's
          ``redundancy`` covers the drop);
        * ``replicated`` — decode-from-any-subset: wait for the first
          ``W - (replication-1)`` responses and decode the EXACT
          full-sketch Hessian via ``coding.decode_coeffs`` (sketch
          redundancy replaces physical replication, so there are W
          logical workers and every response is useful work).
        """
        cfg = self.cfg
        W = cfg.n_workers
        t_comp = np.zeros(W)
        t_comm = np.zeros(W)
        inner = np.zeros(W, np.int64)
        round_start = self.sim_time

        # respawn checks first, in wid order (same pool-RNG draw sequence
        # for the loop and batched engines -> identical traces)
        extras = np.zeros(W)
        for wid in range(W):
            extras[wid] = self._maybe_respawn(wid)
        if self._engine_batched:
            msgs, iters_all = self.problem.round_messages_all(self.z, W)
        else:
            out = [self.problem.round_message(wid, W, self.z)
                   for wid in range(W)]
            msgs = [m for m, _ in out]
            iters_all = [it for _, it in out]
        for wid in range(W):
            inner[wid] = int(iters_all[wid])

        timing_iters = inner.copy()
        if cfg.iter_smoothing:
            timing_iters[:] = max(int(np.median(inner)), 1)
        rx = self.pool.comm_time(4 * self.wire_d)      # dense z downlink
        tx = self.pool.comm_time(self.msg_bytes)       # block message up
        arrivals = []
        for wid in range(W):
            tc = self.pool.compute_time(
                self.pool.workers[wid], int(timing_iters[wid]),
                self.problem.n_samples(wid, W))
            t_comp[wid] = tc
            t_comm[wid] = rx + tx
            arrivals.append((round_start + extras[wid] + rx + tc + tx,
                             wid))

        if cfg.mode == "drop_slowest":
            n_wait = W - int(cfg.drop_frac * W)
            waited = sorted(arrivals)[:n_wait]
        elif cfg.mode == "replicated":
            waited = sorted(arrivals)[:W - (cfg.replication - 1)]
        else:
            waited = sorted(arrivals)

        master_done = fanin_drain(waited, cfg.fanin, self.pool, cfg.tree,
                                  self.msg_bytes, W)

        responders = sorted(wid for _, wid in waited)
        z_new, r_norm, s_norm = self.problem.master_step(
            self.z, np.stack([np.asarray(msgs[w]) for w in responders]),
            np.asarray(responders, np.int64), W)
        self.z_prev, self.z = self.z, jnp.asarray(z_new, self.z.dtype)

        bcast = self.pool.comm_time(4 * self.wire_d)
        self.sim_time = master_done + bcast
        round_wall = self.sim_time - round_start
        t_idle = round_wall - t_comp
        self.k += 1

        # billing: identical story to run_round — every worker holds its
        # memory for the whole round, every block uplink + z downlink
        # crosses the boundary, the coordinator runs throughout
        self._bill_spawns()
        self.meter.record_duration(round_wall * W - float(extras.sum()))
        self.meter.record_master(round_wall)
        self.meter.record_bytes(W * (self.msg_bytes + 4 * self.wire_d))

        thresh = np.quantile([t for t, _ in arrivals], 0.9)
        rec = spans.current()
        m = RoundMetrics(
            k=self.k, sim_time=self.sim_time, r_norm=r_norm, s_norm=s_norm,
            rho=self.rho, t_comp=t_comp, t_comm=t_comm, t_idle=t_idle,
            inner_iters=inner, n_respawns=self.n_respawns,
            slowest10=np.array([t >= thresh for t, _ in arrivals]),
            round_wall_s=round_wall,
            t_fanin_wait=master_done - max(t for t, _ in waited),
            cost_usd=self.meter.total_usd(), n_workers=W, z_nnz=-1,
            span_s=None if rec is None else rec.span_s)
        self.history.append(m)
        return m

    # ------------------------------------------------------------------
    def run_async(self, max_updates: int,
                  on_round: Optional[Callable] = None) -> List[RoundMetrics]:
        """Bounded-staleness async ADMM: master updates z every
        ``async_batch`` arrivals; workers beyond ``staleness_bound`` block.
        ``on_round`` fires once per z-update, like the sync family."""
        cfg = self.cfg
        W = cfg.n_workers
        z_version = 0
        worker_version = np.zeros(W, np.int64)
        pending: List[Tuple[float, int]] = []      # (arrival time, wid)
        since_update = 0

        def launch(wid: int, at: float):
            self._round_results = {}
            omega, q, it, extra = self._worker_pass(wid)
            self._commit_xu(self._logical(wid))
            lw = self._logical(wid)
            tc = self.pool.compute_time(
                self.pool.workers[wid], it,
                self.problem.n_samples(lw, self.n_logical))
            rx = self.pool.comm_time(4 * self.wire_d)   # dense z downlink
            tx = self.pool.comm_time(self.msg_bytes)    # compressed ω up
            arrive = at + extra + rx + tc + tx
            heapq.heappush(pending, (arrive, wid, float(q)))
            self._async_omega[wid] = omega
            self._async_tcomp[wid] = tc
            self._async_iters[wid] = it
            # one invocation: billed for its active span + its wire
            # bytes; a respawn's init (extra) is carved out — init
            # billing is _bill_spawns' job, gated on bill_cold_init
            self.meter.record_duration(arrive - at - extra)
            self.meter.record_bytes(self.msg_bytes + 4 * self.wire_d)

        self._async_omega: Dict[int, jnp.ndarray] = {}
        self._async_tcomp: Dict[int, float] = {}
        self._async_iters: Dict[int, int] = {}
        blocked: List[int] = []
        master_billed_to = self.sim_time

        for wid in range(W):
            launch(wid, self.pool.workers[wid].ready_at)

        updates = 0
        while updates < max_updates and pending:
            arrive, wid, q = heapq.heappop(pending)
            self.sim_time = max(self.sim_time, arrive)
            self.omega_table = self.omega_table.at[wid].set(
                self._async_omega[wid])
            self.q_table[wid] = q
            since_update += 1

            if since_update >= cfg.async_batch:
                since_update = 0
                omega_bar = jnp.mean(self.omega_table, axis=0)
                # FIXED penalty in async mode: the bounded-staleness
                # analyses this path follows (Zhang & Kwok '14, Chang et
                # al. '16) assume a constant rho, and residual balancing
                # here would act on a STALE r (the q-cache lags z) against
                # a per-micro-update s — spurious rho changes then rescale
                # u under in-flight omegas computed with the old rho, which
                # destabilizes the run precisely near convergence.
                r_norm, s_norm = self._master_z_update(
                    omega_bar, float(self.q_table.sum()), W,
                    adapt_rho=False)
                z_version += 1
                updates += 1
                self.k += 1
                self._bill_spawns()
                self.meter.record_master(self.sim_time - master_billed_to)
                master_billed_to = self.sim_time
                t_comp = np.array([self._async_tcomp.get(i, 0.0)
                                   for i in range(W)])
                m = RoundMetrics(
                    k=self.k, sim_time=self.sim_time, r_norm=r_norm,
                    s_norm=s_norm, rho=self.rho, t_comp=t_comp,
                    t_comm=np.zeros(W), t_idle=np.zeros(W),
                    inner_iters=np.array([self._async_iters.get(i, 0)
                                          for i in range(W)]),
                    n_respawns=self.n_respawns,
                    slowest10=np.zeros(W, bool),
                    cost_usd=self.meter.total_usd(), n_workers=W,
                    z_nnz=self._z_nnz)
                self.history.append(m)
                if on_round:
                    on_round(m)
                # unblock stale workers: the z-update IS the rebroadcast —
                # every blocked worker receives the fresh z and relaunches
                # at the current version.  (The bound is re-checked at each
                # relaunch; a worker can never run ahead of the rebroadcast
                # by more than one in-flight solve.)
                for bw in blocked:
                    worker_version[bw] = z_version
                    launch(bw, self.sim_time)
                blocked.clear()

            # relaunch this worker against the current z
            if z_version - worker_version[wid] > cfg.staleness_bound:
                blocked.append(wid)
            else:
                worker_version[wid] = z_version
                launch(wid, max(arrive, self.sim_time))
        return self.history

    # ------------------------------------------------------------------
    def step(self, on_round: Optional[Callable] = None
             ) -> Tuple[RoundMetrics, bool]:
        """Drive ONE synchronous-family round and everything that hangs
        off it — the callback, the convergence check, the autoscaler —
        then hand control back.  Returns (metrics, done).

        This is the reentrancy point the multi-tenant cluster
        (``runtime/cluster.py``) needs: many schedulers interleave by
        each being stepped one round at a time in event order, with no
        state crossing between calls.  ``solve()`` is exactly a loop
        over ``step()``, so the single-experiment path is unchanged."""
        cfg = self.cfg
        if cfg.mode == "async_":
            raise ValueError("step() drives the synchronous-family modes; "
                             "async_ paces itself per-arrival (run_async)")
        if cfg.autoscale.policy != "off" and self.autoscaler is None:
            self.autoscaler = Autoscaler(cfg.autoscale, quantum=self.repl)
        # the round's record stays open until the "round" span has closed,
        # so the metrics this returns (and history holds) carry every span
        with spans.record(), spans.span("round"):
            m = (self.run_round_newton() if self._second_order
                 else self.run_round())
            if on_round:
                on_round(m)
            done = (m.r_norm <= cfg.admm.eps_primal
                    and m.s_norm <= cfg.admm.eps_dual)
            if not done and self.autoscaler is not None:
                self.autoscaler.observe(
                    round_wall_s=m.round_wall_s,
                    t_comp_mean=float(m.t_comp.mean()),
                    t_fanin_wait=m.t_fanin_wait)
                new_w = self.autoscaler.decide(self.cfg.n_workers)
                if new_w is not None:
                    self.rescale(new_w)
        return m, bool(done)

    def solve(self, *, max_rounds: Optional[int] = None,
              on_round: Optional[Callable] = None) -> jnp.ndarray:
        cfg = self.cfg
        K = max_rounds or cfg.admm.max_iters
        if cfg.mode == "async_":
            self.run_async(K, on_round=on_round)
            return self.z
        for _ in range(K):
            _, done = self.step(on_round)
            if done:
                break
        return self.z

    # -- elastic rescale ----------------------------------------------------
    def rescale(self, new_w: int):
        """Change the worker count mid-run (the paper's elasticity claim).

        Data re-sharding is free (pure regeneration); x/u are re-seeded from
        the consensus z — warm restarts keep ADMM convergent (z is the
        authoritative state; per-worker duals restart at 0)."""
        d = self.problem.n_features
        if new_w % self.repl:
            raise ValueError("new worker count must keep r | W")
        old_w = self.cfg.n_workers
        self.cfg = dataclasses.replace(self.cfg, n_workers=new_w)
        self.n_logical = new_w // self.repl
        WL = self.n_logical
        dt = getattr(self.problem, "dtype", jnp.float32)
        self.x = jnp.broadcast_to(self.z, (WL, d)).astype(dt)
        self.u = jnp.zeros((WL, d), dt)
        self.omega_table = jnp.broadcast_to(self.z, (WL, d)).astype(dt).copy()
        self.q_table = np.zeros((WL,), np.float64)
        self.codec.reset()
        # shrink: retired slots hand their sandboxes to the provider's
        # keep-alive pool (free respawn capacity for the survivors)
        if new_w < old_w:
            self.pool.retire(list(range(new_w, old_w)), at=self.sim_time)
        t0 = self.sim_time
        self.pool.spawn_bulk(list(range(new_w)), at=self.sim_time)
        self.sim_time = max(w.ready_at for w in self.pool.workers.values())
        self._bill_spawns()
        # the respawn-wave stall is billed like the __init__ ramp: ready
        # workers idle until the slowest spawn, the coordinator runs on
        for w in self.pool.workers.values():
            self.meter.record_duration(self.sim_time - w.ready_at)
        self.meter.record_master(self.sim_time - t0)
