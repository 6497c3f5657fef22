"""Reduction of a JAX profiler trace of the measured window to numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  The window is the host span the
benchmark opens around it (``harness.WINDOW_SPAN``).  On a TPU each chip
is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation run and whose line ``XLA Modules`` holds one event per program
run, named after the jitted function (``jit_<name>(<id>)``).

* busy time: the union of a chip's operation intervals inside the window,
  averaged over the chips that ran anything;
* per-program device time: the durations of each program's module events
  inside the window, by program name;
* the operations that took the most time, by self time (an operation
  such as a ``while`` loop holds the operations of its body on the same
  line), and the longest idle gaps, each named by the innermost host span
  open at its middle on the thread that ran the window.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, List, Sequence, Tuple

SOLVE_PROGRAM = "jit_run_all"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
TOP = 10

Interval = Tuple[float, float]


class TraceError(RuntimeError):
    """The trace lacks what the reduction has to find."""


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    program_s: Dict[str, float]
    solve_program: str
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self) -> dict:
        return {"device_ops": [list(t) for t in self.top_ops[:TOP]],
                "idle_gaps": [list(t) for t in self.idle_gaps[:TOP]]}


def find_xplane(tracedir: str) -> str:
    found = glob.glob(os.path.join(tracedir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise TraceError(f"expected one .xplane.pb under {tracedir}, "
                         f"found {found}")
    return found[0]


def program_name(module_event_name: str) -> str:
    """``jit_run_all(123)`` -> ``jit_run_all``."""
    return re.sub(r"\(\d+\)$", "", module_event_name.strip())


def clip(events, t0: float, t1: float) -> List[Tuple[str, float, float]]:
    out = []
    for name, start, end in events:
        s, e = max(start, t0), min(end, t1)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Each name's total duration less that of the events nested in it."""
    out: Dict[str, float] = {}
    stack: List[list] = []

    def close():
        name, s, e, inner = stack.pop()
        out[name] = out.get(name, 0.0) + (e - s) - inner
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][2] <= s:
            close()
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return out


def op_label(hlo: str) -> str:
    """``%fusion.51 = f32[6000000]{0:T(1024)} fusion(...)`` ->
    ``fusion.51 f32[6000000] fusion``; other names are kept, cut to 80."""
    m = re.match(r"%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])\S* (?:.*?\) )?"
                 r"([a-z][a-z0-9_-]*)\(", hlo)
    return " ".join(m.groups()) if m else hlo[:80]


def gaps(busy: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def innermost(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    """Name of the shortest host span open at ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host"


@dataclasses.dataclass
class Events:
    """The events the reduction reads, in nanoseconds, each (name, start,
    end): host spans by thread, and per device its operations and its
    program runs."""
    host: Dict[str, List[Tuple[str, float, float]]]
    ops: Dict[str, List[Tuple[str, float, float]]]
    modules: Dict[str, List[Tuple[str, float, float]]]


def read_events(path: str) -> Events:
    """Events of an ``.xplane.pb`` (or a gzip of one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    host, ops, modules = {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == OPS_LINE:
                    ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.setdefault(f"{plane.name}/{line.name}", []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
    return Events(host=host, ops=ops, modules=modules)


def reduce(events: Events, window_span: str,
           solve_program: str = SOLVE_PROGRAM) -> Summary:
    windows = [(thread, s, e) for thread, spans in events.host.items()
               for name, s, e in spans if name == window_span]
    if len(windows) != 1:
        raise TraceError(f"expected one host span {window_span!r}, found "
                         f"{len(windows)}")
    thread, t0, t1 = windows[0]
    # the thread that ran the window: what it was doing names each gap
    host = [h for h in events.host[thread] if h[1] < t1 and h[2] > t0
            and h[0] != window_span]
    used = {dev: clip(evs, t0, t1) for dev, evs in events.ops.items()}
    used = {dev: evs for dev, evs in used.items() if evs}
    if not used:
        raise TraceError("no device operation ran inside the window")

    busy_ns, op_ns, prog_ns = 0.0, {}, {}
    idle: List[Interval] = []
    for dev, evs in used.items():
        merged = union([(s, e) for _, s, e in evs])
        busy_ns += sum(e - s for s, e in merged)
        for name, t in self_times(evs).items():
            key = op_label(name)
            op_ns[key] = op_ns.get(key, 0.0) + t
        for name, s, e in clip(events.modules.get(dev, []), t0, t1):
            key = program_name(name)
            prog_ns[key] = prog_ns.get(key, 0.0) + (e - s)
        idle.extend(gaps(merged, t0, t1))
    n = len(used)
    program_s = {k: v * 1e-9 / n for k, v in prog_ns.items()}
    if solve_program not in program_s:
        raise TraceError(f"program {solve_program!r} not found in the window; "
                         f"programs: {sorted(program_s)}")
    top = sorted(((k, v * 1e-9 / n) for k, v in op_ns.items()),
                 key=lambda kv: -kv[1])
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    named = [(innermost(host, 0.5 * (s + e)), (e - s) * 1e-9)
             for s, e in longest]
    return Summary(window_s=(t1 - t0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   program_s=program_s, solve_program=solve_program,
                   top_ops=top[:TOP], idle_gaps=named)


def summarize(path: str, window_span: str,
              solve_program: str = SOLVE_PROGRAM) -> Summary:
    return reduce(read_events(path), window_span, solve_program)
