"""Host spans and counters of one scheduler round.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` (a profiler
session, when one runs, shows it on the same clock as the device's
operations) and adds its ``time.perf_counter`` seconds to the round
record open on this thread, if one is.  ``count(name, value)`` stores a
counter on that record.  ``Scheduler.step`` opens the record
(``record()``) and ``RoundMetrics`` carries its tables (``span_s``,
``ls_trials``, ``tol_iters``, ``ls_slots``).  Without a profiler session a
span costs two clock reads and a dictionary update.

A span name ends in ``.wait`` exactly when the span covers a blocking
device-to-host read.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterator, Optional

import jax


class Record:
    """Seconds per span name and counters of one round."""

    def __init__(self):
        self.span_s: Dict[str, float] = {}
        self.counters: Dict[str, Any] = {}


_local = threading.local()


def current() -> Optional[Record]:
    """The round record open on this thread, or None."""
    return getattr(_local, "record", None)


@contextlib.contextmanager
def record() -> Iterator[Record]:
    """Open a fresh round record on this thread until the block ends."""
    outer, rec = current(), Record()
    _local.record = rec
    try:
        yield rec
    finally:
        _local.record = outer


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    rec = current()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if rec is not None:
            rec.span_s[name] = (rec.span_s.get(name, 0.0)
                                + time.perf_counter() - t0)


def count(name: str, value: Any) -> None:
    """Store ``value`` under ``name`` on the open record, if any."""
    rec = current()
    if rec is not None:
        rec.counters[name] = value
