"""Host spans and per-batch phase records of the readout server.

``Stages.span(stage, batch)`` times one call of one server stage on the
server's injected clock: per stage it keeps the seconds, the calls and the
longest single call (``report()["stages"]``). While the JAX profiler is
recording, the span is also a ``jax.profiler.TraceAnnotation`` named
``readout.<stage>`` with the batch id as a stat, so the profile places it
on the same clock as the device's operations. With the profiler off a span
costs two clock reads and one check that no trace is active; a process
that never imported JAX (the host backend) never touches it.

``BatchRing`` keeps the stage timestamps of the newest ``capacity``
drained batches in one preallocated array, and summarizes them into the
phases of ``report()["latency"]["phases"]``:

    staging       coalesced -> launched    host staging and dispatch
    collect_wait  launched  -> collect     the device step, then the wait
                                           for a poll to collect the batch
    drain         collect   -> drained     materialize and fold answers
    handoff       drained   -> delivered   until poll()/flush() returns them

The first three add up to the batch's service time (coalesced ->
drained) exactly: each boundary is one clock reading shared by both
phases it separates.
"""
from __future__ import annotations

import collections
import math
import sys
from typing import Callable, Dict, Optional

import numpy as np

SPAN_PREFIX = "readout."

_annotation_cls = None


def _annotation(stage: str, batch: int):
    """A TraceAnnotation to enter while the profiler records, else None.
    Looks JAX up only once it is loaded: a span never imports it."""
    global _annotation_cls
    if _annotation_cls is None:
        mod = sys.modules.get("jax._src.profiler")
        if mod is None:
            return None
        _annotation_cls = mod.TraceAnnotation
    if not _annotation_cls.is_enabled():
        return None
    return _annotation_cls(SPAN_PREFIX + stage, batch=batch)


class Span:
    """One timed call of one stage; ``t0``/``t1`` are its clock readings,
    for callers that stamp a batch's trace with them."""

    __slots__ = ("_stages", "stage", "batch", "t0", "t1", "_ann")

    def __init__(self, stages: "Stages", stage: str, batch: int):
        self._stages = stages
        self.stage = stage
        self.batch = batch

    def __enter__(self) -> "Span":
        ann = self._ann = _annotation(self.stage, self.batch)
        if ann is not None:
            ann.__enter__()
        self.t0 = self._stages.clock()
        return self

    def __exit__(self, *exc) -> None:
        st = self._stages
        t1 = self.t1 = st.clock()
        dt = t1 - self.t0
        stage = self.stage
        st.seconds[stage] += dt
        st.calls[stage] += 1
        if dt > st.max_s[stage]:
            st.max_s[stage] = dt
        if self._ann is not None:
            self._ann.__exit__(*exc)


class Stages:
    """Per-stage accumulators: seconds, calls and the longest call."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.calls: Dict[str, int] = collections.defaultdict(int)
        self.max_s: Dict[str, float] = collections.defaultdict(float)

    def span(self, stage: str, batch: int) -> Span:
        return Span(self, stage, batch)

    def reset_max(self) -> None:
        self.max_s.clear()

    def report(self) -> Dict[str, Dict[str, float]]:
        return {k: {"seconds": self.seconds[k], "calls": self.calls[k],
                    "max_s": self.max_s.get(k, 0.0)}
                for k in sorted(self.seconds)}


COLUMNS = ("batch", "events", "padded", "t_enqueued", "t_coalesced",
           "t_encoded", "t_launched", "t_collect", "t_drained",
           "t_delivered", "compiled")
_COL = {c: i for i, c in enumerate(COLUMNS)}
PHASES = {
    "staging": ("t_coalesced", "t_launched"),
    "collect_wait": ("t_launched", "t_collect"),
    "drain": ("t_collect", "t_drained"),
    "handoff": ("t_drained", "t_delivered"),
}
# A 51 s window at ~112 dispatches/s is ~5,700 batches.
DEFAULT_CAPACITY = 8192


class BatchRing:
    """The newest ``capacity`` drained batches' timestamps, one row each
    (``COLUMNS``; NaN where a path has no such stage)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._rows = np.empty((capacity, len(COLUMNS)), np.float64)
        self.n = 0                  # batches recorded since the reset
        self._undelivered = []      # rows drained, not yet returned

    @property
    def capacity(self) -> int:
        return len(self._rows)

    def record(self, batch: int, events: int, padded: int,
               trace: Dict[str, float], compiled: bool) -> None:
        i = self.n % self.capacity
        get = trace.get
        self._rows[i] = (batch, events, padded, get("t_enqueued", math.nan),
                         get("t_coalesced", math.nan),
                         get("t_encoded", math.nan),
                         get("t_launched", math.nan),
                         get("t_collect", math.nan),
                         get("t_drained", math.nan), math.nan, compiled)
        self._undelivered.append(i)
        self.n += 1

    def deliver_at(self, clock: Callable[[], float]) -> None:
        """The batches drained since the last delivery reach the caller
        now; reads the clock only if there are any."""
        if self._undelivered:
            self._rows[self._undelivered, _COL["t_delivered"]] = clock()
            self._undelivered.clear()

    def reset(self) -> None:
        self.n = 0
        self._undelivered.clear()

    def rows(self) -> np.ndarray:
        """The kept rows, oldest first."""
        n, cap = self.n, self.capacity
        if n <= cap:
            return self._rows[:n]
        i = n % cap
        return np.concatenate([self._rows[i:], self._rows[:i]])

    def newest(self) -> Optional[Dict[str, float]]:
        if not self.n:
            return None
        row = self._rows[(self.n - 1) % self.capacity]
        return {c: float(row[_COL[c]]) for c in COLUMNS}

    def summary(self) -> Dict[str, object]:
        """Per phase: batches timed, p50/p99/max and the event-weighted
        mean (microseconds)."""
        rows = self.rows()
        out: Dict[str, object] = {
            "batches": self.n, "dropped": max(self.n - self.capacity, 0),
            "compiled_batches": int(np.nansum(rows[:, _COL["compiled"]])),
        }
        events = rows[:, _COL["events"]]
        for name, (a, b) in PHASES.items():
            d = (rows[:, _COL[b]] - rows[:, _COL[a]]) * 1e6
            ok = np.isfinite(d)
            d, w = d[ok], events[ok]
            if not len(d):
                out[name] = {"count": 0, "p50_us": 0.0, "p99_us": 0.0,
                             "max_us": 0.0, "mean_us": 0.0}
                continue
            out[name] = {
                "count": int(len(d)),
                "p50_us": float(np.percentile(d, 50)),
                "p99_us": float(np.percentile(d, 99)),
                "max_us": float(d.max()),
                "mean_us": (float((d * w).sum() / w.sum()) if w.sum()
                            else float(d.mean())),
            }
        return out
