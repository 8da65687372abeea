"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, time per device operation and per compiled
module, and the device's idle gaps attributed to the benchmark's host span
that covered them.

The traced window is the extent of the host annotation ``bench.window``.
Device time is taken from each TPU plane's ``XLA Ops`` line (every line but
``XLA Modules`` and ``Steps`` where there is none), clipped to the window;
modules from its ``XLA Modules`` line.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU|CPU):\d+$")
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "(no bench span)"


def find_xplane(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list, spans: List[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Idle seconds by the host span that overlaps each gap most."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    longest = 0.0
    for s, e, _ in spans:
        longest = max(longest, e - s)
    out: Dict[str, float] = collections.defaultdict(float)
    for gs, ge in gap_list:
        best, who = 0.0, NO_SPAN
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(spans) and spans[i][0] < ge:
            ov = min(ge, spans[i][1]) - max(gs, spans[i][0])
            if ov > best:
                best, who = ov, spans[i][2]
            i += 1
        out[who] += (ge - gs) * 1e-9
    return dict(out)


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def reduce_profile(profile, host_spans=None, anchor_s: float = 0.0
                   ) -> Dict:
    """``profile``: a ``jax.profiler.ProfileData``. Times in the result are
    seconds. The host spans that idle gaps are attributed to are the
    trace's own ``bench.*`` annotations, or ``host_spans`` [(start, end,
    name)] in seconds of a host clock whose reading ``anchor_s`` is the
    start of the ``bench.window`` annotation."""
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = window
    if host_spans is not None:
        spans = [(lo + (a - anchor_s) * 1e9, lo + (b - anchor_s) * 1e9, n)
                 for a, b, n in host_spans]
    ops: Dict[str, float] = collections.defaultdict(float)
    op_text: Dict[str, str] = {}
    modules: Dict[str, float] = collections.defaultdict(float)
    module_runs: Dict[str, int] = collections.defaultdict(int)
    busy_s, n_busy_devices, idle = 0.0, 0, collections.defaultdict(float)
    for plane in devices:
        lines = {l.name: l for l in plane.lines}
        if "XLA Ops" in lines:
            op_lines = [lines["XLA Ops"]]
        else:
            op_lines = [l for n, l in lines.items()
                        if n not in ("XLA Modules", "Steps")]
        intervals = []
        for line in op_lines:
            for ev in line.events:
                iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if iv is None:
                    continue
                intervals.append(iv)
                ops[ev.name] += (iv[1] - iv[0]) * 1e-9
                if ev.name not in op_text:
                    op_text[ev.name] = " ".join(
                        [ev.name] + [str(v) for v in _stats(ev).values()
                                     if isinstance(v, str)])
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if iv is not None:
                    modules[ev.name] += (iv[1] - iv[0]) * 1e-9
                    module_runs[ev.name] += 1
        busy = union(intervals)
        if not busy:
            continue
        n_busy_devices += 1
        busy_s += sum(e - s for s, e in busy) * 1e-9
        for k, v in attribute(gaps(busy, lo, hi), spans).items():
            idle[k] += v
    n = max(n_busy_devices, 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s / n,
        "n_devices": n_busy_devices,
        "ops": dict(ops),
        "op_text": op_text,
        "modules": dict(modules),
        "module_runs": dict(module_runs),
        "idle_by_span": {k: v / n for k, v in idle.items()},
        "n_spans": len(spans),
    }


def op_seconds(trace: Dict, pattern: str) -> float:
    """Device seconds of the ops whose name or string stats (HLO op, its
    source op name, its module) contain ``pattern``."""
    return sum(v for k, v in trace["ops"].items()
               if pattern in trace["op_text"].get(k, k))


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])
            [:n]]
