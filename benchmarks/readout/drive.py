"""The traffic generator: one general driver for every mix in ``traffic/``.

``closed_loop``: each module keeps ``blocks_outstanding`` blocks of
``block_events`` frames in flight and sends the next block when one is
fully answered.

``open_loop``: ``bunch_events``-frame bunches arrive at the times of a
Poisson process of ``rate_events_per_s`` events per second over all
modules, each bunch to a module drawn from the seed. The count of bunches
is fixed by the rate and the window (their times are uniform order
statistics, which is a Poisson process given its count), so every seed
offers the same work in another order. A bunch is sent when its time has
come, whatever the server is doing, and each event is timed from that due
time.

Both cycle the deployment's frame pool. Every call into the server is a
host span of the benchmark's own (``bench.submit_frames``, ``bench.poll``,
``bench.flush``).
"""
from __future__ import annotations

import array
import collections
import time
from typing import Callable, Dict, List, Optional

import numpy as np


def fixed_count_arrivals(rate_hz: float, seconds: float, rng) -> np.ndarray:
    """round(rate * seconds) sorted uniform times in [0, seconds): a
    Poisson process of that rate conditioned on its count."""
    return np.sort(rng.uniform(0.0, seconds, int(round(rate_hz * seconds))))


class Spans:
    """Host spans around the benchmark's calls into the server: seconds and
    calls per name, and, while ``recording``, each call's start and end
    (perf_counter seconds) for the trace's idle-gap attribution. They are
    kept here and not written into the profile as annotations: a spin loop
    polls some 10^5 times a second, and annotating every call stalled the
    traced run's generator by seconds."""

    def __init__(self):
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.calls: Dict[str, int] = collections.defaultdict(int)
        self.recording = False
        self.names: List[str] = []
        self._code: Dict[str, int] = {}
        self.log_code = array.array("i")
        self.log_t = array.array("d")      # start, end, start, end, ...

    def call(self, name: str, fn: Callable, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        self.seconds[name] += t1 - t0
        self.calls[name] += 1
        if self.recording:
            if name not in self._code:
                self._code[name] = len(self.names)
                self.names.append(name)
            self.log_code.append(self._code[name])
            self.log_t.append(t0)
            self.log_t.append(t1)
        return out

    def logged(self):
        """[(start, end, name)] of the recorded calls, perf_counter
        seconds."""
        t = self.log_t
        return [(t[2 * i], t[2 * i + 1], self.names[c])
                for i, c in enumerate(self.log_code)]


class Run:
    """What one window offered and got back, kept cheaply while it runs
    and turned into arrays once it has closed."""

    def __init__(self, pool_size: int):
        self.pool_size = pool_size
        self.sub_first: List[int] = []    # seq of each submission's first event
        self.sub_n: List[int] = []
        self.sub_module: List[int] = []
        self.sub_pool: List[int] = []     # pool index of its first event
        self.sub_due: List[float] = []    # perf_counter time it was due
        self.sub_sent: List[float] = []
        # per poll that answered: (drain time, seq, chip, score, keep)
        # arrays; no answer object outlives its poll, so the window never
        # carries a growing heap for the collector to walk
        self.returned: List = []
        self.cursor: Dict[int, int] = collections.defaultdict(int)

    def answered(self, got, t: float) -> np.ndarray:
        """Keep one poll's answers, drained at ``t``, as arrays; returns
        their seqs."""
        n = len(got)
        seq = np.fromiter((e.seq for e in got), np.int64, n)
        self.returned.append((
            np.full(n, t), seq,
            np.fromiter((e.chip for e in got), np.int64, n),
            np.fromiter((e.score_raw for e in got), np.int64, n),
            np.fromiter((e.keep for e in got), np.bool_, n)))
        return seq

    def take(self, module: int, n: int) -> int:
        start = self.cursor[module]
        self.cursor[module] = (start + n) % self.pool_size
        return start

    def events(self):
        """Per submitted event (index = seq): module, pool index and due
        time; per answer: seq, chip, score, keep and drain time."""
        n = np.asarray(self.sub_n, np.int64)
        first = np.asarray(self.sub_first, np.int64)
        total = int(n.sum())
        if len(n) and not np.array_equal(first, np.concatenate(
                [[0], np.cumsum(n)[:-1]]) + first[0]):
            raise RuntimeError("submissions were not given consecutive seqs")
        rep = lambda v: np.repeat(np.asarray(v), n)
        offs = np.arange(total) - rep(first - first[0]) if total else []
        ev = {
            "module": rep(self.sub_module).astype(np.int64),
            "pool": (rep(self.sub_pool) + offs) % self.pool_size,
            "due": rep(self.sub_due).astype(np.float64),
        }
        cat = lambda k: (np.concatenate([r[k] for r in self.returned])
                         if self.returned else np.zeros(0, np.int64))
        ans = {"seq": cat(1) - first[0], "chip": cat(2), "score": cat(3),
               "keep": cat(4).astype(bool), "t": cat(0)}
        return ev, ans


def _submit(server, spans: Spans, run: Run, frames, y0, module: int, n: int,
            due: float) -> None:
    start = run.take(module, n)
    if start + n <= run.pool_size:
        f, z = frames[module, start:start + n], y0[module, start:start + n]
    else:
        idx = (start + np.arange(n)) % run.pool_size
        f, z = frames[module, idx], y0[module, idx]
    seqs = spans.call("bench.submit_frames", server.submit_frames, module,
                      f, z)
    if seqs[0] is None or seqs[-1] - seqs[0] != n - 1:
        raise RuntimeError("the server shed or reordered a submission")
    run.sub_first.append(seqs[0])
    run.sub_n.append(n)
    run.sub_module.append(module)
    run.sub_pool.append(start)
    run.sub_due.append(due)
    run.sub_sent.append(time.perf_counter())


def closed_loop(server, traffic: Dict, frames, y0, seconds: float,
                spans: Spans, on_tick: Optional[Callable] = None) -> Run:
    C, P = y0.shape
    blk, depth = traffic["block_events"], traffic["blocks_outstanding"]
    run = Run(P)
    left: Dict[int, int] = {}              # submission index -> unanswered
    t0 = time.perf_counter()
    t_end = t0 + seconds
    for _ in range(depth):
        for m in range(C):
            left[len(run.sub_n)] = blk
            _submit(server, spans, run, frames, y0, m, blk, t0)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if on_tick is not None:
            on_tick(now - t0)
        got = spans.call("bench.poll", server.poll)
        if not got:
            continue
        t = time.perf_counter()
        seqs = run.answered(got, t)
        sub = np.searchsorted(run.sub_first, seqs, side="right") - 1
        done = []
        for s, k in zip(*np.unique(sub, return_counts=True)):
            left[int(s)] -= int(k)
            if left[int(s)] == 0:
                del left[int(s)]
                done.append(int(s))
        for s in done:
            if t < t_end:
                left[len(run.sub_n)] = blk
                _submit(server, spans, run, frames, y0, run.sub_module[s],
                        blk, t)
    run.t0, run.t_end = t0, t_end
    return run


def open_loop(server, traffic: Dict, frames, y0, seconds: float,
              spans: Spans, rng, on_tick: Optional[Callable] = None) -> Run:
    C, P = y0.shape
    bunch = traffic["bunch_events"]
    due = fixed_count_arrivals(traffic["rate_events_per_s"] / bunch,
                               seconds, rng)
    module = rng.integers(0, C, len(due))
    run = Run(P)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    i, n = 0, len(due)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while i < n and t0 + due[i] <= now:
            _submit(server, spans, run, frames, y0, int(module[i]), bunch,
                    t0 + due[i])
            i += 1
        if on_tick is not None:
            on_tick(now - t0)
        got = spans.call("bench.poll", server.poll)
        if got:
            run.answered(got, time.perf_counter())
    # events offered but not yet dispatched when the window closes: still
    # queued in the server, or due and not yet sent by the generator
    run.backlog_at_close = server.queue_depth + (n - i) * bunch
    # bunches still due by the close are sent now: late, and timed from
    # their due time like every other
    while i < n:
        _submit(server, spans, run, frames, y0, int(module[i]), bunch,
                t0 + due[i])
        i += 1
    run.t0, run.t_end = t0, t_end
    return run


def settle(server, spans: Spans, run: Run) -> None:
    """After the close: force out everything queued and in flight (flush
    blocks until the device has answered)."""
    got = spans.call("bench.flush", server.flush)
    run.answered(got, time.perf_counter())


DRIVERS = {"closed_loop": closed_loop, "open_loop": open_loop}
