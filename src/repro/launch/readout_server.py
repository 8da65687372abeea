"""Multi-chip streaming readout server (the scaled-up §5 front end).

One deployed detector is not one chip: many sensors feed many configured
eFPGAs, all filtering the same 40 MHz bunch-crossing stream before the
off-detector links. This server models that as a serving system with TWO
ingestion stages, one per deployment style:

    submit(chip, features)            pre-computed features (host frontend)
    submit_frames(chip, frames, y0)   RAW charge frames (fused frontend)
      -> micro-batch queue            (coalesce: max_batch / max_latency)
      -> scoring dispatch
           features ... host featurize (quantize + bit pack) -> ONE
                        sharded chip-batched dispatch that evaluates,
                        votes (TMR), decodes scores and applies the
                        trigger cut on device (fabric_eval_multi_scored)
           frames ..... ONE fused dispatch (kernels/frontend.py):
                        yprofile -> quantize -> bit pack -> lut_eval ->
                        vote -> score -> keep/drop, all on device, chip
                        axis sharded over the "chips" mesh — no host
                        materialization between stages
      -> sparse trigger compression   (optional: only keep-flagged events
                                       cross the host link as a packed
                                       (indices, scores) pair)
      -> background config scrubbing  (optional: readback -> CRC verify ->
                                       heal of the served configuration
                                       memory, interleaved with dispatches)
      -> per-chip trigger report      (rates, reduction, link bytes,
                                       per-stage host spans, per-replica
                                       SEU disagreement counters, scrub
                                       detections / healed bits / latency)

Key properties:

  * Loading a bitstream stays an array swap: all chips share one padded
    geometry (core.fabric.StackGeometry, which also carries the
    feature-stage metadata for frames ingestion), so ``reconfigure``
    hot-swaps a chip's arrays — lut_eval stack AND fused encode plan —
    with no recompile. Under ``redundancy="tmr"`` the swap re-encodes all
    three replica slots; still no retrace.
  * SEU resilience as a serving mode: ``ServerConfig.redundancy="tmr"``
    serves every chip as three placement-distinct replica encodings
    (core.tmr.replicate_config) voted on device with a 2-of-3 majority
    before decode. A single configuration-bit upset in any one replica
    cannot change any served output (tests/test_seu.py sweeps every
    bit); the per-replica disagreement counters in the report are the
    SEU health monitor, and ``inject_seu`` is the fault-injection port
    (flips one bit of one served replica, both backends).
  * Scrubbing closes the SEU loop (mask -> detect -> repair): TMR only
    masks a fault until a second upset lands in the same logical LUT
    (tests/test_seu.py's double-fault controls prove that is fatal), so
    ``ServerConfig(scrub_interval=k)`` runs a background scrub task every
    k dispatches: read back one replica frame's LIVE truth-table image
    (device arrays on the kernel backend, the MultiFabricSim scrub twin
    on the host oracle), CRC-verify it against the golden store
    (core.bitstream.GoldenImageStore, snapshotted at (re)configuration),
    and on mismatch re-encode ONLY the corrupted replica from the golden
    bitstream via the existing no-retrace swap machinery. Frames are
    scrubbed round-robin; ``scrub_mode="steered"`` additionally jumps to
    the replica whose disagreement counters climbed since its last scrub
    (the PR 4 SEU health monitor steering the repair), while the
    round-robin turn still advances every step — steering can never
    starve a frame. Kernel-backend readbacks are issued as ASYNC
    device->host copies and verified one scrub step later, so the scrub
    task interleaves behind the in-flight dispatches instead of stalling
    the triple-buffered pipeline (a synchronous readback cost ~25%
    events/s and the async split under 5%, both measured on a CPU with
    the matmul layouts; neither is measured on the chip). Works without
    redundancy too: CRC-only detection heals an unprotected chip
    (outputs may be wrong until the heal — exactly the window scrubbing
    bounds).
  * At-source link compression: ``ServerConfig.sparse=True`` drops
    rejected events *before* the host link — the drain materializes only
    the packed (flat index, score) pairs of keep-flagged events
    (parallel.compression.sparse_trigger_pack), and the report carries
    the measured bytes-on-wire vs the dense equivalent.
  * Pipelined host/device overlap: device dispatch is asynchronous (JAX),
    and up to ``pipeline_depth`` batches stay in flight while the host
    prepares the next one. The default depth of 2 is triple buffering
    (host builds batch k+2 while the device holds k and k+1); depth 1 is
    the classic double buffer. ``poll()`` never blocks: a batch is
    retired as soon as its device arrays are actually ready
    (``jax.Array.is_ready``), and while the pipeline is at capacity new
    dispatches are DEFERRED — backlog accumulates in the submit queue
    where admission control can see (and shed) it, instead of silently
    backpressuring the caller. Only ``flush()`` blocks.
  * Staging in place (kernel frames path): a dispatch writes its frames
    into a reused host arena (launch/staging.py) and zeroes only the pad
    rows past each chip's count; nothing batch-sized is allocated,
    zero-filled or freed per dispatch. An arena returns to the free list
    when its batch drains, never while the transfer or step may still
    read it. The cost is resident host memory: at most
    ``pipeline_depth + 2`` arenas, each the size of the largest batch
    seen (4 x 71.6 MB for 4 chips x 2,048 frames at the default depth).
  * Deadline-aware serving: the trigger chain gives every event a hard
    latency budget — data that misses the window is physics lost, so
    overload must degrade gracefully instead of queueing unboundedly.
    Per-event latency is measured end to end (enqueue -> coalesce ->
    launch -> drain, one injected monotonic clock everywhere) into
    fixed-bucket log-scale histograms with p50/p99/p99.9 and a CDF in
    the report. ``ServerConfig(deadline_us=, overload_policy=)`` then
    makes the loop ACT on it: admission control sheds new submissions
    when the queue's oldest-event slack (deadline minus wait minus the
    EWMA service estimate) goes negative — every shed is counted per
    chip, never silent; the micro-batch coalescer adaptively shrinks
    ``max_batch``/``max_latency_s`` under pressure and re-grows them
    when slack recovers; and under ``overload_policy="degrade"`` a
    hysteretic ladder steps through configurable rungs on sustained
    deadline misses (widen the scrub interval -> CRC-only scrub with
    deferred heals -> sparse-only egress), every transition counted and
    timestamped. Keep/drop decisions on admitted events stay bit-exact
    vs the host oracle at every rung — the rungs trade repair latency
    and link bytes, never correctness (tests/test_deadline.py).
  * The host-oracle backend (backend="host") is bit-identical to the
    kernel path on BOTH ingestion stages and under every redundancy /
    sparse mode — the numpy path votes with the same
    core.tmr.majority_vote and packs with the same compaction rule — the
    basis of tests/test_readout_server.py, test_frontend.py and
    test_seu.py.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import logging
import math
import time
from typing import (
    Callable, Deque, Dict, Iterable, List, NamedTuple, Optional, Sequence,
    Tuple,
)

import numpy as np

from repro.core.bitstream import GoldenImageStore
from repro.core.fabric import (
    FabricSim,
    FrontendSpec,
    MultiFabricSim,
    StackGeometry,
    check_stackable,
    packed_table_image,
    stack_event_bits,
)
from repro.core.readout import ReadoutChip
from repro.core.tmr import (
    N_REPLICAS,
    inject_seu as _inject_seu_config,
    majority_vote,
    replica_table_images,
    replicate_config,
)
from repro.data.smartpixel import N_T, N_X, N_Y
from repro.data.smartpixel import N_FEATURES as _N_FEATURES
from repro.launch.spans import BatchRing, Stages
from repro.launch.staging import StagingArenas
from repro.parallel.compression import (
    DENSE_BYTES_PER_EVENT,
    SPARSE_BYTES_PER_EVENT,
    SPARSE_HEADER_BYTES,
)

# The documented default scrub budget: one readback->verify step every
# this many scoring dispatches. Chosen when a sustained stream's
# throughput cost at this interval measured under 5% on a CPU, with the
# matmul layouts; the cost on the chip is not measured. Deployments trade
# detection latency against overhead by setting
# ServerConfig(scrub_interval=...) directly.
DEFAULT_SCRUB_INTERVAL = 4

# The degrade ladder's known rungs, in the order the default ladder steps
# down through them (cheapest concession first). Every rung trades repair
# latency or link bytes, NEVER the correctness of admitted events:
#   scrub_relax     widen the scrub interval by SCRUB_RELAX_FACTOR
#                   (slower repair; TMR keeps masking, CRC still detects)
#   scrub_crc_only  keep CRC detection live but defer the heals (the
#                   re-encode + array swap) until the rung exits, so the
#                   repair cost leaves the overloaded critical path
#   sparse_egress   ship only keep-flagged events on the host link (the
#                   scores of non-keeps are dropped at source), even on a
#                   dense-configured server
DEGRADE_RUNGS = ("scrub_relax", "scrub_crc_only", "sparse_egress")
SCRUB_RELAX_FACTOR = 4

_LOG = logging.getLogger("repro.launch.readout_server")


# --------------------------------------------------------------------------
# Latency observability: fixed log-scale histograms
# --------------------------------------------------------------------------

# One shared bucket grid for every histogram: 8 log-scale buckets per
# decade from 1 us to 100 s, plus an underflow and an overflow slot. A
# FIXED grid (rather than per-stream quantile sketches) keeps the state
# O(1) no matter how many events stream through, makes histograms
# mergeable across chips and runs, and gives ``report()`` a stable,
# machine-comparable CDF axis.
_HIST_BUCKETS_PER_DECADE = 8
_HIST_DECADES = 8
_HIST_N = _HIST_BUCKETS_PER_DECADE * _HIST_DECADES
_HIST_EDGES_US = np.power(
    10.0, np.arange(_HIST_N + 1) / _HIST_BUCKETS_PER_DECADE)


class LatencyHistogram:
    """Streaming latency histogram on the shared log-scale grid.

    ``add_many`` is one vectorized bincount per drained batch; percentile
    queries interpolate log-linearly inside the owning bucket, so
    p50/p99/p99.9 are exact to within one bucket width (~33% at 8
    buckets/decade) — tail-shape fidelity at O(1) memory, which is what a
    long-running trigger service can actually afford to keep per chip.
    """

    __slots__ = ("counts", "_sum_us", "_max_us")

    def __init__(self):
        # counts[0] = underflow (<1 us), [1..N] = grid, [N+1] = overflow
        self.counts = np.zeros(_HIST_N + 2, np.int64)
        self._sum_us = 0.0
        self._max_us = 0.0

    @property
    def count(self) -> int:
        return int(self.counts.sum())

    def add(self, us: float) -> None:
        self.add_many(np.asarray([us], np.float64))

    def add_many(self, us: np.ndarray) -> None:
        us = np.asarray(us, np.float64)
        if us.size == 0:
            return
        idx = np.zeros(us.shape, np.int64)
        pos = us >= 1.0
        if pos.any():
            idx[pos] = 1 + np.minimum(
                (np.log10(us[pos]) * _HIST_BUCKETS_PER_DECADE).astype(
                    np.int64),
                _HIST_N,  # >= the top edge lands in the overflow slot
            )
        self.counts += np.bincount(idx, minlength=_HIST_N + 2)
        self._sum_us += float(us.sum())
        self._max_us = max(self._max_us, float(us.max()))

    def merge(self, other: "LatencyHistogram") -> None:
        self.counts += other.counts
        self._sum_us += other._sum_us
        self._max_us = max(self._max_us, other._max_us)

    def percentile(self, q: float) -> float:
        """q in [0, 100] -> latency in us, log-interpolated in-bucket."""
        total = int(self.counts.sum())
        if total == 0:
            return 0.0
        target = total * (q / 100.0)
        cum = np.cumsum(self.counts)
        b = int(np.searchsorted(cum, target, side="left"))
        if b <= 0:
            return float(_HIST_EDGES_US[0])     # underflow: "< 1 us"
        if b >= _HIST_N + 1:
            return float(self._max_us)          # overflow: observed max
        lo, hi = float(_HIST_EDGES_US[b - 1]), float(_HIST_EDGES_US[b])
        inside = int(self.counts[b])
        frac = ((target - float(cum[b - 1])) / inside) if inside else 0.0
        return lo * (hi / lo) ** min(max(frac, 0.0), 1.0)

    def cdf(self) -> List[List[float]]:
        """[[upper edge us, cumulative fraction], ...] over the non-empty
        buckets — the machine-readable CDF ``report()`` exports.
        Underflow folds into the first emitted point; the final point is
        the observed max at fraction 1.0."""
        total = int(self.counts.sum())
        if total == 0:
            return []
        cum = np.cumsum(self.counts)
        out: List[List[float]] = []
        prev = -1
        for i in range(1, _HIST_N + 2):
            c = int(cum[i])
            if c != prev:
                edge = (float(_HIST_EDGES_US[i - 1]) if i <= _HIST_N
                        else float(self._max_us))
                out.append([round(edge, 3), round(c / total, 6)])
                prev = c
            if c == total:
                break
        return out

    def summary(self) -> Dict[str, float]:
        n = self.count
        return {
            "count": n,
            "mean_us": (self._sum_us / n) if n else 0.0,
            "max_us": self._max_us,
            "p50_us": self.percentile(50.0),
            "p99_us": self.percentile(99.0),
            "p999_us": self.percentile(99.9),
        }


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Micro-batching knobs. Validated on construction — a bad knob fails
    HERE with a named error, not three layers down as a shape mismatch.

    max_batch: coalesce at most this many events (across all chips) into
        one dispatch; a full queue triggers a dispatch immediately.
    max_latency_s: a partial batch is dispatched once its oldest event has
        waited this long (the trigger-latency budget).
    backend: "kernel" (chip-batched Pallas dispatch) or "host" (numpy
        MultiFabricSim oracle, bit-identical).
    batch_tile: Pallas batch tile — every stage of the fused frames
        dispatch tiles with it, so it must be a multiple of 128 (the TPU
        lane width both kernels assume).
    band: the fan-in-reach *envelope* of the kernel stack — None
        auto-selects it whenever the chips' shared fan-in reach K is
        smaller than the level count; True/False force banded/dense.
        The band is layout-independent (a hardware routing constraint,
        not a kernel structure): with layout="matmul" it additionally
        selects the windowed selection tensor (per-level routing cost
        drops from the full padded net buffer to the input segment + a
        K-level window); with layout="bitsliced" the gather kernel is
        unchanged and the band is a pure reach budget, validated at
        pack time and enforced on every hot-swap (swap_chip/
        swap_replica reject configs whose reach exceeds it). The host
        oracle is unaffected.
    layout: device layout of the kernel stack. None (default) selects
        "bitsliced" — the word-parallel serving path, band or no band.
        "matmul" is the Pallas selection-matmul kernel, banded/dense per
        ``band``. "bitsliced" evaluates 32 events per uint32 word as
        pure bitwise mux logic with the TMR vote folded into the same
        pass (kernels/lut_eval/bitsliced.py) — the cheap-TMR, genuinely
        chip-parallel serving mode. Bit-identical to the host oracle
        either way; hot-swap stays a retrace-free array swap in both
        layouts.
    redundancy: "none" or "tmr". TMR serves three placement-distinct
        replica encodings of every chip, votes 2-of-3 on device before
        decode, and surfaces per-replica disagreement counters in the
        report (the SEU health monitor). Cost: 3x the fabric-evaluation
        work plus the (elementwise) voter.
    sparse: only keep-flagged events cross the host link, as a packed
        (flat index, score) pair; dropped events never materialize on the
        host and the report carries measured bytes-on-wire. Drained
        results then contain ONLY kept events.
    scrub_interval: None disables scrubbing; an int k runs one background
        scrub step (readback -> CRC verify -> heal of one replica frame,
        plus the steered extra below) every k scoring dispatches.
        DEFAULT_SCRUB_INTERVAL is the documented <5%-overhead budget.
    scrub_mode: "round_robin" scrubs frames strictly in slot order;
        "steered" (default) additionally CRC-checks the replica frame
        whose SEU disagreement counters climbed most since its last
        scrub, BEFORE taking the round-robin turn — so an active fault is
        repaired within ~one scrub interval of its first voted-against
        dispatch instead of waiting for its round-robin turn. The
        round-robin turn always advances, so steering never starves a
        frame (every frame is scrubbed within one full cycle —
        tests/test_scrub.py's fairness property).
    pipeline_depth: batches kept in flight on the device while the host
        prepares the next (2 = triple buffering, 1 = double buffering).
    threshold_electrons: per-pixel zero suppression of the frames->
        features stage (frames ingestion only).
    deadline_us: per-event latency budget (enqueue -> drained result) in
        microseconds, or None (no deadline — latency is still measured,
        never acted on). With a deadline every drained event is scored
        met/missed in the report's deadline ledger.
    overload_policy: what the loop DOES about the deadline.
        "observe" (default) measures misses but never sheds or adapts;
        "shed" adds admission control (submissions are rejected — seq
        None — while the queue's oldest-event slack is negative, every
        shed counted per chip) and adaptive micro-batch sizing (the
        effective max_batch/max_latency_s halve when a drained batch
        blows the budget and re-grow once batches clear half of it);
        "degrade" adds the hysteretic rung ladder below on top of
        shedding. Policies other than "observe" require deadline_us.
    degrade_rungs: the ladder, stepped through in order under
        ``overload_policy="degrade"`` (see DEGRADE_RUNGS for the rung
        semantics). Must be non-empty, known names, no duplicates —
        validated even when the ladder is inactive.
    degrade_window: drained (admitted) events per ladder evaluation.
    degrade_enter_frac / degrade_exit_frac: a window whose deadline-miss
        fraction is >= enter steps DOWN one rung; <= exit steps back UP.
        enter >> exit is the hysteresis — at most one transition per
        window, so the ladder cannot flap within a window.
    min_batch: floor of the adaptive micro-batch shrink (clamped to
        max_batch when max_batch is smaller).
    tenant_quota_queued: per-TENANT cap on outstanding (queued, not yet
        drained) events, enforced by the fleet layer (launch/fleet.py)
        on top of the server's own two-predictor deadline admission —
        a submission past the quota is shed and counted in the tenant's
        ``quota_shed``, so one chatty tenant cannot starve the bucket's
        queue. None (default) disables the per-tenant cap; the server
        itself never reads this knob (a standalone server has no
        tenants), it simply rides the ServerConfig so a fleet is
        configured in one place.
    """

    max_batch: int = 2048
    max_latency_s: float = 5e-3
    backend: str = "kernel"
    batch_tile: int = 128
    band: Optional[bool] = None
    layout: Optional[str] = None
    redundancy: str = "none"
    sparse: bool = False
    scrub_interval: Optional[int] = None
    scrub_mode: str = "steered"
    pipeline_depth: int = 2
    threshold_electrons: float = 800.0
    deadline_us: Optional[float] = None
    overload_policy: str = "observe"
    degrade_rungs: Tuple[str, ...] = DEGRADE_RUNGS
    degrade_window: int = 64
    degrade_enter_frac: float = 0.5
    degrade_exit_frac: float = 0.05
    min_batch: int = 32
    tenant_quota_queued: Optional[int] = None

    def __post_init__(self):
        if not (isinstance(self.max_batch, int) and self.max_batch > 0):
            raise ValueError(f"max_batch must be a positive int, got "
                             f"{self.max_batch!r}")
        if self.max_latency_s <= 0:
            raise ValueError(f"max_latency_s must be > 0, got "
                             f"{self.max_latency_s!r}")
        if not (isinstance(self.batch_tile, int) and self.batch_tile > 0
                and self.batch_tile % 128 == 0):
            raise ValueError(
                f"batch_tile must be a positive multiple of 128 (the TPU "
                f"lane width), got {self.batch_tile!r}")
        if self.backend not in ("kernel", "host"):
            raise ValueError(f"unknown backend {self.backend!r} "
                             "(expected 'kernel' or 'host')")
        if self.band is not None and not isinstance(self.band, bool):
            raise ValueError(
                f"band must be True, False or None (auto), got "
                f"{self.band!r}")
        if self.layout is not None and self.layout not in (
                "matmul", "bitsliced"):
            raise ValueError(f"unknown layout {self.layout!r} "
                             "(expected 'matmul' or 'bitsliced', or None "
                             "= auto-select)")
        if self.redundancy not in ("none", "tmr"):
            raise ValueError(f"unknown redundancy {self.redundancy!r} "
                             "(expected 'none' or 'tmr')")
        if not isinstance(self.sparse, bool):
            raise ValueError(f"sparse must be a bool, got {self.sparse!r}")
        if self.scrub_interval is not None and not (
                isinstance(self.scrub_interval, int)
                and not isinstance(self.scrub_interval, bool)
                and self.scrub_interval > 0):
            raise ValueError(
                f"scrub_interval must be a positive int (dispatches between "
                f"scrub steps) or None to disable, got "
                f"{self.scrub_interval!r}")
        if self.scrub_mode not in ("round_robin", "steered"):
            raise ValueError(
                f"unknown scrub_mode {self.scrub_mode!r} "
                "(expected 'round_robin' or 'steered')")
        if not (isinstance(self.pipeline_depth, int)
                and self.pipeline_depth >= 1):
            raise ValueError(f"pipeline_depth must be an int >= 1, got "
                             f"{self.pipeline_depth!r}")
        if self.threshold_electrons < 0:
            raise ValueError(f"threshold_electrons must be >= 0, got "
                             f"{self.threshold_electrons!r}")
        if self.deadline_us is not None and not (
                isinstance(self.deadline_us, (int, float))
                and not isinstance(self.deadline_us, bool)
                and math.isfinite(self.deadline_us)
                and self.deadline_us > 0):
            raise ValueError(
                f"deadline_us must be a positive finite number (per-event "
                f"latency budget in microseconds) or None to disable, got "
                f"{self.deadline_us!r}")
        if self.overload_policy not in ("observe", "shed", "degrade"):
            raise ValueError(
                f"unknown overload_policy {self.overload_policy!r} "
                "(expected 'observe', 'shed' or 'degrade')")
        if self.overload_policy != "observe" and self.deadline_us is None:
            raise ValueError(
                f"overload_policy={self.overload_policy!r} needs "
                "deadline_us set — without a deadline there is no slack "
                "to act on")
        rungs = self.degrade_rungs
        if isinstance(rungs, list):
            rungs = tuple(rungs)
            object.__setattr__(self, "degrade_rungs", rungs)
        if not (isinstance(rungs, tuple) and rungs):
            raise ValueError(
                f"degrade_rungs must be a non-empty tuple of rung names, "
                f"got {self.degrade_rungs!r}")
        for r in rungs:
            if r not in DEGRADE_RUNGS:
                raise ValueError(
                    f"unknown degrade rung {r!r} "
                    f"(known rungs: {list(DEGRADE_RUNGS)})")
        if len(set(rungs)) != len(rungs):
            raise ValueError(f"duplicate degrade rungs in {rungs!r}")
        if not (isinstance(self.degrade_window, int)
                and not isinstance(self.degrade_window, bool)
                and self.degrade_window >= 1):
            raise ValueError(
                f"degrade_window must be an int >= 1 (drained events per "
                f"ladder evaluation), got {self.degrade_window!r}")
        if not (0.0 < self.degrade_exit_frac
                < self.degrade_enter_frac <= 1.0):
            raise ValueError(
                "need 0 < degrade_exit_frac < degrade_enter_frac <= 1 "
                "(the hysteresis gap), got "
                f"exit={self.degrade_exit_frac!r} "
                f"enter={self.degrade_enter_frac!r}")
        if not (isinstance(self.min_batch, int)
                and not isinstance(self.min_batch, bool)
                and self.min_batch > 0):
            raise ValueError(f"min_batch must be a positive int, got "
                             f"{self.min_batch!r}")
        if self.tenant_quota_queued is not None and not (
                isinstance(self.tenant_quota_queued, int)
                and not isinstance(self.tenant_quota_queued, bool)
                and self.tenant_quota_queued > 0):
            raise ValueError(
                f"tenant_quota_queued must be a positive int (max "
                f"outstanding events per tenant) or None to disable, got "
                f"{self.tenant_quota_queued!r}")

    @property
    def n_replicas(self) -> int:
        return N_REPLICAS if self.redundancy == "tmr" else 1

    @property
    def effective_layout(self) -> str:
        """The layout actually served. ``layout=None`` selects
        "bitsliced" (the fast, cheap-TMR word-parallel evaluator)
        unconditionally — the band is a layout-independent reach
        envelope, so forcing it no longer forces the matmul kernel."""
        return self.layout if self.layout is not None else "bitsliced"

    @property
    def deadline_s(self) -> Optional[float]:
        return None if self.deadline_us is None else self.deadline_us * 1e-6


class ScoredEvent(NamedTuple):
    """One event's answer: immutable and equal by value. A retired
    batch's answers are built together from its columns
    (``ReadoutServer._answers``), not one constructor call per event."""

    seq: int          # submission order (global, monotone)
    chip: int
    score_raw: int    # integer-domain fabric score (voted under TMR)
    keep: bool        # False = classified as pileup, dropped at source


@dataclasses.dataclass
class ChipStreamStats:
    """Running trigger/reduction accounting for one chip slot."""

    n_in: int = 0
    n_kept: int = 0
    n_dispatches: int = 0
    # events rejected by deadline admission control at submit time (the
    # shed traffic — always visible in the report, never silent)
    n_shed: int = 0
    # per-replica SEU health: events where replica r's output word was
    # voted against (always zeros on a healthy or non-redundant server)
    disagreements: List[int] = dataclasses.field(default_factory=list)

    def fraction_kept(self) -> float:
        return self.n_kept / self.n_in if self.n_in else 1.0


class ShardLedger:
    """What each device of the "chips" mesh was given by the kernel frames
    path since the last reset: ``report()["shards"]``. The chip axis is
    split into contiguous groups of ``modules_per_device`` modules (the
    ``shard_map`` over the mesh), so device k holds the rows of modules
    k*m .. k*m + m - 1. Counted once per dispatch, from the per-module
    event counts, never per event."""

    def __init__(self, devices: int, n_modules: int):
        self.devices = devices
        self.modules_per_device = n_modules // devices if devices else 0
        self.reset()

    def reset(self) -> None:
        self.dispatches = 0
        self.rows = [0] * self.devices
        self.events = [0] * self.devices
        self.bytes = [0] * self.devices

    def add(self, counts: Sequence[int], width: int, row_bytes: int) -> None:
        """One dispatch: ``counts`` real events per module, ``width`` rows
        placed per module, ``row_bytes`` placed per row."""
        m = self.modules_per_device
        self.dispatches += 1
        for k in range(self.devices):
            self.rows[k] += m * width
            self.events[k] += sum(counts[k * m:(k + 1) * m])
            self.bytes[k] += m * width * row_bytes

    def report(self) -> Dict[str, object]:
        return {"devices": self.devices,
                "modules_per_device": self.modules_per_device,
                "dispatches": self.dispatches,
                "rows_per_device": list(self.rows),
                "events_per_device": list(self.events),
                "bytes_per_device": list(self.bytes)}


# (seq, chip, kind, payload, t_enqueue); payload is a features row for
# kind="features", an (frame, y0) pair for kind="frames".
_Event = Tuple[int, int, str, object, float]
# (kind, pending, per_chip_seq, counts, meta). Both ingestion stages
# converge on the same two inflight kinds:
#   "scored": pending = (score (C,B), keep (C,B), disagree (C,R)) —
#       device arrays on the kernel backend (materialized at drain),
#       numpy on the host oracle;
#   "sparse": pending = (count, idx, vals, disagree (C,R), B) — the
#       packed keep-flagged events; only the count-prefix of idx/vals
#       crosses the host link at drain time.
# meta = {"t_enq": per-chip enqueue-time lists (every admitted event,
# kept or not — the latency ledger), "trace": the batch's monotonic
# stage timestamps}.
_Inflight = Tuple[str, object, List[List[int]], List[int], Dict]


class ReadoutServer:
    """Serves N configured ReadoutChips from one micro-batched event loop."""

    def __init__(
        self,
        chips: Sequence[ReadoutChip],
        config: ServerConfig = ServerConfig(),
        clock=time.monotonic,
        envelope: Optional[StackGeometry] = None,
    ):
        """``envelope`` pins the server's fixed geometry to a GIVEN
        StackGeometry instead of the chips' union — the bucketed-pool
        mode (kernels.lut_eval.ops.bucket_envelope / launch/fleet.py):
        every chip must fit it, the kernel stack pads to it, and its
        fan-in-reach budget decides banded-vs-dense (``config.band`` is
        ignored for the band choice, since the envelope IS the band
        contract). Servers sharing an envelope share every static
        kernel dimension, so a chip can move between them — or a new
        tenant can admit — via ``reconfigure`` with zero retraces."""
        if not chips:
            raise ValueError("need at least one chip")
        self.chips: List[ReadoutChip] = list(chips)
        self.config = config
        self._clock = clock
        # Scores decode on DEVICE (two's-complement int32) on the kernel
        # backend; enforce the width bound on both backends so a
        # deployment validated on the host oracle cannot overflow on the
        # kernel.
        for i, c in enumerate(self.chips):
            if len(c.config.output_nets) > 31:
                raise ValueError(
                    f"device score decode is int32: chip {i} has "
                    f"{len(c.config.output_nets)} output bits > 31")
        # the server's FIXED envelope: set at construction, never shrinks.
        # Both backends validate hot-swaps against it — including the
        # fan-in-reach budget a banded kernel stack depends on — so a
        # deployment validated on the host oracle behaves identically on
        # the kernel. The budget mirrors the stack's actual band choice:
        # a dense stack (config.band=False, or reach >= levels) carries
        # none, so forcing dense keeps full hot-swap flexibility. The
        # envelope also carries the feature-stage contract: every server
        # can ingest raw frames, so a hot-swapped chip must be encodable
        # from the featurizer's output (checked in ``reconfigure``).
        # TMR replication is envelope-invariant (placement rotation
        # changes neither level sizes, widths nor reach), so one geometry
        # covers every replica slot.
        geo = check_stackable([c.config for c in self.chips])
        if envelope is not None:
            for i, c in enumerate(self.chips):
                if not envelope.admits(c.config):
                    raise ValueError(
                        f"chip {i} does not fit the pinned envelope "
                        f"{envelope} (levels={len(c.config.level_sizes)}, "
                        f"widest={max(c.config.level_sizes, default=1)}, "
                        f"inputs={c.config.n_inputs}, "
                        f"outputs={len(c.config.output_nets)}, "
                        f"fanin_reach={c.config.fanin_reach()})")
            geo = envelope
            banded = (envelope.fanin_reach is not None
                      and envelope.fanin_reach < envelope.n_levels)
        else:
            banded = (
                config.band is not False
                and (geo.fanin_reach or geo.n_levels) < geo.n_levels
            )
        # resolve layout=None here, once — everything downstream (stack
        # packing, the fused frontend, the report) uses the resolved
        # value. There is no matmul fallback: the band is a layout-
        # independent reach envelope, so a banded geometry serves
        # bit-sliced like everything else.
        self.layout = config.effective_layout
        self.geometry: StackGeometry = dataclasses.replace(
            geo if banded else dataclasses.replace(geo, fanin_reach=None),
            frontend=FrontendSpec(
                n_features=_N_FEATURES,
                frame_shape=(N_T, N_Y, N_X),
                threshold_electrons=config.threshold_electrons,
            ),
        )
        self.n_replicas = config.n_replicas
        # the SERVED replica encodings, slot-major: replica r of chip c is
        # _replica_configs[c*R + r]. This is the injection surface of
        # ``inject_seu`` and the source of the host oracle's simulators,
        # so both backends agree on every replica's config image.
        self._replica_configs: List = [
            replicate_config(c.config, r)
            for c in self.chips for r in range(self.n_replicas)
        ]
        # integer trigger cuts, baked per slot (refreshed on reconfigure)
        # so both backends cut on the same value for a given dispatch.
        self._thr_raw = np.array(
            [c.score_threshold_raw for c in self.chips], np.int32)
        self._stack = None
        self._frontend = None  # fused frames dispatch, built on first use
        self._mesh = None
        if config.backend == "kernel":
            from repro.kernels.lut_eval import ops as lut_ops
            from repro.launch.mesh import make_readout_mesh

            self._lut_ops = lut_ops
            # ONE readout mesh for both ingestion stages: the features
            # path shards its scoring dispatch over the same "chips" axis
            # as the fused frames frontend, and each device holds its own
            # chips' rows of the stack.
            self._mesh = make_readout_mesh(self.n_chips)
            self._stack = lut_ops.pack_fabrics(
                [c.config for c in self.chips], band=config.band,
                redundancy=config.redundancy, layout=self.layout,
                geometry=(None if envelope is None else
                          dataclasses.replace(self.geometry, frontend=None)),
            ).on_mesh(self._mesh)
            self._out_weight = lut_ops.decode_plan(
                [c.config for c in self.chips], self._stack.n_outputs)
        else:
            self._multisim = MultiFabricSim(
                self._replica_configs, geometry=self.geometry)

        self._queue: Deque[_Event] = collections.deque()
        self._seq = 0
        # per-slot FabricSim cache (one sim per replica) for the staged
        # (host) frames path — pure function of the slot's replica
        # configs, invalidated on reconfigure/inject_seu, so repeated
        # dispatches don't re-pay construction (and the staged_score
        # stage timing stays honest).
        self._frame_sims: List[Optional[List[FabricSim]]] = (
            [None] * len(self.chips))
        # the pipeline: up to config.pipeline_depth batches on the device
        self._inflight: Deque[_Inflight] = collections.deque()
        # host staging buffers of the kernel frames path (launch/staging.py):
        # a batch is staged only while pipeline_depth or fewer are in
        # flight, so pipeline_depth + 1 arenas serve; one more is slack
        self._arenas = StagingArenas(config.pipeline_depth + 2)
        # rows, real events and bytes each device received per dispatch
        self._shards = ShardLedger(
            0 if self._mesh is None else self._mesh.size, self.n_chips)
        self._stats = [
            ChipStreamStats(disagreements=[0] * self.n_replicas)
            for _ in self.chips
        ]
        # per-stage host spans (seconds, calls, longest call), annotated
        # into the profile while one is recording (launch/spans.py)
        self._stages = Stages(clock)
        self._batches_launched = 0   # the next batch's id
        # dispatches of the fused step that grew its jit cache (compiled
        # or loaded a program), and their seconds
        self._compiles = 0
        self._compile_s = 0.0
        # the throughput window: first dispatch and last drain since the
        # last reset, and the events drained in between
        self._t_start: Optional[float] = None
        self._t_last: Optional[float] = None
        self._n_drained_window = 0
        # answers retired since the last reset, and the batches whose
        # answers needed a sort into seq order (report()["drain"])
        self._drain_answers = 0
        self._drain_reordered = 0
        # measured host-link accounting: bytes actually materialized on
        # the wire (sparse packs when a batch drains sparse, dense rows
        # otherwise — the sparse_egress rung can mix both on one server)
        # vs the dense equivalent for the same events
        self._link_bytes_wire = 0
        self._link_bytes_dense = 0

        # ---- latency observability (module doc: deadline-aware serving).
        # End-to-end latency (enqueue -> drained result) per chip and
        # total, plus the queue-wait (enqueue -> coalesce) and service
        # (coalesce -> drained) attributions of the same batches — the
        # full/no-transfer-style overlay that says WHERE a tail lives.
        self._hist_total = LatencyHistogram()
        self._hist_queue = LatencyHistogram()
        self._hist_service = LatencyHistogram()
        self._hist_chip = [LatencyHistogram() for _ in self.chips]
        # the newest drained batches' monotonic stage timestamps
        # (enqueue-oldest -> coalesce -> encode/stack -> launch -> collect
        # -> drain -> delivered), summarized as report()["latency"]["phases"]
        self._ring = BatchRing()
        self._n_batches_drained = 0

        # ---- deadline enforcement state.
        self._deadline_met = 0
        self._deadline_missed = 0
        # EWMA of the batch service time (coalesce -> drained): the
        # admission controller's estimate of how long a newly admitted
        # event will wait beyond the queue's current oldest-event wait
        self._service_ewma_s = 0.0
        # (t_drained, n_events) of recent retired batches — the sliding
        # window behind _drain_rate(), admission's backlog-drain term
        self._drain_hist: Deque[Tuple[float, int]] = collections.deque(
            maxlen=16)
        # adaptive micro-batch knobs: the coalescer reads THESE, the
        # config fields stay the (immutable) ceilings
        self._eff_max_batch = config.max_batch
        self._min_batch = min(config.min_batch, config.max_batch)
        if (config.deadline_s is not None
                and config.overload_policy != "observe"):
            # never coalesce past half the budget — the other half is
            # for service (the EWMA refines this cap adaptively)
            self._lat_cap_s = min(config.max_latency_s,
                                  config.deadline_s / 2.0)
        else:
            self._lat_cap_s = config.max_latency_s
        self._eff_max_latency_s = self._lat_cap_s
        self._batch_shrinks = 0
        self._batch_grows = 0

        # ---- degrade ladder state (overload_policy="degrade").
        # level k = the first k rungs of config.degrade_rungs are active;
        # evaluated once per degrade_window drained events, hysteretically
        self._rung_level = 0
        self._ladder_transitions: List[Dict[str, object]] = []
        self._window_missed = 0
        self._window_drained = 0
        # (slot, replica) frames whose CRC failed while the
        # scrub_crc_only rung deferred the heal — repaired on rung exit
        self._deferred_heals: List[Tuple[int, int]] = []

        # ---- scrubbing state (readback -> verify -> heal; module doc).
        # One shared image layout for readbacks AND golden digests: the
        # kernel stack's padded (levels, m_pad) geometry, mirrored by the
        # same formula on the host backend so either backend's readback
        # verifies against the same digest.
        if self._stack is not None:
            self._img_levels = self._stack.n_levels
            self._img_m_pad = self._stack.m_pad
        else:
            self._img_levels = self.geometry.n_levels
            self._img_m_pad = -(-self.geometry.max_level_size // 128) * 128
        self._golden = GoldenImageStore()
        for i in range(self.n_chips):
            self._register_golden(i)
        self._dispatch_idx = 0
        n_frames = self.n_chips * self.n_replicas
        self._scrub_rr = 0          # round-robin frame pointer
        self._scrub_cycles = 0      # completed full round-robin passes
        self._scrub_steps = 0
        self._scrub_detections = 0
        self._scrub_healed_bits = 0
        # per-detection staleness window: dispatches since the corrupted
        # frame's last clean scrub — the measured detection latency
        self._scrub_latencies: List[int] = []
        self._scrub_per_frame = [0] * n_frames
        # disagreement snapshot at each frame's last scrub (steering key)
        self._scrub_last_dis = [0] * n_frames
        # dispatch index at each frame's last scrub (latency reference)
        self._scrub_last_pass = [0] * n_frames
        # kernel-backend readbacks in flight: (frame, generation, device
        # array, prev_pass, issue_idx). The device->host copy is issued
        # async and
        # VERIFIED on a later scrub step, so scrubbing never blocks on
        # the dispatch just launched (a synchronous readback would stall
        # the triple-buffered pipeline every interval — measured at ~25%
        # events/s, 5x the scrub budget).
        self._scrub_pending: Deque[Tuple[int, int, object, int, int]] = (
            collections.deque())
        # bumped whenever a frame's served arrays are re-encoded (inject,
        # heal, reconfigure): a pending readback sampled before the bump
        # is stale and must not be verified against the new truth
        self._frame_gen = [0] * n_frames

        # ---- network front door accounting (net/ingress.py attaches a
        # stats provider; report()["net"] surfaces it — per-client drop/
        # reorder/resync counters live with the front door, not here)
        self._net_stats_provider: Optional[Callable[[], Dict]] = None

    def attach_net_stats(self, provider: Callable[[], Dict]) -> None:
        """Register the network front door's ``stats`` callable; its
        snapshot appears under ``report()["net"]``. Pass None to detach."""
        self._net_stats_provider = provider

    # ------------------------------------------------------------- intake
    @property
    def n_chips(self) -> int:
        return len(self.chips)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def _admit(self, chip: int, now: float) -> bool:
        """Deadline admission control (overload_policy "shed"/"degrade"):
        a new submission is shed — counted per chip, never silent — when
        its predicted completion blows the deadline. Two predictors, the
        worse one decides:

        * oldest-event slack: the queue head has waited ``wait``; it
          still needs ~one EWMA service time. If the HEAD is already
          blowing the budget, everything behind it is too.
        * backlog drain: a newcomer joins the BACK of the queue — it
          waits ~queue_len / drain_rate before its batch even coalesces.
          Under a fast-building burst this term trips long before the
          head's elapsed wait does.

        Rejecting at submit is the only place the loss is cheap. With
        both predictors under budget (or no deadline / "observe") every
        submission is admitted — tests/test_deadline.py's admission
        property."""
        dl = self.config.deadline_s
        if dl is None or self.config.overload_policy == "observe":
            return True
        if not self._queue and not self._inflight:
            # idle probe: with nothing queued or in flight a lone event
            # can only miss if service itself exceeds the deadline — and
            # admitting it is the ONLY way to refresh a stale EWMA (one
            # slow batch, e.g. a jit compile, would otherwise lock the
            # server into shedding everything forever)
            return True
        wait = (now - self._queue[0][4]) if self._queue else 0.0
        rate = self._drain_rate()
        backlog = (len(self._queue) / rate) if rate > 0.0 else 0.0
        if max(wait, backlog) + self._service_ewma_s < dl:
            return True
        self._stats[chip].n_shed += 1
        return False

    def submit(self, chip: int, features: np.ndarray) -> Optional[int]:
        """Enqueue one pre-featurized event for one chip; returns its seq,
        or None when deadline admission control shed it (the shed is
        counted in the chip's ``n_shed``)."""
        assert 0 <= chip < self.n_chips, chip
        with self._stages.span("admit", self._batches_launched) as sp:
            return self._submit_features(chip, features, sp.t0)

    def submit_batch(self, chip: int, X: np.ndarray) -> List[Optional[int]]:
        """Enqueue a block of pre-featurized events (rows of X); shed
        rows yield None in the returned seq list."""
        assert 0 <= chip < self.n_chips, chip
        with self._stages.span("admit", self._batches_launched):
            return [self._submit_features(chip, row, self._clock())
                    for row in np.asarray(X)]

    def _submit_features(self, chip: int, features, now: float
                         ) -> Optional[int]:
        if not self._admit(chip, now):
            return None
        seq = self._seq
        self._seq += 1
        self._queue.append(
            (seq, chip, "features", np.asarray(features, np.float64), now)
        )
        return seq

    def cancel_queued(self, chip: int) -> int:
        """Drop every QUEUED (admitted, not yet coalesced) event of one
        chip slot; returns how many were dropped.

        The eviction port of the fleet layer (launch/fleet.py): when a
        tenant is evicted without draining, its queued events are
        cancelled here — and counted by the fleet as
        ``evicted_while_queued``, so the per-tenant accounting identity
        still closes. Events already coalesced into an in-flight batch
        are NOT cancelled (the device is already scoring them); they
        drain normally and are delivered before the slot is reused.
        Other chips' events are untouched.
        """
        assert 0 <= chip < self.n_chips, chip
        n0 = len(self._queue)
        self._queue = collections.deque(
            e for e in self._queue if e[1] != chip)
        return n0 - len(self._queue)

    def submit_frames(
        self, chip: int, frames: np.ndarray, y0: np.ndarray
    ) -> List[Optional[int]]:
        """Enqueue raw-frame events: (n, T, Y, X) charge + (n,) y0.

        These score through the frames pipeline — on the kernel backend
        the FUSED single-dispatch frontend, on the host backend the same
        pipeline staged. Mixing frames and features for the same chip in
        one micro-batch is allowed but scores as two dispatch groups, so
        cross-kind result order within that batch follows the groups, not
        the global seq order (every event stays seq-tagged).
        """
        assert 0 <= chip < self.n_chips, chip
        frames = np.asarray(frames, np.float32)
        y0 = np.asarray(y0, np.float32)
        assert frames.ndim == 4 and frames.shape[1:] == (N_T, N_Y, N_X), \
            frames.shape
        assert len(frames) == len(y0), (len(frames), len(y0))
        seqs: List[Optional[int]] = []
        with self._stages.span("admit", self._batches_launched) as sp:
            now = sp.t0
            for i in range(len(frames)):
                if not self._admit(chip, now):
                    seqs.append(None)
                    continue
                seq = self._seq
                self._seq += 1
                self._queue.append(
                    (seq, chip, "frames", (frames[i], float(y0[i])), now))
                seqs.append(seq)
        return seqs

    # ------------------------------------------------------------ the loop
    def poll(self) -> List[ScoredEvent]:
        """One turn of the event loop: retire any in-flight batches that
        finished, dispatch if a micro-batch is due and the pipeline has
        room, and return completed results (seq-ordered per batch).

        Never blocks. When the pipeline is at capacity the due batch
        stays in the queue — its wait is then visible to `_admit`, so
        overload turns into counted sheds instead of an invisible stall
        of the submitting thread."""
        out = self._drain_ready()
        if self._due() and len(self._inflight) <= self.config.pipeline_depth:
            out.extend(self._dispatch(self._coalesce()))
        self._ring.deliver_at(self._clock)
        return out

    def flush(self) -> List[ScoredEvent]:
        """Force out everything: queued events and in-flight results.

        With scrubbing enabled the flush also settles the scrub loop:
        readback samples still in flight are resolved (the device is
        idle now, so this blocks on nothing), and a final steered check
        chases any disagreement counters that only folded during this
        drain — so a fault implicated by the stream's last batches is
        healed at flush instead of waiting for the next stream."""
        out: List[ScoredEvent] = []
        while self._queue:
            out.extend(self._dispatch(self._coalesce()))
            while len(self._inflight) > self.config.pipeline_depth:
                out.extend(self._drain_one())       # flush MAY block
        out.extend(self._drain_all())
        if self.config.scrub_interval is not None:
            with self._stages.span("scrub", self._batches_launched - 1):
                self.scrub_flush()
                if self.config.scrub_mode == "steered":
                    self._scrub_steered_check()
                    self.scrub_flush()      # device idle: resolve it now
        self._ring.deliver_at(self._clock)
        return out

    def score_stream(
        self, batches: Iterable[Tuple[int, np.ndarray]]
    ) -> Iterable[List[ScoredEvent]]:
        """Drive the loop over an iterable of (chip, features-block) pairs,
        yielding completed results as they become available."""
        for chip, X in batches:
            self.submit_batch(chip, X)
            got = self.poll()
            if got:
                yield got
        tail = self.flush()
        if tail:
            yield tail

    def _due(self) -> bool:
        # the EFFECTIVE knobs, not the config ceilings: under deadline
        # pressure the adaptive sizer shrinks both (see _adapt_batch)
        if not self._queue:
            return False
        if len(self._queue) >= self._eff_max_batch:
            return True
        oldest = self._queue[0][4]
        return (self._clock() - oldest) >= self._eff_max_latency_s

    def _coalesce(self) -> List[_Event]:
        take = min(len(self._queue), self._eff_max_batch)
        return [self._queue.popleft() for _ in range(take)]

    def _dispatch(self, events: List[_Event]) -> List[ScoredEvent]:
        """Launch one micro-batch and return any batches the pipeline
        retired: with the kernel backend dispatches are asynchronous, so
        up to ``pipeline_depth`` batches stay on the device while the
        host prepares the next (triple buffering at the default depth 2).
        Retirement is non-blocking — a batch comes off only once its
        device arrays are ready; ``flush`` settles the rest.
        """
        if not events:
            return []
        if self._t_start is None:
            self._t_start = self._clock()

        frame_events = [e for e in events if e[2] == "frames"]
        feat_events = [e for e in events if e[2] == "features"]
        if frame_events:
            self._inflight.append(self._launch_frames(frame_events))
        if feat_events:
            self._inflight.append(self._launch_features(feat_events))

        done = self._drain_ready()
        # background scrub task, interleaved with dispatches: runs after
        # the drain so freshly-folded disagreement counters can steer it,
        # while the just-launched batch is still computing on the device
        self._dispatch_idx += 1
        si = self._effective_scrub_interval()
        if si is not None and self._dispatch_idx % si == 0:
            self.scrub_step()
        return done

    def _effective_scrub_interval(self) -> Optional[int]:
        """The configured scrub interval, widened by SCRUB_RELAX_FACTOR
        while the ladder's scrub_relax rung is active (slower repair
        buys dispatch headroom; TMR keeps masking meanwhile)."""
        si = self.config.scrub_interval
        if si is not None and self._rung_active("scrub_relax"):
            si = si * SCRUB_RELAX_FACTOR
        return si

    def _group(
        self, events: List[_Event]
    ) -> Tuple[List[List[int]], List[List[object]], List[int],
               List[List[float]]]:
        per_chip_seq: List[List[int]] = [[] for _ in self.chips]
        per_chip_payload: List[List[object]] = [[] for _ in self.chips]
        per_chip_t: List[List[float]] = [[] for _ in self.chips]
        for seq, chip, _, payload, t_enq in events:
            per_chip_seq[chip].append(seq)
            per_chip_payload[chip].append(payload)
            per_chip_t[chip].append(t_enq)
        counts = [len(s) for s in per_chip_seq]
        for i, n in enumerate(counts):
            if n:
                self._stats[i].n_dispatches += 1
        return per_chip_seq, per_chip_payload, counts, per_chip_t

    @staticmethod
    def _pad_batch(B: int) -> int:
        """Round a kernel-backend batch width up to the next power of
        two. The jit signature of a dispatch is its padded shape: with
        raw ``max(counts)`` widths every queue wobble (and every move of
        the adaptive batch sizer) mints a fresh shape and pays a fresh
        compile — ~150 ms, i.e. many deadlines — exactly when the server
        is under pressure. Bucketing bounds the compiled set to
        log2(max_batch) shapes, all touched during warmup."""
        return 1 << (max(int(B), 1) - 1).bit_length()

    def _valid_mask(self, counts: List[int], B: int) -> np.ndarray:
        """(C, B) bool: True on real event rows, False on zero-padding —
        the mask that keeps phantom padded events out of the keep/drop
        decisions, the sparse pack and the disagreement counters."""
        return (np.arange(max(B, 1))[None, :]
                < np.asarray(counts)[:, None])

    def _sparse_active(self) -> bool:
        """Sparse egress is on when configured OR forced by the degrade
        ladder's sparse_egress rung (keep/drop stays bit-exact — only the
        NON-kept scores stop crossing the link)."""
        return self.config.sparse or self._rung_active("sparse_egress")

    def _word_sparse_active(self) -> bool:
        """True when a launch should use the WORD-domain sparse dispatch:
        sparse egress on a bit-sliced kernel stack. There the keep cut,
        SEU counters and compaction all run on sliced words inside the
        scoring jit itself — dropped events are never transposed back to
        event order, so there is no separate pack dispatch at all."""
        return (self._sparse_active()
                and self.config.backend == "kernel"
                and self._stack is not None and self._stack.bitsliced)

    def _finish_launch_sparse(
        self, count, idx, vals, disagree, B, per_chip_seq, counts, meta
    ) -> _Inflight:
        """Output stage of the word-domain sparse dispatches: the packed
        (count, idx, vals) wire tuple came straight out of the scoring
        jit (same format as sparse_trigger_pack), so there is nothing
        left to pack — just record the launch and enqueue."""
        meta["trace"]["t_launched"] = self._clock()
        return ("sparse", (count, idx, vals, disagree, int(B)),
                per_chip_seq, counts, meta)

    def _finish_launch(
        self, score, keep, disagree, per_chip_seq, counts, meta
    ) -> _Inflight:
        """Common output stage: dense (score, keep) or the sparse packed
        (indices, scores) pair. On the kernel backend the pack is one
        extra device dispatch, still asynchronous — nothing materializes
        until the drain (bit-sliced kernel launches never get here with
        sparse on: their pack is fused into the scoring jit, see
        ``_word_sparse_active``)."""
        sparse = self._sparse_active()
        if not sparse:
            meta["trace"]["t_launched"] = self._clock()
            return ("scored", (score, keep, disagree), per_chip_seq,
                    counts, meta)
        B = int(np.shape(keep)[1])
        with self._stages.span("sparse_pack", meta["batch"]):
            if self.config.backend == "kernel":
                from repro.parallel.compression import sparse_trigger_pack_jit

                count, idx, vals = sparse_trigger_pack_jit(score, keep)
            else:
                flat = np.asarray(keep).ravel()
                idx = np.flatnonzero(flat).astype(np.int32)
                vals = np.asarray(score).ravel()[idx].astype(np.int32)
                count = len(idx)
        meta["trace"]["t_launched"] = self._clock()
        return ("sparse", (count, idx, vals, disagree, B),
                per_chip_seq, counts, meta)

    def _new_batch(self, events: List[_Event], B: int,
                   per_chip_t: List[List[float]]) -> Dict:
        """A coalesced batch's id and trace: the ledger ``_observe_batch``
        folds and the row ``report()["latency"]["phases"]`` reads."""
        bid = self._batches_launched
        self._batches_launched += 1
        trace = {"t_enqueued": min(e[4] for e in events),
                 "t_coalesced": self._clock()}
        return {"t_enq": per_chip_t, "trace": trace, "batch": bid,
                "padded": B, "compiled": False}

    def _launch_features(self, events: List[_Event]) -> _Inflight:
        """Features path: host featurization (quantize + offset-binary bit
        packing, timed as ``encode_host``) into ONE sharded chip-batched
        scoring dispatch — fabric evaluation (all replicas), majority
        vote, score decode and trigger cut all on device
        (lut_eval.ops.fabric_eval_multi_scored), chip axis over the
        readout mesh."""
        per_chip_seq, per_chip_X, counts, per_chip_t = self._group(events)
        B = max(counts) if counts else 0
        if self.config.backend == "kernel":
            B = self._pad_batch(B)      # stable jit signatures (pow2)
        meta = self._new_batch(events, B, per_chip_t)
        trace, bid = meta["trace"], meta["batch"]

        with self._stages.span("encode_host", bid) as sp:
            per_chip_bits: List[np.ndarray] = []
            for i, chip in enumerate(self.chips):
                if per_chip_X[i]:
                    bits = chip.encode_features(np.stack(per_chip_X[i]))
                else:
                    bits = np.zeros((0, chip.config.n_inputs), np.uint8)
                per_chip_bits.append(bits)
        trace["t_encoded"] = sp.t1

        word_sparse = self._word_sparse_active()
        with self._stages.span("launch_score", bid):
            valid = self._valid_mask(counts, B)
            if self.config.backend == "kernel":
                lead = per_chip_bits[0]
                if len(lead) < B:       # stack_event_bits pads to the max
                    per_chip_bits[0] = np.vstack(
                        [lead, np.zeros((B - len(lead), lead.shape[1]),
                                        np.uint8)])
                stacked = self._lut_ops.stack_input_bits(
                    self._stack, per_chip_bits)
                # async on device, NOT materialized yet; the word-sparse
                # form fuses the keep cut and the compaction into the jit
                score = (self._lut_ops.fabric_eval_multi_scored_sparse
                         if word_sparse
                         else self._lut_ops.fabric_eval_multi_scored)
                out = score(self._stack, stacked, self._out_weight,
                            self._thr_raw, valid=valid, mesh=self._mesh,
                            batch_tile=self.config.batch_tile)
            else:
                stacked = stack_event_bits(per_chip_bits,
                                           self.geometry.n_inputs)
                out = self._score_bits_host(stacked, valid)
        if word_sparse:
            return self._finish_launch_sparse(
                *out, B, per_chip_seq, counts, meta)
        return self._finish_launch(*out, per_chip_seq, counts, meta)

    def _score_bits_host(
        self, stacked: np.ndarray, valid: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The numpy oracle of the device scoring stage: evaluate every
        replica (MultiFabricSim over the served replica configs), vote
        with THE SAME core.tmr.majority_vote, decode two's-complement
        scores, cut, count disagreements — bit-identical by construction."""
        C, B = stacked.shape[0], stacked.shape[1]
        R = self.n_replicas
        rep = np.repeat(stacked, R, axis=0) if R > 1 else stacked
        outs = self._multisim.run(rep)                  # (R*C, B, O)
        g = outs.reshape(C, R, B, outs.shape[-1])
        if R > 1:
            voted = majority_vote(g[:, 0], g[:, 1], g[:, 2])
            disagree = (g != voted[:, None]).any(-1)    # (C, R, B)
        else:
            voted = g[:, 0]
            disagree = np.zeros((C, 1, B), bool)
        score = np.zeros((C, B), np.int64)
        for i, chip in enumerate(self.chips):
            n_out = len(chip.config.output_nets)
            score[i] = chip.synth.decode_outputs(voted[i, :, :n_out])
        keep = (score <= self._thr_raw[:, None]) & valid
        dis = (disagree & valid[:, None, :]).sum(-1).astype(np.int64)
        return score, keep, dis

    def _launch_frames(self, events: List[_Event]) -> _Inflight:
        """Frames path. Kernel backend: ONE fused dispatch over the
        sharded chip axis (timed ``launch_fused`` — featurize, quantize,
        pack, replica evaluation, vote and score all live inside it,
        invisible to the host by design). Host backend: the same
        pipeline STAGED, each stage materialized and timed
        (``staged_featurize`` / ``staged_encode`` / ``staged_score``) —
        the breakdown the fused path removes.
        """
        per_chip_seq, per_chip_fy, counts, per_chip_t = self._group(events)
        cfg = self.config
        B = max(counts) if counts else 0
        if cfg.backend == "kernel":
            B = self._pad_batch(B)      # stable jit signatures (pow2)
        meta = self._new_batch(events, B, per_chip_t)
        bid = meta["batch"]
        valid = self._valid_mask(counts, B)

        if cfg.backend == "kernel":
            with self._stages.span("stack_frames", bid) as sp:
                # rows are written in place into a reused arena; only the
                # pad rows past each chip's count are zeroed
                shape = (self.n_chips, B, N_T, N_Y, N_X)
                size = math.prod(shape)
                arena = self._arenas.take(size)
                frames = arena[:size].reshape(shape)
                y0 = np.zeros((self.n_chips, B), np.float32)
                for i, rows in enumerate(per_chip_fy):
                    n = len(rows)
                    if n:
                        np.stack([fr for fr, _ in rows], out=frames[i, :n])
                        y0[i, :n] = [z for _, z in rows]
                    frames[i, n:] = 0.0
            meta["trace"]["t_encoded"] = sp.t1
            meta["arena"] = arena

            frontend = self._get_frontend()
            word_sparse = self._word_sparse_active()
            n_programs = frontend.compiled_programs()
            with self._stages.span("launch_fused", bid) as sp:
                with self._stages.span("place_frames", bid):
                    placed = frontend.place(frames, y0, valid)
                out = frontend.score_placed(placed, sparse=word_sparse)
            self._shards.add(counts, placed.width, row_bytes=(
                frames[0, 0].nbytes + y0.itemsize + valid.itemsize))
            if frontend.compiled_programs() > n_programs:
                # this dispatch compiled (or loaded) a program
                self._compiles += 1
                self._compile_s += sp.t1 - sp.t0
                meta["compiled"] = True
            if word_sparse:
                return self._finish_launch_sparse(
                    *out, B, per_chip_seq, counts, meta)
            return self._finish_launch(*out, per_chip_seq, counts, meta)

        # host backend: staged oracle, per chip, one sim per replica
        R = self.n_replicas
        score = np.zeros((self.n_chips, B), np.int64)
        disagree = np.zeros((self.n_chips, R, B), bool)
        for i, chip in enumerate(self.chips):
            if not per_chip_fy[i]:
                continue
            n = counts[i]
            frames_i = np.stack([fr for fr, _ in per_chip_fy[i]])
            y0_i = np.asarray([z for _, z in per_chip_fy[i]], np.float32)
            from repro.kernels.yprofile import ops as yp_ops

            with self._stages.span("staged_featurize", bid):
                feats = np.asarray(yp_ops.yprofile(
                    frames_i, y0_i,
                    threshold_electrons=cfg.threshold_electrons,
                    batch_tile=cfg.batch_tile))
            with self._stages.span("staged_encode", bid):
                bits = chip.encode_features(feats)
            with self._stages.span("staged_score", bid):
                if self._frame_sims[i] is None:
                    self._frame_sims[i] = [
                        FabricSim(self._replica_configs[i * R + r])
                        for r in range(R)
                    ]
                g = np.stack([np.asarray(sim.run(bits)[0])
                              for sim in self._frame_sims[i]])  # (R, n, O_i)
                if R > 1:
                    voted = majority_vote(g[0], g[1], g[2])
                    disagree[i, :, :n] = (g != voted[None]).any(-1)
                else:
                    voted = g[0]
                score[i, :n] = chip.synth.decode_outputs(voted)
        keep = (score <= self._thr_raw[:, None]) & valid
        dis = (disagree & valid[:, None, :]).sum(-1).astype(np.int64)
        return self._finish_launch(score, keep, dis, per_chip_seq, counts,
                                   meta)

    def _get_frontend(self):
        if self._frontend is None:
            from repro.kernels import frontend as fe

            self._frontend = fe.pack_frontend(
                [c.config for c in self.chips],
                [c.frontend_spec() for c in self.chips],
                band=self.config.band,
                redundancy=self.config.redundancy,
                layout=self.layout,
                batch_tile=self.config.batch_tile,
                threshold_electrons=self.config.threshold_electrons,
                mesh=self._mesh,
                stack=self._stack,  # share the server's packed arrays
            )
        return self._frontend

    @staticmethod
    def _result_ready(x: object) -> bool:
        """True when materializing ``x`` will not block: jax Arrays
        answer via ``is_ready()``; host-backend results are plain numpy
        (or Python ints) and are always ready."""
        probe = getattr(x, "is_ready", None)
        return True if probe is None else bool(probe())

    def _head_ready(self) -> bool:
        """Non-blocking probe: is the OLDEST in-flight batch finished?"""
        if not self._inflight:
            return False
        kind, pending = self._inflight[0][0], self._inflight[0][1]
        parts = pending[:4] if kind == "sparse" else pending  # drop int B
        return all(self._result_ready(p) for p in parts)

    def _drain_ready(self) -> List[ScoredEvent]:
        """Retire every finished in-flight batch, oldest first, never
        blocking. Retirement must NOT wait for the pipeline to go over
        capacity: a ready batch lingering in flight would count its idle
        time as service, inflating the EWMA that admission control
        subtracts from the deadline — under shedding (no new dispatches
        to push it out) that feedback locks the server into rejecting
        everything. Batches whose device arrays are still cooking stay
        put — the capacity gate in ``poll`` then defers new dispatches so
        backlog lands in the submit queue, in admission's line of sight."""
        out: List[ScoredEvent] = []
        while self._head_ready():
            out.extend(self._drain_one())
        return out

    def _drain_one(self) -> List[ScoredEvent]:
        """Materialize the OLDEST in-flight batch and fold it into the
        reports (``drain_wait`` is the host-visible blocking time). With
        sparse readout only the count-prefix of the packed (idx, score)
        pair crosses the host link — the measured wire bytes."""
        if not self._inflight:
            return []
        kind, pending, per_chip_seq, counts, meta = self._inflight.popleft()
        n_events = int(sum(counts))
        with self._stages.span("drain_wait", meta["batch"]) as sp:
            if kind == "sparse":
                count, idx, vals, dis, B = pending
                n_kept = int(np.asarray(count))             # blocks here
                idx_h = np.asarray(idx[:n_kept]).astype(np.int64)
                vals_h = np.asarray(vals[:n_kept]).astype(np.int64)
                self._link_bytes_wire += (
                    SPARSE_HEADER_BYTES + SPARSE_BYTES_PER_EVENT * n_kept)
                self._link_bytes_dense += DENSE_BYTES_PER_EVENT * n_events
                chip, pos = np.divmod(idx_h, max(B, 1))
                kept_per_chip = np.bincount(chip, minlength=self.n_chips)
                for i, st in enumerate(self._stats):
                    st.n_in += counts[i]
                    st.n_kept += int(kept_per_chip[i])
                # every admitted event's seq, chip-major, and where each
                # chip's events start in it
                seq = np.concatenate(
                    [np.asarray(s, np.int64) for s in per_chip_seq])
                first = np.cumsum(counts) - counts
                results = self._answers(seq[first[chip] + pos], chip, vals_h,
                                        np.ones(n_kept, bool))
            else:  # "scored"
                score, keep, dis = pending
                score = np.asarray(score)                   # blocks here
                keep = np.asarray(keep, bool)
                self._link_bytes_wire += DENSE_BYTES_PER_EVENT * n_events
                self._link_bytes_dense += DENSE_BYTES_PER_EVENT * n_events
                cols: List[Tuple[np.ndarray, ...]] = []
                for i in range(self.n_chips):
                    n = counts[i]
                    if n:
                        self._fold_chip(cols, i, per_chip_seq[i],
                                        score[i, :n], keep[i, :n])
                results = self._answers(*map(np.concatenate, zip(*cols)))
            self._fold_disagreements(dis)
        # the results have materialized, so the step that read the staged
        # frames is done: their arena may take the next batch
        self._arenas.give(meta.get("arena"))
        self._n_drained_window += n_events
        meta["trace"]["t_collect"] = sp.t0
        t_done = sp.t1
        self._t_last = t_done
        self._observe_batch(meta, t_done)
        return results

    def _answers(self, seq: np.ndarray, chip: np.ndarray, score: np.ndarray,
                 keep: np.ndarray) -> List[ScoredEvent]:
        """One retired batch's answers in seq order, built from its
        columns (one entry per answer) in one pass. The columns come
        chip-major; they are already in seq order unless blocks of
        different chips interleaved in the queue, and only then take one
        stable argsort (counted as ``report()["drain"]["reordered"]``)."""
        if not (seq[1:] > seq[:-1]).all():
            order = np.argsort(seq, kind="stable")
            seq, chip, score, keep = (
                seq[order], chip[order], score[order], keep[order])
            self._drain_reordered += 1
        self._drain_answers += len(seq)
        # tuple.__new__ is what ScoredEvent._make calls, without its
        # per-answer Python frame and length check (zip gives 4 fields)
        return list(map(tuple.__new__, itertools.repeat(ScoredEvent), zip(
            seq.tolist(), chip.tolist(), score.tolist(), keep.tolist())))

    # ------------------------------------------- latency / deadline loop
    def reset_latency_metrics(self) -> None:
        """Zero the latency/deadline ledger (histograms, the batch phase
        ring, the stages' longest calls, the compile counter, the staging
        arenas' reused/fresh counts, the drain counters, met/missed/shed
        counters, the EWMA seed and the throughput window) without
        touching trigger accounting, scrub state or the ladder level —
        for measuring a warmed-up server: jit compilation of the first
        dispatch otherwise dominates every percentile of a short run."""
        self._hist_total = LatencyHistogram()
        self._hist_queue = LatencyHistogram()
        self._hist_service = LatencyHistogram()
        self._hist_chip = [LatencyHistogram() for _ in self.chips]
        self._ring.reset()
        self._stages.reset_max()
        self._compiles = 0
        self._compile_s = 0.0
        self._arenas.reset_counts()
        self._shards.reset()
        self._n_drained_window = 0
        self._n_batches_drained = 0
        self._drain_answers = 0
        self._drain_reordered = 0
        self._deadline_met = 0
        self._deadline_missed = 0
        self._service_ewma_s = 0.0
        self._drain_hist.clear()
        self._window_missed = 0
        self._window_drained = 0
        self._batch_shrinks = 0
        self._batch_grows = 0
        self._t_start = None
        self._t_last = None
        for st in self._stats:
            st.n_shed = 0

    def _observe_batch(self, meta: Dict, t_done: float) -> None:
        """Fold one drained batch into the latency ledger, then let the
        deadline machinery act: EWMA service update (feeds admission),
        adaptive micro-batch sizing, and the degrade-ladder evaluation.
        Every ADMITTED event is observed — kept or not, sparse or dense —
        so the histograms and the met/missed ledger cover exactly the
        traffic admission control let through."""
        trace = meta["trace"]
        trace["t_drained"] = t_done
        self._n_batches_drained += 1
        t_co = trace.get("t_coalesced", t_done)
        dl = self.config.deadline_s
        worst_s = 0.0
        n_batch = 0
        for i, ts in enumerate(meta["t_enq"]):
            if not ts:
                continue
            t_enq = np.asarray(ts, np.float64)
            lat_s = np.maximum(t_done - t_enq, 0.0)
            us = lat_s * 1e6
            self._hist_chip[i].add_many(us)
            self._hist_total.add_many(us)
            self._hist_queue.add_many(
                np.maximum(t_co - t_enq, 0.0) * 1e6)
            worst_s = max(worst_s, float(lat_s.max()))
            n_batch += len(ts)
            if dl is not None:
                missed = int((lat_s > dl).sum())
                self._deadline_missed += missed
                self._deadline_met += len(ts) - missed
                self._window_missed += missed
        self._hist_service.add(max(t_done - t_co, 0.0) * 1e6)
        self._ring.record(meta["batch"], n_batch, meta["padded"], trace,
                          meta["compiled"])
        self._window_drained += n_batch
        # EWMA of the batch service time — the admission controller's
        # look-ahead: how long will a newly admitted event take AFTER
        # the queue's current wait. Seeded with the first batch.
        svc = max(t_done - t_co, 0.0)
        self._service_ewma_s = (
            svc if self._n_batches_drained == 1
            else 0.7 * self._service_ewma_s + 0.3 * svc)
        # sliding drain-rate window — the admission controller's backlog
        # term: how fast does the queue in front of a newcomer drain
        self._drain_hist.append((t_done, n_batch))
        if dl is None or self.config.overload_policy == "observe":
            return
        self._adapt_batch(svc, dl)
        if self.config.overload_policy == "degrade":
            self._ladder_evaluate(t_done)

    def _drain_rate(self) -> float:
        """Recent drain throughput (events/s) over the sliding window of
        retired batches; 0.0 until two drains have landed."""
        h = self._drain_hist
        if len(h) < 2:
            return 0.0
        span = h[-1][0] - h[0][0]
        if span <= 0.0:
            return 0.0
        return (sum(n for _, n in h) - h[0][1]) / span

    def _adapt_batch(self, svc_s: float, dl: float) -> None:
        """Adaptive micro-batch sizing, keyed on the SERVICE component
        (coalesce -> drain) — the only part of an event's latency the
        batch size controls. A batch whose service ate over half the
        budget halves the effective max_batch AND max_latency_s (smaller
        batches drain sooner — latency traded against per-dispatch
        efficiency); service back under a quarter of the budget grows
        both toward the config ceilings. Keying on total event latency
        instead would shrink batches when the QUEUE is long — cutting
        throughput exactly when capacity is short. Floors: min_batch and
        deadline/8 — the coalescer never degenerates to one-event
        dispatches."""
        if svc_s > dl / 2.0:
            nb = max(self._min_batch, self._eff_max_batch // 2)
            nl = max(dl / 8.0, self._eff_max_latency_s / 2.0)
            if nb < self._eff_max_batch or nl < self._eff_max_latency_s:
                self._batch_shrinks += 1
            self._eff_max_batch, self._eff_max_latency_s = nb, nl
        elif svc_s <= dl / 4.0:
            nb = min(self.config.max_batch, self._eff_max_batch * 2)
            nl = min(self._lat_cap_s, self._eff_max_latency_s * 2.0)
            if nb > self._eff_max_batch or nl > self._eff_max_latency_s:
                self._batch_grows += 1
            self._eff_max_batch, self._eff_max_latency_s = nb, nl

    def _rung_active(self, rung: str) -> bool:
        """Ladder level k activates the FIRST k configured rungs."""
        return rung in self.config.degrade_rungs[: self._rung_level]

    def _ladder_evaluate(self, now: float) -> None:
        """One hysteretic ladder evaluation per degrade_window drained
        events: a window missing at >= enter_frac steps DOWN one rung, at
        <= exit_frac steps back UP; in between the ladder holds. One
        transition per window at most — the ladder cannot flap."""
        if self._window_drained < self.config.degrade_window:
            return
        miss_frac = self._window_missed / self._window_drained
        self._window_missed = 0
        self._window_drained = 0
        level = self._rung_level
        if miss_frac >= self.config.degrade_enter_frac:
            new = min(level + 1, len(self.config.degrade_rungs))
        elif miss_frac <= self.config.degrade_exit_frac:
            new = max(level - 1, 0)
        else:
            new = level
        if new != level:
            self._set_rung_level(new, miss_frac, now)

    def _set_rung_level(self, new: int, miss_frac: float,
                        now: float) -> None:
        old = self._rung_level
        rungs = self.config.degrade_rungs
        crc_was_active = self._rung_active("scrub_crc_only")
        self._rung_level = new
        self._ladder_transitions.append({
            "t": now,
            "from_level": old,
            "to_level": new,
            "rung": rungs[new - 1] if new > old else rungs[old - 1],
            "direction": "down" if new > old else "up",
            "miss_frac": round(miss_frac, 4),
        })
        if crc_was_active and not self._rung_active("scrub_crc_only"):
            self._apply_deferred_heals()

    def _apply_deferred_heals(self) -> None:
        """Repair every frame whose heal the scrub_crc_only rung
        deferred: fresh readback, re-verify (the fault may have been
        healed by a reconfigure meanwhile), heal on mismatch."""
        pending, self._deferred_heals = self._deferred_heals, []
        for slot, replica in pending:
            image = np.asarray(
                self.readback_frame(slot, replica)).astype(np.uint8)
            if not self._golden.verify(slot, replica, image):
                self._scrub_healed_bits += self._heal_frame(
                    slot, replica, image)

    def _fold_chip(self, cols, i, seqs, scores, keep) -> None:
        """Count chip i's share of a dense batch and add its answers to
        the batch's columns as one (seq, chip, score, keep) group."""
        st = self._stats[i]
        st.n_in += len(seqs)
        st.n_kept += int(np.count_nonzero(keep))
        cols.append((np.asarray(seqs, np.int64), np.full(len(seqs), i),
                     scores, keep))

    def _fold_disagreements(self, dis) -> None:
        dis = np.asarray(dis)                           # (C, R)
        for i, st in enumerate(self._stats):
            st.disagreements = [
                a + int(b) for a, b in zip(st.disagreements, dis[i])
            ]

    def _drain_all(self) -> List[ScoredEvent]:
        out: List[ScoredEvent] = []
        while self._inflight:
            out.extend(self._drain_one())
        return out

    # ------------------------------------------------------- reconfigure
    def reconfigure(self, slot: int, new_chip: ReadoutChip) -> List[ScoredEvent]:
        """Hot-swap slot's bitstream: array swap, no recompile.

        Pending events are flushed first (they were submitted against the
        old configuration); returns their results. The new config must fit
        the server's fixed envelope — enforced identically on both
        backends, and ``self.geometry`` never changes, so callers can keep
        pre-checking candidates with ``server.geometry.admits(cfg)``. When
        the fused frames frontend is live, the swap also replaces the
        chip's encode-plan row (used features, ap_fixed spec, trigger
        cut), still with no retrace. Under TMR all three replica slots
        are re-encoded from the new bitstream.
        """
        assert 0 <= slot < self.n_chips, slot
        cfg = new_chip.config
        if cfg.n_ffs or not self.geometry.admits(cfg):
            raise ValueError(
                f"new config does not fit server envelope {self.geometry} "
                f"(levels={len(cfg.level_sizes)}, "
                f"widest={max(cfg.level_sizes, default=1)}, "
                f"inputs={cfg.n_inputs}, outputs={len(cfg.output_nets)}, "
                f"ffs={cfg.n_ffs}, fanin_reach={cfg.fanin_reach()})"
            )
        # feature-stage contract: enforced on BOTH backends at swap time
        # (same promise as admits, for the featurizer axes) — not deferred
        # to an index error inside a later frames dispatch.
        from repro.kernels.frontend import validate_chip_frontend

        validate_chip_frontend(cfg, new_chip.frontend_spec(),
                               self.geometry.frontend.n_features)
        done = self.flush()
        R = self.n_replicas
        self._replica_configs[slot * R : (slot + 1) * R] = [
            replicate_config(cfg, r) for r in range(R)
        ]
        self.chips[slot] = new_chip
        self._thr_raw = np.array(
            [c.score_threshold_raw for c in self.chips], np.int32)
        if self.config.backend == "kernel":
            self._stack = self._stack.swap_chip(slot, cfg)
            self._out_weight = self._lut_ops.decode_plan(
                [c.config for c in self.chips], self._stack.n_outputs)
            if self._frontend is not None:
                self._frontend = self._frontend.swap_chip(
                    slot, cfg, new_chip.frontend_spec(), stack=self._stack)
        self._frame_sims[slot] = None
        if self.config.backend == "host":
            self._multisim = MultiFabricSim(
                self._replica_configs, geometry=self.geometry)
        # the slot's golden truth IS the new bitstream now; re-snapshot the
        # digests and re-baseline the steering counters so stale
        # disagreements from the old configuration don't attract scrubs
        self._register_golden(slot)
        for r in range(self.n_replicas):
            fi = self._frame_index(slot, r)
            self._frame_gen[fi] += 1    # pending samples of the old
            self._scrub_last_dis[fi] = (   # bitstream are stale now
                self._stats[slot].disagreements[r])
        return done

    def rebind_mesh(self, mesh) -> List[ScoredEvent]:
        """Re-place the kernel stack onto a (possibly different) device
        mesh — the fleet grow/shrink port (launch/fleet.py).

        Pending work is flushed first (returned, like ``reconfigure``),
        then the packed stack and the fused frontend's encode plan (if
        live) are placed on the new mesh, each chip's rows on the device
        that serves it (``PackedFabricStack.on_mesh``), and the
        ``shards`` ledger starts over for the new device count. Rebinding
        to a mesh EQUAL to the current one (same devices, same axes) is
        free: jit static-arg caching compares meshes by value, so nothing
        retraces. A genuinely different slab retraces once on the next
        dispatch — grow/shrink is a control-plane event, not the
        zero-retrace tenant-admission path. No-op on the host backend.
        """
        if self.config.backend != "kernel":
            return []
        done = self.flush()
        if mesh == self._mesh:
            return done
        self._mesh = mesh
        self._stack = self._stack.on_mesh(mesh)
        self._shards = ShardLedger(mesh.size, self.n_chips)
        if self._frontend is not None:
            self._frontend = self._frontend.on_mesh(mesh, self._stack)
        return done

    # ----------------------------------------------------- fault injection
    def inject_seu(self, slot: int, replica: int, lut_index: int,
                   bit: int) -> None:
        """Flip one configuration bit of ONE served replica — the
        fault-injection port of the SEU campaign (tests/test_seu.py).

        ``lut_index``/``bit`` address the replica's OWN decoded bitstream
        (its placement-rotated encoding), exactly as a configuration-
        memory upset would. Takes effect on the next dispatch; batches
        already in flight scored against the pre-fault arrays, which is
        what a real upset does too. Works on both backends (the host
        oracle's simulators are rebuilt from the same perturbed config),
        and on a non-redundant server (replica 0) as the unprotected
        negative control. Repeated calls accumulate flips.
        """
        assert 0 <= slot < self.n_chips, slot
        R = self.n_replicas
        if not 0 <= replica < R:
            raise ValueError(f"replica must be in [0, {R}), got {replica!r}")
        i = slot * R + replica
        self._frame_gen[i] += 1     # invalidates pre-flip scrub samples
        self._replica_configs[i] = _inject_seu_config(
            self._replica_configs[i], lut_index, bit)
        if self.config.backend == "kernel":
            if R > 1:
                self._stack = self._stack.swap_replica(
                    slot, replica, self._replica_configs[i])
            else:
                self._stack = self._stack.swap_chip(
                    slot, self._replica_configs[i])
            if self._frontend is not None:
                self._frontend = dataclasses.replace(
                    self._frontend, stack=self._stack)
        else:
            # only the flipped replica's simulator rebuilds — a sweep
            # flips thousands of bits, a fleet rebuild per flip won't do
            self._multisim.swap_config(i, self._replica_configs[i])
        self._frame_sims[slot] = None

    # ----------------------------------------------------------- scrubbing
    def _register_golden(self, slot: int) -> None:
        """Snapshot slot's golden truth (bitstream + per-replica digests)
        — at construction and again on every reconfigure."""
        cfg = self.chips[slot].config
        self._golden.register(slot, cfg, replica_table_images(
            cfg, self._img_levels, self._img_m_pad, self.n_replicas))

    def _frame_index(self, slot: int, replica: int) -> int:
        return slot * self.n_replicas + replica

    def readback_frame(self, slot: int, replica: int = 0) -> np.ndarray:
        """LIVE truth-table image of one served replica frame, in the
        shared padded scrub layout: the device stack's arrays on the
        kernel backend (PackedFabricStack.readback_replica), the
        MultiFabricSim scrub twin on the host oracle — both return what
        is actually being evaluated with, including any injected upset."""
        assert 0 <= slot < self.n_chips, slot
        R = self.n_replicas
        if not 0 <= replica < R:
            raise ValueError(f"replica must be in [0, {R}), got {replica!r}")
        if self.config.backend == "kernel":
            return self._stack.readback_replica(slot, replica)
        return self._multisim.readback_tables(
            self._frame_index(slot, replica),
            self._img_levels, self._img_m_pad)

    def verify_frame(self, slot: int, replica: int = 0) -> bool:
        """CRC-check one replica frame's readback against its golden
        digest (no heal) — the detection half of the scrub loop alone."""
        return self._golden.verify(
            slot, replica, self.readback_frame(slot, replica))

    def scrub_step(self) -> List[Dict[str, int]]:
        """ONE background scrub step: resolve earlier readbacks, then
        sample the next frames (readback -> CRC verify -> heal).

        Always samples the next round-robin frame; in ``steered`` mode a
        replica frame whose disagreement counters climbed since its last
        scrub is sampled FIRST (the health monitor pointing the repair at
        the likely upset), without consuming the round-robin turn — so
        steering accelerates repair but can never starve a frame. On the
        kernel backend the sample is an ASYNC device->host copy verified
        on a later step (see ``_scrub_pending``); the host oracle
        verifies in place. Returns one record per healed frame:
        {"slot", "replica", "healed_bits", "detection_latency_dispatches"}.
        """
        with self._stages.span("scrub", self._batches_launched - 1):
            healed: List[Dict[str, int]] = []
            # resolve readbacks whose device->host copies have completed —
            # and ONLY those: with a short interval the sampled batch can
            # still be in flight behind the pipeline, and blocking on it
            # here would stall exactly the overlap scrubbing must not touch.
            # A copy that never reports ready is force-resolved once the
            # queue exceeds one full frame cycle (bounded staleness).
            n_frames = self.n_chips * self.n_replicas
            still_pending = collections.deque()
            while self._scrub_pending:
                entry = self._scrub_pending.popleft()
                arr = entry[2]
                ready = not hasattr(arr, "is_ready") or arr.is_ready()
                if ready or len(self._scrub_pending) >= n_frames:
                    rec = self._resolve_readback(*entry)
                    if rec:
                        healed.append(rec)
                else:
                    still_pending.append(entry)
            self._scrub_pending = still_pending
            R = self.n_replicas
            if self.config.scrub_mode == "steered":
                healed.extend(self._scrub_steered_check())
            f = self._scrub_rr
            self._scrub_rr = (f + 1) % n_frames
            if self._scrub_rr == 0:
                self._scrub_cycles += 1
            rec = self._issue_scrub(f // R, f % R)
            if rec:
                healed.append(rec)
            self._scrub_steps += 1
        return healed

    def scrub_flush(self) -> List[Dict[str, int]]:
        """Resolve every readback still in flight (blocks on the copies)
        — the scrub analogue of ``flush``."""
        healed: List[Dict[str, int]] = []
        while self._scrub_pending:
            rec = self._resolve_readback(*self._scrub_pending.popleft())
            if rec:
                healed.append(rec)
        return healed

    def scrub_cycle(self) -> List[Dict[str, int]]:
        """Force one full verified pass over every replica frame
        (n_chips x n_replicas scrub steps, then resolve the tail) —
        e.g. before a controlled handover."""
        out: List[Dict[str, int]] = []
        for _ in range(self.n_chips * self.n_replicas):
            out.extend(self.scrub_step())
        out.extend(self.scrub_flush())
        return out

    def _scrub_steered_check(self) -> List[Dict[str, int]]:
        """Sample the replica frame whose disagreement counters climbed
        most since its last scrub (no-op when none climbed) — the health
        monitor pointing the repair at the likely upset. Does not consume
        the round-robin turn."""
        R = self.n_replicas
        n_frames = self.n_chips * R
        deltas = [
            self._stats[f // R].disagreements[f % R]
            - self._scrub_last_dis[f]
            for f in range(n_frames)
        ]
        hot = int(np.argmax(deltas))
        if deltas[hot] <= 0:
            return []
        rec = self._issue_scrub(hot // R, hot % R)
        return [rec] if rec else []

    def _issue_scrub(self, slot: int, replica: int) -> Optional[Dict[str, int]]:
        """Sample one frame's live truth-table image. Host backend: a
        numpy view — verify right here. Kernel backend: enqueue the
        device->host copy asynchronously and verify on a later step, so
        the scrub task never synchronizes with the dispatch it just
        interleaved behind."""
        fi = self._frame_index(slot, replica)
        self._scrub_per_frame[fi] += 1
        # snapshot the health counter: future steering reacts to NEW
        # disagreements only (a healed fault stops attracting scrubs)
        self._scrub_last_dis[fi] = self._stats[slot].disagreements[replica]
        prev_pass = self._scrub_last_pass[fi]
        self._scrub_last_pass[fi] = self._dispatch_idx
        if self.config.backend != "kernel":
            return self._verify_heal(
                slot, replica,
                self._multisim.readback_tables(
                    fi, self._img_levels, self._img_m_pad),
                prev_pass)
        arr = self._stack.tables[fi]
        if hasattr(arr, "copy_to_host_async"):
            arr.copy_to_host_async()
        self._scrub_pending.append(
            (fi, self._frame_gen[fi], arr, prev_pass, self._dispatch_idx))
        return None

    def _resolve_readback(
        self, fi: int, gen: int, arr, prev_pass: int, issue_idx: int
    ) -> Optional[Dict[str, int]]:
        if gen != self._frame_gen[fi]:
            # the frame was re-encoded (inject/heal/reconfigure) after
            # this sample was taken: drop it, and roll back the issue-time
            # bookkeeping so the report never counts an unverified sample
            # as a completed scrub (the frame's next turn re-samples it).
            # Roll the latency reference back ONLY if no newer sample of
            # this frame has advanced it since — a later issue's
            # timestamp must win over this dropped one.
            self._scrub_per_frame[fi] -= 1
            if self._scrub_last_pass[fi] == issue_idx:
                self._scrub_last_pass[fi] = prev_pass
            return None
        R = self.n_replicas
        return self._verify_heal(
            fi // R, fi % R, np.asarray(arr).astype(np.uint8), prev_pass)

    def _verify_heal(
        self, slot: int, replica: int, image: np.ndarray, prev_pass: int
    ) -> Optional[Dict[str, int]]:
        """CRC-verify one sampled image against the golden digest and
        heal on mismatch. ``prev_pass`` is the frame's previous scrub
        dispatch — the detection latency is measured from there."""
        if self._golden.verify(slot, replica, image):
            return None
        latency = self._dispatch_idx - prev_pass
        self._scrub_detections += 1
        self._scrub_latencies.append(latency)
        if self._rung_active("scrub_crc_only"):
            # the ladder's CRC-only rung: detection stays live (the
            # counter above), but the heal — re-encode + array swap on
            # the critical path — is deferred until the rung exits.
            # TMR keeps masking the fault meanwhile.
            key = (slot, replica)
            if key not in self._deferred_heals:
                self._deferred_heals.append(key)
            return {"slot": slot, "replica": replica,
                    "healed_bits": 0, "deferred": 1,
                    "detection_latency_dispatches": latency}
        healed_bits = self._heal_frame(slot, replica, image)
        self._scrub_healed_bits += healed_bits
        return {"slot": slot, "replica": replica,
                "healed_bits": healed_bits,
                "detection_latency_dispatches": latency}

    def _heal_frame(self, slot: int, replica: int, image: np.ndarray) -> int:
        """Re-encode ONE corrupted replica from the golden bitstream —
        the same no-retrace swap machinery as fault injection, pointed
        the other way. Returns the number of healed configuration bits."""
        golden_cfg = self._golden.golden_config(slot)
        rep_cfg = replicate_config(golden_cfg, replica)
        golden_img = packed_table_image(
            rep_cfg, self._img_levels, self._img_m_pad)
        healed_bits = int(np.count_nonzero(image != golden_img))
        i = self._frame_index(slot, replica)
        self._frame_gen[i] += 1
        self._replica_configs[i] = rep_cfg
        if self.config.backend == "kernel":
            self._stack = self._stack.swap_replica(slot, replica, rep_cfg)
            if self._frontend is not None:
                self._frontend = dataclasses.replace(
                    self._frontend, stack=self._stack)
        else:
            self._multisim.swap_config(i, rep_cfg)
        self._frame_sims[slot] = None
        return healed_bits

    # ------------------------------------------------------------ report
    def report(self) -> Dict[str, object]:
        """Per-chip trigger/reduction accounting aggregated over the stream,
        plus the per-stage host spans (seconds, calls and the longest call per
        pipeline stage — for fused frames dispatches the
        featurize/quantize/pack/vote/score stages are a single ``launch_fused``
        entry by design, with the sharded placement of the batch timed inside
        it as ``place_frames``; the staged host path itemizes them), the
        per-replica SEU disagreement counters, the measured host-link bytes
        (sparse wire vs dense equivalent), and the scrub accounting
        (steps/cycles/frames, CRC detections, healed config bits, per-detection
        latency in dispatches). The deadline-aware additions: per-chip and
        total latency histograms (p50/p99/p99.9 + CDF), the last drained
        batch's stage trace and the phases of the newest batches
        (``latency.phases``), the fused-step dispatches that compiled while
        serving (``compiles``), the kernel frames path's staging arenas
        (``staging``: dispatches that ``reused`` an arena or allocated a
        ``fresh`` buffer since the reset, the ``arenas`` alive and their
        ``resident_bytes``), what each device of the "chips" mesh was given by
        those dispatches (``shards``: ``devices``, ``modules_per_device``, and
        since the reset or a mesh rebind the ``dispatches`` and per device the
        event ``rows_per_device`` placed, the real ``events_per_device`` and
        the ``bytes_per_device`` of frames, y0 and valid), the retired batches
        (``drain``: ``batches``, ``answers`` returned and the batches
        ``reordered`` into seq order by a sort), the met/missed/shed
        deadline ledger, the adaptive coalescer's effective knobs, and the
        degrade ladder's level + timestamped transitions. With a network front
        door attached (net/ingress.py), ``"net"`` carries its per-client
        drop/reorder/resync accounting snapshot; otherwise ``{"attached":
        False}``."""
        cfg = self.config
        per_chip = []
        for i, st in enumerate(self._stats):
            frac = st.fraction_kept()
            per_chip.append({
                "chip": i,
                "n_in": st.n_in,
                "n_kept": st.n_kept,
                "n_dispatches": st.n_dispatches,
                "n_shed": st.n_shed,
                "fraction_kept": frac,
                "data_reduction_factor": 1.0 / max(frac, 1e-9),
                "seu_disagreements": list(st.disagreements),
                "latency_p99_us": self._hist_chip[i].percentile(99.0),
            })
        n_in = sum(s.n_in for s in self._stats)
        n_kept = sum(s.n_kept for s in self._stats)
        dt = (
            (self._t_last - self._t_start)
            if (self._t_start is not None and self._t_last is not None)
            else 0.0
        )
        newest = self._ring.newest() or {}
        t_base = newest.get("t_enqueued")
        trace_us = {
            k: (v - t_base) * 1e6 for k, v in newest.items()
            if k.startswith("t_") and math.isfinite(v)
        } if t_base is not None else {}
        n_shed = sum(s.n_shed for s in self._stats)
        return {
            "backend": cfg.backend,
            "layout": self.layout,
            "redundancy": cfg.redundancy,
            "n_replicas": self.n_replicas,
            "sparse": cfg.sparse,
            "n_chips": self.n_chips,
            # ids of the devices the chip axis is sharded over (kernel
            # backend; empty on the host oracle)
            "devices": ([] if self._mesh is None else
                        [int(d.id) for d in self._mesh.devices.flat]),
            "n_in": n_in,
            "n_kept": n_kept,
            "fraction_kept": n_kept / n_in if n_in else 1.0,
            "events_per_s": (self._n_drained_window / dt if dt > 0
                             else float("nan")),
            "queue_depth": self.queue_depth,
            "inflight_batches": len(self._inflight),
            "seu_disagreement_total": int(
                sum(sum(s.disagreements) for s in self._stats)),
            "scrub": {
                "enabled": cfg.scrub_interval is not None,
                "interval": cfg.scrub_interval,
                "mode": cfg.scrub_mode,
                "steps": self._scrub_steps,
                "cycles": self._scrub_cycles,
                "frames_scrubbed": int(sum(self._scrub_per_frame)),
                "detections": self._scrub_detections,
                "healed_bits": self._scrub_healed_bits,
                "detection_latency_dispatches": {
                    "mean": (float(np.mean(self._scrub_latencies))
                             if self._scrub_latencies else 0.0),
                    "max": int(max(self._scrub_latencies, default=0)),
                },
                "per_frame_scrubs": list(self._scrub_per_frame),
            },
            "link_bytes": {
                "on_wire": self._link_bytes_wire,
                "dense_equivalent": self._link_bytes_dense,
                "wire_reduction": (
                    self._link_bytes_dense / self._link_bytes_wire
                    if self._link_bytes_wire
                    and self._link_bytes_wire != self._link_bytes_dense
                    else 1.0),
            },
            "latency": {
                "total": self._hist_total.summary(),
                "queue_wait": self._hist_queue.summary(),
                "service": self._hist_service.summary(),
                "cdf_us": self._hist_total.cdf(),
                "last_batch_trace_us": trace_us,
                "phases": self._ring.summary(),
            },
            "compiles": {"dispatches": self._compiles,
                         "seconds": self._compile_s},
            "staging": self._arenas.report(),
            "shards": self._shards.report(),
            "drain": {"batches": self._n_batches_drained,
                      "answers": self._drain_answers,
                      "reordered": self._drain_reordered},
            "deadline": {
                "deadline_us": cfg.deadline_us,
                "policy": cfg.overload_policy,
                "met": self._deadline_met,
                "missed": self._deadline_missed,
                "shed": n_shed,
                "miss_fraction": (
                    self._deadline_missed
                    / max(self._deadline_met + self._deadline_missed, 1)),
                "service_ewma_us": self._service_ewma_s * 1e6,
                "drain_rate_ev_s": self._drain_rate(),
                "effective_max_batch": self._eff_max_batch,
                "effective_max_latency_s": self._eff_max_latency_s,
                "batch_shrinks": self._batch_shrinks,
                "batch_grows": self._batch_grows,
                "ladder": {
                    "level": self._rung_level,
                    "active_rungs": list(
                        cfg.degrade_rungs[: self._rung_level]),
                    "transitions": list(self._ladder_transitions),
                    "deferred_heals_pending": len(self._deferred_heals),
                },
            },
            "stages": self._stages.report(),
            "net": (self._net_stats_provider()
                    if self._net_stats_provider is not None
                    else {"attached": False}),
            "per_chip": per_chip,
        }
