"""Deadline-aware serving (launch/readout_server.py).

Covers the deadline/overload machinery end to end:
  * ServerConfig validation of the deadline knobs (budget, policy, rungs,
    window, hysteresis gap, min_batch);
  * the layout auto-select default ("bitsliced") for every band value
    (the band is a reach envelope, not a layout knob — no fallback);
  * LatencyHistogram percentiles / CDF / merge on the fixed log grid;
  * the admission-control property (seeded sweeps via tests/_propshim):
    a submission whose predicted completion still has positive slack is
    NEVER shed, and a blown prediction is always shed AND counted;
  * the hysteretic degrade ladder: deterministic down/up transitions
    under a fake clock, one per window, with the scrub_relax rung
    actually widening the effective scrub interval;
  * keep/drop bit-exactness vs the host oracle at EVERY ladder rung
    (sparse_egress returns only the kept events — none mislabeled);
  * service-keyed adaptive micro-batch sizing (shrink/hold/grow bands,
    floors and ceilings);
  * the single injected monotonic clock: wall time passing does NOT
    advance the server's notion of time (satellite: coalesce clock);
  * report() exposing the latency histograms, stage trace, deadline
    ledger and ladder state;
  * sustained 2x overload under "shed" on both backends: results + shed
    == submitted, and the report counts every shed.
"""
import inspect
import logging
import time

import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.readout import ReadoutChip
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.launch import readout_server as rs_mod
from repro.launch.readout_server import (
    DEGRADE_RUNGS, LatencyHistogram, ReadoutServer, ServerConfig,
)
from tests._propshim import given, settings, strategies as st


class FakeClock:
    """Deterministic injected clock (mirrors test_readout_server)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# Module-level chip cache instead of only a fixture: the _propshim
# property tests are zero-argument wrappers (no fixture injection), so
# they pull the same two chips through this memo.
_CACHE = {}


def _duo():
    if "chips" not in _CACHE:
        d = generate(SmartPixelConfig(n_events=8_000, seed=11))
        tr, te = train_test_split(d)
        chips = []
        for depth, leaves in [(4, 8), (3, 5)]:
            clf = GradientBoostedClassifier(
                n_estimators=1, max_depth=depth, max_leaf_nodes=leaves,
                min_samples_leaf=200,
            ).fit(tr["features"], tr["label"])
            chip = ReadoutChip.build(clf)
            chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
            chips.append(chip)
        _CACHE["chips"] = chips
        _CACHE["X"] = te["features"]
    return _CACHE["chips"], _CACHE["X"]


@pytest.fixture(scope="module")
def duo():
    return _duo()


# ------------------------------------------------------- config validation
@pytest.mark.parametrize(
    "kw,msg",
    [
        (dict(deadline_us=0), "deadline_us must be a positive finite"),
        (dict(deadline_us=-3.5), "deadline_us must be a positive finite"),
        (dict(deadline_us=float("nan")),
         "deadline_us must be a positive finite"),
        (dict(deadline_us=float("inf")),
         "deadline_us must be a positive finite"),
        (dict(deadline_us=True), "deadline_us must be a positive finite"),
        (dict(deadline_us=500.0, overload_policy="panic"),
         "unknown overload_policy"),
        (dict(overload_policy="shed"), "needs deadline_us set"),
        (dict(overload_policy="degrade"), "needs deadline_us set"),
        (dict(degrade_rungs=()), "non-empty tuple"),
        (dict(degrade_rungs=("scrub_relax", "scrub_relax")),
         "duplicate degrade rungs"),
        (dict(degrade_rungs=("warp_core",)), "unknown degrade rung"),
        (dict(degrade_window=0), "degrade_window must be an int >= 1"),
        (dict(degrade_window=True), "degrade_window must be an int >= 1"),
        (dict(degrade_enter_frac=0.05, degrade_exit_frac=0.05),
         "hysteresis gap"),
        (dict(degrade_enter_frac=0.2, degrade_exit_frac=0.5),
         "hysteresis gap"),
        (dict(min_batch=0), "min_batch must be a positive int"),
        (dict(min_batch=True), "min_batch must be a positive int"),
    ],
)
def test_serverconfig_rejects_bad_deadline_knobs(kw, msg):
    with pytest.raises(ValueError, match=msg):
        ServerConfig(**kw)


def test_serverconfig_accepts_deadline_knobs():
    cfg = ServerConfig(
        deadline_us=750.0, overload_policy="degrade",
        degrade_rungs=["sparse_egress", "scrub_relax"],  # list coerces
        degrade_window=64, degrade_enter_frac=0.4, degrade_exit_frac=0.1,
        min_batch=16,
    )
    assert cfg.deadline_s == pytest.approx(7.5e-4)
    # rung ORDER is the ladder order — a custom order is preserved
    assert cfg.degrade_rungs == ("sparse_egress", "scrub_relax")
    # no deadline (the default) is fine with the default observe policy
    assert ServerConfig().deadline_s is None


# -------------------------------------------------- layout default (sat b)
def test_layout_defaults_bitsliced_for_every_band(duo, caplog):
    chips, _ = duo
    # auto-select: bit-sliced regardless of band — the band is a fan-in
    # reach envelope, not a kernel-structure knob, so banded geometry
    # packs bit-sliced directly and the matmul fallback no longer exists
    assert ServerConfig().effective_layout == "bitsliced"
    assert ServerConfig(band=True).effective_layout == "bitsliced"
    assert ServerConfig(band=False).effective_layout == "bitsliced"
    assert ServerConfig(layout="matmul").effective_layout == "matmul"

    logger = "repro.launch.readout_server"
    for cfg in (ServerConfig(backend="host"),
                ServerConfig(backend="host", band=False),
                ServerConfig(backend="host", band=True)):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger=logger):
            srv = ReadoutServer(chips, cfg)
        assert srv.layout == "bitsliced", cfg.band
        assert not any("falling back" in r.getMessage()
                       for r in caplog.records), cfg.band


# --------------------------------------------------------- histogram unit
_BUCKET_W = 10.0 ** (1.0 / 8.0)     # one log bucket: the stated precision


def test_latency_histogram_percentiles_within_one_bucket():
    h = LatencyHistogram()
    h.add_many(np.asarray([10.0] * 90 + [10_000.0] * 10))
    assert h.count == 100
    assert 10.0 / _BUCKET_W <= h.percentile(50.0) <= 10.0 * _BUCKET_W
    assert (10_000.0 / _BUCKET_W <= h.percentile(99.0)
            <= 10_000.0 * _BUCKET_W)
    s = h.summary()
    assert s["count"] == 100
    assert s["max_us"] == 10_000.0
    assert s["mean_us"] == pytest.approx((90 * 10 + 10 * 10_000) / 100)
    # in-bucket interpolation may overshoot the observed max by up to
    # one bucket width — but never more
    assert s["p50_us"] <= s["p99_us"] <= s["p999_us"]
    assert s["p999_us"] <= s["max_us"] * _BUCKET_W


def test_latency_histogram_underflow_overflow_and_merge():
    a, b = LatencyHistogram(), LatencyHistogram()
    a.add_many(np.asarray([5.0, 50.0, 500.0]))
    b.add(0.2)          # underflow: below the 1 us grid floor
    b.add(2e9)          # overflow: above the 100 s grid ceiling
    b.add(7.0)
    a.merge(b)
    assert a.count == 6
    # overflow percentiles report the observed max, not a bucket edge
    assert a.percentile(100.0) == 2e9
    cdf = a.cdf()
    edges = [e for e, _ in cdf]
    fracs = [f for _, f in cdf]
    assert edges == sorted(edges)
    assert fracs == sorted(fracs)
    assert fracs[-1] == 1.0
    assert edges[-1] == 2e9    # final CDF point is the observed max
    # the underflow event is folded into the first point, never dropped
    assert fracs[0] >= 1.0 / 6.0
    assert LatencyHistogram().cdf() == []
    assert LatencyHistogram().percentile(99.0) == 0.0


# ------------------------------------------------ admission property (sat d)
@settings(max_examples=25)
@given(
    deadline_ms=st.floats(5.0, 50.0),
    ewma_ms=st.floats(0.0, 60.0),
    age_ms=st.floats(0.0, 60.0),
    depth=st.integers(0, 32),
)
def test_admission_never_sheds_positive_slack(
    deadline_ms, ewma_ms, age_ms, depth
):
    """The admission controller's contract, swept over (deadline, EWMA,
    queue age, queue depth): an event whose predicted completion
    (max(oldest wait, backlog drain) + service EWMA) is inside the
    budget is NEVER shed; a blown prediction is always shed and counted
    in the chip's n_shed — no silent drops either way."""
    chips, X = _duo()
    clock = FakeClock()
    srv = ReadoutServer(
        chips[:1],
        ServerConfig(backend="host", max_batch=4096, max_latency_s=1e9,
                     deadline_us=deadline_ms * 1e3, overload_policy="shed"),
        clock=clock,
    )
    if depth:
        seqs = srv.submit_batch(0, X[:depth])
        # queue was empty and the EWMA unseeded: all of these had slack
        assert all(s is not None for s in seqs)
    srv._service_ewma_s = ewma_ms * 1e-3
    clock.advance(age_ms * 1e-3)

    # recompute the controller's prediction independently: no drains
    # have landed, so the backlog term is 0 and the oldest-event wait
    # is exactly the fake-clock age of the queue head
    wait_s = age_ms * 1e-3 if depth else 0.0
    predicted_s = wait_s + srv._service_ewma_s

    seq = srv.submit(0, X[depth])
    n_shed = srv.report()["per_chip"][0]["n_shed"]
    if depth == 0 or predicted_s < deadline_ms * 1e-3:
        # positive slack (or the idle probe): must admit
        assert seq is not None
        assert n_shed == 0
    else:
        assert seq is None
        assert n_shed == 1


def test_observe_policy_and_no_deadline_never_shed(duo):
    chips, X = duo
    clock = FakeClock()
    srv = ReadoutServer(
        chips[:1],
        ServerConfig(backend="host", max_batch=4096, max_latency_s=1e9,
                     deadline_us=10.0, overload_policy="observe"),
        clock=clock,
    )
    srv.submit_batch(0, X[:16])
    clock.advance(1.0)          # queue head is 100_000 deadlines old
    srv._service_ewma_s = 1.0
    assert all(s is not None for s in srv.submit_batch(0, X[16:32]))
    got = srv.poll() + srv.flush()
    assert len(got) == 32       # observe: counted, never shed
    rep = srv.report()["deadline"]
    # only the first batch aged past the budget; the point is shed == 0
    assert rep["shed"] == 0
    assert rep["missed"] == 16 and rep["met"] == 16


# ----------------------------------------------------- degrade ladder
def test_degrade_ladder_hysteretic_descend_and_recover(duo):
    """Deterministic ladder walk under a fake clock: three all-miss
    windows step down one rung each (scrub_relax -> scrub_crc_only ->
    sparse_egress), three all-met windows step back up one each. The
    scrub_relax rung visibly widens the effective scrub interval while
    active, and every transition is timestamped with its miss_frac."""
    chips, X = duo
    clock = FakeClock()
    srv = ReadoutServer(
        chips[:1],
        ServerConfig(backend="host", max_batch=8, min_batch=1,
                     max_latency_s=1e9, deadline_us=1_000.0,
                     overload_policy="degrade", degrade_window=8,
                     degrade_enter_frac=0.5, degrade_exit_frac=0.05,
                     scrub_interval=5),
        clock=clock,
    )
    assert srv._effective_scrub_interval() == 5

    def round_trip(stall_s):
        # 8 submissions land at one instant (queue empty + zero EWMA ->
        # all admitted), then the clock jumps before the batch drains:
        # every event's end-to-end latency == stall_s, all met or all
        # missed vs the 1 ms budget. The drain-rate window is cleared
        # first: these deliberately stalled drains would otherwise teach
        # the admission controller's backlog term to shed mid-test, and
        # admission has its own property test — here the ladder is the
        # subject
        srv._drain_hist.clear()
        seqs = srv.submit_batch(0, X[:8])
        assert all(s is not None for s in seqs)
        clock.advance(stall_s)
        got = srv.poll()
        got += srv.flush()
        return got

    levels = [srv._rung_level]
    for _ in range(3):
        round_trip(0.005)       # 5 ms latency: the whole window misses
        levels.append(srv._rung_level)
    assert levels == [0, 1, 2, 3]
    rep = srv.report()["deadline"]["ladder"]
    assert rep["active_rungs"] == list(DEGRADE_RUNGS)
    # scrub_relax active: configured interval 5 widened by the factor
    assert srv._effective_scrub_interval() == 5 * rs_mod.SCRUB_RELAX_FACTOR

    # a fourth all-miss window cannot go below the last rung
    round_trip(0.005)
    assert srv._rung_level == 3

    for _ in range(3):
        round_trip(0.0)         # instant drains: the whole window meets
        levels.append(srv._rung_level)
    assert levels == [0, 1, 2, 3, 2, 1, 0]
    assert srv._effective_scrub_interval() == 5     # relax rung exited

    trans = srv.report()["deadline"]["ladder"]["transitions"]
    assert [t["direction"] for t in trans] == ["down"] * 3 + ["up"] * 3
    assert [t["rung"] for t in trans] == list(DEGRADE_RUNGS) + list(
        reversed(DEGRADE_RUNGS))
    assert all(t["miss_frac"] in (0.0, 1.0) for t in trans)
    ts = [t["t"] for t in trans]
    assert ts == sorted(ts)     # timestamped on the injected clock


def test_degrade_ladder_holds_between_hysteresis_bands(duo):
    """A window whose miss fraction falls INSIDE the hysteresis gap
    (exit_frac < miss < enter_frac) moves the ladder in neither
    direction — the no-flap guarantee."""
    chips, X = duo
    clock = FakeClock()
    srv = ReadoutServer(
        chips[:1],
        ServerConfig(backend="host", max_batch=8, min_batch=1,
                     max_latency_s=1e9, deadline_us=1_000.0,
                     overload_policy="degrade", degrade_window=8,
                     degrade_enter_frac=0.75, degrade_exit_frac=0.10),
        clock=clock,
    )
    srv._rung_level = 1         # start mid-ladder
    # first four age 0.8 ms before the rest arrive (still inside the
    # 1 ms budget, so admission control admits everything), then the
    # batch drains 0.3 ms later: the first four land at 1.1 ms (miss),
    # the last four at 0.3 ms (met) -> miss_frac 0.5, inside the gap
    srv.submit_batch(0, X[:4])
    clock.advance(0.0008)
    assert all(s is not None for s in srv.submit_batch(0, X[4:8]))
    clock.advance(0.0003)
    got = srv.poll() + srv.flush()
    assert len(got) == 8
    assert srv._rung_level == 1
    assert srv.report()["deadline"]["ladder"]["transitions"] == []


@pytest.mark.parametrize("level", [0, 1, 2, 3])
def test_rung_keep_drop_bit_exact_vs_host_oracle(duo, level):
    """Acceptance bar: keep/drop on ADMITTED events is bit-exact against
    the per-chip host oracle at every ladder rung. Rungs 1-2 touch only
    the scrub loop; rung 3 (sparse_egress) changes the EGRESS — only
    kept events cross the link — but never which events are kept, nor
    their scores."""
    chips, X = duo
    srv = ReadoutServer(
        chips,
        ServerConfig(backend="host", max_batch=64, max_latency_s=1e9,
                     deadline_us=60_000.0, overload_policy="degrade"),
    )
    srv._rung_level = level     # white-box: pin the ladder at this rung
    sub = {}
    for c in range(len(chips)):
        block = X[c * 40:(c + 1) * 40]
        seqs = srv.submit_batch(c, block)
        assert all(s is not None for s in seqs)
        sub[c] = (seqs, block)
    got = srv.poll() + srv.flush()
    by_seq = {r.seq: r for r in got}

    sparse = "sparse_egress" in srv.config.degrade_rungs[:level]
    for c, chip in enumerate(chips):
        seqs, block = sub[c]
        want_raw = chip.infer_raw(block, backend="host")
        want_keep = want_raw <= chip.score_threshold_raw
        if sparse:
            kept = {s for s, k in zip(seqs, want_keep) if k}
            assert set(seqs) & set(by_seq) == kept
            for s, raw, k in zip(seqs, want_raw, want_keep):
                if k:
                    assert by_seq[s].keep
                    assert by_seq[s].score_raw == raw
        else:
            for s, raw, k in zip(seqs, want_raw, want_keep):
                assert by_seq[s].keep == k
                assert by_seq[s].score_raw == raw
    # accounting sees every admitted event even when egress is sparse
    rep = srv.report()
    assert rep["n_in"] == len(chips) * 40
    assert rep["deadline"]["met"] + rep["deadline"]["missed"] == rep["n_in"]


# ------------------------------------------------- adaptive micro-batching
def test_adaptive_sizing_service_keyed_bands(duo):
    chips, _ = duo
    srv = ReadoutServer(
        chips[:1],
        ServerConfig(backend="host", max_batch=64, min_batch=8,
                     max_latency_s=1.0, deadline_us=10_000.0,
                     overload_policy="shed"),
    )
    dl = 0.010
    # construction: the coalesce window is pre-capped at half the budget
    assert srv._eff_max_batch == 64
    assert srv._lat_cap_s == pytest.approx(dl / 2)
    assert srv._eff_max_latency_s == pytest.approx(dl / 2)

    srv._adapt_batch(0.006, dl)             # svc > dl/2: shrink both
    assert srv._eff_max_batch == 32
    assert srv._eff_max_latency_s == pytest.approx(dl / 4)
    assert srv._batch_shrinks == 1

    for _ in range(10):
        srv._adapt_batch(0.006, dl)
    assert srv._eff_max_batch == 8          # floored at min_batch
    assert srv._eff_max_latency_s == pytest.approx(dl / 8)  # floored
    shrinks = srv._batch_shrinks

    srv._adapt_batch(0.004, dl)             # dl/4 < svc <= dl/2: hold
    assert srv._eff_max_batch == 8
    assert srv._batch_shrinks == shrinks and srv._batch_grows == 0

    srv._adapt_batch(0.002, dl)             # svc <= dl/4: grow both
    assert srv._eff_max_batch == 16
    assert srv._batch_grows == 1

    for _ in range(10):
        srv._adapt_batch(0.0, dl)
    assert srv._eff_max_batch == 64         # back at the config ceiling
    assert srv._eff_max_latency_s == pytest.approx(dl / 2)  # lat cap


# ------------------------------------------------ injected clock (sat c)
def test_single_injected_clock_ignores_wall_time(duo):
    """Coalesce-window and deadline decisions run on the ONE injected
    clock: real wall time passing moves nothing, advancing the fake
    clock moves everything, and the recorded latencies are fake-clock
    quantities."""
    chips, X = duo
    clock = FakeClock()
    srv = ReadoutServer(
        chips[:1],
        ServerConfig(backend="host", max_batch=64, max_latency_s=0.010,
                     deadline_us=20_000.0, overload_policy="shed"),
        clock=clock,
    )
    assert all(s is not None for s in srv.submit_batch(0, X[:4]))
    time.sleep(0.03)            # 3x the coalesce window of REAL time
    assert srv.poll() == []     # fake clock unmoved: batch not due
    assert srv.queue_depth == 4

    clock.advance(0.011)        # now due on the injected clock
    got = srv.poll()
    assert sorted(r.seq for r in got) == [0, 1, 2, 3]
    total = srv.report()["latency"]["total"]
    assert total["count"] == 4
    # 11 ms of fake time, NOT the 30+ ms of wall time we slept
    assert total["max_us"] == pytest.approx(11_000.0)
    rep = srv.report()["deadline"]
    assert rep["met"] == 4 and rep["missed"] == 0 and rep["shed"] == 0


def test_server_source_has_no_wall_clock_calls():
    """The injectable default is the ONLY monotonic reference and
    time.time() appears nowhere — mixing clocks is how coalesce-window
    bugs are born."""
    src = inspect.getsource(rs_mod)
    assert "time.time(" not in src
    assert src.count("time.monotonic") == 1     # the __init__ default


# ------------------------------------------------------ report + overload
def test_report_exposes_latency_and_deadline_sections(duo):
    chips, X = duo
    clock = FakeClock()
    srv = ReadoutServer(
        chips,
        ServerConfig(backend="host", max_batch=16, max_latency_s=1e9,
                     deadline_us=5_000.0, overload_policy="degrade"),
        clock=clock,
    )
    for c in range(len(chips)):
        srv.submit_batch(c, X[:8])
    clock.advance(0.001)
    got = srv.poll() + srv.flush()
    assert len(got) == 16

    rep = srv.report()
    lat = rep["latency"]
    for section in ("total", "queue_wait", "service"):
        s = lat[section]
        assert {"count", "mean_us", "max_us",
                "p50_us", "p99_us", "p999_us"} <= set(s)
    assert lat["total"]["count"] == 16
    fracs = [f for _, f in lat["cdf_us"]]
    assert fracs == sorted(fracs) and fracs[-1] == 1.0
    # monotonic stage trace of the last drained batch, offsets from the
    # oldest enqueue
    trace = lat["last_batch_trace_us"]
    stages = ["t_enqueued", "t_coalesced", "t_launched", "t_drained"]
    assert set(stages) <= set(trace)
    offs = [trace[k] for k in stages]
    assert offs[0] == 0.0 and offs == sorted(offs)

    dead = rep["deadline"]
    assert dead["deadline_us"] == 5_000.0 and dead["policy"] == "degrade"
    assert dead["met"] + dead["missed"] == 16
    assert dead["shed"] == 0
    assert {"miss_fraction", "service_ewma_us", "drain_rate_ev_s",
            "effective_max_batch", "effective_max_latency_s",
            "batch_shrinks", "batch_grows", "ladder"} <= set(dead)
    lad = dead["ladder"]
    assert {"level", "active_rungs", "transitions",
            "deferred_heals_pending"} <= set(lad)
    # per-chip tail + shed accounting surface in the per-chip rows too
    for row in rep["per_chip"]:
        assert "latency_p99_us" in row and "n_shed" in row


def _frame_pool():
    if "frames" not in _CACHE:
        d = generate(SmartPixelConfig(n_events=64, seed=13),
                     return_frames=True)
        _CACHE["frames"] = (d["frames"], d["features"][:, 13])
    return _CACHE["frames"]


@pytest.mark.parametrize("backend", ["host", "kernel"])
def test_sustained_overload_sheds_and_accounts_every_event(duo, backend):
    """Sustained 2x overload under overload_policy="shed" on the injected
    clock: each poll period the loop can dispatch one batch of B frames
    while 2B arrive. Admission must shed (the queue would otherwise grow
    without bound), every admitted event comes back exactly once, and
    results + shed == submitted, with report()["deadline"]["shed"] equal
    to the sheds the caller saw."""
    chips, _ = duo
    frames, y0 = _frame_pool()
    B, period_s = 16, 0.010
    clock = FakeClock()
    srv = ReadoutServer(
        chips,
        ServerConfig(backend=backend, max_batch=B, max_latency_s=period_s,
                     deadline_us=50_000.0, overload_policy="shed"),
        clock=clock,
    )
    admitted, n_sub, n_shed, results = [], 0, 0, []
    for tick in range(24):
        clock.advance(period_s)
        for k in range(2):                  # 2B arrivals per period
            lo = (2 * tick + k) * B % len(frames)
            seqs = srv.submit_frames(
                (tick + k) % srv.n_chips, frames[lo:lo + B], y0[lo:lo + B])
            n_sub += len(seqs)
            n_shed += sum(s is None for s in seqs)
            admitted += [s for s in seqs if s is not None]
        results += srv.poll()
    results += srv.flush()

    assert n_shed > 0, "2x sustained overload with a deadline must shed"
    assert len(results) + n_shed == n_sub
    assert sorted(r.seq for r in results) == sorted(admitted)
    rep = srv.report()
    assert rep["deadline"]["shed"] == n_shed
    assert sum(c["n_shed"] for c in rep["per_chip"]) == n_shed
