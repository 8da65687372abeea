"""Spans, batch phases and the compile counter of the readout server
(launch/spans.py, launch/readout_server.py), and the fused step's named
device scopes (kernels/frontend.py).

  * every batch's phase timestamps are monotonic, and staging +
    collect_wait + drain equal its service time exactly;
  * handoff counts the staging of the next batch that poll() does after a
    drain;
  * ``admit`` is one span per submit call, never one per event;
  * reset_latency_metrics() clears the longest calls, the phase ring and
    the compile counter;
  * ``compiles`` counts a dispatch whose batch shape is new to the fused
    step's jit, and not a repeat;
  * the lowered fused step carries the stage scopes in its op metadata;
  * under the JAX profiler the spans are ``readout.<stage>`` annotations
    with the batch id as a stat, and poll() has none.
"""
import glob

import jax
import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.readout import ReadoutChip
from repro.data.smartpixel import (
    N_T, N_X, N_Y, SmartPixelConfig, generate, train_test_split,
)
from repro.kernels import frontend as fe
from repro.launch.readout_server import ReadoutServer, ServerConfig
from repro.launch.spans import COLUMNS, BatchRing

TICK = 2.0 ** -10   # a power of two: differences of readings are exact


class TickClock:
    """Advances by TICK at every reading; ``advance`` adds more."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += TICK
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def chip_and_data():
    d = generate(SmartPixelConfig(n_events=6_000, seed=13))
    tr, te = train_test_split(d)
    clf = GradientBoostedClassifier(
        n_estimators=1, max_depth=3, max_leaf_nodes=5, min_samples_leaf=200,
    ).fit(tr["features"], tr["label"])
    chip = ReadoutChip.build(clf)
    chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
    dd = generate(SmartPixelConfig(n_events=64, seed=3), return_frames=True)
    return chip, te["features"], dd["frames"], dd["features"][:, 13]


def _rows(srv):
    return {c: srv._ring.rows()[:, i] for i, c in enumerate(COLUMNS)}


def _host_server(chip, clock, **kw):
    cfg = dict(backend="host", max_batch=16, max_latency_s=2.0 ** -8)
    cfg.update(kw)
    return ReadoutServer([chip, chip], ServerConfig(**cfg), clock=clock)


def test_phases_are_monotonic_and_add_up_to_service(chip_and_data):
    chip, X, _, _ = chip_and_data
    clock = TickClock()
    srv = _host_server(chip, clock)
    got = []
    for k in range(6):
        srv.submit_batch(k % 2, X[8 * k: 8 * k + 5 + k])
        clock.advance(2.0 ** -7)
        got += srv.poll()
    got += srv.flush()
    assert len(got) == sum(5 + k for k in range(6))

    r = _rows(srv)
    order = ["t_enqueued", "t_coalesced", "t_encoded", "t_launched",
             "t_collect", "t_drained", "t_delivered"]
    stamps = np.stack([r[c] for c in order])
    assert np.isfinite(stamps).all()
    assert (np.diff(stamps, axis=0) >= 0).all()
    staging = r["t_launched"] - r["t_coalesced"]
    collect_wait = r["t_collect"] - r["t_launched"]
    drain = r["t_drained"] - r["t_collect"]
    service = r["t_drained"] - r["t_coalesced"]
    np.testing.assert_array_equal(staging + collect_wait + drain, service)
    assert (staging > 0).all() and (drain > 0).all()

    rep = srv.report()
    ph = rep["latency"]["phases"]
    n = len(r["batch"])
    assert ph["batches"] == n == rep["latency"]["service"]["count"]
    assert ph["dropped"] == 0
    for name in ("staging", "collect_wait", "drain", "handoff"):
        assert ph[name]["count"] == n
        assert 0 <= ph[name]["p50_us"] <= ph[name]["p99_us"] \
            <= ph[name]["max_us"]
    # event-weighted: a batch counts once per event it carries
    w = r["events"]
    assert ph["staging"]["mean_us"] == pytest.approx(
        1e6 * (staging * w).sum() / w.sum())
    assert list(np.sort(r["batch"])) == list(range(n))
    assert set(r["padded"]) <= {float(b) for b in range(1, 17)}


def test_handoff_counts_staging_after_a_drain(chip_and_data):
    """poll() drains a finished batch, then coalesces and stages the next
    before it returns: the drained answers wait that long."""
    chip, X, _, _ = chip_and_data
    clock = TickClock()
    srv = _host_server(chip, clock)
    ready = [False]
    srv._result_ready = lambda x: ready[0]
    launch = srv._launch_features
    staging_s = 2.0 ** -6

    def slow_launch(events):
        clock.advance(staging_s)
        return launch(events)

    srv.submit_batch(0, X[:4])
    clock.advance(2.0 ** -7)
    assert srv.poll() == []            # batch 0 launched, still in flight
    srv._launch_features = slow_launch
    srv.submit_batch(1, X[4:8])
    clock.advance(2.0 ** -7)
    ready[0] = True
    got = srv.poll()                   # drain batch 0, stage batch 1
    assert len(got) == 8
    r = _rows(srv)
    handoff = dict(zip(r["batch"], r["t_delivered"] - r["t_drained"]))
    assert handoff[0] >= staging_s
    assert handoff[1] < staging_s
    assert srv.report()["latency"]["phases"]["handoff"]["max_us"] \
        == pytest.approx(1e6 * handoff[0])


def test_admit_is_one_span_per_submit_call(chip_and_data):
    chip, X, _, _ = chip_and_data
    srv = _host_server(chip, TickClock())
    frames = np.zeros((5, N_T, N_Y, N_X), np.float32)
    srv.submit_frames(0, frames, np.zeros(5, np.float32))
    assert srv.report()["stages"]["admit"]["calls"] == 1
    srv.submit(1, X[0])
    srv.submit_batch(1, X[1:8])
    admit = srv.report()["stages"]["admit"]
    assert admit["calls"] == 3
    # a reading at each end of a call; only the row-by-row batch form
    # reads the clock per row, as each row's enqueue time
    assert admit["seconds"] == 10 * TICK
    assert srv.queue_depth == 13


def test_reset_clears_longest_calls_ring_and_rate_window(chip_and_data):
    chip, X, _, _ = chip_and_data
    clock = TickClock()
    srv = _host_server(chip, clock)
    srv.submit_batch(0, X[:12])
    srv.flush()
    rep = srv.report()
    assert rep["stages"]["launch_score"]["max_s"] > 0
    assert rep["latency"]["phases"]["batches"] == 1
    srv._compiles, srv._compile_s = 2, 0.5
    srv.reset_latency_metrics()
    rep = srv.report()
    assert all(s["max_s"] == 0.0 for s in rep["stages"].values())
    assert rep["stages"]["launch_score"]["calls"] == 1   # sums are kept
    assert rep["latency"]["phases"]["batches"] == 0
    assert rep["latency"]["phases"]["staging"]["count"] == 0
    assert rep["latency"]["last_batch_trace_us"] == {}
    assert rep["compiles"] == {"dispatches": 0, "seconds": 0.0}
    assert np.isnan(rep["events_per_s"])
    # the rate counts events drained since the reset, over that window
    srv.submit_batch(1, X[:6])
    clock.advance(1.0)
    srv.poll()
    srv.submit_batch(0, X[6:10])
    clock.advance(1.0)
    srv.poll()
    rep = srv.report()
    assert rep["n_in"] == 22
    win = srv._t_last - srv._t_start
    assert rep["events_per_s"] == pytest.approx(10 / win)


def test_ring_keeps_the_newest_batches_and_counts_the_dropped():
    ring = BatchRing(capacity=4)
    for b in range(6):
        ring.record(b, 10 + b, 16, {"t_coalesced": float(b),
                                    "t_launched": b + 0.5}, compiled=b == 5)
    ring.deliver_at(lambda: 9.0)
    assert list(ring.rows()[:, 0]) == [2, 3, 4, 5]
    s = ring.summary()
    assert (s["batches"], s["dropped"], s["compiled_batches"]) == (6, 2, 1)
    assert s["staging"]["count"] == 4
    assert s["staging"]["p50_us"] == pytest.approx(0.5e6)
    assert s["collect_wait"]["count"] == 0     # no collect stamp recorded
    assert ring.newest()["batch"] == 5.0


@pytest.fixture(scope="module")
def kernel_server(chip_and_data):
    """A kernel-backend server whose featurizer threshold no other test
    uses, so its batch shapes are new to the fused step's jit."""
    chip, _, _, _ = chip_and_data
    return ReadoutServer([chip, chip], ServerConfig(
        max_batch=64, max_latency_s=1e9, threshold_electrons=812.5))


def test_compiles_count_new_batch_shapes_only(chip_and_data, kernel_server):
    _, _, frames, y0 = chip_and_data
    srv = kernel_server
    srv.submit_frames(0, frames[:8], y0[:8])
    assert len(srv.flush()) == 8
    c = srv.report()["compiles"]
    assert c["dispatches"] == 1 and c["seconds"] > 0
    assert srv.report()["latency"]["phases"]["compiled_batches"] == 1
    srv.submit_frames(1, frames[8:13], y0[8:13])     # pads to 8 again
    assert len(srv.flush()) == 5
    rep = srv.report()
    assert rep["compiles"]["dispatches"] == 1
    assert rep["latency"]["phases"]["compiled_batches"] == 1
    srv.reset_latency_metrics()
    assert srv.report()["compiles"]["dispatches"] == 0


def test_profiler_records_server_spans_with_batch_ids(
        chip_and_data, kernel_server, tmp_path):
    from jax.profiler import ProfileData

    _, _, frames, y0 = chip_and_data
    srv = kernel_server
    srv.submit_frames(0, frames[:8], y0[:8])
    srv.flush()                                       # warm
    with jax.profiler.trace(str(tmp_path)):
        for k in range(3):
            srv.submit_frames(k % 2, frames[8 * k: 8 * k + 8],
                              y0[8 * k: 8 * k + 8])
            srv.flush()
            srv.poll()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("readout."):
                    seen.setdefault(ev.name, []).append(
                        dict(ev.stats).get("batch"))
    for name in ("readout.admit", "readout.stack_frames",
                 "readout.launch_fused", "readout.drain_wait"):
        assert len(seen.get(name, ())) == 3, (name, seen)
    assert len(set(seen["readout.launch_fused"])) == 3
    assert seen["readout.launch_fused"] == seen["readout.drain_wait"]
    assert not any("poll" in n for n in seen)


@pytest.mark.parametrize("sparse", [False, True])
def test_lowered_fused_step_carries_stage_scopes(chip_and_data, sparse):
    chip, _, frames, y0 = chip_and_data
    front = fe.pack_frontend([chip.config], [chip.frontend_spec()],
                             layout="bitsliced")
    s = front.stack
    B = 128
    fr = np.zeros((1, B, N_T, N_Y, N_X), np.float32)
    z = np.zeros((1, B), np.float32)
    valid = np.ones((1, B), bool)
    lowered = fe._score_frames.lower(
        fr, z, s.sel, s.tables, s.level_base, s.win_base, s.output_nets,
        front.plan, valid, s.src, mesh=front.mesh, n_replicas=s.n_replicas,
        threshold_electrons=front.threshold_electrons, n_inputs=s.n_inputs,
        in_seg=s.in_seg, n_nets_pad=s.n_nets_pad,
        batch_tile=front.batch_tile, interpret=front.interpret,
        sparse=sparse)
    text = lowered.as_text(debug_info=True)
    for scope in ("readout_featurize", "readout_encode",
                  "readout_fabric_eval", "readout_decode"):
        assert scope in text, scope
    assert ("readout_compact" in text) == sparse
