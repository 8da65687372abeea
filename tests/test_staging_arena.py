"""Staging arenas of the kernel frames path (launch/staging.py,
``ReadoutServer._launch_frames``):

  (a) answers are bit-identical to the host backend over a stream whose
      per-module counts grow, shrink and grow again, with uneven modules,
      at pipeline depths 1 and 2;
  (b) with the pipeline full, N same-shape dispatches allocate at most
      ``pipeline_depth + 2`` buffers and reuse an arena for the rest, and
      no arena handed to a new batch is held by a batch in flight;
  (c) the pad rows of a reused arena read zero;
plus the free list's own rules and the ``staging`` counters of report().
"""
import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.readout import ReadoutChip
from repro.data.smartpixel import (
    N_T, N_X, N_Y, SmartPixelConfig, generate, train_test_split,
)
from repro.launch.readout_server import ReadoutServer, ServerConfig
from repro.launch.staging import StagingArenas

POOL = 700
FRAME = N_T * N_Y * N_X


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def chips_and_frames():
    d = generate(SmartPixelConfig(n_events=6_000, seed=13))
    tr, _ = train_test_split(d)
    chips = []
    for depth, leaves in ((3, 5), (4, 8)):
        clf = GradientBoostedClassifier(
            n_estimators=1, max_depth=depth, max_leaf_nodes=leaves,
            min_samples_leaf=200,
        ).fit(tr["features"], tr["label"])
        chip = ReadoutChip.build(clf)
        chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
        chips.append(chip)
    dd = generate(SmartPixelConfig(n_events=POOL, seed=3),
                  return_frames=True)
    return chips, dd["frames"], dd["features"][:, 13]


def _server(chips, backend, depth=2):
    clock = Clock()
    srv = ReadoutServer(list(chips), ServerConfig(
        backend=backend, max_batch=10 ** 5, max_latency_s=1.0,
        pipeline_depth=depth), clock=clock)
    return srv, clock


def _stream(frames, y0, counts, seed=0):
    """Per batch, per module: frames and y0 drawn from the pool."""
    rng = np.random.default_rng(seed)
    out = []
    for per_module in counts:
        batch = []
        for n in per_module:
            idx = rng.integers(0, POOL, n)
            batch.append((frames[idx], y0[idx]))
        out.append(batch)
    return out


def _drive(srv, clock, stream):
    """Each batch of the stream becomes one dispatch: submit it, make it
    due, and poll until it has left the queue (poll defers it while the
    pipeline is full), so batches stay in flight behind it."""
    got = []
    for batch in stream:
        for m, (fr, z) in enumerate(batch):
            if len(fr):
                srv.submit_frames(m, fr, z)
        clock.t += 2.0
        while srv.queue_depth:
            got.extend(srv.poll())
    got.extend(srv.flush())
    return sorted((r.seq, r.chip, r.score_raw, r.keep) for r in got)


# ------------------------------------------------------------------ (a)
# B per module: 8 -> 512 -> 64 -> 2,048 -> 256, never even across modules,
# one module empty once: reused arenas carry pad rows
COUNTS = [(8, 3), (512, 37), (64, 64), (2048, 1500), (0, 200)]


@pytest.fixture(scope="module")
def stream_and_host_answers(chips_and_frames):
    chips, frames, y0 = chips_and_frames
    stream = _stream(frames, y0, COUNTS, seed=1)
    srv, clock = _server(chips, "host")
    answers = _drive(srv, clock, stream)
    # the host backend stages per chip, without arenas
    assert srv.report()["staging"] == {
        "reused": 0, "fresh": 0, "arenas": 0, "resident_bytes": 0}
    return stream, answers


@pytest.mark.parametrize("depth", [1, 2])
def test_kernel_answers_equal_host_as_batches_grow_and_shrink(
        chips_and_frames, stream_and_host_answers, depth):
    chips = chips_and_frames[0]
    stream, want = stream_and_host_answers
    srv, clock = _server(chips, "kernel", depth)
    got = _drive(srv, clock, stream)
    assert len(got) == sum(map(sum, COUNTS))
    assert got == want
    st = srv.report()["staging"]
    assert st["reused"] + st["fresh"] == len(COUNTS)


# ------------------------------------------------------------------ (b)
@pytest.mark.parametrize("depth", [1, 2])
def test_arena_is_reused_only_after_its_batch_drained(
        chips_and_frames, depth, monkeypatch):
    chips, frames, y0 = chips_and_frames
    srv, clock = _server(chips, "kernel", depth)
    # poll retires nothing by itself: a dispatch waits for room until the
    # loop below drains the oldest batch, so the pipeline stays full
    monkeypatch.setattr(srv, "_head_ready", lambda: False)
    orig_take = srv._arenas.take
    handed, held_at_take = [], []

    def take(size):
        buf = orig_take(size)
        held = [rec[4]["arena"] for rec in srv._inflight]
        held_at_take.append(len(held))
        assert not any(np.shares_memory(buf, a) for a in held)
        handed.append(buf)
        return buf
    monkeypatch.setattr(srv._arenas, "take", take)

    N = 8
    stream = _stream(frames, y0, [(128, 100)] * N, seed=5)
    max_inflight = 0
    for batch in stream:
        for m, (fr, z) in enumerate(batch):
            srv.submit_frames(m, fr, z)
        clock.t += 2.0
        srv.poll()
        if srv.queue_depth:             # the pipeline was full
            assert len(srv._inflight) == depth + 1
            srv._drain_one()
            srv.poll()
        assert srv.queue_depth == 0
        max_inflight = max(max_inflight, len(srv._inflight))
    srv.flush()
    assert max_inflight == depth + 1
    assert max(held_at_take) == depth
    st = srv.report()["staging"]
    assert len(handed) == N
    assert st["fresh"] <= depth + 2
    assert st["reused"] == N - st["fresh"]
    assert st["arenas"] == st["fresh"]
    assert st["resident_bytes"] == st["arenas"] * 2 * 128 * FRAME * 4
    srv.reset_latency_metrics()
    st = srv.report()["staging"]
    assert (st["reused"], st["fresh"]) == (0, 0)
    assert st["arenas"] > 0


# ------------------------------------------------------------------ (c)
def test_pad_rows_of_a_reused_arena_read_zero(chips_and_frames, monkeypatch):
    chips, frames, y0 = chips_and_frames
    srv, clock = _server(chips, "kernel")
    srv.submit_frames(0, frames[:64], y0[:64])
    srv.submit_frames(1, frames[64:128], y0[64:128])
    srv.flush()                         # both modules' rows fill the arena
    monkeypatch.setattr(srv, "_head_ready", lambda: False)
    srv.submit_frames(0, frames[128:192], y0[128:192])
    srv.submit_frames(1, frames[192:197], y0[192:197])
    clock.t += 2.0
    srv.poll()
    assert srv.report()["staging"]["reused"] == 1
    arena = srv._inflight[-1][4]["arena"]
    staged = arena[: 2 * 64 * FRAME].reshape(2, 64, N_T, N_Y, N_X)
    np.testing.assert_array_equal(staged[0], frames[128:192])
    np.testing.assert_array_equal(staged[1, :5], frames[192:197])
    assert frames[69:128].any()         # what the pad rows held before
    assert not staged[1, 5:].any()
    srv.flush()


# ------------------------------------------------------- the free list
def test_free_list_grows_to_the_largest_request_and_caps_its_arenas():
    pool = StagingArenas(limit=2)
    a = pool.take(10)
    b = pool.take(10)
    c = pool.take(10)                   # both arenas in flight: a one-off
    assert (pool.fresh, pool.reused) == (3, 0)
    assert pool.report()["arenas"] == 2
    pool.give(c)                        # not an arena: dropped
    assert pool.report()["arenas"] == 2
    pool.give(a)
    assert pool.take(4) is a            # a smaller request reuses
    pool.give(a)
    pool.give(b)
    big = pool.take(25)                 # too large for both: replaces one
    assert big.size == 25 and pool.fresh == 4
    assert pool.report() == {"reused": 1, "fresh": 4, "arenas": 2,
                             "resident_bytes": 4 * (10 + 25)}
    pool.give(big)
    assert pool.take(25) is big
