"""A retired batch's answers are built from its columns
(``ReadoutServer._answers``): the same answers, field for field, as one
built per event from the batch's own device arrays, in seq order, as plain
immutable Python values, with ``report()["drain"]`` counting the batches
that needed a sort."""
import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.fabric import FabricSim
from repro.core.readout import ReadoutChip
from repro.core.tmr import inject_seu, replica_lut_index
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.launch.readout_server import ReadoutServer, ScoredEvent, ServerConfig


@pytest.fixture(scope="module")
def farm():
    """Four small single-tree modules of different shapes, and features."""
    d = generate(SmartPixelConfig(n_events=8_000, seed=16))
    tr, te = train_test_split(d)
    chips = []
    for depth, leaves in [(3, 5), (3, 6), (2, 4), (3, 7)]:
        clf = GradientBoostedClassifier(
            n_estimators=1, max_depth=depth, max_leaf_nodes=leaves,
            min_samples_leaf=300,
        ).fit(tr["features"], tr["label"])
        chip = ReadoutChip.build(clf)
        chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.6)
        chips.append(chip)
    return chips, te["features"]


def _record(server):
    """Wrap the server's drain: keep each retired batch's in-flight entry
    (kind, pending arrays, per-chip seqs, counts) beside its answers."""
    batches = []
    orig = server._drain_one

    def drain():
        head = server._inflight[0] if server._inflight else None
        out = orig()
        if head is not None:
            batches.append((head, out))
        return out
    server._drain_one = drain
    return batches


def _per_event(head):
    """The batch's answers built per event from its own arrays, in row
    order: dense from (score, keep), sparse from the packed (idx, vals)."""
    kind, pending, per_chip_seq, _counts, _meta = head
    if kind == "sparse":
        count, idx, vals, _dis, B = pending
        n = int(np.asarray(count))
        ref = [(per_chip_seq[k // B][k % B], k // B, int(v), True)
               for k, v in zip(np.asarray(idx)[:n].tolist(),
                               np.asarray(vals)[:n].tolist())]
    else:
        score, keep = np.asarray(pending[0]), np.asarray(pending[1])
        ref = [(s, i, int(score[i, j]), bool(keep[i, j]))
               for i, seqs in enumerate(per_chip_seq)
               for j, s in enumerate(seqs)]
    return ref


def _needs_sort(head) -> bool:
    ref = _per_event(head)
    return ref != sorted(ref)


# blocks (module, events) per round, out of module order. With 24-event
# batches the poll of round 2 retires the first 24 events (both rounds
# mixed) and its flush the rest of round 2; round 3 is in module order.
_ROUNDS = [[(2, 7), (0, 5), (3, 6), (1, 4)],
           [(1, 6), (3, 3), (0, 5), (2, 4)],
           [(0, 3), (1, 2)]]


def _serve(server, X):
    out, seqs, pos = [], [], 0
    for k, blocks in enumerate(_ROUNDS):
        for m, n in blocks:
            seqs.extend(server.submit_batch(m, X[pos:pos + n]))
            pos += n
        out.extend(server.poll())
        if k:
            out.extend(server.flush())
    return out, seqs


@pytest.mark.parametrize("backend", ["host", "kernel"])
def test_dense_drain_builds_the_per_event_answers_in_seq_order(farm, backend):
    chips, X = farm
    server = ReadoutServer(chips, ServerConfig(
        max_batch=24, max_latency_s=1e9, backend=backend))
    batches = _record(server)
    out, seqs = _serve(server, X)

    assert [h[0] for h, _ in batches] == ["scored"] * 3
    assert sorted(r.seq for r in out) == sorted(seqs)
    # at least one batch holds pad rows past some module's count
    assert any(min(h[3]) < np.shape(h[1][0])[1] for h, _ in batches)
    for head, got in batches:
        assert [tuple(r) for r in got] == sorted(_per_event(head))
        assert all(b.seq > a.seq for a, b in zip(got, got[1:]))
        for r in got:
            assert type(r) is ScoredEvent
            assert [type(v) for v in r] == [int, int, int, bool]
    reordered = sum(_needs_sort(h) for h, _ in batches)
    assert reordered == 2       # the first two batches mix the modules
    assert server.report()["drain"] == {
        "batches": 3, "answers": len(seqs), "reordered": reordered}
    server.reset_latency_metrics()
    assert server.report()["drain"] == {
        "batches": 0, "answers": 0, "reordered": 0}


def test_scored_event_is_an_immutable_value():
    a = ScoredEvent(seq=3, chip=1, score_raw=-7, keep=True)
    assert a == ScoredEvent(3, 1, -7, True) and hash(a) == hash(
        ScoredEvent(3, 1, -7, True))
    assert a != ScoredEvent(3, 1, -7, False)
    assert ScoredEvent._fields == ("seq", "chip", "score_raw", "keep")
    with pytest.raises(AttributeError):
        a.keep = False


def _effective_seu(chip, X):
    """A (lut, bit) flip that changes the chip's decoded scores on X."""
    bits = chip.encode_features(X)
    clean = chip.synth.decode_outputs(np.asarray(FabricSim(chip.config).run(
        bits)[0]))
    for li in range(chip.config.n_luts):
        for bi in range(16):
            outs, _ = FabricSim(inject_seu(chip.config, li, bi)).run(bits)
            if not np.array_equal(
                    chip.synth.decode_outputs(np.asarray(outs)), clean):
                return li, bi
    raise AssertionError("no effective flip found")


@pytest.mark.parametrize("backend", ["host", "kernel"])
def test_sparse_drain_matches_per_event_reference_and_dense_twin(
        farm, backend):
    """Host sparse (a pack after the dense step) and word-sparse on the
    bit-sliced stack (the pack inside the step): the kept answers, their
    scores and the per-module kept and SEU counters equal a per-event
    reference and a dense server fed the same upset and events."""
    chips, X = farm
    pos = np.cumsum([0] + [n for r in _ROUNDS for _, n in r])
    rows0 = np.concatenate([np.arange(a, a + n) for a, (m, n) in zip(
        pos, [b for r in _ROUNDS for b in r]) if m == 0])
    li, bi = _effective_seu(chips[0], X[rows0])     # seen by module 0
    servers = {}
    for sparse in (False, True):
        srv = ReadoutServer(chips, ServerConfig(
            max_batch=24, max_latency_s=1e9, backend=backend,
            redundancy="tmr", sparse=sparse))
        srv.inject_seu(0, 1, replica_lut_index(chips[0].config, 1, li), bi)
        servers[sparse] = srv
    batches = _record(servers[True])
    got, seqs = _serve(servers[True], X)
    dense, _ = _serve(servers[False], X)

    assert [h[0] for h, _ in batches] == ["sparse"] * 3
    if backend == "kernel":
        assert servers[True]._word_sparse_active()
    assert any(min(h[3]) < h[1][4] for h, _ in batches)   # pad rows
    for head, out in batches:
        assert [tuple(r) for r in out] == sorted(_per_event(head))
        assert all(b.seq > a.seq for a, b in zip(out, out[1:]))
        assert all([type(v) for v in r] == [int, int, int, bool]
                   for r in out)
    assert sorted(got) == sorted(r for r in dense if r.keep)
    assert 0 < len(got) < len(seqs)
    rs, rd = servers[True].report(), servers[False].report()
    for a, b in zip(rs["per_chip"], rd["per_chip"]):
        for key in ("n_in", "n_kept", "seu_disagreements"):
            assert a[key] == b[key], key
    assert rs["per_chip"][0]["seu_disagreements"][1] > 0
    assert rs["drain"] == {
        "batches": 3, "answers": len(got),
        "reordered": sum(_needs_sort(h) for h, _ in batches)}
