"""Bit-sliced LUT evaluation: the cross-backend conformance suite.

The tentpole claim under test: packing 32 events per uint32 lane and
evaluating every 4-LUT as 15 bitwise mux ops over whole words — with the
TMR majority vote folded into the same bitwise pass — is BIT-EXACT
against every other evaluator in the repo. The matrix:

  evaluators   bitsliced kernel (layout="bitsliced", traceable jnp)
               x banded Pallas x dense Pallas
               x FabricSim / MultiFabricSim (levelized host oracle)
               x BitslicedSim (independent numpy word-parallel twin,
                 written against RAW net ids, not the packed layout)
  axes         every registered fabric x TMR on/off x sparse on/off
               x batch sizes off the 32-event word boundary

plus the satellite guarantees:
  * word-transpose properties (seeded sweeps via tests/_propshim):
    pack/unpack round-trips in both directions, arbitrary event counts
    including non-multiple-of-32 tails, and padding lanes that never
    leak into outputs or scores;
  * hot-swap (swap_chip / swap_replica) on a bit-sliced stack is an
    array swap — no retrace — and readback returns the same scrub-loop
    table image as the matmul layouts;
  * layout/band validation errors name the offending field and the
    allowed values, identically at pack_fabric(s) and ServerConfig.
"""
import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.fabric import (
    FABRICS,
    BitslicedSim,
    FabricSim,
    MultiFabricSim,
    pack_event_words,
    place_and_route,
    unpack_event_words,
)
from repro.core.readout import ReadoutChip
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.kernels.lut_eval import bitsliced, ops as lut_ops
from repro.launch.mesh import make_readout_mesh
from repro.launch.readout_server import ReadoutServer, ServerConfig
from tests._propshim import given, settings, strategies as st
from tests.test_banded import (
    _layered_netlist, _long_edge_netlist, assert_sparse_ships_kept,
)
from tests.test_kernels import _random_netlist

import repro.core.tmr  # noqa: F401  (registers efpga_28nm_xl)


# ------------------------------------------------------------ helpers
def _cfg(seed, name="efpga_28nm", n_inputs=10, n_luts=48):
    return place_and_route(_random_netlist(seed, n_inputs, n_luts),
                           FABRICS[name])


@pytest.fixture(scope="module")
def farm():
    """Two heterogeneous chips + a feature batch whose size (37) is NOT a
    multiple of the 32-event word, so every served batch exercises the
    tail-lane masking."""
    d = generate(SmartPixelConfig(n_events=10_000, seed=11))
    tr, te = train_test_split(d)
    chips = []
    for fabric, depth in (("efpga_28nm", 3), ("efpga_130nm", 3)):
        clf = GradientBoostedClassifier(
            n_estimators=1, max_depth=depth, max_leaf_nodes=5,
            min_samples_leaf=300,
        ).fit(tr["features"], tr["label"])
        chip = ReadoutChip.build(clf, fabric=fabric)
        chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
        chips.append(chip)
    return chips, te["features"][:37]


def _golden(chip, X):
    return chip.golden.decision_function_raw(chip.golden.quantize_features(X))


def _serve(server, X, chip_slot=0):
    server.submit_batch(chip_slot, X)
    res = sorted(server.flush(), key=lambda r: r.seq)
    return [(r.seq, r.chip, r.score_raw, r.keep) for r in res]


# --------------------------------------- word-transpose properties
@given(seed=st.integers(0, 10_000), n_events=st.integers(1, 200),
       n_nets=st.integers(1, 12))
@settings(max_examples=30, deadline=None)
def test_word_transpose_roundtrip_bits(seed, n_events, n_nets):
    """unpack(pack(bits)) == bits for arbitrary event counts (including
    non-multiple-of-32 tails), on BOTH the jnp packer and its numpy twin
    — and the two packers agree word for word."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (n_events, n_nets)).astype(np.uint8)
    w_np = pack_event_words(bits)
    w_jx = np.asarray(bitsliced.pack_words(bits))
    assert w_np.dtype == np.uint32 and w_jx.dtype == np.uint32
    assert w_np.shape == (max(-(-n_events // 32), 1), n_nets)
    np.testing.assert_array_equal(w_np, w_jx)
    np.testing.assert_array_equal(unpack_event_words(w_np, n_events), bits)
    np.testing.assert_array_equal(
        np.asarray(bitsliced.unpack_words(w_jx, n_events)), bits)


@given(seed=st.integers(0, 10_000), n_words=st.integers(1, 5),
       n_nets=st.integers(1, 12))
@settings(max_examples=30, deadline=None)
def test_word_transpose_roundtrip_words(seed, n_words, n_nets):
    """pack(unpack(w)) == w: the transpose is a bijection on full words,
    so no configuration of 32-event lanes is unreachable or aliased."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 2 ** 32, (n_words, n_nets), dtype=np.uint64)
    w = w.astype(np.uint32)
    bits = unpack_event_words(w, n_words * 32)
    np.testing.assert_array_equal(pack_event_words(bits), w)
    np.testing.assert_array_equal(
        np.asarray(bitsliced.pack_words(bits)), w)


@given(seed=st.integers(0, 1000), n_events=st.integers(1, 70))
@settings(max_examples=8, deadline=None)
def test_padding_lanes_never_leak(seed, n_events):
    """Outputs for a B-event batch are identical whether B fills its last
    32-lane word or not, and equal the per-event host oracle — garbage in
    the padding lanes of the final word can never reach a real event."""
    cfg = _cfg(7, n_luts=30)
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (n_events, cfg.n_inputs)).astype(np.uint8)
    want, _ = FabricSim(cfg).run(bits)
    got = np.asarray(lut_ops.fabric_eval(cfg, bits, layout="bitsliced"))
    np.testing.assert_array_equal(got, want)
    # same events embedded in a bigger batch (different tail occupancy)
    pad = rng.integers(0, 2, (91 - n_events, cfg.n_inputs)).astype(np.uint8)
    big = np.concatenate([bits, pad])
    got_big = np.asarray(lut_ops.fabric_eval(cfg, big, layout="bitsliced"))
    np.testing.assert_array_equal(got_big[:n_events], want)


@pytest.mark.parametrize("red", ["none", "tmr"])
@pytest.mark.parametrize("band", [False, None])
@pytest.mark.parametrize("layout", ["matmul", "bitsliced"])
def test_padding_lanes_never_leak_into_scores(farm, layout, band, red):
    """The scored dispatch (the server's launch path) on a batch that
    straddles a word boundary, through each packing the server can be
    configured with (layout x band dense/auto x TMR off/on): scores ==
    golden and keep == the cut, event for event, with no replica
    disagreement; the bit-sliced packings' word-domain sparse egress
    ships exactly the kept events."""
    chips, X = farm
    chip = chips[0]
    assert len(X) % 32 != 0
    bits = chip.encode_features(X)[None]
    thr = np.array([chip.score_threshold_raw], np.int32)
    golden = _golden(chip, X)
    stack = lut_ops.pack_fabrics([chip.config], band=band, redundancy=red,
                                 layout=layout)
    w = lut_ops.decode_plan([chip.config], stack.n_outputs)
    score, keep, dis = lut_ops.fabric_eval_multi_scored(
        stack, bits, w, thr, mesh=make_readout_mesh(1))
    np.testing.assert_array_equal(np.asarray(score)[0], golden)
    np.testing.assert_array_equal(np.asarray(keep)[0],
                                  golden <= chip.score_threshold_raw)
    assert not np.asarray(dis).any()
    if layout == "bitsliced":
        assert_sparse_ships_kept(stack, bits, w, thr, golden,
                                 f"band={band} red={red}")


# --------------------------------------------- the conformance matrix
def test_bitsliced_conformance_every_fabric():
    """Every registered fabric: bitsliced kernel == banded == dense ==
    FabricSim == BitslicedSim, bit for bit, on a batch off the word
    boundary. THE acceptance bar of the tentpole."""
    fabric_names = sorted({s.name for s in FABRICS.values()})
    assert {"efpga_130nm", "efpga_28nm", "efpga_28nm_xl"} <= set(fabric_names)
    for fi, name in enumerate(fabric_names):
        cfg = place_and_route(_random_netlist(60 + fi, 10, 48), FABRICS[name])
        rng = np.random.default_rng(fi)
        bits = rng.integers(0, 2, (41, cfg.n_inputs)).astype(np.uint8)
        want, _ = FabricSim(cfg).run(bits)
        evals = {
            "bitsliced": np.asarray(
                lut_ops.fabric_eval(cfg, bits, layout="bitsliced")),
            "banded": np.asarray(lut_ops.fabric_eval(cfg, bits, band=True)),
            "dense": np.asarray(lut_ops.fabric_eval(cfg, bits, band=False)),
            "host_word_oracle": BitslicedSim(cfg).run(bits),
        }
        for which, got in evals.items():
            np.testing.assert_array_equal(
                got, want, err_msg=f"{name} via {which}")


def test_bitsliced_stack_tmr_matches_plain_and_multisim(farm):
    """Multi-chip bit-sliced stack, TMR on and off, vs MultiFabricSim and
    vs the matmul stack: the folded word-majority vote changes nothing on
    healthy replicas."""
    chips, X = farm
    configs = [c.config for c in chips]
    per_bits = [c.encode_features(X) for c in chips]
    want = MultiFabricSim(configs).run(
        lut_ops.stack_input_bits(
            lut_ops.pack_fabrics(configs, layout="bitsliced"), per_bits))
    for red in ("none", "tmr"):
        stack = lut_ops.pack_fabrics(configs, redundancy=red,
                                     layout="bitsliced")
        assert stack.layout == "bitsliced" and stack.bitsliced
        assert stack.sel is None and stack.src is not None
        bits = lut_ops.stack_input_bits(stack, per_bits)
        got = np.asarray(lut_ops.fabric_eval_multi(stack, bits))
        np.testing.assert_array_equal(got, want, err_msg=f"red={red}")
        matmul = lut_ops.pack_fabrics(configs, redundancy=red)
        np.testing.assert_array_equal(
            got, np.asarray(lut_ops.fabric_eval_multi(matmul, bits)),
            err_msg=f"red={red} vs matmul")


@pytest.fixture(scope="module")
def quad():
    """Four paper-sized chips of differing depth and leaf count, and a
    feature batch off the 32-event word boundary."""
    d = generate(SmartPixelConfig(n_events=10_000, seed=2024))
    tr, te = train_test_split(d)
    chips = [
        ReadoutChip.build(GradientBoostedClassifier(
            n_estimators=1, max_depth=5 - (i % 2),
            max_leaf_nodes=10 - (i % 3), min_samples_leaf=500,
        ).fit(tr["features"], tr["label"]))
        for i in range(4)
    ]
    return chips, te["features"][:100]


@pytest.mark.parametrize("n_chips", [1, 2, 4])
def test_multichip_stack_one_dispatch_matches_multisim(quad, n_chips):
    """1, 2 and 4 heterogeneous chips stacked on the bit-sliced layout and
    evaluated in ONE fabric_eval_multi dispatch equal the per-chip host
    oracle bit for bit."""
    chips, X = quad
    configs = [c.config for c in chips[:n_chips]]
    stack = lut_ops.pack_fabrics(configs, layout="bitsliced")
    bits = lut_ops.stack_input_bits(
        stack, [c.encode_features(X) for c in chips[:n_chips]])
    got = np.asarray(lut_ops.fabric_eval_multi(stack, bits))
    assert got.shape[:2] == (n_chips, len(X))
    np.testing.assert_array_equal(got, MultiFabricSim(configs).run(bits))


def test_server_matrix_bitsliced_matches_matmul(farm):
    """The served results (scores, keep decisions, sequence) through the
    kernel server are identical for layout='bitsliced' and 'matmul'
    across the TMR x sparse matrix — and equal the golden model."""
    chips, X = farm
    golden = _golden(chips[0], X)
    kept = golden <= chips[0].score_threshold_raw
    for red in ("none", "tmr"):
        for sparse in (False, True):
            out = {}
            for layout in ("matmul", "bitsliced"):
                srv = ReadoutServer([chips[0]], ServerConfig(
                    max_batch=len(X), max_latency_s=1e9, backend="kernel",
                    layout=layout, redundancy=red, sparse=sparse))
                out[layout] = _serve(srv, X)
                assert srv.report()["seu_disagreement_total"] == 0
            assert out["bitsliced"] == out["matmul"], (red, sparse)
            scores = np.array([s for _, _, s, _ in out["bitsliced"]])
            np.testing.assert_array_equal(
                scores, golden[kept] if sparse else golden,
                err_msg=f"red={red} sparse={sparse}")


def test_server_frames_bitsliced_matches_matmul(farm):
    """The fused frames path (frames -> features -> bits -> score in one
    dispatch) with the fabric stage routed through the bit-sliced
    evaluator: served results identical to the matmul layout, under
    TMR."""
    chips, _ = farm
    d = generate(SmartPixelConfig(n_events=90, seed=9), return_frames=True)
    frames, y0 = d["frames"], d["features"][:, 13]
    out = {}
    for layout in ("matmul", "bitsliced"):
        srv = ReadoutServer([chips[0]], ServerConfig(
            max_batch=64, max_latency_s=1e9, backend="kernel",
            layout=layout, redundancy="tmr"))
        srv.submit_frames(0, frames, y0)
        res = sorted(srv.flush(), key=lambda r: r.seq)
        out[layout] = [(r.seq, r.score_raw, r.keep) for r in res]
    assert out["bitsliced"] == out["matmul"]
    assert len(out["bitsliced"]) == len(frames)


@pytest.mark.parametrize("red", ["none", "tmr"])
def test_server_frames_sparse_two_chips_matches_golden(farm, red):
    """The fused frames path with sparse egress on two chips (kernel
    backend, bit-sliced, the sparse body plus its cross-chip compaction):
    the served answers are exactly the golden model's kept events, as
    (seq, chip, score), and the same as the kept answers of the dense
    twin."""
    import copy

    from repro.kernels.yprofile import ops as yp_ops

    d = generate(SmartPixelConfig(n_events=90, seed=9), return_frames=True)
    frames, y0 = d["frames"], d["features"][:, 13]
    feats = np.asarray(yp_ops.yprofile(frames, y0, batch_tile=128))
    parts = (slice(0, 45), slice(45, 90))   # chip i serves seqs 45i..
    chips, want = [], []
    for i, (c, sl) in enumerate(zip(farm[0], parts)):
        g = _golden(c, feats[sl])
        c = copy.copy(c)        # a median cut: sparse drops about half
        c.score_threshold_raw = int(np.median(g))
        chips.append(c)
        want += [(sl.start + j, i, int(s)) for j, s in enumerate(g)
                 if s <= c.score_threshold_raw]
    assert 0 < len(want) < len(frames)
    out = {}
    for sparse in (False, True):
        srv = ReadoutServer(chips, ServerConfig(
            max_batch=64, max_latency_s=1e9, backend="kernel",
            layout="bitsliced", redundancy=red, sparse=sparse))
        for i, sl in enumerate(parts):
            srv.submit_frames(i, frames[sl], y0[sl])
        res = sorted(srv.flush(), key=lambda r: r.seq)
        assert srv.report()["seu_disagreement_total"] == 0
        out[sparse] = [(r.seq, r.chip, r.score_raw) for r in res
                       if r.keep]
        if not sparse:
            assert len(res) == len(frames)
    assert out[True] == want
    assert out[False] == want


# ------------------------------------------------ hot-swap / no-retrace
def test_bitsliced_swap_chip_no_retrace(farm):
    """swap_chip on a bit-sliced stack rewrites (src, tables,
    output_nets) rows — same pytree structure, so the jit cache must not
    grow — and the swapped slot evaluates as the new config."""
    if not hasattr(lut_ops._eval_stack_arrays, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this JAX")
    cfgs = [_cfg(80 + i, n_luts=30) for i in range(3)]
    stack = lut_ops.pack_fabrics(cfgs, layout="bitsliced")
    rng = np.random.default_rng(4)
    per = [rng.integers(0, 2, (37, c.n_inputs)).astype(np.uint8)
           for c in cfgs]
    bits = lut_ops.stack_input_bits(stack, per)
    np.asarray(lut_ops.fabric_eval_multi(stack, bits))
    n0 = lut_ops._eval_stack_arrays._cache_size()

    new = place_and_route(_layered_netlist(99, 10, 5, levels=3),
                          FABRICS["efpga_28nm"])
    stack2 = stack.swap_chip(1, new)
    per2 = list(per)
    per2[1] = rng.integers(0, 2, (37, new.n_inputs)).astype(np.uint8)
    bits2 = lut_ops.stack_input_bits(stack2, per2)
    got = np.asarray(lut_ops.fabric_eval_multi(stack2, bits2))
    assert lut_ops._eval_stack_arrays._cache_size() == n0, "swap retraced"
    from repro.core.fabric import StackGeometry

    geo = StackGeometry(
        n_levels=stack.n_levels, max_level_size=stack.m_pad,
        n_inputs=stack.n_inputs, n_outputs=stack.n_outputs)
    want = MultiFabricSim([cfgs[0], new, cfgs[2]], geometry=geo).run(bits2)
    np.testing.assert_array_equal(got, want)


def test_bitsliced_swap_replica_and_readback(farm):
    """swap_replica perturbs ONE replica of a bit-sliced TMR stack; the
    vote masks it, the disagreement monitor sees it, and readback returns
    the live (perturbed) scrub-loop table image — the whole
    readback->verify->heal loop works unchanged on this layout."""
    from repro.core.fabric import packed_table_image
    from repro.core.tmr import inject_seu, replicate_config

    chips, X = farm
    chip = chips[0]
    stack = lut_ops.pack_fabrics([chip.config], redundancy="tmr",
                                 layout="bitsliced")
    img0 = stack.readback_replica(0, 1)
    np.testing.assert_array_equal(
        img0, packed_table_image(replicate_config(chip.config, 1),
                                 stack.n_levels, stack.m_pad))
    seu = inject_seu(replicate_config(chip.config, 1), 0, 3)
    stack2 = stack.swap_replica(0, 1, seu)
    assert (stack2.readback_replica(0, 1) != img0).sum() == 1
    bits = lut_ops.stack_input_bits(stack2, [chip.encode_features(X)])
    got = np.asarray(lut_ops.fabric_eval_multi(stack2, bits))
    want, _ = FabricSim(chip.config).run(chip.encode_features(X))
    np.testing.assert_array_equal(got[0], want)


# ------------------------------------------- banded conformance matrix
def test_banded_bitsliced_conformance_matrix():
    """Every registered fabric x band auto/off x TMR on/off x sparse
    on/off: a BANDED bit-sliced stack (the band is a reach envelope —
    same gather kernel, stricter admission) serves scores bit-exact vs
    MultiFabricSim and the banded BitslicedSim host oracle, and the
    word-domain sparse egress ships exactly the kept subset."""
    mesh = make_readout_mesh(1)
    fabric_names = sorted({s.name for s in FABRICS.values()})
    assert {"efpga_130nm", "efpga_28nm", "efpga_28nm_xl"} <= set(fabric_names)
    rng = np.random.default_rng(5)
    for fi, name in enumerate(fabric_names):
        cfg = place_and_route(_layered_netlist(70 + fi, 8, 6, levels=4),
                              FABRICS[name])
        assert cfg.fanin_reach() == 1
        B = 37                          # off the 32-event word boundary
        bits = rng.integers(0, 2, (1, B, cfg.n_inputs)).astype(np.uint8)
        want = MultiFabricSim([cfg]).run(bits)
        np.testing.assert_array_equal(
            BitslicedSim(cfg, band_k=1).run(bits[0]), want[0],
            err_msg=f"{name} banded host oracle")
        for band in (None, False):
            for red in ("none", "tmr"):
                tag = f"{name} band={band} red={red}"
                stack = lut_ops.pack_fabrics(
                    [cfg], band=band, redundancy=red, layout="bitsliced")
                assert stack.bitsliced
                assert stack.banded == (band is None), tag  # reach 1 < L
                w = lut_ops.decode_plan([cfg], stack.n_outputs)
                golden = (want[0].astype(np.int64) * w[0]).sum(-1)
                thr = np.array([int(np.median(golden))], np.int32)
                kept = golden <= thr[0]
                score, keep, dis = lut_ops.fabric_eval_multi_scored(
                    stack, bits, w, thr, mesh=mesh)
                np.testing.assert_array_equal(
                    np.asarray(score)[0], golden, err_msg=tag)
                np.testing.assert_array_equal(
                    np.asarray(keep)[0], kept, err_msg=tag)
                assert not np.asarray(dis).any(), tag
                # sparse cell: word-domain egress == the kept subset
                assert_sparse_ships_kept(stack, bits, w, thr, golden, tag)


@given(seed=st.integers(0, 500))
@settings(max_examples=5, deadline=None)
def test_bitsliced_swap_reach_exceeding_band_raises_and_preserves(seed):
    """Property: swap_chip of a config whose fan-in reach exceeds a
    banded bit-sliced stack's envelope raises the named admission error
    and leaves the stack unchanged — arrays untouched, outputs
    identical."""
    cfgs = [place_and_route(_layered_netlist(seed + i, 6, 5, levels=5),
                            FABRICS["efpga_28nm"]) for i in range(2)]
    stack = lut_ops.pack_fabrics(cfgs, band=True, layout="bitsliced")
    assert stack.bitsliced and stack.banded and stack.band_k == 1
    rng = np.random.default_rng(seed)
    per = [rng.integers(0, 2, (37, c.n_inputs)).astype(np.uint8)
           for c in cfgs]
    bits = lut_ops.stack_input_bits(stack, per)
    before = np.asarray(lut_ops.fabric_eval_multi(stack, bits))
    src0 = np.asarray(stack.src).copy()
    tbl0 = np.asarray(stack.tables).copy()
    deep = place_and_route(_long_edge_netlist(2, chain=4),
                           FABRICS["efpga_28nm"])
    assert deep.fanin_reach() > stack.band_k
    assert len(deep.level_sizes) <= stack.n_levels  # only the band blocks
    with pytest.raises(ValueError, match="envelope"):
        stack.swap_chip(0, deep)
    with pytest.raises(ValueError, match="envelope"):
        stack.swap_replica(0, 0, deep)
    np.testing.assert_array_equal(np.asarray(stack.src), src0)
    np.testing.assert_array_equal(np.asarray(stack.tables), tbl0)
    np.testing.assert_array_equal(
        np.asarray(lut_ops.fabric_eval_multi(stack, bits)), before)


# ----------------------------------------------------- validation errors
def test_pack_layout_validation_names_field_and_values():
    cfg = _cfg(3, n_luts=12)
    with pytest.raises(ValueError, match=r"unknown layout 'packed'.*"
                       r"'matmul' or 'bitsliced'"):
        lut_ops.pack_fabric(cfg, layout="packed")
    # the band is a layout-independent reach ENVELOPE: every spelling
    # (auto / forced-on / forced-dense) packs on the bit-sliced layout
    for band in (None, True, False):
        assert lut_ops.pack_fabric(cfg, band=band,
                                   layout="bitsliced").bitsliced
        assert lut_ops.pack_fabrics([cfg], band=band,
                                    layout="bitsliced").bitsliced


def test_pack_reach_vs_band_named_error():
    """A config whose fan-in reach exceeds the band K is rejected with
    the named reach-vs-band error by the bit-sliced packer AND by the
    banded host oracle (BitslicedSim band_k) — the conformance pair
    agrees on admission, not just on outputs."""
    cfg = place_and_route(_long_edge_netlist(2, chain=5),
                          FABRICS["efpga_28nm"])
    assert cfg.fanin_reach() == 4
    L = max(len(cfg.level_sizes), 1)
    m_pad = lut_ops._round_up(max(cfg.level_sizes, default=1), 128)
    in_seg = lut_ops._round_up(2 + cfg.n_inputs, 128)
    with pytest.raises(ValueError,
                       match=r"fan-in reach exceeds band: K=2"):
        lut_ops._pack_arrays_bitsliced(cfg, L, m_pad, in_seg,
                                       len(cfg.output_nets), band_k=2)
    with pytest.raises(ValueError,
                       match=r"fan-in reach exceeds band: K=2"):
        BitslicedSim(cfg, band_k=2)
    # at or above the true reach both admit — and the band changes
    # ADMISSION only, never the evaluation
    bits = np.random.default_rng(0).integers(
        0, 2, (37, cfg.n_inputs)).astype(np.uint8)
    np.testing.assert_array_equal(
        BitslicedSim(cfg, band_k=4).run(bits), BitslicedSim(cfg).run(bits))


def test_serverconfig_layout_validation_names_field_and_values():
    ServerConfig(layout="bitsliced")                    # valid
    ServerConfig(layout="bitsliced", redundancy="tmr")  # valid
    # the band is layout-independent: every pairing is a valid config
    ServerConfig(layout="bitsliced", band=True)
    ServerConfig(layout="bitsliced", band=False)
    ServerConfig(layout="matmul", band=True)
    assert ServerConfig().effective_layout == "bitsliced"
    with pytest.raises(ValueError, match=r"unknown layout 'dense'.*"
                       r"'matmul' or 'bitsliced'"):
        ServerConfig(layout="dense")
    with pytest.raises(ValueError, match=r"band must be True, False or "
                       r"None \(auto\), got 'banded'"):
        ServerConfig(band="banded")


# ------------------------------------------------------------- slow tier
@pytest.mark.slow
def test_bitsliced_seeded_sweep_every_fabric():
    """Long conformance sweep: several random netlists per fabric,
    bit-sliced == banded == dense == FabricSim == BitslicedSim across
    randomized batch sizes (word-aligned and not)."""
    fabric_names = sorted({s.name for s in FABRICS.values()})
    for fi, name in enumerate(fabric_names):
        rng = np.random.default_rng(900 + fi)
        for seed in range(4):
            nl = _random_netlist(
                800 + 10 * fi + seed, int(rng.integers(4, 16)),
                int(rng.integers(20, 140)))
            cfg = place_and_route(nl, FABRICS[name])
            B = int(rng.integers(1, 130))
            bits = rng.integers(0, 2, (B, cfg.n_inputs)).astype(np.uint8)
            want, _ = FabricSim(cfg).run(bits)
            for which, got in (
                ("bitsliced", lut_ops.fabric_eval(cfg, bits,
                                                  layout="bitsliced")),
                ("banded", lut_ops.fabric_eval(cfg, bits, band=True)),
                ("dense", lut_ops.fabric_eval(cfg, bits, band=False)),
                ("host_word_oracle", BitslicedSim(cfg).run(bits)),
            ):
                np.testing.assert_array_equal(
                    np.asarray(got), want,
                    err_msg=f"{name} seed={seed} B={B} via {which}")
