"""Banded lut_eval routing + depth-reducing ensemble synthesis.

Covers the coupled perf optimizations end to end:
  (a) fan-in-reach analysis (netlist / decoded-bitstream level)
  (b) banded-vs-dense bit-exactness across every registered fabric and an
      ensemble chip, through encode -> pack(banded) -> evaluate vs the
      host oracle
  (c) carry-select adders + balanced tree reduction: exhaustive adder
      exactness, ensemble exactness, and the depth/reach reduction itself
  (d) banded stacks keep the hot-swap guarantees: no retrace on swap, and
      configs whose reach exceeds the band are rejected identically at
      the stack and server envelope layers
"""
import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.bitstream import decode, encode
from repro.core.fabric import (
    FABRICS, FabricSim, MultiFabricSim, StackGeometry, place_and_route,
)
from repro.core.netlist import NetlistBuilder
from repro.core.quantize import FixedSpec
from repro.core.synth import _carry_select_add, synth_ensemble, verify_against_golden
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.kernels.lut_eval import ops as lut_ops
from repro.kernels.lut_eval.ref import fabric_eval_ref
from tests.test_kernels import _random_netlist

import repro.core.tmr  # noqa: F401  (registers efpga_28nm_xl)


# ------------------------------------------------------------ helpers
def _layered_netlist(seed: int, n_inputs: int, width: int, levels: int):
    """Netlist whose every LUT reads only the immediately preceding level
    (or primary inputs at level 0) -> fan-in reach exactly 1."""
    rng = np.random.default_rng(seed)
    b = NetlistBuilder()
    prev = b.input_bus(n_inputs)
    for _ in range(levels):
        nxt = []
        for _ in range(width):
            srcs = rng.choice(len(prev), size=min(4, len(prev)), replace=False)
            nxt.append(b.lut(int(rng.integers(0, 2**16)), [prev[s] for s in srcs]))
        prev = nxt
    for n in prev:
        b.mark_output(n)
    return b.build()


def _long_edge_netlist(n_inputs: int, chain: int):
    """A buffer chain whose last LUT also reads the chain's first LUT
    output -> fan-in reach == chain - 1."""
    b = NetlistBuilder()
    ins = b.input_bus(n_inputs)
    first = b.buf(ins[0])
    cur = first
    for _ in range(chain - 2):
        cur = b.buf(cur)
    out = b.fn(lambda x, y: x ^ y, cur, first)  # spans the whole chain
    b.mark_output(out)
    return b.build()


def assert_sparse_ships_kept(stack, bits, w, thr, golden, tag=""):
    """One scored dispatch through the word-domain sparse egress ships
    exactly the events at or under the cut, with their golden scores,
    and no replica disagrees."""
    from repro.launch.mesh import make_readout_mesh
    from repro.parallel.compression import sparse_trigger_unpack

    kept = golden <= thr[0]
    count, idx, vals, dis = lut_ops.fabric_eval_multi_scored_sparse(
        stack, bits, w, thr, mesh=make_readout_mesh(1))
    assert int(np.asarray(count)) == int(kept.sum()), tag
    s2, k2 = sparse_trigger_unpack(np.asarray(idx), np.asarray(vals),
                                   (1, len(golden)))
    np.testing.assert_array_equal(k2[0], kept, err_msg=tag)
    np.testing.assert_array_equal(s2[0], golden * kept, err_msg=tag)
    assert not np.asarray(dis).any(), tag


@pytest.fixture(scope="module")
def ensemble_parts():
    d = generate(SmartPixelConfig(n_events=10_000, seed=21))
    tr, te = train_test_split(d)
    clf = GradientBoostedClassifier(
        n_estimators=4, max_depth=3, max_leaf_nodes=6, min_samples_leaf=300,
    ).fit(tr["features"], tr["label"])
    ens = clf.quantized(FixedSpec(width=16, int_bits=8))
    return ens, te["features"]


# ------------------------------------------------------------------ (a)
def test_fanin_reach_layered_is_one():
    nl = _layered_netlist(0, 8, 6, levels=4)
    lv = nl.to_levelized()
    assert lv.fanin_reach() == 1
    cfg = place_and_route(nl, FABRICS["efpga_28nm"])
    assert cfg.fanin_reach() == 1


def test_fanin_reach_long_edge():
    nl = _long_edge_netlist(2, chain=7)
    assert nl.to_levelized().fanin_reach() == 6
    cfg = place_and_route(nl, FABRICS["efpga_28nm"])
    assert cfg.fanin_reach() == 6
    # reach survives the wire format (derived from decoded arrays)
    assert decode(encode(cfg)).fanin_reach() == 6


def test_fanin_reach_in_union_geometry():
    cfgs = [
        place_and_route(_layered_netlist(1, 6, 4, levels=3), FABRICS["efpga_28nm"]),
        place_and_route(_long_edge_netlist(2, chain=5), FABRICS["efpga_28nm"]),
    ]
    geo = StackGeometry.union(cfgs)
    assert geo.fanin_reach == 4
    assert all(geo.admits(c) for c in cfgs)


# ------------------------------------------------------------------ (b)
def test_banded_vs_dense_bit_exact_every_fabric():
    """encode -> decode -> pack(banded / dense) -> evaluate == host oracle,
    for every distinct registered fabric (open-ended set)."""
    import jax.numpy as jnp

    fabric_names = sorted({s.name for s in FABRICS.values()})
    assert {"efpga_130nm", "efpga_28nm", "efpga_28nm_xl"} <= set(fabric_names)
    for fi, name in enumerate(fabric_names):
        nl = _random_netlist(40 + fi, 10, 48)
        cfg = decode(encode(place_and_route(nl, FABRICS[name])))
        rng = np.random.default_rng(fi)
        bits = rng.integers(0, 2, (17, cfg.n_inputs)).astype(np.uint8)
        want, _ = FabricSim(cfg).run(bits)
        for band in (True, False):
            packed = lut_ops.pack_fabric(cfg, band=band)
            assert packed.banded == (band and packed.band_k < packed.n_levels)
            got = np.asarray(lut_ops.fabric_eval(packed, bits))
            ref = np.asarray(fabric_eval_ref(packed, jnp.asarray(bits)))
            np.testing.assert_array_equal(got, want, err_msg=f"{name} band={band}")
            np.testing.assert_array_equal(ref, want, err_msg=f"{name} band={band}")


def test_banded_window_is_smaller_and_aligned():
    nl = _layered_netlist(3, 12, 10, levels=8)
    cfg = place_and_route(nl, FABRICS["efpga_28nm"])
    packed = lut_ops.pack_fabric(cfg)  # auto: reach 1 << 8 levels -> banded
    assert packed.banded and packed.band_k == 1
    assert packed.sel.shape[1] == packed.in_seg + packed.band_k * packed.m_pad
    assert packed.sel.shape[1] < packed.n_nets_pad
    win = np.asarray(packed.win_base)
    assert (win % 128 == 0).all()
    assert win[0] == packed.in_seg
    # window of level l starts at level max(0, l-K)
    want = packed.in_seg + np.maximum(
        np.arange(packed.n_levels) - packed.band_k, 0) * packed.m_pad
    np.testing.assert_array_equal(win, want)


def test_dense_fallback_when_band_not_cheaper():
    # single-level netlist: the window would cover every level, so the
    # auto choice falls back to the dense layout
    b = NetlistBuilder()
    x = b.input_bus(4)
    b.mark_output(b.fn(lambda a, c: a & c, x[0], x[1]))
    b.mark_output(b.fn(lambda a, c: a ^ c, x[2], x[3]))
    cfg = place_and_route(b.build(), FABRICS["efpga_28nm"])
    assert cfg.fanin_reach() == 1 and len(cfg.level_sizes) == 1
    packed = lut_ops.pack_fabric(cfg)  # auto
    assert not packed.banded
    assert packed.sel.shape[1] == packed.n_nets_pad

    # worst-case reach (level L-1 reads level 0) still bands, but the
    # window only drops a single level's worth of rows
    nl = _long_edge_netlist(2, chain=6)
    cfg2 = place_and_route(nl, FABRICS["efpga_28nm"])
    L = len(cfg2.level_sizes)
    assert cfg2.fanin_reach() == L - 1
    p2 = lut_ops.pack_fabric(cfg2)
    assert p2.banded and p2.band_k == L - 1
    assert p2.sel.shape[1] == p2.in_seg + (L - 1) * p2.m_pad
    # forcing dense is always available
    p3 = lut_ops.pack_fabric(cfg2, band=False)
    assert not p3.banded and p3.sel.shape[1] == p3.n_nets_pad


def test_ensemble_chip_banded_through_bitstream(ensemble_parts):
    """The deep-ensemble chip (tree-reduction synthesis), through the wire
    format, evaluated banded — must match the host oracle and the golden
    quantized model on every event."""
    ens, X = ensemble_parts
    synth = synth_ensemble(ens, adder="tree")
    cfg = decode(encode(place_and_route(synth.netlist, FABRICS["efpga_28nm_xl"])))
    packed = lut_ops.pack_fabric(cfg)
    assert packed.banded, "tree-reduction ensembles must band (reach << depth)"

    X_raw = ens.quantize_features(X[:96])
    bits = synth.encode_inputs(X_raw)
    want, _ = FabricSim(cfg).run(bits)
    got = np.asarray(lut_ops.fabric_eval(packed, bits))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        synth.decode_outputs(got), ens.decision_function_raw(X_raw)
    )


@pytest.mark.slow
def test_banded_vs_dense_every_fabric_seeded_sweep():
    """Long sweep: several random netlists per fabric, banded == dense ==
    host oracle bit for bit."""
    fabric_names = sorted({s.name for s in FABRICS.values()})
    for fi, name in enumerate(fabric_names):
        rng = np.random.default_rng(500 + fi)
        for seed in range(4):
            nl = _random_netlist(700 + 10 * fi + seed, int(rng.integers(4, 16)),
                                 int(rng.integers(20, 140)))
            cfg = place_and_route(nl, FABRICS[name])
            bits = rng.integers(0, 2, (65, cfg.n_inputs)).astype(np.uint8)
            want, _ = FabricSim(cfg).run(bits)
            banded = np.asarray(lut_ops.fabric_eval(cfg, bits, band=True))
            dense = np.asarray(lut_ops.fabric_eval(cfg, bits, band=False))
            np.testing.assert_array_equal(banded, want)
            np.testing.assert_array_equal(dense, want)


# ------------------------------------------------------------------ (c)
@pytest.mark.parametrize("block", [1, 2, 3, 4, 5])
def test_carry_select_add_exhaustive(block):
    """All 64x64 6-bit operand pairs, every block size: wraps exactly."""
    b = NetlistBuilder()
    a = b.input_bus(6)
    c = b.input_bus(6)
    for net in _carry_select_add(b, a, c, block=block):
        b.mark_output(net)
    nl = b.build()
    xs = np.arange(64)
    A, C = [m.ravel() for m in np.meshgrid(xs, xs, indexing="ij")]
    bits = np.concatenate(
        [(A[:, None] >> np.arange(6)) & 1, (C[:, None] >> np.arange(6)) & 1],
        axis=1,
    ).astype(np.uint8)
    out, _ = nl.evaluate(bits)
    got = (out * (1 << np.arange(6))).sum(-1)
    np.testing.assert_array_equal(got, (A + C) & 63)


def test_tree_reduction_cuts_depth_and_reach(ensemble_parts):
    ens, X = ensemble_parts
    s_ripple = synth_ensemble(ens, adder="ripple")
    s_tree = synth_ensemble(ens, adder="tree")
    lv_r = s_ripple.netlist.to_levelized()
    lv_t = s_tree.netlist.to_levelized()
    assert len(lv_t.level_sizes) < len(lv_r.level_sizes)
    assert lv_t.fanin_reach() < lv_r.fanin_reach()
    # both summation structures are exact vs the golden quantized model
    X_raw = ens.quantize_features(X[:600])
    assert verify_against_golden(s_ripple, ens, X_raw)["accuracy"] == 1.0
    assert verify_against_golden(s_tree, ens, X_raw)["accuracy"] == 1.0


@pytest.mark.parametrize("band,layout", [(False, "matmul"),
                                         (None, "matmul"),
                                         (None, "bitsliced")])
@pytest.mark.parametrize("adder", ["ripple", "tree"])
def test_deep_ensemble_every_layout_matches_golden(ensemble_parts, adder,
                                                   band, layout):
    """The 4-tree ensemble on efpga_28nm_xl equals the golden quantized
    model under both summation structures through every evaluator: dense
    and auto-banded matmul, and bit-sliced. The bit-sliced word-domain
    sparse egress ships exactly the events under the cut at 90, 50 and
    10 % accept."""
    ens, X = ensemble_parts
    synth = synth_ensemble(ens, adder=adder)
    cfg = place_and_route(synth.netlist, FABRICS["efpga_28nm_xl"])
    X_raw = ens.quantize_features(X[:100])
    golden = ens.decision_function_raw(X_raw)
    bits = synth.encode_inputs(X_raw)
    got = np.asarray(lut_ops.fabric_eval(
        lut_ops.pack_fabric(cfg, band=band, layout=layout), bits))
    np.testing.assert_array_equal(synth.decode_outputs(got), golden)
    if layout != "bitsliced":
        return

    stack = lut_ops.pack_fabrics([cfg], layout="bitsliced")
    w = lut_ops.decode_plan([cfg], stack.n_outputs)
    for pct in (90, 50, 10):
        thr = np.array([int(np.percentile(golden, pct))], np.int32)
        assert_sparse_ships_kept(stack, bits[None], w, thr, golden,
                                 f"p{pct}")


def test_synth_rejects_unknown_adder(ensemble_parts):
    ens, _ = ensemble_parts
    with pytest.raises(ValueError, match="adder"):
        synth_ensemble(ens, adder="kogge-stone")


# ------------------------------------------------------------------ (d)
def _banded_stack_and_bits(seed=0):
    cfgs = [
        place_and_route(_layered_netlist(seed + i, 8, 6, levels=5 + i),
                        FABRICS["efpga_28nm"])
        for i in range(3)
    ]
    stack = lut_ops.pack_fabrics(cfgs, band=True)
    rng = np.random.default_rng(seed)
    per = [rng.integers(0, 2, (9, c.n_inputs)).astype(np.uint8) for c in cfgs]
    return cfgs, stack, per


def test_banded_stack_matches_host_oracle():
    cfgs, stack, per = _banded_stack_and_bits()
    assert stack.banded and stack.band_k == 1
    bits = lut_ops.stack_input_bits(stack, per)
    got = np.asarray(lut_ops.fabric_eval_multi(stack, bits))
    want = MultiFabricSim(cfgs).run(bits)
    np.testing.assert_array_equal(got, want)


def test_banded_swap_chip_no_retrace():
    """Hot-swap into a *banded* stack: array swap, no recompile, still
    bit-exact (fast tier — the stack is tiny)."""
    if not hasattr(lut_ops._eval_stack_arrays, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this JAX")
    cfgs, stack, per = _banded_stack_and_bits(seed=10)
    bits = lut_ops.stack_input_bits(stack, per)
    np.asarray(lut_ops.fabric_eval_multi(stack, bits))
    n0 = lut_ops._eval_stack_arrays._cache_size()

    new = place_and_route(
        _layered_netlist(99, 6, 5, levels=4), FABRICS["efpga_28nm"])
    assert new.fanin_reach() <= stack.band_k
    stack2 = stack.swap_chip(1, new)
    assert stack2.band_k == stack.band_k
    rng = np.random.default_rng(2)
    per2 = list(per)
    per2[1] = rng.integers(0, 2, (9, new.n_inputs)).astype(np.uint8)
    bits2 = lut_ops.stack_input_bits(stack2, per2)
    got = np.asarray(lut_ops.fabric_eval_multi(stack2, bits2))
    assert lut_ops._eval_stack_arrays._cache_size() == n0, "swap retraced"

    swapped = [cfgs[0], new, cfgs[2]]
    geo = StackGeometry(
        n_levels=stack.n_levels, max_level_size=stack.m_pad,
        n_inputs=stack.n_inputs, n_outputs=stack.n_outputs,
    )
    want = MultiFabricSim(swapped, geometry=geo).run(bits2)
    np.testing.assert_array_equal(got, want)


def test_banded_swap_rejects_reach_exceeding_band():
    cfgs, stack, _ = _banded_stack_and_bits(seed=20)
    deep = place_and_route(_long_edge_netlist(2, chain=4), FABRICS["efpga_28nm"])
    assert deep.fanin_reach() > stack.band_k
    assert len(deep.level_sizes) <= stack.n_levels  # only the band blocks it
    with pytest.raises(ValueError, match="envelope"):
        stack.swap_chip(0, deep)
    # a dense stack over the same configs admits the same chip fine
    dense = lut_ops.pack_fabrics(cfgs, band=False)
    dense.swap_chip(0, deep)


def test_server_envelope_includes_reach_on_both_backends():
    """A reach-exceeding hot-swap is refused by the geometry check on the
    host AND kernel servers — before any backend-specific packing."""
    import types

    from repro.core.readout import ReadoutChip  # noqa: F401 (import check)
    from repro.launch.readout_server import ReadoutServer, ServerConfig

    d = generate(SmartPixelConfig(n_events=6_000, seed=31))
    tr, _ = train_test_split(d)
    chips = []
    for depth in (4, 3):
        clf = GradientBoostedClassifier(
            n_estimators=1, max_depth=depth, max_leaf_nodes=8,
            min_samples_leaf=200,
        ).fit(tr["features"], tr["label"])
        chips.append(ReadoutChip.build(clf))
    geo = StackGeometry.union([c.config for c in chips])
    deep = place_and_route(
        _long_edge_netlist(2, chain=geo.n_levels), FABRICS["efpga_28nm"])
    assert deep.fanin_reach() > (geo.fanin_reach or 0)
    for backend in ("host", "kernel"):
        # the fan-in-reach envelope is layout-independent: the band is a
        # reach budget, not a kernel structure, so a banded stack refuses
        # the swap identically via the matmul kernel and the bit-sliced
        # word path
        for layout in ("matmul", "bitsliced"):
            srv = ReadoutServer(list(chips), ServerConfig(
                max_batch=1_000, max_latency_s=1e9, backend=backend,
                layout=layout))
            with pytest.raises(ValueError, match="envelope"):
                srv.reconfigure(0, types.SimpleNamespace(config=deep))
        # forcing dense opts out of the band — and of its reach budget, so
        # the same swap is admitted (identically on both backends)
        srv_dense = ReadoutServer(list(chips), ServerConfig(
            max_batch=1_000, max_latency_s=1e9, backend=backend, band=False))
        assert srv_dense.geometry.fanin_reach is None
        assert srv_dense.geometry.admits(deep)
