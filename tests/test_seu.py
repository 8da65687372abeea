"""SEU fault-injection campaign for the TMR serving stage + sparse link.

The resilience claim under test (ISSUE acceptance bar): with
``ServerConfig(redundancy="tmr")`` a SINGLE configuration-bit flip in any
one replica of any served chip leaves the voted server outputs
bit-identical to the unperturbed golden model — on both backends, banded
and dense — while the per-replica disagreement counters (the SEU health
monitor) record the upset. Structure:

  fast tier
    * voter / replica-encoding / coordinate-translation properties
      (seeded sweeps via tests/_propshim);
    * a seeded random SUBSAMPLE of (replica, lut, bit) flips per
      registered fabric, injected through the live server on both
      backends (kernel: banded, dense AND the bit-sliced layout, whose
      majority vote is fused into the word-parallel bitwise pass) via
      ``server.inject_seu`` — flips are healed by re-flipping the same
      bit, so one server serves the whole subsample with no repacking;
    * the double-fault negative controls, the sparse-readout semantics,
      hot-swap/no-retrace under TMR, and config validation.
  slow tier (nightly)
    * the FULL sweep — every LUT x every truth-table bit of one replica —
      per registered fabric on the host-oracle server, plus an every-LUT
      kernel-dispatch sweep (banded, dense, and bit-sliced on every
      fabric) through the same scoring dispatch the server launches
      (fabric_eval_multi_scored), and a banded bit-sliced sub-campaign
      through the WORD-domain sparse dispatch
      (fabric_eval_multi_scored_sparse). Writes the disagreement-counter
      campaign summary to $REPRO_SEU_REPORT for the CI artifact.

Replica-vote math note: a config upset perturbs ONE replica, so the two
healthy replicas always outvote it — what the sweep actually proves is
the serving plumbing (placement-rotated replica encodings pack into
aligned output lanes, banded windows survive the rotation, the vote and
decode read the right slots). Those are exactly the failure modes a
plumbing bug would introduce.
"""
import json
import os

import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.fabric import FABRICS, FabricSim
from repro.core.readout import ReadoutChip
from repro.core.tmr import (
    N_REPLICAS,
    inject_seu,
    majority_vote,
    replica_lut_index,
    replicate_config,
)
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.launch.readout_server import ReadoutServer, ServerConfig
from tests._propshim import given, settings, strategies as st

import repro.core.tmr  # noqa: F401  (registers efpga_28nm_xl)


# ------------------------------------------------------------ helpers
def _golden(chip, X):
    return chip.golden.decision_function_raw(chip.golden.quantize_features(X))


@pytest.fixture(scope="module")
def farm():
    """One SMALL chip per registered fabric (the sweep cost scales with
    LUT count x 16 bits), plus a feature batch and its golden scores."""
    d = generate(SmartPixelConfig(n_events=10_000, seed=11))
    tr, te = train_test_split(d)
    fabric_names = sorted({s.name for s in FABRICS.values()})
    assert {"efpga_130nm", "efpga_28nm", "efpga_28nm_xl"} <= set(fabric_names)
    chips = {}
    for name in fabric_names:
        clf = GradientBoostedClassifier(
            n_estimators=1, max_depth=3, max_leaf_nodes=5,
            min_samples_leaf=300,
        ).fit(tr["features"], tr["label"])
        chip = ReadoutChip.build(clf, fabric=name)
        chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
        chips[name] = chip
    X = te["features"][:96]
    return chips, X


def _serve_features(server, X, chip_slot=0):
    server.submit_batch(chip_slot, X)
    res = sorted(server.flush(), key=lambda r: r.seq)
    return (np.array([r.score_raw for r in res]),
            np.array([r.keep for r in res]))


# ---------------------------------------------------- voter properties
@given(seed=st.integers(0, 10_000), n=st.integers(1, 200))
@settings(max_examples=40, deadline=None)
def test_majority_vote_two_agreeing_always_win(seed, n):
    """vote(a,a,b) == a in every argument order, for all bit patterns —
    the property that makes any single-replica fault maskable."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2, n).astype(np.uint8)
    b = rng.integers(0, 2, n).astype(np.uint8)
    np.testing.assert_array_equal(majority_vote(a, a, b), a)
    np.testing.assert_array_equal(majority_vote(a, b, a), a)
    np.testing.assert_array_equal(majority_vote(b, a, a), a)
    np.testing.assert_array_equal(majority_vote(a, a, a), a)


def test_majority_vote_exhaustive_truth_table():
    a, b, c = np.meshgrid(*[np.arange(2, dtype=np.uint8)] * 3, indexing="ij")
    got = majority_vote(a, b, c)
    want = ((a + b + c) >= 2).astype(np.uint8)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------- replica encoding properties
def test_replicas_functionally_identical_all_fabrics(farm):
    chips, X = farm
    for name, chip in chips.items():
        bits = chip.encode_features(X)
        want = _golden(chip, X)
        for r in range(N_REPLICAS):
            rc = replicate_config(chip.config, r)
            outs, _ = FabricSim(rc).run(bits)
            got = chip.synth.decode_outputs(np.asarray(outs))
            np.testing.assert_array_equal(got, want, err_msg=f"{name} r={r}")
            # the fan-in reach (the banded-routing budget) is invariant
            assert rc.fanin_reach() == chip.config.fanin_reach(), (name, r)


def test_replica_placements_distinct(farm):
    """Replica encodings must be different configuration-memory images
    wherever a level is wide enough to permute (>= 3 slots) — the
    common-mode-aliasing defence."""
    chips, _ = farm
    for name, chip in chips.items():
        cfgs = [replicate_config(chip.config, r) for r in range(N_REPLICAS)]
        assert any(s >= 3 for s in chip.config.level_sizes), name
        for i in range(N_REPLICAS):
            for j in range(i + 1, N_REPLICAS):
                assert not np.array_equal(
                    cfgs[i].lut_tables, cfgs[j].lut_tables), (name, i, j)


@given(seed=st.integers(0, 1000), replica=st.integers(1, 2))
@settings(max_examples=10, deadline=None)
def test_replica_lut_index_tracks_tables(seed, replica, _cache={}):
    """replica_lut_index(cfg, r, j) points at the slot holding base LUT
    j's truth table in replica r's encoding — the coordinate translation
    the double-fault campaign relies on."""
    if "cfg" not in _cache:
        d = generate(SmartPixelConfig(n_events=8_000, seed=3))
        tr, _ = train_test_split(d)
        clf = GradientBoostedClassifier(
            n_estimators=1, max_depth=3, max_leaf_nodes=5,
            min_samples_leaf=300).fit(tr["features"], tr["label"])
        _cache["cfg"] = ReadoutChip.build(clf).config
    cfg = _cache["cfg"]
    rc = replicate_config(cfg, replica)
    rng = np.random.default_rng(seed)
    for j in rng.integers(0, cfg.n_luts, 8):
        k = replica_lut_index(cfg, replica, int(j))
        np.testing.assert_array_equal(rc.lut_tables[k], cfg.lut_tables[j])


# ----------------------------------------------------- inject_seu bounds
def test_inject_seu_bounds_checked(farm):
    chips, _ = farm
    cfg = next(iter(chips.values())).config
    with pytest.raises(ValueError, match="lut_index"):
        inject_seu(cfg, -1, 0)       # numpy would wrap to the last LUT
    with pytest.raises(ValueError, match="lut_index"):
        inject_seu(cfg, cfg.n_luts, 0)
    with pytest.raises(ValueError, match="bit"):
        inject_seu(cfg, 0, -3)
    with pytest.raises(ValueError, match="bit"):
        inject_seu(cfg, 0, 16)
    with pytest.raises(ValueError, match="lut_index"):
        inject_seu(cfg, 1.5, 0)
    # a valid flip still flips exactly one bit
    seu = inject_seu(cfg, 2, 5)
    diff = seu.lut_tables.astype(np.int64) - cfg.lut_tables.astype(np.int64)
    assert np.abs(diff).sum() == 1 and diff[2, 5] != 0


def test_server_inject_seu_validates(farm):
    chips, _ = farm
    srv = ReadoutServer([chips["efpga_28nm"]], ServerConfig(
        max_batch=64, max_latency_s=1e9, backend="host", redundancy="tmr"))
    with pytest.raises(ValueError, match="replica"):
        srv.inject_seu(0, 3, 0, 0)
    with pytest.raises(ValueError, match="lut_index"):
        srv.inject_seu(0, 0, -1, 0)


# ------------------------------------------------- TMR serving, healthy
def test_tmr_server_matches_plain_and_golden_both_backends(farm):
    chips, X = farm
    pool = [chips["efpga_28nm"], chips["efpga_130nm"]]
    want = _golden(pool[0], X)
    for backend in ("host", "kernel"):
        out = {}
        for red in ("none", "tmr"):
            srv = ReadoutServer(list(pool), ServerConfig(
                max_batch=64, max_latency_s=1e9, backend=backend,
                redundancy=red))
            out[red] = _serve_features(srv, X)
            rep = srv.report()
            assert rep["seu_disagreement_total"] == 0, (backend, red)
            assert rep["redundancy"] == red
        np.testing.assert_array_equal(out["tmr"][0], out["none"][0])
        np.testing.assert_array_equal(out["tmr"][0], want)
        np.testing.assert_array_equal(out["tmr"][1], out["none"][1])


def test_tmr_stack_voted_eval_matches_plain(farm):
    """fabric_eval_multi on a redundant stack returns the voted output
    word — equal to the plain stack's, banded and dense."""
    from repro.kernels.lut_eval import ops as lut_ops

    chips, X = farm
    pool = [chips["efpga_28nm"], chips["efpga_130nm"]]
    configs = [c.config for c in pool]
    per_bits = [c.encode_features(X[:40]) for c in pool]
    for band in (None, False):
        plain = lut_ops.pack_fabrics(configs, band=band)
        tmr = lut_ops.pack_fabrics(configs, band=band, redundancy="tmr")
        assert tmr.n_chips == 2 and tmr.n_replicas == 3
        assert tmr.sel.shape[0] == 6
        bits = lut_ops.stack_input_bits(tmr, per_bits)
        got = np.asarray(lut_ops.fabric_eval_multi(tmr, bits))
        want = np.asarray(lut_ops.fabric_eval_multi(plain, bits))
        np.testing.assert_array_equal(got, want, err_msg=f"band={band}")


# --------------------------------------- single-SEU subsample (fast tier)
def _sweep_flips(server, chip, X, flips, golden, *, heal=True):
    """Inject each (replica, lut, bit), serve, compare, optionally heal
    (re-flipping the same bit restores the config). Returns per-replica
    disagreement totals accumulated over the sweep."""
    masked = 0
    for replica, li, bi in flips:
        server.inject_seu(0, replica, li, bi)
        scores, keeps = _serve_features(server, X)
        np.testing.assert_array_equal(
            scores, golden,
            err_msg=f"SEU not masked: replica={replica} lut={li} bit={bi}")
        np.testing.assert_array_equal(
            keeps, golden <= chip.score_threshold_raw)
        masked += 1
        if heal:
            server.inject_seu(0, replica, li, bi)
    return masked


def test_single_seu_subsample_every_fabric_host(farm):
    """Seeded random subsample of single-bit flips per registered fabric,
    through the live host-oracle server: voted outputs stay golden."""
    chips, X = farm
    rng = np.random.default_rng(2026)
    for name, chip in chips.items():
        srv = ReadoutServer([chip], ServerConfig(
            max_batch=len(X), max_latency_s=1e9, backend="host",
            redundancy="tmr"))
        n = chip.config.n_luts
        flips = [(int(rng.integers(0, 3)), int(rng.integers(0, n)),
                  int(rng.integers(0, 16))) for _ in range(10)]
        golden = _golden(chip, X)
        assert _sweep_flips(srv, chip, X, flips, golden) == len(flips)
        # healed server is disagreement-free again on a fresh batch
        base = srv.report()["seu_disagreement_total"]
        _serve_features(srv, X)
        assert srv.report()["seu_disagreement_total"] == base, name


def test_single_seu_subsample_kernel_banded_and_dense(farm):
    """The same campaign through the kernel backend, banded AND dense —
    the acceptance bar's backend x routing matrix, subsampled."""
    chips, X = farm
    rng = np.random.default_rng(7)
    for name, chip in chips.items():
        golden = _golden(chip, X)
        for band in (None, False):
            srv = ReadoutServer([chip], ServerConfig(
                max_batch=len(X), max_latency_s=1e9, backend="kernel",
                redundancy="tmr", band=band))
            n = chip.config.n_luts
            flips = [(int(rng.integers(0, 3)), int(rng.integers(0, n)),
                      int(rng.integers(0, 16))) for _ in range(2)]
            assert _sweep_flips(srv, chip, X, flips, golden) == len(flips)


def test_single_seu_subsample_kernel_bitsliced(farm):
    """The same campaign through the bit-sliced kernel layout, per
    registered fabric: the vote folded into the word-parallel bitwise
    pass masks every subsampled flip exactly like the matmul voter."""
    chips, X = farm
    rng = np.random.default_rng(13)
    for name, chip in chips.items():
        golden = _golden(chip, X)
        srv = ReadoutServer([chip], ServerConfig(
            max_batch=len(X), max_latency_s=1e9, backend="kernel",
            redundancy="tmr", layout="bitsliced"))
        n = chip.config.n_luts
        flips = [(int(rng.integers(0, 3)), int(rng.integers(0, n)),
                  int(rng.integers(0, 16))) for _ in range(3)]
        assert _sweep_flips(srv, chip, X, flips, golden) == len(flips)


def test_seu_disagreement_counter_is_live(farm):
    """An EFFECTIVE flip (one that changes the faulty replica's outputs)
    must fire that replica's disagreement counter while outputs stay
    golden — the health monitor actually monitors."""
    chips, X = farm
    chip = chips["efpga_28nm"]
    golden = _golden(chip, X)
    srv = ReadoutServer([chip], ServerConfig(
        max_batch=len(X), max_latency_s=1e9, backend="host",
        redundancy="tmr"))
    # find a flip that matters: perturb the PLAIN config until outputs move
    rep1 = replicate_config(chip.config, 1)
    bits = chip.encode_features(X)
    eff = None
    for li in range(rep1.n_luts):
        for bi in range(16):
            outs, _ = FabricSim(inject_seu(rep1, li, bi)).run(bits)
            if not np.array_equal(
                    chip.synth.decode_outputs(np.asarray(outs)), golden):
                eff = (li, bi)
                break
        if eff:
            break
    assert eff is not None, "no effective flip found (degenerate chip?)"
    srv.inject_seu(0, 1, *eff)
    scores, _ = _serve_features(srv, X)
    np.testing.assert_array_equal(scores, golden)
    dis = srv.report()["per_chip"][0]["seu_disagreements"]
    assert dis[1] > 0 and dis[0] == 0 and dis[2] == 0, dis


# ------------------------------------------------- double-fault controls
def test_double_fault_same_logical_lut_detectably_wrong(farm):
    """Two SEUs at the SAME logical LUT/bit in two replicas: the majority
    is now wrong wherever the fault manifests — the voted output MUST
    differ from golden (it is not silently maskable) and the healthy
    minority replica's counter fires. Guards against a 'voter' that
    reads a single replica and would hide nothing."""
    chips, X = farm
    chip = chips["efpga_28nm"]
    golden = _golden(chip, X)
    bits = chip.encode_features(X)
    # effective flip in base coordinates
    eff = None
    for li in range(chip.config.n_luts):
        for bi in range(16):
            outs, _ = FabricSim(inject_seu(chip.config, li, bi)).run(bits)
            faulty = chip.synth.decode_outputs(np.asarray(outs))
            if not np.array_equal(faulty, golden):
                eff, want_faulty = (li, bi), faulty
                break
        if eff:
            break
    assert eff is not None
    li, bi = eff
    for backend, layout in (("host", "matmul"), ("kernel", "matmul"),
                            ("kernel", "bitsliced")):
        srv = ReadoutServer([chip], ServerConfig(
            max_batch=len(X), max_latency_s=1e9, backend=backend,
            redundancy="tmr", layout=layout))
        srv.inject_seu(0, 0, replica_lut_index(chip.config, 0, li), bi)
        srv.inject_seu(0, 1, replica_lut_index(chip.config, 1, li), bi)
        scores, _ = _serve_features(srv, X)
        # the double fault outvotes the healthy replica: served == faulty
        np.testing.assert_array_equal(
            scores, want_faulty, err_msg=f"{backend}/{layout}")
        assert not np.array_equal(scores, golden), (backend, layout)
        dis = srv.report()["per_chip"][0]["seu_disagreements"]
        # healthy minority voted against
        assert dis[2] > 0, (backend, layout, dis)


def test_double_fault_different_luts_counters_fire(farm):
    """Two effective SEUs at DIFFERENT logical LUTs in different
    replicas: each faulty replica is voted against on its own fault's
    events, so BOTH counters fire (and, faults being independent, the
    voted output stays golden wherever at most one replica is wrong)."""
    chips, X = farm
    chip = chips["efpga_28nm"]
    golden = _golden(chip, X)
    bits = chip.encode_features(X)
    effective = []
    for li in range(chip.config.n_luts):
        if len(effective) == 2:
            break
        for bi in range(16):
            outs, _ = FabricSim(inject_seu(chip.config, li, bi)).run(bits)
            if not np.array_equal(
                    chip.synth.decode_outputs(np.asarray(outs)), golden):
                effective.append((li, bi))
                break
    assert len(effective) == 2, "need two effective faults"
    srv = ReadoutServer([chip], ServerConfig(
        max_batch=len(X), max_latency_s=1e9, backend="host",
        redundancy="tmr"))
    (l0, b0), (l1, b1) = effective
    srv.inject_seu(0, 0, replica_lut_index(chip.config, 0, l0), b0)
    srv.inject_seu(0, 1, replica_lut_index(chip.config, 1, l1), b1)
    _serve_features(srv, X)
    dis = srv.report()["per_chip"][0]["seu_disagreements"]
    assert dis[0] > 0 and dis[1] > 0, dis


# ------------------------------------------------------- sparse readout
def test_sparse_server_returns_kept_subset_only(farm):
    chips, X = farm
    pool = [chips["efpga_28nm"], chips["efpga_130nm"]]
    for backend in ("host", "kernel"):
        # one micro-batch => exactly one sparse header on the wire
        dense_srv = ReadoutServer(list(pool), ServerConfig(
            max_batch=1000, max_latency_s=1e9, backend=backend))
        sparse_srv = ReadoutServer(list(pool), ServerConfig(
            max_batch=1000, max_latency_s=1e9, backend=backend, sparse=True))
        for srv in (dense_srv, sparse_srv):
            srv.submit_batch(0, X[:50])
            srv.submit_batch(1, X[50:90])
        dense = sorted(dense_srv.flush(), key=lambda r: r.seq)
        sparse = sorted(sparse_srv.flush(), key=lambda r: r.seq)
        want = [(r.seq, r.chip, r.score_raw, r.keep) for r in dense if r.keep]
        got = [(r.seq, r.chip, r.score_raw, r.keep) for r in sparse]
        assert got == want, backend
        # accounting: n_in counts DROPPED events too; wire bytes measured
        rep = sparse_srv.report()
        assert rep["n_in"] == 90 and rep["n_kept"] == len(want)
        lb = rep["link_bytes"]
        assert lb["on_wire"] == 4 + 8 * len(want)
        assert lb["dense_equivalent"] == 5 * 90


def test_serverconfig_validates_redundancy_and_sparse():
    ServerConfig(redundancy="tmr", sparse=True)  # valid
    with pytest.raises(ValueError, match="redundancy"):
        ServerConfig(redundancy="dmr")
    with pytest.raises(ValueError, match="sparse"):
        ServerConfig(sparse=1)


# ---------------------------------------------- hot-swap / no-retrace
def test_tmr_hot_swap_and_inject_do_not_retrace(farm):
    from repro.kernels import frontend as fe
    from repro.kernels.lut_eval import ops as lut_ops

    if not hasattr(lut_ops._eval_stack_scored, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this JAX")
    chips, X = farm
    a, b = chips["efpga_28nm"], chips["efpga_130nm"]
    srv = ReadoutServer([a, b], ServerConfig(
        max_batch=64, max_latency_s=1e9, backend="kernel",
        redundancy="tmr", sparse=True))
    _serve_features(srv, X[:32])
    n0 = lut_ops._eval_stack_scored._cache_size()
    srv.reconfigure(0, b)
    srv.inject_seu(1, 2, 0, 3)
    scores, _ = _serve_features(srv, X[:32])
    assert lut_ops._eval_stack_scored._cache_size() == n0
    # swapped slot now scores as chip b (sparse: only kept events return),
    # and the SEU on slot 1 stays masked
    want = _golden(b, X[:32])
    kept = want <= b.score_threshold_raw
    np.testing.assert_array_equal(scores, want[kept])


def test_tmr_swap_replica_rejects_mismatched_io(farm):
    from repro.kernels.lut_eval import ops as lut_ops

    chips, _ = farm
    a, b = chips["efpga_28nm"], chips["efpga_130nm"]
    stack = lut_ops.pack_fabrics([a.config], redundancy="tmr")
    if b.config.n_inputs != a.config.n_inputs:
        with pytest.raises(ValueError, match="IO widths|envelope"):
            stack.swap_replica(0, 1, b.config)
    with pytest.raises(ValueError, match="replica"):
        stack.swap_replica(0, 5, a.config)


# ------------------------------------------------------------- slow tier
def _campaign_record(summary):
    """Append the campaign summary for the CI artifact (nightly uploads
    $REPRO_SEU_REPORT)."""
    path = os.environ.get("REPRO_SEU_REPORT", "")
    if not path:
        return
    doc = {"campaign": "seu_single_fault_full_sweep", "fabrics": summary}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


@pytest.mark.slow
def test_single_seu_full_sweep_host_every_fabric(farm):
    """THE campaign: every LUT x every truth-table bit of one replica
    (the placement-rotated replica 1), per registered fabric, through the
    host-oracle server. 100% of flips must leave voted outputs golden."""
    chips, X = farm
    Xs = X[:48]
    summary = {}
    for name, chip in chips.items():
        golden = _golden(chip, Xs)
        srv = ReadoutServer([chip], ServerConfig(
            max_batch=len(Xs), max_latency_s=1e9, backend="host",
            redundancy="tmr"))
        n_flips = 0
        for li in range(chip.config.n_luts):
            for bi in range(16):
                srv.inject_seu(0, 1, li, bi)
                scores, _ = _serve_features(srv, Xs)
                np.testing.assert_array_equal(
                    scores, golden,
                    err_msg=f"{name}: SEU lut={li} bit={bi} not masked")
                srv.inject_seu(0, 1, li, bi)  # heal
                n_flips += 1
        rep = srv.report()
        summary[name] = {
            "n_flips": n_flips,
            "n_luts": chip.config.n_luts,
            "masked": n_flips,
            "seu_disagreements_by_replica": [
                int(v) for v in rep["per_chip"][0]["seu_disagreements"]],
            "events_per_flip": len(Xs),
        }
    _campaign_record(summary)


@pytest.mark.slow
def test_single_seu_sweep_kernel_every_lut_banded_and_dense(farm):
    """Kernel sweep through the SAME scoring dispatch the server launches
    (fabric_eval_multi_scored), banded and dense: EVERY LUT of replica 1,
    one seeded truth-table bit each, every flip swapped in via
    swap_replica (pure array swap, one compiled dispatch reused
    throughout). The per-bit exhaustive axis lives in the host sweep
    above — the kernel is proven bit-identical to the host oracle on
    perturbed stacks by the fast-tier subsample, and a full 16-bit kernel
    sweep costs ~40 min in CPU interpret mode (it is a ~2 s/flip
    dispatch; compiled TPU would do it in seconds)."""
    from repro.kernels.lut_eval import ops as lut_ops
    from repro.launch.mesh import make_readout_mesh

    chips, X = farm
    chip = chips["efpga_28nm"]
    Xs = X[:32]
    bits = chip.encode_features(Xs)[None]
    golden = _golden(chip, Xs)
    mesh = make_readout_mesh(1)
    rng = np.random.default_rng(404)
    for band in (None, False):
        stack = lut_ops.pack_fabrics(
            [chip.config], band=band, redundancy="tmr")
        w = lut_ops.decode_plan([chip.config], stack.n_outputs)
        thr = np.array([chip.score_threshold_raw], np.int32)
        rep1 = replicate_config(chip.config, 1)
        for li in range(chip.config.n_luts):
            bi = int(rng.integers(0, 16))
            stack2 = stack.swap_replica(0, 1, inject_seu(rep1, li, bi))
            score, _, _ = lut_ops.fabric_eval_multi_scored(
                stack2, bits, w, thr, mesh=mesh)
            np.testing.assert_array_equal(
                np.asarray(score)[0], golden,
                err_msg=f"band={band} lut={li} bit={bi}")


@pytest.mark.slow
def test_single_seu_sweep_bitsliced_every_lut_every_fabric(farm):
    """Bit-sliced every-LUT sweep, EVERY registered fabric, through the
    scoring dispatch (fabric_eval_multi_scored with layout='bitsliced'):
    each flip is swapped into replica 1 as a pure array update (the
    bit-sliced stack keeps the no-retrace swap) and must be outvoted by
    the word-majority pass fused into the evaluator. The bit-sliced
    evaluator is traceable XLA, not interpret-mode Pallas, so this sweep
    covers every fabric where the matmul sweep above can afford one."""
    from repro.kernels.lut_eval import ops as lut_ops
    from repro.launch.mesh import make_readout_mesh

    chips, X = farm
    Xs = X[:32]
    mesh = make_readout_mesh(1)
    rng = np.random.default_rng(808)
    for name, chip in chips.items():
        bits = chip.encode_features(Xs)[None]
        golden = _golden(chip, Xs)
        stack = lut_ops.pack_fabrics(
            [chip.config], redundancy="tmr", layout="bitsliced")
        w = lut_ops.decode_plan([chip.config], stack.n_outputs)
        thr = np.array([chip.score_threshold_raw], np.int32)
        rep1 = replicate_config(chip.config, 1)
        for li in range(chip.config.n_luts):
            bi = int(rng.integers(0, 16))
            stack2 = stack.swap_replica(0, 1, inject_seu(rep1, li, bi))
            score, _, dis = lut_ops.fabric_eval_multi_scored(
                stack2, bits, w, thr, mesh=mesh)
            np.testing.assert_array_equal(
                np.asarray(score)[0], golden,
                err_msg=f"{name} lut={li} bit={bi} (bitsliced)")


@pytest.mark.slow
def test_single_seu_sweep_bitsliced_banded_sparse(farm):
    """BANDED bit-sliced TMR stacks under SEU, served through the
    word-domain sparse dispatch (fabric_eval_multi_scored_sparse): every
    sampled replica-1 flip must be outvoted — the packed (count, idx,
    vals) egress stays bit-identical to the golden kept set — proving
    the band (a pure reach envelope) and the fused word-domain egress
    change neither the vote nor the wire contents. Sub-campaign of the
    nightly SEU tier, every registered fabric."""
    from repro.kernels.lut_eval import ops as lut_ops
    from repro.launch.mesh import make_readout_mesh
    from repro.parallel.compression import sparse_trigger_unpack

    chips, X = farm
    Xs = X[:37]                         # off the 32-event word boundary
    mesh = make_readout_mesh(1)
    rng = np.random.default_rng(811)
    for name, chip in chips.items():
        bits = chip.encode_features(Xs)[None]
        golden = _golden(chip, Xs)
        kept = golden <= chip.score_threshold_raw
        stack = lut_ops.pack_fabrics(
            [chip.config], band=True, redundancy="tmr", layout="bitsliced")
        if not stack.banded:
            continue                    # reach covers the depth: no band
        w = lut_ops.decode_plan([chip.config], stack.n_outputs)
        thr = np.array([chip.score_threshold_raw], np.int32)
        rep1 = replicate_config(chip.config, 1)
        for li in range(0, chip.config.n_luts, 3):
            bi = int(rng.integers(0, 16))
            stack2 = stack.swap_replica(0, 1, inject_seu(rep1, li, bi))
            count, idx, vals, dis = lut_ops.fabric_eval_multi_scored_sparse(
                stack2, bits, w, thr, mesh=mesh)
            tag = f"{name} lut={li} bit={bi} (banded bitsliced sparse)"
            assert int(np.asarray(count)) == int(kept.sum()), tag
            s2, k2 = sparse_trigger_unpack(
                np.asarray(idx), np.asarray(vals), (1, len(Xs)))
            np.testing.assert_array_equal(k2[0], kept, err_msg=tag)
            np.testing.assert_array_equal(
                s2[0], golden * kept, err_msg=tag)
            d = np.asarray(dis)
            assert d[0, 0] == 0 and d[0, 2] == 0, tag  # healthy replicas
