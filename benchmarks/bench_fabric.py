"""Fabric execution throughput: host oracle vs Pallas kernels (events/s).

Covers the paper's bring-up firmware (counter §2.4.1/4.4.1, loopback
§4.4.3) as functional benchmarks, the BDT classifier as the throughput
benchmark, and a deep-ensemble scenario exercising the two optimizations
that keep multi-tree chips fast: banded lut_eval routing (per-level matmul
touches only the fan-in window) and carry-select tree-reduction synthesis
(shallow, reach-bounded adders). The headline BDT kernel record and the
multi-chip/TMR scenarios run the bit-sliced layout (32 events per uint32
lane, LUTs as bitwise mux trees, the TMR vote folded into the same
bitwise pass); the matmul Pallas kernels run in interpret mode on CPU
(compiled on TPU), so their derived events/s is a CPU lower bound; the
TPU-side roofline is in benchmarks/roofline.py.

Besides the CSV rows printed through ``emit``, every record lands in
``BENCH_fabric.json`` (override the path with REPRO_BENCH_JSON) so the
perf trajectory is machine-readable PR-over-PR. REPRO_BENCH_SMOKE=1
shrinks event counts to CI-smoke size.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.core.bdt import GradientBoostedClassifier
from repro.core.fabric import FABRIC_28NM, FabricSim, place_and_route
from repro.core.netlist import counter_netlist, loopback_netlist
from repro.core.readout import ReadoutChip
from repro.core.synth import synth_ensemble
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.kernels.bdt_infer import ops as bdt_ops
from repro.kernels.compat import default_interpret
from repro.kernels.lut_eval import ops as lut_ops

_SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "") == "1"
_JSON_PATH = os.environ.get("REPRO_BENCH_JSON", "BENCH_fabric.json")
_PROFILE_DIR = os.environ.get("REPRO_BENCH_PROFILE", "")


def _time(fn, *args, reps=3):
    fn(*args)  # warmup / jit
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    return (time.perf_counter() - t0) / reps, out


class _Recorder:
    """Mirrors every emit() row into a machine-readable record list."""

    def __init__(self, emit):
        self._emit = emit
        self.records = []

    def __call__(self, name: str, us: float, derived: str = "", **fields):
        if fields and not derived:
            derived = ";".join(f"{k}={v}" for k, v in fields.items())
        self._emit(name, us, derived)
        rec = {"name": name, "us_per_call": round(float(us), 2)}
        for part in derived.split(";"):
            if "=" not in part:
                continue
            k, v = part.split("=", 1)
            if v.lower() in ("true", "false"):
                rec[k] = v.lower() == "true"
                continue
            try:
                rec[k] = float(v) if "." in v or "e" in v.lower() else int(v)
            except ValueError:
                rec[k] = v
        rec.update(fields)
        self.records.append(rec)

    def dump(self, path: str):
        doc = {
            "benchmark": "fabric",
            "smoke": _SMOKE,
            "unit": {"us_per_call": "microseconds", "events_per_s": "1/s"},
            "records": self.records,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")


def _bench_deep_ensemble(note, tr, te):
    """Deep-ensemble scenario: n_estimators>=4 — the regime where ripple
    adders levelize ~2-3x deeper and the dense kernel's quadratic cost in
    depth bites. Measures the 2x2 of {ripple, tree-reduction} synthesis x
    {dense, banded} routing, bit-exact against the host oracle."""
    from repro.core.fabric import FABRICS
    from repro.core.quantize import FixedSpec
    import repro.core.tmr  # noqa: F401  (registers efpga_28nm_xl)

    B = 128 if _SMOKE else 512
    spec = FixedSpec(width=16, int_bits=8)
    clf = GradientBoostedClassifier(
        n_estimators=4, max_depth=3, max_leaf_nodes=6, min_samples_leaf=300,
    ).fit(tr["features"], tr["label"])
    ens = clf.quantized(spec)
    fabric = FABRICS["efpga_28nm_xl"]  # 4 trees + adders exceed the 448-cell chip

    synths = {a: synth_ensemble(ens, adder=a) for a in ("ripple", "tree")}
    configs = {a: place_and_route(s.netlist, fabric) for a, s in synths.items()}
    X_raw = ens.quantize_features(te["features"][:B])
    golden = ens.decision_function_raw(X_raw)

    ev_s = {}
    for adder, band, label in [
        ("ripple", False, "dense_ripple"),   # the pre-optimization baseline
        ("ripple", None, "auto_ripple"),     # band rarely pays: reach ~ depth
        ("tree", False, "dense_tree"),
        ("tree", None, "banded_tree"),       # both optimizations together
    ]:
        cfg = configs[adder]
        packed = lut_ops.pack_fabric(cfg, band=band)
        bits = synths[adder].encode_inputs(X_raw)
        t, out = _time(
            lambda p=packed, b=bits: np.asarray(lut_ops.fabric_eval(p, b)),
            reps=1 if _SMOKE else 2,
        )
        got = synths[adder].decode_outputs(np.asarray(out))
        exact = bool(np.array_equal(got, golden))
        assert exact, f"deep-ensemble {label} diverged from golden model"
        ev_s[label] = B / t
        note(
            f"fabric.deep_ensemble4_{label}_{B}ev", t * 1e6,
            f"events_per_s={B / t:.0f};adder={adder};"
            f"banded={str(packed.banded).lower()};band_k={packed.band_k};"
            f"levels={packed.n_levels};fanin_reach={cfg.fanin_reach()};"
            f"sel_rows={packed.sel.shape[1]};n_nets_pad={packed.n_nets_pad};"
            f"bit_exact_vs_golden={str(exact).lower()}",
        )

    depth_r = len(configs["ripple"].level_sizes)
    depth_t = len(configs["tree"].level_sizes)
    speedup = ev_s["banded_tree"] / ev_s["dense_ripple"]
    note(
        "fabric.deep_ensemble4_banded_tree_speedup", 0.0,
        f"speedup={speedup:.2f};"
        f"speedup_vs_dense_ripple={speedup:.2f}x;"
        f"events_per_s_baseline={ev_s['dense_ripple']:.0f};"
        f"events_per_s_optimized={ev_s['banded_tree']:.0f};"
        f"depth_ripple={depth_r};depth_tree={depth_t};"
        f"reach_ripple={configs['ripple'].fanin_reach()};"
        f"reach_tree={configs['tree'].fanin_reach()};"
        f"luts_ripple={synths['ripple'].netlist.n_luts};"
        f"luts_tree={synths['tree'].netlist.n_luts}",
    )
    assert depth_t < depth_r, "tree reduction must cut levelized depth"

    # --- bit-sliced cells: the SAME configs through the word-parallel
    # evaluator (32 events per uint32 lane, 15 bitwise ops per LUT). The
    # deep ensemble is where the matmul kernel's quadratic cost in depth
    # bites hardest, so this speedup is the word-domain headline.
    for adder in ("ripple", "tree"):
        cfg = configs[adder]
        packed = lut_ops.pack_fabric(cfg, layout="bitsliced")
        bits = synths[adder].encode_inputs(X_raw)
        t, out = _time(
            lambda p=packed, b=bits: np.asarray(lut_ops.fabric_eval(p, b)),
            reps=1 if _SMOKE else 2,
        )
        got = synths[adder].decode_outputs(np.asarray(out))
        exact = bool(np.array_equal(got, golden))
        assert exact, f"deep-ensemble bitsliced_{adder} diverged from golden"
        label = f"bitsliced_{adder}"
        ev_s[label] = B / t
        note(
            f"fabric.deep_ensemble4_{label}_{B}ev", t * 1e6,
            f"events_per_s={B / t:.0f};adder={adder};layout=bitsliced;"
            f"banded={str(packed.banded).lower()};band_k={packed.band_k};"
            f"events_per_word=32;bit_exact_vs_golden={str(exact).lower()}",
        )

    bs_speedup = ev_s["bitsliced_tree"] / ev_s["dense_ripple"]
    note(
        "fabric.deep_ensemble4_bitsliced_speedup", 0.0,
        f"speedup={bs_speedup:.2f};"
        f"speedup_vs_dense_ripple={bs_speedup:.2f}x;"
        f"events_per_s_baseline={ev_s['dense_ripple']:.0f};"
        f"events_per_s_bitsliced={ev_s['bitsliced_tree']:.0f};"
        f"matmul_banded_tree_speedup={speedup:.2f}",
    )
    if not _SMOKE:
        assert bs_speedup >= 50.0, (
            f"deep-ensemble bit-sliced eval must be >=50x the dense matmul "
            f"baseline, got {bs_speedup:.1f}x")

    # --- word-domain sparse egress on the deep ensemble: compaction runs
    # on keep WORDS (popcount prefix sums) before any word->event
    # transpose, and the wire bytes (count header + 8 B per kept event vs
    # the 5 B/event dense frame) must track the trigger accept fraction.
    from repro.launch.mesh import make_readout_mesh
    from repro.parallel.compression import (
        DENSE_BYTES_PER_EVENT, SPARSE_BYTES_PER_EVENT, SPARSE_HEADER_BYTES,
        sparse_trigger_unpack,
    )

    cfg = configs["tree"]
    stack = lut_ops.pack_fabrics([cfg], layout="bitsliced")
    w = lut_ops.decode_plan([cfg], stack.n_outputs)
    sbits = synths["tree"].encode_inputs(X_raw)[None]
    mesh = make_readout_mesh(1)
    dense_bytes = B * DENSE_BYTES_PER_EVENT
    ratios = {}
    for pct in (90, 50, 10):
        thr = np.array([int(np.percentile(golden, pct))], np.int32)
        kept = golden <= int(thr[0])
        t, (count, idx, vals, _dis) = _time(
            lambda th=thr: lut_ops.fabric_eval_multi_scored_sparse(
                stack, sbits, w, th, mesh=mesh),
            reps=1 if _SMOKE else 2,
        )
        n_kept = int(np.asarray(count))
        assert n_kept == int(kept.sum()), (pct, n_kept, int(kept.sum()))
        s2, k2 = sparse_trigger_unpack(np.asarray(idx), np.asarray(vals),
                                       (1, B))
        assert np.array_equal(k2[0], kept), f"sparse keep mask p{pct}"
        assert np.array_equal(s2[0], golden * kept), f"sparse scores p{pct}"
        wire = SPARSE_HEADER_BYTES + n_kept * SPARSE_BYTES_PER_EVENT
        ratios[pct] = wire / dense_bytes
        note(
            f"fabric.deep_ensemble4_sparse_p{pct}_{B}ev", t * 1e6,
            f"events_per_s={B / t:.0f};accept_pct={pct};"
            f"fraction_kept={n_kept / B:.3f};layout=bitsliced;"
            f"link_bytes_on_wire={wire};link_bytes_dense={dense_bytes};"
            f"bytes_ratio={wire / dense_bytes:.3f}",
        )
    note(
        "fabric.deep_ensemble4_sparse_egress", 0.0,
        f"bytes_ratio={ratios[10]:.3f};accept_pct=10;"
        f"bytes_ratio_p50={ratios[50]:.3f};bytes_ratio_p90={ratios[90]:.3f};"
        f"dense_bytes={dense_bytes};"
        f"bytes_per_kept_event={SPARSE_BYTES_PER_EVENT};"
        f"header_bytes={SPARSE_HEADER_BYTES}",
    )
    # on-wire bytes must scale with the accept fraction and beat the
    # dense frame at trigger-like (10%) accept rates
    assert ratios[10] <= ratios[50] <= ratios[90], ratios
    assert ratios[10] < ratios[90] and ratios[10] < 1.0, ratios


def _bench_tmr_sparse(note, chip_pool, tr, frames, y0f):
    """SEU-resilient serving + sparse trigger readout: the TMR voted
    server (3 placement-distinct replicas per chip, 2-of-3 device vote)
    and the sparse (indices, scores) host link vs the plain dense path —
    events/s AND measured bytes-on-wire, bit-exact asserted throughout.
    The trigger cut is pinned at the 15th score percentile of the
    TRAINING stream (a link-budget-style cut; the benchmark's frame
    stream then lands at ~27% accept) so the wire numbers reflect a
    pileup-dominated trigger."""
    import copy

    from repro.kernels.yprofile import ops as yp_ops
    from repro.launch.readout_server import ReadoutServer, ServerConfig

    B = 128 if _SMOKE else 512
    n_chips = 2
    chips = []
    for c in chip_pool[:n_chips]:
        # the link-budget cut (15th training-score percentile) on a copy
        # so the other scenarios keep their calibrated thresholds
        c2 = copy.copy(c)
        raw = c2.golden.decision_function_raw(
            c2.golden.quantize_features(tr["features"][:2000]))
        c2.score_threshold_raw = int(np.percentile(raw, 15))
        chips.append(c2)
    fr = frames[:B]
    z = y0f[:B]
    feats = np.asarray(yp_ops.yprofile(fr, z, batch_tile=128))
    golden = {
        i: c.golden.decision_function_raw(c.golden.quantize_features(feats))
        for i, c in enumerate(chips)
    }

    def serve(redundancy, sparse):
        # bit-sliced fabric evaluation: the replicated stage is 15 bitwise
        # ops/LUT over 32-event words, so the voted path no longer pays
        # the 8.3x matmul-replication penalty
        srv = ReadoutServer(chips, ServerConfig(
            max_batch=n_chips * B, max_latency_s=1e9, backend="kernel",
            redundancy=redundancy, sparse=sparse, layout="bitsliced"))
        def go():
            for i in range(n_chips):
                srv.submit_frames(i, fr, z)
            return srv.flush()
        t, res = _time(go, reps=1)
        return srv, t, res

    ev = n_chips * B
    results = {}
    for label, red, sp in [("plain", "none", False),
                           ("tmr", "tmr", False),
                           ("tmr_sparse", "tmr", True)]:
        srv, t, res = serve(red, sp)
        rep = srv.report()
        results[label] = (t, res, rep)
        # bit-exactness: every returned score equals the golden model's
        # (chip i's events are seqs i*B .. i*B+B-1, so pos = seq % B)
        for r in res:
            assert r.score_raw == golden[r.chip][r.seq % B], (label, r.seq)
        note(
            f"fabric.tmr_sparse_{label}_{ev}ev", t * 1e6,
            f"events_per_s={ev / t:.0f};redundancy={red};"
            f"sparse={str(sp).lower()};chips={n_chips};"
            f"layout=bitsliced;n_results={len(res)};"
            f"link_bytes_on_wire={rep['link_bytes']['on_wire']};"
            f"bit_exact_vs_golden=true",
        )

    t_plain = results["plain"][0]
    t_tmr = results["tmr"][0]
    rep_sp = results["tmr_sparse"][2]
    note(
        "fabric.tmr_sparse_link_bytes", 0.0,
        f"link_bytes_sparse={rep_sp['link_bytes']['on_wire']};"
        f"link_bytes_plain={rep_sp['link_bytes']['dense_equivalent']};"
        f"wire_reduction={rep_sp['link_bytes']['wire_reduction']:.2f};"
        f"fraction_kept={rep_sp['fraction_kept']:.3f};"
        f"tmr_overhead_vs_plain={t_tmr / t_plain:.2f};"
        f"seu_disagreements={rep_sp['seu_disagreement_total']}",
    )
    assert (rep_sp["link_bytes"]["on_wire"]
            < rep_sp["link_bytes"]["dense_equivalent"]), rep_sp["link_bytes"]

    # the headline resilience-cost record: TMR throughput overhead on the
    # served path with the bit-sliced evaluator (vote folded into the
    # word-parallel bitwise pass) — was 8.3x with the matmul layouts
    overhead = t_tmr / t_plain
    note(
        "fabric.bitsliced_tmr_overhead", 0.0,
        f"tmr_overhead={overhead:.2f};efficiency={1 / overhead:.3f};"
        f"layout=bitsliced;matmul_baseline_overhead=8.3;"
        f"events_per_s_plain={ev / t_plain:.0f};"
        f"events_per_s_tmr={ev / t_tmr:.0f}",
    )
    assert overhead <= 2.0, (
        f"bit-sliced TMR overhead must be <=2x plain, got {overhead:.2f}x")


def _bench_scrub(note, chip_pool, frames, y0f):
    """Background config-memory scrubbing (readback -> CRC verify -> heal):
    (1) the sustained-throughput cost of scrubbing at the documented
    default interval on a TMR frame stream — the <5% budget the interval
    was chosen for — and (2) mean-time-to-heal under a Poisson
    configuration-fault injector with disagreement-steered scrubbing.
    Both are `fabric.scrub_*` records the CI regression gate validates."""
    from repro.launch.readout_server import (
        DEFAULT_SCRUB_INTERVAL, ReadoutServer, ServerConfig,
    )

    B = 128                     # batch_tile floor: smaller batches pad up
    n_batches = 4 if _SMOKE else 8
    n_chips = 2
    chips = chip_pool[:n_chips]
    fr = frames[:B]
    z = y0f[:B]

    def make(scrub_interval, scrub_mode="steered"):
        return ReadoutServer(chips, ServerConfig(
            max_batch=n_chips * B, max_latency_s=1e9, backend="kernel",
            redundancy="tmr", scrub_interval=scrub_interval,
            scrub_mode=scrub_mode))

    def stream(srv, n):
        for _ in range(n):
            for c in range(n_chips):
                srv.submit_frames(c, fr, z)
            srv.poll()
        srv.flush()

    # --- scrub overhead on a sustained stream (default interval)
    ev = n_chips * B * n_batches
    ev_s = {}
    for label, interval in [("off", None), ("on", DEFAULT_SCRUB_INTERVAL)]:
        srv = make(interval)
        stream(srv, 2)          # warmup: jit + first readback
        t0 = time.perf_counter()
        stream(srv, n_batches)
        t = time.perf_counter() - t0
        ev_s[label] = ev / t
        rep = srv.report()["scrub"]
        note(
            f"fabric.scrub_{label}_{ev}ev", t * 1e6,
            f"events_per_s={ev / t:.0f};redundancy=tmr;chips={n_chips};"
            f"scrub_interval={interval if interval else 0};"
            f"scrub_steps={rep['steps']};"
            f"frames_scrubbed={rep['frames_scrubbed']};"
            f"detections={rep['detections']}",
        )
    ratio = ev_s["on"] / ev_s["off"]
    note(
        "fabric.scrub_overhead", 0.0,
        f"events_per_s_ratio={ratio:.3f};"
        f"overhead_frac={max(0.0, 1.0 - ratio):.3f};"
        f"target_overhead_frac=0.05;"
        f"interval={DEFAULT_SCRUB_INTERVAL};"
        f"events_per_s_scrub_off={ev_s['off']:.0f};"
        f"events_per_s_scrub_on={ev_s['on']:.0f}",
    )

    # --- mean-time-to-heal under a Poisson fault injector: one
    # outstanding fault at a time (unambiguous attribution), arrivals
    # thinned per batch, heal detected by the report's scrub counter
    rng = np.random.default_rng(20260726)
    n_mtth = 10 if _SMOKE else 24
    rate = 0.3
    srv = make(2)               # tighter interval bounds the rr worst case
    stream(srv, 1)              # warmup
    outstanding = None
    det_seen = srv.report()["scrub"]["detections"]
    heal_batches = []
    n_injected = 0
    for bi in range(n_mtth):
        # Poisson-thinned arrivals, one outstanding fault at a time; the
        # first arrival is forced so even the smoke run measures a heal
        if outstanding is None and (n_injected == 0 or rng.random() < rate):
            slot = int(rng.integers(0, n_chips))
            replica = int(rng.integers(0, srv.n_replicas))
            cfg = srv.chips[slot].config
            srv.inject_seu(slot, replica, int(rng.integers(0, cfg.n_luts)),
                           int(rng.integers(0, 16)))
            outstanding = bi
            n_injected += 1
        stream(srv, 1)
        det = srv.report()["scrub"]["detections"]
        if outstanding is not None and det > det_seen:
            heal_batches.append(bi - outstanding + 1)
            det_seen = det
            outstanding = None
    rep = srv.report()["scrub"]
    mean_heal = float(np.mean(heal_batches)) if heal_batches else 0.0
    note(
        "fabric.scrub_mtth", 0.0,
        f"mean_batches_to_heal={mean_heal:.2f};"
        f"max_batches_to_heal={max(heal_batches, default=0)};"
        f"faults_injected={n_injected};faults_healed={len(heal_batches)};"
        f"healed_bits={rep['healed_bits']};"
        f"poisson_rate_per_batch={rate};scrub_interval=2;mode=steered;"
        f"detection_latency_mean_dispatches="
        f"{rep['detection_latency_dispatches']['mean']:.2f}",
    )
    assert len(heal_batches) == n_injected or outstanding is not None, (
        "scrubber lost track of an injected fault")


def run(emit):
    """Run the fabric suite. When ``--profile DIR`` (or
    REPRO_BENCH_PROFILE=DIR) is set, the whole suite runs under a
    ``jax.profiler`` trace written to DIR — open it with
    ``tensorboard --logdir DIR`` or xprof to see the per-dispatch
    timeline (word-domain eval, sparse compaction)."""
    if _PROFILE_DIR:
        jax.profiler.start_trace(_PROFILE_DIR)
    try:
        _run(emit)
    finally:
        if _PROFILE_DIR:
            jax.profiler.stop_trace()


def _run(emit):
    note = _Recorder(emit)

    # --- bring-up firmware
    n_cycles = 100 if _SMOKE else 1000
    nl = counter_netlist(16)
    cfgf = place_and_route(nl, FABRIC_28NM)
    sim = FabricSim(cfgf)
    t, _ = _time(lambda: sim.run(np.zeros((1, 0)), n_cycles=n_cycles))
    note(f"fabric.counter_{n_cycles}cycles", t * 1e6,
         "cycles_per_s=%.0f" % (n_cycles / t))

    lb = place_and_route(loopback_netlist(8), FABRIC_28NM)
    simlb = FabricSim(lb)
    n_lanes, n_beats = (16, 50) if _SMOKE else (64, 200)
    ins = np.random.default_rng(0).integers(
        0, 2, (n_lanes, n_beats, 10)).astype(np.uint8)
    t, _ = _time(lambda: simlb.run(ins, n_cycles=n_beats))
    note(f"fabric.loopback_{n_lanes}x{n_beats}", t * 1e6,
         "beats_per_s=%.0f" % (n_lanes * n_beats / t))

    # --- BDT classifier throughput: host sim vs lut_eval vs bdt_infer
    n_events = 6_000 if _SMOKE else 60_000
    data = generate(SmartPixelConfig(n_events=n_events, seed=2024))
    tr, te = train_test_split(data)
    clf = GradientBoostedClassifier(
        n_estimators=1, max_depth=5, max_leaf_nodes=10, min_samples_leaf=500
    ).fit(tr["features"], tr["label"])
    chip = ReadoutChip.build(clf)
    n_ev = 512 if _SMOKE else 8192
    X = te["features"][:n_ev]
    X_raw = chip.golden.quantize_features(X)
    bits = chip.synth.encode_inputs(X_raw)

    t_host, _ = _time(lambda: FabricSim(chip.config).run(bits))
    note(f"fabric.bdt_hostsim_{n_ev}ev", t_host * 1e6,
         f"events_per_s={n_ev / t_host:.0f}")

    # hot-swap cost = host-side pack latency (vectorized numpy scatter)
    t_pack, packed = _time(lambda: lut_ops.pack_fabric(chip.config))
    note("fabric.pack_fabric_latency", t_pack * 1e6,
         f"packs_per_s={1 / t_pack:.0f};banded={str(packed.banded).lower()};"
         f"band_k={packed.band_k};levels={packed.n_levels}")

    # the backend whose Pallas interpreter ran the kernel, or "off" where
    # Mosaic compiled it (TPU)
    interpret_mode = jax.default_backend() if default_interpret() else "off"
    t_mm, out = _time(lambda: np.asarray(lut_ops.fabric_eval(packed, bits)))
    note(f"fabric.bdt_lut_eval_matmul_{n_ev}ev", t_mm * 1e6,
         f"events_per_s={n_ev / t_mm:.0f};interpret_mode={interpret_mode};"
         f"banded={str(packed.banded).lower()}")

    # --- bit-sliced evaluation: 32 events per uint32 lane, each LUT a
    # 15-op bitwise mux tree over whole words (traceable XLA, no Pallas
    # interpret penalty). THE headline kernel record — bit-exact vs the
    # matmul path and the independent word-parallel host oracle.
    from repro.core.fabric import BitslicedSim

    packed_bs = lut_ops.pack_fabric(chip.config, layout="bitsliced")
    t_kern, out_bs = _time(
        lambda: np.asarray(lut_ops.fabric_eval(packed_bs, bits)))
    assert np.array_equal(out_bs, np.asarray(out)), \
        "bitsliced diverged from matmul lut_eval"
    assert np.array_equal(out_bs, BitslicedSim(chip.config).run(bits)), \
        "bitsliced kernel diverged from host word oracle"
    bs_speedup = t_mm / t_kern
    note(f"fabric.bdt_lut_eval_kernel_{n_ev}ev", t_kern * 1e6,
         f"events_per_s={n_ev / t_kern:.0f};layout=bitsliced;"
         f"events_per_word=32;bit_exact_vs_matmul=true;"
         f"speedup_vs_matmul={bs_speedup:.1f}x")
    note("fabric.bitsliced_speedup", 0.0,
         f"speedup={bs_speedup:.2f};"
         f"events_per_s_matmul={n_ev / t_mm:.0f};"
         f"events_per_s_bitsliced={n_ev / t_kern:.0f}")
    assert bs_speedup >= 10.0, (
        f"bit-sliced lut_eval must be >=10x the matmul kernel, "
        f"got {bs_speedup:.1f}x")

    ens_packed = bdt_ops.pack_ensemble(chip.golden, n_features=14)
    xi = X_raw.astype(np.int32)
    t_tree, _ = _time(lambda: np.asarray(bdt_ops.bdt_infer(ens_packed, xi)))
    note(f"fabric.bdt_infer_kernel_{n_ev}ev", t_tree * 1e6,
         f"events_per_s={n_ev / t_tree:.0f};speedup_vs_fabric={t_kern / t_tree:.1f}x")

    # full front-end path: frames -> features (yprofile kernel) -> fabric
    from repro.kernels.yprofile import ops as yp_ops

    n_fe = 512 if _SMOKE else 2_048
    d2 = generate(SmartPixelConfig(n_events=n_fe, seed=7), return_frames=True)
    t_fe, feats = _time(lambda: np.asarray(
        yp_ops.yprofile(d2["frames"], d2["features"][:, 13])))
    note(f"fabric.yprofile_kernel_{n_fe}ev", t_fe * 1e6,
         f"events_per_s={n_fe / t_fe:.0f}")

    # --- fused on-device frontend: frames -> features -> bits -> score in
    # ONE dispatch (kernels/frontend.py) vs the host-featurize baseline
    # (featurizer materialized, numpy quantize+pack, then the SAME packed
    # lut_eval backend) — the paper's at-source pipeline end to end.
    from repro.kernels import frontend as fe

    frames, y0f = d2["frames"], d2["features"][:, 13]
    # the fabric stage of the fused dispatch runs the bit-sliced layout
    # (PR 6's evaluator) — the featurizer/encode stages are unchanged, so
    # the fused speedup now reflects the sliced fabric too
    front = fe.pack_frontend([chip.config], [chip.frontend_spec()],
                             layout="bitsliced", batch_tile=128)

    def host_featurize_path():
        feats = np.asarray(yp_ops.yprofile(frames, y0f, batch_tile=128))
        return np.asarray(
            lut_ops.fabric_eval(packed, chip.encode_features(feats)))

    def fused_path():
        s, k = front.score_frames(frames[None], y0f[None])
        return np.asarray(s), np.asarray(k)

    t_staged, staged_out = _time(host_featurize_path)
    staged_scores = chip.synth.decode_outputs(np.asarray(staged_out))
    t_fused, (fscores, _fkeep) = _time(fused_path)
    fexact = bool(np.array_equal(fscores[0], staged_scores))
    assert fexact, "fused frontend diverged from the staged host path"
    note(f"fabric.frames_host_featurize_{n_fe}ev", t_staged * 1e6,
         f"events_per_s={n_fe / t_staged:.0f};"
         f"stages=featurize+encode+lut_eval;host_materialized=true")
    note(f"fabric.frames_fused_{n_fe}ev", t_fused * 1e6,
         f"events_per_s={n_fe / t_fused:.0f};one_dispatch=true;"
         f"sharded_chips=1;layout={front.stack.layout};"
         f"bit_exact_vs_staged={str(fexact).lower()}")
    note("fabric.frames_fused_speedup", 0.0,
         f"speedup={t_staged / t_fused:.2f};"
         f"events_per_s_host_featurize={n_fe / t_staged:.0f};"
         f"events_per_s_fused={n_fe / t_fused:.0f}")

    # exactness cross-check while we're here
    got = chip.synth.decode_outputs(out)
    want = chip.golden.decision_function_raw(X_raw)
    note("fabric.kernel_exactness", 0.0,
         f"match={float((got == want).mean()):.4f};paper=1.0")

    # --- multi-chip streaming: events/s vs chip count, ONE batched dispatch
    from repro.core.fabric import MultiFabricSim

    chip_pool = [chip] + [
        ReadoutChip.build(
            GradientBoostedClassifier(
                n_estimators=1, max_depth=5 - (i % 2),
                max_leaf_nodes=10 - (i % 3), min_samples_leaf=500,
            ).fit(tr["features"], tr["label"])
        )
        for i in range(1, 4)
    ]
    B = 128 if _SMOKE else 512  # interpret mode on CPU; TPU compiles full batch
    multichip_ev_s = []
    for n_chips in (1, 2, 4):
        chips = chip_pool[:n_chips]
        configs = [c.config for c in chips]
        # bit-sliced layout: chips are a leading batch axis of ONE fused
        # XLA computation, so events/s grows (not shrinks) with chip count
        stack = lut_ops.pack_fabrics(configs, layout="bitsliced")
        per_chip_bits = [
            c.synth.encode_inputs(c.golden.quantize_features(
                te["features"][: B]))
            for c in chips
        ]
        sbits = lut_ops.stack_input_bits(stack, per_chip_bits)
        t_multi, mout = _time(
            lambda: np.asarray(lut_ops.fabric_eval_multi(stack, sbits)),
            reps=1)
        ev = n_chips * B
        # bit-exactness vs the per-chip host oracle (hard requirement)
        oracle = MultiFabricSim(configs).run(sbits)
        exact = bool(np.array_equal(np.asarray(mout), oracle))
        multichip_ev_s.append(ev / t_multi)
        note(f"fabric.multichip_{n_chips}x{B}ev", t_multi * 1e6,
             f"events_per_s={ev / t_multi:.0f};chips={n_chips};"
             f"one_dispatch=true;layout=bitsliced;"
             f"bit_exact_vs_host={str(exact).lower()}")
        assert exact, f"multi-chip kernel diverged from host oracle ({n_chips} chips)"
    # scaling must be non-decreasing in chip count (0.75 tolerance factor
    # absorbs timer noise on the sub-ms dispatches)
    for i in range(1, len(multichip_ev_s)):
        assert multichip_ev_s[i] >= 0.75 * multichip_ev_s[i - 1], (
            f"multichip events/s decreased with chip count: "
            f"{[f'{v:.0f}' for v in multichip_ev_s]}")

    # --- deep-ensemble: banded routing x tree-reduction synthesis
    _bench_deep_ensemble(note, tr, te)

    # --- TMR voted serving + sparse trigger readout vs the plain path
    _bench_tmr_sparse(note, chip_pool, tr, frames, y0f)

    # --- background config scrubbing: overhead + mean-time-to-heal
    _bench_scrub(note, chip_pool, frames, y0f)

    # --- deadline-aware serving: open-loop bursty load, tail latency,
    # admission-control shed accounting and the degrade ladder
    from benchmarks import bench_latency

    bench_latency.bench_deadline(note, chip_pool[:2], frames, y0f,
                                 smoke=_SMOKE)

    # --- network front door: loopback replay vs in-process serving
    from benchmarks import bench_net

    bench_net.bench_net_scenario(note, chip_pool[:1], frames, y0f,
                                 smoke=_SMOKE)

    # --- elastic multi-tenant fleet: admission latency, evict/re-admit,
    # events/s vs tenant count over the bucketed geometry pools
    from benchmarks import bench_fleet

    bench_fleet.bench_fleet_scenario(note, chip_pool, te, smoke=_SMOKE)

    note.dump(_JSON_PATH)


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--profile", metavar="DIR", default="",
        help="write a jax.profiler trace of the whole suite under DIR "
             "(same as REPRO_BENCH_PROFILE=DIR); tracing adds "
             "per-dispatch overhead, so the suite's timing assertions "
             "can trip under it — use for timeline archaeology, not for "
             "regenerating the committed baseline")
    args = ap.parse_args(argv)
    global _PROFILE_DIR
    if args.profile:
        os.environ["REPRO_BENCH_PROFILE"] = args.profile
        _PROFILE_DIR = args.profile
    print("name,us_per_call,derived")
    run(lambda name, us, derived="": print(
        f"{name},{us:.2f},{derived}", flush=True))


if __name__ == "__main__":
    main()
