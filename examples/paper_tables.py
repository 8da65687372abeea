"""The paper's tables, reproduced: BDT operating points, power, resources.

    PYTHONPATH=src python examples/paper_tables.py
    REPRO_BENCH_FULL=1 PYTHONPATH=src python examples/paper_tables.py  # 500k events

Prints ``name,us_per_call,derived`` CSV rows, in this order:

* ``bdt.*`` — Table 1 and the §5 float model: "Before quantization, a
  background rejection of 4.35% is achieved for a signal efficiency of
  97.53%"; the synthesized model's (96.4, 5.8), (97.8, 3.9), (99.6, 1.1).
  The simulated dataset's equivalents are reported at the same target
  signal efficiencies.
* ``power.*`` — Fig. 5 / Fig. 10 (power against clock) and the §3
  scaling factors.
* ``resources.*`` — §2.1/§4.1/§5: fabric capacities, the BDT's fit, the
  NN baseline's non-fit, TMR and ensemble scaling.

``us_per_call`` times host (numpy) work on the machine that runs this;
it is no device speed. The chip's numbers are in ``PERF.md``.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.bdt import GradientBoostedClassifier, operating_point_at_signal_eff
from repro.core.fabric import FABRIC_130NM, FABRIC_28NM, place_and_route
from repro.core.nn_baseline import MLPSpec, lut_cost
from repro.core.power import (
    area_efficiency_ratio, core_power_ratio, energy_per_inference_nj, sweep,
)
from repro.core.synth import synth_ensemble
from repro.core.tmr import FABRIC_28NM_XL, triplicate
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split

N_EVENTS = 500_000 if os.environ.get("REPRO_BENCH_FULL") else 120_000


def _paper_bdt(tr):
    """The paper's model: one tree, depth 5, at most 10 leaves."""
    return GradientBoostedClassifier(
        n_estimators=1, max_depth=5, max_leaf_nodes=10, min_samples_leaf=500
    ).fit(tr["features"], tr["label"])


def bdt_table(emit):
    data = generate(SmartPixelConfig(n_events=N_EVENTS, seed=2024))
    tr, te = train_test_split(data)

    t0 = time.perf_counter()
    clf = _paper_bdt(tr)
    fit_us = (time.perf_counter() - t0) * 1e6
    emit("bdt.fit_single_tree_depth5", fit_us, f"n_train={len(tr['label'])}")

    t0 = time.perf_counter()
    score_f = clf.predict_proba(te["features"])
    f_us = (time.perf_counter() - t0) * 1e6 / len(te["label"])
    _, se, br = operating_point_at_signal_eff(score_f, te["label"], 0.9753)
    emit("bdt.float_op@sig_eff_0.9753", f_us,
         f"sig_eff={se:.4f};bkg_rej={br:.4f};paper=0.9753/0.0435")

    q = clf.quantized()
    t0 = time.perf_counter()
    score_q = q.predict_proba(te["features"])
    q_us = (time.perf_counter() - t0) * 1e6 / len(te["label"])
    for target, paper in [(0.964, 0.058), (0.978, 0.039), (0.996, 0.011)]:
        _, se, br = operating_point_at_signal_eff(score_q, te["label"], target)
        emit(f"bdt.table1_quant@sig_eff_{target}", q_us,
             f"sig_eff={se:.4f};bkg_rej={br:.4f};paper_rej={paper}")

    # threshold count / used features (paper: 9 thresholds, 7 inputs)
    t = clf.trees[0]
    emit("bdt.model_complexity", 0.0,
         f"internal_nodes={t.n_internal};used_features={len(t.used_features())};"
         f"paper=9_thresholds_7_inputs")


def power_table(emit):
    for node in ("130nm", "28nm"):
        for row in sweep(node):
            emit(
                f"power.{node}@{int(row['f_mhz'])}MHz", 0.0,
                f"core_mw={row['core_mw']:.1f};io_mw={row['io_mw']:.1f};"
                f"total_mw={row['total_mw']:.1f};readback_ok={int(row['sugoi_readback_ok'])}",
            )
    emit("power.core_ratio@100MHz", 0.0,
         f"ratio={core_power_ratio(100):.2f};paper=2.8")
    emit("power.core_ratio@125MHz", 0.0,
         f"ratio={core_power_ratio(125):.2f};paper=approx_3 (one third)")
    emit("power.area_efficiency_28nm_vs_130nm", 0.0,
         f"ratio={area_efficiency_ratio():.1f};paper=21")
    emit("power.energy_per_inference@200MHz", 0.0,
         f"nj={energy_per_inference_nj('28nm', 200.0, cycles=5):.3f}")


def resources_table(emit):
    for spec in (FABRIC_130NM, FABRIC_28NM):
        t = spec.totals()
        emit(f"resources.fabric_{spec.node}", 0.0,
             f"logic_cells={t['logic_cells']};dsp={t['dsp_slices']};"
             f"lutram_bits={t['lutram_bits']};io_in={spec.input_capacity}")

    data = generate(SmartPixelConfig(n_events=60_000, seed=2024))
    tr, _ = train_test_split(data)
    clf = _paper_bdt(tr)
    t0 = time.perf_counter()
    synth = synth_ensemble(clf.quantized())
    synth_us = (time.perf_counter() - t0) * 1e6
    cfgf = place_and_route(synth.netlist, FABRIC_28NM)
    u = cfgf.utilization()
    emit("resources.bdt_synthesis", synth_us,
         f"luts={synth.report['luts']};depth={synth.report['depth']};"
         f"thresholds={synth.n_thresholds};paper_luts=294;capacity=448;"
         f"utilization={u['lut_utilization']:.2f}")

    nn = lut_cost(MLPSpec())
    emit("resources.nn_baseline_luts", 0.0,
         f"lut_total={nn['lut_total']};paper=>6000;fits_448={nn['lut_total'] <= 448}")

    # TMR (paper §5 future work): 3x replicas + voters
    tmr = triplicate(synth.netlist)
    emit("resources.bdt_tmr", 0.0,
         f"luts={tmr.resource_report()['luts']};fits_448={tmr.n_luts <= 448};"
         f"fits_next_gen_{FABRIC_28NM_XL.n_logic_cells}={tmr.n_luts <= FABRIC_28NM_XL.n_logic_cells}")

    # ensemble scaling: biggest ensemble that still fits 448 LUTs, under
    # both summation structures (tree = default, fast/deep-friendly;
    # ripple = minimal area — the speed/area trade is the point here)
    for n_est, depth in [(1, 5), (2, 4), (3, 3)]:
        c = GradientBoostedClassifier(
            n_estimators=n_est, max_depth=depth, max_leaf_nodes=8
        ).fit(tr["features"], tr["label"])
        parts = []
        for adder in ("tree", "ripple"):
            s = synth_ensemble(c.quantized(), adder=adder)
            parts.append(
                f"luts_{adder}={s.report['luts']};"
                f"depth_{adder}={s.report['depth']};"
                f"fits_28nm_{adder}={str(s.report['luts'] <= 448).lower()}"
            )
        emit(f"resources.ensemble_{n_est}x{depth}", 0.0, ";".join(parts))


def main() -> None:
    print("name,us_per_call,derived")

    def emit(name: str, us: float, derived: str = ""):
        print(f"{name},{us:.2f},{derived}", flush=True)

    for table in (bdt_table, power_table, resources_table):
        table(emit)


if __name__ == "__main__":
    main()
