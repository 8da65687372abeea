"""A copy of the benchmark's data files in a temporary root, plus tiny
deployments, traffic mixes and cells that a CPU test run can hold.

``tiny_ens4_xl_tmr`` is no deployment of the benchmark: a 4-tree ensemble on
the larger grid, served as three voted replicas with a scrub, it keeps the
harness's ensemble, TMR and scrub paths under test."""
from __future__ import annotations

import json
import pathlib
import shutil

from readout import spec


ENS4_XL_TMR = {
    "modules": 4,
    "sensor": {"frame": [8, 13, 21], "y0": True,
               "threshold_electrons": 800.0},
    "featurizer_precision": "float32",
    "classifier": {
        "n_estimators": 4, "max_depth": 3, "max_leaf_nodes": 6,
        "min_samples_leaf": 300, "learning_rate": 0.1, "adder": "tree",
        "fixed": {"width": 16, "int_bits": 8, "rounding": "trn",
                  "overflow": "wrap"}},
    "training": {"n_events": 30000, "test_fraction": 0.3, "split_seed": 7,
                 "target_signal_efficiency": 0.97},
    "fabric": "efpga_28nm_xl",
    "envelope": {"n_levels": 24, "max_level_size": 256, "n_inputs": 254,
                 "n_outputs": 16},
    "server": {"max_batch": 8192, "max_latency_s": 0.005,
               "layout": "bitsliced", "redundancy": "tmr", "sparse": False,
               "scrub_interval": 4, "scrub_mode": "steered",
               "pipeline_depth": 2, "batch_tile": 128},
    "pool_events_per_module": 8192,
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "readout"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(spec.HERE / sub, root / sub)
    shutil.copy(spec.HERE / "peaks.json", root / "peaks.json")
    bench = spec.load_benchmark()
    for base in ("paper_bdt_28nm", "ens4_xl_tmr"):
        cfg = (spec.load_config(base) if base == "paper_bdt_28nm"
               else json.loads(json.dumps(ENS4_XL_TMR)))
        cfg.update(name="tiny_" + base, modules=2, pool_events_per_module=256)
        cfg["training"]["n_events"] = 6000
        cfg["classifier"]["min_samples_leaf"] = 100
        write(root / "configs" / f"tiny_{base}.json", cfg)
        bench["configs"].append({"name": "tiny_" + base, "source": "test",
                                 "file": "test", "reduced": [], "why": "test"})
    write(root / "traffic" / "tiny_closed.json", {
        "name": "tiny_closed", "kind": "closed_loop", "block_events": 64,
        "blocks_outstanding": 2, "warm_batches": [64, 128]})
    write(root / "traffic" / "tiny_open.json", {
        "name": "tiny_open", "kind": "open_loop", "bunch_events": 8,
        "rate_events_per_s": 800, "warm_batches": [8, 16, 32, 64, 128]})
    for cell, cfg, mix in (("tiny.closed", "tiny_paper_bdt_28nm", "tiny_closed"),
                           ("tiny.open", "tiny_paper_bdt_28nm", "tiny_open"),
                           ("tiny_tmr.closed", "tiny_ens4_xl_tmr",
                            "tiny_closed")):
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            kind = "closed" if "events_per_s" == m["name"] else "open"
            m["workloads"].append(f"tiny.{kind}")
    write(root / "BENCHMARK.json", bench)
    return root


def write(path: pathlib.Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def last_json(out: str):
    return json.loads(out.strip().splitlines()[-1])
