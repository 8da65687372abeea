"""The four-chip cell: its two readers (shard_fill_pct, place_us_per_event),
its deployment file against the 1-chip one, and one whole harness run of a
tiny 4-chip cell on four virtual CPU devices, in a subprocess (this process
keeps its one device)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from readout import spec
from readout.tests import tiny

CELL = "tiny4.closed"


def shards_record(events, rows, dispatches=3):
    return {"report": {"shards": {
        "devices": len(events), "modules_per_device": 4,
        "dispatches": dispatches, "rows_per_device": rows,
        "events_per_device": events,
        "bytes_per_device": [r * 8741 for r in rows]}}}


def place_record(seconds, calls, events):
    return {"events_in_window": events,
            "stages": {"launch_fused": {"seconds": 2 * seconds,
                                        "calls": calls},
                       "place_frames": {"seconds": seconds,
                                        "calls": calls}}}


def read(name, rec):
    return spec.load_reader(name)(rec)


@pytest.mark.parametrize("events,rows,want", [
    ([8192] * 4, [8192] * 4, 100.0),
    ([8192, 8192, 4096, 0], [8192] * 4, 62.5),
    ([6000], [8000], 75.0),
])
def test_shard_fill_reads_the_share_of_placed_rows_with_an_event(
        events, rows, want):
    assert read("shard_fill_pct", shards_record(events, rows)) == \
        pytest.approx(want)


def test_shard_fill_reads_nothing_where_the_program_has_no_counter():
    rec = shards_record([8192], [8192])
    del rec["report"]["shards"]
    assert read("shard_fill_pct", rec) is None


def test_shard_fill_reads_nothing_without_a_dispatch():
    assert read("shard_fill_pct", shards_record([0] * 4, [0] * 4, 0)) is None


def test_place_reads_its_span_per_event():
    assert read("place_us_per_event", place_record(0.25, 500, 1_000_000)) \
        == pytest.approx(0.25)


def test_place_reads_nothing_where_the_program_has_no_span():
    rec = place_record(0.25, 500, 1_000_000)
    del rec["stages"]["place_frames"]
    assert read("place_us_per_event", rec) is None


@pytest.mark.parametrize("calls,events", [(0, 1_000_000), (500, 0)])
def test_place_reads_nothing_without_a_dispatch(calls, events):
    assert read("place_us_per_event", place_record(0.0, calls, events)) \
        is None


def test_16_module_deployment_is_the_paper_one_at_four_chips():
    one = spec.load_config("paper_bdt_28nm")
    four = spec.load_config("paper_bdt_28nm_16mod")
    differ = {k for k in one.keys() | four.keys() if one.get(k) != four.get(k)}
    assert differ == {"name", "source", "deployment", "modules", "server",
                      "assumed"}
    assert (one["modules"], four["modules"]) == (4, 16)
    assert four["server"] == dict(one["server"],
                                  max_batch=one["server"]["max_batch"] * 4)
    assert four["reduced"] == []
    # The same paper, named down to the part and the scale of this deployment.
    assert four["source"].startswith(one["source"] + " ")
    assert "16 modules" in four["source"]


def test_the_benchmark_runs_the_16_module_cell_on_four_chips():
    bench = spec.load_benchmark()
    cell = spec.resolve_cell(bench, "paper_bdt_28nm_16mod.saturate")
    assert cell["workload"]["chips"] == 4
    assert cell["traffic"]["name"] == "saturate"
    names = {m["name"] for m in cell["per_layer"]}
    assert {"shard_fill_pct", "place_us_per_event"} <= names
    assert [m["name"] for m in cell["end_to_end"]] == [
        "events_per_s", "setup_s"]


_RUN = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, cell, bench_dir = sys.argv[1], sys.argv[2], sys.argv[3]
sys.path.insert(0, bench_dir)
import pathlib
from readout import run
for trace in ("0", "1"):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 7),
                   "--seconds", "1.5", "--trace", trace],
                  require_tpu=False, root=pathlib.Path(root),
                  bench_path=pathlib.Path(root) / "BENCHMARK.json")
    assert rc == 0, rc
"""


@pytest.fixture(scope="module")
def four_chip_runs(tmp_path_factory):
    """A tiny 4-chip cell of 8 modules (2 per device) under a tiny closed
    loop, run untraced and traced by the harness in one process."""
    root = tiny.make_root(tmp_path_factory.mktemp("four"))
    cfg = spec.load_config("paper_bdt_28nm_16mod")
    cfg.update(name="tiny_16mod", modules=8, pool_events_per_module=256)
    cfg["training"]["n_events"] = 6000
    cfg["classifier"]["min_samples_leaf"] = 100
    cfg["server"]["max_batch"] = 8 * 64
    tiny.write(root / "configs" / "tiny_16mod.json", cfg)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_16mod", "source": "test",
                             "file": "test", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_16mod",
                               "traffic": "tiny_closed", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "paper_bdt_28nm_16mod.saturate" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    tiny.write(root / "BENCHMARK.json", bench)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", _RUN, str(root), CELL,
         str(spec.HERE.parent)],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    assert len(lines) == 2, r.stdout[-2000:]
    return lines


def test_four_chip_cell_is_correct_on_four_devices(four_chip_runs):
    for res in four_chip_runs:
        assert res["correct"] is True, res["checks"]
        assert all(c["value"] == 0 for c in res["checks"].values())
        assert res["device"]["count"] == 4
    assert set(four_chip_runs[0]["metrics"]) == {"events_per_s", "setup_s"}


def test_traced_four_chip_run_reports_both_new_readers(four_chip_runs):
    metrics = four_chip_runs[1]["metrics"]
    assert 0 < metrics["shard_fill_pct"]["value"] <= 100
    assert metrics["place_us_per_event"]["value"] > 0
