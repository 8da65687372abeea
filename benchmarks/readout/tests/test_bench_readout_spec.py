"""The loaders: every name in BENCHMARK.json resolves to its file, an unknown
name fails by name, and a new deployment, mix and metric are picked up
from new files alone."""
from __future__ import annotations

import hashlib
import re

import pytest

from readout import spec
from readout.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_in_the_benchmark_resolves():
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmarks/readout/run.py"]
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert spec.load_config(c["name"])["name"] == c["name"]
        assert (spec.CHECKOUT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = spec.resolve_cell(bench, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        names = [m["name"] for m in cell["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            # the end-to-end metric a layer metric moves is one this cell
            # reports
            assert m["moves"] in names, (w["name"], m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert callable(spec.load_reader(m["name"]))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("what,call", [
    ("workload", lambda b: spec.resolve_cell(b, "no_such.cell")),
    ("configuration", lambda b: spec.load_config("no_such_config")),
    ("traffic mix", lambda b: spec.load_traffic("no_such_mix")),
    ("metric", lambda b: spec.load_reader("no_such_metric")),
])
def test_an_unknown_name_fails_by_name(what, call):
    with pytest.raises(spec.SpecError, match=f"unknown {what} .no_such"):
        call(spec.load_benchmark())


def test_new_files_alone_add_a_deployment_mix_and_metric(tmp_path):
    before = {p: hashlib.sha256(p.read_bytes()).hexdigest()
              for p in spec.HERE.rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    root = tiny.make_root(tmp_path)
    (root / "metrics" / "tiny_events.py").write_text(
        "def read(rec):\n    return rec['events_in_window']\n")
    bench = spec.load_benchmark(root / "BENCHMARK.json")
    bench["per_layer"].append({
        "name": "tiny_events", "unit": "events", "better": "higher",
        "source": "host_clock", "layer": "test", "moves": "events_per_s",
        "workloads": ["tiny.closed"]})
    cell = spec.resolve_cell(bench, "tiny.closed", root)
    assert cell["config"]["name"] == "tiny_paper_bdt_28nm"
    assert cell["traffic"]["name"] == "tiny_closed"
    assert [m["name"] for m in cell["per_layer"]] == ["tiny_events"]
    assert spec.load_reader("tiny_events", root)(
        {"events_in_window": 7}) == 7
    after = {p: hashlib.sha256(p.read_bytes()).hexdigest() for p in before}
    assert after == before
