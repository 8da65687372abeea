"""A whole run of the harness on the CPU at a tiny size: the chip check
skipped, everything else as on the chip."""
from __future__ import annotations

import pytest

from readout import run
from readout.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tiny.make_root(tmp_path_factory.mktemp("run"))
    (r / "metrics" / "tiny_events.py").write_text(
        "def read(rec):\n    return rec['events_in_window']\n")
    bench = tiny.json.loads((r / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny_events", "unit": "events", "better": "higher",
        "source": "host_clock", "layer": "test", "moves": "events_per_s",
        "workloads": ["tiny.closed"]})
    tiny.write(r / "BENCHMARK.json", bench)
    return r


def go(root, cell, trace, capsys, **kw):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 5),
                   "--seconds", "1.5", "--trace", str(trace)],
                  require_tpu=False, root=root,
                  bench_path=root / "BENCHMARK.json", **kw)
    return rc, capsys.readouterr()


def test_closed_loop_cell_reports_its_metrics(root, capsys):
    rc, cap = go(root, "tiny.closed", 0, capsys)
    res = tiny.last_json(cap.out)
    assert rc == 0 and res["correct"] is True, cap.err[-2000:]
    assert set(res["metrics"]) == {"events_per_s", "setup_s"}
    assert res["metrics"]["events_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong"] == {"value": 0, "limit": 0}
    assert res["device"]["platform"] == "cpu"
    assert cap.err.strip().splitlines()[-1].startswith("check ")


def test_traced_run_reports_per_layer_metrics_and_a_new_reader(root, capsys):
    rc, cap = go(root, "tiny.closed", 1, capsys)
    res = tiny.last_json(cap.out)
    assert rc == 0 and res["correct"] is True, cap.err[-2000:]
    assert res["metrics"]["tiny_events"]["value"] > 0
    assert "busy_s" in res["device"] and res["device"]["window_s"] > 0


def test_open_loop_cell_reports_latency(root, capsys):
    rc, cap = go(root, "tiny.open", 0, capsys)
    res = tiny.last_json(cap.out)
    assert rc == 0 and res["correct"] is True, cap.err[-2000:]
    assert set(res["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert res["metrics"]["latency_p50_ms"]["value"] > 0
    assert "generator lateness" in cap.err


def test_no_tpu_no_result(root, capsys):
    rc = run.main(["--workload", "tiny.closed", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], require_tpu=True,
                  root=root, bench_path=root / "BENCHMARK.json")
    cap = capsys.readouterr()
    assert rc == run.NO_RESULT and cap.out == ""
    assert "No result" in cap.err
