"""Docs-coverage gate (fast tier): the operator docs cannot silently
drift from the code. Every ``report()`` top-level key (server AND
fleet, plus the per-tenant ledger) must appear in
docs/architecture.md — keys are derived LIVE from the running code, so
adding a counter without documenting it fails CI — and every cell and
metric that BENCHMARK.json declares must appear in docs/benchmarks.md.
"""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
DOCS = ROOT / "docs"


def test_architecture_documents_every_report_key():
    from repro.launch.fleet import TenantFleet
    from repro.launch.readout_server import ReadoutServer, ServerConfig
    from tests.test_fleet import _get_farm

    chips, X = _get_farm()
    cfg = ServerConfig(max_batch=64, max_latency_s=1e9, backend="host")
    srv = ReadoutServer([chips[0]], cfg)
    srv.submit_batch(0, X[:4])
    srv.flush()
    fleet = TenantFleet(cfg)
    fleet.admit("t", chips[0])
    fleet.submit_batch("t", X[:2])
    fleet.flush()
    frep = fleet.report()
    keys = (list(srv.report()) + list(frep)
            + list(frep["tenants"]["t"]) + list(frep["buckets"][0]))
    text = (DOCS / "architecture.md").read_text()
    missing = sorted({k for k in keys if f"`{k}`" not in text})
    assert not missing, (
        f"report() keys missing from docs/architecture.md: {missing}")


def test_benchmarks_doc_names_every_cell_and_metric():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = ([w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"]]
             + [m["name"] for m in bench["per_layer"]])
    text = (DOCS / "benchmarks.md").read_text()
    missing = [n for n in names if f"`{n}`" not in text]
    assert not missing, (
        f"BENCHMARK.json names missing from docs/benchmarks.md: {missing}")
