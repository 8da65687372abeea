"""The readers of the server's own spans and phases: each reads its value
from a record that holds it, and nothing from a record of a program
without them."""
from __future__ import annotations

import pytest

from readout import spec


def phase(p50_us, count=5):
    return {"count": count, "p50_us": p50_us, "p99_us": 2 * p50_us,
            "max_us": 3 * p50_us, "mean_us": p50_us}


def record():
    """A window of 2,000 submitted events whose server spans and phases
    are all there."""
    return {
        "events_submitted": 2000,
        "stages": {"admit": {"seconds": 0.004, "calls": 10},
                   "stack_frames": {"seconds": 0.02, "calls": 3}},
        "report": {
            "stages": {
                "admit": {"seconds": 0.01, "calls": 30, "max_s": 0.0002},
                "stack_frames": {"seconds": 0.03, "calls": 4,
                                 "max_s": 0.125},
            },
            "latency": {"phases": {
                "batches": 5, "dropped": 0, "compiled_batches": 0,
                "staging": phase(3100.0), "collect_wait": phase(2500.0),
                "drain": phase(400.0), "handoff": phase(1200.0)}},
        },
    }


def parent_record():
    """The same window from a program with no ``admit`` span, no phases
    and no longest calls."""
    rec = record()
    del rec["stages"]["admit"]
    rec["report"] = {
        "stages": {"stack_frames": {"seconds": 0.03, "calls": 4}},
        "latency": {"last_batch_trace_us": {}},
    }
    return rec


READINGS = [
    ("admit_us_per_event", 1e6 * 0.004 / 2000),
    ("staging_ms_p50.steady", 3.1),
    ("collect_wait_ms_p50.steady", 2.5),
    ("handoff_ms_p50.steady", 1.2),
    ("stage_max_ms.steady", 125.0),
]


@pytest.mark.parametrize("name,want", READINGS)
def test_reader_reads_its_value(name, want):
    assert spec.load_reader(name)(record()) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in READINGS])
def test_reader_reads_nothing_where_the_program_has_no_such_span(name):
    assert spec.load_reader(name)(parent_record()) is None


@pytest.mark.parametrize("name", ["admit_us_per_event",
                                  "staging_ms_p50.steady"])
def test_reader_reads_nothing_from_an_empty_window(name):
    rec = record()
    rec["events_submitted"] = 0
    rec["report"]["latency"]["phases"]["staging"]["count"] = 0
    assert spec.load_reader(name)(rec) is None
