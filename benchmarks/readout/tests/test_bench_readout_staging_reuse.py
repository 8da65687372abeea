"""The staging_reuse_pct reader: it reads the share of the window's
dispatches that staged into a reused arena from report()["staging"], and
nothing from a record of a program without that counter or from a window
with no dispatch."""
from __future__ import annotations

import pytest

from readout import spec


def record(reused, fresh):
    return {
        "events_submitted": 2000,
        "report": {
            "stages": {"stack_frames": {"seconds": 0.03, "calls": 4}},
            "staging": {"reused": reused, "fresh": fresh,
                        "arenas": min(reused + fresh, 3),
                        "resident_bytes": 3 * 71_565_312},
        },
    }


def read(rec):
    return spec.load_reader("staging_reuse_pct")(rec)


@pytest.mark.parametrize("reused,fresh,want",
                         [(3, 1, 75.0), (25, 0, 100.0), (0, 4, 0.0)])
def test_staging_reuse_reads_its_share(reused, fresh, want):
    assert read(record(reused, fresh)) == pytest.approx(want)


def test_staging_reuse_reads_nothing_where_the_program_has_no_counter():
    rec = record(3, 1)
    del rec["report"]["staging"]
    assert read(rec) is None


def test_staging_reuse_reads_nothing_without_a_dispatch():
    assert read(record(0, 0)) is None
