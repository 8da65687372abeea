"""The comparison that decides ``correct`` sees a broken timed path: a whole
CPU run of the harness with the server broken underneath reads false."""
from __future__ import annotations

import pytest

from readout import run
from readout.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"))


def drop_half(server):
    """Half of every drained batch never comes back."""
    orig = server._drain_one

    def drain():
        out = orig()
        return out[::2]
    server._drain_one = drain


def alter_one(server):
    """One answer of every batch altered where the device's scores become
    answers."""
    orig = server._fold_chip

    def fold(results, i, seqs, scores, keep):
        scores = scores.copy()
        scores[0] += 1
        orig(results, i, seqs, scores, keep)
    server._fold_chip = fold


@pytest.mark.parametrize("cell,fault,count", [
    ("tiny.closed", drop_half, "missing"),
    ("tiny.closed", alter_one, "wrong"),
    ("tiny_tmr.closed", alter_one, "wrong"),
])
def test_a_broken_path_reads_not_correct(root, capsys, cell, fault, count):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 9),
                   "--seconds", "1", "--trace", "0"], require_tpu=False,
                  root=root, bench_path=root / "BENCHMARK.json", fault=fault)
    cap = capsys.readouterr()
    res = tiny.last_json(cap.out)
    assert rc == 0 and res["correct"] is False
    assert res["checks"][count]["value"] > 0
    assert res["failed"] > 0
