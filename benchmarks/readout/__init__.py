"""On-chip benchmark of the readout server's frames -> trigger path.

Run one cell of ``BENCHMARK.json`` (at the repository root) with

    python3 benchmarks/readout/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one deployment, one traffic mix or one metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``.
"""
