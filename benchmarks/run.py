# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure + the roofline
report derived from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run bdt power  # subset
    PYTHONPATH=src python -m benchmarks.run fabric --profile=trace_dir
    REPRO_BENCH_FULL=1 ...                             # 500k events (paper scale)
"""
from __future__ import annotations

import os
import sys
import traceback

from benchmarks import (
    bench_bdt, bench_fabric, bench_latency, bench_net, bench_power,
    bench_resources, layout_matrix, roofline,
)
from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "bdt": bench_bdt,              # Table 1 + §5 float numbers
    "power": bench_power,          # Fig. 5 / Fig. 10 + §3 factors
    "resources": bench_resources,  # §2.1/§4.1/§5 resource table
    "latency": bench_latency,      # §5 <25 ns
    "fabric": bench_fabric,        # counter/loopback/classifier throughput
    "net": bench_net,              # wire protocol + loopback replay toll
    "layout_matrix": layout_matrix,  # layout x band x redundancy sweep
    "roofline": roofline,          # framework perf report (§Roofline)
}


def main() -> None:
    names = []
    for arg in sys.argv[1:]:
        # --profile[=DIR]: jax.profiler trace of the fabric suite
        if arg == "--profile" or arg.startswith("--profile="):
            _, _, trace_dir = arg.partition("=")
            os.environ["REPRO_BENCH_PROFILE"] = trace_dir or "bench_trace"
            bench_fabric._PROFILE_DIR = os.environ["REPRO_BENCH_PROFILE"]
            continue
        names.append(arg)
    names = names or list(MODULES)
    enable_compile_cache()
    print("name,us_per_call,derived")

    def emit(name: str, us: float, derived: str = ""):
        print(f"{name},{us:.2f},{derived}", flush=True)

    failed = []
    for n in names:
        try:
            MODULES[n].run(emit)
        except Exception:
            failed.append(n)
            traceback.print_exc()
    if failed:
        raise SystemExit(f"benchmark modules failed: {failed}")


if __name__ == "__main__":
    main()
