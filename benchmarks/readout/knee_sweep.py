"""Find the knee of a deployment under the open-loop bunch traffic: the
highest offered rate whose backlog when the window closes (events queued in
the server plus events due but not yet sent) is no larger than at its start
plus one ``max_batch``.

    python3 benchmarks/readout/knee_sweep.py --config <name> \
        --traffic <open-loop mix> --seed <n> --seconds 10 \
        --rates 20000 40000 60000

One process, one server, warmed once; each rate runs its own window and is
flushed before the next. Prints one JSON line per rate: backlog at start
and close, generator lateness, p50, p99 and max latency, and the
collector's passes. It runs on the device it is started on, and the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

if __package__ in (None, ""):
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from readout import deploy, drive, run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.CHECKOUT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg, traffic = spec.load_config(args.config), spec.load_traffic(
        args.traffic)
    modules = deploy.build_modules(cfg, args.seed)
    frames, y0 = deploy.frame_pool(cfg, args.seed)
    server = deploy.make_server(cfg, modules)
    run.warm(server, cfg, traffic, frames, y0)
    max_batch = cfg["server"]["max_batch"]
    for i, rate in enumerate(args.rates):
        start = server.queue_depth
        t = dict(traffic, rate_events_per_s=rate)
        with run.Collector() as gc_:
            r = drive.open_loop(server, t, frames, y0, args.seconds,
                                drive.Spans(), deploy.substream(
                                    args.seed, deploy.ARRIVALS, i))
        drive.settle(server, drive.Spans(), r)
        ev, ans = r.events()
        drained = np.full(len(ev["due"]), np.nan)
        drained[ans["seq"]] = ans["t"]
        lat = drained - ev["due"]
        late = np.asarray(r.sub_sent) - np.asarray(r.sub_due)
        print(json.dumps({
            "config": args.config, "rate_events_per_s": rate,
            "seconds": args.seconds, "backlog_start": start,
            "backlog_close": r.backlog_at_close,
            "sustained": r.backlog_at_close <= start + max_batch,
            "lateness_p99_s": float(np.quantile(late, 0.99)),
            "lateness_max_s": float(late.max()),
            "latency_p50_ms": 1e3 * float(np.nanmedian(lat)),
            "latency_p99_ms": 1e3 * float(np.nanquantile(lat, 0.99)),
            "latency_max_ms": 1e3 * float(np.nanmax(lat)),
            "gc_passes": dict(gc_.passes), "gc_s": gc_.seconds,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
