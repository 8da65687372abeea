"""Run one cell of the readout benchmark on the accelerator it is started on.

    python3 benchmarks/readout/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from the start of this script to the first
timed event): device check, the deployment's classifiers trained from the
seed, the frame pool, the server inside its fixed envelope, and one
dispatch of every batch shape the cell's traffic uses (compiled, or loaded
from JAX's persistent cache in the checkout). Then the traffic runs for
``--seconds``; nothing compiles inside that window. Once it has closed,
everything still queued is flushed, the device's peak memory is read, the
server is freed, and every answer is checked against the plain reference
(reference.py).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each compared number
beside its limit. The same checks are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

if __package__ in (None, ""):  # run as a script: import the package
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from readout import deploy, drive, reference, spec, work, xplane  # noqa: E402

NO_RESULT = 3
# An XLA op's trace name is its whole HLO instruction; the breakdown keeps
# its head (name, shape, operation).
OP_NAME_CHARS = 120
# Seconds at the end of the window that --trace 1 profiles. The profiler
# stops only after the window has closed and everything is flushed: writing
# the trace out stalls the host for seconds.
TRACE_SECONDS = 4.0


class CompileCounter:
    """Programs lowered (compiled, or loaded from the persistent cache) by
    this process."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


class Collector:
    """The Python garbage collector's passes inside the window, counted
    and timed for standard error. The window starts from a collected heap;
    the collector is otherwise left as the program has it."""

    def __init__(self):
        self.passes = collections.Counter()
        self.seconds = 0.0
        self._t = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.passes[info["generation"]] += 1

    def __enter__(self):
        gc.collect()
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


class Tracer:
    """Profiles the last part of the window (``--trace 1``) and reduces the
    trace. The traced part is the ``bench.window`` annotation; the
    benchmark's host spans are logged in-process and placed on the trace's
    clock by that annotation's start."""

    def __init__(self, seconds: float, spans: drive.Spans):
        self.start_at = seconds - min(TRACE_SECONDS, 0.5 * seconds)
        self.spans = spans
        self.dir = tempfile.mkdtemp(prefix="readout-trace-")
        self.state = "before"
        self.t = [None, None]

    def tick(self, elapsed: float) -> None:
        import jax

        if self.state == "before" and elapsed >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation(xplane.WINDOW)
            self._window.__enter__()
            self.t[0] = time.perf_counter()
            self.spans.recording = True
            self.state = "on"

    def close(self) -> None:
        """The window has closed: end the traced part."""
        if self.state != "on":
            return
        self.spans.recording = False
        self.t[1] = time.perf_counter()
        self._window.__exit__(None, None, None)
        self.state = "closed"

    def stop(self) -> None:
        """Stop the profiler and write the trace out."""
        import jax

        if self.state == "on":
            self.close()
        if self.state == "closed":
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self) -> Optional[Dict]:
        from jax.profiler import ProfileData

        try:
            path = xplane.find_xplane(self.dir)
            if self.state != "done" or path is None:
                return None
            return xplane.reduce_profile(
                ProfileData.from_file(path),
                host_spans=self.spans.logged(), anchor_s=self.t[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def warm(server, cfg: Dict, traffic: Dict, frames, y0) -> int:
    """One dispatch of each batch shape the traffic uses (each module sends
    ``b`` events, then everything is flushed), then, where the deployment
    scrubs, one full scrub cycle, which reads back every replica frame once.
    Returns the shapes warmed."""
    C = y0.shape[0]
    for b in traffic["warm_batches"]:
        for m in range(C):
            server.submit_frames(m, frames[m, :b], y0[m, :b])
        server.flush()
    if cfg["server"].get("scrub_interval"):
        server.scrub_cycle()
    return len(traffic["warm_batches"])


def stage_delta(before: Dict, after: Dict) -> Dict:
    out = {}
    for k, v in after.items():
        b = before.get(k, {"seconds": 0.0, "calls": 0})
        out[k] = {"seconds": v["seconds"] - b["seconds"],
                  "calls": v["calls"] - b["calls"]}
    return out


def device_info(devices, n_used: int) -> Dict:
    used = devices[:n_used]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(args, cell: Dict, require_tpu: bool = True,
             fault: Optional[Callable] = None) -> Optional[Dict]:
    """Set up, drive, check. ``fault`` (tests only) may break the server
    under test before the window."""
    import jax

    devices = jax.devices()
    chips = cell["workload"]["chips"]
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        say(f"readout benchmark: cell {args.workload!r} needs {chips} TPU "
            f"chip(s); JAX found {len(devices)} {devices[0].platform!r} "
            "device(s). No result.")
        return None
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        say(f"readout benchmark: the program under test is missing ({e}). "
            "No result.")
        return None
    if require_tpu:
        say(f"compile cache: {enable_compile_cache()}")
    cfg, traffic = cell["config"], cell["traffic"]
    compiles = CompileCounter()

    modules = deploy.build_modules(cfg, args.seed)
    frames, y0 = deploy.frame_pool(cfg, args.seed)
    server = deploy.make_server(cfg, modules)
    if len(devices) > chips:
        from jax.sharding import Mesh

        server.rebind_mesh(Mesh(np.asarray(devices[:chips]), ("chips",)))
    if fault is not None:
        fault(server)
    n_warm = warm(server, cfg, traffic, frames, y0)
    server.reset_latency_metrics()
    stages0 = server.report()["stages"]

    spans = drive.Spans()
    tracer = Tracer(args.seconds, spans) if args.trace else None
    compiles_before = compiles.n
    t_setup = time.perf_counter() - T_PROCESS
    kind = traffic["kind"]
    tick = tracer.tick if tracer else None
    with Collector() as collector:
        if kind == "closed_loop":
            run = drive.closed_loop(server, traffic, frames, y0,
                                    args.seconds, spans, tick)
        elif kind == "open_loop":
            run = drive.open_loop(server, traffic, frames, y0, args.seconds,
                                  spans, deploy.substream(
                                      args.seed, deploy.ARRIVALS), tick)
        else:
            raise spec.SpecError(f"traffic {traffic['name']!r} has unknown "
                                 f"kind {kind!r}")
    if tracer:
        tracer.close()
    rep = server.report()
    compiles_in_window = compiles.n - compiles_before
    window_spans = {k: {"seconds": spans.seconds[k], "calls": spans.calls[k]}
                    for k in spans.seconds}
    drive.settle(server, spans, run)
    if tracer:
        tracer.stop()
    dev = device_info(devices, chips)
    del server
    gc.collect()

    ev, ans = run.events()
    in_window = (ans["t"] >= run.t0) & (ans["t"] < run.t_end)
    # per event: when its answer drained (inf if it never did)
    drained = np.full(len(ev["due"]), np.inf)
    ok = (ans["seq"] >= 0) & (ans["seq"] < len(drained))
    drained[ans["seq"][ok]] = ans["t"][ok]
    due_in_window = ev["due"] < run.t_end
    latency = np.where(np.isfinite(drained), drained - ev["due"],
                       time.perf_counter() - ev["due"])[due_in_window]
    lateness = np.asarray(run.sub_sent) - np.asarray(run.sub_due)
    trace = tracer.reduce() if tracer else None
    traced_events = 0
    if tracer and tracer.t[1] is not None:
        traced_events = int(((ans["t"] >= tracer.t[0])
                             & (ans["t"] < tracer.t[1])).sum())

    models = reference.build_models(cfg, modules)
    want = reference.expected(models, frames, y0)
    counts = reference.compare(want, ev["module"], ev["pool"], ans["seq"],
                               ans["chip"], ans["score"], ans["keep"])
    record = {
        "workload": cell["workload"], "config": cfg, "traffic": traffic,
        "setup_s": t_setup,
        "window_s": run.t_end - run.t0,
        "events_in_window": int(in_window.sum()),
        "events_submitted": int(due_in_window.sum()),
        "latency_s": latency,
        "spans": window_spans,
        "stages": stage_delta(stages0, rep["stages"]),
        "report": rep,
        "trace": trace,
        "traced_events": traced_events,
        "device": dev,
        "peaks": work.load_peaks(dev["kind"]) if require_tpu else None,
    }
    return {"record": record, "counts": counts, "n_warm": n_warm,
            "compiles_in_window": compiles_in_window, "lateness": lateness,
            "collector": collector,
            "backlog": getattr(run, "backlog_at_close", None)}


def metrics_of(entries: List[Dict], record: Dict, root) -> Dict:
    out = {}
    for m in entries:
        v = spec.load_reader(m["name"], root)(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(trace: Optional[Dict]) -> Optional[Dict]:
    if not trace:
        return None
    ops = [[name[:OP_NAME_CHARS], t] for name, t in xplane.top(trace["ops"])]
    return {"device_ops": ops, "idle_gaps": xplane.top(trace["idle_by_span"])}


def main(argv=None, require_tpu: bool = True, root=spec.HERE,
         bench_path=spec.BENCHMARK_JSON, fault=None) -> int:
    args = parse(argv)
    cell = spec.resolve_cell(spec.load_benchmark(bench_path), args.workload,
                             root)
    src = str(spec.CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    out = run_cell(args, cell, require_tpu, fault)
    if out is None:
        return NO_RESULT
    rec, counts = out["record"], out["counts"]
    late = out["lateness"]
    say(f"set-up: {rec['setup_s']!r} s, {out['n_warm']} batch shapes warmed; "
        f"programs lowered inside the window: {out['compiles_in_window']}")
    gc_ = out["collector"]
    say(f"collector passes in the window by generation: "
        f"{dict(sorted(gc_.passes.items()))}, {gc_.seconds!r} s in all")
    launches = rec["stages"].get("launch_fused", {"calls": 0})["calls"]
    say(f"dispatches in the window: {launches}, "
        f"{rec['events_in_window'] / max(launches, 1)!r} events each")
    say(f"generator lateness: median {float(np.median(late))!r} s, p99 "
        f"{float(np.quantile(late, 0.99))!r} s, max {float(late.max())!r} s "
        f"over {len(late)} submissions")
    if out["backlog"] is not None:
        lat = 1e3 * rec["latency_s"]
        say(f"latency: p50 {float(np.median(lat))!r} ms, p99 "
            f"{float(np.quantile(lat, 0.99))!r} ms, max {float(lat.max())!r} "
            f"ms; backlog at the close {out['backlog']} events")
    say(f"answers: {counts['compared']} compared, {counts['judged']} judged, "
        f"{counts['unjudged']} within {reference.AMBIGUOUS_ELECTRONS} e- of "
        f"a flip (not judged); events in window {rec['events_in_window']}")
    section = "per_layer" if args.trace else "end_to_end"
    metrics = metrics_of(cell[section], rec, root)
    correct = reference.verdict(counts)
    result = {
        "correct": correct,
        "attempted": rec["events_submitted"],
        "failed": counts["missing"] + counts["wrong"],
        "metrics": metrics,
        "device": dict(rec["device"]),
    }
    if args.trace:
        tr = rec["trace"] or {}
        result["device"]["busy_s"] = tr.get("busy_s", 0.0)
        result["device"]["window_s"] = tr.get("window_s", 0.0)
        bd = breakdown(rec["trace"])
        if bd:
            result["breakdown"] = bd
    result["checks"] = {k: {"value": counts[k], "limit": lim}
                        for k, lim in reference.LIMITS.items()}
    for k, lim in reference.LIMITS.items():
        say(f"check {k}: {counts[k]} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
