"""The trace-to-metrics reduction, on a synthetic trace and on one recorded
on the CPU."""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import pytest

from readout import spec, xplane


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: List[Tuple[str, object]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Profile:
    planes: List[Plane]


STEP = "jit__score_frames_impl"
KERNEL = ('%_score_frames_impl.1 = f32[4,128,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')


def synthetic() -> Profile:
    host = Plane("/host:CPU", [Line("python", [
        Ev(xplane.WINDOW, 1000, 1000),
        Ev("bench.poll", 1000, 300),
        Ev("bench.submit_frames", 1300, 300),
        Ev("bench.poll", 1600, 400),
        Ev("unrelated", 1000, 1000),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            Ev(KERNEL, 1100, 100, [("hlo_module", STEP)]),
            Ev("eval_fusion", 1150, 100, [("hlo_module", STEP)]),
            Ev("eval_fusion", 1700, 100, [("hlo_module", STEP)]),
            Ev("outside_window", 2500, 100),
        ]),
        Line("XLA Modules", [Ev(STEP + "(1)", 1100, 150),
                             Ev(STEP + "(1)", 1700, 100)]),
        Line("Steps", [Ev("0", 1000, 1000)]),
    ])
    return Profile([host, dev])


def test_union_and_gaps():
    assert xplane.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert xplane.gaps([(1, 4), (5, 7)], 0, 10) == [(0, 1), (4, 5), (7, 10)]


def test_busy_kernel_time_and_idle_by_span():
    r = xplane.reduce_profile(synthetic())
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops [1100,1200] and [1150,1250] overlap: busy 150 + 100 ns
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["n_devices"] == 1
    assert r["ops"][KERNEL] == pytest.approx(100e-9)
    assert r["ops"]["eval_fusion"] == pytest.approx(200e-9)
    assert "outside_window" not in r["ops"]
    assert xplane.op_seconds(r, "tpu_custom_call") == pytest.approx(100e-9)
    assert r["modules"][STEP + "(1)"] == pytest.approx(250e-9)
    assert r["module_runs"][STEP + "(1)"] == 2
    # idle [1000,1100] under poll; [1250,1700] mostly under submit_frames
    # (300 of its 450 ns); [1800,2000] under poll
    assert r["idle_by_span"]["bench.poll"] == pytest.approx(300e-9)
    assert r["idle_by_span"]["bench.submit_frames"] == pytest.approx(450e-9)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert xplane.top(r["ops"], 1) == [["eval_fusion", pytest.approx(2e-7)]]


def test_host_spans_logged_in_process_are_placed_by_the_window():
    prof = synthetic()
    prof.planes[0].lines[0].events = [Ev(xplane.WINDOW, 1000, 1000)]
    # the same spans as the annotations above, on a clock that read 5.0 s
    # when the window opened
    logged = [(5.0, 5.0 + 300e-9, "bench.poll"),
              (5.0 + 300e-9, 5.0 + 600e-9, "bench.submit_frames"),
              (5.0 + 600e-9, 5.0 + 1000e-9, "bench.poll")]
    r = xplane.reduce_profile(prof, host_spans=logged, anchor_s=5.0)
    assert r["n_spans"] == 3
    assert r["idle_by_span"]["bench.poll"] == pytest.approx(300e-9)
    assert r["idle_by_span"]["bench.submit_frames"] == pytest.approx(450e-9)


def test_device_readers_on_the_reduction():
    r = xplane.reduce_profile(synthetic())
    cfg = spec.load_config("paper_bdt_28nm")
    rec = {"trace": r, "traced_events": 100, "config": cfg,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda name: spec.load_reader(name)(rec)
    assert read("device_idle_pct.sat") == pytest.approx(75.0)
    assert read("fused_step_ns_per_event") == pytest.approx(2.5)
    assert read("eval_ns_per_event") == pytest.approx(1.5)
    # 100 events x 8,796 B at 819 GB/s = 1.074 us against 100 ns measured:
    # a share above 100 % is the reading that says the work is over-counted
    assert read("yprofile_roofline") == pytest.approx(
        100 * 100 * 8796 / 819e9 / 100e-9)
    # the same events' frame bytes over the fused step's 250 ns
    assert read("serve_mfu_pct") == pytest.approx(
        100 * 100 * 8740 / 819e9 / 250e-9)


def test_readers_return_nothing_without_a_trace():
    rec = {"trace": None, "traced_events": 0, "config": {}, "peaks": None}
    for name in ("device_idle_pct.sat", "fused_step_ns_per_event",
                 "eval_ns_per_event", "yprofile_roofline", "serve_mfu_pct"):
        assert spec.load_reader(name)(rec) is None


def test_reduction_of_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.poll"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = xplane.reduce_profile(ProfileData.from_file(
        xplane.find_xplane(str(tmp_path))))
    assert r["window_s"] > 0 and r["n_spans"] == 3
