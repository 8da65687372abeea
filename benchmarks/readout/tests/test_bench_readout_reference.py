"""The plain reference against the program at a small size on the CPU, and
the work counts behind the rooflines."""
from __future__ import annotations

import numpy as np
import pytest

from readout import deploy, reference, spec, work
from readout.tests import tiny

CONFIGS = ("paper_bdt_28nm", "ens4_xl_tmr")


@pytest.fixture(scope="module")
def deployments(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("ref"))
    out = {}
    for base in CONFIGS:
        cfg = spec.load_config("tiny_" + base, root)
        mods = deploy.build_modules(cfg, 2**31 + 17)
        out[base] = (cfg, mods, reference.build_models(cfg, mods))
    return out


@pytest.mark.parametrize("fixed", [
    dict(width=28, int_bits=19, rounding="trn", overflow="wrap"),
    dict(width=16, int_bits=8, rounding="trn", overflow="wrap"),
    dict(width=10, int_bits=4, rounding="rnd", overflow="sat"),
])
def test_quantize_matches_ap_fixed(fixed):
    from repro.core.quantize import FixedSpec, quantize_raw

    x = np.random.default_rng(0).normal(0, 200, 5000)
    x[:4] = [127.99, 128.0, -128.0, 0.0]
    np.testing.assert_array_equal(reference.quantize(x, fixed),
                                  quantize_raw(x, FixedSpec(**fixed)))


@pytest.mark.parametrize("base", CONFIGS)
def test_model_and_cut_match_the_golden_bdt(deployments, base):
    cfg, mods, models = deployments[base]
    for mod, model in zip(mods, models):
        X = mod.train_features
        want = mod.chip.golden.decision_function_raw(
            mod.chip.golden.quantize_features(X))
        got, _ = model.score(np.asarray(X, np.float64))
        np.testing.assert_array_equal(got, want)
        assert model.cut == mod.chip.score_threshold_raw


@pytest.mark.parametrize("base", CONFIGS)
def test_kernel_server_agrees_with_the_reference(deployments, base):
    cfg, mods, models = deployments[base]
    frames, y0 = deploy.frame_pool(cfg, 2**31 + 17)
    server = deploy.make_server(cfg, mods)
    C, P = y0.shape
    sub_m, sub_p, got = [], [], []
    for m in range(C):
        seqs = server.submit_frames(m, frames[m], y0[m])
        assert seqs == list(range(m * P, (m + 1) * P))
        sub_m += [m] * P
        sub_p += list(range(P))
    got = server.flush()
    want = reference.expected(models, frames, y0)
    counts = reference.compare(
        want, np.asarray(sub_m), np.asarray(sub_p),
        [e.seq for e in got], [e.chip for e in got],
        [e.score_raw for e in got], [e.keep for e in got])
    assert counts["compared"] == C * P
    assert counts["wrong"] == counts["missing"] == counts["extra"] == 0
    assert counts["judged"] >= 0.99 * C * P
    assert reference.verdict(counts)
    # a planted wrong answer and a lost one are seen
    bad = [e.score_raw + (i == 5) for i, e in enumerate(got)]
    counts = reference.compare(
        want, np.asarray(sub_m), np.asarray(sub_p),
        [e.seq for e in got][1:], [e.chip for e in got][1:], bad[1:],
        [e.keep for e in got][1:])
    assert counts["missing"] == 1 and counts["wrong"] <= 1
    assert not reference.verdict(counts)


@pytest.mark.parametrize("base", CONFIGS)
def test_answers_hold_inside_the_flip_margin(deployments, base):
    cfg, mods, models = deployments[base]
    frames, y0 = deploy.frame_pool(cfg, 7, 256)
    model = models[0]
    prof = reference.profile_f64(frames[0])
    te = cfg["sensor"]["threshold_electrons"]
    score, margin = model.score(reference.features(prof, y0[0], te), prof)
    rng = np.random.default_rng(1)
    finite = np.where(np.isfinite(margin), margin, 1e6)[:, None]
    nudged = prof + 0.99 * finite * rng.uniform(-1, 1, prof.shape)
    again, _ = model.score(reference.features(nudged, y0[0], te), nudged)
    np.testing.assert_array_equal(again, score)


def test_bf16_control_fails_where_the_reference_holds(deployments):
    cfg, mods, models = deployments["paper_bdt_28nm"]
    frames, y0 = deploy.frame_pool(cfg, 11, 2048)
    want = reference.expected(models, frames, y0)
    same = reference.expected(models, frames, y0)
    ctl = reference.expected(models, frames, y0,
                             profile=reference.profile_bf16)
    assert reference.control_wrong(want, same) == 0
    assert reference.control_wrong(want, ctl) > 0


def test_work_counts():
    cfg = spec.load_config("paper_bdt_28nm")
    assert work.frame_bytes(cfg) == 8740
    ops, nbytes = work.yprofile_work(cfg, 10)
    assert nbytes == 10 * (8740 + 14 * 4)
    assert ops == 10 * (8 * 13 * 21 + 3 * 13)
    peaks = work.load_peaks("TPU v5 lite")
    t, bound = work.roofline_seconds(ops, nbytes, peaks)
    assert bound == "hbm" and t == pytest.approx(nbytes / 819e9)
    assert work.roofline_seconds(1e15, 1.0, peaks)[1] == "compute"
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        work.load_peaks("cpu")
