"""Fused on-device frontend: frames -> features -> bits -> score.

Covers the tentpole guarantees of kernels/frontend.py and the server's
frames ingestion:
  (a) the device quantize + offset-binary packer (core/quantize) is
      bit-exact vs the host packer across specs (property sweep via
      tests/_propshim);
  (b) frames -> score through the fused single-dispatch pipeline is
      bit-identical to the staged host oracle (host-materialized yprofile
      + host quantize/pack + FabricSim) on EVERY registered fabric,
      banded and dense — the acceptance bar of the refactor;
  (c) the multi-chip server paths agree across backends, the device
      keep/drop equals the host integer cut, and hot-swapping a chip's
      whole frontend (fabric arrays + encode plan) does not retrace;
  (d) ServerConfig validates on construction with named errors.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.bdt import GradientBoostedClassifier
from repro.core.fabric import FABRICS, FabricSim, place_and_route
from repro.core.quantize import (
    AP_FIXED_28_19,
    FixedSpec,
    encode_offset_binary_jax,
    quantize_raw,
    quantize_raw_jax,
    to_unsigned_bits,
    to_unsigned_bits_jax,
)
from repro.core.readout import ReadoutChip, get_backend
from repro.core.synth import synth_ensemble
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.kernels import frontend as fe
from repro.kernels.yprofile import ops as yp_ops
from repro.launch.readout_server import ReadoutServer, ServerConfig
from tests._propshim import given, settings, strategies as st

import repro.core.tmr  # noqa: F401  (registers efpga_28nm_xl)


# ------------------------------------------------------------ helpers
def staged_scores(chip, frames, y0, tile=128, threshold=800.0):
    """THE staged host oracle: featurizer dispatch materialized on host,
    then numpy quantize + offset-binary packing + FabricSim + numpy
    decode. Every integer stage is an independent implementation of what
    the fused path does on device."""
    feats = np.asarray(yp_ops.yprofile(
        frames, y0, threshold_electrons=threshold, batch_tile=tile))
    bits = chip.encode_features(feats)
    outs, _ = FabricSim(chip.config).run(bits)
    return chip.synth.decode_outputs(np.asarray(outs))


def _train(tr, fabric, depth, leaves, n_estimators=1, spec=AP_FIXED_28_19):
    clf = GradientBoostedClassifier(
        n_estimators=n_estimators, max_depth=depth, max_leaf_nodes=leaves,
        min_samples_leaf=200,
    ).fit(tr["features"], tr["label"])
    chip = ReadoutChip.build(clf, fabric=fabric, spec=spec)
    chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
    return chip


@pytest.fixture(scope="module")
def farm():
    """One chip per distinct registered fabric (open-ended set) plus the
    frames to feed them. Heterogeneous on purpose: tree shapes, used
    features AND fixed-point specs differ across chips, so the stacked
    encode plan is exercised, not just the padded fabric envelope."""
    d = generate(SmartPixelConfig(n_events=12_000, seed=5))
    tr, _ = train_test_split(d)
    fabric_names = sorted({s.name for s in FABRICS.values()})
    assert {"efpga_130nm", "efpga_28nm", "efpga_28nm_xl"} <= set(fabric_names)
    chips = {}
    for fi, name in enumerate(fabric_names):
        if name == "efpga_130nm":
            chips[name] = _train(tr, name, depth=3, leaves=5)
        elif name == "efpga_28nm":
            chips[name] = _train(tr, name, depth=4 + fi % 2, leaves=8)
        else:  # the XL fabric fits a small ensemble on a narrower grid
            chips[name] = _train(tr, name, depth=3, leaves=6,
                                 n_estimators=2, spec=FixedSpec(16, 8))
    dd = generate(SmartPixelConfig(n_events=256, seed=9), return_frames=True)
    return chips, dd["frames"], dd["features"][:, 13]


# ------------------------------------------------------------------ (a)
@given(width=st.integers(8, 28), int_frac=st.floats(0.1, 0.9),
       seed=st.integers(0, 10_000), overflow=st.sampled_from(["wrap", "sat"]))
@settings(max_examples=25, deadline=None)
def test_device_quantize_bit_exact_vs_host_packer(width, int_frac, seed,
                                                  overflow):
    """quantize_raw_jax / to_unsigned_bits_jax / encode_offset_binary_jax
    == the numpy host packer, including wraparound and saturation, on
    float32 inputs (the featurizer's output dtype)."""
    int_bits = max(2, int(round(width * int_frac)))
    int_bits = min(int_bits, width)
    spec = FixedSpec(width=width, int_bits=int_bits, overflow=overflow)
    rng = np.random.default_rng(seed)
    span = 2.0 ** (int_bits - 1)
    x = (rng.uniform(-1.6 * span, 1.6 * span, 257)).astype(np.float32)
    x[:3] = [0.0, spec.max_value, spec.min_value]  # grid corners

    want_raw = quantize_raw(x, spec)
    got_raw = np.asarray(quantize_raw_jax(x, spec)).astype(np.int64)
    np.testing.assert_array_equal(got_raw, want_raw)

    want_u = to_unsigned_bits(want_raw, spec)
    got_u = np.asarray(to_unsigned_bits_jax(want_raw.astype(np.int32), spec))
    np.testing.assert_array_equal(got_u.astype(np.int64), want_u)

    want_bits = ((want_u[..., None] >> np.arange(width)) & 1).astype(np.uint8)
    got_bits = np.asarray(encode_offset_binary_jax(x, spec)).astype(np.uint8)
    np.testing.assert_array_equal(got_bits, want_bits)


def test_device_quantize_round_half_up_small_range():
    """AP_RND needs the +0.5 ulp to survive float32 — exact in the
    documented |scaled| < 2**23 regime."""
    spec = FixedSpec(width=16, int_bits=8, rounding="rnd")
    rng = np.random.default_rng(0)
    x = rng.uniform(-120, 120, 1024).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(quantize_raw_jax(x, spec)).astype(np.int64),
        quantize_raw(x, spec))


def test_stacked_yprofile_matches_single_chip_kernel():
    """The chip-batched featurizer == C separate single-chip calls,
    bit-for-bit (identical per-tile dot)."""
    import jax

    rng = np.random.default_rng(3)
    frames = rng.exponential(500.0, (3, 256, 8, 13, 21)).astype(np.float32)
    y0 = rng.normal(0.0, 10.0, (3, 256)).astype(np.float32)
    run = jax.jit(lambda f, z: yp_ops.yprofile_traced(
        f, z, threshold=800.0, batch_tile=128, interpret=True))
    got = np.asarray(run(frames, y0))[:, :, :yp_ops.N_FEATURES]
    want = np.stack([
        np.asarray(yp_ops.yprofile(frames[c], y0[c], batch_tile=128))
        for c in range(3)
    ])
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ (b)
def test_fused_bit_identical_to_staged_every_fabric(farm):
    """frames -> keep/drop through ONE fused dispatch == the staged host
    oracle, for every registered fabric, banded and dense."""
    chips, frames, y0 = farm
    for name, chip in chips.items():
        want = staged_scores(chip, frames[:48], y0[:48])
        for band in (None, False):
            front = fe.pack_frontend(
                [chip.config], [chip.frontend_spec()], band=band)
            score, keep = front.score_frames(frames[None, :48], y0[None, :48])
            np.testing.assert_array_equal(
                np.asarray(score)[0], want, err_msg=f"{name} band={band}")
            np.testing.assert_array_equal(
                np.asarray(keep)[0], want <= chip.score_threshold_raw,
                err_msg=f"{name} band={band}")


@given(n=st.integers(1, 40), lo=st.integers(0, 200),
       band=st.sampled_from([None, False]))
@settings(max_examples=6, deadline=None)
def test_fused_matches_staged_property(n, lo, band, _farm_cache={}):
    """Property sweep: arbitrary batch sizes/offsets through the fused
    backend path == staged oracle. (Fixtureless by design — _propshim
    wraps the test in a zero-arg sweep, so the farm is module-cached.)"""
    if "farm" not in _farm_cache:
        d = generate(SmartPixelConfig(n_events=12_000, seed=5))
        tr, _ = train_test_split(d)
        dd = generate(SmartPixelConfig(n_events=256, seed=9),
                      return_frames=True)
        _farm_cache["farm"] = (
            _train(tr, "efpga_28nm", depth=4, leaves=8),
            dd["frames"], dd["features"][:, 13],
        )
    chip, frames, y0 = _farm_cache["farm"]
    lo = min(lo, len(frames) - n)
    fr, z = frames[lo:lo + n], y0[lo:lo + n]
    want = staged_scores(chip, fr, z)
    from repro.core.readout import KernelBackend

    backend = KernelBackend(band=band)
    got = backend.score_frames(chip, fr, z)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ (c)
def test_server_frames_kernel_vs_host_bit_identical(farm):
    """Multi-chip frames ingestion: fused sharded dispatch == staged host
    server, event for event (scores AND device keep/drop decisions)."""
    chips, frames, y0 = farm
    stack_chips = [chips["efpga_28nm"], chips["efpga_130nm"]]
    out = {}
    for backend in ("kernel", "host"):
        srv = ReadoutServer(list(stack_chips), ServerConfig(
            max_batch=64, max_latency_s=1e9, backend=backend))
        srv.submit_frames(0, frames[:90], y0[:90])
        srv.submit_frames(1, frames[90:170], y0[90:170])
        res = sorted(srv.flush(), key=lambda r: r.seq)
        out[backend] = [(r.seq, r.chip, r.score_raw, r.keep) for r in res]
    assert out["kernel"] == out["host"]
    # and both equal the per-chip staged oracle + integer cut
    want0 = staged_scores(stack_chips[0], frames[:90], y0[:90])
    got0 = [s for _, c, s, _ in out["host"] if c == 0]
    np.testing.assert_array_equal(got0, want0)
    keep0 = [k for _, c, _, k in out["kernel"] if c == 0]
    np.testing.assert_array_equal(
        keep0, want0 <= stack_chips[0].score_threshold_raw)


def test_fused_hot_swap_no_retrace_and_correct(farm):
    """Swapping a chip's whole frontend (fabric arrays + encode plan +
    trigger cut) must not grow the fused dispatch's jit cache — the
    'array swap, no recompile' guarantee extended to the full pipeline."""
    chips, frames, y0 = farm
    a, b = chips["efpga_28nm"], chips["efpga_130nm"]
    front = fe.pack_frontend(
        [a.config, b.config], [a.frontend_spec(), b.frontend_spec()])
    fr = np.stack([frames[:32], frames[32:64]])
    z = np.stack([y0[:32], y0[32:64]])
    np.asarray(front.score_frames(fr, z)[0])

    if not hasattr(fe._score_frames, "_cache_size"):
        pytest.skip("jit cache introspection unavailable on this JAX")
    n0 = fe._score_frames._cache_size()
    front2 = front.swap_chip(0, b.config, b.frontend_spec())
    score2, keep2 = front2.score_frames(fr, z)
    assert fe._score_frames._cache_size() == n0
    np.testing.assert_array_equal(
        np.asarray(score2)[0], staged_scores(b, frames[:32], y0[:32]))
    # threshold retarget is an array-row update too
    front3 = front2.set_threshold(0, -(10 ** 6))
    assert not np.asarray(front3.score_frames(fr, z)[1])[0].any()
    assert fe._score_frames._cache_size() == n0


def test_server_reconfigure_updates_fused_frontend(farm):
    chips, frames, y0 = farm
    a, b = chips["efpga_28nm"], chips["efpga_130nm"]
    srv = ReadoutServer([a, b], ServerConfig(
        max_batch=10_000, max_latency_s=1e9, backend="kernel"))
    srv.submit_frames(0, frames[:16], y0[:16])
    srv.flush()
    srv.reconfigure(0, b)
    srv.submit_frames(0, frames[16:48], y0[16:48])
    got = [r.score_raw for r in sorted(srv.flush(), key=lambda r: r.seq)]
    np.testing.assert_array_equal(
        got, staged_scores(b, frames[16:48], y0[16:48]))


def test_kernel_backend_honors_per_call_featurizer_threshold(farm):
    """The cached fused frontend is keyed by (config, threshold): a
    different zero-suppression threshold must rebuild, not silently reuse
    a stale dispatch — kernel==host on every call."""
    chips, frames, y0 = farm
    chip = chips["efpga_28nm"]
    from repro.core.readout import KernelBackend

    kb = KernelBackend()
    for thr in (0.0, 20_000.0, 800.0):
        got = kb.score_frames(chip, frames[:32], y0[:32],
                              threshold_electrons=thr)
        want = staged_scores(chip, frames[:32], y0[:32], threshold=thr)
        np.testing.assert_array_equal(got, want, err_msg=f"thr={thr}")


def test_reconfigure_enforces_frontend_contract_on_both_backends(farm):
    """A chip that fits the fabric envelope but violates the featurizer
    contract is rejected at swap time with a named error — on the host
    backend too, and before any frames dispatch has run."""
    import types

    chips, _, _ = farm
    a, b = chips["efpga_28nm"], chips["efpga_130nm"]
    bad_spec = dataclasses.replace(
        b.frontend_spec(),
        used_features=tuple([99] + list(b.frontend_spec().used_features[1:])))
    impostor = types.SimpleNamespace(
        config=b.config, frontend_spec=lambda: bad_spec)
    for backend in ("host", "kernel"):
        srv = ReadoutServer([a, b], ServerConfig(
            max_batch=64, max_latency_s=1e9, backend=backend))
        with pytest.raises(ValueError, match="featurizer"):
            srv.reconfigure(1, impostor)


def test_pack_frontend_validates_chip_contract(farm):
    chips, _, _ = farm
    chip = chips["efpga_28nm"]
    good = chip.frontend_spec()
    with pytest.raises(ValueError, match="int32"):
        fe.pack_frontend(
            [chip.config],
            [dataclasses.replace(good, spec=FixedSpec(width=40, int_bits=20))])
    with pytest.raises(ValueError, match="used features"):
        fe.pack_frontend(
            [chip.config],
            [dataclasses.replace(good,
                                 used_features=good.used_features[:-1])])
    with pytest.raises(ValueError, match="featurizer"):
        bad = tuple([99] + list(good.used_features[1:]))
        fe.pack_frontend([chip.config],
                         [dataclasses.replace(good, used_features=bad)])


# ------------------------------------------------------------------ (d)
def test_server_config_validates_on_construction():
    ServerConfig()  # defaults are valid
    with pytest.raises(ValueError, match="max_batch"):
        ServerConfig(max_batch=0)
    with pytest.raises(ValueError, match="max_batch"):
        ServerConfig(max_batch=-5)
    with pytest.raises(ValueError, match="batch_tile"):
        ServerConfig(batch_tile=100)
    with pytest.raises(ValueError, match="batch_tile"):
        ServerConfig(batch_tile=0)
    with pytest.raises(ValueError, match="max_latency_s"):
        ServerConfig(max_latency_s=0.0)
    with pytest.raises(ValueError, match="backend"):
        ServerConfig(backend="gpu")
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServerConfig(pipeline_depth=0)
    with pytest.raises(ValueError, match="threshold_electrons"):
        ServerConfig(threshold_electrons=-1.0)


def test_readout_mesh_single_device():
    from repro.launch.mesh import make_readout_mesh

    for n in (1, 3, 4):
        mesh = make_readout_mesh(n)
        assert mesh.axis_names == ("chips",)
        assert mesh.devices.size in {d for d in range(1, n + 1) if n % d == 0}
    with pytest.raises(ValueError):
        make_readout_mesh(0)


# ------------------------------------------------------------- slow tier
@pytest.mark.slow
def test_fused_wide_sweep_all_fabrics_banded_dense(farm):
    """The wide frames->score sweep: every registered fabric x banded/
    dense x several batch shapes, fused vs staged, plus the multi-fabric
    heterogeneous stack through the server on both backends."""
    chips, frames, y0 = farm
    for name, chip in chips.items():
        for band in (None, True, False):
            front = fe.pack_frontend(
                [chip.config], [chip.frontend_spec()], band=band)
            for lo, n in [(0, 1), (7, 129), (60, 196)]:
                fr, z = frames[lo:lo + n], y0[lo:lo + n]
                want = staged_scores(chip, fr, z)
                score, keep = front.score_frames(fr[None], z[None])
                np.testing.assert_array_equal(
                    np.asarray(score)[0], want,
                    err_msg=f"{name} band={band} n={n}")
                np.testing.assert_array_equal(
                    np.asarray(keep)[0], want <= chip.score_threshold_raw)

    stack_chips = list(chips.values())
    out = {}
    for backend in ("kernel", "host"):
        srv = ReadoutServer(list(stack_chips), ServerConfig(
            max_batch=97, max_latency_s=1e9, backend=backend))
        for i in range(len(stack_chips)):
            srv.submit_frames(i, frames[i::4][:40], y0[i::4][:40])
            srv.poll()
        res = sorted(srv.flush(), key=lambda r: r.seq)
        out[backend] = [(r.seq, r.chip, r.score_raw, r.keep) for r in res]
    assert out["kernel"] == out["host"]
