"""On-chip smoke test of the readout server's frames -> trigger path.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # the chip axis sharded over 4 chips

Default phases, each printing one line of what it checked:

  device      the platform is a TPU and Pallas kernels compile with Mosaic
              (no interpret mode);
  served      4 tenant chips with the paper's BDT (1 tree, depth 5, at most
              10 leaves, efpga_28nm) behind ``ReadoutServer`` with the
              kernel backend, fed raw ``FrameStream`` frames, with a
              mid-stream hot swap; run plain, then with TMR + word-domain
              sparse egress + scrubbing and an injected SEU. Every
              (seq, chip, score, keep) must equal a host-oracle server's;
  featurizer  the device ``yprofile`` kernels against a float64 numpy
              evaluation of the same frames (one electron tolerance);
  paper       §5: fabric-vs-golden on generated events through the Pallas
              matmul kernel (auto-banded and dense) and the bit-sliced
              layout, 100% match required.

``--four-chips`` runs only the sharded path: the same server on a
4-device "chips" mesh against the host oracle and against a 1-device
mesh, score shards on all 4 devices, and a two-bucket ``TenantFleet``
whose meshes own disjoint devices.

Without a TPU the script exits non-zero and prints no result: there is no
CPU or interpret-mode fallback. Any failed check exits non-zero before the
last line, which on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
BDTs, frames and events come from the fixed seeds below. Everything runs
in this one process.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# The paper's BDT (§5): one tree of depth 5 with at most 10 leaves.
PAPER_BDT = dict(n_estimators=1, max_depth=5, max_leaf_nodes=10,
                 min_samples_leaf=500)
TENANT_SEEDS = (2024, 2025, 2026, 2027)
SWAP_SEED = 31            # the chip hot-swapped into slot 0 mid-stream
SMALL_SEED = 41           # the fleet's second-envelope tenant
STREAM_SEED = 700
FEATURIZER_SEED = 701
PAPER_SEED = 2031

N_SENSORS = 4
BATCH = 2048              # events per sensor per stream batch
N_BATCHES = 8             # 8 x 4 x 2048 = 65,536 events per served run
SWAP_AT = 2
SEU_AT = 4                # after the swap, so the swap cannot heal it
N_FEATURIZER = 8192
N_PAPER = 131_072


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def train_chip(seed: int, max_depth: int = 5, max_leaf_nodes: int = 10):
    """Train, synthesize and calibrate one readout chip on efpga_28nm, as
    examples/serve_readout.py does."""
    from repro.core.bdt import GradientBoostedClassifier
    from repro.core.readout import ReadoutChip
    from repro.data.smartpixel import (
        SmartPixelConfig, generate, train_test_split)

    tr, _ = train_test_split(generate(SmartPixelConfig(n_events=30_000,
                                                       seed=seed)))
    clf = GradientBoostedClassifier(**dict(
        PAPER_BDT, max_depth=max_depth, max_leaf_nodes=max_leaf_nodes,
    )).fit(tr["features"], tr["label"])
    chip = ReadoutChip.build(clf, fabric="efpga_28nm")
    chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.97)
    return chip


def serve_stream(servers, swap_chip, *, n_batches=N_BATCHES, batch=BATCH,
                 seu_at=None):
    """Feed the same seeded frame stream to every server in lockstep, with
    chip 0 hot-swapped at SWAP_AT and (optionally) an SEU injected into
    chip 0's replica 1 at ``seu_at``. Returns each server's sorted
    (seq, chip, score_raw, keep) tuples."""
    from repro.data.pipeline import FrameStream, FrameStreamConfig

    n_chips = servers[0].n_chips
    stream = FrameStream(FrameStreamConfig(
        n_sensors=n_chips, batch=batch, seed=STREAM_SEED))
    results = [[] for _ in servers]
    for bi in range(n_batches):
        blocks = [stream.batch_at(bi, c) for c in range(n_chips)]
        for srv, out in zip(servers, results):
            if bi == SWAP_AT:
                out += srv.reconfigure(0, swap_chip)
            if bi == seu_at:
                srv.inject_seu(0, replica=1, lut_index=3, bit=7)
            for c, blk in enumerate(blocks):
                srv.submit_frames(c, blk["frames"], blk["y0"])
            out += srv.poll()
    for srv, out in zip(servers, results):
        out += srv.flush()
    return [sorted((e.seq, e.chip, e.score_raw, e.keep) for e in out)
            for out in results]


def mismatches(got, want) -> int:
    """Events whose (chip, score, keep) differ, or that one side lacks."""
    g = {r[0]: r for r in got}
    w = {r[0]: r for r in want}
    return sum(g.get(s) != w.get(s) for s in set(g) | set(w))


def served_phase(chips, swap_chip, kind, *, redundancy="none",
                 sparse=False, scrub_interval=None, seu_at=None,
                 n_batches=N_BATCHES, batch=BATCH) -> None:
    from repro.launch.readout_server import ReadoutServer, ServerConfig

    t0 = time.perf_counter()
    knobs = dict(max_batch=8192, redundancy=redundancy, sparse=sparse,
                 scrub_interval=scrub_interval)
    kernel = ReadoutServer(chips, ServerConfig(backend="kernel", **knobs))
    host = ReadoutServer(chips, ServerConfig(backend="host", **knobs))
    check(kernel.layout == "bitsliced",
          f"server layout resolved to {kernel.layout!r}, not bitsliced")
    got, want = serve_stream([kernel, host], swap_chip,
                             n_batches=n_batches, batch=batch, seu_at=seu_at)
    rep = kernel.report()
    n_events = n_batches * len(chips) * batch
    bad = mismatches(got, want)
    label = "tmr+sparse+scrub" if redundancy == "tmr" else "plain"
    msg = (f"served[{label}]: {rep['n_in']} events, kept fraction "
           f"{rep['fraction_kept']!r}, {len(got)} results, {bad} "
           f"(seq, chip, score, keep) mismatches vs the host oracle")
    if seu_at is not None:
        sc = rep["scrub"]
        msg += (f"; SEU detected {sc['detections']}x, "
                f"{sc['healed_bits']} config bits healed, frame verifies "
                f"after heal: {kernel.verify_frame(0, 1)}")
    print(f"{msg}; wall {time.perf_counter() - t0:.1f} s incl. compile "
          f"on {kind}", flush=True)
    check(rep["n_in"] == n_events, f"{label}: served {rep['n_in']} of "
          f"{n_events} events")
    check(got and bad == 0, f"{label}: {bad} mismatches vs host oracle")
    if seu_at is not None:
        sc = rep["scrub"]
        check(sc["detections"] >= 1 and sc["healed_bits"] >= 1,
              f"injected SEU not detected and healed: {sc}")
        check(kernel.verify_frame(0, 1), "healed frame fails its CRC")


def _yprofile_f64(frames: np.ndarray, y0: np.ndarray,
                  threshold: float = 800.0) -> np.ndarray:
    """float64 numpy form of kernels/yprofile/ref.py: (n, T, Y, X), (n,)
    -> (n, Y+1) with the profile in ke- and y0 last."""
    prof = np.maximum(frames.astype(np.float64).sum(axis=(1, 3)), 0.0)
    prof = np.where(prof > threshold, prof, 0.0) / 1000.0
    return np.concatenate([prof, y0.astype(np.float64)[:, None]], axis=1)


def featurizer_phase(kind, n_events=N_FEATURIZER) -> None:
    import jax

    from repro.data.pipeline import FrameStream, FrameStreamConfig
    from repro.kernels.compat import default_interpret
    from repro.kernels.yprofile import ops as yp_ops

    stream = FrameStream(FrameStreamConfig(
        n_sensors=N_SENSORS, batch=n_events // N_SENSORS,
        seed=FEATURIZER_SEED))
    blocks = [stream.batch_at(0, c) for c in range(N_SENSORS)]
    frames = np.stack([b["frames"] for b in blocks]).astype(np.float32)
    y0 = np.stack([b["y0"] for b in blocks]).astype(np.float32)
    flat_frames = frames.reshape((-1,) + frames.shape[2:])
    want = _yprofile_f64(flat_frames, y0.reshape(-1))
    # the host oracle's single-chip kernel and the fused path's stacked one
    single = np.asarray(yp_ops.yprofile(flat_frames, y0.reshape(-1)))
    stacked = jax.jit(lambda f, z: yp_ops.yprofile_traced(
        f, z, threshold=800.0, batch_tile=128,
        interpret=default_interpret()))(frames, y0)
    stacked = np.asarray(stacked)[..., :yp_ops.N_FEATURES].reshape(
        want.shape)
    err_single = float(np.max(np.abs(single - want)))
    err_stacked = float(np.max(np.abs(stacked - want)))
    print(f"featurizer: {len(want)} events, max |device - float64| = "
          f"{err_single!r} (single-chip kernel), {err_stacked!r} (stacked "
          f"kernel) in ke- on {kind}; limit 1e-3", flush=True)
    check(max(err_single, err_stacked) <= 1e-3,
          f"yprofile off the float64 reference by "
          f"{max(err_single, err_stacked)} ke-")


def paper_phase(chip, kind, n_events=N_PAPER) -> None:
    from repro.core.readout import KernelBackend
    from repro.data.smartpixel import SmartPixelConfig, generate
    from repro.kernels.lut_eval import ops as lut_ops

    t0 = time.perf_counter()
    X = generate(SmartPixelConfig(n_events=n_events,
                                  seed=PAPER_SEED))["features"]
    banded = lut_ops.pack_fabric(chip.config).banded
    backends = [
        (f"matmul ({'banded' if banded else 'dense'}, auto)", "kernel"),
        ("matmul (dense)", KernelBackend(band=False)),
        ("bitsliced", KernelBackend(layout="bitsliced")),
    ]
    parts = []
    for label, backend in backends:
        r = chip.verify_vs_golden(X, backend=backend)
        parts.append(f"{label} {int(r['n_match'])}/{int(r['n'])}")
        check(r["n"] == n_events and r["n_match"] == r["n"],
              f"paper §5 through {label}: {r}")
    print(f"paper §5 fabric vs golden: {'; '.join(parts)}; wall "
          f"{time.perf_counter() - t0:.1f} s incl. compile on {kind}",
          flush=True)


def four_chip_phase(chips, swap_chip, small_chip, *,
                    n_batches=N_BATCHES, batch=BATCH) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.data.pipeline import FrameStream, FrameStreamConfig
    from repro.kernels import frontend as fe
    from repro.launch.fleet import TenantFleet
    from repro.launch.readout_server import ReadoutServer, ServerConfig

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    one = Mesh(np.asarray(devices[:1]), ("chips",))

    # the served path: 4-device mesh vs the host oracle vs a 1-device mesh
    cfg = ServerConfig(backend="kernel", max_batch=8192)
    sharded = ReadoutServer(chips, cfg)
    single = ReadoutServer(chips, cfg)
    single.rebind_mesh(one)
    host = ReadoutServer(chips, ServerConfig(backend="host", max_batch=8192))
    got4, got1, want = serve_stream([sharded, single, host], swap_chip,
                                    n_batches=n_batches, batch=batch)
    devs = sharded.report()["devices"]
    bad_host, bad_one = mismatches(got4, want), mismatches(got4, got1)
    print(f"four-chip served: {len(got4)} events on devices {devs}, "
          f"{bad_host} mismatches vs host oracle, {bad_one} vs the 1-device "
          f"mesh", flush=True)
    check(len(set(devs)) == 4, f"chip axis on devices {devs}, not 4")
    check(got4 and bad_host == 0 and bad_one == 0,
          "4-device server diverged from the host oracle or 1-device mesh")

    # the fused dispatch's outputs are sharded over all 4 devices
    stream = FrameStream(FrameStreamConfig(
        n_sensors=len(chips), batch=batch, seed=STREAM_SEED))
    blocks = [stream.batch_at(0, c) for c in range(len(chips))]
    frames = np.stack([b["frames"] for b in blocks])
    y0 = np.stack([b["y0"] for b in blocks])
    configs = [c.config for c in chips]
    specs = [c.frontend_spec() for c in chips]
    score4, keep4 = fe.pack_frontend(
        configs, specs, layout="bitsliced").score_frames(frames, y0)
    score1, keep1 = fe.pack_frontend(
        configs, specs, layout="bitsliced", mesh=one).score_frames(frames, y0)
    shard_devs = sorted(int(s.device.id) for s in score4.addressable_shards)
    same = (np.array_equal(np.asarray(score4), np.asarray(score1))
            and np.array_equal(np.asarray(keep4), np.asarray(keep1)))
    print(f"four-chip dispatch: score shards on devices {shard_devs}, "
          f"bit-exact vs 1-device mesh: {same}", flush=True)
    check(len(set(shard_devs)) == 4, f"score shards on {shard_devs}")
    check(same, "4-device fused dispatch differs from the 1-device mesh")

    # a fleet with two envelope buckets: disjoint device slabs, bit-exact
    fleets = [TenantFleet(ServerConfig(backend=b, max_batch=8192))
              for b in ("kernel", "host")]
    outs = []
    for fleet in fleets:
        fleet.admit("paper", chips[0])
        fleet.admit("small", small_chip)
        for t, blk in zip(("paper", "small"), blocks):
            fleet.submit_frames(t, blk["frames"], blk["y0"])
        outs.append(sorted((e.seq, e.tenant, e.score_raw, e.keep)
                           for e in fleet.flush()))
    rep = fleets[0].report()
    slabs = [set(b["server"]["devices"]) for b in rep["buckets"]]
    bad = mismatches(outs[0], outs[1])
    print(f"four-chip fleet: {rep['n_buckets']} buckets on devices "
          f"{[sorted(s) for s in slabs]}, {len(outs[0])} events, {bad} "
          f"mismatches vs host-oracle fleet", flush=True)
    check(len(slabs) == 2 and all(slabs) and not (slabs[0] & slabs[1]),
          f"fleet buckets do not own disjoint devices: {slabs}")
    check(outs[0] and bad == 0, "fleet diverged from the host oracle")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the path sharded over 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r}); refusing "
              "to run on anything else", file=sys.stderr)
        return 1
    kind, count = devices[0].device_kind, len(devices)

    from repro.kernels.compat import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        check(default_interpret() is False,
              "Pallas kernels would run in interpret mode on a TPU")
        print(f"device: {platform} {kind!r} x{count}, jax "
              f"{jax.__version__}, jaxlib {_version('jaxlib')}, libtpu "
              f"{_version('libtpu')}, Mosaic kernels (interpret=False), "
              f"compile cache {cache_dir}", flush=True)
        chips = [train_chip(s) for s in TENANT_SEEDS]
        swap_chip = train_chip(SWAP_SEED)
        if args.four_chips:
            four_chip_phase(chips, swap_chip,
                            train_chip(SMALL_SEED, max_depth=2,
                                       max_leaf_nodes=4))
        else:
            served_phase(chips, swap_chip, kind)
            served_phase(chips, swap_chip, kind, redundancy="tmr",
                         sparse=True, scrub_interval=2, seu_at=SEU_AT)
            featurizer_phase(kind)
            paper_phase(chips[0], kind)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n_cached = sum(len(files) for _, _, files in os.walk(cache_dir))
    print(f"compile cache: {n_cached} files in {cache_dir}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
