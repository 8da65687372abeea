"""One deployment, built from its configuration file and the run's seed:
the modules' classifiers (trained, synthesized, calibrated), the frame pool
the traffic cycles, and the readout server under test.

The seed decides every input and every classifier. The served geometry is
the configuration's fixed envelope, so every seed compiles the same
programs and a warm compile cache serves all of them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

# Streams drawn from one run seed; each (role, module) pair gets its own.
TRAIN, POOL, ARRIVALS = 1, 2, 3


def substream(seed: int, role: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, role, index]))


def subseed(seed: int, role: int, index: int = 0) -> int:
    """A 32-bit seed for program code that takes an int."""
    ss = np.random.SeedSequence([seed, role, index])
    return int(ss.generate_state(1, np.uint32)[0])


@dataclasses.dataclass
class Module:
    """One sensor module's classifier, as the reference needs it (the float
    trees and their training data) and as the server serves it (the chip)."""

    chip: object              # repro.core.readout.ReadoutChip
    clf: object               # repro.core.bdt.GradientBoostedClassifier
    train_features: np.ndarray
    train_labels: np.ndarray


def fixed_spec(cfg: Dict):
    from repro.core.quantize import FixedSpec

    return FixedSpec(**cfg["classifier"]["fixed"])


def build_modules(cfg: Dict, seed: int) -> List[Module]:
    """Train, synthesize, place and calibrate one classifier per module."""
    import repro.core.tmr  # noqa: F401  (registers efpga_28nm_xl)
    from repro.core.bdt import GradientBoostedClassifier
    from repro.core.readout import ReadoutChip
    from repro.data.smartpixel import (
        SmartPixelConfig, generate, train_test_split)

    c, t = cfg["classifier"], cfg["training"]
    mods = []
    for m in range(cfg["modules"]):
        data = generate(SmartPixelConfig(n_events=t["n_events"],
                                         seed=subseed(seed, TRAIN, m)))
        tr, _ = train_test_split(data, test_fraction=t["test_fraction"],
                                 seed=t["split_seed"])
        clf = GradientBoostedClassifier(
            n_estimators=c["n_estimators"], max_depth=c["max_depth"],
            max_leaf_nodes=c["max_leaf_nodes"],
            min_samples_leaf=c["min_samples_leaf"],
            learning_rate=c["learning_rate"],
        ).fit(tr["features"], tr["label"])
        chip = ReadoutChip.build(clf, fabric=cfg["fabric"],
                                 spec=fixed_spec(cfg), adder=c["adder"])
        chip.calibrate(tr["features"], tr["label"],
                       target_sig_eff=t["target_signal_efficiency"])
        mods.append(Module(chip, clf, tr["features"], tr["label"]))
    return mods


def frame_pool(cfg: Dict, seed: int, n_per_module: int = None):
    """(C, P, T, Y, X) float32 frames and (C, P) float32 y0: the events the
    traffic cycles, generated once in set-up (generation costs far more
    host time per event than serving does)."""
    from repro.data.smartpixel import SmartPixelConfig, generate_batch

    n = n_per_module or cfg["pool_events_per_module"]
    frames, y0 = [], []
    for m in range(cfg["modules"]):
        b = generate_batch(substream(seed, POOL, m), SmartPixelConfig(), n,
                           return_frames=True)
        frames.append(b["frames"])
        y0.append(b["features"][:, -1])
    return (np.stack(frames).astype(np.float32),
            np.stack(y0).astype(np.float32))


def make_server(cfg: Dict, modules: List[Module]):
    """The server under test: the kernel backend inside the configuration's
    fixed envelope."""
    from repro.core.fabric import StackGeometry
    from repro.launch.readout_server import ReadoutServer, ServerConfig

    knobs = dict(cfg["server"])
    knobs["threshold_electrons"] = cfg["sensor"]["threshold_electrons"]
    return ReadoutServer(
        [m.chip for m in modules],
        ServerConfig(backend="kernel", **knobs),
        envelope=StackGeometry(**cfg["envelope"]),
    )
