"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place, with its featurizer in a lower
precision, judged by the same rule as the program's answers.

    python3 benchmarks/readout/control.py --config <name> --seeds 1 2 3

For each seed it builds the deployment's classifiers and frame pool as a
run does, and prints, over every pool event the reference judges, how many
answers change when the y-profile is summed

  bf16     from bfloat16 charges (the control: one precision below the
           float32 the configuration states);
  default  by a float32 matmul at ``Precision.DEFAULT`` (one bf16 pass on
           a TPU: the program's kernel without its ``HIGHEST``);
  high     by a float32 matmul at ``Precision.HIGH`` (three bf16 passes);
  highest  by a float32 matmul at ``Precision.HIGHEST``, as the program's
           kernel sums.

It runs on the default JAX device; the readings in PERF.md are from a TPU.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Dict

import numpy as np

if __package__ in (None, ""):
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from readout import deploy, reference, spec  # noqa: E402


def profile_matmul(precision: str):
    """The y-profile as one float32 matmul against a one-hot fold, at the
    given ``jax.lax.Precision``."""
    import jax
    import jax.numpy as jnp

    prec = getattr(jax.lax.Precision, precision)

    def fold(shape):
        t, y, x = shape
        f = np.zeros((t * y * x, y), np.float32)
        idx = np.arange(t * y * x)
        f[idx, (idx // x) % y] = 1.0
        return jnp.asarray(f)

    def run(frames, block=8192):
        n, t, y, x = frames.shape
        fm = fold((t, y, x))
        f = jax.jit(lambda a: jnp.dot(a.reshape(a.shape[0], -1), fm,
                                      precision=prec,
                                      preferred_element_type=jnp.float32))
        return np.concatenate([np.asarray(f(frames[i:i + block]))
                               for i in range(0, n, block)]).astype(
                                   np.float64)
    return run


PROFILES = {
    "bf16": reference.profile_bf16,
    "default": profile_matmul("DEFAULT"),
    "high": profile_matmul("HIGH"),
    "highest": profile_matmul("HIGHEST"),
}


def readings(cfg: Dict, seed: int, n_pool: int = None) -> Dict[str, int]:
    modules = deploy.build_modules(cfg, seed)
    frames, y0 = deploy.frame_pool(cfg, seed, n_pool)
    models = reference.build_models(cfg, modules)
    want = reference.expected(models, frames, y0)
    out = {"events": int(want["score"].size),
           "judged": int((want["margin"] > reference.AMBIGUOUS_ELECTRONS)
                         .sum())}
    for name, prof in PROFILES.items():
        other = reference.expected(models, frames, y0, profile=prof)
        out[name] = reference.control_wrong(want, other)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.CHECKOUT / "src"))
    import jax

    cfg = spec.load_config(args.config)
    dev = jax.devices()[0]
    for seed in args.seeds:
        r = readings(cfg, seed)
        r.update(config=args.config, seed=seed, device=dev.device_kind)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
