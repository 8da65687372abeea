"""Elastic scaling + failure handling helpers.

Scenario this supports (DESIGN.md §5): a pod (or some hosts) drops out
mid-run. Recovery path:

  1. the run restarts from the latest atomic checkpoint (launch/train.py
     --resume does this automatically);
  2. ``reshard`` places the checkpointed state onto the NEW mesh — any DP
     degree works because checkpoints are stored unsharded and the sharding
     rules are pure functions of (config, mesh);
  3. the data pipeline needs no state migration at all: batches are pure
     functions of (seed, step, shard) (data/pipeline.py), so the surviving
     hosts simply recompute their shards from the restored step.

Straggler mitigation at this layer: the synchronous SPMD step makes
per-host stragglers a hardware-level concern (the TPU runtime handles ICI
retries); at the job level the mitigations are (a) deterministic shard
reassignment — a slow host's data shard can be handed to any other host,
(b) checkpoint/restart with elastic reshard onto the shrunken mesh, and
(c) bounded step timeout in the driver loop (launch/train.py --step-timeout)
that triggers (b) rather than waiting on a sick host forever.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.parallel import sharding as shd

PyTree = Any


def reshard_params(cfg: ArchConfig, mesh: Mesh, params_host: PyTree) -> PyTree:
    """Place host (numpy) params onto a (possibly different) mesh."""
    specs = shd.param_specs(cfg, mesh, jax.eval_shape(lambda t: t, params_host))
    sh = shd.named(mesh, specs)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), params_host, sh)


def reshard_opt_state(cfg: ArchConfig, mesh: Mesh, opt_host: PyTree,
                      params_template: PyTree) -> PyTree:
    pspecs = shd.param_specs(cfg, mesh, params_template)
    ospecs = shd.opt_state_specs(
        cfg, mesh, jax.eval_shape(lambda t: t, opt_host), pspecs
    )
    sh = shd.named(mesh, ospecs)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), opt_host, sh)


def gather_to_host(tree: PyTree) -> PyTree:
    """Fully replicate/gather device arrays back to host numpy (pre-save)."""
    import numpy as np

    return jax.tree.map(lambda x: np.asarray(x), tree)
