"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — tests and benches must keep seeing one CPU
device; only launch/dryrun.py sets the 512-device XLA flag.

Production topology (TPU v5e): a pod is a 16x16 mesh (256 chips) with axes
("data", "model"); the multi-pod config prepends a pure-DP "pod" axis of
size 2 (512 chips) that crosses the DCN — the axis the compressed gradient
all-reduce targets (parallel/compression.py). Designs generalize to N pods
by growing the pod axis; nothing in the sharding rules hard-codes 2.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh_compat(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-sharded), the axis
    type all of the repo's meshes use."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests on CPU)."""
    return make_mesh_compat((data, model), ("data", "model"))


def make_readout_mesh(n_chips: int) -> Mesh:
    """One-axis "chips" mesh for the fused readout frontend.

    The chip axis of the fused frames->score dispatch shards across the
    largest device count that divides it evenly — every device then owns
    an identical (C/d, B) slab, so the shard_map body stays shape-uniform
    and swap-friendly. On a single-device host (tests, CI) this degrades
    to a size-1 axis: same code path, no data movement.
    """
    if n_chips < 1:
        raise ValueError(f"need n_chips >= 1, got {n_chips}")
    n_dev = jax.local_device_count()
    d = max(k for k in range(1, min(n_dev, n_chips) + 1) if n_chips % k == 0)
    return make_mesh_compat((d,), ("chips",))


def make_fleet_meshes(bucket_chip_counts: Sequence[int]) -> List[Mesh]:
    """One "chips" readout mesh per fleet bucket, over DISJOINT devices.

    The multi-tenant fleet (launch/fleet.py) runs one ReadoutServer per
    geometry bucket; each wants its own device slab so buckets never
    contend. Local devices are split into contiguous slices proportional
    to each bucket's chip count (every bucket gets at least one device;
    with fewer devices than buckets the slices wrap, which on the
    single-device CI host degrades every bucket to the same size-1 mesh
    — same code path, no movement). Within its slice a bucket uses the
    largest divisor of its chip count, the same rule as
    ``make_readout_mesh``, so the shard_map body stays shape-uniform.

    Called again after every grow/shrink: because jax ``Mesh`` equality
    is by device assignment, an unchanged bucket's re-planned mesh
    compares equal to its old one and its compiled dispatch is reused —
    only buckets whose device slab actually moved pay a re-place (and
    retrace) through ``ReadoutServer.rebind_mesh``.
    """
    if not bucket_chip_counts:
        return []
    for n in bucket_chip_counts:
        if n < 1:
            raise ValueError(
                f"every bucket needs >= 1 chip, got {bucket_chip_counts!r}")
    devices = jax.local_devices()
    n_dev, n_buckets = len(devices), len(bucket_chip_counts)
    total = sum(bucket_chip_counts)
    meshes: List[Mesh] = []
    start = 0
    for b, n_chips in enumerate(bucket_chip_counts):
        if n_dev >= n_buckets:
            # proportional contiguous slice, >= 1 device per bucket
            width = max(1, (n_chips * n_dev) // total)
            width = min(width, n_dev - start - (n_buckets - 1 - b))
            slab = devices[start : start + width]
            start += width
        else:
            slab = [devices[b % n_dev]]
        d = max(k for k in range(1, min(len(slab), n_chips) + 1)
                if n_chips % k == 0)
        meshes.append(Mesh(np.asarray(slab[:d]), ("chips",)))
    return meshes


# HBM of one TPU v5e chip; the LM dry run checks its programs against it.
HBM_BYTES = 16 * 1024**3      # 16 GiB
