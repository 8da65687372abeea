import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import (jax locks device count on first init).
# (No `from __future__ import annotations` here — the XLA_FLAGS lines above
# are required to be the first statements of the module.)

#: Multi-pod dry-run docs follow
_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the step function (train_step for train_4k, forward-loss for
     prefill_32k, serve_step for decode_32k / long_500k),
  2. jit's it with explicit in/out shardings from parallel/sharding.py,
  3. ``.lower(**ShapeDtypeStruct inputs).compile()`` on the production mesh
     — 16x16 ("data","model") single-pod and 2x16x16 ("pod","data","model")
     multi-pod,
  4. prints ``compiled.memory_analysis()`` (fits-in-HBM proof) and
     ``cost_analysis()`` (FLOPs/bytes for §Roofline), parses collective
     wire bytes from the compiled HLO,
  5. writes reports/dryrun/<mesh>/<arch>__<shape>.json.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system — the run aborts non-zero unless --keep-going.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single          # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch grok-1-314b --shape decode_32k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both --resume
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_arch
from repro.configs.base import SHAPES, ArchConfig, ShapeSpec
from repro.launch import specs as S
from repro.launch.mesh import HBM_BYTES, make_production_mesh
from repro.parallel import sharding as shd
from repro.parallel.hlo_analysis import summarize_compiled
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import (
    default_opt_config, make_opt_init, make_prefill_step, make_serve_step,
    make_train_step,
)


def _adjust_cfg(cfg: ArchConfig, shape: ShapeSpec, mesh) -> ArchConfig:
    """Mesh/shape-dependent config fix-ups: act-shard axes, microbatch
    divisibility, MoE group divisibility."""
    dp = shd.dp_axes(mesh)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    changes: Dict = {"act_dp_axes": tuple(dp)}
    if shape.kind == "train":
        n_mb = max(cfg.num_microbatches, 1)
        while n_mb > 1 and (shape.global_batch % (n_mb * n_dp) != 0):
            n_mb //= 2
        changes["num_microbatches"] = n_mb
    else:
        changes["num_microbatches"] = 1
    if shd.profile_of(cfg) in ("dp", "fsdp_pure") and cfg.act_shard == "none":
        # dp-profile: re-pin pure-DP activation sharding between blocks so
        # XLA never drifts to replicated activations inside the layer scan.
        n_mb = changes["num_microbatches"]
        per_mb = shape.global_batch // max(n_mb, 1)
        bdim = shd.batch_dim(cfg, mesh, per_mb)
        if bdim is not None:
            axes = bdim if isinstance(bdim, tuple) else (bdim,)
            changes["act_shard"] = "batch"
            changes["act_dp_axes"] = tuple(axes)
    if cfg.family == "moe":
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        g = min(cfg.moe_group_size, max(tokens // max(n_dp, 1), 1))
        changes["moe_group_size"] = g
        if cfg.moe_token_axes:
            # per-microbatch token count determines the group count G
            if shape.kind == "train":
                mb_tokens = (shape.global_batch // changes["num_microbatches"]) * shape.seq_len
            elif shape.kind == "prefill":
                mb_tokens = (shape.global_batch // max(cfg.prefill_microbatches, 1)) * shape.seq_len
            else:
                mb_tokens = shape.global_batch
            axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
            n_all = 1
            for a in axes:
                n_all *= mesh.shape[a]
            # shrink the group so G divides the full device count
            while g > 1 and (mb_tokens // g) % n_all != 0:
                g //= 2
            if g >= 1 and mb_tokens >= g and (mb_tokens // g) % n_all == 0:
                changes["moe_group_size"] = g
                changes["moe_token_axes"] = axes
            else:
                changes["moe_token_axes"] = ()
    return dataclasses.replace(cfg, **changes)


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               opt_override: Optional[OptimizerConfig] = None,
               cfg_override: Optional[ArchConfig] = None,
               compress_pod: bool = False):
    """Returns (lowered, compiled, summary_dict)."""
    shape = SHAPES[shape_name]
    cfg = cfg_override or get_arch(arch)
    if shape.name in cfg.skip_shapes:
        raise ValueError(f"{arch} skips {shape_name} (see DESIGN.md)")
    cfg = _adjust_cfg(cfg, shape, mesh)
    n_dev = mesh.devices.size

    t0 = time.time()
    with mesh:
        params_sds = S.params_sds(cfg)
        pspecs = shd.param_specs(cfg, mesh, params_sds)
        params_sh = shd.named(mesh, pspecs)

        if shape.kind == "train":
            opt_cfg = opt_override or default_opt_config(cfg)
            opt_init = make_opt_init(cfg, opt_cfg)
            opt_sds = jax.eval_shape(opt_init, params_sds)
            ospecs = shd.opt_state_specs(cfg, mesh, opt_sds, pspecs)
            opt_sh = shd.named(mesh, ospecs)
            batch_sh = shd.named(mesh, shd.batch_specs(cfg, mesh, shape))
            batch_sds = S.batch_specs_sds(cfg, shape)
            cp = None
            if compress_pod:
                cp = (mesh, shd.batch_specs(cfg, mesh, shape))
                # inside the shard_map body the pod axis is Manual: sharding
                # constraints in the loss may only reference Auto axes.
                cfg = dataclasses.replace(
                    cfg, act_dp_axes=tuple(
                        a for a in cfg.act_dp_axes if a != "pod"))
            step = make_train_step(
                cfg, opt_cfg, compress_pod=cp,
                grad_specs=shd.named(mesh, shd.grad_specs(cfg, mesh, params_sds)))
            metrics_sh = {
                "loss": NamedSharding(mesh, P()),
                "grad_norm": NamedSharding(mesh, P()),
                "lr": NamedSharding(mesh, P()),
            }
            jf = jax.jit(
                step,
                in_shardings=(params_sh, opt_sh, batch_sh),
                out_shardings=(params_sh, opt_sh, metrics_sh),
                donate_argnums=(0, 1),
            )
            lowered = jf.lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            batch_sh = shd.named(mesh, shd.batch_specs(cfg, mesh, shape))
            batch_sds = S.batch_specs_sds(cfg, shape)
            step = make_prefill_step(cfg)
            jf = jax.jit(
                step,
                in_shardings=(params_sh, batch_sh),
                out_shardings=NamedSharding(mesh, P()),
            )
            lowered = jf.lower(params_sds, batch_sds)
        else:  # decode
            cache_sds = S.cache_sds(cfg, shape)
            cspecs = shd.cache_specs(cfg, mesh, shape, cache_sds)
            cache_sh = shd.named(mesh, cspecs)
            tok_sds = S.decode_tokens_sds(cfg, shape)
            tok_sh = NamedSharding(mesh, shd.decode_tokens_spec(cfg, mesh, shape))
            step = make_serve_step(cfg)
            jf = jax.jit(
                step,
                in_shardings=(params_sh, cache_sh, tok_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            )
            lowered = jf.lower(params_sds, cache_sds, tok_sds)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    summary = summarize_compiled(compiled, n_dev)
    summary.update({
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": n_dev,
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "num_microbatches": cfg.num_microbatches,
        "act_shard": cfg.act_shard,
        "profile": shd.profile_of(cfg),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    })
    mem = summary.get("memory", {})
    if isinstance(mem.get("peak_bytes"), int):
        summary["fits_hbm"] = bool(mem["peak_bytes"] <= HBM_BYTES)
    return lowered, compiled, summary


def cells_for(arch: str):
    cfg = get_arch(arch)
    return [s.name for s in cfg.shapes()]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose JSON already exists")
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    archs = [args.arch] if args.arch else list(ARCHS)
    failures = []
    for mesh_name, mesh in meshes:
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            shapes = [args.shape] if args.shape else cells_for(arch)
            for shape_name in shapes:
                if shape_name in get_arch(arch).skip_shapes:
                    print(f"[skip] {arch} x {shape_name} (sub-quadratic required)")
                    continue
                path = os.path.join(outdir, f"{arch}__{shape_name}.json")
                if args.resume and os.path.exists(path):
                    print(f"[resume] {arch} x {shape_name} exists")
                    continue
                print(f"[lower+compile] {mesh_name}: {arch} x {shape_name} ...",
                      flush=True)
                try:
                    _, compiled, summary = lower_cell(arch, shape_name, mesh, mesh_name)
                    mem = summary["memory"]
                    print(
                        f"  ok: flops/dev={summary['flops_per_device']:.3e} "
                        f"bytes/dev={summary['bytes_per_device']:.3e} "
                        f"coll_wire/dev={summary['collective_wire_bytes_per_device']:.3e} "
                        f"peak={mem.get('peak_bytes', -1)/2**30:.2f}GiB "
                        f"fits={summary.get('fits_hbm')} "
                        f"compile={summary['compile_s']}s",
                        flush=True,
                    )
                    with open(path, "w") as f:
                        json.dump(summary, f, indent=1)
                    del compiled
                except Exception as e:
                    failures.append((mesh_name, arch, shape_name, repr(e)))
                    traceback.print_exc()
                    if not args.keep_going:
                        return 1
    if failures:
        print(f"{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        return 1
    print("dry-run complete: all cells lowered + compiled.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
