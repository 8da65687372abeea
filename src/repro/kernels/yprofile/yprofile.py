"""Pallas TPU kernel: smart-pixel frame -> feature reduction (front end).

Completes the on-device readout path: raw charge frames stream in over the
data plane, this kernel folds (T, Y, X) -> the 13-bin y-profile + y0, and
the result feeds bdt_infer / lut_eval without a host round-trip.

Shape strategy: the physical frame is tiny (8x13x21 = 2184 floats), far
below lane granularity — so the kernel works on the FLATTENED event layout
(B_TILE, T*Y*X padded to a 128 multiple) and reduces with a precomputed
one-hot fold matrix (T*Y*X_pad, Y_pad): charge cell (t, y, x) contributes
to profile bin y. The reduction is a single MXU matmul per tile — the same
"spatial structure -> dense contraction" adaptation as lut_eval
(DESIGN.md §3); zero suppression and the ke- scaling run on the VPU.

VMEM per tile: frames 256 x 2304 x 4B = 2.3 MiB + fold 2304 x 128 x 4B
= 1.2 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _fold_dot(flat, fold):
    """Charge (B, TYX_pad) x one-hot fold (TYX_pad, Y_pad), at f32.

    Charges are fractional electrons in the thousands: a bf16 operand pass
    would move a profile bin by many electrons, so the MXU is asked for
    full f32 precision explicitly.
    """
    return jax.lax.dot(flat, fold, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _kernel(frames_ref, fold_ref, y0_ref, out_ref, *, threshold: float):
    flat = frames_ref[...]                      # (B, TYX_pad)
    fold = fold_ref[...]                        # (TYX_pad, Y_pad)
    prof = _fold_dot(flat, fold)
    prof = jnp.maximum(prof, 0.0)
    prof = jnp.where(prof > threshold, prof, 0.0) / 1000.0
    # slot y0 (um) into the first padding column after the Y bins
    y0col = y0_ref[...]                         # (B, 128) with y0 in col 0
    out_ref[...] = prof + y0col


def yprofile_pallas(
    frames_flat: jnp.ndarray,   # (B, TYX_pad) f32
    fold: jnp.ndarray,          # (TYX_pad, Y_pad=128) f32 one-hot
    y0_cols: jnp.ndarray,       # (B, 128) f32 — y0 value in column n_y
    *,
    threshold: float,
    batch_tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, TYX = frames_flat.shape
    assert B % batch_tile == 0 and TYX % 128 == 0
    kernel = functools.partial(_kernel, threshold=threshold)
    return pl.pallas_call(
        kernel,
        grid=(B // batch_tile,),
        in_specs=[
            pl.BlockSpec((batch_tile, TYX), lambda b: (b, 0)),
            pl.BlockSpec((TYX, 128), lambda b: (0, 0)),
            pl.BlockSpec((batch_tile, 128), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((batch_tile, 128), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 128), jnp.float32),
        interpret=interpret,
        name="yprofile",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(frames_flat, fold, y0_cols)


def _kernel_stacked(frames_ref, fold_ref, y0_ref, out_ref, *, threshold: float):
    flat = frames_ref[0]                        # (B, TYX_pad)
    fold = fold_ref[...]                        # (TYX_pad, Y_pad)
    prof = _fold_dot(flat, fold)
    prof = jnp.maximum(prof, 0.0)
    prof = jnp.where(prof > threshold, prof, 0.0) / 1000.0
    out_ref[0] = prof + y0_ref[0]


def yprofile_pallas_stacked(
    frames_flat: jnp.ndarray,   # (C, B, TYX_pad) f32 — chip-batched frames
    fold: jnp.ndarray,          # (TYX_pad, Y_pad=128) f32 one-hot, shared
    y0_cols: jnp.ndarray,       # (C, B, 128) f32 — y0 value in column n_y
    *,
    threshold: float,
    batch_tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chip-batched featurization: C sensors' frame streams reduced in ONE
    dispatch, the front half of the fused readout frontend
    (kernels/frontend.py). Grid (C, B//tile) with both axes parallel —
    same shape strategy as the chip axis of lut_eval_pallas_stacked, and
    the per-tile dot is identical to the single-chip kernel's, so the
    stacked path is bit-identical to C separate yprofile_pallas calls.
    """
    C, B, TYX = frames_flat.shape
    assert B % batch_tile == 0 and TYX % 128 == 0
    kernel = functools.partial(_kernel_stacked, threshold=threshold)
    return pl.pallas_call(
        kernel,
        grid=(C, B // batch_tile),
        in_specs=[
            pl.BlockSpec((1, batch_tile, TYX), lambda c, b: (c, b, 0)),
            pl.BlockSpec((TYX, 128), lambda c, b: (0, 0)),
            pl.BlockSpec((1, batch_tile, 128), lambda c, b: (c, b, 0)),
        ],
        out_specs=pl.BlockSpec((1, batch_tile, 128), lambda c, b: (c, b, 0)),
        out_shape=jax.ShapeDtypeStruct((C, B, 128), jnp.float32),
        interpret=interpret,
        name="yprofile",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(frames_flat, fold, y0_cols)
