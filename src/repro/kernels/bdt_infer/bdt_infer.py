"""Pallas TPU kernel: quantized BDT ensemble inference.

This is the *performance* path for at-source classification (the fabric
kernel lut_eval is the *fidelity* path — bit-identical to the silicon). The
tree ensemble is evaluated node-parallel with one-hot matmuls instead of
pointer-chasing gathers, the TPU-native reformulation of tree traversal
(DESIGN.md §3):

  * all trees traverse simultaneously: the padded node axis P concatenates
    every tree's nodes (block-diagonal child matrices), the initial one-hot
    marks every root;
  * per depth step: route the one-hot mass left/right with two (B,P)x(P,P)
    MXU matmuls; leaves self-loop so depth-D traversal is exact for any
    tree shape;
  * feature lookup: 14 static broadcast-multiply-accumulate steps in int32
    on the VPU (raw fixed-point values up to 2^27 exceed f32's exact-int
    range, so the compare side stays integer);
  * leaf readout: value matmuls split into 14-bit halves so f32 stays
    integer-exact; scores come back as exact int32 raw fixed-point.

Block shapes: B_TILE x P with P = 128-padded node count (a depth-5 tree has
<= 63 nodes, so one lane group handles 2 trees' worth; the paper's single
tree uses P=128). Whole node table + child matrices live in VMEM:
P=128: 2 * 128x128x4B = 128 KiB. Batch is the only blocked axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(
    x_ref, featsel_ref, thr_ref, root_ref, left_ref, right_ref,
    vhi_ref, vlo_ref, out_ref, *, depth: int, n_features: int,
):
    x = x_ref[...]                       # (B, F) int32
    featsel = featsel_ref[...]           # (F, P) int32 0/1
    B = x.shape[0]
    P = featsel.shape[1]

    # fval[b, p] = x[b, feature(p)] — static MAC loop, exact int32.
    fval = jnp.zeros((B, P), jnp.int32)
    for f in range(n_features):
        fval = fval + x[:, f : f + 1] * featsel[f : f + 1, :]

    cond = (fval <= thr_ref[...]).astype(jnp.float32)      # (B, P)
    h = jnp.broadcast_to(root_ref[...], (B, P)).astype(jnp.float32)

    left = left_ref[...].astype(jnp.float32)
    right = right_ref[...].astype(jnp.float32)
    for _ in range(depth):
        go_l = h * cond
        go_r = h - go_l  # h * (1 - cond), one fewer multiply
        h = jax.lax.dot(go_l, left, preferred_element_type=jnp.float32)
        h = h + jax.lax.dot(go_r, right, preferred_element_type=jnp.float32)

    hi = jax.lax.dot(h, vhi_ref[...], preferred_element_type=jnp.float32)
    lo = jax.lax.dot(h, vlo_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = (hi.astype(jnp.int32) << 14) + lo.astype(jnp.int32)


def bdt_infer_pallas(
    x_raw: jnp.ndarray,      # (B, F) int32
    featsel: jnp.ndarray,    # (F, P) int32
    thr: jnp.ndarray,        # (1, P) int32  (+inf-like for leaves/pad)
    root_onehot: jnp.ndarray,  # (1, P) f32
    left: jnp.ndarray,       # (P, P) f32 0/1 (leaves self-loop)
    right: jnp.ndarray,      # (P, P) f32 0/1
    value_hi: jnp.ndarray,   # (P, 128) f32 — leaf value >> 14, col 0
    value_lo: jnp.ndarray,   # (P, 128) f32 — leaf value & 0x3FFF, col 0
    *,
    depth: int,
    batch_tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (B, 128) int32; column 0 holds Σ_trees leaf_value (no f0)."""
    B, F = x_raw.shape
    P = featsel.shape[1]
    assert B % batch_tile == 0

    kernel = functools.partial(_kernel, depth=depth, n_features=F)
    return pl.pallas_call(
        kernel,
        grid=(B // batch_tile,),
        in_specs=[
            pl.BlockSpec((batch_tile, F), lambda b: (b, 0)),
            pl.BlockSpec((F, P), lambda b: (0, 0)),
            pl.BlockSpec((1, P), lambda b: (0, 0)),
            pl.BlockSpec((1, P), lambda b: (0, 0)),
            pl.BlockSpec((P, P), lambda b: (0, 0)),
            pl.BlockSpec((P, P), lambda b: (0, 0)),
            pl.BlockSpec((P, 128), lambda b: (0, 0)),
            pl.BlockSpec((P, 128), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((batch_tile, 128), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 128), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(x_raw, featsel, thr, root_onehot, left, right, value_hi, value_lo)
