"""Pallas TPU kernel: batched eFPGA fabric evaluation.

The fabric's *spatial* parallelism (hundreds of LUT4s switching per clock)
maps to TPU as *batch* parallelism over events (DESIGN.md §3). A LUT4 read
is a 16-entry gather; random gathers are hostile to the TPU vector unit, so
routing is reformulated as a dense one-hot contraction on the MXU and the
lookup as a lane-dense shift on the VPU:

  stage 1 (routing):  ins = V @ S_l      — selecting each LUT's 4 input nets
                      is a (B,N) x (N,4M) bf16 matmul with a 0/1 matrix;
  stage 2 (lookup):   out = (T_l >> idx) & 1 — each LUT's 16 truth-table
                      bits packed into one int32 word, shifted by the
                      4-bit address idx.

Memory layout: net values live in a VMEM-resident (B_TILE, N) f32 buffer.
N is the *segmented* padded net count — [consts+inputs | level 0 | level 1
| ...] with every segment 128-lane aligned, so each level's write is a
statically-aligned dynamic slice (no sub-lane stores). The const0/const1
columns are part of the input segment (the ops wrapper prepends them), so
initialization is a single aligned block copy.

Grid: (chips, batch_tiles, n_levels); chip and batch axes are parallel,
the level axis is "arbitrary" (sequential) and revisits the same output
block, which Pallas keeps resident in VMEM across the level steps — the
standard accumulator pattern. The chip axis serves a *multi-chip readout
server* (launch/readout_server.py): N configured fabrics, padded to one
shared geometry, score their event streams in a single dispatch. Per-level
write offsets are scalar-prefetched (SMEM) so the dynamic slice start is
known to the DMA engine up front.

VMEM budget per step (BDT module, N=2048, M=128, B=128):
  V 128x2048x4B = 1.0 MiB, S block 2048x512x2B (bf16) = 2.0 MiB,
  table words 128x4B => ~3 MiB, under Mosaic's default 16 MiB scoped limit.
Deep ensembles on efpga_28nm_xl (M=256, N up to ~6.5k) need more; the
scoped limit is sized from the blocks (``_compiler_params``).

The selection matmul does ~B*N*4M flops per level — far more "arithmetic"
than the fabric's actual logic, but it is dense MXU work at 197 TFLOP/s
instead of serialized gathers. What it buys on the chip against the
bit-sliced layout is not measured.

Banded variant (``lut_eval_pallas_banded_stacked``): levelized netlists
have bounded fan-in reach — a level-l LUT reads only primary inputs plus a
window of K preceding levels (core.netlist.fanin_reach). The dense kernel's
per-level matmul nevertheless pays for the *full* padded net buffer
(N = in_seg + L*m_pad), so total routing cost grows ~quadratically with
level count. The banded kernel's selection tensor has only
``in_seg + K*m_pad`` rows per level; the kernel concatenates the input
segment with a scalar-prefetched dynamic window of the net buffer
([win_base[l], win_base[l]+K*m_pad), always 128-aligned) and matmuls
against that — O(L*(in_seg+K*m_pad)*4M), near-linear in depth when K << L.
Levels earlier than the window's written prefix read zero-initialized
columns whose selection rows are all-zero, so the contraction is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _level_out(v, sel, tbl, m_pad: int):
    """One level of every LUT: route, then look the 4-bit address up.

    ``v`` (B, rows) holds 0/1 net values and ``sel`` is a 0/1 one-hot, so
    the bf16 routing matmul is exact and never needs an f32 copy of the
    selection block. ``tbl`` (1, M) int32 packs each LUT's 16 truth-table
    bits, so the lookup is one lane-dense shift: a (B, M, 16) one-hot
    would pad its 16-wide minor axis to 128 lanes in VMEM.
    """
    ins = jax.lax.dot(v.astype(jnp.bfloat16), sel,
                      preferred_element_type=jnp.float32)  # (B, 4*M)
    idx = (
        ins[:, :m_pad]
        + 2.0 * ins[:, m_pad : 2 * m_pad]
        + 4.0 * ins[:, 2 * m_pad : 3 * m_pad]
        + 8.0 * ins[:, 3 * m_pad :]
    ).astype(jnp.int32)                                 # (B, M)
    return (jnp.right_shift(tbl, idx) & 1).astype(jnp.float32)


def _table_words(tables: jnp.ndarray) -> jnp.ndarray:
    """(C, L, M, 16) 0/1 truth tables -> (C, L, 1, M) int32 bit masks
    (bit k = table entry k), the kernels' lookup operand."""
    bits = (tables > 0.5).astype(jnp.int32) << jnp.arange(16, dtype=jnp.int32)
    return jnp.sum(bits, axis=-1, dtype=jnp.int32)[:, :, None, :]


def _compiler_params(*, batch_tile, in_seg, n_nets_pad, sel_rows, m_pad):
    """Grid semantics plus a scoped-VMEM budget sized to the blocks.

    Mosaic's default scoped limit is 16 MiB; a deep ensemble's selection
    block alone can exceed it. Budget the double-buffered blocks and as
    much again for in-kernel temporaries, never below the default.
    """
    block_bytes = (4 * batch_tile * (in_seg + n_nets_pad)   # bits, net buffer
                   + 2 * sel_rows * 4 * m_pad               # bf16 selection
                   + 4 * 8 * m_pad)                         # table words
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(16 << 20, 4 * block_bytes),
    )


def _kernel(base_ref, bits_ref, sel_ref, tbl_ref, vals_ref, *, in_seg: int, m_pad: int):
    l = pl.program_id(2)

    # First level-visit of a (chip, batch-tile) cell: init the net buffer.
    @pl.when(l == 0)
    def _init():
        vals_ref[...] = jnp.zeros_like(vals_ref)
        vals_ref[0, :, : in_seg] = bits_ref[0]  # [const0, const1, inputs, pad]

    out = _level_out(vals_ref[0], sel_ref[0, 0], tbl_ref[0, 0], m_pad)
    # Mosaic must see that the lane offset is tile-aligned; the packer
    # makes every level base a multiple of 128.
    vals_ref[0, :, pl.dslice(pl.multiple_of(base_ref[l], 128), m_pad)] = out


def lut_eval_pallas_stacked(
    bits_ext: jnp.ndarray,   # (C, B, in_seg) f32 — [const0, const1, inputs, 0-pad]
    sel: jnp.ndarray,        # (C, L, N, 4*M) 0/1 selection (bf16)
    tables: jnp.ndarray,     # (C, L, M, 16) f32
    level_base: jnp.ndarray, # (L,) int32 — 128-aligned write offset per level
    *,
    n_nets_pad: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chip-batched fabric evaluation: C configured chips x B events in ONE
    dispatch. Returns the padded net-value tensor (C, B, N) f32.

    The chip axis is an outer parallel grid dimension: each (chip, batch
    tile) cell walks the levels sequentially over its own VMEM-resident net
    buffer, streaming that chip's selection/table blocks. All chips share
    one padded geometry (L, N, M) — see ops.pack_fabrics — so swapping any
    chip's bitstream is an array swap with no recompile.
    """
    C, B, in_seg = bits_ext.shape
    Cs, L, N, M4 = sel.shape
    M = M4 // 4
    assert Cs == C, (Cs, C)
    assert N == n_nets_pad and in_seg % 128 == 0 and M % 128 == 0
    assert B % batch_tile == 0, (B, batch_tile)

    kernel = functools.partial(_kernel, in_seg=in_seg, m_pad=M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C, B // batch_tile, L),
        in_specs=[
            pl.BlockSpec((1, batch_tile, in_seg), lambda c, b, l, base: (c, b, 0)),
            pl.BlockSpec((1, 1, N, M4), lambda c, b, l, base: (c, l, 0, 0)),
            pl.BlockSpec((1, 1, 1, M), lambda c, b, l, base: (c, l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, batch_tile, N), lambda c, b, l, base: (c, b, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, B, N), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(
            batch_tile=batch_tile, in_seg=in_seg, n_nets_pad=N, sel_rows=N,
            m_pad=M),
    )(level_base, bits_ext.astype(jnp.float32), sel, _table_words(tables))


def _banded_kernel(
    base_ref, win_ref, bits_ref, sel_ref, tbl_ref, vals_ref,
    *, in_seg: int, m_pad: int, band_m: int,
):
    """Banded level step: route from [input segment | K-level window] only.

    The net-value buffer keeps the full dense layout (writes land at
    base_ref[l] exactly like the dense kernel), but the selection matmul's
    row space is the band — win_ref[l] = in_seg + max(0, l-K)*m_pad points
    the window at the K levels preceding l.
    """
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        vals_ref[...] = jnp.zeros_like(vals_ref)
        vals_ref[0, :, : in_seg] = bits_ref[0]  # [const0, const1, inputs, pad]

    v_in = vals_ref[0, :, :in_seg]                      # (B, in_seg)
    v_win = vals_ref[0, :, pl.dslice(pl.multiple_of(win_ref[l], 128), band_m)]
    v = jnp.concatenate([v_in, v_win], axis=-1)         # (B, in_seg + K*M)
    out = _level_out(v, sel_ref[0, 0], tbl_ref[0, 0], m_pad)
    vals_ref[0, :, pl.dslice(pl.multiple_of(base_ref[l], 128), m_pad)] = out


def lut_eval_pallas_banded_stacked(
    bits_ext: jnp.ndarray,   # (C, B, in_seg) f32 — [const0, const1, inputs, 0-pad]
    sel: jnp.ndarray,        # (C, L, in_seg + K*M, 4*M) 0/1 banded selection (bf16)
    tables: jnp.ndarray,     # (C, L, M, 16) f32
    level_base: jnp.ndarray, # (L,) int32 — 128-aligned write offset per level
    win_base: jnp.ndarray,   # (L,) int32 — 128-aligned window read offset per level
    *,
    n_nets_pad: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chip-batched *banded* fabric evaluation.

    Identical contract to ``lut_eval_pallas_stacked`` (returns the full
    padded net-value tensor (C, B, N) f32) but each level's routing matmul
    touches only ``in_seg + K*m_pad`` net columns, K the shared fan-in
    reach of the stacked configs (ops.pack_fabrics computes it and falls
    back to the dense kernel when the band wouldn't be cheaper).
    """
    C, B, in_seg = bits_ext.shape
    Cs, L, n_rows, M4 = sel.shape
    M = M4 // 4
    band_m = n_rows - in_seg
    assert Cs == C, (Cs, C)
    assert in_seg % 128 == 0 and M % 128 == 0 and band_m % M == 0
    assert 0 < band_m <= n_nets_pad - in_seg, (band_m, n_nets_pad, in_seg)
    assert B % batch_tile == 0, (B, batch_tile)

    kernel = functools.partial(
        _banded_kernel, in_seg=in_seg, m_pad=M, band_m=band_m
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(C, B // batch_tile, L),
        in_specs=[
            pl.BlockSpec(
                (1, batch_tile, in_seg), lambda c, b, l, base, win: (c, b, 0)
            ),
            pl.BlockSpec(
                (1, 1, n_rows, M4), lambda c, b, l, base, win: (c, l, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, 1, M), lambda c, b, l, base, win: (c, l, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, batch_tile, n_nets_pad), lambda c, b, l, base, win: (c, b, 0)
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, B, n_nets_pad), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(
            batch_tile=batch_tile, in_seg=in_seg, n_nets_pad=n_nets_pad,
            sel_rows=n_rows, m_pad=M),
    )(level_base, win_base, bits_ext.astype(jnp.float32), sel,
      _table_words(tables))


def lut_eval_pallas(
    bits_ext: jnp.ndarray,   # (B, in_seg) f32 — [const0, const1, inputs, 0-pad]
    sel: jnp.ndarray,        # (L, N, 4*M) 0/1 selection (bf16)
    tables: jnp.ndarray,     # (L, M, 16) f32
    level_base: jnp.ndarray, # (L,) int32 — 128-aligned write offset per level
    *,
    n_nets_pad: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-chip evaluation: the C=1 slice of the stacked kernel.
    Returns the full padded net-value matrix (B, N) f32."""
    return lut_eval_pallas_stacked(
        bits_ext[None],
        sel[None],
        tables[None],
        level_base,
        n_nets_pad=n_nets_pad,
        batch_tile=batch_tile,
        interpret=interpret,
    )[0]


def lut_eval_pallas_banded(
    bits_ext: jnp.ndarray,   # (B, in_seg) f32
    sel: jnp.ndarray,        # (L, in_seg + K*M, 4*M) banded selection (bf16)
    tables: jnp.ndarray,     # (L, M, 16) f32
    level_base: jnp.ndarray, # (L,) int32
    win_base: jnp.ndarray,   # (L,) int32
    *,
    n_nets_pad: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-chip banded evaluation: the C=1 slice of the banded kernel."""
    return lut_eval_pallas_banded_stacked(
        bits_ext[None],
        sel[None],
        tables[None],
        level_base,
        win_base,
        n_nets_pad=n_nets_pad,
        batch_tile=batch_tile,
        interpret=interpret,
    )[0]
