"""Ahead-of-time compiles of the served path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets Mosaic and XLA compile (not run) the kernels at
served shapes. Interpret mode on CPU cannot see what Mosaic refuses —
unaligned dynamic lane slices, scoped-VMEM overflow — so these tests
guard it. The topology is described inside a fixture (never at import):
only one process at a time may load the TPU library, and every xdist
worker imports this file.

Served shapes: 4 sensors x 2048 events per dispatch, and the paper BDT's
packed geometry (13 levels of <= 128 LUTs, 224 input bits, 28 output
bits: in_seg 256, 1920 padded nets, fan-in band 7 -> 1152 rows).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

C, B, TILE = 4, 2048, 128
# the paper BDT stack (ops.pack_fabrics of the four tenant chips)
L, M, IN_SEG, N_IN, N_OUT, BAND_K = 13, 128, 256, 224, 28, 7
N_PAD = IN_SEG + L * M
# deep ensembles on efpga_28nm_xl (4 trees of depth 3, ripple or tree
# adders): (levels, band rows or None for dense), m_pad 256
DEEP = {"dense_ripple": (25, None), "banded_ripple": (25, 128 + 18 * 256),
        "dense_tree": (19, None), "banded_tree": (19, 128 + 6 * 256)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower and compile for the described chip; raises what Mosaic/XLA
    would raise on the real one."""
    return jax.jit(fn).lower(*args).compile()


def test_yprofile_compiles_at_served_shape(one_chip):
    from repro.kernels.yprofile.ops import TYX_PAD
    from repro.kernels.yprofile.yprofile import yprofile_pallas_stacked

    s = lambda *a: _shape(one_chip, *a)
    compiled = _compile(
        lambda f, fold, y0: yprofile_pallas_stacked(
            f, fold, y0, threshold=800.0, batch_tile=TILE),
        s((C, B, TYX_PAD), jnp.float32), s((TYX_PAD, 128), jnp.float32),
        s((C, B, 128), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


def _lut_eval_args(one_chip, n_chips, levels, m_pad, in_seg, rows):
    s = lambda *a: _shape(one_chip, *a)
    return [s((n_chips, B, in_seg), jnp.float32),
            s((n_chips, levels, rows, 4 * m_pad), jnp.bfloat16),
            s((n_chips, levels, m_pad, 16), jnp.float32),
            s((levels,), jnp.int32)]


@pytest.mark.parametrize("layout", ["dense", "banded"])
@pytest.mark.parametrize("n_replicas", [1, 3])
def test_lut_eval_compiles_at_paper_geometry(one_chip, layout, n_replicas):
    """The dense and banded matmul kernels at the paper BDT's geometry —
    the kernels whose 128-lane dynamic offsets Mosaic once refused."""
    from repro.kernels.lut_eval.lut_eval import (
        lut_eval_pallas_banded_stacked, lut_eval_pallas_stacked)

    n = C * n_replicas
    if layout == "dense":
        fn = lambda *a: lut_eval_pallas_stacked(
            *a, n_nets_pad=N_PAD, batch_tile=TILE)
        args = _lut_eval_args(one_chip, n, L, M, IN_SEG, N_PAD)
    else:
        fn = lambda *a: lut_eval_pallas_banded_stacked(
            *a, n_nets_pad=N_PAD, batch_tile=TILE)
        args = _lut_eval_args(one_chip, n, L, M, IN_SEG, IN_SEG + BAND_K * M)
        args.append(_shape(one_chip, (L,), jnp.int32))
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


@pytest.mark.parametrize("variant", sorted(DEEP))
def test_lut_eval_compiles_at_deep_ensemble_geometry(one_chip, variant):
    """Selection blocks past Mosaic's default 16 MiB scoped VMEM: the
    kernel must size its own VMEM budget."""
    from repro.kernels.lut_eval.lut_eval import (
        lut_eval_pallas_banded_stacked, lut_eval_pallas_stacked)

    levels, rows = DEEP[variant]
    m_pad, in_seg = 256, 128
    n_pad = in_seg + levels * m_pad
    if rows is None:
        fn = lambda *a: lut_eval_pallas_stacked(
            *a, n_nets_pad=n_pad, batch_tile=TILE)
        args = _lut_eval_args(one_chip, 1, levels, m_pad, in_seg, n_pad)
    else:
        fn = lambda *a: lut_eval_pallas_banded_stacked(
            *a, n_nets_pad=n_pad, batch_tile=TILE)
        args = _lut_eval_args(one_chip, 1, levels, m_pad, in_seg, rows)
        args.append(_shape(one_chip, (levels,), jnp.int32))
    _compile(fn, *args)


def test_bitsliced_eval_words_compiles_at_served_shape(one_chip):
    from repro.kernels.lut_eval.bitsliced import eval_words

    s = lambda *a: _shape(one_chip, *a)
    _compile(eval_words,
             s((C, L, M, 4), jnp.int32), s((C, L, M, 16), jnp.float32),
             s((C, N_OUT), jnp.int32), s((C, B // 32, IN_SEG), jnp.uint32))


def _plan_shapes(sharding):
    from repro.kernels.frontend import _PLAN_KEYS

    i32, f32 = jnp.int32, jnp.float32
    shapes = {
        "feat_idx": ((C, N_IN), i32), "bit_idx": ((C, N_IN), i32),
        "bit_valid": ((C, N_IN), i32), "out_weight": ((C, N_OUT), i32),
        "threshold_raw": ((C,), i32), "scale": ((C,), f32),
        "rnd_off": ((C,), f32), "wrap_mask": ((C,), i32),
        "sign_bit": ((C,), i32), "sat_lo": ((C,), i32),
        "sat_hi": ((C,), i32),
    }
    assert set(shapes) == set(_PLAN_KEYS)
    return {k: _shape(sharding, *v) for k, v in shapes.items()}


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("mode", ["plain", "tmr_sparse"])
def test_fused_score_frames_compiles(topo, n_devices, mode):
    """The whole fused frames -> trigger dispatch (bit-sliced layout, the
    server's default), on one chip and sharded over the 2x2 host."""
    from repro.data.smartpixel import N_T, N_X, N_Y
    from repro.kernels.frontend import _score_frames

    R = 3 if mode == "tmr_sparse" else 1
    mesh = Mesh(np.asarray(topo.devices[:n_devices]), ("chips",))
    rep = NamedSharding(mesh, P())
    chips = NamedSharding(mesh, P("chips"))
    s = lambda *a: _shape(rep, *a)
    args = (
        _shape(chips, (C, B, N_T, N_Y, N_X), jnp.float32),   # frames
        _shape(chips, (C, B), jnp.float32),                  # y0
        None,                                                # sel
        s((R * C, L, M, 16), jnp.float32),                   # tables
        s((L,), jnp.int32), s((L,), jnp.int32),              # bases
        s((R * C, N_OUT), jnp.int32),                        # output nets
        _plan_shapes(rep),
        _shape(chips, (C, B), jnp.bool_),                    # valid
        s((R * C, L, M, 4), jnp.int32),                      # src
    )
    compiled = _score_frames.lower(
        *args, mesh=mesh, n_replicas=R, threshold_electrons=800.0,
        n_inputs=N_IN, in_seg=IN_SEG, n_nets_pad=N_PAD, batch_tile=TILE,
        interpret=False, sparse=(mode == "tmr_sparse")).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the yprofile kernel
