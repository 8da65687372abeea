"""At-source compression: int8 quantization bounds + compressed all-reduce."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # degrade to the seeded sweep shim (tests/_propshim.py)
    from tests._propshim import given, settings, strategies as st

from repro.parallel.compression import (
    dequantize_int8, dequantize_kv, quantize_int8, quantize_kv,
    sparse_trigger_pack, sparse_trigger_pack_jit, sparse_trigger_pack_words,
    sparse_trigger_unpack, WireFormatError,
)


@given(scale=st.floats(1e-3, 1e3), seed=st.integers(0, 100))
@settings(max_examples=50, deadline=None)
def test_int8_error_bound(scale, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, scale, 256).astype(np.float32))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    bound = float(jnp.max(jnp.abs(x))) / 254 + 1e-6
    assert err.max() <= bound * 1.01


def test_int8_wire_format():
    q, s = quantize_int8(jnp.ones((4, 4)))
    assert q.dtype == jnp.int8
    assert s.shape == ()


@given(seed=st.integers(0, 10_000), c=st.integers(1, 5), b=st.integers(1, 64),
       p_keep=st.floats(0.0, 1.0))
@settings(max_examples=30, deadline=None)
def test_sparse_trigger_roundtrip_identity(seed, c, b, p_keep):
    """compress -> decompress is the identity on arbitrary keep masks:
    unpack(pack(score, keep)) == (score * keep, keep) — the sparse host
    link loses nothing about kept events and nothing leaks about dropped
    ones."""
    rng = np.random.default_rng(seed)
    score = rng.integers(-(2 ** 20), 2 ** 20, (c, b)).astype(np.int32)
    keep = rng.random((c, b)) < p_keep
    count, idx, vals = jax.jit(sparse_trigger_pack)(
        jnp.asarray(score), jnp.asarray(keep))
    n = int(np.asarray(count))
    assert n == int(keep.sum())
    # padded region is -1/0; the count-prefix is what crosses the wire
    idx_np = np.asarray(idx)
    assert (idx_np[n:] == -1).all() and (np.asarray(vals)[n:] == 0).all()
    assert (np.diff(idx_np[:n]) > 0).all()  # ascending flat indices
    got_score, got_keep = sparse_trigger_unpack(idx, vals, score.shape)
    np.testing.assert_array_equal(got_keep, keep)
    np.testing.assert_array_equal(got_score, score * keep)
    # the count-sliced wire form round-trips identically
    got_score2, got_keep2 = sparse_trigger_unpack(
        idx_np[:n], np.asarray(vals)[:n], score.shape)
    np.testing.assert_array_equal(got_keep2, keep)
    np.testing.assert_array_equal(got_score2, score * keep)


def test_sparse_trigger_all_keep_and_all_drop():
    score = np.arange(12, dtype=np.int32).reshape(3, 4) - 5
    for keep in (np.ones((3, 4), bool), np.zeros((3, 4), bool)):
        count, idx, vals = sparse_trigger_pack_jit(
            jnp.asarray(score), jnp.asarray(keep))
        s, k = sparse_trigger_unpack(idx, vals, score.shape)
        np.testing.assert_array_equal(k, keep)
        np.testing.assert_array_equal(s, score * keep)
        assert int(np.asarray(count)) == int(keep.sum())


# --------------------------------------------- word-domain sparse egress
def _word_form(score, keep):
    """Event-domain (C, B) -> the word-domain egress inputs, zero/False
    padded to the 32-event word boundary: (keep_w (C, W) uint32, lane
    scores (C, W, 32) int32, padded event-domain (score, keep))."""
    from repro.kernels.lut_eval import bitsliced

    C, B = score.shape
    W = max(-(-B // 32), 1)
    sp = np.zeros((C, W * 32), np.int32)
    sp[:, :B] = score
    kp = np.zeros((C, W * 32), bool)
    kp[:, :B] = keep
    keep_w = jax.jit(bitsliced.mask_words)(jnp.asarray(kp))
    return keep_w, jnp.asarray(sp.reshape(C, W, 32)), sp, kp


@given(seed=st.integers(0, 10_000), c=st.integers(1, 4),
       b=st.integers(1, 130), p_keep=st.floats(0.0, 1.0))
@settings(max_examples=30, deadline=None)
def test_sparse_word_pack_matches_event_oracle(seed, c, b, p_keep):
    """The word-domain popcount prefix-sum compaction reproduces the
    event-domain ``sparse_trigger_pack`` wire format byte for byte —
    count, ascending -1-padded flat indices, 0-padded scores — for
    arbitrary keep masks, full-range int32 scores and batch sizes off
    the 32-event word boundary."""
    rng = np.random.default_rng(seed)
    score = rng.integers(-(2 ** 31), 2 ** 31, (c, b),
                         dtype=np.int64).astype(np.int32)
    keep = rng.random((c, b)) < p_keep
    keep_w, scores_w, sp, kp = _word_form(score, keep)
    count0, idx0, vals0 = sparse_trigger_pack_jit(
        jnp.asarray(sp), jnp.asarray(kp))
    count1, idx1, vals1 = jax.jit(sparse_trigger_pack_words)(
        keep_w, scores_w)
    assert int(np.asarray(count1)) == int(np.asarray(count0)) \
        == int(keep.sum())
    np.testing.assert_array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_array_equal(np.asarray(vals0), np.asarray(vals1))
    # round-trip through the host inverse recovers exactly the kept set
    s2, k2 = sparse_trigger_unpack(np.asarray(idx1), np.asarray(vals1),
                                   sp.shape)
    np.testing.assert_array_equal(k2[:, :b], keep)
    np.testing.assert_array_equal(s2[:, :b], score * keep)
    assert not k2[:, b:].any()      # padding lanes never ship


def test_sparse_word_pack_all_keep_all_drop_and_tails():
    """The degenerate masks on word-aligned AND ragged batch sizes: all
    keep ships everything in order, all drop ships the empty prefix."""
    for b in (1, 31, 32, 33, 64, 95):
        score = (np.arange(2 * b, dtype=np.int32).reshape(2, b) - b)
        for keep_all in (True, False):
            keep = np.full((2, b), keep_all)
            keep_w, scores_w, sp, kp = _word_form(score, keep)
            count, idx, vals = sparse_trigger_pack_words(keep_w, scores_w)
            assert int(np.asarray(count)) == int(keep.sum()), (b, keep_all)
            s2, k2 = sparse_trigger_unpack(
                np.asarray(idx), np.asarray(vals), sp.shape)
            np.testing.assert_array_equal(k2, kp, err_msg=f"{b} {keep_all}")
            np.testing.assert_array_equal(s2, sp * kp,
                                          err_msg=f"{b} {keep_all}")


def test_sparse_unpack_rejects_oversized_count_prefix():
    """Regression: a count prefix larger than the record buffer used to
    be silently clamped by numpy slicing — a corrupt/forged wire count
    produced a truncated dense batch with no error. It must now raise
    the named WireFormatError family (what net/protocol.py surfaces as
    FieldBoundsError) before any scatter happens."""
    idx = np.array([0, 2, -1, -1], np.int32)
    vals = np.array([5, 7, 0, 0], np.int32)
    # valid counts, including the exact buffer size, still work
    for count in (0, 1, 2, 4):
        s, k = sparse_trigger_unpack(idx, vals, (4,), count=count)
        assert int(k.sum()) <= count
    s, k = sparse_trigger_unpack(idx, vals, (4,), count=2)
    np.testing.assert_array_equal(k, [True, False, True, False])
    np.testing.assert_array_equal(s, [5, 0, 7, 0])
    for bad in (5, 6, 1 << 20, -1):
        with pytest.raises(WireFormatError, match="count prefix"):
            sparse_trigger_unpack(idx, vals, (4,), count=bad)


def test_sparse_unpack_rejects_out_of_range_indices():
    """An index at/above prod(shape), or below the -1 padding sentinel,
    is corrupt wire data: named error, not a numpy IndexError or a
    silent negative-index aliasing scatter."""
    with pytest.raises(WireFormatError, match="outside dense shape"):
        sparse_trigger_unpack(np.array([0, 4]), np.array([1, 1]), (2, 2))
    with pytest.raises(WireFormatError, match="outside dense shape"):
        sparse_trigger_unpack(np.array([-2, 1]), np.array([1, 1]), (2, 2))
    # boundary: the largest valid flat index and the padding sentinel
    s, k = sparse_trigger_unpack(np.array([3, -1]), np.array([9, 0]), (2, 2))
    np.testing.assert_array_equal(s, [[0, 0], [0, 9]])
    assert int(k.sum()) == 1


def test_sparse_unpack_rejects_mismatched_buffers():
    with pytest.raises(WireFormatError, match="disagree"):
        sparse_trigger_unpack(np.array([0, 1, 2]), np.array([1, 2]), (4,))


def test_kv_quantization_per_vector():
    rng = np.random.default_rng(0)
    kv = jnp.asarray(rng.normal(0, 1, (2, 16, 4, 32)).astype(np.float32))
    q, s = quantize_kv(kv)
    assert q.dtype == jnp.int8 and s.shape == (2, 16, 4, 1)
    back = np.asarray(dequantize_kv(q, s, jnp.float32))
    rel = np.abs(back - np.asarray(kv)).max() / np.abs(np.asarray(kv)).max()
    assert rel < 0.01


_POD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.compression import make_compressed_value_and_grad

from repro.launch.mesh import make_mesh_compat
mesh = make_mesh_compat((2, 2, 2), ("pod", "data", "model"))

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)

rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.normal(0, 1, (8, 4)).astype(np.float32))}
batch = {"x": jnp.asarray(rng.normal(0, 1, (16, 8)).astype(np.float32)),
         "y": jnp.asarray(rng.normal(0, 1, (16, 4)).astype(np.float32))}
specs = {"x": P("pod", None), "y": P("pod", None)}

with mesh:
    f = jax.jit(make_compressed_value_and_grad(loss_fn, mesh, specs))
    loss_c, grads_c = f(params, batch)
    loss_e, grads_e = jax.jit(jax.value_and_grad(loss_fn))(params, batch)

assert abs(float(loss_c) - float(loss_e)) < 1e-4, (loss_c, loss_e)
gc, ge = np.asarray(grads_c["w"]), np.asarray(grads_e["w"])
# int8-per-pod-partial error bound: each pod's partial grad quantized
bound = 2 * np.abs(ge).max() / 254 + 1e-5
assert np.abs(gc - ge).max() < bound * 4, (np.abs(gc - ge).max(), bound)
print("COMPRESSED_ALLREDUCE_OK", np.abs(gc - ge).max())
"""


@pytest.mark.slow
def test_compressed_gradient_allreduce_multipod():
    """Runs in a subprocess so the 8-fake-device flag never leaks into this
    test process (tests must keep seeing 1 device)."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _POD_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPRESSED_ALLREDUCE_OK" in r.stdout
