"""At-source compression before the expensive link (the paper's core insight
carried into the distributed runtime — DESIGN.md §3).

The paper reduces detector data *on the sensor ASIC* because transmission is
the scarce resource. In a multi-pod trainer the analogous scarce resource is
the cross-pod (DCN) link crossed by the gradient all-reduce. We compress at
the source: per-pod partial gradients are int8-quantized (per-leaf absmax
scale) before crossing the pod axis, cutting pod-link bytes 2x vs bf16 / 4x
vs f32, then dequantized and averaged.

Mechanics: jax.shard_map with ``axis_names={"pod"}`` — the pod axis becomes
manual (we own the collective), while "data"/"model" stay auto (GSPMD keeps
sharding them as usual). The quantized reduction is an int8 all_gather +
local dequant-sum: int8 summation would overflow, and this keeps the wire
format 8-bit, which is what the HLO collective-bytes parse (and the real
DCN) sees.

Error bound: absmax int8 quantization has per-element error <= scale/2
= max|g| / 254; tests/test_compression.py checks the end-to-end bound and
that training still converges on the quickstart model.

Serve-side: ``quantize_kv`` / ``dequantize_kv`` give int8 KV caches (the
decode-memory hillclimb lever in EXPERIMENTS.md §Perf).

Trigger-side: ``sparse_trigger_pack`` / ``sparse_trigger_unpack`` are the
paper's at-source reduction applied to the readout server's host link.
The keep/drop cut already ran on device (behind the TMR vote when
redundancy is on); instead of shipping the dense (chips, events) score +
keep tensors across the host link, only keep-flagged events cross it as
a packed (flat indices, scores) pair — bytes on the wire scale with the
trigger rate, not the bunch-crossing rate. The pack is shape-static
(padded with -1) so it lives inside jit; the server slices the true
``count`` prefix when materializing, which is what actually crosses the
link. Round-trip identity (including all-keep / all-drop masks) is
property-tested in tests/test_compression.py.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """absmax-scaled symmetric int8. Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantized_psum_leaf(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Sum ``x`` over the manual axis with an int8 wire format."""
    q, s = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)        # int8 across the link
    ss = jax.lax.all_gather(s, axis_name)        # one f32 scale per shard
    return jnp.sum(qs.astype(jnp.float32) * ss.reshape(
        (-1,) + (1,) * x.ndim), axis=0).astype(x.dtype)


def quantized_psum(tree: PyTree, axis_name: str) -> PyTree:
    return jax.tree.map(lambda x: quantized_psum_leaf(x, axis_name), tree)


def make_compressed_value_and_grad(
    loss_fn: Callable,
    mesh: Mesh,
    batch_spec_tree: PyTree,
    grad_specs: PyTree = None,
):
    """value_and_grad with int8-compressed gradient reduction over "pod".

    loss_fn(params, batch) -> scalar. The batch must have its leading batch
    dim divisible by the pod axis; params are replicated across pods.
    Inside, "data"/"model" remain auto-sharded by GSPMD.

    grad_specs (PartitionSpec tree over the intra-pod axes) is ESSENTIAL:
    without it the per-pod partial grads are unconstrained inside the manual
    body, XLA replicates them over data/model, and every device exchanges
    the FULL gradient across the pod link instead of its 1/256 shard — the
    first measured iteration of EXPERIMENTS.md §Perf C (refuted, 6.7x worse)
    was exactly this bug.
    """
    if "pod" not in mesh.axis_names:
        raise ValueError("compressed grad reduction needs a 'pod' mesh axis")
    n_pod = mesh.shape["pod"]

    def strip_pod(spec: P) -> P:
        parts = []
        for s in spec:
            if isinstance(s, (tuple, list)):
                kept = tuple(a for a in s if a != "pod")
                parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            else:
                parts.append(None if s == "pod" else s)
        return P(*parts)

    inner_grad_specs = (
        jax.tree.map(strip_pod, grad_specs, is_leaf=lambda x: isinstance(x, P))
        if grad_specs is not None else None
    )

    def pod_dim_only(spec: P) -> P:
        # keep only the "pod" component of the batch spec for the manual axis
        parts = []
        for s in spec:
            if s == "pod":
                parts.append("pod")
            elif isinstance(s, (tuple, list)) and "pod" in s:
                parts.append("pod")
            else:
                parts.append(None)
        return P(*parts)

    in_batch_specs = jax.tree.map(
        pod_dim_only, batch_spec_tree, is_leaf=lambda x: isinstance(x, P)
    )

    def body(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # intra-pod sharding constraints (data/model stay auto inside the
        # partial-manual region)
        if inner_grad_specs is not None:
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, inner_grad_specs)
        grads = quantized_psum(grads, "pod")               # int8 on the wire
        grads = jax.tree.map(lambda g: g / n_pod, grads)   # mean over pods
        loss = jax.lax.pmean(loss, "pod")
        return loss, grads

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), in_batch_specs),
        out_specs=(P(), P()),
        axis_names=frozenset({"pod"}),
        check_vma=False,
    )


# ------------------------------------------------- sparse trigger readout
# Wire cost model for the report's accounting: a sparse event ships a
# flat int32 index + int32 score; the dense alternative ships an int32
# score + a keep byte for EVERY scored event, kept or not.
SPARSE_BYTES_PER_EVENT = 8
DENSE_BYTES_PER_EVENT = 5
SPARSE_HEADER_BYTES = 4  # the count word
# Little-endian struct formats of the sparse wire units — net/protocol.py
# frames exactly these on the socket, so the in-process host link and the
# network egress share one byte layout (changing either breaks both test
# suites, by design).
SPARSE_RECORD_STRUCT = "<ii"   # (flat index i32, score i32) per kept event
SPARSE_COUNT_STRUCT = "<I"     # the SPARSE_HEADER_BYTES count prefix


class WireFormatError(ValueError):
    """A wire-format unit failed validation (count prefix out of range,
    index out of the dense shape, mismatched index/score buffers).

    Base of the named-error family shared with the network protocol
    (net/protocol.py's ProtocolError subclasses this): every malformed
    buffer raises from this family — never a raw numpy IndexError, never
    a silent partial decode."""


def sparse_trigger_pack(
    score: jnp.ndarray, keep: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compact keep-flagged events: (count, flat indices, scores).

    score/keep are any matching shape (the server uses (chips, events)).
    Returns (count () int32 — number of kept events; idx (n,) int32 —
    ascending flat indices of kept events, -1 padded to the static size;
    vals (n,) int32 — the kept scores, 0 on padding). Shape-static so it
    composes inside jit; jit'd module-level as ``sparse_trigger_pack_jit``
    so the server's drain launches it without retracing.
    """
    flat_keep = keep.ravel()
    flat_score = score.ravel().astype(jnp.int32)
    idx = jnp.nonzero(flat_keep, size=flat_keep.size, fill_value=-1)[0]
    idx = idx.astype(jnp.int32)
    safe = jnp.clip(idx, 0, flat_keep.size - 1)
    vals = jnp.where(idx >= 0, flat_score[safe], 0)
    count = jnp.sum(flat_keep.astype(jnp.int32))
    return count, idx, vals


sparse_trigger_pack_jit = jax.jit(sparse_trigger_pack)


def sparse_trigger_pack_words(
    keep_w: jnp.ndarray,        # (C, W) uint32 keep words (bit e = event w*32+e)
    scores: jnp.ndarray,        # (C, W, 32) int32 per-lane scores
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``sparse_trigger_pack`` computed FROM the word domain: popcount
    prefix-sum compaction over keep words, so the (chips, events) bool
    mask never materializes and dropped events are never transposed back
    to event order.

    Each word's kept-lane count comes from one ``population_count``; an
    exclusive cumsum over words gives every word its output base; a
    lane's within-word rank is the popcount of the keep bits below it.
    Kept lanes scatter to ``base + rank`` (dropped lanes aim one past
    the end and fall off via ``mode="drop"``), which reproduces the
    ascending-index wire format of ``sparse_trigger_pack`` bit for bit:
    (count () int32, idx (C*W*32,) int32 ascending flat indices -1
    padded, vals int32 0 padded). Property-tested against the event-
    domain oracle in tests/test_compression.py.
    """
    C, W = keep_w.shape
    n = C * W * 32
    flat_kw = keep_w.reshape(C * W)
    counts = jax.lax.population_count(flat_kw).astype(jnp.int32)
    word_base = jnp.cumsum(counts) - counts              # exclusive cumsum
    count = jnp.sum(counts)

    lane = jnp.arange(32, dtype=jnp.uint32)
    below = (jnp.uint32(1) << lane) - jnp.uint32(1)      # bits strictly below
    keep_bit = (flat_kw[:, None] >> lane) & jnp.uint32(1)       # (CW, 32)
    rank = jax.lax.population_count(
        flat_kw[:, None] & below[None, :]).astype(jnp.int32)
    dest = jnp.where(keep_bit == 1, word_base[:, None] + rank, n)
    flat_idx = (
        jnp.arange(C * W, dtype=jnp.int32)[:, None] * 32
        + lane.astype(jnp.int32)
    )
    idx = jnp.full((n,), -1, jnp.int32).at[dest.reshape(-1)].set(
        flat_idx.reshape(-1), mode="drop")
    vals = jnp.zeros((n,), jnp.int32).at[dest.reshape(-1)].set(
        scores.reshape(-1).astype(jnp.int32), mode="drop")
    return count, idx, vals


def sparse_trigger_unpack(
    idx, vals, shape, count: int | None = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side inverse of ``sparse_trigger_pack``.

    Accepts the packed pair (padded or already count-sliced) and the
    dense shape; returns (score (shape) int32 — 0 where dropped, keep
    (shape) bool). ``unpack(pack(s, k)) == (s * k, k)`` for every keep
    mask, including all-keep and all-drop.

    ``count``, when given, is the wire's count prefix: the first
    ``count`` records of idx/vals are the payload, the rest padding.
    The buffers are VALIDATED before any scatter — a count prefix
    larger than the buffer, mismatched idx/vals lengths, or an index
    outside the dense shape raises :class:`WireFormatError` (the same
    named family as the network decoder) instead of silently slicing
    short or crashing with a raw numpy IndexError.
    """
    idx = np.asarray(idx, np.int64).ravel()
    vals = np.asarray(vals, np.int64).ravel()
    if idx.shape != vals.shape:
        raise WireFormatError(
            f"sparse trigger buffers disagree: {idx.size} indices vs "
            f"{vals.size} scores")
    if count is not None:
        if not (0 <= count <= idx.size):
            raise WireFormatError(
                f"sparse trigger count prefix {count} outside the "
                f"record buffer (0..{idx.size})")
        idx = idx[:count]
        vals = vals[:count]
    n = int(np.prod(shape))
    kept = idx >= 0
    kidx = idx[kept]
    if kidx.size and (int(kidx.max()) >= n or int(idx.min()) < -1):
        raise WireFormatError(
            f"sparse trigger index outside dense shape {tuple(shape)}: "
            f"indices span [{int(idx.min())}, {int(kidx.max())}], "
            f"valid flat range is [-1 (padding), {n - 1}]")
    score = np.zeros(n, np.int32)
    keep = np.zeros(n, bool)
    score[kidx] = vals[kept]
    keep[kidx] = True
    return score.reshape(shape), keep.reshape(shape)


# ------------------------------------------------------------- KV caches
def quantize_kv(kv: jnp.ndarray, axis: int = -1):
    """Per-vector absmax int8 along head_dim (decode-memory compression)."""
    xf = kv.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis, keepdims=True) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale).astype(dtype)
