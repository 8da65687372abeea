"""Fused on-device readout frontend: frames -> features -> bits -> score.

The paper's point is data reduction *at the source*: the eFPGA sees raw
sensor charge, not pre-computed features — the whole frontend (featurize,
quantize, classify, keep/drop) lives in the readout path (PAPER.md §5).
This module is that path on TPU, as ONE jit'd dispatch with the chip axis
sharded across devices:

    frames (C, B, T, Y, X) + y0 (C, B)
      -> yprofile                 (kernels/yprofile, chip-batched Pallas)
      -> ap_fixed quantize        (core/quantize device path, int32)
      -> offset-binary bit pack   (per-chip gather plan, below)
      -> lut_eval                 (kernels/lut_eval, banded/dense Pallas)
      -> score decode + keep/drop (two's-complement weights, int32 cut)

No stage materializes on the host: the feature tensor, the bit tensor and
the net-value buffer live and die on the device. The host sees only the
(C, B) integer scores and keep mask.

Staying swap-friendly is the design constraint. Everything per-chip —
which features feed which input bit, the fixed-point spec, the output
decode weights, the trigger threshold — is carried as *dynamic* (C, ...)
arrays (the "encode plan"), never as static jit arguments. Hot-swapping a
chip is therefore an array-row update on top of
``PackedFabricStack.swap_chip``: no retrace, the same guarantee the
lut_eval stack already makes, now for the whole frontend. Input bit j of
chip c reads bit ``bit_idx[c, j]`` of feature ``feat_idx[c, j]``'s
offset-binary pattern (zeroed where j >= n_inputs_c), which turns the
host packer's reshape into a device gather that tolerates heterogeneous
specs and used-feature sets per chip.

Sharding: the chip axis is a `shard_map` over the "chips" mesh axis
(launch/mesh.py `make_readout_mesh`), so C chips spread over d | C
devices with every stage — including both Pallas kernels — running on the
local (C/d, B) slab. On a single-device host the axis has size 1: same
code path, bit-identical.

Bit-exactness vs the staged host path (yprofile materialized, numpy
quantize+pack, FabricSim) is asserted in tests/test_frontend.py; the
integer stages are exact by construction (core/quantize device-path
contract), and the featurize stage runs the identical per-tile Pallas dot
in both paths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fabric import FabricConfig, FrontendSpec
from repro.core.quantize import (
    FixedSpec,
    quantize_pattern_device,
    spec_device_params,
)
from repro.data.smartpixel import N_T, N_X, N_Y
from repro.kernels.compat import default_interpret
from repro.kernels.lut_eval import bitsliced as _bitsliced
from repro.kernels.lut_eval import ops as lut_ops
from repro.kernels.yprofile import ops as yp_ops
from repro.launch.mesh import make_readout_mesh
from repro.parallel.compression import sparse_trigger_pack_words


@dataclasses.dataclass(frozen=True)
class ChipFrontendSpec:
    """Per-chip encode/decode contract of the fused frontend.

    used_features: feature indices feeding the fabric, in input-bus order
        (SynthResult.used_features).
    spec: the chip's ap_fixed grid (int32-representable, W <= 31).
    threshold_raw: integer-domain trigger cut — keep iff score <= cut.
    """

    used_features: Tuple[int, ...]
    spec: FixedSpec
    threshold_raw: int


def default_frontend_spec(threshold_electrons: float = 800.0) -> FrontendSpec:
    """The smart-pixel featurizer contract (13 y-profile bins + y0)."""
    return FrontendSpec(
        n_features=yp_ops.N_FEATURES,
        frame_shape=(N_T, N_Y, N_X),
        threshold_electrons=threshold_electrons,
    )


def validate_chip_frontend(config: FabricConfig, cs: ChipFrontendSpec,
                           n_features: int) -> None:
    """Named, fail-fast check that a chip is encodable from the
    featurizer's output — the feature-stage half of what
    StackGeometry.admits checks for the fabric axes. Raised at pack/swap
    time (and by the server's ``reconfigure``) instead of surfacing as an
    index error inside a dispatch."""
    W = cs.spec.width
    if W > 31:
        raise ValueError(
            f"fused frontend quantizes in int32: spec width {W} > 31")
    if len(cs.used_features) * W != config.n_inputs:
        raise ValueError(
            f"encode plan mismatch: {len(cs.used_features)} used features x "
            f"W={W} bits != config n_inputs={config.n_inputs}")
    if cs.used_features and max(cs.used_features) >= n_features:
        raise ValueError(
            f"chip reads feature {max(cs.used_features)} but the featurizer "
            f"produces only {n_features}")
    if len(config.output_nets) > 31:
        raise ValueError(
            "fused frontend decodes scores in int32: "
            f"{len(config.output_nets)} output bits > 31")


def _plan_row(
    config: FabricConfig, cs: ChipFrontendSpec, J: int, O: int,
) -> Dict[str, np.ndarray]:
    """One chip's encode-plan row, zero-padded to the stack envelope."""
    W = cs.spec.width
    n_in = len(cs.used_features) * W
    assert n_in <= J and len(config.output_nets) <= O
    feat = np.zeros(J, np.int32)
    bit = np.zeros(J, np.int32)
    valid = np.zeros(J, np.int32)
    j = np.arange(n_in)
    if n_in:
        feat[:n_in] = np.asarray(cs.used_features, np.int64)[j // W]
        bit[:n_in] = j % W
        valid[:n_in] = 1
    weight = np.zeros(O, np.int64)
    n_out = len(config.output_nets)
    weight[:n_out] = 1 << np.arange(n_out)
    if n_out:
        weight[n_out - 1] = -(1 << (n_out - 1))  # two's-complement sign bit
    row = {"feat_idx": feat, "bit_idx": bit, "bit_valid": valid,
           "out_weight": weight.astype(np.int32),
           "threshold_raw": np.int32(cs.threshold_raw)}
    row.update(spec_device_params(cs.spec))
    return row


_PLAN_KEYS = ("feat_idx", "bit_idx", "bit_valid", "out_weight",
              "threshold_raw", "scale", "rnd_off", "wrap_mask", "sign_bit",
              "sat_lo", "sat_hi")


# Each stage of the fused step runs under a named scope, which its device
# ops carry in their op metadata, so a profile can tell the stages apart:
# readout_featurize (the yprofile kernel and the frames' relayout),
# readout_encode (quantize and bit pack), readout_fabric_eval (bit-sliced
# or matmul evaluation and the vote), readout_decode (score decode and
# cut) and, on the sparse path, readout_compact.
#
# Static args are the ENVELOPE only (never per-chip values), so hot-swaps
# and threshold updates are array swaps with no retrace — the same rule as
# lut_eval's _eval_stack_arrays.
def _score_frames_impl(
    frames: jnp.ndarray,        # (C, B, T, Y, X) f32
    y0: jnp.ndarray,            # (C, B) f32
    sel: jnp.ndarray,           # (R*C, L, rows, 4M)
    tables: jnp.ndarray,        # (R*C, L, M, 16)
    level_base: jnp.ndarray,    # (L,) shared
    win_base: jnp.ndarray,      # (L,) shared
    output_nets: jnp.ndarray,   # (R*C, O)
    plan: Dict[str, jnp.ndarray],
    valid: jnp.ndarray,         # (C, B) bool — kills padded event rows
    src: jnp.ndarray = None,    # (R*C, L, M, 4) — bit-sliced layout only
    *,
    mesh: Mesh,
    n_replicas: int,
    threshold_electrons: float,
    n_inputs: int,
    in_seg: int,
    n_nets_pad: int,
    batch_tile: int,
    interpret: bool,
    sparse: bool = False,
):
    def encode(frames, y0, plan):
        # 1. featurize: chip-batched yprofile -> (Cl, B, 128) feature cols
        with jax.named_scope("readout_featurize"):
            feats = yp_ops.yprofile_traced(
                frames, y0, threshold=threshold_electrons,
                batch_tile=batch_tile, interpret=interpret)
        with jax.named_scope("readout_encode"):
            # 2. quantize every feature column to its chip's offset-binary
            #    pattern (per-chip spec params broadcast over (B, 128))
            c1 = lambda a: a[:, None, None]
            u = quantize_pattern_device(
                feats, scale=c1(plan["scale"]), rnd_off=c1(plan["rnd_off"]),
                wrap_mask=c1(plan["wrap_mask"]),
                sign_bit=c1(plan["sign_bit"]),
                sat_lo=c1(plan["sat_lo"]), sat_hi=c1(plan["sat_hi"]))
            # 3. pack input bits: bit j of chip c = bit bit_idx[c,j] of
            #    feature feat_idx[c,j]'s pattern (the host packer's
            #    reshape, as a gather that survives heterogeneous chips)
            taken = jnp.take_along_axis(u, plan["feat_idx"][:, None, :],
                                        axis=2)
            return jnp.bitwise_and(
                jnp.right_shift(taken, plan["bit_idx"][:, None, :]),
                jnp.int32(1)) * plan["bit_valid"][:, None, :]

    shard = P("chips")

    if sparse:
        if src is None:
            raise ValueError(
                "sparse frame scoring needs the word domain: pack the "
                "frontend with layout='bitsliced'")

        def body_sparse(frames, y0, sel, tables, output_nets, plan, valid,
                        src):
            bits = encode(frames, y0, plan)
            # The event->word bit transpose (bitsliced.input_words) is
            # fused HERE, on device, against the just-encoded bit tensor —
            # packing never round-trips the host — and everything after it
            # stays in the word domain.
            with jax.named_scope("readout_fabric_eval"):
                voted_w, dis_w = _bitsliced.eval_words_voted(
                    src, tables, output_nets, bits,
                    n_replicas=n_replicas, n_inputs=n_inputs, in_seg=in_seg)
            with jax.named_scope("readout_decode"):
                return lut_ops.decode_keep_words_device(
                    voted_w, dis_w, plan["out_weight"],
                    plan["threshold_raw"], valid)

        keep_w, scores, dis = jax.shard_map(
            body_sparse, mesh=mesh,
            in_specs=(shard,) * 8,
            out_specs=(shard, shard, shard),
            check_vma=False,
        )(frames, y0, sel, tables, output_nets, plan, valid, src)
        # Cross-chip compaction: one ascending flat index space, so it runs
        # after the manual region but inside the same jit.
        with jax.named_scope("readout_compact"):
            count, idx, vals = sparse_trigger_pack_words(keep_w, scores)
        return count, idx, vals, dis

    def body(frames, y0, sel, tables, output_nets, plan, valid, src):
        bits = encode(frames, y0, plan)
        # 4. fabric evaluation on the device-resident bit tensor — on a
        #    redundant stack every replica slot evaluates here and the
        #    2-of-3 majority vote reduces them before decode; a
        #    bit-sliced stack (src not None) routes through the word
        #    evaluator with the vote folded into the bitwise pass
        with jax.named_scope("readout_fabric_eval"):
            outs, disagree = lut_ops.fabric_eval_bits_voted(
                sel, tables, level_base, win_base, output_nets, bits,
                n_replicas=n_replicas, n_inputs=n_inputs,
                n_nets_pad=n_nets_pad, in_seg=in_seg,
                batch_tile=batch_tile, interpret=interpret,
                src=src)                                 # (Cl, B, O) uint8
        # 5. score decode + trigger decision + SEU health counts — the
        #    SAME device tail as the features path's scoring dispatch
        with jax.named_scope("readout_decode"):
            return lut_ops.decode_scores_device(
                outs, disagree, plan["out_weight"], plan["threshold_raw"],
                valid)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard,) * 8,
        out_specs=(shard, shard, shard),
        check_vma=False,
    )(frames, y0, sel, tables, output_nets, plan, valid, src)


_score_frames = functools.partial(
    jax.jit,
    static_argnames=("mesh", "n_replicas", "threshold_electrons", "n_inputs",
                     "in_seg", "n_nets_pad", "batch_tile", "interpret",
                     "sparse"),
)(_score_frames_impl)


def _place_plan(plan: Dict, mesh: Mesh) -> Dict[str, jax.Array]:
    """The encode plan's (C, ...) rows, each on the device that serves
    its chip, as the dispatch's ``shard_map`` splits them."""
    return jax.device_put(plan, NamedSharding(mesh, P("chips")))


class PlacedFrames(NamedTuple):
    """One batch on the mesh (``FusedFrontend.place``): the tile-padded
    frames, y0 and valid mask, each sharded over the "chips" axis, and
    ``batch``, the caller's batch width B before the tile padding."""

    frames: jax.Array           # (C, Bp, T, Y, X) f32
    y0: jax.Array               # (C, Bp) f32
    valid: jax.Array            # (C, Bp) bool
    batch: int

    @property
    def width(self) -> int:
        """Bp: event rows placed per chip."""
        return self.frames.shape[1]


@dataclasses.dataclass(frozen=True)
class FusedFrontend:
    """N configured chips' whole frontends, one sharded device dispatch.

    Built by ``pack_frontend``; ``score_frames`` launches asynchronously
    (JAX dispatch) and returns device arrays — the readout server keeps
    batches in flight and materializes late (triple buffering).
    """

    stack: lut_ops.PackedFabricStack
    chip_specs: Tuple[ChipFrontendSpec, ...]
    plan: Dict[str, jnp.ndarray]        # (C, ...) dynamic encode plan
    mesh: Mesh
    batch_tile: int
    threshold_electrons: float
    interpret: bool

    @property
    def n_chips(self) -> int:
        return self.stack.n_chips

    @property
    def n_replicas(self) -> int:
        """TMR replica slots per chip (1 = no redundancy)."""
        return self.stack.n_replicas

    @property
    def spec(self) -> FrontendSpec:
        """The feature-stage contract (StackGeometry.frontend metadata)."""
        return default_frontend_spec(self.threshold_electrons)

    @staticmethod
    def compiled_programs() -> int:
        """Programs the fused step's jit holds, one per batch shape and
        static flag set: a dispatch that grows it compiled a program (or
        loaded one from the persistent compile cache)."""
        return _score_frames._cache_size()

    def score_frames(
        self, frames, y0
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(C, B, T, Y, X) charge + (C, B) y0 -> ((C, B) int32 raw scores,
        (C, B) bool keep). One dispatch; results are NOT materialized —
        ``np.asarray`` them (or let the server drain) to block. On a
        redundant stack the scores are decoded from the majority-voted
        output word; ``score_frames_voted`` also exposes the per-replica
        disagreement counters."""
        score, keep, _ = self.score_frames_voted(frames, y0)
        return score, keep

    def score_frames_voted(
        self, frames, y0, valid=None
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Like ``score_frames`` but also returns the SEU health signal:
        disagree_counts (C, n_replicas) int32 — events (among ``valid``
        rows; None = all rows) where that replica's output word was voted
        against. All-zero on a healthy (or non-redundant) stack.

        ``frames``/``y0``/``valid`` are host arrays; the dispatch places
        its own copies on the mesh (``place``, then ``score_placed``)."""
        return self.score_placed(self.place(frames, y0, valid))

    def score_frames_sparse(
        self, frames, y0, valid=None
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Word-domain sparse egress form of ``score_frames_voted``
        (bit-sliced stacks only): the trigger cut, SEU counters and the
        popcount prefix-sum compaction all run on sliced words inside the
        SAME fused dispatch — dropped events are never transposed back to
        event order, and only the kept prefix need cross the host link.

        Returns (count () int32, idx (C*B,) int32 ascending flat indices
        ``chip*B + event`` -1 padded, vals (C*B,) int32 kept scores 0
        padded, disagree_counts (C, R) int32) — the
        ``parallel.compression.sparse_trigger_pack`` wire format. Results
        are NOT materialized; slice ``idx[:count]`` on device before
        np.asarray to ship exactly the kept events (the server's drain
        does)."""
        return self.score_placed(self.place(frames, y0, valid), sparse=True)

    def place(self, frames, y0, valid=None) -> PlacedFrames:
        """The first half of a dispatch: pad the host batch to the tile
        and put it on the mesh, each chip-axis shard on its own device.
        ``valid`` (C, B) bool marks the real event rows (None = all)."""
        frames = np.asarray(frames, np.float32)
        y0 = np.asarray(y0, np.float32)
        C, B = frames.shape[0], frames.shape[1]
        assert C == self.n_chips, (C, self.n_chips)
        valid = (np.ones((C, B), np.bool_) if valid is None
                 else np.asarray(valid, np.bool_))
        Bp = (max(B, 1) + self.batch_tile - 1) // self.batch_tile
        Bp *= self.batch_tile
        if Bp != B:
            pad = ((0, 0), (0, Bp - B))
            frames = np.pad(frames, pad + ((0, 0),) * 3)
            y0 = np.pad(y0, pad)
            valid = np.pad(valid, pad)
        # Host buffers go straight to their chip-axis shards: one transfer
        # per device, instead of a copy onto the default device that the
        # sharded dispatch would then have to redistribute.
        frames, y0, valid = jax.device_put(
            (frames, y0, valid), NamedSharding(self.mesh, P("chips")))
        return PlacedFrames(frames, y0, valid, B)

    def score_placed(self, placed: PlacedFrames, *, sparse: bool = False):
        """The second half of a dispatch: the fused step on a placed
        batch. Returns what ``score_frames_voted`` (or, with ``sparse``,
        ``score_frames_sparse``) returns for the caller's batch width."""
        s = self.stack
        out = _score_frames(
            placed.frames, placed.y0, s.sel, s.tables, s.level_base,
            s.win_base, s.output_nets, self.plan, placed.valid, s.src,
            mesh=self.mesh, n_replicas=s.n_replicas,
            threshold_electrons=self.threshold_electrons,
            n_inputs=s.n_inputs, in_seg=s.in_seg, n_nets_pad=s.n_nets_pad,
            batch_tile=self.batch_tile, interpret=self.interpret,
            sparse=sparse)
        B, Bp = placed.batch, placed.width
        if not sparse:
            score, keep, dis = out
            return score[:, :B], keep[:, :B], dis
        count, idx, vals, dis = out
        if Bp != B:
            # Kept lanes sit below B (``valid`` kills the pad tail):
            # restride tile-padded flat indices to the caller's batch.
            C = placed.frames.shape[0]
            idx = jnp.where(idx >= 0, (idx // Bp) * B + (idx % Bp), -1)
            idx = idx[: C * B]
            vals = vals[: C * B]
        return count, idx, vals, dis

    def on_mesh(self, mesh: Mesh,
                stack: lut_ops.PackedFabricStack) -> "FusedFrontend":
        """This frontend moved to ``mesh``, serving ``stack`` (already
        placed there, ``PackedFabricStack.on_mesh``); the encode plan's
        rows go to the devices that serve their chips."""
        return dataclasses.replace(
            self, stack=stack, mesh=mesh, plan=_place_plan(self.plan, mesh))

    def swap_chip(
        self, slot: int, config: FabricConfig, chip_spec: ChipFrontendSpec,
        stack: Optional[lut_ops.PackedFabricStack] = None,
    ) -> "FusedFrontend":
        """Hot-swap one chip's whole frontend: fabric arrays via
        PackedFabricStack.swap_chip plus this stack's encode-plan row —
        all dynamic, so the compiled dispatch is reused as-is. A caller
        that already swapped its own shared stack (the readout server)
        passes it via ``stack`` so the arrays are rebuilt once, not
        twice."""
        validate_chip_frontend(config, chip_spec, self.spec.n_features)
        if stack is None:
            stack = self.stack.swap_chip(slot, config)
        row = _plan_row(config, chip_spec, stack.n_inputs, stack.n_outputs)
        plan = {
            k: self.plan[k].at[slot].set(jnp.asarray(row[k]))
            for k in _PLAN_KEYS
        }
        specs = list(self.chip_specs)
        specs[slot] = chip_spec
        return dataclasses.replace(
            self, stack=stack, plan=plan, chip_specs=tuple(specs))

    def set_threshold(self, slot: int, threshold_raw: int) -> "FusedFrontend":
        """Retarget one chip's trigger cut (array-row update, no repack)."""
        specs = list(self.chip_specs)
        specs[slot] = dataclasses.replace(
            specs[slot], threshold_raw=int(threshold_raw))
        plan = dict(self.plan)
        plan["threshold_raw"] = self.plan["threshold_raw"].at[slot].set(
            jnp.int32(threshold_raw))
        return dataclasses.replace(self, plan=plan, chip_specs=tuple(specs))


def pack_frontend(
    configs: Sequence[FabricConfig],
    chip_specs: Sequence[ChipFrontendSpec],
    *,
    band: Optional[bool] = None,
    redundancy: str = "none",
    layout: str = "matmul",
    batch_tile: int = 128,
    threshold_electrons: float = 800.0,
    mesh: Optional[Mesh] = None,
    interpret: Optional[bool] = None,
    stack: Optional[lut_ops.PackedFabricStack] = None,
) -> FusedFrontend:
    """Pack N (config, frontend-spec) pairs into one fused dispatch.

    ``band``/``layout``/``batch_tile`` feed the lut_eval stage exactly as
    in ``pack_fabrics`` (layout="bitsliced" routes the fabric stage
    through the 32-events-per-word evaluator with the TMR vote folded
    into the bitwise pass); ``batch_tile`` is also the featurizer tile, so the
    staged comparison path must featurize with the same tile to stay
    bit-identical (ScoringBackend.score_frames does). ``mesh`` defaults
    to launch.mesh.make_readout_mesh(len(configs)); the encode plan, and
    the stack when packed here, are placed on it once, each chip's rows
    on the device that serves it (``PackedFabricStack.on_mesh``). A
    caller that already packed the configs (the readout server's
    lut_eval stack) shares the arrays via ``stack``, placed by that
    caller, instead of packing them a second time.

    ``redundancy="tmr"`` serves every chip as three placement-distinct
    replica encodings voted on device (see lut_eval.ops.pack_fabrics);
    the encode plan stays per logical chip — featurize/quantize/pack run
    once per chip, only the fabric stage is triplicated.
    """
    if len(configs) != len(chip_specs):
        raise ValueError(f"{len(configs)} configs vs {len(chip_specs)} specs")
    n_features = default_frontend_spec(threshold_electrons).n_features
    for config, cs in zip(configs, chip_specs):
        validate_chip_frontend(config, cs, n_features)
    mesh = mesh if mesh is not None else make_readout_mesh(len(configs))
    if stack is None:
        stack = lut_ops.pack_fabrics(
            list(configs), band=band, redundancy=redundancy,
            layout=layout).on_mesh(mesh)
    elif redundancy != "none" and stack.n_replicas == 1:
        raise ValueError(
            f"redundancy={redundancy!r} but the shared stack is not "
            "redundant — pack it with pack_fabrics(redundancy=...)")
    assert stack.n_chips == len(configs), (stack.n_chips, len(configs))
    rows = [
        _plan_row(c, cs, stack.n_inputs, stack.n_outputs)
        for c, cs in zip(configs, chip_specs)
    ]
    plan = {
        k: jnp.asarray(np.stack([r[k] for r in rows])) for k in _PLAN_KEYS
    }
    return FusedFrontend(
        stack=stack,
        chip_specs=tuple(chip_specs),
        plan=_place_plan(plan, mesh),
        mesh=mesh,
        batch_tile=batch_tile,
        threshold_electrons=float(threshold_electrons),
        interpret=default_interpret() if interpret is None else interpret,
    )
