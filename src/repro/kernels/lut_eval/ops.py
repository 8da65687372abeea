"""jit'd wrappers + packing for the lut_eval kernel (single and multi-chip).

``pack_fabric`` turns a decoded bitstream (core.fabric.FabricConfig) into
the dense, 128-aligned arrays the kernel consumes; ``fabric_eval`` runs a
batch of events through one configured fabric. ``pack_fabrics`` stacks N
decoded bitstreams into ONE chip-batched structure sharing a padded
geometry, and ``fabric_eval_multi`` evaluates (chips, events) in a single
kernel dispatch — the device half of launch/readout_server.py.

Reconfiguring a fabric = repacking arrays; the compiled kernel is reused
across bitstreams with the same padded geometry (the paper's
reconfigurability property, DESIGN.md §3). For a stack this extends
per-slot: ``PackedFabricStack.swap_chip`` replaces one chip's arrays in
place, no recompile, as long as the new config fits the stack's envelope.

Routing is packed *banded* whenever it is cheaper: level l's selection
rows cover only [input segment | window of the K preceding levels], K the
config's fan-in reach (core.netlist.fanin_reach), cutting per-level matmul
cost from (in_seg + L*m_pad)*4M to (in_seg + K*m_pad)*4M. The dense layout
is the automatic fallback when K >= L (the window would span every level).
The band is part of the stack envelope: hot-swaps must fit it, which
StackGeometry.admits enforces via its fanin_reach budget. The band is a
*reach envelope*, not a kernel structure — the bit-sliced layout accepts
it too (its index gathers need no routing window, so the budget is pure
admission control, validated at pack and swap time).

Redundancy: ``pack_fabrics(..., redundancy="tmr")`` packs THREE
independently-encoded replicas of every chip (core.tmr.replicate_config —
distinct placements, so one configuration-memory address maps to
different logical LUTs per replica) as contiguous chip slots
``slot*3 .. slot*3+2``. All replica slots evaluate in the same
chip-batched dispatch; ``fabric_eval_bits_voted`` reduces them with the
2-of-3 majority vote before the output gather reaches the caller, and
reports which replicas disagreed with the vote (the SEU health monitor).
``swap_chip`` re-encodes all three replicas (hot-swap stays a pure array
swap); ``swap_replica`` replaces ONE replica's arrays — the
fault-injection port used by the SEU campaign (tests/test_seu.py).

Scrubbing: ``PackedFabricStack.readback_chip/readback_replica`` read the
LIVE device-side truth-table arrays back to the host in the padded
scrub-loop layout (core.fabric.packed_table_image — the same function
that packs them, so readback-vs-golden is a structural identity). The
readout server's background scrub task CRC-verifies these images against
its golden store (core.bitstream.GoldenImageStore) and heals a corrupted
replica through ``swap_replica`` — closing the mask -> detect -> repair
loop that TMR voting alone leaves open.

``fabric_eval_multi_scored`` is the serving entry for pre-packed input
bits: one jit'd dispatch that evaluates (and votes) the stack, decodes
two's-complement scores on device and applies the integer trigger cut —
with the chip axis shard_map'd over the "chips" readout mesh, so the
features ingestion path scales with devices exactly like the fused
frames frontend (kernels/frontend.py).

On CPU (this container) the kernel runs in interpret mode; on TPU it
compiles to Mosaic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fabric import (
    FabricConfig,
    StackGeometry,
    check_stackable,
    packed_table_image,
    stack_event_bits as fabric_stack_event_bits,
)
from repro.core.tmr import N_REPLICAS, majority_vote, replicate_config
from repro.kernels.compat import default_interpret as _default_interpret
from repro.kernels.lut_eval import bitsliced as _bitsliced
from repro.parallel.compression import sparse_trigger_pack_words
from repro.kernels.lut_eval.lut_eval import (
    lut_eval_pallas,
    lut_eval_pallas_banded,
    lut_eval_pallas_banded_stacked,
    lut_eval_pallas_stacked,
)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedFabric:
    """Device-array form of a decoded bitstream (pytree).

    ``band_k`` < ``n_levels`` means the selection tensor is *banded*:
    ``sel`` has ``in_seg + band_k*m_pad`` rows per level (input segment +
    a window of band_k preceding levels) and ``win_base[l]`` holds the
    window's read offset into the full net buffer. ``band_k == n_levels``
    is the dense layout (sel rows == n_nets_pad, win_base all in_seg).

    ``layout="bitsliced"`` (pack_fabric) replaces the one-hot ``sel``
    tensor with the compact ``src`` gather indices and ``sel`` is None:
    evaluation goes through the bit-parallel word path (bitsliced.py)
    instead of the Pallas matmul kernel. ``tables`` keeps the identical
    scrub-loop image in every layout.
    """

    sel: jnp.ndarray          # (L, n_rows, 4*M) bf16 0/1 — None if bitsliced
    tables: jnp.ndarray       # (L, M, 16) f32
    level_base: jnp.ndarray   # (L,) int32
    output_nets: jnp.ndarray  # (n_outputs,) int32 (padded layout)
    win_base: jnp.ndarray     # (L,) int32 — banded window read offsets
    n_inputs: int = dataclasses.field(metadata=dict(static=True))
    n_nets_pad: int = dataclasses.field(metadata=dict(static=True))
    m_pad: int = dataclasses.field(metadata=dict(static=True))
    n_levels: int = dataclasses.field(metadata=dict(static=True))
    in_seg: int = dataclasses.field(metadata=dict(static=True))
    band_k: int = dataclasses.field(metadata=dict(static=True))
    src: jnp.ndarray = None   # (L, M, 4) int32 — bitsliced layout only

    @property
    def banded(self) -> bool:
        return self.band_k < self.n_levels

    @property
    def bitsliced(self) -> bool:
        return self.src is not None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedFabricStack:
    """N decoded bitstreams stacked into one chip-batched pytree.

    All chips share the padded geometry (L, N, M, in_seg); narrower chips
    are zero-padded. ``output_nets`` is padded with net 0 (const0), so
    padded output lanes evaluate to 0 — matching MultiFabricSim's zero
    padding. Per-chip true widths live in the static tuples.

    ``n_replicas`` > 1 is the TMR layout: the leading array axis holds
    ``n_replicas`` independently-encoded replica slots per LOGICAL chip,
    grouped contiguously (slot ``c`` occupies rows ``c*R .. c*R+R-1``).
    The static width tuples stay per logical chip — replicas share their
    chip's IO widths by construction.
    """

    sel: jnp.ndarray          # (R*C, L, n_rows, 4*M) bf16 0/1 — None if bitsliced
    tables: jnp.ndarray       # (R*C, L, M, 16) f32
    level_base: jnp.ndarray   # (L,) int32 — shared
    output_nets: jnp.ndarray  # (R*C, n_outputs_max) int32 (padded layout)
    win_base: jnp.ndarray     # (L,) int32 — shared banded window offsets
    n_inputs: int = dataclasses.field(metadata=dict(static=True))       # max
    n_outputs: int = dataclasses.field(metadata=dict(static=True))      # max
    n_inputs_each: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    n_outputs_each: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))
    n_nets_pad: int = dataclasses.field(metadata=dict(static=True))
    m_pad: int = dataclasses.field(metadata=dict(static=True))
    n_levels: int = dataclasses.field(metadata=dict(static=True))
    in_seg: int = dataclasses.field(metadata=dict(static=True))
    band_k: int = dataclasses.field(metadata=dict(static=True))  # shared band
    n_replicas: int = dataclasses.field(default=1, metadata=dict(static=True))
    src: jnp.ndarray = None   # (R*C, L, M, 4) int32 — bitsliced layout only

    @property
    def n_chips(self) -> int:
        """LOGICAL chip count (replica slots are n_replicas * n_chips)."""
        return len(self.n_inputs_each)

    @property
    def banded(self) -> bool:
        return self.band_k < self.n_levels

    @property
    def bitsliced(self) -> bool:
        return self.src is not None

    @property
    def layout(self) -> str:
        """'bitsliced', 'banded' or 'dense' — how this stack evaluates."""
        if self.bitsliced:
            return "bitsliced"
        return "banded" if self.banded else "dense"

    @property
    def redundant(self) -> bool:
        return self.n_replicas > 1

    def on_mesh(self, mesh: Mesh) -> "PackedFabricStack":
        """This stack placed for the sharded dispatches on ``mesh``: each
        per-slot array row-sharded over "chips", as the dispatches'
        ``shard_map`` splits it, so every device holds its own chips'
        tables and routing; the shared (L,) arrays replicated. Placed once
        (at construction and on a mesh rebind), a dispatch moves only its
        batch; a hot swap keeps the placement."""
        chips = NamedSharding(mesh, P("chips"))
        shared = NamedSharding(mesh, P())
        put = lambda x, to: None if x is None else jax.device_put(x, to)
        return dataclasses.replace(
            self, sel=put(self.sel, chips), src=put(self.src, chips),
            tables=put(self.tables, chips),
            output_nets=put(self.output_nets, chips),
            level_base=put(self.level_base, shared),
            win_base=put(self.win_base, shared))

    def _envelope(self) -> StackGeometry:
        return StackGeometry(
            n_levels=self.n_levels,
            max_level_size=self.m_pad,
            n_inputs=self.n_inputs,
            n_outputs=self.n_outputs,
            fanin_reach=self.band_k if self.banded else None,
        )

    def _check_admits(self, config: FabricConfig) -> None:
        geo = self._envelope()
        if config.n_ffs or not geo.admits(config):
            raise ValueError(
                f"config does not fit stack envelope {geo} "
                f"(levels={len(config.level_sizes)}, "
                f"widest={max(config.level_sizes, default=1)}, "
                f"inputs={config.n_inputs}, outputs={len(config.output_nets)},"
                f" ffs={config.n_ffs}, fanin_reach={config.fanin_reach()})"
            )

    def swap_chip(self, slot: int, config: FabricConfig) -> "PackedFabricStack":
        """Hot-swap one chip's bitstream: pure array swap, no recompile.

        The new config must fit the stack's padded envelope (StackGeometry
        admits it — including the fan-in-reach budget when the stack is
        banded); true per-chip widths update so callers decode the right
        output lanes. On a redundant stack all ``n_replicas`` replica
        slots are re-encoded (core.tmr.replicate_config), so the swapped
        chip keeps the full TMR protection.
        """
        self._check_admits(config)
        R = self.n_replicas
        pack_one = (
            self._pack_slot_bitsliced if self.bitsliced else self._pack_slot
        )
        packed = [
            pack_one(replicate_config(config, r) if R > 1 else config)
            for r in range(R)
        ]
        # all R replica rows are contiguous: stack host-side and update in
        # ONE functional write per array (a .at[].set copies the whole
        # stack, so per-replica writes would triple the swap latency)
        lo = slot * R
        arrays = dict(
            tables=self.tables.at[lo : lo + R].set(
                jnp.asarray(np.stack([p[1] for p in packed]), jnp.float32)),
            output_nets=self.output_nets.at[lo : lo + R].set(
                jnp.asarray(np.stack([p[2] for p in packed]), jnp.int32)),
        )
        if self.bitsliced:
            arrays["src"] = self.src.at[lo : lo + R].set(
                jnp.asarray(np.stack([p[0] for p in packed]), jnp.int32))
        else:
            arrays["sel"] = self.sel.at[lo : lo + R].set(
                jnp.asarray(np.stack([p[0] for p in packed]), jnp.bfloat16))
        each_in = list(self.n_inputs_each)
        each_out = list(self.n_outputs_each)
        each_in[slot] = config.n_inputs
        each_out[slot] = len(config.output_nets)
        return dataclasses.replace(
            self,
            n_inputs_each=tuple(each_in),
            n_outputs_each=tuple(each_out),
            **arrays,
        )

    def _pack_slot(self, config: FabricConfig):
        """(sel, tables, out_nets) host arrays for one replica slot."""
        return _pack_arrays(
            config, self.n_levels, self.m_pad, self.in_seg, self.n_outputs,
            band_k=self.band_k if self.banded else None,
        )

    def _pack_slot_bitsliced(self, config: FabricConfig):
        """(src, tables, out_nets) host arrays for one replica slot."""
        return _pack_arrays_bitsliced(
            config, self.n_levels, self.m_pad, self.in_seg, self.n_outputs,
            band_k=self.band_k if self.banded else None,
        )

    def swap_replica(
        self, slot: int, replica: int, config: FabricConfig
    ) -> "PackedFabricStack":
        """Replace ONE replica's arrays — the fault-injection port.

        The SEU campaign perturbs a single replica's decoded bitstream
        (core.tmr.inject_seu on its replica-encoded config) and swaps it
        in here; the other replicas and the per-chip widths are
        untouched, so the voted output should mask the fault. Still an
        array swap: no recompile. The config must keep the slot's IO
        widths — a replica cannot disagree with its siblings about the
        chip's interface.
        """
        R = self.n_replicas
        if not 0 <= replica < R:
            raise ValueError(f"replica must be in [0, {R}), got {replica!r}")
        self._check_admits(config)
        if (config.n_inputs != self.n_inputs_each[slot]
                or len(config.output_nets) != self.n_outputs_each[slot]):
            raise ValueError(
                f"replica IO widths ({config.n_inputs} in, "
                f"{len(config.output_nets)} out) must match slot {slot}'s "
                f"({self.n_inputs_each[slot]} in, "
                f"{self.n_outputs_each[slot]} out)"
            )
        row = slot * R + replica
        if self.bitsliced:
            s, t, o = self._pack_slot_bitsliced(config)
            routing = dict(src=self.src.at[row].set(jnp.asarray(s, jnp.int32)))
        else:
            s, t, o = self._pack_slot(config)
            routing = dict(sel=self.sel.at[row].set(jnp.asarray(s, jnp.bfloat16)))
        return dataclasses.replace(
            self,
            tables=self.tables.at[row].set(jnp.asarray(t, jnp.float32)),
            output_nets=self.output_nets.at[row].set(jnp.asarray(o, jnp.int32)),
            **routing,
        )

    def readback_replica(self, slot: int, replica: int = 0) -> np.ndarray:
        """Read back ONE replica's LIVE configuration-memory truth tables
        from the device arrays: (n_levels, m_pad, 16) uint8 in the padded
        scrub-loop layout (core.fabric.packed_table_image).

        This is the detection half of the scrub loop (readback -> verify
        -> heal): it returns what the device is *actually* evaluating
        with — including any upset injected via ``swap_replica`` — so a
        CRC mismatch against the golden digest (core.bitstream.
        GoldenImageStore) proves corruption instead of inferring it from
        vote disagreements. The device tables are exact 0.0/1.0 float32,
        so the uint8 cast is lossless.
        """
        R = self.n_replicas
        if not 0 <= slot < self.n_chips:
            raise ValueError(
                f"slot must be in [0, {self.n_chips}), got {slot!r}")
        if not 0 <= replica < R:
            raise ValueError(f"replica must be in [0, {R}), got {replica!r}")
        return np.asarray(self.tables[slot * R + replica]).astype(np.uint8)

    def readback_chip(self, slot: int) -> np.ndarray:
        """Read back ALL replica slots of one logical chip:
        (n_replicas, n_levels, m_pad, 16) uint8."""
        return np.stack([
            self.readback_replica(slot, r) for r in range(self.n_replicas)
        ])


def _win_base(L: int, band_k: int, m_pad: int, in_seg: int) -> np.ndarray:
    """Per-level window read offsets: level l sees levels [max(0,l-K), l)."""
    return (
        in_seg + np.maximum(np.arange(L, dtype=np.int64) - band_k, 0) * m_pad
    ).astype(np.int32)


def _pack_arrays(
    c: FabricConfig,
    L: int,
    m_pad: int,
    in_seg: int,
    n_out_pad: int,
    band_k: int | None = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack one config into a forced (L, m_pad, in_seg) geometry.

    Fully vectorized (numpy scatter) — this is the hot-swap path, so pack
    latency must not scale with a Python loop over LUT count x 4.

    band_k=None packs the dense layout: sel rows are the full padded net
    space. With band_k=K, sel rows are [input segment | K-level window]
    and every comb source row is shifted by the packing-time window start
    max(0, l-K)*m_pad of its consumer's level l.

    Returns (sel (L, n_rows, 4*M) f32, tables (L, M, 16) f32, output_nets
    (n_out_pad,) int32 in the full padded layout, const0-padded).
    """
    if c.n_ffs:
        raise ValueError(
            "lut_eval kernel handles combinational modules (the readout "
            "classifier); sequential firmware uses core.fabric.FabricSim"
        )
    assert len(c.level_sizes) <= L
    assert max(c.level_sizes, default=1) <= m_pad
    assert 2 + c.n_inputs <= in_seg
    K = L if band_k is None else min(band_k, L)
    n_rows = in_seg + K * m_pad

    n_luts = c.n_luts
    remap, lut_level, pos = _net_layout(c, m_pad, in_seg)

    sel = np.zeros((L, n_rows, 4 * m_pad), np.float32)
    # the device tables ARE the scrub-loop image: readback_replica reads
    # them back verbatim, and the golden CRC digests are computed over
    # the same packed_table_image function (core/fabric.py)
    tables = packed_table_image(c, L, m_pad).astype(np.float32)
    if n_luts:
        src = remap[c.lut_inputs]                  # (n_luts, 4) dense rows
        # band shift: comb rows move into their consumer level's window
        shift = np.maximum(lut_level - K, 0) * m_pad
        rows = np.where(src >= in_seg, src - shift[:, None], src)
        if band_k is not None:
            bad = (src >= in_seg) & ((rows < in_seg) | (rows >= n_rows))
            if bad.any():
                raise ValueError(
                    f"fan-in reach exceeds band: K={K} but a LUT reads "
                    f"{int(bad.sum())} net(s) from outside its window"
                )
        cols = np.arange(4)[None, :] * m_pad + pos[:, None]
        sel[lut_level[:, None], rows, cols] = 1.0

    out_nets = np.zeros(n_out_pad, np.int64)  # pad with net 0 == const0
    out_nets[: len(c.output_nets)] = remap[c.output_nets]
    return sel, tables, out_nets.astype(np.int32)


def _net_layout(
    c: FabricConfig, m_pad: int, in_seg: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Kernel-order net ids -> the dense padded segmented layout shared
    by every device layout ([const0 | const1 | inputs | level slots]).

    Returns (remap (n_nets,), lut_level (n_luts,), pos (n_luts,)) — the
    one net-numbering convention, factored out so the matmul and
    bitsliced packers cannot drift apart.
    """
    level_sizes = np.asarray(c.level_sizes, np.int64)
    n_luts = c.n_luts
    base_comb = 2 + c.n_inputs  # no FFs
    remap = np.zeros(c.n_nets, np.int64)
    remap[1] = 1
    remap[2:base_comb] = np.arange(2, base_comb)
    if n_luts:
        lut_level = np.repeat(np.arange(len(level_sizes)), level_sizes)
        level_start = np.concatenate([[0], np.cumsum(level_sizes)])
        pos = np.arange(n_luts) - level_start[lut_level]
        remap[base_comb : base_comb + n_luts] = in_seg + lut_level * m_pad + pos
    else:
        lut_level = np.zeros(0, np.int64)
        pos = np.zeros(0, np.int64)
    return remap, lut_level, pos


def _pack_arrays_bitsliced(
    c: FabricConfig,
    L: int,
    m_pad: int,
    in_seg: int,
    n_out_pad: int,
    band_k: int | None = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack one config into the bit-sliced (L, m_pad) geometry.

    Instead of the one-hot selection tensor, the routing is the compact
    per-LUT gather indices ``src`` (L, m_pad, 4) int32 into the SAME
    dense padded net layout _pack_arrays uses. Padded LUT slots read net
    0 (const0) with an all-zero table, so they evaluate to 0 — identical
    to the matmul layout's zero padding.

    band_k=K enforces the fan-in-reach *envelope*: the gather indices do
    not change shape (index gathers have no routing window), but a LUT
    at level l may only read nets from levels [l-K, l) — the hardware
    reach budget a banded stack promises its hot-swap admission check.
    band_k=None admits any reach (the dense envelope).

    Returns (src (L, m_pad, 4) int32, tables (L, M, 16) f32 — the
    unchanged scrub-loop image, output_nets (n_out_pad,) int32).
    """
    if c.n_ffs:
        raise ValueError(
            "lut_eval kernel handles combinational modules (the readout "
            "classifier); sequential firmware uses core.fabric.FabricSim"
        )
    assert len(c.level_sizes) <= L
    assert max(c.level_sizes, default=1) <= m_pad
    assert 2 + c.n_inputs <= in_seg
    remap, lut_level, pos = _net_layout(c, m_pad, in_seg)
    tables = packed_table_image(c, L, m_pad).astype(np.float32)
    src = np.zeros((L, m_pad, 4), np.int64)
    if c.n_luts:
        rows = remap[c.lut_inputs]                 # (n_luts, 4) dense rows
        if band_k is not None:
            K = min(band_k, L)
            src_level = (rows - in_seg) // m_pad
            bad = (rows >= in_seg) & (lut_level[:, None] - src_level > K)
            if bad.any():
                raise ValueError(
                    f"fan-in reach exceeds band: K={K} but a LUT reads "
                    f"{int(bad.sum())} net(s) from outside its window"
                )
        src[lut_level, pos] = rows
    out_nets = np.zeros(n_out_pad, np.int64)  # pad with net 0 == const0
    out_nets[: len(c.output_nets)] = remap[c.output_nets]
    return src.astype(np.int32), tables, out_nets.astype(np.int32)


def _check_layout(layout: str, band: bool | None) -> None:
    """Validate the layout name. The band is layout-independent: it is a
    fan-in-reach *envelope* (a hardware routing constraint), not a kernel
    structure, so every layout accepts band=None/True/False."""
    del band  # accepted by every layout — kept for signature stability
    if layout not in ("matmul", "bitsliced"):
        raise ValueError(
            f"unknown layout {layout!r} (expected 'matmul' or 'bitsliced')")


def _band_choice(reach: int, L: int, band: bool | None) -> int:
    """Resolve the band width: auto-band iff strictly cheaper than dense.

    Returns band_k in [1, L]; band_k == L is the dense layout (the
    fallback when the window would cover every level anyway).
    """
    K = min(max(reach, 1), L)
    if band is None:
        band = K < L
    return K if (band and K < L) else L


def pack_fabric(
    config: FabricConfig,
    band: bool | None = None,
    layout: str = "matmul",
) -> PackedFabric:
    """Pack one decoded bitstream. band=None picks the banded *envelope*
    automatically when the config's fan-in reach fits a window narrower
    than the full depth (K < L); band=False forces the dense envelope.
    The band is layout-independent: for matmul it also selects the
    windowed selection tensor (the cheaper kernel), for bitsliced it is
    a pure reach budget validated at pack time.

    layout="bitsliced" packs the bit-parallel word layout instead
    (compact ``src`` gather indices, no selection tensor); evaluation
    then runs the 32-events-per-word path (bitsliced.py) rather than the
    Pallas matmul kernel.
    """
    _check_layout(layout, band)
    c = config
    if c.n_ffs:
        raise ValueError(
            "lut_eval kernel handles combinational modules (the readout "
            "classifier); sequential firmware uses core.fabric.FabricSim"
        )
    L = max(len(c.level_sizes), 1)
    m_pad = _round_up(max(c.level_sizes, default=1), 128)
    in_seg = _round_up(2 + c.n_inputs, 128)
    n_pad = in_seg + L * m_pad
    band_k = _band_choice(c.fanin_reach(), L, band)
    if layout == "bitsliced":
        src, tables, out_nets = _pack_arrays_bitsliced(
            c, L, m_pad, in_seg, len(c.output_nets),
            band_k=band_k if band_k < L else None,
        )
        sel = None
    else:
        sel_np, tables, out_nets = _pack_arrays(
            c, L, m_pad, in_seg, len(c.output_nets),
            band_k=band_k if band_k < L else None,
        )
        sel = jnp.asarray(sel_np, jnp.bfloat16)
        src = None
    return PackedFabric(
        sel=sel,
        tables=jnp.asarray(tables, jnp.float32),
        level_base=jnp.asarray(
            [in_seg + l * m_pad for l in range(L)], jnp.int32
        ),
        output_nets=jnp.asarray(out_nets, jnp.int32),
        win_base=jnp.asarray(_win_base(L, band_k, m_pad, in_seg)),
        n_inputs=c.n_inputs,
        n_nets_pad=n_pad,
        m_pad=m_pad,
        n_levels=L,
        in_seg=in_seg,
        band_k=band_k,
        src=None if src is None else jnp.asarray(src, jnp.int32),
    )


def pack_fabrics(
    configs: Sequence[FabricConfig],
    band: bool | None = None,
    redundancy: str = "none",
    layout: str = "matmul",
    geometry: StackGeometry | None = None,
) -> PackedFabricStack:
    """Stack N decoded bitstreams into one chip-batched structure.

    The shared geometry is the union envelope over all configs
    (core.fabric.StackGeometry); every chip is padded to it, so one
    compiled kernel serves heterogeneous designs. The band is shared too:
    K = max fan-in reach over the stack (auto-dense when not cheaper).

    ``geometry`` overrides the union envelope: every config must fit it
    (``StackGeometry.admits``, including its fan-in-reach budget), and
    the stack pads to the GIVEN envelope rather than the tightest one.
    This is the bucketed-pool primitive: stacks packed against the same
    quantized envelope (``bucket_envelope``) share one compiled kernel,
    so a config never seen before admits into a warm stack through
    ``swap_chip`` with zero retraces. When ``geometry.fanin_reach`` is
    set the stack is packed banded to exactly that reach budget (unless
    it already spans every level); when None it is packed dense.

    ``redundancy="tmr"`` packs three placement-distinct replica
    encodings of every chip (core.tmr.replicate_config) as contiguous
    slots. Replication is envelope-invariant — a within-level rotation
    changes neither level sizes, IO widths, nor fan-in reach — so the
    geometry (and the band) is computed from the base configs.

    ``layout="bitsliced"`` packs the bit-parallel word layout (compact
    ``src`` gather indices instead of the one-hot selection tensor);
    evaluation then runs 32 events per uint32 word with the chip axis as
    one batched XLA computation (bitsliced.py). The band applies here
    too, as a pure reach *envelope*: packing validates every LUT's
    fan-in reach against it and hot-swap admission enforces it, while
    the gather kernel itself is unchanged. The scrub-loop ``tables``
    image, hot-swap ports and readback are identical across layouts.
    """
    if redundancy not in ("none", "tmr"):
        raise ValueError(
            f"unknown redundancy {redundancy!r} (expected 'none' or 'tmr')")
    _check_layout(layout, band)
    n_replicas = N_REPLICAS if redundancy == "tmr" else 1
    geo = check_stackable(configs)
    if geometry is not None:
        for i, c in enumerate(configs):
            if not geometry.admits(c):
                raise ValueError(
                    f"config {i} does not fit the requested envelope "
                    f"{geometry} (levels={len(c.level_sizes)}, "
                    f"widest={max(c.level_sizes, default=1)}, "
                    f"inputs={c.n_inputs}, outputs={len(c.output_nets)}, "
                    f"fanin_reach={c.fanin_reach()})")
        geo = geometry
    L = geo.n_levels
    m_pad = _round_up(geo.max_level_size, 128)
    in_seg = _round_up(2 + geo.n_inputs, 128)
    n_pad = in_seg + L * m_pad
    bitsliced = layout == "bitsliced"
    # the band is shared across layouts: K = max fan-in reach over the
    # stack (auto-dense when the window would span every level anyway).
    # A pinned envelope pins the band too — its reach budget IS the
    # band (dense when unset), so every stack packed against the same
    # envelope resolves to the same static band_k and shares one jit.
    if geometry is not None:
        band_k = (min(geometry.fanin_reach, L)
                  if geometry.fanin_reach is not None else L)
    else:
        band_k = _band_choice(geo.fanin_reach or L, L, band)

    slot_configs = [
        replicate_config(c, r) for c in configs for r in range(n_replicas)
    ] if n_replicas > 1 else list(configs)
    sels, tbls, outs = [], [], []
    for c in slot_configs:
        if bitsliced:
            sel, tables, out_nets = _pack_arrays_bitsliced(
                c, L, m_pad, in_seg, geo.n_outputs,
                band_k=band_k if band_k < L else None,
            )
        else:
            sel, tables, out_nets = _pack_arrays(
                c, L, m_pad, in_seg, geo.n_outputs,
                band_k=band_k if band_k < L else None,
            )
        sels.append(sel)
        tbls.append(tables)
        outs.append(out_nets)

    return PackedFabricStack(
        sel=(None if bitsliced
             else jnp.asarray(np.stack(sels), jnp.bfloat16)),
        src=(jnp.asarray(np.stack(sels), jnp.int32) if bitsliced else None),
        tables=jnp.asarray(np.stack(tbls), jnp.float32),
        level_base=jnp.asarray(
            [in_seg + l * m_pad for l in range(L)], jnp.int32
        ),
        output_nets=jnp.asarray(np.stack(outs), jnp.int32),
        win_base=jnp.asarray(_win_base(L, band_k, m_pad, in_seg)),
        n_inputs=geo.n_inputs,
        n_outputs=geo.n_outputs,
        n_inputs_each=tuple(c.n_inputs for c in configs),
        n_outputs_each=tuple(len(c.output_nets) for c in configs),
        n_nets_pad=n_pad,
        m_pad=m_pad,
        n_levels=L,
        in_seg=in_seg,
        band_k=band_k,
        n_replicas=n_replicas,
    )


def _next_pow2(x: int) -> int:
    return 1 << max(0, x - 1).bit_length()


def bucket_envelope(
    config: FabricConfig,
    band: bool | None = None,
    width_quant: int = 128,
) -> StackGeometry:
    """Quantize one config's shape into a padded bucket envelope.

    The envelope axes are snapped to coarse grid points so that MANY
    distinct tenant configs collapse onto a SMALL set of envelopes —
    the bucket key of the geometry pool (``pack_fabric_pool``). Two
    configs with the same bucket envelope can live in (or hot-swap
    into) the same ``PackedFabricStack`` and therefore share one
    compiled kernel; admitting a never-seen config costs an array swap,
    never a retrace.

    Quantization per axis (all are ceilings, so the envelope always
    ``admits`` the config that produced it):

    * ``n_levels``        -> next power of two (depth drives both jit
      specialization and banded-window shape).
    * ``max_level_size``  -> next multiple of ``width_quant`` (the
      kernel pads level width to 128 lanes anyway, so width headroom
      inside the same multiple is free).
    * ``n_inputs``        -> fills the 128-aligned input segment
      (``in_seg - 2``): the pad bits exist either way.
    * ``n_outputs``       -> next power of two, capped at 31 (the
      score-decode limit ``decode_plan`` enforces).
    * ``fanin_reach``     -> next power of two, capped at the quantized
      depth; ``None`` (dense) when the window would span every level or
      when ``band=False`` forces the dense envelope. ``band=True``
      keeps the banded budget even when it equals the depth ceiling.

    The returned ``StackGeometry`` is hashable — use it directly as the
    bucket key.
    """
    c = config
    L = _next_pow2(max(len(c.level_sizes), 1))
    width = _round_up(max(c.level_sizes, default=1), width_quant)
    n_inputs = _round_up(2 + c.n_inputs, 128) - 2
    n_outputs = min(_next_pow2(max(len(c.output_nets), 1)), 31)
    reach: int | None = min(_next_pow2(max(c.fanin_reach(), 1)), L)
    if band is False or (band is None and reach >= L):
        reach = None
    return StackGeometry(
        n_levels=L,
        max_level_size=width,
        n_inputs=n_inputs,
        n_outputs=n_outputs,
        fanin_reach=reach,
    )


@dataclasses.dataclass(frozen=True)
class FabricBucket:
    """One geometry bucket of a fabric pool.

    ``stack`` is packed against the quantized ``envelope`` (not the
    member union), so any future config whose ``bucket_envelope``
    equals this envelope hot-swaps in with zero retraces. ``members``
    maps stack slots back to the caller's config indices:
    ``members[j]`` is the index (into the configs passed to
    ``pack_fabric_pool``) occupying stack slot ``j``.
    """

    envelope: StackGeometry
    stack: PackedFabricStack
    members: Tuple[int, ...]


def pack_fabric_pool(
    configs: Sequence[FabricConfig],
    band: bool | None = None,
    redundancy: str = "none",
    layout: str = "matmul",
    width_quant: int = 128,
) -> List[FabricBucket]:
    """Bin configs into bucketed geometry pools: one padded stack per
    quantized envelope, one jit per bucket.

    Where ``pack_fabrics`` pads every config to the tightest union
    envelope (one stack, one jit — but ANY new shape retraces),
    ``pack_fabric_pool`` groups configs by ``bucket_envelope`` and
    packs each group against its quantized envelope. The pool trades a
    bounded amount of padding (each axis rounds up to a grid point) for
    a hard no-retrace property: a tenant config that lands in an
    existing bucket admits via ``PackedFabricStack.swap_chip`` without
    compiling anything, because every static kernel dimension is a
    function of the envelope alone.

    Buckets are returned in first-seen order of their envelope;
    ``redundancy`` / ``layout`` apply uniformly (they are part of the
    pool identity, not the per-bucket key). The serving-layer analogue
    — per-bucket servers, tenant admission, LRU eviction — lives in
    ``launch/fleet.py``.
    """
    bins: dict = {}
    for i, c in enumerate(configs):
        bins.setdefault(bucket_envelope(c, band, width_quant), []).append(i)
    return [
        FabricBucket(
            envelope=env,
            stack=pack_fabrics(
                [configs[i] for i in idxs],
                band=band,
                redundancy=redundancy,
                layout=layout,
                geometry=env,
            ),
            members=tuple(idxs),
        )
        for env, idxs in bins.items()
    ]


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def _eval_packed(
    packed: PackedFabric,
    bits: jnp.ndarray,
    *,
    batch_tile: int,
    interpret: bool,
) -> jnp.ndarray:
    B = bits.shape[0]
    if packed.bitsliced:
        return _bitsliced.eval_bits(
            packed.src[None], packed.tables[None], packed.output_nets[None],
            bits[None],
            n_inputs=packed.n_inputs, in_seg=packed.in_seg,
        )[0]
    bits_ext = jnp.zeros((B, packed.in_seg), jnp.float32)
    bits_ext = bits_ext.at[:, 1].set(1.0)
    bits_ext = bits_ext.at[:, 2 : 2 + packed.n_inputs].set(
        bits.astype(jnp.float32)
    )
    if packed.banded:
        vals = lut_eval_pallas_banded(
            bits_ext,
            packed.sel,
            packed.tables,
            packed.level_base,
            packed.win_base,
            n_nets_pad=packed.n_nets_pad,
            batch_tile=batch_tile,
            interpret=interpret,
        )
    else:
        vals = lut_eval_pallas(
            bits_ext,
            packed.sel,
            packed.tables,
            packed.level_base,
            n_nets_pad=packed.n_nets_pad,
            batch_tile=batch_tile,
            interpret=interpret,
        )
    return jnp.take(vals, packed.output_nets, axis=1).astype(jnp.uint8)


def fabric_eval_bits(
    sel: jnp.ndarray,
    tables: jnp.ndarray,
    level_base: jnp.ndarray,
    win_base: jnp.ndarray,
    output_nets: jnp.ndarray,
    bits: jnp.ndarray,        # (C, B, n_inputs_max)
    *,
    n_inputs: int,
    n_nets_pad: int,
    in_seg: int,
    batch_tile: int,
    interpret: bool,
    src: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Traceable chip-batched evaluation of DEVICE-RESIDENT bit tensors.

    The un-jit'd core of ``fabric_eval_multi``: no numpy conversion, no
    padding, no host round-trip — ``bits`` may be the live output of an
    upstream device stage (the fused frontend's on-device quantize+pack,
    kernels/frontend.py) and this call composes inside the enclosing
    jit/shard_map. Requires B % batch_tile == 0.

    A non-None ``src`` selects the bit-sliced layout (``sel`` is None
    then): the word evaluator replaces the Pallas kernel. The branch is
    on the argument's pytree STRUCTURE, which jit caches on — a swap
    keeps the same structure, so hot-swaps still never retrace.
    """
    C, B = bits.shape[0], bits.shape[1]
    if src is not None:
        return _bitsliced.eval_bits(
            src, tables, output_nets, bits,
            n_inputs=n_inputs, in_seg=in_seg,
        )
    bits_ext = jnp.zeros((C, B, in_seg), jnp.float32)
    bits_ext = bits_ext.at[:, :, 1].set(1.0)
    bits_ext = bits_ext.at[:, :, 2 : 2 + n_inputs].set(
        bits.astype(jnp.float32)
    )
    # sel's row count is static under jit: fewer rows than the padded net
    # space means the banded layout (see PackedFabricStack).
    if sel.shape[2] < n_nets_pad:
        vals = lut_eval_pallas_banded_stacked(
            bits_ext,
            sel,
            tables,
            level_base,
            win_base,
            n_nets_pad=n_nets_pad,
            batch_tile=batch_tile,
            interpret=interpret,
        )                                               # (C, B, N)
    else:
        vals = lut_eval_pallas_stacked(
            bits_ext,
            sel,
            tables,
            level_base,
            n_nets_pad=n_nets_pad,
            batch_tile=batch_tile,
            interpret=interpret,
        )                                               # (C, B, N)
    idx = output_nets[:, None, :].astype(jnp.int32)     # (C, 1, O)
    return jnp.take_along_axis(vals.astype(jnp.int32), idx, axis=2).astype(
        jnp.uint8
    )


# NOTE: takes the stack's arrays and envelope scalars, NOT the
# PackedFabricStack pytree — its static per-chip width tuples change on
# swap_chip, and passing them through jit would retrace/recompile on every
# hot-swap, exactly the cost the stacked geometry exists to avoid.
_eval_stack_arrays = functools.partial(
    jax.jit,
    static_argnames=("n_inputs", "n_nets_pad", "in_seg", "batch_tile",
                     "interpret"),
)(fabric_eval_bits)


def fabric_eval_bits_voted(
    sel: jnp.ndarray,
    tables: jnp.ndarray,
    level_base: jnp.ndarray,
    win_base: jnp.ndarray,
    output_nets: jnp.ndarray,
    bits: jnp.ndarray,        # (C, B, n_inputs_max) — per LOGICAL chip
    *,
    n_replicas: int,
    n_inputs: int,
    n_nets_pad: int,
    in_seg: int,
    batch_tile: int,
    interpret: bool,
    src: jnp.ndarray | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traceable redundant evaluation: replicas in ONE dispatch, then the
    2-of-3 majority vote before the caller sees outputs.

    ``bits`` is per logical chip; each event is broadcast to that chip's
    ``n_replicas`` contiguous replica slots, all R*C slots evaluate in the
    same chip-batched kernel dispatch, and the vote reduces them. Returns
    (voted output bits (C, B, O) uint8, disagree (C, R, B) bool — True
    where a replica's output bits differ from the voted word, the per-
    replica SEU health signal). n_replicas == 1 degrades to the plain
    evaluation with an all-False disagree tensor.

    A non-None ``src`` (bit-sliced layout) routes to the word evaluator,
    whose majority vote is folded into the same bitwise pass
    (core.tmr.majority_vote_words on sliced uint32 words) — the cheap-TMR
    serving mode.
    """
    C, B = bits.shape[0], bits.shape[1]
    if src is not None:
        return _bitsliced.eval_bits_voted(
            src, tables, output_nets, bits,
            n_replicas=n_replicas, n_inputs=n_inputs, in_seg=in_seg,
        )
    rep_bits = (
        jnp.repeat(bits, n_replicas, axis=0) if n_replicas > 1 else bits
    )
    outs = fabric_eval_bits(
        sel, tables, level_base, win_base, output_nets, rep_bits,
        n_inputs=n_inputs, n_nets_pad=n_nets_pad, in_seg=in_seg,
        batch_tile=batch_tile, interpret=interpret,
    )                                                   # (R*C, B, O) uint8
    if n_replicas == 1:
        return outs, jnp.zeros((C, 1, B), jnp.bool_)
    assert n_replicas == N_REPLICAS, n_replicas
    g = outs.reshape(C, n_replicas, B, outs.shape[-1])
    voted = majority_vote(g[:, 0], g[:, 1], g[:, 2])    # (C, B, O)
    disagree = jnp.any(g != voted[:, None], axis=-1)    # (C, R, B)
    return voted, disagree


_eval_stack_voted = functools.partial(
    jax.jit,
    static_argnames=("n_replicas", "n_inputs", "n_nets_pad", "in_seg",
                     "batch_tile", "interpret"),
)(fabric_eval_bits_voted)


def decode_scores_device(
    outs: jnp.ndarray,          # (C, B, O) voted output bits
    disagree: jnp.ndarray,      # (C, R, B) bool replica-vs-vote mismatches
    out_weight: jnp.ndarray,    # (C, O) int32 two's-complement weights
    threshold_raw: jnp.ndarray, # (C,) int32
    valid: jnp.ndarray,         # (C, B) bool
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared device tail of BOTH serving dispatches (the features path's
    _eval_stack_scored and the fused frontend's _score_frames): decode
    two's-complement scores, apply the integer trigger cut masked by
    ``valid``, and count valid-row disagreements per replica. One
    definition so the trigger semantics cannot fork between ingestion
    paths."""
    score = jnp.sum(outs.astype(jnp.int32) * out_weight[:, None, :], axis=-1)
    keep = (score <= threshold_raw[:, None]) & valid
    dis = jnp.sum((disagree & valid[:, None, :]).astype(jnp.int32), axis=-1)
    return score, keep, dis


def decode_keep_words_device(
    voted_w: jnp.ndarray,       # (C, W, O) uint32 voted output words
    dis_w: jnp.ndarray,         # (C, R, W) uint32 disagreement words
    out_weight: jnp.ndarray,    # (C, O) int32 two's-complement weights
    threshold_raw: jnp.ndarray, # (C,) int32
    valid: jnp.ndarray,         # (C, B) bool
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``decode_scores_device`` stopped in the WORD domain: the trigger
    cut, per-lane scores and SEU counters computed on sliced words,
    without the word->event transpose — so sparse egress can compact
    BEFORE any event-order tensor exists and only kept events are ever
    transposed/shipped.

    Returns (keep_w (C, W) uint32 keep-mask words masked by ``valid``,
    scores (C, W, 32) int32 per-lane scores — lane ``e`` of word ``w`` is
    event ``w*32+e``, and disagree counts (C, R) int32 — identical to the
    event-domain tail's third output). Cut semantics match
    ``decode_scores_device`` bit for bit: sign-extended two's-complement
    planes -> bit-serial biased unsigned compare ``score <= threshold``.
    """
    valid_w = _bitsliced.mask_words(valid)                  # (C, W)
    planes = _bitsliced.sign_extended_planes(voted_w, out_weight)
    keep_w = _bitsliced.keep_words(planes, threshold_raw, valid_w)
    scores = _bitsliced.lane_scores(planes)
    dis = _bitsliced.disagree_counts_words(dis_w, valid_w)
    return keep_w, scores, dis


def decode_plan(
    configs: Sequence[FabricConfig],
    n_outputs: int,
) -> np.ndarray:
    """Per-chip score-decode weights for the device scoring stage.

    Returns out_weight (C, n_outputs) int32 — two's-complement bit
    weights, zero on padded lanes. Same contract as the fused frontend's
    encode plan rows (kernels.frontend._plan_row), restated here so the
    features ingestion path can decode on device without a featurizer.
    Output width must be int32-representable (<= 31 bits). The integer
    trigger cuts are NOT derived here — the caller (the readout server)
    owns one threshold array and ships it to the dispatch directly, so
    there is exactly one copy to keep current.
    """
    C = len(configs)
    weight = np.zeros((C, n_outputs), np.int64)
    for i, c in enumerate(configs):
        n_out = len(c.output_nets)
        if n_out > 31:
            raise ValueError(
                f"device score decode is int32: chip {i} has {n_out} "
                "output bits > 31"
            )
        weight[i, :n_out] = 1 << np.arange(n_out)
        if n_out:
            weight[i, n_out - 1] = -(1 << (n_out - 1))
    return weight.astype(np.int32)


# Static args are the ENVELOPE + mesh only (never per-chip values): the
# same no-retrace rule as _eval_stack_arrays and the fused frontend's
# _score_frames — hot-swaps and threshold updates stay array swaps.
@functools.partial(
    jax.jit,
    static_argnames=("mesh", "n_replicas", "n_inputs", "n_nets_pad",
                     "in_seg", "batch_tile", "interpret", "sparse"),
)
def _eval_stack_scored(
    sel: jnp.ndarray,
    tables: jnp.ndarray,
    level_base: jnp.ndarray,
    win_base: jnp.ndarray,
    output_nets: jnp.ndarray,
    bits: jnp.ndarray,          # (C, B, n_inputs_max)
    out_weight: jnp.ndarray,    # (C, n_outputs_max) int32
    threshold_raw: jnp.ndarray, # (C,) int32
    valid: jnp.ndarray,         # (C, B) bool — kills padded event rows
    src: jnp.ndarray | None = None,  # bit-sliced gather indices (or None)
    *,
    mesh: Mesh,
    n_replicas: int,
    n_inputs: int,
    n_nets_pad: int,
    in_seg: int,
    batch_tile: int,
    interpret: bool,
    sparse: bool = False,
):
    """Sharded serving dispatch for pre-packed input bits: evaluate (all
    replicas), vote, decode two's-complement scores and apply the integer
    trigger cut — chip axis shard_map'd over the "chips" readout mesh.

    Dense mode (``sparse=False``) returns (score (C, B) int32, keep
    (C, B) bool — already masked by ``valid``, disagree_counts (C, R)
    int32 — voted-against events per replica, counted over valid rows
    only).

    ``sparse=True`` (bit-sliced stacks only — requires ``src``) keeps the
    whole pipeline in the word domain: per shard the trigger cut and SEU
    counters come off sliced words (``decode_keep_words_device``), then
    — after the shard_map, where the chip axis is global again — the
    popcount prefix-sum compaction packs ONLY the kept events
    (``sparse_trigger_pack_words``). Returns (count () int32, idx
    (C*B*?,) int32 ascending flat indices -1 padded, vals int32 0
    padded, disagree_counts (C, R) int32) — the same wire format as
    ``parallel.compression.sparse_trigger_pack``, produced without ever
    materializing a dense event-order score tensor. The flag is static
    (one retrace per (shape, flag), bounded — it only toggles on the
    degrade ladder's sparse_egress rung or a config change).
    """

    shard = P("chips")

    if sparse:
        if src is None:
            raise ValueError(
                "sparse=True needs the word domain: pack the stack with "
                "layout='bitsliced' (matmul stacks have no word form)")

        def body_sparse(sel, tables, output_nets, bits, out_weight,
                        threshold_raw, valid, src):
            voted_w, dis_w = _bitsliced.eval_words_voted(
                src, tables, output_nets, bits,
                n_replicas=n_replicas, n_inputs=n_inputs, in_seg=in_seg,
            )
            return decode_keep_words_device(
                voted_w, dis_w, out_weight, threshold_raw, valid)

        keep_w, scores, dis = jax.shard_map(
            body_sparse, mesh=mesh,
            in_specs=(shard,) * 8,
            out_specs=(shard, shard, shard),
            check_vma=False,
        )(sel, tables, output_nets, bits, out_weight, threshold_raw,
          valid, src)
        # Compaction is CROSS-chip (one ascending flat index space), so it
        # runs after the manual region but inside the same jit: nothing
        # event-ordered exists until only kept events remain.
        count, idx, vals = sparse_trigger_pack_words(keep_w, scores)
        return count, idx, vals, dis

    def body(sel, tables, output_nets, bits, out_weight, threshold_raw,
             valid, src):
        outs, disagree = fabric_eval_bits_voted(
            sel, tables, level_base, win_base, output_nets, bits,
            n_replicas=n_replicas, n_inputs=n_inputs,
            n_nets_pad=n_nets_pad, in_seg=in_seg, batch_tile=batch_tile,
            interpret=interpret, src=src,
        )
        return decode_scores_device(
            outs, disagree, out_weight, threshold_raw, valid)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(shard,) * 8,
        out_specs=(shard, shard, shard),
        check_vma=False,
    )(sel, tables, output_nets, bits, out_weight, threshold_raw, valid, src)


def fabric_eval_multi_scored(
    stack: PackedFabricStack,
    bits,
    out_weight,
    threshold_raw,
    valid=None,
    *,
    mesh: Mesh,
    batch_tile: int = 128,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Score (chips, events) input bits in one sharded, voted dispatch.

    The serving form of ``fabric_eval_multi``: replicas evaluated and
    majority-voted on device (redundant stacks), scores decoded on device
    (``decode_plan`` arrays) and the keep/drop cut applied there too —
    the host sees only (score, keep, per-replica disagreement counts),
    and with sparse readout (parallel.compression) only the kept events.
    Results are NOT materialized; np.asarray them (or let the readout
    server drain) to block.
    """
    if interpret is None:
        interpret = _default_interpret()
    bits = jnp.asarray(bits)
    C, B = bits.shape[0], bits.shape[1]
    assert C == stack.n_chips, (C, stack.n_chips)
    Bp = _round_up(max(B, 1), batch_tile)
    if valid is None:
        valid = jnp.ones((C, B), jnp.bool_)
    else:
        valid = jnp.asarray(valid, jnp.bool_)
    if Bp != B:
        bits = jnp.pad(bits, ((0, 0), (0, Bp - B), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, Bp - B)))
    score, keep, dis = _eval_stack_scored(
        stack.sel, stack.tables, stack.level_base, stack.win_base,
        stack.output_nets, bits,
        jnp.asarray(out_weight, jnp.int32),
        jnp.asarray(threshold_raw, jnp.int32),
        valid,
        stack.src,
        mesh=mesh, n_replicas=stack.n_replicas, n_inputs=stack.n_inputs,
        n_nets_pad=stack.n_nets_pad, in_seg=stack.in_seg,
        batch_tile=batch_tile, interpret=interpret,
    )
    return score[:, :B], keep[:, :B], dis


def fabric_eval_multi_scored_sparse(
    stack: PackedFabricStack,
    bits,
    out_weight,
    threshold_raw,
    valid=None,
    *,
    mesh: Mesh,
    batch_tile: int = 128,
    interpret: bool | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Word-domain sparse twin of ``fabric_eval_multi_scored``.

    Same inputs; instead of dense (score, keep) it returns the packed
    sparse wire tuple (count () int32, idx (C*B,) int32 ascending flat
    indices ``chip*B + event`` -1 padded, vals (C*B,) int32 kept scores 0
    padded, disagree_counts (C, R) int32). The keep cut, SEU counters and
    compaction all run on sliced words inside one jit — dropped events
    are never transposed back to event order and never leave the device.
    Bit-sliced stacks only (``stack.src`` must exist). Results are NOT
    materialized; slice ``idx[:count]`` on device and np.asarray to ship
    exactly the kept prefix (what the readout server's drain does).
    """
    if stack.src is None:
        raise ValueError(
            "fabric_eval_multi_scored_sparse needs layout='bitsliced' "
            "(word-domain egress has no matmul form)")
    if interpret is None:
        interpret = _default_interpret()
    bits = jnp.asarray(bits)
    C, B = bits.shape[0], bits.shape[1]
    assert C == stack.n_chips, (C, stack.n_chips)
    Bp = _round_up(max(B, 1), batch_tile)
    if valid is None:
        valid = jnp.ones((C, B), jnp.bool_)
    else:
        valid = jnp.asarray(valid, jnp.bool_)
    if Bp != B:
        bits = jnp.pad(bits, ((0, 0), (0, Bp - B), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, Bp - B)))
    count, idx, vals, dis = _eval_stack_scored(
        stack.sel, stack.tables, stack.level_base, stack.win_base,
        stack.output_nets, bits,
        jnp.asarray(out_weight, jnp.int32),
        jnp.asarray(threshold_raw, jnp.int32),
        valid,
        stack.src,
        mesh=mesh, n_replicas=stack.n_replicas, n_inputs=stack.n_inputs,
        n_nets_pad=stack.n_nets_pad, in_seg=stack.in_seg,
        batch_tile=batch_tile, interpret=interpret, sparse=True,
    )
    if Bp != B:
        # Kept lanes always sit below B (``valid`` kills the pad tail), so
        # restriding the flat index from the tile-padded batch to the
        # caller's keeps ascending order and fits the packed vectors in
        # C*B slots.
        idx = jnp.where(idx >= 0, (idx // Bp) * B + (idx % Bp), -1)
        idx = idx[: C * B]
        vals = vals[: C * B]
    return count, idx, vals, dis


def fabric_eval(
    config_or_packed,
    bits,
    batch_tile: int = 128,
    interpret: bool | None = None,
    band: bool | None = None,
    layout: str = "matmul",
) -> jnp.ndarray:
    """Evaluate a batch of events on the configured fabric.

    bits: (B, n_inputs) 0/1. Returns (B, n_outputs) uint8. B is padded up to
    a batch_tile multiple internally. ``band``/``layout`` select the device
    layout when packing a raw config (ignored for an already-packed fabric).
    """
    packed = (
        config_or_packed
        if isinstance(config_or_packed, PackedFabric)
        else pack_fabric(config_or_packed, band=band, layout=layout)
    )
    if interpret is None:
        interpret = _default_interpret()
    bits = jnp.asarray(bits)
    B = bits.shape[0]
    Bp = _round_up(max(B, 1), batch_tile)
    if Bp != B:
        bits = jnp.pad(bits, ((0, Bp - B), (0, 0)))
    out = _eval_packed(packed, bits, batch_tile=batch_tile, interpret=interpret)
    return out[:B]


def stack_input_bits(
    stack: PackedFabricStack, per_chip_bits: Sequence[np.ndarray]
) -> np.ndarray:
    """Zero-pad per-chip (B_i, n_inputs_i) bit arrays into the stacked
    (C, B_max, n_inputs_max) layout the multi kernel consumes."""
    assert len(per_chip_bits) == stack.n_chips, (
        len(per_chip_bits), stack.n_chips)
    for i, b in enumerate(per_chip_bits):
        if np.asarray(b).size:
            assert np.asarray(b).shape[1] == stack.n_inputs_each[i], (
                np.asarray(b).shape, stack.n_inputs_each[i])
    return fabric_stack_event_bits(per_chip_bits, stack.n_inputs)


def fabric_eval_multi(
    stack_or_configs: Union[PackedFabricStack, Sequence[FabricConfig]],
    bits,
    batch_tile: int = 128,
    interpret: bool | None = None,
    band: bool | None = None,
    layout: str = "matmul",
) -> jnp.ndarray:
    """Evaluate (chips, events) in ONE chip-batched kernel dispatch.

    bits: (C, B, n_inputs_max) 0/1 (see stack_input_bits), or a list of
    per-chip (B_i, n_inputs_i) arrays — always per LOGICAL chip. Returns
    (C, B, n_outputs_max) uint8 with padded lanes reading 0; slice lane i
    to n_outputs_each[i]. On a redundant stack all replicas evaluate in
    the same dispatch and the returned bits are the majority-voted word
    (use ``fabric_eval_multi_scored`` to also read the per-replica
    disagreement counters). ``band``/``layout`` select the device layout
    when packing raw configs.
    """
    stack = (
        stack_or_configs
        if isinstance(stack_or_configs, PackedFabricStack)
        else pack_fabrics(list(stack_or_configs), band=band, layout=layout)
    )
    if not isinstance(bits, (jnp.ndarray, np.ndarray)):
        bits = stack_input_bits(stack, bits)
    if interpret is None:
        interpret = _default_interpret()
    bits = jnp.asarray(bits)
    C, B = bits.shape[0], bits.shape[1]
    assert C == stack.n_chips, (C, stack.n_chips)
    Bp = _round_up(max(B, 1), batch_tile)
    if Bp != B:
        bits = jnp.pad(bits, ((0, 0), (0, Bp - B), (0, 0)))
    if stack.redundant:
        out, _ = _eval_stack_voted(
            stack.sel, stack.tables, stack.level_base, stack.win_base,
            stack.output_nets, bits,
            n_replicas=stack.n_replicas, n_inputs=stack.n_inputs,
            n_nets_pad=stack.n_nets_pad, in_seg=stack.in_seg,
            batch_tile=batch_tile, interpret=interpret, src=stack.src,
        )
    else:
        out = _eval_stack_arrays(
            stack.sel, stack.tables, stack.level_base, stack.win_base,
            stack.output_nets, bits,
            n_inputs=stack.n_inputs, n_nets_pad=stack.n_nets_pad,
            in_seg=stack.in_seg, batch_tile=batch_tile, interpret=interpret,
            src=stack.src,
        )
    return out[:, :B]
