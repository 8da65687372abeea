"""The work the algorithm needs per real event, counted from shapes, and the
table of peaks it is held against.

Padding (pow2 batch buckets, the batch tile, the envelope's spare levels)
adds no work here: it shows as a lower share of the roofline.
"""
from __future__ import annotations

import json
import math
from typing import Dict, Tuple

from readout.spec import HERE

F32 = 4


def frame_bytes(cfg: Dict) -> int:
    """Compulsory input bytes per event: the float32 frame and its y0, read
    once (8 x 13 x 21 x 4 + 4 = 8,740 B for the smart-pixel sensor)."""
    return math.prod(cfg["sensor"]["frame"]) * F32 + F32


def yprofile_work(cfg: Dict, n_events: int) -> Tuple[float, float]:
    """(operations, bytes) of featurizing ``n_events``: one add per charge
    into its y bin, a compare and a scale per bin; read the frame and y0,
    write the Y bins and y0."""
    t, y, x = cfg["sensor"]["frame"]
    ops = (t * y * x + 3 * y) * n_events
    nbytes = (frame_bytes(cfg) + (y + 1) * F32) * n_events
    return float(ops), float(nbytes)


def load_peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def roofline_seconds(ops: float, nbytes: float, peaks: Dict[str, float]
                     ) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it. The
    compute bound uses the bf16 MXU peak, the highest the table has."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "compute")
