"""Reused host staging buffers (arenas) for the readout server's frames path.

A kernel-backend frames dispatch stages C x B charge frames of (T, Y, X)
float32 on the host before ``jax.device_put`` ships them: 71.6 MB for
4 modules x 2,048 frames. Allocating, zero-filling and freeing a buffer
that size for every dispatch costs more than the copy itself (each fresh
page faults and is zeroed), so the server stages into arenas instead: flat
C-contiguous float32 buffers that outlive the dispatch and are written in
place by the next one.

Lifetime: the transfer may still read the host buffer after
``device_put`` returns, and on the CPU backend the device array may alias
it outright. So an arena comes back (``give``) only once the batch staged
in it has drained, which implies the computation that read it is done.
``take`` never hands out an arena a batch in flight still holds: with none
free it allocates, and counts that as ``fresh``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


class StagingArenas:
    """A free list of at most ``limit`` flat float32 arenas. Capacity
    follows the largest request seen: a request too large for every free
    arena replaces one of them with an arena of its size.

    ``reused`` and ``fresh`` count the requests served from a free arena
    and by an allocation since the last ``reset_counts``."""

    def __init__(self, limit: int):
        self.limit = limit
        self._free: List[np.ndarray] = []
        self._lent: Dict[int, np.ndarray] = {}   # id -> arena, in flight
        self.reused = 0
        self.fresh = 0

    def take(self, size: int) -> np.ndarray:
        """A flat float32 buffer of at least ``size`` elements that no
        batch in flight holds. Its contents are whatever it last held."""
        for k, arena in enumerate(self._free):
            if arena.size >= size:
                self.reused += 1
                return self._lend(self._free.pop(k))
        if self._free:                   # too small for this batch
            self._free.pop(0)
        self.fresh += 1
        buf = np.empty(size, np.float32)
        if len(self._free) + len(self._lent) >= self.limit:
            return buf                   # a one-off: ``give`` drops it
        return self._lend(buf)

    def _lend(self, arena: np.ndarray) -> np.ndarray:
        self._lent[id(arena)] = arena
        return arena

    def give(self, buf: Optional[np.ndarray]) -> None:
        """Return ``buf`` (from ``take``) once its batch has drained."""
        arena = self._lent.pop(id(buf), None)
        if arena is not None:
            self._free.append(arena)

    def reset_counts(self) -> None:
        self.reused = 0
        self.fresh = 0

    def report(self) -> Dict[str, int]:
        """``reused``/``fresh`` since the reset, the ``arenas`` alive now
        (free and in flight) and the bytes they hold."""
        alive = self._free + list(self._lent.values())
        return {"reused": self.reused, "fresh": self.fresh,
                "arenas": len(alive),
                "resident_bytes": int(sum(a.nbytes for a in alive))}
