"""LRU-bounded compiled-function cache — THE ``_fns`` pattern.

One implementation for every per-shape jit cache of the serving stack
(inference's generate cache, MoEServer._fns, serving/backend.py's
DensePrograms._fns — a backend itself holds none): a
long-lived process sweeping shapes (batch buckets, growing scan lengths,
several max_seq tiers) would otherwise retain a compiled executable per
shape forever. A small cap comfortably covers a server's steady-state
shape set while letting XLA reclaim evicted programs.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable


class LRUFnCache:
    """``get(key, build)``: return the cached value or build+insert it,
    evicting least-recently-used entries beyond ``cap``."""

    def __init__(self, cap: int = 16):
        if cap < 1:
            raise ValueError(f"cap must be >= 1, got {cap}")
        self.cap = cap
        self._d: OrderedDict = OrderedDict()

    def get(self, key, build: Callable):
        val = self._d.get(key)
        if val is None:
            val = self._d[key] = build()
            while len(self._d) > self.cap:
                self._d.popitem(last=False)
        else:
            self._d.move_to_end(key)  # LRU: a hit refreshes recency
        return val

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d
