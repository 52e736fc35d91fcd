"""Trusted light-block store.

Reference parity: light/store/db — persisted light blocks keyed by height
with first/last queries and pruning.
"""

from __future__ import annotations

import struct
from typing import Optional

from ..db import DB
from ..observability import trace as _trace
from .provider import LightBlock

_span = _trace.span

_PREFIX = b"lb/"


def _key(height: int) -> bytes:
    return _PREFIX + struct.pack(">q", height)


class LightStore:
    def __init__(self, db: DB):
        self._db = db

    def save_light_block(self, lb: LightBlock) -> None:
        with _span("light.store.save", height=lb.height):
            self._db.set(_key(lb.height), lb.encode())

    def light_block(self, height: int) -> Optional[LightBlock]:
        raw = self._db.get(_key(height))
        return None if raw is None else LightBlock.decode(raw)

    def first_light_block_height(self) -> int:
        for k, _ in self._db.iterator(_key(0), _key((1 << 62))):
            return struct.unpack(">q", k[len(_PREFIX):])[0]
        return -1

    def last_light_block_height(self) -> int:
        for k, _ in self._db.reverse_iterator(_key(0), _key((1 << 62))):
            return struct.unpack(">q", k[len(_PREFIX):])[0]
        return -1

    def latest_light_block(self) -> Optional[LightBlock]:
        h = self.last_light_block_height()
        return self.light_block(h) if h >= 0 else None

    def light_block_before(self, height: int) -> Optional[LightBlock]:
        for k, _ in self._db.reverse_iterator(_key(0), _key(height)):
            return self.light_block(struct.unpack(">q", k[len(_PREFIX):])[0])
        return None

    def prune(self, size: int) -> int:
        """Keep only the newest `size` blocks (store/db prune)."""
        heights = [
            struct.unpack(">q", k[len(_PREFIX):])[0]
            for k, _ in self._db.iterator(_key(0), _key(1 << 62))
        ]
        pruned = 0
        for h in heights[: max(0, len(heights) - size)]:
            self._db.delete(_key(h))
            pruned += 1
        return pruned
