"""The plain reference: a dict of the same operations over the preload.

It imports nothing of the store.  A preloaded record's value follows from
its id (``value_of(id)``), so the 1M-record preload needs no dict; the
dict holds only what the window and warm-up wrote on top of it.
"""

from __future__ import annotations

from ycsb import id_of, value_of


class Reference:
    def __init__(self, records: int, value_size: int):
        self.records = records
        self.width = value_size
        self.written: dict[bytes, bytes] = {}

    def put(self, key: bytes, value: bytes):
        self.written[key] = value

    def get(self, key: bytes) -> bytes | None:
        v = self.written.get(key)
        if v is not None:
            return v
        i = id_of(key)
        if i is not None and i < self.records:
            return value_of(i, self.width)
        return None


def count_mismatches(got, keys, ref: Reference) -> int:
    return sum(1 for g, k in zip(got, keys) if g != ref.get(k))
