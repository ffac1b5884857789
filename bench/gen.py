"""The one traffic generator: turns a mix's data file into operations.

A mix (``bench/traffic/<name>.json``) gives:

* ``mix``: share of each operation kind, ``read``, ``update``, ``insert``;
* ``keys``: ``zipfian`` (with ``zipf_theta``) or ``uniform`` over the
  preloaded records, for reads and updates; inserts always take new ids;
* ``read_call``: ``get`` (one key per call) or ``multi_get`` (``batch``
  keys per call).

Every seed gets the same work in another order: each chunk holds exactly
the mix's share of each kind, and the seed only draws the order, the
record ids and the values.  Record ``i`` of the preload is
``key_of(i) -> value_of(i)`` whatever the seed.
"""

from __future__ import annotations

import numpy as np

from ycsb import ZipfianGenerator, key_of, value_of

GET, MULTI_GET, PUT = 0, 1, 2
KINDS = ("read", "update", "insert")

#: new-record ids start here (past any preload), warm-up below the window
INSERT_BASE_WARMUP = 1 << 30
INSERT_BASE_WINDOW = 1 << 31
#: update values are numbered from here, apart from every record id
VALUE_BASE_WARMUP = 2 * 10**15
VALUE_BASE_WINDOW = 10**15

#: keys generated at a time
CHUNK = 4096


def seed_state(seed: int, stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % (1 << 64), stream])


class OpStream:
    """Deterministic operations of one mix: ``next_chunk()`` returns
    ``(kinds, keys, values)`` lists, where a ``MULTI_GET`` op's key is a
    list of keys and a read's value is None."""

    def __init__(self, mix: dict, *, records: int, value_size: int,
                 seed: int, stream: int, warmup: bool = False):
        shares = mix["mix"]
        unknown = set(shares) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown operation kinds {sorted(unknown)}")
        self.shares = [float(shares.get(k, 0.0)) for k in KINDS]
        if abs(sum(self.shares) - 1.0) > 1e-9:
            raise ValueError(f"mix shares sum to {sum(self.shares)}, not 1")
        self.records = records
        self.width = value_size
        self.read_call = mix.get("read_call", "get")
        self.batch = int(mix.get("batch", 1)) \
            if self.read_call == "multi_get" else 1
        ss = seed_state(seed, stream)
        self.rng = np.random.default_rng(ss)
        dist = mix.get("keys", "uniform")
        if dist == "zipfian":
            self.zipf = ZipfianGenerator(
                records, theta=float(mix.get("zipf_theta", 0.99)),
                seed=int(ss.generate_state(1, np.uint64)[0] >> 1))
        elif dist == "uniform":
            self.zipf = None
        else:
            raise ValueError(f"unknown key distribution {dist!r}")
        if warmup:
            self.next_insert = records + INSERT_BASE_WARMUP
            self.value_base = VALUE_BASE_WARMUP
        else:
            # a range of its own per seed, beyond the preload
            self.next_insert = (records + INSERT_BASE_WINDOW +
                                (seed % (1 << 16)) * (1 << 24))
            self.value_base = VALUE_BASE_WINDOW + (seed % 10**6) * 10**8
        self.n_ops = 0
        #: operations per chunk: about CHUNK keys
        self.chunk = max(16, CHUNK // self.batch)

    def _ids(self, n: int) -> list[int]:
        if self.zipf is not None:
            return self.zipf.sample(n).tolist()
        return self.rng.integers(0, self.records, n).tolist()

    def next_chunk(self, n: int | None = None):
        n = self.chunk if n is None else n
        counts = [int(round(s * n)) for s in self.shares]
        counts[int(np.argmax(self.shares))] += n - sum(counts)
        order = self.rng.permutation(np.repeat(np.arange(3), counts))
        reads = counts[0] * self.batch
        read_ids = iter(self._ids(reads)) if reads else iter(())
        upd_ids = iter(self._ids(counts[1])) if counts[1] else iter(())
        kinds, keys, values = [], [], []
        for j, kind in enumerate(order.tolist()):
            if kind == 0:
                if self.batch > 1 or self.read_call == "multi_get":
                    kinds.append(MULTI_GET)
                    keys.append([key_of(next(read_ids))
                                 for _ in range(self.batch)])
                else:
                    kinds.append(GET)
                    keys.append(key_of(next(read_ids)))
                values.append(None)
            elif kind == 1:
                kinds.append(PUT)
                keys.append(key_of(next(upd_ids)))
                values.append(value_of(self.value_base + self.n_ops + j,
                                       self.width))
            else:
                i = self.next_insert
                self.next_insert += 1
                kinds.append(PUT)
                keys.append(key_of(i))
                values.append(value_of(i, self.width))
        self.n_ops += n
        return kinds, keys, values


def preload_records(records: int, value_size: int):
    """YCSB's load phase: record ``i`` in id order.  Depends on the
    configuration only, never on a seed."""
    for i in range(records):
        yield key_of(i), value_of(i, value_size)
