"""YCSB generator and latency percentiles, kept with the benchmark.

Copied from the store's own driver (``repro.data.ycsb`` and
``benchmarks/ycsb_bench.percentiles``) so that a change to the program
cannot change the yardstick.

* ``ZipfianGenerator``: Gray et al.'s rejection-free zipfian over
  ``[0, n)``, as in the YCSB reference implementation (constant 0.99).
* ``key_of``: the 16-byte key of record ``i`` (``user`` + 12 hex digits of
  a 48-bit multiplicative hash, so the zipfian head is spread over the
  key space, as YCSB hashes its record ids).  ``id_of`` inverts it.
* ``value_of``: YCSB's value of record ``i``: its 16-digit number repeated
  to the width.
* ``percentiles``: linear interpolation between closest ranks (numpy's
  default ``percentile`` rule).
"""

from __future__ import annotations

import numpy as np

ZIPF_CONST = 0.99

_MULT = 0x9E3779B97F4A7C15
_MASK = (1 << 48) - 1
_INV = pow(_MULT & _MASK, -1, 1 << 48)


class ZipfianGenerator:
    """Gray's zipfian generator over [0, n)."""

    def __init__(self, n: int, theta: float = ZIPF_CONST, seed: int = 0):
        self.n = n
        self.theta = theta
        self.rng = np.random.default_rng(seed)
        self.alpha = 1.0 / (1.0 - theta)
        self.zetan = self._zeta(n)
        self.zeta2 = self._zeta(2)
        self.eta = ((1 - (2.0 / n) ** (1 - theta)) /
                    (1 - self.zeta2 / self.zetan))

    def _zeta(self, n: int) -> float:
        return float(np.sum(1.0 / np.arange(1, n + 1) ** self.theta))

    def sample(self, size: int | None = None) -> np.ndarray:
        u = self.rng.random(size if size is not None else ())
        uz = u * self.zetan
        out = np.where(
            uz < 1.0, 0,
            np.where(uz < 1.0 + 0.5 ** self.theta, 1,
                     (self.n * (self.eta * u - self.eta + 1.0)
                      ** self.alpha).astype(np.int64)))
        return np.clip(out, 0, self.n - 1)


def key_of(i: int) -> bytes:
    h = (i * _MULT) & _MASK
    return b"user%012x" % h


def id_of(key: bytes) -> int | None:
    """The record id whose key is ``key``, or None for a key that
    ``key_of`` cannot produce."""
    if len(key) != 16 or not key.startswith(b"user"):
        return None
    try:
        h = int(key[4:], 16)
    except ValueError:
        return None
    i = (h * _INV) & _MASK
    return i if key_of(i) == key else None


def value_of(i: int, width: int) -> bytes:
    body = (b"%016d" % i) * (width // 16 + 1)
    return body[:width]


def percentiles(lat_us, qs=(50.0, 99.0, 99.9)) -> dict[float, float]:
    """{q: latency} with linear interpolation between closest ranks."""
    if not len(lat_us):
        return {q: 0.0 for q in qs}
    arr = sorted(lat_us)
    n = len(arr)
    out = {}
    for q in qs:
        pos = (q / 100.0) * (n - 1)
        lo = int(pos)
        hi = min(lo + 1, n - 1)
        out[q] = arr[lo] + (arr[hi] - arr[lo]) * (pos - lo)
    return out
