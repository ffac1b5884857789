"""Planted faults and the control, to show that ``correct`` can fail.

The benchmark's own runs plant nothing.  ``run.py --fault <name>`` (for
the control on the chip) and the tests plant one, by patching the store's
classes for the length of a run:

* ``lost_ack`` (the control of the write cells): the write-ahead log
  appends nothing, the change a PR tempted by faster puts would make.  An
  acknowledged write that is still in the memtable at ``close()`` is then
  gone after reopen, which breaks the stated durability guarantee.
* ``bloom_false_negative`` (the control of the read cells): the batched
  read path's filter stage drops one in 64 of the blocks it should keep,
  as an approximate filter tuned for speed would.  A key that is there
  then reads as absent, which breaks exact answers.
* ``put_unapplied``: a put is acknowledged and leaves the store as it was
  (a step that returns its state unchanged).
* ``half_batch``: ``multi_get`` answers the first half of its keys and
  leaves the rest out.
* ``altered_answer``: one answer in 101 of ``get`` and ``multi_get`` has
  its first byte changed where it is produced.
"""

from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("lost_ack", "bloom_false_negative", "put_unapplied", "half_batch",
          "altered_answer")


def _alter(v):
    return v if v is None or not v else bytes([v[0] ^ 1]) + v[1:]


@contextlib.contextmanager
def planted(names):
    """Patch the store for each fault named, and undo it on exit."""
    from repro.lsm import read as lsm_read
    from repro.lsm import wal
    from repro.lsm.db import LsmDB
    unknown = set(names) - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}; have {FAULTS}")
    undo = []

    def patch(owner, attr, new):
        undo.append((owner, attr, owner.__dict__[attr]))
        setattr(owner, attr, new)

    if "lost_ack" in names:
        patch(wal.WALWriter, "append", lambda self, *a, **k: None)
    if "bloom_false_negative" in names:
        stage = lsm_read._bloom_stage
        rng = np.random.default_rng(64)

        def lossy(filters, probes, geom, backend):
            keep = np.asarray(stage(filters, probes, geom, backend)).copy()
            keep &= rng.random(keep.shape[0]) >= 1 / 64
            return keep
        patch(lsm_read, "_bloom_stage", lossy)
    if "put_unapplied" in names:
        patch(LsmDB, "put", lambda self, key, value, opts=None: None)
    if "half_batch" in names:
        mget = LsmDB.multi_get

        def half(self, keys, opts=None):
            keys = list(keys)
            h = len(keys) // 2 or len(keys)
            return mget(self, keys[:h], opts) + [None] * (len(keys) - h)
        patch(LsmDB, "multi_get", half)
    if "altered_answer" in names:
        get, mget = LsmDB.get, LsmDB.multi_get
        calls = [0]

        def get2(self, key, opts=None):
            calls[0] += 1
            v = get(self, key, opts)
            return _alter(v) if calls[0] % 101 == 0 else v

        def mget2(self, keys, opts=None):
            out = mget(self, keys, opts)
            calls[0] += 1
            i = calls[0] % 101
            if i < len(out):
                out[i] = _alter(out[i])
            return out
        patch(LsmDB, "get", get2)
        patch(LsmDB, "multi_get", mget2)
    try:
        yield
    finally:
        for owner, attr, old in reversed(undo):
            setattr(owner, attr, old)
