"""SST file I/O, the ``TableReader`` read protocol, and the host caches.

The on-disk format is the raw dump of the device wire image (DESIGN.md §2):

  magic "LUDASST1"
  u32 n_blocks, block_kvs, key_lanes, value_words, bloom_groups, bloom_words
  keys   uint32 LE [n_blocks, block_kvs, key_lanes]
  meta   uint32 LE [n_blocks, block_kvs]
  vals   uint32 LE [n_blocks, block_kvs, value_words]
  shared int32  LE [n_blocks, block_kvs]
  nvalid int32  LE [n_blocks]
  crc    uint32 LE [n_blocks]
  bloom  uint32 LE [bloom_groups, bloom_words]
  u32 file_crc  -- crc32 of everything before this field

Trailing all-zero blocks (``nvalid == 0``) are trimmed on write: compaction
outputs are sized for worst case, real files only pay for live blocks.

Read protocol (docs/read_path.md): ``TableReader`` is the ONE decode entry
point for point reads.  Metadata (raw arrays, per-block first keys, bloom
rows) loads lazily on first touch; individual blocks decode on demand
through a shared ``BlockCache``, so a point lookup pays for one block,
never the whole file.  ``TableReader.get/multi_get/scan`` mirror the
``LsmDB``/``ShardedDB`` signatures.  (The pre-protocol entry points --
``DecodedTable.get`` and the eager whole-file ``TableCache.get`` --
finished their deprecation cycle and are gone.)
"""

from __future__ import annotations

import binascii
import bisect
import dataclasses
import os
import struct
import threading
import time
from collections import OrderedDict

import numpy as np

from repro.core import formats
from repro.core.formats import SSTGeometry, SSTImage
from repro.lsm import faults
from repro.obs.trace import NULL_TRACER

MAGIC = b"LUDASST1"
SENTINEL = np.uint32(0xFFFFFFFF)   # all-ones key: sorts after any real key


@dataclasses.dataclass
class FileMeta:
    file_no: int
    path: str
    smallest: bytes           # first live user key (trimmed)
    largest: bytes            # last live user key (trimmed)
    n_entries: int
    size_bytes: int

    def to_json(self):
        return dict(file_no=self.file_no, path=self.path,
                    smallest=self.smallest.hex(), largest=self.largest.hex(),
                    n_entries=self.n_entries, size_bytes=self.size_bytes)

    @classmethod
    def from_json(cls, d):
        return cls(file_no=d["file_no"], path=d["path"],
                   smallest=bytes.fromhex(d["smallest"]),
                   largest=bytes.fromhex(d["largest"]),
                   n_entries=d["n_entries"], size_bytes=d["size_bytes"])


def _np_image(img: SSTImage) -> SSTImage:
    return SSTImage(*(np.asarray(a) for a in img))


def trim_image(img: SSTImage) -> SSTImage:
    """Drop trailing empty blocks (static-shape compaction padding)."""
    nvalid = np.asarray(img.nvalid)
    live = int((nvalid > 0).sum())
    live = max(1, live)
    img = _np_image(img)
    if img.bloom.shape[0] == img.keys.shape[0]:  # block-granularity blooms
        bloom = img.bloom[:live]
    else:
        bloom = img.bloom
    return SSTImage(keys=img.keys[:live], meta=img.meta[:live],
                    vals=img.vals[:live], shared=img.shared[:live],
                    nvalid=img.nvalid[:live], crc=img.crc[:live],
                    bloom=bloom)


def write_sst(path: str, img: SSTImage, file_no: int) -> FileMeta:
    img = trim_image(img)
    b, k, lanes = img.keys.shape
    vw = img.vals.shape[-1]
    g, w = img.bloom.shape
    header = MAGIC + struct.pack("<6I", b, k, lanes, vw, g, w)
    payload = b"".join([
        header,
        img.keys.astype("<u4").tobytes(),
        img.meta.astype("<u4").tobytes(),
        img.vals.astype("<u4").tobytes(),
        img.shared.astype("<i4").tobytes(),
        img.nvalid.astype("<i4").tobytes(),
        img.crc.astype("<u4").tobytes(),
        img.bloom.astype("<u4").tobytes(),
    ])
    payload += struct.pack("<I", binascii.crc32(payload) & 0xFFFFFFFF)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        if faults.fire("sst.write") is faults.TORN:
            f.write(payload[: max(1, len(payload) // 2)])
            f.flush()
            raise faults.SimulatedCrash("sst.write")
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    faults.fire("sst.rename")   # a crash here leaves a complete orphan .tmp
    os.replace(tmp, path)  # atomic install
    # rename durability: the new name must survive a crash, not just the bytes
    faults.fsync_dir(os.path.dirname(path) or ".")

    smallest, largest, n_entries = image_bounds(img)
    return FileMeta(file_no=file_no, path=path,
                    smallest=smallest, largest=largest,
                    n_entries=n_entries, size_bytes=len(payload))


def image_bounds(img: SSTImage, restart_interval: int = 16):
    """(smallest_key, largest_key, n_entries) without a full decode.

    Block starts are restart points (full keys), so ``smallest`` reads
    directly; ``largest`` decodes only the final restart interval."""
    from repro.lsm import cpu_engine as ce
    nvalid = np.asarray(img.nvalid)
    keys = np.asarray(img.keys, np.uint32)
    n_entries = int(nvalid.sum())
    if n_entries == 0:
        return b"", b"", 0
    smallest = formats.unpack_key_bytes(keys[0, 0]).rstrip(b"\x00")
    b_last = int(np.nonzero(nvalid > 0)[0][-1])
    nv = int(nvalid[b_last])
    # the last restart interval: r is a restart point (shared[r] == 0), so
    # decoding the slice alone reconstructs full keys
    r = (nv - 1) // restart_interval * restart_interval
    seg = ce.np_prefix_decode(np.asarray(img.shared)[b_last, r:nv],
                              keys[b_last, r:nv], restart_interval)
    largest = formats.unpack_key_bytes(seg[-1]).rstrip(b"\x00")
    return smallest, largest, n_entries


def read_sst(path: str) -> SSTImage:
    with open(path, "rb") as f:
        data = f.read()
    (want,) = struct.unpack_from("<I", data, len(data) - 4)
    if binascii.crc32(data[:-4]) & 0xFFFFFFFF != want:
        raise IOError(f"file checksum mismatch: {path}")
    assert data[:8] == MAGIC, f"bad magic in {path}"
    b, k, lanes, vw, g, w = struct.unpack_from("<6I", data, 8)
    off = 8 + 24

    def take(shape, dt):
        nonlocal off
        n = int(np.prod(shape)) * 4
        arr = np.frombuffer(data, dtype=dt, count=int(np.prod(shape)),
                            offset=off).reshape(shape)
        off += n
        return arr

    keys = take((b, k, lanes), "<u4")
    meta = take((b, k), "<u4")
    vals = take((b, k, vw), "<u4")
    shared = take((b, k), "<i4")
    nvalid = take((b,), "<i4")
    crc = take((b,), "<u4")
    bloom = take((g, w), "<u4")
    return SSTImage(keys=keys, meta=meta, vals=vals, shared=shared,
                    nvalid=nvalid, crc=crc, bloom=bloom)


@dataclasses.dataclass
class DecodedBlock:
    """One decoded data block (the block-cache unit).

    ``keys_u32`` rows at or beyond ``nvalid`` hold the all-ones sentinel
    (sorts after every real key), so the row order is total -- the
    contract the batched ``lookup_blocks`` launch and the host
    ``searchsorted`` path both rely on.  ``keys_packed`` is the big-endian
    byte view of the same rows (``S{4L}``), whose memcmp order equals the
    uint32-lane lexicographic order."""
    keys_u32: np.ndarray      # uint32 [K, L]  full (prefix-restored) keys
    keys_packed: np.ndarray   # bytes  [K]     big-endian packed rows
    meta: np.ndarray          # uint32 [K]     seq << 1 | is_value
    vals: np.ndarray          # uint32 [K, Vw]
    nvalid: int

    @property
    def nbytes(self) -> int:
        return (self.keys_u32.nbytes + self.keys_packed.nbytes +
                self.meta.nbytes + self.vals.nbytes)


class BlockCache:
    """Host-side LRU cache of ``DecodedBlock``s, shared by every reader of
    a store (keyed ``(file_no, block)``; file numbers are never reused).

    Thread-safe; ``on_hit``/``on_miss`` hooks feed the store's metrics
    counters.  Capacity is in blocks: with the default geometry one block
    is ~4 KB of values, so the default 4096 blocks is a ~16-32 MB working
    set (see docs/read_path.md for sizing)."""

    def __init__(self, capacity: int = 4096, *, on_hit=None, on_miss=None):
        self.capacity = capacity
        # guarded-by: _lock
        self._c: OrderedDict[tuple[int, int], DecodedBlock] = OrderedDict()
        self._lock = threading.Lock()
        self._on_hit = on_hit
        self._on_miss = on_miss

    def get(self, file_no: int, block: int) -> DecodedBlock | None:
        with self._lock:
            blk = self._c.get((file_no, block))
            if blk is not None:
                self._c.move_to_end((file_no, block))
        if self._on_hit is not None and blk is not None:
            self._on_hit()
        elif self._on_miss is not None and blk is None:
            self._on_miss()
        return blk

    def put(self, file_no: int, block: int, blk: DecodedBlock):
        if self.capacity <= 0:
            return
        faults.fire("cache.insert")
        with self._lock:
            self._c[(file_no, block)] = blk
            while len(self._c) > self.capacity:
                self._c.popitem(last=False)

    def drop_file(self, file_no: int):
        with self._lock:
            for k in [k for k in self._c if k[0] == file_no]:
                del self._c[k]

    def __len__(self) -> int:
        with self._lock:
            return len(self._c)


def _pack_rows(keys_u32: np.ndarray) -> np.ndarray:
    """Big-endian byte view of uint32 key rows: memcmp order == lane
    order, so ``np.searchsorted`` works directly on the packed column."""
    be = np.ascontiguousarray(keys_u32.astype(">u4"))
    return be.view(f"S{4 * keys_u32.shape[-1]}").ravel()


class TableReader:
    """The single decode entry point for point reads on one SST.

    Lazy at every level: constructing a reader touches nothing; the first
    read maps the file (whole-file CRC verified once) and builds only the
    block-level metadata (per-block first keys + bloom rows); individual
    blocks decode on demand through the shared ``BlockCache``.

    Read API mirrors ``LsmDB``/``ShardedDB``: ``get(key, opts=None)``,
    ``multi_get(keys, opts=None)``, ``scan(start, end, opts=None)``.
    ``probe(key, opts)`` is the tombstone-aware primitive the DB read path
    uses (``get`` cannot distinguish absent from deleted)."""

    def __init__(self, meta: FileMeta, geom: SSTGeometry, *,
                 block_cache: BlockCache | None = None):
        self.meta = meta
        self.geom = geom
        self.block_cache = block_cache
        self._lock = threading.Lock()
        self._img: SSTImage | None = None             # guarded-by: _lock
        self._first_keys: list[bytes] | None = None   # guarded-by: _lock

    # -- lazy loading ---------------------------------------------------

    def _load(self) -> SSTImage:
        with self._lock:
            if self._img is None:
                self._img = read_sst(self.meta.path)  # file CRC verified
            return self._img

    @property
    def first_keys(self) -> list[bytes]:
        """Per-block smallest user key (block starts are restart points,
        so row 0 of the raw lanes is already the full key -- no decode)."""
        fk = self._first_keys
        if fk is not None:
            return fk
        img = self._load()
        keys = np.asarray(img.keys, np.uint32)
        fk = [formats.unpack_key_bytes(keys[b, 0]).rstrip(b"\x00")
              for b in range(keys.shape[0])]
        with self._lock:
            self._first_keys = fk
        return fk

    @property
    def opened(self) -> bool:
        """Whether a read has opened the table (file read, first keys)."""
        with self._lock:
            return self._first_keys is not None

    @property
    def n_blocks(self) -> int:
        return self._load().keys.shape[0]

    def candidate_block(self, key: bytes) -> int:
        """The one block that can contain ``key`` (keys are unique per
        table, so the rightmost block whose first key <= key)."""
        return max(0, bisect.bisect_right(self.first_keys, key) - 1)

    def bloom_row(self, block: int) -> np.ndarray | None:
        """The filter row guarding ``block`` (``None`` when the table
        carries no filters).  Block-granularity blooms map 1:1; the
        sst-granularity single row guards every block."""
        bloom = np.asarray(self._load().bloom)
        if bloom.shape[0] == 0:
            return None
        return bloom[min(block, bloom.shape[0] - 1)]

    # -- block decode (the one entry point) -----------------------------

    def block(self, b: int, *, fill_cache: bool = True,
              verify_crc: bool = False) -> DecodedBlock:
        """Decode block ``b`` (through the shared block cache when one is
        attached).  All read paths -- scalar probe, batched multi_get,
        scan -- come through here, so a block is decoded at most once
        while it stays cached."""
        blk = self.cached_block(b)
        if blk is not None:
            return blk
        return self.decode_block(b, fill_cache=fill_cache,
                                 verify_crc=verify_crc)

    def cached_block(self, b: int) -> DecodedBlock | None:
        """Block ``b`` if (and only if) it sits in the shared cache;
        counts one cache hit or miss.  Read paths use residency to decide
        whether a bloom probe is worth it: the filter's only job is to
        spare a decode, so an already-decoded block skips the probe."""
        if self.block_cache is None:
            return None
        return self.block_cache.get(self.meta.file_no, b)

    def decode_block(self, b: int, *, fill_cache: bool = True,
                     verify_crc: bool = False) -> DecodedBlock:
        """Decode block ``b`` directly -- no cache lookup (the caller
        already missed via ``cached_block``) -- and optionally fill."""
        blk = self._decode_block(b, verify_crc=verify_crc)
        if self.block_cache is not None and fill_cache:
            self.block_cache.put(self.meta.file_no, b, blk)
        return blk

    def _decode_block(self, b: int, *, verify_crc: bool) -> DecodedBlock:
        from repro.lsm import cpu_engine as ce
        img = self._load()
        keys_raw = np.asarray(img.keys, np.uint32)[b]
        shared = np.asarray(img.shared)[b]
        meta = np.asarray(img.meta, np.uint32)[b]
        vals = np.asarray(img.vals, np.uint32)[b]
        nv = int(np.asarray(img.nvalid)[b])
        if verify_crc:
            wire = np.concatenate([
                np.asarray([nv], np.uint32),
                keys_raw.reshape(-1), meta,
                vals.reshape(-1), shared.astype(np.uint32)])
            want = int(np.asarray(img.crc, np.uint32)[b])
            if int(ce.np_crc_blocks(wire[None])[0]) != want:
                raise IOError(
                    f"SST block checksum mismatch: {self.meta.path} "
                    f"block {b}")
        keys = ce.np_prefix_decode(shared, keys_raw,
                                   self.geom.restart_interval).copy()
        keys[nv:] = SENTINEL
        return DecodedBlock(keys_u32=keys, keys_packed=_pack_rows(keys),
                            meta=meta, vals=vals, nvalid=nv)

    # -- reads ----------------------------------------------------------

    def _opts(self, opts):
        if opts is None:
            from repro.lsm import DEFAULT_READ_OPTIONS
            return DEFAULT_READ_OPTIONS
        return opts

    def probe(self, key: bytes, opts=None, *, tracer=NULL_TRACER,
              hashes: list[int] | None = None
              ) -> tuple[bool, bytes | None, bool]:
        """``(found, value|None, bloom_pruned)``: the tombstone-aware
        lookup.  ``found=True, value=None`` means a tombstone shadows the
        key; ``bloom_pruned=True`` means the filter proved absence without
        decoding a block.  ``hashes`` holds the key's filter hash pair
        (``cpu_engine.bloom_hashes_of``) once a filter check computed it:
        a get over several tables passes one list, so the key is hashed
        at most once, and only when a filter is checked.  With
        ``tracer`` enabled, the table's first read (the whole file, its
        CRC and the blocks' first keys) is one ``get.table_load`` span, a
        filter check one ``get.filter`` span (arg ``pruned``), and a
        block-cache miss one ``get.block_load`` span (the block's decode,
        with its CRC check when ``opts.verify_crc``); a cached table or
        block records nothing.

        Searching ``keys_packed`` with the plain user key is exact:
        numpy ``S`` comparisons zero-pad the scalar to the item width,
        which is precisely the fixed-width packing, and user keys never
        end with NUL (enforced at ``put``) so trailing-NUL stripping on
        itemget cannot alias two keys."""
        opts = self._opts(opts)
        from repro.lsm import cpu_engine as ce
        if not (self.meta.smallest <= key <= self.meta.largest):
            return False, None, False
        if tracer.enabled and not self.opened:
            # the table's first read: whole file, its CRC, first keys
            t0 = time.perf_counter_ns()
            b = self.candidate_block(key)
            tracer.complete("get.table_load", t0,
                            time.perf_counter_ns() - t0)
        else:
            b = self.candidate_block(key)
        blk = self.cached_block(b)
        if blk is None:
            # bloom-probe only when the block is NOT already decoded: a
            # host bloom probe costs more than searching a cached block
            row = self.bloom_row(b)
            if row is not None:
                t0 = time.perf_counter_ns() if tracer.enabled else 0
                if hashes is None:
                    hashes = []
                if not hashes:
                    hashes += ce.bloom_hashes_of(key, self.geom.key_bytes)
                hit = ce.bloom_row_may_contain(row, *hashes,
                                               self.geom.bloom_probes)
                if tracer.enabled:
                    tracer.complete("get.filter", t0,
                                    time.perf_counter_ns() - t0,
                                    args={"pruned": not hit})
                if not hit:
                    return False, None, True
            t0 = time.perf_counter_ns() if tracer.enabled else 0
            blk = self.decode_block(b, fill_cache=opts.fill_cache,
                                    verify_crc=opts.verify_crc)
            if tracer.enabled:
                tracer.complete("get.block_load", t0,
                                time.perf_counter_ns() - t0)
        i = int(np.searchsorted(blk.keys_packed, key))
        if i >= blk.nvalid or blk.keys_packed[i] != key:
            return False, None, False
        if not (int(blk.meta[i]) & 1):
            return True, None, False          # tombstone
        return True, formats.unpack_value_bytes(blk.vals[i]), False

    def get(self, key: bytes, opts=None) -> bytes | None:
        """Value bytes, or None when absent or deleted (use ``probe`` to
        tell the two apart)."""
        _, value, _ = self.probe(key, opts)
        return value

    def multi_get(self, keys, opts=None) -> list[bytes | None]:
        """Batched ``get`` over this one table: bloom-prunes the whole
        batch in one stacked probe, then resolves survivors with one
        batched search/gather launch (see ``lsm.read``)."""
        opts = self._opts(opts)
        from repro.lsm import read as lsm_read
        keys = list(keys)
        out: list[bytes | None] = [None] * len(keys)
        cands = [lsm_read.Candidate(slot=i, rank=0, reader=self, key=k)
                 for i, k in enumerate(keys)
                 if self.meta.smallest <= k <= self.meta.largest]
        resolved = lsm_read.resolve_candidates(cands, self.geom, opts)
        for slot, (_, value) in resolved.items():
            out[slot] = value
        return out

    def scan(self, start: bytes, end: bytes, opts=None
             ) -> list[tuple[bytes, int, bytes | None]]:
        """``[(key, seq, value|None)]`` for start <= key < end, in key
        order (tombstones included -- the DB-level merge needs them)."""
        opts = self._opts(opts)
        if self.meta.largest < start or self.meta.smallest >= end:
            return []
        out = []
        fk = self.first_keys
        b = self.candidate_block(start)
        while b < len(fk) and fk[b] < end:
            blk = self.block(b, fill_cache=opts.fill_cache,
                             verify_crc=opts.verify_crc)
            lo = int(np.searchsorted(blk.keys_packed, start))
            for i in range(lo, blk.nvalid):
                k = formats.unpack_key_bytes(
                    blk.keys_u32[i]).rstrip(b"\x00")
                if k >= end:
                    return out
                m = int(blk.meta[i])
                v = formats.unpack_value_bytes(blk.vals[i]) \
                    if m & 1 else None
                out.append((k, m >> 1, v))
            b += 1
        return out


class TableCache:
    """LRU cache of per-file ``TableReader``s plus the shared block cache
    (thread-safe: the async write path has readers, flush workers and the
    compaction worker sharing it).

    ``reader(meta)`` is the single entry point."""

    def __init__(self, capacity: int = 64, *,
                 geom: SSTGeometry | None = None,
                 block_cache: BlockCache | None = None):
        self.capacity = capacity
        self.geom = geom
        self.block_cache = block_cache
        self._c: OrderedDict[int, TableReader] = OrderedDict()  # guarded-by: _lock
        self._lock = threading.Lock()

    def reader(self, meta: FileMeta,
               geom: SSTGeometry | None = None) -> TableReader:
        """The (cached) ``TableReader`` for ``meta`` -- nothing is read
        from disk until the reader is first probed."""
        with self._lock:
            rdr = self._c.get(meta.file_no)
            if rdr is not None:
                self._c.move_to_end(meta.file_no)
                return rdr
            rdr = TableReader(meta, geom or self.geom or SSTGeometry(),
                              block_cache=self.block_cache)
            self._c[meta.file_no] = rdr
            while len(self._c) > self.capacity:
                self._c.popitem(last=False)
            return rdr

    def drop(self, file_no: int):
        with self._lock:
            self._c.pop(file_no, None)
        if self.block_cache is not None:
            self.block_cache.drop_file(file_no)


# REPRO_SANITIZE=1 turns the guarded-by annotations above into runtime
# assertions (see repro.analysis.sanitize); free when unset.
from repro.analysis.sanitize import maybe_instrument as _maybe_instrument  # noqa: E402

_maybe_instrument(BlockCache)
_maybe_instrument(TableReader)
_maybe_instrument(TableCache)
