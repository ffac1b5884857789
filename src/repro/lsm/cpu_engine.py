"""CPU compaction baselines (the LevelDB / RocksDB side of the paper).

Pure numpy + binascii -- no JAX.  The math mirrors the device kernels
exactly (same CRC, same bloom hash, same prefix rules), so for identical
inputs the CPU and device engines emit **bit-identical** SST files; the
test suite asserts this, which cross-validates both engines.

``threads`` models RocksDB's multi-threaded compaction: the work here is
single-threaded (1-core container) and the benchmark harness divides the
measured CPU seconds by the effective parallelism of the simulated server
(see benchmarks/contention.py).
"""

from __future__ import annotations

import binascii
import collections
import dataclasses
import struct
import time

import numpy as np

from repro.core.formats import SSTGeometry, SSTImage
from repro.kernels.tables import (FNV_OFFSET, FNV_PRIME, H2_SALT, H2_SEED,
                                  MIX_1, MIX_2)
from repro.lsm import faults
from repro.obs.trace import NULL_TRACER

U32 = np.uint32


# ---------------------------------------------------------------------------
# numpy mirrors of the kernel math
# ---------------------------------------------------------------------------


def np_u32_to_bytes(words: np.ndarray) -> np.ndarray:
    shifts = (8 * (3 - np.arange(4, dtype=np.uint32))).astype(np.uint32)
    b = (words[..., None] >> shifts) & U32(0xFF)
    return b.reshape(*words.shape[:-1], words.shape[-1] * 4).astype(np.uint8)


def np_bytes_to_u32(b: np.ndarray) -> np.ndarray:
    L = b.shape[-1] // 4
    b4 = b.reshape(*b.shape[:-1], L, 4).astype(np.uint32)
    shifts = (8 * (3 - np.arange(4, dtype=np.uint32))).astype(np.uint32)
    return (b4 << shifts).sum(-1).astype(np.uint32)


def np_prefix_encode(keys: np.ndarray, restart_interval: int) -> np.ndarray:
    kb = np_u32_to_bytes(keys)
    prev = np.roll(kb, 1, axis=0)
    eq = (kb == prev).astype(np.int32)
    shared = np.cumprod(eq, axis=-1).sum(-1)
    idx = np.arange(keys.shape[0])
    return np.where(idx % restart_interval == 0, 0, shared).astype(np.int32)


def np_prefix_decode(shared: np.ndarray, keys_raw: np.ndarray,
                     restart_interval: int) -> np.ndarray:
    """Vectorized across restart intervals: the serial chain is only
    ``restart_interval`` steps deep (LevelDB's same parallelism window)."""
    kb = np_u32_to_bytes(keys_raw).copy()
    n, B = kb.shape
    r = restart_interval
    pad = (-n) % r
    if pad:
        kb = np.concatenate([kb, np.zeros((pad, B), kb.dtype)])
        shared = np.concatenate([shared, np.zeros(pad, shared.dtype)])
    ki = kb.reshape(-1, r, B)
    sh = shared.reshape(-1, r)
    pos = np.arange(B)[None, :]
    for t in range(1, r):
        m = pos < sh[:, t, None]
        ki[:, t] = np.where(m, ki[:, t - 1], ki[:, t])
    out = ki.reshape(-1, B)[:n]
    return np_bytes_to_u32(out)


def np_crc_blocks(words: np.ndarray) -> np.ndarray:
    """binascii per block over the little-endian word serialization (this is
    how LevelDB computes block trailers: one C CRC pass per block)."""
    return np.array([binascii.crc32(row.astype("<u4").tobytes()) & 0xFFFFFFFF
                     for row in words], dtype=np.uint32)


# The filter hash of ``kernels/ref.py``, on its constants: the numpy
# functions below and the scalar pair after them.
_MASK = 0xFFFFFFFF


def _np_mix32(h):
    h = h ^ (h >> U32(16))
    h = (h * U32(MIX_1)).astype(U32)
    h = h ^ (h >> U32(13))
    h = (h * U32(MIX_2)).astype(U32)
    return h ^ (h >> U32(16))


def np_bloom_hashes(keys: np.ndarray):
    keys = keys.astype(U32)
    h1 = np.full(keys.shape[:-1], FNV_OFFSET, U32)
    h2 = np.full(keys.shape[:-1], FNV_OFFSET ^ H2_SEED, U32)
    for lane in range(keys.shape[-1]):
        h1 = ((h1 ^ keys[..., lane]) * U32(FNV_PRIME)).astype(U32)
        h2 = ((h2 ^ U32(H2_SALT) ^ keys[..., lane]) *
              U32(FNV_PRIME)).astype(U32)
    return _np_mix32(h1), _np_mix32(h2) | U32(1)


def np_bloom_build(keys: np.ndarray, valid: np.ndarray, n_words: int,
                   n_probes: int) -> np.ndarray:
    g, k, _ = keys.shape
    h1, h2 = np_bloom_hashes(keys)
    out = np.zeros((g, n_words), U32)
    m_bits = U32(n_words * 32)
    for i in range(n_probes):
        pos = ((h1 + U32(i) * h2) % m_bits)
        w = (pos >> 5).astype(np.int64)
        bit = (U32(1) << (pos & U32(31))).astype(U32)
        for gi in range(g):
            np.bitwise_or.at(out[gi], w[gi][valid[gi]], bit[gi][valid[gi]])
    return out


def np_bloom_query(filters: np.ndarray, keys: np.ndarray,
                   n_probes: int) -> np.ndarray:
    h1, h2 = np_bloom_hashes(keys)
    n_words = filters.shape[-1]
    m_bits = U32(n_words * 32)
    ok = np.ones(h1.shape, bool)
    for i in range(n_probes):
        pos = (h1 + U32(i) * h2) % m_bits
        word = np.take_along_axis(filters, (pos >> 5).astype(np.int64),
                                  axis=-1)
        ok &= ((word >> (pos & U32(31))) & 1).astype(bool)
    return ok


def _mix32(h: int) -> int:
    h ^= h >> 16
    h = (h * MIX_1) & _MASK
    h ^= h >> 13
    h = (h * MIX_2) & _MASK
    return h ^ (h >> 16)


def bloom_hashes_of(key: bytes, key_bytes: int) -> tuple[int, int]:
    """``np_bloom_hashes`` of one user key, in Python integers: the
    scalar get hashes a key once and checks every table's filter row
    with it (``bloom_row_may_contain``)."""
    h1, h2 = FNV_OFFSET, FNV_OFFSET ^ H2_SEED
    for w in struct.unpack(f">{key_bytes // 4}I",
                           key.ljust(key_bytes, b"\x00")):
        h1 = ((h1 ^ w) * FNV_PRIME) & _MASK
        h2 = ((h2 ^ H2_SALT ^ w) * FNV_PRIME) & _MASK
    return _mix32(h1), _mix32(h2) | 1


def bloom_row_may_contain(row: np.ndarray, h1: int, h2: int,
                          n_probes: int) -> bool:
    """``np_bloom_query`` of one key against one filter row, reading the
    row's words in place: False proves the key absent."""
    m_bits = row.shape[-1] * 32
    for i in range(n_probes):
        pos = ((h1 + i * h2) & _MASK) % m_bits
        if not (row.item(pos >> 5) >> (pos & 31)) & 1:
            return False
    return True


def np_wire_words(img: SSTImage) -> np.ndarray:
    b, k, lanes = img.keys.shape
    vw = img.vals.shape[-1]
    return np.concatenate([
        np.asarray(img.nvalid, U32)[:, None],
        np.asarray(img.keys, U32).reshape(b, k * lanes),
        np.asarray(img.meta, U32),
        np.asarray(img.vals, U32).reshape(b, k * vw),
        np.asarray(img.shared).astype(U32),
    ], axis=-1)


def _np_merge_run_order(packed: np.ndarray, run_lens) -> np.ndarray:
    """Order indices sorting ``packed`` (unique fixed-width byte keys laid
    out as back-to-back sorted runs).

    Per-run stable argsort (timsort: O(run) when the run is already sorted,
    which it is by construction; kept for robustness to arbitrary callers)
    followed by pairwise ``searchsorted`` merges -- the host mirror of the
    device merge path, O(n log k) instead of lexsort's O(n log n)."""
    from repro.kernels.common import tree_merge
    segs = []
    off = 0
    for ln in run_lens:
        seg = packed[off:off + ln]
        o = np.argsort(seg, kind="stable")
        segs.append((seg[o], (off + o).astype(np.int64)))
        off += ln
    if not segs:
        return np.zeros(0, np.int64)

    def merge2(a, b):
        (ak, ai), (bk, bi) = a, b
        pa = np.arange(len(ak)) + np.searchsorted(bk, ak, side="left")
        pb = np.arange(len(bk)) + np.searchsorted(ak, bk, side="right")
        keys_m = np.empty(len(ak) + len(bk), ak.dtype)
        idx_m = np.empty(len(ai) + len(bi), np.int64)
        keys_m[pa], idx_m[pa] = ak, ai
        keys_m[pb], idx_m[pb] = bk, bi
        return keys_m, idx_m

    return tree_merge(segs, merge2)[1]


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineStats:
    """Per-job compaction accounting.

    ``sort_seconds`` is the phase-2 (tuple ordering) share: measured wall
    time for the CPU engine (contained in ``host_seconds``), the modeled
    roofline share of ``device_seconds`` for the device engine -- so
    benchmark output can show where compaction time goes per sort mode.
    """
    n_input: int = 0
    n_live: int = 0
    n_dropped: int = 0
    crc_ok: bool = True
    bytes_in: int = 0
    bytes_out: int = 0
    host_seconds: float = 0.0
    device_seconds: float = 0.0
    sort_seconds: float = 0.0
    batched: bool = False   # produced by a stacked multi-job launch
    fallback: bool = False  # completed via the CPU degraded mode after
    #   the device launch failed (docs/robustness.md)


class CpuCompactionEngine:
    """LevelDB-like compaction entirely on the host CPU."""

    name = "cpu"

    def __init__(self, geom: SSTGeometry, threads: int = 1, tracer=None):
        self.geom = geom
        self.threads = threads
        self.tracer = tracer if tracer is not None else NULL_TRACER

    # -- phase 1 -----------------------------------------------------------
    def _unpack(self, img: SSTImage):
        g = self.geom
        b, k, lanes = img.keys.shape
        crc_ok = bool((np_crc_blocks(np_wire_words(img)) ==
                       np.asarray(img.crc, U32)).all())
        keys = np_prefix_decode(
            np.asarray(img.shared).reshape(b * k),
            np.asarray(img.keys, U32).reshape(b * k, lanes),
            g.restart_interval)
        valid = (np.arange(k)[None, :] <
                 np.asarray(img.nvalid)[:, None]).reshape(b * k)
        return keys, np.asarray(img.meta, U32).reshape(b * k), \
            np.asarray(img.vals, U32).reshape(b * k, -1), valid, crc_ok

    # -- public API (mirrors CompactionExecutor) ----------------------------
    def compact(self, images: list[SSTImage], *, bottom_level: bool = False
                ) -> tuple[SSTImage, EngineStats]:
        t0 = time.perf_counter()
        g = self.geom
        tr = self.tracer
        with tr.span("compact.crc_verify", inputs=len(images)):
            parts = [self._unpack(SSTImage(*(np.asarray(a) for a in im)))
                     for im in images]
        keys = np.concatenate([p[0] for p in parts])
        meta = np.concatenate([p[1] for p in parts])
        vals = np.concatenate([p[2] for p in parts])
        valid = np.concatenate([p[3] for p in parts])
        crc_ok = all(p[4] for p in parts)

        # phase 2: run-aware k-way merge + dedup (key asc, seq desc).
        # Every input image is a sorted run, so merge the runs instead of
        # lexsorting the concatenation; the unique trailing index makes
        # the order identical to the old full lexsort bit for bit.
        t_sort0 = time.perf_counter()
        with tr.span("compact.merge_phase2", runs=len(parts)):
            sk = np.where(valid[:, None], keys, U32(0xFFFFFFFF))
            inv_meta = (~meta).astype(U32)
            idx = np.arange(len(sk), dtype=U32)
            packed = np.ascontiguousarray(
                np.concatenate([sk, inv_meta[:, None], idx[:, None]],
                               axis=1).astype(">u4")).view(
                f"S{4 * (sk.shape[1] + 2)}").ravel()
            order = _np_merge_run_order(packed,
                                        [p[0].shape[0] for p in parts])
        t_sort = time.perf_counter() - t_sort0
        keys_s, meta_s, valid_s = keys[order], meta[order], valid[order]
        vals_s = vals[order]
        neq = np.any(keys_s != np.roll(keys_s, 1, axis=0), axis=1)
        neq[0] = True
        live = valid_s & neq
        if bottom_level:
            live &= (meta_s & 1).astype(bool)

        with tr.span("compact.format"):
            out = self.build_image(keys_s[live], meta_s[live], vals_s[live],
                                   n_blocks=sum(im.keys.shape[0]
                                                for im in images))
        wire = g.wire_words_per_block * 4
        stats = EngineStats(
            n_input=int(valid.sum()), n_live=int(live.sum()),
            n_dropped=int(valid.sum() - live.sum()), crc_ok=crc_ok,
            bytes_in=sum(im.keys.shape[0] for im in images) * wire,
            bytes_out=int((np.asarray(out.nvalid) > 0).sum()) * wire,
            host_seconds=0.0, sort_seconds=t_sort)
        stats.host_seconds = time.perf_counter() - t0
        return out, stats

    def compact_paths(self, paths: list[str], *, bottom_level: bool = False
                      ) -> tuple[SSTImage, EngineStats]:
        """Compact straight from SST files (CPU path reads serially).
        Read I/O counts toward host_seconds, matching the device path."""
        from repro.lsm import sstable
        t0 = time.perf_counter()
        images = [sstable.read_sst(p) for p in paths]
        t_read = time.perf_counter() - t0
        out, stats = self.compact(images, bottom_level=bottom_level)
        stats.host_seconds += t_read
        return out, stats

    def compact_many(self, jobs: list[tuple[list[str], bool]]
                     ) -> list[tuple[SSTImage, EngineStats]]:
        """Sequential per-job fallback (the CPU has no batch dimension to
        exploit); same interface as the device engine so ``ShardedDB`` can
        share either engine across shards."""
        return [self.compact_paths(paths, bottom_level=bottom)
                for paths, bottom in jobs]

    def build_image(self, keys, meta, vals, n_blocks: int | None = None
                    ) -> SSTImage:
        """Pack sorted entries into a wire image (numpy phase 3)."""
        g = self.geom
        keys = np.asarray(keys, U32)
        meta = np.asarray(meta, U32)
        vals = np.asarray(vals, U32)
        n = keys.shape[0]
        k = g.block_kvs
        nb = max(1, -(-n // k)) if n_blocks is None else max(1, n_blocks)
        n_pad = nb * k
        keys = np.pad(keys, ((0, n_pad - n), (0, 0)))
        meta = np.pad(meta, (0, n_pad - n))
        vals = np.pad(vals, ((0, n_pad - n), (0, 0)))
        valid = np.arange(n_pad) < n

        shared = np_prefix_encode(keys, g.restart_interval)
        shared = np.where(valid, shared, 0).astype(np.int32)
        kb = np_u32_to_bytes(keys)
        bpos = np.arange(kb.shape[-1])
        kb_wire = np.where(bpos[None, :] < shared[:, None], 0, kb)
        kb_wire = np.where(valid[:, None], kb_wire, 0).astype(np.uint8)
        keys_wire = np_bytes_to_u32(kb_wire)
        meta_w = np.where(valid, meta, 0).astype(U32)
        nvalid = np.clip(n - np.arange(nb) * k, 0, k).astype(np.int32)

        img = SSTImage(
            keys=keys_wire.reshape(nb, k, g.key_lanes),
            meta=meta_w.reshape(nb, k),
            vals=vals.reshape(nb, k, g.value_words),
            shared=shared.reshape(nb, k), nvalid=nvalid,
            crc=np.zeros(nb, U32), bloom=np.zeros((1, 1), U32))
        crc = np_crc_blocks(np_wire_words(img))
        groups, per = g.filter_groups(n_pad)
        bloom = np_bloom_build(keys.reshape(groups, per, g.key_lanes),
                               valid.reshape(groups, per),
                               g.bloom_words(per), g.bloom_probes)
        return SSTImage(keys=img.keys, meta=img.meta, vals=img.vals,
                        shared=img.shared, nvalid=img.nvalid, crc=crc,
                        bloom=bloom)


#: a job of a run-slot class the engine never launched runs in a
#: launched class of at most this many times its blocks; a wider launch
#: would merge, lay out and copy back more padding than a compile costs
#: a long-running store (a class compiles once per process, or loads
#: from the persistent compile cache)
MAX_CLASS_WIDENING = 2


class DeviceCompactionEngine:
    """The LUDA path: wraps the jitted device pipeline behind the same
    interface as the CPU engine."""

    name = "device"

    def __init__(self, geom: SSTGeometry, sort_mode: str = "merge",
                 backend: str = "auto", tracer=None):
        from repro.core.offload import CompactionExecutor
        self.geom = geom
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.executor = CompactionExecutor(geom, sort_mode=sort_mode,
                                           backend=backend)
        self._reader = None
        # jit cache bookkeeping: every job is laid out in run slots
        # (``offload.run_slots``), so jobs of one slot class reuse the
        # compiled program.  The key is the launch's jit signature:
        # ``("one", slots, slot_blocks)``, or ``("batch", jobs, slots,
        # slot_blocks)`` for a stacked launch; a miss = first launch of a
        # signature.  A job whose own class was never launched runs in
        # the smallest launched class that holds it, at most
        # ``MAX_CLASS_WIDENING`` times its size (``_launch_class``);
        # ``class_reuses`` counts those jobs.
        self.jit_signature_counts: dict[tuple, int] = {}
        self.jit_bucket_hits = 0
        self.jit_bucket_misses = 0
        self.class_reuses = 0
        # batched-launch accounting (compact_many): one "launch" is one
        # stacked vmapped dispatch covering >=2 same-signature jobs
        self.batch_launches = 0
        self.batch_jobs = 0
        self.max_batch_jobs = 0
        # degraded-mode accounting: a failed (or CRC-failed) device launch
        # retries once, then the job completes through a CPU engine that
        # emits bit-identical output (docs/robustness.md)
        self._cpu = None            # lazy CpuCompactionEngine
        self.fallbacks = 0          # jobs completed via the CPU fallback
        self.launch_retries = 0     # device launches retried before fallback
        # "<type>: <message>" of the newest launch faults behind the
        # retries and fallbacks above
        self.fault_causes: collections.deque[str] = collections.deque(
            maxlen=64)

    def close(self):
        if self._reader is not None:
            self._reader.close()
            self._reader = None

    def _note_signature(self, sig: tuple) -> bool:
        """Count a launch under its jit signature; True if it was seen
        before (the launch reuses a compiled program)."""
        seen = self.jit_signature_counts.get(sig, 0)
        self.jit_signature_counts[sig] = seen + 1
        if seen:
            self.jit_bucket_hits += 1
        else:
            self.jit_bucket_misses += 1
        return bool(seen)

    def _launch_class(self, kind: tuple, cls: tuple[int, int]
                      ) -> tuple[int, int]:
        """The run-slot class to launch a job of class ``cls`` in, as
        ``kind`` (``("one",)``, or ``("batch", jobs)``): ``cls`` itself
        if it was launched so before or no launched class holds the job,
        else the smallest launched class with at least as many slots and
        blocks per slot, at most ``MAX_CLASS_WIDENING`` times ``cls``'s
        blocks, whose filter groups hold as many keys as ``cls``'s.  Its
        extra slots and blocks are sentinel runs, so its output cut to
        ``cls`` (``_cut``) is ``cls``'s own, byte for byte, and no
        program is compiled for ``cls``."""
        if kind + cls in self.jit_signature_counts:
            return cls
        slots, blocks = cls
        k = self.geom.block_kvs
        per = self.geom.filter_groups(slots * blocks * k)[1]
        held = sorted(
            (s * b, s, b) for *head, s, b in self.jit_signature_counts
            if tuple(head) == kind and s >= slots and b >= blocks
            and s * b <= MAX_CLASS_WIDENING * slots * blocks
            and self.geom.filter_groups(s * b * k)[1] == per)
        return held[0][1:] if held else cls

    def _cut(self, out: SSTImage, cls: tuple[int, int], batched: bool
             ) -> SSTImage:
        """A launch's output cut to the blocks and filter groups of the
        job's own class ``cls`` (a no-op when it ran in that class)."""
        n = cls[0] * cls[1]
        groups = self.geom.filter_groups(n * self.geom.block_kvs)[0]
        lead = (slice(None),) if batched else ()
        return SSTImage(*(a[lead + (slice(n),)] for a in out[:-1]),
                        bloom=out.bloom[lead + (slice(groups),)])

    def _cpu_engine(self) -> CpuCompactionEngine:
        """The lazily-built degraded-mode twin (bit-identical output)."""
        if self._cpu is None:
            self._cpu = CpuCompactionEngine(self.geom, tracer=self.tracer)
        return self._cpu

    def _note_fault(self, cause) -> str:
        """Keep the cause of a launch fault: an exception or a reason."""
        text = cause if isinstance(cause, str) else \
            f"{type(cause).__name__}: {cause}"
        self.fault_causes.append(text)
        return text

    def _with_fallback(self, attempt, fallback):
        """Run one compaction job with launch resilience: a launch fault
        -- an injected ``FaultInjected`` or a negative CRC verdict --
        retries once, then the job completes through the CPU engine,
        whose output is bit-identical by construction, so degraded mode
        changes latency, never bytes.  Each cause is kept in
        ``fault_causes`` and on the ``compact.fallback`` span.  Any other
        exception propagates: a kernel that fails to trace, lower or
        compile, or a device error, is not a launch fault, and a CPU
        rerun would hide it.  ``SimulatedCrash`` (process death)
        propagates too.  A genuinely corrupt input fails CRC on the CPU
        too, so ``apply_compaction``'s inputs-retained abort is
        preserved, just with an authoritative host verdict."""
        cause = None
        for is_retry in (False, True):
            if is_retry:
                self.launch_retries += 1
            try:
                out, es = attempt()
            except faults.FaultInjected as e:
                cause = self._note_fault(e)
                continue
            if es.crc_ok:
                return out, es
            cause = self._note_fault("negative CRC verdict")
        self.fallbacks += 1
        with self.tracer.span("compact.fallback", engine="cpu", cause=cause):
            out, es = fallback()
        es.fallback = True
        return out, es

    def compact(self, images, *, bottom_level: bool = False):
        def attempt():
            from repro.core.offload import run_slots
            t0 = time.perf_counter()  # layout and H2D staging are host work
            blocks = [im.keys.shape[0] for im in images]
            cls = run_slots(blocks)
            launch = self._launch_class(("one",), cls)
            staged = self.executor.stage([images], *launch)
            return self._compact_staged(staged, cls, launch, bottom_level,
                                        real_blocks=sum(blocks), t0=t0)

        return self._with_fallback(
            attempt,
            lambda: self._cpu_engine().compact(images,
                                               bottom_level=bottom_level))

    def compact_paths(self, paths: list[str], *, bottom_level: bool = False):
        """Compact straight from SST files: a dedicated I/O thread reads
        ahead of the consumer, and the job's runs are then laid out in
        their run slots on the host and staged to the device once."""
        def attempt():
            from repro.core.background import PrefetchReader
            from repro.core.offload import run_slots
            from repro.lsm import sstable
            t0 = time.perf_counter()
            if self._reader is None:
                self._reader = PrefetchReader()
            with self.tracer.span("compact.read_inputs",
                                  files=len(paths)) as sp:
                imgs = list(self._reader.read_all(paths, sstable.read_sst))
                blocks = [im.keys.shape[0] for im in imgs]
                cls = run_slots(blocks)
                launch = self._launch_class(("one",), cls)
                staged = self.executor.stage([imgs], *launch)
                sp.set(h2d_bytes=staged[2])
            return self._compact_staged(staged, cls, launch, bottom_level,
                                        real_blocks=sum(blocks), t0=t0)

        return self._with_fallback(
            attempt,
            lambda: self._cpu_engine().compact_paths(
                paths, bottom_level=bottom_level))

    def compact_many(self, jobs: list[tuple[list[str], bool]]
                     ) -> list[tuple[SSTImage, EngineStats]]:
        """Compact several independent jobs, coalescing jobs of one
        run-slot class into single stacked device launches.

        ``jobs``: ``[(input_paths, bottom_level)]`` -- typically one job
        per shard, published by ``ShardedDB``'s global queue.  Jobs are
        grouped by ``offload.run_slots`` of their *actual* input block
        counts (the bottom level is a per-job flag of the launch); each
        group is laid out, in its launch class (``_launch_class``), and
        staged as one image.  A >=2-job group becomes ONE vmapped
        dispatch (``offload.compact_batch``) with per-job CRC verdicts;
        singleton groups take the ordinary single-job path.  Results come
        back in input order and are bit-identical to per-job
        ``compact_paths``.
        """
        from repro.core.background import PrefetchReader
        from repro.core.offload import run_slots
        from repro.lsm import sstable
        t_many0 = time.perf_counter_ns()
        t_read0 = time.perf_counter()
        if self._reader is None:
            self._reader = PrefetchReader()
        flat_paths = [p for paths, _ in jobs for p in paths]
        job_imgs, groups, off = [], {}, 0
        with self.tracer.span("compact.read_inputs",
                              files=len(flat_paths)) as sp:
            flat_imgs = list(self._reader.read_all(flat_paths,
                                                   sstable.read_sst))
            for j, (paths, bottom) in enumerate(jobs):
                imgs = flat_imgs[off:off + len(paths)]
                off += len(paths)
                job_imgs.append(imgs)
                cls = run_slots([im.keys.shape[0] for im in imgs])
                groups.setdefault(cls, []).append(j)
            launches = {
                cls: self._launch_class(
                    ("one",) if len(idxs) == 1 else ("batch", len(idxs)),
                    cls)
                for cls, idxs in groups.items()}
            staged = {cls: self.executor.stage([job_imgs[j] for j in idxs],
                                               *launches[cls])
                      for cls, idxs in groups.items()}
            sp.set(h2d_bytes=sum(st[2] for st in staged.values()))
        read_share = (time.perf_counter() - t_read0) / max(1, len(jobs))
        results: list = [None] * len(jobs)

        def single(j, cls, staged_at=None):
            """One prefetched job through the device path (+ fallback);
            ``staged_at`` is ``(staged, launch class)`` of a job the read
            phase laid out already."""
            def attempt():
                t0 = time.perf_counter()
                if staged_at is None:
                    launch = self._launch_class(("one",), cls)
                    staged = self.executor.stage([job_imgs[j]], *launch)
                else:
                    staged, launch = staged_at
                out, es = self._compact_staged(
                    staged, cls, launch, jobs[j][1],
                    real_blocks=sum(im.keys.shape[0] for im in job_imgs[j]),
                    t0=t0)
                es.host_seconds += read_share
                return out, es

            return self._with_fallback(
                attempt,
                lambda: self._cpu_engine().compact(
                    job_imgs[j], bottom_level=jobs[j][1]))

        for cls, idxs in groups.items():
            st = staged.pop(cls)
            if len(idxs) == 1:
                results[idxs[0]] = single(idxs[0], cls,
                                          (st, launches[cls]))
                continue
            try:
                results_group = self._compact_batched(
                    st, cls, launches[cls], [jobs[j][1] for j in idxs],
                    [job_imgs[j] for j in idxs], read_share=read_share)
            except faults.FaultInjected as e:
                # the stacked launch faulted: isolate by re-running the
                # group's jobs one by one (device retry + CPU fallback
                # per job), so one bad launch cannot wedge every shard
                self._note_fault(e)
                self.launch_retries += 1
                results_group = None
            if results_group is None:
                for j in idxs:
                    results[j] = single(j, cls)
            else:
                for j, res in zip(idxs, results_group):
                    if not res[1].crc_ok:
                        # per-job negative verdict inside a batch: get an
                        # authoritative single-job verdict (still fails
                        # for genuinely corrupt inputs -- on the CPU)
                        res = single(j, cls)
                    results[j] = res
        if self.tracer.enabled:
            self.tracer.complete(
                "compact_many", t_many0,
                time.perf_counter_ns() - t_many0,
                args={"jobs": len(jobs), "groups": len(groups)})
        return results

    def _launch_args(self, kind: tuple, jobs: int, cls: tuple[int, int],
                     launch: tuple[int, int]) -> dict:
        """Count a launch of ``jobs`` jobs of class ``cls`` in class
        ``launch``; the launch span's args."""
        slots, slot_blocks = launch
        args = {"jobs": jobs, "bucket": slots * slot_blocks, "slots": slots,
                "slot_blocks": slot_blocks,
                "sig_hit": self._note_signature(kind + launch)}
        if launch != cls:
            self.class_reuses += jobs
            args["reused_class"] = True
        return args

    def _sort_model(self, launch: tuple[int, int]) -> float:
        slots, slot_blocks = launch
        return model_sort_seconds(
            slots * slot_blocks * self.geom.block_kvs,
            self.geom.key_lanes + 2, slots, self.executor.sort_mode)

    def _compact_batched(self, staged, cls, launch, bottom_levels,
                         group_imgs, *, read_share):
        """One stacked launch over >=2 jobs of one run-slot class."""
        t0 = time.perf_counter()
        img, run_lens, _ = staged
        n_jobs = len(group_imgs)
        args = self._launch_args(("batch", n_jobs), n_jobs, cls, launch)
        self.batch_launches += 1
        self.batch_jobs += n_jobs
        self.max_batch_jobs = max(self.max_batch_jobs, n_jobs)
        (out, st), exec_wall = self._execute(
            "compact.batch_launch", args,
            lambda: self.executor.launch_many(img, run_lens,
                                              bottom_levels=bottom_levels))
        out = self._cut(out, cls, batched=True)
        host_share = max(time.perf_counter() - t0 - exec_wall, 0.0) / n_jobs
        wire = self.geom.wire_words_per_block * 4
        results = []
        for j, raw in enumerate(group_imgs):
            stats = EngineStats(
                n_input=int(st.n_input[j]), n_live=int(st.n_live[j]),
                n_dropped=int(st.n_dropped[j]), crc_ok=bool(st.crc_ok[j]),
                bytes_in=sum(im.keys.shape[0] for im in raw) * wire,
                bytes_out=int(st.bytes_out[j]), batched=True)
            stats.host_seconds = host_share + read_share
            stats.device_seconds = model_device_seconds(
                stats.bytes_in, stats.bytes_out, self.geom)
            stats.sort_seconds = self._sort_model(launch)
            results.append((SSTImage(*(a[j] for a in out)), stats))
        return results

    def _execute(self, span: str, args: dict, launch):
        """One device launch, traced as ``span`` with three measured,
        consecutive children: ``compact.dispatch`` (the call into the
        jitted pipeline: trace, lower, compile or persistent-cache load,
        enqueue), ``compact.device_wait`` (until the outputs are ready)
        and ``compact.d2h`` (the outputs copied to the host).
        ``launch()`` returns ``(image, stats)``, stacked on a job axis for
        a batch; returns them as host arrays, and the launch's wall
        seconds."""
        import jax
        t0 = time.perf_counter_ns()
        faults.fire("engine.launch")
        t1 = time.perf_counter_ns()
        res = launch()
        t2 = time.perf_counter_ns()
        faults.fire("engine.crc")
        jax.block_until_ready(res)
        t3 = time.perf_counter_ns()
        out, stats = jax.tree.map(np.asarray, res)
        t4 = time.perf_counter_ns()
        tr = self.tracer
        if tr.enabled:
            tr.complete(span, t0, t4 - t0, args=args)
            tr.complete("compact.dispatch", t1, t2 - t1)
            tr.complete("compact.device_wait", t2, t3 - t2)
            tr.complete("compact.d2h", t3, t4 - t3,
                        args={"bytes": sum(a.nbytes for a in out)})
        return (out, stats), (t4 - t0) / 1e9

    def _compact_staged(self, staged, cls, launch, bottom_level, *,
                        real_blocks, t0):
        img, run_lens, _ = staged
        args = self._launch_args(("one",), 1, cls, launch)
        # the launch is device work, not host coordination: its wall time
        # is kept out of host_seconds
        (out, s), exec_wall = self._execute(
            "compact.execute", args,
            lambda: self.executor.launch(img, run_lens,
                                         bottom_level=bottom_level))
        out = self._cut(out, cls, batched=False)
        wire = self.geom.wire_words_per_block * 4
        stats = EngineStats(
            n_input=int(s.n_input), n_live=int(s.n_live),
            n_dropped=int(s.n_dropped), crc_ok=bool(s.crc_ok),
            bytes_in=real_blocks * wire, bytes_out=int(s.bytes_out))
        stats.host_seconds = max(time.perf_counter() - t0 - exec_wall, 0.0)
        stats.device_seconds = model_device_seconds(
            stats.bytes_in, stats.bytes_out, self.geom)
        stats.sort_seconds = self._sort_model(launch)
        return out, stats

    def build_image(self, keys, meta, vals, n_blocks=None) -> SSTImage:
        import jax.numpy as jnp

        from repro.core import offload
        keys = np.asarray(keys, U32)
        meta = np.asarray(meta, U32)
        vals = np.asarray(vals, U32)
        n = keys.shape[0]
        k = self.geom.block_kvs
        n_pad = offload.next_pow2(max(1, -(-n // k))) * k
        keys = np.pad(keys, ((0, n_pad - n), (0, 0)))
        meta = np.pad(meta, (0, n_pad - n))
        vals = np.pad(vals, ((0, n_pad - n), (0, 0)))
        img = offload.build_image(
            jnp.asarray(keys), jnp.asarray(meta), jnp.asarray(vals),
            jnp.int32(n), geom=self.geom, backend=self.executor.backend)
        return SSTImage(*(np.asarray(a) for a in img))


def model_sort_seconds(n_rows: int, lanes: int, n_runs: int,
                       sort_mode: str) -> float:
    """Roofline model of the phase-2 (tuple ordering) share of the device
    pipeline: tuple-buffer bytes per pass x passes.

    * ``merge``: ``ceil(log2 k)`` merge-tree levels, each one read + one
      write pass over the tuples (merge-path partitioning is balanced, so
      a level is exactly one streaming pass);
    * ``device`` (bitonic): ``log2(n)*(log2(n)+1)/2`` compare-exchange
      stages;
    * ``xla``: ~``log2 n`` radix-style passes;
    * ``cooperative``: one D2H + H2D tuple round trip over the host link
      (the host-side sort time is measured, not modeled).
    """
    from repro.roofline import constants
    chip = constants.peaks()
    tup = n_rows * lanes * 4
    log_n = max(1, (max(n_rows, 2) - 1).bit_length())
    if sort_mode == "merge":
        levels = max(1, (max(n_runs, 1) - 1).bit_length())
        return levels * 2 * tup / chip.hbm_bw
    if sort_mode == "device":
        stages = log_n * (log_n + 1) // 2
        return stages * 2 * tup / chip.hbm_bw
    if sort_mode == "xla":
        return log_n * 2 * tup / chip.hbm_bw
    return 2 * tup / constants.HOST_LINK_BW_ASSUMED  # cooperative round trip


def model_device_seconds(bytes_in: int, bytes_out: int,
                         geom: SSTGeometry) -> float:
    """Roofline model of the device-side compaction time, from the peaks
    of the device kind in ``roofline.constants`` (the modelled v5e when
    no TPU is attached; an unlisted TPU raises).  The pipeline is
    memory-bound: ~3 HBM passes (unpack read, sort traffic, pack write)
    plus the H2D/D2H copies at the assumed host-link rate."""
    from repro.roofline import constants
    chip = constants.peaks()
    moved = 3 * (bytes_in + bytes_out)
    return (moved / chip.hbm_bw
            + (bytes_in + bytes_out) / constants.HOST_LINK_BW_ASSUMED + 20e-6)
