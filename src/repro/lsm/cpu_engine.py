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
import time

import numpy as np

from repro.core.formats import SSTGeometry, SSTImage
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


def _np_mix32(h):
    h = h ^ (h >> U32(16))
    h = (h * U32(0x85EBCA6B)).astype(U32)
    h = h ^ (h >> U32(13))
    h = (h * U32(0xC2B2AE35)).astype(U32)
    return h ^ (h >> U32(16))


def np_bloom_hashes(keys: np.ndarray):
    keys = keys.astype(U32)
    h1 = np.full(keys.shape[:-1], 2166136261, U32)
    h2 = np.full(keys.shape[:-1], 2166136261 ^ 0xDEADBEEF, U32)
    for lane in range(keys.shape[-1]):
        h1 = ((h1 ^ keys[..., lane]) * U32(16777619)).astype(U32)
        h2 = ((h2 ^ U32(0x9E3779B9) ^ keys[..., lane]) *
              U32(16777619)).astype(U32)
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
        if g.bloom_granularity == "block":
            groups, per = nb, k
        else:
            per = min(g.sst_kvs, n_pad)
            groups = n_pad // per
        bloom = np_bloom_build(keys.reshape(groups, per, g.key_lanes),
                               valid.reshape(groups, per),
                               g.bloom_words(per), g.bloom_probes)
        return SSTImage(keys=img.keys, meta=img.meta, vals=img.vals,
                        shared=img.shared, nvalid=img.nvalid, crc=crc,
                        bloom=bloom)


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
        # shape-bucketed jit cache bookkeeping: every job is padded to a
        # power-of-two block count, so repeated jobs of similar size reuse
        # the trace instead of recompiling.  A miss = first job at a bucket.
        self.jit_bucket_counts: dict[int, int] = {}
        self.jit_bucket_hits = 0
        self.jit_bucket_misses = 0
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

    def _note_bucket(self, bucket: int):
        seen = self.jit_bucket_counts.get(bucket, 0)
        self.jit_bucket_counts[bucket] = seen + 1
        if seen:
            self.jit_bucket_hits += 1
        else:
            self.jit_bucket_misses += 1

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
            import jax.numpy as jnp
            t0 = time.perf_counter()  # H2D staging counts as host work
            imgs = [SSTImage(*(jnp.asarray(np.asarray(a)) for a in im))
                    for im in images]
            real_blocks = sum(np.asarray(im.keys).shape[0] for im in images)
            return self._compact_staged(imgs, real_blocks,
                                        bottom_level=bottom_level, t0=t0)

        return self._with_fallback(
            attempt,
            lambda: self._cpu_engine().compact(images,
                                               bottom_level=bottom_level))

    def compact_paths(self, paths: list[str], *, bottom_level: bool = False):
        """Compact straight from SST files, double-buffering host reads:
        while image *i* is staged host->device, a dedicated I/O thread is
        already reading file *i+1* -- and because JAX dispatch is async,
        the first reads of this job overlap the device tail of the
        previous one (the paper's cross-job "judicious data movement")."""
        def attempt():
            import jax.numpy as jnp

            from repro.core.background import PrefetchReader
            from repro.lsm import sstable
            t0 = time.perf_counter()
            if self._reader is None:
                self._reader = PrefetchReader()
            with self.tracer.span("compact.read_inputs", files=len(paths)):
                imgs, real_blocks = [], 0
                for im in self._reader.read_all(paths, sstable.read_sst):
                    real_blocks += im.keys.shape[0]
                    imgs.append(SSTImage(*(jnp.asarray(a) for a in im)))
            return self._compact_staged(imgs, real_blocks,
                                        bottom_level=bottom_level, t0=t0)

        return self._with_fallback(
            attempt,
            lambda: self._cpu_engine().compact_paths(
                paths, bottom_level=bottom_level))

    def compact_many(self, jobs: list[tuple[list[str], bool]]
                     ) -> list[tuple[SSTImage, EngineStats]]:
        """Compact several independent jobs, coalescing same-shape-bucket
        jobs into single stacked device launches.

        ``jobs``: ``[(input_paths, bottom_level)]`` -- typically one job
        per shard, published by ``ShardedDB``'s global queue.  Jobs are
        grouped by ``scheduler.batch_signature`` of their *actual* input
        block counts; each >=2-job group becomes ONE vmapped dispatch
        (``offload.compact_batch``) with per-job CRC verdicts, singleton
        groups take the ordinary single-job path.  Results come back in
        input order and are bit-identical to per-job ``compact_paths``.
        """
        import jax.numpy as jnp

        from repro.core.background import PrefetchReader
        from repro.core.scheduler import batch_signature
        from repro.lsm import sstable
        t_many0 = time.perf_counter_ns()
        t_read0 = time.perf_counter()
        if self._reader is None:
            self._reader = PrefetchReader()
        flat_paths = [p for paths, _ in jobs for p in paths]
        with self.tracer.span("compact.read_inputs", files=len(flat_paths)):
            flat_imgs = list(self._reader.read_all(flat_paths,
                                                   sstable.read_sst))
        t_read = time.perf_counter() - t_read0
        job_imgs, job_blocks, off = [], [], 0
        for paths, _ in jobs:
            imgs = flat_imgs[off:off + len(paths)]
            off += len(paths)
            job_imgs.append(imgs)
            job_blocks.append([im.keys.shape[0] for im in imgs])

        groups: dict[tuple, list[int]] = {}
        for j, (_, bottom) in enumerate(jobs):
            sig = batch_signature(job_blocks[j], bottom,
                                  sort_mode=self.executor.sort_mode)
            groups.setdefault(sig, []).append(j)

        results: list = [None] * len(jobs)
        read_share = t_read / max(1, len(jobs))

        def single(j):
            """One prefetched job through the device path (+ fallback)."""
            def attempt():
                t0 = time.perf_counter()
                imgs = [SSTImage(*(jnp.asarray(a) for a in im))
                        for im in job_imgs[j]]
                out, es = self._compact_staged(
                    imgs, sum(job_blocks[j]), bottom_level=jobs[j][1],
                    t0=t0)
                es.host_seconds += read_share
                return out, es

            return self._with_fallback(
                attempt,
                lambda: self._cpu_engine().compact(
                    job_imgs[j], bottom_level=jobs[j][1]))

        for sig, idxs in groups.items():
            if len(idxs) == 1:
                results[idxs[0]] = single(idxs[0])
                continue
            try:
                results_group = self._compact_batched(
                    [job_imgs[j] for j in idxs], bucket=sig[1],
                    bottom_level=jobs[idxs[0]][1], read_share=read_share)
            except faults.FaultInjected as e:
                # the stacked launch faulted: isolate by re-running the
                # group's jobs one by one (device retry + CPU fallback
                # per job), so one bad launch cannot wedge every shard
                self._note_fault(e)
                self.launch_retries += 1
                results_group = None
            if results_group is None:
                for j in idxs:
                    results[j] = single(j)
            else:
                for j, res in zip(idxs, results_group):
                    if not res[1].crc_ok:
                        # per-job negative verdict inside a batch: get an
                        # authoritative single-job verdict (still fails
                        # for genuinely corrupt inputs -- on the CPU)
                        res = single(j)
                    results[j] = res
        if self.tracer.enabled:
            self.tracer.complete(
                "compact_many", t_many0,
                time.perf_counter_ns() - t_many0,
                args={"jobs": len(jobs), "groups": len(groups)})
        return results

    def _compact_batched(self, group_imgs, *, bucket, bottom_level,
                         read_share):
        """One stacked launch over >=2 same-signature jobs."""
        import jax.numpy as jnp

        from repro.core import offload
        t0 = time.perf_counter()
        staged = []
        for imgs in group_imgs:
            imgs = [SSTImage(*(jnp.asarray(np.asarray(a)) for a in im))
                    for im in imgs]
            if self.executor.sort_mode == "merge":
                imgs = [offload.pad_image_blocks(
                    im, offload.next_pow2(im.keys.shape[0]), self.geom)
                    for im in imgs]
            staged.append(imgs)
        n_jobs = len(staged)
        self._note_bucket(bucket)
        self.batch_launches += 1
        self.batch_jobs += n_jobs
        self.max_batch_jobs = max(self.max_batch_jobs, n_jobs)
        t_exec0 = time.perf_counter()
        t_exec0_ns = time.perf_counter_ns()
        faults.fire("engine.launch")
        outs = self.executor.compact_many(staged, bottom_level=bottom_level,
                                          pad_blocks=bucket)
        faults.fire("engine.crc")
        outs = [(SSTImage(*(np.asarray(a) for a in out)), s)
                for out, s in outs]
        exec_wall = time.perf_counter() - t_exec0
        host_share = max(time.perf_counter() - t0 - exec_wall, 0.0) / n_jobs
        wire = self.geom.wire_words_per_block * 4
        results = []
        for (out, s), imgs, raw in zip(outs, staged, group_imgs):
            total_blocks = sum(im.keys.shape[0] for im in imgs)
            stats = EngineStats(
                n_input=int(s.n_input), n_live=int(s.n_live),
                n_dropped=int(s.n_dropped), crc_ok=bool(s.crc_ok),
                bytes_in=sum(im.keys.shape[0] for im in raw) * wire,
                bytes_out=int(s.bytes_out), batched=True)
            stats.host_seconds = host_share + read_share
            stats.device_seconds = model_device_seconds(
                stats.bytes_in, stats.bytes_out, self.geom)
            n_runs = len(imgs) + (1 if bucket > total_blocks else 0)
            stats.sort_seconds = model_sort_seconds(
                bucket * self.geom.block_kvs, self.geom.key_lanes + 2,
                n_runs, self.executor.sort_mode)
            results.append((out, stats))
        if self.tracer.enabled:
            self.tracer.complete("compact.batch_launch", t_exec0_ns,
                                 int(exec_wall * 1e9),
                                 args={"jobs": n_jobs, "bucket": bucket})
            self._trace_modeled_phases(
                t_exec0_ns, exec_wall,
                sum(s.device_seconds for _, s in results),
                sum(s.sort_seconds for _, s in results),
                sum(s.bytes_in for _, s in results),
                sum(s.bytes_out for _, s in results))
        return results

    def _compact_staged(self, imgs, real_blocks, *, bottom_level, t0):
        from repro.core import offload
        if self.executor.sort_mode == "merge":
            # run-aligned bucketing: the per-run entry counts are part of
            # the merge pipeline's jit cache key, so pad every input run
            # up to a pow2 block count (padding rows carry the sentinel
            # key and sort last inside their run) -- repeated jobs with
            # similar input sizes then reuse the trace
            imgs = [offload.pad_image_blocks(
                im, offload.next_pow2(im.keys.shape[0]), self.geom)
                for im in imgs]
        # bucket the total block count to a power of two: stable jit shapes
        # across jobs (padding blocks are empty and carry the zero-block
        # CRC; the executor appends them as a trailing sentinel run)
        total_blocks = sum(im.keys.shape[0] for im in imgs)
        bucket = offload.next_pow2(total_blocks)
        self._note_bucket(bucket)
        # the jitted pipeline call stands in for the TPU execution: its
        # wall time is NOT host coordination work (the roofline model
        # supplies the accelerator time) -- time it separately
        t_exec0 = time.perf_counter()
        t_exec0_ns = time.perf_counter_ns()
        faults.fire("engine.launch")
        out, s = self.executor.compact(imgs, bottom_level=bottom_level,
                                       pad_blocks=bucket)
        faults.fire("engine.crc")
        out = SSTImage(*(np.asarray(a) for a in out))
        exec_wall = time.perf_counter() - t_exec0
        wire = self.geom.wire_words_per_block * 4
        stats = EngineStats(
            n_input=int(s.n_input), n_live=int(s.n_live),
            n_dropped=int(s.n_dropped), crc_ok=bool(s.crc_ok),
            bytes_in=real_blocks * wire, bytes_out=int(s.bytes_out))
        stats.host_seconds = max(time.perf_counter() - t0 - exec_wall, 0.0)
        stats.device_seconds = model_device_seconds(
            stats.bytes_in, stats.bytes_out, self.geom)
        # the trailing padding run only exists when the bucket pad is
        # non-empty
        n_runs = len(imgs) + (1 if bucket > total_blocks else 0)
        stats.sort_seconds = model_sort_seconds(
            bucket * self.geom.block_kvs, self.geom.key_lanes + 2,
            n_runs, self.executor.sort_mode)
        if self.tracer.enabled:
            self.tracer.complete("compact.execute", t_exec0_ns,
                                 int(exec_wall * 1e9),
                                 args={"jobs": 1, "bucket": bucket})
            self._trace_modeled_phases(
                t_exec0_ns, exec_wall, stats.device_seconds,
                stats.sort_seconds, stats.bytes_in, stats.bytes_out)
        return out, stats

    def _trace_modeled_phases(self, t0_ns: int, wall_s: float,
                              device_s: float, sort_s: float,
                              bytes_in: int, bytes_out: int):
        """Nest the roofline-modeled device phases inside the measured
        launch span: CRC verify -> merge phase 2 -> SST format.  The
        jitted pipeline call stands in for the accelerator, so the
        child durations come from the model (their args carry
        ``modeled: True``), split pro-rata by I/O share and scaled down
        when the model total exceeds the measured wall so the nesting
        stays well-formed."""
        tr = self.tracer
        io = bytes_in + bytes_out
        rest = max(device_s - sort_s, 0.0)
        crc = rest * (bytes_in / io) if io else 0.0
        phases = (("compact.crc_verify", crc),
                  ("compact.merge_phase2", max(sort_s, 0.0)),
                  ("compact.format", rest - crc))
        total = sum(d for _, d in phases)
        if total <= 0.0:
            return
        scale = min(1.0, wall_s / total)
        cur = t0_ns
        for name, d in phases:
            dur = int(d * scale * 1e9)
            tr.complete(name, cur, dur, args={"modeled": True})
            cur += dur

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
