"""The LSM key-value store: public API over memtable + WAL + levels +
pluggable compaction engine (device = LUDA, cpu = LevelDB-like baseline).

Write path (see docs/async.md for the diagram):

    put() -> WAL append -> active memtable
                |  (memtable full)
                v
        sync mode:  flush + compaction cascade inline (blocks the writer)
        async mode: rotate the active table onto the immutable queue and
                    return immediately; flush workers build + install L0
                    SSTs in rotation order, and a single compaction worker
                    drains the scheduler, reading inputs double-buffered
                    against device work (``engine.compact_paths``).

All metadata (versions, manifest, scheduler state, memtable list) is
guarded by one RLock; version application is copy-on-write so readers can
search a snapshot outside the lock.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time

import numpy as np

from repro.core import formats
from repro.core.background import BackgroundExecutor, InstallSequencer
from repro.core.formats import SSTGeometry, SSTImage
from repro.core.scheduler import (CompactionJob, CompactionScheduler,
                                  SchedulerConfig)
from repro.lsm import (DEFAULT_READ_OPTIONS, DEFAULT_WRITE_OPTIONS,
                       ReadOptions, WriteOptions)
from repro.lsm import cpu_engine as ce
from repro.lsm import faults
from repro.lsm import memtable
from repro.lsm.faults import BackgroundError
from repro.lsm import read as lsm_read
from repro.lsm import sstable, wal
from repro.lsm.memtable import ImmutableMemTable
from repro.lsm.sstable import BlockCache, FileMeta, TableCache
from repro.lsm.version import VersionEdit, VersionSet
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, watch_jit


@dataclasses.dataclass
class DBConfig:
    geom: SSTGeometry = dataclasses.field(default_factory=SSTGeometry)
    engine: str = "device"          # "device" | "cpu"
    sort_mode: str = "merge"        # device engine phase-2 mode:
    #   "merge" (run-aware merge path) | "device" (bitonic) | "xla"
    #   | "cooperative" (paper-faithful host sort)
    threads: int = 1                # modeled CPU compaction threads
    memtable_bytes: int | None = None
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig)
    table_cache: int = 64
    block_cache_blocks: int = 4096  # host LRU of decoded blocks (0 = off)
    sync_wal: bool = False
    sync_writes: bool = False       # full durability for acks: fsync every
    #   WAL append AND the parent-directory entries of created/renamed
    #   files (the crash-consistency matrix runs with this on; see
    #   docs/robustness.md)
    auto_compact: bool = True
    async_compaction: bool = False  # non-blocking writes + bg flush/compact
    flush_workers: int = 1          # image builds overlap; installs ordered
    max_pending_memtables: int = 4  # immutable-queue depth before stalling
    metrics: object | None = None   # obs.MetricsRegistry (None -> private
    #   registry; pass obs.NULL_REGISTRY to opt out of instrumentation)
    tracer: object | None = None    # obs.Tracer (None -> NULL_TRACER)
    failpoints: object | None = None    # fault-injection spec (str | dict),
    #   installed into the process-global registry at open -- see
    #   repro.lsm.faults and docs/robustness.md
    bg_max_retries: int = 3         # transient background-failure retries
    bg_retry_base_s: float = 0.005  # backoff base (doubles + jitter)


@dataclasses.dataclass
class DBStats:
    """Point-in-time statistics snapshot.

    The live counters behind these fields are atomic ``obs`` registry
    counters (``lsm.<field>``, labeled by shard when the DB is part of a
    ``ShardedDB``); ``LsmDB.stats`` materializes a snapshot on access,
    so this stays the stable reporting API while increments from
    background flush/compaction threads are race-free."""

    puts: int = 0
    write_batches: int = 0         # write_batch() calls
    batch_ops: int = 0             # ops applied through write_batch()
    gets: int = 0
    multi_gets: int = 0            # multi_get() calls
    multi_get_keys: int = 0        # keys resolved through multi_get()
    deletes: int = 0
    flushes: int = 0
    compactions: int = 0
    trivial_moves: int = 0
    compact_bytes_in: int = 0
    compact_bytes_out: int = 0
    compact_entries_in: int = 0
    compact_entries_dropped: int = 0
    compact_host_seconds: float = 0.0
    compact_device_seconds: float = 0.0
    compact_sort_seconds: float = 0.0   # phase-2 share (see EngineStats)
    flush_host_seconds: float = 0.0
    bloom_negative_skips: int = 0
    block_cache_hits: int = 0
    block_cache_misses: int = 0
    write_stalls: int = 0
    batched_compactions: int = 0   # jobs installed from a stacked launch
    bg_retries: int = 0            # transient background-failure retries
    bg_resumes: int = 0            # resume() calls that cleared a bg_error
    orphans_removed: int = 0       # stale .tmp / unreferenced SSTs GC'd
    engine_fallbacks: int = 0      # compactions installed via CPU fallback

    def add(self, other: "DBStats") -> "DBStats":
        """Field-wise sum (aggregation across shards)."""
        return DBStats(**{f.name: getattr(self, f.name) +
                          getattr(other, f.name)
                          for f in dataclasses.fields(DBStats)})


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """Pinned read view from ``LsmDB.snapshot()``.

    Pins the SST version and the memtable *set* as of capture: a read
    sequence against one snapshot observes one consistent file set (no
    mid-read re-snapshot retries).  The active memtable is captured by
    reference and stays live -- this is a consistent view of immutable
    state, not MVCC point-in-time isolation (the memtable keeps only the
    newest version per key, so older point-in-time values are already
    gone).  Files compacted away while the snapshot is held raise
    ``FileNotFoundError`` on access."""

    mems: tuple          # newest-first: (active, imm_newest, ..., oldest)
    version: object      # pinned lsm.version.Version


def make_engine(cfg: DBConfig):
    """Build the compaction engine a ``DBConfig`` describes (shared by
    ``LsmDB`` and ``ShardedDB``).  The engine inherits ``cfg.tracer`` so
    compaction-phase spans (CRC verify, merge, format) land in the same
    trace as the store's."""
    if cfg.engine == "device":
        return ce.DeviceCompactionEngine(cfg.geom, sort_mode=cfg.sort_mode,
                                         tracer=cfg.tracer)
    if cfg.engine == "cpu":
        return ce.CpuCompactionEngine(cfg.geom, threads=cfg.threads,
                                      tracer=cfg.tracer)
    raise ValueError(f"unknown engine {cfg.engine!r}")


class LsmDB:
    def __init__(self, path: str, cfg: DBConfig | None = None, *,
                 engine=None, compaction_sink=None, metrics=None,
                 tracer=None, metric_labels=None):
        """``engine``: inject a (possibly shared) compaction engine instead
        of building one from ``cfg`` -- ``ShardedDB`` passes one engine to
        every shard so batched cross-shard launches share a jit cache.
        ``compaction_sink``: when set, this DB never runs compactions
        itself; it calls ``compaction_sink(self)`` whenever it has
        compaction work, and the sink owner drives ``pick_compaction`` /
        ``apply_compaction`` (see ``core.background.GlobalCompactionQueue``).
        ``metrics``/``tracer``/``metric_labels``: observability injection
        (``ShardedDB`` shares one registry + tracer across shards, with a
        per-shard ``shard=i`` label); they win over the ``cfg`` fields.
        """
        self.path = path
        self.cfg = cfg or DBConfig()
        if self.cfg.failpoints is not None:
            faults.FAILPOINTS.install(self.cfg.failpoints)
        os.makedirs(path, exist_ok=True)
        self.geom = self.cfg.geom
        self._lock = threading.RLock()
        self._imm_cv = threading.Condition(self._lock)
        self.versions = VersionSet(path)
        self.versions.open()
        self.scheduler = CompactionScheduler(self.cfg.scheduler)
        self.scheduler.compact_pointer = dict(self.versions.compact_pointer)
        self._init_obs(metrics, tracer, metric_labels)
        # obs first: the block cache streams hit/miss counts straight into
        # the registry counters (no per-access dict lookup on the DB)
        self.block_cache = BlockCache(
            self.cfg.block_cache_blocks,
            on_hit=self._c["block_cache_hits"].inc,
            on_miss=self._c["block_cache_misses"].inc)
        self.cache = TableCache(self.cfg.table_cache, geom=self.geom,
                                block_cache=self.block_cache)
        self.mem = memtable.MemTable()            # guarded-by: _lock
        self.imm: list[ImmutableMemTable] = []    # guarded-by: _lock
        self._owns_engine = engine is None
        self._compaction_sink = compaction_sink
        self.engine = engine if engine is not None else self._make_engine()
        self._memtable_limit = self.cfg.memtable_bytes or self.geom.sst_bytes
        self._wal_path = os.path.join(path, "wal.log")
        self._wal_seg_no = 0                      # guarded-by: _lock
        self._active_extra_wals: list[str] = []   # guarded-by: _lock
        self._wal_sync = self.cfg.sync_wal or self.cfg.sync_writes
        with self._lock:
            self._replay_wal_locked()
            self._gc_orphans_locked()
        self._wal = wal.WALWriter(self._wal_path,
                                  sync=self._wal_sync)  # guarded-by: _lock
        self._async = bool(self.cfg.async_compaction)
        self._install_seq = InstallSequencer()
        self._compact_scheduled = False           # guarded-by: _lock
        self._closed = False                      # guarded-by: _lock
        self._bg_error: BackgroundError | None = None   # guarded-by: _lock
        if self._async:
            self._flush_exec = BackgroundExecutor(
                workers=max(1, self.cfg.flush_workers), name="flush")
            # with a compaction sink the sink owner runs compactions --
            # a per-DB worker thread would only ever sit idle
            self._compact_exec = None if compaction_sink is not None else \
                BackgroundExecutor(workers=1, name="compact")
        else:
            self._flush_exec = self._compact_exec = None
        # JAX's trace/lower/compile steps as jit.* spans while tracing;
        # released at close()
        self._unwatch_jit = watch_jit(self.tracer)

    @classmethod
    def open(cls, path: str, cfg: DBConfig | None = None, *,
             repair: bool = False, **kw) -> "LsmDB":
        """Open a store, optionally running crash repair first.

        ``repair=True`` runs :func:`repro.lsm.repair.repair` on the
        directory before opening: corrupt SSTs are quarantined to
        ``lost/``, torn WAL tails truncated, and the MANIFEST rebuilt
        from surviving files (also available offline as
        ``python -m repro.lsm.repair <dir>``)."""
        if repair and os.path.isdir(path):
            from repro.lsm import repair as repair_mod
            repair_mod.repair(path)
        return cls(path, cfg, **kw)

    def _init_obs(self, metrics, tracer, metric_labels):
        """Registry counters supersede the old ad-hoc ``DBStats`` fields:
        every mutation below goes through an atomic counter (safe from
        flush workers and the compaction drainer without the DB lock) and
        ``self.stats`` snapshots them back into a ``DBStats``."""
        if metrics is None:
            metrics = self.cfg.metrics
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        t = tracer if tracer is not None else self.cfg.tracer
        self.tracer = t if t is not None else NULL_TRACER
        labels = dict(metric_labels or {})
        self._span_args = labels or None
        # per-shard counter-track suffix so Perfetto draws one stepped
        # track per shard instead of interleaving samples on one
        self._track = "".join(f"[{k}={v}]" for k, v in sorted(labels.items()))
        self._c = {f.name: self.metrics.counter(f"lsm.{f.name}", **labels)
                   for f in dataclasses.fields(DBStats)}
        self._h_put = self.metrics.histogram("lsm.op.latency_us",
                                             op="put", **labels)
        self._h_get = self.metrics.histogram("lsm.op.latency_us",
                                             op="get", **labels)
        self._h_multi_get = self.metrics.histogram("lsm.op.latency_us",
                                                   op="multi_get", **labels)
        self._h_write_batch = self.metrics.histogram(
            "lsm.op.latency_us", op="write_batch", **labels)
        self._g_imm = self.metrics.gauge("lsm.imm_queue.depth", **labels)
        self._g_debt = self.metrics.gauge("lsm.compaction.debt", **labels)
        # 0 = healthy, 1 = transient bg_error (resume() recovers),
        # 2 = hard bg_error (run repair first) -- docs/robustness.md
        self._g_bg_error = self.metrics.gauge("lsm.bg_error", **labels)

    @property
    def stats(self) -> DBStats:
        """Point-in-time ``DBStats`` snapshot of the registry counters."""
        return DBStats(**{
            f.name: (float(v) if isinstance(f.default, float) else int(v))
            for f in dataclasses.fields(DBStats)
            for v in (self._c[f.name].value,)})

    def _sample_pressure_locked(self):
        """Gauge the write-pressure signals (immutable-queue depth +
        compaction debt) onto the registry and, when tracing, onto
        Perfetto counter tracks.  Called on state transitions."""
        depth = len(self.imm)
        debt = self.scheduler.debt(self.versions.current)
        self._g_imm.set(depth)
        self._g_debt.set(debt)
        tr = self.tracer
        if tr.enabled:
            tr.counter("lsm.imm_queue.depth" + self._track, depth)
            tr.counter("lsm.compaction.debt" + self._track, round(debt, 3))

    def _make_engine(self):
        eng = make_engine(self.cfg)
        # a tracer injected via the LsmDB kwarg (not cfg) must still reach
        # the owned engine, so compaction-phase spans land in the trace
        eng.tracer = self.tracer
        return eng

    def _replay_wal_locked(self):
        """Replay rotated WAL segments (oldest first), then the active WAL.
        Replayed segments stay on disk until the recovered memtable
        flushes; a crash during recovery loses nothing."""
        import glob
        segs = sorted(glob.glob(os.path.join(self.path, "wal-*.log")))
        if segs:
            self._wal_seg_no = max(
                int(os.path.basename(p)[4:-4]) for p in segs)
        self._active_extra_wals = list(segs)
        for p in segs + [self._wal_path]:
            for kind, seq, key, value in wal.replay(p):
                if kind == wal.PUT:
                    self.mem.put(key, seq, value)
                else:
                    self.mem.delete(key, seq)
                self.versions.last_seq = max(self.versions.last_seq, seq)

    def _gc_orphans_locked(self):
        """Delete crash leftovers: stale ``*.tmp`` files and SSTs no
        version references.  Safe because an unreferenced SST is either a
        flush that never logged its edit (its data is still in the WAL we
        just replayed) or a compaction input whose deletion crashed
        mid-unlink (its data lives in the installed outputs)."""
        live = {fm.file_no for _, fm in self.versions.current.all_files()}
        removed = 0
        for name in os.listdir(self.path):
            p = os.path.join(self.path, name)
            if not os.path.isfile(p):
                continue
            stale = False
            if name.endswith(".tmp"):
                stale = True
            elif name.endswith(".sst"):
                try:
                    stale = int(name[:-4]) not in live
                except ValueError:
                    continue
            if stale:
                try:
                    os.remove(p)
                    removed += 1
                except FileNotFoundError:
                    pass
        if removed:
            self._c["orphans_removed"].inc(removed)

    # ------------------------------------------------------------------
    # writes
    # ------------------------------------------------------------------

    def _check_key(self, key: bytes):
        if len(key) > self.geom.key_bytes:
            raise ValueError(f"key too long ({len(key)} > "
                             f"{self.geom.key_bytes} bytes)")
        if key.endswith(b"\x00") or not key:
            raise ValueError("keys must be non-empty and not end with NUL "
                             "(fixed-width key format)")

    def _check_value(self, value: bytes):
        if len(value) > self.geom.value_bytes - 4:
            raise ValueError(f"value too long ({len(value)} > "
                             f"{self.geom.value_bytes - 4} bytes)")

    def put(self, key: bytes, value: bytes,
            opts: WriteOptions | None = None):
        opts = opts or DEFAULT_WRITE_OPTIONS
        self._check_key(key)
        self._check_value(value)
        t0 = time.perf_counter_ns()
        with self._lock:
            self._check_open_locked()
            seq = self._next_seq()
            self._wal.append(wal.PUT, seq, key, value, sync=opts.sync)
            self.mem.put(key, seq, value)
            self._maybe_flush_locked(wait_stall=opts.wait_stall)
        # hot path: an atomic counter bump and a lock-free histogram
        # append (drained lazily) -- see tests/test_obs.py overhead check
        dt = time.perf_counter_ns() - t0
        self._c["puts"].inc()
        self._h_put.pend(dt / 1000.0)
        tr = self.tracer
        if tr.enabled:
            tr.complete("db.put", t0, dt)

    def delete(self, key: bytes, opts: WriteOptions | None = None):
        opts = opts or DEFAULT_WRITE_OPTIONS
        with self._lock:
            self._check_open_locked()
            seq = self._next_seq()
            self._wal.append(wal.DELETE, seq, key, sync=opts.sync)
            self.mem.delete(key, seq)
            self._maybe_flush_locked(wait_stall=opts.wait_stall)
        self._c["deletes"].inc()

    @staticmethod
    def _normalize_batch(ops) -> list[tuple[int, bytes, bytes]]:
        """Normalize ``write_batch`` ops into WAL ``(kind, key, value)``
        rows.  Accepts ``("put", key, value)`` and ``("delete", key)``."""
        out = []
        for op in ops:
            if op[0] == "put":
                _, key, value = op
                out.append((wal.PUT, key, value))
            elif op[0] == "delete":
                out.append((wal.DELETE, op[1], b""))
            else:
                raise ValueError(f"unknown batch op {op[0]!r} "
                                 "(want 'put' or 'delete')")
        return out

    def write_batch(self, ops, opts: WriteOptions | None = None) -> int:
        """Atomically apply a group of writes.

        ``ops``: iterable of ``("put", key, value)`` / ``("delete", key)``
        tuples, applied in order (a later op on the same key wins).  The
        whole batch is ONE CRC-framed WAL record and one locked memtable
        apply: after a crash, replay recovers either every op or none --
        a torn or unsynced record discards the batch wholesale, never a
        prefix (docs/serving.md).  Returns the number of ops applied.

        Atomicity is with respect to *crash recovery*: a concurrent
        reader racing the apply may observe a prefix of the batch (the
        store's reads are lock-free by design, same as put)."""
        opts = opts or DEFAULT_WRITE_OPTIONS
        rows = self._normalize_batch(ops)
        # validate everything BEFORE the first side effect: a bad op must
        # reject the whole batch, not tear it
        for kind, key, value in rows:
            self._check_key(key)
            if kind == wal.PUT:
                self._check_value(value)
        if not rows:
            return 0
        t0 = time.perf_counter_ns()
        with self._lock:
            self._check_open_locked()
            first_seq = self.versions.last_seq + 1
            self.versions.last_seq += len(rows)
            self._wal.append_batch(rows, first_seq, sync=opts.sync)
            # crash window: the WAL record is durable but the memtable is
            # not -- replay on reopen applies the whole batch (all ops or,
            # had the append torn, none)
            faults.fire("db.write_batch")
            for i, (kind, key, value) in enumerate(rows):
                if kind == wal.PUT:
                    self.mem.put(key, first_seq + i, value)
                else:
                    self.mem.delete(key, first_seq + i)
            self._maybe_flush_locked(wait_stall=opts.wait_stall)
        dt = time.perf_counter_ns() - t0
        self._c["write_batches"].inc()
        self._c["batch_ops"].inc(len(rows))
        self._h_write_batch.pend(dt / 1000.0)
        tr = self.tracer
        if tr.enabled:
            tr.complete("db.write_batch", t0, dt,
                        args={"n_ops": len(rows),
                              **(self._span_args or {})})
        return len(rows)

    def _check_open_locked(self):
        """Writes after ``close()`` must fail loudly: the WAL handle is
        (or is about to be) closed, so accepting the write would either
        raise a bare ValueError from the file object or -- worse -- land
        in the memtable with no durability and vanish."""
        if self._closed:
            raise IOError("database is closed")

    def _next_seq(self) -> int:
        self.versions.last_seq += 1
        return self.versions.last_seq

    def _maybe_flush_locked(self, wait_stall: bool = True):
        if self.mem.approx_bytes < self._memtable_limit:
            return
        if self._async:
            self._rotate_locked(wait_stall=wait_stall)
        else:
            self.flush()
            if self.cfg.auto_compact:
                self.maybe_compact()

    def _rotate_locked(self, wait_stall: bool = True):
        """Move the active memtable onto the immutable queue (O(1): close +
        rename the WAL segment) and hand it to a flush worker."""
        # surface any earlier background-flush failure BEFORE mutating
        # rotation state (a raise after issuing the install ticket would
        # orphan it and wedge every later flush)
        self._flush_exec.check()
        if self._bg_error is not None:
            raise IOError("writes halted: a background flush failed "
                          f"earlier: {self._bg_error!r}; call resume() "
                          "to restart the pipeline")
        tr = self.tracer
        while len(self.imm) >= self.cfg.max_pending_memtables:
            if not wait_stall:
                # WriteOptions(wait_stall=False): shed load instead of
                # parking the writer behind the flush pipeline.  The
                # triggering write is already durable in the WAL + active
                # memtable -- only the rotation is refused.
                raise IOError(
                    "write stall: immutable-memtable queue is full and "
                    "WriteOptions.wait_stall is False")
            self._c["write_stalls"].inc()
            self._sample_pressure_locked()
            t_stall = time.perf_counter_ns()
            ok = self._imm_cv.wait(timeout=60.0)
            if tr.enabled:
                tr.complete("write_stall", t_stall,
                            time.perf_counter_ns() - t_stall,
                            args={"cause": "imm_queue_full",
                                  "depth": len(self.imm),
                                  **(self._span_args or {})})
            if not ok:
                raise IOError("write stalled >60s: immutable queue not "
                              "draining (background flush dead?)")
            if self._bg_error is not None:
                raise IOError("writes halted: a background flush failed "
                              f"while stalled: {self._bg_error!r}; call "
                              "resume() to restart the pipeline")
        t_rot = time.perf_counter_ns()
        self._wal.close()
        self._wal_seg_no += 1
        seg = os.path.join(self.path, f"wal-{self._wal_seg_no:06d}.log")
        os.rename(self._wal_path, seg)
        if self._wal_sync:
            faults.fsync_dir(self.path)   # segment rename durability
        entry = ImmutableMemTable(
            table=self.mem,
            wal_paths=self._active_extra_wals + [seg],
            ticket=self._install_seq.issue())
        self._active_extra_wals = []
        self.imm.append(entry)
        self.mem = memtable.MemTable()
        self._wal = wal.WALWriter(self._wal_path, sync=self._wal_sync)
        self._sample_pressure_locked()
        if tr.enabled:
            tr.complete("memtable.rotate", t_rot,
                        time.perf_counter_ns() - t_rot,
                        args=self._span_args)
        self._flush_exec.submit(self._background_flush, entry)

    def _set_bg_error(self, err: BaseException,
                      op: str = "flush") -> BaseException:
        """Record the first background error (classified, resume-able) and
        wake stalled writers.  Returns the error the caller should raise:
        the classified wrapper, except ``SimulatedCrash`` which must stay
        a BaseException (the crash matrix relies on it being uncatchable
        by ``except Exception``)."""
        if not isinstance(err, (BackgroundError, faults.SimulatedCrash)):
            err = BackgroundError(op, err)
        with self._lock:
            if self._bg_error is None and \
                    isinstance(err, BackgroundError):
                self._bg_error = err
                self._g_bg_error.set(1 if err.severity == "transient" else 2)
            # wake writers stalled on a full immutable queue -- it will
            # never drain now, and they should fail with the root cause
            self._imm_cv.notify_all()
        return err

    def resume(self) -> bool:
        """Clear a background error and restart the halted pipeline.

        Re-issues install tickets for every memtable still parked on the
        immutable queue (in rotation order) and resubmits their flushes,
        then reschedules compaction.  Returns True when an error was
        cleared.  For a hard error (corruption) the damage is still on
        disk -- run repair first (docs/robustness.md)."""
        t0 = time.perf_counter_ns()
        if self._async:
            # drain in-flight background work first: anything still queued
            # is failing/skipping against the standing bg_error, and its
            # errors are exactly the condition being cleared
            try:
                self._flush_exec.wait_idle()
            except Exception:
                pass
        with self._lock:
            err = self._bg_error
            if err is None:
                return False
            self._bg_error = None
            self._g_bg_error.set(0)
            resub = [dataclasses.replace(e, ticket=self._install_seq.issue())
                     for e in self.imm]
            self.imm = resub
            self._imm_cv.notify_all()
        self._c["bg_resumes"].inc()
        for e in resub:
            self._flush_exec.submit(self._background_flush, e)
        if self.cfg.auto_compact and \
                (self._async or self._compaction_sink is not None):
            self._schedule_compaction()
        tr = self.tracer
        if tr.enabled:
            tr.complete("db.resume", t0, time.perf_counter_ns() - t0,
                        args={"cleared": repr(err), "requeued": len(resub),
                              **(self._span_args or {})})
        return True

    def _background_flush(self, entry: ImmutableMemTable):
        t0 = time.perf_counter()

        def build():
            with self.tracer.span("flush.build", **(self._span_args or {})):
                entries = entry.table.sorted_entries()
                faults.fire("flush.build")
                if not entries:
                    return None
                keys, meta, vals = self._pack_entries(entries)
                return self.engine.build_image(keys, meta, vals)

        try:
            # transient build failures (I/O hiccups, injected soft faults)
            # retry in-line with backoff before escalating to bg_error
            img = faults.with_retries(
                build, retries=self.cfg.bg_max_retries,
                base_s=self.cfg.bg_retry_base_s,
                on_retry=self._c["bg_retries"].inc)
        except BaseException as e:
            # halt the flush pipeline (RocksDB-style bg_error): a younger
            # memtable must NOT install beneath this still-queued older
            # one, or its data would permanently shadow newer L0 data.
            # Consume our ticket so waiters aren't wedged; the entry stays
            # queued and readable.
            err = self._set_bg_error(e)
            self._install_seq.wait_turn(entry.ticket)
            self._install_seq.done(entry.ticket)
            raise err
        # installs land in rotation order: L0 reads resolve overwrites by
        # file number, so a newer memtable must not install below an older
        self._install_seq.wait_turn(entry.ticket)
        try:
            with self._lock:
                bg_error = self._bg_error
            if bg_error is not None:
                # an older memtable failed before our turn came: skip the
                # install (data stays readable in the immutable queue,
                # WAL segments stay on disk for replay in rotation order)
                raise IOError(
                    "flush halted: earlier background flush failed: "
                    f"{bg_error!r}")
            t_inst = time.perf_counter_ns()
            edit = VersionEdit()
            if img is not None:
                self._install_ssts(img, level=0, edit=edit)  # files on disk
            with self._lock:
                if img is not None:
                    self._log_edit(edit)
                self.imm.remove(entry)
                self._imm_cv.notify_all()
                self._sample_pressure_locked()
            self._c["flushes"].inc()
            self._c["flush_host_seconds"].add(time.perf_counter() - t0)
            if self.tracer.enabled:
                self.tracer.complete(
                    "flush.install_l0", t_inst,
                    time.perf_counter_ns() - t_inst, args=self._span_args)
            # WAL segments die inside the sequenced region: an older
            # memtable's segments are always unlinked before a newer
            # one's, so a crash can never leave old WAL data that would
            # replay over newer installed L0 data
            for p in entry.wal_paths:
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass
        except BaseException as e:
            raise self._set_bg_error(e)
        finally:
            self._install_seq.done(entry.ticket)
        if self.cfg.auto_compact:
            self._schedule_compaction()

    def _pack_entries(self, entries):
        keys = np.stack([formats.pack_key_bytes(k, self.geom.key_bytes)
                         for k, _, _ in entries])
        meta = np.array([(s << 1) | (1 if v is not None else 0)
                         for _, s, v in entries], np.uint32)
        vals = np.stack([formats.pack_value_bytes(v or b"",
                                                  self.geom.value_bytes)
                         for _, _, v in entries])
        return keys, meta, vals

    # ------------------------------------------------------------------
    # reads
    # ------------------------------------------------------------------

    def snapshot(self) -> Snapshot:
        """Capture a pinned read view (pass as ``ReadOptions.snapshot``)."""
        with self._lock:
            mems = (self.mem,) + tuple(e.table
                                       for e in reversed(self.imm))
            return Snapshot(mems=mems, version=self.versions.current)

    def _read_view(self, opts: ReadOptions):
        """(mems newest-first, version) for one read attempt."""
        if opts.snapshot is not None:
            return opts.snapshot.mems, opts.snapshot.version
        # lock-free snapshot.  Safe because writers publish in the
        # opposite order: rotation appends to imm BEFORE swapping the
        # active table, and flush installs the L0 version BEFORE
        # removing from imm -- so reading mem -> imm -> version can
        # only ever see a key twice, never lose it.
        mems = [self.mem] + [e.table for e in reversed(list(self.imm))]
        return mems, self.versions.current

    def get(self, key: bytes, opts: ReadOptions | None = None):
        """value bytes, or None if absent / deleted."""
        t0 = time.perf_counter_ns()
        try:
            return self._get_inner(key, opts or DEFAULT_READ_OPTIONS)
        finally:
            # gets used to bump a plain field with no lock at all (get is
            # lock-free by design); the registry counter is atomic
            dt = time.perf_counter_ns() - t0
            self._c["gets"].inc()
            self._h_get.pend(dt / 1000.0)
            tr = self.tracer
            if tr.enabled:
                tr.complete("db.get", t0, dt)

    def _get_inner(self, key: bytes, opts: ReadOptions):
        err = None
        for _ in range(8):
            mems, version = self._read_view(opts)
            for m in mems:
                found, value = m.get(key)
                if found:
                    return value
            try:
                return self._search_version(version, key, opts)
            except FileNotFoundError as e:
                if opts.snapshot is not None:
                    raise  # pinned view: the file is gone for good
                # background compaction deleted an input under this
                # snapshot; re-snapshot (the new version excludes it)
                err = e
        raise err

    def multi_get(self, keys, opts: ReadOptions | None = None
                  ) -> list[bytes | None]:
        """Vectorized ``get``: resolve K keys with (at most) one stacked
        bloom-probe launch and one stacked search/gather launch instead of
        K scalar searches.  Returns values positionally; bit-identical to
        ``[self.get(k, opts) for k in keys]``."""
        keys = list(keys)
        opts = opts or DEFAULT_READ_OPTIONS
        t0 = time.perf_counter_ns()
        try:
            return self._multi_get_inner(keys, opts)
        finally:
            self._c["multi_gets"].inc()
            self._c["multi_get_keys"].inc(len(keys))
            dt = time.perf_counter_ns() - t0
            self._h_multi_get.pend(dt / 1000.0)
            tr = self.tracer
            if tr.enabled:
                tr.complete("db.multi_get", t0, dt,
                            args={"n_keys": len(keys),
                                  **(self._span_args or {})})

    def _multi_get_inner(self, keys: list, opts: ReadOptions):
        err = None
        for _ in range(8):
            mems, version = self._read_view(opts)
            out: list[bytes | None] = [None] * len(keys)
            unresolved: list[tuple[int, bytes]] = []
            for i, key in enumerate(keys):
                for m in mems:
                    found, value = m.get(key)
                    if found:
                        out[i] = value
                        break
                else:
                    unresolved.append((i, key))
            try:
                cands = lsm_read.version_candidates(
                    version, unresolved, self.cache, self.geom)
                resolved = lsm_read.resolve_candidates(
                    cands, self.geom, opts, counters=self._c,
                    tracer=self.tracer, span_args=self._span_args)
            except FileNotFoundError as e:
                if opts.snapshot is not None:
                    raise
                err = e
                continue
            for slot, (_, value) in resolved.items():
                out[slot] = value
            return out
        raise err

    def _search_version(self, version, key: bytes,
                        opts: ReadOptions | None = None):
        if len(key) > self.geom.key_bytes:
            return None     # longer than any key put accepts
        # the key's filter hash pair: the first filter check fills it,
        # the tables after it reuse it
        hashes: list[int] = []
        # L0: overlapping files, newest first
        for fm in sorted(version.levels[0], key=lambda f: -f.file_no):
            if fm.smallest <= key <= fm.largest:
                found, value = self._table_get(fm, key, opts, hashes)
                if found:
                    return value
        # deeper levels: disjoint ranges
        for level in range(1, len(version.levels)):
            for fm in version.levels[level]:
                if fm.smallest <= key <= fm.largest:
                    found, value = self._table_get(fm, key, opts, hashes)
                    if found:
                        return value
                    break
        return None

    def _table_get(self, fm: FileMeta, key: bytes,
                   opts: ReadOptions | None, hashes: list[int]):
        found, value, pruned = self.cache.reader(fm, self.geom).probe(
            key, opts, tracer=self.tracer, hashes=hashes)
        if pruned:
            self._c["bloom_negative_skips"].inc()
        return found, value

    def scan(self, start: bytes, end: bytes,
             opts: ReadOptions | None = None):
        """[(key, value)] for start <= key < end, newest versions, no
        tombstones."""
        opts = opts or DEFAULT_READ_OPTIONS
        err = None
        for _ in range(8):
            with self._lock:
                # only the active table's entries are copied under the
                # lock (it mutates under concurrent puts); immutable
                # tables are frozen and sort safely outside it
                if opts.snapshot is not None:
                    imm_tables = list(opts.snapshot.mems[1:])
                    active_entries = opts.snapshot.mems[0].sorted_entries()
                    version = opts.snapshot.version
                else:
                    imm_tables = [e.table for e in self.imm]
                    active_entries = self.mem.sorted_entries()
                    version = self.versions.current
            mem_entries = [m.sorted_entries() for m in imm_tables] + \
                [active_entries]
            best: dict[bytes, tuple[int, bytes | None]] = {}
            # memtables oldest->newest so newer entries overwrite by seq
            for entries in mem_entries:
                for k, seq, v in entries:
                    if start <= k < end and \
                            (k not in best or best[k][0] < seq):
                        best[k] = (seq, v)
            try:
                for _, fm in version.all_files():
                    if fm.largest < start or fm.smallest >= end:
                        continue
                    rdr = self.cache.reader(fm, self.geom)
                    for k, seq, v in rdr.scan(start, end, opts):
                        if k not in best or best[k][0] < seq:
                            best[k] = (seq, v)
                return [(k, v) for k, (_, v) in sorted(best.items())
                        if v is not None]
            except FileNotFoundError as e:
                if opts.snapshot is not None:
                    raise
                err = e
        raise err

    # ------------------------------------------------------------------
    # flush + compaction
    # ------------------------------------------------------------------

    def flush(self):
        """Synchronously persist the active memtable (async mode: rotate it
        and drain the flush queue)."""
        if self._async:
            with self._lock:
                if len(self.mem):
                    self._rotate_locked()
            self._flush_exec.wait_idle()
            return
        with self._lock:
            if len(self.mem) == 0:
                return
            t0 = time.perf_counter()
            with self.tracer.span("flush.sync", **(self._span_args or {})):
                faults.fire("flush.build")
                keys, meta, vals = self._pack_entries(
                    self.mem.sorted_entries())
                img = self.engine.build_image(keys, meta, vals)
                self._install_ssts(img, level=0)
                self.mem = memtable.MemTable()
                self._wal.close()
                for p in self._active_extra_wals + [self._wal_path]:
                    try:
                        os.remove(p)
                    except FileNotFoundError:
                        pass
                self._active_extra_wals = []
                self._wal = wal.WALWriter(self._wal_path,
                                          sync=self._wal_sync)
            self._c["flushes"].inc()
            self._c["flush_host_seconds"].add(time.perf_counter() - t0)
            self._sample_pressure_locked()

    def _install_ssts(self, img: SSTImage, level: int,
                      edit: VersionEdit | None = None) -> list[FileMeta]:
        """Split a (possibly multi-SST) image into files and install.

        File *writes* happen outside the DB lock (only file-number
        allocation and the manifest log take it), so background installs
        do not stall foreground puts/gets.  When ``edit`` is supplied the
        caller logs it (compaction bundles deletions into the same edit).
        """
        img = sstable.trim_image(img)
        nvalid = np.asarray(img.nvalid)
        live_blocks = max(1, int((nvalid > 0).sum()))
        bps = self.geom.blocks_per_sst
        own_edit = edit is None
        edit = edit or VersionEdit()
        metas = []
        for start in range(0, live_blocks, bps):
            stop = min(start + bps, live_blocks)
            sub = SSTImage(
                keys=img.keys[start:stop], meta=img.meta[start:stop],
                vals=img.vals[start:stop], shared=img.shared[start:stop],
                nvalid=img.nvalid[start:stop], crc=img.crc[start:stop],
                bloom=img.bloom[start:stop]
                if img.bloom.shape[0] == img.keys.shape[0] else img.bloom)
            with self._lock:
                no = self.versions.new_file_no()
            path = os.path.join(self.path, f"{no:06d}.sst")
            fm = sstable.write_sst(path, sub, no)
            edit.added.append((level, fm))
            metas.append(fm)
        if own_edit:
            with self._lock:
                self._log_edit(edit)
        return metas

    def _log_edit(self, edit: VersionEdit):
        """Stamp counters and make the edit durable.  Caller holds the
        lock; files named by the edit must already be on disk."""
        edit.last_seq = self.versions.last_seq
        edit.next_file_no = self.versions.next_file_no
        self.versions.log_and_apply(edit)

    def _schedule_compaction(self):
        """Enqueue the background compaction drain (at most one in flight)."""
        if self._compaction_sink is not None:
            self._compaction_sink(self)
            return
        with self._lock:
            if self._compact_scheduled or self._closed:
                return
            self._compact_scheduled = True
        try:
            self._compact_exec.submit(self._background_compact)
        except BaseException:
            with self._lock:
                self._compact_scheduled = False
            raise

    def _background_compact(self):
        try:
            while True:
                with self._lock:
                    job = self.scheduler.pick(self.versions.current)
                    if job is None:
                        self._compact_scheduled = False
                        return
                # transient failures (I/O hiccups, injected soft faults)
                # retry with backoff; hard ones (CRC) propagate untouched
                faults.with_retries(
                    lambda: self.compact_job(job),
                    retries=self.cfg.bg_max_retries,
                    base_s=self.cfg.bg_retry_base_s,
                    on_retry=self._c["bg_retries"].inc)
                if self.cfg.scheduler.paper_faithful:
                    # the paper's artifact (§IV-C): at most one job per
                    # flush -- don't drain the scheduler
                    with self._lock:
                        self._compact_scheduled = False
                    return
        except BaseException as e:
            with self._lock:
                self._compact_scheduled = False
            # same halt-and-resume contract as flushes: the classified
            # error surfaces on wait_idle(); resume() reschedules
            raise self._set_bg_error(e, op="compact")

    def maybe_compact(self):
        if self._compaction_sink is not None or self._async:
            # foreground compaction would race the sink owner / background
            # worker on the same job (double-installing overlapping
            # outputs); route through the single drain instead
            self._schedule_compaction()
            return
        if self.cfg.scheduler.paper_faithful:
            # the paper's prototype artifact (§IV-C): compaction triggers
            # only on a full L0 and pending memtable dumps are not folded
            # into the running job -- at most one job per flush, so L0
            # rebuilds and the next job's key overlap widens (more
            # compaction data, as in Fig. 11)
            self.compact_once()
            return
        guard = 0
        while guard < 16:
            with self._lock:
                job = self.scheduler.pick(self.versions.current)
            if job is None:
                return
            self.compact_job(job)
            guard += 1

    def compact_once(self) -> bool:
        if self._compaction_sink is not None or self._async:
            # side-effect-free pending check (pick() advances the
            # round-robin pointer), then hand off to the drain
            with self._lock:
                v = self.versions.current
                pending = any(
                    self.scheduler.score(v, lvl) >= 1.0
                    for lvl in range(len(v.levels) - 1))
            if pending:
                self._schedule_compaction()
            return pending
        with self._lock:
            job = self.scheduler.pick(self.versions.current)
        if job is None:
            return False
        self.compact_job(job)
        return True

    def _pointer_edit(self, level: int):
        ptr = self.scheduler.compact_pointer.get(level)
        return (level, ptr.hex()) if ptr is not None else None

    def pick_compaction(self) -> CompactionJob | None:
        """Pick the next compaction job (advances the round-robin pointer).
        External coordinators (``GlobalCompactionQueue``) pair this with
        ``apply_trivial_move`` / ``apply_compaction``."""
        with self._lock, \
                self.tracer.span("compact.pick", **(self._span_args or {})):
            return self.scheduler.pick(self.versions.current)

    @staticmethod
    def is_trivial_move(job: CompactionJob) -> bool:
        # single input, nothing overlapping below
        return len(job.inputs_lo) == 1 and not job.inputs_hi and job.level > 0

    def apply_trivial_move(self, job: CompactionJob):
        fm = job.inputs_lo[0]
        with self._lock, \
                self.tracer.span("compact.trivial_move", level=job.level,
                                 **(self._span_args or {})):
            edit = VersionEdit(
                added=[(job.level + 1, fm)],
                deleted=[(job.level, fm.file_no)],
                compact_pointer=self._pointer_edit(job.level))
            self.versions.log_and_apply(edit)
            self._sample_pressure_locked()
        self._c["trivial_moves"].inc()

    def apply_compaction(self, job: CompactionJob, out: SSTImage, es):
        """Install a compaction result computed by the engine: verify the
        per-job CRC verdict, install outputs at ``level+1``, log one edit
        bundling additions + input deletions, drop inputs."""
        if not es.crc_ok:
            # durability: verify inputs BEFORE installing outputs, logging
            # the version edit, or deleting anything -- a corrupt input
            # must leave the store exactly as it was
            raise IOError("compaction input failed CRC verification; "
                          "inputs retained")
        faults.fire("compact.install")
        edit = VersionEdit(
            deleted=[(job.level, f.file_no) for f in job.inputs_lo] +
                    [(job.level + 1, f.file_no) for f in job.inputs_hi],
            compact_pointer=self._pointer_edit(job.level))
        with self.tracer.span("compact.install", level=job.level,
                              **(self._span_args or {})):
            self._install_ssts(out, level=job.level + 1, edit=edit)
            with self._lock:
                self._log_edit(edit)
                for f in job.all_inputs:
                    self.cache.drop(f.file_no)
                self._sample_pressure_locked()
        c = self._c
        c["compactions"].inc()
        c["compact_bytes_in"].inc(es.bytes_in)
        c["compact_bytes_out"].inc(es.bytes_out)
        c["compact_entries_in"].inc(es.n_input)
        c["compact_entries_dropped"].inc(es.n_dropped)
        c["compact_host_seconds"].add(es.host_seconds)
        c["compact_device_seconds"].add(es.device_seconds)
        c["compact_sort_seconds"].add(es.sort_seconds)
        if getattr(es, "batched", False):
            c["batched_compactions"].inc()
        if getattr(es, "fallback", False):
            c["engine_fallbacks"].inc()
        for f in job.all_inputs:
            try:
                os.remove(f.path)
            except FileNotFoundError:
                pass

    def compact_job(self, job: CompactionJob):
        if self.is_trivial_move(job):
            self.apply_trivial_move(job)
            return
        paths = [f.path for f in job.all_inputs]
        with self.tracer.span("compact.job", level=job.level,
                              inputs=len(paths),
                              **(self._span_args or {})):
            out, es = self.engine.compact_paths(
                paths, bottom_level=job.bottom_level)
            self.apply_compaction(job, out, es)

    # ------------------------------------------------------------------

    def wait_idle(self):
        """Barrier: block until every queued flush and compaction has
        completed (async mode).  Re-raises background errors."""
        if not self._async:
            return
        while True:
            self._flush_exec.wait_idle()
            if self._compact_exec is not None:
                self._compact_exec.wait_idle()
            with self._lock:
                if not self.imm and not self._compact_scheduled:
                    return
                if self.imm and self._flush_exec.pending == 0:
                    # a flush died earlier (its error was already raised):
                    # the queued memtable will never drain -- say so
                    # instead of spinning
                    raise IOError(
                        "immutable memtables not draining; an earlier "
                        "background flush failed (data remains readable "
                        "from the queued memtable; call resume() to "
                        "retry the flush)")

    def close(self):
        # claim the close under the lock: concurrent/double close becomes
        # a no-op, and once _closed is set every put()/delete() fails with
        # a clean IOError instead of racing the WAL teardown below (the
        # old unlocked teardown let a late put append to a closed file or
        # land in the memtable with no durability)
        with self._lock:
            if self._closed:
                return
            self._closed = True
        try:
            if self._async:
                self.wait_idle()
        finally:
            if self._async:
                self._flush_exec.shutdown(wait=False)
                if self._compact_exec is not None:
                    self._compact_exec.shutdown(wait=False)
            close_engine = getattr(self.engine, "close", None)
            if close_engine and self._owns_engine:
                close_engine()
            with self._lock:
                self._wal.flush()
                self._wal.close()
                self.versions.close()
            self._unwatch_jit()

    def level_sizes(self):
        with self._lock:
            return [len(files) for files in self.versions.current.levels]


# REPRO_SANITIZE=1 turns the guarded-by annotations above into runtime
# assertions (see repro.analysis.sanitize); free when unset.
from repro.analysis.sanitize import maybe_instrument as _maybe_instrument  # noqa: E402

_maybe_instrument(LsmDB)
