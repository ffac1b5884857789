"""YCSB measurement harness behind the paper's figures (7, 8, 9, 11, 12).

One measured run per (engine, value_size); the contention model expands
each measurement to the paper's {0, 40, 80}% CPU-overhead grid.

``python benchmarks/ycsb_bench.py --engine device --async`` runs the
paper's tail-latency stability comparison: the same workload against a
synchronous store (writes stall on flush + the compaction cascade) and an
asynchronous one (immutable-queue rotation + background flush/compaction),
reporting p50/p99/p99.9 per-op latencies and verifying the two stores
converge to identical contents after ``wait_idle()``.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

# runnable both as `python -m benchmarks.ycsb_bench` and as a script
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.contention import MeasuredRun, simulate
from repro.configs.luda_paper import bench_geometry
from repro.core.scheduler import SchedulerConfig
from repro.data.ycsb import WorkloadSpec, YCSBWorkload
from repro.launch.compile_cache import enable_compile_cache
from repro.lsm.db import DBConfig, LsmDB

ENGINES = {
    # name -> (engine, modeled compaction threads)
    "leveldb-cpu": ("cpu", 1),
    "rocksdb-cpu-4t": ("cpu", 4),
    "luda-tpu": ("device", 1),
}


def measure(engine: str, value_size: int, records: int, operations: int,
            seed: int = 42, warmup: bool = True, sort_mode: str = "merge"
            ) -> tuple[MeasuredRun, dict]:
    if warmup:
        # populate jit caches at the same workload size (device-engine
        # compile time must not count as compaction work -- on the real
        # system kernels are compiled once per geometry at store open)
        measure(engine, value_size, records, operations, seed=seed,
                warmup=False, sort_mode=sort_mode)
    path = tempfile.mkdtemp(prefix=f"bench-{engine}-{value_size}-")
    db = LsmDB(path, DBConfig(
        geom=bench_geometry(value_size), engine=engine,
        sort_mode=sort_mode, memtable_bytes=64 * 1024,
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=512 * 1024)))
    spec = WorkloadSpec.ycsb_a(records=records, operations=operations,
                               value_size=value_size, seed=seed)
    wl = YCSBWorkload(spec)
    try:
        for op, key, val in wl.load_ops():
            db.put(key, val)
        read_lat, write_lat = [], []
        stamps = []
        t_run0 = time.perf_counter()
        for op, key, val in wl.run_ops():
            t0 = time.perf_counter()
            if op == "read":
                db.get(key)
            else:
                db.put(key, val)
            dt_us = (time.perf_counter() - t0) * 1e6
            (read_lat if op == "read" else write_lat).append(dt_us)
            stamps.append((time.perf_counter() - t_run0, op, dt_us))
        t_run = time.perf_counter() - t_run0
        s = db.stats
        fore = t_run - s.compact_host_seconds - s.flush_host_seconds
        run = MeasuredRun(
            n_ops=operations,
            foreground_seconds=max(fore, 1e-9),
            compact_host_seconds=s.compact_host_seconds,
            compact_device_seconds=s.compact_device_seconds,
            flush_host_seconds=s.flush_host_seconds,
            read_latencies_us=read_lat, write_latencies_us=write_lat)
        extras = {
            "compact_bytes_in": s.compact_bytes_in,
            "compact_bytes_out": s.compact_bytes_out,
            "compactions": s.compactions,
            "entries_dropped": s.compact_entries_dropped,
            "compact_sort_seconds": s.compact_sort_seconds,
            "sort_mode": sort_mode if engine == "device" else "cpu",
            "stamps": stamps,
        }
        return run, extras
    finally:
        db.close()
        shutil.rmtree(path)


def sweep(records: int, operations: int, value_sizes=(128, 256, 1024),
          overheads=(0.0, 0.4, 0.8), sort_mode: str = "merge"):
    """Measure every (engine x value); simulate every overhead level.
    Returns rows of dicts."""
    rows = []
    for name, (engine, threads) in ENGINES.items():
        for vs in value_sizes:
            run, extras = measure(engine, vs, records, operations,
                                  sort_mode=sort_mode)
            for o in overheads:
                sim = simulate(run, overhead=o, engine=engine,
                               threads=threads)
                rows.append({
                    "store": name, "value_size": vs, "overhead": o,
                    **sim, **{k: v for k, v in extras.items()
                              if k != "stamps"},
                    "stamps": extras["stamps"] if o == 0.0 else None,
                })
    return rows


def percentiles(lat_us, qs=(50.0, 99.0, 99.9)) -> dict[float, float]:
    """{q: latency_us} with linear interpolation between closest ranks
    (``numpy.percentile`` default semantics).  The previous
    truncating-rank pick collapsed p99 and p99.9 onto the same sample at
    bench-sized n and biased small-sample tails low by up to a full
    sample gap."""
    if not lat_us:
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


def measure_latency(engine: str, *, async_mode: bool, records: int,
                    operations: int, value_size: int = 128, seed: int = 42,
                    flush_workers: int = 2, path: str | None = None,
                    sort_mode: str = "merge", metrics=None, tracer=None,
                    workload: str = "A", distribution: str | None = None
                    ) -> tuple[LsmDB, dict]:
    """Run load + one YCSB workload (A/B/C/D) against one store; record
    every op's latency.

    Returns the still-open DB (drained via ``wait_idle``) plus a report
    with p50/p99/p99.9 split by op type.  Caller closes the DB.

    ``metrics``/``tracer`` (obs registry / tracer) flow into the store;
    the bench also records its own externally-measured op latencies as
    ``ycsb.op.latency_us`` histograms in the same registry, so the
    store-side histogram estimates can be cross-checked against ground
    truth (see ``check_histogram_p99``)."""
    own_path = path is None
    path = path or tempfile.mkdtemp(
        prefix=f"lat-{engine}-{'async' if async_mode else 'sync'}-")
    db = LsmDB(path, DBConfig(
        geom=bench_geometry(value_size), engine=engine,
        sort_mode=sort_mode,
        # small memtable so the default workload sizes actually rotate,
        # flush and compact -- the stalls under comparison
        memtable_bytes=8 * 1024,
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=128 * 1024),
        async_compaction=async_mode, flush_workers=flush_workers,
        metrics=metrics, tracer=tracer))
    h_put = h_get = None
    if metrics is not None:
        h_put = metrics.histogram("ycsb.op.latency_us", op="put",
                                  help="bench-measured op latency (us)")
        h_get = metrics.histogram("ycsb.op.latency_us", op="get")
    kw = dict(records=records, operations=operations,
              value_size=value_size, seed=seed)
    if distribution is not None:
        kw["distribution"] = distribution
    spec = WorkloadSpec.named(workload, **kw)
    wl = YCSBWorkload(spec)
    read_lat, write_lat = [], []
    t_run0 = time.perf_counter()
    try:
        for ops in (wl.load_ops(), wl.run_ops()):
            for op, key, val in ops:
                t0 = time.perf_counter()
                if op == "read":
                    db.get(key)
                else:   # update and (workload D) insert are both puts
                    db.put(key, val)
                dt_us = (time.perf_counter() - t0) * 1e6
                if op == "read":
                    read_lat.append(dt_us)
                    if h_get is not None:
                        h_get.pend(dt_us)
                else:
                    write_lat.append(dt_us)
                    if h_put is not None:
                        h_put.pend(dt_us)
        t_ops = time.perf_counter() - t_run0
        db.wait_idle()
        t_drained = time.perf_counter() - t_run0
    except BaseException:
        try:
            db.close()  # may itself raise after a background failure
        except Exception:
            pass
        if own_path:
            shutil.rmtree(path, ignore_errors=True)
        raise
    report = {
        "engine": engine, "mode": "async" if async_mode else "sync",
        "workload": spec.name, "distribution": spec.distribution,
        "put_percentiles_us": percentiles(write_lat),
        "get_percentiles_us": percentiles(read_lat),
        "ops_per_sec": (len(read_lat) + len(write_lat)) / t_ops,
        "drain_seconds": t_drained - t_ops,
        "write_stalls": db.stats.write_stalls,
        "flushes": db.stats.flushes,
        "compactions": db.stats.compactions,
        "engine_fallbacks": db.stats.engine_fallbacks,
        "path": path, "own_path": own_path, "records": records,
    }
    return db, report


def measure_multi_get(engine: str, *, records: int, operations: int,
                      batch: int, value_size: int = 128, seed: int = 42,
                      workload: str = "C", distribution: str = "zipfian",
                      sort_mode: str = "merge", metrics=None,
                      tracer=None) -> dict:
    """Batched vs scalar read comparison on one store.

    Loads the records, applies the workload's writes, then replays the
    *same deterministic read sequence* twice: once as scalar ``get``
    calls, once as ``multi_get`` batches of ``batch`` keys.  Both passes
    run against a warmed block cache (an untimed warmup pass touches
    every read key first) so the comparison isolates per-op dispatch +
    search cost, not first-touch decode.  Verifies bit-identity between
    the passes; reports per-key p50/p99 for both, per-batch percentiles,
    and the block-cache hit rate as a first-class metric."""
    path = tempfile.mkdtemp(prefix=f"mget-{engine}-{batch}-")
    db = LsmDB(path, DBConfig(
        geom=bench_geometry(value_size), engine=engine,
        sort_mode=sort_mode, memtable_bytes=8 * 1024,
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=128 * 1024),
        metrics=metrics, tracer=tracer))
    spec = WorkloadSpec.named(workload, records=records,
                              operations=operations,
                              value_size=value_size, seed=seed,
                              distribution=distribution)
    wl = YCSBWorkload(spec)
    try:
        for _, key, val in wl.load_ops():
            db.put(key, val)
        read_keys = []
        for op, key, val in wl.run_ops():
            if op == "read":
                read_keys.append(key)
            else:
                db.put(key, val)
        s0 = db.stats
        for key in read_keys:       # untimed warmup: fill the block cache
            db.get(key)
        # one untimed batch warms the batched path's lazy one-time costs
        # (jax platform query, module imports) out of the timed pass
        db.multi_get(read_keys[:batch])
        warm = db.stats
        scalar_lat, scalar_out = [], []
        for key in read_keys:
            t0 = time.perf_counter()
            scalar_out.append(db.get(key))
            scalar_lat.append((time.perf_counter() - t0) * 1e6)
        batch_lat, perkey_lat, batched_out = [], [], []
        for i in range(0, len(read_keys), batch):
            chunk = read_keys[i:i + batch]
            t0 = time.perf_counter()
            batched_out.extend(db.multi_get(chunk))
            dt_us = (time.perf_counter() - t0) * 1e6
            batch_lat.append(dt_us)
            perkey_lat.extend([dt_us / len(chunk)] * len(chunk))
        mismatches = sum(1 for a, b in zip(scalar_out, batched_out)
                         if a != b)
        s = db.stats
        hits = s.block_cache_hits - s0.block_cache_hits
        misses = s.block_cache_misses - s0.block_cache_misses
        hit_rate = hits / max(1, hits + misses)
        sp, bp = percentiles(scalar_lat), percentiles(perkey_lat)
        return {
            "engine": engine, "workload": spec.name,
            "distribution": spec.distribution, "batch": batch,
            "reads": len(read_keys),
            "scalar_percentiles_us": sp,
            "batched_perkey_percentiles_us": bp,
            "batch_percentiles_us": percentiles(batch_lat),
            "p99_speedup": sp[99.0] / max(bp[99.0], 1e-9),
            "block_cache_hit_rate": hit_rate,
            "block_cache_hits": hits, "block_cache_misses": misses,
            "warmup_misses": (warm.block_cache_misses -
                              s0.block_cache_misses),
            "bloom_negative_skips": (s.bloom_negative_skips -
                                     s0.bloom_negative_skips),
            "multi_gets": s.multi_gets, "mismatches": mismatches,
        }
    finally:
        db.close()
        shutil.rmtree(path, ignore_errors=True)


def _print_multi_get(rep):
    sp = rep["scalar_percentiles_us"]
    bp = rep["batched_perkey_percentiles_us"]
    bt = rep["batch_percentiles_us"]
    print(f"engine={rep['engine']} workload={rep['workload']} "
          f"dist={rep['distribution']} reads={rep['reads']} "
          f"batch={rep['batch']}")
    print(f"  scalar get    p50/p99 = {sp[50.0]:.1f}/{sp[99.0]:.1f}us "
          "per key")
    print(f"  multi_get     p50/p99 = {bp[50.0]:.1f}/{bp[99.0]:.1f}us "
          f"per key ({bt[50.0]:.1f}/{bt[99.0]:.1f}us per batch)")
    print(f"  p99 speedup  {rep['p99_speedup']:.2f}x  "
          f"block-cache hit rate {rep['block_cache_hit_rate']:.1%} "
          f"({rep['block_cache_hits']} hits / "
          f"{rep['block_cache_misses']} misses)  "
          f"bloom skips {rep['bloom_negative_skips']}")
    print(f"  scalar vs batched bit-identity over {rep['reads']} reads: "
          f"{'OK' if rep['mismatches'] == 0 else str(rep['mismatches']) + ' MISMATCHES'}")


def measure_sharded(engine: str, *, shards: int, records: int,
                    operations: int, value_size: int = 128, seed: int = 42,
                    async_mode: bool = False, sort_mode: str = "merge",
                    metrics=None, tracer=None) -> dict:
    """Multi-tenant mode: one ``ShardedDB`` with a learned boundary table,
    per-op latencies tagged by owning shard.

    Reports aggregate p50/p99 + per-shard p99 (tail fairness across
    tenants) and the batched-compaction counters -- the cross-shard
    ``compact_many`` coalescing is the thing under measurement."""
    from repro.data.ycsb import key_of
    from repro.lsm.sharded import ShardedDB
    path = tempfile.mkdtemp(prefix=f"shard-{engine}-{shards}-")
    # YCSB keys live in a thin slice of byte space: learn the boundary
    # table from a uniform sample of the key population
    sample = [key_of(i) for i in range(0, records,
                                       max(1, records // 1024))]
    # small per-shard memtable + quotas so the default workload sizes
    # rotate, flush and compact in every shard -- cross-shard rounds
    # with >=2 same-shape jobs then coalesce into stacked launches,
    # which is the thing under measurement (and under tracing)
    db = ShardedDB(path, DBConfig(
        geom=bench_geometry(value_size), engine=engine,
        sort_mode=sort_mode, memtable_bytes=2 * 1024,
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=32 * 1024),
        async_compaction=async_mode, metrics=metrics, tracer=tracer),
        shards=shards, sample_keys=sample)
    h_put = h_get = None
    if metrics is not None:
        h_put = metrics.histogram("ycsb.op.latency_us", op="put",
                                  help="bench-measured op latency (us)")
        h_get = metrics.histogram("ycsb.op.latency_us", op="get")
    spec = WorkloadSpec.ycsb_a(records=records, operations=operations,
                               value_size=value_size, seed=seed)
    wl = YCSBWorkload(spec)
    shard_lat: list[list[float]] = [[] for _ in range(db.n_shards)]
    all_lat: list[float] = []
    t0_run = time.perf_counter()
    try:
        for ops in (wl.load_ops(), wl.run_ops()):
            for op, key, val in ops:
                t0 = time.perf_counter()
                if op == "read":
                    db.get(key)
                else:
                    db.put(key, val)
                dt_us = (time.perf_counter() - t0) * 1e6
                shard_lat[db.shard_of(key)].append(dt_us)
                all_lat.append(dt_us)
                h = h_get if op == "read" else h_put
                if h is not None:
                    h.pend(dt_us)
        t_ops = time.perf_counter() - t0_run
        db.flush()
        db.maybe_compact()
        db.wait_idle()
        s = db.stats
        eng = db.engine
        report = {
            "engine": engine, "shards": db.n_shards,
            "mode": "async" if async_mode else "sync",
            "ops_per_sec": len(all_lat) / t_ops,
            "aggregate_percentiles_us": percentiles(all_lat),
            "per_shard_p99_us": [percentiles(lat)[99.0]
                                 for lat in shard_lat],
            "per_shard_p999_us": [percentiles(lat)[99.9]
                                  for lat in shard_lat],
            "per_shard_ops": [len(lat) for lat in shard_lat],
            "write_stalls": s.write_stalls,
            "flushes": s.flushes, "compactions": s.compactions,
            "batched_compactions": s.batched_compactions,
            "batch_launches": getattr(eng, "batch_launches", 0),
            "batch_jobs": getattr(eng, "batch_jobs", 0),
            "max_batch_jobs": getattr(eng, "max_batch_jobs", 0),
            # a clean-path run must never silently degrade to the CPU
            # fallback engine -- CI asserts this stays 0 (docs/robustness.md)
            "engine_fallbacks": s.engine_fallbacks,
        }
    except BaseException:
        try:
            db.close()   # may re-raise after a background failure --
        except Exception:   # don't mask the original traceback
            pass
        shutil.rmtree(path, ignore_errors=True)
        raise
    # success path: a close() failure (late background error) must
    # surface, but the temp dir dies either way
    try:
        db.close()
    finally:
        shutil.rmtree(path, ignore_errors=True)
    return report


def _print_sharded(rep):
    agg = rep["aggregate_percentiles_us"]
    print(f"engine={rep['engine']} shards={rep['shards']} "
          f"mode={rep['mode']}  {rep['ops_per_sec']:.0f} ops/s  "
          f"aggregate p50/p99/p99.9 = {agg[50.0]:.1f}/{agg[99.0]:.1f}/"
          f"{agg[99.9]:.1f}us")
    for i, (p99, p999, n) in enumerate(zip(rep["per_shard_p99_us"],
                                           rep["per_shard_p999_us"],
                                           rep["per_shard_ops"])):
        print(f"  shard {i}: {n:>7d} ops  p99 {p99:>10.1f}us  "
              f"p99.9 {p999:>10.1f}us")
    print(f"  write_stalls={rep['write_stalls']} "
          f"compactions={rep['compactions']} "
          f"batched={rep['batched_compactions']} "
          f"launches={rep['batch_launches']} "
          f"(jobs={rep['batch_jobs']}, max/launch="
          f"{rep['max_batch_jobs']})  "
          f"engine_fallbacks={rep['engine_fallbacks']}")


def measure_chaos(engine: str, *, inject: str, records: int,
                  operations: int, value_size: int = 128, seed: int = 42,
                  sort_mode: str = "merge", metrics=None, tracer=None,
                  max_op_attempts: int = 8) -> dict:
    """Chaos mode: the YCSB-A workload with probabilistic faults armed.

    ``inject`` is ``name:rate[,name:rate...]`` -- each named failpoint
    fires a *transient* fault with the given probability (``raise:pRATE``
    in the spec grammar), so the run exercises the whole self-healing
    stack: in-line retry/backoff, bg_error halts, ``resume()``, and the
    device->CPU engine fallback.  Ops that hit a halted store call
    ``resume()`` and retry; the full wall-clock of every logical op
    (retries included) lands in its latency sample, so the reported
    ``put p99`` is the paper-honest tail *under faults*.

    After the workload the failpoints are disarmed and the report's
    ``recovery_seconds`` measures time-to-green: how long
    ``resume()`` + drain takes until the store is healthy
    (``bg_error`` clear, pipeline idle).  See docs/robustness.md."""
    from repro.lsm import faults
    specs = {}
    for part in inject.split(","):
        name, _, rate = part.partition(":")
        name = name.strip()
        if name not in faults.KNOWN_POINTS:
            raise ValueError(
                f"unknown failpoint {name!r} "
                f"(one of {sorted(faults.KNOWN_POINTS)})")
        specs[name] = f"raise:p{float(rate) if rate else 1.0:g}"
    path = tempfile.mkdtemp(prefix=f"chaos-{engine}-")
    # async mode: background failures land as classified bg_error (the
    # halt/resume contract under test) instead of foreground raises
    db = LsmDB(path, DBConfig(
        geom=bench_geometry(value_size), engine=engine,
        sort_mode=sort_mode, memtable_bytes=8 * 1024,
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=128 * 1024),
        async_compaction=True, failpoints=specs,
        bg_retry_base_s=1e-4, metrics=metrics, tracer=tracer))
    spec = WorkloadSpec.ycsb_a(records=records, operations=operations,
                               value_size=value_size, seed=seed)
    wl = YCSBWorkload(spec)
    read_lat, write_lat = [], []
    resumes = halted_ops = 0

    def apply(op, key, val):
        # a halted store surfaces BackgroundError/IOError; resume and
        # retry -- the op's latency sample covers the whole recovery
        nonlocal resumes, halted_ops
        for _ in range(max_op_attempts):
            try:
                if op == "read":
                    db.get(key)
                else:
                    db.put(key, val)
                return
            except (faults.SimulatedCrash, KeyboardInterrupt):
                raise
            except Exception:
                halted_ops += 1
                if db.resume():
                    resumes += 1
        raise RuntimeError(
            f"store did not recover after {max_op_attempts} attempts")

    t0_run = time.perf_counter()
    try:
        for ops in (wl.load_ops(), wl.run_ops()):
            for op, key, val in ops:
                t0 = time.perf_counter()
                apply(op, key, val)
                dt_us = (time.perf_counter() - t0) * 1e6
                (read_lat if op == "read" else write_lat).append(dt_us)
        t_ops = time.perf_counter() - t0_run
        fired = {n: faults.FAILPOINTS.fired(n) for n in specs}
        # recovery-time-to-green: disarm, then resume + drain until the
        # pipeline is idle and healthy
        faults.FAILPOINTS.clear()
        t_rec0 = time.perf_counter()
        green = False
        for _ in range(64):
            db.resume()
            try:
                db.flush()
                db.wait_idle()
            except Exception:
                continue
            if db._bg_error is None:
                green = True
                break
        recovery_s = time.perf_counter() - t_rec0
        s = db.stats
        eng = db.engine
        return {
            "engine": engine, "mode": "chaos", "inject": specs,
            "fired": fired,
            "put_percentiles_us": percentiles(write_lat),
            "get_percentiles_us": percentiles(read_lat),
            "ops_per_sec": (len(read_lat) + len(write_lat)) / t_ops,
            "halted_ops": halted_ops, "resumes": resumes,
            "bg_retries": s.bg_retries, "bg_resumes": s.bg_resumes,
            "engine_fallbacks": s.engine_fallbacks,
            "launch_retries": getattr(eng, "launch_retries", 0),
            "recovery_seconds": recovery_s, "green": green,
        }
    finally:
        faults.FAILPOINTS.clear()
        try:
            db.close()
        except Exception:
            pass
        shutil.rmtree(path, ignore_errors=True)


def _print_chaos(rep):
    p, g = rep["put_percentiles_us"], rep["get_percentiles_us"]
    fired = ", ".join(f"{n} x{c}" for n, c in rep["fired"].items())
    print(f"engine={rep['engine']} mode=chaos "
          f"inject={rep['inject']}  fired: {fired}")
    print(f"  put p50/p99/p99.9 under faults = {p[50.0]:.1f}/{p[99.0]:.1f}/"
          f"{p[99.9]:.1f}us  get p50/p99 = {g[50.0]:.1f}/{g[99.0]:.1f}us  "
          f"{rep['ops_per_sec']:.0f} ops/s")
    print(f"  halted_ops={rep['halted_ops']} resumes={rep['resumes']} "
          f"bg_retries={rep['bg_retries']} "
          f"fallbacks={rep['engine_fallbacks']} "
          f"launch_retries={rep['launch_retries']}")
    print(f"  recovery-time-to-green: {rep['recovery_seconds'] * 1e3:.1f}ms "
          f"({'GREEN' if rep['green'] else 'STILL RED'})")


def _fmt_row(rep):
    p, g = rep["put_percentiles_us"], rep["get_percentiles_us"]
    return (f"{rep['mode']:<6} {p[50.0]:>10.1f} {p[99.0]:>10.1f} "
            f"{p[99.9]:>10.1f} {g[50.0]:>10.1f} {g[99.0]:>10.1f} "
            f"{rep['ops_per_sec']:>10.0f} {rep['flushes']:>5d} "
            f"{rep['compactions']:>5d} {rep['write_stalls']:>6d}")


def compare_sync_async(engine: str, *, records: int, operations: int,
                       value_size: int = 128, seed: int = 42,
                       warmup: bool = True, sort_mode: str = "merge",
                       metrics=None, tracer=None) -> dict:
    """The paper's Fig.-12-style stability comparison: identical workload,
    sync vs async write path.  Verifies post-drain get() equivalence."""
    from repro.data.ycsb import key_of
    if warmup:
        # populate process-level jit caches so device-engine compile time
        # (paid once per geometry at store open on the real system) does
        # not pollute either mode's tail
        db, _ = measure_latency(engine, async_mode=False, records=records,
                                operations=operations,
                                value_size=value_size, seed=seed,
                                sort_mode=sort_mode)
        db.close()
        shutil.rmtree(_["path"], ignore_errors=True)
    db_s, rep_s = measure_latency(engine, async_mode=False, records=records,
                                  operations=operations,
                                  value_size=value_size, seed=seed,
                                  sort_mode=sort_mode, metrics=metrics,
                                  tracer=tracer)
    try:
        db_a, rep_a = measure_latency(engine, async_mode=True,
                                      records=records,
                                      operations=operations,
                                      value_size=value_size, seed=seed,
                                      sort_mode=sort_mode, metrics=metrics,
                                      tracer=tracer)
    except BaseException:
        try:
            db_s.close()
        except Exception:
            pass
        if rep_s["own_path"]:
            shutil.rmtree(rep_s["path"], ignore_errors=True)
        raise
    try:
        mismatches = sum(
            1 for i in range(records)
            if db_s.get(key_of(i)) != db_a.get(key_of(i)))
    finally:
        for db, rep in ((db_s, rep_s), (db_a, rep_a)):
            db.close()
            if rep["own_path"]:
                shutil.rmtree(rep["path"], ignore_errors=True)
    p99_s = rep_s["put_percentiles_us"][99.0]
    p99_a = rep_a["put_percentiles_us"][99.0]
    header = (f"{'mode':<6} {'p50 put':>10} {'p99 put':>10} "
              f"{'p99.9 put':>10} {'p50 get':>10} {'p99 get':>10} "
              f"{'ops/s':>10} {'flush':>5} {'comps':>5} {'stalls':>6}")
    print(f"engine={engine} records={records} operations={operations} "
          f"value_size={value_size} (latencies in us)")
    print(header)
    print(_fmt_row(rep_s))
    print(_fmt_row(rep_a))
    print(f"async p99 put {p99_a:.1f}us < sync p99 put {p99_s:.1f}us: "
          f"{p99_a < p99_s}")
    print(f"post-drain get() equivalence over {records} keys: "
          f"{'OK' if mismatches == 0 else f'{mismatches} MISMATCHES'}")
    return {"sync": rep_s, "async": rep_a, "mismatches": mismatches,
            "p99_improved": p99_a < p99_s}


def check_histogram_p99(metrics, exact_p99_us: float, op: str | None
                        ) -> tuple[float, float, bool]:
    """Cross-check the registry's ``ycsb.op.latency_us`` histogram p99
    estimate against the exact bench-computed p99.  ``op=None`` merges
    every op's series (vs an all-ops exact percentile) -- exercising the
    bucket-wise merge the per-shard aggregation relies on.

    Returns ``(estimate, exact, ok)``.  The histogram reports geometric
    bucket midpoints from 2**(1/4)-wide buckets, so a correct estimate
    sits within half a bucket of the sample plus at most one bucket of
    rank error: tolerance factor ``2**0.5``."""
    from repro.obs import merge_histograms
    if op is None:
        h = merge_histograms(metrics.find("ycsb.op.latency_us"))
    else:
        h = metrics.find("ycsb.op.latency_us", op=op)
    if h is None or h.snapshot()[1] == 0:
        return 0.0, exact_p99_us, False
    est = h.percentile(99.0)
    tol = 2.0 ** 0.5
    ok = (exact_p99_us / tol <= est <= exact_p99_us * tol
          if exact_p99_us > 0 else True)
    return est, exact_p99_us, ok


def _make_obs(args):
    """(metrics, tracer) when any obs export flag is set, else Nones."""
    if not (args.trace_out or args.metrics_out or args.prom_out):
        return None, None
    from repro.obs import MetricsRegistry, Tracer
    return MetricsRegistry(), Tracer()


def _export_obs(args, metrics, tracer, exact_p99_us=None, op=None) -> bool:
    """Write the requested artifacts; cross-check the histogram p99
    against the bench-exact value when available.  Returns ok."""
    ok = True
    if metrics is None:
        return ok
    from repro.obs import write_metrics, write_prometheus
    if args.trace_out:
        tracer.export(args.trace_out)
        print(f"trace written to {args.trace_out} "
              f"({len(tracer)} events)")
    if args.metrics_out:
        write_metrics(metrics, args.metrics_out)
        print(f"metrics JSON written to {args.metrics_out}")
    if args.prom_out:
        write_prometheus(metrics, args.prom_out)
        print(f"Prometheus text written to {args.prom_out}")
    if exact_p99_us is not None:
        est, exact, ok = check_histogram_p99(metrics, exact_p99_us, op)
        print(f"histogram p99 cross-check ({op or 'all ops'}): estimate "
              f"{est:.1f}us vs exact {exact:.1f}us within one bucket: {ok}")
    return ok


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", default="device", choices=["device", "cpu"])
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="compare sync vs async write path")
    ap.add_argument("--sort-mode", default="merge",
                    choices=["merge", "device", "xla", "cooperative"],
                    help="device-engine phase-2 mode (run-aware merge "
                         "path vs full re-sorts)")
    ap.add_argument("--shards", type=int, default=0,
                    help="multi-tenant mode: run a ShardedDB with N "
                         "range shards sharing one batching compaction "
                         "backend; reports aggregate + per-shard p99")
    ap.add_argument("--workload", default="A",
                    choices=["A", "B", "C", "D"],
                    help="YCSB workload mix: A=50/50 update/read, "
                         "B=95/5, C=read-only, D=read-latest+insert")
    ap.add_argument("--multi-get", type=int, default=0, metavar="K",
                    help="batched-read mode: replay the workload's reads "
                         "as multi_get batches of K keys and report "
                         "batched vs scalar get p50/p99 + block-cache "
                         "hit rate")
    ap.add_argument("--distribution", default=None,
                    choices=["zipfian", "uniform", "latest"],
                    help="request distribution (default: the workload's "
                         "own -- zipfian for A/B/C, latest for D)")
    ap.add_argument("--zipfian", action="store_true",
                    help="shorthand for --distribution zipfian")
    ap.add_argument("--inject", default=None, metavar="NAME:RATE",
                    help="chaos mode: arm failpoints (comma-separated "
                         "name:rate, e.g. flush.build:0.25) and report "
                         "put p99 under faults + recovery-time-to-green "
                         "(docs/robustness.md)")
    ap.add_argument("--records", type=int, default=400)
    ap.add_argument("--operations", type=int, default=800)
    ap.add_argument("--value-size", type=int, default=128)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "run (load chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry snapshot as JSON")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write the metrics registry in Prometheus text "
                         "exposition format")
    args = ap.parse_args(argv)
    if args.zipfian:
        args.distribution = "zipfian"
    metrics, tracer = _make_obs(args)
    if args.inject:
        rep = measure_chaos(
            args.engine, inject=args.inject, records=args.records,
            operations=args.operations, value_size=args.value_size,
            seed=args.seed, sort_mode=args.sort_mode, metrics=metrics,
            tracer=tracer)
        _print_chaos(rep)
        _export_obs(args, metrics, tracer)
        return 0 if rep["green"] else 1
    if args.multi_get > 0:
        rep = measure_multi_get(
            args.engine, records=args.records, operations=args.operations,
            batch=args.multi_get, value_size=args.value_size,
            seed=args.seed, workload=args.workload,
            distribution=args.distribution or "zipfian",
            sort_mode=args.sort_mode, metrics=metrics, tracer=tracer)
        _print_multi_get(rep)
        _export_obs(args, metrics, tracer)
        return 0 if rep["mismatches"] == 0 else 1
    if args.shards > 0:
        rep = measure_sharded(
            args.engine, shards=args.shards, records=args.records,
            operations=args.operations, value_size=args.value_size,
            seed=args.seed, async_mode=args.async_mode,
            sort_mode=args.sort_mode, metrics=metrics, tracer=tracer)
        _print_sharded(rep)
        ok = _export_obs(args, metrics, tracer,
                         rep["aggregate_percentiles_us"][99.0], op=None)
        return 0 if ok else 1
    if args.async_mode:
        if metrics is not None:
            print("note: --trace-out/--metrics-out/--prom-out merge both "
                  "modes of the sync/async comparison into one export")
        res = compare_sync_async(
            args.engine, records=args.records, operations=args.operations,
            value_size=args.value_size, seed=args.seed,
            warmup=not args.no_warmup, sort_mode=args.sort_mode,
            metrics=metrics, tracer=tracer)
        _export_obs(args, metrics, tracer)
        return 0 if (res["mismatches"] == 0 and res["p99_improved"]) else 1
    db, rep = measure_latency(
        args.engine, async_mode=False, records=args.records,
        operations=args.operations, value_size=args.value_size,
        seed=args.seed, sort_mode=args.sort_mode, metrics=metrics,
        tracer=tracer, workload=args.workload,
        distribution=args.distribution)
    db.close()
    shutil.rmtree(rep["path"], ignore_errors=True)
    p, g = rep["put_percentiles_us"], rep["get_percentiles_us"]
    print(f"engine={args.engine} mode=sync sort={args.sort_mode} "
          f"workload={rep['workload']} dist={rep['distribution']} "
          f"put p50/p99/p99.9 = {p[50.0]:.1f}/{p[99.0]:.1f}/"
          f"{p[99.9]:.1f}us  get p50/p99 = {g[50.0]:.1f}/{g[99.0]:.1f}us  "
          f"{rep['ops_per_sec']:.0f} ops/s")
    ok = _export_obs(args, metrics, tracer, p[99.0], op="put")
    return 0 if ok else 1


def p99_timeline(stamps, n_windows: int = 20):
    """[(t_mid, p99_us)] over the run (paper Fig. 12)."""
    if not stamps:
        return []
    t_end = stamps[-1][0]
    out = []
    for w in range(n_windows):
        lo, hi = w * t_end / n_windows, (w + 1) * t_end / n_windows
        lat = [dt for t, _, dt in stamps if lo <= t < hi]
        if lat:
            out.append((0.5 * (lo + hi), percentiles(lat, (99.0,))[99.0]))
    return out


if __name__ == "__main__":
    sys.exit(main())
