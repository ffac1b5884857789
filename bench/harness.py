"""One run of one cell: set-up, the measured window, the checks, the line.

Protocol (see ``PERF.md`` for why):

1. Set-up (``setup_s``, from process start to the window): find a TPU,
   copy the preload (built once per checkout), open one ``LsmDB``
   (device engine, background flush and compaction), warm up with the
   cell's warm-up traffic from a fixed stream, and drain.
2. Window: one closed-loop client thread drives the store for
   ``--seconds``, or, where the mix gives ``memtables_per_second``, for
   that many memtables' worth of calls per second of ``--seconds`` (a
   closed-loop writer outruns compaction many times over, so a window of
   fixed time would leave debt without bound); each call is timed on the
   client side.  Cells with writes then wait for the store to go idle,
   and that drain belongs to the window.
3. Checks, after the window: every answer read in the window (or a
   seeded sample of the ``multi_get`` calls) against the plain dict
   reference replayed from the same seed; then a seeded sample of
   written, preloaded and absent keys by ``get`` and ``multi_get``,
   before and after ``close()`` and reopen.  Any wrong answer, any
   compaction that left the device, any kernel interpreted makes the run
   not correct.
4. The last line of standard output is the result; the last lines of
   standard error are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import contextlib
import gc
import gzip
import json
import os
import shutil
import sys
import time

import numpy as np

import devtrace
import faults as faults_mod
import gen
import reference
import spec
import store
import ycsb
from peaks import peaks

#: warm-up traffic is the same in every run, apart from the measured seed
WARMUP_SEED = 0x5EED
#: ``multi_get`` calls whose answers the check keeps: one in this many
MGET_KEEP_EVERY = 4


def log(msg: str):
    print(msg, flush=True)


class CompileCounter:
    """Counts JAX traces, compiles and compile-cache hits while armed."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0}

        def on_duration(event, duration, **kw):
            if self.armed and event in self.EVENTS:
                self.counts[self.EVENTS[event]] += 1

        def on_event(event, **kw):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.counts["cache_hits"] += 1
        self._listeners = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def close(self):
        import jax.monitoring as mon
        on_duration, on_event = self._listeners
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)


def engine_counters(db) -> dict:
    s, e = db.stats, db.engine
    return {"flushes": s.flushes, "compactions": s.compactions,
            "trivial_moves": s.trivial_moves,
            "write_stalls": s.write_stalls,
            "compact_bytes_in": s.compact_bytes_in,
            "compact_bytes_out": s.compact_bytes_out,
            "block_cache_hits": s.block_cache_hits,
            "block_cache_misses": s.block_cache_misses,
            "jit_bucket_misses": getattr(e, "jit_bucket_misses", 0),
            "fallbacks": getattr(e, "fallbacks", 0),
            "launch_retries": getattr(e, "launch_retries", 0),
            "engine_fallbacks": s.engine_fallbacks}


def left_device(c: dict) -> int:
    return c["fallbacks"] + c["launch_retries"] + c["engine_fallbacks"]


@contextlib.contextmanager
def counting_l0_jobs(db):
    """Counts L0->L1 compactions the store runs (warm-up only)."""
    n = [0]
    orig = db.compact_job

    def job(j):
        orig(j)
        if j.level == 0:
            n[0] += 1
    db.compact_job = job
    try:
        yield n
    finally:
        del db.compact_job


def warm_up(db, cell: spec.Cell, cfg: dict, ref: reference.Reference):
    """The cell's warm-up traffic, from a fixed stream.  Writes go to the
    reference too.  With ``until_l0_compactions`` the store is filled one
    memtable at a time and left to go idle after each, so the flushes and
    compactions of the warm-up, and the programs they compile, are the
    same in every run."""
    w = cell.traffic["warmup"]
    mix = dict(cell.traffic, **w.get("traffic", {}))
    stream = gen.OpStream(mix, records=cfg["records"],
                          value_size=cfg["value_size"], seed=WARMUP_SEED,
                          stream=1, warmup=True)
    # every power-of-two batch up to the call's size, so each shape the
    # batched read path pads to is compiled before the window
    for size in w.get("batch_sweep", []):
        _, keys, _ = stream.next_chunk(1)
        flat = keys[0] if isinstance(keys[0], list) else keys
        for b in (1 << i for i in range(size.bit_length())):
            db.multi_get((flat * b)[:b])
    until = w.get("until_l0_compactions", 0)
    # operations that fill one memtable (when they all write new keys)
    step = memtable_ops(cfg) if until else w["max_ops"]
    done = 0
    with counting_l0_jobs(db) as l0_jobs:
        while done < w["max_ops"] and (not until or l0_jobs[0] < until):
            left = min(step, w["max_ops"] - done)
            while left:
                kinds, keys, values = stream.next_chunk(
                    min(stream.chunk, left))
                for kind, key, value in zip(kinds, keys, values):
                    if kind == gen.PUT:
                        db.put(key, value)
                        ref.put(key, value)
                    elif kind == gen.GET:
                        db.get(key)
                    else:
                        db.multi_get(key)
                left -= len(kinds)
                done += len(kinds)
            db.wait_idle()
    return done, l0_jobs[0]


def drive(db, stream: gen.OpStream, seconds: float, keep_every: int,
          keep_phase: int, max_ops: int | None = None):
    """The closed loop: one call at a time for ``seconds``, or for
    ``max_ops`` calls where that is given.  Returns the latencies (ns) by
    call, the answers kept, and the counts."""
    put, get, mget = db.put, db.get, db.multi_get
    clock = time.perf_counter_ns
    lat = {gen.PUT: [], gen.GET: [], gen.MULTI_GET: []}
    got, kept = [], []
    n = keys_read = failed = 0
    t0 = now = clock()
    deadline = t0 + int(seconds * 1e9) if max_ops is None else None
    while (n < max_ops) if deadline is None else (now < deadline):
        kinds, keys, values = stream.next_chunk(
            None if max_ops is None else min(stream.chunk, max_ops - n))
        for kind, key, value in zip(kinds, keys, values):
            a = clock()
            try:
                if kind == gen.PUT:
                    put(key, value)
                elif kind == gen.GET:
                    got.append(get(key))
                else:
                    r = mget(key)
                    keys_read += len(r)
                    if n % keep_every == keep_phase:
                        kept.append((n, r))
            except Exception as e:  # a failed call counts, and the run goes on
                failed += 1
                if failed == 1:
                    log(f"window: call {n} failed: {e!r}")
                if kind == gen.GET:
                    got.append(e)
            now = clock()
            lat[kind].append(now - a)
            n += 1
            if deadline is not None and now >= deadline:
                break
    return {"t0": t0, "t1": now, "n": n, "keys_read": keys_read,
            "failed": failed, "lat": lat, "got": got, "kept": kept}


def replay(stream: gen.OpStream, n: int, ref: reference.Reference,
           got, kept) -> tuple[int, int, list[bytes]]:
    """Replay the window's ``n`` operations into the reference and compare
    each answer read.  Returns (mismatches, answers compared, keys
    written)."""
    kept = dict(kept)
    got = iter(got)
    bad = compared = i = 0
    written = []
    while i < n:
        kinds, keys, values = stream.next_chunk()
        for kind, key, value in zip(kinds, keys, values):
            if i >= n:
                break
            if kind == gen.PUT:
                ref.put(key, value)
                written.append(key)
            elif kind == gen.GET:
                bad += next(got) != ref.get(key)
                compared += 1
            elif i in kept:
                bad += reference.count_mismatches(kept[i], key, ref)
                compared += len(key)
            i += 1
    return bad, compared, written


def read_back(db, keys, ref: reference.Reference, batch: int) -> int:
    bad = sum(db.get(k) != ref.get(k) for k in keys)
    for j in range(0, len(keys), batch):
        part = keys[j:j + batch]
        bad += reference.count_mismatches(db.multi_get(part), part, ref)
    return bad


def check_sample(cell: spec.Cell, cfg: dict, written, seed: int):
    """Seeded sample of keys written in the window, preloaded keys and
    keys never written."""
    c = cell.traffic["check"]
    rng = np.random.default_rng(gen.seed_state(seed, 3))
    keys = []
    if written:
        # the newest writes are the ones still in the memtable and the log
        keys += written[-c["newest"]:]
        keys += [written[int(i)] for i in
                 rng.integers(0, len(written), c["written"])]
    keys += [ycsb.key_of(int(i)) for i in
             rng.integers(0, cfg["records"], c["preload"])]
    keys += [ycsb.key_of(cfg["records"] + (1 << 46) + j)
             for j in range(c["absent"])]
    return [keys[int(i)] for i in rng.permutation(len(keys))]


def memtable_ops(cfg: dict) -> int:
    """Writes of new keys that fill one memtable."""
    return cfg["memtable_bytes"] // (cfg["key_bytes"] + cfg["value_size"]) + 1


def fixed_work(traffic: dict, cfg: dict, seconds: float) -> int | None:
    """Calls in a window of fixed work (``memtables_per_second`` of the
    mix times ``seconds``), or None for a window of fixed time."""
    rate = traffic.get("memtables_per_second")
    if rate is None:
        return None
    return max(1, int(round(rate * seconds * memtable_ops(cfg))))


def check_writes(cfg: dict, seed: int, n: int):
    """``n`` new records the check writes after the window."""
    base = cfg["records"] + (1 << 45) + (seed % (1 << 16)) * (1 << 20)
    return [(ycsb.key_of(base + j), ycsb.value_of(base + j,
                                                  cfg["value_size"]))
            for j in range(n)]


def pct(lat_ns) -> dict:
    p = ycsb.percentiles([x / 1000.0 for x in lat_ns])
    return {"p50": p[50.0], "p99": p[99.0], "p99.9": p[99.9],
            "n": len(lat_ns)}


def layer_spans(tracer) -> tuple[list, float, float]:
    """The store's spans as ``(name, start_s, dur_s)`` relative to the
    window's start mark, and the marks' relative positions (seconds)."""
    events = tracer.to_chrome()["traceEvents"]
    marks = {e["name"]: e["ts"] / 1e6 for e in events if e["ph"] == "i"}
    w0, w1 = marks["bench.window.start"], marks["bench.window.end"]
    spans = [(e["name"], e["ts"] / 1e6 - w0, e["dur"] / 1e6)
             for e in events if e["ph"] == "X"]
    return spans, w0, w1


class RunData:
    """What a per-layer metric reader gets."""

    def __init__(self, cell, window_s, spans, counters, device, chip):
        self.cell = cell              # cell name
        self.window_s = window_s      # the traced window (load + drain)
        self.spans = spans            # [(name, start_s, dur_s)] in window
        self.counters = counters      # store counters: window deltas
        self.device = device          # devtrace.reduce() + the raw trace
        self.peaks = chip             # peaks.DevicePeaks of the device

    def span_seconds(self, name: str) -> list[float]:
        return [d for n, s, d in self.spans
                if n == name and s >= 0 and s + d <= self.window_s]


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, faults=(),
             work_root: str | None = None):
    """Run one cell.  Returns the result object, or None where the run
    cannot be made (no chip)."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if require_tpu:
        if dev.platform != "tpu":
            print("bench: JAX found no TPU", file=sys.stderr)
            return None
        if len(devs) < cell.chips:
            print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX "
                  f"found {len(devs)}", file=sys.stderr)
            return None
        chip = peaks(dev.device_kind)
    else:
        chip = peaks("TPU v5 lite")
    from repro.kernels import common
    from repro.lsm.db import LsmDB
    from repro.obs import Tracer

    cfg = cell.config
    traffic = cell.traffic
    counter = CompileCounter()
    with faults_mod.planted(faults):
        path = store.fresh_copy(cell.config_name, cell.config_path, cfg,
                                root=work_root, log=log)
        tracer = Tracer(maxlen=8_000_000) if trace else None
        dbcfg = store.db_config(cfg, async_mode=True, tracer=tracer)
        db = LsmDB(path, dbcfg)
        ref = reference.Reference(cfg["records"], cfg["value_size"])
        try:
            t_w = time.perf_counter()
            warm_ops, warm_l0 = warm_up(db, cell, cfg, ref)
            log(f"warm-up: {warm_ops} ops, {warm_l0} L0->L1 compactions, "
                f"{time.perf_counter() - t_w:.3f} s")
            c0 = engine_counters(db)
            stream = gen.OpStream(traffic, records=cfg["records"],
                                  value_size=cfg["value_size"], seed=seed,
                                  stream=0)
            gc.collect()
            gc.freeze()
            setup_s = time.perf_counter() - t_start
            prof_dir = None
            if trace:
                prof_dir = os.path.join(store.work_dir(work_root), "trace")
                shutil.rmtree(prof_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(prof_dir, profiler_options=opts)
                tracer.instant("bench.window.start")
            counter.armed = True
            with jax.profiler.TraceAnnotation("bench.window"):
                w = drive(db, stream, seconds,
                          MGET_KEEP_EVERY, seed % MGET_KEEP_EVERY,
                          max_ops=fixed_work(traffic, cfg, seconds))
                drain_s = 0.0
                if traffic.get("drain"):
                    a = time.perf_counter_ns()
                    db.wait_idle()
                    drain_s = (time.perf_counter_ns() - a) / 1e9
            counter.armed = False
            if trace:
                tracer.instant("bench.window.end")
                jax.profiler.stop_trace()
            gc.unfreeze()
            load_s = (w["t1"] - w["t0"]) / 1e9
            c1 = engine_counters(db)
            stats = dev.memory_stats() or {}
            device["memory_peak_bytes"] = int(
                stats.get("peak_bytes_in_use", 0))

            lat = w["lat"]
            n_puts, n_gets = len(lat[gen.PUT]), len(lat[gen.GET])
            n_mget = len(lat[gen.MULTI_GET])
            if n_mget:
                ops_per_s = w["keys_read"] / load_s
            else:
                ops_per_s = w["n"] / (load_s + drain_s)
            e2e = {"ops_per_s": ops_per_s, "setup_s": setup_s}
            for name, kind in (("put_p99_us", gen.PUT),
                               ("get_p99_us", gen.GET),
                               ("multi_get_p99_us", gen.MULTI_GET)):
                if lat[kind]:
                    p = pct(lat[kind])
                    e2e[name] = p["p99"]
                    log(f"latency {name[:-len('_p99_us')]}: p50 {p['p50']} "
                        f"us, p99 {p['p99']} us, p99.9 {p['p99.9']} us, "
                        f"{p['n']} calls")
            delta = {k: c1[k] - c0[k] for k in c0}
            log(f"window: {w['n']} calls ({n_puts} put, {n_gets} get, "
                f"{n_mget} multi_get, {w['keys_read']} keys read) in "
                f"{load_s} s, drain {drain_s} s")
            log(f"window counters: {delta}")
            log(f"window compiles: {counter.counts}; jit_bucket_misses in "
                f"the window: {delta['jit_bucket_misses']}")
            log(f"end-to-end: {e2e}")

            per_layer = {}
            breakdown = None
            if trace:
                spans, w0, w1 = layer_spans(tracer)
                tr = devtrace.read_xplane(prof_dir)
                # the store's spans on the trace's clock: both windows
                # open together
                lo, _ = devtrace.window_of(tr)
                host = [(n, int(s * 1e9) + lo, int((s + d) * 1e9) + lo)
                        for n, s, d in spans]
                red = devtrace.reduce(tr, host)
                red["trace"] = tr
                log(f"trace planes: {tr['planes']}")
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                breakdown = red["breakdown"]
                run = RunData(cell.name, w1 - w0, spans, delta, red, chip)
                for m in cell.per_layer:
                    v = spec.load_reader(m.name)(run)
                    if v is not None:
                        per_layer[m.name] = {"value": v, "unit": m.unit}
                log(f"per-layer: {per_layer}")
                shutil.rmtree(prof_dir, ignore_errors=True)
                # the reduced trace of the newest traced run, for a look
                # by hand
                with gzip.open(prof_dir + ".json.gz", "wt") as f:
                    json.dump({"trace": tr, "spans": spans[:200_000],
                               "window": [w0, w1]}, f)

            # -- checks, after the window
            t_c = time.perf_counter()
            n_calls, failed = w["n"], w["failed"]
            check = gen.OpStream(traffic, records=cfg["records"],
                                 value_size=cfg["value_size"], seed=seed,
                                 stream=0)
            bad, compared, written = replay(check, n_calls, ref, w["got"],
                                            w["kept"])
            del w
            sample = check_sample(cell, cfg, written, seed)
            if traffic["check"].get("reopen"):
                # acknowledged writes that are surely still in the
                # memtable and the log when the store closes
                extra = check_writes(cfg, seed, traffic["check"]["newest"])
                for k, v in extra:
                    db.put(k, v)
                    ref.put(k, v)
                sample += [k for k, _ in extra]
            batch = traffic.get("batch", 256)
            bad_back = read_back(db, sample, ref, batch)
            c_end = engine_counters(db)
        finally:
            db.close()
        bad_reopen = 0
        if traffic["check"].get("reopen"):
            db = LsmDB(path, store.db_config(cfg, async_mode=True))
            try:
                bad_reopen = read_back(db, sample, ref, batch)
                reopen_left = left_device(engine_counters(db))
            finally:
                db.close()
        else:
            reopen_left = 0
        shutil.rmtree(path, ignore_errors=True)
    counter.close()
    log(f"checks: {compared} window answers compared, {bad} wrong; "
        f"{len(sample)} keys read back, {bad_back} wrong; after reopen "
        f"{bad_reopen} wrong; {time.perf_counter() - t_c} s")
    interp = sorted(common.interpreted_kernels())
    if interp:
        log(f"kernels interpreted: {interp}")
    checks = {
        "wrong_answers": {"value": bad + bad_back + bad_reopen, "limit": 0},
        "failed_calls": {"value": failed, "limit": 0},
        "left_device": {"value": left_device(c_end) + reopen_left,
                        "limit": 0},
        "interpreted_kernels": {"value": len(interp), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if trace:
        metrics = per_layer
    else:
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": n_calls,
              "failed": failed + bad, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
