"""Session paging benchmark: page-out pressure, batched-vs-scalar
resume latency, and drop/overwrite churn against every SessionStore
backend.

Drives N synthetic KV-cache-shaped sessions through the store and
measures the serving-side contract end to end:

1. **page-out** -- save N sessions, then overwrite rounds until the LSM
   backend flushes and compacts (superseded pages must be reclaimed);
2. **resume** -- time ``load_many`` (two multi_get waves) against the
   scalar ``load`` loop, p50/p99 per session, and verify the batched
   states are BIT-IDENTICAL to the scalar ones (a mismatch makes the
   run exit non-zero: the batched path being fast is worthless if it
   is wrong);
3. **churn** -- drop half the sessions and overwrite the rest, then
   flush + compact and report reclaim stats.

CLI (the ``serve-smoke`` CI job)::

    python benchmarks/serve_bench.py --backend lsm --sessions 16
    python benchmarks/serve_bench.py --backend sharded --engine cpu
    python benchmarks/serve_bench.py --backend memory   # no LSM at all

``measure_resume()`` is the importable entry point the regression gate
uses for its ``serve.resume.p99_cpu_smoke`` row.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

# runnable both as `python -m benchmarks.serve_bench` and as a script
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax.numpy as jnp
import numpy as np

from repro.core.formats import SSTGeometry
from repro.core.scheduler import SchedulerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.lsm.db import DBConfig, LsmDB
from repro.lsm.sharded import ShardedDB, uniform_boundaries
from repro.serving.session_store import (LsmSessionStore, MemorySessionStore)

GEOM = SSTGeometry(key_bytes=16, value_bytes=1024, block_bytes=8 * 1024,
                   sst_bytes=64 * 1024)


def template():
    # KV-cache-shaped: one "layer" of keys/values plus a position -- the
    # tree STRUCTURE is all that matters for decode
    return {"k": jnp.zeros((1, 1), jnp.float32),
            "v": jnp.zeros((1, 1), jnp.float32),
            "pos": jnp.zeros((1,), jnp.int32)}


def make_state(rng: np.random.Generator, i: int, state_kb: int):
    n = max(1, (state_kb * 1024) // (2 * 4 * 64))
    return {"k": jnp.asarray(rng.standard_normal((n, 64)), jnp.float32),
            "v": jnp.asarray(rng.standard_normal((n, 64)), jnp.float32),
            "pos": jnp.asarray([i], jnp.int32)}


def _leaves_bytes(state) -> list[bytes]:
    import jax
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(state)]


def open_store(backend: str, engine: str, path: str):
    """(store, db-or-None) for a backend cell."""
    if backend == "memory":
        return MemorySessionStore(template), None
    cfg = DBConfig(geom=GEOM, engine=engine, memtable_bytes=32 * 1024,
                   scheduler=SchedulerConfig(l0_trigger=3,
                                             base_bytes=512 * 1024))
    if backend == "sharded":
        db = ShardedDB.open(path, cfg, boundaries=uniform_boundaries(4))
    else:
        db = LsmDB(path, cfg)
    return LsmSessionStore(db, template), db


def measure_resume(backend: str = "lsm", engine: str = "cpu", *,
                   sessions: int = 16, resume_batch: int = 8,
                   saves: int = 3, state_kb: int = 8, reps: int = 5,
                   seed: int = 0, workdir: str | None = None) -> dict:
    """Run all three phases; returns the measurement dict."""
    rng = np.random.default_rng(seed)
    top = workdir or tempfile.mkdtemp(prefix=f"serve-bench-{backend}-")
    store, db = open_store(backend, engine, os.path.join(top, "pages"))
    names = [f"sess-{i:03d}" for i in range(sessions)]
    states = {s: make_state(rng, i, state_kb)
              for i, s in enumerate(names)}

    # -- phase 1: page-out pressure -------------------------------------
    t0 = time.perf_counter()
    records = 0
    for round_no in range(saves):
        for i, s in enumerate(names):
            if round_no:
                states[s] = make_state(rng, i + round_no * sessions,
                                       state_kb)
            records += store.save(s, states[s])
    if db is not None:
        db.flush()
        db.maybe_compact()
        if hasattr(db, "wait_idle"):
            db.wait_idle()
    page_out_s = time.perf_counter() - t0

    # -- phase 2: batched vs scalar resume ------------------------------
    scalar_us, batched_us = [], []
    mismatches = 0
    for rep in range(reps):
        batch = list(rng.choice(names, size=min(resume_batch, sessions),
                                replace=False))
        t0 = time.perf_counter_ns()
        scalar = [store.load(s) for s in batch]
        dt = (time.perf_counter_ns() - t0) / 1000.0
        scalar_us += [dt / len(batch)] * len(batch)
        t0 = time.perf_counter_ns()
        batched = store.load_many(batch)
        dt = (time.perf_counter_ns() - t0) / 1000.0
        batched_us += [dt / len(batch)] * len(batch)
        for s, a, b in zip(batch, scalar, batched):
            if _leaves_bytes(a) != _leaves_bytes(b) or \
                    _leaves_bytes(b) != _leaves_bytes(states[s]):
                mismatches += 1

    # -- phase 3: drop/overwrite churn ----------------------------------
    t0 = time.perf_counter()
    for s in names[::2]:
        store.drop(s)
    for i, s in enumerate(names[1::2]):
        states[s] = make_state(rng, 10_000 + i, state_kb)
        store.save(s, states[s])
    if db is not None:
        db.flush()
        db.maybe_compact()
        if hasattr(db, "wait_idle"):
            db.wait_idle()
    churn_s = time.perf_counter() - t0
    survivors = store.load_many(names, missing_ok=True)
    for s, got in zip(names, survivors):
        want_absent = s in names[::2]
        if want_absent != (got is None):
            mismatches += 1
        elif got is not None and _leaves_bytes(got) != \
                _leaves_bytes(states[s]):
            mismatches += 1

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    if db is not None:
        st = db.stats
        stats = {"flushes": st.flushes, "compactions": st.compactions,
                 "entries_dropped": st.compact_entries_dropped,
                 "write_batches": st.write_batches,
                 "batch_ops": st.batch_ops}
        db.close()
    else:
        stats = {}
    if workdir is None:
        shutil.rmtree(top, ignore_errors=True)
    return {
        "backend": backend, "engine": engine, "sessions": sessions,
        "resume_batch": resume_batch, "saves": saves,
        "state_kb": state_kb, "records": records,
        "page_out_seconds": page_out_s, "churn_seconds": churn_s,
        "scalar_p50_us": pct(scalar_us, 50),
        "scalar_p99_us": pct(scalar_us, 99),
        "batched_p50_us": pct(batched_us, 50),
        "batched_p99_us": pct(batched_us, 99),
        "mismatches": mismatches,
        "stats": stats,
    }


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="lsm",
                    choices=("memory", "lsm", "sharded"))
    ap.add_argument("--engine", default="cpu", choices=("cpu", "device"))
    ap.add_argument("--sessions", type=int, default=16)
    ap.add_argument("--resume-batch", type=int, default=8)
    ap.add_argument("--saves", type=int, default=3)
    ap.add_argument("--state-kb", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    rep = measure_resume(args.backend, args.engine,
                         sessions=args.sessions,
                         resume_batch=args.resume_batch, saves=args.saves,
                         state_kb=args.state_kb, reps=args.reps,
                         seed=args.seed)
    print(f"serve_bench backend={rep['backend']} engine={rep['engine']} "
          f"sessions={rep['sessions']} x {rep['saves']} saves "
          f"({rep['records']} records, {rep['page_out_seconds']:.2f}s)")
    print(f"  resume  scalar  p50 {rep['scalar_p50_us']:9.1f}us   "
          f"p99 {rep['scalar_p99_us']:9.1f}us")
    print(f"  resume  batched p50 {rep['batched_p50_us']:9.1f}us   "
          f"p99 {rep['batched_p99_us']:9.1f}us")
    if rep["stats"]:
        s = rep["stats"]
        print(f"  store   flushes={s['flushes']} "
              f"compactions={s['compactions']} "
              f"reclaimed={s['entries_dropped']} "
              f"write_batches={s['write_batches']} "
              f"batch_ops={s['batch_ops']}")
    print(f"  churn   {rep['churn_seconds']:.2f}s "
          f"(drop half, overwrite rest)")
    if rep["mismatches"]:
        print(f"FAIL: {rep['mismatches']} batched resume states differ "
              "from the scalar oracle", file=sys.stderr)
        return 1
    print("  bit-identity: batched == scalar == saved (ok)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
