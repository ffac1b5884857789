#!/usr/bin/env python3
"""Chip smoke test: drive the device-engine store once on one TPU chip.

    python3 chip_smoke.py [--seed N]

Run it from the repository root; it needs one TPU and no options.

* Phase 0, kernels: each main-path Pallas kernel (CRC-32, bloom build,
  prefix encode, merge path, multi-probe, block lookup), compiled for the
  chip, against its jnp oracle on a random input at the paper's widths.
* Phase 1, ``LsmDB``: ``engine="device"``, sync, the paper's geometry
  (16 B keys, 128 B values, 4 KB blocks, 4 MB SSTs and memtable).  Loads
  YCSB records in shuffled order (flushes and a 4-SST L0->L1 compaction
  on the device), runs YCSB-A, one 4,096-key ``multi_get`` and scalar
  ``get``s, closes, reopens and reads acknowledged keys back.  Every read
  is checked against a dict of the same operations.
* Phase 2, ``ShardedDB``: 4 range shards sharing one engine on the chip,
  loaded until the shared queue makes a stacked (vmapped) launch of
  several shards' compactions, then checked the same way.

Earlier lines print the device, the phase counters, the host wall seconds
per phase and the device's peak memory.  The last line is
``{"ok": true, "device": {...}}``.  The script exits non-zero without
that line when JAX finds no TPU, on any wrong answer, on any compaction
that fell back to the CPU, and if any kernel ran in interpret mode.
``--rehearse`` runs phases 1 and 2 on any platform (use it on a CPU with
small sizes); it still fails at the end off the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
STORE_DIR = os.path.join(ROOT, ".smoke_store")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase 0: kernels against their oracles
# ---------------------------------------------------------------------------


def phase_kernels(geom, seed: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    k, lanes, vw = geom.block_kvs, geom.key_lanes, geom.value_words
    n_words = geom.bloom_words(k)
    probes = geom.bloom_probes

    def u32(*shape):
        return jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))

    def same(name, got, want):
        for g, w in zip(got if isinstance(got, tuple) else (got,),
                        want if isinstance(want, tuple) else (want,)):
            check(np.array_equal(np.asarray(g), np.asarray(w)),
                  f"kernel {name} disagrees with its oracle")
        log(f"  kernel {name}: matches oracle")

    blocks = 64
    sections = [u32(blocks, w) for w in (1, k * lanes, k, k * vw, k)]
    same("crc32", ops.crc32_sections(sections, backend="pallas"),
         ref.crc32_words_sections(sections))

    keys = u32(blocks, k, lanes)
    valid = jnp.asarray(rng.integers(0, 2, (blocks, k), dtype=np.uint32))
    same("bloom_build",
         ops.bloom_build(keys, valid, n_words=n_words, n_probes=probes,
                         backend="pallas"),
         ref.bloom_build(keys, n_words=n_words, n_probes=probes,
                         valid=valid != 0))

    srt = np.asarray(rng.integers(0, 2**8, (blocks * k, lanes),
                                  dtype=np.uint32))
    srt = jnp.asarray(srt[np.lexsort(srt.T[::-1])])
    same("prefix_encode",
         ops.prefix_encode(srt, restart_interval=geom.restart_interval,
                           backend="pallas"),
         ref.prefix_encode(srt, restart_interval=geom.restart_interval))

    run_lens = (700, 300, 1024, 1)
    runs = []
    for i, n in enumerate(run_lens):
        body = np.asarray(rng.integers(0, 64, (n, lanes + 1),
                                       dtype=np.uint32))
        body = body[np.lexsort(body.T[::-1])]
        idx = np.arange(sum(run_lens[:i]), sum(run_lens[:i + 1]),
                        dtype=np.uint32)
        runs.append(np.concatenate([body, idx[:, None]], axis=1))
    rows = jnp.asarray(np.concatenate(runs))
    same("merge_path", ops.merge_runs(rows, run_lens, backend="pallas"),
         ref.sort_tuples(rows, rows.shape[1]))

    cands = 1024
    members = u32(cands, 4, lanes)
    filt = ref.bloom_build(members, n_words=n_words, n_probes=probes)
    qkeys = jnp.where(jnp.asarray(rng.random((cands, 1)) < 0.5),
                      members[:, 0], u32(cands, lanes))
    same("multi_probe",
         ops.bloom_multi_probe(filt, qkeys, n_probes=probes,
                               backend="pallas"),
         ref.bloom_multi_probe(filt, qkeys, n_probes=probes))

    bk = np.asarray(rng.integers(0, 2**4, (cands, k, lanes),
                                 dtype=np.uint32))
    bk = np.stack([b[np.lexsort(b.T[::-1])] for b in bk])
    nvalid = rng.integers(0, k + 1, cands).astype(np.int32)
    bk[np.arange(k)[None, :] >= nvalid[:, None]] = 0xFFFFFFFF
    q = np.where(rng.random((cands, 1)) < 0.5,
                 bk[np.arange(cands), rng.integers(0, k, cands)],
                 rng.integers(0, 2**4, (cands, lanes), dtype=np.uint32))
    args = (jnp.asarray(bk), u32(cands, k), u32(cands, k, vw),
            jnp.asarray(nvalid), jnp.asarray(q.astype(np.uint32)))
    same("lookup_blocks", ops.lookup_blocks(*args, backend="pallas"),
         ref.lookup_blocks(*args))


# ---------------------------------------------------------------------------
# phases 1 and 2: the store through its entry points
# ---------------------------------------------------------------------------


def ycsb(records: int, seed: int):
    from repro.data.ycsb import WorkloadSpec, YCSBWorkload
    return YCSBWorkload(WorkloadSpec.ycsb_a(records=records, operations=1000,
                                            value_size=128, seed=seed))


def load(db, records, ids, model: dict):
    """Insert ``records[i]`` (YCSB load-phase ops) for each ``i`` of ids."""
    for i in ids:
        _, key, value = records[i]
        db.put(key, value)
        model[key] = value


def check_reads(db, model: dict, rng, *, label: str, batch: int = 4096,
                scalar: int = 1000):
    """One ``batch``-key multi_get (one key in ten never written) and
    ``scalar`` gets, each against the dict model."""
    from repro.data.ycsb import key_of
    present = list(model)
    keys = [present[int(i)] for i in rng.integers(0, len(present), batch)]
    for j in range(0, batch, 10):
        keys[j] = key_of(10**9 + j)                  # never written
    got = db.multi_get(keys)
    bad = sum(got[i] != model.get(k) for i, k in enumerate(keys))
    check(bad == 0, f"{label}: {bad}/{batch} multi_get answers differ")
    for i in rng.integers(0, len(present), scalar):
        k = present[int(i)]
        check(db.get(k) == model[k], f"{label}: get({k!r}) differs")
    log(f"  {label}: multi_get({batch}) and {scalar} gets match the model")


def run_ycsb_a(db, wl, model: dict) -> int:
    """The workload's 1,000 ops: updates go to the store and the model,
    reads are checked against the model.  Returns the reads checked."""
    reads = 0
    for op, key, value in wl.run_ops():
        if op == "read":
            check(db.get(key) == model.get(key), f"YCSB-A read {key!r}")
            reads += 1
        else:
            db.put(key, value)
            model[key] = value
    return reads


def engine_counters(db, engine) -> dict:
    s = db.stats
    return {"flushes": s.flushes, "compactions": s.compactions,
            "trivial_moves": s.trivial_moves,
            "batched_compactions": s.batched_compactions,
            "batch_launches": engine.batch_launches,
            "batch_jobs": engine.batch_jobs,
            "jit_bucket_misses": engine.jit_bucket_misses,
            "fallbacks": engine.fallbacks,
            "launch_retries": engine.launch_retries,
            "engine_fallbacks": s.engine_fallbacks}


def check_on_device(counters: dict, label: str):
    from repro.kernels import common
    for name in ("fallbacks", "launch_retries", "engine_fallbacks"):
        check(counters[name] == 0,
              f"{label}: {name}={counters[name]}: a compaction left the "
              "device")
    interp = common.interpreted_kernels()
    check(not interp, f"{label}: kernels ran in interpret mode: "
          f"{sorted(interp)}")


def phase_lsm(cfg, args) -> dict:
    import numpy as np

    from repro.lsm.db import LsmDB
    from repro.obs import Tracer
    rng = np.random.default_rng(args.seed)
    wl = ycsb(args.records, args.seed)
    model: dict = {}
    tracer = Tracer()
    db = LsmDB(os.path.join(STORE_DIR, "lsm"), cfg, tracer=tracer)
    load(db, list(wl.load_ops()), rng.permutation(args.records), model)
    c = engine_counters(db, db.engine)
    jobs = [e["args"] for e in tracer.to_chrome()["traceEvents"]
            if e["name"] == "compact.job"]
    widest = max((a["inputs"] for a in jobs if a["level"] == 0), default=0)
    log(f"  loaded {args.records} records: {c}; compaction inputs "
        f"{[(a['level'], a['inputs']) for a in jobs]}")
    check(c["flushes"] >= 4, f"phase 1: {c['flushes']} flushes, want >= 4")
    check(widest >= 4, "phase 1: no L0->L1 compaction of 4 or more SSTs")
    reads = run_ycsb_a(db, wl, model)
    log(f"  YCSB-A: 1000 ops, {reads} reads match the model")
    check_reads(db, model, rng, label="phase 1")
    c = engine_counters(db, db.engine)
    check_on_device(c, "phase 1")
    db.close()
    db = LsmDB(os.path.join(STORE_DIR, "lsm"), cfg)
    acked = list(model)
    keys = [acked[int(i)] for i in rng.integers(0, len(acked), 1000)]
    got = db.multi_get(keys)
    bad = sum(g != model[k] for g, k in zip(got, keys))
    check(bad == 0, f"phase 1 reopen: {bad}/1000 acknowledged keys differ")
    log("  reopen: 1000 acknowledged keys read back")
    check_on_device(engine_counters(db, db.engine), "phase 1 reopen")
    db.close()
    return c


def phase_sharded(cfg, args) -> dict:
    import numpy as np

    from repro.data.ycsb import key_of
    from repro.lsm.sharded import ShardedDB
    rng = np.random.default_rng(args.seed + 1)
    cap = args.shard_records_cap
    records = list(ycsb(cap, args.seed + 1).load_ops())
    order = rng.permutation(cap)
    sample = [key_of(int(i)) for i in order[:4096]]
    db = ShardedDB(os.path.join(STORE_DIR, "sharded"), cfg, shards=4,
                   sample_keys=sample)
    model: dict = {}
    loaded, step = 0, max(1, cap // 40)
    while loaded < cap and db.engine.batch_launches == 0:
        load(db, records, order[loaded:loaded + step], model)
        loaded += step
    db.wait_idle()
    c = engine_counters(db, db.engine)
    log(f"  loaded {loaded} records over 4 shards: {c}")
    check(c["batch_launches"] >= 1,
          f"phase 2: no stacked launch after {loaded} records")
    check_reads(db, model, rng, label="phase 2")
    c = engine_counters(db, db.engine)
    check_on_device(c, "phase 2")
    db.close()
    return c


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--records", type=int, default=200_000,
                    help="phase 1 load (default: about 29 MB)")
    ap.add_argument("--shard-records-cap", type=int, default=800_000,
                    help="phase 2 loads until a stacked launch, at most "
                         "this many records")
    ap.add_argument("--sst-kb", type=int, default=4096,
                    help="SST and memtable size (the paper's 4 MB)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any platform; still fails off the chip")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".tpu_logs"))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax

        from repro.configs.luda_paper import PAPER
        from repro.launch.compile_cache import enable_compile_cache
        from repro.lsm.db import DBConfig
    except ImportError as e:
        print(f"chip_smoke: cannot import the store ({e}); run it from "
              "the repository root", file=sys.stderr)
        return 2

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if dev.platform != "tpu" and not args.rehearse:
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")

    geom = dataclasses.replace(PAPER.geometry(128),
                               sst_bytes=args.sst_kb * 1024)
    cfg = DBConfig(geom=geom, engine="device",
                   memtable_bytes=args.sst_kb * 1024,
                   scheduler=PAPER.scheduler())
    shutil.rmtree(STORE_DIR, ignore_errors=True)
    phases = [("kernels", lambda: phase_kernels(geom, args.seed)),
              ("lsm", lambda: phase_lsm(cfg, args)),
              ("sharded", lambda: phase_sharded(cfg, args))]
    if dev.platform != "tpu":
        phases = phases[1:]   # off the chip the kernels can only interpret
    walls = {}
    try:
        for name, phase in phases:
            log(f"phase {name}:")
            t0 = time.perf_counter()
            phase()
            walls[name] = time.perf_counter() - t0
            log(f"  host wall seconds, phase {name}: {walls[name]}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(STORE_DIR, ignore_errors=True)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    if dev.platform != "tpu":
        print(f"chip_smoke: rehearsal on {dev.platform}: not a chip run",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
