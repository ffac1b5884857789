"""Bench regression gate: catch catastrophic kernel-path slowdowns in CI.

Two subcommands:

    python -m benchmarks.regression_gate emit current.json
        Run the kernel microbenches in smoke mode (1 measurement iter,
        every kernel path compiles + executes) and write the rows as JSON.

    python -m benchmarks.regression_gate compare baseline.json current.json \
        [--threshold 3.0] [--min-us 50]
        Fail (exit 1) when any benchmark got more than ``threshold`` times
        slower than the committed baseline, or when a baseline row
        disappeared (lost coverage is a regression too).

The threshold is deliberately generous: CI machines are noisy and slower
than the machine that produced ``benchmarks/baseline.json``, so only
catastrophic regressions (an accidental O(n^2) path, a kernel silently
falling back to interpret mode, a 10x compile-per-call bug) should trip
it.  Rows faster than ``--min-us`` in the baseline are compared against
the ``--min-us`` floor instead, so sub-noise timings cannot flake the
gate.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys

# runnable both as `python -m benchmarks.regression_gate` and as a script
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.launch.compile_cache import enable_compile_cache

# a zipfian read working set this small must live almost entirely in the
# block cache; a hit rate below this floor means the cache (or its
# counters) broke, regardless of how fast the machine is
MIN_SMOKE_HIT_RATE = 0.5


def _ycsb_rows() -> dict:
    """End-to-end YCSB smoke rows for the gate.

    ``ycsb.get.p99_cpu_smoke``: scalar get tail latency walks the full
    read path (memtable, immutable queue, L0 newest-first, leveled binary
    search) -- a regression surface the kernel microbenches cannot see.

    ``ycsb.multi_get.p99_cpu_smoke``: the batched read path (stacked
    bloom prune + stacked search/gather) on a zipfian YCSB-C smoke; also
    enforces correctness gates directly (batched results must be
    bit-identical to scalar, and the block-cache hit rate on the zipfian
    replay must clear ``MIN_SMOKE_HIT_RATE`` -- a hit rate of ~0 means
    the cache is broken and every 'fast' number below is a lie).

    ``ycsb.put.p99_under_faults``: chaos mode -- the same smoke with
    ``flush.build`` failing transiently half the time, so the tail
    includes in-line retry/backoff and any bg_error halt + resume()
    round trips.  A blowup here means the self-healing path got slow
    (or stopped healing: the run must end green, and a clean-path run
    must show zero engine fallbacks).  See docs/robustness.md.

    Sync cpu engine, tiny stores, so this adds a few seconds to emit."""
    import shutil

    from benchmarks.ycsb_bench import (measure_chaos, measure_latency,
                                       measure_multi_get)
    db, rep = measure_latency("cpu", async_mode=False, records=120,
                              operations=240, value_size=64)
    db.close()
    shutil.rmtree(rep["path"], ignore_errors=True)
    mg = measure_multi_get("cpu", records=120, operations=240, batch=32,
                           value_size=64, workload="C",
                           distribution="zipfian")
    if mg["mismatches"]:
        raise AssertionError(
            f"multi_get smoke: {mg['mismatches']} results differ from "
            "scalar get -- batched read path is wrong, not slow")
    if mg["block_cache_hit_rate"] < MIN_SMOKE_HIT_RATE:
        raise AssertionError(
            f"multi_get smoke: block-cache hit rate "
            f"{mg['block_cache_hit_rate']:.1%} below the "
            f"{MIN_SMOKE_HIT_RATE:.0%} floor on a zipfian working set "
            "that fits in cache -- the cache is not caching")
    ch = measure_chaos("cpu", inject="flush.build:0.5", records=120,
                       operations=240, value_size=64)
    if not ch["green"]:
        raise AssertionError(
            "chaos smoke: store did not return to green after the "
            "faults were disarmed -- resume()/drain is broken")
    if rep["engine_fallbacks"]:
        raise AssertionError(
            "clean-path smoke: engine fell back to CPU without any "
            "injected fault -- silent degradation")
    return {
        "ycsb.get.p99_cpu_smoke": {
            "us": rep["get_percentiles_us"][99.0],
            "derived": "records=120;ops=240;value=64;sync",
        },
        "ycsb.multi_get.p99_cpu_smoke": {
            "us": mg["batched_perkey_percentiles_us"][99.0],
            "derived": (f"records=120;ops=240;value=64;batch=32;C;zipfian;"
                        f"hit_rate={mg['block_cache_hit_rate']:.3f}"),
        },
        "ycsb.put.p99_under_faults": {
            "us": ch["put_percentiles_us"][99.0],
            "derived": (f"records=120;ops=240;value=64;A;chaos="
                        f"flush.build:0.5;fired="
                        f"{ch['fired']['flush.build']};"
                        f"bg_retries={ch['bg_retries']};"
                        f"resumes={ch['resumes']};"
                        f"recovery_ms={ch['recovery_seconds'] * 1e3:.1f}"),
        },
    }


def _serve_rows() -> dict:
    """Session-resume smoke row for the gate.

    ``serve.resume.p99_cpu_smoke``: batched ``load_many`` tail latency
    per session on the LSM session-store backend -- the two-wave
    multi_get resume path of ``LsmSessionStore.load_many``.  The
    row also enforces correctness directly: the batched states must be
    bit-identical to the scalar ``load`` loop (and to what was saved),
    or the emit aborts -- a fast wrong resume is not a benchmark."""
    from benchmarks.serve_bench import measure_resume
    rep = measure_resume("lsm", "cpu", sessions=12, resume_batch=6,
                         saves=2, state_kb=4, reps=3)
    if rep["mismatches"]:
        raise AssertionError(
            f"serve smoke: {rep['mismatches']} batched resume states "
            "differ from the scalar oracle -- the batched page-in path "
            "is wrong, not slow")
    return {
        "serve.resume.p99_cpu_smoke": {
            "us": rep["batched_p99_us"],
            "derived": (f"sessions=12;batch=6;saves=2;state_kb=4;lsm;"
                        f"write_batches={rep['stats']['write_batches']};"
                        f"reclaimed={rep['stats']['entries_dropped']}"),
        },
    }


def emit(out_path: str, iters: int = 1) -> dict:
    from benchmarks.kernel_bench import bench_kernels
    rows = {name: {"us": us, "derived": derived}
            for name, us, derived in bench_kernels(iters=iters)}
    rows.update(_ycsb_rows())
    rows.update(_serve_rows())
    doc = {
        "rows": rows,
        "meta": {
            "iters": iters,
            "python": platform.python_version(),
            "machine": platform.machine(),
        },
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(rows)} rows to {out_path}")
    return doc


def compare(baseline_path: str, current_path: str, *, threshold: float,
            min_us: float) -> int:
    with open(baseline_path) as f:
        base = json.load(f)["rows"]
    with open(current_path) as f:
        cur = json.load(f)["rows"]
    failures = []
    width = max((len(n) for n in base), default=10)
    print(f"{'benchmark':<{width}}  {'base_us':>10}  {'cur_us':>10}  "
          f"{'ratio':>6}  verdict")
    for name in sorted(base):
        b = float(base[name]["us"])
        if name not in cur:
            failures.append(f"{name}: present in baseline, missing from "
                            "current run (lost bench coverage)")
            print(f"{name:<{width}}  {b:>10.1f}  {'MISSING':>10}")
            continue
        c = float(cur[name]["us"])
        floor = max(b, min_us)
        ratio = c / floor
        ok = ratio <= threshold
        print(f"{name:<{width}}  {b:>10.1f}  {c:>10.1f}  {ratio:>6.2f}  "
              f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(
                f"{name}: {c:.1f}us vs baseline {b:.1f}us "
                f"({ratio:.1f}x > {threshold:.1f}x threshold)")
    extra = sorted(set(cur) - set(base))
    if extra:
        print(f"note: {len(extra)} rows not in baseline (new benches?): "
              + ", ".join(extra))
    if failures:
        print(f"\nREGRESSION GATE FAILED ({len(failures)}):",
              file=sys.stderr)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    print("\nregression gate passed")
    return 0


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    ap_e = sub.add_parser("emit", help="run smoke benches, write JSON")
    ap_e.add_argument("out")
    ap_e.add_argument("--iters", type=int, default=1)
    ap_c = sub.add_parser("compare", help="compare current vs baseline")
    ap_c.add_argument("baseline")
    ap_c.add_argument("current")
    ap_c.add_argument("--threshold", type=float, default=3.0)
    ap_c.add_argument("--min-us", type=float, default=50.0)
    args = ap.parse_args(argv)
    if args.cmd == "emit":
        emit(args.out, iters=args.iters)
        return 0
    return compare(args.baseline, args.current, threshold=args.threshold,
                   min_us=args.min_us)


if __name__ == "__main__":
    sys.exit(main())
