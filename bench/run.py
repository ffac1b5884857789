#!/usr/bin/env python3
"""The benchmark's entry point: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run it from the root of a checkout, on a machine with the TPU chips the
cell asks for.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``); the last lines of
standard error are the numbers compared, each beside its limit.  With no
TPU, or fewer chips than the cell asks for, it prints no result and exits
non-zero.  ``--fault <name>`` plants a fault (``bench/faults.py``) to show
that the checks catch it; the benchmark's own runs plant none.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", action="append", default=[])
    args = ap.parse_args(argv)

    # the compile cache and the runtime's logs stay inside the checkout,
    # at fixed paths, whatever the environment says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH,
                                                           ".jax_cache")
    os.environ["TPU_LOG_DIR"] = os.path.join(BENCH, ".tpu_logs")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax

        import repro.lsm.db  # noqa: F401  (the store under test)
    except ImportError as e:
        print(f"bench: cannot import the store ({e}); run from the root "
              "of a checkout", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness
    import spec
    cell = spec.find_cell(args.workload, ROOT)
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              faults=args.fault)
    if result is None:
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
