"""Per-kernel microbenchmarks (compute layers the paper offloads).

Measures the jitted pure-jnp reference implementations (XLA-compiled on
this CPU -- the honest measurable number here), the Pallas interpret-mode
kernels (correctness-path timing, NOT a TPU number), and reports the
modeled TPU v5e time from the roofline terms for each kernel's working
set.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitonic_sort, bloom, crc32, ops, prefix, ref
from repro.lsm.cpu_engine import model_sort_seconds
from repro.roofline import constants


def _time(fn, *args, iters=5):
    # warm up exactly once (jit compile + first dispatch); block on the
    # result pytree whatever its structure
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_kernels(iters: int = 5):
    """Returns rows: (name, us_per_call, derived-string).

    ``iters=1`` is the CI smoke mode: every kernel path still compiles and
    executes once, so kernel-layer regressions fail loudly without paying
    the full measurement loop."""
    rows = []
    rng = np.random.default_rng(0)
    _t = functools.partial(_time, iters=iters)

    # crc32: 256 blocks x 1024 words (1 MB)
    words = jnp.asarray(rng.integers(0, 2**32, (256, 1024), np.uint32))
    us_ref = _t(jax.jit(ref.crc32_words), words)
    n_bytes = words.size * 4
    model_us = n_bytes / constants.peaks().hbm_bw * 1e6 + 5
    rows.append(("kernel.crc32.ref_cpu", us_ref,
                 f"{n_bytes/1e6:.1f}MB;tpu_model={model_us:.1f}us"))
    us_pallas = _t(lambda w: crc32.crc32_blocks(w, interpret=True),
                      words[:8, :64])
    rows.append(("kernel.crc32.pallas_interp", us_pallas,
                 "8x64words;correctness-path"))

    # bloom: 64 groups x 256 keys
    keys = jnp.asarray(rng.integers(0, 2**32, (64, 256, 4), np.uint32))
    valid = jnp.ones((64, 256), jnp.uint32)
    us_ref = _t(jax.jit(
        lambda k: ref.bloom_build(k, n_words=80, n_probes=7)), keys)
    rows.append(("kernel.bloom.ref_cpu", us_ref, "64x256keys"))
    us_pallas = _t(lambda k, v: bloom.bloom_build(
        k, v, n_words=80, n_probes=7, interpret=True),
        keys[:4], valid[:4])
    rows.append(("kernel.bloom.pallas_interp", us_pallas, "4x256keys"))

    # prefix encode: 4096 sorted keys
    k = rng.integers(0, 2**16, (4096, 4), dtype=np.uint32)
    k = jnp.asarray(np.array(sorted(map(tuple, k)), np.uint32))
    us_ref = _t(jax.jit(
        lambda x: ref.prefix_encode(x, restart_interval=16)), k)
    rows.append(("kernel.prefix.ref_cpu", us_ref, "4096keys"))
    us_pallas = _t(lambda x: prefix.prefix_encode(
        x, restart_interval=16, interpret=True), k[:512])
    rows.append(("kernel.prefix.pallas_interp", us_pallas, "512keys"))

    # tuple sort: 16384 rows x 6 lanes
    rows_arr = jnp.asarray(rng.integers(0, 2**32, (16384, 6), np.uint32))
    us_ref = _t(jax.jit(lambda r: ref.sort_tuples(r, 6)), rows_arr)
    sort_bytes = rows_arr.size * 4
    model_us = (17 * 18 / 2) * sort_bytes / constants.peaks().hbm_bw * 1e6  # stages
    rows.append(("kernel.sort.xla_cpu", us_ref,
                 f"16k-rows;tpu_bitonic_model={model_us:.0f}us"))
    us_pallas = _t(lambda r: bitonic_sort.bitonic_sort(
        r, interpret=True), rows_arr[:256])
    rows.append(("kernel.sort.pallas_interp", us_pallas, "256rows"))

    # phase-2 bitonic vs merge-path: 2^14 rows as 8 sorted runs.  Both are
    # the XLA-on-CPU executions of the exact device algorithms (the bitonic
    # compare-exchange network vs the run-aware merge tree), plus the
    # modeled TPU roofline for each.
    n_rows, n_runs, lanes = 1 << 14, 8, 6
    per = n_rows // n_runs
    run_parts = []
    for r in range(n_runs):
        body = rng.integers(0, 2**32, (per, lanes - 1), dtype=np.uint32)
        body = body[np.lexsort(body.T[::-1])]
        idx = (np.arange(per) + r * per).astype(np.uint32)
        run_parts.append(np.concatenate([body, idx[:, None]], axis=1))
    runs_arr = jnp.asarray(np.concatenate(run_parts))
    run_lens = (per,) * n_runs
    us_bitonic = _t(bitonic_sort.bitonic_sort_xla, runs_arr)
    merge_fn = jax.jit(functools.partial(ops.merge_runs, run_lens=run_lens,
                                         backend="ref"))
    us_merge = _t(merge_fn, runs_arr)
    model_bit = model_sort_seconds(n_rows, lanes, n_runs, "device") * 1e6
    model_merge = model_sort_seconds(n_rows, lanes, n_runs, "merge") * 1e6
    rows.append(("kernel.sort.bitonic_xla_cpu", us_bitonic,
                 f"2^14rows;tpu_model={model_bit:.0f}us"))
    rows.append(("kernel.sort.merge_xla_cpu", us_merge,
                 f"2^14rows;8runs;tpu_model={model_merge:.0f}us;"
                 f"speedup_vs_bitonic={us_bitonic / us_merge:.1f}x"))

    # end-to-end compaction pipeline (ref backend, jitted)
    from repro.core import compaction, offload
    from repro.core.formats import SSTGeometry
    geom = SSTGeometry(key_bytes=16, value_bytes=272, block_bytes=4096,
                       sst_bytes=64 * 1024)
    n = 4096
    keys = jnp.asarray(np.sort(
        rng.integers(0, 2**32, (n, 4), dtype=np.uint32).view(np.uint32),
        axis=0))
    meta = jnp.asarray((np.arange(n, dtype=np.uint32) << 1) | 1)
    vals = jnp.asarray(rng.integers(0, 2**32, (n, geom.value_words),
                                    np.uint32))
    img = offload.build_image(keys, meta, vals, geom=geom)
    jax.block_until_ready(img)

    def compact_once(im):
        out, stats = compaction.compact(im, geom=geom, sort_mode="xla",
                                        backend="ref")
        return out.crc
    us = _t(compact_once, img)
    wire = geom.wire_words_per_block * 4 * img.keys.shape[0]
    from repro.lsm.cpu_engine import model_device_seconds
    model_us = model_device_seconds(wire, wire, geom) * 1e6
    rows.append(("pipeline.compact.ref_cpu", us,
                 f"{wire/1e6:.1f}MB;tpu_model={model_us:.0f}us"))
    return rows
