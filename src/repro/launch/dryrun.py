import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the
# device count at first init).  This process-level flag is why the dry-run
# is its own entry point and never imported by tests or benchmarks.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config, skip_reason  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline import constants  # noqa: E402
from repro.roofline.hlo_flops import (hlo_collective_bytes,  # noqa: E402
                                      hlo_dot_flops, hlo_traffic_bytes)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory/cost/collective analysis -- the proof that the distribution
config is coherent, and the data source for EXPERIMENTS.md §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b \
      --shape decode_32k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --compaction --multi-pod
"""

OUT_DIR = "experiments/dryrun"


def _analyze(compiled, mesh, *, seconds, extra):
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo_txt = compiled.as_text()
    coll = hlo_collective_bytes(hlo_txt)
    dot = hlo_dot_flops(hlo_txt)
    n = mesh.size
    # trip-count-aware dot flops / traffic (cost_analysis counts loop
    # bodies once; see roofline/hlo_flops.py); raw numbers kept as ref
    flops_dev = max(float(dot["flops"]), float(ca.get("flops", 0.0)))
    bytes_dev = max(float(hlo_traffic_bytes(hlo_txt)["bytes"]),
                    float(ca.get("bytes accessed", 0.0)))
    coll_dev = coll["total_bytes"]
    chip = constants.peaks()
    rec = {
        "mesh": {"shape": dict(mesh.shape), "devices": n},
        "compile_seconds": seconds,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
            "hbm_per_chip": chip.hbm_bytes,
            "fits": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     - ma.alias_size_in_bytes) < chip.hbm_bytes,
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev,
                 "flops_global": flops_dev * n,
                 "bytes_global": bytes_dev * n,
                 "cost_analysis_flops_per_device":
                     float(ca.get("flops", 0.0)),
                 "dot_flop_stats": dot},
        "collectives": coll,
        "roofline": {
            "compute_s": flops_dev / chip.bf16_flops,
            "memory_s": bytes_dev / chip.hbm_bw,
            "collective_s": coll_dev / chip.ici_link_bw,
        },
        **extra,
    }
    terms = rec["roofline"]
    rec["roofline"]["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    return rec


def run_lm_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch).with_(dtype="bfloat16", attn_chunk_min_seq=4096)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    reason = skip_reason(arch, shape_name)
    if reason:
        return {"skipped": reason}

    t0 = time.time()
    if shape.kind == "train":
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_step import shard_train_step
        # capacity-bound giants store Adam moments bf16 (update math
        # stays fp32); EXPERIMENTS.md §Perf cell B it.7
        opt_cfg = AdamWConfig(
            state_dtype="bfloat16" if cfg.param_count() > 1e11
            else "float32")
        fn, state_s, batch_s = shard_train_step(cfg, mesh,
                                                batch=shape.batch,
                                                seq=shape.seq,
                                                opt_cfg=opt_cfg)
        with mesh:
            compiled = fn.lower(state_s, batch_s).compile()
        tokens = shape.batch * shape.seq
        model_flops = 6 * cfg.active_param_count() * tokens
    elif shape.kind == "prefill":
        from repro.serving.serve_step import shard_prefill
        fn, params_s, batch_s = shard_prefill(cfg, mesh, batch=shape.batch,
                                              seq=shape.seq)
        with mesh:
            compiled = fn.lower(params_s, batch_s).compile()
        tokens = shape.batch * shape.seq
        model_flops = 2 * cfg.active_param_count() * tokens
    else:  # decode
        from repro.serving.serve_step import shard_decode_step
        # fsdp=True: serving weights shard over the data axes too
        # (ZeRO-inference); without it jamba-398B replicates 50 GB/chip
        fn, params_s, cache_s, tok_s, pos_s, enc_s = shard_decode_step(
            cfg, mesh, batch=shape.batch, cache_len=shape.seq, fsdp=True)
        args = (params_s, cache_s, tok_s, pos_s) + \
            ((enc_s,) if cfg.enc_dec else ())
        with mesh:
            compiled = fn.lower(*args).compile()
        tokens = shape.batch
        model_flops = 2 * cfg.active_param_count() * tokens
    dt = time.time() - t0

    rec = _analyze(compiled, mesh, seconds=dt, extra={
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "tokens_per_step": tokens,
        "model_flops": model_flops,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    })
    rec["useful_flops_ratio"] = (model_flops /
                                 max(rec["cost"]["flops_global"], 1.0))
    return rec


def run_compaction_cell(multi_pod: bool, blocks_per_shard: int = 2048
                        ) -> dict:
    """The paper's technique on the production mesh: range-partitioned
    device compaction, one LUDA pipeline per chip (DESIGN.md §2)."""
    import functools

    from repro.configs.luda_paper import PAPER
    from repro.core.formats import SSTImage

    geom = PAPER.geometry(256)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n = mesh.size
    b = n * blocks_per_shard
    k, lanes, vw = geom.block_kvs, geom.key_lanes, geom.value_words
    img = SSTImage(
        keys=jax.ShapeDtypeStruct((b, k, lanes), jnp.uint32),
        meta=jax.ShapeDtypeStruct((b, k), jnp.uint32),
        vals=jax.ShapeDtypeStruct((b, k, vw), jnp.uint32),
        shared=jax.ShapeDtypeStruct((b, k), jnp.int32),
        nvalid=jax.ShapeDtypeStruct((b,), jnp.int32),
        crc=jax.ShapeDtypeStruct((b,), jnp.uint32),
        bloom=jax.ShapeDtypeStruct((b, geom.bloom_words(k)), jnp.uint32))

    from repro.core.offload import sharded_compact
    axes = tuple(mesh.axis_names)

    fn = jax.jit(functools.partial(
        sharded_compact, mesh=mesh, axes=axes, geom=geom,
        sort_mode="xla", backend="ref"))
    t0 = time.time()
    with mesh:
        compiled = fn.lower(img).compile()
    dt = time.time() - t0
    wire_bytes = geom.wire_words_per_block * 4 * b
    return _analyze(compiled, mesh, seconds=dt, extra={
        "arch": "luda-compaction", "shape": f"{blocks_per_shard}bps",
        "kind": "compaction",
        "wire_bytes_global": wire_bytes,
        "entries_global": b * k,
        "model_flops": 0,
    })


def cell_name(arch, shape, multi_pod):
    mesh = "pod2" if multi_pod else "pod1"
    return f"{arch}--{shape}--{mesh}"


def run_and_save(arch, shape, multi_pod, out_dir=OUT_DIR,
                 skip_existing=False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_name(arch, shape, multi_pod) + ".json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    try:
        if arch == "luda-compaction":
            rec = run_compaction_cell(multi_pod)
        else:
            rec = run_lm_cell(arch, shape, multi_pod)
    except Exception as e:  # a failure here is a bug in the system
        rec = {"error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()}
    rec["cell"] = cell_name(arch, shape, multi_pod)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None,
                   help="arch id or 'luda-compaction'")
    p.add_argument("--shape", default=None, choices=list(SHAPES))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--compaction", action="store_true")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--out", default=OUT_DIR)
    args = p.parse_args()

    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]
    jobs = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in SHAPES:
                jobs.append((arch, shape))
        jobs.append(("luda-compaction", "paper"))
    elif args.compaction:
        jobs.append(("luda-compaction", "paper"))
    else:
        assert args.arch and (args.shape or args.arch == "luda-compaction")
        jobs.append((args.arch, args.shape or "paper"))

    t_start = time.time()
    for arch, shape in jobs:
        for mp in meshes:
            rec = run_and_save(arch, shape, mp, args.out,
                               args.skip_existing)
            status = ("SKIP: " + rec["skipped"]) if "skipped" in rec else \
                ("ERROR: " + rec["error"]) if "error" in rec else \
                ("ok %.0fs fits=%s dom=%s" % (
                    rec["compile_seconds"], rec["memory"]["fits"],
                    rec["roofline"]["dominant"]))
            print(f"[{time.time()-t_start:7.0f}s] "
                  f"{cell_name(arch, shape, mp):55s} {status}", flush=True)
            if "memory" in rec:
                print("    memory_analysis: args=%.2fGB temp=%.2fGB "
                      "peak=%.2fGB" % (
                          rec["memory"]["argument_bytes"] / 2**30,
                          rec["memory"]["temp_bytes"] / 2**30,
                          rec["memory"]["peak_estimate_bytes"] / 2**30),
                      flush=True)


if __name__ == "__main__":
    main()
