"""The LUDA compaction pipeline: unpack -> (delete and) sort -> pack.

This is the paper's contribution as a composable JAX module.  The whole
pipeline is one jitted function over static-shaped device arrays; the three
phases map to the paper's CUDA kernels:

* phase 1 ``unpack``     -> CRC verify (``kernels.crc32``) + prefix restore
* phase 2 ``sort``       -> lightweight ``<K, V_offset>`` tuple ordering:
                            run-aware merge path (default) / device bitonic
                            / XLA sort / cooperative host
* phase 3 ``shared_key`` -> ``kernels.prefix`` on the survivor keys
          ``encode``     -> value gather (lazy value movement) + CRC
          ``filter``     -> ``kernels.bloom``

Phase 2 exploits the strongest structural fact about compaction inputs:
every input SST is already a sorted run, so ``sort_mode="merge"`` merges
the runs (O(n log k)) instead of re-sorting the concatenation
(O(n log^2 n) bitonic).  Callers supply ``run_lens``, the per-input entry
counts (see ``formats.concat_images(..., with_runs=True)``); see
docs/compaction.md for the plumbing contract.

Values are touched exactly once (the phase-3 gather): the sort operates on
tuples whose last lane is the pair-buffer offset, which is the paper's
``<K, V_offset>`` lightweight-sort mechanism.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import formats
from repro.core.formats import SSTGeometry, SSTImage
from repro.kernels import ops, ref


class CompactionStats(NamedTuple):
    n_input: jax.Array     # live entries in
    n_live: jax.Array      # entries out
    n_dropped: jax.Array   # stale/shadowed/tombstone-collected entries
    crc_ok: jax.Array      # bool: all input blocks verified
    bytes_in: jax.Array    # wire bytes read
    bytes_out: jax.Array   # wire bytes written (live blocks only)


class Unpacked(NamedTuple):
    keys: jax.Array   # uint32 [N, L] fully restored user keys
    meta: jax.Array   # uint32 [N]
    vals: jax.Array   # uint32 [N, Vw]  (the KV pair buffer)
    valid: jax.Array  # bool   [N]
    crc_ok: jax.Array  # bool [n_blocks]


# ---------------------------------------------------------------------------
# Phase 1: unpack
# ---------------------------------------------------------------------------


def unpack(img: SSTImage, geom: SSTGeometry, *,
           backend: str = "auto") -> Unpacked:
    b, k, lanes = img.keys.shape
    crc_ok = ops.crc32_sections(formats.wire_sections(img),
                                backend=backend) == img.crc
    keys = ops.prefix_decode(
        img.shared.reshape(b * k), img.keys.reshape(b * k, lanes),
        restart_interval=geom.restart_interval)
    valid = formats.entry_validity(img).reshape(b * k)
    return Unpacked(keys=keys, meta=img.meta.reshape(b * k),
                    vals=img.vals.reshape(b * k, -1), valid=valid,
                    crc_ok=crc_ok)


# ---------------------------------------------------------------------------
# Phase 2: delete + sort (lightweight tuples)
# ---------------------------------------------------------------------------


def build_tuples(up: Unpacked) -> jax.Array:
    """``<K, ~meta, V_offset>`` rows; padding rows get the all-ones key so
    they sort to the end."""
    n, lanes = up.keys.shape
    keys = jnp.where(up.valid[:, None], up.keys,
                     jnp.uint32(0xFFFFFFFF))
    inv_meta = ~up.meta  # descending seq within equal keys
    idx = jnp.arange(n, dtype=jnp.uint32)
    return jnp.concatenate([keys, inv_meta[:, None], idx[:, None]], axis=1)


def cooperative_sort(rows: jax.Array) -> jax.Array:
    """Paper-faithful phase 2: ship tuples to the host, sort there, ship the
    order back (LUDA's *cooperative sort mechanism*).  Expressed as a
    ``pure_callback`` so it stays inside the jitted pipeline and the
    host round trip is visible to XLA as a data dependency."""
    import numpy as np

    def host_sort(r):
        # materialize on the host first: indexing a jax.Array here would
        # dispatch primitives from the callback thread, racing the main
        # thread's dispatch (observed livelock under pytest)
        r = np.asarray(r)
        order = np.lexsort(tuple(r[:, lane]
                                 for lane in reversed(range(r.shape[1]))))
        return np.ascontiguousarray(r[order])

    return jax.pure_callback(
        host_sort, jax.ShapeDtypeStruct(rows.shape, rows.dtype), rows,
        vmap_method="sequential")


def sort_phase(rows: jax.Array, *, sort_mode: str, backend: str = "auto",
               run_lens: tuple[int, ...] | None = None) -> jax.Array:
    """Order the phase-2 tuples.  ``"merge"`` consumes ``run_lens`` (the
    per-input-SST entry counts; each run is sorted by construction after
    ``build_tuples`` since SST blocks are key-ordered and padding rows
    carry the all-ones sentinel key) -- ``None`` means one sorted run.
    The other modes ignore run structure and re-sort everything."""
    if sort_mode == "merge":
        return ops.merge_runs(rows, run_lens, backend=backend)
    if sort_mode == "cooperative":
        return cooperative_sort(rows)
    if sort_mode == "device":
        return ops.sort_tuples(rows, backend=backend)
    if sort_mode == "xla":
        return ref.sort_tuples(rows, rows.shape[1])
    raise ValueError(f"unknown sort_mode {sort_mode!r}")


def survivor_mask(rows: jax.Array, valid: jax.Array, key_lanes: int,
                  bottom_level) -> jax.Array:
    """Phase-2 delete logic on sorted tuples: keep the newest version of
    each user key; drop shadowed versions; collect tombstones only at the
    bottom level (older levels must keep them to shadow deeper data).
    ``bottom_level`` is a traced boolean, so one program serves jobs at
    every level."""
    keys_s = rows[:, :key_lanes]
    meta = ~rows[:, key_lanes]
    idx = rows[:, key_lanes + 1].astype(jnp.int32)
    valid_s = valid[idx]
    neq_prev = jnp.any(keys_s != jnp.roll(keys_s, 1, axis=0), axis=1)
    first = neq_prev | (jnp.arange(rows.shape[0]) == 0)
    return valid_s & first & (formats.meta_is_value(meta)
                              | jnp.logical_not(bottom_level))


# ---------------------------------------------------------------------------
# Phase 3: pack
# ---------------------------------------------------------------------------


def pack(rows: jax.Array, live: jax.Array, vals: jax.Array,
         geom: SSTGeometry, *, backend: str = "auto") -> SSTImage:
    n, _ = rows.shape
    lanes = geom.key_lanes
    k = geom.block_kvs
    n_blocks = n // k

    # compact survivors to the front (static shapes; out-of-range dropped)
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    tgt = jnp.where(live, pos, n)
    count = jnp.where(live, 1, 0).sum()

    keys_c = jnp.zeros((n, lanes), jnp.uint32).at[tgt].set(
        rows[:, :lanes], mode="drop")
    meta_c = jnp.zeros((n,), jnp.uint32).at[tgt].set(
        ~rows[:, lanes], mode="drop")
    src_idx = rows[:, lanes + 1].astype(jnp.int32)
    # lazy value movement: single gather from the pair buffer, then scatter
    # into the compacted layout.
    vals_c = jnp.zeros_like(vals).at[tgt].set(vals[src_idx], mode="drop")

    slot = jnp.arange(n)
    valid_c = slot < count

    # shared_key kernel on the compacted keys
    shared = ops.prefix_encode(keys_c, restart_interval=geom.restart_interval,
                               backend=backend)
    shared = jnp.where(valid_c, shared, 0).astype(jnp.int32)
    # zero the shared prefix bytes in u32 lane space: the canonical
    # compressed representation (no byte-expansion round trip)
    keys_wire = formats.zero_prefix_lanes(keys_c, shared)
    keys_wire = jnp.where(valid_c[:, None], keys_wire, 0)
    meta_c = jnp.where(valid_c, meta_c, 0)

    nvalid = jnp.clip(count - jnp.arange(n_blocks) * k, 0, k).astype(jnp.int32)

    img = SSTImage(
        keys=keys_wire.reshape(n_blocks, k, lanes),
        meta=meta_c.reshape(n_blocks, k),
        vals=vals_c.reshape(n_blocks, k, -1),
        shared=shared.reshape(n_blocks, k),
        nvalid=nvalid,
        crc=jnp.zeros((n_blocks,), jnp.uint32),
        bloom=jnp.zeros((1, 1), jnp.uint32),
    )
    # encode kernel: CRC over the wire form (sectioned -- no concat copy)
    crc = ops.crc32_sections(formats.wire_sections(img), backend=backend)

    # filter kernel: bloom per block or per SST on *restored* keys
    groups, per = geom.filter_groups(n)
    gk = keys_c.reshape(groups, per, lanes)
    gv = valid_c.reshape(groups, per)
    bloom = ops.bloom_build(gk, gv.astype(jnp.uint32),
                            n_words=geom.bloom_words(per),
                            n_probes=geom.bloom_probes, backend=backend)
    return img._replace(crc=crc, bloom=bloom)


# ---------------------------------------------------------------------------
# End-to-end pipeline
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("geom", "sort_mode",
                                             "backend", "run_lens"))
def compact(img: SSTImage, bottom_level=False, *, geom: SSTGeometry,
            sort_mode: str = "device", backend: str = "auto",
            run_lens: tuple[int, ...] | None = None
            ) -> tuple[SSTImage, CompactionStats]:
    """Run one full compaction over the concatenated input image.

    ``bottom_level`` (traced boolean: tombstones are collected) is data,
    not part of the jit cache key.

    ``run_lens`` (static, entries per input SST; only consumed by
    ``sort_mode="merge"``) preserves the sorted-run structure of the
    concatenation; it is part of the jit cache key, so callers lay runs
    out in pow2 run slots (``offload.slot_layout``).  Merge mode
    *requires* it -- the input image is normally a concatenation of runs,
    and silently treating it as one sorted run would corrupt the output
    (use ``formats.concat_images(..., with_runs=True)``; a genuinely
    single-run input is ``run_lens=(n_entries,)``)."""
    if sort_mode == "merge" and run_lens is None:
        raise ValueError(
            'sort_mode="merge" requires run_lens (the per-input entry '
            "counts; see formats.concat_images(..., with_runs=True))")
    # one named scope per phase: the device profile and the HLO op
    # metadata name each operation's phase
    with jax.named_scope("unpack"):
        up = unpack(img, geom, backend=backend)
    with jax.named_scope("tuples"):
        rows = build_tuples(up)
    with jax.named_scope("merge" if sort_mode == "merge" else "sort"):
        rows_s = sort_phase(rows, sort_mode=sort_mode, backend=backend,
                            run_lens=run_lens)
    with jax.named_scope("survivors"):
        live = survivor_mask(rows_s, up.valid, geom.key_lanes, bottom_level)
    with jax.named_scope("pack"):
        out = pack(rows_s, live, up.vals, geom, backend=backend)

    n_in = up.valid.sum()
    n_live = live.sum()
    wire_bytes = geom.wire_words_per_block * 4
    live_blocks_out = (out.nvalid > 0).sum()
    stats = CompactionStats(
        n_input=n_in, n_live=n_live, n_dropped=n_in - n_live,
        crc_ok=up.crc_ok.all(),
        bytes_in=jnp.int64(img.n_blocks) * wire_bytes
        if jax.config.jax_enable_x64 else jnp.int32(img.n_blocks) * wire_bytes,
        bytes_out=live_blocks_out * wire_bytes,
    )
    return out, stats
