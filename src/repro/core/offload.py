"""Device offload executor + range-partitioned (multi-chip) compaction.

``CompactionExecutor`` is the host-facing object the LSM store talks to: it
owns the sort-mode / backend configuration, dispatches jitted compactions
asynchronously (JAX dispatch is async by construction -- the host thread is
free as soon as the computation is enqueued, mirroring LUDA's
CPU-as-coordinator role), and exposes the split D2H transfer of Fig. 6(b):
data blocks can be fetched before the filter blocks finish.

``sharded_compact`` scales the paper's single-GPU design to a pod: a mesh
axis carries disjoint key-range partitions and each device runs one LUDA
pipeline on its shard (compaction is embarrassingly parallel across ranges;
the only cross-device traffic is the stats reduction).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import compaction
from repro.core.formats import SSTGeometry, SSTImage


@dataclasses.dataclass
class CompactionExecutor:
    """Host handle for device-offloaded compactions.

    ``sort_mode="merge"`` (the default) is run-aware: ``compact`` lays
    the input images out one per run slot (``slot_layout``) and threads
    the slot lengths through the pipeline, so callers must pass one
    *sorted* image per input SST (every SST written by this codebase is;
    see docs/compaction.md for the contract).  ``debug_check_runs=True``
    (or env ``REPRO_CHECK_RUNS=1``) host-verifies that precondition on
    every job.
    """
    geom: SSTGeometry
    sort_mode: str = "merge"       # "merge" | "device" | "cooperative" | "xla"
    backend: str = "auto"          # kernel backend selection
    debug_check_runs: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "REPRO_CHECK_RUNS", "").strip().lower()
        in ("1", "true", "yes", "on"))

    def compact(self, images: list[SSTImage], *, bottom_level: bool = False
                ) -> tuple[SSTImage, compaction.CompactionStats]:
        """Compact one job's input set: stage it in its run slots and
        launch it."""
        img, run_lens, _ = self.stage(
            [images], *run_slots([im.keys.shape[0] for im in images]))
        return self.launch(img, run_lens, bottom_level=bottom_level)

    def stage(self, jobs: list[list[SSTImage]], slots: int,
              slot_blocks: int) -> tuple[SSTImage, tuple[int, ...], int]:
        """Lay the jobs' runs out in run slots on the host
        (``slot_layout``) and copy each field to the device once, stacked
        on a leading job axis when there are several jobs, so no device
        operation before the launch sees another shape than the launch's.
        Returns ``(image, run_lens, bytes staged)``."""
        host, run_lens = slot_layout(jobs, self.geom, slots, slot_blocks)
        if len(jobs) == 1:
            host = SSTImage(*(a[0] for a in host))
        return (SSTImage(*(jnp.asarray(a) for a in host)), run_lens,
                sum(a.nbytes for a in host))

    def launch(self, img: SSTImage, run_lens: tuple[int, ...], *,
               bottom_level: bool = False
               ) -> tuple[SSTImage, compaction.CompactionStats]:
        """One compaction over a staged slot image (``slot_layout``)."""
        if self.debug_check_runs and self.sort_mode == "merge":
            self._check_runs(img, run_lens)
        return compaction.compact(
            img, np.bool_(bottom_level), geom=self.geom,
            sort_mode=self.sort_mode, backend=self.backend,
            run_lens=run_lens if self.sort_mode == "merge" else None)

    def _check_runs(self, img: SSTImage, run_lens: tuple[int, ...]):
        """Debug path: assert every input run's phase-2 tuples are sorted
        (eager, outside the jitted pipeline)."""
        from repro.kernels import merge_path
        up = compaction.unpack(img, self.geom, backend=self.backend)
        rows = compaction.build_tuples(up)
        merge_path.assert_runs_sorted(rows, run_lens)

    def launch_many(self, img: SSTImage, run_lens: tuple[int, ...], *,
                    bottom_levels
                    ) -> tuple[SSTImage, compaction.CompactionStats]:
        """Several jobs in one stacked device launch: ``img`` is
        ``slot_layout``'s image of J jobs, every field ``[J, ...]``, and
        ``bottom_levels`` the J jobs' bottom-level flags.  Returns the
        stacked output image and per-job stats, each lane bit-identical
        to ``launch`` of that job alone: ``vmap`` runs the same integer
        pipeline per batch lane."""
        if self.debug_check_runs and self.sort_mode == "merge":
            for j in range(img.keys.shape[0]):
                self._check_runs(SSTImage(*(a[j] for a in img)), run_lens)
        return compact_batch(
            img, np.asarray(bottom_levels, np.bool_), geom=self.geom,
            sort_mode=self.sort_mode, backend=self.backend,
            run_lens=run_lens if self.sort_mode == "merge" else None)

    def compact_overlapped(self, images: list[SSTImage], *,
                           bottom_level: bool = False):
        """Fig. 6(b): yield the data-block arrays first (they are ready
        before the filter kernel output), then the filter blocks.  Callers
        can begin serializing data blocks while blooms build."""
        out, stats = self.compact(images, bottom_level=bottom_level)
        data_part = (out.keys, out.meta, out.vals, out.shared, out.nvalid,
                     out.crc)
        for a in data_part:
            a.block_until_ready()
        yield ("data", data_part)
        out.bloom.block_until_ready()
        yield ("bloom", out.bloom)
        yield ("stats", jax.tree.map(lambda x: x.block_until_ready(), stats))

    def build_image(self, keys, meta, vals) -> SSTImage:
        """Build a fresh SST image from sorted entries (memtable flush path;
        SST generation itself is offloaded, as in the paper)."""
        return build_image(keys, meta, vals, geom=self.geom,
                           backend=self.backend)


@functools.partial(jax.jit, static_argnames=("geom", "sort_mode",
                                             "backend", "run_lens"))
def compact_batch(img: SSTImage, bottom_level, *, geom: SSTGeometry,
                  sort_mode: str = "device", backend: str = "auto",
                  run_lens: tuple[int, ...] | None = None):
    """One stacked device launch over a leading *job* axis.

    ``img`` holds J independent compaction jobs stacked on axis 0 (every
    field is ``[J, ...]`` of one job's shape), ``bottom_level`` their J
    boolean flags.  Compaction procedures are data-independent (the
    paper's core scaling argument), so the whole batch is a single
    ``vmap`` over the job axis: one dispatch, one jit cache entry per
    (job count, run-slot layout), J jobs of occupancy.
    Returns the stacked output image plus per-job ``CompactionStats``
    (``crc_ok`` stays a per-job verdict -- one corrupt input must not
    taint its batch mates)."""
    def one(im: SSTImage, bottom):
        return compaction.compact(
            im, bottom, geom=geom, sort_mode=sort_mode, backend=backend,
            run_lens=run_lens)
    return jax.vmap(one)(img, bottom_level)


@functools.partial(jax.jit, static_argnames=("geom", "backend"))
def build_image(keys: jax.Array, meta: jax.Array, vals: jax.Array,
                n_live: jax.Array | None = None, *,
                geom: SSTGeometry, backend: str = "auto") -> SSTImage:
    """Pack already-sorted entries into a wire SST image (reuses phase 3).

    ``n_live``: traced count of real rows (callers may pad the arrays to a
    bucketed size to stabilize jit shapes; padding rows must sort last and
    are ignored)."""
    n = keys.shape[0]
    k = geom.block_kvs
    n_pad = max(k, -(-n // k) * k)
    keys = jnp.pad(keys.astype(jnp.uint32), ((0, n_pad - n), (0, 0)))
    meta = jnp.pad(meta.astype(jnp.uint32), (0, n_pad - n))
    vals = jnp.pad(vals.astype(jnp.uint32), ((0, n_pad - n), (0, 0)))
    rows = jnp.concatenate([
        keys, (~meta)[:, None],
        jnp.arange(n_pad, dtype=jnp.uint32)[:, None]], axis=1)
    live = jnp.arange(n_pad) < (n if n_live is None else n_live)
    return compaction.pack(rows, live, vals, geom, backend=backend)


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def run_slots(block_counts) -> tuple[int, int]:
    """``(slots, slot_blocks)`` of a job's run-slot layout: a power-of-two
    number of slots, one per input run, each a power-of-two number of
    blocks that holds the largest run.  The jit signature of a launch
    depends on this pair alone: jobs of one class present identical
    array shapes and identical static ``run_lens``, so they reuse one
    compiled program, and several of them stack into one vmapped launch
    (``DeviceCompactionEngine.compact_many``)."""
    return next_pow2(len(block_counts)), next_pow2(max(block_counts))


def slot_layout(jobs: list[list[SSTImage]], geom: SSTGeometry, slots: int,
                slot_blocks: int) -> tuple[SSTImage, tuple[int, ...]]:
    """Lay out the input runs of one or more jobs on the host.

    Returns a numpy image whose fields are ``[J, slots * slot_blocks,
    ...]`` (J = ``len(jobs)``) and the launch's ``run_lens``
    (``(slot_blocks * block_kvs,) * slots``).  Run ``i`` of job ``j``
    fills the front of slot ``i`` in input order; the rest of each slot,
    and every slot past the job's runs, are empty blocks (``nvalid`` 0)
    carrying the CRC of an all-zero wire block, so phase-1 verification
    passes.  Their rows get the all-ones sentinel key in
    ``build_tuples`` and sort last inside their slot, so every slot is a
    sorted run and the merge stays exact.  The input filters are not
    read by the pipeline: ``bloom`` is a ``[J, 1, 1]`` placeholder."""
    from repro.kernels import tables
    total = slots * slot_blocks
    k, lanes, vw = geom.block_kvs, geom.key_lanes, geom.value_words
    n = len(jobs)
    zero_crc = tables.crc32_zero_message(geom.wire_words_per_block * 4)
    img = SSTImage(
        keys=np.zeros((n, total, k, lanes), np.uint32),
        meta=np.zeros((n, total, k), np.uint32),
        vals=np.zeros((n, total, k, vw), np.uint32),
        shared=np.zeros((n, total, k), np.int32),
        nvalid=np.zeros((n, total), np.int32),
        crc=np.full((n, total), zero_crc, np.uint32),
        bloom=np.zeros((n, 1, 1), np.uint32))
    for j, images in enumerate(jobs):
        if len(images) > slots:
            raise ValueError(f"{len(images)} runs do not fit {slots} slots")
        for i, im in enumerate(images):
            b = im.keys.shape[0]
            if b > slot_blocks:
                raise ValueError(f"a run of {b} blocks does not fit a "
                                 f"slot of {slot_blocks}")
            at = i * slot_blocks
            for dst, src in zip(img[:-1], im[:-1]):
                dst[j, at:at + b] = np.asarray(src)
    return img, (slot_blocks * k,) * slots


def sharded_compact(img: SSTImage, mesh: Mesh, axes, *, geom: SSTGeometry,
                    bottom_level: bool = False, sort_mode: str = "device",
                    backend: str = "auto"):
    """Range-partitioned compaction across ``axes`` of ``mesh``.

    ``img`` holds ``n_shards`` concatenated per-range images along the block
    axis (the host partitions SSTs by key range; ranges are disjoint so no
    cross-shard merge is needed -- the paper's single-device pipeline is the
    per-shard unit).  Returns the sharded output image and per-shard stats.

    ``sort_mode="merge"`` is not supported here: per-shard run boundaries
    are not representable through ``shard_map``'s uniform specs, so shards
    re-sort (``device``/``xla``).
    """
    from jax.experimental.shard_map import shard_map

    if sort_mode == "merge":
        raise ValueError(
            'sharded_compact does not support sort_mode="merge": per-shard '
            "run boundaries are not representable through shard_map's "
            'uniform specs; use "device" or "xla"')

    def per_shard(im: SSTImage):
        out, stats = compaction.compact(
            im, bottom_level, geom=geom, sort_mode=sort_mode,
            backend=backend)
        stats = jax.tree.map(lambda x: x.reshape(1, *jnp.shape(x)), stats)
        return out, stats

    spec_img = SSTImage(keys=P(axes), meta=P(axes), vals=P(axes),
                        shared=P(axes), nvalid=P(axes), crc=P(axes),
                        bloom=P(axes))
    spec_stats = compaction.CompactionStats(*([P(axes)] * 6))
    fn = shard_map(per_shard, mesh=mesh, in_specs=(spec_img,),
                   out_specs=(spec_img, spec_stats), check_rep=False)
    return fn(img)


def place_sharded(img: SSTImage, mesh: Mesh, axes) -> SSTImage:
    """Device-put an image with its block axis sharded over ``axes``."""
    sh = NamedSharding(mesh, P(axes))
    return SSTImage(*(jax.device_put(a, sh) for a in img))
