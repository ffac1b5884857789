"""Merge-path k-way tuple merge Pallas kernel (run-aware phase 2).

Compaction inputs are already sorted runs (every input SST is key-ordered,
and padding rows carry the all-ones sentinel key so each run stays sorted
through ``build_tuples``).  Re-sorting the concatenation throws that
structure away; this kernel merges instead: O(n log k) with perfectly
balanced parallel work, against O(n log^2 n) for the bitonic network.

Two-stage merge path (ModernGPU-style):

* **partition** -- for every output chunk boundary, binary-search the
  cross-diagonal of the merge matrix to find the exact (a, b) split whose
  merged prefix has that length.  Vectorized over all diagonals (one XLA
  gather per search step).
* **merge** -- one grid cell per output chunk.  Scalar-prefetched splits
  drive element-offset index maps, so each cell DMAs only its two ``chunk``-row
  windows into VMEM and serially merges an equal-size chunk.  VMEM per cell
  is ``3 * chunk * lanes`` words regardless of n, which removes the bitonic
  path's single-block 2^17-row cap.

Ties break toward the earlier run (``a``), matching a stable sort; callers
append a unique index lane, which makes the order total and the output
bit-identical to ``ref.sort_tuples`` of the concatenation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

# Sentinel rows sort after all real rows (matches bitonic_sort.PAD_WORD).
PAD_WORD = jnp.uint32(0xFFFFFFFF)
# rows per sublane tile: windows must start on a tile boundary
_TILE = 8


def rows_sorted(rows: np.ndarray) -> bool:
    """Host check: rows ``[n, L]`` lexicographically nondecreasing."""
    r = np.ascontiguousarray(np.asarray(rows, np.uint32).astype(">u4"))
    if r.shape[0] <= 1:
        return True
    packed = r.view(f"S{4 * r.shape[1]}").ravel()
    return bool((packed[:-1] <= packed[1:]).all())


def assert_runs_sorted(rows: np.ndarray, run_lens: tuple[int, ...]) -> None:
    """Debug check of the merge-path precondition: every run sorted.
    Raises explicitly (not via ``assert``) so the safety net survives
    ``python -O``."""
    off = 0
    for i, ln in enumerate(run_lens):
        if not rows_sorted(np.asarray(rows)[off:off + ln]):
            raise AssertionError(
                f"run {i} (rows {off}:{off + ln}) is not sorted; "
                "merge-path phase 2 requires sorted input runs")
        off += ln


def _partition(a_p: jax.Array, b_p: jax.Array, na: int, nb: int,
               n_chunks: int, chunk: int) -> jax.Array:
    """Cross-diagonal binary search: for each output diagonal
    ``d = g * chunk`` find ``i`` = rows of ``a`` among the first ``d``
    merged rows (ties to ``a``).  ``a_p``/``b_p`` are sentinel-padded so
    the clamped gathers of inactive search lanes stay in bounds."""
    lanes = a_p.shape[1]
    d = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    lo = jnp.maximum(0, d - nb)
    hi = jnp.minimum(d, na)
    for _ in range(max(1, (na + 1).bit_length())):
        go = lo < hi
        mid = (lo + hi) >> 1
        a_row = a_p[jnp.clip(mid, 0, max(na - 1, 0))]
        bj = d - 1 - mid
        b_row = b_p[jnp.clip(bj, 0, max(nb - 1, 0))]
        # keep taking a while a[mid] <= b[d-1-mid] (a wins ties)
        a_le_b = jnp.logical_not(common.lex_less(b_row, a_row, lanes))
        lo = jnp.where(go & a_le_b, mid + 1, lo)
        hi = jnp.where(go & ~a_le_b, mid, hi)
    return lo


def _merge_kernel(starts_ref, a_ref, b_ref, out_ref, *, chunk, lanes):
    """Serially merge one equal-size output chunk from two VMEM windows.

    Each window begins at the 8-row tile boundary at or below this cell's
    merge-path split and holds ``chunk + 8`` rows, so the merge starts at
    the split's offset inside its tile.  From the split, the first
    ``chunk`` picks of a bounds-free two-way merge are exactly output rows
    ``[g*chunk, (g+1)*chunk)``; window overruns hit sentinel rows, which
    compare greater than everything real.  Rows compare as whole
    ``[1, lanes]`` vectors: the first differing lane (a min over a lane
    iota) decides, and ties go to ``a``."""
    g = pl.program_id(0)
    sa = starts_ref[g]
    sb = g * chunk - sa
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def body(t, carry):
        ia, ib = carry
        a_row = a_ref[pl.ds(ia, 1), :]
        b_row = b_ref[pl.ds(ib, 1), :]
        first = jnp.min(jnp.where(a_row != b_row, lane, lanes))
        take_b = jnp.max(jnp.where(lane == first,
                                   (b_row < a_row).astype(jnp.int32), 0))
        out_ref[pl.ds(t, 1), :] = jnp.where(take_b == 1, b_row, a_row)
        return ia + 1 - take_b, ib + take_b

    jax.lax.fori_loop(0, chunk, body, (sa % _TILE, sb % _TILE))


def _window(split):
    """Element offset of a window: the tile boundary at or below ``split``."""
    return pl.multiple_of(split // _TILE * _TILE, _TILE)


def merge_sorted(a: jax.Array, b: jax.Array, *, chunk: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    """Merge two sorted uint32 row arrays on device via merge path."""
    interpret = common.resolve_interpret(interpret, "merge_path")
    na, nb = a.shape[0], b.shape[0]
    lanes = a.shape[1]
    if na == 0:
        return b
    if nb == 0:
        return a
    total = na + nb
    n_chunks = -(-total // chunk)
    win = chunk + _TILE
    pad = jnp.full((win, lanes), PAD_WORD, jnp.uint32)
    a_p = jnp.concatenate([a.astype(jnp.uint32), pad], axis=0)
    b_p = jnp.concatenate([b.astype(jnp.uint32), pad], axis=0)
    starts = _partition(a_p, b_p, na, nb, n_chunks, chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((pl.Element(win), pl.Element(lanes)),
                         lambda g, s: (_window(s[g]), 0)),
            pl.BlockSpec((pl.Element(win), pl.Element(lanes)),
                         lambda g, s: (_window(g * chunk - s[g]), 0)),
        ],
        out_specs=pl.BlockSpec((chunk, lanes), lambda g, s: (g, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_merge_kernel, chunk=chunk, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_chunks * chunk, lanes), jnp.uint32),
        interpret=interpret,
        name="merge_runs",
    )(starts, a_p, b_p)
    return out[:total]


@functools.partial(jax.jit, static_argnames=("run_lens", "chunk",
                                             "interpret"))
def merge_runs(rows: jax.Array, run_lens: tuple[int, ...], *,
               chunk: int = 256,
               interpret: bool | None = None) -> jax.Array:
    """Merge ``k`` pre-sorted runs stored back to back in ``rows``.

    ``run_lens``: static per-run row counts summing to ``rows.shape[0]``
    (zero-length runs are skipped; ``k=1`` is a passthrough).  Pairwise
    merge tree over ``merge_sorted``: ``ceil(log2 k)`` full passes."""
    if sum(run_lens) != rows.shape[0]:
        raise ValueError(f"run_lens {run_lens} must cover {rows.shape[0]} "
                         "rows")
    offs = np.concatenate([[0], np.cumsum(run_lens)]).astype(int)
    runs = [rows[offs[i]:offs[i + 1]]
            for i in range(len(run_lens)) if run_lens[i] > 0]
    if not runs:
        return rows.astype(jnp.uint32)
    merged = common.tree_merge(
        runs, lambda a, b: merge_sorted(a, b, chunk=chunk,
                                        interpret=interpret))
    return merged.astype(jnp.uint32)
