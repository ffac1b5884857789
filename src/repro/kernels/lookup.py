"""Batched point-lookup Pallas kernel (the read-path gather launch).

LUDA's core observation -- per-key procedures are data-independent, so a
wide launch fills the device -- applies to lookups exactly as it does to
compactions.  ``multi_get`` stacks one *candidate* (query key, decoded SST
block) pair per row and resolves every one in a single launch:

* **rank** -- per candidate, the lower bound of the query among the
  block's ``K`` sorted key rows, counted as the number of rows that
  compare below it (the rows are sorted, so this equals a binary search's
  answer).  All ``K`` rows are compared at once: ``O(K * L)`` vector work
  with K = keys per block (small by geometry) and no data-dependent row
  gather, which is pathological on the VPU.
* **gather** -- a one-hot select of the ranked row's meta word and value
  slot, OR-folded over the rows and masked by the found verdict.

Layout: candidates lie along the vector lanes, and every key lane and
value word is its own ``[K, C]`` plane, so every vector is dense and all
compares and selects are on ``uint32``.  The wrapper transposes to and
from the callers' row-major layout.  Grid is 1-D over candidate tiles;
VMEM per tile is ``TC * K * (L + Vw + 1)`` words, independent of the
candidate count.

Sentinel contract (matches ``merge_path.PAD_WORD``): block rows at or
beyond ``nvalid`` must hold all-ones keys so the per-block order is total;
padded candidate rows carry ``nvalid = 0`` and therefore report not-found.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

PAD_WORD = jnp.uint32(0xFFFFFFFF)


def _lookup_kernel(keys_ref, meta_ref, vals_ref, nvalid_ref, q_ref,
                   found_ref, meta_out_ref, val_out_ref):
    n_lanes, n_kvs, tc = keys_ref.shape
    zero = jnp.uint32(0)
    below = jnp.zeros((n_kvs, tc), jnp.int32)   # row < query
    equal = jnp.ones((n_kvs, tc), jnp.int32)    # row == query so far
    for lane in range(n_lanes):
        row = keys_ref[lane]                    # [K, TC]
        q = q_ref[lane:lane + 1, :]             # [1, TC]
        below = jnp.maximum(below, equal * (row < q).astype(jnp.int32))
        equal = equal * (row == q).astype(jnp.int32)
    rank = jnp.sum(below, axis=0, keepdims=True)              # [1, TC]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (n_kvs, tc), 0)
    match = common.fold(jnp.where(iota_k == rank, equal, 0), 0,
                        jnp.bitwise_or)
    found = match * (rank < nvalid_ref[...]).astype(jnp.int32)  # [1, TC]
    pick = iota_k == jnp.where(found != 0, rank, -1)  # <= 1 row per lane
    found_ref[...] = found.astype(jnp.uint32)
    meta_out_ref[...] = common.fold(jnp.where(pick, meta_ref[...], zero), 0,
                                    jnp.bitwise_or)
    for w in range(vals_ref.shape[0]):
        val_out_ref[w:w + 1, :] = common.fold(
            jnp.where(pick, vals_ref[w], zero), 0, jnp.bitwise_or)


@functools.partial(jax.jit, static_argnames=("cand_tile", "interpret"))
def lookup_blocks(keys: jax.Array, meta: jax.Array, vals: jax.Array,
                  nvalid: jax.Array, queries: jax.Array, *,
                  cand_tile: int = 512, interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Resolve C stacked (query, block) candidates in one launch.

    Shapes/contract as ``ref.lookup_blocks`` (rows >= ``nvalid`` must be
    all-ones sentinels).  Returns ``(found bool [C], meta uint32 [C],
    value uint32 [C, Vw])``, meta/value zeroed where not found."""
    interpret = common.resolve_interpret(interpret, "lookup_blocks")
    C, K, L = keys.shape
    Vw = vals.shape[-1]
    tc, Cp = common.lane_tile(C, cand_tile)
    keys_t = jnp.transpose(keys.astype(jnp.uint32), (2, 1, 0))   # [L, K, C]
    meta_t = meta.astype(jnp.uint32).T                           # [K, C]
    vals_t = jnp.transpose(vals.astype(jnp.uint32), (2, 1, 0))   # [Vw, K, C]
    nvalid_t = nvalid.astype(jnp.int32).reshape(1, C)
    q_t = queries.astype(jnp.uint32).T                           # [L, C]
    if Cp != C:   # nvalid=0 -> padded candidates are never found
        pad = ((0, 0),) * 2 + ((0, Cp - C),)
        keys_t = jnp.pad(keys_t, pad, constant_values=PAD_WORD)
        vals_t = jnp.pad(vals_t, pad)
        meta_t, nvalid_t, q_t = (jnp.pad(a, ((0, 0), (0, Cp - C)))
                                 for a in (meta_t, nvalid_t, q_t))
    found, m, v = pl.pallas_call(
        _lookup_kernel,
        grid=(Cp // tc,),
        in_specs=[
            pl.BlockSpec((L, K, tc), lambda i: (0, 0, i)),
            pl.BlockSpec((K, tc), lambda i: (0, i)),
            pl.BlockSpec((Vw, K, tc), lambda i: (0, 0, i)),
            pl.BlockSpec((1, tc), lambda i: (0, i)),
            pl.BlockSpec((L, tc), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, tc), lambda i: (0, i)),
            pl.BlockSpec((1, tc), lambda i: (0, i)),
            pl.BlockSpec((Vw, tc), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Cp), jnp.uint32),
            jax.ShapeDtypeStruct((1, Cp), jnp.uint32),
            jax.ShapeDtypeStruct((Vw, Cp), jnp.uint32),
        ],
        interpret=interpret,
    )(keys_t, meta_t, vals_t, nvalid_t, q_t)
    return found[0, :C] != 0, m[0, :C], v[:, :C].T
