"""Shared-key (prefix-compression) encode Pallas kernel (phase 3
``shared_key`` kernel).

Computes, for each sorted key, the byte length of the prefix it shares with
its predecessor, reset at LevelDB restart points.  Fully parallel, in
``int32``/``uint32`` lanes only: per key lane, the XOR with the previous
key's lane gives the equal leading bytes by three threshold compares, and
lanes after the first differing one add nothing.

Layout: each key lane is a plane of rows laid out ``[n / 128, 128]``, so
every vector is dense.  The previous row is a rotate by one lane; the wrap
at lane 0 is harmless because 128 is a multiple of the restart interval,
so lane 0 always holds a restart point, whose length is forced to 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

_LANES = 128


def _prefix_kernel(keys_ref, out_ref, *, restart_interval):
    shape = keys_ref.shape[1:]                       # [TR, 128]
    shared = jnp.zeros(shape, jnp.int32)
    differs = jnp.zeros(shape, jnp.int32)            # 1 once a lane differs
    for lane in range(keys_ref.shape[0]):
        cur = keys_ref[lane]
        x = cur ^ pltpu.roll(cur, 1, 1)              # vs the previous row
        # equal leading bytes of this lane: 4 if x == 0, else clz(x) // 8
        same = ((x < jnp.uint32(1 << 24)).astype(jnp.int32)
                + (x < jnp.uint32(1 << 16)).astype(jnp.int32)
                + (x < jnp.uint32(1 << 8)).astype(jnp.int32)
                + (x == jnp.uint32(0)).astype(jnp.int32))
        shared = shared + same * (1 - differs)
        differs = jnp.maximum(differs, (x != jnp.uint32(0)).astype(jnp.int32))
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    out_ref[...] = jnp.where(col % restart_interval == 0, 0, shared)


@functools.partial(jax.jit, static_argnames=(
    "restart_interval", "row_tile", "interpret"))
def prefix_encode(keys: jax.Array, *, restart_interval: int = 16,
                  row_tile: int = 8192,
                  interpret: bool | None = None) -> jax.Array:
    """Shared-prefix lengths. ``keys``: uint32 ``[n, lanes]`` (sorted);
    returns int32 ``[n]``.  ``n`` must be a multiple of restart_interval,
    and the restart interval must divide 128."""
    interpret = common.resolve_interpret(interpret, "prefix_encode")
    n, lanes = keys.shape
    assert n % restart_interval == 0, "rows must fill restart intervals"
    assert _LANES % restart_interval == 0, "restart interval must divide 128"
    rows = -(-n // _LANES)
    tr = max(8, row_tile // _LANES)
    if rows <= tr:
        tr = rows
    padded = common.round_up(rows, tr)
    keys_t = jnp.pad(keys.astype(jnp.uint32).T,
                     ((0, 0), (0, padded * _LANES - n)))
    out = pl.pallas_call(
        functools.partial(_prefix_kernel, restart_interval=restart_interval),
        grid=(padded // tr,),
        in_specs=[pl.BlockSpec((lanes, tr, _LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tr, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, _LANES), jnp.int32),
        interpret=interpret,
    )(keys_t.reshape(lanes, padded, _LANES))
    return out.reshape(-1)[:n]
