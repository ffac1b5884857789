"""Bloom-filter Pallas kernels: construction (phase 3 ``filter`` kernel)
and the batched read path's pairwise probe.

LUDA's ``filter`` CUDA kernels build one bloom block per SST.  A bit-scatter
is pathological on TPU, so the adaptation builds the bitmap word by word:
for every (key, probe) it compares the probe's word index with the word
and ORs in ``1 << bit`` -- compare/select/OR, all VPU.

Layout: the groups (filters) of a tile lie along the vector lanes and each
key lane is its own plane, so every operation is lane-dense and no value
is ever sliced out of a boolean vector.  The wrappers transpose to and
from the callers' row-major layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common, ref


def _bloom_kernel(keys_ref, valid_ref, out_ref, *, n_probes, n_words):
    lanes = [keys_ref[lane] for lane in range(keys_ref.shape[0])]  # [K, TG]
    h1, h2 = ref.bloom_hashes_lanes(lanes)
    valid = valid_ref[...]                                # [K, TG] 0/1
    m_bits = jnp.uint32(n_words * 32)
    widx, bits = [], []
    for i in range(n_probes):
        pos = (h1 + jnp.uint32(i) * h2) % m_bits
        widx.append(pos >> jnp.uint32(5))
        bits.append((valid << (pos & jnp.uint32(31))))    # 0 if padded
    for w in range(n_words):
        acc = jnp.zeros_like(h1)
        for i in range(n_probes):
            acc = acc | jnp.where(widx[i] == jnp.uint32(w), bits[i],
                                  jnp.uint32(0))
        out_ref[w:w + 1, :] = common.fold(acc, 0, jnp.bitwise_or)


@functools.partial(jax.jit, static_argnames=(
    "n_words", "n_probes", "group_tile", "interpret"))
def bloom_build(keys: jax.Array, valid: jax.Array, *, n_words: int,
                n_probes: int, group_tile: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """Build bloom filters on device.

    ``keys``: uint32 ``[groups, keys_per_group, lanes]``;
    ``valid``: uint32/bool ``[groups, keys_per_group]`` (0 = padded slot).
    Returns uint32 ``[groups, n_words]``.
    """
    interpret = common.resolve_interpret(interpret, "bloom_build")
    g, k, lanes = keys.shape
    tg, gp = common.lane_tile(g, group_tile)
    keys_t = jnp.transpose(keys.astype(jnp.uint32), (2, 1, 0))  # [L, K, G]
    valid_t = (valid != 0).astype(jnp.uint32).T                 # [K, G]
    if gp != g:   # valid=0 -> padded groups build empty filters
        keys_t = jnp.pad(keys_t, ((0, 0), (0, 0), (0, gp - g)))
        valid_t = jnp.pad(valid_t, ((0, 0), (0, gp - g)))
    out = pl.pallas_call(
        functools.partial(_bloom_kernel, n_probes=n_probes, n_words=n_words),
        grid=(gp // tg,),
        in_specs=[
            pl.BlockSpec((lanes, k, tg), lambda i: (0, 0, i)),
            pl.BlockSpec((k, tg), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n_words, tg), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_words, gp), jnp.uint32),
        interpret=interpret,
        name="bloom_build",
    )(keys_t, valid_t)
    return out[:, :g].T


def _multi_probe_kernel(filters_ref, keys_ref, out_ref, *, n_probes,
                        n_words):
    lanes = [keys_ref[lane:lane + 1, :] for lane in range(keys_ref.shape[0])]
    h1, h2 = ref.bloom_hashes_lanes(lanes)                # [1, TC]
    m_bits = jnp.uint32(n_words * 32)
    ok = jnp.ones_like(h1)
    for i in range(n_probes):
        pos = (h1 + jnp.uint32(i) * h2) % m_bits
        widx = pos >> jnp.uint32(5)
        # gather each candidate's probed word: one select per filter word
        word = jnp.zeros_like(h1)
        for w in range(n_words):
            word = word | jnp.where(widx == jnp.uint32(w),
                                    filters_ref[w:w + 1, :], jnp.uint32(0))
        ok = ok & (word >> (pos & jnp.uint32(31)))
    out_ref[...] = ok & jnp.uint32(1)


@functools.partial(jax.jit, static_argnames=("n_probes", "cand_tile",
                                             "interpret"))
def multi_probe(filters: jax.Array, keys: jax.Array, *, n_probes: int,
                cand_tile: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """Pairwise membership probe: key row ``i`` against filter row ``i``.

    The batched read path stacks one filter row per lookup candidate and
    prunes the whole candidate set in a single launch.  ``filters``:
    uint32 ``[C, W]``; ``keys``: uint32 ``[C, lanes]``.  Returns bool
    ``[C]`` (True = maybe present)."""
    interpret = common.resolve_interpret(interpret, "multi_probe")
    c, lanes = keys.shape
    n_words = filters.shape[-1]
    tc, cp = common.lane_tile(c, cand_tile)
    filters_t = filters.astype(jnp.uint32).T    # [W, C]
    keys_t = keys.astype(jnp.uint32).T          # [L, C]
    if cp != c:   # zero filters -> padded rows report absent
        filters_t = jnp.pad(filters_t, ((0, 0), (0, cp - c)))
        keys_t = jnp.pad(keys_t, ((0, 0), (0, cp - c)))
    out = pl.pallas_call(
        functools.partial(_multi_probe_kernel, n_probes=n_probes,
                          n_words=n_words),
        grid=(cp // tc,),
        in_specs=[
            pl.BlockSpec((n_words, tc), lambda i: (0, i)),
            pl.BlockSpec((lanes, tc), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, cp), jnp.uint32),
        interpret=interpret,
        name="multi_probe",
    )(filters_t, keys_t)
    return out[0, :c] != 0


def bloom_query(filters: jax.Array, keys: jax.Array, *, n_probes: int,
                cand_tile: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """Membership probe on device.  ``filters``: uint32 ``[groups, W]``;
    ``keys``: uint32 ``[groups, queries, lanes]``.  Returns bool
    ``[groups, queries]`` (True = maybe present).  Every query is paired
    with its group's filter row and resolved by ``multi_probe``."""
    g, q, lanes = keys.shape
    rows = jnp.repeat(filters, q, axis=0)
    hit = multi_probe(rows, keys.reshape(g * q, lanes), n_probes=n_probes,
                      cand_tile=cand_tile, interpret=interpret)
    return hit.reshape(g, q)
