"""Bit-parallel CRC-32 Pallas kernel (phase 1 + phase 3 checksum hot spot).

LUDA offloads block checksum computation to the accelerator.  On GPU this is
a table-driven byte loop per thread; on TPU we use the GF(2)-linear
formulation (see ``tables.py``): the CRC of a fixed-length block is an XOR
reduction of per-bit operator words -- pure VPU work with no gathers and no
serial dependence.

Grid: one program per tile of blocks.  Each program loads a ``[TB, W]``
uint32 tile plus the shared operator table, stored transposed as
``[32, W]`` so that bit ``j``'s operator words are one lane-dense row, into
VMEM, does 32 shift/mask rounds and one XOR halving tree over the lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common, tables


def _crc32_kernel(words_ref, table_ref, out_ref):
    words = words_ref[...]  # [TB, W] uint32
    acc = jnp.zeros_like(words)
    for j in range(32):
        bit = (words >> jnp.uint32(j)) & jnp.uint32(1)
        # 0 - bit is all ones where the bit is set: a mask, not a select
        acc = acc ^ (table_ref[j:j + 1, :] & (jnp.uint32(0) - bit))
    out_ref[...] = common.fold(acc, 1, jnp.bitwise_xor)  # [TB, 1]


def _raw_contrib(words: jax.Array, T: jax.Array, *, block_tile: int,
                 interpret: bool, name: str) -> jax.Array:
    """XOR-fold of per-bit contributions (no final base xor).  ``T`` is
    the operator table transposed, ``[32, n_words]``; ``name`` is the
    kernel's name on the device (the calling wrapper's)."""
    n_blocks, n_words = words.shape
    tb = min(block_tile, n_blocks)
    padded = common.round_up(n_blocks, tb)
    if padded != n_blocks:
        words = jnp.pad(words, ((0, padded - n_blocks), (0, 0)))
    out = pl.pallas_call(
        _crc32_kernel,
        grid=(padded // tb,),
        in_specs=[
            pl.BlockSpec((tb, n_words), lambda i: (i, 0)),
            pl.BlockSpec((32, n_words), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 1), jnp.uint32),
        interpret=interpret,
        name=name,
    )(words.astype(jnp.uint32), T)
    return out[:n_blocks, 0]


@functools.partial(jax.jit, static_argnames=("block_tile", "interpret"))
def crc32_blocks(words: jax.Array, *, block_tile: int = 8,
                 interpret: bool | None = None) -> jax.Array:
    """CRC-32 of each block.

    ``words``: uint32 ``[n_blocks, n_words]`` (little-endian serialization of
    each block's bytes).  Returns uint32 ``[n_blocks]``, bit-exact with
    ``binascii.crc32`` on each row's bytes.
    """
    interpret = common.resolve_interpret(interpret, "crc32")
    n_words = words.shape[1]
    T = jnp.asarray(tables.crc32_operator_table(n_words).T)
    base = jnp.uint32(tables.crc32_zero_message(n_words * 4))
    return _raw_contrib(words, T, block_tile=block_tile,
                        interpret=interpret, name="crc32_blocks") ^ base


@functools.partial(jax.jit, static_argnames=("block_tile", "interpret"))
def crc32_blocks_sections(sections, *, block_tile: int = 8,
                          interpret: bool | None = None) -> jax.Array:
    """CRC-32 of the *logical concatenation* of per-block sections,
    without materializing the concatenated buffer.

    CRC is GF(2)-affine, so the CRC of ``concat(s_0..s_k)`` is the XOR of
    each section's contributions under its position-offset operator table
    slice, xor the zero-message constant.  Each section streams through
    VMEM once -- the concat copy (one full extra image pass of HBM
    traffic in the compaction pipeline) disappears.

    ``sections``: list of uint32 ``[n_blocks, w_i]``.
    """
    interpret = common.resolve_interpret(interpret, "crc32")
    total = sum(s.shape[1] for s in sections)
    T = jnp.asarray(tables.crc32_operator_table(total).T)
    base = jnp.uint32(tables.crc32_zero_message(total * 4))
    acc = base
    off = 0
    for s in sections:
        w = s.shape[1]
        acc = acc ^ _raw_contrib(s, T[:, off:off + w],
                                 block_tile=block_tile,
                                 interpret=interpret,
                                 name="crc32_blocks_sections")
        off += w
    return acc
