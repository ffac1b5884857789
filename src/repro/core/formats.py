"""Device-side SST format: the TPU-native adaptation of LevelDB's table.

TPUs require static shapes, so the device format uses **fixed-width key
lanes** (the paper fixes key size at 16 B in all experiments) and fixed-size
value slots.  Prefix compression is represented by *zeroing* the shared
prefix bytes in the fixed lanes + a per-entry ``shared_len`` word; the CRC
covers this canonical fixed-width serialization, so integrity protection,
shared-key computation, sorting and filter construction -- all of LUDA's
offloaded compute -- run on device.  (Byte-level squeezing of the
fixed-width form into LevelDB's variable-length disk encoding is a host
serialization detail, measured separately; see DESIGN.md §2.)

An SST image is a struct-of-arrays over data blocks:

* ``keys``   uint32 ``[blocks, block_kvs, key_lanes]``   prefix-zeroed keys
* ``meta``   uint32 ``[blocks, block_kvs]``              ``seq << 1 | is_value``
* ``vals``   uint32 ``[blocks, block_kvs, value_words]`` value slots
* ``shared`` int32  ``[blocks, block_kvs]``              shared-prefix bytes
* ``nvalid`` int32  ``[blocks]``                         live entries/block
* ``crc``    uint32 ``[blocks]``                         CRC-32 per block
* ``bloom``  uint32 ``[filter_groups, bloom_words]``     filter block(s)

Keys are big-endian packed so lexicographic uint32-lane order equals byte
order.  The all-ones key is reserved as the padding sentinel.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SSTGeometry:
    """Static geometry shared by every SST in a store (paper defaults:
    16 B keys, 4 KB data blocks, 4 MB SSTs, 10 bloom bits/key)."""
    key_bytes: int = 16
    value_bytes: int = 256
    block_bytes: int = 4096
    sst_bytes: int = 4 * 1024 * 1024
    restart_interval: int = 16
    bloom_bits_per_key: int = 10
    bloom_granularity: str = "block"  # "block" | "sst"

    def __post_init__(self):
        assert self.key_bytes % 4 == 0 and self.value_bytes % 4 == 0
        # the prefix-encode kernel lays rows out 128 to a lane row, so a
        # restart interval must divide 128 (checked here for both engines)
        if self.restart_interval < 1 or 128 % self.restart_interval:
            raise ValueError(f"restart_interval={self.restart_interval} "
                             "must divide 128")

    @property
    def key_lanes(self) -> int:
        return self.key_bytes // 4

    @property
    def value_words(self) -> int:
        return self.value_bytes // 4

    @property
    def entry_bytes(self) -> int:
        # key + meta word + value slot + shared word
        return self.key_bytes + 4 + self.value_bytes + 4

    @property
    def block_kvs(self) -> int:
        n = self.block_bytes // self.entry_bytes
        # multiple of the restart interval so blocks start at restart points
        n = max(self.restart_interval,
                n // self.restart_interval * self.restart_interval)
        return n

    @property
    def blocks_per_sst(self) -> int:
        return max(1, self.sst_bytes // self.block_bytes)

    @property
    def sst_kvs(self) -> int:
        return self.block_kvs * self.blocks_per_sst

    @property
    def bloom_probes(self) -> int:
        # LevelDB: k = bits_per_key * ln2, capped
        return max(1, min(30, int(self.bloom_bits_per_key * 0.69)))

    def bloom_words(self, keys_per_group: int) -> int:
        bits = max(64, keys_per_group * self.bloom_bits_per_key)
        return (bits + 31) // 32

    @property
    def wire_words_per_block(self) -> int:
        """uint32 words per block covered by the CRC (header + payload)."""
        k = self.block_kvs
        return 1 + k * self.key_lanes + k + k * self.value_words + k


class SSTImage(NamedTuple):
    """Struct-of-arrays device image of one-or-more SSTs (see module doc)."""
    keys: jax.Array    # uint32 [B, K, L]  prefix-zeroed
    meta: jax.Array    # uint32 [B, K]
    vals: jax.Array    # uint32 [B, K, Vw]
    shared: jax.Array  # int32  [B, K]
    nvalid: jax.Array  # int32  [B]
    crc: jax.Array     # uint32 [B]
    bloom: jax.Array   # uint32 [G, W]

    @property
    def n_blocks(self) -> int:
        return self.keys.shape[0]

    @property
    def n_entries(self) -> int:
        return self.keys.shape[0] * self.keys.shape[1]


VALUE_TYPE = 1
DELETE_TYPE = 0


def make_meta(seq, is_value) -> jax.Array:
    return (jnp.uint32(seq) << jnp.uint32(1)) | jnp.uint32(is_value)


def meta_seq(meta: jax.Array) -> jax.Array:
    return meta >> jnp.uint32(1)


def meta_is_value(meta: jax.Array) -> jax.Array:
    return (meta & jnp.uint32(1)) == 1


def wire_words(img: SSTImage) -> jax.Array:
    """Serialize each block to its CRC-covered uint32 word row
    ``[blocks, wire_words_per_block]``."""
    b, k, lanes = img.keys.shape
    vw = img.vals.shape[-1]
    return jnp.concatenate([
        img.nvalid.astype(jnp.uint32)[:, None],
        img.keys.reshape(b, k * lanes),
        img.meta,
        img.vals.reshape(b, k * vw),
        img.shared.astype(jnp.uint32),
    ], axis=-1)


def wire_sections(img: SSTImage) -> list:
    """The same CRC-covered serialization as ``wire_words`` but as a list
    of per-block sections -- the sectioned CRC kernel consumes these
    without materializing the concatenated copy (one full image pass of
    HBM traffic saved; EXPERIMENTS.md §Perf compaction it.1)."""
    b, k, lanes = img.keys.shape
    vw = img.vals.shape[-1]
    return [
        img.nvalid.astype(jnp.uint32)[:, None],
        img.keys.reshape(b, k * lanes),
        img.meta,
        img.vals.reshape(b, k * vw),
        img.shared.astype(jnp.uint32),
    ]


def zero_prefix_lanes(keys: jax.Array, shared: jax.Array) -> jax.Array:
    """Zero the first ``shared[i]`` bytes of each big-endian-lane key
    directly in u32 lane space (no 4x byte-expansion round trip)."""
    lanes = keys.shape[-1]
    i4 = 4 * jnp.arange(lanes)
    nz = jnp.clip(shared[:, None] - i4[None, :], 0, 4).astype(jnp.uint32)
    mask = jnp.where(nz >= 4, jnp.uint32(0),
                     jnp.uint32(0xFFFFFFFF) >> (jnp.uint32(8) * nz))
    return keys.astype(jnp.uint32) & mask


def concat_images(images: list[SSTImage], *, with_runs: bool = False):
    """Concatenate SST images along the block axis (compaction input set).

    ``with_runs=True`` additionally returns the per-input run lengths in
    *entries* (``blocks * block_kvs`` each): every input SST is a sorted
    run, and the run-aware merge sort path (``sort_mode="merge"``) needs
    those boundaries -- a plain concatenation destroys them.
    """
    img = SSTImage(*(jnp.concatenate(parts, axis=0)
                     for parts in zip(*images)))
    if with_runs:
        run_lens = tuple(im.keys.shape[0] * im.keys.shape[1]
                         for im in images)
        return img, run_lens
    return img


def entry_validity(img: SSTImage) -> jax.Array:
    """bool [B, K]: which slots hold live entries."""
    k = img.keys.shape[1]
    return jnp.arange(k)[None, :] < img.nvalid[:, None]


# ---------------------------------------------------------------------------
# Host-side helpers (numpy; used by the store shim and tests)
# ---------------------------------------------------------------------------


def pack_key_bytes(key: bytes, key_bytes: int) -> np.ndarray:
    """Pack a user key (<= key_bytes, zero padded) into big-endian uint32
    lanes so lane order == byte order.

    Keys may not end with a NUL byte: the fixed-width device format pads
    with zeros, so the padded form is only reversible under that rule
    (enforced at the DB API)."""
    assert len(key) <= key_bytes, "key too long for geometry"
    assert not key.endswith(b"\x00"), "keys must not end with NUL"
    raw = key.ljust(key_bytes, b"\x00")
    return np.frombuffer(raw, dtype=">u4").astype(np.uint32)


def unpack_key_bytes(lanes: np.ndarray) -> bytes:
    return lanes.astype(">u4").tobytes()


def pack_value_bytes(value: bytes, value_bytes: int) -> np.ndarray:
    """Length-prefixed value in fixed uint32 slots (little-endian words)."""
    assert len(value) <= value_bytes - 4, "value too long for geometry"
    raw = len(value).to_bytes(4, "little") + value
    raw = raw.ljust(value_bytes, b"\x00")
    return np.frombuffer(raw, dtype="<u4").astype(np.uint32)


def unpack_value_bytes(words: np.ndarray) -> bytes:
    raw = words.astype("<u4").tobytes()
    n = int.from_bytes(raw[:4], "little")
    return raw[4:4 + n]
