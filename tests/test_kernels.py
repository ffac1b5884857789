"""Per-kernel tests: Pallas interpret-mode vs pure-jnp oracle vs host truth.

The CRC chain is anchored to ``binascii.crc32`` (canonical CRC-32), so an
agreement of kernel == ref == binascii is a proof of bit-exactness.
"""

import binascii

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing.hypo import given, settings, st

from repro.kernels import bitonic_sort, bloom, crc32, prefix, ref

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# CRC-32
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_blocks,n_words", [(1, 4), (3, 16), (8, 64),
                                              (5, 128), (17, 32)])
def test_crc32_matches_binascii(n_blocks, n_words):
    rng = np.random.default_rng(n_blocks * 1000 + n_words)
    words = rng.integers(0, 2**32, size=(n_blocks, n_words), dtype=np.uint32)
    want = np.array(
        [binascii.crc32(row.astype("<u4").tobytes()) & 0xFFFFFFFF
         for row in words], dtype=np.uint32)
    got_ref = np.asarray(ref.crc32_words(jnp.asarray(words)))
    got_pallas = np.asarray(crc32.crc32_blocks(jnp.asarray(words),
                                               interpret=True))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pallas, want)


@given(st.binary(min_size=4, max_size=256))
@settings(max_examples=30, deadline=None)
def test_crc32_property_random_bytes(data):
    # pad to word multiple
    pad = (-len(data)) % 4
    data = data + b"\x00" * pad
    words = np.frombuffer(data, dtype="<u4")[None, :]
    want = binascii.crc32(data) & 0xFFFFFFFF
    got = int(ref.crc32_words(jnp.asarray(words))[0])
    assert got == want


@pytest.mark.parametrize("widths", [(1, 4, 3), (16, 16), (2, 30, 12, 20)])
def test_crc32_sections_match_concat(widths):
    """Sectioned (affine-combined) CRC == CRC of the concatenation."""
    rng = np.random.default_rng(sum(widths))
    parts = [jnp.asarray(rng.integers(0, 2**32, (5, w), dtype=np.uint32))
             for w in widths]
    concat = jnp.concatenate(parts, axis=1)
    want = np.asarray(ref.crc32_words(concat))
    got_ref = np.asarray(ref.crc32_words_sections(parts))
    got_pallas = np.asarray(crc32.crc32_blocks_sections(
        tuple(parts), interpret=True))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pallas, want)


def test_zero_prefix_lanes_matches_byte_path():
    from repro.core import formats
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 2**32, (64, 4), dtype=np.uint32))
    shared = jnp.asarray(rng.integers(0, 17, 64, dtype=np.int32))
    kb = ref.u32_to_bytes(keys)
    pos = jnp.arange(16)
    want = ref.bytes_to_u32(
        jnp.where(pos[None, :] < shared[:, None], 0, kb))
    got = formats.zero_prefix_lanes(keys, shared)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_crc32_detects_corruption():
    rng = np.random.default_rng(0)
    words = rng.integers(0, 2**32, size=(4, 32), dtype=np.uint32)
    good = np.asarray(ref.crc32_words(jnp.asarray(words)))
    corrupted = words.copy()
    corrupted[2, 7] ^= 0x00010000
    bad = np.asarray(ref.crc32_words(jnp.asarray(corrupted)))
    assert bad[2] != good[2]
    assert (bad[[0, 1, 3]] == good[[0, 1, 3]]).all()


# ---------------------------------------------------------------------------
# Bloom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups,keys,lanes,n_words,n_probes",
                         [(1, 16, 4, 8, 7), (4, 64, 4, 16, 7),
                          (3, 33, 2, 4, 5), (2, 500, 4, 64, 7)])
def test_bloom_pallas_matches_ref(groups, keys, lanes, n_words, n_probes):
    rng = np.random.default_rng(42)
    k = jnp.asarray(rng.integers(0, 2**32, (groups, keys, lanes),
                                 dtype=np.uint32))
    valid = jnp.asarray(rng.integers(0, 2, (groups, keys), dtype=np.uint32))
    want = ref.bloom_build(k, n_words=n_words, n_probes=n_probes,
                           valid=valid != 0)
    got = bloom.bloom_build(k, valid, n_words=n_words, n_probes=n_probes,
                            group_tile=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("groups,n_keys,queries,n_words",
                         [(1, 32, 16, 16), (3, 100, 33, 40), (5, 64, 7, 24)])
def test_bloom_query_pallas_matches_ref(groups, n_keys, queries, n_words):
    rng = np.random.default_rng(groups * 100 + queries)
    keys = jnp.asarray(rng.integers(0, 2**32, (groups, n_keys, 4),
                                    dtype=np.uint32))
    filt = ref.bloom_build(keys, n_words=n_words, n_probes=7)
    probe = jnp.asarray(rng.integers(0, 2**32, (groups, queries, 4),
                                     dtype=np.uint32))
    want = np.asarray(ref.bloom_query(filt, probe, n_probes=7))
    got = np.asarray(bloom.bloom_query(filt, probe, n_probes=7,
                                       cand_tile=16, interpret=True))
    np.testing.assert_array_equal(got, want)
    # inserted keys must always hit through the kernel path too
    hit = np.asarray(bloom.bloom_query(filt, keys, n_probes=7,
                                       interpret=True))
    assert hit.all()


def test_bloom_no_false_negatives_and_fpr():
    rng = np.random.default_rng(7)
    n, lanes = 512, 4
    keys = jnp.asarray(rng.integers(0, 2**32, (1, n, lanes), dtype=np.uint32))
    n_words = (n * 10 + 31) // 32   # 10 bits/key, paper setting
    filt = ref.bloom_build(keys, n_words=n_words, n_probes=7)
    hit = ref.bloom_query(filt, keys, n_probes=7)
    assert bool(hit.all()), "bloom filters must never produce false negatives"
    probe = jnp.asarray(rng.integers(0, 2**32, (1, 4096, lanes),
                                     dtype=np.uint32))
    fpr = float(ref.bloom_query(filt, probe, n_probes=7).mean())
    assert fpr < 0.05, f"false positive rate too high: {fpr}"


# ---------------------------------------------------------------------------
# Prefix (shared key) encode / decode
# ---------------------------------------------------------------------------

def _sorted_keys(rng, n, lanes):
    k = rng.integers(0, 2**16, (n, lanes), dtype=np.uint32)  # force overlaps
    rows = [tuple(r) for r in k]
    rows.sort()
    return jnp.asarray(np.array(rows, dtype=np.uint32))


@pytest.mark.parametrize("n,lanes,restart", [(32, 4, 16), (256, 4, 16),
                                             (64, 2, 8), (48, 6, 16)])
def test_prefix_encode_pallas_matches_ref(n, lanes, restart):
    rng = np.random.default_rng(n)
    keys = _sorted_keys(rng, n, lanes)
    want = ref.prefix_encode(keys, restart_interval=restart)
    got = prefix.prefix_encode(keys, restart_interval=restart, row_tile=32,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefix_roundtrip():
    rng = np.random.default_rng(3)
    keys = _sorted_keys(rng, 128, 4)
    shared = ref.prefix_encode(keys, restart_interval=16)
    # emulate the wire format: zero out the shared prefix bytes
    kb = ref.u32_to_bytes(keys)
    pos = jnp.arange(kb.shape[-1])
    wire = jnp.where(pos[None, :] < shared[:, None], 0, kb)
    restored = ref.prefix_decode(shared, ref.bytes_to_u32(wire),
                                 restart_interval=16)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(keys))


@given(st.integers(1, 9))
@settings(max_examples=8, deadline=None)
def test_prefix_roundtrip_property(seed):
    rng = np.random.default_rng(seed)
    keys = _sorted_keys(rng, 64, 4)
    shared = ref.prefix_encode(keys, restart_interval=16)
    kb = ref.u32_to_bytes(keys)
    pos = jnp.arange(kb.shape[-1])
    wire = jnp.where(pos[None, :] < shared[:, None], 0, kb)
    restored = ref.prefix_decode(shared, ref.bytes_to_u32(wire),
                                 restart_interval=16)
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(keys))


def test_prefix_restart_points_are_zero():
    rng = np.random.default_rng(11)
    keys = _sorted_keys(rng, 64, 4)
    shared = np.asarray(ref.prefix_encode(keys, restart_interval=16))
    assert (shared[::16] == 0).all()


# ---------------------------------------------------------------------------
# Bitonic sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,lanes", [(8, 3), (64, 5), (100, 5), (256, 6),
                                     (1, 2), (33, 4)])
def test_bitonic_sort_matches_lax_sort(n, lanes):
    rng = np.random.default_rng(n * 7 + lanes)
    # last lane = original index (unique) -> total order, stable equivalence
    body = rng.integers(0, 8, (n, lanes - 1), dtype=np.uint32)  # collisions!
    idx = np.arange(n, dtype=np.uint32)[:, None]
    rows = jnp.asarray(np.concatenate([body, idx], axis=1))
    want = ref.sort_tuples(rows, lanes)
    got = bitonic_sort.bitonic_sort(rows, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=64))
@settings(max_examples=20, deadline=None)
def test_bitonic_sort_property(xs):
    n = len(xs)
    rows = jnp.asarray(
        np.stack([np.array(xs, np.uint32),
                  np.arange(n, dtype=np.uint32)], axis=1))
    got = np.asarray(bitonic_sort.bitonic_sort(rows, interpret=True))
    assert (np.diff(got[:, 0].astype(np.int64)) >= 0).all()
    assert sorted(got[:, 0].tolist()) == sorted(xs)


def test_sort_is_stable_via_index_lane():
    rows = jnp.asarray(np.array(
        [[5, 0], [1, 1], [5, 2], [1, 3], [5, 4]], dtype=np.uint32))
    got = np.asarray(bitonic_sort.bitonic_sort(rows, interpret=True))
    np.testing.assert_array_equal(
        got, np.array([[1, 1], [1, 3], [5, 0], [5, 2], [5, 4]], np.uint32))
