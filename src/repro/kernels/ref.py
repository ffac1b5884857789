"""Pure-jnp reference oracles for every Pallas kernel in this package.

These are the ground truth used by the kernel allclose tests.  They are
written for clarity over speed (the CRC oracle is additionally validated
against ``binascii.crc32`` in the tests, so the whole chain is anchored to
the canonical CRC-32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import tables

# ---------------------------------------------------------------------------
# CRC-32 (bit-parallel affine formulation)
# ---------------------------------------------------------------------------


def _crc_contrib(words: jax.Array, T: jax.Array) -> jax.Array:
    words = words.astype(jnp.uint32)
    acc = jnp.zeros(words.shape, jnp.uint32)
    for j in range(32):
        bit = (words >> jnp.uint32(j)) & jnp.uint32(1)
        acc = acc ^ jnp.where(bit.astype(bool), T[:, j], jnp.uint32(0))
    return jax.lax.reduce(acc, np.uint32(0), jax.lax.bitwise_xor,
                          (acc.ndim - 1,))


def crc32_words(words: jax.Array) -> jax.Array:
    """CRC-32 of each row of ``words``.

    ``words``: uint32 ``[..., n_words]``; the message bytes are the
    little-endian serialization of the row.  Returns uint32 ``[...]`` equal to
    ``binascii.crc32(row.tobytes())``.
    """
    n_words = words.shape[-1]
    T = jnp.asarray(tables.crc32_operator_table(n_words))  # [W, 32]
    base = jnp.uint32(tables.crc32_zero_message(n_words * 4))
    return _crc_contrib(words, T) ^ base


def crc32_words_sections(sections) -> jax.Array:
    """CRC-32 of the logical concat of sections (affine combination --
    no concatenated copy).  ``sections``: list of uint32 [..., w_i]."""
    total = sum(s.shape[-1] for s in sections)
    T = jnp.asarray(tables.crc32_operator_table(total))
    acc = jnp.uint32(tables.crc32_zero_message(total * 4))
    off = 0
    for s in sections:
        w = s.shape[-1]
        acc = acc ^ _crc_contrib(s, T[off:off + w])
        off += w
    return acc


# ---------------------------------------------------------------------------
# Bloom filter (LevelDB-style double hashing, 32-bit FNV/murmur mix)
# ---------------------------------------------------------------------------

_FNV_OFFSET = np.uint32(tables.FNV_OFFSET)
_FNV_PRIME = np.uint32(tables.FNV_PRIME)


def _mix32(h: jax.Array) -> jax.Array:
    """murmur3 fmix32 finalizer."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(tables.MIX_1)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(tables.MIX_2)
    h = h ^ (h >> jnp.uint32(16))
    return h


def bloom_hashes(keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Two 32-bit hashes per key. ``keys``: uint32 ``[..., lanes]``."""
    keys = keys.astype(jnp.uint32)
    return bloom_hashes_lanes([keys[..., lane]
                               for lane in range(keys.shape[-1])])


def bloom_hashes_lanes(lanes: list) -> tuple[jax.Array, jax.Array]:
    """``bloom_hashes`` over keys given as one uint32 array per key lane
    (the layout the Pallas kernels hold them in)."""
    h1 = jnp.full(lanes[0].shape, _FNV_OFFSET, jnp.uint32)
    h2 = jnp.full(lanes[0].shape, _FNV_OFFSET ^ jnp.uint32(tables.H2_SEED),
                  jnp.uint32)
    for lane in lanes:
        h1 = (h1 ^ lane) * _FNV_PRIME
        h2 = (h2 ^ jnp.uint32(tables.H2_SALT) ^ lane) * _FNV_PRIME
    h1 = _mix32(h1)
    h2 = _mix32(h2) | jnp.uint32(1)  # odd delta: full-period double hashing
    return h1, h2


def bloom_build(keys: jax.Array, *, n_words: int, n_probes: int,
                valid: jax.Array | None = None) -> jax.Array:
    """Build bloom filters.

    ``keys``: uint32 ``[groups, keys_per_group, lanes]``.
    ``valid``: optional bool ``[groups, keys_per_group]`` mask (padded slots).
    Returns uint32 ``[groups, n_words]`` bitmaps (m = 32 * n_words bits).
    """
    h1, h2 = bloom_hashes(keys)  # [G, K]
    m_bits = jnp.uint32(n_words * 32)
    out = jnp.zeros((keys.shape[0], n_words * 32), bool)
    for i in range(n_probes):
        pos = (h1 + jnp.uint32(i) * h2) % m_bits  # [G, K]
        hit = jax.nn.one_hot(pos, n_words * 32, dtype=jnp.bool_)
        if valid is not None:
            hit = hit & valid[..., None]
        out = out | hit.any(axis=1)
    bits = out.reshape(keys.shape[0], n_words, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (bits.astype(jnp.uint32) * weights).sum(-1, dtype=jnp.uint32)


def bloom_query(filters: jax.Array, keys: jax.Array, *,
                n_probes: int) -> jax.Array:
    """Membership probe. ``filters``: uint32 ``[G, W]``; ``keys``:
    ``[G, Q, lanes]``. Returns bool ``[G, Q]`` (True = maybe present)."""
    h1, h2 = bloom_hashes(keys)
    n_words = filters.shape[-1]
    m_bits = jnp.uint32(n_words * 32)
    ok = jnp.ones(h1.shape, bool)
    for i in range(n_probes):
        pos = (h1 + jnp.uint32(i) * h2) % m_bits
        word = jnp.take_along_axis(filters, (pos >> jnp.uint32(5)).astype(
            jnp.int32), axis=-1)
        bit = (word >> (pos & jnp.uint32(31))) & jnp.uint32(1)
        ok = ok & (bit == 1)
    return ok


def bloom_multi_probe(filters: jax.Array, keys: jax.Array, *,
                      n_probes: int) -> jax.Array:
    """Pairwise membership probe: key row ``i`` against filter row ``i``.

    The batched read path stacks one (per-SST, per-block-group) filter row
    per lookup candidate, so a K-key multi_get prunes every candidate in a
    single launch.  ``filters``: uint32 ``[C, W]``; ``keys``: uint32
    ``[C, lanes]``.  Returns bool ``[C]`` (True = maybe present)."""
    return bloom_query(filters, keys[:, None, :], n_probes=n_probes)[:, 0]


# ---------------------------------------------------------------------------
# Shared-key (prefix) encode  -- LevelDB block builder phase on device
# ---------------------------------------------------------------------------


def u32_to_bytes(words: jax.Array) -> jax.Array:
    """Expand uint32 lanes ``[..., L]`` to big-endian bytes ``[..., 4L]``
    so that lexicographic byte order == lexicographic lane order."""
    shifts = jnp.uint32(8) * (jnp.uint32(3) - jnp.arange(4, dtype=jnp.uint32))
    b = (words[..., None] >> shifts) & jnp.uint32(0xFF)
    return b.reshape(*words.shape[:-1], words.shape[-1] * 4)


def prefix_encode(keys: jax.Array, *, restart_interval: int) -> jax.Array:
    """Shared-prefix lengths for sorted keys.

    ``keys``: uint32 ``[n, lanes]``.  Returns int32 ``[n]``: the number of
    leading bytes shared with the previous key; forced to 0 at restart points
    (every ``restart_interval`` rows), matching LevelDB block builder
    semantics.
    """
    kb = u32_to_bytes(keys)  # [n, B]
    prev = jnp.roll(kb, 1, axis=0)
    eq = (kb == prev).astype(jnp.int32)
    shared = jnp.cumprod(eq, axis=-1).sum(-1)
    idx = jnp.arange(keys.shape[0])
    return jnp.where(idx % restart_interval == 0, 0, shared).astype(jnp.int32)


def prefix_decode(shared: jax.Array, keys_raw: jax.Array, *,
                  restart_interval: int) -> jax.Array:
    """Inverse of the fixed-lane prefix encoding (phase-1 key restore).

    ``keys_raw`` holds, for every row, only the *unshared* suffix bytes valid
    (byte positions >= shared[i]); shared prefix bytes are garbage.  Restores
    full keys.  Sequential within a restart interval (data dependence of the
    paper's phase 1), parallel across intervals.
    """
    n, lanes = keys_raw.shape
    kb = u32_to_bytes(keys_raw)  # [n, B]
    B = kb.shape[-1]
    kb_i = kb.reshape(n // restart_interval, restart_interval, B)
    sh_i = shared.reshape(n // restart_interval, restart_interval)

    def step(prev_key, inp):
        row, s = inp
        pos = jnp.arange(B)
        full = jnp.where(pos < s, prev_key, row)
        return full, full

    def per_interval(rows, shs):
        _, out = jax.lax.scan(step, jnp.zeros((B,), rows.dtype), (rows, shs))
        return out

    full_b = jax.vmap(per_interval)(kb_i, sh_i).reshape(n, B)
    return bytes_to_u32(full_b)


def bytes_to_u32(b: jax.Array) -> jax.Array:
    """Pack big-endian bytes ``[..., 4L]`` back to uint32 lanes ``[..., L]``."""
    L = b.shape[-1] // 4
    b4 = b.reshape(*b.shape[:-1], L, 4).astype(jnp.uint32)
    shifts = jnp.uint32(8) * (jnp.uint32(3) - jnp.arange(4, dtype=jnp.uint32))
    return (b4 << shifts).sum(-1, dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Tuple sort (lexicographic over uint32 lanes)
# ---------------------------------------------------------------------------


def sort_tuples(rows: jax.Array, num_keys: int) -> jax.Array:
    """Sort rows ``[n, L]`` ascending lexicographically by the first
    ``num_keys`` lanes, carrying remaining lanes as payload.  Stable."""
    ops = tuple(rows[:, i] for i in range(rows.shape[1]))
    sorted_ops = jax.lax.sort(ops, num_keys=num_keys, is_stable=True)
    return jnp.stack(sorted_ops, axis=1)


# ---------------------------------------------------------------------------
# Run-aware merge (phase-2 merge path, k sorted runs -> one sorted run)
# ---------------------------------------------------------------------------


def _lex_less(a: jax.Array, b: jax.Array) -> jax.Array:
    """Lexicographic ``a < b`` over all lanes of the last axis."""
    res = jnp.zeros(a.shape[:-1], bool)
    eq = jnp.ones(a.shape[:-1], bool)
    for lane in range(a.shape[-1]):
        res = res | (eq & (a[..., lane] < b[..., lane]))
        eq = eq & (a[..., lane] == b[..., lane])
    return res


def lex_searchsorted(hay: jax.Array, q: jax.Array, *,
                     side: str = "left") -> jax.Array:
    """Vectorized binary search of rows ``q`` in sorted rows ``hay``.

    ``side="left"``: number of hay rows strictly less than each query;
    ``side="right"``: number of hay rows less-or-equal.  Both compare
    lexicographically over all uint32 lanes.  int32 ``[m]``.
    """
    n = hay.shape[0]
    m = q.shape[0]
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), n, jnp.int32)
    if n == 0:
        return lo
    for _ in range((n + 1).bit_length()):
        go = lo < hi
        mid = (lo + hi) >> 1
        row = hay[jnp.clip(mid, 0, n - 1)]
        if side == "left":
            descend = _lex_less(row, q)            # hay[mid] <  q
        else:
            descend = ~_lex_less(q, row)           # hay[mid] <= q
        lo = jnp.where(go & descend, mid + 1, lo)
        hi = jnp.where(go & ~descend, mid, hi)
    return lo


def merge_sorted(a: jax.Array, b: jax.Array) -> jax.Array:
    """Merge two sorted row arrays (``[m, L]`` + ``[n, L]`` -> ``[m+n, L]``).

    Ranks each side in the other via ``lex_searchsorted`` and scatters both
    to their final positions -- two O(n log n) gather passes plus one
    scatter, no full re-sort.  Ties break toward ``a`` (the earlier run),
    so with a trailing unique index lane this equals a stable sort of the
    concatenation.
    """
    m, n = a.shape[0], b.shape[0]
    if m == 0:
        return b
    if n == 0:
        return a
    pos_a = jnp.arange(m, dtype=jnp.int32) + lex_searchsorted(b, a,
                                                              side="left")
    pos_b = jnp.arange(n, dtype=jnp.int32) + lex_searchsorted(a, b,
                                                              side="right")
    out = jnp.zeros((m + n, a.shape[1]), a.dtype)
    out = out.at[pos_a].set(a)
    return out.at[pos_b].set(b)


def lookup_blocks(keys: jax.Array, meta: jax.Array, vals: jax.Array,
                  nvalid: jax.Array, queries: jax.Array
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched point lookup: query row ``i`` binary-searched in block ``i``.

    ``keys``: uint32 ``[C, K, L]`` -- per-candidate decoded block keys,
    sorted; rows at or beyond ``nvalid[i]`` MUST hold the all-ones sentinel
    so order is total.  ``meta``: uint32 ``[C, K]``; ``vals``: uint32
    ``[C, K, Vw]``; ``nvalid``: int32 ``[C]``; ``queries``: uint32
    ``[C, L]``.

    Returns ``(found bool [C], meta uint32 [C], value uint32 [C, Vw])``
    with meta/value zeroed where not found.  The leftmost match is
    returned, which (entries sorted key-asc, seq-desc) is the newest
    version of the key in the block.
    """
    C, K, _ = keys.shape
    lo = jnp.zeros((C,), jnp.int32)
    hi = jnp.full((C,), K, jnp.int32)
    for _ in range((K + 1).bit_length()):
        go = lo < hi
        mid = (lo + hi) >> 1
        row = jnp.take_along_axis(
            keys, jnp.clip(mid, 0, K - 1)[:, None, None], axis=1)[:, 0, :]
        descend = _lex_less(row, queries)          # keys[mid] < q
        lo = jnp.where(go & descend, mid + 1, lo)
        hi = jnp.where(go & ~descend, mid, hi)
    idx = jnp.clip(lo, 0, K - 1)
    hit = jnp.take_along_axis(keys, idx[:, None, None], axis=1)[:, 0, :]
    found = (hit == queries).all(axis=-1) & (lo < nvalid.astype(jnp.int32))
    m = jnp.take_along_axis(meta, idx[:, None], axis=1)[:, 0]
    v = jnp.take_along_axis(vals, idx[:, None, None], axis=1)[:, 0, :]
    return (found,
            jnp.where(found, m, jnp.uint32(0)),
            jnp.where(found[:, None], v, jnp.uint32(0)))


def merge_runs(rows: jax.Array, run_lens: tuple[int, ...]) -> jax.Array:
    """Merge ``k`` pre-sorted runs stored back to back in ``rows``.

    ``run_lens`` (static python ints summing to ``rows.shape[0]``) give the
    length of each run.  Pairwise merge tree: ``ceil(log2 k)`` levels, each
    a single pass -- O(n log k) versus O(n log^2 n) for the bitonic
    network.  ``k=1`` is a passthrough."""
    from repro.kernels import common
    if sum(run_lens) != rows.shape[0]:
        raise ValueError(f"run_lens {run_lens} must cover {rows.shape[0]} "
                         "rows")
    offs = np.concatenate([[0], np.cumsum(run_lens)])
    runs = [rows[offs[i]:offs[i + 1]]
            for i in range(len(run_lens)) if run_lens[i] > 0]
    if not runs:
        return rows
    return common.tree_merge(runs, merge_sorted)
