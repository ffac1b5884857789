"""Shared helpers for Pallas kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# kernels traced with ``interpret=True`` in this process (see
# ``resolve_interpret``); a chip run asserts this stays empty
_INTERPRETED: set[str] = set()


def lex_less(a: jax.Array, b: jax.Array, num_keys: int) -> jax.Array:
    """Lexicographic ``a < b`` over the first ``num_keys`` lanes of the last
    axis.  Inputs ``[..., L]`` uint32; output bool ``[...]``."""
    res = jnp.zeros(a.shape[:-1], bool)
    eq = jnp.ones(a.shape[:-1], bool)
    for lane in range(num_keys):
        res = res | (eq & (a[..., lane] < b[..., lane]))
        eq = eq & (a[..., lane] == b[..., lane])
    return res


def default_interpret() -> bool:
    """Pallas ``interpret=`` default: interpret on CPU, compiled on TPU."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None, kernel: str) -> bool:
    """Resolve a kernel wrapper's ``interpret=`` argument (``None`` means
    ``default_interpret()``) and record the kernel when it runs in the
    interpreter.  Called while the wrapper traces, so the record covers
    every compiled program that embeds the interpreter."""
    if interpret is None:
        interpret = default_interpret()
    if interpret:
        _INTERPRETED.add(kernel)
    return interpret


def interpreted_kernels() -> frozenset[str]:
    """Names of the kernels traced in interpret mode so far."""
    return frozenset(_INTERPRETED)


def fold(x: jax.Array, axis: int, op) -> jax.Array:
    """Reduce ``axis`` of ``x`` to size 1 with the associative, commutative
    ``op`` by a halving tree of static slices.  Mosaic has no XOR or OR
    reduction, so kernels fold with the elementwise op; the slice left
    over at an odd width is carried and combined at the end."""
    n = x.shape[axis]
    carry = None
    while n > 1:
        h = n // 2
        if n % 2:
            tail = jax.lax.slice_in_dim(x, 2 * h, n, axis=axis)
            carry = tail if carry is None else op(carry, tail)
        x = op(jax.lax.slice_in_dim(x, 0, h, axis=axis),
               jax.lax.slice_in_dim(x, h, 2 * h, axis=axis))
        n = h
    return x if carry is None else op(x, carry)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def lane_tile(n: int, tile: int) -> tuple[int, int]:
    """``(block, padded n)`` for an axis laid along the vector lanes: the
    whole axis when it fits one tile, else ``tile`` (a multiple of 128)."""
    if n <= tile:
        return n, n
    return tile, round_up(n, tile)


def tree_merge(items: list, merge2):
    """Pairwise merge-tree reduction: ``ceil(log2 k)`` levels of
    ``merge2(left, right)`` over adjacent pairs, odd leftover carried to
    the next level.  Left operands always precede right operands in the
    original order, so a ties-to-left ``merge2`` yields a stable merge.
    Shared by the Pallas merge-path kernel, the jnp oracle, and the CPU
    engine's host mirror so their tree shapes cannot diverge."""
    items = list(items)
    if not items:
        raise ValueError("tree_merge needs at least one item")
    while len(items) > 1:
        nxt = [merge2(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]
