"""On-device bitonic tuple sort Pallas kernel (beyond-paper phase 2).

LUDA could not find an efficient GPU library sort for small ``<K, V_offset>``
tuples and fell back to a *cooperative sort* on the CPU (a device->host->
device round trip).  On TPU the picture is different: the whole tuple buffer
for a compaction batch fits VMEM and a bitonic network is purely regular
compare-exchange traffic, so the round trip can be eliminated.  This kernel
is the on-device path (``sort_mode="device"``); the paper-faithful
cooperative path lives in ``core/offload.py``.

Rows are ``[n, L]`` uint32 lanes sorted ascending lexicographically over all
``L`` lanes (callers put an original-index lane last, which makes the total
order unique and therefore equal to a stable sort on the key lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

# Sentinel rows sort after all real rows (keys are never all-ones).
PAD_WORD = jnp.uint32(0xFFFFFFFF)


def bitonic_network(x: jax.Array) -> jax.Array:
    """The bitonic compare-exchange network as pure jnp: sorts ``[n, L]``
    uint32 rows ascending over all lanes.  ``n`` must be a power of two.
    Shared by the Pallas kernel (VMEM-resident) and the XLA-measurable
    path ``bitonic_sort_xla`` -- O(log^2 n) full-array passes either way."""
    n, lanes = x.shape
    log_n = n.bit_length() - 1
    for stage in range(1, log_n + 1):
        k = 1 << stage
        for sub in range(stage - 1, -1, -1):
            j = 1 << sub
            xr = x.reshape(n // (2 * j), 2, j, lanes)
            a, b = xr[:, 0], xr[:, 1]
            g = jax.lax.broadcasted_iota(jnp.int32, (n // (2 * j), j), 0)
            t = jax.lax.broadcasted_iota(jnp.int32, (n // (2 * j), j), 1)
            i_low = g * (2 * j) + t
            asc = (i_low & k) == 0
            swap = jnp.where(asc, common.lex_less(b, a, lanes),
                             common.lex_less(a, b, lanes))
            new_a = jnp.where(swap[..., None], b, a)
            new_b = jnp.where(swap[..., None], a, b)
            x = jnp.stack([new_a, new_b], axis=1).reshape(n, lanes)
    return x


def _bitonic_kernel(rows_ref, out_ref, *, n, lanes):
    del n, lanes
    out_ref[...] = bitonic_network(rows_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(rows: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """Sort rows ascending lexicographically over all lanes.

    ``rows``: uint32 ``[n, L]``.  n is padded to a power of two with
    all-ones sentinel rows; the original count of rows is returned in order
    at the front.  Single-block kernel: whole buffer lives in VMEM (fine for
    compaction batches up to ~2^17 rows; larger sorts use the XLA path in
    ``ops.sort_tuples``).
    """
    interpret = common.resolve_interpret(interpret, "bitonic_sort")
    n, lanes = rows.shape
    n_pad = 1 << max(1, (n - 1).bit_length())
    if n_pad != n:
        pad = jnp.full((n_pad - n, lanes), PAD_WORD, jnp.uint32)
        rows = jnp.concatenate([rows.astype(jnp.uint32), pad], axis=0)
    out = pl.pallas_call(
        functools.partial(_bitonic_kernel, n=n_pad, lanes=lanes),
        in_specs=[pl.BlockSpec((n_pad, lanes), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n_pad, lanes), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, lanes), jnp.uint32),
        interpret=interpret,
    )(rows.astype(jnp.uint32))
    return out[:n]


@jax.jit
def bitonic_sort_xla(rows: jax.Array) -> jax.Array:
    """The same bitonic network executed directly by XLA (no Pallas) --
    the honest CPU-measurable cost of the device bitonic path, used by
    ``benchmarks/kernel_bench.py`` as the merge-path baseline."""
    n, lanes = rows.shape
    n_pad = 1 << max(1, (n - 1).bit_length())
    if n_pad != n:
        pad = jnp.full((n_pad - n, lanes), PAD_WORD, jnp.uint32)
        rows = jnp.concatenate([rows.astype(jnp.uint32), pad], axis=0)
    return bitonic_network(rows.astype(jnp.uint32))[:n]
