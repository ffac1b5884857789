"""Selective-scan (Mamba-1 SSM) forward Pallas kernel.

EXPERIMENTS.md §Perf cell B showed Mamba training/prefill is bound by the
``[B, S, d_inner, d_state]`` state materialization of the XLA scan
(~68 GB/layer/pass at jamba scale).  The original CUDA selective-scan
kernel exists precisely to keep the recurrent state in SRAM; this is the
TPU analogue: the state ``h [d_tile, d_state]`` lives in VMEM while the
kernel walks the sequence, so HBM traffic collapses to the u/dt/B/C
streams + y (ds+2 words per channel-step instead of ~2·ds·log(S)).

Grid: ``(batch, d_inner tiles)``; each program scans the full sequence
for its channel tile.  Sequences longer than the VMEM budget are chunked
by the wrapper with the carried state threaded through ``h0``.

Used on the inference/prefill path (forward only); the training backward
still runs the XLA scan (a recompute-based backward kernel is the
follow-up).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, h_ref, *, seq: int):
    a = a_ref[...]                    # [dT, ds] fp32 (negative)
    d_skip = d_ref[...].reshape(-1)   # [dT] (1-D blocks may load as 2-D)

    def row(ref, t):
        return ref[0, pl.ds(t, 1), :][0]

    def step(t, h):
        dt_t = row(dt_ref, t)                        # [dT] fp32
        u_t = row(u_ref, t).astype(jnp.float32)      # [dT]
        b_t = row(b_ref, t).astype(jnp.float32)      # [ds]
        c_t = row(c_ref, t).astype(jnp.float32)      # [ds]
        da = jnp.exp(dt_t[:, None] * a)              # [dT, ds]
        dbu = (dt_t * u_t)[:, None] * b_t[None, :]
        h = da * h + dbu
        y = (h * c_t[None, :]).sum(-1) + d_skip * u_t
        y_ref[0, pl.ds(t, 1), :] = y[None, :].astype(y_ref.dtype)
        return h

    h0 = h0_ref[...].reshape(a.shape).astype(jnp.float32)  # [dT, ds]
    h = jax.lax.fori_loop(0, seq, step, h0)
    h_ref[...] = h[None]


@functools.partial(jax.jit, static_argnames=("d_tile", "interpret"))
def selective_scan(u, dt, b, c, a_log, d_skip, h0=None, *,
                   d_tile: int = 256, interpret: bool | None = None):
    """Mamba-1 recurrence with VMEM-resident state.

    u/dt: [B, S, di]; b/c: [B, S, ds]; a_log: [di, ds]; d_skip: [di];
    h0: optional [B, di, ds] carried state.
    Returns (y [B, S, di] fp32, h_last [B, di, ds] fp32).
    """
    interpret = common.resolve_interpret(interpret, "selective_scan")
    bsz, seq, di = u.shape
    ds = b.shape[-1]
    dt_t = min(d_tile, di)
    assert di % dt_t == 0
    a = -jnp.exp(a_log.astype(jnp.float32))
    if h0 is None:
        h0 = jnp.zeros((bsz, di, ds), jnp.float32)

    grid = (bsz, di // dt_t)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, seq=seq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, seq, dt_t), lambda i, j: (i, 0, j)),  # u
            pl.BlockSpec((1, seq, dt_t), lambda i, j: (i, 0, j)),  # dt
            pl.BlockSpec((1, seq, ds), lambda i, j: (i, 0, 0)),    # b
            pl.BlockSpec((1, seq, ds), lambda i, j: (i, 0, 0)),    # c
            pl.BlockSpec((dt_t, ds), lambda i, j: (j, 0)),         # a
            pl.BlockSpec((dt_t,), lambda i, j: (j,)),              # d
            pl.BlockSpec((1, dt_t, ds), lambda i, j: (i, j, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((1, seq, dt_t), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, dt_t, ds), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, seq, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, di, ds), jnp.float32),
        ],
        interpret=interpret,
    )(u, dt.astype(jnp.float32), b, c, a, d_skip.astype(jnp.float32), h0)
    return y, h


def selective_scan_ref(u, dt, b, c, a_log, d_skip, h0=None):
    """Naive jnp oracle (sequential lax.scan over the sequence)."""
    bsz, seq, di = u.shape
    ds = b.shape[-1]
    a = -jnp.exp(a_log.astype(jnp.float32))
    if h0 is None:
        h0 = jnp.zeros((bsz, di, ds), jnp.float32)

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        da = jnp.exp(dt_t[..., None] * a)
        dbu = (dt_t * u_t.astype(jnp.float32))[..., None] * b_t[:, None, :]
        h = da * h + dbu
        y = jnp.einsum("bis,bs->bi", h, c_t) \
            + d_skip * u_t.astype(jnp.float32)
        return h, y

    xs = (u.swapaxes(0, 1), dt.astype(jnp.float32).swapaxes(0, 1),
          b.astype(jnp.float32).swapaxes(0, 1),
          c.astype(jnp.float32).swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1), h
