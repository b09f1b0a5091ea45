"""Pallas TPU kernel for the Mamba-1 selective scan.

TPU adaptation: the CUDA implementation fuses a warp-parallel prefix scan
over shared memory.  TPUs have no cross-lane scan primitive, but the
recurrence is *diagonal* per (channel, state) pair, so we tile the channel
axis into VMEM blocks (grid = (batch, channel_blocks)) and run the time
loop sequentially *inside* the kernel with the (block_d, N) state held in
registers/VMEM.  Each grid step touches HBM once for its (S, block_d)
slab — the scan itself is entirely on-chip, which is the whole point on
the HBM->VMEM hierarchy.

The sequence axis is unblocked (one slab per grid step).  For very long
sequences the surrounding layer chunks S before calling (see
repro.models.mamba); kernel-side S-chunking with state handoff would use
input_output_aliases and is left as a documented production extension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 256


def _mamba_kernel(u_ref, delta_ref, a_ref, b_ref, c_ref, d_ref, o_ref):
    u = u_ref[0].astype(jnp.float32)          # (S, bd)
    delta = delta_ref[0].astype(jnp.float32)  # (S, bd)
    A = a_ref[...].astype(jnp.float32)        # (bd, N)
    B = b_ref[0].astype(jnp.float32)          # (S, N)
    C = c_ref[0].astype(jnp.float32)          # (S, N)
    D = d_ref[...].astype(jnp.float32)        # (bd,)

    bd, n = A.shape

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs               # (bd,), (bd,), (N,), (N,)
        coef = jnp.exp(d_t[:, None] * A)      # (bd, N)
        h = coef * h + (d_t * u_t)[:, None] * b_t[None, :]
        y = (h * c_t[None, :]).sum(axis=1) + D * u_t
        return h, y

    h0 = jnp.zeros((bd, n), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (u, delta, B, C))
    o_ref[0] = ys.astype(o_ref.dtype)


def selective_scan(u, delta, A, B, C, D, *, interpret: bool,
                   block_d: int = DEFAULT_BLOCK_D):
    """Selective scan via pl.pallas_call; args as in ref.selective_scan_ref."""
    bt, s, dm = u.shape
    n = A.shape[1]
    block_d = min(block_d, dm)
    assert dm % block_d == 0, (dm, block_d)
    grid = (bt, dm // block_d)

    return pl.pallas_call(
        _mamba_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, block_d), lambda b, i: (b, 0, i)),   # u
            pl.BlockSpec((1, s, block_d), lambda b, i: (b, 0, i)),   # delta
            pl.BlockSpec((block_d, n), lambda b, i: (i, 0)),         # A
            pl.BlockSpec((1, s, n), lambda b, i: (b, 0, 0)),         # B
            pl.BlockSpec((1, s, n), lambda b, i: (b, 0, 0)),         # C
            pl.BlockSpec((block_d,), lambda b, i: (i,)),             # D
        ],
        out_specs=pl.BlockSpec((1, s, block_d), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bt, s, dm), jnp.float32),
        interpret=interpret,
        name="mamba_selective_scan",
    )(u, delta, A, B, C, D)
