"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

Same TPU adaptation story as the Mamba scan: the recurrence is diagonal
per channel, so we tile channels into VMEM blocks (grid =
(batch, channel_blocks)) and scan time on-chip.  State is a (block_d,)
vector — trivially resident.  This is the decode-path workhorse for
recurrentgemma where the sequential scan (not the parallel one) is what
runs per new token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 512


def _rglru_kernel(x_ref, a_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)   # (S, bd)
    a = a_ref[0].astype(jnp.float32)   # (S, bd)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * x

    def step(h, xs):
        a_t, b_t = xs
        h = a_t * h + b_t
        return h, h

    h0 = jnp.zeros((x.shape[1],), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (a, b))
    o_ref[0] = hs.astype(o_ref.dtype)


def rglru(x, a, *, interpret: bool, block_d: int = DEFAULT_BLOCK_D):
    """RG-LRU scan via pl.pallas_call; args as in ref.rglru_ref."""
    bt, s, dm = x.shape
    block_d = min(block_d, dm)
    assert dm % block_d == 0, (dm, block_d)
    grid = (bt, dm // block_d)

    return pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, block_d), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, s, block_d), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, s, block_d), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bt, s, dm), jnp.float32),
        interpret=interpret,
        name="rglru_scan",
    )(x, a)
