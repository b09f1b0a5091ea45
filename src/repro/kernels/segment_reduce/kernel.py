"""Pallas TPU kernel: segment sum as a one-hot matmul.

The engine's per-tick reductions (OST setup-work/IOPS/bandwidth
aggregation over OSCs, NIC aggregation over clients, workload stripe
scatter/gather) are all segment sums over a *static* segment mapping.
XLA lowers ``jax.ops.segment_sum`` to scatter-add, which serializes on
TPU; with a static, small segment count the same reduction is one
``(1, E) @ (E, S)`` one-hot matmul — dense MXU work, no scatter at all.

The values axis is tiled by BlockSpec; each grid step builds the one-hot
block on the fly from the resident segment-id tile (iota compare — never
materialized in HBM) and accumulates its partial product into the single
(S,)-block output, which stays VMEM-resident across the whole grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_E = 1024


def _segment_sum_kernel(x_ref, seg_ref, out_ref, *, num_segments: int):
    """One grid step: accumulate a (1, BLOCK_E) tile into the (1, S)
    output."""
    x = x_ref[...]                              # (1, BE) float32
    seg = seg_ref[0, :]                         # (BE,) int32

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # one-hot built in VMEM from an iota compare; (1, BE) @ (BE, S) on MXU
    onehot = (seg[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, num_segments), 1)
              ).astype(jnp.float32)
    out_ref[...] += jnp.dot(x, onehot, preferred_element_type=jnp.float32)


def segment_sum(values, segment_ids, num_segments: int, *, interpret: bool,
                block_e: int = DEFAULT_BLOCK_E):
    """Segment sum of 1-D ``values`` via one-hot matmul tiles.

    ``interpret=True`` runs the kernel body in the Pallas interpreter
    (CPU validation); the TPU compiles it with ``interpret=False``.
    The kernel reads and accumulates float32 (the TPU compiler cannot
    lower float64 into a Mosaic call), so ``values`` are cast on the way
    in and the sums cast back to ``values.dtype`` on the way out.
    Out-of-range padding ids are handled by padding with ``num_segments``
    (their one-hot row is all zeros, so they contribute nothing).

    Operands ride as ``(1, E)`` rows and the sums as a ``(1, S)`` row:
    every block's last two dimensions are then whole array dimensions or
    multiples of the TPU's (8, 128) tile, also when ``vmap`` prepends a
    batch axis (1-D blocks would put that axis second to last).
    """
    out_dtype = values.dtype
    e = values.shape[0]
    e_pad = -e % block_e
    values = jnp.pad(values.astype(jnp.float32), (0, e_pad))[None]
    segment_ids = jnp.pad(segment_ids.astype(jnp.int32), (0, e_pad),
                          constant_values=num_segments)[None]
    # int32 block indices even when traced under x64 (the engine's scan),
    # where a bare 0 would lower as an i64 Mosaic return
    zero = lambda: jnp.int32(0)
    out = pl.pallas_call(
        functools.partial(_segment_sum_kernel, num_segments=num_segments),
        grid=((e + e_pad) // block_e,),
        in_specs=[
            pl.BlockSpec((1, block_e), lambda i: (zero(), i)),   # values
            pl.BlockSpec((1, block_e), lambda i: (zero(), i)),   # segment ids
        ],
        out_specs=pl.BlockSpec((1, num_segments),
                               lambda i: (zero(), zero())),    # resident
        out_shape=jax.ShapeDtypeStruct((1, num_segments), jnp.float32),
        interpret=interpret,
        name="segment_sum_onehot",
    )(values, segment_ids)
    return out[0].astype(out_dtype)
