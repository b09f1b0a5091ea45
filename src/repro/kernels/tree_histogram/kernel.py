"""Pallas TPU kernel: GBDT training histograms as one-hot matmuls.

One boosting level needs, for every (tree node, feature, bin) cell, the
sum of each sample's channel statistics (gradient / hessian / count).
XLA lowers the obvious ``segment_sum`` formulation to scatter-add, which
serializes on TPU.  But the cell count per feature is static and small
(``n_nodes * n_bins``), so — exactly like
:mod:`repro.kernels.segment_reduce` — the reduction is a dense matmul
against a one-hot matrix built on the fly in VMEM:

    combined = node * n_bins + bin            (BE,)    per feature tile
    onehot   = combined[:, None] == iota      (BE, S)  S = n_nodes*n_bins
    partial  = values @ onehot                (C, S)   MXU work

The grid walks (feature, sample-tile); the per-feature (C, S) output
block stays VMEM-resident across all sample tiles, and all C channels
ride one matmul, so a whole level's gradient+hessian+count histograms
are a single launch.

Layout: bin codes are read feature-major as ``(F, 1, E)``, node ids as
a ``(1, E)`` row, and the output is written as ``(F, C, S)``, so every
block's last two dimensions are either whole array dimensions or
multiples of the TPU's (8, 128) tile — also when ``vmap`` prepends a
forest axis.  A ``(BE, 1)`` column of an ``(E, F)`` array is not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_E = 1024


def _tree_histogram_kernel(values_ref, bins_ref, node_ref, out_ref, *,
                           n_nodes: int, n_bins: int):
    """One grid step: fold a (C, BE) value tile of one feature into the
    feature's resident (C, S) histogram block."""
    s = n_nodes * n_bins
    values = values_ref[...]                           # (C, BE) float32
    bins = bins_ref[0, :]                              # (BE,) int32
    node = node_ref[0, :]                              # (BE,) int32

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    combined = node * n_bins + bins                    # (BE,)
    onehot = (combined[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
              ).astype(jnp.float32)                    # (BE, S)
    partial = jnp.dot(values, onehot,
                      preferred_element_type=jnp.float32)  # (C, S)
    out_ref[...] += partial


def tree_histogram(values, bins, node, n_nodes: int, n_bins: int, *,
                   interpret: bool, block_e: int = DEFAULT_BLOCK_E):
    """Multi-channel (node, feature, bin) histograms via one-hot matmuls.

    Args/shapes as :func:`repro.kernels.tree_histogram.ref
    .tree_histogram_np`; returns ``(C, n_nodes, F, n_bins)`` float32.
    ``interpret=True`` runs the kernel body in the Pallas interpreter
    (CPU validation); the TPU compiles it with ``interpret=False``.
    Sample padding uses node id ``n_nodes`` so its one-hot row is all
    zeros and contributes nothing.
    """
    values = jnp.asarray(values, dtype=jnp.float32)
    bins = jnp.asarray(bins, dtype=jnp.int32)
    node = jnp.asarray(node, dtype=jnp.int32)
    c, e = values.shape
    f = bins.shape[1]
    s = n_nodes * n_bins
    e_pad = -e % block_e
    if e_pad:
        values = jnp.pad(values, ((0, 0), (0, e_pad)))
        bins = jnp.pad(bins, ((0, e_pad), (0, 0)))
        node = jnp.pad(node, (0, e_pad), constant_values=n_nodes)
    grid = (f, (e + e_pad) // block_e)
    bins_fm = bins.T[:, None, :]                       # (F, 1, E)

    out = pl.pallas_call(
        functools.partial(_tree_histogram_kernel,
                          n_nodes=n_nodes, n_bins=n_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, block_e), lambda fi, i: (0, i)),   # values
            pl.BlockSpec((None, 1, block_e),
                         lambda fi, i: (fi, 0, i)),             # bin codes
            pl.BlockSpec((1, block_e), lambda fi, i: (0, i)),   # node ids
        ],
        out_specs=pl.BlockSpec((None, c, s), lambda fi, i: (fi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((f, c, s), jnp.float32),
        interpret=interpret,
        name="tree_histogram_onehot",
    )(values, bins_fm, node[None])
    # (F, C, n_nodes * n_bins) -> (C, n_nodes, F, n_bins)
    return jnp.transpose(out.reshape(f, c, n_nodes, n_bins), (1, 2, 0, 3))
