"""Jitted public wrappers for GBDT forest inference.

The paired read/write forests are scored by
:func:`repro.kernels.gbdt_forest.dense.paired_scorer`, the one scorer of
the host fleet predictor here and of the device-resident loop
(:mod:`repro.pfs.loop_jax`); the gather descent in
:mod:`repro.kernels.gbdt_forest.ref` is its test oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gbdt_forest import ref as _ref
from repro.kernels.gbdt_forest.dense import paired_scorer


def make_predictor(forest):
    """Build a jitted ``X -> probabilities`` closure for a DenseForest.

    The forest arrays are closed over (donated to the device once);
    only the sample matrix streams per call.
    """
    feature = jnp.asarray(forest.feature, dtype=jnp.int32)
    threshold = jnp.asarray(forest.threshold, dtype=jnp.float32)
    leaf = jnp.asarray(forest.leaf, dtype=jnp.float32)
    base = float(forest.base_score)
    depth = int(forest.depth)

    @jax.jit
    def predict(x):
        m = _ref.forest_margin_ref(x.astype(jnp.float32), feature, threshold,
                                   leaf, base, depth)
        return 1.0 / (1.0 + jnp.exp(-jnp.clip(m, -30.0, 30.0)))

    return predict


# ---------------------------------------------------------------------- #
# fleet inference: both forests, mixed-op row batch, one launch
# ---------------------------------------------------------------------- #
def _pad_forest(feature, threshold, leaf, depth: int, to_depth: int,
                to_trees: int):
    """Pad one dense forest to ``(to_trees, to_depth)`` without changing
    its predictions.

    Depth grows by turning every leaf into a pass-through internal node
    (threshold ``+inf`` descends left) whose left child carries the old
    leaf value; extra trees are all-pass-through with 0-valued leaves.
    """
    feature = np.asarray(feature, dtype=np.int32)
    threshold = np.asarray(threshold, dtype=np.float32)
    leaf = np.asarray(leaf, dtype=np.float32)
    t = feature.shape[0]
    for _ in range(to_depth - depth):
        n_leaves = leaf.shape[1]
        feature = np.concatenate(
            [feature, np.zeros((t, n_leaves), dtype=np.int32)], axis=1)
        threshold = np.concatenate(
            [threshold, np.full((t, n_leaves), np.inf, dtype=np.float32)],
            axis=1)
        new_leaf = np.zeros((t, 2 * n_leaves), dtype=np.float32)
        new_leaf[:, 0::2] = leaf            # left child of each pass-through
        leaf = new_leaf
    if to_trees > t:
        n_internal, n_leaves = feature.shape[1], leaf.shape[1]
        pad = to_trees - t
        feature = np.concatenate(
            [feature, np.zeros((pad, n_internal), dtype=np.int32)], axis=0)
        threshold = np.concatenate(
            [threshold, np.full((pad, n_internal), np.inf, dtype=np.float32)],
            axis=0)
        leaf = np.concatenate(
            [leaf, np.zeros((pad, n_leaves), dtype=np.float32)], axis=0)
    return feature, threshold, leaf


def pair_forests(read_forest, write_forest):
    """Stack the read and write DenseForests into one paired tensor set.

    Returns ``(feature, threshold, leaf, base, depth, n_features)`` with
    forest axis 0 = read, 1 = write, both padded to the larger depth /
    tree count.  Sample matrices must be zero-padded to ``n_features``
    columns (the larger of the two models' input dims); padding never
    changes a prediction because pass-through trees and spines carry the
    original leaf values and inert trees contribute exactly 0.
    """
    depth = max(read_forest.depth, write_forest.depth)
    t = max(read_forest.n_trees, write_forest.n_trees)
    fr = _pad_forest(read_forest.feature, read_forest.threshold,
                     read_forest.leaf, read_forest.depth, depth, t)
    fw = _pad_forest(write_forest.feature, write_forest.threshold,
                     write_forest.leaf, write_forest.depth, depth, t)
    feature = np.stack([fr[0], fw[0]])          # (2, T, 2^D - 1)
    threshold = np.stack([fr[1], fw[1]])
    leaf = np.stack([fr[2], fw[2]])             # (2, T, 2^D)
    base = np.array([read_forest.base_score, write_forest.base_score],
                    dtype=np.float32)
    n_features = max(read_forest.n_features, write_forest.n_features)
    return feature, threshold, leaf, base, depth, n_features


def _round_up_pow2(n: int, floor: int = 32) -> int:
    cap = floor
    while cap < n:
        cap *= 2
    return cap


def make_fleet_predictor(read_forest, write_forest):
    """Build the fleet scorer: ``(X_read, X_write) -> (p_read, p_write)``.

    Both ops' (interface x config) rows are fused into one padded batch
    with a per-row forest selector and scored in a **single** launch —
    the per-tick inference cost no longer scales with the number of
    Python-level agents or with having two models.  Row counts are
    bucketed to powers of two so jit traces a handful of shapes total.
    """
    feature, threshold, leaf, base, _, n_features = pair_forests(
        read_forest, write_forest)
    score = paired_scorer(feature, threshold, leaf, base)

    @jax.jit
    def _predict(x, op):
        m = score(x.astype(jnp.float32), op)
        return 1.0 / (1.0 + jnp.exp(-jnp.clip(m, -30.0, 30.0)))

    def predict(x_read: np.ndarray, x_write: np.ndarray):
        nr = 0 if x_read is None else x_read.shape[0]
        nw = 0 if x_write is None else x_write.shape[0]
        n = nr + nw
        if n == 0:
            return np.zeros(0), np.zeros(0)
        cap = _round_up_pow2(n)
        x = np.zeros((cap, n_features), dtype=np.float32)
        op = np.zeros(cap, dtype=np.int32)
        if nr:
            x[:nr, :x_read.shape[1]] = x_read
        if nw:
            x[nr:n, :x_write.shape[1]] = x_write
            op[nr:n] = 1
        p = np.asarray(_predict(x, op))
        return p[:nr], p[nr:n]

    return predict
