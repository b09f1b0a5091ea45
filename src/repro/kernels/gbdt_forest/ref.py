"""Pure-jnp oracle for dense-forest GBDT inference.

A static ``depth``-step level-synchronous descent through complete binary
trees laid out in dense arrays, gathering each row's node and leaf at
every level: the leaves :meth:`repro.core.gbdt.DenseForest.predict_margin`
reaches on float32 inputs.  :func:`paired_forest_margin_ref` is the oracle
of the gather-free paired scorer (:mod:`repro.kernels.gbdt_forest.dense`),
which scores forests deeper than its cut-off with it.
"""

from __future__ import annotations

import jax.numpy as jnp


def forest_margin_ref(x, feature, threshold, leaf, base_score: float,
                      depth: int):
    """Reference forest margins.

    Args:
        x:         (N, F) float32 samples.
        feature:   (T, 2^D - 1) int32 split features.
        threshold: (T, 2^D - 1) float32 split thresholds (+inf = pass left).
        leaf:      (T, 2^D) float32 leaf values.
        base_score: scalar initial margin.
        depth:     D, static.

    Returns:
        (N,) float32 margins (pre-sigmoid).
    """
    n = x.shape[0]
    t = feature.shape[0]
    n_internal = feature.shape[1]
    # flatten forests for (sample, tree) gathers
    feat_flat = feature.reshape(-1)
    thr_flat = threshold.reshape(-1)
    leaf_flat = leaf.reshape(-1)
    tree_off = jnp.arange(t, dtype=jnp.int32) * n_internal

    idx = jnp.zeros((n, t), dtype=jnp.int32)
    for _ in range(depth):
        node = idx + tree_off[None, :]
        f = feat_flat[node]                      # (N, T)
        thr = thr_flat[node]                     # (N, T)
        xv = jnp.take_along_axis(x, f, axis=1)   # (N, T)
        go_right = (xv > thr).astype(jnp.int32)
        idx = 2 * idx + 1 + go_right
    leaf_idx = idx - n_internal
    vals = leaf_flat[leaf_idx + jnp.arange(t, dtype=jnp.int32)[None, :] * leaf.shape[1]]
    return vals.sum(axis=1).astype(jnp.float32) + jnp.float32(base_score)


def forest_proba_ref(x, feature, threshold, leaf, base_score: float, depth: int):
    m = forest_margin_ref(x, feature, threshold, leaf, base_score, depth)
    return 1.0 / (1.0 + jnp.exp(-jnp.clip(m, -30.0, 30.0)))


def paired_forest_margin_ref(x, op, feature, threshold, leaf, base,
                             depth: int):
    """Margins with per-row forest selection (the fleet inference oracle).

    Two forests (read / write) are stacked on a leading axis; each row of
    ``x`` traverses the forest named by ``op``.  Selection is just an
    extra per-row offset into the flattened forest arrays — no extra
    traversal work for the unselected forest.

    Args:
        x:         (N, F) float32 samples (F = max of both forests' dims).
        op:        (N,) int32 forest selector, 0 or 1.
        feature:   (2, T, 2^D - 1) int32.
        threshold: (2, T, 2^D - 1) float32 (+inf = pass left).
        leaf:      (2, T, 2^D) float32.
        base:      (2,) float32 per-forest base margin.
        depth:     D, static.

    Returns:
        (N,) float32 margins (pre-sigmoid).
    """
    n = x.shape[0]
    _, t, n_internal = feature.shape
    n_leaves = leaf.shape[2]
    feat_flat = feature.reshape(-1)
    thr_flat = threshold.reshape(-1)
    leaf_flat = leaf.reshape(-1)
    tree_off = jnp.arange(t, dtype=jnp.int32) * n_internal
    forest_off = op.astype(jnp.int32) * (t * n_internal)     # (N,)

    idx = jnp.zeros((n, t), dtype=jnp.int32)
    for _ in range(depth):
        node = idx + tree_off[None, :] + forest_off[:, None]
        f = feat_flat[node]
        thr = thr_flat[node]
        xv = jnp.take_along_axis(x, f, axis=1)
        idx = 2 * idx + 1 + (xv > thr).astype(jnp.int32)
    leaf_off = jnp.arange(t, dtype=jnp.int32) * n_leaves
    leaf_forest_off = op.astype(jnp.int32) * (t * n_leaves)
    vals = leaf_flat[(idx - n_internal) + leaf_off[None, :]
                     + leaf_forest_off[:, None]]
    return tree_sum(vals) + base[op]


def tree_sum(vals):
    """Sum (N, T) per-tree values over trees, tree 0 first.

    A reduction's order is the compiler's choice, and differs with the
    backend, the shape and the fusion around it; a fixed left-to-right
    sum gives every scorer of the paired forests the same float32 bits.
    """
    margin = jnp.zeros(vals.shape[0], jnp.float32)
    for t in range(vals.shape[1]):
        margin = margin + vals[:, t]
    return margin
