"""Gather-free scoring of the paired read/write forests.

The level-synchronous descent of :mod:`repro.kernels.gbdt_forest.ref`
gathers each row's node feature, threshold and feature value at every
level, and the leaf at the end: about ``3 * D + 1`` gathers of ``N * T``
elements.  A TPU runs such gathers far below its vector rate.  This
scorer does the same work with none, from the forests' static structure:

* **Compares, grouped by feature.**  Every split node compares one
  feature with one threshold, and both are known when the scorer is
  built.  The nodes of a block of trees are laid out in *slots* grouped
  by feature, so a block's bits are ``x[:, f:f+1] > thresholds`` for each
  feature ``f`` (static slices, no gather).  Nodes whose threshold is
  ``+inf`` always descend left and take no slot.
* **Routing, one small integer matmul.**  Leaf ``l`` of a tree is
  reached iff every ancestor on its path turned the way the path turns.
  With ``P[node, l]`` = +1 (the path turns right there), -1 (left) or 0
  (not an ancestor), ``bits @ P`` equals the number of right turns on
  leaf ``l``'s path iff ``l`` is the row's leaf.  The operands are 0/1
  and ±1 and the sums are at most ``D`` in magnitude, so the product is
  exact at any matmul precision (bfloat16 operands, float32 sums).
* **Leaf values, a one-hot where-sum.**  Exactly one leaf matches per
  tree; summing ``where(match, leaf, 0)`` returns its value unchanged.

Trees are scored in blocks of ``128 // 2^D`` per forest (one 128-lane
row of leaves), under one ``lax.scan`` over blocks whose slot layout is
shared, so neither memory nor program size grows with the tree count.
Both forests are scored for every row and the row's forest is chosen
with ``where``.  The (N, T) per-tree values are the reference's gathered
leaves, and :func:`~repro.kernels.gbdt_forest.ref.tree_sum` sums both in
one fixed order, so the margins are bit-equal to
:func:`~repro.kernels.gbdt_forest.ref.paired_forest_margin_ref` on every
backend.

The dense work per row grows with ``2^D`` per tree where the gather's
grows with ``D``: above :data:`DENSE_MAX_DEPTH` the scorer keeps the
gather reference.  Every forest this repository trains has depth 5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.gbdt_forest.ref import paired_forest_margin_ref, tree_sum

DENSE_MAX_DEPTH = 8
_LANES = 128


def _paths(depth: int):
    """Path matrix ``(2^D - 1, 2^D)`` in {-1, 0, +1} and each leaf's count
    of right turns ``(2^D,)``, for the dense complete-tree layout (node
    ``i``'s children are ``2i + 1`` and ``2i + 2``)."""
    n_internal, n_leaves = 2 ** depth - 1, 2 ** depth
    path = np.zeros((n_internal, n_leaves), dtype=np.float32)
    rights = np.zeros(n_leaves, dtype=np.float32)
    for leaf in range(n_leaves):
        node = leaf + n_internal
        while node:
            parent = (node - 1) // 2
            right = node == 2 * parent + 2
            path[parent, leaf] = 1.0 if right else -1.0
            rights[leaf] += right
            node = parent
    return path, rights


def _block_tables(feature, threshold, leaf, depth: int):
    """Slot layout and per-block tables of the paired forests.

    Returns ``(groups, thr, route, leaves, rights)``: ``groups`` lists
    ``(feature, first slot, slot count)`` in slot order, shared by every
    block of both forests; ``thr`` is ``(blocks, 2, K)`` slot thresholds
    (``+inf`` in unused slots); ``route`` ``(blocks, 2, K, B * 2^D)`` the
    path rows of each slot's node; ``leaves`` ``(blocks, 2, B * 2^D)``;
    ``rights`` ``(B * 2^D,)``.
    """
    _, t, n_internal = feature.shape
    n_leaves = leaf.shape[2]
    per_block = max(1, _LANES // n_leaves)
    n_blocks = -(-t // per_block)
    path, rights = _paths(depth)

    # live nodes of each (block, forest), by feature
    live = {}
    n_slots = np.zeros(int(feature.max(initial=0)) + 1, dtype=np.int64)
    for b in range(n_blocks):
        for k in range(2):
            by_feature = {}
            for lt, tree in enumerate(range(b * per_block,
                                            min(t, (b + 1) * per_block))):
                for j in range(n_internal):
                    if threshold[k, tree, j] == np.inf:
                        continue
                    by_feature.setdefault(int(feature[k, tree, j]),
                                          []).append((lt, tree, j))
            live[b, k] = by_feature
            for f, nodes in by_feature.items():
                n_slots[f] = max(n_slots[f], len(nodes))
    if not n_slots.any():
        n_slots[0] = 1                     # every node passes: one idle slot
    offset = np.concatenate([[0], np.cumsum(n_slots)])
    groups = [(f, int(offset[f]), int(c)) for f, c in enumerate(n_slots) if c]
    k_slots = int(offset[-1])

    width = per_block * n_leaves
    thr = np.full((n_blocks, 2, k_slots), np.inf, dtype=np.float32)
    route = np.zeros((n_blocks, 2, k_slots, width), dtype=np.float32)
    leaves = np.zeros((n_blocks, 2, width), dtype=np.float32)
    for (b, k), by_feature in live.items():
        for f, nodes in by_feature.items():
            for s, (lt, tree, j) in enumerate(nodes, start=offset[f]):
                thr[b, k, s] = threshold[k, tree, j]
                route[b, k, s, lt * n_leaves:(lt + 1) * n_leaves] = path[j]
        for lt, tree in enumerate(range(b * per_block,
                                        min(t, (b + 1) * per_block))):
            leaves[b, k, lt * n_leaves:(lt + 1) * n_leaves] = leaf[k, tree]
    return groups, thr, route, leaves, np.tile(rights, per_block)


def paired_scorer(feature, threshold, leaf, base):
    """Build the paired-forest scorer ``(x, op) -> (N,) float32 margins``.

    Takes the numpy arrays of
    :func:`~repro.kernels.gbdt_forest.ops.pair_forests` (forest axis 0 =
    read, 1 = write); ``x`` is ``(N, F)`` float32 and ``op`` ``(N,)``
    selects each row's forest.  Forests up to :data:`DENSE_MAX_DEPTH`
    deep are scored gather-free (module docstring), deeper ones by the
    gather reference; both give the reference's margins bit for bit.
    The returned function is traceable and is meant to be called inside
    a jitted program.
    """
    feature = np.asarray(feature, dtype=np.int32)
    threshold = np.asarray(threshold, dtype=np.float32)
    leaf = np.asarray(leaf, dtype=np.float32)
    base = np.asarray(base, dtype=np.float32)
    depth = int(leaf.shape[2]).bit_length() - 1

    if depth > DENSE_MAX_DEPTH:
        def score_gather(x, op):
            return paired_forest_margin_ref(
                x, op, jnp.asarray(feature), jnp.asarray(threshold),
                jnp.asarray(leaf), jnp.asarray(base), depth)
        return score_gather

    groups, thr, route, leaves, rights = _block_tables(
        feature, threshold, leaf, depth)
    n_trees, n_leaves = leaf.shape[1:]
    route = route.astype(jnp.bfloat16)

    def score(x, op):
        n = x.shape[0]
        write = (op == 1)[:, None]

        def forest_block(thr_k, route_k, leaves_k):
            bits = jnp.concatenate(
                [x[:, f:f + 1] > thr_k[o:o + c] for f, o, c in groups],
                axis=1)
            turns = jnp.dot(bits.astype(jnp.bfloat16), route_k,
                            preferred_element_type=jnp.float32)
            hit = jnp.where(turns == rights, leaves_k, 0.0)
            return hit.reshape(n, -1, n_leaves).sum(axis=2)

        def block(_, tables):
            thr_b, route_b, leaves_b = tables
            v_read = forest_block(thr_b[0], route_b[0], leaves_b[0])
            v_write = forest_block(thr_b[1], route_b[1], leaves_b[1])
            return None, jnp.where(write, v_write, v_read)

        _, vals = jax.lax.scan(block, None, (thr, route, leaves))
        vals = jnp.moveaxis(vals, 0, 1).reshape(n, -1)[:, :n_trees]
        return tree_sum(vals) + jnp.where(op == 1, base[1], base[0])

    return score

