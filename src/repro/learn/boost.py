"""Jitted GBDT training: the whole forest as one fixed-shape program.

:class:`~repro.core.gbdt.GBDTClassifier` grows trees with Python loops
over trees x depths x features — the one stage of the collect -> train
-> evaluate pipeline that could not ride the fused JAX engine.  Because
the exported :class:`~repro.core.gbdt.DenseForest` is a *complete*
binary tree of static depth D, growth itself is level-synchronous and
fixed-shape:

* every boosting level is one multi-channel histogram reduction
  (:mod:`repro.kernels.tree_histogram`: gradient / hessian per
  (node, feature, bin) cell) followed by dense cumsum/argmax gain math;
  the default ``matmul`` strategy hoists the static bin one-hot out of
  the whole forest, and every strategy halves its work with the
  sibling-subtraction trick (left children reduced from samples,
  right = parent - left);
* the depth loop is unrolled level-synchronously over the static D
  levels, each with its exact ``2^d`` node count;
* the tree loop is a ``lax.scan`` carrying the margin vector;
* a whole *batch* of forests (the read+write pair, or a campaign
  hyperparameter sweep) trains in one ``vmap``-ed launch — datasets are
  padded to a common shape with zero-weight rows and inert features.

Split selection replicates the numpy trainer decision-for-decision:
identical quantile binning (:func:`repro.core.gbdt.quantile_edges` /
:func:`~repro.core.gbdt.bin_codes` — the same code path), identical
XGBoost gain, identical first-occurrence tie-breaking (lowest feature,
then lowest bin), identical pass-through / empty-leaf inheritance, and
the identical subsample mask stream, so ``fit_forest`` reproduces
``GBDTClassifier.fit`` splits and leaves to float tolerance
(``tests/test_learn.py`` pins <= 1e-5).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core.gbdt import (DenseForest, GBDTParams, bin_codes,
                             quantile_edges, quantize_gains)

_INT32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------- #
# numpy-side preparation: binning, padding, subsample masks
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class BinnedDataset:
    """One dataset in the fixed-shape layout the jitted trainer consumes.

    ``edges_pad`` is the per-feature edge table padded to ``n_bins - 1``
    columns with ``+inf``; ``bin_count[f]`` is the real number of bins
    (``len(edges[f]) + 1``), so valid split bins are ``b < bin_count - 1``.
    ``valid`` marks real rows (padding rows carry zero weight and zero
    count everywhere).
    """

    Xb: np.ndarray          # (n, F) int32 bin codes
    edges_pad: np.ndarray   # (F, n_bins - 1) float64
    bin_count: np.ndarray   # (F,) int32
    y: np.ndarray           # (n,) float64
    valid: np.ndarray       # (n,) float64 1/0
    base: float             # log-odds base score
    n_features: int         # pre-padding feature count
    n_rows: int             # pre-padding row count


def bin_dataset(X: np.ndarray, y: np.ndarray, n_bins: int) -> BinnedDataset:
    """Quantile-bin one dataset (the numpy trainer's exact binning)."""
    X = np.asarray(X, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    n, n_feat = X.shape
    edges = quantile_edges(X, n_bins)
    Xb = bin_codes(X, edges).astype(np.int32)
    edges_pad = np.full((n_feat, n_bins - 1), np.inf)
    for f, e in enumerate(edges):
        edges_pad[f, :len(e)] = e
    bin_count = np.array([len(e) + 1 for e in edges], dtype=np.int32)
    pos = y.mean()
    base = float(np.log(max(pos, 1e-6) / max(1 - pos, 1e-6)))
    return BinnedDataset(Xb=Xb, edges_pad=edges_pad, bin_count=bin_count,
                         y=y, valid=np.ones(n), base=base,
                         n_features=n_feat, n_rows=n)


def pad_dataset(ds: BinnedDataset, n: int, n_feat: int) -> BinnedDataset:
    """Pad to ``(n, n_feat)``: extra rows are zero-weight, extra features
    are single-bin (never splittable), so padding changes nothing."""
    dn, dF = ds.Xb.shape
    if (dn, dF) == (n, n_feat):
        return ds
    Xb = np.zeros((n, n_feat), dtype=np.int32)
    Xb[:dn, :dF] = ds.Xb
    edges_pad = np.full((n_feat, ds.edges_pad.shape[1]), np.inf)
    edges_pad[:dF] = ds.edges_pad
    bin_count = np.ones(n_feat, dtype=np.int32)
    bin_count[:dF] = ds.bin_count
    y = np.zeros(n)
    y[:dn] = ds.y
    valid = np.zeros(n)
    valid[:dn] = ds.valid
    return dataclasses.replace(ds, Xb=Xb, edges_pad=edges_pad,
                               bin_count=bin_count, y=y, valid=valid)


def sort_structs(Xb: np.ndarray,
                 n_bins: int) -> tuple[np.ndarray, np.ndarray]:
    """Static per-feature sample ordering for the ``cumsum`` histogram
    strategy: ``perm[f]`` sorts samples by feature f's bin code, and
    ``bnd[f, b]`` is the offset of bin b's first sample in that order —
    both fixed for a whole training run (bin codes never change)."""
    perm = np.argsort(Xb, axis=0, kind="stable").astype(np.int32).T
    sorted_codes = np.take_along_axis(Xb, perm.T.astype(np.int64), axis=0)
    bnd = np.stack([np.searchsorted(sorted_codes[:, f],
                                    np.arange(n_bins + 1))
                    for f in range(Xb.shape[1])]).astype(np.int32)
    return perm, bnd                   # (F, n), (F, n_bins + 1)


def subsample_masks(params: GBDTParams, n_rows: int, n: int) -> np.ndarray:
    """The numpy trainer's per-tree subsample stream, padded to ``n``
    columns (padding rows always masked out)."""
    masks = np.zeros((params.n_trees, n))
    if params.subsample < 1.0:
        rng = np.random.default_rng(params.seed)
        masks[:, :n_rows] = (rng.random((params.n_trees, n_rows))
                             < params.subsample)
    else:
        masks[:, :n_rows] = 1.0
    return masks


# ---------------------------------------------------------------------- #
# the jitted trainer
# ---------------------------------------------------------------------- #
def _grow_forest(Xb, edges_pad, bin_count, y, valid, masks, perm, bnd,
                 base, lr, lam, min_gain, min_child_hess, *,
                 max_depth: int, hist_backend: str, precision: str):
    """Grow one forest; pure and traceable (vmap over every array arg).

    Shapes: ``Xb (n, F)``, ``edges_pad (F, NB-1)``, ``bin_count (F,)``,
    ``y/valid (n,)``, ``masks (T, n)``, ``perm (F, n)`` / ``bnd
    (F, NB+1)`` (the :func:`sort_structs` orderings, used by the
    ``cumsum`` strategy); scalars are traced (sweepable under vmap).
    Returns ``(feature (T, 2^D-1) int32, threshold (T, 2^D-1) f32,
    leaf (T, 2^D) f32)``.

    The depth loop is unrolled (D is tiny and static) so every level
    carries its exact ``2^d`` node count, and levels d >= 1 use the
    sibling-subtraction trick: only *left*-child histograms are reduced
    from samples (right-child samples park on the drop id), the right
    halves come free as ``parent - left``.

    Histogram strategies (``hist_backend``): ``matmul`` (default) is
    the one-hot GEMM with the bin one-hot hoisted across the forest —
    the fastest option under XLA CPU, whose scatter-add runs tens of
    ns per element; ``cumsum`` masks each node's samples in the
    per-feature bin-sorted order, prefix-sums them, and reads bin
    totals off the static boundary offsets — O(nodes * F * n), for
    accelerators with fast associative scans; anything else resolves
    through :func:`make_tree_histogram` (``jax`` scatter-add,
    ``pallas`` kernel, ...).

    ``precision="exact"`` (float64 under ``enable_x64``) replicates the
    numpy trainer split for split, including its quantized tie-breaking
    and float32-threshold partition quirk; ``"fast"`` runs everything
    in float32 and skips the quirk emulation — statistically equivalent
    forests (AUC-parity tested) at half the memory traffic, the
    production choice for online refits.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.tree_histogram.ops import (bin_onehot,
                                                  make_tree_histogram,
                                                  matmul_histogram)

    fast = precision == "fast"
    if fast:
        edges_pad = edges_pad.astype(jnp.float32)
        y = y.astype(jnp.float32)
        valid = valid.astype(jnp.float32)
        masks = masks.astype(jnp.float32)
    n, n_feat = Xb.shape
    n_bins = edges_pad.shape[1] + 1
    n_internal = 2 ** max_depth - 1
    n_leaves = 2 ** max_depth
    dt = edges_pad.dtype

    Xb = Xb.astype(jnp.int32)
    split_ok = (jnp.arange(n_bins - 1)[None, :]
                < (bin_count[:, None] - 1))          # (F, NB-1)
    leaf_j = jnp.arange(n_leaves)

    if hist_backend == "cumsum":
        perm = perm.astype(jnp.int32)

        def make_hist(gh2):
            vperm = gh2[:, perm]                     # (C, F, n) per tree
            c = vperm.shape[0]

            def hist_fn(node_ids, n_rows):
                idsp = node_ids[perm]                # (F, n) sorted order
                sel = (idsp[None, :, :]
                       == jnp.arange(n_rows)[:, None, None])
                cs = jnp.cumsum(vperm[:, None] * sel[None], axis=-1)
                cs0 = jnp.concatenate(
                    [jnp.zeros_like(cs[..., :1]), cs], axis=-1)
                idx = jnp.broadcast_to(bnd[None, None],
                                       (c, n_rows) + bnd.shape)
                pref = jnp.take_along_axis(cs0, idx, axis=-1)
                return pref[..., 1:] - pref[..., :-1]

            return hist_fn
    elif hist_backend == "matmul":
        # hoist the static bin one-hot out of the whole forest: bin codes
        # never change across levels or trees, only node ids do
        onehot = bin_onehot(Xb, n_bins, dt)

        def make_hist(gh2):
            def hist_fn(node_ids, n_rows):
                return matmul_histogram(gh2, onehot, node_ids, n_rows,
                                        n_bins)

            return hist_fn
    else:
        generic = make_tree_histogram(hist_backend)

        def make_hist(gh2):
            def hist_fn(node_ids, n_rows):
                return generic(gh2, Xb, node_ids, n_rows,
                               n_bins).astype(dt)

            return hist_fn

    def tree_body(margin, mask):
        prob = 1.0 / (1.0 + jnp.exp(-jnp.clip(margin, -30.0, 30.0)))
        g = (prob - y) * mask
        h = jnp.maximum(prob * (1.0 - prob), 1e-6) * mask
        gh2 = jnp.stack([g, h])                      # (2, n)
        hist_fn = make_hist(gh2)

        node = jnp.zeros(n, dtype=jnp.int32)
        feat_parts, thr_parts = [], []
        hist = vals = None
        for d in range(max_depth):
            n_here = 1 << d
            level_start = n_here - 1
            loc = node - level_start                 # in [0, n_here)
            if d == 0:
                hist = hist_fn(jnp.zeros(n, dtype=jnp.int32), 1)
            else:
                half = n_here // 2
                left_ids = jnp.where(loc % 2 == 0, loc // 2, half
                                     ).astype(jnp.int32)
                left = hist_fn(left_ids, half)
                hist = jnp.stack([left, hist - left], axis=2
                                 ).reshape(2, n_here, n_feat, n_bins)
            gh, hh = hist[0], hist[1]                # (n_here, F, NB)
            GL = jnp.cumsum(gh, axis=-1)[..., :-1]
            HL = jnp.cumsum(hh, axis=-1)[..., :-1]
            G = gh.sum(-1)[..., None]
            H = hh.sum(-1)[..., None]
            GR, HR = G - GL, H - HL
            gain = 0.5 * (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                          - G ** 2 / (H + lam))
            ok = (split_ok[None] & (HL >= min_child_hess)
                  & (HR >= min_child_hess))
            gain = jnp.where(ok, gain, -jnp.inf)
            if not fast:
                gain = quantize_gains(gain)   # backend-stable ties

            # first-occurrence argmax over the flattened (F, NB-1) grid ==
            # the numpy trainer's lowest-feature-then-lowest-bin tie-break
            flat = gain.reshape(n_here, -1)
            best = jnp.argmax(flat, axis=1)
            best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
            f_best = (best // (n_bins - 1)).astype(jnp.int32)
            b_best = (best % (n_bins - 1)).astype(jnp.int32)
            has_split = best_gain > min_gain         # -inf never passes

            # Newton value of every level node (pass-through spine values)
            g_sum = gh[:, 0, :].sum(-1)              # any feature's bins
            h_sum = hh[:, 0, :].sum(-1)              # sum to the node total
            vals = -lr * g_sum / (h_sum + lam)       # (n_here,)

            feat_level = jnp.where(has_split, f_best, 0)
            edge_val = edges_pad[f_best, b_best]
            thr_level = jnp.where(has_split, edge_val, jnp.inf)

            feat_parts.append(feat_level)
            thr_parts.append(thr_level)

            def descend(ptr, tb_level):
                lc = ptr - level_start
                f_node = feat_level[lc]
                tb_node = tb_level[lc]
                xb = jnp.take_along_axis(Xb, f_node[:, None], axis=1)[:, 0]
                return 2 * ptr + 1 + (xb > tb_node).astype(ptr.dtype)

            if fast:
                # code > b  <=>  raw x > threshold
                tb = jnp.where(has_split, b_best, _INT32_MAX)
            else:
                # The numpy trainer keeps thresholds in float32 and
                # recovers the partition bin with searchsorted(edges,
                # float32(thr)): when float32 rounds the edge *up*, bin
                # b+1 goes left as well.  Histograms, leaves and the
                # margin update all follow that one partition.
                up = edge_val.astype(jnp.float32).astype(dt) > edge_val
                tb = jnp.where(has_split, b_best + up.astype(jnp.int32),
                               _INT32_MAX)
            node = descend(node, tb)

        # level-order concatenation == global node ids 0, 1-2, 3-6, ...
        feature = jnp.concatenate(feat_parts)
        threshold = jnp.concatenate(thr_parts)

        # leaves: Newton where occupied, direct-parent value where empty;
        # per-leaf sums are a dense (2^D, n) one-hot matvec — no bins
        loc = node - n_internal
        sel = (loc[None, :] == leaf_j[:, None]).astype(dt)   # (2^D, n)
        g_leaf = sel @ g
        h_leaf = sel @ h
        cnt = sel @ valid
        newton = -lr * g_leaf / (h_leaf + lam)
        leaf = jnp.where(cnt > 0, newton, vals[leaf_j // 2])
        return margin + leaf[node - n_internal], (feature, threshold, leaf)

    margin0 = jnp.full(n, base, dtype=dt)
    _, (features, thresholds, leaves) = jax.lax.scan(
        tree_body, margin0, masks)
    return (features, thresholds.astype(jnp.float32),
            leaves.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _make_grow_fn(max_depth: int, hist_backend: str, batched: bool,
                  precision: str):
    """Jitted grower per (depth, histogram backend, batched, precision)
    signature; array shapes key jit's own cache."""
    import jax

    fn = functools.partial(_grow_forest, max_depth=max_depth,
                           hist_backend=hist_backend, precision=precision)
    if batched:
        fn = jax.vmap(fn)
    return jax.jit(fn)


def _x64_ctx(precision: str):
    import contextlib

    import jax

    return (jax.enable_x64(True) if precision == "exact"
            else contextlib.nullcontext())


def _check_hist_backend(hist_backend: str, precision: str) -> str:
    """Resolve ``auto`` and refuse combinations that cannot honor the
    exact-parity contract: the Pallas kernel accumulates in float32, so
    its histograms cannot back ``precision="exact"`` float64 gains."""
    if hist_backend == "auto":
        from repro.kernels.tree_histogram.ops import _default_jax_backend

        hist_backend = _default_jax_backend()
    if precision == "exact" and hist_backend.startswith("pallas"):
        raise ValueError(
            "hist_backend='pallas' accumulates histograms in float32 and "
            "cannot provide precision='exact' split parity; use "
            "precision='fast' or hist_backend='matmul'/'jax'")
    return hist_backend


# ---------------------------------------------------------------------- #
# public entry points
# ---------------------------------------------------------------------- #
def _scalar_args(p: GBDTParams):
    return (float(p.learning_rate), float(p.reg_lambda),
            float(p.min_gain), float(p.min_child_hess))


def fit_forest(X: np.ndarray, y: np.ndarray,
               params: GBDTParams | None = None,
               hist_backend: str = "matmul",
               precision: str = "exact") -> DenseForest:
    """Train one :class:`DenseForest` under jit — the drop-in counterpart
    of ``GBDTClassifier(params).fit(X, y).forest``."""
    import jax

    p = params or GBDTParams()
    hist_backend = _check_hist_backend(hist_backend, precision)
    ds = bin_dataset(X, y, p.n_bins)
    masks = subsample_masks(p, ds.n_rows, ds.n_rows)
    if hist_backend == "cumsum":
        perm, bnd = sort_structs(ds.Xb, p.n_bins)
    else:
        # unused traced args on the other strategies — don't pay the
        # O(n F log n) argsort (this runs on every online refit)
        perm = bnd = np.zeros((1, 1), dtype=np.int32)
    grow = _make_grow_fn(p.max_depth, hist_backend, False, precision)
    with _x64_ctx(precision):
        out = grow(ds.Xb, ds.edges_pad, ds.bin_count, ds.y, ds.valid,
                   masks, perm, bnd, ds.base, *_scalar_args(p))
        out = jax.tree.map(lambda a: a.block_until_ready(), out)
    feature, threshold, leaf = (np.asarray(a) for a in out)
    return DenseForest(feature=feature, threshold=threshold, leaf=leaf,
                       base_score=ds.base, depth=p.max_depth,
                       n_features=ds.n_features)


def fit_forest_batch(datasets, params: GBDTParams | list | None = None,
                     hist_backend: str = "matmul",
                     precision: str = "exact") -> list[DenseForest]:
    """Train B forests in one vmapped launch.

    ``datasets`` is a list of ``(X, y)`` pairs (row/feature counts may
    differ — they are padded to a common shape with inert rows and
    features).  ``params`` is one :class:`GBDTParams` for all forests or
    a per-forest list; continuous hyperparameters (``learning_rate``,
    ``reg_lambda``, ``min_gain``, ``min_child_hess``) may vary per
    forest and ride the vmap, while the structural ones (``n_trees``,
    ``max_depth``, ``n_bins``) must be shared.
    """
    import jax

    hist_backend = _check_hist_backend(hist_backend, precision)
    if params is None:
        params = GBDTParams()
    plist = (list(params) if isinstance(params, (list, tuple))
             else [params] * len(datasets))
    if len(plist) != len(datasets):
        raise ValueError("one GBDTParams per dataset (or a single shared)")
    p0 = plist[0]
    for p in plist[1:]:
        if (p.n_trees, p.max_depth, p.n_bins) != (p0.n_trees, p0.max_depth,
                                                  p0.n_bins):
            raise ValueError("structural params (n_trees, max_depth, "
                             "n_bins) must be shared across a batch")

    binned = [bin_dataset(X, y, p.n_bins)
              for (X, y), p in zip(datasets, plist)]
    n = max(ds.n_rows for ds in binned)
    n_feat = max(ds.n_features for ds in binned)
    padded = [pad_dataset(ds, n, n_feat) for ds in binned]
    masks = np.stack([subsample_masks(p, ds.n_rows, n)
                      for ds, p in zip(binned, plist)])
    if hist_backend == "cumsum":
        sorts = [sort_structs(ds.Xb, p0.n_bins) for ds in padded]
        perm = np.stack([s[0] for s in sorts])
        bnd = np.stack([s[1] for s in sorts])
    else:
        # unused traced args on the other strategies (vmap only needs
        # the leading batch axis) — skip the per-forest argsorts
        perm = bnd = np.zeros((len(padded), 1, 1), dtype=np.int32)

    def stack(attr):
        return np.stack([getattr(ds, attr) for ds in padded])

    scal = np.array([_scalar_args(p) for p in plist])   # (B, 4)
    base = np.array([ds.base for ds in binned])
    grow = _make_grow_fn(p0.max_depth, hist_backend, True, precision)
    with _x64_ctx(precision):
        out = grow(stack("Xb"), stack("edges_pad"), stack("bin_count"),
                   stack("y"), stack("valid"), masks, perm, bnd, base,
                   scal[:, 0], scal[:, 1], scal[:, 2], scal[:, 3])
        out = jax.tree.map(lambda a: a.block_until_ready(), out)
    features, thresholds, leaves = (np.asarray(a) for a in out)
    return [DenseForest(feature=features[i], threshold=thresholds[i],
                        leaf=leaves[i], base_score=binned[i].base,
                        depth=p0.max_depth,
                        n_features=binned[i].n_features)
            for i in range(len(binned))]


def train_models_jax(data: dict, gbdt_params: GBDTParams | None = None,
                     space=None, hist_backend: str = "matmul",
                     precision: str = "exact"):
    """The jax counterpart of :func:`repro.core.dataset.train_models`:
    the read and write forests train together in one vmapped launch."""
    from repro.core.config_space import SPACE
    from repro.core.model import DIALModel

    for op_name in ("read", "write"):
        if len(data[op_name][0]) == 0:
            raise ValueError(f"no {op_name} samples collected")
    fr, fw = fit_forest_batch([data["read"], data["write"]], gbdt_params,
                              hist_backend=hist_backend,
                              precision=precision)
    return DIALModel(read_forest=fr, write_forest=fw,
                     space=space or SPACE)
