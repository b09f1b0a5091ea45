"""Gradient-boosted decision trees (the paper's ML architecture, SIII-B).

Training is histogram-based boosting with logistic loss, implemented in
numpy (no sklearn offline).  The fitted forest is exported in a *dense
complete-binary-tree layout* that the jitted inference traversal
(:mod:`repro.kernels.gbdt_forest`) walks:

    feature   : (T, 2^D - 1) int32    -- split feature per internal node
    threshold : (T, 2^D - 1) float32  -- split threshold (+inf = pass left)
    leaf      : (T, 2^D)     float32  -- leaf values (lr baked in)

Every tree is padded to full depth D: a node that stops early becomes a
pass-through (threshold=+inf so traversal always descends left) and its
leaf value is replicated down the left spine.  Traversal is therefore a
*static* D-step loop with no data-dependent control flow — exactly what a
TPU wants (level-synchronous predicated descent) and what GPU
warp-per-tree implementations cannot map onto the MXU/VPU model.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-np.clip(x, -30, 30)))


def quantize_gains(gain):
    """Split gains as the argmax compares them: rounded to float32.

    Mathematically-equal candidates (e.g. two features isolating the same
    sample set) then stay tied under any float summation order, and ties
    break on (feature, bin) order in every trainer backend (the numpy
    loop and :mod:`repro.learn.boost` must agree split for split).  The
    rounding is relative: gains grow with the node's sample count, and
    rounding to a fixed number of decimals let the ~1e-13 relative
    summation noise of gains in the thousands straddle a rounding step.
    """
    return gain.astype(np.float32)


# ---------------------------------------------------------------------- #
# quantile binning, shared by this trainer and repro.learn.boost (the
# jitted trainer reproduces this trainer's splits only because both bin
# through the exact same code path)
# ---------------------------------------------------------------------- #
def quantile_edges(X: np.ndarray, n_bins: int) -> list[np.ndarray]:
    """Per-feature quantile bin edges (deduplicated, possibly empty)."""
    X = np.asarray(X, dtype=np.float64)
    qs_grid = np.linspace(0, 1, n_bins + 1)[1:-1]
    return [np.unique(np.quantile(X[:, f], qs_grid))
            for f in range(X.shape[1])]


def bin_codes(X: np.ndarray, edges: list[np.ndarray]) -> np.ndarray:
    """Integer bin codes: ``code > b  <=>  x > edges[f][b]`` (side-right
    searchsorted, the raw-threshold-compatible binning semantics)."""
    X = np.asarray(X, dtype=np.float64)
    Xb = np.empty(X.shape, dtype=np.int16)
    for f, e in enumerate(edges):
        Xb[:, f] = np.searchsorted(e, X[:, f], side="right")
    return Xb


@dataclasses.dataclass
class DenseForest:
    """Inference-ready forest in dense layout (see module docstring)."""

    feature: np.ndarray    # (T, 2^D - 1) int32
    threshold: np.ndarray  # (T, 2^D - 1) float32
    leaf: np.ndarray       # (T, 2^D) float32
    base_score: float
    depth: int
    n_features: int

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    def predict_margin(self, X: np.ndarray) -> np.ndarray:
        """Reference numpy traversal (the oracle for the Pallas kernel)."""
        X = np.asarray(X, dtype=np.float32)
        n, _ = X.shape
        out = np.full(n, self.base_score, dtype=np.float64)
        n_internal = self.feature.shape[1]
        for t in range(self.n_trees):
            idx = np.zeros(n, dtype=np.int64)
            for _ in range(self.depth):
                f = self.feature[t, idx]
                thr = self.threshold[t, idx]
                go_right = X[np.arange(n), f] > thr
                idx = 2 * idx + 1 + go_right
            out += self.leaf[t, idx - n_internal]
        return out

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return _sigmoid(self.predict_margin(X))

    def save(self, path: str) -> None:
        np.savez_compressed(
            path, feature=self.feature, threshold=self.threshold,
            leaf=self.leaf, base_score=self.base_score, depth=self.depth,
            n_features=self.n_features)

    @staticmethod
    def load(path: str) -> "DenseForest":
        z = np.load(path)
        return DenseForest(
            feature=z["feature"], threshold=z["threshold"], leaf=z["leaf"],
            base_score=float(z["base_score"]), depth=int(z["depth"]),
            n_features=int(z["n_features"]))


@dataclasses.dataclass
class GBDTParams:
    n_trees: int = 160
    max_depth: int = 5
    learning_rate: float = 0.1
    reg_lambda: float = 1.0
    min_gain: float = 1e-4
    min_child_hess: float = 1.0
    n_bins: int = 48
    subsample: float = 0.85
    seed: int = 0


class GBDTClassifier:
    """Binary GBDT with histogram splits; produces a :class:`DenseForest`."""

    def __init__(self, params: GBDTParams | None = None):
        self.params = params or GBDTParams()
        self.forest: DenseForest | None = None

    # ------------------------------------------------------------------ #
    def fit(self, X: np.ndarray, y: np.ndarray) -> "GBDTClassifier":
        p = self.params
        X = np.asarray(X, dtype=np.float64)
        y = np.asarray(y, dtype=np.float64)
        n, n_feat = X.shape
        rng = np.random.default_rng(p.seed)

        # quantile binning: per-feature edges; small-int binned codes
        edges = quantile_edges(X, p.n_bins)
        Xb = bin_codes(X, edges)
        self._edges = edges

        pos = y.mean()
        base = float(np.log(max(pos, 1e-6) / max(1 - pos, 1e-6)))
        F = np.full(n, base)

        n_internal = 2 ** p.max_depth - 1
        n_leaves = 2 ** p.max_depth
        feats = np.zeros((p.n_trees, n_internal), dtype=np.int32)
        thrs = np.full((p.n_trees, n_internal), np.inf, dtype=np.float32)
        leaves = np.zeros((p.n_trees, n_leaves), dtype=np.float32)

        for t in range(p.n_trees):
            prob = _sigmoid(F)
            g = prob - y
            h = np.maximum(prob * (1 - prob), 1e-6)
            if p.subsample < 1.0:
                mask = rng.random(n) < p.subsample
                g_t = np.where(mask, g, 0.0)
                h_t = np.where(mask, h, 0.0)
            else:
                g_t, h_t = g, h
            tf, tt, tl, leaf_of = self._build_tree(Xb, g_t, h_t, edges)
            feats[t], thrs[t], leaves[t] = tf, tt, tl
            # update margins with the new tree only, on the partition its
            # leaves were fitted to
            F += tl[leaf_of]

        self.forest = DenseForest(
            feature=feats, threshold=thrs, leaf=leaves, base_score=base,
            depth=p.max_depth, n_features=n_feat)
        return self

    # ------------------------------------------------------------------ #
    def _build_tree(self, Xb, g, h, edges):
        """Grow one depth-wise tree over binned features (XGBoost gains)."""
        p = self.params
        n, n_feat = Xb.shape
        n_internal = 2 ** p.max_depth - 1
        n_leaves = 2 ** p.max_depth
        feature = np.zeros(n_internal, dtype=np.int32)
        threshold = np.full(n_internal, np.inf, dtype=np.float32)
        leaf = np.zeros(n_leaves, dtype=np.float32)

        # node assignment per sample, in *level-order global* node ids
        node = np.zeros(n, dtype=np.int64)
        # value carried by pass-through spines
        node_value = {0: 0.0}

        for depth in range(p.max_depth):
            level_start = 2 ** depth - 1
            level_nodes = np.arange(level_start, 2 ** (depth + 1) - 1)
            local = node - level_start
            active = (local >= 0) & (local < len(level_nodes))
            loc = np.where(active, local, 0)

            best = {}
            n_level = len(level_nodes)
            for f in range(n_feat):
                nb = len(edges[f]) + 1
                if nb <= 1:
                    continue
                gh = np.zeros((n_level, nb))
                hh = np.zeros((n_level, nb))
                flat = loc * nb + Xb[:, f]
                gh_f = np.bincount(flat[active], weights=g[active],
                                   minlength=n_level * nb)
                hh_f = np.bincount(flat[active], weights=h[active],
                                   minlength=n_level * nb)
                gh = gh_f.reshape(n_level, nb)
                hh = hh_f.reshape(n_level, nb)
                GL = np.cumsum(gh, axis=1)[:, :-1]
                HL = np.cumsum(hh, axis=1)[:, :-1]
                G = GL[:, -1:] + gh[:, -1:]
                H = HL[:, -1:] + hh[:, -1:]
                GR = G - GL
                HR = H - HL
                lam = p.reg_lambda
                gain = 0.5 * (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                              - G ** 2 / (H + lam))
                gain = np.where((HL >= p.min_child_hess)
                                & (HR >= p.min_child_hess), gain, -np.inf)
                gain = quantize_gains(gain)
                for j in range(n_level):
                    b = int(np.argmax(gain[j]))
                    gj = gain[j, b]
                    if np.isfinite(gj) and gj > best.get(j, (p.min_gain, 0, 0))[0]:
                        best[j] = (gj, f, b)

            # compute node values (Newton leaf) for every level node, used
            # by pass-through spines and final leaves
            g_sum = np.bincount(loc[active], weights=g[active], minlength=n_level)
            h_sum = np.bincount(loc[active], weights=h[active], minlength=n_level)
            for j in range(n_level):
                node_value[level_start + j] = float(
                    -p.learning_rate * g_sum[j] / (h_sum[j] + p.reg_lambda))

            for j in range(n_level):
                gid = level_start + j
                if j in best:
                    _, f, b = best[j]
                    feature[gid] = f
                    threshold[gid] = edges[f][b] if b < len(edges[f]) else np.inf
                # else: stays (feature=0, threshold=+inf) = pass-through left

            # descend samples on raw-threshold semantics via binned compare:
            # x > thr  <=>  bin(x) > bin_index(thr). threshold is the upper
            # edge of bin b, i.e. edges[f][b]; bin codes <= b go left.
            f_arr = feature[node]
            thr_bin = np.empty(n, dtype=np.int64)
            for j in range(n_level):
                gid = level_start + j
                sel = node == gid
                if not sel.any():
                    continue
                if np.isinf(threshold[gid]):
                    thr_bin[sel] = np.iinfo(np.int32).max
                else:
                    f = feature[gid]
                    b = int(np.searchsorted(edges[f], threshold[gid]))
                    thr_bin[sel] = b
            go_right = Xb[np.arange(n), f_arr] > thr_bin
            node = 2 * node + 1 + go_right

        # finalize leaves
        leaf_start = n_internal
        loc = node - leaf_start
        g_sum = np.bincount(loc, weights=g, minlength=n_leaves)
        h_sum = np.bincount(loc, weights=h, minlength=n_leaves)
        counts = np.bincount(loc, minlength=n_leaves)
        for j in range(n_leaves):
            if counts[j] > 0:
                leaf[j] = -p.learning_rate * g_sum[j] / (h_sum[j] + p.reg_lambda)
            else:
                # empty leaf: inherit nearest ancestor value (pass-through)
                anc = (leaf_start + j - 1) // 2
                while anc > 0 and anc not in node_value:
                    anc = (anc - 1) // 2
                leaf[j] = node_value.get(anc, 0.0)
        return feature, threshold, leaf, loc

    # ------------------------------------------------------------------ #
    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        assert self.forest is not None, "fit first"
        return self.forest.predict_proba(X)
