"""The DIAL policy every cell runs: two GBDT forests made from a seed.

Random forests from a seed stand in for trained ones, as random weights
do for a model: the fused loop's work per decision depends on the
forests' shape (trees, depth, features), not on their values, and the
reference scores the same arrays.  The forests are made here from
``bench/policy.json`` and never by the program's trainer, so the
reference takes nothing the program made.

Every split threshold lies halfway between two neighbouring quantiles of
that feature (from ``policy.json``), never on a value a feature takes,
and every leaf is a multiple of ``leaf_step``: a margin is then a sum of
dyadic numbers, exact in float32 and float64 in any order, so the
program and the reference agree on every margin bit for bit.

The forests are compile-time constants of the fused program.  So they
come from the policy's own seed, never from ``--seed``: one seed per
forest would compile a new program in every run.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_policy_config(path: str = os.path.join(HERE, "policy.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def make_forest(rng, quantiles, cfg: dict) -> dict:
    """One forest in the dense complete-binary-tree layout."""
    n_feat = len(quantiles)
    t, d = cfg["n_trees"], cfg["depth"]
    cuts = [np.unique(np.asarray(q, dtype=np.float64)) for q in quantiles]
    mids = [(c[1:] + c[:-1]) / 2 if len(c) > 1 else c + 0.5 for c in cuts]
    # a share of the splits falls on the four candidate-θ features (its
    # log2 knobs and its step from the applied knobs), so that scores
    # differ between candidates and decisions change knobs
    w = np.full(n_feat, (1.0 - cfg["theta_split_share"]) / (n_feat - 4))
    w[-4:] = cfg["theta_split_share"] / 4
    feature = rng.choice(n_feat, size=(t, 2**d - 1), p=w).astype(np.int32)
    threshold = np.empty((t, 2**d - 1), dtype=np.float32)
    for idx in np.ndindex(*feature.shape):
        threshold[idx] = rng.choice(mids[feature[idx]])
    step = cfg["leaf_step"]
    leaf = (np.round(rng.normal(0.0, cfg["leaf_sd"], size=(t, 2**d)) / step)
            * step).astype(np.float32)
    return {"feature": feature, "threshold": threshold, "leaf": leaf,
            "base": np.float32(cfg["base"]), "depth": int(d),
            "n_features": n_feat}


def make_policy(cfg: dict | None = None) -> dict:
    """``{"read": forest, "write": forest}`` from the policy's seed."""
    cfg = cfg or load_policy_config()
    rng = np.random.default_rng(cfg["seed"])
    return {op: make_forest(rng, cfg["quantiles"][op], cfg)
            for op in ("read", "write")}


def as_program_model(policy: dict):
    """The same forests as the program's ``DIALModel``."""
    from repro.core.gbdt import DenseForest
    from repro.core.model import DIALModel

    def dense(f):
        return DenseForest(feature=f["feature"], threshold=f["threshold"],
                           leaf=f["leaf"], base_score=float(f["base"]),
                           depth=f["depth"], n_features=f["n_features"])
    return DIALModel(read_forest=dense(policy["read"]),
                     write_forest=dense(policy["write"]))
