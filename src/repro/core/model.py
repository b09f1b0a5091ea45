"""DIAL's learned models: f(theta, H_t) -> P(improvement > 1+eps).

One :class:`DIALModel` bundles the two GBDT forests (read / write —
separate models per paper SIII-B) and the batched scorer that evaluates
the *entire* configuration space against the current history in one shot.

Backends:
    'numpy'  -- DenseForest.predict_proba (always available; the oracle)
    'jax'    -- jitted traversal (repro.kernels.gbdt_forest.ops): the fleet
                path scores both forests gather-free in one launch

The batched evaluation (n_oscs x |Theta| rows per tick) is the paper's
inference hot spot (Table III: ~10-13.5 ms per interface); the TPU
formulation evaluates all interfaces x configs in a single launch.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from repro.core.config_space import ConfigSpace, SPACE
from repro.core.gbdt import DenseForest
from repro.core.metrics import Snapshot, feature_vector
from repro.pfs.engine import READ, WRITE


def dataset_fingerprint(data: dict) -> dict:
    """Row counts + a cheap content hash of a ``{'read': (X, y), 'write':
    (X, y)}`` training dict — persisted with trained artifacts so
    evaluations can refuse models trained on a different dataset."""
    import hashlib

    h = hashlib.sha256()
    counts = {}
    for op_name in ("read", "write"):
        X, y = data[op_name]
        counts[op_name] = int(len(X))
        h.update(np.ascontiguousarray(np.asarray(X, dtype=np.float32)))
        h.update(np.ascontiguousarray(np.asarray(y, dtype=np.float64)))
    return {"rows": counts, "sha256": h.hexdigest()[:16]}


@dataclasses.dataclass
class DIALModel:
    read_forest: DenseForest
    write_forest: DenseForest
    space: ConfigSpace = SPACE
    backend: str = "numpy"
    k: int = 1  # history length (paper uses k=1)
    # provenance: trainer backend + dataset fingerprint, persisted by
    # save/load so artifact consumers can detect mismatched models
    train_meta: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.backend not in ("numpy", "jax"):
            raise ValueError(f"unknown DIALModel backend {self.backend!r} "
                             f"(want 'numpy' or 'jax')")
        self._theta_feats = self.space.as_features()  # (|Theta|, 2) log2
        self._jax_fns = {}
        # bumped by update_forests: consumers that baked forest copies
        # onto the device (e.g. the fused-loop cache) key on it so a
        # refit can never serve stale trees
        self._version = getattr(self, "_version", 0)

    def update_forests(self, read_forest: DenseForest | None = None,
                       write_forest: DenseForest | None = None) -> None:
        """Swap retrained forests in place (the online-refit path).

        Invalidates every cached jitted predictor — the old closures
        hold the stale forests on device — so the next score builds
        fresh ones from the new arrays.
        """
        if read_forest is not None:
            self.read_forest = read_forest
        if write_forest is not None:
            self.write_forest = write_forest
        self._jax_fns.clear()
        self._version += 1

    def forest(self, op: int) -> DenseForest:
        return self.read_forest if op == READ else self.write_forest

    # ------------------------------------------------------------------ #
    def features_for_space(self, history: list[Snapshot], op: int) -> np.ndarray:
        """(|Theta|, dim) feature matrix: H_t broadcast against every theta."""
        from repro.core.metrics import READ_KNOB_IDX, WRITE_KNOB_IDX

        hist = feature_vector(history, op, self._theta_feats[0])[:-4]
        knobs = READ_KNOB_IDX if op == READ else WRITE_KNOB_IDX
        last = (history[-1].read if op == READ else history[-1].write)
        cur = np.array([last[knobs[0]], last[knobs[1]]])
        m = len(self.space)
        out = np.empty((m, hist.shape[0] + 4), dtype=np.float32)
        out[:, :-4] = hist
        out[:, -4:-2] = self._theta_feats
        out[:, -2:] = self._theta_feats - cur[None, :]
        return out

    def score_space(self, history: list[Snapshot], op: int) -> np.ndarray:
        """f(theta, H_t) for every theta in space order."""
        X = self.features_for_space(history, op)
        return self.predict_proba(op, X)

    def score_space_batch(self, histories: list[list[Snapshot]],
                          op: int) -> np.ndarray:
        """(n_oscs, |Theta|) probabilities — one launch for all interfaces."""
        X = np.concatenate([self.features_for_space(h, op) for h in histories])
        p = self.predict_proba(op, X)
        return p.reshape(len(histories), len(self.space))

    def score_fleet(self, X_read: np.ndarray,
                    X_write: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Probabilities for mixed read/write row batches — the fleet path.

        ``X_read`` / ``X_write`` are the stacked (interface x config)
        feature rows from :func:`repro.core.metrics.fleet_feature_matrix`.
        On the jax backend both ops are fused into **one** launch
        with a per-row forest selector (the two forests live stacked on
        device); the numpy backend scores each forest once — still one
        batched traversal per op, never one call per interface.
        """
        if self.backend == "numpy":
            p_read = (self.read_forest.predict_proba(X_read)
                      if len(X_read) else np.zeros(0))
            p_write = (self.write_forest.predict_proba(X_write)
                       if len(X_write) else np.zeros(0))
            return p_read, p_write
        from repro.kernels.gbdt_forest import ops as kops  # lazy import
        key = ("fleet", self.backend)
        if key not in self._jax_fns:
            self._jax_fns[key] = kops.make_fleet_predictor(
                self.read_forest, self.write_forest)
        return self._jax_fns[key](X_read, X_write)

    def paired_arrays(self):
        """Both forests stacked into one paired tensor set (numpy).

        ``(feature, threshold, leaf, base, depth, n_features)`` with
        forest axis 0 = read, 1 = write — the arrays the fused fleet
        predictor and the device-resident loop
        (:mod:`repro.pfs.loop_jax`) traverse with a per-row op selector.
        Cached until :meth:`update_forests` swaps the forests.
        """
        from repro.kernels.gbdt_forest import ops as kops  # lazy import
        key = ("paired",)
        if key not in self._jax_fns:
            self._jax_fns[key] = kops.pair_forests(self.read_forest,
                                                   self.write_forest)
        return self._jax_fns[key]

    # ------------------------------------------------------------------ #
    def predict_proba(self, op: int, X: np.ndarray) -> np.ndarray:
        f = self.forest(op)
        if self.backend == "numpy":
            return f.predict_proba(X)
        from repro.kernels.gbdt_forest import ops as kops  # lazy import
        key = (op, self.backend)
        if key not in self._jax_fns:
            self._jax_fns[key] = kops.make_predictor(f)
        return np.asarray(self._jax_fns[key](np.asarray(X, dtype=np.float32)))

    # ------------------------------------------------------------------ #
    def save(self, prefix: str) -> None:
        self.read_forest.save(prefix + ".read.npz")
        self.write_forest.save(prefix + ".write.npz")
        meta_path = prefix + ".meta.json"
        if self.train_meta:
            with open(meta_path, "w") as f:
                json.dump(self.train_meta, f, indent=2, default=str)
        elif os.path.exists(meta_path):
            # never leave another model's provenance attached to these
            # forests — a stale meta.json would defeat the artifact guard
            os.remove(meta_path)

    @staticmethod
    def load(prefix: str, backend: str = "numpy") -> "DIALModel":
        meta = {}
        meta_path = prefix + ".meta.json"
        if os.path.exists(meta_path):
            try:
                with open(meta_path) as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                meta = {}
        return DIALModel(
            read_forest=DenseForest.load(prefix + ".read.npz"),
            write_forest=DenseForest.load(prefix + ".write.npz"),
            backend=backend, train_meta=meta)
