"""GBDT training throughput: vmapped jitted trainer vs the numpy loop.

The learn layer's claim is that model fitting no longer has to leave
the array program: a whole forest grows under ``jit`` (scan over trees,
unrolled level-synchronous depth loop, one-hot-matmul histograms) and a
``vmap`` trains a *batch* of forests — the read+write pair, or a whole
campaign hyperparameter sweep — in one launch.

This sweep builds B campaign-shaped datasets (smoke-campaign scale:
~384 rows x 32 designed-metric features per cell dataset, 32 quantile
bins — ample at ~12 rows/bin; both trainers bin identically) and times

    numpy     one ``GBDTClassifier.fit`` per dataset (the sequential
              oracle loop: Python over trees x depths x features);
    vmap      one ``fit_forest_batch`` launch for all B
              (``precision="fast"``: float32, the production online-
              refit configuration; compile excluded);
    vmap-x64  the same launch in ``precision="exact"`` (float64,
              split-for-split parity with the numpy loop),

reporting forests trained per wall-clock second (best of three timed
repetitions per path — the host is shared) and the speedup at each B.

Run:  PYTHONPATH=src python benchmarks/train_scaling.py [--quick] [--json]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.gbdt import GBDTClassifier, GBDTParams
from repro.learn.boost import fit_forest_batch

N_ROWS = 384          # smoke-campaign-sized cell dataset
N_FEATURES = 32       # the read model's designed-metric dimension
PARAMS = GBDTParams(n_trees=40, max_depth=5, n_bins=32)
NUMPY_CAP = 8         # numpy forests actually fitted (cost extrapolated)
REPS = 3              # timed repetitions; best is reported


def _datasets(batch: int, n: int = N_ROWS, n_feat: int = N_FEATURES):
    """B synthetic campaign-shaped datasets (distinct nonlinear rules)."""
    out = []
    for i in range(batch):
        rng = np.random.default_rng(1000 + i)
        X = rng.normal(size=(n, n_feat))
        y = ((X[:, i % n_feat] + 0.5 * X[:, (i + 3) % n_feat] > 0.2)
             | (X[:, (i + 5) % n_feat] * X[:, (i + 7) % n_feat] > 0.9)
             ).astype(float)
        out.append((X, y))
    return out


def _best_of(fn, reps: int = REPS) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench(batch: int, params: GBDTParams = PARAMS) -> dict:
    datasets = _datasets(batch)

    # numpy loop (cap the measured forests; the loop is embarrassingly
    # linear in B, so the per-forest cost extrapolates exactly)
    n_np = min(batch, NUMPY_CAP)

    def numpy_loop():
        for X, y in datasets[:n_np]:
            GBDTClassifier(params).fit(X, y)

    t_numpy = _best_of(numpy_loop, reps=2) * batch / n_np

    # jitted vmap launches (compile excluded via one warm call each)
    fit_forest_batch(datasets, params, precision="fast")
    t_fast = _best_of(
        lambda: fit_forest_batch(datasets, params, precision="fast"))

    fit_forest_batch(datasets, params, precision="exact")
    t_exact = _best_of(
        lambda: fit_forest_batch(datasets, params, precision="exact"))

    return {
        "batch_size": batch,
        "n_rows": N_ROWS,
        "n_features": N_FEATURES,
        "numpy_forests_per_s": batch / t_numpy,
        "fast_forests_per_s": batch / t_fast,
        "exact_forests_per_s": batch / t_exact,
        "fast_speedup": t_numpy / t_fast,
        "exact_speedup": t_numpy / t_exact,
    }


def run(scales=(8, 16, 32)) -> list[dict]:
    return [bench(b) for b in scales]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batches", type=int, nargs="*", default=[8, 16, 32])
    ap.add_argument("--quick", action="store_true",
                    help="sweep 8..16 forests only")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    scales = ([b for b in args.batches if b <= 16] if args.quick
              else args.batches)

    print(f"forests/s, {PARAMS.n_trees} trees x depth {PARAMS.max_depth}, "
          f"{N_ROWS} rows x {N_FEATURES} features per dataset "
          f"(compile excluded)")
    print(f"{'B':>4} {'numpy f/s':>10} {'fast f/s':>9} {'exact f/s':>10} "
          f"{'fast x':>7} {'exact x':>8}")
    rows = []
    for b in scales:
        r = bench(b)
        rows.append(r)
        print(f"{r['batch_size']:>4} {r['numpy_forests_per_s']:>10.2f} "
              f"{r['fast_forests_per_s']:>9.2f} "
              f"{r['exact_forests_per_s']:>10.2f} "
              f"{r['fast_speedup']:>6.1f}x {r['exact_speedup']:>7.1f}x")
    if args.json:
        for r in rows:
            print(json.dumps(r))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
