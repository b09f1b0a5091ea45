"""Ragged catalog economics: padded-ragged vs per-structure vs sequential.

A heterogeneous scenario catalog (mixed topologies, mixed workload-table
shapes from the PR-6 fuzz generator) historically paid one fused
dispatch — and one compiled program family — per *scenario*, or at best
per exact structure bucket.  Ragged pad-and-mask batching
(:func:`repro.lab.batch.bucket_scenarios`) collapses the catalog into
one dispatch per padded shape class.  This sweep drives the identical
tuned physics through all three groupings:

    sequential   one fused ``run_batch`` per scenario;
    structure    one per exact structure bucket (``ragged=False``);
    ragged       one per padded shape-class bucket (pad-and-mask).

reporting, per mode: fused dispatches, new compiled-loop instances
(cache misses on the cold pass — the cache persists across modes, so a
mode that reuses an earlier mode's wiring shows 0), and completed
scenario-seconds of simulation per wall-clock second on the warm pass
(compile excluded; per-element results are bit-equal across modes —
tests/test_ragged.py).

Run:  PYTHONPATH=src python benchmarks/ragged_scaling.py [--quick] [--json]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.gbdt import GBDTClassifier, GBDTParams
from repro.core.metrics import feature_dim
from repro.core.model import DIALModel
from repro.lab.batch import (bucket_scenarios, loop_cache_stats,
                             reset_loop_cache_stats, run_batch,
                             stack_scenarios)
from repro.lab.fuzz import FuzzConfig, generate_spec
from repro.lab.scenarios import build
from repro.pfs.state import READ, WRITE

SECONDS = 1.0              # 2 tuning intervals per scenario
INTERVAL = 0.5

#: catalog generator: four topology classes and fuzz-drawn workload
#: tables, so scenario count >> structure count >> pad-class count
CATALOG = FuzzConfig(seed=7, min_events=1, max_events=2)


def _tiny_model(k: int = 1) -> DIALModel:
    """A small self-contained forest pair — the sweep benchmarks
    dispatch structure, not model quality."""
    rng = np.random.default_rng(0)

    def forest(dim):
        x = rng.normal(size=(400, dim)).astype(np.float32)
        y = (x[:, 0] + x[:, -1] > -1.0).astype(np.int64)
        return GBDTClassifier(GBDTParams(n_trees=8, max_depth=3)).fit(
            x, y).forest

    return DIALModel(read_forest=forest(feature_dim(READ, k)),
                     write_forest=forest(feature_dim(WRITE, k)),
                     backend="jax", k=k)


def _groups(specs, mode: str):
    """The catalog regrouped for one execution mode (fresh state)."""
    built = [build(s) for s in specs]
    if mode == "sequential":
        return [stack_scenarios([b]) for b in built]
    return [batch for _, batch in
            bucket_scenarios(built, ragged=(mode == "ragged"))]


def _drive(groups, model, seg_backend: str) -> None:
    for batch in groups:
        run_batch(batch, model=model, seconds=SECONDS, interval=INTERVAL,
                  seg_backend=seg_backend, fused=True)


def bench(n_scenarios: int, seg_backend: str = "auto",
          model: DIALModel | None = None) -> dict:
    specs = [generate_spec(CATALOG, i) for i in range(n_scenarios)]
    model = model or _tiny_model()
    sim_seconds = n_scenarios * SECONDS
    out = {"n_scenarios": n_scenarios}
    for mode in ("sequential", "structure", "ragged"):
        groups = _groups(specs, mode)
        reset_loop_cache_stats()
        _drive(groups, model, seg_backend)       # cold: misses counted
        misses = loop_cache_stats()["misses"]
        groups = _groups(specs, mode)
        t0 = time.perf_counter()
        _drive(groups, model, seg_backend)       # warm: cache hits only
        t = time.perf_counter() - t0
        out[f"{mode}_dispatches"] = len(groups)
        out[f"{mode}_loop_misses"] = misses
        out[f"{mode}_sim_s_per_s"] = sim_seconds / max(t, 1e-12)
        out[f"_{mode}_wall_s"] = t
    out["ragged_speedup_vs_seq"] = (out.pop("_sequential_wall_s")
                                    / max(out["_ragged_wall_s"], 1e-12))
    out["ragged_speedup_vs_structure"] = (out.pop("_structure_wall_s")
                                          / max(out.pop("_ragged_wall_s"),
                                                1e-12))
    return out


def run(scales=(8, 16, 32), seg_backend: str = "auto") -> list[dict]:
    model = _tiny_model()
    return [bench(n, seg_backend, model=model) for n in scales]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--catalogs", type=int, nargs="*", default=[8, 16, 32])
    ap.add_argument("--seg-backend", default="auto")
    ap.add_argument("--quick", action="store_true",
                    help="sweep 8..16 scenarios only")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    scales = ([n for n in args.catalogs if n <= 16] if args.quick
              else args.catalogs)

    print(f"mixed-catalog tuning, {SECONDS:.0f} s per scenario at "
          f"{INTERVAL} s intervals (fused; warm pass timed, loop-cache "
          f"misses counted on the cold pass)")
    print(f"{'N':>4} {'mode':>10} {'dispatch':>8} {'loopmiss':>8} "
          f"{'sim-s/s':>10}")
    rows = []
    model = _tiny_model()
    for n in scales:
        r = bench(n, args.seg_backend, model=model)
        rows.append(r)
        for mode in ("sequential", "structure", "ragged"):
            print(f"{n:>4} {mode:>10} {r[f'{mode}_dispatches']:>8} "
                  f"{r[f'{mode}_loop_misses']:>8} "
                  f"{r[f'{mode}_sim_s_per_s']:>9.1f}")
        print(f"     ragged speedup: {r['ragged_speedup_vs_seq']:.1f}x vs "
              f"sequential, {r['ragged_speedup_vs_structure']:.1f}x vs "
              f"per-structure")
    if args.json:
        for r in rows:
            print(json.dumps({"schema": "dial-ragged-scaling-v1", **r}))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
