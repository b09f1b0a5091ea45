"""Engine scaling: Python tick loop vs fused JAX interval scan.

PR 1 removed the per-interface Python overhead from the *tuning* tick;
the remaining hot path is the simulator itself, stepped tick-by-tick
from Python.  This sweep drives identical workload mixes through

    loop    the numpy oracle: legacy ``Workload`` objects + one
            ``sim.step()`` Python call per 5 ms tick;
    fused   the execution layer: the same workloads frozen into a
            ``WorkloadTable`` and a whole 100-tick tuning interval run
            as one jitted ``lax.scan`` (``repro.pfs.engine_jax``).

and reports simulated ticks/second at 16 -> 1024 OSC interfaces.  Rows
mirror the ``fleet_scaling.py`` JSON shape (one dict per scale with a
``speedup`` key); compile time is excluded (one warmup interval).

Run:  PYTHONPATH=src python benchmarks/sim_scaling.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.workloads import random_stream, sequential_stream, table_from_sim

TICKS_PER_INTERVAL = 100   # 0.5 s tuning interval at the 5 ms tick
TIMED_INTERVALS = 4
N_OSTS = 2


def build_sim(n_clients: int, n_osts: int = N_OSTS, seed: int = 1) -> PFSSim:
    sim = PFSSim(n_clients=n_clients, n_osts=n_osts, seed=seed)
    for c in range(n_clients):
        if c % 2 == 0:
            sim.attach(sequential_stream(c, READ, 4 * 2**20, ost=c % n_osts))
        else:
            sim.attach(random_stream(c, WRITE, 256 * 1024, ost=c % n_osts,
                                     n_threads=2))
    sim.set_knobs(np.arange(sim.n_osc), window_pages=64, rpcs_in_flight=2)
    return sim


def bench(n_osc: int, seg_backend: str = "auto") -> dict:
    from repro.pfs.engine_jax import FusedEngine

    n_clients = n_osc // N_OSTS

    # numpy loop: warmup one interval, then time
    sim_l = build_sim(n_clients)
    for _ in range(TICKS_PER_INTERVAL):
        sim_l.step()
    t0 = time.perf_counter()
    for _ in range(TIMED_INTERVALS * TICKS_PER_INTERVAL):
        sim_l.step()
    t_loop = time.perf_counter() - t0

    # fused scan: warmup interval covers compile, then time
    sim_f = build_sim(n_clients)
    table, wstate = table_from_sim(sim_f)
    engine = FusedEngine(sim_f.params, sim_f.topo, table, TICKS_PER_INTERVAL,
                         seg_backend=seg_backend)
    state = sim_f.state
    state, wstate = engine.run_interval(state, wstate)
    t0 = time.perf_counter()
    for _ in range(TIMED_INTERVALS):
        state, wstate = engine.run_interval(state, wstate)
    t_fused = time.perf_counter() - t0

    ticks = TIMED_INTERVALS * TICKS_PER_INTERVAL
    return {"n_clients": n_clients, "n_osc": n_osc,
            "loop_ticks_per_s": ticks / t_loop,
            "fused_ticks_per_s": ticks / t_fused,
            "speedup": t_loop / max(t_fused, 1e-12)}


def run(scales=(16, 64, 256, 1024), seg_backend: str = "auto") -> list[dict]:
    return [bench(n, seg_backend) for n in scales]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--oscs", type=int, nargs="*",
                    default=[16, 64, 256, 1024])
    ap.add_argument("--seg-backend", default="auto",
                    choices=("auto", "jax", "pallas", "pallas_interpret"),
                    help="segment-reduce backend for the fused path")
    ap.add_argument("--quick", action="store_true",
                    help="sweep 16..256 OSCs only")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON row per scale")
    args = ap.parse_args()
    scales = [n for n in args.oscs if n <= 256] if args.quick else args.oscs

    print(f"ticks/sec over {TIMED_INTERVALS} x {TICKS_PER_INTERVAL}-tick "
          f"intervals (compile excluded)")
    print(f"{'oscs':>6} {'loop t/s':>12} {'fused t/s':>12} {'speedup':>8}")
    rows = []
    for n in scales:
        r = bench(n, args.seg_backend)
        rows.append(r)
        print(f"{r['n_osc']:>6} {r['loop_ticks_per_s']:>11.0f} "
              f"{r['fused_ticks_per_s']:>11.0f} {r['speedup']:>7.1f}x")
    if args.json:
        for r in rows:
            print(json.dumps(r))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
