"""Scenario throughput: vmapped batch path vs sequential per-scenario loop.

The lab's claim is that scenario *count* is free: B structurally-
identical scenarios advance one tuning interval in a single vmapped
jitted launch, where the historical approach runs one Python interval
loop per scenario (the schedule ``core/dataset.collect`` and every
per-scenario experiment used to pay).

This sweep builds B jittered variants of one disturbed scenario
(``noisy_neighbor``: mixed reads under background contention bursts)
and drives the identical physics through

    sequential   one numpy ``run_interval`` per scenario per interval
                 (demand_step + engine_step, the oracle path);
    batched      one ``BatchEngine.run_interval`` for all B scenarios
                 (vmap of the fused lax.scan; compile excluded).

reporting completed scenario-seconds of simulation per wall-clock
second and the batch/sequential speedup at each B.

Run:  PYTHONPATH=src python benchmarks/lab_scaling.py [--quick] [--json]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.lab.batch import BatchEngine, stack_scenarios
from repro.lab.scenarios import build, get_scenario, variants
from repro.pfs.workloads import run_interval as np_run_interval

TICKS_PER_INTERVAL = 100   # 0.5 s tuning interval at the 5 ms tick
TIMED_INTERVALS = 2
BASE_SCENARIO = "noisy_neighbor"


def bench(batch_size: int, seg_backend: str = "auto",
          base: str = BASE_SCENARIO) -> dict:
    specs = variants(get_scenario(base), batch_size, seed=11)
    interval_s = TICKS_PER_INTERVAL * 0.005

    # sequential numpy loop over per-scenario intervals
    built = [build(s) for s in specs]
    t0 = time.perf_counter()
    for b in built:
        st, ws = b.state, b.wstate
        for i in range(TIMED_INTERVALS):
            sched = b.schedule(i * TICKS_PER_INTERVAL, TICKS_PER_INTERVAL)
            st, ws = np_run_interval(b.params, b.topo, b.table, st, ws,
                                     TICKS_PER_INTERVAL, schedule=sched)
    t_seq = time.perf_counter() - t0

    # vmapped batch (compile excluded via one warmup interval)
    batch = stack_scenarios([build(s) for s in specs])
    engine = BatchEngine(batch.params, batch.topo, TICKS_PER_INTERVAL,
                         seg_backend=seg_backend)
    sched = batch.schedule(0, TICKS_PER_INTERVAL)
    engine.run_interval(batch.table, batch.state, batch.wstate, sched)
    batch = stack_scenarios([build(s) for s in specs])
    t0 = time.perf_counter()
    for i in range(TIMED_INTERVALS):
        sched = batch.schedule(i * TICKS_PER_INTERVAL, TICKS_PER_INTERVAL)
        batch.state, batch.wstate = engine.run_interval(
            batch.table, batch.state, batch.wstate, sched)
    t_batch = time.perf_counter() - t0

    sim_seconds = batch_size * TIMED_INTERVALS * interval_s
    return {
        "batch_size": batch_size,
        "seq_scenario_s_per_s": sim_seconds / t_seq,
        "batch_scenario_s_per_s": sim_seconds / t_batch,
        "speedup": t_seq / max(t_batch, 1e-12),
    }


def run(scales=(8, 32, 128), seg_backend: str = "auto") -> list[dict]:
    return [bench(b, seg_backend) for b in scales]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batches", type=int, nargs="*", default=[8, 32, 128])
    ap.add_argument("--seg-backend", default="auto")
    ap.add_argument("--quick", action="store_true",
                    help="sweep 8..32 scenarios only")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    scales = [b for b in args.batches if b <= 32] if args.quick else args.batches

    print(f"scenario-seconds simulated per wall second over "
          f"{TIMED_INTERVALS} x {TICKS_PER_INTERVAL}-tick intervals "
          f"({BASE_SCENARIO} variants; compile excluded)")
    print(f"{'B':>5} {'seq sim-s/s':>12} {'batch sim-s/s':>14} {'speedup':>8}")
    rows = []
    for b in scales:
        r = bench(b, args.seg_backend)
        rows.append(r)
        print(f"{r['batch_size']:>5} {r['seq_scenario_s_per_s']:>11.1f} "
              f"{r['batch_scenario_s_per_s']:>13.1f} {r['speedup']:>7.1f}x")
    if args.json:
        for r in rows:
            print(json.dumps(r))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
