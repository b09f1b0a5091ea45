"""Benchmark orchestrator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus the detailed tables.
``--json PATH`` additionally writes the rows as a machine-readable
``BENCH_*.json`` (one object per benchmark: name / us_per_call /
derived key-values) so the perf trajectory can be tracked across
commits (``make bench-json``).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import time


def _record(records: list, name: str, us_per_call: float,
            derived: dict) -> None:
    pairs = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us_per_call:.0f},{pairs}")
    records.append({"name": name, "us_per_call": round(us_per_call),
                    "derived": derived})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the summary rows as JSON "
                         "(e.g. reports/BENCH_latest.json)")
    args = ap.parse_args(argv)

    import benchmarks.fig3_dlio as fig3
    import benchmarks.fleet_scaling as fleet
    import benchmarks.fleet_weak_scaling as fws
    import benchmarks.lab_scaling as labsc
    import benchmarks.loop_scaling as loopsc
    import benchmarks.obs_overhead as obsov
    import benchmarks.ragged_scaling as raggedsc
    import benchmarks.sim_scaling as simsc
    import benchmarks.table2_h5bench as t2
    import benchmarks.table3_overhead as t3
    import benchmarks.train_scaling as trainsc

    records: list[dict] = []
    print("name,us_per_call,derived")

    t0 = time.time()
    rows2 = t2.run()
    el = (time.time() - t0) * 1e6 / max(len(rows2), 1)
    worst = min(r["dial_frac_of_optimal"] for r in rows2)
    _record(records, "table2_h5bench", el,
            {"min_frac_of_optimal": round(worst, 3)})

    t0 = time.time()
    rows3 = fig3.run()
    el = (time.time() - t0) * 1e6 / max(len(rows3), 1)
    best = max(r["speedup"] for r in rows3)
    _record(records, "fig3_dlio", el,
            {"max_speedup_vs_default": round(best, 2)})

    t0 = time.time()
    res = t3.run(backend="numpy")
    el = (time.time() - t0) * 1e6
    _record(records, "table3_overhead", el,
            {"read_e2e_ms": round(res["read"]["end_to_end_ms"], 2),
             "write_e2e_ms": round(res["write"]["end_to_end_ms"], 2)})

    for sharded, tag in ((False, "table3_fused"), (True, "table3_sharded")):
        t0 = time.time()
        rfu = t3.run_fused(sharded=sharded, seconds=10.0)
        el = (time.time() - t0) * 1e6
        _record(records, tag, el,
                {"tuning_ms_per_if_interval":
                     rfu["tuning_ms_per_interface_interval"],
                 "tuned_execute_s": rfu["tuned"]["execute_s"],
                 "tuned_compile_s": rfu["tuned"]["compile_s"],
                 "engine_only_execute_s": rfu["engine_only"]["execute_s"]})

    t0 = time.time()
    ro = obsov.bench(seconds=10.0)
    el = (time.time() - t0) * 1e6
    _record(records, "obs_overhead", el,
            {"stride": ro["stride"],
             "untraced_execute_ms":
                 round(ro["untraced"]["execute_s"] * 1e3, 1),
             "decisions_only_overhead_pct":
                 ro["decisions_only"]["overhead_pct"],
             "default_overhead_pct": ro["default"]["overhead_pct"]})

    t0 = time.time()
    fm = fleet.get_model("numpy")
    rf = fleet.bench(128, 2, fm)
    el = (time.time() - t0) * 1e6
    _record(records, "fleet_scaling", el,
            {"fleet_ms_per_osc": round(rf["fleet_ms"], 3),
             "loop_ms_per_osc": round(rf["loop_ms"], 3),
             "speedup": round(rf["speedup"], 1)})

    t0 = time.time()
    rs = simsc.bench(256)
    el = (time.time() - t0) * 1e6
    _record(records, "sim_scaling", el,
            {"loop_tps": round(rs["loop_ticks_per_s"]),
             "fused_tps": round(rs["fused_ticks_per_s"]),
             "speedup": round(rs["speedup"], 1)})

    t0 = time.time()
    rl = labsc.bench(32)
    el = (time.time() - t0) * 1e6
    _record(records, "lab_scaling", el,
            {"seq_sim_s_per_s": round(rl["seq_scenario_s_per_s"], 1),
             "batch_sim_s_per_s": round(rl["batch_scenario_s_per_s"], 1),
             "speedup": round(rl["speedup"], 1)})

    t0 = time.time()
    rr = raggedsc.bench(16)
    el = (time.time() - t0) * 1e6
    _record(records, "ragged_scaling", el,
            {"n_scenarios": rr["n_scenarios"],
             "seq_dispatches": rr["sequential_dispatches"],
             "structure_dispatches": rr["structure_dispatches"],
             "ragged_dispatches": rr["ragged_dispatches"],
             "ragged_loop_misses": rr["ragged_loop_misses"],
             "ragged_sim_s_per_s": round(rr["ragged_sim_s_per_s"], 1),
             "speedup_vs_seq": round(rr["ragged_speedup_vs_seq"], 1),
             "speedup_vs_structure":
                 round(rr["ragged_speedup_vs_structure"], 2)})

    t0 = time.time()
    rlp = loopsc.bench(256)
    el = (time.time() - t0) * 1e6
    _record(records, "loop_scaling", el,
            {"host_loop_ips": round(rlp["host_numpy_ips"], 2),
             "fused_ips": round(rlp["fused_ips"], 2),
             "speedup_vs_host_loop": round(rlp["speedup_vs_host_numpy"], 1)})

    t0 = time.time()
    rt = trainsc.bench(16)
    el = (time.time() - t0) * 1e6
    _record(records, "train_scaling", el,
            {"numpy_forests_per_s": round(rt["numpy_forests_per_s"], 2),
             "fast_forests_per_s": round(rt["fast_forests_per_s"], 2),
             "exact_forests_per_s": round(rt["exact_forests_per_s"], 2),
             "fast_speedup": round(rt["fast_speedup"], 1)})

    # in this process, over the devices JAX already sees: a second JAX
    # process could not reach a chip this one holds
    t0 = time.time()
    wk = fws.run()
    el = time.time() - t0
    last, probe = wk["points"][-1], wk["max_fleet"]
    _record(records, "fleet_weak_scaling", el * 1e6,
            {"max_devices": last["devices"],
             "if_intervals_per_s": last["if_intervals_per_s"],
             "speedup_vs_1dev": last["speedup_vs_1dev"],
             "host_cores": wk["host_cores"],
             "max_fleet_interfaces": probe["interfaces"],
             "max_fleet_seconds": probe["seconds"]})

    if args.json:
        from repro.obs.provenance import collect_provenance

        payload = {
            "schema": "dial-bench-v1",
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "provenance": collect_provenance(),
            "benchmarks": records,
        }
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"\nwrote {args.json}")

    print("\n--- Table II detail ---")
    for r in rows2:
        print(f"{r['workload']:28s} optimal={r['optimal_mbs']:8.1f} "
              f"DIAL={r['dial_mbs']:8.1f} ({100*r['dial_frac_of_optimal']:.1f}%)")
    print("\n--- Fig. 3 detail ---")
    for r in rows3:
        print(f"DLIO-{r['kernel']:9s} t={r['threads']:2d} osts={r['osts']}: "
              f"default={r['default_mbs']:7.1f} DIAL={r['dial_mbs']:7.1f} "
              f"({r['speedup']:.2f}x)")
    print("\n--- Table III detail (numpy backend) ---")
    for op in ("read", "write"):
        r = res[op]
        print(f"{op:5s}: snapshot={r['snapshot_ms']:.2f} ms "
              f"inference={r['inference_ms']:.2f} ms "
              f"end_to_end={r['end_to_end_ms']:.2f} ms")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
