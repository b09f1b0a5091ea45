"""Decision-loop scaling: per-interval host loop vs one jit per run.

The tuned simulator has three execution schedules for the same
algorithm (decisions are identical on all of them —
tests/test_loop_fused.py):

    host-numpy  the run_fleet default: Python tick loop for the engine,
                host probe/snapshot/featurize/Algorithm 1 per interval;
    host-jax    jitted engine interval scan, but the decision path still
                surfaces per interval (one device round trip + host
                numpy tuning every 0.5 s of simulated time);
    fused       repro.pfs.loop_jax.FusedLoop — N intervals of engine
                *and* tuning as a single jitted dispatch.

This sweep reports **tuned intervals per second** at 64 / 256 / 1024
OSC interfaces.  The headline number is fused vs the per-interval host
loop (run_fleet's default backend); fused vs host-jax isolates what
fusing just the decision path buys on top of the already-fused engine.
Compile time is excluded (one warmup run per path).

Run:  PYTHONPATH=src python benchmarks/loop_scaling.py [--quick] [--json]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.fleet import FleetAgent, SimFleetPort
from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.workloads import random_stream, sequential_stream, table_from_sim

TICKS_PER_INTERVAL = 100   # 0.5 s tuning interval at the 5 ms tick
N_INTERVALS = 6            # timed tuned intervals per path
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


def get_model(backend: str = "jax"):
    try:                                    # as benchmarks.loop_scaling
        from benchmarks.fleet_scaling import get_model as _get
    except ModuleNotFoundError:             # as a standalone script
        from fleet_scaling import get_model as _get
    return _get(backend)


def _bench_host_numpy(n_clients: int, model) -> float:
    sim = build_sim(n_clients)
    fleet = FleetAgent(SimFleetPort(sim), model)
    for _ in range(TICKS_PER_INTERVAL):     # warmup interval: compiles
        sim.step()                          # the model predictor
    fleet.tick()
    t0 = time.perf_counter()
    for _ in range(N_INTERVALS):
        for _ in range(TICKS_PER_INTERVAL):
            sim.step()
        fleet.tick()
    return time.perf_counter() - t0


def _bench_host_jax(n_clients: int, model, seg_backend: str) -> float:
    from repro.pfs.engine_jax import FusedEngine

    sim = build_sim(n_clients)
    table, wstate = table_from_sim(sim)
    engine = FusedEngine(sim.params, sim.topo, table, TICKS_PER_INTERVAL,
                         seg_backend=seg_backend)
    fleet = FleetAgent(SimFleetPort(sim), model)
    sim.state, wstate = engine.run_interval(sim.state, wstate)  # compile
    fleet.tick()
    t0 = time.perf_counter()
    for _ in range(N_INTERVALS):
        sim.state, wstate = engine.run_interval(sim.state, wstate)
        fleet.tick()
    return time.perf_counter() - t0


def _bench_fused(n_clients: int, model, seg_backend: str) -> float:
    from repro.pfs.loop_jax import FusedLoop

    sim = build_sim(n_clients)
    table, wstate = table_from_sim(sim)
    loop = FusedLoop(sim.params, sim.topo, TICKS_PER_INTERVAL, model,
                     seg_backend=seg_backend)
    state = sim.state
    loop.run(table, state, wstate, N_INTERVALS)     # compile + warm
    t0 = time.perf_counter()
    loop.run(table, state, wstate, N_INTERVALS)
    return time.perf_counter() - t0


def bench(n_osc: int, seg_backend: str = "auto", model=None) -> dict:
    model = model if model is not None else get_model("jax")
    n_clients = n_osc // N_OSTS
    t_np = _bench_host_numpy(n_clients, model)
    t_jax = _bench_host_jax(n_clients, model, seg_backend)
    t_fused = _bench_fused(n_clients, model, seg_backend)
    ips = lambda t: N_INTERVALS / t
    return {
        "n_osc": n_osc,
        "host_numpy_ips": ips(t_np),
        "host_jax_ips": ips(t_jax),
        "fused_ips": ips(t_fused),
        "speedup_vs_host_numpy": t_np / max(t_fused, 1e-12),
        "speedup_vs_host_jax": t_jax / max(t_fused, 1e-12),
    }


def run(scales=(64, 256, 1024), seg_backend: str = "auto") -> list[dict]:
    model = get_model("jax")
    return [bench(n, seg_backend, model) for n in scales]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--oscs", type=int, nargs="*", default=[64, 256, 1024])
    ap.add_argument("--seg-backend", default="auto")
    ap.add_argument("--quick", action="store_true",
                    help="sweep 64..256 interfaces only")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON row per scale")
    args = ap.parse_args()
    scales = [n for n in args.oscs if n <= 256] if args.quick else args.oscs

    model = get_model("jax")
    print(f"tuned intervals/sec over {N_INTERVALS} x {TICKS_PER_INTERVAL}"
          f"-tick intervals (compile excluded)")
    print(f"{'oscs':>6} {'host-numpy':>11} {'host-jax':>10} {'fused':>10} "
          f"{'vs numpy':>9} {'vs jax':>8}")
    rows = []
    for n in scales:
        r = bench(n, args.seg_backend, model)
        rows.append(r)
        print(f"{r['n_osc']:>6} {r['host_numpy_ips']:>10.2f} "
              f"{r['host_jax_ips']:>9.2f} {r['fused_ips']:>9.2f} "
              f"{r['speedup_vs_host_numpy']:>8.1f}x "
              f"{r['speedup_vs_host_jax']:>7.1f}x")
    if args.json:
        for r in rows:
            print(json.dumps(r))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
