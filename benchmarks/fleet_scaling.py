"""Fleet scaling: amortized per-interface tuning cost, 16 -> 512 clients.

The paper's Table III prices one tuning round at ~10-13.5 ms *per OSC
interface* — fine for one client, but a fleet of hundreds of clients
re-pays the Python/probe/model-entry overhead per interface every
interval.  This sweep drives identical simulator traces with

    loop   one :class:`ReferenceLoopAgent` per client (the paper's
           measured implementation: probe + model launch per interface);
    fleet  one :class:`FleetAgent` over every interface (one stacked
           probe, one fused model launch, one batched Algorithm 1).

and reports wall-clock per interface per tuning tick.  Decisions are
identical (tests/test_fleet.py); only the execution schedule differs, so
the gap is pure overhead amortization — and it must widen with scale.

Run:  PYTHONPATH=src python benchmarks/fleet_scaling.py [--quick]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.agent import ReferenceLoopAgent, SimClientPort
from repro.core.fleet import FleetAgent, SimFleetPort
from repro.core.model import DIALModel
from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.workloads import random_stream, sequential_stream

WARMUP_TICKS = 3   # agent warmup (2) + history fill (k=1)
TIMED_TICKS = 4
INTERVAL = 0.5     # paper probe interval [s]


def get_model(backend: str) -> DIALModel:
    try:
        model = DIALModel.load("models/dial", backend=backend)
        print("loaded pretrained forests from models/dial.*")
    except FileNotFoundError:
        from repro.core.dataset import CollectConfig, collect, train_models
        from repro.core.gbdt import GBDTParams

        print("training a quick model (no models/dial.* found)...")
        data = collect(CollectConfig(seconds=25.0, reps=1))
        model = train_models(data, GBDTParams(n_trees=40, max_depth=5))
        model.backend = backend
    return model


def build_sim(n_clients: int, n_osts: int, seed: int = 1) -> PFSSim:
    sim = PFSSim(n_clients=n_clients, n_osts=n_osts, seed=seed)
    for c in range(n_clients):
        # alternate op so both models stay hot; stripe over the OSTs
        if c % 2 == 0:
            sim.attach(sequential_stream(c, READ, 4 * 2**20, ost=c % n_osts))
        else:
            sim.attach(random_stream(c, WRITE, 256 * 1024, ost=c % n_osts,
                                     n_threads=2))
    sim.set_knobs(np.arange(sim.n_osc), window_pages=64, rpcs_in_flight=2)
    return sim


def _drive(sim, tick_fns, steps: int) -> float:
    """Advance ``WARMUP_TICKS + TIMED_TICKS`` intervals; return the total
    wall-clock seconds spent inside agent ticks after warmup."""
    spent = 0.0
    for interval in range(WARMUP_TICKS + TIMED_TICKS):
        for _ in range(steps):
            sim.step()
        t0 = time.perf_counter()
        for fn in tick_fns:
            fn()
        dt = time.perf_counter() - t0
        if interval >= WARMUP_TICKS:
            spent += dt
    return spent


def bench(n_clients: int, n_osts: int, model: DIALModel) -> dict:
    n_osc = n_clients * n_osts

    sim_l = build_sim(n_clients, n_osts)
    steps = int(round(INTERVAL / sim_l.params.tick))
    loop = [ReferenceLoopAgent(SimClientPort(sim_l, c), model)
            for c in range(n_clients)]
    t_loop = _drive(sim_l, [a.tick for a in loop], steps)

    sim_f = build_sim(n_clients, n_osts)
    fleet = FleetAgent(SimFleetPort(sim_f), model)
    t_fleet = _drive(sim_f, [fleet.tick], steps)

    per = lambda t: t / TIMED_TICKS / n_osc * 1e3
    return {"n_clients": n_clients, "n_osc": n_osc,
            "loop_ms": per(t_loop), "fleet_ms": per(t_fleet),
            "speedup": t_loop / max(t_fleet, 1e-12)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, nargs="*",
                    default=[16, 64, 128, 256, 512])
    ap.add_argument("--osts", type=int, default=2,
                    help="OSTs (= OSC interfaces per client)")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax"), help="model backend")
    ap.add_argument("--quick", action="store_true",
                    help="sweep 16..128 clients only")
    args = ap.parse_args()
    clients = [c for c in args.clients if c <= 128] if args.quick \
        else args.clients

    model = get_model(args.backend)
    print(f"\nbackend={model.backend}  interval={INTERVAL}s  "
          f"timed ticks={TIMED_TICKS}  (ms per interface per tuning tick)")
    print(f"{'clients':>8} {'oscs':>6} {'loop':>10} {'fleet':>10} "
          f"{'speedup':>8}")
    for c in clients:
        r = bench(c, args.osts, model)
        print(f"{r['n_clients']:>8} {r['n_osc']:>6} {r['loop_ms']:>9.3f}ms "
              f"{r['fleet_ms']:>9.3f}ms {r['speedup']:>7.1f}x")
    print("\npaper Table III prices the loop at 10-13.5 ms/interface on a "
          "16-core host;\nthe fleet path amortizes probe + launch overhead "
          "across the whole batch.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
