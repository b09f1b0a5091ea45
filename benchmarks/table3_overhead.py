"""Paper Table III: DIAL execution overheads per OSC interface.

Wall-clock times for snapshot creation, model inference over the whole
configuration space, and the end-to-end tuning round — per operation type,
for the numpy reference backend and the jitted JAX path.

Since the fleet refactor, :class:`DIALAgent` scores all of its client's
interfaces per tick in one batch, so the reported figures are the batch
cost amortized per interface — the honest per-interface price an
operator pays.  ``benchmarks/fleet_scaling.py`` sweeps the same figure
against the historical per-interface loop at fleet scale.
"""

from __future__ import annotations

import numpy as np

from repro.core.agent import DIALAgent, SimClientPort
from repro.core.model import DIALModel
from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.workloads import random_stream, sequential_stream


def run(model_path: str = "models/dial", backend: str = "numpy",
        seconds: float = 20.0) -> dict:
    model = DIALModel.load(model_path)
    model.backend = backend
    sim = PFSSim(n_clients=1, n_osts=2, seed=3)
    sim.attach(sequential_stream(0, READ, 2**20, ost=0, n_threads=4))
    sim.attach(random_stream(0, WRITE, 64 * 1024, ost=1, n_threads=4))
    agent = DIALAgent(SimClientPort(sim, 0), model, measure_overhead=True)
    steps = int(round(0.5 / sim.params.tick))
    for _ in range(int(seconds / 0.5)):
        for _ in range(steps):
            sim.step()
        agent.tick()
    out = {}
    for op, name in ((READ, "read"), (WRITE, "write")):
        out[name] = agent.timings[op].summary()
    return out


def _fused_sim():
    sim = PFSSim(n_clients=2, n_osts=2, seed=3)
    sim.attach(sequential_stream(0, READ, 2**20, ost=0, n_threads=4))
    sim.attach(random_stream(1, WRITE, 64 * 1024, ost=1, n_threads=4))
    return sim


def run_fused(model_path: str = "models/dial", sharded: bool = False,
              seconds: float = 20.0, interval: float = 0.5) -> dict:
    """Table III analog for the device-resident paths.

    The fused loop admits no per-stage host timing — the whole run is
    one dispatch — so the honest per-interface figure is differential:
    wall time of the tuned dispatch minus the engine-only dispatch,
    amortized over the (interface × interval) decisions it covered.
    Each loop is dispatched twice on fresh state; the second call is
    the compiled-program cost (the first includes compilation, reported
    separately as ``compile_s``).  ``sharded=True`` times the
    ``shard_map`` program over the local device mesh instead.
    """
    import jax

    from repro.core.model import DIALModel
    from repro.pfs.loop_jax import FusedLoop
    from repro.pfs.workloads import table_from_sim

    model = DIALModel.load(model_path)
    model.backend = "jax"
    sim = _fused_sim()
    steps = max(int(round(interval / sim.params.tick)), 1)
    n_intervals = int(round(seconds / interval))
    mesh = None
    if sharded:
        from repro.distributed.sharding import fleet_mesh
        mesh = fleet_mesh()
    lift = (lambda tree: jax.tree.map(
        lambda a: np.stack([np.asarray(a)]), tree)) if sharded else \
        (lambda tree: tree)

    import time as _time
    out = {}
    for name, tuned in (("tuned", True), ("engine_only", False)):
        loop = FusedLoop(sim.params, sim.topo, steps,
                         model if tuned else None, seg_backend="auto",
                         tuned=tuned, batched=sharded, mesh=mesh)
        walls = []
        for rep in range(2):            # rep 0 pays compilation
            s = _fused_sim()
            table, wstate = table_from_sim(s)
            t0 = _time.perf_counter()
            loop.run(lift(table), lift(s.state), lift(wstate),
                     n_intervals)
            walls.append(_time.perf_counter() - t0)
        out[name] = {"compile_s": round(walls[0] - walls[1], 3),
                     "execute_s": round(walls[1], 3),
                     "phases": loop.timers.summary()}
    per_if = (out["tuned"]["execute_s"] - out["engine_only"]["execute_s"]) \
        / (n_intervals * sim.n_osc) * 1e3
    out["tuning_ms_per_interface_interval"] = round(per_if, 4)
    out["n_intervals"] = n_intervals
    out["n_interfaces"] = sim.n_osc
    return out


def main():
    for backend in ("numpy", "jax"):
        res = run(backend=backend)
        for op in ("read", "write"):
            r = res[op]
            print(f"[{backend:7s}] {op:5s}: snapshot={r['snapshot_ms']:6.2f} ms  "
                  f"inference={r['inference_ms']:6.2f} ms  "
                  f"end-to-end={r['end_to_end_ms']:6.2f} ms")
    for sharded in (False, True):
        rf = run_fused(sharded=sharded, seconds=10.0)
        tag = "jax-sharded" if sharded else "jax-fused"
        print(f"[{tag:11s}] tuning={rf['tuning_ms_per_interface_interval']:.4f} ms"
              f"/interface/interval  (tuned exec {rf['tuned']['execute_s']:.2f} s, "
              f"engine-only {rf['engine_only']['execute_s']:.2f} s, "
              f"compile {rf['tuned']['compile_s']:.2f} s)")
    print("(paper Table III: read 0.33/10.06/24.64 ms, "
          "write 0.85/13.51/28.82 ms on a 16-core host)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
