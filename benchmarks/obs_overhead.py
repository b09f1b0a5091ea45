"""Tracing overhead on the device-resident loop.

The telemetry contract is "free when off, cheap when on": the untraced
program is literally unchanged (trace records are additional scan
outputs, added only when a ``TraceConfig`` is passed at construction),
and at the default timeline stride the traced dispatch must stay within
a few percent of wall clock.  This benchmark pins the "cheap when on"
half: identical fused runs, untraced vs traced (decision provenance
only, and decisions + timeline at the default stride), compiled-program
execute time via double dispatch, compile time reported separately.

``overhead_pct`` at the default stride is the figure the perf ledger
guards (<= 10%); it rides ``benchmarks/run.py --json`` into
``BENCH_*.json`` and ``benchmarks/compare.py``.
"""

from __future__ import annotations

import time

import numpy as np

from repro.obs.schema import TraceConfig
from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.workloads import (random_stream, sequential_stream,
                                 table_from_sim)


def _sim(n_clients: int = 8, n_osts: int = 4):
    sim = PFSSim(n_clients=n_clients, n_osts=n_osts, seed=3)
    for c in range(n_clients):
        if c % 2 == 0:
            sim.attach(sequential_stream(c, READ, 2**20,
                                         ost=c % n_osts, n_threads=4))
        else:
            sim.attach(random_stream(c, WRITE, 64 * 1024,
                                     ost=c % n_osts, n_threads=4))
    return sim


def _time_loop(model, trace, seconds: float, interval: float,
               reps: int = 3) -> dict:
    """Best-of-``reps`` execute wall for one loop variant (first extra
    dispatch pays compilation, reported as ``compile_s``)."""
    from repro.pfs.loop_jax import FusedLoop

    proto = _sim()
    steps = max(int(round(interval / proto.params.tick)), 1)
    n_intervals = int(round(seconds / interval))
    loop = FusedLoop(proto.params, proto.topo, steps, model,
                     seg_backend="auto", trace=trace)
    walls = []
    for _ in range(reps + 1):
        s = _sim()
        table, wstate = table_from_sim(s)
        t0 = time.perf_counter()
        loop.run(table, s.state, wstate, n_intervals)
        walls.append(time.perf_counter() - t0)
    return {"execute_s": min(walls[1:]),
            "compile_s": walls[0] - min(walls[1:]),
            "n_intervals": n_intervals,
            "n_interfaces": proto.n_osc}


def bench(model=None, seconds: float = 20.0, interval: float = 0.5,
          stride: int = 20) -> dict:
    """Untraced vs traced fused runs; ``overhead_pct`` per variant."""
    if model is None:
        from repro.core.model import DIALModel
        model = DIALModel.load("models/dial")
        model.backend = "jax"

    base = _time_loop(model, None, seconds, interval)
    variants = {
        "decisions_only": TraceConfig(stride=stride, timeline=False),
        "default": TraceConfig(stride=stride, timeline=True),
    }
    out = {"untraced": base, "stride": stride}
    for name, cfg in variants.items():
        r = _time_loop(model, cfg, seconds, interval)
        r["overhead_pct"] = round(
            100.0 * (r["execute_s"] - base["execute_s"])
            / max(base["execute_s"], 1e-9), 2)
        out[name] = r
    return out


def main():
    res = bench()
    b = res["untraced"]
    print(f"untraced       : execute={b['execute_s']*1e3:8.1f} ms  "
          f"compile={b['compile_s']:.2f} s  "
          f"({b['n_intervals']} intervals x {b['n_interfaces']} interfaces)")
    for name in ("decisions_only", "default"):
        r = res[name]
        print(f"{name:15s}: execute={r['execute_s']*1e3:8.1f} ms  "
              f"compile={r['compile_s']:.2f} s  "
              f"overhead={r['overhead_pct']:+.1f}%")
    print(f"(timeline stride {res['stride']}; ledger guard: default "
          f"<= 10%)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
