"""Weak-scaling fleet: tuned interfaces/sec vs device count, fixed
per-device load — plus the largest fleet the box holds.

DIAL's decentralization makes the fused decision loop embarrassingly
partitionable along the interface/batch axis: every decision reads only
its own interface's local counters, so sharding the batch over a 1-D
mesh (``FusedLoop(mesh=...)``) yields per-device programs with **zero
collectives**.  This benchmark holds the *per-device* load fixed
(``--per-device`` batch elements of a ``--clients x --osts`` mixed
scenario each) and grows the device count, so ideal weak scaling is a
flat time — i.e. tuned interface-intervals/sec growing linearly with
devices.

The sweep runs over the devices JAX can see, and asking for more is an
error.  On a CPU, more than one device comes only from the caller's
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.  Forced host
devices share the machine's physical cores: on a single-core box the
shards serialize and the curve is flat-per-device rather than linear —
the number to trust there is the per-interface cost and the max-fleet
capacity, and the curve itself on multi-core hardware.

The second phase lifts one mesh over *all* forced devices to the target
fleet size (``--max-fleet`` interfaces, default 2^17) and completes a
multi-interval tuned run — the O(10^5) capacity probe.

Run:  PYTHONPATH=src python benchmarks/fleet_weak_scaling.py
          [--devices 1 2 4] [--per-device 64] [--quick] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

TICKS_PER_INTERVAL = 100   # 0.5 s tuning interval at the 5 ms tick
N_INTERVALS = 4            # tuned intervals per timed run


def _parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, nargs="*", default=None,
                    help="device counts to sweep (default: every power "
                         "of two up to the visible count; quick: the "
                         "first two)")
    ap.add_argument("--per-device", type=int, default=None,
                    help="batch elements per device (default 64; "
                         "quick: 8)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--osts", type=int, default=2)
    ap.add_argument("--max-fleet", type=int, default=None,
                    help="capacity-probe target in interfaces "
                         "(default 2^17; quick: 4096; 0 disables)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 2 device points, small loads")
    ap.add_argument("--json", action="store_true",
                    help="emit the result dict as one final JSON line")
    return ap.parse_args(argv)


def run(devices=None, per_device: int = 64, clients: int = 8,
        osts: int = 2, max_fleet: int = 1 << 17) -> dict:
    """The weak-scaling sweep in this process; returns the result dict.

    ``devices`` defaults to every power of two up to the visible device
    count; a requested count above it raises ``ValueError``.
    """
    import numpy as np

    import jax

    from repro.distributed.sharding import fleet_mesh
    from repro.pfs.loop_jax import FusedLoop
    from repro.pfs.workloads import table_from_sim

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from fleet_scaling import get_model
    from loop_scaling import build_sim

    n_avail = jax.device_count()
    if devices is None:
        devices = [1 << k for k in range(n_avail.bit_length())]
    too_many = [d for d in devices if d > n_avail]
    if too_many:
        raise ValueError(f"asked for {too_many} devices but {n_avail} are "
                         f"visible (on a CPU, force more with XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N)")

    model = get_model("jax")
    sim = build_sim(clients, osts)
    n = sim.n_osc
    table, wstate0 = table_from_sim(sim)
    elem = (table, sim.state, wstate0)

    def lifted(b):
        """One scenario element tiled to a (b, ...) batch."""
        return jax.tree.map(
            lambda a: np.repeat(np.asarray(a)[None], b, axis=0), elem)

    def timed_run(n_dev: int, b: int) -> float:
        mesh = fleet_mesh(n_dev)
        loop = FusedLoop(sim.params, sim.topo, TICKS_PER_INTERVAL, model,
                         seg_backend="auto", batched=True, mesh=mesh)
        bt, bs, bw = lifted(b)
        loop.run(bt, bs, bw, N_INTERVALS)         # compile + warm
        t0 = time.perf_counter()
        loop.run(bt, bs, bw, N_INTERVALS)
        return time.perf_counter() - t0

    print(f"weak scaling: {per_device} elements/device x {n} interfaces, "
          f"{N_INTERVALS} x {TICKS_PER_INTERVAL}-tick tuned intervals "
          f"(compile excluded); {n_avail} devices visible, "
          f"{os.cpu_count()} host cores")
    print(f"{'devices':>8} {'interfaces':>11} {'s/run':>8} "
          f"{'if-intervals/s':>15} {'vs 1 dev':>9}")
    points, base_rate = [], None
    for d in devices:
        b = d * per_device
        t = timed_run(d, b)
        rate = b * n * N_INTERVALS / t
        base_rate = base_rate if base_rate is not None else rate
        points.append({"devices": d, "batch": b, "interfaces": b * n,
                       "seconds": round(t, 4),
                       "if_intervals_per_s": round(rate),
                       "speedup_vs_1dev": round(rate / base_rate, 2)})
        print(f"{d:>8} {b * n:>11} {t:>8.3f} {rate:>15.0f} "
              f"{rate / base_rate:>8.2f}x")

    probe = None
    if max_fleet:
        d = max(devices)
        b = max(max_fleet // n, d)
        b += (-b) % d                              # divisible fleet
        t = timed_run(d, b)
        rate = b * n * N_INTERVALS / t
        probe = {"devices": d, "interfaces": b * n,
                 "intervals": N_INTERVALS, "seconds": round(t, 3),
                 "if_intervals_per_s": round(rate)}
        print(f"max-fleet probe: {b * n} interfaces on {d} device(s), "
              f"{N_INTERVALS} tuned intervals in {t:.2f} s "
              f"({rate:.0f} if-intervals/s)")

    return {"schema": "dial-weak-scaling-v1",
            "interfaces_per_element": n,
            "per_device_elements": per_device,
            "host_cores": os.cpu_count(),
            "points": points, "max_fleet": probe}


def main(argv=None) -> None:
    args = _parse(argv)
    devices = args.devices
    if devices is None and args.quick:
        import jax

        devices = [1 << k for k in range(jax.device_count().bit_length())]
        devices = devices[:2]
    result = run(devices=devices,
                 per_device=args.per_device or (8 if args.quick else 64),
                 clients=args.clients, osts=args.osts,
                 max_fleet=(args.max_fleet if args.max_fleet is not None
                            else (4096 if args.quick else 1 << 17)))
    if args.json:
        print(json.dumps(result))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
