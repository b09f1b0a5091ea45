#!/usr/bin/env python3
"""Smoke run of DIAL's device-resident tuning loop on a TPU.

    python chip_smoke.py [--seed 0]            # one chip
    python chip_smoke.py --four-chips [--seed 0]

This proves that the main path compiles and runs on the chip and that
what it computes agrees with the numpy oracle.  It is not a benchmark:
the times it prints come from one cold run each.

One chip, in one process:

1. refuses to run unless JAX's first device is a TPU;
2. trains the model from ``--seed`` through the campaign path (smoke
   config, jax trainer), so the learn layer runs on the chip too;
3. catalog: ``lab.evaluate.evaluate`` over the whole registry (every
   static θ arm plus DIAL, ragged, fused), then the DIAL arms of
   ``vpic_checkpoint`` and ``bdcats_analysis`` against the numpy oracle
   (``lab.batch.run_oracle``): θ trajectories exact, MB/s within 1e-6;
4. deployment: the VPIC-IO + BDCATS-IO recipe checked against the oracle
   at 128 clients x 16 OSTs, then run at 1024 clients x 64 OSTs (65,536
   OSC interfaces), 20 tuned intervals of 100 ticks in one dispatch.

``--four-chips`` runs only the mesh path and its reference: 16 seeded
variants of the recipe at 128 x 16, 3 s each, through
``run_batch(fused=True, mesh=fleet_mesh(4))`` against the same batch on
one chip (θ bit-equal, counters within 1e-6), and checks that the
sharded program holds no collective.

Any failed check or raised error exits non-zero.  On success the last
line of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SECONDS = 10.0          # simulated seconds per run ...
INTERVAL = 0.5          # ... in 20 tuned intervals of 100 ticks
RTOL = 1e-6
CHECK_SHAPE = (128, 16)     # recipe checked against the oracle
DEPLOY_SHAPE = (1024, 64)   # 65,536 OSC interfaces in one fused dispatch
MESH_SHAPE = (128, 16)      # per variant, 16 variants over four chips ...
MESH_SECONDS = 3.0          # ... for 6 intervals (DIAL first decides in the
#                             4th): cut from 256 x 16 and 10 s to fit the
#                             four-chip time
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
COUNTERS = ("ctr_bytes_done", "ctr_rpcs_sent", "ctr_rpc_bytes",
            "ctr_partial_rpcs", "ctr_latency_sum", "ctr_rpcs_done",
            "ctr_req_count", "ctr_req_bytes", "ctr_cache_hit_bytes",
            "ctr_block_time", "ctr_pending_integral", "ctr_active_integral",
            "ctr_dirty_integral", "ctr_grant_integral")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def require_tpu(n_chips: int):
    import importlib.metadata as md

    import jax

    devs = jax.devices()
    d = devs[0]

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "absent"

    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} python={sys.version.split()[0]} "
        f"jax={jax.__version__} jaxlib={version('jaxlib')} "
        f"libtpu={version('libtpu')} numpy={version('numpy')}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX's first device is "
                         f"{d.platform}); this check runs only on the chip")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips needed, "
                         f"{len(devs)} visible")
    return devs


def train(seed: int):
    from repro.lab.campaign import run_campaign, smoke_campaign

    cfg, gbdt = smoke_campaign()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as out_root:   # artifacts unused
        _, model, info = run_campaign(
            dataclasses.replace(cfg, seed=seed), out_root=out_root,
            gbdt_params=gbdt, smoke=True, trainer_backend="jax")
    log(f"train: smoke campaign seed={seed}, trainer "
        f"{info['train_meta']['trainer_backend']}, samples "
        f"{info['samples']}, {time.perf_counter() - t0:.1f} s")
    return model


def trajectory(decisions) -> list:
    return [(r.oscs.tolist(), r.ops.tolist(), r.decisions.theta.tolist(),
             r.decisions.changed.tolist()) for r in decisions]


def rel_diff(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, dtype=float), np.asarray(b, dtype=float)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


def check_vs_oracle(label: str, spec, model, dial_mbs=None) -> None:
    """One fused DIAL run of ``spec`` against the numpy oracle."""
    from repro.lab.batch import run_batch, run_oracle, stack_scenarios
    from repro.lab.scenarios import build

    batch = stack_scenarios([build(spec)])
    t0 = time.perf_counter()
    res = run_batch(batch, model=model, seconds=SECONDS, interval=INTERVAL,
                    fused=True)
    t_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    fleet, oracle = run_oracle(build(spec), model, seconds=SECONDS,
                               interval=INTERVAL)
    t_oracle = time.perf_counter() - t0
    got = trajectory(res.decisions)
    want = trajectory(fleet.decisions)
    n_decided = sum(len(r[0]) for r in want)
    n_changed = sum(sum(r[3]) for r in want)
    check(n_decided > 0, f"{label}: the oracle run decided nothing — the "
                         f"θ comparison would be vacuous")
    check(got == want, f"{label}: θ trajectory differs from the oracle")
    mbs = float(batch.throughput(SECONDS)["total_mbs"][0])
    mbs_o = float(oracle.throughput(SECONDS)["total_mbs"][0])
    err = rel_diff(mbs, mbs_o)
    check(err <= RTOL, f"{label}: fused {mbs!r} MB/s vs oracle {mbs_o!r} "
                       f"(rel {err!r})")
    line = (f"oracle {label}: {spec.n_clients}x{spec.n_osts} "
            f"({spec.n_clients * spec.n_osts} OSCs), θ trajectory exact "
            f"({n_decided} decisions, {n_changed} knob changes), MB/s "
            f"fused {mbs!r} oracle {mbs_o!r} rel {err!r}")
    if dial_mbs is not None:
        err_c = rel_diff(dial_mbs, mbs_o)
        check(err_c <= RTOL, f"{label}: catalog DIAL arm {dial_mbs!r} MB/s "
                             f"vs oracle {mbs_o!r} (rel {err_c!r})")
        line += f", catalog DIAL arm {dial_mbs!r} rel {err_c!r}"
    log(line + f"; fused {t_fused:.1f} s, oracle {t_oracle:.1f} s")


def catalog_phase(model) -> None:
    import numpy as np

    from repro.lab.evaluate import evaluate
    from repro.lab.scenarios import SCENARIOS, get_scenario

    t0 = time.perf_counter()
    report = evaluate(model=model, seconds=SECONDS, interval=INTERVAL)
    wall = time.perf_counter() - t0
    s = report["summary"]
    rows = {r["scenario"]: r for r in report["scenarios"]}
    check(set(rows) == set(SCENARIOS), "catalog report misses scenarios")
    for r in rows.values():
        vals = [r["default_mbs"], r["best_static_mbs"], r["dial_mbs"]]
        check(np.all(np.isfinite(vals)) and min(vals) > 0,
              f"catalog {r['scenario']}: non-finite or zero MB/s {vals}")
    log(f"catalog: {s['n_scenarios']} scenarios x 25 arms, "
        f"{s['n_buckets']} buckets, {s['n_dispatches']} fused dispatches, "
        f"{wall:.1f} s; mean DIAL vs default "
        f"{s['mean_dial_vs_default']!r}x")
    for name in ("vpic_checkpoint", "bdcats_analysis"):
        check_vs_oracle(name, get_scenario(name), model,
                        dial_mbs=rows[name]["dial_mbs"])


def deployment_phase(model) -> None:
    import jax
    import numpy as np

    from repro.core.config_space import SPACE
    from repro.lab.batch import run_batch, stack_scenarios
    from repro.lab.scenarios import build, vpic_bdcats_deployment

    check_vs_oracle("deployment", vpic_bdcats_deployment(*CHECK_SHAPE),
                    model)

    spec = vpic_bdcats_deployment(*DEPLOY_SHAPE)
    n_osc = spec.n_clients * spec.n_osts
    n_intervals = int(round(SECONDS / INTERVAL))
    batch = stack_scenarios([build(spec)])
    # one cold dispatch: JAX reports how long it spent compiling
    compile_s = []
    listen = (lambda event, secs, **_: compile_s.append(secs)
              if event in COMPILE_EVENTS else None)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        t0 = time.perf_counter()
        res = run_batch(batch, model=model, seconds=SECONDS,
                        interval=INTERVAL, fused=True)
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    t_compile = sum(compile_s)
    t_exec = wall - t_compile
    st = batch.state
    for f in ("ctr_bytes_done", "ctr_rpcs_done", "ctr_latency_sum",
              "dirty_bytes", "pending"):
        a = np.asarray(getattr(st, f))
        check(np.all(np.isfinite(a)) and np.all(a >= -1e-6),
              f"deployment: {f} has non-finite or negative values")
    check(np.asarray(st.window_pages).shape == (1, n_osc),
          "deployment: state has the wrong shape")
    theta = set(zip(np.asarray(st.window_pages).ravel().tolist(),
                    np.asarray(st.rpcs_in_flight).ravel().tolist()))
    check(theta <= set(SPACE.configs()), "deployment: θ left the Θ grid")
    n_decided = sum(len(r) for r in res.decisions)
    n_changed = sum(int(r.decisions.changed.sum()) for r in res.decisions)
    check(n_decided > 0, "deployment: no interface decided")
    mbs = float(batch.throughput(SECONDS)["total_mbs"][0])
    check(mbs > 0, "deployment: no bytes moved")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"deployment (smoke run, not a benchmark): {spec.n_clients} clients "
        f"x {spec.n_osts} OSTs = {n_osc} OSCs, {n_intervals} tuned "
        f"intervals x {int(round(INTERVAL / batch.params.tick))} ticks in "
        f"one dispatch; wall {wall!r} s = compile {t_compile!r} s (trace, "
        f"lower, XLA) + execute {t_exec!r} s; tuned "
        f"interface-intervals/s {n_osc * n_intervals / t_exec!r}; "
        f"peak_bytes_in_use {peak}; {n_decided} decisions, {n_changed} "
        f"knob changes; "
        f"simulated {mbs!r} MB/s")


def four_chip_phase(model, seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.distributed.sharding import fleet_mesh
    from repro.lab.batch import run_batch, stack_scenarios
    from repro.lab.scenarios import build, variants, vpic_bdcats_deployment
    from repro.pfs.loop_jax import FusedLoop

    specs = variants(vpic_bdcats_deployment(*MESH_SHAPE), 16, seed=seed)
    mesh = fleet_mesh(4)
    out = {}
    for tag, m in (("mesh", mesh), ("one_chip", None)):
        batch = stack_scenarios([build(s) for s in specs])
        t0 = time.perf_counter()
        res = run_batch(batch, model=model, seconds=MESH_SECONDS,
                        interval=INTERVAL, fused=True, mesh=m)
        out[tag] = (time.perf_counter() - t0, batch, res)
    (t_m, b_m, r_m), (t_1, b_1, r_1) = out["mesh"], out["one_chip"]
    check(trajectory(r_m.decisions) == trajectory(r_1.decisions),
          "four chips: θ trajectories differ from the one-chip run")
    n_changed = sum(int(r.decisions.changed.sum()) for r in r_1.decisions)
    check(sum(len(r) for r in r_1.decisions) > 0,
          "four chips: no interface decided")
    worst = 0.0
    for f in COUNTERS:
        a, b = (np.asarray(getattr(x.state, f), dtype=float)
                for x in (b_m, b_1))
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=1e-6, err_msg=f)
        worst = max(worst, float(np.max(np.abs(a - b)
                                        / np.maximum(np.abs(b), 1e-6))))

    # the sharded program, compiled for the same shapes: no collective
    steps = int(round(INTERVAL / b_1.params.tick))
    n_int = int(round(MESH_SECONDS / INTERVAL))
    loop = FusedLoop(b_1.params, b_1.topo, steps, model, batched=True,
                     mesh=mesh)
    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    with jax.enable_x64(True):
        sched = loop._shape_schedule(b_1.schedule(0, n_int * steps), n_int)
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                           sharding=sharding),
            (b_1.table, b_1.state, b_1.wstate, sched,
             np.ones((len(specs), b_1.n_osc), bool)))
    text = loop.lower(*args).compile().as_text()
    found = [c for c in COLLECTIVES if c in text]
    check(not found, f"four chips: the sharded program holds {found}")
    log(f"four chips: 16 x {specs[0].n_clients}x{specs[0].n_osts} "
        f"({16 * b_1.n_osc} OSCs) over fleet_mesh(4): θ bit-equal to one "
        f"chip ({n_changed} knob changes), counters max rel {worst!r}, no "
        f"collective in the compiled program; mesh {t_m:.1f} s, one chip "
        f"{t_1:.1f} s (cold, smoke run)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the training campaign and the variants")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path on four chips and its "
                         "one-chip reference")
    args = ap.parse_args(argv)

    devs = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    model = train(args.seed)
    if args.four_chips:
        four_chip_phase(model, args.seed)
    else:
        catalog_phase(model)
        deployment_phase(model)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devs)}}))


if __name__ == "__main__":
    main()
