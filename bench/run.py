#!/usr/bin/env python3
"""DIAL's chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``<configuration>.<traffic mix>``; its files are found by name:
``bench/configs/<configuration>.json`` with the builder it names,
``bench/builders/<builder>.py``; ``bench/traffic/<mix>.json`` with the
entry it names, ``bench/entries/<entry>.py``; and, for each per-layer
metric, ``bench/metrics/<metric>.py`` (the name up to its first dot).
The run

1. refuses to start unless JAX's devices are TPUs, as many as the cell
   asks for;
2. sets up: makes the DIAL policy from its seed, then runs one whole job
   so that every program the window uses is compiled or loaded from the
   persistent compilation cache (``<checkout>/.jax_cache``, or
   ``JAX_COMPILATION_CACHE_DIR`` where set);
3. measures: runs jobs back to back, closed loop, one at a time, each
   made from ``--seed`` and its index, and stops at the end of the first
   job that ends after ``--seconds``.  With ``--trace 1`` the profiler
   traces the window's first whole job and the per-layer metrics are
   reported instead of the end-to-end ones;
4. checks: re-runs a seeded sample of the window's work on the plain
   numpy reference and compares (``check.py``);
5. prints the compared numbers beside their limits on standard error and,
   as the last line of standard output, one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """A run that cannot be measured; the message says why."""


def load_cell(name: str, root: str = ROOT) -> tuple:
    """``(benchmark, cell, config, traffic)`` for a cell name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{', '.join(cells)}")
    cell = cells[name]

    def data(kind, key):
        with open(os.path.join(HERE, kind, f"{key}.json")) as f:
            return json.load(f)
    config, traffic = data("configs", cell["config"]), data(
        "traffic", cell["traffic"])
    for kind, key in (("builders", config["builder"]),
                      ("entries", traffic["entry"])):
        if not os.path.isfile(os.path.join(HERE, kind, f"{key}.py")):
            raise BenchError(f"no bench/{kind}/{key}.py")
    return bench, cell, config, traffic


def require_device(chips: int):
    """JAX's devices, which must be ``chips`` TPUs or more, of a kind in
    ``bench/peaks.json``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform}; this benchmark runs only on "
                         f"the chip")
    if len(devs) < chips:
        raise BenchError(f"{chips} chips needed, {len(devs)} visible")
    with open(os.path.join(HERE, "peaks.json")) as f:
        if devs[0].device_kind not in json.load(f):
            raise BenchError(f"{devs[0].device_kind!r} is not in "
                             f"bench/peaks.json")
    return devs


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, every program kept."""
    import jax

    from repro.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cell_metrics(bench: dict, cell: dict, key: str) -> list:
    """The metrics of ``bench[key]`` that this cell reports."""
    out = []
    for m in bench[key]:
        cells = m.get("workloads")
        if cells is None and key == "per_layer":
            moved = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
            cells = moved[0].get("workloads") if moved else None
        if cells is None or cell["name"] in cells:
            out.append(m)
    return out


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, seg_backend: str = "auto") -> dict:
    """Set up, measure, check; returns the result object."""
    import jax
    import numpy as np

    import check
    import devtrace
    from jobs import Driver, job_seed, load_part
    from policy import as_program_model, make_policy

    policy = make_policy()
    # host spans land in a profiler trace beside the device's ops
    span = jax.profiler.TraceAnnotation
    driver = Driver(config, traffic, as_program_model(policy), span,
                    seg_backend=seg_backend)
    driver.run(seed, -1)                    # warm-up: every program, once
    setup_s = time.perf_counter() - t_start

    compiles = []
    listener = (lambda event, secs, **_: compiles.append(secs)
                if event == COMPILE_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    # the profiler takes the window's first whole job: writing out a
    # trace costs some 40 us of host time per device op, and a sweep
    # runs millions of them
    driver.profile_dir = trace_dir
    jobs = []
    marked = driver.host_spans() if trace else contextlib.nullcontext()
    try:
        with marked:
            t0 = time.perf_counter()
            with span("bench.window"):
                while True:
                    jobs.append(driver.run(seed, len(jobs)))
                    if time.perf_counter() - t0 >= seconds:
                        break
            window_s = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    reduced = None
    if trace:
        ops, host_spans, dropped = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        texts = driver.hlo_texts()
        scatter_ops = None
        if texts:
            scatter_ops = set().union(
                *(devtrace.scatter_instructions(t) for t in texts))
            named = {devtrace.instruction(t) for t in texts for t in
                     t.splitlines() if " = " in t}
            seen = {devtrace.instruction(o[2]) for o in ops}
            print(f"trace: {len(seen & named)} of {len(seen)} traced ops "
                  f"named in the compiled program, {len(scatter_ops)} "
                  f"scatter instructions", file=sys.stderr)
        traced = sorted(s for s in host_spans if s[2] == "bench.job")
        window = (devtrace.traced_window(ops, traced[0], dropped)
                  if traced else None)
        reduced = devtrace.reduce(ops, host_spans, window=window,
                                  scatter_ops=scatter_ops)
        if reduced is not None:
            reduced["complete"] = not dropped
            if traced:
                reduced["job_s"] = (traced[0][1] - traced[0][0]) / 1e9
        if dropped:
            print(f"trace: {dropped} trace buffers dropped; the reduction "
                  f"covers the job's first {reduced and reduced['window_s']}"
                  f" s", file=sys.stderr)

    rng = np.random.default_rng(job_seed(seed, -2))
    nums, failed = check.check(jobs, config, traffic, policy, rng)
    correct = not failed
    checks = check.lines(nums)

    record = {"jobs": jobs, "traced_intervals": driver.n_int,
              "window_s": window_s, "trace": reduced,
              "compiles_in_window": len(compiles), "peak_bytes": peak}
    metrics = {}
    if trace:
        for m in cell_metrics(bench, cell, "per_layer"):
            v = load_part("metrics", m["name"].split(".")[0]).read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        work = sum(j.work for j in jobs)
        for m in cell_metrics(bench, cell, "end_to_end"):
            v = (setup_s if m["name"] == "setup_s" else
                 work / window_s if m["name"] == traffic["rate_metric"]
                 else None)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    result = {"correct": correct, "attempted": len(jobs),
              "failed": len(failed), "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    print(f"window: {len(jobs)} jobs in {window_s!r} s; "
          f"{len(compiles)} compiles in the window; checked "
          f"{nums['elements']} elements, {nums['decisions']} decisions, "
          f"{nums['knob_changes']} knob changes (not compared)",
          file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic = load_cell(args.workload)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise BenchError("the program under test (src/repro) is not in "
                             "this checkout")
        devices = require_device(int(cell["chips"]))
    except (BenchError, OSError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    enable_compile_cache()
    result = run_cell(bench, cell, config, traffic, args.seed, args.seconds,
                      bool(args.trace), devices)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
