#!/usr/bin/env python3
"""Readings that the limits of ``check.py`` are set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--control]

Runs one job of the cell per seed in one process, at the cell's own size,
and prints the numbers the check compares for each, one JSON line per
seed.  Without ``--control`` it runs the program as the benchmark does
(the sound readings); with ``--control`` it runs the program's own
lower-precision path, float32 segment sums (the Pallas kernel), which a
correct check must refuse.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one job each")
    ap.add_argument("--control", action="store_true",
                    help="float32 segment sums (the control)")
    args = ap.parse_args(argv)
    try:
        _, cell, config, traffic = run.load_cell(args.workload)
        run.require_device(int(cell["chips"]))
    except (run.BenchError, OSError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.enable_compile_cache()

    import check
    from jobs import Driver, job_seed
    from policy import as_program_model, make_policy

    policy = make_policy()
    driver = Driver(config, traffic, as_program_model(policy),
                    lambda name: contextlib.nullcontext(),
                    seg_backend="pallas" if args.control else "auto")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        job = driver.run(seed, 0)
        t_job = time.perf_counter() - t0
        nums, failed = check.check([job], config, traffic, policy,
                                   np.random.default_rng(job_seed(seed, -2)))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "job_s": t_job,
                          "check_s": time.perf_counter() - t0 - t_job,
                          "correct": not failed, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
