"""A job through ``lab.evaluate.evaluate``: each configuration scenario's
seeded jitter under every static θ and DIAL, bucketed, padded and
dispatched by the lab itself.

A wrapper around the sweep's ``run_batch`` keeps each dispatched batch
and its result for the check."""

import numpy as np

from jobs import Job, elements_of


def run(driver, seed: int, index: int) -> Job:
    import repro.lab.evaluate as ev
    from repro.core.config_space import SPACE
    from repro.lab.scenarios import register

    with driver.span("bench.prep"):
        specs, meta = driver.specs(seed, index)
        for s in specs:
            register(s)
    calls = []
    inner = ev.run_batch

    def recording_run_batch(batch, **kw):
        with driver.span("bench.dispatch"):
            res = inner(batch, **kw)
        calls.append((batch, kw.get("tune_cols"), res))
        return res

    ev.run_batch = recording_run_batch
    try:
        with driver.span("bench.sweep"):
            ev.evaluate(names=[s.name for s in specs], model=driver.model,
                        seconds=driver.seconds, interval=driver.interval,
                        seg_backend=driver.seg_backend)
    finally:
        ev.run_batch = inner

    with driver.span("bench.collect"):
        by_name = {s.name: m for s, m in zip(specs, meta)}
        elements = []
        for batch, tune_cols, res in calls:
            n = batch.n_osc
            tuned = set((np.asarray(tune_cols) // n).tolist())
            emeta = []
            for s in batch.specs:
                base, s_seed, _ = by_name[s.name]
                emeta.append((base, s_seed, tuple(s.initial_theta)))
            elements += elements_of(batch, res, tuned, emeta)
    n_arms = len(specs) * (len(SPACE) + 1)
    return Job(index, elements, float(n_arms * driver.seconds),
               [c[0].pad_stats() for c in calls])
