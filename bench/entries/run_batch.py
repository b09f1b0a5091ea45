"""A job through ``lab.batch.run_batch(fused=True)``: the traffic's
seeded jitters stacked into one batch, every interface DIAL-tuned, one
fused dispatch (sharded over ``fleet_mesh`` where the traffic names a
``mesh``)."""

import numpy as np

from jobs import Job, compiled_text, finish, prepare


def run(driver, seed: int, index: int) -> Job:
    from repro.lab.batch import run_batch

    batch, meta = prepare(driver, seed, index)
    driver.last_batch = batch
    with driver.span("bench.dispatch"):
        result = run_batch(batch, model=driver.model,
                           seconds=driver.seconds,
                           interval=driver.interval, fused=True,
                           mesh=driver.mesh,
                           seg_backend=driver.seg_backend)
    return finish(driver, index, batch, result, set(range(len(batch))),
                  meta)


def hlo_texts(driver) -> list:
    from repro.pfs.loop_jax import FusedLoop

    if driver.mesh is not None:
        return []
    b = driver.last_batch
    loop = FusedLoop(b.params, b.topo, driver.steps(b), driver.model,
                     seg_backend=driver.seg_backend, batched=True)
    return [compiled_text(loop, b, driver,
                          (np.ones((len(b), b.n_osc), dtype=bool),))]
