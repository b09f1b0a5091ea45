"""A job through the lean engine-only ``FusedLoop``: the traffic's seeded
jitters stacked into one batch, every interface at its initial θ, one
fused dispatch with no decision step."""

from jobs import compiled_text, finish, prepare


def lean_loop(driver, batch):
    """One ``FusedLoop(tuned=False)`` for the driver's whole life."""
    from repro.pfs.loop_jax import FusedLoop

    loop = getattr(driver, "lean_loop", None)
    if loop is None:
        loop = driver.lean_loop = FusedLoop(
            batch.params, batch.topo, driver.steps(batch), None,
            seg_backend=driver.seg_backend, batched=True, tuned=False)
    return loop


def run(driver, seed: int, index: int):
    batch, meta = prepare(driver, seed, index)
    driver.last_batch = batch
    with driver.span("bench.dispatch"):
        result = lean_loop(driver, batch).run(
            batch.table, batch.state, batch.wstate, driver.n_int,
            schedule=batch.schedule(0, driver.n_int * driver.steps(batch)))
    batch.state, batch.wstate = result.state, result.wstate
    return finish(driver, index, batch, result, set(), meta)


def hlo_texts(driver) -> list:
    b = driver.last_batch
    return [compiled_text(lean_loop(driver, b), b, driver)]
