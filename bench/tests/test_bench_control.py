"""``correct`` comes out false for the control and for faults planted in
the timed path.  Each test drives a whole run of the harness at a tiny
size on the CPU; only the look for a chip is skipped.

The control is the program's own lower-precision path: segment sums in
float32 (the Pallas kernel, in interpret mode here).  The faults: a run
that returns its state unchanged, half of the batch left out, and an
answer altered where it is produced.  The cells run on one chip, so there
is no exchange between chips to leave out.
"""

import dataclasses

import jax
import numpy as np
import pytest

import run
from repro.pfs.loop_jax import FusedLoop

REAL_RUN = FusedLoop.run


def _cell(bench, name):
    """The cell of ``BENCHMARK.json``, or one made from its name where the
    cell is kept out of the benchmark (``catalog.sweep``, PERF.md)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    config, traffic = name.split(".")
    return cells.get(name, {"name": name, "config": config,
                            "traffic": traffic, "chips": 1})


def _run(bench, name, cfg, traffic, **kw):
    cell = _cell(bench, name)
    return run.run_cell(bench, cell, cfg, traffic, 2**31 + 77, 0.1, False,
                        jax.devices(), t_start=0.0, **kw)


def test_sound_run_is_correct(bench, tiny_deploy):
    assert _run(bench, "vpic_bdcats_256x16.dial", *tiny_deploy)["correct"]


def test_control_float32_segment_sums_fail(bench, tiny_deploy):
    res = _run(bench, "vpic_bdcats_256x16.dial", *tiny_deploy,
               seg_backend="pallas_interpret")
    assert not res["correct"]
    assert res["checks"]["counter_rel"]["value"] > res["checks"][
        "counter_rel"]["limit"]


def _unchanged(self, table, state, wstate, *a, **kw):
    out = REAL_RUN(self, table, state, wstate, *a, **kw)
    return dataclasses.replace(out, state=state, wstate=wstate)


def _half_left_out(self, table, state, wstate, *a, **kw):
    out = REAL_RUN(self, table, state, wstate, *a, **kw)
    b = np.asarray(state.window_pages).shape[0]
    keep = jax.tree.map(lambda new, old: np.concatenate(
        [np.asarray(new)[:b // 2 or 1], np.asarray(old)[b // 2 or 1:]]),
        out.state, state)
    return dataclasses.replace(out, state=keep)


def _altered(self, *a, **kw):
    out = REAL_RUN(self, *a, **kw)
    for r in out.decisions:
        if len(r.oscs):
            th = r.decisions.theta
            th[0] = (16, 1) if tuple(th[0]) != (16, 1) else (1024, 32)
            break
    return out


@pytest.mark.parametrize("cell,fault", [
    ("vpic_bdcats_256x16.dial", _unchanged),
    ("vpic_bdcats_256x16.dial", _half_left_out),
    ("vpic_bdcats_256x16.dial", _altered),
    ("vpic_bdcats_256x16.static", _unchanged),
    ("catalog.sweep", _half_left_out),
])
def test_planted_fault_fails(bench, tiny_deploy, tiny_sweep, monkeypatch,
                             cell, fault):
    cfg, traffic = tiny_sweep if cell.startswith("catalog") else tiny_deploy
    if fault is _half_left_out and not cell.startswith("catalog"):
        traffic = dict(traffic, variants=2)
    if cell.endswith("static"):
        traffic = dict(traffic, entry="fused_loop")
    monkeypatch.setattr(FusedLoop, "run", fault)
    assert not _run(bench, cell, cfg, traffic)["correct"]
