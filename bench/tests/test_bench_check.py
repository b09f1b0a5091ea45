"""The comparison that decides ``correct``, at a tiny size on the CPU.

``correct`` must not depend on whether DIAL changed a knob: a run whose
policy never changes one is correct when it agrees with the reference.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import check
from jobs import Driver, prepare
from policy import as_program_model, load_policy_config, make_policy


def _nospan(name):
    import contextlib
    return contextlib.nullcontext()


def _job(cfg, traffic, policy, seed=261275288):
    driver = Driver(cfg, traffic, as_program_model(policy), _nospan)
    return driver.run(seed, 0)


@pytest.fixture(scope="module")
def still_policy():
    """Every margin -4 (P about 0.018 < tau): DIAL decides, never changes."""
    cfg = dict(load_policy_config(), base=-4.0, leaf_sd=0.0)
    return make_policy(cfg)


def test_no_knob_change_is_correct(tiny_deploy, still_policy):
    cfg, traffic = tiny_deploy
    job = _job(cfg, traffic, still_policy)
    nums, failed = check.check([job], cfg, traffic, still_policy,
                               np.random.default_rng(0))
    assert nums["knob_changes"] == 0
    assert nums["decisions"] > 0
    assert nums["theta_mismatch"] == 0
    assert failed == []


@pytest.mark.parametrize("fault", ["counter", "theta"])
def test_altered_answer_is_incorrect(tiny_deploy, fault):
    cfg, traffic = tiny_deploy
    policy = make_policy()
    job = _job(cfg, traffic, policy)
    assert check.check([job], cfg, traffic, policy,
                       np.random.default_rng(0))[1] == []
    e = job.elements[0]
    if fault == "counter":
        c = e.counters["ctr_rpcs_done"]
        c[np.unravel_index(np.argmax(c), c.shape)] *= 1 + 1e-6
    else:
        i = next(i for i, d in enumerate(e.decisions) if len(d[0]))
        oscs, theta, changed = e.decisions[i]
        theta = theta.copy()
        theta[0] = (16, 1) if tuple(theta[0]) != (16, 1) else (1024, 32)
        e.decisions[i] = (oscs, theta, changed)
    nums, failed = check.check([job], cfg, traffic, policy,
                               np.random.default_rng(0))
    assert failed == [0]
    assert not check.agrees(nums)


def test_cpu_run_is_refused_before_measuring(paths):
    bench_dir, root = paths
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench_dir, "run.py"), "--workload",
         "vpic_bdcats_256x16.dial", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("kind,key", [("configs", "builder"),
                                      ("traffic", "entry")])
def test_unknown_builder_or_entry_is_refused(tmp_path, monkeypatch, kind,
                                             key):
    import json
    import shutil

    import run

    for d in ("configs", "traffic", "builders", "entries"):
        shutil.copytree(os.path.join(run.HERE, d), tmp_path / d)
    name = {"configs": "vpic_bdcats_256x16", "traffic": "dial"}[kind]
    path = tmp_path / kind / f"{name}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    **{key: "ior_hard"})))
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    with pytest.raises(run.BenchError, match="ior_hard"):
        run.load_cell("vpic_bdcats_256x16.dial")


def test_host_spans_mark_and_restore(tiny_deploy):
    import contextlib

    from repro.lab import batch as lab_batch

    cfg, traffic = tiny_deploy
    marked = []

    def span(name):
        marked.append(name)
        return contextlib.nullcontext()
    real = lab_batch.stack_scenarios
    driver = Driver(cfg, dict(traffic, host_spans=[
        "repro.lab.batch:stack_scenarios"]), None, span)
    with driver.host_spans():
        assert lab_batch.stack_scenarios is not real
        prepare(driver, 1, 0)
    assert lab_batch.stack_scenarios is real
    assert "bench.stack_scenarios" in marked


def test_mesh_traffic_runs_sharded_and_correct(tiny_deploy):
    cfg, traffic = tiny_deploy
    policy = make_policy()
    traffic = dict(traffic, mesh=1)
    driver = Driver(cfg, traffic, as_program_model(policy), _nospan)
    assert driver.mesh is not None
    job = driver.run(5, 0)
    assert check.check([job], cfg, traffic, policy,
                       np.random.default_rng(0))[1] == []
