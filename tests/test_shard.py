"""Sharded fused loop: mesh helpers, padding, donation, and the
sharded-vs-single-device equivalence (subprocess with 8 forced host
devices — conftest keeps the in-process tests on the real device set).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# the tiny-synthetic-model + scenario-batch prelude every subprocess
# shares: fast to fit, exercises both forests, decisions still fire
PRELUDE = """
import numpy as np
from repro.core.gbdt import GBDTClassifier, GBDTParams
from repro.core.metrics import feature_dim
from repro.core.model import DIALModel
from repro.pfs.state import READ, WRITE

rng = np.random.default_rng(0)
def _forest(dim):
    x = rng.normal(size=(400, dim)).astype(np.float32)
    y = (x[:, 0] + x[:, -1] > -1.0).astype(np.int64)
    return GBDTClassifier(GBDTParams(n_trees=8, max_depth=3)).fit(x, y).forest
k = 1
model = DIALModel(read_forest=_forest(feature_dim(READ, k)),
                  write_forest=_forest(feature_dim(WRITE, k)),
                  backend="jax", k=k)

def traj(decisions):
    return [(i, int(o), int(op), int(t[0]), int(t[1]))
            for i, r in enumerate(decisions)
            for o, op, t in zip(r.oscs, r.ops, r.decisions.theta)]
"""


# ---------------------------------------------------------------------- #
# helpers: mesh construction + pad/unpad (single device, in process)
# ---------------------------------------------------------------------- #
def test_fleet_mesh_single_device():
    from repro.distributed.sharding import FLEET_AXIS, fleet_mesh
    from repro.launch.mesh import make_fleet_mesh

    m = fleet_mesh()
    assert m.axis_names == (FLEET_AXIS,)
    assert m.devices.size >= 1
    assert make_fleet_mesh(1).devices.size == 1


def test_fleet_mesh_too_many_devices_raises():
    import jax

    from repro.distributed.sharding import fleet_mesh

    with pytest.raises(ValueError, match="force host devices"):
        fleet_mesh(jax.device_count() + 1)


def test_pad_unpad_roundtrip():
    from repro.distributed.sharding import (fleet_batch_size, pad_fleet,
                                            unpad_fleet)

    tree = {"a": np.arange(30.0).reshape(5, 3, 2), "b": np.arange(5)}
    assert fleet_batch_size(tree) == 5
    padded, n_pad = pad_fleet(tree, 4)
    assert n_pad == 3
    assert padded["a"].shape == (8, 3, 2)
    # phantom rows replicate element 0
    np.testing.assert_array_equal(padded["a"][5:],
                                  np.repeat(tree["a"][:1], 3, axis=0))
    back = unpad_fleet(padded, n_pad)
    np.testing.assert_array_equal(back["a"], tree["a"])
    np.testing.assert_array_equal(back["b"], tree["b"])
    # already divisible: no copy-shaped change
    same, n0 = pad_fleet(tree, 5)
    assert n0 == 0 and same["a"].shape == (5, 3, 2)


def test_fused_loop_mesh_requires_batched():
    from repro.distributed.sharding import fleet_mesh
    from repro.lab.scenarios import SCENARIOS, build
    from repro.pfs.loop_jax import FusedLoop

    b = build(SCENARIOS["degraded_ost"])
    with pytest.raises(ValueError, match="batched=True"):
        FusedLoop(b.params, b.topo, 10, None, tuned=False,
                  mesh=fleet_mesh(1))


def test_run_batch_mesh_requires_fused():
    from repro.distributed.sharding import fleet_mesh
    from repro.lab.batch import run_batch, stack_scenarios
    from repro.lab.scenarios import SCENARIOS, build

    batch = stack_scenarios([build(SCENARIOS["degraded_ost"])])
    with pytest.raises(ValueError, match="fused=True"):
        run_batch(batch, None, seconds=1.0, mesh=fleet_mesh(1))


def test_run_fleet_mesh_needs_sharded_backend():
    from repro.core.fleet import run_fleet
    from repro.distributed.sharding import fleet_mesh
    from repro.pfs import PFSSim

    sim = PFSSim(n_clients=2, n_osts=2, seed=0)
    with pytest.raises(ValueError, match="jax-sharded"):
        run_fleet(sim, None, seconds=1.0, backend="numpy",
                  mesh=fleet_mesh(1))


# ---------------------------------------------------------------------- #
# 8 forced host devices: equivalence, padding, donation (subprocess)
# ---------------------------------------------------------------------- #
def test_sharded_matches_single_device_8dev():
    """Mixed disturbed batch on an 8-device mesh: θ trajectories exactly
    equal to the single-device fused dispatch, probe counters ≤1e-6."""
    out = run_py(PRELUDE + """
import jax
from repro.distributed.sharding import fleet_mesh
from repro.lab.batch import run_batch, stack_scenarios
from repro.lab.scenarios import SCENARIOS, build, variants

assert jax.device_count() == 8
spec = SCENARIOS["failing_ost"]
ba = stack_scenarios([build(s) for s in variants(spec, 8, seed=2)])
bb = stack_scenarios([build(s) for s in variants(spec, 8, seed=2)])
ra = run_batch(ba, model, seconds=4.0, interval=0.5, fused=True)
rb = run_batch(bb, model, seconds=4.0, interval=0.5, fused=True,
               mesh=fleet_mesh(8))
ta, tb = traj(ra.decisions), traj(rb.decisions)
assert ta == tb, (len(ta), len(tb))
assert len(tb) > 0, "batch never decided — test is vacuous"
for f in ("ctr_bytes_done", "ctr_rpcs_sent", "ctr_latency_sum",
          "ctr_pending_integral", "ctr_block_time"):
    np.testing.assert_allclose(np.asarray(getattr(ba.state, f)),
                               np.asarray(getattr(bb.state, f)),
                               rtol=1e-6, err_msg=f)
print("OK", len(tb))
""")
    assert "OK" in out


def test_sharded_padding_non_divisible_8dev():
    """B=5 on a 4-device mesh: padded to 8, phantom elements masked out,
    outputs sliced back — results equal the unsharded run."""
    out = run_py(PRELUDE + """
from repro.distributed.sharding import fleet_mesh
from repro.lab.batch import run_batch, stack_scenarios
from repro.lab.scenarios import SCENARIOS, build, variants

spec = SCENARIOS["noisy_neighbor"]
ba = stack_scenarios([build(s) for s in variants(spec, 5, seed=3)])
bb = stack_scenarios([build(s) for s in variants(spec, 5, seed=3)])
ra = run_batch(ba, model, seconds=4.0, interval=0.5, fused=True)
rb = run_batch(bb, model, seconds=4.0, interval=0.5, fused=True,
               mesh=fleet_mesh(4))
assert traj(ra.decisions) == traj(rb.decisions)
# every output came back at the caller's batch size, not the padded one
for tree in (rb.state, rb.wstate, rb.trace, rb.hist):
    import jax
    for leaf in jax.tree.leaves(tree):
        assert np.asarray(leaf).shape[0] == 5, np.asarray(leaf).shape
np.testing.assert_allclose(np.asarray(ba.state.ctr_bytes_done),
                           np.asarray(bb.state.ctr_bytes_done), rtol=1e-6)
# no decision ever references a phantom element's fleet column
n = ba.n_osc
assert all(int(o) < 5 * n for r in rb.decisions for o in r.oscs)
print("OK")
""")
    assert "OK" in out


def test_run_fleet_jax_sharded_matches_fused_8dev():
    """run_fleet(backend='jax-sharded') pins to jax-fused: same θ
    trajectory, same counters, and host ticks continue seamlessly after
    the fused run (history ring adopted)."""
    out = run_py(PRELUDE + """
import sys
sys.path.insert(0, "tests")
import test_loop_fused as tlf
from repro.core.fleet import run_fleet

sim_a, sim_b = tlf._mixed_sim(0), tlf._mixed_sim(0)
fa = run_fleet(sim_a, model, seconds=4.0, interval=0.5,
               backend="jax-fused", seg_backend="jax")
fb = run_fleet(sim_b, model, seconds=4.0, interval=0.5,
               backend="jax-sharded", seg_backend="jax")
assert tlf._traj(fa.decisions) == tlf._traj(fb.decisions)
assert sum(len(r.oscs) for r in fb.decisions) > 0
tlf._assert_counters_close(sim_a.state, sim_b.state, rtol=1e-6)
for _ in range(100):
    sim_a.step()
for _ in range(100):
    sim_b.step()
fa.tick(); fb.tick()
assert tlf._traj(fa.decisions) == tlf._traj(fb.decisions)
print("OK")
""")
    assert "OK" in out


def test_donation_consumes_state_buffers_8dev():
    """donate_argnums really donates: pre-sharded state/wstate buffers
    are consumed by the dispatch (no silent resharding copy doubling
    peak memory); the un-donated table stays alive."""
    out = run_py(PRELUDE + """
import jax
from repro.distributed.sharding import fleet_mesh, fleet_sharding
from repro.lab.batch import stack_scenarios
from repro.lab.scenarios import SCENARIOS, build, variants
from repro.pfs.loop_jax import FusedLoop

mesh = fleet_mesh(8)
batch = stack_scenarios(
    [build(s) for s in variants(SCENARIOS["degraded_ost"], 8, seed=1)])
loop = FusedLoop(batch.params, batch.topo, 20, model, seg_backend="jax",
                 batched=True, mesh=mesh)
sched = loop._shape_schedule(batch.schedule(0, 2 * 20), 2)
with jax.enable_x64(True):
    sh = fleet_sharding(mesh)
    jargs = jax.tree.map(lambda a: jax.device_put(np.asarray(a), sh),
                         (batch.table, batch.state, batch.wstate, sched,
                          np.ones((8, batch.n_osc), dtype=bool)))
    out = loop._run(*jargs)
    jax.block_until_ready(out)
    assert all(x.is_deleted() for x in jax.tree.leaves(jargs[1])), \\
        "SimState inputs survived the dispatch — donation didn't happen"
    assert all(x.is_deleted() for x in jax.tree.leaves(jargs[2])), \\
        "WorkloadState inputs survived the dispatch"
    assert not any(x.is_deleted() for x in jax.tree.leaves(jargs[0])), \\
        "table was donated but is not in donate_argnums"
print("OK")
""")
    assert "OK" in out


def test_donation_consumes_state_buffers_single_device():
    """The unsharded jit path donates too (default device placement)."""
    out = run_py(PRELUDE + """
import jax
import jax.numpy as jnp
from repro.lab.batch import stack_scenarios
from repro.lab.scenarios import SCENARIOS, build, variants
from repro.pfs.loop_jax import FusedLoop

batch = stack_scenarios(
    [build(s) for s in variants(SCENARIOS["degraded_ost"], 4, seed=1)])
loop = FusedLoop(batch.params, batch.topo, 20, model, seg_backend="jax",
                 batched=True)
sched = loop._shape_schedule(batch.schedule(0, 2 * 20), 2)
with jax.enable_x64(True):
    jargs = jax.tree.map(jnp.asarray,
                         (batch.table, batch.state, batch.wstate, sched,
                          np.ones((4, batch.n_osc), dtype=bool)))
    out = loop._run(*jargs)
    jax.block_until_ready(out)
    assert all(x.is_deleted() for x in jax.tree.leaves(jargs[1]))
    assert all(x.is_deleted() for x in jax.tree.leaves(jargs[2]))
print("OK")
""", devices=1)
    assert "OK" in out


def test_fuzz_mesh_matches_unmeshed_report_8dev():
    """A smoke fuzz sweep through --mesh produces the same triage as the
    single-device sweep on the same seed and model (same mesh caveat as
    PR 6: comparisons hold within one mesh shape; this pins 8-dev vs
    1-dev on the smoke config's tame scenario population)."""
    out = run_py(PRELUDE + """
import dataclasses
from repro.distributed.sharding import fleet_mesh
from repro.lab.fuzz import SMOKE, run_sweep

cfg = dataclasses.replace(SMOKE, n_scenarios=8, seconds=2.0)
ra = run_sweep(cfg, model)
rb = run_sweep(cfg, model, mesh=fleet_mesh(8))
sa, sb = ra["summary"], rb["summary"]
assert [r["fingerprint"] for r in ra["scenarios"]] == \\
       [r["fingerprint"] for r in rb["scenarios"]]
# counts match exactly; throughput fractions to float tolerance (XLA
# may fuse the per-shard program differently than the full-batch one)
for key in ("n_scenarios", "n_buckets", "n_unique_specs", "n_losses"):
    assert sa[key] == sb[key], (key, sa, sb)
import numpy as _np
_np.testing.assert_allclose(
    [r["dial_frac_of_best_static"] for r in ra["scenarios"]],
    [r["dial_frac_of_best_static"] for r in rb["scenarios"]], rtol=1e-6)
print("OK", sa["n_scenarios"])
""")
    assert "OK" in out


def test_weak_scaling_benchmark_smoke_8dev():
    """The headline benchmark runs end to end (quick mode) and reports a
    parsable weak-scaling curve."""
    import json

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "fleet_weak_scaling.py"),
         "--quick", "--json", "--max-fleet", "512"],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["schema"] == "dial-weak-scaling-v1"
    assert [p["devices"] for p in r["points"]] == [1, 2]
    assert all(p["if_intervals_per_s"] > 0 for p in r["points"])
    assert r["max_fleet"]["interfaces"] >= 512
