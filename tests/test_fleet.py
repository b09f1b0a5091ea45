"""Fleet path: batched probing/metrics/tuning must match the per-agent
loop exactly — same simulator trace, same seeds, same knob trajectory."""

import numpy as np
import pytest

from repro.core.config_space import SPACE
from repro.core.metrics import snapshot, snapshot_all
from repro.core.tuner import (TunerParams, conditional_score_greedy,
                              conditional_score_greedy_batch)
from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.stats import probe, probe_all, stack_stats
from repro.pfs.workloads import random_stream, sequential_stream


def _busy_sim(seed=11):
    sim = PFSSim(n_clients=2, n_osts=2, seed=seed)
    sim.attach(sequential_stream(0, READ, 4 * 2**20, ost=0))
    sim.attach(random_stream(0, WRITE, 64 * 1024, ost=1, n_threads=2))
    sim.attach(sequential_stream(1, WRITE, 2 * 2**20, ost=0, n_threads=2))
    sim.attach(random_stream(1, READ, 256 * 1024, ost=1))
    return sim


# ---------------------------------------------------------------------- #
# probing + metrics: stacked arrays == per-interface scalars, bit for bit
# ---------------------------------------------------------------------- #
def test_probe_all_matches_probe():
    sim = _busy_sim()
    sim.run(0.5)
    fleet = probe_all(sim)
    for i in range(sim.n_osc):
        one = probe(sim, i)
        col = fleet.one(i)
        for field in ("bytes_done", "rpcs_sent", "rpc_bytes", "latency_sum",
                      "req_bytes", "pending_integral", "active_integral",
                      "randomness"):
            np.testing.assert_array_equal(getattr(col, field),
                                          getattr(one, field), err_msg=field)
        assert (col.cache_hit_bytes, col.block_time, col.window_pages,
                col.rpcs_in_flight) == (one.cache_hit_bytes, one.block_time,
                                        one.window_pages, one.rpcs_in_flight)


def test_snapshot_all_matches_snapshot_bitwise():
    sim = _busy_sim()
    prev_f = probe_all(sim)
    prev_s = [probe(sim, i) for i in range(sim.n_osc)]
    sim.run(0.5)
    cur_f = probe_all(sim)
    fleet = snapshot_all(prev_f, cur_f)
    for i in range(sim.n_osc):
        s = snapshot(prev_s[i], probe(sim, i))
        np.testing.assert_array_equal(fleet.read[i], s.read)
        np.testing.assert_array_equal(fleet.write[i], s.write)
        assert fleet.read_volume[i] == s.read_volume
        assert fleet.write_volume[i] == s.write_volume


def test_stack_stats_round_trips_probe_all():
    sim = _busy_sim()
    sim.run(0.3)
    ids = np.arange(sim.n_osc)
    stacked = stack_stats([probe(sim, int(i)) for i in ids], ids)
    direct = probe_all(sim, ids)
    np.testing.assert_array_equal(stacked.bytes_done, direct.bytes_done)
    np.testing.assert_array_equal(stacked.window_pages, direct.window_pages)
    np.testing.assert_array_equal(stacked.dirty_integral,
                                  direct.dirty_integral)


# ---------------------------------------------------------------------- #
# Algorithm 1, batched == scalar per row
# ---------------------------------------------------------------------- #
def test_batch_tuner_matches_scalar_rows():
    rng = np.random.default_rng(3)
    m = 64
    configs = SPACE.configs()
    probs = rng.uniform(0.0, 1.0, size=(m, len(SPACE)))
    probs[:8] = 0.5                      # rows where nothing clears tau
    ops = rng.integers(0, 2, size=m)
    current = np.array([configs[j] for j in
                        rng.integers(0, len(configs), size=m)])
    params = TunerParams()
    batch = conditional_score_greedy_batch(probs, ops, current,
                                           SPACE, params)
    for i in range(m):
        want = conditional_score_greedy(probs[i], int(ops[i]),
                                        (int(current[i, 0]),
                                         int(current[i, 1])),
                                        SPACE, params)
        got = batch.one(i)
        assert got.theta == want.theta, i
        assert got.changed == want.changed, i
        assert got.n_candidates == want.n_candidates, i
        assert got.score == pytest.approx(want.score, abs=0), i


def test_batch_tuner_tie_break_matches_scalar():
    """Exact ties must resolve to the same (first-max) config."""
    probs = np.full((1, len(SPACE)), 0.9)
    for op in (READ, WRITE):
        got = conditional_score_greedy_batch(
            probs, np.array([op]), np.array([[256, 8]])).one(0)
        want = conditional_score_greedy(probs[0], op, (256, 8))
        assert got.theta == want.theta


# ---------------------------------------------------------------------- #
# end-to-end: fleet trajectory == per-agent loop trajectory
# ---------------------------------------------------------------------- #
def test_fleet_matches_loop_agents_trajectory(dial_model):
    """Same seeds, same workloads: the batched fleet and the per-agent
    Python loop must produce the identical decision sequence and knob
    trajectory (the tentpole equivalence guarantee)."""
    from repro.core.agent import ReferenceLoopAgent, SimClientPort
    from repro.core.fleet import FleetAgent, SimFleetPort

    def build():
        sim = _busy_sim(seed=5)
        sim.set_knobs(np.arange(sim.n_osc), window_pages=64,
                      rpcs_in_flight=2)
        return sim

    sim_l = build()
    loop = [ReferenceLoopAgent(SimClientPort(sim_l, c), dial_model)
            for c in range(2)]
    sim_f = build()
    fleet = FleetAgent(SimFleetPort(sim_f), dial_model)

    steps = int(round(0.5 / sim_l.params.tick))
    for _ in range(10):
        for _ in range(steps):
            sim_l.step()
            sim_f.step()
        loop_tick = []
        for a in loop:
            loop_tick.extend(a.tick())
        fleet_tick = fleet.tick().as_list()
        assert len(loop_tick) == len(fleet_tick)
        for (lo, lop, ld), (fo, fop, fd) in zip(loop_tick, fleet_tick):
            assert (lo, lop) == (fo, fop)
            assert ld.theta == fd.theta
            assert ld.changed == fd.changed
            assert ld.n_candidates == fd.n_candidates
            np.testing.assert_array_equal(ld.probs, fd.probs)
        # knobs applied identically -> identical traces going forward
        np.testing.assert_array_equal(sim_l.window_pages, sim_f.window_pages)
        np.testing.assert_array_equal(sim_l.rpcs_in_flight,
                                      sim_f.rpcs_in_flight)


def test_dial_agent_adapter_matches_loop(dial_model):
    """DIALAgent (now a fleet adapter) must still equal the reference
    loop for a single client, through the generic ClientPort surface."""
    from repro.core.agent import DIALAgent, ReferenceLoopAgent, SimClientPort

    def run(cls):
        sim = PFSSim(n_clients=1, n_osts=2, seed=9)
        sim.attach(sequential_stream(0, READ, 8 * 2**20, ost=0))
        sim.set_knobs(sim.client_oscs(0), window_pages=16, rpcs_in_flight=1)
        agent = cls(SimClientPort(sim, 0), dial_model)
        steps = int(round(0.5 / sim.params.tick))
        out = []
        for _ in range(8):
            for _ in range(steps):
                sim.step()
            out.extend((o, op, d.theta, d.changed) for o, op, d in
                       agent.tick())
        return out, sim.window_pages.copy(), sim.rpcs_in_flight.copy()

    dec_l, win_l, rif_l = run(ReferenceLoopAgent)
    dec_f, win_f, rif_f = run(DIALAgent)
    assert dec_l == dec_f
    np.testing.assert_array_equal(win_l, win_f)
    np.testing.assert_array_equal(rif_l, rif_f)


def test_fleet_jax_backend_matches_numpy_decisions(dial_model):
    """The fused single-launch predictor must not change any decision."""
    import copy

    from repro.core.fleet import FleetAgent, SimFleetPort

    def run(backend):
        model = copy.copy(dial_model)
        model.backend = backend
        model.__post_init__()
        sim = _busy_sim(seed=13)
        fleet = FleetAgent(SimFleetPort(sim), model)
        steps = int(round(0.5 / sim.params.tick))
        out = []
        for _ in range(6):
            for _ in range(steps):
                sim.step()
            r = fleet.tick()
            out.append((r.oscs.tolist(), r.ops.tolist(),
                        r.decisions.theta.tolist()))
        return out

    assert run("numpy") == run("jax")


# ---------------------------------------------------------------------- #
# paired-forest traversal vs the split numpy forests
# ---------------------------------------------------------------------- #
def test_paired_forest_kernel_matches_split_forests():
    import jax.numpy as jnp

    from repro.core.gbdt import GBDTClassifier, GBDTParams
    from repro.kernels.gbdt_forest.ops import (make_fleet_predictor,
                                               pair_forests)
    from repro.kernels.gbdt_forest.ref import paired_forest_margin_ref

    rng = np.random.default_rng(0)
    Xr = rng.normal(size=(1500, 10))
    fr = GBDTClassifier(GBDTParams(n_trees=12, max_depth=3)).fit(
        Xr, (Xr[:, 0] > 0).astype(float)).forest
    Xw = rng.normal(size=(1500, 14))
    fw = GBDTClassifier(GBDTParams(n_trees=20, max_depth=5)).fit(
        Xw, (Xw[:, 1] * Xw[:, 2] > 0).astype(float)).forest

    feature, threshold, leaf, base, depth, n_feat = pair_forests(fr, fw)
    n = 100
    x = np.zeros((n, n_feat), dtype=np.float32)
    op = rng.integers(0, 2, size=n).astype(np.int32)
    xr = rng.normal(size=(n, 10)).astype(np.float32)
    xw = rng.normal(size=(n, 14)).astype(np.float32)
    x[op == 0, :10] = xr[op == 0]
    x[op == 1, :14] = xw[op == 1]

    args = (jnp.asarray(x), jnp.asarray(op), jnp.asarray(feature),
            jnp.asarray(threshold), jnp.asarray(leaf), jnp.asarray(base))
    ref = np.asarray(paired_forest_margin_ref(*args, depth))
    # against the unpadded numpy oracles
    want = np.where(op == 0, fr.predict_margin(x[:, :10]),
                    fw.predict_margin(x[:, :14]))
    np.testing.assert_allclose(ref, want, rtol=1e-4, atol=1e-4)
    # and the fleet predictor's split read/write batches
    p_r, p_w = make_fleet_predictor(fr, fw)(xr[op == 0], xw[op == 1])
    np.testing.assert_allclose(p_r, fr.predict_proba(xr[op == 0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(p_w, fw.predict_proba(xw[op == 1]),
                               rtol=1e-4, atol=1e-4)
