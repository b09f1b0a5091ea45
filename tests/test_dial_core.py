"""DIAL core: metrics extraction, Algorithm 1, agent behaviour."""

import numpy as np
import pytest

from repro.core.config_space import SPACE
from repro.core.metrics import READ_FEATURES, WRITE_FEATURES, snapshot
from repro.core.tuner import TunerParams, conditional_score_greedy
from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.stats import probe
from repro.pfs.workloads import random_stream, sequential_stream


def test_snapshot_features_finite_and_shaped():
    sim = PFSSim(n_clients=1, n_osts=2, seed=0)
    sim.attach(sequential_stream(0, READ, 2**20, ost=0))
    sim.attach(random_stream(0, WRITE, 8192, ost=1, n_threads=2))
    osc = sim.osc_id(0, 0)
    prev = probe(sim, osc)
    sim.run(0.5)
    cur = probe(sim, osc)
    s = snapshot(prev, cur)
    assert s.read.shape == (len(READ_FEATURES),)
    assert s.write.shape == (len(WRITE_FEATURES),)
    assert np.isfinite(s.read).all() and np.isfinite(s.write).all()
    assert s.read[0] > 0  # read throughput flowing
    assert s.read_volume > 0


# ---------------------------------------------------------------------- #
# Algorithm 1 semantics
# ---------------------------------------------------------------------- #
def test_tuner_keeps_current_when_no_candidate_clears_tau():
    probs = np.full(len(SPACE), 0.5)
    d = conditional_score_greedy(probs, READ, current=(256, 8))
    assert d.theta == (256, 8) and not d.changed and d.n_candidates == 0


def test_tuner_write_score_prefers_larger_theta_on_ties():
    """WriteScore = f * (1 + beta * sum(theta_norm)): with uniform
    probabilities above tau, the largest config wins (SIII-C)."""
    probs = np.full(len(SPACE), 0.9)
    d = conditional_score_greedy(probs, WRITE, current=(16, 1))
    assert d.theta == (1024, 32)


def test_tuner_read_score_structure():
    """ReadScore = f*(1 + alpha*theta1_norm) + theta2_norm: theta2 adds
    outside the product, so max in-flight dominates ties."""
    probs = np.full(len(SPACE), 0.9)
    d = conditional_score_greedy(probs, READ, current=(16, 1))
    assert d.theta[1] == 32  # max rpcs-in-flight among survivors


def test_tuner_model_veto_beats_regularizer():
    """A high-probability small config must beat a below-tau large one —
    the regularizer only ranks configurations that cleared tau."""
    probs = np.zeros(len(SPACE))
    i_small = SPACE.index_of((64, 4))
    probs[i_small] = 0.95
    i_big = SPACE.index_of((1024, 32))
    probs[i_big] = 0.5           # model predicts no improvement
    d = conditional_score_greedy(probs, WRITE, current=(256, 8))
    assert d.theta == (64, 4)


def test_minmax_normalization_over_subset():
    t = np.array([[64, 4], [256, 8], [1024, 16]], dtype=float)
    n = SPACE.minmax_normalize(t)
    assert n.min() == 0.0 and n.max() == 1.0
    assert n[0, 0] == 0.0 and n[2, 0] == 1.0


# ---------------------------------------------------------------------- #
# end-to-end agent behaviour
# ---------------------------------------------------------------------- #
def test_agent_recovers_bad_seq_config(dial_model):
    """From a pathologically small (window, inflight), DIAL must recover
    most of the sequential-stream bandwidth (paper SIV-B behaviour)."""
    from repro.core.agent import run_with_agents

    def tput(tuned):
        sim = PFSSim(n_clients=1, n_osts=4, seed=7)
        wl = sequential_stream(0, READ, 16 * 2**20, ost=0)
        sim.attach(wl)
        sim.set_knobs(sim.client_oscs(0), window_pages=16, rpcs_in_flight=1)
        if tuned:
            run_with_agents(sim, dial_model, [0], seconds=15.0)
        else:
            sim.run(15.0)
        return wl.done_bytes(sim) / 15.0 / 1e6

    static, dial = tput(False), tput(True)
    assert dial > 5 * static, (static, dial)


def test_agent_does_not_wreck_saturated_workload(dial_model):
    """On an already-optimal config the agent must not lose throughput
    (tau-gated decisions; paper Table II 'on par with optimal')."""
    from repro.core.agent import run_with_agents

    def tput(tuned):
        sim = PFSSim(n_clients=1, n_osts=4, seed=9)
        wl = sequential_stream(0, READ, 16 * 2**20, ost=0)
        sim.attach(wl)
        sim.set_knobs(sim.client_oscs(0), window_pages=1024, rpcs_in_flight=16)
        if tuned:
            run_with_agents(sim, dial_model, [0], seconds=12.0)
        else:
            sim.run(12.0)
        return wl.done_bytes(sim) / 12.0 / 1e6

    static, dial = tput(False), tput(True)
    assert dial > 0.9 * static, (static, dial)


def test_agent_only_two_snapshots_in_memory(dial_model):
    """Paper SIV-C: DIAL keeps only two snapshots per interface."""
    from repro.core.agent import DIALAgent, SimClientPort

    sim = PFSSim(n_clients=1, n_osts=4, seed=0)
    sim.attach(sequential_stream(0, READ, 2**20, ost=0))
    agent = DIALAgent(SimClientPort(sim, 0), dial_model, k=1)
    for _ in range(6):
        sim.run(0.5)
        agent.tick()
    for osc, hist in agent._hist.items():
        assert len(hist) <= 2


def test_log2_knob_jnp_matches_numpy_bitwise():
    """The jitted knob log2 (compares only, no 64-bit bitcast) equals
    np.log2 bit for bit on every Θ value and on the powers of two up to
    2^20; any 1-ulp slip flips GBDT splits at threshold 0."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.metrics import _log2_knob

    vals = np.unique(np.concatenate([
        np.asarray(SPACE.window_pages, dtype=np.int64),
        np.asarray(SPACE.rpcs_in_flight, dtype=np.int64),
        2 ** np.arange(21, dtype=np.int64)]))
    want = np.log2(vals.astype(np.float64))
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(lambda x: _log2_knob(x, jnp))(vals))
        got_f = np.asarray(jax.jit(lambda x: _log2_knob(x, jnp))(
            vals.astype(np.float64)))
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
    np.testing.assert_array_equal(got_f.view(np.int64), want.view(np.int64))
    # off-grid knobs fall back to the backend log2
    with jax.enable_x64(True):
        odd = np.asarray(jax.jit(lambda x: _log2_knob(x, jnp))(
            np.array([3.0, 48.0, 1000.0])))
    np.testing.assert_allclose(odd, np.log2([3.0, 48.0, 1000.0]),
                               rtol=1e-15)
