"""Engine layering equivalence: legacy loop == vectorized table == fused
JAX scan, plus byte-conservation properties on every backend.

The numpy tick loop (legacy ``Workload`` objects driving ``sim.step()``)
is the oracle; the vectorized ``WorkloadTable`` demand path and the
jitted ``lax.scan`` interval path must reproduce its per-OSC counters to
tight tolerance, and ``FleetAgent`` tuning on top of either engine
backend must produce identical knob trajectories.
"""

import numpy as np
import pytest

from repro.pfs import PFSSim
from repro.pfs.engine import READ, WRITE
from repro.pfs.workloads import (WorkloadTable, bdcats_read, dlio_reader,
                                 random_stream, run_interval,
                                 sequential_stream, table_from_sim,
                                 vpic_write)

TICKS_PER_INTERVAL = 100   # 0.5 s tuning interval at the 5 ms tick
N_INTERVALS = 3

PROBE_COUNTERS = (
    "ctr_bytes_done", "ctr_rpcs_sent", "ctr_rpc_bytes", "ctr_partial_rpcs",
    "ctr_latency_sum", "ctr_rpcs_done", "ctr_req_count", "ctr_req_bytes",
    "ctr_cache_hit_bytes", "ctr_block_time", "ctr_pending_integral",
    "ctr_active_integral", "ctr_dirty_integral", "ctr_grant_integral",
    "randomness",
)
FLUID_FIELDS = (
    "pending", "queue_rpcs", "queue_bytes", "active_rpcs", "setup_work",
    "unready_bytes", "ready_bytes", "dirty_bytes", "grant_used",
    "write_blocked",
)


def mixed_workloads():
    """The paper's evaluation mix (vpic + bdcats + dlio + filebench),
    including overlapping stripes that force multi-wave demand."""
    wls = []
    for c in range(0, 4):
        wls.append(vpic_write(c, dims=1 + c % 3, osts=(0, 1, 2, 3)))
    for c, mode in zip(range(4, 8), ("partial", "strided", "full", "partial")):
        wls.append(bdcats_read(c, mode, osts=(0, 1, 2, 3)))
    for c in range(8, 12):
        wls.append(dlio_reader(c, "bert" if c % 2 else "megatron",
                               n_threads=2 + c % 3, osts=(c % 4,)))
    for c in range(12, 16):
        if c % 2:
            wls.append(sequential_stream(c, READ, 4 * 2**20, ost=c % 4))
        else:
            wls.append(random_stream(c, WRITE, 256 * 1024, ost=c % 4,
                                     n_threads=2))
    # overlapping same-client stripes -> multi-wave table
    wls.append(bdcats_read(4, "full", osts=(2, 3)))
    wls.append(vpic_write(0, dims=1, osts=(2, 3)))
    return wls


def build_sim(seed=0):
    sim = PFSSim(n_clients=16, n_osts=4, seed=seed)   # 64 OSC interfaces
    for w in mixed_workloads():
        sim.attach(w)
    return sim


def run_oracle(n_ticks):
    sim = build_sim()
    for _ in range(n_ticks):
        sim.step()
    return sim


def assert_states_close(oracle_state, state, fields, rtol):
    for f in fields:
        a = np.asarray(getattr(oracle_state, f), dtype=float)
        b = np.asarray(getattr(state, f), dtype=float)
        err = np.max(np.abs(a - b) / np.maximum(np.abs(a), 1.0))
        assert err <= rtol, (f, err)


# ---------------------------------------------------------------------- #
# layer equivalence
# ---------------------------------------------------------------------- #
def test_workload_table_matches_legacy_loop():
    """Vectorized demand (numpy backend) == per-object Workload.tick."""
    n = TICKS_PER_INTERVAL * N_INTERVALS
    oracle = run_oracle(n)
    sim = build_sim()
    table, wstate = table_from_sim(sim)
    assert table.n_waves >= 2   # the overlap rows exercise wave sequencing
    state, wstate = run_interval(sim.params, sim.topo, table, sim.state,
                                 wstate, n)
    assert_states_close(oracle.state, state, PROBE_COUNTERS, 1e-9)
    assert_states_close(oracle.state, state, FLUID_FIELDS, 1e-9)
    # per-row delivered bytes and closed-loop issued state match the
    # legacy objects too (the handoff sync_workloads_from_table relies on)
    done = table.done_bytes(state, wstate)
    for i, w in enumerate(oracle._workloads):
        assert done[i] == pytest.approx(w.done_bytes(oracle), rel=1e-9)
        assert wstate.issued[i] == pytest.approx(w._issued, rel=1e-9,
                                                 abs=1e-3)


def test_jax_scan_matches_numpy_oracle():
    """Acceptance: mixed vpic/bdcats/dlio, 64 OSCs, 3 fused intervals ->
    per-OSC ctr_bytes_done and every probe counter within 1e-6 relative
    of the numpy oracle."""
    jax = pytest.importorskip("jax")
    from repro.pfs.engine_jax import FusedEngine

    oracle = run_oracle(TICKS_PER_INTERVAL * N_INTERVALS)
    sim = build_sim()
    table, wstate = table_from_sim(sim)
    engine = FusedEngine(sim.params, sim.topo, table, TICKS_PER_INTERVAL,
                         seg_backend="jax")
    state = sim.state
    for _ in range(N_INTERVALS):
        state, wstate = engine.run_interval(state, wstate)
    assert state.tick_index == oracle.state.tick_index
    assert_states_close(oracle.state, state, PROBE_COUNTERS, 1e-6)
    assert_states_close(oracle.state, state, FLUID_FIELDS, 1e-6)


def test_fleet_agent_trajectories_identical_across_backends(dial_model):
    """FleetAgent tuning on the fused scan == on the Python tick loop:
    same decisions, same knob trajectory, interval for interval."""
    pytest.importorskip("jax")
    from repro.core.fleet import run_fleet

    def run(backend):
        sim = PFSSim(n_clients=8, n_osts=2, seed=3)
        for c in range(8):
            if c % 2:
                sim.attach(sequential_stream(c, READ, 4 * 2**20, ost=c % 2))
            else:
                sim.attach(random_stream(c, WRITE, 256 * 1024, ost=c % 2,
                                         n_threads=2))
        sim.set_knobs(np.arange(sim.n_osc), window_pages=64, rpcs_in_flight=2)
        fleet = run_fleet(sim, dial_model, seconds=3.0, interval=0.5,
                          backend=backend)
        traj = [(r.oscs.tolist(), r.ops.tolist(), r.decisions.theta.tolist(),
                 r.decisions.changed.tolist()) for r in fleet.decisions]
        return traj, sim.window_pages.copy(), sim.rpcs_in_flight.copy()

    traj_np, win_np, rif_np = run("numpy")
    traj_jax, win_jax, rif_jax = run("jax")
    assert traj_np == traj_jax
    np.testing.assert_array_equal(win_np, win_jax)
    np.testing.assert_array_equal(rif_np, rif_jax)


# ---------------------------------------------------------------------- #
# conservation properties
# ---------------------------------------------------------------------- #
def check_conservation(state):
    """Over any workload mix: per-op submitted bytes == completed +
    in-pipeline bytes, and all state arrays stay non-negative."""
    s = state
    atol = 1e-3   # bytes; counters reach ~1e10
    # reads: everything submitted is either done or still in the pipeline
    read_pipe = (s.pending[READ] + s.queue_bytes[READ]
                 + s.unready_bytes[READ] + s.ready_bytes[READ])
    np.testing.assert_allclose(
        np.asarray(s.ctr_req_bytes[READ]),
        np.asarray(s.ctr_bytes_done[READ] + read_pipe),
        rtol=1e-9, atol=atol, err_msg="read byte conservation")
    # writes: app-visible completion == acceptance into the dirty cache,
    # and the write pipeline mirrors the dirty cache exactly
    np.testing.assert_allclose(
        np.asarray(s.ctr_req_bytes[WRITE]),
        np.asarray(s.ctr_bytes_done[WRITE]),
        rtol=1e-9, atol=atol, err_msg="write acceptance accounting")
    write_pipe = (s.pending[WRITE] + s.queue_bytes[WRITE]
                  + s.unready_bytes[WRITE] + s.ready_bytes[WRITE])
    np.testing.assert_allclose(
        np.asarray(s.dirty_bytes), np.asarray(write_pipe),
        rtol=1e-9, atol=atol, err_msg="dirty cache vs write pipeline")
    for f in FLUID_FIELDS + PROBE_COUNTERS:
        assert (np.asarray(getattr(s, f), dtype=float) >= -1e-6).all(), f


@pytest.mark.parametrize("seed", [0, 7])
def test_conservation_numpy_backend(seed):
    sim = build_sim(seed=seed)
    for i in range(6):
        for _ in range(50):
            sim.step()
        check_conservation(sim.state)


def test_conservation_jax_backend():
    pytest.importorskip("jax")
    from repro.pfs.engine_jax import FusedEngine

    sim = build_sim()
    table, wstate = table_from_sim(sim)
    engine = FusedEngine(sim.params, sim.topo, table, 50, seg_backend="jax")
    state = sim.state
    for _ in range(6):
        state, wstate = engine.run_interval(state, wstate)
        check_conservation(state)


# ---------------------------------------------------------------------- #
# segment_reduce kernel
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("e,s,block", [(37, 4, 64), (1024, 8, 256),
                                       (5000, 33, 1024)])
def test_segment_sum_kernel_matches_refs(e, s, block):
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.segment_reduce.kernel import segment_sum as pallas_ss
    from repro.kernels.segment_reduce.ops import segment_sum_np
    from repro.kernels.segment_reduce.ref import segment_sum_ref

    rng = np.random.default_rng(e)
    x = rng.normal(size=e).astype(np.float32)
    seg = rng.integers(0, s, size=e)
    want = segment_sum_np(x, seg, s)
    got_ref = np.asarray(segment_sum_ref(jnp.asarray(x), jnp.asarray(seg), s))
    got_pal = np.asarray(pallas_ss(jnp.asarray(x), jnp.asarray(seg), s,
                                   block_e=block, interpret=True))
    np.testing.assert_allclose(got_ref, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got_pal, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n_osc,n_ost", [(512, 8), (2048, 16)])
def test_segment_sum_f64_path_matches_numpy(n_osc, n_ost):
    """The segment reduction the engine calls under x64, through the
    Pallas kernel's float32 cast: float64 in, float64 out, within the
    engine-equivalence bound of the numpy oracle at engine widths."""
    jax = pytest.importorskip("jax")
    from repro.kernels.segment_reduce.ops import (make_segment_sum,
                                                  segment_sum_np)

    segsum = make_segment_sum("pallas_interpret")
    rng = np.random.default_rng(n_osc)
    osc_ost = np.arange(n_osc) % n_ost
    for scale in (1.0, 1e9):          # unit fractions and byte counters
        x = rng.random(n_osc) * scale * (rng.random(n_osc) < 0.7)
        with jax.enable_x64(True):
            got = np.asarray(jax.jit(lambda v, i: segsum(v, i, n_ost))(
                x, osc_ost))
        assert got.dtype == np.float64
        np.testing.assert_allclose(got, segment_sum_np(x, osc_ost, n_ost),
                                   rtol=1e-6)


@pytest.mark.parametrize("name", ["vpic_checkpoint", "filebench_mix",
                                  "dlio_bert", "client_eviction"])
def test_engine_steps_hold_under_rounding_noise(name):
    """A stand-in for a backend that rounds float64 differently (XLA's
    CPU FMAs, the TPU's emulated f64): every float state field is
    multiplied by (1 + 1e-13 * N(0, 1)) after every tick.  The engine's
    discrete steps (window packing, partial expiry, completion, a
    reader's next request, duty cycles) must not flip, so the counters
    stay within 1e-6 of the clean run."""
    from repro.lab.scenarios import build, get_scenario
    from repro.pfs.state import _STATE_FIELDS, engine_step

    rng = np.random.default_rng(0)
    n_ticks = 2000                      # 10 s at the 5 ms tick

    def noisy(a):
        a = np.asarray(a)
        if a.dtype.kind != "f":
            return a
        return a * (1.0 + 1e-13 * rng.standard_normal(a.shape))

    def run(noise):
        b = build(get_scenario(name))
        state, wstate = b.state, b.wstate
        sched = b.schedule(0, n_ticks)
        for t in range(n_ticks):
            demand, wstate = b.table.demand_step(b.params, wstate, state)
            state = engine_step(b.params, b.topo, state, demand,
                                disturbance=sched.at_tick(t))
            if noise:
                for f in _STATE_FIELDS:
                    if f != "tick_index":
                        setattr(state, f, noisy(getattr(state, f)))
                state.now = float(noisy(state.now))
                wstate.issued = noisy(wstate.issued)
        return state

    clean, jittered = run(False), run(True)
    assert clean.ctr_bytes_done.sum() > 0
    for f in PROBE_COUNTERS:
        np.testing.assert_allclose(getattr(jittered, f), getattr(clean, f),
                                   rtol=1e-6, atol=1e-3, err_msg=f)
