"""Device-resident telemetry: tracing must never change a decision,
the host mirror must produce the same rows as the fused trace, and the
sinks (JSONL, Chrome trace_event, markdown) must round-trip / validate.
Also covers the perf-ledger tooling (compare.py, provenance, host spans).
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.fleet import run_fleet  # noqa: E402
from repro.obs.schema import (DECISION_FIELDS, TIMELINE_FIELDS, RunTrace,
                              TraceConfig)  # noqa: E402
from repro.pfs import PFSSim  # noqa: E402
from repro.pfs.engine import READ, WRITE  # noqa: E402
from repro.pfs.workloads import random_stream, sequential_stream  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_EXACT = ("decided", "ops", "theta", "changed", "n_candidates", "active",
          "steady", "warm")
_CLOSE = ("t", "score", "probs", "vol_r", "vol_w", "ratio")


def _mixed_sim(seed=5):
    sim = PFSSim(n_clients=4, n_osts=2, seed=seed)
    sim.attach(sequential_stream(0, READ, 4 * 2**20, ost=0))
    sim.attach(random_stream(1, WRITE, 64 * 1024, ost=1, n_threads=2))
    sim.attach(sequential_stream(2, WRITE, 2 * 2**20, ost=0, n_threads=2))
    sim.attach(random_stream(3, READ, 256 * 1024, ost=1))
    sim.set_knobs(np.arange(sim.n_osc), window_pages=64, rpcs_in_flight=2)
    return sim


def _traj(decisions):
    return [(r.oscs.tolist(), r.ops.tolist(), r.decisions.theta.tolist(),
             r.decisions.changed.tolist()) for r in decisions]


def _counters_close(state_a, state_b, rtol=1e-6):
    for f in ("ctr_bytes_done", "ctr_rpcs_sent", "ctr_latency_sum",
              "ctr_pending_integral", "ctr_block_time"):
        np.testing.assert_allclose(
            np.asarray(getattr(state_a, f), dtype=np.float64),
            np.asarray(getattr(state_b, f), dtype=np.float64),
            rtol=rtol, atol=1e-6, err_msg=f)


@pytest.fixture(scope="module")
def fused_traced(dial_model):
    cfg = TraceConfig(stride=5)
    sim = _mixed_sim()
    fleet = run_fleet(sim, dial_model, seconds=4.0, interval=0.5,
                      backend="jax-fused", trace=cfg)
    return fleet, sim, cfg


# ---------------------------------------------------------------------- #
# schema guards
# ---------------------------------------------------------------------- #
def test_trace_config_stride_validates():
    with pytest.raises(ValueError, match="stride"):
        TraceConfig(stride=0)
    assert TraceConfig().stride >= 1


# ---------------------------------------------------------------------- #
# tentpole: tracing is decision-neutral on every path
# ---------------------------------------------------------------------- #
def test_traced_fused_is_decision_neutral(dial_model, fused_traced):
    """Trace records are *additional* scan outputs: the traced fused
    dispatch produces bit-identical θ and ≤1e-6 counters vs untraced."""
    f_tr, sim_tr, _ = fused_traced
    sim = _mixed_sim()
    f = run_fleet(sim, dial_model, seconds=4.0, interval=0.5,
                  backend="jax-fused")
    assert _traj(f_tr.decisions) == _traj(f.decisions)
    assert any(len(r) for r in f_tr.decisions), "run never decided"
    np.testing.assert_array_equal(sim_tr.window_pages, sim.window_pages)
    np.testing.assert_array_equal(sim_tr.rpcs_in_flight,
                                  sim.rpcs_in_flight)
    _counters_close(sim_tr.state, sim.state)
    trace = f_tr.trace
    assert isinstance(trace, RunTrace)
    trace.validate()
    assert trace.decisions["decided"].any()


def test_traced_numpy_is_decision_neutral(dial_model):
    sim_a, sim_b = _mixed_sim(), _mixed_sim()
    fa = run_fleet(sim_a, dial_model, seconds=3.0, interval=0.5,
                   backend="numpy")
    fb = run_fleet(sim_b, dial_model, seconds=3.0, interval=0.5,
                   backend="numpy", trace=TraceConfig(stride=5))
    assert _traj(fa.decisions) == _traj(fb.decisions)
    np.testing.assert_array_equal(sim_a.window_pages, sim_b.window_pages)
    _counters_close(sim_a.state, sim_b.state)
    fb.trace.validate()


def test_host_trace_mirrors_fused_trace(dial_model, fused_traced):
    """The host tick loop with the HostTracer produces the same rows —
    every decision field and every timeline track — as the in-dispatch
    fused trace (the host model scores through the same fused float32
    predictor, so probabilities match bitwise)."""
    f_fused, _, cfg = fused_traced
    model_jax = copy.copy(dial_model)
    model_jax.backend = "jax"
    model_jax.__post_init__()
    sim = _mixed_sim()
    f_host = run_fleet(sim, model_jax, seconds=4.0, interval=0.5,
                       backend="numpy", trace=cfg)
    th, tf = f_host.trace, f_fused.trace
    th.validate()
    assert th.n_intervals == tf.n_intervals
    assert th.n_interfaces == tf.n_interfaces
    for f in _EXACT:
        np.testing.assert_array_equal(th.decisions[f], tf.decisions[f],
                                      err_msg=f)
    for f in _CLOSE:
        np.testing.assert_allclose(th.decisions[f], tf.decisions[f],
                                   rtol=1e-5, atol=1e-8, err_msg=f)
    assert set(th.decisions) == set(DECISION_FIELDS)
    assert th.timeline is not None and tf.timeline is not None
    assert set(th.timeline) == set(TIMELINE_FIELDS)
    for f in TIMELINE_FIELDS:
        np.testing.assert_allclose(th.timeline[f], tf.timeline[f],
                                   rtol=1e-5, atol=1e-6, err_msg=f)


def test_split_batch_trace_covers_untuned_elements(dial_model):
    """Mixed tuned/untuned batch: tracing stays decision-neutral, the
    merged trace covers every element's timeline, and never-tuned
    elements carry the inert placeholder decision record (decided
    false, θ = applied knobs)."""
    from repro.lab.batch import run_batch, stack_scenarios
    from repro.lab.scenarios import SCENARIOS, build, variants

    cfg = TraceConfig(stride=10)
    spec = SCENARIOS["degraded_ost"]

    def batch():
        return stack_scenarios([build(s) for s in variants(spec, 3,
                                                           seed=4)])
    ba, bb = batch(), batch()
    n = ba.n_osc
    # tune only elements 0 and 2; element 1 runs the lean program
    cols = np.concatenate([np.arange(n), 2 * n + np.arange(n)])
    ra = run_batch(ba, dial_model, seconds=3.0, interval=0.5, fused=True,
                   tune_cols=cols)
    rb = run_batch(bb, dial_model, seconds=3.0, interval=0.5, fused=True,
                   tune_cols=cols, trace=cfg)
    assert _traj(ra.decisions) == _traj(rb.decisions)
    trace = RunTrace.from_fused(rb, cfg, bb.params.tick)
    trace.validate()
    assert trace.n_interfaces == 3 * n
    decided = trace.decisions["decided"]
    assert not decided[:, n:2 * n].any(), "lean program cannot decide"
    assert decided[:, :n].any() or decided[:, 2 * n:].any()
    # untuned columns: θ is the element's applied (never-changed) knobs
    theta_u = trace.decisions["theta"][:, n:2 * n]
    want = np.stack([np.asarray(bb.state.window_pages)[1],
                     np.asarray(bb.state.rpcs_in_flight)[1]], axis=-1)
    np.testing.assert_array_equal(
        theta_u, np.broadcast_to(want, theta_u.shape))
    assert not trace.decisions["changed"][:, n:2 * n].any()
    # the timeline merged from both programs: finite, all elements hot
    tl = trace.timeline
    assert tl["read_bytes"].shape[1] == 3 * bb.topo.n_osts
    assert np.isfinite(tl["read_bytes"]).all()
    assert (tl["read_bytes"] + tl["write_bytes"]).sum() > 0


def test_sharded_traced_matches_untraced_8dev(dial_model):
    """Traced sharded dispatch on 8 forced host devices: θ identical to
    the untraced single-device run, trace validates at full batch."""
    code = """
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

import jax
from repro.distributed.sharding import fleet_mesh
from repro.lab.batch import run_batch, stack_scenarios
from repro.lab.scenarios import SCENARIOS, build, variants
from repro.obs.schema import RunTrace, TraceConfig

assert jax.device_count() == 8
cfg = TraceConfig(stride=10)
spec = SCENARIOS["failing_ost"]
ba = stack_scenarios([build(s) for s in variants(spec, 8, seed=2)])
bb = stack_scenarios([build(s) for s in variants(spec, 8, seed=2)])
ra = run_batch(ba, model, seconds=3.0, interval=0.5, fused=True)
rb = run_batch(bb, model, seconds=3.0, interval=0.5, fused=True,
               mesh=fleet_mesh(8), trace=cfg)
ta = [(i, int(o), int(op), int(t[0]), int(t[1]))
      for i, r in enumerate(ra.decisions)
      for o, op, t in zip(r.oscs, r.ops, r.decisions.theta)]
tb = [(i, int(o), int(op), int(t[0]), int(t[1]))
      for i, r in enumerate(rb.decisions)
      for o, op, t in zip(r.oscs, r.ops, r.decisions.theta)]
assert ta == tb and len(tb) > 0
trace = RunTrace.from_fused(rb, cfg, bb.params.tick)
trace.validate()
assert trace.n_interfaces == 8 * ba.n_osc
assert trace.decisions["decided"].any()
print("OK", len(tb))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------- #
# sinks
# ---------------------------------------------------------------------- #
def test_jsonl_roundtrip(tmp_path, fused_traced):
    from repro.obs.sinks import read_jsonl, write_jsonl

    fleet, _, _ = fused_traced
    path = write_jsonl(fleet.trace, str(tmp_path / "trace.jsonl"))
    back = read_jsonl(path)
    a, b = fleet.trace, back
    assert a.n_intervals == b.n_intervals
    assert a.n_interfaces == b.n_interfaces
    assert a.config == b.config
    np.testing.assert_array_equal(a.oscs, b.oscs)
    # the sink rounds floats to 9 decimals: lossless for flags/θ,
    # absolute 1e-9 for probabilities and gate metrics
    for f in DECISION_FIELDS:
        np.testing.assert_allclose(a.decisions[f], b.decisions[f],
                                   rtol=1e-6, atol=1e-9, err_msg=f)
    for f in TIMELINE_FIELDS:
        np.testing.assert_allclose(a.timeline[f], b.timeline[f],
                                   rtol=1e-6, atol=1e-9, err_msg=f)
    back.validate()


def test_chrome_trace_valid_and_monotone(tmp_path, fused_traced):
    from repro.obs.sinks import write_chrome

    fleet, _, _ = fused_traced
    path = write_chrome(fleet.trace, str(tmp_path / "trace.chrome.json"))
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, "empty chrome trace"
    assert {e["ph"] for e in events} <= {"C", "i", "M"}
    timed = [e["ts"] for e in events if e["ph"] != "M"]
    assert all(t >= 0 for t in timed)
    assert timed == sorted(timed), "timestamps not monotone"
    # counter tracks exist for every OST and decisions made it in
    assert any(e["ph"] == "i" for e in events)
    assert any(e["ph"] == "C" for e in events)


def test_render_summary(fused_traced):
    from repro.obs.sinks import render_summary

    fleet, _, _ = fused_traced
    md = render_summary(fleet.trace, title="mixed")
    assert "mixed" in md
    assert "decided" in md
    assert "OST" in md


# ---------------------------------------------------------------------- #
# fuzz triage replay recipes
# ---------------------------------------------------------------------- #
def test_trace_recipe_roundtrip(tmp_path):
    from repro.lab.fuzz import fingerprint, spec_to_dict, trace_recipe
    from repro.lab.scenarios import SCENARIOS
    from repro.lab.trace import load_spec_from_report

    spec = SCENARIOS["degraded_ost"]
    fp = fingerprint(spec)
    report = {"triage": {"losses": [
        {"name": spec.name, "fingerprint": fp,
         "spec": spec_to_dict(spec)}]}}
    path = str(tmp_path / "report.json")
    with open(path, "w") as f:
        json.dump(report, f)
    recipe = trace_recipe(path, fp)
    assert "--from-report" in recipe and fp in recipe
    back = load_spec_from_report(path, fp)
    assert back.n_clients == spec.n_clients
    assert back.n_osts == spec.n_osts
    with pytest.raises(KeyError, match="not in"):
        load_spec_from_report(path, "no-such-fp")


# ---------------------------------------------------------------------- #
# perf ledger: host spans, provenance, compare gate
# ---------------------------------------------------------------------- #
def test_phase_timers():
    """Host phases are ``obs.spans``: nested spans each count their own
    calls and seconds, and a compile event goes to the innermost open
    span (or to "none" outside every span)."""
    import time

    import jax

    from repro.obs import spans

    compile_ev = "/jax/core/compile/backend_compile_duration"
    before = spans.totals()
    with spans.span("test.outer"):
        with spans.span("test.inner"):
            time.sleep(0.01)
            jax.monitoring.record_event_duration_secs(compile_ev, 0.25)
            jax.monitoring.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", 0.5)
    with spans.span("test.inner"):
        pass
    jax.monitoring.record_event_duration_secs(
        "/jax/some/other_event", 1.0)          # not a compile event
    d = spans.since(before)
    rows = d["spans"]
    assert rows["test.outer"]["calls"] == 1
    assert rows["test.inner"]["calls"] == 2
    assert rows["test.inner"]["seconds"] >= 0.01
    assert rows["test.outer"]["seconds"] >= rows["test.inner"]["seconds"] \
        - 1e-3
    inner, outer = d["compiles"]["test.inner"], d["compiles"]["test.outer"]
    assert inner["compiles"] == 1 and inner["backend_s"] == 0.25
    assert inner["cache_loads"] == 1 and inner["cache_s"] == 0.125
    assert outer["compiles"] == 0 and outer["trace_s"] == 0.5
    jax.monitoring.record_event_duration_secs(compile_ev, 0.0625)
    assert spans.since(before)["compiles"]["none"]["backend_s"] == 0.0625
    # a span closes on an exception too, and the stack unwinds
    with pytest.raises(RuntimeError):
        with spans.span("test.raises"):
            raise RuntimeError("x")
    assert spans.since(before)["spans"]["test.raises"]["calls"] == 1
    jax.monitoring.record_event_duration_secs(compile_ev, 0.0625)
    assert spans.since(before)["compiles"]["none"]["compiles"] == 2


def test_collect_provenance():
    from repro.obs.provenance import collect_provenance

    p = collect_provenance()
    for key in ("git_sha", "platform", "python", "jax_version",
                "device_count", "device_kind", "default_backend"):
        assert key in p, key
    assert p["device_count"] >= 1
    assert isinstance(p["git_sha"], str)


def test_compare_direction_and_gate(tmp_path):
    sys.path.insert(0, REPO)
    try:
        from benchmarks.compare import compare, direction, main
    finally:
        sys.path.remove(REPO)

    assert direction("speedup") == +1
    assert direction("read_e2e_ms") == -1
    assert direction("default_overhead_pct") == -1
    assert direction("us_per_call") == 0

    base = {"schema": "dial-bench-v1", "benchmarks": [
        {"name": "x", "us_per_call": 100,
         "derived": {"speedup": 10.0, "exec_ms": 5.0}}]}
    good = {"schema": "dial-bench-v1", "benchmarks": [
        {"name": "x", "us_per_call": 900,
         "derived": {"speedup": 10.5, "exec_ms": 4.9}}]}
    bad = {"schema": "dial-bench-v1", "benchmarks": [
        {"name": "x", "us_per_call": 100,
         "derived": {"speedup": 5.0, "exec_ms": 9.0}}]}
    assert compare(base, good)["regressions"] == []
    r = compare(base, bad)
    assert {x["metric"] for x in r["regressions"]} == \
        {"x.speedup", "x.exec_ms"}
    # a looser threshold passes what the default flags
    assert compare(base, bad, threshold=1.0)["regressions"] == []

    pb, pc = str(tmp_path / "b.json"), str(tmp_path / "c.json")
    with open(pb, "w") as f:
        json.dump(base, f)
    with open(pc, "w") as f:
        json.dump(bad, f)
    assert main([pb, pb]) == 0
    assert main([pb, pc]) == 1
    assert main([pb, pc, "--report-only"]) == 0


def test_compare_asymmetric_records(tmp_path):
    """Metrics on only one side: first-class new/removed rows in the
    table (and render), never a gate failure."""
    sys.path.insert(0, REPO)
    try:
        from benchmarks.compare import compare, main, render
    finally:
        sys.path.remove(REPO)

    base = {"schema": "dial-bench-v1", "benchmarks": [
        {"name": "x", "us_per_call": 100, "derived": {"speedup": 10.0}},
        {"name": "old", "us_per_call": 50, "derived": {"exec_ms": 3.0}}]}
    cand = {"schema": "dial-bench-v1", "benchmarks": [
        {"name": "x", "us_per_call": 110, "derived": {"speedup": 10.1}},
        {"name": "fresh", "us_per_call": 70, "derived": {"gain": 2.0}}]}
    r = compare(base, cand)
    verdicts = {row["metric"]: row["verdict"] for row in r["rows"]}
    assert verdicts["old.exec_ms"] == "removed"
    assert verdicts["old.us_per_call"] == "removed"
    assert verdicts["fresh.gain"] == "new"
    assert verdicts["fresh.us_per_call"] == "new"
    # removed rows keep their baseline value, new rows their candidate
    by_metric = {row["metric"]: row for row in r["rows"]}
    assert by_metric["old.exec_ms"]["baseline"] == 3.0
    assert by_metric["old.exec_ms"]["candidate"] is None
    assert by_metric["fresh.gain"]["candidate"] == 2.0
    assert by_metric["fresh.gain"]["baseline"] is None
    assert r["regressions"] == []          # asymmetry never fails
    out = render(r)
    assert "removed" in out and "new" in out
    assert "old.exec_ms" in out and "fresh.gain" in out

    pb, pc = str(tmp_path / "b.json"), str(tmp_path / "c.json")
    with open(pb, "w") as f:
        json.dump(base, f)
    with open(pc, "w") as f:
        json.dump(cand, f)
    assert main([pb, pc]) == 0
