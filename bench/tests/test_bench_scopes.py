"""The program's named scopes and host spans in a trace, on synthetic op
lists and HLO text, the readings taken from them, and the reader of the
program's compile table."""

import jax
import pytest

import run
import scopes
from jobs import load_part

S = 1_000_000_000        # ns per second

HLO = """HloModule m

%region_0.1 (a: f64[], b: f64[]) -> f64[] {
  ROOT %add.1 = f64[] add(f64[] %a, f64[] %b), metadata={op_name="dial.tick/dial.segsum/add"}
}

%fused_computation.3 (p0: f64[8], p1: s32[4]) -> f64[8] {
  %p0 = f64[8]{0} parameter(0)
  ROOT %scatter.1 = f64[8]{0} scatter(f64[8]{0} %p0, s32[4]{0} %p1), to_apply=%region_0.1, metadata={op_name="jit(run)/while/body/dial.tick/while/body/dial.engine/dial.segsum/scatter-add" stack_frame_id=3}
}

%body.2 (p: f64[8]) -> f64[8] {
  %copy-start.3 = (f64[8]{0}, f64[8]{0}, u32[]) copy-start(f64[8]{0} %p)
  %copy-done.4 = f64[8]{0} copy-done((f64[8]{0}, f64[8]{0}, u32[]) %copy-start.3)
  %subtract.6 = f64[8]{0} subtract(f64[8]{0} %p, f64[8]{0} %p), metadata={op_name="jit(run)/while/body/dial.tick/while/body/dial.demand/sub"}
  ROOT %multiply.5 = f64[8]{0} multiply(f64[8]{0} %copy-done.4, f64[8]{0} %subtract.6), metadata={op_name="jit(run)/while/body/dial.tick/while/body/dial.engine/mul"}
}

ENTRY %main (a: f64[8]) -> f64[8] {
  %fusion.5 = f64[8]{0} fusion(f64[8]{0} %a), kind=kLoop, calls=%fused_computation.3
  %fusion.6 = f32[8]{0} fusion(f64[8]{0} %fusion.5), kind=kLoop, calls=%c, metadata={op_name="jit(run)/while/body/dial.decide/dial.forest/gather;jit(run)/while/body/dial.decide/dial.features/concatenate"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %fusion.6), kind=kLoop, calls=%d, metadata={op_name="jit(run)/while/body/dial.decide/dial.forest/exp;jit(run)/while/body/add"}
  %copy.8 = f32[8]{0} copy(f32[8]{0} %fusion.7), metadata={op_name="jit(run)/while/body/dynamic_update_slice"}
  ROOT %while.9 = (f64[8]{0}) while((f64[8]{0}) %t), condition=%cond, body=%body
}
"""


def test_instruction_scopes_from_compiled_text():
    sc = scopes.instruction_scopes([HLO])
    assert sc["%scatter.1"] == ("dial.tick", "dial.engine", "dial.segsum")
    assert sc["%add.1"] == ("dial.tick", "dial.segsum")
    # a fusion without metadata takes its fused computation's scopes
    assert sc["%fusion.5"] == sc["%scatter.1"]
    # merged instructions keep what their scoped parts share
    assert sc["%fusion.6"] == ("dial.decide",)
    assert sc["%fusion.7"] == ("dial.decide", "dial.forest")
    assert sc["%copy.8"] == ()
    # a copy the compiler added takes what its computation's ops share
    assert sc["%copy-done.4"] == ("dial.tick",)
    assert sc["%multiply.5"] == ("dial.tick", "dial.engine")
    assert scopes.scope_path("a/b;c") == ()


REBUILT = """HloModule m

%fused_computation.1 (p0: f32[8], p1: s32[4]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %transpose.2 = s32[4]{0} transpose(s32[4]{0} %p1), dimensions={0}, metadata={op_name="jit(run)/while/body/dial.tick/dial.demand/gather"}
  %add.3 = f32[8]{0} add(f32[8]{0} %p0, f32[8]{0} %p0), metadata={op_name="jit(run)/while/body/dial.tick/dial.demand/add"}
  ROOT %scatter.4 = f32[8]{0} scatter(f32[8]{0} %add.3, s32[4]{0} %transpose.2), to_apply=%region_0.1
}

%fused_computation.5 (p0: f32[8], p1: s32[4]) -> f32[8] {
  %add.6 = f32[8]{0} add(f32[8]{0} %p0, f32[8]{0} %p0), metadata={op_name="jit(run)/while/body/dial.tick/dial.demand/add"}
  ROOT %scatter.7 = f32[8]{0} scatter(f32[8]{0} %add.6, s32[4]{0} %p1), to_apply=%region_0.1, metadata={op_name="jit(run)/while/body/dial.tick/dial.demand/dial.segsum/scatter-add"}
}

%fused_computation.8 (p0: f32[8], p1: s32[4]) -> f32[8] {
  %add.9 = f32[8]{0} add(f32[8]{0} %p0, f32[8]{0} %p0), metadata={op_name="jit(run)/while/body/dial.tick/dial.engine/add"}
  ROOT %scatter.10 = f32[8]{0} scatter(f32[8]{0} %add.9, s32[4]{0} %p1), to_apply=%region_0.1, metadata={op_name="jit(run)/while/body/dial.tick/dial.engine/scatter-add"}
}

ENTRY %main (a: f32[8], i: s32[4]) -> f32[8] {
  %fusion.11 = f32[8]{0} fusion(f32[8]{0} %a, s32[4]{0} %i), kind=kLoop, calls=%fused_computation.1
  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %fusion.11, s32[4]{0} %i), kind=kLoop, calls=%fused_computation.5
  %fusion.13 = f32[8]{0} fusion(f32[8]{0} %fusion.12, s32[4]{0} %i), kind=kLoop, calls=%fused_computation.8
  ROOT %scatter.14 = f32[8]{0} scatter(f32[8]{0} %fusion.13, s32[4]{0} %i), to_apply=%region_0.1
}
"""


def test_scatter_rebuilt_without_metadata_is_a_segment_reduction():
    """The TPU compiler rebuilds some scatters without ``op_name``; they
    and their fusions are charged to ``dial.segsum`` beneath the scopes
    their fused neighbours share, as is a fusion whose scatter kept its
    scope.  A scatter scoped elsewhere is left where it is."""
    sc = scopes.instruction_scopes([REBUILT])
    seg = ("dial.tick", "dial.demand", "dial.segsum")
    assert sc["%scatter.4"] == seg
    assert sc["%fusion.11"] == seg
    assert sc["%transpose.2"] == ("dial.tick", "dial.demand")
    assert sc["%fusion.12"] == seg
    assert sc["%fusion.13"] == ("dial.tick", "dial.engine")
    assert sc["%scatter.10"] == ("dial.tick", "dial.engine")
    assert sc["%scatter.14"] == ("dial.segsum",)
    ops = [(0, 2 * S, "%fusion.11 = f32[8]{0} fusion(f32[8]{0} %a)"),
           (2 * S, 3 * S, "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %b)"),
           (3 * S, 4 * S, "%fusion.13 = f32[8]{0} fusion(f32[8]{0} %c)"),
           (4 * S, 5 * S, "%scatter.14 = f32[8]{0} scatter(f32[8]{0} %d)")]
    r = scopes.reduce(ops, [], window=(0, 5 * S), scopes=sc)
    assert r["scope_s"]["dial.segsum"] == pytest.approx(4.0)
    assert r["scope_s"]["dial.demand"] == pytest.approx(3.0)
    assert scopes.readings(r, 20)["segsum_share"] == pytest.approx(0.8)
    # a program that names no dial.segsum (one older than the scope)
    unnamed = scopes.instruction_scopes(
        [REBUILT.replace("/dial.segsum/scatter-add", "/scatter-add")])
    assert unnamed["%fusion.11"] == ("dial.tick", "dial.demand")
    assert unnamed["%scatter.14"] == ()
    r = scopes.reduce(ops, [], window=(0, 5 * S), scopes=unnamed)
    assert "segsum_share" not in scopes.readings(r, 20)


def _ops():
    """A loop around four leaf ops, 10 s of busy time and two idle gaps:
    2-3 s and 8-9 s."""
    return [(0, 12 * S, "%while.9 = (f64[8]{0}) while((f64[8]{0}) %t)"),
            (0, 2 * S, "%fusion.5 = f64[8]{0} fusion(f64[8]{0} %a)"),
            (3 * S, 6 * S, "%fusion.6 = f32[8]{0} fusion(f64[8]{0} %x)"),
            (6 * S, 7 * S, "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %y)"),
            (7 * S, 8 * S, "%copy.8 = f32[8]{0} copy(f32[8]{0} %z)"),
            (9 * S, 12 * S, "%reduce.99 = f32[] reduce(f32[8]{0} %q)")]


def _leaf_ops():
    return _ops()[1:]


def test_scope_seconds_are_leaf_ops_on_each_scope_of_their_path():
    r = scopes.reduce(_leaf_ops(), [], window=(0, 12 * S),
                      scopes=scopes.instruction_scopes([HLO]))
    assert r["busy_s"] == pytest.approx(10.0)
    assert r["scope_s"] == pytest.approx({
        "dial.tick": 2.0, "dial.engine": 2.0, "dial.segsum": 2.0,
        "dial.demand": 0.0, "dial.decide": 4.0, "dial.forest": 1.0})
    # the copy (no scope) and an op the program does not name
    assert r["unscoped_s"] == pytest.approx(4.0)
    assert r["unscoped_share"] == pytest.approx(0.4)
    assert r["unnamed_ops"] == 1
    with_loop = scopes.reduce(_ops(), [], window=(0, 12 * S),
                              scopes=scopes.instruction_scopes([HLO]))
    assert with_loop["scope_s"] == pytest.approx(r["scope_s"])
    assert with_loop["busy_s"] == pytest.approx(12.0)
    assert scopes.reduce([], [], window=(0, S)) is None


def test_program_without_scopes_has_no_scope_seconds():
    r = scopes.reduce(_leaf_ops(), [], window=(0, 12 * S), scopes={})
    assert r["scope_s"] == {}
    assert r["unscoped_share"] == pytest.approx(1.0)
    assert dict(r["idle_gaps"]) == pytest.approx({"none": 2.0})


def test_idle_gap_is_cut_at_span_edges_and_goes_to_innermost_span():
    spans = [(1 * S, 10 * S, "dial.loop.wait"),
             (int(2.5 * S), 3 * S, "dial.inner"),
             (int(8.5 * S), 11 * S, "dial.loop.to_host")]
    outer = [(int(1.5 * S), int(8.75 * S), "bench.FusedLoop.run")]
    r = scopes.reduce(_leaf_ops(), spans, window=(0, 12 * S),
                      scopes=scopes.instruction_scopes([HLO]), outer=outer)
    assert dict(r["idle_gaps"]) == pytest.approx({
        "dial.loop.wait": 1.0, "dial.inner": 0.5, "dial.loop.to_host": 0.5})
    inside = r["idle_within"]["bench.FusedLoop.run"]
    assert inside["idle_s"] == pytest.approx(1.75)
    assert inside["by_span"] == pytest.approx({
        "dial.loop.wait": 1.0, "dial.inner": 0.5, "dial.loop.to_host": 0.25})
    # a gap under no span goes to "none"
    r = scopes.reduce(_leaf_ops(), spans[:1], window=(0, 12 * S))
    assert dict(r["idle_gaps"]) == pytest.approx({"dial.loop.wait": 2.0})
    r = scopes.reduce(_leaf_ops(), [(0, int(2.5 * S), "dial.a")],
                      window=(0, 12 * S))
    assert dict(r["idle_gaps"]) == pytest.approx({"dial.a": 0.5,
                                                  "none": 1.5})


def test_readings_of_a_traced_job_and_none_without_scopes():
    scoped = scopes.reduce(_leaf_ops(), [], window=(0, 12 * S),
                           scopes=scopes.instruction_scopes([HLO]))
    assert scopes.readings(scoped, 20) == pytest.approx({
        "tick_ms_per_interval": 2e3 / 20, "decide_ms_per_interval": 4e3 / 20,
        "segsum_share": 0.2, "forest_share": 0.1, "unscoped_share": 0.4})
    # a trace that dropped events holds no whole number of intervals
    assert scopes.readings(scoped, 20, complete=False) == pytest.approx({
        "segsum_share": 0.2, "forest_share": 0.1, "unscoped_share": 0.4})
    unscoped = scopes.reduce(_leaf_ops(), [], window=(0, 12 * S))
    assert scopes.readings(unscoped, 20) == {"unscoped_share": 1.0}
    assert scopes.readings(None, 20) == {}


def test_setup_compile_reader_sums_the_program_spans_rows(monkeypatch):
    from repro.obs import spans

    compiles = {"trace_s": 1.0, "lower_s": 2.0, "backend_s": 4.0,
                "cache_s": 3.0, "compiles": 2, "cache_loads": 2}
    table = {"spans": {}, "compiles": {"none": compiles,
                                       "dial.loop.dispatch": compiles,
                                       "dial.lab.stack": compiles}}
    monkeypatch.setattr(spans, "totals", lambda: table)
    reader = load_part("metrics", "setup_compile_s")
    # the cache retrieval is inside the backend compile's seconds
    assert reader.read({"compiles_in_window": 0}) == pytest.approx(14.0)
    assert reader.read({"compiles_in_window": 1}) is None
    table["compiles"] = {"none": compiles}
    assert reader.read({"compiles_in_window": 0}) is None


def test_load_spans_reads_program_spans_from_a_profile(tmp_path):
    from repro.obs import spans

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("dial.test.outer"):
            with spans.span("dial.test.inner"):
                jax.numpy.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.other"):
            pass
    got = sorted(scopes.load_spans(str(tmp_path)))
    assert [n for _, _, n in got] == ["dial.test.outer", "dial.test.inner"]
    (s0, e0, _), (s1, e1, _) = got
    assert s0 <= s1 < e1 <= e0


def test_traced_cpu_run_reports_the_program_counters(bench, tiny_deploy):
    """On the CPU the trace has no device ops, so only the program's
    compile table is read."""
    cell = {w["name"]: w for w in bench["workloads"]}[
        "vpic_bdcats_256x16.static"]
    cfg, traffic = tiny_deploy
    traffic = dict(traffic, entry="fused_loop")
    r = run.run_cell(bench, cell, cfg, traffic, 4, 0.1, True,
                     jax.devices(), t_start=0.0)
    m = r["metrics"]
    assert m["setup_compile_s.deploy"]["value"] > 0
    assert "device_ms_per_interval.deploy" not in m


def test_profile_of_a_cpu_job_has_every_span_and_no_device_readings(
        tiny_deploy):
    """``bench/scopes.py`` on one tiny job: the job's span table holds the
    lab's and the fused loop's spans; the CPU's trace has no device ops,
    so there is nothing to reduce."""
    from jobs import Driver
    from policy import as_program_model, make_policy

    cfg, traffic = tiny_deploy
    driver = Driver(cfg, traffic, as_program_model(make_policy()),
                    jax.profiler.TraceAnnotation)
    driver.run(5, -1)
    prof = scopes.profile(driver, 5)
    calls = {n: row["calls"] for n, row in prof["spans"]["spans"].items()}
    assert calls == {n: 1 for n in (
        "dial.lab.stack", "dial.lab.schedule", "dial.lab.loop_cache",
        "dial.loop.shape", "dial.loop.device_put", "dial.loop.dispatch",
        "dial.loop.wait", "dial.loop.to_host", "dial.loop.decode")}
    assert prof["spans"]["compiles"] == {}
    assert prof["scoped"] is None and prof["readings"] == {}
    assert prof["job_s"] > 0 and prof["dropped"] == 0
