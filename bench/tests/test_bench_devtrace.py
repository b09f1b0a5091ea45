"""The trace reduction and the window's rate on synthetic and tiny runs."""

import time

import jax
import pytest

import devtrace
import run
from jobs import Driver

S = 1_000_000_000        # ns per second


def _ops(stall=0):
    """Two busy stretches around a host gap; ``stall`` ns of extra idle."""
    return [(0, 2 * S, "fusion.1"), (2 * S, 3 * S, "scatter-add.7"),
            (5 * S + stall, 9 * S + stall, "fusion.1")]


def _spans(stall=0):
    return [(0, 10 * S + stall, "bench.window"),
            (3 * S, 5 * S + stall, "bench.prep"),
            (9 * S + stall, 10 * S + stall, "bench.collect")]


def test_reduce_busy_idle_scatter_and_gaps():
    r = devtrace.reduce(_ops(), _spans(), window=(0, 10 * S),
                        scatter_ops={"scatter-add.7"})
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(7.0)
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["scatter_share"] == pytest.approx(1 / 7)
    assert dict(r["device_ops"]) == pytest.approx({"fusion.1": 6.0,
                                                   "scatter-add.7": 1.0})
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.prep": 2.0,
                                                  "bench.collect": 1.0})


def test_reduce_clips_to_window_and_unions_overlaps():
    ops = [(-S, S, "a"), (S // 2, S, "b"), (3 * S, 5 * S, "a")]
    r = devtrace.reduce(ops, [], window=(0, 4 * S))
    assert r["busy_s"] == pytest.approx(2.0)
    assert dict(r["idle_gaps"]) == pytest.approx({"none": 2.0})
    assert devtrace.reduce([], [], window=(0, S)) is None


HLO = """HloModule m

%fused_computation.3 (p0: f32[8], p1: s32[4]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %scatter.1 = f32[8]{0} scatter(f32[8]{0} %p0, s32[4]{0} %p1), to_apply=%add
}

%fused_computation.4 (p0: f32[8]) -> f32[8] {
  ROOT %multiply.2 = f32[8]{0:T(128)} multiply(f32[8]{0} %p0, f32[8]{0} %p0)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %fusion.5 = f32[8]{0:T(128)} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.3
  %fusion.6 = f32[8]{0:T(128)} fusion(f32[8]{0} %fusion.5), kind=kLoop, calls=%fused_computation.4
  ROOT %scatter-add.7 = f32[8]{0} scatter(f32[8]{0} %fusion.6, s32[4]{0} %i), to_apply=%add
}
"""


def test_scatter_share_counts_leaf_scatters_only():
    sc = devtrace.scatter_instructions(HLO)
    assert sc == {"%fusion.5", "%scatter-add.7", "%scatter.1"}
    ops = [(0, 10 * S, "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t)"),
           (0, 2 * S, "%fusion.5 = f32[8]{0:T(128)} fusion(f32[8]{0} %a)"),
           (2 * S, 6 * S, "%fusion.6 = f32[8]{0:T(128)} fusion(f32[8] %b)"),
           (6 * S, 7 * S, "%scatter-add.7 = f32[8]{0} scatter(f32[8] %c)")]
    r = devtrace.reduce(ops, [], window=(0, 10 * S), scatter_ops=sc)
    assert r["busy_s"] == pytest.approx(10.0)
    assert r["scatter_share"] == pytest.approx(0.3)
    assert [n for n, _ in r["device_ops"]][0].startswith("%fusion.6 = f32[8]")
    assert devtrace.reduce(ops, [], window=(0, 10 * S))["scatter_share"] \
        is None


def test_gap_goes_to_innermost_span():
    spans = _spans() + [(3 * S + 1, 5 * S - 1, "bench.inner")]
    r = devtrace.reduce(_ops(), spans, window=(0, 10 * S))
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.inner": 2.0,
                                                  "bench.collect": 1.0})


def test_stall_raises_idle_share():
    base = devtrace.reduce(_ops(), _spans(), window=(0, 10 * S))
    stalled = devtrace.reduce(_ops(2 * S), _spans(2 * S),
                              window=(0, 12 * S))
    assert stalled["busy_s"] == pytest.approx(base["busy_s"])
    assert stalled["idle_share"] > base["idle_share"]
    assert dict(stalled["idle_gaps"])["bench.prep"] == pytest.approx(4.0)


def test_stall_lowers_rate(bench, tiny_deploy, monkeypatch):
    cell = {w["name"]: w for w in bench["workloads"]}[
        "vpic_bdcats_256x16.dial"]

    def rate():
        r = run.run_cell(bench, cell, *tiny_deploy, 5, 0.5, False,
                         jax.devices(), t_start=0.0)
        return r["metrics"]["if_int_per_s"]["value"]

    fast = rate()
    real = Driver.run

    def stalled(self, seed, index):
        job = real(self, seed, index)
        if index >= 0:
            time.sleep(0.2)
        return job

    monkeypatch.setattr(Driver, "run", stalled)
    assert rate() < 0.8 * fast


def test_traced_window_stops_at_last_op_kept_when_events_dropped():
    ops = [(1 * S, 2 * S, "a"), (3 * S, 4 * S, "b")]
    job = (0, 10 * S, "bench.job")
    assert devtrace.traced_window(ops, job, 0) == (0, 10 * S)
    assert devtrace.traced_window(ops, job, 5) == (0, 4 * S)
    assert devtrace.traced_window([], job, 5) == (0, 10 * S)


def test_host_time_is_job_time_outside_device_calls(bench, tiny_deploy):
    cell = {w["name"]: w for w in bench["workloads"]}[
        "vpic_bdcats_256x16.dial"]
    cfg, traffic = tiny_deploy
    r = run.run_cell(bench, cell, cfg, traffic, 3, 0.1, True,
                     jax.devices(), t_start=0.0)
    assert 0 < r["metrics"]["host_prep_s.deploy"]["value"] < 60
