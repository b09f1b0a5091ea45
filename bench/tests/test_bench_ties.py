"""Rounding ties: where the reference may take the program's choice, and
where it may not.  At a tiny size on the CPU.

The reference follows the program only at a near tie that float rounding
can make: a candidate's score just below the best, a gate's value just
off its boundary.  An exact tie is decided by the rule itself (the first
maximum, ``ratio <= 2``), so a program that breaks it the other way is
not correct.
"""

import dataclasses

import jax
import numpy as np
import pytest

import check
import policy as bench_policy
import reference as ref
import run
from jobs import Element
from repro.pfs import loop_jax

W_LOW, W_HIGH = -4.0, 4.0
MAKE_POLICY = bench_policy.make_policy


def tie_policy():
    """Forests under which a writer's only candidates above tau are
    (16, 32) and (1024, 1), with the same margin: their write scores tie
    exactly, and the first maximum is (16, 32)."""
    pol = MAKE_POLICY()
    out = {}
    for op, f in pol.items():
        nf = f["n_features"]
        win, rif = nf - 4, nf - 3           # the candidate's log2 knobs
        feature = np.array([[win, win, rif, rif, win, win, win]],
                           dtype=np.int32)
        threshold = np.array([[9.0, 5.0, 0.5, 4.5, 1e30, 1e30, 1e30]],
                             dtype=np.float32)
        leaf = np.array([[W_LOW, W_HIGH, W_LOW, W_LOW,
                          W_HIGH, W_HIGH, W_LOW, W_LOW]], dtype=np.float32)
        out[op] = dict(f, feature=feature, threshold=threshold, leaf=leaf,
                       base=np.float32(0.0), depth=3)
    return out


def test_tie_policy_ties_exactly():
    pol = tie_policy()
    X = np.zeros((24, pol["write"]["n_features"]), dtype=np.float32)
    X[:, -4:-2] = ref.THETA_FEATS
    p = ref.forest_proba(pol["write"], X)
    kept = [tuple(int(v) for v in t) for t in ref.THETAS[p > ref.TAU]]
    assert kept == [(16, 32), (1024, 1)]
    theta, _, followed = ref.algorithm1(
        p[None], np.array([ref.WRITE]), np.array([[256, 8]]),
        follow=[(1024, 1)])
    assert tuple(theta[0]) == (16, 32) and followed == 0


def _run(bench, tiny_deploy, monkeypatch):
    monkeypatch.setattr(bench_policy, "make_policy", tie_policy)
    cell = {w["name"]: w for w in bench["workloads"]}[
        "vpic_bdcats_256x16.dial"]
    return run.run_cell(bench, cell, *tiny_deploy, 2**31 + 91, 0.1, False,
                        jax.devices(), t_start=0.0)


def test_exact_tie_first_maximum_is_correct(bench, tiny_deploy,
                                            monkeypatch):
    res = _run(bench, tiny_deploy, monkeypatch)
    assert res["correct"]
    assert res["checks"]["ties_followed"]["value"] == 0


def test_exact_tie_last_maximum_fails(bench, tiny_deploy, monkeypatch):
    real = loop_jax.score_greedy_arrays

    def last_max(probs, ops, current, thetas, params, xp=np):
        return real(probs[:, ::-1], ops, current, thetas[::-1], params,
                    xp=xp)

    monkeypatch.setattr(loop_jax, "score_greedy_arrays", last_max)
    res = _run(bench, tiny_deploy, monkeypatch)
    assert not res["correct"]
    assert res["checks"]["theta_mismatch"]["value"] > 0


# ---------------------------------------------------------------------- #
# the ratio gate, with the reference put in the program's place
# ---------------------------------------------------------------------- #
AT, OSC = 4, 0          # the interval and the writer interface forced


def forced_snapshot(factor):
    """``reference.snapshot`` with interface ``OSC``'s write volume in
    interval ``AT`` set to ``factor`` times the interval before."""
    real, calls = ref.snapshot, []

    def snap(prev, cur):
        r, w, vr, vw = real(prev, cur)
        if len(calls) == AT:
            vw = vw.copy()
            vw[OSC] = factor * calls[-1]
        calls.append(vw[OSC])
        return r, w, vr, vw
    return snap


def _element(res: ref.Result, spec) -> Element:
    return Element(spec.name, 0, tuple(spec.initial_theta), True,
                   {f: getattr(res.state, f) for f in ref.COUNTERS},
                   res.decisions)


@pytest.mark.parametrize("program_gate,program_factor,ref_factor,agree", [
    ("<=", 2.0, 2.0, True),            # exact boundary, the gate's rule
    ("<", 2.0, 2.0, False),            # exact boundary broken the other way
    ("<=", 2.0, 2.0 * (1 + 1e-12), True),   # rounding: followed
    ("<=", 2.0, 2.0 * (1 + 1e-6), False),   # past rounding: not followed
    # the reference on the boundary, the program a rounding past it: the
    # gate's rule decides, and the program is not correct (the chip's
    # float64 did this in a catalog sweep, PERF.md)
    ("<=", 2.0 * (1 + 1e-12), 2.0, False),
])
def test_ratio_gate_boundary(monkeypatch, program_gate, program_factor,
                             ref_factor, agree):
    spec = ref.vpic_bdcats_deployment(8, 8, 8)
    policy = bench_policy.make_policy()
    kw = dict(seconds=3.0, interval=0.5)
    with monkeypatch.context() as m:
        m.setattr(ref, "snapshot", forced_snapshot(program_factor))
        if program_gate == "<":
            m.setattr(ref, "steady", lambda r: (r >= 0.5) & (r < 2.0))
        got = _element(ref.run(spec, policy, **kw), spec)
    assert any(OSC in d[0] for d in got.decisions[AT:AT + 1]) == (
        program_gate == "<=" and program_factor <= 2.0)
    monkeypatch.setattr(ref, "snapshot", forced_snapshot(ref_factor))
    want = ref.run(spec, policy, follow=got.decisions, **kw)
    nums = check.compare_element(got, want)
    assert check.agrees(nums) == agree
    assert nums["ties_followed"] == (1 if ref_factor != program_factor
                                     and agree else 0)


def test_too_many_ties_followed_fail(tiny_deploy, monkeypatch):
    cfg, traffic = tiny_deploy
    e = Element("x", 0, (256, 8), False, {}, None)
    job = dataclasses.make_dataclass("J", ["index", "elements"])(0, [e])
    nums = {"theta_mismatch": 0, "decisions": 0, "knob_changes": 0,
            "ties_followed": check.LIMITS["ties_followed"] + 1,
            "counter_rel": 0.0, "mbs_rel": 0.0}
    monkeypatch.setattr(check, "run_reference", lambda *a: None)
    monkeypatch.setattr(check, "compare_element", lambda *a: nums)
    total, failed = check.check([job], cfg, traffic, {},
                                np.random.default_rng(0))
    assert total["ties_followed"] > check.LIMITS["ties_followed"]
    assert failed == [0]
