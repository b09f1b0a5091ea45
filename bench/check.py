"""The comparison that decides ``correct``.

Each checked element of a finished job is simulated again by the plain
reference (``reference.py``) from its configuration scenario and jitter
seed, and compared with what the timed run produced:

``theta_mismatch``  decision rows (interval, interface) whose decided
                    set, chosen θ or changed flag differ — exact;
``decisions``       decision rows in the checked DIAL-tuned elements —
                    at least one, so a θ comparison is never vacuous (the
                    cell's shape fixes it: every active, steady interface
                    decides from the fourth interval on).  Whether a
                    decision *changes* a knob is the policy's business
                    and is printed, never compared;
``counter_rel``     the widest gap of any cumulative counter, relative to
                    that counter's largest reference value in its element;
``mbs_rel``         the widest relative gap of an element's simulated
                    MB/s;
``ties_followed``   decisions in which the reference took the program's
                    choice at a rounding tie (``reference.TIE_RTOL``,
                    ``reference.GATE_RTOL``), over the whole check: a
                    cap on how far the reference defers to the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import reference as ref
from jobs import reference_bases

# limits, each set between the readings of sound runs and of the
# control (see PERF.md, "How correct is decided")
LIMITS = {"theta_mismatch": 0, "decisions": 1,
          "counter_rel": 3e-8, "mbs_rel": 2e-9, "ties_followed": 6}


def compare_element(got, want: ref.Result) -> dict:
    """Numbers for one element against its reference run."""
    out = {"theta_mismatch": 0, "decisions": 0, "knob_changes": 0,
           "ties_followed": want.ties_followed}
    if got.decisions is not None:
        if len(got.decisions) != len(want.decisions):
            out["theta_mismatch"] = max(
                1, sum(len(w[0]) for w in want.decisions))
        for (go, gt, gc), (wo, wt, wc) in zip(got.decisions,
                                               want.decisions):
            out["decisions"] += len(wo)
            out["knob_changes"] += int(np.sum(wc))
            if len(go) != len(wo) or not np.array_equal(go, wo):
                out["theta_mismatch"] += len(set(go.tolist())
                                             ^ set(wo.tolist())) or len(wo)
                continue
            out["theta_mismatch"] += int(np.sum(
                np.any(np.asarray(gt) != wt, axis=1)
                | (np.asarray(gc) != wc)))
    worst = 0.0
    for f in ref.COUNTERS:
        g = np.asarray(got.counters[f], dtype=np.float64)
        w = np.asarray(getattr(want.state, f), dtype=np.float64)
        if g.shape != w.shape:
            worst = np.inf
            break
        scale = max(float(np.max(np.abs(w))) if w.size else 0.0, 1e-9)
        worst = max(worst, float(np.max(np.abs(g - w))) / scale
                    if w.size else 0.0)
    out["counter_rel"] = worst
    g_mbs = float(np.sum(got.counters["ctr_bytes_done"]) / want.seconds
                  / 1e6)
    w_mbs = want.mbs()
    out["mbs_rel"] = abs(g_mbs - w_mbs) / max(abs(w_mbs), 1e-300)
    return out


def select(jobs, traffic: dict, rng) -> list:
    """The elements the reference re-runs: every element of a sample of
    the window's jobs drawn from the seed; in a policy sweep, the DIAL arm
    of every scenario and a seeded draw of the static arms."""
    n = min(int(traffic.get("check_jobs", 1)), len(jobs))
    out = []
    for j in sorted(rng.choice(len(jobs), size=n, replace=False).tolist()):
        elems = jobs[j].elements
        if "check_static_arms" in traffic:
            static = [e for e in elems if not e.tuned]
            pick = rng.choice(len(static), replace=False,
                              size=int(traffic["check_static_arms"]))
            elems = ([e for e in elems if e.tuned]
                     + [static[i] for i in sorted(pick)])
        out += [(jobs[j].index, e) for e in elems]
    return out


def run_reference(element, config: dict, traffic: dict, policy: dict):
    base = reference_bases(config)[element.base]
    spec = dataclasses.replace(ref.variant(base, element.seed),
                               initial_theta=tuple(element.theta0))
    return ref.run(spec, policy if element.tuned else None,
                   seconds=float(traffic["sim_seconds"]),
                   interval=float(traffic["interval"]),
                   follow=element.decisions)


def check(jobs, config: dict, traffic: dict, policy: dict, rng,
          limits: dict = LIMITS) -> tuple[dict, list]:
    """Re-run the sampled elements and fold their numbers.

    Returns ``(numbers, failed)``: the worst value of each compared number
    (plus ``knob_changes``, ``elements`` and ``any_tuned`` for the record)
    and the indices of the jobs found wrong.  Checked DIAL-tuned elements
    without a single decision make every checked job wrong: their θ
    comparison would be vacuous.  So do more rounding ties followed than
    the limit allows.
    """
    total = {"theta_mismatch": 0, "decisions": 0, "knob_changes": 0,
             "ties_followed": 0, "counter_rel": 0.0, "mbs_rel": 0.0,
             "elements": 0, "any_tuned": False}
    failed, checked = set(), set()
    for index, e in select(jobs, traffic, rng):
        nums = compare_element(e, run_reference(e, config, traffic, policy))
        checked.add(index)
        total["elements"] += 1
        total["any_tuned"] |= e.tuned
        for k in ("theta_mismatch", "decisions", "knob_changes",
                  "ties_followed"):
            total[k] += nums[k]
        for k in ("counter_rel", "mbs_rel"):
            total[k] = max(total[k], nums[k])
        if not agrees(nums, limits):
            failed.add(index)
    if total["any_tuned"] and total["decisions"] < limits["decisions"]:
        failed |= checked
    if total["ties_followed"] > limits["ties_followed"]:
        failed |= checked
    return total, sorted(failed)


def agrees(nums: dict, limits: dict = LIMITS) -> bool:
    """θ exact, counters and MB/s within their limits."""
    return (nums["theta_mismatch"] <= limits["theta_mismatch"]
            and nums["counter_rel"] <= limits["counter_rel"]
            and nums["mbs_rel"] <= limits["mbs_rel"])


def lines(nums: dict, limits: dict = LIMITS) -> dict:
    """Each compared number beside its limit, for the result line."""
    out = {k: {"value": nums[k], "limit": limits[k]}
           for k in ("theta_mismatch", "counter_rel", "mbs_rel",
                     "ties_followed")}
    if nums["any_tuned"]:
        out["decisions"] = {"value": nums["decisions"],
                            "limit": limits["decisions"]}
    return out
