"""The program's own layers in a profiler trace: ``dial.*`` named scopes
on the device, ``dial.*`` host spans on the host.

    python3 bench/scopes.py --workload <cell> --seed <n>

sets a cell up as ``bench/run.py`` does (the policy, one warm-up job),
profiles the job that a traced run profiles (the window's first, index
0) and prints, on standard error, its device seconds by scope, idle gaps
by span and the program's span table, and, as the last line of standard
output, one JSON object with them and the readings of :func:`readings`.
It runs on the chip only, as the benchmark does.

The fused program marks its layers with ``jax.named_scope``
(``dial.tick``, ``dial.decide``, ``dial.segsum``, ...), which every
compiled instruction carries in ``metadata={op_name="..."}``;
:func:`instruction_scopes` reads that from the compiled HLO text, and
:func:`reduce` charges each leaf op's device time to every ``dial.*``
scope on its path.  The program's host spans (``repro.obs.spans``) are
profiler annotations on the host plane (:func:`load_spans`);
an idle gap is cut where the spans begin and end, and each piece is
charged to the innermost ``dial.*`` span open over it, or to ``"none"``.

A program without scopes or spans (one that predates them) gives empty
maps and no readings.  The arithmetic is tested on synthetic lists
(``bench/tests/test_bench_scopes.py``).
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import shutil
import sys
import tempfile

import devtrace

PREFIX = "dial."
SEGSUM = PREFIX + "segsum"
NONE = "none"
_INSTR = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _common(paths) -> tuple:
    """The scopes that every path in ``paths`` opens with."""
    paths = list(paths)
    if not paths:
        return ()
    common = paths[0]
    for p in paths[1:]:
        n = 0
        while n < min(len(common), len(p)) and common[n] == p[n]:
            n += 1
        common = common[:n]
    return tuple(common)


def scope_path(op_name: str) -> tuple:
    """The ``dial.*`` scopes of an ``op_name``, outermost first.

    An instruction that XLA merged from several (``a;b``) keeps the
    scopes its scoped parts share."""
    paths = []
    for part in op_name.split(";"):
        path = []
        for c in part.split("/"):
            if c.startswith(PREFIX) and (not path or path[-1] != c):
                path.append(c)
        if path:
            paths.append(tuple(path))
    return _common(paths)


def instruction_scopes(hlo_texts) -> dict:
    """``{instruction: scope path}`` over compiled HLO modules.

    An instruction without metadata of its own (a fusion, or a copy the
    compiler added) takes the scopes shared by the instructions of the
    computation it calls, else by those of the computation it is in.

    A scatter is a segment reduction: ``make_segment_sum`` is the
    program's only source of them (``tests/test_scopes.py``).  The TPU
    compiler rebuilds some of them without metadata, so a scatter with
    no ``op_name``, or one under ``dial.segsum``, charges itself and any
    instruction that calls its computation (its fusion) to
    ``dial.segsum`` too, beneath the scopes they share.  That holds only
    in a program that names ``dial.segsum`` somewhere: in one without
    the scope, a scatter is charged to nothing."""
    own, comps, found, segsum = {}, {}, [], set()
    for text in hlo_texts:
        comp = None
        for line in text.splitlines():
            if line.endswith("{") and not line.startswith(" "):
                comp = (line.split()[1] if line.startswith("ENTRY")
                        else line.split()[0]).lstrip("%")
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            name, rhs = m.groups()
            op = _OP_NAME.search(rhs)
            callee = _CALLS.search(rhs)
            scatter = devtrace.opcode(line.strip()) == "scatter"
            found.append((name, comp, callee and callee.group(1), scatter))
            if op is not None:
                own[name] = scope_path(op.group(1))
                comps.setdefault(comp, []).append(own[name])
            if scatter and SEGSUM in own.get(name, (SEGSUM,)):
                segsum.add(comp)
    shared = {c: _common(paths) for c, paths in comps.items()}
    named = any(SEGSUM in path for path in own.values())
    out = {}
    for name, comp, callee, scatter in found:
        path = (own[name] if name in own else
                shared.get(callee, shared.get(comp, ())))
        if named and SEGSUM not in path and (
                callee in segsum or (scatter and name not in own)):
            path += (SEGSUM,)
        out[name] = path
    return out


def load_spans(trace_dir: str) -> list:
    """``(start_ns, end_ns, name)`` of every ``dial.*`` host span in the
    newest trace under a dir."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def _innermost(spans) -> list:
    """The timeline cut at every span boundary: sorted ``(start, end,
    name)`` pieces, each named by the innermost span open over it (the
    latest to start), pieces under no span left out."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        covering = [sp for sp in spans if sp[0] <= mid < sp[1]]
        if covering:
            out.append((a, b, max(covering,
                                  key=lambda sp: (sp[0], -sp[1]))[2]))
    return out


def _charge(gaps, pieces) -> dict:
    """Seconds of ``gaps`` under each piece's name, the rest to
    ``"none"``."""
    starts = [p[0] for p in pieces]
    out: dict = {}
    for s, e in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(pieces) and pieces[i][0] < e:
            a, b, name = pieces[i]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0) + d / 1e9
                covered += d
            i += 1
        if e - s > covered:
            out[NONE] = out.get(NONE, 0) + (e - s - covered) / 1e9
    return out


def _within(gaps, spans):
    """The parts of ``gaps`` that lie inside any of ``spans``."""
    out = []
    for a, b in devtrace._union((s, e) for s, e, _ in spans):
        out.extend((max(s, a), min(e, b)) for s, e in gaps
                   if e > a and s < b)
    return out


def reduce(device_ops, spans, window=None, scopes=None, outer=()) -> dict:
    """Scope and span numbers of one traced window (times in seconds).

    ``device_ops`` and ``window`` are as :func:`devtrace.reduce` takes
    them; ``spans`` the ``dial.*`` host spans (:func:`load_spans`);
    ``scopes`` the map of :func:`instruction_scopes`.  ``outer`` are
    host spans (``(start_ns, end_ns, name)``) whose idle time is broken
    down by ``dial.*`` span apart, such as the benchmark's marks of the
    program's device calls.  Returns ``None`` when no op ran.

    ``scope_s`` holds every scope the program has, with the device
    seconds of the leaf ops on its path (nested scopes count in each);
    ``unscoped_s`` the leaf ops under none, ops the program does not
    name among them.
    """
    scopes = scopes or {}
    if window is None:
        if not device_ops:
            return None
        window = (min(o[0] for o in device_ops),
                  max(o[1] for o in device_ops))
    t0, t1 = window
    clipped = [(max(s, t0), min(e, t1), n) for s, e, n in device_ops
               if e > t0 and s < t1]
    if not clipped:
        return None
    busy = devtrace._union((s, e) for s, e, _ in clipped)
    busy_ns = sum(e - s for s, e in busy)

    scope_ns = {c: 0 for path in scopes.values() for c in path}
    unscoped_ns, unnamed = 0, set()
    for s, e, n in clipped:
        if devtrace.opcode(n) in devtrace.CONTAINERS:
            continue
        name = devtrace.instruction(n)
        if name not in scopes:
            unnamed.add(name)
        path = scopes.get(name, ())
        for c in path:
            scope_ns[c] += e - s
        if not path:
            unscoped_ns += e - s

    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    pieces = _innermost(spans)
    idle = _charge(gaps, pieces)
    within = {}
    for name in sorted({sp[2] for sp in outer}):
        inside = _within(gaps, [sp for sp in outer if sp[2] == name])
        within[name] = {"idle_s": sum(e - s for s, e in inside) / 1e9,
                        "by_span": _charge(inside, pieces)}
    return {
        "busy_s": busy_ns / 1e9,
        "scope_s": {c: v / 1e9 for c, v in sorted(scope_ns.items())},
        "unscoped_s": unscoped_ns / 1e9,
        "unscoped_share": unscoped_ns / busy_ns,
        "unnamed_ops": len(unnamed),
        "idle_gaps": sorted(([n, v] for n, v in idle.items()),
                            key=lambda kv: -kv[1]),
        "idle_within": within,
    }



# scope -> what the traced job reads of it: ms an interval, or a share of
# busy time
PER_INTERVAL = {PREFIX + "tick": "tick_ms_per_interval",
                PREFIX + "decide": "decide_ms_per_interval"}
SHARE = {SEGSUM: "segsum_share", PREFIX + "forest": "forest_share"}


def readings(scoped, intervals: int, complete: bool = True) -> dict:
    """Per-layer readings of one traced job (:func:`reduce`): device
    milliseconds an interval under ``dial.tick`` and ``dial.decide``,
    where the trace kept every op, and the shares of busy time under
    ``dial.segsum`` and ``dial.forest``, for the scopes the program
    has; the unscoped share always."""
    if scoped is None:
        return {}
    s = scoped["scope_s"]
    out = {"unscoped_share": scoped["unscoped_share"]}
    if complete:
        out.update({name: s[c] * 1e3 / intervals
                    for c, name in PER_INTERVAL.items() if c in s})
    out.update({name: s[c] / scoped["busy_s"]
                for c, name in SHARE.items() if c in s})
    return out


def profile(driver, seed: int, index: int = 0) -> dict:
    """Job ``index`` of ``driver``'s cell, traced whole: the program's span
    table over the job (``spans.since``), and :func:`reduce` of its
    trace (``None`` where no device op ran), with the idle time inside
    the traffic's ``device_calls`` broken down by ``dial.*`` span."""
    from repro.obs import spans

    trace_dir = tempfile.mkdtemp(prefix="scopes_trace_")
    try:
        driver.profile_dir = trace_dir
        before = spans.totals()
        with driver.host_spans():
            driver.run(seed, index)
        table = spans.since(before)
        ops, host_spans, dropped = devtrace.load(trace_dir)
        dial_spans = load_spans(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    traced = sorted(s for s in host_spans if s[2] == "bench.job")
    window = (devtrace.traced_window(ops, traced[0], dropped)
              if traced else None)
    calls = {"bench." + p.partition(":")[2]
             for p in driver.traffic.get("device_calls", ())}
    scoped = reduce(ops, dial_spans, window=window,
                    scopes=instruction_scopes(driver.hlo_texts()),
                    outer=[s for s in host_spans if s[2] in calls])
    return {"spans": table, "scoped": scoped, "dropped": dropped,
            "job_s": (traced[0][1] - traced[0][0]) / 1e9 if traced
            else None,
            "readings": readings(scoped, driver.n_int, not dropped)}


def report(prof: dict, setup: dict, out=sys.stderr):
    """A profile's scopes, idle gaps and span table, and the compile
    table at the end of set-up, one line each."""
    scoped = prof["scoped"]
    if scoped is not None:
        busy = scoped["busy_s"]
        for name, v in scoped["scope_s"].items():
            print(f"scope {name}: {v!r} s, {v / busy!r} of busy time",
                  file=out)
        print(f"scope (none): {scoped['unscoped_s']!r} s, "
              f"{scoped['unscoped_share']!r} of busy time "
              f"({scoped['unnamed_ops']} traced ops not in the program)",
              file=out)
        for name, v in scoped["idle_gaps"]:
            print(f"idle under {name}: {v!r} s", file=out)
        for outer, row in scoped["idle_within"].items():
            loop = sum(v for n, v in row["by_span"].items()
                       if n.startswith(PREFIX + "loop."))
            print(f"idle inside {outer}: {row['idle_s']!r} s, {loop!r} s "
                  f"of it under {PREFIX}loop.* spans: "
                  f"{sorted(row['by_span'].items())}", file=out)
    for name, row in sorted(prof["spans"]["spans"].items()):
        print(f"span {name}: {row['calls']} calls, {row['seconds']!r} s",
              file=out)
    for name, row in sorted(setup["compiles"].items()):
        print(f"set-up compiles under {name}: {row}", file=out)
    print(f"job: {prof['job_s']!r} s traced, {prof['dropped']} trace "
          f"buffers dropped", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import run

    try:
        _, cell, config, traffic = run.load_cell(args.workload)
        run.require_device(int(cell["chips"]))
    except (run.BenchError, OSError) as e:
        print(f"scopes: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.enable_compile_cache()
    import jax

    from jobs import Driver
    from policy import as_program_model, make_policy
    from repro.obs import spans

    driver = Driver(config, traffic, as_program_model(make_policy()),
                    jax.profiler.TraceAnnotation)
    driver.run(args.seed, -1)               # warm-up: every program, once
    setup = spans.totals()
    prof = profile(driver, args.seed)
    report(prof, setup)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "readings": prof["readings"], "job_s": prof["job_s"],
                      "scoped": prof["scoped"], "spans": prof["spans"],
                      "setup_compiles": setup["compiles"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
