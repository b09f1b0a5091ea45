"""Reduction of a profiler trace to the benchmark's device numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
returns plain event lists; :func:`reduce` turns them into busy and idle
time, op time by name, the scatter share and the idle gaps attributed to
the benchmark's host spans.  The two are apart so that the arithmetic is
tested on small synthetic lists (``bench/tests/test_devtrace.py``).

Busy time is the union of the intervals in which an op ran on the
device, clipped to the traced window; idle is the rest of the window.
An idle gap is charged to the innermost benchmark span (``bench.*``,
written with ``jax.profiler.TraceAnnotation``) that covers its midpoint,
or to ``"none"``.

The TPU trace names each op by its HLO instruction text.  Loops
(``while``, ``conditional``, ``call``) span the ops they run, so op time
by name and the scatter share count leaf ops only.  A fusion's name does
not say what it fuses: :func:`scatter_instructions` reads that from the
compiled program's HLO text.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9-]*)\(")


def instruction(text: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = f32[8]{0} fusion(...)``."""
    return text.split(" = ", 1)[0].strip()


def opcode(text: str) -> str | None:
    """The HLO opcode of an instruction's text (``fusion``, ``while``)."""
    _, _, rhs = text.partition(" = ")
    m = _OPCODE.search(_LAYOUT.sub("", rhs))
    return m.group(1) if m else None


def short(text: str, width: int = 160) -> str:
    """An op's name without layouts, cut to ``width`` characters."""
    return _LAYOUT.sub("", text)[:width]


def scatter_instructions(hlo_text: str) -> set:
    """Instructions of a compiled HLO module that scatter: scatter ops and
    fusions whose fused computation holds one."""
    comps, cur = {}, None
    calls = []
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1] if line.startswith("ENTRY") else \
                line.split()[0]
            cur = comps.setdefault(name.lstrip("%"), set())
            continue
        m = re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = (.*)$", line)
        if not m or cur is None:
            continue
        op = opcode(line.strip())
        cur.add(op)
        c = re.search(r"calls=%([\w.\-]+)", m.group(2))
        calls.append((m.group(1), op, c.group(1) if c else None))
    return {name for name, op, callee in calls
            if op == "scatter" or (op == "fusion" and callee
                                   and "scatter" in comps.get(callee, ()))}


def load(trace_dir: str):
    """``(device_ops, host_spans, dropped)`` from the newest trace under a
    dir.

    ``device_ops`` are ``(start_ns, end_ns, name)`` of the TPU's XLA op
    line (one per op execution); ``host_spans`` the benchmark's own
    annotations on any host thread, both on the profiler's clock.
    ``dropped`` is the device's ``dropped_traces`` count: once the
    profiler's trace buffers are full it drops the later events, and the
    ops kept are then a complete prefix of the traced stretch.
    """
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return [], [], 0
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, spans, dropped = [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dropped += int(dict(plane.stats).get("dropped_traces", 0) or 0)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return ops, spans, dropped


def traced_window(device_ops, span, dropped: int):
    """The stretch of ``span`` (``(start_ns, end_ns, ...)``) that the
    trace covers whole: all of it, or, where events were dropped, up to
    the end of the last op kept."""
    if not dropped or not device_ops:
        return span[0], span[1]
    return span[0], min(span[1], max(o[1] for o in device_ops))


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(device_ops, host_spans, window=None, scatter_ops=None,
           top: int = 10) -> dict:
    """Device numbers of one traced window (times in seconds).

    ``window`` is ``(start_ns, end_ns)``; by default the extent of the
    ops.  ``scatter_ops`` is the set of
    instruction names that scatter (:func:`scatter_instructions`);
    without it the scatter share is ``None``.  Returns ``None`` when no op
    ran.
    """
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
    busy = _union((s, e) for s, e, _ in clipped)
    busy_ns = sum(e - s for s, e in busy)
    by_name: dict = {}
    for s, e, n in clipped:
        if opcode(n) not in CONTAINERS:
            by_name[n] = by_name.get(n, 0) + (e - s)
    scatter = None
    if scatter_ops is not None:
        scatter = sum(v for n, v in by_name.items()
                      if instruction(n) in scatter_ops) / busy_ns

    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    spans = [s for s in host_spans if s[2] != SPAN_PREFIX + "window"]
    by_span: dict = {}
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [sp for sp in spans if sp[0] <= mid < sp[1]]
        name = (max(covering, key=lambda sp: sp[0])[2] if covering
                else "none")
        by_span[name] = by_span.get(name, 0) + (e - s)

    window_ns = t1 - t0
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "scatter_share": scatter,
        "device_ops": [[short(n), v / 1e9] for n, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v / 1e9] for n, v in
                      sorted(by_span.items(), key=lambda kv: -kv[1])[:top]],
    }
