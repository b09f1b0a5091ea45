"""Host spans and compile accounting, on the profiler's clock.

The fused loop is one dispatch; what the host does around it (stacking
scenarios, building schedules, putting arrays on the device, enqueueing
the program, waiting for it, copying results back, decoding decisions)
is timed here and nowhere else.

:func:`span` marks a stretch of host work.  It always does two things:

* opens a ``jax.profiler.TraceAnnotation`` of the same name, so that a
  profiled run shows the span on the host plane, on the same clock as
  the device's op line (``jax.profiler.trace``; the span costs about a
  microsecond when no profiler runs);
* adds the span's wall seconds and one call to a process-wide table.

One ``jax.monitoring`` listener, registered on import, charges every
compile event to the innermost open span of the calling thread (or to
``"none"``): programs compiled or loaded, and seconds of tracing,
lowering to MLIR, backend compile and persistent-cache retrieval.  A
backend compile's seconds include the cache retrieval it made, since
JAX times the two around each other.

:func:`totals` returns a copy of both tables; :func:`since` the
difference of two of them, for a stretch of a run.

The program's spans are named ``dial.<layer>.<step>``: ``dial.lab.*``
in :mod:`repro.lab.batch`, ``dial.loop.*`` in
:meth:`repro.pfs.loop_jax.FusedLoop.run`.  The device program's own
layers are named scopes (``jax.named_scope``: ``dial.tick``,
``dial.decide``, ...) and reach a profile through the compiled
program's op metadata, not through this module.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

# jax.monitoring event -> the compile table's column it adds to
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_s",
}
COMPILE_COLUMNS = ("compiles", "cache_loads", "trace_s", "lower_s",
                   "backend_s", "cache_s")
NO_SPAN = "none"

_lock = threading.Lock()
_open = threading.local()          # .stack: names of this thread's spans
_spans: dict = {}                  # name -> {"seconds", "calls"}
_compiles: dict = {}               # span name -> COMPILE_COLUMNS


def _stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


@contextlib.contextmanager
def span(name: str):
    """Times the block as span ``name`` and marks it for the profiler."""
    stack = _stack()
    stack.append(name)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        stack.pop()
        with _lock:
            row = _spans.setdefault(name, {"seconds": 0.0, "calls": 0})
            row["seconds"] += dt
            row["calls"] += 1


def _on_duration(event: str, secs: float, **_):
    column = COMPILE_EVENTS.get(event)
    if column is None:
        return
    stack = getattr(_open, "stack", None)
    owner = stack[-1] if stack else NO_SPAN
    with _lock:
        row = _compiles.setdefault(owner, dict.fromkeys(COMPILE_COLUMNS, 0))
        row[column] += secs
        if column == "backend_s":
            row["compiles"] += 1
        elif column == "cache_s":
            row["cache_loads"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def totals() -> dict:
    """``{"spans": {name: {"seconds", "calls"}}, "compiles": {span:
    {"compiles", "cache_loads", "trace_s", "lower_s", "backend_s",
    "cache_s"}}}``, a copy of the process's tables so far."""
    with _lock:
        return {"spans": {k: dict(v) for k, v in _spans.items()},
                "compiles": {k: dict(v) for k, v in _compiles.items()}}


def since(before: dict, after: dict | None = None) -> dict:
    """``after - before`` of two :func:`totals` (``after`` defaults to
    now); rows that did not change are left out."""
    after = totals() if after is None else after
    out = {}
    for table, rows in after.items():
        diff = {}
        for name, row in rows.items():
            old = before.get(table, {}).get(name, {})
            d = {k: v - old.get(k, 0) for k, v in row.items()}
            if any(d.values()):
                diff[name] = d
        out[table] = diff
    return out
