"""Lightweight host-phase timers and run provenance.

The fused loop is a single dispatch — a 131k-interface run is one
opaque ``jit`` call from the host's point of view.  :class:`PhaseTimers`
gives the host side back its phase breakdown at near-zero cost
(``perf_counter`` pairs around device_put / dispatch / host transfer),
and :func:`compile_execute_split` separates compile from execute for a
jitted callable via AOT lowering — the number an operator actually
wants when a "slow run" might just be a cold cache.

:func:`collect_provenance` stamps bench records with what produced
them (git SHA, platform, device kind/count, jax version) so checked-in
baselines like ``BENCH_8.json`` stay attributable.
"""

from __future__ import annotations

import collections
import contextlib
import platform
import subprocess
import time


class PhaseTimers:
    """Accumulate named wall-clock phases; ~100 ns per measurement."""

    def __init__(self):
        self.seconds = collections.defaultdict(float)
        self.calls = collections.defaultdict(int)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] += time.perf_counter() - t0
            self.calls[name] += 1

    def add(self, name: str, seconds: float) -> None:
        self.seconds[name] += seconds
        self.calls[name] += 1

    def reset(self) -> None:
        self.seconds.clear()
        self.calls.clear()

    def summary(self) -> dict:
        """``{phase: {"seconds": total, "calls": n}}``, insertion order."""
        return {k: {"seconds": self.seconds[k], "calls": self.calls[k]}
                for k in self.seconds}


def compile_execute_split(jit_fn, *args, **kwargs) -> dict:
    """Compile-vs-execute wall split for one jitted callable.

    AOT-lowers and compiles ``jit_fn`` for ``args``, then times one
    execution of the compiled object (blocking on the result).  Returns
    ``{"compile_s", "execute_s", "out"}``.  Falls back to timing a
    single traced call as pure execute when the callable does not
    support ``.lower`` (e.g. a plain function).
    """
    lower = getattr(jit_fn, "lower", None)
    if lower is None:
        t0 = time.perf_counter()
        out = jit_fn(*args, **kwargs)
        return {"compile_s": 0.0,
                "execute_s": time.perf_counter() - t0, "out": out}
    t0 = time.perf_counter()
    compiled = lower(*args, **kwargs).compile()
    t1 = time.perf_counter()
    out = compiled(*args, **kwargs)
    import jax
    out = jax.block_until_ready(out)
    t2 = time.perf_counter()
    return {"compile_s": t1 - t0, "execute_s": t2 - t1, "out": out}


def collect_provenance() -> dict:
    """Git/platform/device metadata for bench records (best effort —
    every field degrades to a placeholder rather than raising)."""
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    try:
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, timeout=10).stdout.strip())
    except (OSError, subprocess.SubprocessError):
        dirty = False
    prov = {
        "git_sha": sha,
        "git_dirty": dirty,
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    # a record must name the device it ran on: a failed JAX or device
    # init raises here instead of writing a placeholder
    import jax

    from repro.lab.batch import loop_cache_stats

    devs = jax.devices()
    prov["jax_version"] = jax.__version__
    prov["device_count"] = len(devs)
    prov["device_kind"] = devs[0].device_kind
    prov["default_backend"] = jax.default_backend()
    prov["loop_cache"] = loop_cache_stats()
    return prov
