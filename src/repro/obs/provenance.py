"""Run provenance for bench records.

:func:`collect_provenance` stamps bench records with what produced
them (git SHA, platform, device kind/count, jax version) so checked-in
baselines like ``BENCH_8.json`` stay attributable.
"""

from __future__ import annotations

import platform
import subprocess


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
