"""Shared segment-sum helper: the engine's one reduction primitive.

Every per-tick aggregation in the PFS engine (six historical
``np.bincount`` call sites across formation/drain/bandwidth plus the
workload stripe scatter) routes through :func:`segment_sum`, so one
dispatch decides the execution strategy for the whole tick:

==========  ============================================================
backend      implementation
==========  ============================================================
``numpy``    ``np.bincount(..., weights=...)`` (the oracle)
``jax``      ``jax.ops.segment_sum`` (XLA scatter-add)
``pallas``   one-hot-matmul Pallas kernel (MXU, no scatter; float32)
``auto``     jax, on every platform
==========  ============================================================

``auto`` picks XLA's scatter-add on the TPU too.  The engine runs under
x64, and a Mosaic call cannot take float64 operands, so the kernel casts
to float32 around the call.  Its sums then miss the engine's oracle: on
a TPU v5e a DIAL-tuned vpic_checkpoint ends 6.9e-4 off the numpy
oracle's MB/s and bdcats_analysis decides differently, where the
scatter-add agrees to 1e-14.  The kernel stays selectable by name for
kernel-level measurements.
"""

from __future__ import annotations

import functools

import numpy as np

# NOTE: the jax/pallas implementations are imported lazily inside
# make_segment_sum so that the numpy oracle (and everything that imports
# it, e.g. repro.pfs.workloads) stays importable without jax.


def segment_sum_np(values, segment_ids, num_segments: int):
    """Numpy oracle: ``np.bincount`` with weights."""
    return np.bincount(segment_ids, weights=np.asarray(values, dtype=float),
                       minlength=num_segments)


def make_segment_sum(backend: str = "auto"):
    """Return ``segment_sum(values, segment_ids, num_segments)`` for a
    backend name; the returned callable is safe to close over under jit."""
    if backend == "numpy":
        return segment_sum_np
    if backend in ("auto", "jax"):
        from repro.kernels.segment_reduce import ref as _ref
        return _ref.segment_sum_ref
    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels.segment_reduce import kernel as _kernel
        interpret = backend == "pallas_interpret"
        return functools.partial(_kernel.segment_sum, interpret=interpret)
    raise ValueError(f"unknown segment_sum backend {backend!r}")


def segment_sum(values, segment_ids, num_segments: int,
                backend: str = "auto"):
    """One-call convenience over :func:`make_segment_sum`."""
    return make_segment_sum(backend)(values, segment_ids, num_segments)
