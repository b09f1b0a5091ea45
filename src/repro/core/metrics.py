"""Designed client-side local metrics (paper SIII-A step 1 and SIII-B).

Two consecutive raw probes of one OSC interface (simulated
``/proc/fs/lustre`` counters, :mod:`repro.pfs.stats`) are differenced into
one *interval snapshot* ``s_t`` — the "designed metrics ... extracted from
raw system statistics".  All metrics are strictly client-local.

Read and write snapshots are separate vectors with op-specific members
(grant/dirty/block for writes, readahead hits for reads), because Lustre
handles the two paths differently (SIII-B) and DIAL trains separate
models per op.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.pfs.engine import PAGE_SIZE, READ, WRITE
from repro.pfs.stats import FleetStats, OSCStats

# Ordered feature names for each op's snapshot vector.  Keep stable: the
# GBDT models and the Pallas inference kernel index by position.
READ_FEATURES = (
    "throughput_mbs",      # app-visible read MB/s this interval
    "rpc_rate",            # RPCs sent per second
    "avg_pages_per_rpc",   # mean formed-RPC size in pages
    "partial_rpc_frac",    # fraction of RPCs dispatched below the window
    "avg_rpc_latency_ms",  # mean sojourn of completed RPCs
    "avg_pending_mb",      # time-avg bytes waiting for a slot
    "avg_active_rpcs",     # time-avg RPCs in flight
    "slot_utilization",    # avg_active / rpcs_in_flight knob
    "req_rate",            # app requests per second
    "avg_req_kb",          # mean app request size
    "randomness",          # client-side offset-jump estimate [0,1]
    "cache_hit_rate",      # readahead-covered fraction of request bytes
    "window_pages_log2",   # knob in effect during the interval
    "rpcs_in_flight_log2",
)

WRITE_FEATURES = (
    "throughput_mbs",
    "rpc_rate",
    "avg_pages_per_rpc",
    "partial_rpc_frac",
    "avg_rpc_latency_ms",
    "avg_pending_mb",
    "avg_active_rpcs",
    "slot_utilization",
    "req_rate",
    "avg_req_kb",
    "randomness",
    "block_frac",          # fraction of interval the app sat grant-blocked
    "avg_dirty_mb",        # time-avg dirty cache occupancy
    "avg_grant_mb",        # time-avg grant consumption
    "window_pages_log2",
    "rpcs_in_flight_log2",
)

N_READ = len(READ_FEATURES)
N_WRITE = len(WRITE_FEATURES)


@dataclasses.dataclass
class Snapshot:
    """One interval's designed metrics for one OSC interface."""

    t: float
    dt: float
    read: np.ndarray          # (N_READ,)
    write: np.ndarray         # (N_WRITE,)
    read_volume: float        # bytes moved (model-selection signal)
    write_volume: float


def _safe_div(a: float, b: float) -> float:
    return a / b if b > 0 else 0.0


def snapshot(prev: OSCStats, cur: OSCStats) -> Snapshot:
    """Difference two consecutive probes into the designed metrics."""
    dt = max(cur.t - prev.t, 1e-9)

    def common(op: int) -> list[float]:
        d_bytes = float(cur.bytes_done[op] - prev.bytes_done[op])
        d_rpcs = float(cur.rpcs_sent[op] - prev.rpcs_sent[op])
        d_rpc_bytes = float(cur.rpc_bytes[op] - prev.rpc_bytes[op])
        d_partial = float(cur.partial_rpcs[op] - prev.partial_rpcs[op])
        d_done = float(cur.rpcs_done[op] - prev.rpcs_done[op])
        d_lat = float(cur.latency_sum[op] - prev.latency_sum[op])
        d_reqs = float(cur.req_count[op] - prev.req_count[op])
        d_req_bytes = float(cur.req_bytes[op] - prev.req_bytes[op])
        d_pend = float(cur.pending_integral[op] - prev.pending_integral[op])
        d_act = float(cur.active_integral[op] - prev.active_integral[op])
        return [
            d_bytes / dt / 1e6,
            d_rpcs / dt,
            _safe_div(d_rpc_bytes, d_rpcs) / PAGE_SIZE,
            _safe_div(d_partial, d_rpcs),
            _safe_div(d_lat, d_done) * 1e3,
            d_pend / dt / 2**20,
            d_act / dt,
            _safe_div(d_act / dt, cur.rpcs_in_flight),
            d_reqs / dt,
            _safe_div(d_req_bytes, d_reqs) / 1024.0,
            float(cur.randomness[op]),
        ]

    knobs = [np.log2(cur.window_pages), np.log2(cur.rpcs_in_flight)]

    r = common(READ)
    d_req_bytes_r = float(cur.req_bytes[READ] - prev.req_bytes[READ])
    d_hit = float(cur.cache_hit_bytes - prev.cache_hit_bytes)
    r.append(_safe_div(d_hit, d_req_bytes_r))
    read_vec = np.array(r + knobs)

    w = common(WRITE)
    w.append(float(cur.block_time - prev.block_time) / dt)
    w.append(float(cur.dirty_integral - prev.dirty_integral) / dt / 2**20)
    w.append(float(cur.grant_integral - prev.grant_integral) / dt / 2**20)
    write_vec = np.array(w + knobs)

    return Snapshot(
        t=cur.t,
        dt=dt,
        read=read_vec,
        write=write_vec,
        read_volume=float(cur.bytes_done[READ] - prev.bytes_done[READ]),
        write_volume=float(cur.bytes_done[WRITE] - prev.bytes_done[WRITE]),
    )


# positions of the knob features inside each op's snapshot vector
READ_KNOB_IDX = (READ_FEATURES.index("window_pages_log2"),
                 READ_FEATURES.index("rpcs_in_flight_log2"))
WRITE_KNOB_IDX = (WRITE_FEATURES.index("window_pages_log2"),
                  WRITE_FEATURES.index("rpcs_in_flight_log2"))


def feature_vector(history: list[Snapshot], op: int,
                   theta_feat: np.ndarray) -> np.ndarray:
    """Assemble the model input ``(theta, H_t)`` (paper SIII-B, k=1).

    ``history`` is ``[s_{t-k}, ..., s_t]``; vectors concatenate oldest to
    newest, then the candidate theta's log2 features, then the *delta*
    between candidate and currently-applied theta.  The deltas are part of
    the "designed metrics": whether a configuration improves performance
    depends on how it *differs* from the one producing H_t, a relation
    axis-aligned tree splits cannot synthesize from absolute values alone.
    """
    vecs = [(h.read if op == READ else h.write) for h in history]
    th = np.asarray(theta_feat, dtype=np.float64)
    knobs = READ_KNOB_IDX if op == READ else WRITE_KNOB_IDX
    last = vecs[-1]
    delta = np.array([th[0] - last[knobs[0]], th[1] - last[knobs[1]]])
    return np.concatenate(vecs + [th, delta])


def feature_dim(op: int, k: int = 1) -> int:
    base = N_READ if op == READ else N_WRITE
    return base * (k + 1) + 4


# ---------------------------------------------------------------------- #
# fleet snapshots: the same designed metrics for every interface at once
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class FleetSnapshot:
    """One interval's designed metrics for *all* probed interfaces.

    Row ``i`` of ``read`` / ``write`` is exactly the vector
    :func:`snapshot` would produce for interface ``oscs[i]`` — the fleet
    path differences the whole stacked probe in a few array ops instead
    of one Python loop iteration per interface.
    """

    t: float
    dt: float
    oscs: np.ndarray          # (n,)
    read: np.ndarray          # (n, N_READ)
    write: np.ndarray         # (n, N_WRITE)
    read_volume: np.ndarray   # (n,) bytes moved (model-selection signal)
    write_volume: np.ndarray

    def one(self, i: int) -> Snapshot:
        """Row ``i`` as a scalar :class:`Snapshot` (compat / debugging)."""
        return Snapshot(t=self.t, dt=self.dt,
                        read=self.read[i], write=self.write[i],
                        read_volume=float(self.read_volume[i]),
                        write_volume=float(self.write_volume[i]))


def _log2_knob(x, xp):
    """``log2`` of a knob column, bit-stable across backends.

    XLA's ``log2`` can land 1 ulp off libm even on exact powers of two;
    that error survives the float32 feature cast through the θ-delta
    subtraction (6.0 - 5.999…e0 ≈ 9e-16 instead of exactly 0.0) and can
    flip GBDT splits whose threshold sits at 0.  Knob values are powers
    of two (the Θ grid), whose exponent is counted exactly by comparing
    against the powers ``2**0 .. 2**62``; other values (only reachable
    by writing knobs outside Θ) fall back to the backend ``log2``.
    Compares only: ``frexp`` lowers to a 64-bit bitcast, which the TPU
    compiler cannot emulate.
    """
    if xp is np:
        return np.log2(x)
    x = x.astype(np.float64)
    pows = xp.asarray(2.0 ** np.arange(63))
    e = xp.maximum(xp.sum(x[..., None] >= pows, axis=-1) - 1, 0)
    return xp.where(x == pows[e], e.astype(np.float64), xp.log2(x))


def snapshot_arrays(prev, cur, xp=np):
    """Backend-agnostic core of :func:`snapshot_all`.

    ``prev`` / ``cur`` expose the :class:`FleetStats` field surface
    (stacked cumulative counters); ``xp`` is the array namespace.  With
    ``xp=np`` this is the oracle; :mod:`repro.pfs.loop_jax` calls it with
    ``xp=jnp`` so the device-resident loop differences probes with the
    *literal same* arithmetic, in the same op order, on float64.

    Returns ``(dt, read_mat, write_mat, read_volume, write_volume)``.
    """
    dt = xp.maximum(cur.t - prev.t, 1e-9)

    def safe_div(a, b):
        """Elementwise ``a/b`` where ``b > 0`` else 0 (no divide-by-0)."""
        ok = b > 0
        return xp.where(ok, a / xp.where(ok, b, 1.0), 0.0)

    def common(op: int) -> list:
        d_bytes = (cur.bytes_done[op] - prev.bytes_done[op]).astype(np.float64)
        d_rpcs = (cur.rpcs_sent[op] - prev.rpcs_sent[op]).astype(np.float64)
        d_rpc_bytes = (cur.rpc_bytes[op] - prev.rpc_bytes[op]).astype(np.float64)
        d_partial = (cur.partial_rpcs[op] - prev.partial_rpcs[op]).astype(np.float64)
        d_done = (cur.rpcs_done[op] - prev.rpcs_done[op]).astype(np.float64)
        d_lat = (cur.latency_sum[op] - prev.latency_sum[op]).astype(np.float64)
        d_reqs = (cur.req_count[op] - prev.req_count[op]).astype(np.float64)
        d_req_bytes = (cur.req_bytes[op] - prev.req_bytes[op]).astype(np.float64)
        d_pend = (cur.pending_integral[op] - prev.pending_integral[op]).astype(np.float64)
        d_act = (cur.active_integral[op] - prev.active_integral[op]).astype(np.float64)
        return [
            d_bytes / dt / 1e6,
            d_rpcs / dt,
            safe_div(d_rpc_bytes, d_rpcs) / PAGE_SIZE,
            safe_div(d_partial, d_rpcs),
            safe_div(d_lat, d_done) * 1e3,
            d_pend / dt / 2**20,
            d_act / dt,
            safe_div(d_act / dt, cur.rpcs_in_flight),
            d_reqs / dt,
            safe_div(d_req_bytes, d_reqs) / 1024.0,
            cur.randomness[op].astype(np.float64),
        ]

    knobs = [_log2_knob(cur.window_pages, xp),
             _log2_knob(cur.rpcs_in_flight, xp)]

    r = common(READ)
    d_req_bytes_r = (cur.req_bytes[READ] - prev.req_bytes[READ]).astype(np.float64)
    d_hit = (cur.cache_hit_bytes - prev.cache_hit_bytes).astype(np.float64)
    r.append(safe_div(d_hit, d_req_bytes_r))
    read_mat = xp.stack(r + knobs, axis=1)

    w = common(WRITE)
    w.append((cur.block_time - prev.block_time).astype(np.float64) / dt)
    w.append((cur.dirty_integral - prev.dirty_integral).astype(np.float64) / dt / 2**20)
    w.append((cur.grant_integral - prev.grant_integral).astype(np.float64) / dt / 2**20)
    write_mat = xp.stack(w + knobs, axis=1)

    read_vol = (cur.bytes_done[READ] - prev.bytes_done[READ]).astype(np.float64)
    write_vol = (cur.bytes_done[WRITE] - prev.bytes_done[WRITE]).astype(np.float64)
    return dt, read_mat, write_mat, read_vol, write_vol


def snapshot_all(prev: FleetStats, cur: FleetStats) -> FleetSnapshot:
    """Vectorized :func:`snapshot` over two consecutive fleet probes.

    Arithmetic is elementwise-identical to the scalar path (same ops in
    the same order on float64), so fleet rows match per-interface
    snapshots bit for bit — the fleet/loop equivalence tests rely on it.
    """
    dt, read_mat, write_mat, read_vol, write_vol = snapshot_arrays(prev, cur)
    return FleetSnapshot(
        t=cur.t,
        dt=float(dt),
        oscs=cur.oscs,
        read=read_mat,
        write=write_mat,
        read_volume=read_vol,
        write_volume=write_vol,
    )


def fleet_feature_matrix(history: list[FleetSnapshot], op: int,
                         rows: np.ndarray,
                         theta_feats: np.ndarray) -> np.ndarray:
    """Model inputs for selected interfaces against every candidate theta.

    ``history`` is ``[s_{t-k}, ..., s_t]`` of fleet snapshots, ``rows``
    indexes the interfaces to score, ``theta_feats`` is the ``(M, 2)``
    log2 grid from :meth:`ConfigSpace.as_features`.  Returns a
    ``(len(rows) * M, dim)`` float32 matrix: interface-major, so row
    ``i * M + j`` is (theta_j, H_t of interface rows[i]) — identical
    row-for-row to stacking :meth:`DIALModel.features_for_space` outputs.
    """
    rows = np.asarray(rows)
    mats = [(h.read if op == READ else h.write)[rows] for h in history]
    hist = np.concatenate(mats, axis=1)            # (r, N*(k+1)) float64
    knobs = READ_KNOB_IDX if op == READ else WRITE_KNOB_IDX
    cur = mats[-1][:, list(knobs)]                 # (r, 2) currently applied
    r, m = hist.shape[0], theta_feats.shape[0]
    theta_tiled = np.tile(theta_feats, (r, 1))     # (r*M, 2) float64
    out = np.empty((r * m, hist.shape[1] + 4), dtype=np.float32)
    out[:, :-4] = np.repeat(hist, m, axis=0)
    out[:, -4:-2] = theta_tiled
    out[:, -2:] = theta_tiled - np.repeat(cur, m, axis=0)
    return out
