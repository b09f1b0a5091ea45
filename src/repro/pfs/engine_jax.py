"""JAX execution layer: one full tuning interval as a single fused scan.

The numpy oracle (:func:`repro.pfs.state.engine_step`) is driven
tick-by-tick from Python — ~100 interpreter round trips per 0.5 s tuning
interval.  This module compiles the *whole interval* into one jitted
``lax.scan`` over the identical transition:

    (SimState, WorkloadState) --[demand_step ∘ engine_step]*n_ticks-->
    (SimState', WorkloadState')

with every per-OST / per-client / per-stripe reduction routed through
the shared :mod:`repro.kernels.segment_reduce` helper (by default XLA's
``jax.ops.segment_sum``, on every platform).

Numerics: the scan is traced under ``jax.enable_x64`` so arithmetic
matches the float64 numpy oracle (the equivalence tests hold both paths
to ≤1e-6 relative error on all probe counters; in practice they agree
to ~1e-12).  The Pallas segment kernel, selectable by name, accumulates
in float32.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce.ops import make_segment_sum
from repro.pfs.state import (BYTE_TOL, PAGE_SIZE, READ, TIME_TOL, WRITE,
                             Demand, Disturbance, SimParams, SimState,
                             SimTopo)
from repro.pfs.workloads import WorkloadState, WorkloadTable


def _div_where(num, den, cond, fallback):
    """``np.divide(num, den, out=fallback, where=cond)`` in functional jnp."""
    safe = jnp.where(cond, den, 1.0)
    return jnp.where(cond, num / safe, fallback)


def engine_step_jax(params: SimParams, topo: SimTopo, state: SimState,
                    demand: Demand | None, segsum,
                    disturbance: Disturbance | None = None) -> SimState:
    """Pure-jnp mirror of :func:`repro.pfs.state.engine_step`.

    Same phase structure and same arithmetic, with the bincount call
    sites replaced by ``segsum`` and the in-place updates rewritten as
    functional rebinding.  Tested element-for-element against the numpy
    oracle (tests/test_engine_equivalence.py).
    """
    p = params
    dt = p.tick
    n_osts, n_clients = topo.n_osts, topo.n_clients
    osc_ost, osc_client = topo.osc_ost, topo.osc_client
    dist = (disturbance if disturbance is not None
            else Disturbance.neutral(topo))

    # unpack per-op rows as locals (functional SSA instead of mutation)
    pending = [state.pending[READ], state.pending[WRITE]]
    hold_age = [state.hold_age[READ], state.hold_age[WRITE]]
    queue_rpcs = [state.queue_rpcs[READ], state.queue_rpcs[WRITE]]
    queue_bytes = [state.queue_bytes[READ], state.queue_bytes[WRITE]]
    active_rpcs = [state.active_rpcs[READ], state.active_rpcs[WRITE]]
    setup_work = [state.setup_work[READ], state.setup_work[WRITE]]
    unready = [state.unready_bytes[READ], state.unready_bytes[WRITE]]
    ready_b = [state.ready_bytes[READ], state.ready_bytes[WRITE]]
    avg_size = [state.active_avg_size[READ], state.active_avg_size[WRITE]]
    disp_num = [state.dispatch_time_num[READ], state.dispatch_time_num[WRITE]]
    randomness = [state.randomness[READ], state.randomness[WRITE]]
    ctr_bytes_done = [state.ctr_bytes_done[READ], state.ctr_bytes_done[WRITE]]
    ctr_rpcs_sent = [state.ctr_rpcs_sent[READ], state.ctr_rpcs_sent[WRITE]]
    ctr_rpc_bytes = [state.ctr_rpc_bytes[READ], state.ctr_rpc_bytes[WRITE]]
    ctr_partial = [state.ctr_partial_rpcs[READ], state.ctr_partial_rpcs[WRITE]]
    ctr_lat = [state.ctr_latency_sum[READ], state.ctr_latency_sum[WRITE]]
    ctr_rpcs_done = [state.ctr_rpcs_done[READ], state.ctr_rpcs_done[WRITE]]
    ctr_req_count = state.ctr_req_count
    ctr_req_bytes = state.ctr_req_bytes
    ctr_cache_hit = state.ctr_cache_hit_bytes
    ctr_pend_int = [state.ctr_pending_integral[READ],
                    state.ctr_pending_integral[WRITE]]
    ctr_act_int = [state.ctr_active_integral[READ],
                   state.ctr_active_integral[WRITE]]
    dirty = state.dirty_bytes
    grant = state.grant_used
    blocked = state.write_blocked
    now = state.now

    # (1) workloads deposit demand
    if demand is not None:
        pending[READ] = pending[READ] + demand.pending_read_add
        dirty = dirty + demand.dirty_add
        grant = grant + demand.dirty_add
        ctr_req_count = ctr_req_count + demand.req_count_add
        ctr_req_bytes = ctr_req_bytes + demand.req_bytes_add
        ctr_cache_hit = ctr_cache_hit + demand.cache_hit_add
        ctr_bytes_done[WRITE] = ctr_bytes_done[WRITE] + demand.dirty_add
        randomness = [demand.randomness_new[READ], demand.randomness_new[WRITE]]
        blocked = demand.write_blocked_new

    # write path: dirty cache continuously feeds the pending queue
    in_pipe = (pending[WRITE] + queue_bytes[WRITE]
               + unready[WRITE] + ready_b[WRITE])
    pending[WRITE] = pending[WRITE] + jnp.maximum(dirty - in_pipe, 0.0)

    # (2) RPC formation: full windows pack immediately; partials wait
    win_bytes = (state.window_pages * PAGE_SIZE).astype(jnp.float64)
    for op in (READ, WRITE):
        pend = pending[op]
        room = jnp.maximum(p.max_rpc_queue - queue_rpcs[op], 0.0)
        n_full = jnp.minimum(jnp.floor((pend + BYTE_TOL) / win_bytes), room)
        full_bytes = n_full * win_bytes
        queue_rpcs[op] = queue_rpcs[op] + n_full
        queue_bytes[op] = queue_bytes[op] + full_bytes
        pend = pend - full_bytes
        partial = pend > BYTE_TOL
        hold_age[op] = jnp.where(partial, hold_age[op] + dt, 0.0)
        expire = (partial & (hold_age[op] >= p.hold_time(op) - TIME_TOL)
                  & (room > n_full))
        queue_rpcs[op] = queue_rpcs[op] + expire
        queue_bytes[op] = queue_bytes[op] + jnp.where(expire, pend, 0.0)
        ctr_partial[op] = ctr_partial[op] + expire
        pending[op] = jnp.where(expire, 0.0, pend)
        hold_age[op] = jnp.where(expire, 0.0, hold_age[op])

    # (3) dispatch up to rpcs_in_flight (reads first: sync-read bias)
    slots = jnp.maximum(
        state.rpcs_in_flight - (active_rpcs[READ] + active_rpcs[WRITE]), 0.0)
    for op in (READ, WRITE):
        take = jnp.minimum(queue_rpcs[op], slots)
        frac = _div_where(take, queue_rpcs[op], queue_rpcs[op] > 0, 0.0)
        bytes_out = queue_bytes[op] * frac
        queue_rpcs[op] = queue_rpcs[op] - take
        queue_bytes[op] = queue_bytes[op] - bytes_out
        slots = slots - take
        active_rpcs[op] = active_rpcs[op] + take
        per_rpc = p.setup_time(randomness[op]) + p.rtt
        setup_work[op] = setup_work[op] + take * per_rpc
        unready[op] = unready[op] + bytes_out
        tot_bytes = unready[op] + ready_b[op]
        avg_size[op] = jnp.where(
            active_rpcs[op] > 0,
            tot_bytes / jnp.maximum(active_rpcs[op], 1e-9),
            avg_size[op])
        ctr_rpcs_sent[op] = ctr_rpcs_sent[op] + take
        ctr_rpc_bytes[op] = ctr_rpc_bytes[op] + bytes_out
        disp_num[op] = disp_num[op] + take * now

    # (4) OST setup service + IOPS ceiling
    total_work = setup_work[READ] + setup_work[WRITE]
    ost_work = segsum(total_work, osc_ost, n_osts)
    cap = dt * p.ost_setup_parallel * dist.iops_scale
    drain_frac_ost = _div_where(cap, ost_work, ost_work > cap, 1.0)
    for op in (READ, WRITE):
        work = setup_work[op]
        drained = work * drain_frac_ost[osc_ost]
        per_rpc = p.setup_time(randomness[op]) + p.rtt
        setups_done = _div_where(drained, per_rpc, per_rpc > 0, 0.0)
        ost_setups = segsum(setups_done, osc_ost, n_osts)
        iops_cap = p.ost_iops * dt * dist.iops_scale
        iops_frac = _div_where(iops_cap, ost_setups, ost_setups > iops_cap, 1.0)
        effective = drained * iops_frac[osc_ost]
        setup_work[op] = work - effective
        ready = jnp.minimum(
            _div_where(effective, per_rpc, per_rpc > 0, 0.0) * avg_size[op],
            unready[op])
        ready = jnp.where(setup_work[op] <= 1e-12, unready[op], ready)
        unready[op] = unready[op] - ready
        ready_b[op] = ready_b[op] + ready

    # (5) bandwidth: OST fair share + congestion decay + NIC cap
    want = ready_b[READ] + ready_b[WRITE]
    queued = unready[READ] + unready[WRITE] + ready_b[READ] + ready_b[WRITE]
    ost_queued = segsum(queued, osc_ost, n_osts) + dist.bg_bytes
    eff = jnp.where(
        ost_queued > p.ost_buffer_bytes,
        jnp.power(p.ost_buffer_bytes / jnp.maximum(ost_queued, 1.0),
                  p.congestion_exp),
        1.0)
    active_transfer = jnp.where(want > BYTE_TOL,
                                active_rpcs[READ] + active_rpcs[WRITE], 0.0)
    ost_shares = segsum(active_transfer, osc_ost, n_osts)
    share = _div_where(active_transfer, ost_shares[osc_ost],
                       ost_shares[osc_ost] > 0, 0.0)
    ost_bw_eff = p.ost_bandwidth * dist.bw_scale * eff
    # background traffic is served first, shrinking the foreground
    # budget; same subtraction form as the numpy oracle so the
    # zero-background case keeps the historical multiplication order
    bg_served = jnp.minimum(dist.bg_bytes, ost_bw_eff * dt)
    alloc = jnp.minimum(
        share * ost_bw_eff[osc_ost] * dt - share * bg_served[osc_ost], want)
    leftover = (ost_bw_eff * dt - bg_served) - segsum(alloc, osc_ost, n_osts)
    hungry = want - alloc
    ost_hungry = segsum(hungry, osc_ost, n_osts)
    bonus_frac = _div_where(leftover, ost_hungry, ost_hungry > 0, 0.0)
    alloc = alloc + hungry * jnp.minimum(bonus_frac[osc_ost], 1.0)
    nic_cap = p.nic_bandwidth * dist.nic_scale * dt
    client_alloc = segsum(alloc, osc_client, n_clients)
    nic_frac = _div_where(nic_cap, client_alloc,
                          client_alloc > nic_cap, 1.0)
    alloc = alloc * nic_frac[osc_client]

    # (6) completions
    for op in (READ, WRITE):
        frac = _div_where(ready_b[op], want, want > 0, 0.0)
        drained = alloc * frac
        ready_b[op] = ready_b[op] - drained
        avg = jnp.maximum(avg_size[op], 1.0)
        done_rpcs = jnp.minimum(drained / avg, active_rpcs[op])
        inflight_bytes = unready[op] + ready_b[op]
        done_rpcs = jnp.where(inflight_bytes <= BYTE_TOL, active_rpcs[op],
                              done_rpcs)
        prev_active = active_rpcs[op]
        active_rpcs[op] = active_rpcs[op] - done_rpcs
        ctr_rpcs_done[op] = ctr_rpcs_done[op] + done_rpcs
        if op == READ:
            ctr_bytes_done[READ] = ctr_bytes_done[READ] + drained
        else:
            dirty = jnp.maximum(dirty - drained, 0.0)
            grant = jnp.maximum(grant - drained, 0.0)
        avg_disp = disp_num[op] / jnp.maximum(prev_active, 1e-9)
        lat = jnp.maximum(now + dt - avg_disp, dt)
        ctr_lat[op] = ctr_lat[op] + done_rpcs * lat
        keep = active_rpcs[op] / jnp.maximum(prev_active, 1e-9)
        disp_num[op] = disp_num[op] * keep

    # blocked-writer accounting
    ctr_block_time = state.ctr_block_time + blocked * dt
    room = jnp.minimum(p.max_dirty_bytes - dirty, p.grant_bytes - grant)
    blocked = jnp.logical_and(blocked, room < PAGE_SIZE)

    # time-integrals for interval averages
    for op in (READ, WRITE):
        ctr_pend_int[op] = ctr_pend_int[op] + (pending[op] + queue_bytes[op]) * dt
        ctr_act_int[op] = ctr_act_int[op] + active_rpcs[op] * dt
    ctr_dirty_int = state.ctr_dirty_integral + dirty * dt
    ctr_grant_int = state.ctr_grant_integral + grant * dt

    stack = jnp.stack
    return SimState(
        now=now + dt,
        tick_index=state.tick_index + 1,
        window_pages=state.window_pages,
        rpcs_in_flight=state.rpcs_in_flight,
        pending=stack(pending),
        hold_age=stack(hold_age),
        queue_rpcs=stack(queue_rpcs),
        queue_bytes=stack(queue_bytes),
        active_rpcs=stack(active_rpcs),
        setup_work=stack(setup_work),
        unready_bytes=stack(unready),
        ready_bytes=stack(ready_b),
        active_avg_size=stack(avg_size),
        dispatch_time_num=stack(disp_num),
        randomness=stack(randomness),
        dirty_bytes=dirty,
        grant_used=grant,
        write_blocked=blocked,
        ctr_bytes_done=stack(ctr_bytes_done),
        ctr_rpcs_sent=stack(ctr_rpcs_sent),
        ctr_rpc_bytes=stack(ctr_rpc_bytes),
        ctr_partial_rpcs=stack(ctr_partial),
        ctr_latency_sum=stack(ctr_lat),
        ctr_rpcs_done=stack(ctr_rpcs_done),
        ctr_req_count=ctr_req_count,
        ctr_req_bytes=ctr_req_bytes,
        ctr_cache_hit_bytes=ctr_cache_hit,
        ctr_block_time=ctr_block_time,
        ctr_pending_integral=stack(ctr_pend_int),
        ctr_active_integral=stack(ctr_act_int),
        ctr_dirty_integral=ctr_dirty_int,
        ctr_grant_integral=ctr_grant_int,
        ost_valid=state.ost_valid,
        client_valid=state.client_valid,
    )


# ---------------------------------------------------------------------- #
# fused interval runner
# ---------------------------------------------------------------------- #
def _to_numpy_state(state: SimState) -> SimState:
    # np.array (not asarray): device buffers convert to read-only views,
    # and the stateful wrapper mutates these in place (set_knobs, submit_*)
    out = jax.tree.map(np.array, state)
    out.now = float(out.now)
    out.tick_index = int(out.tick_index)
    return out


class FusedEngine:
    """One tuning interval (``n_ticks`` engine ticks) per jitted call.

    Compiles ``lax.scan`` over ``demand_step ∘ engine_step`` once at
    construction scope (first call), then every interval is a single
    device dispatch.  Inputs/outputs are numpy ``SimState`` /
    ``WorkloadState`` so the stateful :class:`~repro.pfs.engine.PFSSim`
    wrapper and the probe/tuning layers never see jax arrays.
    """

    def __init__(self, params: SimParams, topo: SimTopo,
                 table: WorkloadTable, n_ticks: int,
                 seg_backend: str = "auto"):
        self.params = params
        self.topo = topo
        self.table = table
        self.n_ticks = int(n_ticks)
        segsum = make_segment_sum(seg_backend)
        # every interval scans over a per-tick Disturbance schedule; an
        # undisturbed run scans the (exact-identity) neutral schedule so
        # jit sees a single signature either way
        self._neutral_sched = Disturbance.neutral(topo, n_ticks=self.n_ticks)

        def body(carry, dist):
            state, wstate = carry
            demand, wstate = table.demand_step(params, wstate, state,
                                               xp=jnp, segsum=segsum)
            state = engine_step_jax(params, topo, state, demand, segsum,
                                    disturbance=dist)
            return (state, wstate), None

        @jax.jit
        def run(state, wstate, sched):
            (state, wstate), _ = jax.lax.scan(
                body, (state, wstate), sched, length=self.n_ticks)
            return state, wstate

        self._run = run

    def run_interval(self, state: SimState, wstate: WorkloadState,
                     schedule: Disturbance | None = None):
        """Advance one interval; numpy in, numpy out (float64 end to end).

        ``schedule`` is a :class:`Disturbance` whose arrays carry a
        leading ``(n_ticks, ...)`` time axis — tick ``i`` of the scan
        consumes row ``i`` (scan ``xs``), exactly as the numpy reference
        :func:`repro.pfs.workloads.run_interval` indexes it.
        """
        if schedule is None:
            schedule = self._neutral_sched
        with jax.enable_x64(True):
            jstate = jax.tree.map(jnp.asarray, state)
            jws = jax.tree.map(jnp.asarray, wstate)
            jsched = jax.tree.map(jnp.asarray, schedule)
            jstate, jws = self._run(jstate, jws, jsched)
            jstate, jws = jax.tree.map(lambda x: x.block_until_ready()
                                       if hasattr(x, "block_until_ready")
                                       else x, (jstate, jws))
        return _to_numpy_state(jstate), jax.tree.map(np.array, jws)


def fused_engine_for(sim, table: WorkloadTable, n_ticks: int,
                     seg_backend: str = "auto") -> FusedEngine:
    """Build a :class:`FusedEngine` for a live :class:`PFSSim`."""
    return FusedEngine(sim.params, sim.topo, table, n_ticks,
                       seg_backend=seg_backend)
