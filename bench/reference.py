"""Plain numpy reference: the simulated Lustre cluster and DIAL's tuning loop.

The yardstick the benchmark holds the program to.  It imports nothing of
the program: the engine tick, the workload demand, the scenario builder
with its seeded jitter, the designed metrics, the forest traversal and
Algorithm 1 are copied here from the repository's own numpy oracle
(``pfs/state.py``, ``pfs/workloads.py``, ``lab/scenarios.py``,
``core/metrics.py``, ``core/tuner.py``, ``core/fleet.py``), one tick and
one interface row at a time in float64, so that a later change to the
program cannot move the reference with it.

:func:`run` simulates one scenario, DIAL-tuned or at its static θ, and
returns the θ decision of every interval and the final counters.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math

import numpy as np

PAGE_SIZE = 4096
BYTE_TOL = 0.5          # discrete engine steps compare with half a byte ...
TIME_TOL = 1e-9         # ... and a nanosecond of tolerance
READ, WRITE = 0, 1

WINDOW_PAGES = (16, 64, 256, 1024)
RPCS_IN_FLIGHT = (1, 2, 4, 8, 16, 32)
DEFAULT_THETA = (256, 8)
THETAS = np.array(list(itertools.product(WINDOW_PAGES, RPCS_IN_FLIGHT)),
                  dtype=np.float64)                   # (24, 2), row-major
THETA_FEATS = np.log2(THETAS)

TAU, ALPHA, BETA = 0.8, 0.3, 0.25      # Algorithm 1 (paper SIII-C)
MIN_VOLUME = 256 * 1024                # bytes an interval must move
WARMUP, K = 2, 1                       # first decision in interval 4

COUNTERS = ("ctr_bytes_done", "ctr_rpcs_sent", "ctr_rpc_bytes",
            "ctr_partial_rpcs", "ctr_latency_sum", "ctr_rpcs_done",
            "ctr_req_count", "ctr_req_bytes", "ctr_cache_hit_bytes",
            "ctr_block_time", "ctr_pending_integral", "ctr_active_integral",
            "ctr_dirty_integral", "ctr_grant_integral")


# ---------------------------------------------------------------------- #
# the cluster
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Params:
    tick: float = 0.005
    ost_bandwidth: float = 520e6
    ost_setup_parallel: float = 4.0
    ost_iops: float = 2600.0
    setup_time_seq: float = 300e-6
    setup_time_rand: float = 3.5e-3
    rtt: float = 120e-6
    nic_bandwidth: float = 2.9e9
    hold_time_read: float = 0.012
    hold_time_write: float = 0.025
    ost_buffer_bytes: float = 64 * 2**20
    congestion_exp: float = 0.35
    max_dirty_bytes: float = 64 * 2**20
    grant_bytes: float = 96 * 2**20
    readahead_bytes: float = 8 * 2**20
    max_rpc_queue: int = 4096

    def setup_time(self, randomness):
        return self.setup_time_seq + randomness * self.setup_time_rand

    def hold_time(self, op: int) -> float:
        return self.hold_time_read if op == READ else self.hold_time_write


@dataclasses.dataclass(frozen=True)
class Topo:
    """One OSC interface per (client, OST) pair, client-major."""

    n_clients: int
    n_osts: int

    @property
    def n_osc(self) -> int:
        return self.n_clients * self.n_osts

    @property
    def osc_client(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_clients), self.n_osts)

    @property
    def osc_ost(self) -> np.ndarray:
        return np.tile(np.arange(self.n_osts), self.n_clients)


class State:
    """Every per-tick array of the cluster, by the program's field names."""

    def __init__(self, **fields):
        self.__dict__.update(fields)

    def copy(self) -> "State":
        return State(**{k: (np.array(v) if isinstance(v, np.ndarray) else v)
                        for k, v in self.__dict__.items()})


def init_state(topo: Topo, theta) -> State:
    n = topo.n_osc
    z2 = lambda: np.zeros((2, n))
    return State(
        now=0.0,
        window_pages=np.full(n, int(theta[0]), dtype=np.int64),
        rpcs_in_flight=np.full(n, int(theta[1]), dtype=np.int64),
        pending=z2(), hold_age=z2(), queue_rpcs=z2(), queue_bytes=z2(),
        active_rpcs=z2(), setup_work=z2(), unready_bytes=z2(),
        ready_bytes=z2(), active_avg_size=np.full((2, n), float(PAGE_SIZE)),
        dispatch_time_num=z2(), randomness=z2(),
        dirty_bytes=np.zeros(n), grant_used=np.zeros(n),
        write_blocked=np.zeros(n, dtype=bool),
        ctr_bytes_done=z2(), ctr_rpcs_sent=z2(), ctr_rpc_bytes=z2(),
        ctr_partial_rpcs=z2(), ctr_latency_sum=z2(), ctr_rpcs_done=z2(),
        ctr_req_count=z2(), ctr_req_bytes=z2(),
        ctr_cache_hit_bytes=np.zeros(n), ctr_block_time=np.zeros(n),
        ctr_pending_integral=z2(), ctr_active_integral=z2(),
        ctr_dirty_integral=np.zeros(n), ctr_grant_integral=np.zeros(n))


def engine_step(p: Params, topo: Topo, state: State, demand: dict,
                dist: dict) -> State:
    """One tick: demand in, RPC formation, dispatch, OST setup service,
    bandwidth sharing with congestion, completions, accounting."""
    dt = p.tick
    s = state.copy()
    n_osts = topo.n_osts
    osc_ost, osc_client = topo.osc_ost, topo.osc_client

    # (1) the workloads' submissions
    s.pending[READ] += demand["pending_read_add"]
    s.dirty_bytes += demand["dirty_add"]
    s.grant_used += demand["dirty_add"]
    s.ctr_req_count += demand["req_count_add"]
    s.ctr_req_bytes += demand["req_bytes_add"]
    s.ctr_cache_hit_bytes += demand["cache_hit_add"]
    s.ctr_bytes_done[WRITE] += demand["dirty_add"]
    s.randomness[...] = demand["randomness_new"]
    s.write_blocked[...] = demand["write_blocked_new"]

    in_pipe = (s.pending[WRITE] + s.queue_bytes[WRITE]
               + s.unready_bytes[WRITE] + s.ready_bytes[WRITE])
    s.pending[WRITE] += np.maximum(s.dirty_bytes - in_pipe, 0.0)

    # (2) RPC formation: full windows pack, partials wait out the hold
    win_bytes = (s.window_pages * PAGE_SIZE).astype(float)
    for op in (READ, WRITE):
        pend = s.pending[op]
        room = np.maximum(p.max_rpc_queue - s.queue_rpcs[op], 0.0)
        n_full = np.minimum(np.floor((pend + BYTE_TOL) / win_bytes), room)
        full_bytes = n_full * win_bytes
        s.queue_rpcs[op] += n_full
        s.queue_bytes[op] += full_bytes
        pend = pend - full_bytes
        partial = pend > BYTE_TOL
        s.hold_age[op] = np.where(partial, s.hold_age[op] + dt, 0.0)
        expire = (partial & (s.hold_age[op] >= p.hold_time(op) - TIME_TOL)
                  & (room > n_full))
        s.queue_rpcs[op] += expire
        s.queue_bytes[op] += np.where(expire, pend, 0.0)
        s.ctr_partial_rpcs[op] += expire
        s.pending[op] = np.where(expire, 0.0, pend)
        s.hold_age[op] = np.where(expire, 0.0, s.hold_age[op])

    # (3) dispatch up to rpcs_in_flight, reads first
    slots = np.maximum(
        s.rpcs_in_flight - (s.active_rpcs[READ] + s.active_rpcs[WRITE]), 0.0)
    for op in (READ, WRITE):
        take = np.minimum(s.queue_rpcs[op], slots)
        frac = np.divide(take, s.queue_rpcs[op], out=np.zeros_like(take),
                         where=s.queue_rpcs[op] > 0)
        bytes_out = s.queue_bytes[op] * frac
        s.queue_rpcs[op] -= take
        s.queue_bytes[op] -= bytes_out
        slots = slots - take
        s.active_rpcs[op] += take
        per_rpc = p.setup_time(s.randomness[op]) + p.rtt
        s.setup_work[op] += take * per_rpc
        s.unready_bytes[op] += bytes_out
        tot_bytes = s.unready_bytes[op] + s.ready_bytes[op]
        s.active_avg_size[op] = np.where(
            s.active_rpcs[op] > 0,
            tot_bytes / np.maximum(s.active_rpcs[op], 1e-9),
            s.active_avg_size[op])
        s.ctr_rpcs_sent[op] += take
        s.ctr_rpc_bytes[op] += bytes_out
        s.dispatch_time_num[op] += take * s.now

    # (4) OST setup service under a context and an IOPS ceiling
    total_work = s.setup_work[READ] + s.setup_work[WRITE]
    ost_work = np.bincount(osc_ost, weights=total_work, minlength=n_osts)
    cap = dt * p.ost_setup_parallel * dist["iops_scale"]
    drain_frac_ost = np.divide(cap, ost_work, out=np.ones(n_osts),
                               where=ost_work > cap)
    for op in (READ, WRITE):
        work = s.setup_work[op]
        drained = work * drain_frac_ost[osc_ost]
        per_rpc = p.setup_time(s.randomness[op]) + p.rtt
        setups_done = np.divide(drained, per_rpc, out=np.zeros_like(drained),
                                where=per_rpc > 0)
        ost_setups = np.bincount(osc_ost, weights=setups_done,
                                 minlength=n_osts)
        iops_cap = p.ost_iops * dt * dist["iops_scale"]
        iops_frac = np.divide(iops_cap, ost_setups, out=np.ones(n_osts),
                              where=ost_setups > iops_cap)
        effective = drained * iops_frac[osc_ost]
        s.setup_work[op] = work - effective
        ready = np.minimum(
            np.divide(effective, per_rpc, out=np.zeros_like(effective),
                      where=per_rpc > 0) * s.active_avg_size[op],
            s.unready_bytes[op])
        ready = np.where(s.setup_work[op] <= 1e-12, s.unready_bytes[op], ready)
        s.unready_bytes[op] -= ready
        s.ready_bytes[op] += ready

    # (5) bandwidth: fair share per OST, congestion past the buffer,
    # background bytes served first, leftover to the hungry, NIC caps
    want = s.ready_bytes[READ] + s.ready_bytes[WRITE]
    queued = (s.unready_bytes[READ] + s.unready_bytes[WRITE]
              + s.ready_bytes[READ] + s.ready_bytes[WRITE])
    ost_queued = np.bincount(osc_ost, weights=queued,
                             minlength=n_osts) + dist["bg_bytes"]
    over = ost_queued > p.ost_buffer_bytes
    eff = np.where(over, np.power(p.ost_buffer_bytes
                                  / np.maximum(ost_queued, 1.0),
                                  p.congestion_exp), 1.0)
    active_transfer = np.where(want > BYTE_TOL,
                               s.active_rpcs[READ] + s.active_rpcs[WRITE],
                               0.0)
    ost_shares = np.bincount(osc_ost, weights=active_transfer,
                             minlength=n_osts)
    share = np.divide(active_transfer, ost_shares[osc_ost],
                      out=np.zeros_like(active_transfer),
                      where=ost_shares[osc_ost] > 0)
    ost_bw_eff = p.ost_bandwidth * dist["bw_scale"] * eff
    bg_served = np.minimum(dist["bg_bytes"], ost_bw_eff * dt)
    alloc = np.minimum(
        share * ost_bw_eff[osc_ost] * dt - share * bg_served[osc_ost], want)
    leftover = (ost_bw_eff * dt - bg_served) - np.bincount(
        osc_ost, weights=alloc, minlength=n_osts)
    hungry = want - alloc
    ost_hungry = np.bincount(osc_ost, weights=hungry, minlength=n_osts)
    bonus_frac = np.divide(leftover, ost_hungry, out=np.zeros(n_osts),
                           where=ost_hungry > 0)
    alloc = alloc + hungry * np.minimum(bonus_frac[osc_ost], 1.0)
    nic_cap = p.nic_bandwidth * dist["nic_scale"] * dt
    client_alloc = np.bincount(osc_client, weights=alloc,
                               minlength=topo.n_clients)
    nic_frac = np.divide(nic_cap, client_alloc, out=np.ones(topo.n_clients),
                         where=client_alloc > nic_cap)
    alloc = alloc * nic_frac[osc_client]

    # (6) completions
    for op in (READ, WRITE):
        frac = np.divide(s.ready_bytes[op], want, out=np.zeros_like(want),
                         where=want > 0)
        drained = alloc * frac
        s.ready_bytes[op] -= drained
        avg = np.maximum(s.active_avg_size[op], 1.0)
        done_rpcs = np.minimum(np.divide(drained, avg), s.active_rpcs[op])
        inflight_bytes = s.unready_bytes[op] + s.ready_bytes[op]
        done_rpcs = np.where(inflight_bytes <= BYTE_TOL, s.active_rpcs[op],
                             done_rpcs)
        prev_active = s.active_rpcs[op].copy()
        s.active_rpcs[op] -= done_rpcs
        s.ctr_rpcs_done[op] += done_rpcs
        if op == READ:
            s.ctr_bytes_done[READ] += drained
        else:
            s.dirty_bytes = np.maximum(s.dirty_bytes - drained, 0.0)
            s.grant_used = np.maximum(s.grant_used - drained, 0.0)
        avg_disp = np.divide(s.dispatch_time_num[op],
                             np.maximum(prev_active, 1e-9))
        lat = np.maximum(s.now + dt - avg_disp, dt)
        s.ctr_latency_sum[op] += done_rpcs * lat
        keep = np.divide(s.active_rpcs[op], np.maximum(prev_active, 1e-9))
        s.dispatch_time_num[op] *= keep

    s.ctr_block_time += s.write_blocked * dt
    room = np.minimum(p.max_dirty_bytes - s.dirty_bytes,
                      p.grant_bytes - s.grant_used)
    s.write_blocked &= room < PAGE_SIZE
    for op in (READ, WRITE):
        s.ctr_pending_integral[op] += (s.pending[op] + s.queue_bytes[op]) * dt
        s.ctr_active_integral[op] += s.active_rpcs[op] * dt
    s.ctr_dirty_integral += s.dirty_bytes * dt
    s.ctr_grant_integral += s.grant_used * dt
    s.now += dt
    return s


# ---------------------------------------------------------------------- #
# workloads: closed-loop readers, grant-throttled writers
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Workload:
    client: int
    op: int
    req_size: float
    randomness: float
    n_threads: int = 1
    osts: tuple = (0,)
    thread_rate: float = 1.2e9
    duty_cycle: float = 1.0
    period: float = 10.0


def sequential_stream(client, op, req_size, ost=0, n_threads=1):
    return Workload(client, op, req_size, 0.0, n_threads, (ost,))


def random_stream(client, op, req_size, ost=0, n_threads=1):
    return Workload(client, op, req_size, 1.0, n_threads, (ost,))


def vpic_write(client, dims, osts=(0, 1, 2, 3)):
    """h5bench VPIC-IO: contiguous 1-, 2- or 3-D particle array writes."""
    req = {1: 16 * 2**20, 2: 8 * 2**20, 3: 4 * 2**20}[dims]
    rnd = {1: 0.0, 2: 0.06, 3: 0.12}[dims]
    return Workload(client, WRITE, req, rnd, 4, tuple(osts))


def bdcats_read(client, mode, osts=(0, 1, 2, 3)):
    """h5bench BDCATS-IO: partial, strided or full reads of VPIC output."""
    req, rnd = {"partial": (1 * 2**20, 0.55), "strided": (2 * 2**20, 0.35),
                "full": (16 * 2**20, 0.0)}[mode]
    return Workload(client, READ, req, rnd, 4, tuple(osts))


def dlio_reader(client, model, n_threads, osts=(0,)):
    """DLIO BERT (small shuffled records) or Megatron (large samples)."""
    if model == "bert":
        return Workload(client, READ, 64 * 2**10, 0.9, n_threads,
                        tuple(osts), duty_cycle=0.85, period=4.0)
    return Workload(client, READ, 2 * 2**20, 0.25, n_threads, tuple(osts),
                    duty_cycle=0.9, period=6.0)


class Table:
    """Workload rows as arrays, with the (row -> interface) stripe map."""

    def __init__(self, workloads, topo: Topo):
        rows = list(workloads)
        self.n_osc = topo.n_osc
        self.op = np.array([w.op for w in rows], dtype=np.int64)
        self.req_size = np.array([w.req_size for w in rows], dtype=float)
        self.randomness = np.array([w.randomness for w in rows], dtype=float)
        self.n_threads = np.array([w.n_threads for w in rows], dtype=float)
        self.thread_rate = np.array([w.thread_rate for w in rows], dtype=float)
        self.duty_cycle = np.array([w.duty_cycle for w in rows], dtype=float)
        self.period = np.array([w.period for w in rows], dtype=float)
        self.stripe_len = np.array([len(w.osts) for w in rows], dtype=float)
        sets, e_row, e_osc = [], [], []
        for i, w in enumerate(rows):
            oscs = [w.client * topo.n_osts + t for t in w.osts]
            sets.append((w.op, frozenset(oscs)))
            e_row += [i] * len(oscs)
            e_osc += oscs
        # rows that share an op and an interface run in later waves
        wave = np.zeros(len(rows), dtype=np.int64)
        for i in range(len(rows)):
            for j in range(i):
                if sets[i][0] == sets[j][0] and sets[i][1] & sets[j][1]:
                    wave[i] = max(wave[i], wave[j] + 1)
        self.wave = wave
        self.n_waves = int(wave.max()) + 1 if len(rows) else 1
        self.entry_row = np.array(e_row, dtype=np.int64)
        self.entry_osc = np.array(e_osc, dtype=np.int64)

    def seg(self, values, ids, n):
        return np.bincount(ids, weights=np.asarray(values, dtype=float),
                           minlength=n)

    def demand(self, p: Params, issued, done_base, s: State):
        """One tick of every row's submissions, resolved per interface."""
        n, r, dt, seg = self.n_osc, len(self.op), p.tick, self.seg
        e_row, e_osc = self.entry_row, self.entry_osc
        slen_e = self.stripe_len[e_row]
        rand_row_e = self.randomness[e_row]
        req_floor_e = np.maximum(self.req_size, 1.0)[e_row]
        rand_r, rand_w = s.randomness[READ], s.randomness[WRITE]
        blocked, dirty, grant = s.write_blocked, s.dirty_bytes, s.grant_used
        zero = np.zeros(n)
        pend_read_add = dirty_add = cache_add = zero
        req_cnt_add, req_bytes_add = [zero, zero], [zero, zero]
        active = ((self.duty_cycle >= 1.0)
                  | (np.mod(s.now + 0.5 * dt, self.period)
                     < self.duty_cycle * self.period))
        cap_row = self.n_threads * self.thread_rate * dt
        done_row = seg(s.ctr_bytes_done[self.op[e_row], e_osc], e_row,
                       r) - done_base
        depth = (self.n_threads * self.req_size
                 + (1.0 - self.randomness) * p.readahead_bytes
                 * self.stripe_len)
        for k in range(self.n_waves):
            in_wave = self.wave == k
            is_r = in_wave & (self.op == READ) & active
            want_r = np.clip(depth - (issued - done_row), 0.0, cap_row)
            want_r = np.where(is_r & (want_r > BYTE_TOL), want_r, 0.0)
            issued = issued + want_r
            per_e = want_r[e_row] / slen_e
            pend_read_add = pend_read_add + seg(per_e, e_osc, n)
            w_e = np.minimum(per_e / (4 * 2**20), 1.0)
            factor = 1.0 - seg(0.2 * w_e, e_osc, n)
            contrib = seg((0.2 * w_e) * rand_row_e, e_osc, n)
            rand_r = factor * rand_r + contrib
            inc_e = np.where(want_r[e_row] > 0,
                             np.maximum(per_e / req_floor_e, 1.0), 0.0)
            req_cnt_add[READ] = req_cnt_add[READ] + seg(inc_e, e_osc, n)
            req_bytes_add[READ] = req_bytes_add[READ] + seg(per_e, e_osc, n)
            cache_add = cache_add + seg((1.0 - rand_row_e) * per_e, e_osc, n)

            blocked_any = seg(np.where(blocked[e_osc], 1.0, 0.0), e_row,
                              r) > 0
            goes = in_wave & (self.op == WRITE) & active & ~blocked_any
            want_w = np.where(goes, cap_row, 0.0)
            per_we = want_w[e_row] / slen_e
            want_osc = seg(per_we, e_osc, n)
            room = np.minimum(p.max_dirty_bytes - dirty,
                              p.grant_bytes - grant)
            accepted = np.clip(want_osc, 0.0, np.maximum(room, 0.0))
            dirty = dirty + accepted
            grant = grant + accepted
            dirty_add = dirty_add + accepted
            w_osc = np.minimum(accepted / (4 * 2**20), 1.0)
            rr_osc = seg(np.where(per_we > 0, rand_row_e, 0.0), e_osc, n)
            rand_w = (1.0 - 0.2 * w_osc) * rand_w + (0.2 * w_osc) * rr_osc
            inc_we = np.where(per_we > 0,
                              np.maximum(per_we / req_floor_e, 1.0), 0.0)
            req_cnt_add[WRITE] = req_cnt_add[WRITE] + seg(inc_we, e_osc, n)
            req_bytes_add[WRITE] = req_bytes_add[WRITE] + accepted
            blocked = np.where(want_osc > 0, accepted < want_osc, blocked)
            issued = issued + seg(np.where(per_we > 0, accepted[e_osc], 0.0),
                                  e_row, r)
        demand = {"pending_read_add": pend_read_add, "dirty_add": dirty_add,
                  "req_count_add": np.stack(req_cnt_add),
                  "req_bytes_add": np.stack(req_bytes_add),
                  "cache_hit_add": cache_add,
                  "randomness_new": np.stack([rand_r, rand_w]),
                  "write_blocked_new": blocked}
        return demand, issued


# ---------------------------------------------------------------------- #
# scenarios: topology, workload rows, disturbance events, seeded jitter
# ---------------------------------------------------------------------- #
FAULT_KINDS = ("ost_fail", "ost_failover", "client_evict")


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str
    targets: tuple
    magnitude: float = 0.0
    start: float = 0.0
    end: float = math.inf
    period: float = 0.0
    duty: float = 1.0
    recovery: float = 0.0

    def active(self, t):
        act = (t >= self.start) & (t < self.end)
        if self.period > 0:
            act &= np.mod(t - self.start, self.period) < self.duty * self.period
        return act

    def capacity_scale(self, t):
        scale = np.where(self.active(t), self.magnitude, 1.0)
        if self.kind == "ost_failover":
            frac = (t - self.end) / self.recovery
            ramp = (t >= self.end) & (frac < 1.0)
            scale = np.where(ramp, self.magnitude
                             + (1.0 - self.magnitude) * frac, scale)
        return scale


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    n_clients: int
    n_osts: int
    workloads: tuple
    events: tuple = ()
    initial_theta: tuple = DEFAULT_THETA
    seed: int = 0


def schedule(spec: Spec, n_ticks: int, tick: float) -> dict:
    """Per-tick exogenous conditions: a pure function of the tick index."""
    t = np.arange(n_ticks) * tick
    o, c = spec.n_osts, spec.n_clients
    d = {"bw_scale": np.ones((n_ticks, o)), "iops_scale": np.ones((n_ticks, o)),
         "bg_bytes": np.zeros((n_ticks, o)), "nic_scale": np.ones((n_ticks, c))}
    for ev in spec.events:
        cols = np.asarray(ev.targets, dtype=np.int64)
        if ev.kind == "ost_slow":
            scale = np.where(ev.active(t), ev.magnitude, 1.0)[:, None]
            d["bw_scale"][:, cols] *= scale
            d["iops_scale"][:, cols] *= scale
        elif ev.kind in ("ost_fail", "ost_failover"):
            scale = ev.capacity_scale(t)[:, None]
            d["bw_scale"][:, cols] *= scale
            d["iops_scale"][:, cols] *= scale
        elif ev.kind == "bg_burst":
            d["bg_bytes"][:, cols] += (ev.active(t) * ev.magnitude
                                       * tick)[:, None]
        elif ev.kind == "nic_slow":
            d["nic_scale"][:, cols] *= np.where(ev.active(t), ev.magnitude,
                                                1.0)[:, None]
        else:                                       # client_evict
            d["nic_scale"][:, cols] *= ev.capacity_scale(t)[:, None]
    return d


def _jitter_event(ev: Event, rng) -> Event:
    if ev.kind == "bg_burst":
        mag = ev.magnitude * rng.uniform(0.6, 1.4)
    elif ev.kind in FAULT_KINDS:
        mag = float(np.clip(ev.magnitude * rng.uniform(0.7, 1.3), 0.0, 0.9))
    else:
        mag = float(np.clip(ev.magnitude * rng.uniform(0.7, 1.3), 0.01, 1.0))
    shift = rng.uniform(0.0, 0.5)
    end = ev.end if math.isinf(ev.end) else ev.end + shift
    return dataclasses.replace(ev, magnitude=mag, start=ev.start + shift,
                               end=end)


def variant(spec: Spec, seed: int) -> Spec:
    """The structure-preserving jitter of the lab's ``variants(spec, 1,
    seed)``: request size, thread rate, randomness and period of every
    row, magnitude and phase of every event."""
    rng = np.random.default_rng((seed << 16) ^ (spec.seed << 8) ^ 0)
    wls = tuple(dataclasses.replace(
        w,
        req_size=float(w.req_size) * 2.0 ** rng.uniform(-0.7, 0.7),
        thread_rate=float(w.thread_rate) * rng.uniform(0.7, 1.3),
        randomness=float(np.clip(w.randomness + rng.uniform(-0.1, 0.1),
                                 0.0, 1.0)),
        period=float(w.period) * rng.uniform(0.8, 1.25),
    ) for w in spec.workloads)
    evs = tuple(_jitter_event(ev, rng) for ev in spec.events)
    return dataclasses.replace(spec, name=f"{spec.name}#0", workloads=wls,
                               events=evs, seed=spec.seed + 1)


BDCATS_MODES = ("partial", "strided", "full")


def vpic_bdcats_deployment(n_clients, n_osts, stripe_count=8) -> Spec:
    """h5bench VPIC-IO writers on the first half of the clients, BDCATS-IO
    readers on the second; client c stripes over stripe_count consecutive
    OSTs from c mod n_osts."""
    half = n_clients // 2
    rows = []
    for c in range(n_clients):
        osts = tuple((c + j) % n_osts for j in range(stripe_count))
        rows.append(vpic_write(c, 1 + c % 3, osts) if c < half
                    else bdcats_read(c, BDCATS_MODES[c % 3], osts))
    return Spec(f"vpic_bdcats_{n_clients}x{n_osts}", n_clients, n_osts,
                tuple(rows))


def catalog() -> dict:
    """The twelve scenarios of the lab's registry, in registry order."""
    specs = [
        Spec("vpic_checkpoint", 4, 4, tuple(
            vpic_write(c, 1 + c % 3, (0, 1, 2, 3)) for c in range(4))),
        Spec("bdcats_analysis", 4, 4, tuple(
            bdcats_read(c, m, (0, 1, 2, 3)) for c, m in
            enumerate(("partial", "strided", "full", "partial")))),
        Spec("dlio_bert", 6, 2, tuple(
            dlio_reader(c, "bert", 2 + c % 3, (c % 2,)) for c in range(6))),
        Spec("dlio_megatron", 6, 2, tuple(
            dlio_reader(c, "megatron", 2 + c % 4, (c % 2,))
            for c in range(6))),
        Spec("filebench_mix", 8, 2, tuple(
            sequential_stream(c, READ, 4 * 2**20, c % 2) if c % 2 else
            random_stream(c, WRITE, 256 * 1024, c % 2, 2)
            for c in range(8)), initial_theta=(64, 2)),
        Spec("noisy_neighbor", 4, 2, tuple(
            sequential_stream(c, READ, 4 * 2**20, c % 2) if c < 2 else
            bdcats_read(c, "strided", (0, 1)) for c in range(4)),
            events=(Event("bg_burst", (0,), 450e6, 1.0, period=4.0, duty=0.5),
                    Event("bg_burst", (1,), 450e6, 3.0, period=4.0,
                          duty=0.5))),
        Spec("degraded_ost", 4, 4, tuple(
            vpic_write(c, 2, (0, 1, 2, 3)) if c < 2 else
            bdcats_read(c, "full", (0, 1, 2, 3)) for c in range(4)),
            events=(Event("ost_slow", (1,), 0.3, 2.0),)),
        Spec("failing_ost", 4, 4, tuple(
            bdcats_read(c, ("partial", "strided")[c % 2], (0, 1, 2, 3))
            for c in range(4)), events=(Event("ost_slow", (0,), 0.05, 3.0),)),
        Spec("failover_ost", 4, 4, tuple(
            bdcats_read(c, ("partial", "strided")[c % 2], (0, 1, 2, 3))
            for c in range(4)),
            events=(Event("ost_failover", (0,), 0.0, 2.0, 4.0,
                          recovery=3.0),)),
        Spec("client_eviction", 6, 2, tuple(
            dlio_reader(c, "bert", 2 + c % 3, (c % 2,)) for c in range(6)),
            events=(Event("client_evict", (1, 4), 0.0, 2.0, 5.0),)),
        Spec("hetero_links", 8, 2, tuple(
            sequential_stream(c, READ, 8 * 2**20, c % 2, 2)
            for c in range(8)),
            events=(Event("nic_slow", (4, 5, 6, 7), 0.12),)),
        Spec("bursty_arrivals", 6, 2, tuple(
            dataclasses.replace(
                dlio_reader(c, "bert" if c % 2 else "megatron", 2 + c % 3,
                            (c % 2,)),
                duty_cycle=0.4 if c % 2 else 0.5,
                period=2.0 if c % 2 else 3.0) for c in range(6)),
            events=(Event("bg_burst", (0, 1), 300e6, 0.5, period=2.0,
                          duty=0.25),)),
    ]
    return {s.name: s for s in specs}


# ---------------------------------------------------------------------- #
# DIAL: designed metrics, forest scores, Algorithm 1, knob write-back
# ---------------------------------------------------------------------- #
def _div(a, b):
    ok = b > 0
    return np.where(ok, a / np.where(ok, b, 1.0), 0.0)


def snapshot(prev: State, cur: State):
    """Two probes of every interface -> (read, write) metric rows and the
    bytes each op moved in the interval."""
    dt = max(cur.now - prev.now, 1e-9)

    def d(name, op=None):
        a, b = getattr(cur, name), getattr(prev, name)
        return (a - b if op is None else a[op] - b[op]).astype(np.float64)

    def common(op):
        d_rpcs, d_done, d_reqs = (d("ctr_rpcs_sent", op),
                                  d("ctr_rpcs_done", op),
                                  d("ctr_req_count", op))
        d_act = d("ctr_active_integral", op)
        return [d("ctr_bytes_done", op) / dt / 1e6,
                d_rpcs / dt,
                _div(d("ctr_rpc_bytes", op), d_rpcs) / PAGE_SIZE,
                _div(d("ctr_partial_rpcs", op), d_rpcs),
                _div(d("ctr_latency_sum", op), d_done) * 1e3,
                d("ctr_pending_integral", op) / dt / 2**20,
                d_act / dt,
                _div(d_act / dt, cur.rpcs_in_flight),
                d_reqs / dt,
                _div(d("ctr_req_bytes", op), d_reqs) / 1024.0,
                cur.randomness[op].astype(np.float64)]

    knobs = [np.log2(cur.window_pages), np.log2(cur.rpcs_in_flight)]
    r = common(READ) + [_div(d("ctr_cache_hit_bytes"),
                             d("ctr_req_bytes", READ))]
    w = common(WRITE) + [d("ctr_block_time") / dt,
                         d("ctr_dirty_integral") / dt / 2**20,
                         d("ctr_grant_integral") / dt / 2**20]
    return (np.stack(r + knobs, axis=1), np.stack(w + knobs, axis=1),
            d("ctr_bytes_done", READ), d("ctr_bytes_done", WRITE))


def features(hist, rows) -> np.ndarray:
    """(theta, H_t) rows, interface-major: history oldest to newest, the
    candidate's log2 knobs, and the candidate minus the applied knobs."""
    hist = np.concatenate([h[rows] for h in hist], axis=1)
    cur = hist[:, -2:]                     # applied knobs are the last two
    r, m = len(rows), len(THETAS)
    out = np.empty((r * m, hist.shape[1] + 4), dtype=np.float32)
    out[:, :-4] = np.repeat(hist, m, axis=0)
    out[:, -4:-2] = np.tile(THETA_FEATS, (r, 1))
    out[:, -2:] = np.tile(THETA_FEATS, (r, 1)) - np.repeat(cur, m, axis=0)
    return out


def forest_proba(forest: dict, X: np.ndarray) -> np.ndarray:
    """P(improvement) of every row: complete binary trees walked to their
    leaves, leaf values summed onto the base margin, then the float32
    logistic of the margin."""
    n = len(X)
    margin = np.full(n, float(forest["base"]), dtype=np.float64)
    feat, thr, leaf = forest["feature"], forest["threshold"], forest["leaf"]
    n_internal = feat.shape[1]
    for t in range(feat.shape[0]):
        idx = np.zeros(n, dtype=np.int64)
        for _ in range(forest["depth"]):
            idx = 2 * idx + 1 + (X[np.arange(n), feat[t, idx]] > thr[t, idx])
        margin += leaf[t, idx - n_internal]
    m32 = np.clip(margin, -30.0, 30.0).astype(np.float32)
    one = np.float32(1.0)
    return (one / (one + np.exp(-m32))).astype(np.float64)


# A candidate whose score lies below the best by no more than this share
# is tied to rounding: the program computes the logistic in float32 on
# its own device, which differs from numpy's in the last bit, so such a
# near tie may break either way.  The reference then takes the
# program's choice (and counts it).  Scores that are equal in the
# reference are no rounding tie: equal margins give equal probabilities
# on any device, and the first maximum is taken, as the program must.
TIE_RTOL = 1e-6

# The gates (an interval's volume against MIN_VOLUME, its ratio to the
# previous interval's against 0.5 and 2) meet their boundaries in the
# simulator: a reader throttled by a square-wave burst moves twice the
# bytes of the interval before.  Which side a value computed within
# GATE_RTOL of a boundary lands on is a matter of float64 rounding,
# which the chip does differently; there the reference takes the
# program's verdict (and counts it).  A value exactly on a boundary
# is no rounding tie: the gate's own rule decides it.
GATE_RTOL = 1e-9


def first_max(score) -> int:
    """Algorithm 1's pick among the candidates: the first maximum."""
    return int(np.argmax(score))


def steady(ratio):
    """The burst guard: within a factor two of the interval before."""
    return (ratio >= 0.5) & (ratio <= 2.0)


def near(value, boundary):
    """Within ``GATE_RTOL`` of a gate's boundary, but not on it."""
    gap = np.abs(value - boundary)
    return (gap > 0) & (gap <= boundary * GATE_RTOL)


def algorithm1(probs, ops, current, follow=None):
    """Conditional Score Greedy: keep θ with P > tau, MinMax-normalise the
    survivors, score with the read or write regulariser, first maximum.

    ``follow`` (one θ or ``None`` per row) is the program's choice; it is
    taken where its score lies below the best within ``TIE_RTOL``.
    Returns ``(theta, changed, n_followed)``.
    """
    thetas, changed, followed = [], [], 0
    for i, (p, op, cur) in enumerate(zip(probs, ops, current)):
        cur = (int(cur[0]), int(cur[1]))
        keep = p > TAU
        if not keep.any():
            thetas.append(cur)
            changed.append(False)
            continue
        S, pS = THETAS[keep], p[keep]
        lo, hi = S.min(axis=0), S.max(axis=0)
        norm = (S - lo) / np.where(hi - lo > 0, hi - lo, 1.0)
        if op == WRITE:
            score = pS * (1.0 + BETA * norm.sum(axis=1))
        else:
            score = pS * (1.0 + ALPHA * norm[:, 0]) + norm[:, 1]
        j = first_max(score)
        th = (int(S[j, 0]), int(S[j, 1]))
        other = None if follow is None else follow[i]
        if other is not None and other != th:
            k = [n for n, t in enumerate(S)
                 if (int(t[0]), int(t[1])) == other]
            if k and score[j] * (1.0 - TIE_RTOL) <= score[k[0]] < score[j]:
                th = other
                followed += 1
        thetas.append(th)
        changed.append(th != cur)
    return (np.array(thetas, dtype=np.int64).reshape(-1, 2),
            np.array(changed, dtype=bool), followed)


@dataclasses.dataclass
class Result:
    decisions: list        # per interval: (oscs, theta (m, 2), changed)
    state: State
    seconds: float
    ties_followed: int = 0

    def mbs(self) -> float:
        return float(self.state.ctr_bytes_done.sum() / self.seconds / 1e6)


def run(spec: Spec, policy: dict | None, seconds: float = 10.0,
        interval: float = 0.5, params: Params = Params(),
        follow=None) -> Result:
    """Simulate ``spec`` for ``seconds``; with a ``policy`` (the read and
    write forests) DIAL tunes every interface at the end of each interval,
    without one every interface keeps its initial θ.

    ``follow`` is the program's decisions, ``(oscs, theta, changed)`` per
    interval, whose choices the reference takes at rounding ties (see
    :func:`algorithm1`)."""
    topo = Topo(spec.n_clients, spec.n_osts)
    steps = max(int(round(interval / params.tick)), 1)
    n_int = int(round(seconds / interval))
    table = Table(spec.workloads, topo)
    state = init_state(topo, spec.initial_theta)
    issued = np.zeros(len(table.op))
    done_base = np.zeros(len(table.op))
    sched = schedule(spec, n_int * steps, params.tick)
    prev = state
    hist = collections.deque(maxlen=K + 1)
    decisions = []
    followed = 0
    for i in range(n_int):
        for j in range(i * steps, (i + 1) * steps):
            demand, issued = table.demand(params, issued, done_base, state)
            state = engine_step(params, topo, state, demand,
                                {k: v[j] for k, v in sched.items()})
        if policy is None:
            continue
        read, write, vol_r, vol_w = snapshot(prev, state)
        prev = state
        hist.append((read, write, vol_r, vol_w))
        if i + 1 <= WARMUP + K:
            decisions.append((np.zeros(0, np.int64), np.zeros((0, 2), np.int64),
                              np.zeros(0, bool)))
            continue
        ops = np.where(vol_r >= vol_w, READ, WRITE)
        active = np.maximum(vol_r, vol_w) >= MIN_VOLUME
        v0 = np.where(ops == READ, hist[0][2], hist[0][3])
        v1 = np.where(ops == READ, vol_r, vol_w)
        ratio = v1 / np.maximum(v0, 1.0)
        ok = active & steady(ratio)
        if follow is not None and i < len(follow):
            theirs = np.zeros(len(ok), dtype=bool)
            theirs[np.asarray(follow[i][0], dtype=np.int64)] = True
            tie = (near(ratio, 2.0) | near(ratio, 0.5)
                   | near(np.maximum(vol_r, vol_w), MIN_VOLUME))
            flip = tie & (ok != theirs)
            ok = np.where(flip, theirs, ok)
            followed += int(flip.sum())
        rows = np.nonzero(ok)[0]
        probs = np.empty((len(rows), len(THETAS)))
        for op, name in ((READ, "read"), (WRITE, "write")):
            sel = ops[rows] == op
            if sel.any():
                X = features([h[op] for h in hist], rows[sel])
                probs[sel] = forest_proba(policy[name], X).reshape(
                    -1, len(THETAS))
        current = np.stack([state.window_pages, state.rpcs_in_flight],
                           axis=1)[rows]
        theirs = None
        if follow is not None and i < len(follow):
            chosen = {int(o): (int(t[0]), int(t[1]))
                      for o, t in zip(follow[i][0], follow[i][1])}
            theirs = [chosen.get(int(r)) for r in rows]
        theta, changed, n_f = algorithm1(probs, ops[rows], current, theirs)
        followed += n_f
        state = state.copy()
        state.window_pages[rows[changed]] = theta[changed, 0]
        state.rpcs_in_flight[rows[changed]] = theta[changed, 1]
        prev = state
        decisions.append((rows.astype(np.int64), theta, changed))
    return Result(decisions=decisions, state=state, seconds=seconds,
                  ties_followed=followed)
