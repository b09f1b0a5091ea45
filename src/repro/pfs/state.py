"""State layer of the PFS engine: flat tick state + the pure step function.

This module is the backend-agnostic core the simulator is built on:

* :class:`SimParams` — physical constants of the simulated cluster;
* :class:`SimTopo`   — the static (client, OST) -> OSC wiring;
* :class:`SimState`  — every mutable per-tick array in one flat dataclass
  (registered as a JAX pytree when jax is importable, so the same object
  threads through ``lax.scan``);
* :class:`Demand`    — one tick's workload submissions, already resolved
  to per-OSC deltas (see :meth:`repro.pfs.workloads.WorkloadTable.demand_step`);
* :func:`engine_step` — the pure numpy transition
  ``(params, topo, state, demand) -> state'``, a verbatim extraction of
  the historical ``PFSSim.step`` phases.  This is the oracle the JAX
  execution layer (:mod:`repro.pfs.engine_jax`) is tested against.

:class:`~repro.pfs.engine.PFSSim` remains the stateful convenience
wrapper: it owns one ``SimState`` and calls :func:`engine_step` per tick,
so every existing caller (stats probing, fleet ports, benchmarks) keeps
working unchanged.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

PAGE_SIZE = 4096  # bytes, Linux page

# The discrete steps of a tick (whole-window RPC packing, partial-RPC
# expiry, "nothing left in flight", a reader's next request) compare
# float64 byte and time quantities against boundaries they often reach
# exactly.  Backends round differently: XLA's CPU backend fuses
# multiply-adds, and the TPU emulates float64 without correct rounding
# (~1e-15 relative per op).  A one-ulp difference at such a boundary
# would flip the step and drift the counters by percents, so each step
# compares with a tolerance far above rounding noise and far below
# anything physical: half a byte, one nanosecond.
BYTE_TOL = 0.5
TIME_TOL = 1e-9

# Operation codes.
READ = 0
WRITE = 1


@dataclasses.dataclass(frozen=True)
class SimParams:
    """Physical constants of the simulated cluster.

    Defaults are calibrated against the paper's CloudLab c6525-25g testbed
    (SIV-A): 4 OSS x 2 OST on SATA SSDs behind 25 GbE, which delivers
    single-client streams in the 300-460 MB/s range (paper Table II).
    """

    tick: float = 0.005                # simulation step [s]
    ost_bandwidth: float = 520e6       # per-OST service bandwidth [B/s]
    ost_setup_parallel: float = 4.0    # concurrent setup contexts per OST
    ost_iops: float = 2600.0           # per-OST RPC completions per second
    setup_time_seq: float = 300e-6     # fixed overhead per sequential RPC [s]
    setup_time_rand: float = 3.5e-3    # extra overhead for fully random RPC [s]
    rtt: float = 120e-6                # client<->OSS network round trip [s]
    nic_bandwidth: float = 2.9e9       # per-client NIC cap [B/s]
    hold_time_read: float = 0.012      # OSC holds a partial read RPC [s]
    hold_time_write: float = 0.025     # writes plug longer (write-behind)
    ost_buffer_bytes: float = 64 * 2**20  # OST service-queue comfort zone
    congestion_exp: float = 0.35       # service efficiency decay past buffer
    max_dirty_bytes: float = 64 * 2**20   # per-OSC dirty cache limit
    grant_bytes: float = 96 * 2**20       # per-OSC server grant
    readahead_bytes: float = 8 * 2**20 # client readahead pipeline depth
    max_rpc_queue: int = 4096          # formed-but-unsent RPC cap per OSC

    def setup_time(self, randomness):
        """Per-RPC fixed overhead as a function of access randomness in [0,1]."""
        return self.setup_time_seq + randomness * self.setup_time_rand

    def hold_time(self, op: int) -> float:
        return self.hold_time_read if op == READ else self.hold_time_write


@dataclasses.dataclass(frozen=True)
class SimTopo:
    """Static topology: one OSC per (client, OST) pair, like Lustre LOV.

    ``ost_valid`` / ``client_valid`` mark which slots are real when the
    topology has been padded up to a ragged-batch bucket shape
    (:mod:`repro.lab.batch`).  ``None`` means all-valid — the default for
    every directly-built topology, so unpadded runs are untouched.
    Phantom slots carry exact arithmetic identities everywhere (zero
    demand, neutral disturbance), so the masks are bookkeeping for the
    tuning/probing layers, not an engine input.
    """

    n_clients: int
    n_osts: int
    osc_client: np.ndarray   # (n_osc,) owning client of each OSC
    osc_ost: np.ndarray      # (n_osc,) backing OST of each OSC
    ost_valid: np.ndarray | None = None      # (n_osts,) bool; None = all
    client_valid: np.ndarray | None = None   # (n_clients,) bool; None = all

    @property
    def n_osc(self) -> int:
        return self.n_clients * self.n_osts

    @classmethod
    def dense(cls, n_clients: int, n_osts: int) -> "SimTopo":
        return cls(
            n_clients=n_clients,
            n_osts=n_osts,
            osc_client=np.repeat(np.arange(n_clients), n_osts),
            osc_ost=np.tile(np.arange(n_osts), n_clients),
        )

    def osc_id(self, client: int, ost: int) -> int:
        return client * self.n_osts + ost

    def client_oscs(self, client: int) -> np.ndarray:
        return np.arange(client * self.n_osts, (client + 1) * self.n_osts)

    def ost_valid_mask(self) -> np.ndarray:
        if self.ost_valid is None:
            return np.ones(self.n_osts, dtype=bool)
        return np.asarray(self.ost_valid, dtype=bool)

    def client_valid_mask(self) -> np.ndarray:
        if self.client_valid is None:
            return np.ones(self.n_clients, dtype=bool)
        return np.asarray(self.client_valid, dtype=bool)

    def osc_valid(self) -> np.ndarray:
        """(n_osc,) bool — an interface is real iff both endpoints are."""
        return (self.client_valid_mask()[self.osc_client]
                & self.ost_valid_mask()[self.osc_ost])


# The SimState fields, in pytree flattening order.  Everything mutable in
# a tick lives here; per-op arrays are (2, n), per-OSC arrays (n,).
_STATE_FIELDS = (
    "now", "tick_index",
    "window_pages", "rpcs_in_flight",
    "pending", "hold_age", "queue_rpcs", "queue_bytes", "active_rpcs",
    "setup_work", "unready_bytes", "ready_bytes", "active_avg_size",
    "dispatch_time_num", "randomness",
    "dirty_bytes", "grant_used", "write_blocked",
    "ctr_bytes_done", "ctr_rpcs_sent", "ctr_rpc_bytes", "ctr_partial_rpcs",
    "ctr_latency_sum", "ctr_rpcs_done", "ctr_req_count", "ctr_req_bytes",
    "ctr_cache_hit_bytes", "ctr_block_time", "ctr_pending_integral",
    "ctr_active_integral", "ctr_dirty_integral", "ctr_grant_integral",
    "ost_valid", "client_valid",
)


@dataclasses.dataclass
class SimState:
    """All mutable engine state as one flat bag of arrays (a pytree).

    ``engine_step`` consumes and returns these; the arrays may be numpy
    (the oracle path) or jax (the fused-interval path) — the dataclass is
    agnostic.  Counters are the simulated ``/proc/fs/lustre`` surface that
    :mod:`repro.pfs.stats` probes.
    """

    now: float
    tick_index: int
    # --- tunable knobs (DIAL's theta), per OSC ------------------------
    window_pages: np.ndarray     # (n,) int64
    rpcs_in_flight: np.ndarray   # (n,) int64
    # --- per-OSC, per-op fluid state ----------------------------------
    pending: np.ndarray          # (2, n) bytes not yet packed into RPCs
    hold_age: np.ndarray
    queue_rpcs: np.ndarray       # formed, waiting for a slot
    queue_bytes: np.ndarray
    active_rpcs: np.ndarray      # dispatched, in the pipeline
    setup_work: np.ndarray       # seconds of setup left (aggregate)
    unready_bytes: np.ndarray
    ready_bytes: np.ndarray      # setup done, transferring
    active_avg_size: np.ndarray
    dispatch_time_num: np.ndarray
    randomness: np.ndarray       # EMA of workload offset jumps
    # --- write path extras --------------------------------------------
    dirty_bytes: np.ndarray      # (n,)
    grant_used: np.ndarray
    write_blocked: np.ndarray    # (n,) bool; cache full last tick
    # --- cumulative counters (the "/proc" the client can probe) -------
    ctr_bytes_done: np.ndarray
    ctr_rpcs_sent: np.ndarray
    ctr_rpc_bytes: np.ndarray
    ctr_partial_rpcs: np.ndarray
    ctr_latency_sum: np.ndarray
    ctr_rpcs_done: np.ndarray
    ctr_req_count: np.ndarray
    ctr_req_bytes: np.ndarray
    ctr_cache_hit_bytes: np.ndarray
    ctr_block_time: np.ndarray
    ctr_pending_integral: np.ndarray
    ctr_active_integral: np.ndarray
    ctr_dirty_integral: np.ndarray
    ctr_grant_integral: np.ndarray
    # --- ragged-batch validity masks (pass-through; engine never reads) ---
    ost_valid: np.ndarray       # (n_osts,) bool; phantom padded OSTs False
    client_valid: np.ndarray    # (n_clients,) bool; phantom clients False

    def copy(self) -> "SimState":
        """Deep copy (fresh numpy arrays) — engine_step mutates the copy."""
        out = {}
        for f in _STATE_FIELDS:
            v = getattr(self, f)
            out[f] = np.array(v) if isinstance(v, np.ndarray) else v
        return SimState(**out)


def init_state(topo: SimTopo) -> SimState:
    """Fresh state for a topology: Lustre-default knobs, everything idle."""
    n = topo.n_osc
    zeros2 = lambda: np.zeros((2, n))
    return SimState(
        now=0.0,
        tick_index=0,
        window_pages=np.full(n, 256, dtype=np.int64),   # Lustre default 1 MiB
        rpcs_in_flight=np.full(n, 8, dtype=np.int64),   # Lustre default
        pending=zeros2(),
        hold_age=zeros2(),
        queue_rpcs=zeros2(),
        queue_bytes=zeros2(),
        active_rpcs=zeros2(),
        setup_work=zeros2(),
        unready_bytes=zeros2(),
        ready_bytes=zeros2(),
        active_avg_size=np.full((2, n), float(PAGE_SIZE)),
        dispatch_time_num=zeros2(),
        randomness=zeros2(),
        dirty_bytes=np.zeros(n),
        grant_used=np.zeros(n),
        write_blocked=np.zeros(n, dtype=bool),
        ctr_bytes_done=zeros2(),
        ctr_rpcs_sent=zeros2(),
        ctr_rpc_bytes=zeros2(),
        ctr_partial_rpcs=zeros2(),
        ctr_latency_sum=zeros2(),
        ctr_rpcs_done=zeros2(),
        ctr_req_count=zeros2(),
        ctr_req_bytes=zeros2(),
        ctr_cache_hit_bytes=np.zeros(n),
        ctr_block_time=np.zeros(n),
        ctr_pending_integral=zeros2(),
        ctr_active_integral=zeros2(),
        ctr_dirty_integral=np.zeros(n),
        ctr_grant_integral=np.zeros(n),
        ost_valid=topo.ost_valid_mask(),
        client_valid=topo.client_valid_mask(),
    )


@dataclasses.dataclass
class Demand:
    """One tick of workload submissions, resolved to per-OSC deltas.

    Produced by :meth:`repro.pfs.workloads.WorkloadTable.demand_step`,
    which runs the closed-loop / grant-acceptance workload semantics and
    leaves only trivially-appliable updates: additive counter deltas plus
    the post-submission absolute values of the two sequentially-mixed
    fields (randomness EMA, write-blocked flags).
    """

    pending_read_add: np.ndarray    # (n,) bytes entering the read pipeline
    dirty_add: np.ndarray           # (n,) write bytes accepted into cache
    req_count_add: np.ndarray       # (2, n)
    req_bytes_add: np.ndarray       # (2, n)
    cache_hit_add: np.ndarray       # (n,)
    randomness_new: np.ndarray      # (2, n) absolute (EMA already applied)
    write_blocked_new: np.ndarray   # (n,) bool, absolute
    # |Demand| == app-visible write completions: bytes_done[WRITE] += dirty_add


_DISTURBANCE_FIELDS = ("bw_scale", "iops_scale", "bg_bytes", "nic_scale")


@dataclasses.dataclass
class Disturbance:
    """One tick of exogenous conditions the simulated cluster is under.

    These are the environment inputs no client controls or observes
    directly — the scenario lab uses them to express noisy neighbours,
    degraded or failing OSTs, and heterogeneous client links as per-tick
    schedules (leading time axis) threaded through the numpy oracle and
    the fused JAX scan identically (scan ``xs``).  The neutral values
    (scales of 1, zero background bytes) are exact arithmetic identities,
    so an undisturbed run is bit-equal to the historical engine.
    """

    bw_scale: np.ndarray    # (n_osts,) multiplier on OST service bandwidth
    iops_scale: np.ndarray  # (n_osts,) multiplier on setup/IOPS capacity
    bg_bytes: np.ndarray    # (n_osts,) background bytes arriving this tick
    nic_scale: np.ndarray   # (n_clients,) multiplier on client NIC cap

    @classmethod
    def neutral(cls, topo: "SimTopo", n_ticks: int | None = None) -> "Disturbance":
        """Identity disturbance; with ``n_ticks`` a whole neutral schedule."""
        shape = (lambda n: (n,)) if n_ticks is None else (lambda n: (n_ticks, n))
        return cls(
            bw_scale=np.ones(shape(topo.n_osts)),
            iops_scale=np.ones(shape(topo.n_osts)),
            bg_bytes=np.zeros(shape(topo.n_osts)),
            nic_scale=np.ones(shape(topo.n_clients)),
        )

    def at_tick(self, i: int) -> "Disturbance":
        """Tick ``i`` of a schedule (arrays carry a leading time axis)."""
        return Disturbance(bw_scale=self.bw_scale[i],
                           iops_scale=self.iops_scale[i],
                           bg_bytes=self.bg_bytes[i],
                           nic_scale=self.nic_scale[i])


@functools.lru_cache(maxsize=64)
def _neutral_cached(n_osts: int, n_clients: int) -> Disturbance:
    """Shared identity Disturbance per topology size — the undisturbed
    per-tick oracle path must not pay four allocations per call.  The
    cached arrays are frozen (``writeable=False``): an in-place edit by
    any caller would silently corrupt every later tick that reuses the
    cache, so mutation raises instead."""
    d = Disturbance(bw_scale=np.ones(n_osts), iops_scale=np.ones(n_osts),
                    bg_bytes=np.zeros(n_osts), nic_scale=np.ones(n_clients))
    for f in _DISTURBANCE_FIELDS:
        getattr(d, f).flags.writeable = False
    return d


# Register the state dataclasses as JAX pytrees when jax is importable so
# they thread through jit / lax.scan; numpy-only deployments skip this.
try:  # pragma: no cover - exercised implicitly by engine_jax tests
    import jax as _jax

    for _cls, _fields in ((SimState, _STATE_FIELDS),
                          (Demand, tuple(f.name for f in
                                         dataclasses.fields(Demand))),
                          (Disturbance, _DISTURBANCE_FIELDS)):
        _jax.tree_util.register_pytree_node(
            _cls,
            (lambda s, _f=_fields: (tuple(getattr(s, n) for n in _f), None)),
            (lambda aux, children, _c=_cls, _f=_fields:
             _c(**dict(zip(_f, children)))),
        )
except ImportError:  # pragma: no cover
    pass


def apply_demand(state: SimState, demand: Demand) -> None:
    """Fold one tick's workload submissions into ``state`` (in place).

    Mirrors what a sequence of ``PFSSim.submit_read`` / ``submit_write``
    calls does, given that ``demand_step`` already resolved acceptance
    and the sequential EMA / blocked-flag mixing.
    """
    state.pending[READ] += demand.pending_read_add
    state.dirty_bytes += demand.dirty_add
    state.grant_used += demand.dirty_add
    state.ctr_req_count += demand.req_count_add
    state.ctr_req_bytes += demand.req_bytes_add
    state.ctr_cache_hit_bytes += demand.cache_hit_add
    state.ctr_bytes_done[WRITE] += demand.dirty_add
    state.randomness[...] = demand.randomness_new
    state.write_blocked[...] = demand.write_blocked_new


def engine_step(params: SimParams, topo: SimTopo, state: SimState,
                demand: Demand | None = None,
                disturbance: Disturbance | None = None) -> SimState:
    """One pure engine tick: ``state' = engine_step(params, topo, state)``.

    A verbatim extraction of the historical ``PFSSim.step`` phases
    (formation -> dispatch -> OST drain -> bandwidth -> completion ->
    accounting) operating on a :class:`SimState`.  ``demand`` carries the
    tick's workload submissions; pass ``None`` when submissions were
    already folded in by the stateful wrapper (legacy ``Workload``
    objects calling ``submit_*`` on the sim).  ``disturbance`` carries
    the tick's exogenous conditions (OST degradation, background
    traffic, NIC heterogeneity); ``None`` means the neutral identity.

    The input state is never mutated; a fresh numpy state is returned.
    This function is the semantic oracle for the fused JAX path.
    """
    p = params
    dt = p.tick
    s = state.copy()
    n_osts = topo.n_osts
    osc_ost = topo.osc_ost
    osc_client = topo.osc_client
    dist = (disturbance if disturbance is not None
            else _neutral_cached(topo.n_osts, topo.n_clients))

    # (1) workloads deposit demand
    if demand is not None:
        apply_demand(s, demand)

    # write path: dirty cache continuously feeds the pending queue
    in_pipe = (s.pending[WRITE] + s.queue_bytes[WRITE]
               + s.unready_bytes[WRITE] + s.ready_bytes[WRITE])
    s.pending[WRITE] += np.maximum(s.dirty_bytes - in_pipe, 0.0)

    # (2) RPC formation: full windows pack immediately; partials wait
    # up to hold_time hoping more data shows up (Lustre plugging).
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

    # (3) dispatch up to rpcs_in_flight (reads first: sync-read bias)
    slots = np.maximum(
        s.rpcs_in_flight - (s.active_rpcs[READ] + s.active_rpcs[WRITE]),
        0.0,
    )
    for op in (READ, WRITE):
        take = np.minimum(s.queue_rpcs[op], slots)
        frac = np.divide(take, s.queue_rpcs[op],
                         out=np.zeros_like(take), where=s.queue_rpcs[op] > 0)
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

    # (4) OST setup service: `ost_setup_parallel` concurrent contexts
    # drain setup work; a separate IOPS ceiling caps completed setups.
    total_work = s.setup_work[READ] + s.setup_work[WRITE]
    ost_work = np.bincount(osc_ost, weights=total_work, minlength=n_osts)
    cap = dt * p.ost_setup_parallel * dist.iops_scale
    drain_frac_ost = np.divide(cap, ost_work,
                               out=np.ones(n_osts), where=ost_work > cap)
    # IOPS ceiling, applied on setups completed this tick per OST
    for op in (READ, WRITE):
        work = s.setup_work[op]
        drained = work * drain_frac_ost[osc_ost]
        per_rpc = p.setup_time(s.randomness[op]) + p.rtt
        setups_done = np.divide(drained, per_rpc,
                                out=np.zeros_like(drained), where=per_rpc > 0)
        ost_setups = np.bincount(osc_ost, weights=setups_done,
                                 minlength=n_osts)
        iops_cap = p.ost_iops * dt * dist.iops_scale
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

    # (5) bandwidth: OST bw fair-shared over transfer-phase RPC counts,
    # then per-client NIC cap rescales.  An OST whose service queue
    # holds far more bytes than its buffer comfort zone degrades
    # (cache thrash / request-queue overhead) -- this is the cost of
    # everyone maxing rpcs_in_flight x window at once, and the reason
    # decentralized agents must moderate under contention.
    want = s.ready_bytes[READ] + s.ready_bytes[WRITE]
    queued = (s.unready_bytes[READ] + s.unready_bytes[WRITE]
              + s.ready_bytes[READ] + s.ready_bytes[WRITE])
    ost_queued = np.bincount(osc_ost, weights=queued,
                             minlength=n_osts) + dist.bg_bytes
    over = ost_queued > p.ost_buffer_bytes
    eff = np.where(
        over,
        np.power(p.ost_buffer_bytes / np.maximum(ost_queued, 1.0),
                 p.congestion_exp),
        1.0,
    )
    active_transfer = np.where(want > BYTE_TOL,
                               s.active_rpcs[READ] + s.active_rpcs[WRITE], 0.0)
    ost_shares = np.bincount(osc_ost, weights=active_transfer,
                             minlength=n_osts)
    share = np.divide(active_transfer, ost_shares[osc_ost],
                      out=np.zeros_like(active_transfer),
                      where=ost_shares[osc_ost] > 0)
    ost_bw_eff = p.ost_bandwidth * dist.bw_scale * eff
    # background traffic is served first (it belongs to clients outside
    # the fleet; the server cannot tell it apart), shrinking this tick's
    # foreground budget.  Written as a subtraction of the background
    # share so the zero-background case keeps the historical
    # multiplication order bit for bit.
    bg_served = np.minimum(dist.bg_bytes, ost_bw_eff * dt)
    alloc = np.minimum(
        share * ost_bw_eff[osc_ost] * dt - share * bg_served[osc_ost], want)
    # redistribute leftover OST bandwidth to still-hungry OSCs
    leftover = (ost_bw_eff * dt - bg_served) - np.bincount(
        osc_ost, weights=alloc, minlength=n_osts)
    hungry = want - alloc
    ost_hungry = np.bincount(osc_ost, weights=hungry, minlength=n_osts)
    bonus_frac = np.divide(leftover, ost_hungry, out=np.zeros(n_osts),
                           where=ost_hungry > 0)
    alloc = alloc + hungry * np.minimum(bonus_frac[osc_ost], 1.0)
    # NIC cap per client
    nic_cap = p.nic_bandwidth * dist.nic_scale * dt
    client_alloc = np.bincount(osc_client, weights=alloc,
                               minlength=topo.n_clients)
    nic_frac = np.divide(nic_cap, client_alloc,
                         out=np.ones(topo.n_clients),
                         where=client_alloc > nic_cap)
    alloc = alloc * nic_frac[osc_client]

    # (6) completions
    for op in (READ, WRITE):
        frac = np.divide(s.ready_bytes[op], want,
                         out=np.zeros_like(want), where=want > 0)
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
            # flushed bytes leave the dirty cache and release grant
            s.dirty_bytes = np.maximum(s.dirty_bytes - drained, 0.0)
            s.grant_used = np.maximum(s.grant_used - drained, 0.0)
        avg_disp = np.divide(s.dispatch_time_num[op], np.maximum(prev_active, 1e-9))
        lat = np.maximum(s.now + dt - avg_disp, dt)
        s.ctr_latency_sum[op] += done_rpcs * lat
        keep = np.divide(s.active_rpcs[op], np.maximum(prev_active, 1e-9))
        s.dispatch_time_num[op] *= keep

    # blocked-writer accounting (workloads stop issuing while blocked)
    s.ctr_block_time += s.write_blocked * dt
    room = np.minimum(p.max_dirty_bytes - s.dirty_bytes,
                      p.grant_bytes - s.grant_used)
    s.write_blocked &= room < PAGE_SIZE

    # time-integrals for interval averages
    for op in (READ, WRITE):
        s.ctr_pending_integral[op] += (s.pending[op] + s.queue_bytes[op]) * dt
        s.ctr_active_integral[op] += s.active_rpcs[op] * dt
    s.ctr_dirty_integral += s.dirty_bytes * dt
    s.ctr_grant_integral += s.grant_used * dt

    s.now += dt
    s.tick_index += 1
    return s
