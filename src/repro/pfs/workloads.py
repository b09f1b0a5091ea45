"""Workload generators driving the PFS simulator.

Each workload models one application process group on one client:
closed-loop reader threads (issue -> wait -> issue, like POSIX sync reads)
or rate-capped writer threads (writes complete into the dirty cache until
it fills, after which the engine blocks them — Lustre's grant/dirty rule).

Generators mirror the paper's evaluation workloads:

* filebench-like single streams (SIV-A): sequential/random x 8K/1MB/16MB,
  one process, one OST — the offline training distribution;
* H5bench VPIC-IO (contiguous 1/2/3-D array writes) and BDCATS-IO
  (partial/strided/full reads) — Table II;
* DLIO BERT / Megatron read kernels with variable thread counts and OST
  spans — Fig. 3.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.kernels.segment_reduce.ops import segment_sum_np as _np_segment_sum
from repro.pfs.engine import READ, WRITE
from repro.pfs.state import (BYTE_TOL, Demand, SimParams, SimState,
                             SimTopo)


@dataclasses.dataclass
class Workload:
    """A closed/open-loop I/O stream bound to one (client, op) pair.

    Attributes:
        client: client index in the sim.
        op: READ or WRITE.
        req_size: application request size in bytes.
        randomness: 0.0 = perfectly sequential offsets, 1.0 = uniform random.
        n_threads: concurrent application threads (closed-loop depth).
        osts: OST indices the file stripes over (stripe_count = len(osts)).
        thread_rate: per-thread issue ceiling [B/s] (CPU-side cost; writers).
        duty_cycle / period: optional on/off bursting (DLIO epochs).
    """

    client: int
    op: int
    req_size: float
    randomness: float
    n_threads: int = 1
    osts: tuple = (0,)
    thread_rate: float = 1.2e9
    duty_cycle: float = 1.0
    period: float = 10.0
    name: str = "workload"

    def bind(self, sim) -> None:
        self._osc_ids = np.array([sim.osc_id(self.client, t) for t in self.osts])
        self._issued = 0.0
        self._done_base = float(sim.ctr_bytes_done[self.op, self._osc_ids].sum())
        self._rr = 0

    # ------------------------------------------------------------------ #
    def _active(self, sim) -> bool:
        if self.duty_cycle >= 1.0:
            return True
        # the tick's midpoint decides, so a tick that starts on a period
        # boundary is on whichever way ``now`` rounded
        return ((sim.now + 0.5 * sim.params.tick) % self.period
                < self.duty_cycle * self.period)

    def done_bytes(self, sim) -> float:
        return float(sim.ctr_bytes_done[self.op, self._osc_ids].sum()) - self._done_base

    def tick(self, sim, dt: float) -> None:
        if not self._active(sim):
            return
        if self.op == READ:
            # closed loop: keep n_threads * req_size bytes outstanding.
            # Sequential streams get extra depth from client readahead,
            # which pipelines ahead of the application threads.
            seq = 1.0 - self.randomness
            depth = (self.n_threads * self.req_size
                     + seq * sim.params.readahead_bytes * len(self._osc_ids))
            outstanding = self._issued - self.done_bytes(sim)
            want = depth - outstanding
            # a thread can re-issue at most thread_rate anyway
            want = min(max(want, 0.0), self.n_threads * self.thread_rate * dt)
            if want <= BYTE_TOL:
                return
            self._issue(sim, want)
        else:
            # writers throttle while the dirty cache / grants are exhausted
            if sim.write_blocked[self._osc_ids].any():
                return
            want = self.n_threads * self.thread_rate * dt
            self._issue(sim, want)

    def _issue(self, sim, nbytes: float) -> None:
        self._issued += nbytes
        per = nbytes / len(self._osc_ids)
        if self.op == READ:
            for osc in self._osc_ids:
                sim.submit_read(int(osc), per, self.randomness, self.req_size)
        else:
            got = 0.0
            for osc in self._osc_ids:
                got += sim.submit_write(int(osc), per, self.randomness,
                                        self.req_size)
            # blocked bytes are retried by the engine; settle the closed-loop
            # accounting once for the whole stripe, so a partially blocked
            # stripe can't distort the depth seen while the rest of the same
            # call is still issuing
            self._issued -= nbytes - got


# ---------------------------------------------------------------------- #
# paper workload presets
# ---------------------------------------------------------------------- #
def sequential_stream(client: int, op: int, req_size: float, ost: int = 0,
                      n_threads: int = 1) -> Workload:
    """Filebench single-stream sequential pattern (training distribution)."""
    return Workload(client=client, op=op, req_size=req_size, randomness=0.0,
                    n_threads=n_threads, osts=(ost,),
                    name=f"seq_{'r' if op == READ else 'w'}_{int(req_size)}")


def random_stream(client: int, op: int, req_size: float, ost: int = 0,
                  n_threads: int = 1) -> Workload:
    """Filebench single-stream random pattern (training distribution)."""
    return Workload(client=client, op=op, req_size=req_size, randomness=1.0,
                    n_threads=n_threads, osts=(ost,),
                    name=f"rand_{'r' if op == READ else 'w'}_{int(req_size)}")


def strided_stream(client: int, op: int, req_size: float, ost: int = 0,
                   n_threads: int = 1) -> Workload:
    return Workload(client=client, op=op, req_size=req_size, randomness=0.5,
                    n_threads=n_threads, osts=(ost,), name="strided")


def vpic_write(client: int, dims: int, osts=(0, 1, 2, 3)) -> Workload:
    """H5bench VPIC-IO: contiguous particle array writes.

    Higher dimensionality fragments the contiguous runs slightly (HDF5
    chunking), which we model as mild randomness growth.
    """
    req = {1: 16 * 2**20, 2: 8 * 2**20, 3: 4 * 2**20}[dims]
    rnd = {1: 0.0, 2: 0.06, 3: 0.12}[dims]
    return Workload(client=client, op=WRITE, req_size=req, randomness=rnd,
                    n_threads=4, osts=tuple(osts), name=f"vpic_{dims}d")


def bdcats_read(client: int, mode: str, osts=(0, 1, 2, 3)) -> Workload:
    """H5bench BDCATS-IO: reads the VPIC output back (partial/strided/full)."""
    cfg = {
        "partial": dict(req_size=1 * 2**20, randomness=0.55, n_threads=4),
        "strided": dict(req_size=2 * 2**20, randomness=0.35, n_threads=4),
        "full": dict(req_size=16 * 2**20, randomness=0.0, n_threads=4),
    }[mode]
    return Workload(client=client, op=READ, osts=tuple(osts),
                    name=f"bdcats_{mode}", **cfg)


def dlio_reader(client: int, model: str, n_threads: int, osts=(0,)) -> Workload:
    """DLIO deep-learning read kernels (Fig. 3).

    BERT: many smallish TFRecord-style reads, shuffled access (random-ish).
    Megatron: larger sequential-ish sample reads from indexed .bin files.
    Both run in epoch bursts (read batch, compute step, repeat).
    """
    if model == "bert":
        # BERT TFRecord shards: many small records, shuffled access
        return Workload(client=client, op=READ, req_size=64 * 2**10,
                        randomness=0.9, n_threads=n_threads, osts=tuple(osts),
                        duty_cycle=0.85, period=4.0, name=f"dlio_bert_t{n_threads}")
    if model == "megatron":
        return Workload(client=client, op=READ, req_size=2 * 2**20,
                        randomness=0.25, n_threads=n_threads, osts=tuple(osts),
                        duty_cycle=0.9, period=6.0, name=f"dlio_megatron_t{n_threads}")
    raise ValueError(f"unknown DLIO model {model!r}")


# ---------------------------------------------------------------------- #
# vectorized workload layer: struct-of-arrays table + fleet demand_step
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class WorkloadState:
    """The per-row mutable workload state threaded through the scan."""

    issued: np.ndarray      # (R,) closed-loop bytes issued so far
    done_base: np.ndarray   # (R,) ctr_bytes_done stripe-sum at bind time


try:  # thread WorkloadState through jit / lax.scan when jax is present
    import jax as _jax

    _jax.tree_util.register_pytree_node(
        WorkloadState,
        lambda s: ((s.issued, s.done_base), None),
        lambda aux, c: WorkloadState(issued=c[0], done_base=c[1]),
    )
except ImportError:  # pragma: no cover
    pass


@dataclasses.dataclass
class WorkloadTable:
    """Struct-of-arrays over every attached workload row.

    The per-object ``Workload.tick`` loop issues per-interface
    ``submit_read``/``submit_write`` calls, which scales linearly with
    Python-level workload count.  This table holds the same information
    as flat arrays — one row per workload, plus a flattened
    (row -> OSC) stripe scatter — so the whole fleet's demand for one
    tick is a single vectorized :meth:`demand_step`.

    Rows that can interact (same op, overlapping stripes: sequential
    randomness-EMA mixing, shared dirty-cache room, blocked-flag reads)
    are partitioned into *waves* preserving attach order; rows within a
    wave are independent and vectorize exactly.  Almost all practical
    scenarios are single-wave.

    Build with :meth:`from_workloads` (the presets above stay the row
    constructors) and pair with :meth:`init_wstate`.
    """

    # per-row static arrays (R,)
    client: np.ndarray       # int64
    op: np.ndarray           # int64, READ/WRITE
    req_size: np.ndarray     # float
    randomness: np.ndarray   # float
    n_threads: np.ndarray    # float
    thread_rate: np.ndarray  # float
    duty_cycle: np.ndarray   # float
    period: np.ndarray       # float
    stripe_len: np.ndarray   # float (len(osts) per row)
    wave: np.ndarray         # int64 conflict-free execution wave
    # flattened stripe scatter (E,) — entry e maps row entry_row[e] to
    # interface entry_osc[e]
    entry_row: np.ndarray    # int64
    entry_osc: np.ndarray    # int64
    # (R,) bool — False rows are phantom padding added by :meth:`padded`
    # (ragged-batch bucketing); they never go active and every per-entry
    # contribution they make is an exact zero
    row_valid: np.ndarray
    n_osc: int
    n_waves: int
    names: tuple = ()

    def __len__(self) -> int:
        return len(self.op)

    @classmethod
    def from_workloads(cls, workloads, topo: SimTopo) -> "WorkloadTable":
        """Append one row per :class:`Workload` (presets stay constructors)."""
        rows = list(workloads)
        r = len(rows)
        osc_sets = []
        entry_row, entry_osc = [], []
        for i, w in enumerate(rows):
            oscs = [topo.osc_id(w.client, t) for t in w.osts]
            osc_sets.append((int(w.op), frozenset(oscs)))
            entry_row.extend([i] * len(oscs))
            entry_osc.extend(oscs)
        # wave partition: a row lands one wave after the latest earlier row
        # it conflicts with (same op, stripe overlap), preserving order
        wave = np.zeros(r, dtype=np.int64)
        for i in range(r):
            for j in range(i):
                if (osc_sets[i][0] == osc_sets[j][0]
                        and osc_sets[i][1] & osc_sets[j][1]):
                    wave[i] = max(wave[i], wave[j] + 1)
        return cls(
            client=np.array([w.client for w in rows], dtype=np.int64),
            op=np.array([w.op for w in rows], dtype=np.int64),
            req_size=np.array([w.req_size for w in rows], dtype=float),
            randomness=np.array([w.randomness for w in rows], dtype=float),
            n_threads=np.array([w.n_threads for w in rows], dtype=float),
            thread_rate=np.array([w.thread_rate for w in rows], dtype=float),
            duty_cycle=np.array([w.duty_cycle for w in rows], dtype=float),
            period=np.array([w.period for w in rows], dtype=float),
            stripe_len=np.array([len(w.osts) for w in rows], dtype=float),
            wave=wave,
            entry_row=np.array(entry_row, dtype=np.int64),
            entry_osc=np.array(entry_osc, dtype=np.int64),
            row_valid=np.ones(r, dtype=bool),
            n_osc=topo.n_osc,
            n_waves=int(wave.max()) + 1 if r else 1,
            names=tuple(w.name for w in rows),
        )

    def padded(self, n_rows: int, n_entries: int, n_waves: int,
               new_n_osc: int, osc_remap=None) -> "WorkloadTable":
        """Pad to a ragged-batch bucket shape with inert phantom rows.

        Phantom rows carry exact arithmetic identities: ``duty_cycle=0``
        (never active), ``n_threads=0`` (zero issue cap), ``row_valid``
        off.  Phantom stripe entries point at the first phantom row, so
        their per-entry shares are exactly ``0.0`` and every segment-sum
        they join is unchanged bitwise.  ``osc_remap`` (old ``n_osc`` ->
        new interface id) rewires the stripe scatter when the topology
        itself was padded; extra waves beyond ``self.n_waves`` run as
        empty (exact-identity) wave iterations.
        """
        r, e = len(self), len(self.entry_row)
        if n_rows < r or n_entries < e or n_waves < self.n_waves:
            raise ValueError("padded shape must cover the existing table")
        if n_entries > e and n_rows == r:
            raise ValueError("phantom entries need at least one phantom row")
        pr = n_rows - r

        def pad_row(a, fill, dtype=None):
            return np.concatenate(
                [np.asarray(a), np.full(pr, fill, dtype=dtype or a.dtype)])

        entry_osc = np.asarray(self.entry_osc)
        if osc_remap is not None:
            entry_osc = np.asarray(osc_remap, dtype=np.int64)[entry_osc]
        pe = n_entries - e
        return WorkloadTable(
            client=pad_row(self.client, 0),
            op=pad_row(self.op, READ),
            req_size=pad_row(self.req_size, 1.0),
            randomness=pad_row(self.randomness, 0.0),
            n_threads=pad_row(self.n_threads, 0.0),
            thread_rate=pad_row(self.thread_rate, 0.0),
            duty_cycle=pad_row(self.duty_cycle, 0.0),
            period=pad_row(self.period, 1.0),
            stripe_len=pad_row(self.stripe_len, 1.0),
            wave=pad_row(self.wave, 0),
            entry_row=np.concatenate(
                [np.asarray(self.entry_row),
                 np.full(pe, r, dtype=np.int64)]),
            entry_osc=np.concatenate(
                [entry_osc, np.zeros(pe, dtype=np.int64)]),
            row_valid=np.concatenate(
                [np.asarray(self.row_valid, dtype=bool),
                 np.zeros(pr, dtype=bool)]),
            n_osc=int(new_n_osc),
            n_waves=int(n_waves),
            names=self.names,
        )

    # ------------------------------------------------------------------ #
    def _row_done(self, state, wstate, xp, segsum):
        """Per-row app-visible completed bytes (stripe sum, net of base)."""
        done_e = state.ctr_bytes_done[self.op[self.entry_row], self.entry_osc]
        return segsum(done_e, self.entry_row, len(self)) - wstate.done_base

    def init_wstate(self, state: SimState) -> WorkloadState:
        """Bind the table to a state (captures the done_bytes baseline)."""
        r = len(self)
        base = np.zeros(r)
        if r:
            done_e = np.asarray(
                state.ctr_bytes_done)[self.op[self.entry_row], self.entry_osc]
            base = _np_segment_sum(done_e, self.entry_row, r)
        return WorkloadState(issued=np.zeros(r), done_base=base)

    def done_bytes(self, state, wstate) -> np.ndarray:
        """Per-row delivered bytes — the vectorized ``Workload.done_bytes``."""
        return self._row_done(state, wstate, np, _np_segment_sum)

    # ------------------------------------------------------------------ #
    def demand_step(self, params: SimParams, wstate: WorkloadState,
                    state: SimState, xp=np, segsum=_np_segment_sum):
        """One tick of demand for the whole fleet, fully vectorized.

        Runs the exact closed-loop reader / grant-throttled writer
        semantics of ``Workload.tick`` for every row at once and resolves
        them to per-OSC deltas.  ``xp``/``segsum`` select the backend
        (numpy by default; :mod:`repro.pfs.engine_jax` passes jnp and the
        shared segment-sum helper), so the same code is the oracle and
        the jitted path.

        Returns ``(demand, wstate')`` — the caller feeds ``demand`` to
        :func:`repro.pfs.state.engine_step`.
        """
        n, r = self.n_osc, len(self)
        dt = params.tick
        now = state.now
        e_row, e_osc = self.entry_row, self.entry_osc
        slen_e = self.stripe_len[e_row]
        rand_row_e = self.randomness[e_row]
        req_floor_e = xp.maximum(self.req_size, 1.0)[e_row]

        # threaded (functional) copies of the sequentially-mixed fields
        rand_r = state.randomness[READ]
        rand_w = state.randomness[WRITE]
        blocked = state.write_blocked
        dirty = state.dirty_bytes
        grant = state.grant_used

        zero_n = xp.zeros(n)
        pend_read_add = zero_n
        dirty_add = zero_n
        cache_add = zero_n
        req_cnt_add = [zero_n, zero_n]
        req_bytes_add = [zero_n, zero_n]
        issued = wstate.issued

        active = xp.logical_and(
            xp.logical_or(
                self.duty_cycle >= 1.0,
                xp.mod(now + 0.5 * dt, self.period)
                < self.duty_cycle * self.period),
            self.row_valid)
        cap_row = self.n_threads * self.thread_rate * dt
        # wave-invariant reader inputs: reads never observe intra-tick
        # counter changes, so the stripe-summed done_bytes uses the
        # tick-start counters, and depth is static per tick
        done_e = state.ctr_bytes_done[self.op[e_row], e_osc]
        done_row = segsum(done_e, e_row, r) - wstate.done_base
        seq = 1.0 - self.randomness
        depth = (self.n_threads * self.req_size
                 + seq * params.readahead_bytes * self.stripe_len)

        for k in range(self.n_waves):
            in_wave = self.wave == k           # static mask
            # ---- closed-loop readers -------------------------------- #
            is_r = xp.logical_and(xp.logical_and(in_wave, self.op == READ),
                                  active)
            want_r = xp.clip(depth - (issued - done_row), 0.0, cap_row)
            want_r = xp.where(xp.logical_and(is_r, want_r > BYTE_TOL),
                              want_r, 0.0)
            issued = issued + want_r
            per_e = want_r[e_row] / slen_e
            pend_read_add = pend_read_add + segsum(per_e, e_osc, n)
            # randomness EMA: stripes within a wave are disjoint per op,
            # so the scatter has at most one contributor per interface
            w_e = xp.minimum(per_e / (4 * 2**20), 1.0)
            factor = 1.0 - segsum(0.2 * w_e, e_osc, n)
            contrib = segsum((0.2 * w_e) * rand_row_e, e_osc, n)
            rand_r = factor * rand_r + contrib
            inc_e = xp.where(want_r[e_row] > 0,
                             xp.maximum(per_e / req_floor_e, 1.0), 0.0)
            req_cnt_add[READ] = req_cnt_add[READ] + segsum(inc_e, e_osc, n)
            req_bytes_add[READ] = req_bytes_add[READ] + segsum(per_e, e_osc, n)
            cache_add = cache_add + segsum((1.0 - rand_row_e) * per_e,
                                           e_osc, n)

            # ---- grant-throttled writers ---------------------------- #
            blocked_any = segsum(xp.where(blocked[e_osc], 1.0, 0.0),
                                 e_row, r) > 0
            goes = xp.logical_and(
                xp.logical_and(in_wave, self.op == WRITE),
                xp.logical_and(active, xp.logical_not(blocked_any)))
            want_w = xp.where(goes, cap_row, 0.0)
            per_we = want_w[e_row] / slen_e
            want_osc = segsum(per_we, e_osc, n)
            room = xp.minimum(params.max_dirty_bytes - dirty,
                              params.grant_bytes - grant)
            accepted = xp.clip(want_osc, 0.0, xp.maximum(room, 0.0))
            dirty = dirty + accepted
            grant = grant + accepted
            dirty_add = dirty_add + accepted
            w_osc = xp.minimum(accepted / (4 * 2**20), 1.0)
            rr_osc = segsum(xp.where(per_we > 0, rand_row_e, 0.0), e_osc, n)
            rand_w = (1.0 - 0.2 * w_osc) * rand_w + (0.2 * w_osc) * rr_osc
            inc_we = xp.where(per_we > 0,
                              xp.maximum(per_we / req_floor_e, 1.0), 0.0)
            req_cnt_add[WRITE] = req_cnt_add[WRITE] + segsum(inc_we, e_osc, n)
            req_bytes_add[WRITE] = req_bytes_add[WRITE] + accepted
            submitted = want_osc > 0
            blocked = xp.where(submitted, accepted < want_osc, blocked)
            # whole-stripe closed-loop settlement (see Workload._issue):
            # only the accepted bytes count as issued, in one correction
            acc_row = segsum(xp.where(per_we > 0, accepted[e_osc], 0.0),
                             e_row, r)
            issued = issued + acc_row

        demand = Demand(
            pending_read_add=pend_read_add,
            dirty_add=dirty_add,
            req_count_add=xp.stack(req_cnt_add),
            req_bytes_add=xp.stack(req_bytes_add),
            cache_hit_add=cache_add,
            randomness_new=xp.stack([rand_r, rand_w]),
            write_blocked_new=blocked,
        )
        return demand, WorkloadState(issued=issued, done_base=wstate.done_base)


# The table is itself a pytree (arrays as children; n_osc / n_waves as
# static aux data) so the scenario lab can stack B structurally-identical
# tables and vmap demand_step over the batch axis.  ``names`` is display
# metadata only and deliberately not round-tripped through tree ops.
_TABLE_ARRAY_FIELDS = (
    "client", "op", "req_size", "randomness", "n_threads", "thread_rate",
    "duty_cycle", "period", "stripe_len", "wave", "entry_row", "entry_osc",
    "row_valid",
)

try:  # pragma: no cover - exercised implicitly by the lab batch tests
    import jax as _jax2

    _jax2.tree_util.register_pytree_node(
        WorkloadTable,
        lambda t: (tuple(getattr(t, f) for f in _TABLE_ARRAY_FIELDS),
                   (t.n_osc, t.n_waves)),
        lambda aux, children: WorkloadTable(
            **dict(zip(_TABLE_ARRAY_FIELDS, children)),
            n_osc=aux[0], n_waves=aux[1]),
    )
except ImportError:  # pragma: no cover
    pass


def table_from_sim(sim):
    """Freeze a live sim's attached workloads into (table, wstate).

    Captures each legacy :class:`Workload`'s closed-loop runtime state
    (``_issued`` / ``_done_base``) so the vectorized path continues the
    exact same trajectories mid-run.
    """
    wls = sim._workloads
    table = WorkloadTable.from_workloads(wls, sim.topo)
    wstate = WorkloadState(
        issued=np.array([w._issued for w in wls], dtype=float),
        done_base=np.array([w._done_base for w in wls], dtype=float))
    return table, wstate


def sync_workloads_from_table(sim, wstate: WorkloadState) -> None:
    """Write the table's closed-loop state back into the legacy objects,
    so ``Workload.done_bytes`` / further ``sim.step()`` keep working."""
    for i, w in enumerate(sim._workloads):
        w._issued = float(wstate.issued[i])


def run_interval(params: SimParams, topo: SimTopo, table: WorkloadTable,
                 state: SimState, wstate: WorkloadState, n_ticks: int,
                 schedule=None):
    """Numpy reference interval runner over the vectorized workload table.

    Steps ``n_ticks`` of ``demand_step`` + :func:`engine_step` — the same
    schedule the fused JAX scan executes, on the oracle backend.
    ``schedule`` is an optional :class:`~repro.pfs.state.Disturbance`
    with a leading ``(n_ticks, ...)`` time axis; tick ``i`` consumes row
    ``i``, mirroring the scan's ``xs`` consumption exactly.
    """
    from repro.pfs.state import engine_step
    for i in range(n_ticks):
        demand, wstate = table.demand_step(params, wstate, state)
        dist = None if schedule is None else schedule.at_tick(i)
        state = engine_step(params, topo, state, demand, disturbance=dist)
    return state, wstate
