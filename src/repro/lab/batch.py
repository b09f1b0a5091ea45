"""Batched scenario execution: N scenarios per jitted launch.

The fused interval scan (:mod:`repro.pfs.engine_jax`) removed the
per-tick Python round trip; this module removes the per-*scenario*
process.  ``stack_scenarios`` stacks B
:class:`~repro.lab.scenarios.BuiltScenario` pytrees along a new leading
batch axis, and :class:`BatchEngine` ``vmap``-s the identical
``demand_step ∘ engine_step`` interval over that axis — hundreds of
independent scenarios advance one tuning interval in a single device
dispatch.

Structurally-identical scenarios (same topology dimensions, same
workload-table shapes) stack directly, exactly as before.  Mismatched
structures stack **ragged**: every element is padded up to a shared
bucket shape class (:func:`pad_class` — OSTs / clients / workload rows /
stripe entries rounded to the next power of two) with phantom OSTs,
clients, and workload rows whose parameters are exact arithmetic
identities (zero demand, neutral disturbance, inert rows) and whose
validity masks are off.  Padded runs pin bit-equal θ trajectories and
≤1e-6 counters against unpadded per-scenario runs (tests/test_ragged.py)
because every phantom contribution is a literal ``+ 0.0``.
:func:`bucket_scenarios` groups a heterogeneous catalog by shape class
so the whole registry executes in one fused dispatch per bucket.

In-batch DIAL tuning reuses the fleet machinery unchanged: a batch of B
scenarios with n interfaces each *is* a fleet of ``B * n`` interfaces
(every row of every fleet matrix is already built purely from one
interface's local counters), so :class:`BatchPort` exposes the stacked
state through the :class:`~repro.core.fleet.FleetPort` protocol and one
:class:`~repro.core.fleet.FleetAgent` tunes every scenario in the batch
with one forest launch per interval.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.fleet import FleetAgent
from repro.core.tuner import TunerParams
from repro.kernels.segment_reduce.ops import make_segment_sum
from repro.lab.scenarios import BuiltScenario, make_schedule
from repro.pfs.engine_jax import engine_step_jax
from repro.pfs.state import (_STATE_FIELDS, Disturbance, SimParams, SimState,
                             SimTopo, init_state)
from repro.pfs.stats import FleetStats
from repro.pfs.workloads import WorkloadState, WorkloadTable


def _tree_stack(trees):
    """Stack a list of identical-structure pytrees along a new axis 0."""
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *trees)


@dataclasses.dataclass
class ScenarioBatch:
    """B stacked scenarios: one pytree per engine-level piece.

    ``table`` / ``state`` / ``wstate`` arrays carry a leading ``(B, ...)``
    batch axis; ``specs`` keeps the per-element provenance (used to
    rebuild each element's disturbance schedule every interval).

    Ragged (pad-and-mask) batches additionally carry ``osc_cols`` — one
    int array per element listing its *real* interface columns within
    the padded layout, in original interface order.  Empty ``osc_cols``
    means nothing was padded (every column real), the historical layout.
    """

    params: SimParams
    topo: SimTopo
    table: WorkloadTable        # batched arrays
    state: SimState             # batched arrays
    wstate: WorkloadState       # batched arrays
    specs: tuple = ()           # per-element ScenarioSpec (may be empty)
    osc_cols: tuple = ()        # per-element real columns (ragged only)

    def __len__(self) -> int:
        return int(np.asarray(self.state.window_pages).shape[0])

    @property
    def n_osc(self) -> int:
        return self.topo.n_osc

    def element_cols(self, b: int) -> np.ndarray:
        """Element ``b``'s real interface columns, in original order."""
        if self.osc_cols:
            return np.asarray(self.osc_cols[b], dtype=np.int64)
        return np.arange(self.n_osc, dtype=np.int64)

    def real_tune_cols(self) -> np.ndarray:
        """Fleet columns (``b * n + osc``) of every real interface."""
        n = self.n_osc
        return np.concatenate([b * n + self.element_cols(b)
                               for b in range(len(self))])

    def pad_stats(self) -> dict:
        """Padding-waste accounting (the fuzz histogram's raw numbers)."""
        n = self.n_osc
        real = sum(len(self.element_cols(b)) for b in range(len(self)))
        total = len(self) * n
        return {"n_elems": len(self), "n_osc": n,
                "real_interfaces": int(real),
                "phantom_interfaces": int(total - real),
                "total_interfaces": int(total),
                "pad_waste": float(1.0 - real / total) if total else 0.0}

    def schedule(self, t0_tick: int, n_ticks: int) -> Disturbance:
        """Stacked ``(B, n_ticks, ...)`` disturbance schedule for one
        interval (neutral for elements without events / without specs)."""
        if self.specs:
            per = [make_schedule(s.events, self.topo, self.params,
                                 t0_tick, n_ticks) for s in self.specs]
        else:
            per = [Disturbance.neutral(self.topo, n_ticks=n_ticks)
                   for _ in range(len(self))]
        return _tree_stack(per)

    # ------------------------------------------------------------------ #
    def throughput(self, seconds: float) -> dict:
        """Per-element aggregate MB/s from the cumulative counters.

        Ragged batches sum each element's real columns by ordered
        gather, so the float summation order is exactly the unpadded
        run's — per-element figures are bit-equal, not merely close.
        """
        done = np.asarray(self.state.ctr_bytes_done)      # (B, 2, n)
        if self.osc_cols:
            read = np.array([done[b, 0, self.element_cols(b)].sum()
                             for b in range(len(self))]) / seconds / 1e6
            write = np.array([done[b, 1, self.element_cols(b)].sum()
                              for b in range(len(self))]) / seconds / 1e6
        else:
            read = done[:, 0].sum(axis=1) / seconds / 1e6
            write = done[:, 1].sum(axis=1) / seconds / 1e6
        return {"read_mbs": read, "write_mbs": write,
                "total_mbs": read + write}


# the structure fields strict stacking compares, in check order — the
# refusal message names the first mismatching one with both values
_STRUCTURE_FIELDS = ("params", "n_clients", "n_osts", "n_rows", "n_waves",
                     "n_entries")


def structure_key(b: BuiltScenario) -> tuple:
    """The structural signature batch elements must share to stack
    *without padding*.

    Physics constants, topology dimensions, and the workload-table shape
    (rows / waves / flattened stripe entries): two built scenarios with
    equal keys always stack — and hit the same compiled vmapped program
    shape — regardless of how their workload parameters, disturbance
    schedules, or initial knobs differ.  Mismatched keys stack too via
    ragged pad-and-mask bucketing (:func:`pad_class`); this key is the
    strict (``ragged=False``) grouping and the zero-waste fast path.
    """
    return (b.params, b.topo.n_clients, b.topo.n_osts,
            len(b.table), b.table.n_waves, len(b.table.entry_row))


def _structure_mismatch(built: list[BuiltScenario]):
    """First (element index, field name, value, element-0 value) whose
    structure differs from element 0's, or ``None`` if all match."""
    k0 = structure_key(built[0])
    for i, b in enumerate(built[1:], start=1):
        k = structure_key(b)
        if k != k0:
            f = next(j for j in range(len(k)) if k[j] != k0[j])
            return i, _STRUCTURE_FIELDS[f], k[f], k0[f]
    return None


def _p2(x: int) -> int:
    """Next power of two ≥ x (bucket dims quantize to powers of two so a
    heterogeneous catalog lands in a handful of shape classes)."""
    return 1 << max(int(x) - 1, 0).bit_length()


def pad_class(b: BuiltScenario) -> tuple:
    """The padded shape class ``(params, C, O, R, E, W)`` of a scenario.

    Clients / OSTs round up to the next power of two; workload rows and
    stripe entries round up to ``p2(x + 1)`` so every padded table owns
    at least one phantom row — phantom stripe entries must reference an
    inactive row to contribute exact zeros.  ``params`` rides the key
    because physics constants are baked into the compiled program and
    cannot be padded away.
    """
    return (b.params, _p2(b.topo.n_clients), _p2(b.topo.n_osts),
            _p2(len(b.table) + 1), _p2(len(b.table.entry_row) + 1),
            _p2(b.table.n_waves))


def pad_scenario(b: BuiltScenario, cls: tuple) -> BuiltScenario:
    """Pad one built scenario up to a bucket shape class.

    Every addition is an exact arithmetic identity: phantom OSTs and
    clients join the dense topology with validity masks off and
    fresh-idle per-interface state (zero queues, zero demand, neutral
    disturbance — every reduction they join adds a literal ``0.0``);
    phantom workload rows are inert (:meth:`WorkloadTable.padded`).
    Real interfaces keep their original interface *order* under the
    remap ``new = (old // O) * O_pad + old % O_pad``, so ordered
    reductions over real columns regroup nothing.
    """
    params, nc, no, nr, ne, nw = cls
    if params != b.params:
        raise ValueError("pad class params mismatch")
    topo_old = b.topo
    if (nc, no) == (topo_old.n_clients, topo_old.n_osts):
        topo = topo_old
        remap = None
    else:
        base = SimTopo.dense(nc, no)
        ost_valid = np.zeros(no, dtype=bool)
        ost_valid[:topo_old.n_osts] = topo_old.ost_valid_mask()
        client_valid = np.zeros(nc, dtype=bool)
        client_valid[:topo_old.n_clients] = topo_old.client_valid_mask()
        topo = dataclasses.replace(base, ost_valid=ost_valid,
                                   client_valid=client_valid)
        old_osc = np.arange(topo_old.n_osc, dtype=np.int64)
        remap = (old_osc // topo_old.n_osts) * no + old_osc % topo_old.n_osts

    state = init_state(topo)
    for f in _STATE_FIELDS:
        old = getattr(b.state, f)
        if f in ("now", "tick_index"):
            setattr(state, f, old)
        elif f in ("ost_valid", "client_valid"):
            pass    # init_state already took them from the padded topo
        elif remap is None:
            setattr(state, f, np.array(np.asarray(old)))
        else:
            new = getattr(state, f)
            new[..., remap] = np.asarray(old)

    table = b.table.padded(nr, ne, nw, topo.n_osc, osc_remap=remap)
    pr = nr - len(b.table)
    wstate = WorkloadState(
        issued=np.concatenate([np.asarray(b.wstate.issued, dtype=float),
                               np.zeros(pr)]),
        done_base=np.concatenate([np.asarray(b.wstate.done_base,
                                             dtype=float), np.zeros(pr)]))
    return BuiltScenario(spec=b.spec, params=b.params, topo=topo,
                         table=table, state=state, wstate=wstate)


def stack_scenarios(built: list[BuiltScenario],
                    ragged: bool = True) -> ScenarioBatch:
    """Stack built scenarios into one batch.

    Structurally-identical elements stack directly (bit-for-bit the
    historical layout, zero padding).  Mismatched structures are padded
    up to the elementwise-max :func:`pad_class` and stacked ragged —
    unless ``ragged=False``, which restores the strict refusal (the
    error names the first mismatching structure field and both values).
    ``SimParams`` must always match: physics is baked into the compiled
    program and cannot be masked off.
    """
    if not built:
        raise ValueError("empty scenario batch")
    b0 = built[0]
    for b in built[1:]:
        if b.params != b0.params:
            raise ValueError("batch elements must share SimParams "
                             "(the engine closes over element 0's)")
    mm = _structure_mismatch(built)
    if mm is not None and not ragged:
        i, field, v, v0 = mm
        raise ValueError(
            f"batch elements must share workload-table structure to "
            f"stack with ragged=False: element {i} has {field}={v} but "
            f"element 0 has {field}={v0} (drop ragged=False to pad-and-"
            f"mask mismatched structures into one bucket)")
    osc_cols: tuple = ()
    if mm is not None:
        classes = [pad_class(b) for b in built]
        cls = (b0.params,) + tuple(
            max(c[j] for c in classes) for j in range(1, 6))
        built = [pad_scenario(b, cls) for b in built]
        osc_cols = tuple(np.nonzero(b.topo.osc_valid())[0].astype(np.int64)
                         for b in built)
        b0 = built[0]
    # per-element validity masks live on the stacked state; the shared
    # static topology is the all-valid bucket shape
    topo = (b0.topo if mm is None
            else dataclasses.replace(b0.topo, ost_valid=None,
                                     client_valid=None))
    return ScenarioBatch(
        params=b0.params,
        topo=topo,
        table=_tree_stack([b.table for b in built]),
        state=_tree_stack([b.state for b in built]),
        wstate=_tree_stack([b.wstate for b in built]),
        specs=tuple(b.spec for b in built),
        osc_cols=osc_cols,
    )


def bucket_scenarios(built: list[BuiltScenario], ragged: bool = True):
    """Group a heterogeneous catalog into stackable buckets.

    Returns ``[(indices, batch), ...]`` where ``indices`` maps each
    batch element back to its position in ``built``.  With ``ragged``
    (default) scenarios group by :func:`pad_class` — the whole registry
    collapses to a handful of padded buckets, each one fused dispatch.
    With ``ragged=False`` they group by exact :func:`structure_key`
    (the historical per-structure bucketing, more buckets, no padding).
    Bucket order is deterministic: sorted by shape class, ties by first
    element index.
    """
    groups: dict = {}
    for i, b in enumerate(built):
        key = pad_class(b) if ragged else structure_key(b)
        groups.setdefault(key, []).append(i)
    out = []
    for key in sorted(groups, key=lambda k: tuple(k[1:])):
        idxs = groups[key]
        out.append((idxs, stack_scenarios([built[i] for i in idxs],
                                          ragged=ragged)))
    return out


# ---------------------------------------------------------------------- #
# vmapped fused interval
# ---------------------------------------------------------------------- #
class BatchEngine:
    """One tuning interval for the whole batch per jitted call.

    ``vmap`` of the exact :class:`~repro.pfs.engine_jax.FusedEngine`
    interval body over the batch axis (state, workload table, and
    disturbance schedule all batched), jitted once per
    (topology, table-structure, n_ticks) shape.  ``seg_backend``
    defaults to the XLA ``segment_sum`` path, which vmaps cleanly on
    every platform; the Pallas one-hot-matmul kernel remains available
    for unbatched TPU intervals via :class:`FusedEngine`.
    """

    def __init__(self, params: SimParams, topo: SimTopo, n_ticks: int,
                 seg_backend: str = "auto"):
        self.params = params
        self.topo = topo
        self.n_ticks = int(n_ticks)
        segsum = make_segment_sum(seg_backend)

        def interval(table, state, wstate, sched):
            def body(carry, dist):
                st, ws = carry
                demand, ws = table.demand_step(params, ws, st,
                                               xp=jnp, segsum=segsum)
                st = engine_step_jax(params, topo, st, demand, segsum,
                                     disturbance=dist)
                return (st, ws), None

            (state, wstate), _ = jax.lax.scan(
                body, (state, wstate), sched, length=self.n_ticks)
            return state, wstate

        self._run = jax.jit(jax.vmap(interval))

    def run_interval(self, table: WorkloadTable, state: SimState,
                     wstate: WorkloadState, sched: Disturbance):
        """Advance every element one interval; numpy in, numpy out."""
        with jax.enable_x64(True):
            args = jax.tree.map(jnp.asarray, (table, state, wstate, sched))
            jstate, jws = self._run(*args)
            jstate, jws = jax.tree.map(
                lambda x: x.block_until_ready()
                if hasattr(x, "block_until_ready") else x, (jstate, jws))
        return jax.tree.map(np.array, jstate), jax.tree.map(np.array, jws)


# ---------------------------------------------------------------------- #
# in-batch DIAL tuning: the batch as one fleet
# ---------------------------------------------------------------------- #
class BatchPort:
    """:class:`~repro.core.fleet.FleetPort` over a stacked batch.

    Interface ``(b, osc)`` of the batch is fleet column ``b * n + osc``.
    ``cols`` restricts the exposed interfaces (e.g. only the DIAL-policy
    element of an evaluation batch, or only measurement cells of a
    campaign); default is every interface of every element.
    """

    def __init__(self, batch: ScenarioBatch, cols=None):
        self.batch = batch
        if cols is None:
            # every *real* interface — identical to the historical
            # all-columns default on unpadded batches, and keeps phantom
            # padded interfaces out of probes and knob write-back
            cols = batch.real_tune_cols()
        self._cols = np.asarray(cols, dtype=np.int64)

    def osc_ids(self) -> np.ndarray:
        return self._cols

    def probe_all(self) -> FleetStats:
        s = self.batch.state
        c = self._cols

        def f2(a):  # (B, 2, n) -> (2, len(cols))
            return np.moveaxis(np.asarray(a), 1, 0).reshape(2, -1)[:, c].copy()

        def f1(a):  # (B, n) -> (len(cols),)
            return np.asarray(a).reshape(-1)[c].copy()

        return FleetStats(
            t=float(np.ravel(np.asarray(s.now))[0]),
            oscs=c,
            bytes_done=f2(s.ctr_bytes_done),
            rpcs_sent=f2(s.ctr_rpcs_sent),
            rpc_bytes=f2(s.ctr_rpc_bytes),
            partial_rpcs=f2(s.ctr_partial_rpcs),
            latency_sum=f2(s.ctr_latency_sum),
            rpcs_done=f2(s.ctr_rpcs_done),
            req_count=f2(s.ctr_req_count),
            req_bytes=f2(s.ctr_req_bytes),
            pending_integral=f2(s.ctr_pending_integral),
            active_integral=f2(s.ctr_active_integral),
            cache_hit_bytes=f1(s.ctr_cache_hit_bytes),
            block_time=f1(s.ctr_block_time),
            dirty_integral=f1(s.ctr_dirty_integral),
            grant_integral=f1(s.ctr_grant_integral),
            randomness=f2(s.randomness),
            window_pages=f1(s.window_pages).astype(np.int64),
            rpcs_in_flight=f1(s.rpcs_in_flight).astype(np.int64),
        )

    def set_knobs_many(self, osc_ids, window_pages, rpcs_in_flight) -> None:
        ids = np.atleast_1d(np.asarray(osc_ids, dtype=np.int64))
        b, o = np.divmod(ids, self.batch.n_osc)
        s = self.batch.state
        s.window_pages[b, o] = np.asarray(window_pages, dtype=np.int64)
        s.rpcs_in_flight[b, o] = np.asarray(rpcs_in_flight, dtype=np.int64)


def run_batch(batch: ScenarioBatch, model=None, seconds: float = 10.0,
              interval: float = 0.5, seg_backend: str = "auto",
              tuner_params: TunerParams | None = None,
              tune_cols=None, engine: BatchEngine | None = None,
              fused: bool = False, mesh=None, trace=None,
              intervene=None):
    """Drive a whole batch for ``seconds``, optionally DIAL-tuning.

    The batched counterpart of :func:`repro.core.fleet.run_fleet`: every
    interval is one vmapped engine launch followed (when ``model`` is
    given) by one fleet tuning tick over ``tune_cols`` (default: every
    interface of every element).  Returns the :class:`FleetAgent` (or
    ``None`` when untuned); final state lives on ``batch.state``.

    ``fused=True`` routes the whole run through the device-resident loop
    (:class:`~repro.pfs.loop_jax.FusedLoop` vmapped over the batch): one
    jitted dispatch covers every interval of engine **and** tuning, with
    each element's whole-run disturbance schedule compiled once up front
    instead of rebuilt per interval.  Knob trajectories are identical to
    the host path (tests/test_loop_fused.py); the return value is a
    :class:`~repro.pfs.loop_jax.FusedLoopResult`, whose ``decisions``
    list matches the host agent's interval-aligned records.

    ``mesh`` (fused only) shards the batch axis across a 1-D device mesh
    (:func:`repro.distributed.sharding.fleet_mesh`): each device runs
    its slice of the batch device-local, no collectives — decisions
    identical to the single-device dispatch (tests/test_shard.py).

    ``trace`` (a :class:`~repro.obs.schema.TraceConfig`) opts the run
    into telemetry.  Fused runs accumulate the records in-dispatch and
    return them on ``result.trace`` (normalize with
    :meth:`~repro.obs.schema.RunTrace.from_fused`); on the split
    tuned/untuned path the timeline covers every element while decision
    columns of never-tuned elements carry the inert placeholder record
    (``decided`` false, applied θ, zeroed gate metrics) — the lean
    engine-only program has no decision path to observe.  The host path
    mirrors decision provenance through the fleet agent's
    :class:`~repro.obs.host.HostTracer` (``fleet.trace``; no timeline —
    the interval engine exposes no per-tick state).

    ``intervene`` (fused only) is a per-interface
    :class:`~repro.pfs.loop_jax.Intervention` with ``(B, n)`` leading
    shape — the counterfactual-replay hook used by
    :mod:`repro.obs.diagnose`.  Rows of never-tuned elements are
    dropped with the element (the lean program has no decision path to
    intervene on).
    """
    steps = max(int(round(interval / batch.params.tick)), 1)
    n_intervals = int(round(seconds / interval))

    if fused:
        if model is None:
            raise ValueError("fused=True requires a model (untuned runs "
                             "gain nothing from fusing the decision loop)")
        if engine is not None:
            raise ValueError("`engine` configures the per-interval host "
                             "path; the fused path compiles its own "
                             "whole-run programs (pass seg_backend "
                             "instead)")
        return _run_batch_fused(batch, model, steps, n_intervals,
                                tuner_params, seg_backend, tune_cols,
                                mesh=mesh, trace=trace,
                                intervene=intervene)
    if intervene is not None:
        raise ValueError("intervene= rides the fused batch path — "
                         "pass fused=True")
    if mesh is not None:
        raise ValueError("mesh sharding rides the fused batch path — "
                         "pass fused=True with mesh")
    if trace is not None and model is None:
        raise ValueError("host-path tracing records decision provenance "
                         "through the fleet agent — untuned host batches "
                         "have neither (use fused=True for timelines)")

    engine = engine or BatchEngine(batch.params, batch.topo, steps,
                                   seg_backend=seg_backend)
    fleet = None
    if model is not None:
        tracer = None
        if trace is not None:
            from repro.obs.host import HostTracer
            tracer = HostTracer(trace, batch.params, batch.topo)
        fleet = FleetAgent(BatchPort(batch, cols=tune_cols), model,
                           tuner_params=tuner_params, tracer=tracer)
        fleet.trace = None
    # precompile the whole run's disturbance schedule once and slice per
    # interval — make_schedule is a pure function of the absolute tick
    # index, so slicing the full-run arrays is exactly the per-interval
    # rebuild without B Python rebuilds per interval
    full_sched = batch.schedule(0, n_intervals * steps)
    for i in range(n_intervals):
        sched = jax.tree.map(
            lambda a: a[:, i * steps:(i + 1) * steps], full_sched)
        batch.state, batch.wstate = engine.run_interval(
            batch.table, batch.state, batch.wstate, sched)
        if fleet is not None:
            fleet.tick()
    if fleet is not None and fleet.tracer is not None:
        fleet.trace = fleet.tracer.run_trace(
            fleet.oscs, interval, batch.params.tick)
    return fleet


def run_oracle(built: BuiltScenario, model, seconds: float = 10.0,
               interval: float = 0.5,
               tuner_params: TunerParams | None = None):
    """The numpy oracle of a DIAL-tuned run of one scenario.

    Each interval steps the numpy engine
    (:func:`repro.pfs.workloads.run_interval` over
    :func:`repro.pfs.state.engine_step`) through the scenario's
    disturbance schedule, then one :class:`FleetAgent` tick over every
    interface.  Shares no code with the jitted engine or the fused loop,
    so a fused run is checked against it: θ trajectories exact and
    simulated MB/s within 1e-6.  Returns ``(fleet, batch)``: the agent
    with its per-interval decisions, and a one-element
    :class:`ScenarioBatch` holding the final state (``batch.throughput``).
    """
    from repro.pfs.workloads import run_interval

    steps = max(int(round(interval / built.params.tick)), 1)
    n_intervals = int(round(seconds / interval))
    batch = stack_scenarios([built])
    fleet = FleetAgent(BatchPort(batch), model, tuner_params=tuner_params)
    sched = built.schedule(0, n_intervals * steps)
    first = lambda tree: jax.tree.map(lambda a: np.array(a[0]), tree)
    for i in range(n_intervals):
        state, wstate = run_interval(
            built.params, built.topo, built.table, first(batch.state),
            first(batch.wstate), steps,
            schedule=jax.tree.map(
                lambda a: a[i * steps:(i + 1) * steps], sched))
        batch.state, batch.wstate = jax.tree.map(
            lambda a: np.asarray(a)[None], (state, wstate))
        fleet.tick()
    return fleet, batch


# compiled fused loops, reused across run_batch calls: scenarios that
# share (model, physics, topology dims, cadence) hit the same FusedLoop
# instance, and jax.jit then caches per (table/state) *structure*, so an
# evaluate sweep compiles a handful of programs instead of one per call.
# Ragged bucketing strengthens this: every scenario in a bucket shares
# the padded topology, so the key is effectively (bucket shape, mesh).
_FUSED_LOOPS: dict = {}

# hit/miss counters for the compiled-loop cache, exposed through bench
# provenance (benchmarks/ragged_scaling.py) so padding waste and
# recompiles are observable rather than inferred
_CACHE_STATS = {"hits": 0, "misses": 0}


def loop_cache_stats() -> dict:
    """Compiled-loop cache counters: ``hits`` / ``misses`` / ``size``."""
    return {**_CACHE_STATS, "size": len(_FUSED_LOOPS)}


def reset_loop_cache_stats() -> None:
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0


def _cached_loop(params, topo, steps, model, tuner_params, seg_backend,
                 tuned: bool, mesh=None, trace=None):
    from repro.pfs.loop_jax import FusedLoop

    key = (None if model is None else id(model),
           0 if model is None else model._version,
           params, topo.n_clients, topo.n_osts,
           # same-sized topologies can differ in wiring (osc -> client /
           # OST maps); the compiled program bakes the wiring in
           np.asarray(topo.osc_client).tobytes(),
           np.asarray(topo.osc_ost).tobytes(),
           int(steps), tuner_params, seg_backend, tuned,
           mesh,    # jax Mesh hashes by (devices, axis_names)
           trace)   # TraceConfig is frozen/hashable; traced programs
    #                 have different outputs and must not alias untraced
    if key not in _FUSED_LOOPS:
        _CACHE_STATS["misses"] += 1
        if len(_FUSED_LOOPS) >= 32:          # bound the cache: evict the
            _FUSED_LOOPS.pop(next(iter(_FUSED_LOOPS)))   # oldest (FIFO)
        # the model is kept alive alongside its loop: the key uses
        # id(model), which is only unique while the object lives — a
        # cached entry must therefore pin the model so a recycled id can
        # never alias someone else's forests to this compiled program
        _FUSED_LOOPS[key] = (FusedLoop(
            params, topo, steps, model, tuner_params=tuner_params,
            seg_backend=seg_backend, batched=True, tuned=tuned,
            mesh=mesh, trace=trace), model)
    else:
        _CACHE_STATS["hits"] += 1
        _FUSED_LOOPS[key][0].timers.add("loop_cache_hit", 0.0)
    return _FUSED_LOOPS[key][0]


def _run_batch_fused(batch: ScenarioBatch, model, steps: int,
                     n_intervals: int, tuner_params, seg_backend: str,
                     tune_cols, mesh=None, trace=None, intervene=None):
    """One (or two) jitted dispatches for the whole batched run.

    Elements with at least one tuned interface go through the
    device-resident decision loop; the rest (e.g. the static-θ arms of
    an evaluate comparison) run a lean engine-only fused program — no
    featurize/forest/Algorithm-1 work for elements that can never
    decide.  Final states are scattered back in element order.
    """
    import dataclasses as _dc

    b, n = len(batch), batch.n_osc
    mask = np.zeros((b, n), dtype=bool)
    cols = (batch.real_tune_cols() if tune_cols is None
            else np.asarray(tune_cols, dtype=np.int64))
    mask[cols // n, cols % n] = True
    # the whole run's schedule, compiled once (pure function of the
    # absolute tick index -> identical to the per-interval rebuild)
    sched = batch.schedule(0, n_intervals * steps)

    t_idx = np.nonzero(mask.any(axis=1))[0]
    u_idx = np.nonzero(~mask.any(axis=1))[0]
    take = lambda tree, idx: jax.tree.map(lambda a: np.asarray(a)[idx],
                                          tree)

    loop_t = _cached_loop(batch.params, batch.topo, steps, model,
                          tuner_params, seg_backend, tuned=True, mesh=mesh,
                          trace=trace)
    if len(u_idx) == 0:
        result = loop_t.run(batch.table, batch.state, batch.wstate,
                            n_intervals, schedule=sched, tune_mask=mask,
                            intervene=intervene)
        batch.state, batch.wstate = result.state, result.wstate
        return result

    res_t = loop_t.run(take(batch.table, t_idx), take(batch.state, t_idx),
                       take(batch.wstate, t_idx), n_intervals,
                       schedule=take(sched, t_idx), tune_mask=mask[t_idx],
                       intervene=(None if intervene is None
                                  else take(intervene, t_idx)))
    loop_u = _cached_loop(batch.params, batch.topo, steps, None,
                          tuner_params, seg_backend, tuned=False, mesh=mesh,
                          trace=trace)
    res_u = loop_u.run(take(batch.table, u_idx), take(batch.state, u_idx),
                       take(batch.wstate, u_idx), n_intervals,
                       schedule=take(sched, u_idx))

    def merge(a_t, a_u):
        out = np.empty((b,) + a_t.shape[1:], dtype=a_t.dtype)
        out[t_idx] = a_t
        out[u_idx] = a_u
        return out

    state = jax.tree.map(merge, res_t.state, res_u.state)
    wstate = jax.tree.map(merge, res_t.wstate, res_u.wstate)
    # decision columns come back indexed within the tuned sub-batch;
    # remap to the caller's element order (b * n + osc fleet columns) —
    # and merge the trace to the same order so both views agree.
    for r in res_t.decisions:
        r.oscs = t_idx[r.oscs // n] * n + r.oscs % n
    merged_trace = _merge_split_trace(res_t.trace, res_u.trace, b, t_idx,
                                      u_idx, state)
    batch.state, batch.wstate = state, wstate
    return _dc.replace(res_t, state=state, wstate=wstate,
                       trace=merged_trace, hist=None)


def _merge_split_trace(tr_t, tr_u, b, t_idx, u_idx, state):
    """Reassemble the two sub-batches' traces in caller element order.

    The timeline exists on both programs and merges losslessly.
    Decision columns only exist on the tuned program: never-tuned
    elements get the inert placeholder record — ``decided`` false
    everywhere, θ = the element's applied knobs (their knobs never
    change, so the final state is the whole-run value), warmup flags
    copied from the tuned sub-batch (pure functions of the interval
    index), and zeros for the gate metrics the lean program never
    computes.
    """
    # untraced runs still carry the decisions-only ys dict (it feeds
    # result.decisions); only opt-in traces (marked by "t") merge —
    # anything else stays dropped, as before, since its leaves index
    # the tuned sub-batch
    if tr_t is None or "t" not in tr_t:
        return None

    def merge(a_t, a_u=None, fill=None):
        a_t = np.asarray(a_t)
        out = np.zeros((b,) + a_t.shape[1:], dtype=a_t.dtype)
        out[t_idx] = a_t
        if a_u is not None:
            out[u_idx] = np.asarray(a_u)
        elif fill is not None:
            out[u_idx] = fill
        return out

    theta_u = np.stack([np.asarray(state.window_pages)[u_idx],
                        np.asarray(state.rpcs_in_flight)[u_idx]],
                       axis=-1).astype(np.int64)[:, None]   # (B_u,1,n,2)
    fills = {"t": np.asarray(tr_t["t"])[0],
             "warm": np.asarray(tr_t["warm"])[0],
             "theta": theta_u, "cur_theta": theta_u}
    out = {}
    for key, v in tr_t.items():
        if key == "timeline":
            out[key] = jax.tree.map(lambda at, au: merge(at, a_u=au),
                                    v, tr_u["timeline"])
        elif key == "t":
            out[key] = merge(v, a_u=tr_u["t"])
        else:
            out[key] = merge(v, fill=fills.get(key))
    return out
