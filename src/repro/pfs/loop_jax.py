"""Device-resident DIAL decision loop: one ``jit`` per tuning *run*.

The host loop (:func:`repro.core.fleet.run_fleet`) pays one device
round trip per tuning interval: the jitted engine scan stops, the whole
``SimState`` converts to numpy, the fleet agent differences/featurizes
on the host, scores with one more jitted launch, runs Algorithm 1 in
numpy, and re-uploads the knobs.  Per paper Table III the decision path
itself budgets 10-13.5 ms per interface — cheap — so at fleet scale the
loop is dominated by dispatch and transfer, not compute.

This module folds the *entire* closed loop into one compiled program:

    lax.scan over intervals
      └─ lax.scan over ticks      demand_step ∘ engine_step_jax
      └─ probe                    counters read straight off SimState
      └─ snapshot                 :func:`repro.core.metrics.snapshot_arrays`
                                  (the literal oracle arithmetic, xp=jnp)
      └─ features                 history ‖ θ ‖ Δθ, float64 → float32
                                  (same rounding as the host matrix)
      └─ forest scoring           :func:`paired_scorer`, gather-free —
                                  both ops, all interfaces × configs,
                                  one pass (the host fleet predictor's
                                  scorer, so the same bits)
      └─ Algorithm 1              :func:`repro.core.tuner.score_greedy_arrays`
                                  (the literal oracle reductions, xp=jnp)
      └─ gating + write-back      volume/steadiness masks, knob update on
                                  the in-scan ``SimState``

so ``N`` intervals of engine + tuning execute as a single jitted
dispatch (:class:`FusedLoop`), and a whole batch of scenarios vmaps over
it (``batched=True``), each element carrying its own precompiled
disturbance schedule — no per-interval ``make_schedule`` rebuild.

Equivalence: the loop is pinned against the (bug-fixed)
:class:`~repro.core.fleet.FleetAgent` oracle — identical knob
trajectories (θ exact) and probe counters (≤1e-6 relative, observed
~1e-15) over multi-interval mixed-workload scenarios on both engine
backends (tests/test_loop_fused.py).

Scale-out: ``mesh=`` shards the batch axis of a ``batched=True`` loop
over a 1-D device mesh with ``shard_map`` (axis
:data:`repro.distributed.sharding.FLEET_AXIS`).  Because every DIAL
decision reads only its own interface's local counters, the per-shard
programs are fully independent — no collectives anywhere in the scanned
body — so the sharded program is the vmapped program split across
devices, and θ trajectories stay *exactly* equal to the single-device
run (tests/test_shard.py).  ``SimState``/``WorkloadState`` buffers are
donated into the dispatch (``donate_argnums``) so a fleet's state is
held once, not twice, at peak.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.config_space import SPACE, ConfigSpace
from repro.distributed.sharding import pad_fleet, unpad_fleet
from repro.obs.schema import TraceConfig, timeline_tap
from repro.obs.spans import span
from repro.core.metrics import (N_READ, N_WRITE, READ_KNOB_IDX,
                                WRITE_KNOB_IDX, snapshot_arrays)
from repro.core.model import DIALModel
from repro.core.tuner import TunerParams, score_greedy_arrays
from repro.kernels.gbdt_forest.dense import paired_scorer
from repro.kernels.segment_reduce.ops import make_segment_sum
from repro.pfs.engine_jax import engine_step_jax
from repro.pfs.state import (READ, WRITE, Disturbance, SimParams, SimState,
                             SimTopo)
from repro.pfs.workloads import WorkloadState, WorkloadTable


class Intervention(NamedTuple):
    """Per-interface counterfactual knobs for one fused run.

    The diagnosis engine (:mod:`repro.obs.diagnose`) re-runs a scenario
    under these interventions and diffs the resulting traces against
    the factual run.  They ride the same mechanism as the trace taps:
    one extra run-constant input pytree on :meth:`FusedLoop.run`, with
    the unintervened graph (``intervene=None``) literally unchanged.
    Every field is built from ``jnp.where``/boolean masks whose neutral
    values are exact identities, so an *all-neutral* intervention
    reproduces the factual run bit-for-bit (θ exact, counters ≤1e-6 —
    tests/test_diagnose.py pins this on the fused, batched, and
    sharded paths).

    ``pin_mask``/``pin_theta``  after every interval's write-back the
                                interface's knobs are forced to
                                ``pin_theta`` — the best-static-oracle
                                replay (pin from t=0 by also building
                                the scenario with ``initial_theta`` =
                                the pin).  The decision graph still
                                runs, so the trace shows what DIAL
                                *would* have chosen on the pinned
                                trajectory.
    ``force_gates``             the volume and steadiness gates are
                                treated as open (warmup and the tune
                                mask still apply) — decisions that were
                                gate-blocked in the factual run fire.
    ``freeze``                  decisions are never applied (θ holds at
                                its initial value) — DIAL's own knob
                                churn, its in-loop exploration, zeroed.

    Shapes: ``(n,)`` bool masks and ``(n, 2)`` pinned knobs per
    interface; batched loops take a leading batch axis like every other
    per-element input.
    """

    pin_mask: np.ndarray
    pin_theta: np.ndarray
    force_gates: np.ndarray
    freeze: np.ndarray

    @classmethod
    def neutral(cls, n: int, batch: int | None = None) -> "Intervention":
        """The do-nothing intervention (the bit-neutrality arm)."""
        lead = (n,) if batch is None else (int(batch), n)
        return cls(pin_mask=np.zeros(lead, dtype=bool),
                   pin_theta=np.zeros(lead + (2,), dtype=np.int64),
                   force_gates=np.zeros(lead, dtype=bool),
                   freeze=np.zeros(lead, dtype=bool))

    @classmethod
    def pin(cls, n: int, theta, batch: int | None = None) -> "Intervention":
        """Pin every interface to ``theta = (window_pages, rpcs)``."""
        iv = cls.neutral(n, batch=batch)
        return iv._replace(
            pin_mask=np.ones_like(iv.pin_mask),
            pin_theta=np.broadcast_to(
                np.asarray(theta, dtype=np.int64),
                iv.pin_theta.shape).copy())

    @classmethod
    def gates_open(cls, n: int, batch: int | None = None) -> "Intervention":
        iv = cls.neutral(n, batch=batch)
        return iv._replace(force_gates=np.ones_like(iv.force_gates))

    @classmethod
    def freeze_theta(cls, n: int,
                     batch: int | None = None) -> "Intervention":
        iv = cls.neutral(n, batch=batch)
        return iv._replace(freeze=np.ones_like(iv.freeze))


class Probe(NamedTuple):
    """Cumulative counters the decision loop reads off ``SimState``.

    Field names mirror :class:`repro.pfs.stats.FleetStats` so
    :func:`repro.core.metrics.snapshot_arrays` consumes either — a probe
    here is zero-copy views of the in-scan state, not a host transfer.
    """

    t: jnp.ndarray
    bytes_done: jnp.ndarray
    rpcs_sent: jnp.ndarray
    rpc_bytes: jnp.ndarray
    partial_rpcs: jnp.ndarray
    latency_sum: jnp.ndarray
    rpcs_done: jnp.ndarray
    req_count: jnp.ndarray
    req_bytes: jnp.ndarray
    pending_integral: jnp.ndarray
    active_integral: jnp.ndarray
    cache_hit_bytes: jnp.ndarray
    block_time: jnp.ndarray
    dirty_integral: jnp.ndarray
    grant_integral: jnp.ndarray
    randomness: jnp.ndarray
    window_pages: jnp.ndarray
    rpcs_in_flight: jnp.ndarray


def probe_state(state: SimState) -> Probe:
    """The fleet probe as views of the (possibly traced) state arrays."""
    return Probe(
        t=state.now,
        bytes_done=state.ctr_bytes_done,
        rpcs_sent=state.ctr_rpcs_sent,
        rpc_bytes=state.ctr_rpc_bytes,
        partial_rpcs=state.ctr_partial_rpcs,
        latency_sum=state.ctr_latency_sum,
        rpcs_done=state.ctr_rpcs_done,
        req_count=state.ctr_req_count,
        req_bytes=state.ctr_req_bytes,
        pending_integral=state.ctr_pending_integral,
        active_integral=state.ctr_active_integral,
        cache_hit_bytes=state.ctr_cache_hit_bytes,
        block_time=state.ctr_block_time,
        dirty_integral=state.ctr_dirty_integral,
        grant_integral=state.ctr_grant_integral,
        randomness=state.randomness,
        window_pages=state.window_pages,
        rpcs_in_flight=state.rpcs_in_flight,
    )


def conditional_score_greedy_jnp(probs, ops, current,
                                 space: ConfigSpace = SPACE,
                                 params: TunerParams | None = None):
    """Batched Algorithm 1 on the JAX backend (the fused-loop tuner).

    Same signature shape as
    :func:`repro.core.tuner.conditional_score_greedy_batch`; returns
    numpy ``(theta, changed, n_candidates, score)``.  Exists mainly so
    the property tests can pin the in-``jit`` Algorithm 1 against both
    the scalar and the batched numpy oracles on adversarial rows.
    """
    params = params if params is not None else TunerParams()
    with jax.enable_x64(True):
        out = score_greedy_arrays(
            jnp.asarray(probs, dtype=jnp.float64),
            jnp.asarray(ops),
            jnp.asarray(current),
            jnp.asarray(space.as_array()),
            params, xp=jnp)
        return tuple(np.asarray(x) for x in out)


@dataclasses.dataclass
class FusedLoopResult:
    """Everything one fused run produced, already back on the host.

    ``decisions`` carries one :class:`~repro.core.fleet.FleetTickResult`
    per interval (empty results for gated intervals), aligned with
    interval indices exactly like the bug-fixed
    :attr:`FleetAgent.decisions` — so every trajectory consumer works on
    either path unchanged.
    """

    state: SimState
    wstate: WorkloadState
    trace: dict | None
    decisions: list
    # final (k+1)-deep snapshot history (read/write matrices + volumes)
    # and the interval length — what a host agent needs to continue
    # ticking after the fused run without re-warming (None when untuned)
    hist: tuple | None = None
    interval_seconds: float = 0.0
    n_run: int = 0

    @property
    def n_intervals(self) -> int:
        return len(self.decisions) if self.decisions else self.n_run


def decisions_from_trace(trace: dict) -> list:
    """Host-side per-interval decision records from a fused trace.

    Batched traces (leaves ``(B, N, ...)``) flatten the batch axis into
    fleet columns ``b * n + osc`` — the same layout
    :class:`~repro.lab.batch.BatchPort` exposes to the host agent.
    """
    from repro.core.fleet import FleetTickResult
    from repro.core.tuner import FleetDecisions

    if np.asarray(trace["decided"]).ndim == 3:  # (B, N, n) -> (N, B*n)
        def flat(x):
            x = np.moveaxis(np.asarray(x), 0, 1)
            return x.reshape(x.shape[0], -1, *x.shape[3:])
    else:
        flat = np.asarray
    decided = flat(trace["decided"])
    ops = flat(trace["ops"])
    theta = flat(trace["theta"])
    changed = flat(trace["changed"])
    n_cand = flat(trace["n_candidates"])
    score = flat(trace["score"])
    probs = flat(trace["probs"])

    out = []
    for i in range(decided.shape[0]):
        rows = np.nonzero(decided[i])[0]
        out.append(FleetTickResult(
            oscs=rows.astype(np.int64),
            ops=ops[i][rows].astype(np.int64),
            decisions=FleetDecisions(
                theta=theta[i][rows].astype(np.int64),
                changed=changed[i][rows].astype(bool),
                n_candidates=n_cand[i][rows].astype(np.int64),
                score=score[i][rows].astype(np.float64),
                probs=probs[i][rows].astype(np.float64))))
    return out


class FusedLoop:
    """N intervals of engine + DIAL tuning per jitted dispatch.

    One instance compiles one ``(topology, table structure, steps,
    n_intervals)`` signature; repeated :meth:`run` calls with the same
    shapes reuse the compiled program.  ``batched=True`` vmaps the whole
    loop over a leading batch axis on table/state/wstate/schedule/mask
    (the scenario-lab fan-out); the forests and tuner constants are
    closed over unbatched.  ``mesh=`` additionally shards that batch
    axis across a 1-D device mesh — the forests stay closed-over
    (replicated to every device by jit), each shard runs its slice of
    the fleet with zero cross-device communication, and :meth:`run`
    pads a non-divisible batch with masked phantom elements it strips
    from every output.

    Decentralization is untouched: every interface's decision still
    reads only that interface's local counters — the fusion is an
    execution strategy, exactly like :class:`~repro.core.fleet.FleetAgent`.
    """

    def __init__(self, params: SimParams, topo: SimTopo,
                 steps_per_interval: int, model: DIALModel | None,
                 space: ConfigSpace = SPACE,
                 tuner_params: TunerParams | None = None,
                 k: int = 1,
                 min_volume_bytes: float = 256 * 1024,
                 warmup_intervals: int = 2,
                 seg_backend: str = "auto",
                 batched: bool = False,
                 tuned: bool = True,
                 mesh: Mesh | None = None,
                 trace: TraceConfig | None = None):
        self.params = params
        self.topo = topo
        self.steps = int(steps_per_interval)
        self.space = space
        self.tuner_params = (tuner_params if tuner_params is not None
                             else TunerParams())
        self.k = int(k)
        self.min_volume = float(min_volume_bytes)
        self.warmup = int(warmup_intervals)
        self.batched = bool(batched)
        self.mesh = mesh
        # opt-in telemetry: None compiles the exact untraced graph (the
        # branch below is taken at trace time, so an untraced loop pays
        # literally nothing); a TraceConfig adds scan *outputs* only —
        # the decision arithmetic is shared, never forked
        self.trace_config = trace
        if mesh is not None and not self.batched:
            raise ValueError("mesh sharding needs batched=True — the "
                             "fleet axis being sharded *is* the batch "
                             "axis")
        if mesh is not None and len(mesh.axis_names) != 1:
            raise ValueError(f"FusedLoop shards one batch axis; got a "
                             f"{len(mesh.axis_names)}-D mesh "
                             f"{mesh.axis_names} (want fleet_mesh())")
        # tuned=False compiles the lean engine-only run (no decision
        # graph at all) — used for the untuned elements of a split batch,
        # where paying featurize/forest/Algorithm-1 per element would
        # waste most of the dispatch (e.g. the 24 static arms of an
        # evaluate comparison)
        self.tuned = bool(tuned)
        if self.tuned and model is None:
            raise ValueError("a tuned FusedLoop needs a model")
        segsum = make_segment_sum(seg_backend)

        n = topo.n_osc
        m = len(space)
        if self.tuned:
            feature, threshold, leaf, base, _, n_features = \
                model.paired_arrays()
            score_forests = paired_scorer(feature, threshold, leaf, base)
            # constants must keep f64 (oracle parity)
            with jax.enable_x64(True):
                theta_raw = jnp.asarray(space.as_array())        # f64
                theta_feats = jnp.asarray(space.as_features())   # log2
            kp1 = self.k + 1
            dim_r = N_READ * kp1 + 4
            dim_w = N_WRITE * kp1 + 4
            if n_features < max(dim_r, dim_w):
                raise ValueError(
                    f"model expects {n_features} features but k={self.k} "
                    f"histories need {max(dim_r, dim_w)} — model trained "
                    f"with a different history length?")
        else:
            kp1 = self.k + 1
        tp = self.tuner_params
        warm_from = self.warmup + self.k + 1   # first deciding interval
        pfsp, pfst = params, topo

        def features(hist, n_feat, knob_idx):
            """(k+1, n, N) history -> (n*M, dim) float32, host layout."""
            h2 = jnp.moveaxis(hist, 0, 1).reshape(n, kp1 * n_feat)
            cur = h2[:, [self.k * n_feat + knob_idx[0],
                         self.k * n_feat + knob_idx[1]]]      # (n, 2)
            x64 = jnp.concatenate([
                jnp.broadcast_to(h2[:, None, :], (n, m, h2.shape[1])),
                jnp.broadcast_to(theta_feats[None], (n, m, 2)),
                theta_feats[None] - cur[:, None, :],
            ], axis=2)
            # float64 -> float32 exactly where the host path stores into
            # its float32 matrix (same rounding, same bits)
            return x64.astype(jnp.float32).reshape(n * m, -1)

        tcfg = self.trace_config
        tap_timeline = tcfg is not None and tcfg.timeline \
            and self.steps >= tcfg.stride

        def tick_body(table):
            def body(carry, dist):
                st, ws = carry
                with jax.named_scope("dial.demand"):
                    demand, ws = table.demand_step(pfsp, ws, st,
                                                   xp=jnp, segsum=segsum)
                with jax.named_scope("dial.engine"):
                    st = engine_step_jax(pfsp, pfst, st, demand, segsum,
                                         disturbance=dist)
                return (st, ws), None
            return body

        def run_ticks(table, state, wstate, dist):
            """One interval of engine ticks -> (state, wstate, taps).

            Untraced: the original single scan over ``steps`` ticks —
            byte-identical graph.  Traced with timeline: the same tick
            body scanned in ``stride``-tick chunks, one
            :func:`timeline_tap` per chunk boundary as scan output (so
            the tap compute is paid once per ``stride`` ticks, not per
            tick, and vmap/shard_map stack it like any other ys).
            Every branch runs under the ``dial.tick`` scope.
            """
            with jax.named_scope("dial.tick"):
                return _run_ticks(table, state, wstate, dist)

        def _run_ticks(table, state, wstate, dist):
            body = tick_body(table)
            if not tap_timeline:
                (state, wstate), _ = jax.lax.scan(
                    body, (state, wstate), dist, length=self.steps)
                return state, wstate, None
            stride = tcfg.stride
            n_chunks = self.steps // stride

            def chunk(carry, dch):
                carry, _ = jax.lax.scan(body, carry, dch, length=stride)
                st, _ = carry
                tap = timeline_tap(pfsp, pfst, st,
                                   jax.tree.map(lambda a: a[-1], dch),
                                   xp=jnp, segsum=segsum)
                return carry, tap

            dmain = jax.tree.map(
                lambda a: a[:n_chunks * stride].reshape(
                    (n_chunks, stride) + a.shape[1:]), dist)
            (state, wstate), taps = jax.lax.scan(
                chunk, (state, wstate), dmain, length=n_chunks)
            rem = self.steps - n_chunks * stride
            if rem:
                drem = jax.tree.map(lambda a: a[n_chunks * stride:], dist)
                (state, wstate), _ = jax.lax.scan(
                    body, (state, wstate), drem, length=rem)
            return state, wstate, taps

        def run_untuned(table, state, wstate, sched):
            def interval(carry, dist):
                st, ws = carry
                st, ws, taps = run_ticks(table, st, ws, dist)
                if tcfg is None:
                    return (st, ws), None
                ys = {"t": st.now}
                if taps is not None:
                    ys["timeline"] = taps
                return (st, ws), ys
            (state, wstate), trace = jax.lax.scan(
                interval, (state, wstate), sched)
            if tcfg is None:
                return state, wstate
            return state, wstate, trace

        def run(table, state, wstate, sched, tune_mask, iv=None):
            hist0 = (jnp.zeros((kp1, n, N_READ)),
                     jnp.zeros((kp1, n, N_WRITE)),
                     jnp.zeros((kp1, n)), jnp.zeros((kp1, n)))

            def interval(carry, dist):
                state, wstate, prev, hist, tick = carry
                state, wstate, taps = run_ticks(table, state, wstate, dist)

                # the decision step, from probe to knob write-back
                with jax.named_scope("dial.decide"):
                    # probe + snapshot: the oracle arithmetic, on device
                    cur = probe_state(state)
                    _, snap_r, snap_w, vol_r, vol_w = snapshot_arrays(
                        prev, cur, xp=jnp)
                    hr, hw, hrv, hwv = hist
                    hist = (jnp.concatenate([hr[1:], snap_r[None]]),
                            jnp.concatenate([hw[1:], snap_w[None]]),
                            jnp.concatenate([hrv[1:], vol_r[None]]),
                            jnp.concatenate([hwv[1:], vol_w[None]]))
                    hr, hw, hrv, hwv = hist
                    tick = tick + 1

                    # gating masks (same predicates as FleetAgent.tick)
                    ops = jnp.where(vol_r >= vol_w, READ, WRITE)
                    active = jnp.maximum(vol_r, vol_w) >= self.min_volume
                    v0 = jnp.where(ops == READ, hrv[0], hwv[0])
                    v1 = jnp.where(ops == READ, vol_r, vol_w)
                    ratio = v1 / jnp.maximum(v0, 1.0)
                    steady = (ratio >= 0.5) & (ratio <= 2.0)
                    warm = tick >= warm_from
                    # interventions (iv) are a trace-time branch: iv=None
                    # compiles the exact unintervened graph, and the
                    # neutral intervention is an arithmetic identity (all
                    # masks False) — counterfactual replays stay diffable
                    # row-for-row against the factual run
                    gate_ok = active & steady
                    if iv is not None:
                        gate_ok = gate_ok | iv.force_gates
                    decide = gate_ok & warm & tune_mask

                    # features + one fused paired-forest pass for all rows
                    with jax.named_scope("dial.features"):
                        x_r = features(hr, N_READ, READ_KNOB_IDX)
                        x_w = features(hw, N_WRITE, WRITE_KNOB_IDX)
                        x_r = jnp.pad(x_r, ((0, 0), (0, n_features - dim_r)))
                        x_w = jnp.pad(x_w, ((0, 0), (0, n_features - dim_w)))
                        op_rows = jnp.repeat(ops, m)
                        x = jnp.where((op_rows == READ)[:, None], x_r, x_w)
                    with jax.named_scope("dial.forest"):
                        margin = score_forests(x, op_rows)
                        p32 = 1.0 / (1.0 + jnp.exp(
                            -jnp.clip(margin, -30.0, 30.0)))
                        probs = p32.astype(jnp.float64).reshape(n, m)

                    # Algorithm 1 (the oracle reductions) + knob
                    # write-back; `current` comes from the probe itself,
                    # never a shadow
                    with jax.named_scope("dial.alg1"):
                        cur_theta = jnp.stack([state.window_pages,
                                               state.rpcs_in_flight], axis=1)
                        theta, changed, n_cand, score = score_greedy_arrays(
                            probs, ops, cur_theta, theta_raw, tp, xp=jnp)
                        apply = decide & changed
                        if iv is not None:
                            apply = apply & ~iv.freeze
                        new_wp = jnp.where(apply, theta[:, 0],
                                           state.window_pages)
                        new_rf = jnp.where(apply, theta[:, 1],
                                           state.rpcs_in_flight)
                        if iv is not None:
                            new_wp = jnp.where(iv.pin_mask,
                                               iv.pin_theta[:, 0], new_wp)
                            new_rf = jnp.where(iv.pin_mask,
                                               iv.pin_theta[:, 1], new_rf)
                        state = dataclasses.replace(
                            state, window_pages=new_wp,
                            rpcs_in_flight=new_rf)

                    ys = {"decided": decide, "ops": ops, "theta": theta,
                          "changed": changed, "n_candidates": n_cand,
                          "score": score, "probs": probs}
                    if tcfg is not None:
                        # provenance extras: every value already exists
                        # in the decision graph — tracing adds outputs,
                        # never arithmetic (bit-neutrality,
                        # tests/test_obs.py)
                        ys.update({"t": state.now, "vol_r": vol_r,
                                   "vol_w": vol_w, "active": active,
                                   "steady": steady, "warm": warm,
                                   "ratio": ratio, "cur_theta": cur_theta})
                        if taps is not None:
                            ys["timeline"] = taps
                return (state, wstate, cur, hist, tick), ys

            tick0 = jnp.asarray(0, dtype=jnp.int64)
            if self.mesh is not None:
                # run constants join a carry that varies over the fleet
                # axis; shard_map's type check needs them marked so
                hist0, tick0 = jax.lax.pcast(
                    (hist0, tick0), self.mesh.axis_names[0], to="varying")
            carry0 = (state, wstate, probe_state(state), hist0, tick0)
            (state, wstate, _, hist, _), trace = jax.lax.scan(
                interval, carry0, sched)
            return state, wstate, trace, hist

        fn = run if self.tuned else run_untuned
        if self.batched:
            fn = jax.vmap(fn)
        self._fn = fn
        # per-arity jitted programs: the tuned loop optionally takes an
        # Intervention pytree as a sixth argument (counterfactual
        # replays, repro.obs.diagnose); shard_map needs one in_spec per
        # call-time argument, so the wrapped callable is built per arity
        # and cached.  donate state + wstate: the engine consumes its
        # own previous state, so at fleet scale keeping the input alive
        # across the dispatch would double peak device memory for no
        # reader.
        self._jits: dict = {}
        self._run = self._get_run(5 if self.tuned else 4)

    def _get_run(self, n_args: int):
        if n_args not in self._jits:
            fn = self._fn
            if self.mesh is not None:
                # one spec per argument pytree, prefix-broadcast to
                # every leaf: the leading batch axis shards, everything
                # trailing (interfaces, workload rows, ticks) stays
                # device-local.  The scanned body has no collectives,
                # so each shard is an independent fleet slice — the
                # paper's decentralization, literal in the partitioning.
                spec = PartitionSpec(self.mesh.axis_names[0])
                fn = jax.shard_map(fn, mesh=self.mesh,
                               in_specs=(spec,) * n_args, out_specs=spec)
            self._jits[n_args] = jax.jit(fn, donate_argnums=(1, 2))
        return self._jits[n_args]

    def lower(self, *args):
        """Lower the jitted run without running it.

        ``args`` are :meth:`run`'s device-side arguments ``(table, state,
        wstate, schedule, [tune_mask, [intervention]])`` — arrays or
        ``jax.ShapeDtypeStruct``s, the schedule already shaped per
        interval, batched and sharded as the loop expects.  Returns the
        ``jax.stages.Lowered``; ``.compile()`` on it shows what the
        target's compiler accepts and emits (kernels, collectives,
        memory) without a dispatch.
        """
        with jax.enable_x64(True):
            return self._get_run(len(args)).lower(*args)

    # ------------------------------------------------------------------ #
    def run_trace(self, result: "FusedLoopResult"):
        """Normalize a traced result to a :class:`~repro.obs.schema.RunTrace`."""
        from repro.obs.schema import RunTrace
        if self.trace_config is None:
            raise ValueError("loop was built without trace=TraceConfig(...)")
        return RunTrace.from_fused(result, self.trace_config,
                                   self.params.tick)

    # ------------------------------------------------------------------ #
    def neutral_schedule(self, n_intervals: int) -> Disturbance:
        """Whole-run identity schedule with a flat leading time axis."""
        return Disturbance.neutral(self.topo,
                                   n_ticks=n_intervals * self.steps)

    def _shape_schedule(self, sched: Disturbance,
                        n_intervals: int) -> Disturbance:
        """Flat ``(…, total_ticks, …)`` -> per-interval scan ``xs``."""
        t_ax = 1 if self.batched else 0

        def reshape(a):
            a = np.asarray(a)
            lead = a.shape[:t_ax]
            return a.reshape(lead + (n_intervals, self.steps)
                             + a.shape[t_ax + 1:])
        return jax.tree.map(reshape, sched)

    def _shape_args(self, table, state, wstate, n_intervals, schedule,
                    tune_mask, intervene):
        """:meth:`run`'s host inputs as the program takes them, and the
        phantom elements padded on for the mesh."""
        if schedule is None:
            schedule = self.neutral_schedule(n_intervals)
            if self.batched:
                b = np.asarray(state.window_pages).shape[0]
                schedule = jax.tree.map(
                    lambda a: np.broadcast_to(a, (b,) + a.shape), schedule)
        sched = self._shape_schedule(schedule, n_intervals)
        args = (table, state, wstate, sched)
        n_pad = 0
        if self.mesh is not None:
            args, n_pad = pad_fleet(args, self.mesh.devices.size)
        if self.tuned:
            if tune_mask is None:
                # default: every *valid* interface decides.  The state's
                # ragged-batch masks (all-true for unpadded runs, so this
                # is the historical all-ones mask) keep phantom padded
                # interfaces out of Algorithm 1, gating, and write-back
                # — they get zero trace weight because they never decide.
                cv = np.asarray(state.client_valid, dtype=bool)
                ov = np.asarray(state.ost_valid, dtype=bool)
                tune_mask = (cv[..., self.topo.osc_client]
                             & ov[..., self.topo.osc_ost])
            tune_mask = np.asarray(tune_mask, dtype=bool)
            if n_pad:
                tune_mask = np.concatenate(
                    [tune_mask,
                     np.zeros((n_pad,) + tune_mask.shape[1:], dtype=bool)])
            args = args + (tune_mask,)
            if intervene is not None:
                if n_pad:
                    # phantom rows get the neutral intervention: they
                    # never decide, and neutral masks are arithmetic
                    # identities, so padding cannot perturb anything.
                    intervene = jax.tree.map(
                        lambda a: np.concatenate(
                            [np.asarray(a),
                             np.zeros((n_pad,) + np.asarray(a).shape[1:],
                                      dtype=np.asarray(a).dtype)]),
                        intervene)
                args = args + (intervene,)
        return args, n_pad

    def run(self, table: WorkloadTable, state: SimState,
            wstate: WorkloadState, n_intervals: int,
            schedule: Disturbance | None = None,
            tune_mask: np.ndarray | None = None,
            intervene: "Intervention | None" = None) -> FusedLoopResult:
        """Advance ``n_intervals`` of engine + tuning in one dispatch.

        ``schedule`` is a whole-run :class:`Disturbance` with a flat
        leading ``(n_intervals * steps, ...)`` time axis (batched: a
        ``(B, total_ticks, ...)`` stack) — compiled **once** by the
        caller, not rebuilt per interval.  ``tune_mask`` restricts which
        interfaces may decide (default: all interfaces the state's
        ragged-batch validity masks mark real).  Numpy in, numpy out.

        ``intervene`` (tuned loops only) applies a per-interface
        :class:`Intervention` counterfactual — ``None`` leaves the
        compiled program literally unchanged.

        With ``mesh=``, a batch that does not divide the device count is
        padded with copies of element 0 whose ``tune_mask`` is forced
        ``False`` (phantom elements never decide); every output is
        sliced back to the caller's batch before returning.
        """
        n_intervals = int(n_intervals)
        if intervene is not None and not self.tuned:
            raise ValueError("intervene= requires a tuned loop")
        with span("dial.loop.shape"):
            args, n_pad = self._shape_args(table, state, wstate, n_intervals,
                                           schedule, tune_mask, intervene)

        with jax.enable_x64(True):
            with span("dial.loop.device_put"):
                if self.mesh is not None:
                    # place inputs *pre-sharded*: jit then donates the
                    # caller's buffers directly instead of donating a
                    # resharding copy (which would leave the originals
                    # alive and defeat donate_argnums)
                    sharding = NamedSharding(
                        self.mesh, PartitionSpec(self.mesh.axis_names[0]))
                    jargs = jax.tree.map(
                        lambda a: jax.device_put(np.asarray(a), sharding),
                        args)
                else:
                    jargs = jax.tree.map(jnp.asarray, args)
            # the call returns once the program is enqueued; its first
            # call also traces and compiles it, or loads it from the
            # persistent cache
            with span("dial.loop.dispatch"):
                run_fn = (self._get_run(6) if intervene is not None
                          else self._run)
                out = run_fn(*jargs)
            with span("dial.loop.wait"):
                out = jax.block_until_ready(out)
        if self.tuned:
            jstate, jws, jtrace, jhist = out
        elif self.trace_config is not None:
            (jstate, jws, jtrace), jhist = out, None
        else:
            (jstate, jws), jtrace, jhist = out, None, None
        with span("dial.loop.to_host"):
            state = jax.tree.map(np.array, jstate)
            if not self.batched:
                state.now = float(state.now)
                state.tick_index = int(state.tick_index)
            wstate = jax.tree.map(np.array, jws)
            trace = (jax.tree.map(np.array, jtrace)
                     if jtrace is not None else None)
            hist = (jax.tree.map(np.array, jhist)
                    if jhist is not None else None)
            if n_pad:
                state = unpad_fleet(state, n_pad)
                wstate = unpad_fleet(wstate, n_pad)
                trace = (unpad_fleet(trace, n_pad) if trace is not None
                         else None)
                hist = unpad_fleet(hist, n_pad) if hist is not None else None
        with span("dial.loop.decode"):
            decisions = (decisions_from_trace(trace)
                         if trace is not None and "decided" in trace
                         else [])
        return FusedLoopResult(
            state=state, wstate=wstate, trace=trace, decisions=decisions,
            hist=hist,
            interval_seconds=self.steps * self.params.tick,
            n_run=n_intervals)
