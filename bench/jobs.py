"""The one general job generator: a configuration file and a traffic file
in, one job per (seed, job index) out, driven through the program's own
entry.

A *configuration* (``bench/configs/<name>.json``) names its deployment's
``builder``, found as ``bench/builders/<builder>.py``: its ``program``
and ``reference`` functions build the configuration's scenarios as the
program and as the plain reference make them.  A *traffic mix*
(``bench/traffic/<name>.json``) says how a job drives them:

``entry``        the program entry a job runs, found as
                 ``bench/entries/<entry>.py``: ``run_batch`` (the lab's
                 fused batch, every interface DIAL-tuned), ``fused_loop``
                 (the lean engine-only fused program, every interface at
                 its initial θ) or ``evaluate`` (the lab's policy sweep:
                 every scenario under each static θ plus DIAL);
``variants``     seeded jitters of each scenario per job;
``mesh``         chips a ``run_batch`` job shards its batch over
                 (``fleet_mesh``); none by default;
``sim_seconds``, ``interval``  simulated length and tuning interval;
``check_jobs``   how many of the window's jobs the reference re-runs;
``check_static_arms``  static arms checked per job, where a job holds
                 more of them than the check re-runs (a policy sweep);
``host_spans``   program functions (``module:qualname``) that a traced
                 run marks as host spans, so that idle device time is
                 charged to the host work it waited on;
``device_calls`` the program functions that dispatch to the device and
                 wait for it: a traced run marks them too, and counts a
                 job's host time as its wall time outside them.

Every job has the same shapes; its parameters come from ``--seed`` and the
job index through :func:`job_seed`, so the same seed gives the same jobs
and no job compiles a new program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import importlib
import importlib.util
import os
import time

import numpy as np

import reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))


def job_seed(seed: int, index: int) -> int:
    """A non-negative 56-bit seed for job ``index`` of run ``seed``."""
    h = hashlib.sha256(f"{int(seed)}:{int(index)}".encode()).digest()
    return int.from_bytes(h[:7], "big")


def load_part(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``; a missing one is an error."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise LookupError(f"no bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_bases(config: dict) -> dict:
    """The configuration's scenarios as the reference builds them."""
    return load_part("builders", config["builder"]).reference(config)


def program_bases(config: dict) -> dict:
    """The configuration's scenarios as the program builds them."""
    return load_part("builders", config["builder"]).program(config)


@dataclasses.dataclass
class Element:
    """One simulated scenario of a job, as the check compares it."""

    base: str                 # configuration scenario it jitters
    seed: int                 # the jitter's seed
    theta0: tuple             # initial θ
    tuned: bool
    counters: dict            # counter name -> (2, n) or (n,) array
    decisions: list | None    # per interval (oscs, theta, changed)


def elements_of(batch, result, tuned_elems, meta) -> list[Element]:
    """Slice a finished batch back into per-scenario elements, in each
    scenario's own interface order (ragged batches drop phantom ones)."""
    n = batch.n_osc
    out = []
    for e in range(len(batch)):
        cols = batch.element_cols(e)
        counters = {}
        for f in ref.COUNTERS:
            a = np.asarray(getattr(batch.state, f))[e]
            counters[f] = a[..., cols]
        decisions = None
        if e in tuned_elems:
            pos = np.full(n, -1, dtype=np.int64)
            pos[cols] = np.arange(len(cols))
            decisions = []
            for r in result.decisions:
                sel = np.asarray(r.oscs) // n == e
                decisions.append((pos[np.asarray(r.oscs)[sel] % n],
                                  np.asarray(r.decisions.theta)[sel],
                                  np.asarray(r.decisions.changed)[sel]))
        base, seed, theta0 = meta[e]
        out.append(Element(base, seed, theta0, e in tuned_elems, counters,
                           decisions))
    return out


@dataclasses.dataclass
class Job:
    """One finished job: its elements and the work it did."""

    index: int
    elements: list
    work: float                # interface-intervals, or arm-seconds
    buckets: list              # pad_stats of each dispatched batch
    host_s: float | None = None   # wall time outside the device calls,
    #                               where they are marked


def _resolve(path: str):
    """``(owner, attribute)`` of a ``module:qualname`` path."""
    module, _, qual = path.partition(":")
    owner = importlib.import_module(module)
    *outer, name = qual.split(".")
    for a in outer:
        owner = getattr(owner, a)
    return owner, name


class Driver:
    """Runs jobs of one cell through the program's entry.

    ``span`` is a context-manager factory ``span(name)`` that marks a
    stretch of host work in a profiler trace.  Setting ``profile_dir``
    has the profiler trace the next whole job into that directory, and
    only that one; the job runs inside a ``bench.job`` span.
    """

    def __init__(self, config: dict, traffic: dict, model, span,
                 seg_backend: str = "auto"):
        self.config, self.traffic = config, traffic
        self.model, self.span = model, span
        self.profile_dir = None
        self.device_s = None       # seconds inside the device calls,
        #                            while they are marked
        self.seg_backend = seg_backend
        self.bases = program_bases(config)
        self.seconds = float(traffic["sim_seconds"])
        self.interval = float(traffic["interval"])
        self.n_int = int(round(self.seconds / self.interval))
        self.entry = load_part("entries", traffic["entry"])
        self.mesh = None
        if traffic.get("mesh"):
            from repro.distributed.sharding import fleet_mesh

            self.mesh = fleet_mesh(int(traffic["mesh"]))

    def specs(self, seed: int, index: int):
        """Seeded jitters of every configuration scenario for one job,
        with ``(base, jitter seed, initial θ)`` of each."""
        from repro.lab.scenarios import variants

        js = job_seed(seed, index)
        n_var = int(self.traffic.get("variants", 1))
        specs, meta = [], []
        for k, (name, spec) in enumerate(self.bases.items()):
            for v in range(n_var):
                s = job_seed(js, k * 1000 + v)
                specs.append(variants(spec, 1, seed=s)[0])
                meta.append((name, s, tuple(spec.initial_theta)))
        return specs, meta

    def steps(self, batch) -> int:
        """Engine ticks per tuning interval."""
        return max(int(round(self.interval / batch.params.tick)), 1)

    def run(self, seed: int, index: int) -> Job:
        """One job, profiled whole when ``profile_dir`` is set."""
        import jax

        trace_dir, self.profile_dir = self.profile_dir, None
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # device ops and spans only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            d0, t0 = self.device_s, time.perf_counter()
            with self.span("bench.job"):
                job = self.entry.run(self, seed, index)
            if d0 is not None:
                job.host_s = (time.perf_counter() - t0
                              - (self.device_s - d0))
            return job
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()

    @contextlib.contextmanager
    def host_spans(self):
        """Marks the traffic's ``host_spans`` and ``device_calls`` as
        ``bench.<qualname>`` spans while the block runs, and times the
        device calls (``device_s``)."""
        saved = []
        self.device_s = 0.0
        try:
            for path, device in (
                    [(p, False) for p in self.traffic.get("host_spans", ())]
                    + [(p, True) for p in self.traffic.get("device_calls",
                                                           ())]):
                owner, name = _resolve(path)
                fn = getattr(owner, name)
                saved.append((owner, name, fn))
                label = "bench." + path.partition(":")[2]

                def marked(*a, _fn=fn, _label=label, _device=device, **kw):
                    t0 = time.perf_counter()
                    try:
                        with self.span(_label):
                            return _fn(*a, **kw)
                    finally:
                        if _device:
                            self.device_s += time.perf_counter() - t0
                setattr(owner, name, functools.wraps(fn)(marked))
            yield
        finally:
            for owner, name, fn in reversed(saved):
                setattr(owner, name, fn)
            self.device_s = None

    def hlo_texts(self) -> list:
        """Compiled HLO of the programs the entry dispatches, where the
        entry can name them (``[]`` otherwise)."""
        read = getattr(self.entry, "hlo_texts", None)
        return read(self) if read is not None else []


# helpers of the entries that stack a job into one batch
def prepare(driver, seed: int, index: int):
    """The job's batch and the meta of its elements."""
    from repro.lab.batch import stack_scenarios
    from repro.lab.scenarios import build

    with driver.span("bench.prep"):
        specs, meta = driver.specs(seed, index)
        return stack_scenarios([build(s) for s in specs]), meta


def finish(driver, index: int, batch, result, tuned, meta) -> Job:
    with driver.span("bench.collect"):
        elements = elements_of(batch, result, tuned, meta)
    n_real = sum(len(batch.element_cols(e)) for e in range(len(batch)))
    return Job(index, elements, float(n_real * driver.n_int),
               [batch.pad_stats()])


def compiled_text(loop, batch, driver, extra=()) -> str:
    """The HLO text of ``loop`` compiled for ``batch``'s shapes (from the
    compile cache where it is there)."""
    import jax

    steps = driver.steps(batch)
    with jax.enable_x64(True):
        sched = loop._shape_schedule(
            batch.schedule(0, driver.n_int * steps), driver.n_int)
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                           np.asarray(a).dtype),
            (batch.table, batch.state, batch.wstate, sched) + tuple(extra))
    return loop.lower(*args).compile().as_text()
