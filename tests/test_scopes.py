"""The fused program's named scopes and the lab's and loop's host spans.

Every instruction of a compiled fused loop carries its layer in
``metadata={op_name=...}`` (``dial.tick``, ``dial.decide``,
``dial.segsum``, ...), and one split ``run_batch(fused=True)`` passes
through every ``dial.lab.*`` and ``dial.loop.*`` host span.
"""

import re

import jax
import numpy as np
import pytest

from repro.core.gbdt import GBDTClassifier, GBDTParams
from repro.core.metrics import feature_dim
from repro.core.model import DIALModel
from repro.pfs.state import READ, WRITE

LOOP_SPANS = ("dial.loop.shape", "dial.loop.device_put",
              "dial.loop.dispatch", "dial.loop.wait", "dial.loop.to_host",
              "dial.loop.decode")
LAB_SPANS = ("dial.lab.stack", "dial.lab.schedule", "dial.lab.loop_cache",
             "dial.lab.merge")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def tiny_model():
    """Two 3-tree forests: scope placement does not depend on the
    model's quality."""
    rng = np.random.default_rng(0)

    def forest(dim):
        x = rng.normal(size=(200, dim)).astype(np.float32)
        return GBDTClassifier(GBDTParams(n_trees=3, max_depth=2)).fit(
            x, (x[:, 0] > 0).astype(float)).forest

    return DIALModel(read_forest=forest(feature_dim(READ, 1)),
                     write_forest=forest(feature_dim(WRITE, 1)),
                     backend="jax")


def _batch(n=1):
    from repro.lab.batch import stack_scenarios
    from repro.lab.scenarios import build, get_scenario, variants

    spec = get_scenario("degraded_ost")
    return stack_scenarios([build(s) for s in
                            [spec] + variants(spec, n - 1, seed=3)])


def _compiled_text(loop, b, n_int):
    with jax.enable_x64(True):
        sched = loop._shape_schedule(b.schedule(0, n_int * loop.steps),
                                     n_int)
        args = (b.table, b.state, b.wstate, sched)
        if loop.tuned:
            args = args + (np.ones((len(b), b.n_osc), dtype=bool),)
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            np.shape(a), np.asarray(a).dtype), args)
    return loop.lower(*args).compile().as_text()


@pytest.mark.parametrize("tuned", [True, False], ids=["tuned", "lean"])
def test_fused_program_instructions_carry_dial_scopes(tiny_model, tuned):
    from repro.pfs.loop_jax import FusedLoop

    b = _batch()
    loop = FusedLoop(b.params, b.topo, 5, tiny_model if tuned else None,
                     batched=True, tuned=tuned)
    text = _compiled_text(loop, b, 4)
    names = [m.group(1) for m in _OP_NAME.finditer(text)]
    scopes = {c for n in names for c in re.split("[/;]", n)
              if c.startswith("dial.")}
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert scatters
    for line in scatters:
        assert "dial.segsum" in _OP_NAME.search(line).group(1), line
    assert {"dial.tick", "dial.demand", "dial.engine",
            "dial.segsum"} <= scopes
    decide = {"dial.decide", "dial.features", "dial.forest", "dial.alg1"}
    if tuned:
        assert decide <= scopes
    else:
        assert not decide & scopes


def test_split_run_batch_passes_every_host_span(tiny_model):
    """A batch of tuned and untuned elements through the fused lab path
    runs both programs and the merge: all ten spans count calls, and the
    programs' first calls charge their compiles to the dispatch span."""
    from repro.lab.batch import run_batch
    from repro.obs import spans

    before = spans.totals()
    b = _batch(3)
    n = b.n_osc
    run_batch(b, model=tiny_model, seconds=2.0, interval=0.5, fused=True,
              tune_cols=n + np.arange(n))          # element 1 only
    d = spans.since(before)
    for name in LOOP_SPANS + LAB_SPANS:
        assert d["spans"].get(name, {}).get("calls", 0) >= 1, name
        assert d["spans"][name]["seconds"] >= 0.0
    assert d["spans"]["dial.loop.dispatch"]["calls"] == 2   # two programs
    compiled = d["compiles"].get("dial.loop.dispatch", {})
    assert compiled.get("compiles", 0) >= 2
    assert compiled["trace_s"] > 0 and compiled["backend_s"] > 0
