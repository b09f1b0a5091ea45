"""Compiles of the main path's programs for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with ``libtpu`` compiles for a
chip that is described, not attached, and refuses here what the chip's
compiler would refuse there (tiling, 64-bit emulation, memory).  The
topology is described inside a module fixture, never while a module is
imported, so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU library.
"""

import os
import re

import numpy as np
import pytest

pytest.importorskip("jax")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), jax.dtypes.canonicalize_dtype(np.asarray(a).dtype),
            sharding=sharding), tree)


@pytest.mark.parametrize("batch", [None, 16])
@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_segment_sum_compiles_for_v5e(one_chip, backend, batch):
    """The engine's segment reduction under x64 at a deployment's widths
    (65,536 OSCs onto 64 OSTs; vmapped as the lab's batched engine runs
    it, 16 x 4,096 onto 16): what ``auto`` picks, and the Pallas kernel
    with float64 operands."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.segment_reduce.ops import make_segment_sum

    segsum = make_segment_sum(backend)
    if batch is None:
        shape, n_seg = (65536,), 64
        fn = jax.jit(lambda v, i: segsum(v, i, n_seg))
    else:
        shape, n_seg = (batch, 4096), 16
        fn = jax.jit(jax.vmap(lambda v, i: segsum(v, i, n_seg)))
    with jax.enable_x64(True):
        compiled = fn.lower(
            jax.ShapeDtypeStruct(shape, jnp.float64, sharding=one_chip),
            jax.ShapeDtypeStruct(shape, jnp.int64, sharding=one_chip),
        ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_tree_histogram_compiles_for_v5e(one_chip):
    """The Pallas refit histogram at a refit's width (3 channels, 20,000
    samples, 34 features, 16 nodes x 32 bins)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.tree_histogram.kernel import tree_histogram

    fn = jax.jit(lambda v, b, n: tree_histogram(v, b, n, 16, 32,
                                                interpret=False))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((3, 20000), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((20000, 34), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((20000,), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _gathers_under(text, scope):
    """Lines of compiled HLO text that gather under a named scope: a
    gather whose ``op_name`` holds the scope, or a fusion that carries
    the scope and calls a computation holding a gather."""
    comp, gathering, lines = None, set(), text.splitlines()
    for line in lines:
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
        elif " gather(" in line:
            gathering.add(comp.lstrip("%"))
    out = []
    for line in lines:
        op = _OP_NAME.search(line)
        if op is None or scope not in op.group(1):
            continue
        calls = re.search(r"calls=%([\w.\-]+)", line)
        if " gather(" in line or (calls and calls.group(1) in gathering):
            out.append(line)
    return out


def _compile_fused_loop(one_chip, model, batched):
    """FusedLoop's jitted run at 64 clients x 8 OSTs (512 OSCs), 20
    intervals of 100 ticks, compiled for one v5e chip."""
    import jax

    from repro.lab.scenarios import build, vpic_bdcats_deployment
    from repro.pfs.loop_jax import FusedLoop

    b = build(vpic_bdcats_deployment(64, 8))
    steps, n_int = 100, 20
    loop = FusedLoop(b.params, b.topo, steps, model, batched=batched)
    with jax.enable_x64(True):
        args = (b.table, b.state, b.wstate, b.schedule(0, steps * n_int),
                np.ones(b.topo.n_osc, dtype=bool))
        if batched:
            args = jax.tree.map(lambda a: np.asarray(a)[None], args)
        table, state, wstate, sched, mask = args
        args = _shapes((table, state, wstate,
                        loop._shape_schedule(sched, n_int), mask), one_chip)
    return loop.lower(*args).compile()


def test_fused_loop_compiles_for_v5e(one_chip, dial_model):
    """The fused loop with its default segment reduction; its forest
    scoring gathers nothing."""
    compiled = _compile_fused_loop(one_chip, dial_model, batched=False)
    text = compiled.as_text()
    assert "while" in text                        # the interval scan
    assert "dial.forest" in text
    assert _gathers_under(text, "dial.forest") == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_batched_fused_loop_scores_without_gathers_for_v5e(one_chip,
                                                           dial_model):
    """Vmapped as the lab and the benchmark run it (a batch of one), the
    forest scoring still gathers nothing."""
    text = _compile_fused_loop(one_chip, dial_model, batched=True).as_text()
    assert "dial.forest" in text
    assert _gathers_under(text, "dial.forest") == []


def test_paired_scorer_compiles_for_v5e(one_chip):
    """The gather-free paired scorer for two trainer-default 160-tree
    forests of depth 5, over 4,096 interfaces x 24 candidates, in under
    1 GiB of temporaries."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.gbdt_forest.dense import paired_scorer

    rng = np.random.default_rng(0)
    t, n_internal, n_features, n = 160, 31, 36, 4096 * 24
    feature = rng.integers(0, n_features, size=(2, t, n_internal))
    threshold = rng.normal(size=(2, t, n_internal)).astype(np.float32)
    leaf = rng.normal(size=(2, t, n_internal + 1)).astype(np.float32)
    score = paired_scorer(feature, threshold, leaf, np.zeros(2, np.float32))
    compiled = jax.jit(score).lower(
        jax.ShapeDtypeStruct((n, n_features), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
    ).compile()
    assert " gather(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_tree_histogram_vmapped_compiles_for_v5e(one_chip):
    """The refit histogram vmapped over a read/write forest pair, as
    ``learn.boost.fit_forest_batch`` runs it."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.tree_histogram.kernel import tree_histogram

    fn = jax.jit(jax.vmap(lambda v, b, n: tree_histogram(
        v, b, n, 16, 32, interpret=False)))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((2, 3, 4096), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((2, 4096, 34), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
