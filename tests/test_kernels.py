"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) and jnp refs vs
the numpy oracles."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.gbdt import GBDTClassifier, GBDTParams

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------- #
# gbdt_forest
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def forest():
    X = RNG.normal(size=(2000, 34)).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]) > 0).astype(float)
    return GBDTClassifier(GBDTParams(n_trees=24, max_depth=5)).fit(X, y).forest


@pytest.mark.parametrize("n", [64, 100, 513, 24])
def test_gbdt_forest_kernel_matches_refs(forest, n):
    """The jitted traversal the fleet predictor uses vs the numpy oracle."""
    from repro.kernels.gbdt_forest.ops import make_predictor
    from repro.kernels.gbdt_forest.ref import forest_margin_ref

    X = jnp.asarray(RNG.normal(size=(n, forest.n_features)), jnp.float32)
    args = (jnp.asarray(forest.feature), jnp.asarray(forest.threshold),
            jnp.asarray(forest.leaf), forest.base_score, forest.depth)
    ref = forest_margin_ref(X, *args)
    np.testing.assert_allclose(np.asarray(ref),
                               forest.predict_margin(np.asarray(X)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(make_predictor(forest)(X)),
                               forest.predict_proba(np.asarray(X)),
                               rtol=1e-4, atol=1e-4)


def _random_forest(rng, n_trees, depth, n_features, base=0.0):
    """A dense forest with arbitrary (non-dyadic) leaves."""
    from repro.core.gbdt import DenseForest

    n_internal = 2 ** depth - 1
    return DenseForest(
        feature=rng.integers(0, n_features, size=(n_trees, n_internal),
                             dtype=np.int32),
        threshold=rng.normal(size=(n_trees, n_internal)).astype(np.float32),
        leaf=rng.normal(size=(n_trees, n_internal + 1)).astype(np.float32),
        base_score=base, depth=depth, n_features=n_features)


def _trained_forest(rng, n_trees, depth, n_features):
    X = rng.normal(size=(1500, n_features))
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    return GBDTClassifier(GBDTParams(n_trees=n_trees, max_depth=depth)).fit(
        X, y).forest


def _paired_case(case, forest):
    """``(read_forest, write_forest, x, op)`` of one scorer case."""
    rng = np.random.default_rng(7)
    n = 257
    if case == "trained":
        fr, fw = forest, _trained_forest(rng, 24, 5, 30)
    elif case == "mixed_depth":       # pass-through spines, inert trees
        fr = _trained_forest(rng, 12, 3, 10)
        fw = _trained_forest(rng, 20, 5, 14)
    elif case == "160_trees":
        fr = _random_forest(rng, 160, 5, 36, base=-0.3)
        fw = _random_forest(rng, 150, 5, 36, base=0.2)
    elif case == "deep":              # above the dense cut-off
        fr = _random_forest(rng, 4, 9, 12, base=0.1)
        fw = _random_forest(rng, 3, 9, 12)
    else:
        fr = _random_forest(rng, 16, 5, 20, base=0.25)
        fw = _random_forest(rng, 16, 4, 20, base=-0.5)
    n_feat = max(fr.n_features, fw.n_features)
    x = rng.normal(size=(n, n_feat)).astype(np.float32)
    if case == "ties":                # feature values equal to thresholds
        for f in (fr, fw):
            t = rng.integers(0, f.n_trees, size=n)
            j = rng.integers(0, f.feature.shape[1], size=n)
            x[np.arange(n), f.feature[t, j]] = f.threshold[t, j]
    elif case == "inf":               # +inf thresholds mid-tree, ±inf, nan
        for f in (fr, fw):
            f.threshold[rng.random(f.threshold.shape) < 0.2] = np.inf
        x[rng.random(x.shape) < 0.05] = np.inf
        x[rng.random(x.shape) < 0.05] = -np.inf
        x[rng.random(x.shape) < 0.02] = np.nan
    elif case == "one_row":
        x, n = x[:1], 1
    op = rng.integers(0, 2, size=n).astype(np.int32)
    if case == "one_row":
        op[:] = 1
    x[op == 0, fr.n_features:] = 0.0
    x[op == 1, fw.n_features:] = 0.0
    return fr, fw, x, op


@pytest.mark.parametrize("case", ["trained", "mixed_depth", "ties", "inf",
                                  "one_row", "160_trees", "deep"])
def test_paired_scorer_matches_refs(forest, case):
    """The paired scorer's margins: bit-equal to the gather reference,
    and to the unpadded numpy forests within float32 summation; gathers
    only above the dense depth cut-off."""
    from repro.kernels.gbdt_forest.dense import (DENSE_MAX_DEPTH,
                                                 paired_scorer)
    from repro.kernels.gbdt_forest.ops import pair_forests
    from repro.kernels.gbdt_forest.ref import paired_forest_margin_ref

    fr, fw, x, op = _paired_case(case, forest)
    feature, threshold, leaf, base, depth, _ = pair_forests(fr, fw)
    score = jax.jit(paired_scorer(feature, threshold, leaf, base))
    got = np.asarray(score(x, op))
    ref = jax.jit(paired_forest_margin_ref, static_argnums=6)(
        x, op, feature, threshold, leaf, base, depth)
    np.testing.assert_array_equal(got, np.asarray(ref))

    for k, f in enumerate((fr, fw)):
        rows = op == k
        want = f.predict_margin(x[rows, :f.n_features])
        bound = np.abs(f.leaf).max(axis=1).sum() + abs(f.base_score)
        np.testing.assert_allclose(got[rows], want, rtol=0,
                                   atol=(f.n_trees + 1) * 2.0**-24 * bound)
    gathers = " gather(" in score.lower(x, op).compile().as_text()
    assert gathers == (depth > DENSE_MAX_DEPTH)


# ---------------------------------------------------------------------- #
# flash attention
# ---------------------------------------------------------------------- #
CASES = [
    dict(b=1, hq=4, hkv=4, sq=64, skv=64, d=32),
    dict(b=2, hq=8, hkv=2, sq=64, skv=64, d=32),                 # GQA
    dict(b=1, hq=4, hkv=1, sq=48, skv=48, d=64),                 # MQA + pad
    dict(b=1, hq=4, hkv=2, sq=64, skv=64, d=32, window=16),
    dict(b=1, hq=4, hkv=4, sq=64, skv=64, d=32, softcap=50.0),
    dict(b=1, hq=4, hkv=2, sq=1, skv=100, d=32),                 # decode
    dict(b=1, hq=2, hkv=2, sq=40, skv=104, d=64, window=32),
    dict(b=1, hq=2, hkv=2, sq=64, skv=64, d=32, causal=False),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_matches_ref(case, dtype, tol):
    from repro.kernels.flash_attention.ops import attention

    c = dict(case)
    b, hq, hkv = c.pop("b"), c.pop("hq"), c.pop("hkv")
    sq, skv, d = c.pop("sq"), c.pop("skv"), c.pop("d")
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)), dtype)
    o_ref = attention(q, k, v, backend="ref", **c)
    o_pal = attention(q, k, v, backend="pallas_interpret",
                      block_q=32, block_kv=32, **c)
    err = float(jnp.abs(o_ref.astype(jnp.float32)
                        - o_pal.astype(jnp.float32)).max())
    assert err < tol, (case, dtype, err)


def test_flash_attention_matches_chunked_production_path():
    """The chunked jnp attention (production lowering path) and the Pallas
    kernel implement identical semantics."""
    from repro.kernels.flash_attention.ops import attention
    from repro.models.attention import chunked_attention

    b, hq, hkv, s, d = 2, 8, 2, 96, 32
    q = jnp.asarray(RNG.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    out_chunked = chunked_attention(q, k, v, causal=True, window=0,
                                    softcap=0.0, q_chunk=32, kv_chunk=32)
    out_kernel = attention(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                           jnp.moveaxis(v, 1, 2),
                           backend="pallas_interpret", block_q=32, block_kv=32)
    err = float(jnp.abs(jnp.moveaxis(out_kernel, 1, 2) - out_chunked).max())
    assert err < 2e-5, err


# ---------------------------------------------------------------------- #
# mamba selective scan
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("bt,s,dm,n,bd", [
    (2, 64, 128, 16, 64), (1, 33, 256, 8, 256), (3, 128, 64, 16, 64),
])
def test_mamba_scan_matches_ref(bt, s, dm, n, bd):
    from repro.kernels.mamba_scan.ops import selective_scan

    u = jnp.asarray(RNG.normal(size=(bt, s, dm)), jnp.float32)
    delta = jnp.asarray(np.abs(RNG.normal(size=(bt, s, dm))) * 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(RNG.normal(size=(dm, n))) - 0.1, jnp.float32)
    B = jnp.asarray(RNG.normal(size=(bt, s, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(bt, s, n)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(dm,)), jnp.float32)
    ref = selective_scan(u, delta, A, B, C, D, backend="ref")
    pal = selective_scan(u, delta, A, B, C, D, backend="pallas_interpret",
                         block_d=bd)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------- #
# rglru scan
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("bt,s,dm,bd", [
    (2, 64, 128, 64), (1, 100, 256, 128), (4, 17, 64, 64),
])
def test_rglru_matches_ref(bt, s, dm, bd):
    from repro.kernels.rglru_scan.ops import rglru

    x = jnp.asarray(RNG.normal(size=(bt, s, dm)), jnp.float32)
    a = jnp.asarray(1 / (1 + np.exp(-RNG.normal(size=(bt, s, dm)))), jnp.float32)
    ref = rglru(x, a, backend="ref")
    pal = rglru(x, a, backend="pallas_interpret", block_d=bd)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=1e-4, atol=1e-4)


def test_rglru_sequential_equals_associative():
    """The associative-scan ref equals a plain sequential recurrence."""
    from repro.kernels.rglru_scan.ref import rglru_ref

    x = RNG.normal(size=(1, 50, 8)).astype(np.float32)
    a = (1 / (1 + np.exp(-RNG.normal(size=(1, 50, 8))))).astype(np.float32)
    h = np.zeros((1, 8), np.float32)
    seq = []
    for t in range(50):
        h = a[:, t] * h + np.sqrt(1 - a[:, t] ** 2) * x[:, t]
        seq.append(h.copy())
    seq = np.stack(seq, axis=1)
    np.testing.assert_allclose(np.asarray(rglru_ref(jnp.asarray(x),
                                                    jnp.asarray(a))),
                               seq, rtol=1e-5, atol=1e-5)
