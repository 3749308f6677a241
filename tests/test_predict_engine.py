"""PredictEngine (serving.py): bit-exactness vs the direct predict path and
the zero-recompilation guarantee after per-bucket warmup."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu.io.pseudo_bins import PseudoRouter
from lightgbm_tpu.ops import predict as P
from lightgbm_tpu.serving import PredictEngine, bucket_rows

RNG = np.random.RandomState(7)


def _direct_predict(booster, X, raw_score=False, pred_leaf=False):
    """The pre-engine Booster.predict tail, verbatim: fresh router, unpadded
    bins, per-call uploads — the reference the engine must match bit-for-bit."""
    trees = booster._ensure_host_trees()
    k = max(booster.num_model_per_iteration(), 1)
    router = PseudoRouter(trees, X.shape[1])
    pbins = jax.device_put(router.bin_matrix(np.asarray(X, dtype=np.float64)))
    na_dev = jnp.asarray(router.na_id)
    if pred_leaf:
        stack_dev = {kk: jnp.asarray(v) for kk, v in router.stack.items()}
        return np.asarray(P.leaf_bins_ensemble(stack_dev, pbins, na_dev,
                                               router.max_steps))
    raw = P.ensemble_raw_scores(
        router.dense_tables(), router.stack, pbins, na_dev, k, len(trees),
        booster._avg_output(), exact_f32=True, max_steps=router.max_steps)
    if raw_score:
        return raw
    obj = booster._objective_for_predict()
    if obj is not None:
        return np.asarray(obj.convert_output(jnp.asarray(raw)))
    return raw


def _train(objective, n=400, f=8, rounds=6, **extra):
    X = RNG.rand(n, f)
    if objective == "multiclass":
        y = RNG.randint(0, extra.get("num_class", 3), n).astype(float)
    elif objective == "binary":
        y = (X[:, 0] + X[:, 1] > 1).astype(float)
    else:
        y = X[:, 0] * 3 + np.sin(X[:, 1] * 6) + RNG.randn(n) * 0.05
    params = {"objective": objective, "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, **extra}
    b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds)
    return b, X


@pytest.fixture(scope="module")
def reg():
    return _train("regression")


@pytest.fixture(scope="module")
def binary():
    return _train("binary")


@pytest.fixture(scope="module")
def multi():
    return _train("multiclass", num_class=4)


@pytest.fixture(scope="module")
def cat():
    X = RNG.rand(400, 6)
    X[:, 2] = RNG.randint(0, 9, 400)   # categorical column
    y = X[:, 0] + (X[:, 2] % 3 == 0) + RNG.randn(400) * 0.05
    b = lgb.train({"objective": "regression", "num_leaves": 15,
                   "verbose": -1, "min_data_in_leaf": 5},
                  lgb.Dataset(X, label=y, categorical_feature=[2]),
                  num_boost_round=6)
    assert any(t.num_cat > 0 for t in b._ensure_host_trees())
    return b, X


# sizes straddling bucket edges: the n=1 fast path, min-bucket (8) +-1,
# and a power-of-two edge +-1
EDGE_SIZES = [1, 2, 7, 8, 9, 31, 32, 33, 100]


@pytest.mark.parametrize("n", EDGE_SIZES)
def test_bucketed_bit_identical_regression(reg, n):
    b, X = reg
    for kw in ({}, {"raw_score": True}, {"pred_leaf": True}):
        got = b.predict(X[:n], **kw)
        want = _direct_predict(b, X[:n], **kw)
        assert got.shape == want.shape
        assert np.array_equal(got, want), kw


@pytest.mark.parametrize("n", [1, 7, 9, 64])
def test_bucketed_bit_identical_binary(binary, n):
    b, X = binary
    for kw in ({}, {"raw_score": True}):
        assert np.array_equal(b.predict(X[:n], **kw),
                              _direct_predict(b, X[:n], **kw)), kw


@pytest.mark.parametrize(
    "n", [1, 7, 9,
          # the 64-bucket variant re-pays a fresh per-bucket warmup (~13s on
          # the 1-core box); bucket-edge coverage stays via n=7/9 + the
          # binary/regression edge params
          pytest.param(50, marks=pytest.mark.slow)])
def test_bucketed_bit_identical_multiclass(multi, n):
    b, X = multi
    assert b._predict_engine_for(b._ensure_host_trees(), X.shape[1],
                                 4).k == 4   # k > 2
    for kw in ({}, {"raw_score": True}, {"pred_leaf": True}):
        got = b.predict(X[:n], **kw)
        want = _direct_predict(b, X[:n], **kw)
        assert np.array_equal(got, want), kw


@pytest.mark.parametrize("n", [1, 8, 33])
def test_bucketed_bit_identical_categorical(binary, cat, n):
    b, X = cat
    # categorical nodes force the walk path (dense tables unavailable)
    assert b._predict_engine_for(
        b._ensure_host_trees(), X.shape[1], 1)._class_dense is None
    for kw in ({}, {"raw_score": True}, {"pred_leaf": True}):
        assert np.array_equal(b.predict(X[:n], **kw),
                              _direct_predict(b, X[:n], **kw)), kw


def test_chunked_bit_identical(reg, multi):
    for b, X in (reg, multi):
        eng = PredictEngine(b._ensure_host_trees(), X.shape[1],
                            max(b.num_model_per_iteration(), 1),
                            b._avg_output(),
                            objective=b._objective_for_predict(),
                            chunk_rows=64)
        for kw in ({}, {"raw_score": True}, {"pred_leaf": True}):
            # chunk edges: exact multiple, +-1, and a ragged tail
            for n in (63, 64, 65, 128, 129, 200):
                got = eng.predict(X[:n], **kw)
                want = _direct_predict(b, X[:n], **kw)
                assert np.array_equal(got, want), (n, kw)
        assert eng.stats["chunked_calls"] > 0 and eng.stats["chunks"] > 0


def test_engine_upload_once_and_invalidation(reg):
    b, X = reg
    b.predict(X[:3])
    eng = b._predict_engine
    b.predict(X[:50])
    assert b._predict_engine is eng           # same tree count -> same engine
    b.predict(X[:3], num_iteration=2)         # fewer trees -> rebuilt
    assert b._predict_engine is not eng
    assert b._predict_engine.n_trees == 2


def test_bucket_rows():
    assert bucket_rows(0) == 1 and bucket_rows(1) == 1
    assert bucket_rows(2) == 8 and bucket_rows(8) == 8
    assert bucket_rows(9) == 16
    assert bucket_rows(10 ** 9, max_bucket=1 << 17) == 1 << 17


def test_zero_recompilations_after_warmup(reg, multi):
    """Acceptance: after one warmup call per bucket, repeated predict calls
    of varying batch sizes lower ZERO new XLA programs."""
    sizes = [1, 3, 5, 8, 9, 17, 33, 64, 100]
    for b, X in (reg, multi):
        b._predict_engine = None              # cold engine, warm jit caches
        for s in sizes:                       # warmup: one call per bucket
            b.predict(X[:s])
            b.predict(X[:s], raw_score=True)
        with jtu.count_jit_and_pmap_lowerings() as count:
            for s in sizes + sizes[::-1]:
                b.predict(X[:s])
                b.predict(X[:s], raw_score=True)
        assert count() == 0, f"{count()} recompilations after warmup"


def test_zero_recompilations_single_row_stream(binary):
    """Online-scoring loop: after the first n=1 call, a stream of single-row
    predicts (the C-API hot path) compiles nothing."""
    b, X = binary
    b._predict_engine = None
    b.predict(X[:1])
    with jtu.count_jit_and_pmap_lowerings() as count:
        for i in range(20):
            b.predict(X[i: i + 1])
    assert count() == 0


def test_warmup_helper_compiles_buckets(reg):
    b, X = reg
    eng = PredictEngine(b._ensure_host_trees(), X.shape[1], 1,
                        b._avg_output(), objective=b._objective_for_predict())
    eng.warmup(sizes=(1, 5, 100), n_features=X.shape[1])
    with jtu.count_jit_and_pmap_lowerings() as count:
        for n in (1, 4, 70, 100):
            eng.predict(X[:n])
    assert count() == 0


def test_sklearn_shares_engine():
    X = RNG.rand(300, 5)
    y = (X[:, 0] > 0.5).astype(int)
    clf = lgb.LGBMClassifier(n_estimators=5, num_leaves=7, verbose=-1)
    clf.fit(X, y)
    p1 = clf.predict_proba(X[:9])
    eng = clf.booster_._predict_engine
    assert eng is not None and 16 in eng.stats["buckets_seen"]
    clf.predict(X[:9])
    assert clf.booster_._predict_engine is eng
    want = _direct_predict(clf.booster_, X[:9])
    assert np.array_equal(p1[:, 1], want)
