"""The one-kernel front (grad+quant+hist0) and the warm-path zero-compile
guarantees.

Kernel parity runs the pallas kernels in interpret mode on CPU and asserts
BIT-exact agreement with the unfused reference chain — the fused front's
contract is bit-identity, not tolerance. End-to-end parity forces
histogram_impl=pallas + quantized gradients through the public train API
and diffs whole models with the fused front monkeypatched away. The
zero-compile tests drive a warmed DART booster and a warmed online refit
cycle under the JAX lowering counter: steady-state work must lower ZERO new
XLA programs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import histogram as hg
from lightgbm_tpu.ops import pallas_hist as ph

N, F, B, L = 1000, 7, 16, 8
SEED = 12345


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, B, size=(N, F)), dtype=jnp.uint8)
    return {
        "bins": bins, "bins_T": bins.T,
        "score": jnp.asarray(rng.normal(size=N).astype(np.float32)),
        "label": jnp.asarray(rng.normal(size=N).astype(np.float32)),
        "label_pos": jnp.asarray((rng.random(N) < 0.5).astype(np.float32)),
        "bag": jnp.asarray((rng.random(N) < 0.8).astype(np.float32)),
        "lid": jnp.asarray(rng.integers(0, L, size=N), dtype=jnp.int32),
        "na_bin": jnp.full((F,), -1, dtype=jnp.int32),
    }


def _logloss_gh(score, label_pos):
    t = 2.0 * label_pos - 1.0
    resp = 1.0 / (1.0 + jnp.exp(t * score))
    return -t * resp, resp * (1.0 - resp)


# ---------------------------------------------------------------------------
# kernel-level bit-identity: fused front vs the unfused chain

def test_grad_quant_hist0_l2_bit_exact(rows):
    grad = rows["score"] - rows["label"]
    bag = rows["bag"]
    g, h = grad * bag, jnp.ones(N) * bag
    c = (bag > 0).astype(jnp.float32)
    q = hg.make_quant(g, h, c, SEED, const_hess=True)
    hist_ref = hg.hist_leaf(rows["bins"], g, h, c, B, impl="pallas", quant=q)
    gq, hq, cq, sg, sh, hist0 = ph.grad_quant_hist0_pallas(
        rows["bins_T"], rows["score"], rows["label"], bag, SEED, ("l2",), B,
        const_hess=True, interpret=True)
    assert hq is None                      # const-hess: no hessian channel
    np.testing.assert_array_equal(np.asarray(q.gq), np.asarray(gq))
    np.testing.assert_array_equal(np.asarray(q.cq), np.asarray(cq))
    assert np.asarray(q.scale_g) == np.asarray(sg)
    assert np.asarray(q.scale_h) == np.asarray(sh)
    np.testing.assert_array_equal(np.asarray(hist_ref), np.asarray(hist0))


def test_grad_quant_hist0_logloss_bit_exact(rows):
    bag = rows["bag"]
    grad, hess = _logloss_gh(rows["score"], rows["label_pos"])
    g, h = grad * bag, hess * bag
    c = (bag > 0).astype(jnp.float32)
    q = hg.make_quant(g, h, c, SEED, const_hess=False)
    hist_ref = hg.hist_leaf(rows["bins"], g, h, c, B, impl="pallas", quant=q)
    gq, hq, cq, sg, sh, hist0 = ph.grad_quant_hist0_pallas(
        rows["bins_T"], rows["score"], rows["label_pos"], bag, SEED,
        ("logloss", 1.0, 1.0, 1.0), B, const_hess=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(q.gq), np.asarray(gq))
    np.testing.assert_array_equal(np.asarray(q.hq), np.asarray(hq))
    np.testing.assert_array_equal(np.asarray(q.cq), np.asarray(cq))
    assert np.asarray(q.scale_g) == np.asarray(sg)
    assert np.asarray(q.scale_h) == np.asarray(sh)
    np.testing.assert_array_equal(np.asarray(hist_ref), np.asarray(hist0))


def test_leaf_sums_grad_bit_exact(rows):
    bag = rows["bag"]
    grad, hess = _logloss_gh(rows["score"], rows["label_pos"])
    g, h = grad * bag, hess * bag
    c = (bag > 0).astype(jnp.float32)
    ref = ph.leaf_sums_pallas(g, h, c, rows["lid"], L, interpret=True)
    got = ph.leaf_sums_grad_pallas(
        rows["score"], rows["label_pos"], bag, rows["lid"],
        ("logloss", 1.0, 1.0, 1.0), L, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ---------------------------------------------------------------------------
# end-to-end: whole models bit-identical with the fused front on vs off

def _train_data():
    rng = np.random.RandomState(0)
    X = rng.rand(400, 8).astype(np.float32)
    yb = (X[:, 0] + 0.3 * rng.rand(400) > 0.65).astype(np.float32)
    yr = (X[:, 1] * 2.0 + rng.rand(400)).astype(np.float32)
    return X, yb, yr


PALLAS_PARAMS = {"num_leaves": 7, "max_bin": 31, "min_data_in_leaf": 5,
                 "verbosity": -1, "prewarm": 0, "histogram_impl": "pallas",
                 "use_quantized_grad": "true"}


@pytest.mark.parametrize("boosting,extra", [
    ("gbdt", {}), ("dart", {"skip_drop": 0.0, "drop_rate": 0.5})],
    ids=["gbdt", "dart"])
@pytest.mark.parametrize("objective,objcls", [("binary", "Binary"),
                                              ("regression", "RegressionL2")])
def test_fused_front_models_bit_identical(monkeypatch, objective, objcls,
                                          boosting, extra):
    import lightgbm_tpu.objectives as O
    X, yb, yr = _train_data()
    y = yb if objective == "binary" else yr
    params = dict(PALLAS_PARAMS, objective=objective, boosting=boosting,
                  **extra)

    def run():
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        num_boost_round=3)
        return bst.predict(X, raw_score=True), bst.model_to_string()

    pred_fused, model_fused = run()
    # same data, same seeds, fused front disabled -> must be bit-equal
    monkeypatch.setattr(getattr(O, objcls), "fused_grad_spec",
                        lambda self: None)
    pred_unfused, model_unfused = run()
    np.testing.assert_array_equal(pred_fused, pred_unfused)
    assert model_fused == model_unfused


# ---------------------------------------------------------------------------
# zero dispatch-time compiles on warmed paths (ISSUE 17 acceptance)

def test_warm_dart_predict_and_update_zero_lowerings():
    """A warmed DART booster: repeat predicts AND extra boosting iterations
    (drop + normalize + re-add every iteration via skip_drop=0) must lower
    nothing new."""
    X, yb, _ = _train_data()
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1, "prewarm": 0,
              "boosting": "dart", "skip_drop": 0.0, "drop_rate": 0.5}
    bst = lgb.train(params, lgb.Dataset(X, label=yb, params=params),
                    num_boost_round=3)
    bst.predict(X)                           # warm the serving path
    with jtu.count_jit_and_pmap_lowerings() as n:
        p1 = bst.predict(X)
        p2 = bst.predict(X)
    assert n() == 0, f"{n()} lowerings in warmed DART predict"
    np.testing.assert_array_equal(p1, p2)
    with jtu.count_jit_and_pmap_lowerings() as n:
        bst.update()
        bst.update()
    assert n() == 0, f"{n()} lowerings in warmed DART iterations"


def test_warm_online_refit_cycle_zero_lowerings():
    """A warmed online refit cycle: with online_max_rows pinning the
    sliding-window dataset shape and leaf refit keeping every tree-table
    shape, a second same-shape feed+cycle must lower ZERO new programs."""
    from lightgbm_tpu.basic import Dataset
    from lightgbm_tpu.online import OnlineTrainer
    rng = np.random.RandomState(3)
    X = rng.rand(240, 6)
    y = X[:, 0] + X[:, 1]
    params = {"objective": "regression", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1, "prewarm": 0,
              "num_boost_round": 3, "online_refit_rows": 240,
              "online_max_rows": 240}
    tr = OnlineTrainer(params, Dataset(X, label=y, params=params))
    Xa, Xb = rng.rand(40, 6), rng.rand(40, 6)
    tr.feed(Xa, Xa[:, 0] + Xa[:, 1])
    assert tr.refit_now() == 1               # warm cycle (append+refit+publish)
    tr.feed(Xb, Xb[:, 0] + Xb[:, 1])
    with jtu.count_jit_and_pmap_lowerings() as n:
        assert tr.refit_now() == 2
    assert n() == 0, f"{n()} lowerings in warmed online refit cycle"
