"""The serial Pallas trainer reads ``Dataset.bins_T`` in place: the cached
matrix has the shape the kernels tile ([F_pad, N_pad],
``ops/pallas_hist.resident_shape``), the growers keep their row vectors N_pad
long for the whole tree (``ops/histogram.resident_rows``), and no level pass
pads or slices an array. CPU, Pallas interpreted, N % 8,192 != 0 and
N % 2,048 != 0.

(a) the same model as through the wrappers' own per-pass padding (the grower
    handed an unpadded ``bins_T``);
(b) the step holds no pad / slice / transpose / copy of the matrix and no pad
    or slice of a row vector inside a level loop;
(c) whatever faces the host has N rows; the ``bins`` setter rebuilds the cache;
(d) a wide case for the feature pad;
(e) the ``hist_path`` event says what the step was handed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import obs, prewarm
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.grow_depthwise import grow_tree_depthwise
from lightgbm_tpu.ops import pallas_hist as PH

N = 3000                     # 3000 % 2048 != 0, 3000 % 8192 != 0
N_PAD = 8192
BASE = {"num_leaves": 7, "max_bin": 63, "verbosity": -1,
        "histogram_impl": "pallas", "min_data_in_leaf": 5}


def _data(kind="binary", f=10, n=N, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f).astype(np.float32)
    z = X[:, 0] + 0.5 * X[:, 1] * X[:, f - 1] + 0.3 * r.randn(n)
    if kind == "l2":
        return X, z.astype(np.float32)
    if kind == "multi":
        return X, np.digitize(z, [-0.5, 0.5]).astype(np.float32)
    return X, (z > 0).astype(np.float32)


@pytest.fixture
def unpadded(monkeypatch):
    """The fallback: ``Dataset.bins_T`` as it was, ``bins.T`` at [F, N], so
    that every wrapper pads and slices per pass (``_pad_rows``)."""
    monkeypatch.setattr(lgb.Dataset, "bins_T",
                        property(lambda self: self.bins.T))
    monkeypatch.setattr(lgb.Dataset, "bins_T_shape",
                        property(lambda self: self.bins.shape[::-1]))


def _train(params, X, y, rounds=3):
    ds = lgb.Dataset(X, label=y, params=params)
    return lgb.train(params, ds, num_boost_round=rounds), ds


# ---- (a) + (d): the same model through both paths --------------------------
CASES = {
    "binary": ("binary", 10, {"objective": "binary"}),
    "l2": ("l2", 10, {"objective": "regression"}),
    "multiclass": ("multi", 10, {"objective": "multiclass", "num_class": 3}),
    "bagging": ("binary", 10, {"objective": "binary", "bagging_freq": 1,
                               "bagging_fraction": 0.6}),
    "goss": ("binary", 10, {"objective": "binary", "boosting": "goss"}),
    "lossguide": ("binary", 10, {"objective": "binary",
                                 "grow_policy": "lossguide"}),
    "lean": ("binary", 10, {"objective": "binary",
                            "histogram_pool_size": 0.01}),
    "l1_renewal": ("l2", 10, {"objective": "regression_l1"}),
    "cegb_lazy": ("binary", 10, {"objective": "binary", "cegb_tradeoff": 1.0,
                                 "cegb_penalty_feature_lazy": [0.1] * 10}),
    # F * B = 40 * 64 > 2,048 and 40 % 32 != 0: the feature pad (d)
    "wide": ("binary", 40, {"objective": "binary"}),
    "wide_lossguide": ("binary", 40, {"objective": "binary",
                                      "grow_policy": "lossguide"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_same_model_as_the_per_pass_padding(case, monkeypatch):
    kind, f, extra = CASES[case]
    X, y = _data(kind, f)
    p = {**BASE, **extra}
    bst, ds = _train(p, X, y)
    f_pad = 64 if f == 40 else f
    assert ds.bins_T.shape == (f_pad, N_PAD)
    text = bst.model_to_string()
    with monkeypatch.context() as m:
        m.setattr(lgb.Dataset, "bins_T", property(lambda self: self.bins.T))
        m.setattr(lgb.Dataset, "bins_T_shape",
                  property(lambda self: self.bins.shape[::-1]))
        ref, ds_ref = _train(p, X, y)
        assert ds_ref.bins_T.shape == (f, N)
    assert text == ref.model_to_string()
    assert bst.num_trees() >= 3 and "split_feature" in text


# ---- (b) what the step is made of ------------------------------------------
def _walk(jaxpr, in_loop=False):
    """(equation, inside a while / cond body) over a jaxpr and the jaxprs
    its equations call; a ``pallas_call``'s kernel is not entered."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        name = eqn.primitive.name
        if name == "pallas_call":
            continue
        inner = in_loop or name in ("while", "cond")
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inner)


_COPIES = ("pad", "slice", "dynamic_slice", "transpose", "copy", "copy_p",
           "concatenate", "dynamic_update_slice")


def _step_copies(f, extra=None):
    """(copies of the matrix anywhere, pads / slices of a row vector inside a
    level loop, Mosaic kernels) of the fused step at F = ``f``."""
    X, y = _data("binary", f)
    p = {**BASE, "objective": "binary", **(extra or {})}
    g = lgb.Booster(p, lgb.Dataset(X, label=y, params=p))._gbdt
    step = g._build_fused_step(False)
    avals = prewarm.step_avals(g)
    eqns = list(_walk(jax.make_jaxpr(step)(*avals).jaxpr))

    def shapes(e):
        return [(v.aval.shape, v.aval.dtype) for v in e.invars + e.outvars
                if hasattr(v.aval, "shape")]
    matrix = [(e.primitive.name, shapes(e)) for e, _ in eqns
              if e.primitive.name in _COPIES
              and any(len(s) == 2 and d == jnp.uint8 and s[1] in (N, N_PAD)
                      for s, d in shapes(e))]
    rows = [(e.primitive.name, shapes(e)) for e, loop in eqns
            if loop and e.primitive.name in ("pad", "slice", "dynamic_slice")
            and any(s in ((N,), (N_PAD,)) for s, _ in shapes(e))]
    kernels = sum(e.primitive.name == "pallas_call" for e, _ in eqns)
    text = step.lower(*avals).as_text()
    return matrix, rows, kernels, text


@pytest.mark.parametrize("f,extra", [
    (10, None), (40, None), (10, {"objective": "regression"})],
    ids=["fused", "wide", "fused_l2"])
def test_step_copies_no_resident_array(f, extra):
    matrix, rows, kernels, text = _step_copies(f, extra)
    assert kernels >= 3                       # front, level group, renewal
    assert not matrix, matrix
    assert not rows, rows
    f_pad = 64 if f == 40 else f
    assert f"tensor<{f_pad}x{N_PAD}xui8>" in text     # the argument is there
    bad = [l.strip()[:200] for l in text.splitlines()
           if ("stablehlo.pad" in l or "stablehlo.slice" in l
               or "stablehlo.transpose" in l)
           and (f"x{N_PAD}xui8>" in l or f"x{N}xui8>" in l)]
    assert not bad, bad


def test_the_reader_sees_the_per_pass_padding(unpadded):
    """The same reader over the fallback: handed an [F, N] matrix the
    wrappers pad it, and the leaf ids, in every pass."""
    matrix, rows, _, text = _step_copies(10)
    assert any(name == "pad" for name, _ in matrix)
    assert any(name == "pad" for name, _ in rows)
    assert any(name == "slice" for name, _ in rows)
    assert any("stablehlo.pad" in l and f"x{N}xui8>" in l
               for l in text.splitlines())


@pytest.mark.parametrize("f,b,slots", [(28, 64, 32), (40, 64, 4)],
                         ids=["fused", "grouped"])
def test_level_pass_wrappers_are_no_ops_on_resident_shapes(f, b, slots):
    """``hist_routed`` handed resident shapes: pallas_calls, no pad, no
    slice of a row-length array."""
    f_pad, n_pad = PH.resident_shape(N, f, b)
    L = 8
    tables = H.RouteTables(
        feat=jnp.zeros(L, jnp.int32), thr=jnp.zeros(L, jnp.int32),
        dleft=jnp.zeros(L, jnp.int32), new_leaf=jnp.arange(L, dtype=jnp.int32),
        slot_left=jnp.zeros(L, jnp.int32), slot_right=jnp.ones(L, jnp.int32))

    def level(bins, bins_T, g, h, c, lid, na):
        quant = H.make_quant(g, h, c, jnp.uint32(1))
        return H.hist_routed(bins, g, h, c, lid, tables, na, slots, b,
                             impl="pallas", bins_T=bins_T, quant=quant)
    rows = jnp.zeros(n_pad, jnp.float32)
    jaxpr = jax.make_jaxpr(level)(
        jnp.zeros((N, f), jnp.uint8), jnp.zeros((f_pad, n_pad), jnp.uint8),
        rows, rows, rows, jnp.zeros(n_pad, jnp.int32),
        jnp.zeros(f, jnp.int32))
    eqns = [e for e, _ in _walk(jaxpr.jaxpr)]
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    bad = [(e.primitive.name, [v.aval.shape for v in e.invars])
           for e in eqns if e.primitive.name in ("pad", "slice")
           and any(n_pad in v.aval.shape for v in e.invars
                   if hasattr(v.aval, "shape"))]
    assert not bad, bad
    hist, lid2 = jax.eval_shape(
        level, jax.ShapeDtypeStruct((N, f), jnp.uint8),
        jax.ShapeDtypeStruct((f_pad, n_pad), jnp.uint8), rows, rows, rows,
        jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        jax.ShapeDtypeStruct((f,), jnp.int32))
    assert hist.shape == (slots, 3, f, b) and lid2.shape == (n_pad,)


# ---- the shape rule ---------------------------------------------------------
@pytest.mark.parametrize("n,f,b,want", [
    (52_500_000, 28, 64, (28, 52_502_528)),        # HIGGS: what was padded to
    (1_200_000, 2000, 64, (2016, 1_204_224)),      # Epsilon: +0.34% rows
    (147_000_000, 28, 64, (28, 147_005_440)),
    (8192 * 3, 32, 64, (32, 8192 * 3)),            # a multiple: nothing
    (1, 40, 64, (64, 8192)),
    (N, 10, 256, (16, N_PAD)),                     # 8 features a group
])
def test_resident_shape(n, f, b, want):
    got = PH.resident_shape(n, f, b)
    assert got == want
    for chunk in (PH._CHUNK, 2048, PH._CHUNK_Q8, 8192):
        assert got[1] % chunk == 0 and 0 <= got[1] - n < PH._ROW_ALIGN
    fg, groups = PH.feature_grouping(got[0], b)
    assert fg * groups == got[0]              # the wrappers pad no feature


def test_resident_rows_pads_with_what_the_wrappers_pad_with():
    n, n_res, L = 5, 8, 7
    v = jnp.arange(1, n + 1, dtype=jnp.float32)
    g, h, c, fused, leaf_id = H.resident_rows(
        jnp.zeros((3, n_res), jnp.uint8), n, L, v, v, v, fused=(v, v, v))
    for x in (g, h, c) + fused:
        np.testing.assert_array_equal(x, [1, 2, 3, 4, 5, 0, 0, 0])
    np.testing.assert_array_equal(leaf_id, [0] * n + [L] * 3)
    assert leaf_id.dtype == jnp.int32
    # an [F, N] matrix, or none (off the Pallas path): nothing is padded
    for bt in (jnp.zeros((3, n), jnp.uint8), None):
        g, _, _, fused, leaf_id = H.resident_rows(bt, n, L, v, v, v)
        assert g is v and fused is None and leaf_id.shape == (n,)


# ---- (c) the host sees N rows ----------------------------------------------
def test_host_facing_shapes_and_the_bins_setter():
    X, y = _data("binary")
    p = {**BASE, "objective": "binary", "metric": "binary_logloss"}
    ds = lgb.Dataset(X, label=y, params=p)
    res = {}
    bst = lgb.train(p, ds, num_boost_round=3, valid_sets=[ds],
                    valid_names=["training"], evals_result=res,
                    verbose_eval=False)
    assert ds.bins.shape == (N, 10) and ds.num_data == N
    assert ds.bins_T.shape == ds.bins_T_shape == (10, N_PAD)
    np.testing.assert_array_equal(ds.bins_T[:, :N], ds.bins.T)
    assert not np.asarray(ds.bins_T[:, N:]).any()
    g = bst._gbdt
    assert g.train_score.shape == (N,)
    assert np.isfinite(np.asarray(g.train_score)).all()
    (_, _, val, _), = g.eval_train()
    assert np.isfinite(val)
    pred = bst.predict(X)
    leaves = bst.predict(X, pred_leaf=True)
    assert pred.shape == (N,) and leaves.shape == (N, 3)
    assert leaves.max() < 7
    # the training-set score is the model's prediction, row for row
    raw = bst.predict(X, raw_score=True)
    np.testing.assert_allclose(np.asarray(g.train_score), raw, rtol=1e-5,
                               atol=1e-5)
    # the setter drops the cache; the next use builds a padded one
    cached = ds.bins_T
    ds.bins = ds.bins
    assert ds._bins_T is None
    assert ds.bins_T.shape == (10, N_PAD) and ds.bins_T is not cached
    np.testing.assert_array_equal(ds.bins_T, cached)


def test_shape_is_known_while_the_matrix_streams():
    """The background prewarm lowers its step once the metadata is
    published and before ``bins`` exists: the avals and the ``hist_path``
    event take the matrix's shape from the metadata."""
    X, y = _data("binary")
    p = {**BASE, "objective": "binary"}
    ds = lgb.Dataset(X, label=y, params=p)
    g = lgb.Booster(p, ds)._gbdt
    want = prewarm.step_avals(g)[12]
    matrix = ds.bins
    ds._bins_dev, ds._constructed = None, False     # as during the stream
    ds._num_features_used = 10
    try:
        assert ds.bins_T_shape == (10, N_PAD)
        got = prewarm.step_avals(g)[12]
        g._build_fused_step(False)                  # emits hist_path
    finally:
        ds._bins_dev, ds._constructed = matrix, True
    assert (got.shape, got.dtype) == (want.shape, want.dtype) \
        == ((10, N_PAD), np.uint8)


def test_scatter_run_pads_nothing():
    """Off the Pallas path nobody asks for ``bins_T`` and the grower's rows
    stay N long."""
    X, y = _data("binary")
    p = {**BASE, "objective": "binary", "histogram_impl": "scatter"}
    bst, ds = _train(p, X, y)
    assert ds._bins_T is None and not bst._gbdt._use_bt()
    _, leaf_id = jax.eval_shape(
        lambda b, v: grow_tree_depthwise(
            b, v, v, v, jnp.full(10, 64, jnp.int32),
            jnp.full(10, 256, jnp.int32), jnp.ones(10, bool), bst._gbdt.gp),
        jax.ShapeDtypeStruct((N, 10), jnp.uint8),
        jax.ShapeDtypeStruct((N,), jnp.float32))
    assert leaf_id.shape == (N,)


# ---- (e) the event ----------------------------------------------------------
@pytest.mark.parametrize("f,impl,want", [
    (10, "pallas", {"bins_T_cached": True, "resident_rows": N_PAD,
                    "resident_features": 10}),
    (40, "pallas", {"bins_T_cached": True, "resident_rows": N_PAD,
                    "resident_features": 64, "feature_groups": 2}),
    (10, "scatter", {"bins_T_cached": False, "resident_rows": N,
                     "resident_features": 10}),
])
def test_hist_path_says_what_the_step_was_handed(f, impl, want):
    X, y = _data("binary", f)
    obs.reset()
    obs.configure(enabled=True)
    try:
        p = {**BASE, "objective": "binary", "histogram_impl": impl,
             "telemetry": True}
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=1)
        events = [e for e in obs.EVENTS.snapshot()
                  if e["type"] == "hist_path"]
    finally:
        obs.configure(enabled=False)
        obs.reset()
    assert events
    got = {k: events[-1][k] for k in want}
    assert got == want
    engaged = got["resident_rows"] % PH._ROW_ALIGN == 0
    assert engaged == (impl == "pallas")
