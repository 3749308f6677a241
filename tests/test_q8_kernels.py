"""The int8 quantised-gradient kernels at one feature group (F x B <= 2,048,
``ops/pallas_hist._ACC_ROWS_MAX``): ``hist_leaf_q8``, the fused level pass
``hist_level_q8`` and the one-kernel front, in their two weight layouts
((g, h, count), and (g, count) under a constant hessian).

Each is held against something that shares no code with it: the leaf kernel
against int64 sums in numpy, the level kernel against the XLA router followed
by the leaf kernel, whole models against the scatter histograms on the same
quantised gradients, and the 2-channel layout against the 3-channel one. The
Pallas kernels run interpreted, the same int8 x int8 contraction the chip
runs. (The path wider than one group is test_wide_path.py's.)"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import grow_depthwise as gd
from lightgbm_tpu.ops import histogram as hg
from lightgbm_tpu.ops import pallas_hist as ph

from _trees import same_trees

N, F, B = 220, 7, 16
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
    }


def _logloss_gh(score, label_pos):
    t = 2.0 * label_pos - 1.0
    resp = 1.0 / (1.0 + jnp.exp(t * score))
    return -t * resp, resp * (1.0 - resp)


def _quant(rows, const_hess):
    bag = rows["bag"]
    if const_hess:
        g, h = (rows["score"] - rows["label"]) * bag, jnp.ones(N) * bag
    else:
        grad, hess = _logloss_gh(rows["score"], rows["label_pos"])
        g, h = grad * bag, hess * bag
    c = (bag > 0).astype(jnp.float32)
    return hg.make_quant(g, h, c, SEED, const_hess=const_hess)


# ---------------------------------------------------------------------------
# the leaf kernel against integer sums

@pytest.mark.parametrize("s", [1, 32, 127])
@pytest.mark.parametrize("const_hess", [False, True])
def test_hist_leaf_q8_equals_integer_sums(rows, const_hess, s):
    """``hist_pallas_q8`` is the int64 sum of (gq, hq, cq) by (slot, feature,
    bin), dequantised as ``_dequant_stack`` does: exactly. Rows in slot ``s``
    are dropped."""
    q = _quant(rows, const_hess)
    hq, ch = hg._q8_h_arg(q)
    assert ch == const_hess
    slot = np.random.default_rng(s).integers(0, s + 1, size=N).astype(np.int32)
    assert (slot == s).any()
    got = np.asarray(ph.hist_pallas_q8(
        rows["bins_T"], q.gq, hq, q.cq, jnp.asarray(slot), s, B,
        q.scale_g, q.scale_h, const_hess=ch, interpret=True))

    bins = np.asarray(rows["bins"]).astype(np.int64)
    keep = slot < s
    acc = np.zeros((3, s, F, B), np.int64)
    chans = (q.gq, q.cq if const_hess else q.hq, q.cq)
    for k, w in enumerate(chans):
        w = np.asarray(w).astype(np.int64)
        for j in range(F):
            np.add.at(acc[k], (slot[keep], j, bins[keep, j]), w[keep])
    sg = np.float32(q.scale_g) * np.float32(1.0 / 127.0)
    sh = np.float32(q.scale_h) * np.float32(1.0 / 127.0)
    ref = np.stack([acc[0].astype(np.float32) * sg,
                    acc[1].astype(np.float32) * sh,
                    acc[2].astype(np.float32)], axis=1)       # [S, 3, F, B]
    assert got.dtype == np.float32 and ref.dtype == np.float32
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# the level kernels' per-row decode against a plain table lookup

@pytest.mark.parametrize("l", [1, 32, 255, 600])
def test_decode_leaf_is_table_lookup(l):
    """``_decode_leaf`` over ``_route_tabs`` is ``table[idx]`` for every row
    of the tables, entries in [-1, 599] (both bytes used), and an id at
    ``l``, past it or negative decodes to -1 everywhere: does not split."""
    r = np.random.default_rng(l)
    rows = r.integers(-1, 600, size=(7, l))
    rows[:, 0], rows[:, -1] = -1, 599
    na_bin = r.integers(-1, 600, size=600)
    na_bin[[0, 599]] = 599, -1
    i32 = lambda a: jnp.asarray(a, dtype=jnp.int32)
    tabs = ph._route_tabs(hg.RouteTables(*(i32(x) for x in rows[:6]),
                                         is_cat=i32(rows[6])), i32(na_bin))
    assert tabs.shape == (16, -(-l // 32) * 32)
    ids = np.concatenate([r.integers(0, l, size=500), [0, l - 1, l, l + 1,
                          tabs.shape[1], tabs.shape[1] + 7, 100_000, -1]])
    c = len(ids)

    def kern(lid_ref, tabs_ref, out_ref):
        out_ref[:] = ph._decode_leaf(lid_ref[:].reshape(1, c), tabs_ref, c)[0]

    got = np.asarray(pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((8, c), jnp.float32),
        interpret=True)(i32(ids), tabs))
    live = (ids >= 0) & (ids < l)
    want = np.full((8, c), -1)
    want[:7, live] = rows[:, ids[live]]
    want[7, live] = na_bin[np.maximum(rows[0, ids[live]], 0)]
    np.testing.assert_array_equal(got, want)
    assert (got[0, ~live] == -1).all() and (~live).sum() >= 6


# ---------------------------------------------------------------------------
# the level kernel against the XLA router followed by the leaf kernel

def _level_tables(s, categorical, thr255=False, last=False):
    """One level over 2s + 2 leaves: the first s may split (one in five does
    not), one child to slot i and the other to the sentinel s (its histogram
    is the parent's less its sibling's), the right child a new leaf.
    ``last``: the level that fills a budget of 2s + 1 leaves (255 at s =
    127): s + 1 leaves hold rows, the first s split, their right children
    the leaves s + 1 to 2s."""
    r = np.random.default_rng(100 + s)
    l = 2 * s + 1 if last else 2 * s + 2
    splits = np.arange(l) < s
    feat = np.where(splits & (last | (r.random(l) < 0.8)),
                    r.integers(0, F, size=l), -1)
    left_small = r.random(l) < 0.5
    own = np.where(splits, np.arange(l), s)
    i32 = lambda a: jnp.asarray(a, dtype=jnp.int32)
    cat = {}
    if categorical:
        cat = {"is_cat": i32(r.random(l) < 0.5),
               "member": jnp.asarray((r.random((l, B)) < 0.5)
                                     .astype(np.float32))}
    thr = r.integers(0, B - 1, size=l)
    if thr255:      # a byte past int8: no bin is above it, every row goes left
        thr[::3] = 255
    new_leaf = (s + 1 if last else s) + np.arange(l)
    return hg.RouteTables(
        i32(feat), i32(thr),
        i32(r.integers(0, 2, size=l)), i32(np.minimum(new_leaf, l - 1)),
        i32(np.where(left_small, own, s)), i32(np.where(left_small, s, own)),
        **cat)


@pytest.mark.parametrize("tabs", ["all_leaves", "live_leaves", "thr255",
                                  "last_level"])
@pytest.mark.parametrize("categorical", [False, True])
@pytest.mark.parametrize("s", [32, 127])
@pytest.mark.parametrize("const_hess", [False, True])
def test_hist_level_q8_equals_route_then_leaf(rows, const_hess, s,
                                              categorical, tabs):
    """``hist_routed_fused_q8`` is ``route_level`` (XLA gathers) followed by
    ``hist_pallas_q8`` on its slots: histograms and new leaf ids, exactly;
    and its leaf ids are the ``route_level`` kernel's over the same matrix
    (what a level that builds no histograms runs: histogram.route_only).
    Half of the features have a missing-value bin, and rows in it meet both
    default directions. ``live_leaves``: the kernel gets the tables cut to
    the s leaves that hold rows (what the grower hands a shallow level),
    the reference the whole tables; ``thr255``: thresholds of 255;
    ``last_level``: the level that fills a budget of 2s + 1 leaves (a
    255-leaf tree's at s = 127), rows on its s + 1 live leaves."""
    q = _quant(rows, const_hess)
    hq, ch = hg._q8_h_arg(q)
    last = tabs == "last_level"
    tables = _level_tables(s, categorical, thr255=tabs == "thr255",
                           last=last)
    l = s if tabs == "live_leaves" else tables.feat.shape[0]
    r = np.random.default_rng(s)
    lid = r.integers(0, s + 1 if last else l, size=N)
    na = np.where(np.arange(F) % 2 == 0, B - 1, -1)
    # a row in its split feature's missing bin, under either default
    feat, bins = np.asarray(tables.feat), np.asarray(rows["bins"])
    for d in (0, 1):
        leaf = next(j for j in range(s) if feat[j] >= 0 and na[feat[j]] >= 0
                    and int(tables.dleft[j]) == d)
        lid[np.flatnonzero(bins[:, feat[leaf]] == B - 1)[d]] = leaf
    lid, na_bin = jnp.asarray(lid, jnp.int32), jnp.asarray(na, jnp.int32)
    hist, lid2 = ph.hist_routed_fused_q8(
        rows["bins_T"], q.gq, hq, q.cq, lid,
        jax.tree.map(lambda a: a[:l], tables), na_bin, s, B,
        q.scale_g, q.scale_h, const_hess=ch, interpret=True)
    slot, lid_ref = hg.route_level(rows["bins"], lid, tables, na_bin, s)
    assert (np.asarray(slot) < s).any() and (np.asarray(slot) == s).any()
    assert (np.asarray(lid_ref) != np.asarray(lid)).any()
    feat_r = feat[np.asarray(lid)]
    in_na = (feat_r >= 0) & (bins[np.arange(N), np.maximum(feat_r, 0)]
                             == na[feat_r])
    assert set(np.asarray(tables.dleft)[np.asarray(lid)][in_na]) == {0, 1}
    ref = ph.hist_pallas_q8(
        rows["bins_T"], q.gq, hq, q.cq, slot, s, B, q.scale_g, q.scale_h,
        const_hess=ch, interpret=True)
    np.testing.assert_array_equal(np.asarray(lid2), np.asarray(lid_ref))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(ref))
    _, lid_routed = ph.route_level_pallas(
        rows["bins_T"], lid, jax.tree.map(lambda a: a[:l], tables), na_bin,
        s, interpret=True)
    np.testing.assert_array_equal(np.asarray(lid_routed), np.asarray(lid2))


# ---------------------------------------------------------------------------
# whole models across the booster x objective matrix

PALLAS_PARAMS = {"num_leaves": 7, "max_bin": 31, "min_data_in_leaf": 5,
                 "verbosity": -1, "prewarm": 0, "histogram_impl": "pallas",
                 "use_quantized_grad": "true"}

BOOSTER_EXTRA = {
    "gbdt": {},
    "dart": {"skip_drop": 0.0, "drop_rate": 0.5},
    "goss": {"top_rate": 0.3, "other_rate": 0.2},
    "rf": {"bagging_freq": 1, "bagging_fraction": 0.8},
}


def _matrix_data(seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(N, F).astype(np.float32)
    yb = (X[:, 0] + 0.3 * rng.rand(N) > 0.65).astype(np.float32)
    yr = (X[:, 1] * 2.0 + rng.rand(N)).astype(np.float32)
    return X, {"binary": yb, "regression": yr}


# binary at seed 0 (and 1) grows a leaf that is all one class: its best
# "split" has a gain of f32 round-off (8e-6 on one path, 2e-5 on the other),
# and whether that dust wins a place in the tree depends on the summation
# order. Seed 2 has no such leaf
DATA_SEED = {"regression": 0, "binary": 2}


def _train(params, X, y):
    return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                     num_boost_round=3)


@pytest.mark.parametrize("boosting", ["gbdt", "dart", "goss", "rf"])
@pytest.mark.parametrize("objective", ["regression", "binary"])
def test_one_group_train_matches_scatter(boosting, objective):
    """``lgb.train`` at one feature group (the fused level kernel, and the
    one-kernel front where its gates pass: gbdt and dart, whose gradients
    are the objective's own) grows the trees the scatter histograms grow
    from the same quantised gradients."""
    X, ys = _matrix_data(DATA_SEED[objective])
    base = dict(PALLAS_PARAMS, objective=objective, boosting=boosting,
                **BOOSTER_EXTRA[boosting])
    a = _train(base, X, ys[objective])
    b = _train(dict(base, histogram_impl="scatter"), X, ys[objective])
    gp = a._gbdt.gp
    assert gp.quant and ph.one_group(X.shape[1], gp.max_bin)
    same_trees(a, b)


def _chain_data(n=3000, f=14, seed=5):
    """A decision list: the label is the first feature that is set, so each
    level peels one pure leaf off and the tree is a chain 14 levels deep."""
    rng = np.random.RandomState(seed)
    X = (rng.rand(n, f) < 0.25).astype(np.float32)
    first = np.where(X.any(axis=1), X.argmax(axis=1), f)
    return X, {"regression": (f - first).astype(np.float32),
               "binary": (first % 2).astype(np.float32)}


@pytest.mark.parametrize("num_leaves", [15, 255])
@pytest.mark.parametrize("objective", ["regression", "binary"])
def test_unbalanced_train_matches_scatter(objective, num_leaves):
    """As ``test_one_group_train_matches_scatter`` on a tree that outgrows
    the unrolled levels: its last levels run in the schedule's ``while_loop``
    tail, whose route tables hold all ``num_leaves``, the first ones in the
    groups that hold a level's leaves (``level_groups``). The gain floor
    keeps round-off "splits" of pure leaves out of both trees."""
    X, ys = _chain_data()
    base = dict(PALLAS_PARAMS, objective=objective, num_leaves=num_leaves,
                min_gain_to_split=0.05)
    a = _train(base, X, ys[objective])
    b = _train(dict(base, histogram_impl="scatter"), X, ys[objective])
    assert a._gbdt.gp.quant and ph.one_group(X.shape[1], a._gbdt.gp.max_bin)
    unrolled = math.ceil(math.log2(num_leaves - 1)) + 1
    groups = gd.level_groups(num_leaves, -1, True)
    assert groups[-1][2] > unrolled and groups[-1][3] == num_leaves
    for tree in same_trees(a, b):
        assert tree.max_depth > unrolled


# ---------------------------------------------------------------------------
# the level schedule's decode widths

@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("max_depth", [-1, 3, 8])
@pytest.mark.parametrize("num_leaves", [2, 15, 31, 32, 255, 600])
def test_level_groups_hold_their_leaves(num_leaves, max_depth, use_pallas):
    """Every group's decode width is at least the most leaves that can exist
    before its last level (a level at most doubles them), the groups tile
    the levels, and the group that holds the tail decodes ``num_leaves``."""
    groups = gd.level_groups(num_leaves, max_depth, use_pallas)
    levels = max_depth if max_depth > 0 else max(1, num_leaves - 1)
    assert groups[0][1] == 0 and groups[-1][2] == levels
    for (w, k0, k1, l_dec), nxt in zip(groups, groups[1:] + [None]):
        assert k0 < k1 and (nxt is None or nxt[1] == k1)
        assert l_dec >= min(num_leaves, 2 ** (k1 - 1))
        assert l_dec <= num_leaves and 1 <= w <= max(1, num_leaves // 2)
        # a level's splits fit its slots
        assert w >= min(2 ** k0, num_leaves // 2, num_leaves - 1)
    unrolled = math.ceil(math.log2(max(num_leaves - 1, 2))) + 1
    if levels > unrolled:                   # a while_loop tail
        assert groups[-1][3] == num_leaves
    if num_leaves == 255 and max_depth < 0 and use_pallas:
        assert [(g[0], g[3]) for g in groups] == [(32, 32), (127, 255)]


@pytest.mark.parametrize("boosting", ["gbdt", "dart"])
def test_models_bit_identical_2ch_vs_3ch(monkeypatch, boosting):
    """Const-hessian elision (2 channels) vs the flag forced off (3
    channels): same trees, bit for bit. Only the auto-gradient boosters
    reach the elided kernels."""
    import lightgbm_tpu.objectives as O
    X, ys = _matrix_data()
    params = dict(PALLAS_PARAMS, objective="regression", boosting=boosting,
                  **BOOSTER_EXTRA[boosting])

    def run():
        bst = _train(params, X, ys["regression"])
        return bst.predict(X, raw_score=True), bst.model_to_string()

    pred_2, model_2 = run()
    monkeypatch.setattr(O.RegressionL2, "is_constant_hessian", False)
    pred_3, model_3 = run()
    np.testing.assert_array_equal(pred_2, pred_3)
    assert model_2 == model_3
