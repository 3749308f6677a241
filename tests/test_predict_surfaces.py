"""Prediction API surfaces untested until round 4: pred_leaf and
num_iteration slicing (reference analogs: test_engine.py pred_leaf cases and
Booster.predict(num_iteration=...))."""
import numpy as np

import lightgbm_tpu as lgb


def _model(rounds=8):
    rng = np.random.RandomState(6)
    X = rng.randn(600, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 15,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), rounds)
    return bst, X


def _leaf_sum(trees, leaves):
    """Sum of each row's indexed leaf values across trees."""
    acc = np.zeros(leaves.shape[0])
    for t, tr in enumerate(trees):
        acc += np.asarray(tr.leaf_value)[leaves[:, t].astype(int)]
    return acc


def test_pred_leaf_shape_and_consistency():
    bst, X = _model()
    trees = bst._ensure_host_trees()
    leaves = bst.predict(X[:50], pred_leaf=True)
    assert leaves.shape == (50, len(trees))
    # indices valid per tree
    for t, tr in enumerate(trees):
        assert leaves[:, t].min() >= 0
        assert leaves[:, t].max() < tr.num_leaves
    # summing the indexed leaf values reproduces the raw score exactly
    raw = bst.predict(X[:50], raw_score=True)
    np.testing.assert_allclose(_leaf_sum(trees, leaves), raw,
                               rtol=1e-5, atol=1e-6)


def test_predict_num_iteration_slicing():
    bst, X = _model(rounds=10)
    raw_full = bst.predict(X[:100], raw_score=True)
    raw_all = bst.predict(X[:100], raw_score=True, num_iteration=10)
    np.testing.assert_allclose(raw_full, raw_all, rtol=1e-7)
    raw_3 = bst.predict(X[:100], raw_score=True, num_iteration=3)
    assert not np.allclose(raw_3, raw_full)
    # the 3-iteration slice must equal the sum of the first 3 trees' values
    trees = bst._ensure_host_trees()[:3]
    leaves = bst.predict(X[:100], pred_leaf=True)[:, :3]
    np.testing.assert_allclose(_leaf_sum(trees, leaves), raw_3,
                               rtol=1e-5, atol=1e-6)


def test_predict_uses_best_iteration_after_early_stop():
    rng = np.random.RandomState(7)
    X = rng.randn(800, 5)
    y = (X[:, 0] > 0).astype(np.float64)
    Xv = rng.randn(300, 5)
    yv = (Xv[:, 0] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 31,
                     "learning_rate": 0.8, "metric": "binary_logloss"},
                    ds, 200,
                    valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)],
                    early_stopping_rounds=3, verbose_eval=False)
    assert 0 < bst.best_iteration < 200
    # default predict slices at best_iteration
    p_default = bst.predict(Xv, raw_score=True)
    p_best = bst.predict(Xv, raw_score=True, num_iteration=bst.best_iteration)
    np.testing.assert_allclose(p_default, p_best, rtol=1e-7)


# ---- the binned walk ends when every row is on a leaf (PR 26) ---------------
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import predict as P

_B = 8            # bins per feature; bin _B - 1 is the NA bin
_F = 5


def _static_walk(t, bins, na_bin, max_steps):
    """The walk as it was: every one of ``max_steps`` steps, for every row.
    Returns the leaves and the last step that still moved a row."""
    ptr = np.full(len(bins), 0 if t["num_leaves"] > 1 else -1, np.int32)
    moved = 0
    for step in range(max_steps):
        if (ptr >= 0).any():
            moved = step + 1
        node = np.maximum(ptr, 0)
        feat = t["split_feature"][node]
        col = bins[np.arange(len(bins)), feat].astype(np.int32)
        left = np.where(col == na_bin[feat], t["default_left"][node],
                        col <= t["threshold_bin"][node])
        if "is_cat" in t:
            left = np.where(t["is_cat"][node], t["cat_mask"][node, col], left)
        nxt = np.where(left, t["left_child"][node], t["right_child"][node])
        ptr = np.where(ptr >= 0, nxt, ptr)
    return ~np.minimum(ptr, -1), moved


def _tree(left, right, rng, cat_nodes=()):
    """Flat arrays of a tree given its child tables (>= 0 a node, ~leaf
    below); features, thresholds and default directions drawn at random."""
    m = len(left)
    t = {"split_feature": rng.randint(0, _F, m).astype(np.int32),
         "threshold_bin": rng.randint(1, _B - 2, m).astype(np.int32),
         "default_left": rng.rand(m) < 0.5,
         "left_child": np.asarray(left, np.int32),
         "right_child": np.asarray(right, np.int32),
         "num_leaves": np.int32(m + 1)}
    if len(cat_nodes):
        t["is_cat"] = np.isin(np.arange(m), cat_nodes)
        t["cat_mask"] = rng.rand(m, _B) < 0.5
    return t


def _balanced(depth, rng, **kw):
    m = 2 ** depth - 1
    first_last = 2 ** (depth - 1) - 1      # first node of the deepest level
    left = [2 * i + 1 if i < first_last else ~(2 * (i - first_last))
            for i in range(m)]
    right = [2 * i + 2 if i < first_last else ~(2 * (i - first_last) + 1)
             for i in range(m)]
    return _tree(left, right, rng, **kw)


def _chain(num_leaves, rng):
    """Every split on the right child: depth num_leaves - 1."""
    m = num_leaves - 1
    t = _tree([~i for i in range(m)],
              [i + 1 if i < m - 1 else ~m for i in range(m)], rng)
    t["default_left"][:] = False
    return t


def _one_leaf(rng):
    t = _tree([-1], [-1], rng)
    t["num_leaves"] = np.int32(1)
    return t


def _bins(rng, n=300):
    bins = rng.randint(0, _B, (n, _F)).astype(np.uint8)   # NA bins among them
    bins[0] = _B - 2        # beyond every threshold: the chain's last leaf
    return bins, np.full(_F, _B - 1, np.int32)


def _route(t, bins, na_bin, max_steps):
    steps = []
    leaf = P.route_bins(
        *(jnp.asarray(t[k]) for k in ("split_feature", "threshold_bin",
                                      "default_left", "left_child",
                                      "right_child", "num_leaves")),
        jnp.asarray(bins), jnp.asarray(na_bin), max_steps,
        is_cat=jnp.asarray(t["is_cat"]) if "is_cat" in t else None,
        cat_mask=jnp.asarray(t["cat_mask"]) if "is_cat" in t else None,
        steps_out=steps)
    return np.asarray(leaf), int(steps[0])


@pytest.mark.parametrize("shape,depth", [
    ("balanced", 4), ("chain", 11), ("one_leaf", 0), ("categorical", 3),
    ("cut_short", 3)])
def test_walk_ends_when_every_row_is_on_a_leaf(shape, depth):
    """Same leaves as the static walk over num_leaves - 1 steps, in as many
    steps as the tree is deep; a ``max_steps`` below the depth still ends
    the walk there (``cut_short``: a chain of depth 11 held to 3)."""
    rng = np.random.RandomState(5)
    t = {"balanced": lambda: _balanced(4, rng),
         "chain": lambda: _chain(12, rng),
         "one_leaf": lambda: _one_leaf(rng),
         "categorical": lambda: _balanced(3, rng, cat_nodes=(0, 2, 5)),
         "cut_short": lambda: _chain(12, rng)}[shape]()
    bins, na_bin = _bins(rng)
    max_steps = 3 if shape == "cut_short" else max(int(t["num_leaves"]) - 1, 1)
    want, moved = _static_walk(t, bins, na_bin, max_steps)
    leaf, steps = _route(t, bins, na_bin, max_steps)
    np.testing.assert_array_equal(leaf, want)
    assert steps == moved == depth


def test_ensemble_of_unequal_depths_is_the_sum_of_its_trees():
    """Under vmap the loop runs until the deepest tree of the stack is done;
    the shallow trees' rows stay parked meanwhile."""
    rng = np.random.RandomState(9)
    trees = [_balanced(2, rng), _chain(8, rng), _one_leaf(rng),
             _balanced(3, rng)]
    bins, na_bin = _bins(rng)
    m = max(len(t["left_child"]) for t in trees)

    def pad(a, fill=0):
        return np.concatenate([a, np.full(m - len(a), fill, a.dtype)])
    stack = {k: np.stack([pad(t[k], -1 if k.endswith("child") else 0)
                          for t in trees])
             for k in ("split_feature", "threshold_bin", "default_left",
                       "left_child", "right_child")}
    stack["num_leaves"] = np.array([t["num_leaves"] for t in trees])
    stack["leaf_value"] = rng.randn(len(trees), m + 1).astype(np.float32)
    got = P.predict_bins_ensemble(
        {k: jnp.asarray(v) for k, v in stack.items()}, jnp.asarray(bins),
        jnp.asarray(na_bin), max_steps=m)
    want = np.zeros(len(bins), np.float32)
    for i, t in enumerate(trees):
        leaf, _ = _static_walk(t, bins, na_bin, m)
        want += stack["leaf_value"][i][leaf]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_rollback_on_a_row_sharded_mesh_takes_back_the_tree():
    """Two shards of the CPU devices conftest forces: the walk's ``any`` is a
    reduction across shards inside the loop's condition. Rolling back the
    last iteration subtracts exactly what the static walk would."""
    rng = np.random.RandomState(3)
    X = rng.randn(1001, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "num_shards": 2}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=3, verbose_eval=False)
    g = bst._gbdt
    assert len(g.train_set.bins.sharding.device_set) == 2
    before = np.asarray(g.train_score)
    tree = jax.tree.map(np.asarray, g.models_dev[-1])
    t = {k: getattr(tree, k) for k in (
        "split_feature", "threshold_bin", "default_left", "left_child",
        "right_child", "num_leaves")}
    leaf, _ = _static_walk(t, np.asarray(g.train_set.bins),
                           np.asarray(g.train_set.na_bin_dev), 14)
    bst.rollback_one_iter()
    want = before - tree.leaf_value[leaf][: len(before)]
    np.testing.assert_array_equal(np.asarray(g.train_score), want)


# ---- the walk as one Mosaic kernel over the feature-major matrix (PR 35) ----
from _trees import level_order_children
from lightgbm_tpu.ops import pallas_hist as PH


def _level_order(num_leaves, rng):
    return _tree(*level_order_children(num_leaves), rng)


def _feature_major(bins, f_pad=None):
    """``Dataset.bins_T`` of the rows: [F_pad, N_pad] uint8, zero padding."""
    n, f = bins.shape
    f_res, n_pad = PH.resident_shape(n, f, _B)
    return PH.resident_bins_T(jnp.asarray(bins), (f_pad or f_res, n_pad))


def _route_both(t, bins, na_bin, max_steps, f_pad=None):
    """(leaves, steps) of the XLA walk and of the kernel walk."""
    assert "is_cat" not in t
    tree = [jnp.asarray(t[k]) for k in (
        "split_feature", "threshold_bin", "default_left", "left_child",
        "right_child", "num_leaves")]
    out = []
    for bins_T in (None, _feature_major(bins, f_pad)):
        steps = []
        assert P.walk_path(bins_T, tree[0]) == (
            "xla" if bins_T is None else "kernel")
        leaf = P.route_bins(*tree, jnp.asarray(bins), jnp.asarray(na_bin),
                            max_steps, steps_out=steps, bins_T=bins_T)
        out.append((np.asarray(leaf), int(steps[0])))
    return out


_KERNEL_CASES = {
    # name: (tree, rows, F_pad of bins_T or None = F, max_steps or None)
    "depth8_255_leaves": (lambda r: _level_order(255, r), 9000, None, None),
    "depth8_complete": (lambda r: _balanced(8, r), 5000, None, None),
    "chain": (lambda r: _chain(40, r), 300, None, None),
    "chain_cut_short": (lambda r: _chain(12, r), 300, None, 3),
    "one_leaf": (_one_leaf, 300, None, None),
    "31_leaves": (lambda r: _level_order(31, r), 700, None, None),
    "1023_leaves": (lambda r: _level_order(1023, r), 4500, None, None),
    "rows_past_a_chunk_features_below_f_pad": (
        lambda r: _level_order(63, r), PH._CHUNK_Q8 + 905, 8, None),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
@pytest.mark.parametrize("missing", ["default_left", "default_right", "none"])
def test_kernel_walk_equals_the_xla_walk(case, missing):
    """Leaf indices and step count of ``walk_tree`` (interpreted) equal the
    XLA walk's exactly: over tree shapes and table lengths, rows and feature
    rows that do not fill the kernel's blocks (the padding walks nowhere and
    is cut), and rows on the missing bin sent either way or, with ``na_bin``
    256, nowhere in particular."""
    make, n, f_pad, max_steps = _KERNEL_CASES[case]
    rng = np.random.RandomState(11)
    t = make(rng)
    bins, na_bin = _bins(rng, n)
    if missing == "none":
        na_bin = np.full(_F, 256, np.int32)
    else:
        t["default_left"][:] = missing == "default_left"
    if case.startswith("chain"):
        t["default_left"][:] = False      # _chain's: bins[0] runs to the end
        bins[0] = _B - 2
    if max_steps is None:
        max_steps = max(int(t["num_leaves"]) - 1, 1)
    (want, want_steps), (leaf, steps) = _route_both(t, bins, na_bin,
                                                    max_steps, f_pad)
    np.testing.assert_array_equal(leaf, want)
    assert steps == want_steps
    static, moved = _static_walk(t, bins, na_bin, max_steps)
    np.testing.assert_array_equal(leaf, static)
    assert steps == moved
    if case == "one_leaf":
        assert steps == 0 and not leaf.any()
    if case == "chain" and missing != "none":
        assert steps == 39 and leaf[0] == 39


def test_categorical_tree_takes_the_xla_walk(monkeypatch):
    """The kernel decodes no bin membership: a tree handed over with
    ``is_cat`` walks in XLA, ``bins_T`` or not, and decides its categorical
    nodes by membership."""
    def no_kernel(*a, **kw):
        raise AssertionError("the kernel walk was asked for a categorical tree")
    monkeypatch.setattr(P, "_kernel_walk", no_kernel)
    rng = np.random.RandomState(5)
    t = _balanced(3, rng, cat_nodes=(0, 2, 5))
    bins, na_bin = _bins(rng)
    tree = [jnp.asarray(t[k]) for k in (
        "split_feature", "threshold_bin", "default_left", "left_child",
        "right_child", "num_leaves")]
    bins_T = _feature_major(bins)
    assert P.walk_path(bins_T, tree[0], jnp.asarray(t["is_cat"])) == "xla"
    steps = []
    leaf = P.route_bins(*tree, jnp.asarray(bins), jnp.asarray(na_bin), 7,
                        is_cat=jnp.asarray(t["is_cat"]),
                        cat_mask=jnp.asarray(t["cat_mask"]), steps_out=steps,
                        bins_T=bins_T)
    want, moved = _static_walk(t, bins, na_bin, 7)
    np.testing.assert_array_equal(np.asarray(leaf), want)
    assert int(steps[0]) == moved == 3


@pytest.mark.parametrize("f_pad,nodes,cat,want", [
    (28, 254, False, "kernel"),            # HIGGS, 255 leaves
    (14, 255, False, "kernel"),            # Covertype's bundled columns
    (128, 1023, False, "kernel"),          # both caps
    (None, 254, False, "xla"),             # no feature-major matrix handed in
    (2016, 254, False, "xla"),             # Epsilon: 2,000 columns
    (129, 254, False, "xla"),
    (28, 1024, False, "xla"),              # num_leaves 1,025: past the table
    (28, 254, True, "xla"),                # categorical nodes
])
def test_walk_path_reads_the_shapes(f_pad, nodes, cat, want):
    bins_T = None if f_pad is None else jax.ShapeDtypeStruct(
        (f_pad, 8192), jnp.uint8)
    sf = jax.ShapeDtypeStruct((nodes,), jnp.int32)
    is_cat = jax.ShapeDtypeStruct((nodes,), jnp.bool_) if cat else None
    assert P.walk_path(bins_T, sf, is_cat) == want


@pytest.fixture
def clean_obs():
    """Telemetry as the suite has it (off, empty) before and after."""
    from lightgbm_tpu import obs
    obs.reset()
    obs.configure(enabled=False, metrics_out="")
    yield
    obs.reset()
    obs.configure(enabled=False, metrics_out="")


def _train_valid_walks(histogram_impl):
    """Three iterations with one validation set, telemetry on: (booster,
    validation Dataset, its metric by iteration, the ``valid_walk`` events)."""
    from lightgbm_tpu import obs
    rng = np.random.RandomState(4)
    X = rng.randn(900, 6)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    X[rng.rand(*X.shape) < 0.05] = np.nan
    Xv = rng.randn(333, 6)
    yv = (Xv[:, 0] + Xv[:, 1] * Xv[:, 2] > 0).astype(np.float64)
    Xv[rng.rand(*Xv.shape) < 0.05] = np.nan
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "metric": "binary_logloss",
              "telemetry": True, "histogram_impl": histogram_impl,
              "use_quantized_grad": False}
    obs.reset()
    ds = lgb.Dataset(X, label=y, params=params)
    vs = ds.create_valid(Xv, label=yv)
    res = {}
    bst = lgb.train(params, ds, 3, valid_sets=[vs], evals_result=res,
                    verbose_eval=False)
    walks = [e for e in obs.EVENTS.snapshot() if e["type"] == "valid_walk"]
    return bst, vs, res["valid_0"]["binary_logloss"], walks


def test_trainer_on_the_cpu_takes_the_xla_walk(clean_obs):
    """The default histogram implementation on the CPU is not the Pallas
    one: the trainer hands the walk no feature-major matrix (and builds
    none), so validation is scored by the program it always was."""
    _, vs, _, walks = _train_valid_walks("auto")
    assert [e["path"] for e in walks] == ["xla"] * 3
    assert vs._bins_T is None


def test_trainer_on_pallas_scores_validation_through_the_kernel(clean_obs,
                                                                monkeypatch):
    """``histogram_impl=pallas`` (interpreted here): every walk is the
    kernel's, and the validation metric of every iteration equals, to the
    bit, that of the same training with the choice held to the XLA walk."""
    bst, vs, kernel_loss, walks = _train_valid_walks("pallas")
    assert [e["path"] for e in walks] == ["kernel"] * 3
    assert vs._bins_T is not None and vs._bins_T.shape[0] == 6
    for e, t in zip(walks, bst.trees):
        assert 1 <= e["steps"] <= t.max_depth
    monkeypatch.setattr(P, "walk_path", lambda *a, **kw: "xla")
    _, _, xla_loss, walks = _train_valid_walks("pallas")
    assert [e["path"] for e in walks] == ["xla"] * 3
    assert kernel_loss == xla_loss


# ---- in-training walks of trees with bin-subset nodes (PR 35) ---------------

def _subset_problem(kind, rng, n):
    """Rows whose label needs a bin-subset split: a categorical column, or
    eight one-hot columns that EFB bundles into one."""
    c = rng.randint(0, 8, n)
    num = rng.randn(n, 3)
    y = (np.isin(c, [1, 5, 7]) ^ (num[:, 1] > 0)).astype(np.float64)
    if kind == "categorical":
        return np.column_stack([c, num]), y
    return np.column_stack([num, np.eye(8)[c]]), y


@pytest.mark.parametrize("boosting", ["gbdt", "dart"])
@pytest.mark.parametrize("kind", ["categorical", "bundled"])
def test_training_scores_validation_by_bin_membership(kind, boosting):
    """The metric the trainer reports on a validation set is that of the
    model's own predictions there, where trees split on a subset of a
    column's bins (the walk is handed ``is_cat`` / ``cat_mask``; it decided
    those nodes by threshold before), and a rollback takes back exactly
    what the iteration added."""
    rng = np.random.RandomState(0)
    X, y = _subset_problem(kind, rng, 3000)
    Xv, yv = _subset_problem(kind, rng, 800)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "metric": "binary_logloss", "min_data_in_leaf": 5,
              "boosting": boosting, "drop_rate": 0.5, "drop_seed": 3}
    ds = lgb.Dataset(X, label=y, params=params,
                     categorical_feature=[0] if kind == "categorical" else "auto")
    res = {}
    bst = lgb.train(params, ds, 6, valid_sets=[ds.create_valid(Xv, label=yv)],
                    evals_result=res, verbose_eval=False)
    g = bst._gbdt
    assert (ds.bundle_meta is not None) == (kind == "bundled")
    assert any(bool(np.asarray(t.is_cat).any()) for t in g.models_dev)
    p = bst.predict(Xv)
    want = -np.mean(yv * np.log(p) + (1 - yv) * np.log(1 - p))
    assert res["valid_0"]["binary_logloss"][-1] == pytest.approx(want, rel=1e-5)
    assert want < 0.45
    if boosting == "gbdt":
        raw = bst.predict(Xv, raw_score=True, num_iteration=5)
        bst.rollback_one_iter()
        np.testing.assert_allclose(np.asarray(g.valid_scores[0]), raw,
                                   rtol=1e-5, atol=1e-6)
