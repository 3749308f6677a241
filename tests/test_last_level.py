"""The level after which no level can run builds no histograms.

``ops/grow_depthwise._ends_tree``: once a level's selected splits fill the
leaf budget, or the level is the last the depth cap allows, nothing reads
the children's histograms its pass would build, so the level only routes
its rows (scope ``route_only``). The histogram-building passes are counted
with a callback planted on ``histogram.hist_routed``, which a taken
``lax.cond`` branch runs and an untaken one does not; and with the
predicate patched to ``False`` every level builds histograms again, as
before, and the models have to be the same, byte for byte.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import grow_depthwise as gd
from lightgbm_tpu.ops import histogram as H

BASE = {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 0, "verbose": -1,
        "prewarm": 0}
PALLAS = {"histogram_impl": "pallas", "use_quantized_grad": True}


def _data(n, f, num_class=0, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    s = X[:, 0] * 3 + X[:, 1] + rng.rand(n) * 0.5
    if not num_class:
        return X, s.astype(np.float32)
    y = np.minimum((s / s.max() * num_class).astype(int), num_class - 1)
    return X, y.astype(np.float32)


def _train(params, X, y, rounds):
    params = dict(BASE, **params)
    return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                     num_boost_round=rounds)


@pytest.fixture
def fresh():
    """Programs traced anew under this test's patches, and after them."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def passes(monkeypatch, fresh):
    """The histogram-building level passes the programs run."""
    seen = []
    real = H.hist_routed

    def counted(*args, **kwargs):
        out = real(*args, **kwargs)
        jax.debug.callback(lambda: seen.append(1))
        return out

    monkeypatch.setattr(H, "hist_routed", counted)
    return seen


def _depth(node):
    if "leaf_index" in node:
        return 0
    return 1 + max(_depth(node["left_child"]), _depth(node["right_child"]))


@pytest.mark.parametrize("params,rows,want", [
    ({"num_leaves": 31, "max_depth": 3}, 4000, 3 - 1),
    ({"num_leaves": 31, "max_depth": 4}, 4000, 4 - 1),
    ({"num_leaves": 31, "max_depth": 4, **PALLAS}, 4000, 4 - 1),
    ({"num_leaves": 15}, 4000, 4 - 1),
    ({"num_leaves": 15, **PALLAS}, 4000, 4 - 1),
    ({"num_leaves": 255}, 20000, 8 - 1),
], ids=["depth3", "depth4", "depth4-pallas", "leaves15", "leaves15-pallas",
        "leaves255"])
def test_the_last_level_builds_no_histograms(passes, params, rows, want):
    """A depth-capped tree builds histograms at max_depth - 1 levels, a
    balanced tree that fills its budget at log2(num_leaves + 1) - 1: the
    root's histogram is not a level pass, the last level is route only."""
    booster = _train(dict(objective="regression", **params),
                     *_data(rows, 6), rounds=1)
    tree = booster.dump_model()["tree_info"][0]
    depth = params.get("max_depth", -1)
    if depth < 0:
        assert tree["num_leaves"] == params["num_leaves"]
        depth = int(np.log2(params["num_leaves"] + 1))
    assert _depth(tree["tree_structure"]) == depth
    assert len(passes) == want


def test_a_tree_that_stops_on_gain_builds_histograms_at_every_level(passes):
    """Neither the budget nor the depth cap ends this tree: every level
    that runs builds its children's histograms, the one that selects
    nothing and so ends the tree too."""
    booster = _train({"objective": "regression", "num_leaves": 255,
                      "min_gain_to_split": 5.0}, *_data(4000, 6), rounds=1)
    tree = booster.dump_model()["tree_info"][0]
    assert tree["num_leaves"] < 255
    assert len(passes) == _depth(tree["tree_structure"]) + 1


FORCED = {"feature": 2, "threshold": 0.5,
          "left": {"feature": 3, "threshold": 0.3}}


@pytest.mark.parametrize("params,classes,features", [
    ({"objective": "binary", "num_leaves": 15}, 2, 6),
    ({"objective": "binary", "num_leaves": 15, **PALLAS}, 2, 6),
    ({"objective": "binary", "num_leaves": 31, "max_depth": 3}, 2, 6),
    ({"objective": "binary", "num_leaves": 31, "max_depth": 3, **PALLAS}, 2,
     6),
    ({"objective": "multiclass", "num_class": 4, "num_leaves": 16,
      "max_depth": 4, "max_bin": 255}, 4, 10),
    # 10 x 256 one-hot rows: the grouped kernel after a route pass of its own
    ({"objective": "multiclass", "num_class": 4, "num_leaves": 16,
      "max_depth": 4, "max_bin": 255, **PALLAS}, 4, 10),
    ({"objective": "binary", "num_leaves": 15, "tree_learner": "voting",
      "top_k": 3, "num_shards": 2}, 2, 6),
    ({"objective": "binary", "num_leaves": 15, "cegb_tradeoff": 0.1,
      "cegb_penalty_split": 0.01,
      "cegb_penalty_feature_lazy": [0.001] * 6}, 2, 6),
    ({"objective": "binary", "num_leaves": 15, "forcedsplits_filename": ""},
     2, 6),
    ({"objective": "binary", "num_leaves": 15,
      "monotone_constraints": [1, -1, 0, 1, 0, 0]}, 2, 6),
    # sharded Pallas trainers get no resident matrix: 40 x 64 one-hot rows
    # take the grouped kernel and the stand-alone router over bins.T
    ({"objective": "binary", "num_leaves": 15, "tree_learner": "data",
      "num_shards": 2, "max_bin": 63, **PALLAS}, 2, 40),
    ({"objective": "binary", "num_leaves": 15, "tree_learner": "data",
      "num_shards": 2, "max_bin": 63, "histogram_impl": "pallas"}, 2, 40),
], ids=["leaves15", "leaves15-pallas", "depth3", "depth3-pallas",
        "multiclass255", "multiclass255-pallas-grouped", "voting", "cegb",
        "forced", "monotone", "dp2-pallas-grouped-q8", "dp2-pallas-grouped"])
def test_models_are_those_of_histograms_at_every_level(
        monkeypatch, passes, tmp_path, params, classes, features):
    """With the predicate patched to False every level builds histograms,
    as before the route-only level: four rounds give the same model, and
    each tree one histogram pass more (on each shard, where the level body
    runs under ``shard_map``)."""
    X, y = _data(3000, features, num_class=classes)
    if "forcedsplits_filename" in params:
        forced = tmp_path / "forced.json"
        forced.write_text(json.dumps(FORCED))
        params = dict(params, forcedsplits_filename=str(forced))
    if classes > 2 and "histogram_impl" in params:
        assert H.hist_path(10, 256, "pallas")["route"] == "pallas"
    if features == 40:
        assert H.hist_path(40, 64, "pallas", True)["route"] == "pallas"
    rounds = 4
    model = _train(params, X, y, rounds).model_to_string()
    ending = len(passes)
    passes.clear()
    monkeypatch.setattr(gd, "_ends_tree", lambda *args: False)
    jax.clear_caches()
    every = _train(params, X, y, rounds).model_to_string()
    assert model == every
    trees = rounds * (classes if classes > 2 else 1)
    assert len(passes) - ending == trees * params.get("num_shards", 1)


@pytest.mark.parametrize("features,quantized", [
    (6, True), (40, True), (40, False)],
    ids=["fused", "grouped-q8", "grouped"])
def test_route_only_routes_as_the_level_pass(features, quantized):
    """``route_only`` gives ``hist_routed``'s new leaf ids on each Pallas
    path, with no resident matrix as the sharded trainers call it
    (``bins_T=None``: both route over ``bins.T``) and with one."""
    n, s, b = 512, 32, 64
    r = np.random.default_rng(features)
    bins = jnp.asarray(r.integers(0, b, size=(n, features)), jnp.uint8)
    g = jnp.asarray(r.normal(size=n), jnp.float32)
    h = jnp.asarray(r.random(n) + 0.5, jnp.float32)
    c = jnp.ones(n, jnp.float32)
    quant = H.make_quant(g, h, c, 7) if quantized else None
    leaves = 2 * s
    splits = np.arange(leaves) < s
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    tables = H.RouteTables(
        feat=i32(np.where(splits, r.integers(0, features, size=leaves), -1)),
        thr=i32(r.integers(0, b - 1, size=leaves)),
        dleft=i32(r.integers(0, 2, size=leaves)),
        new_leaf=i32(np.minimum(s + np.arange(leaves), leaves - 1)),
        slot_left=i32(np.where(splits, np.arange(leaves), s)),
        slot_right=i32(np.full(leaves, s)))
    na_bin = jnp.asarray(np.where(np.arange(features) % 2 == 0, b - 1, -1),
                         jnp.int32)
    leaf_id = i32(r.integers(0, s, size=n))
    path = H.hist_path(features, b, "pallas", quantized)["route"]
    assert path == ("fused" if features == 6 else "pallas")
    _, want = H.hist_routed(bins, g, h, c, leaf_id, tables, na_bin, s, b,
                            "pallas", quant=quant)
    for bins_T in (None, bins.T):
        got = H.route_only(bins, leaf_id, tables, na_bin, s, b, "pallas",
                           bins_T=bins_T, quant=quant)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
