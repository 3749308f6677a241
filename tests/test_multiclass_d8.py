"""K class trees an iteration, depth-capped, over bundled one-hot groups:
``covertype-multiclass-d8``'s shape at a size a test holds.

The program (``lgb.Dataset(...).construct()`` and ``lgb.train`` through the
benchmark's own run, Pallas kernels interpreted) against the plain reference
``benchmark/reference_multiclass.py``, which knows no bundle and no class
scan: at K = 7 (as the cell) and at K = 10, with ``max_depth=8``,
``num_leaves=256`` and the 44 one-hot columns bundled; and bundled against
``enable_bundle=false``: the same trees.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import data_covertype, harness, rehearse
from lightgbm_tpu import obs

CELL = "covertype-multiclass-d8.train"
ROWS = 8192
# the cell's shape but for the rows and the bins they can fill
TINY = dict(rehearse.TINY, train_rows=ROWS, valid_rows=0, block_rows=8192,
            params={"histogram_impl": "pallas", "max_bin": 63,
                    "telemetry": True})
# the cell's limits are calibrated at 29 M rows; at 8,192 one flipped
# near-tie of the int8 lattice is a larger share of a tree's regret
SMALL_SIZE_REGRET = {"split_regret": 5e-3, "split_regret_last": 5e-3,
                     "split_regret_rare": 2e-2}


@pytest.fixture
def cell_with(monkeypatch):
    """``harness.load_cell`` with the cell's parameters overridden and the
    regret limits of the small size."""
    real = harness.load_cell

    def use(**params):
        def patched(name, bench=None):
            cell = real(name, bench)
            cfg = cell["cfg"]
            cell["cfg"] = dict(cfg, params=dict(cfg["params"], **params))
            cell["limits"] = dict(cell["limits"], **SMALL_SIZE_REGRET)
            return cell
        monkeypatch.setattr(harness, "load_cell", patched)
    return use


@pytest.fixture
def telemetry():
    obs.configure(enabled=True)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def _events(kind):
    return [e for e in obs.EVENTS.snapshot() if e["type"] == kind]


@pytest.mark.parametrize("num_class", [7, 10])
def test_program_against_the_plain_reference(cell_with, telemetry, num_class):
    cell_with(num_class=num_class)
    r = rehearse.rehearse(CELL, seed=3, tiny=TINY)
    assert r["correct"], r["checks"]
    assert r["checks"]["class_order_gap"]["value"] == 0.0
    assert r["checks"]["leaf_count_gap"]["value"] == 0.0
    assert r["checks"]["leaf_value_gap"]["value"] < 1e-4
    mc = _events("multiclass")[-1]
    assert (mc["num_class"], mc["trees_per_iter"]) == (num_class, num_class)
    assert mc["class_loop"] == "scan" and mc["labels_arg"] is True
    plan = _events("efb_plan")[-1]
    assert plan["columns_in"] == 54 and plan["bundles"] >= 1
    assert plan["columns_out"] < 20


def _train(num_class, bundle):
    cfg = harness.load_cell(CELL)["cfg"]
    cfg = dict(cfg, params=dict(cfg["params"], num_class=num_class))
    X, y = data_covertype.to_host(data_covertype.seed_key(11), cfg, ROWS,
                                  rows=8192)
    params = dict(cfg["params"], max_bin=63, histogram_impl="pallas",
                  enable_bundle=bundle, verbosity=-1)
    ds = lgb.Dataset(X, label=y, params=params).construct()
    assert (ds.bundle_meta is not None) == bundle
    bst = lgb.train(params, ds, num_boost_round=2, verbose_eval=False)
    return bst, X


@pytest.mark.parametrize("num_class", [7, 10])
def test_bundled_columns_grow_the_trees_of_the_raw_columns(num_class):
    """The bundle's histogram rows are the members' own bins and the int8
    sums are integers, so the bundled matrix has to give the very trees the
    54 columns give: same splits on the same raw columns, same counts."""
    from benchmark.reference import parse_model
    (a, X), (b, _) = _train(num_class, True), _train(num_class, False)
    ta, tb = (parse_model(m.model_to_string()) for m in (a, b))
    assert len(ta) == len(tb) == 2 * num_class
    assert max(t["num_leaves"] for t in ta) > 32      # below the fifth level
    for x, y in zip(ta, tb):
        for k in ("split_feature", "left_child", "right_child", "leaf_count"):
            np.testing.assert_array_equal(x[k], y[k], err_msg=k)
        numeric = x["split_feature"] < data_covertype.NUMERIC
        np.testing.assert_allclose(x["threshold"][numeric],
                                   y["threshold"][numeric])
        # a 0/1 column's zero bin and the empty bin beside it cut alike
        for t in (x, y):
            assert np.all((t["threshold"][~numeric] >= 0)
                          & (t["threshold"][~numeric] < 1))
        np.testing.assert_allclose(x["leaf_value"], y["leaf_value"],
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(a.predict(X[:512]), b.predict(X[:512]),
                               rtol=1e-5, atol=1e-6)
