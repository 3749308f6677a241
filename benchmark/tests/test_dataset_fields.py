"""A generator's ``lgb.Dataset`` fields through the run's own code path
(``harness.run_cell`` on the CPU, the look for a chip skipped): query groups
for a ranking job with a validation set, weights and a categorical column
for a binary one, the helper's refusals, and the accepted cells' calls left
as they were. The generators and checks here are test-only modules, put
where ``run_cell`` looks for a configuration's ``generator`` and ``check``;
no cell of ``BENCHMARK.json`` names them."""
import sys
import time
import types

import numpy as np
import pytest

from benchmark import harness, rehearse as rh
from benchmark import check as C, reference as R

BLOCK = 1 << 12
EVAL_AT = [1, 3, 5, 10]


def _rng(key, first_block, what):
    return np.random.default_rng(
        [int(v) for v in np.asarray(key).ravel()] + [first_block, what])


def _rows(key, cfg, n, first_block):
    """float32 rows, a linear signal over the first columns, and per row a
    relevance that the labels are graded from."""
    rng = _rng(key, first_block, 0)
    x = rng.standard_normal((n, int(cfg["num_features"]))).astype(np.float32)
    rel = (x[:, :4] @ np.array([1.0, -0.7, 0.5, 0.3])
           + 0.8 * rng.standard_normal(n))
    return x, rel


# ---------------------------------------------------------- ranking generator
def _query_sizes(key, n, first_block):
    """Heavy-tailed query lengths in 1..60 that sum to exactly ``n``."""
    rng = _rng(key, first_block, 1)
    sizes = np.clip(np.rint(np.exp(rng.normal(2.3, 1.0, n))), 1, 60
                    ).astype(np.int64)
    sizes = sizes[: int(np.searchsorted(np.cumsum(sizes), n)) + 1]
    sizes[-1] -= sizes.sum() - n
    return sizes[sizes > 0]


def _rank_to_host(key, cfg, n_rows, first_block=0, rows=BLOCK, out=None):
    x, rel = _rows(key, cfg, n_rows, first_block)
    label = np.digitize(rel, [-1.0, 0.0, 0.8, 1.6]).astype(np.float32)
    return x, label                                         # grades 0..4


def _rank_fields(key, cfg, n_rows, first_block=0, rows=BLOCK):
    return {"group": _query_sizes(key, n_rows, first_block)}


def _ndcg_at(label, score, sizes, k):
    """Mean over queries of NDCG@k, float64: gains 2^label - 1 by the order
    of ``score`` (ties by row order), over the ideal order's; a query with
    no relevant row counts 1."""
    out, lo = [], 0
    for size in sizes:
        lab = label[lo:lo + size].astype(np.float64)
        sc = score[lo:lo + size]
        lo += size
        gain = 2.0 ** lab - 1.0
        disc = 1.0 / np.log2(np.arange(2, min(k, size) + 2))
        ideal = float(np.sort(gain)[::-1][:k] @ disc)
        got = float(gain[np.argsort(-sc, kind="stable")][:k] @ disc)
        out.append(got / ideal if ideal > 0 else 1.0)
    return float(np.mean(out))


def _rank_check_cell(cell, seed, produced, n_train, n_valid, block_rows):
    """NDCG@10 of the model's own predictions on the validation rows after
    each iteration, against what the run reported."""
    SEEN.append(produced)
    gen = sys.modules["benchmark._test_rank_data"]
    key, cfg = gen.seed_key(seed), cell["cfg"]
    xv, yv = gen.to_host(key, cfg, n_valid, gen.VALID_STREAM)
    sizes = gen.dataset_fields(key, cfg, n_valid, gen.VALID_STREAM)["group"]
    reported = produced["valid_metrics"]["ndcg@10"]
    score = np.zeros(n_valid, np.float32)
    gaps = []
    for tree, rep in zip(R.parse_model(produced["model_text"]), reported):
        score = score + R.predict_tree(tree, xv).astype(np.float32)
        gaps.append(abs(rep - _ndcg_at(yv, score, sizes, 10)))
    return C.judge({"ndcg_gap": max(gaps) if gaps else float("nan")},
                   cell["limits"])


SEEN = []


def _module(name, **attrs):
    mod = types.ModuleType("benchmark." + name)
    mod.__dict__.update(attrs)
    return mod


def _cell(generator, check, params, valid_rows, limits):
    return {
        "name": "test.cell", "chips": 1, "end_to_end": [], "per_layer": [],
        "cfg": {"params": params, "num_features": 8, "train_rows": 4000,
                "grad_channels": 2, "generator": generator, "check": check},
        "traffic": {"valid_rows": valid_rows, "metric": params.get("metric"),
                    "early_stopping_rounds": 0, "warmup_iters": 1,
                    "checked_trees": [0, -1]},
        "limits": limits,
    }


def _install(monkeypatch, name, **attrs):
    from benchmark import data
    attrs = dict(dict(seed_key=data.seed_key, BLOCK_ROWS=BLOCK,
                      VALID_STREAM=data.VALID_STREAM), **attrs)
    monkeypatch.setitem(sys.modules, "benchmark." + name,
                        _module(name, **attrs))


def _run(cell, seed=2147483911, seconds=0.3):
    return harness.run_cell(cell, seed, seconds, False, t0=time.perf_counter(),
                            rehearse={"block_rows": BLOCK})


@pytest.fixture
def spy_dataset(monkeypatch):
    """Every ``lgb.Dataset`` the harness makes, with its arguments."""
    import lightgbm_tpu as lgb
    real, made = lgb.Dataset, []

    def spy(*args, **kw):
        ds = real(*args, **kw)
        made.append((args, kw, ds))
        return ds
    monkeypatch.setattr(lgb, "Dataset", spy)
    return made


# ------------------------------------------------------------------- (a)
def test_lambdarank_trains_and_its_ndcg_is_the_models(monkeypatch,
                                                      spy_dataset):
    """Training runs (lambdarank stops without query groups), the
    validation set carries groups of its own, every iteration reports
    NDCG@1..10, and NDCG@10 is that of the model's own predictions."""
    _install(monkeypatch, "_test_rank_data", to_host=_rank_to_host,
             dataset_fields=_rank_fields)
    _install(monkeypatch, "_test_rank_check", check_cell=_rank_check_cell)
    params = {"objective": "lambdarank", "metric": "ndcg", "eval_at": EVAL_AT,
              "num_leaves": 15, "learning_rate": 0.1, "min_data_in_leaf": 5,
              "max_bin": 63}
    cell = _cell("_test_rank_data", "_test_rank_check", params, 1000,
                 {"ndcg_gap": 1e-6})
    del SEEN[:]
    r = _run(cell)
    (_, kw, ds), = spy_dataset
    assert int(np.sum(kw["group"])) == 4000 and ds.get_group() is not None
    assert 150 <= len(kw["group"]) <= 400 and max(kw["group"]) <= 60
    produced, = SEEN
    vm = produced["valid_metrics"]
    assert sorted(vm) == sorted(f"ndcg@{k}" for k in EVAL_AT)
    assert len(vm["ndcg@10"]) == produced["iterations"] >= 2
    assert r["checks"]["ndcg_gap"]["value"] <= 1e-6, r["checks"]
    assert r["correct"]


def test_the_ndcg_check_sees_a_wrong_report():
    """The comparison above is one that fails: a report off by a swap of
    the top two rows of one query reads far over 1e-6."""
    label = np.array([3, 0, 1, 2, 0, 4], np.float32)
    score = np.array([0.9, 0.1, 0.5, 0.2, 0.3, 0.8], np.float32)
    sizes = np.array([4, 2])
    good = _ndcg_at(label, score, sizes, 10)
    swapped = score.copy()
    swapped[[0, 2]] = swapped[[2, 0]]
    assert abs(good - _ndcg_at(label, swapped, sizes, 10)) > 1e-3
    assert _ndcg_at(np.zeros(3), np.ones(3), np.array([3]), 10) == 1.0


# ------------------------------------------------------------------- (b)
CAT = 3


def _binary_to_host(key, cfg, n_rows, first_block=0, rows=BLOCK, out=None):
    x, rel = _rows(key, cfg, n_rows, first_block)
    x[:, CAT] = _rng(key, first_block, 2).integers(0, 6, n_rows)
    rel = rel + np.array([0.0, 1.5, -1.0, 0.5, 0.0, -1.5])[
        x[:, CAT].astype(int)]
    return x, (rel > 0).astype(np.float32)


def _binary_fields(key, cfg, n_rows, first_block=0, rows=BLOCK):
    w = 0.5 + _rng(key, first_block, 3).random(n_rows)
    return {"weight": w.astype(np.float32), "categorical_feature": [CAT]}


def test_weights_and_a_categorical_column_reach_the_dataset(monkeypatch,
                                                            spy_dataset):
    from lightgbm_tpu.binning import BIN_CATEGORICAL
    _install(monkeypatch, "_test_weighted_data", to_host=_binary_to_host,
             dataset_fields=_binary_fields)
    _install(monkeypatch, "_test_no_check",
             check_cell=lambda *a, **kw: {})
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 7, "learning_rate": 0.1, "max_bin": 63,
              "min_data_in_leaf": 20}
    cell = _cell("_test_weighted_data", "_test_no_check", params, 500, {})
    _run(cell)
    (args, kw, ds), = spy_dataset
    from benchmark import data
    want = _binary_fields(data.seed_key(2147483911), cell["cfg"], 4000)
    np.testing.assert_array_equal(ds.get_weight(), want["weight"])
    assert kw["categorical_feature"] == [CAT]
    kinds = [m.bin_type == BIN_CATEGORICAL for m in ds.mappers]
    assert kinds == [i == CAT for i in range(8)]


# ------------------------------------------------------------------- (c)
@pytest.mark.parametrize("fields, says", [
    ({"group": [4000], "label_gain": [0, 1]}, "label_gain"),
    ({"group": [2000, 1999]}, "3999"),
])
def test_a_field_it_cannot_take_is_an_error_before_construct(
        monkeypatch, spy_dataset, fields, says):
    _install(monkeypatch, "_test_bad_data", to_host=_rank_to_host,
             dataset_fields=lambda *a, **kw: dict(fields))
    gen = sys.modules["benchmark._test_bad_data"]
    cfg = {"num_features": 8}
    with pytest.raises(ValueError, match=says):
        harness.dataset_fields(gen, gen.seed_key(1), cfg, 4000)
    cell = _cell("_test_bad_data", "check", {"objective": "lambdarank"},
                 0, {})
    with pytest.raises(ValueError, match=says):
        _run(cell)
    assert spy_dataset == []


# ------------------------------------------------------------------- (d)
@pytest.mark.parametrize("generator", ["data", "data_wide", "data_covertype"])
def test_the_accepted_generators_give_no_fields(generator):
    import importlib
    gen = importlib.import_module("benchmark." + generator)
    assert not hasattr(gen, "dataset_fields")
    assert harness.dataset_fields(gen, gen.seed_key(1), {}, 10) == {}


@pytest.mark.parametrize("workload", ["higgs-binary.train",
                                      "higgs-binary.train-valid"])
def test_an_accepted_cell_calls_dataset_as_before(monkeypatch, spy_dataset,
                                                  workload):
    """An accepted cell at the rehearsal's size: ``lgb.Dataset`` gets the
    matrix, the labels and the params, and ``create_valid`` the validation
    matrix and its labels, nothing more. (A window of one iteration: the
    tiny size's later trees read regrets its limits were not set for.)"""
    from lightgbm_tpu import basic
    real, valid = basic.Dataset.create_valid, []

    def spy(self, *args, **kw):
        valid.append((args, kw))
        return real(self, *args, **kw)
    monkeypatch.setattr(basic.Dataset, "create_valid", spy)
    r = rh.rehearse(workload, seconds=0.01)
    assert r["correct"], r["checks"]
    (args, kw, _), = spy_dataset
    assert len(args) == 1 and sorted(kw) == ["label", "params"]
    assert args[0].shape == (rh.TINY["train_rows"], 28)
    if workload.endswith("valid"):
        (args, kw), = valid
        assert len(args) == 1 and sorted(kw) == ["label"]
        assert args[0].shape == (rh.TINY["valid_rows"], 28)
    else:
        assert valid == []
