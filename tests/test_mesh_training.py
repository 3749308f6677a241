"""Mesh-native data-parallel training: sharded-vs-single-chip bit-equality.

conftest.py forces 8 virtual CPU host devices
(``--xla_force_host_platform_device_count=8``), so the full
RowShardPlan path — shard-aligned chunked ingest, per-shard donated
accumulators, ``make_array_from_single_device_arrays`` assembly, and the
in-step histogram psum — runs in-process without TPU hardware.

Bitwise equality across shard counts needs order-independent f32 sums, so
the training fixture quantizes its custom-objective gradients onto a dyadic
lattice (multiples of 2^-9, constant hessian 0.25): every histogram /
leaf-stat partial sum is then EXACT in f32 (magnitudes stay far below 2^24
lattice units), and any psum association — 1 shard or 8 — produces the same
bits. That turns "trees agree up to ulps" into "trees are identical",
including split gains, thresholds, tie-breaks, and leaf values. The row
count is deliberately non-divisible (4097 = 8*512 + 1) so the padded tail
rows of the last shard (masked with zero grad/hess) are covered.
"""
import hashlib

import numpy as np
import pytest

import lightgbm_tpu as lgb

N = 4097            # non-divisible by 8: exercises shard padding masks
F = 10
ROUNDS = 5


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((N, F)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2] ** 2 > 0).astype(np.float32)
    return X, y


def _lattice_fobj(preds, train_data):
    """L2-style gradients snapped to the 2^-9 dyadic lattice; with a constant
    power-of-two hessian every partial sum is exact in f32, so the grown
    trees are bit-identical regardless of summation grouping."""
    labels = train_data.get_label()
    g = np.round((np.asarray(preds, np.float64) - labels) * 512.0) / 512.0
    h = np.full_like(g, 0.25)
    return g.astype(np.float32), h.astype(np.float32)


def _train(X, y, num_shards, rounds=ROUNDS):
    params = {
        "objective": "none", "num_leaves": 15, "learning_rate": 0.1,
        "min_data_in_leaf": 5, "verbose": -1, "seed": 3,
        "metric": "l2", "num_shards": num_shards,
    }
    ds = lgb.Dataset(X, label=y, params=params)
    evals = {}
    bst = lgb.train(params, ds, num_boost_round=rounds, fobj=_lattice_fobj,
                    valid_sets=[ds], valid_names=["train"],
                    evals_result=evals, verbose_eval=False)
    return bst, evals


def _tree_section(model_str):
    """The model string minus the one line that differs by construction:
    the ``[num_shards: k]`` params echo. Everything else — headers, every
    tree table, leaf values/weights — must match bit-for-bit."""
    return "\n".join(l for l in model_str.splitlines()
                     if not l.startswith("[num_shards:"))


def test_plan_published_and_sharded(data):
    X, y = data
    params = {"num_shards": 8, "verbose": -1}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    plan = ds.shard_plan
    assert plan is not None and plan.num_shards == 8
    assert plan.n_rows == N
    assert plan.n_padded == plan.num_shards * plan.rows_per_shard
    assert plan.pad_rows == plan.n_padded - N
    # the device matrix really is row-sharded across all 8 devices
    assert len(set(ds.bins.sharding.device_set)) == 8
    assert ds.bins.shape[0] == plan.n_padded
    assert ds.num_data == N         # padding never leaks into the API


@pytest.mark.parametrize("num_shards", [2, 8])
def test_sharded_training_bit_identical(data, num_shards):
    X, y = data
    b1, ev1 = _train(X, y, num_shards=1)
    bk, evk = _train(X, y, num_shards=num_shards)
    s1 = _tree_section(b1.model_to_string())
    sk = _tree_section(bk.model_to_string())
    # full tree tables: structure, thresholds, gains, leaf values/weights
    assert hashlib.sha256(s1.encode()).hexdigest() == \
        hashlib.sha256(sk.encode()).hexdigest(), (
        "sharded trees differ from single-chip:\n" + "\n".join(
            l1 + "  !=  " + l2
            for l1, l2 in zip(s1.splitlines(), sk.splitlines())
            if l1 != l2)[:2000])
    # eval metrics recorded per iteration must match exactly too
    assert ev1 == evk
    # and so must predictions on the raw feature matrix
    np.testing.assert_array_equal(b1.predict(X), bk.predict(X))


def test_sharded_training_divisible_rows(data):
    """8 | 4096: the zero-pad tail is empty — plan covers rows exactly."""
    X, y = data
    X, y = X[:4096], y[:4096]
    b1, _ = _train(X, y, num_shards=1, rounds=3)
    b8, _ = _train(X, y, num_shards=8, rounds=3)
    assert _tree_section(b1.model_to_string()) == \
        _tree_section(b8.model_to_string())


def test_builtin_objective_close_across_shards(data):
    """Real binary objective: sigmoid gradients are off-lattice so sums may
    round differently per association — trees must still agree to f32 noise
    on predictions (the bitwise guarantee is the lattice test above)."""
    X, y = data
    params = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
              "min_data_in_leaf": 5, "verbose": -1, "seed": 3}
    p1 = lgb.train(dict(params, num_shards=1),
                   lgb.Dataset(X, label=y), num_boost_round=3).predict(X)
    p8 = lgb.train(dict(params, num_shards=8),
                   lgb.Dataset(X, label=y), num_boost_round=3).predict(X)
    np.testing.assert_allclose(p1, p8, rtol=0, atol=1e-5)


def test_mesh_shard_commit_telemetry(data):
    """Sharded ingest emits one ingest_chunk per committed chunk that names
    the shard whose accumulator took it, and every shard id in [0, 8)
    appears."""
    from lightgbm_tpu import obs
    X, y = data
    obs.configure(enabled=True)
    obs.reset()
    try:
        ds = lgb.Dataset(X, label=y, params={"num_shards": 8, "verbose": -1})
        ds.construct()
        ev = [e for e in obs.EVENTS.snapshot()
              if e["type"] == "ingest_chunk"]
        assert ev, "no ingest_chunk events from sharded construct"
        shards = {e["shard"] for e in ev}
        assert shards == set(range(8))
        assert all(e["rows"] > 0 and e["bytes"] > 0 for e in ev)
        assert all(e["thread"] == "ingest-commit" for e in ev)
        assert sum(e["rows"] for e in ev) == N
    finally:
        obs.configure(enabled=False)
        obs.reset()
