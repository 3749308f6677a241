"""The four-chip cell ``higgs-binary-dp4.train`` at a size a test holds.

Its own code path (``benchmark.harness.run_cell`` through
``benchmark.rehearse``: ``lgb.Dataset(...).construct()`` over a row-shard
plan, ``lgb.train`` with ``num_shards=4``, the plain reference over all the
rows) on four of the CPU's virtual devices, a fault planted on the sharded
path that the comparison has to catch, and the ``shard_plan`` event: the grid
the trainer adopted, also after ingest changed it.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark import rehearse
from lightgbm_tpu import obs
from lightgbm_tpu.ops import grow, grow_depthwise
from lightgbm_tpu.utils import faults

CELL = "higgs-binary-dp4.train"
ROWS = 40_000


def _tiny(**params):
    return dict(rehearse.TINY, train_rows=ROWS,
                params=dict(rehearse.TINY["params"], telemetry=True,
                            **params))


def _events(kind):
    return [e for e in obs.EVENTS.snapshot() if e["type"] == kind]


@pytest.fixture
def telemetry():
    obs.configure(enabled=True)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def test_rehearsal_over_four_shards_is_correct(telemetry):
    result = rehearse.rehearse(CELL, seed=2**31 + 5, tiny=_tiny())
    assert result["correct"], result["checks"]
    assert result["checks"]["leaf_count_gap"]["value"] == 0
    assert result["attempted"] >= 1
    plan, = _events("shard_plan")
    assert plan["num_shards"] == 4 and plan["feature_shards"] == 1
    assert plan["rows_per_shard"] == ROWS // 4 and plan["pad_rows"] == 0
    path = _events("hist_path")[-1]
    assert path["front"] == "unfused" and path["bins_T_cached"] is False
    assert path["allreduce_bytes_per_iter"] == \
        grow_depthwise.allreduce_bytes_per_tree(15, -1, 28, 64, True)


@pytest.mark.parametrize("leaves,pallas,slots", [
    (255, True, 6 * 32 + 2 * 127),     # the cell: 6.88 MB a tree
    (255, False, 1 + 2 + 4 + 8 + 16 + 32 + 64 + 127),
    (15, False, 1 + 2 + 4 + 7),
])
def test_allreduce_bytes_per_tree(leaves, pallas, slots):
    """The root's histogram, one of the level's slot width for each level of
    a balanced tree (``slots`` over all of them) but its last, the leaf sums:
    float32 of [3, 28, 64] cells. The last level fills the budget at its
    full width, ``leaves // 2`` slots, and builds no histograms."""
    assert grow_depthwise.allreduce_bytes_per_tree(
        leaves, -1, 28, 64, pallas) == 4 * (
            (1 + slots - leaves // 2) * 3 * 28 * 64 + 3 * leaves)


def test_a_shard_left_out_of_the_reduction_is_not_correct(monkeypatch,
                                                          telemetry):
    """Shard 0's part of every sum the chips exchange is dropped: the trees
    are then a model of three quarters of the rows."""
    real = grow._psum

    def without_shard_0(x, gp):
        if gp.axis_name:
            import jax
            x = x * (jax.lax.axis_index(gp.axis_name) != 0).astype(x.dtype)
        return real(x, gp)
    # every reduction of the growers goes through this one function
    monkeypatch.setattr(grow, "_psum", without_shard_0)
    monkeypatch.setattr(grow_depthwise, "_psum", without_shard_0)
    try:
        # leaves apart from the sound run's: a grower traced anew
        result = rehearse.rehearse(CELL, seed=7, tiny=_tiny(num_leaves=14))
    finally:
        grow_depthwise.grow_tree_depthwise.clear_cache()
    checks = result["checks"]
    assert not result["correct"]
    assert not (checks["leaf_value_gap"]["ok"]
                and checks["split_regret"]["ok"]), checks
    assert checks["leaf_count_gap"]["value"] >= ROWS // 4


@pytest.mark.chaos
@pytest.mark.faults
@pytest.mark.parametrize("policy,shards,rows,pad", [
    ("reshard", 4, 257, 3),            # 2 -> 4 shards of ceil(1025 / 4) rows
    ("fallback_single", 1, 1025, 0),   # the grid dropped: one chip, all rows
])
def test_shard_plan_event_says_the_grid_adopted(telemetry, policy, shards,
                                                rows, pad):
    """Four injected OOMs use up ingest's chunk halvings, so the policy
    changes the grid the Dataset first published (two shards); the trainer's
    event has the one it trains on."""
    rng = np.random.RandomState(5)
    X = rng.rand(1025, 5).astype(np.float32)
    y = (X[:, 0] + 0.1 * rng.rand(1025) > 0.5).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "min_data_in_leaf": 5, "verbose": -1, "prewarm": 0,
              "num_shards": 2, "ingest_chunk_rows": 400, "telemetry": True,
              "faults": "device_put_oom:4", "on_device_fault": policy}
    try:
        ds = lgb.Dataset(X, label=y, params=params)
        lgb.train(params, ds, num_boost_round=2)
    finally:
        faults.reset()
    assert [e["action"] for e in _events("device_fault")][-1] == policy
    plan = _events("shard_plan")[-1]
    assert plan == dict(plan, num_shards=shards, rows_per_shard=rows,
                        pad_rows=pad, feature_shards=1)


def test_a_leafs_rows_cross_the_chips_as_integers():
    """A leaf of 17,000,001 rows, 9,000,001 of them on one shard: summed as
    float32 the count comes out even (float32 holds odd numbers up to 2^24
    only), which a model of 147 M rows showed as ``leaf_count_gap`` 1 on one
    seed in eight; ``_leaf_sums_allreduce`` hands the rows over as int32."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    gp = grow.GrowParams(axis_name="data")
    rows = np.array([[9_000_001, 3], [8_000_000, 1], [0, 2], [0, 5]],
                    np.float32)                       # [shard, leaf]
    local = jnp.stack([jnp.zeros_like(rows), jnp.ones_like(rows), rows],
                      axis=1)                          # [shard, 3, leaf]
    g, h, count = jax.shard_map(
        lambda x: grow._leaf_sums_allreduce(x[0], gp), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False)(local)
    assert count.dtype == jnp.int32
    assert count.tolist() == [17_000_001, 11]
    assert h.tolist() == [4.0, 4.0] and g.tolist() == [0.0, 0.0]
    assert int(np.float32(9_000_001) + np.float32(8_000_000)) != 17_000_001
