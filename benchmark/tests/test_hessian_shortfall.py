"""``hessian_shortfall`` in the HIGGS reference (``reference.py``): a split
the program took with a child a hair under ``min_sum_hessian_in_leaf`` by the
exact sums keeps its gain, so the regrets read numbers and not nan, and a
tree grown under half of the minimum fails on this number alone."""
import numpy as np
import pytest

from benchmark import check, control, data, harness, reference as R

N, BLOCK = 32_768, 16_384


@pytest.fixture(scope="module")
def cell():
    c = harness.load_cell("higgs-binary.train")
    c["cfg"] = dict(c["cfg"], params=dict(c["cfg"]["params"], num_leaves=63))
    return c


def failed(cell, numbers):
    return sorted(k for k, c in check.judge(numbers, cell["limits"]).items()
                  if k in numbers and not c["ok"])


@pytest.mark.parametrize("seed", [5, 2147483921])
def test_a_tree_grown_under_half_the_minimum_fails_the_shortfall(cell, seed):
    """The control's ``lax_minimum`` at a size where the minimum of 100
    binds (32 k rows, 63 leaves): grown under 50, followed under 100, it
    fails ``hessian_shortfall`` and nothing else; the exact reference grown
    and followed under 100 reads 0 and passes."""
    r = control.read_seed(cell, seed, N, 0, BLOCK, trees=2,
                          modes=("lax_minimum", "exact"))
    assert failed(cell, r["exact"]) == []
    assert r["exact"]["hessian_shortfall"] == 0.0
    assert failed(cell, r["lax_minimum"]) == ["hessian_shortfall"]
    assert r["lax_minimum"]["hessian_shortfall"] > 0.1
    assert np.isfinite(r["lax_minimum"]["split_regret_last"]
                       if "split_regret_last" in r["lax_minimum"]
                       else r["lax_minimum"]["split_regret"])


def test_a_child_a_hair_under_the_minimum_reads_a_number(cell):
    """The first tree's hessian is one constant a row, so its smallest leaf
    holds count x h. Followed under a minimum 1e-5 above that, the split
    that made the leaf is one the exact sums forbid: its gain is still read
    (the regret is a number, where the masked table gave nan), the
    shortfall is that 1e-5, and the run is correct."""
    cfg = cell["cfg"]
    sample, _ = data.to_host(data.seed_key(9), cfg, N, rows=BLOCK)
    bounds = R.quantile_bounds(sample, int(cfg["params"]["max_bin"]))
    rows = R.Rows(9, cfg, N, bounds, BLOCK)
    walk = R.walk_tree(rows, cfg, bounds)
    tree = R.grown_to_tree(walk, bounds)
    p = 1.0 / (1.0 + np.exp(-rows.init))
    smallest = float(walk["leaf_count"].min()) * p * (1.0 - p)
    hair = dict(cfg, params=dict(cfg["params"],
                                 min_sum_hessian_in_leaf=smallest * 1.00001))
    numbers = check.follow_trees(9, hair, N, bounds, [tree], [0], BLOCK)
    assert np.isfinite(numbers["split_regret"])
    assert 0 < numbers["hessian_shortfall"] < 1e-4
    assert failed(cell, numbers) == []
