"""The control at a small size, held to limits by the run's own comparison:
the reference in the program's place with int4 gradients, bounds with a
quarter of the bins and bounds from a thousand rows each come out not
correct; the reference at the stated precision (int8) and exact come out
correct. ``split_regret``'s limit is this size's own (at 60 k rows the
second tree reads 0 at int8 and at least 4.4e-5 at int4); the readings at the
cells' own size are in PERF.md."""
import pytest

from benchmark import control, harness

SMALL = dict(n_train=60_000, n_valid=0, block_rows=16_384)


@pytest.fixture(scope="module")
def cell():
    c = harness.load_cell("higgs-binary.train")
    c["cfg"] = dict(c["cfg"], params=dict(
        c["cfg"]["params"], num_leaves=31, min_data_in_leaf=20,
        min_sum_hessian_in_leaf=1e-3))
    c["limits"] = dict(c["limits"], split_regret=1e-5)
    return c


@pytest.mark.parametrize("seed", [3, 2147483900, 77])
def test_controls_come_out_not_correct(cell, seed):
    r = control.read_seed(cell, seed, trees=2, modes=(
        "int4", "int8", "exact", "coarse_bins", "thin_sample"), **SMALL)
    failed = control.verdicts(cell, r)
    assert failed["exact"] == [] and failed["int8"] == []
    assert r["exact"]["split_regret"] == 0.0
    assert failed["int4"] == ["split_regret"]
    assert r["int4"]["split_regret"] > 3 * r["int8"]["split_regret"]
    assert failed["coarse_bins"] == ["bin_count_gap", "bin_occupancy_excess"]
    assert failed["thin_sample"] == ["bin_occupancy_excess"]


def test_histogram_is_exact():
    """The reference's one-hot contraction against a float64 bincount."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference as R
    rng = np.random.default_rng(0)
    n, f = R.SUB * 2, 5
    bins = rng.integers(0, 63, (n, f)).astype(np.uint8)
    g = rng.standard_normal((n, 2)).astype(np.float32)
    slot = rng.integers(-1, 4, n).astype(np.int32)
    p = 7
    acc = jnp.zeros((f * R.NBINS, 4 * p), jnp.float32)
    _, _, acc, _ = R._level_block(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(slot),
        jnp.full((n,), -1, jnp.int32), jnp.zeros((1, f + 5), jnp.float32),
        acc, jnp.zeros_like(acc), n_slots=4, route=False)
    h = np.asarray(acc, np.float64).reshape(f, R.NBINS, 4, p)
    got = h[..., 0] + h[..., 1] + h[..., 2]
    want = np.zeros((f, R.NBINS, 4))
    live = slot >= 0
    for j in range(f):
        np.add.at(want[j], (bins[live, j], slot[live]),
                  g[live, 0].astype(np.float64))
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
    cnt = np.zeros((f, R.NBINS, 4))
    for j in range(f):
        np.add.at(cnt[j], (bins[live, j], slot[live]), 1.0)
    assert np.array_equal(h[..., -1], cnt)
