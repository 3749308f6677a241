"""Tree-by-tree comparison of two boosters, shared by the kernel-path tests
(test_wide_path.py, test_q8_kernels.py)."""
import numpy as np


def same_trees(a, b):
    """Two boosters grew the same three trees; returns the first's."""
    ta, tb = a._ensure_host_trees(), b._ensure_host_trees()
    assert len(ta) == len(tb) == 3
    for t1, t2 in zip(ta, tb):
        k = t1.num_leaves
        assert k == t2.num_leaves and k > 1
        for name in ("split_feature", "threshold_bin", "left_child",
                     "right_child"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t1, name))[: k - 1],
                np.asarray(getattr(t2, name))[: k - 1], err_msg=name)
        np.testing.assert_array_equal(np.asarray(t1.leaf_count)[:k],
                                      np.asarray(t2.leaf_count)[:k])
        np.testing.assert_allclose(np.asarray(t1.leaf_value)[:k],
                                   np.asarray(t2.leaf_value)[:k],
                                   rtol=2e-5, atol=1e-7)
    return ta
