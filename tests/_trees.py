"""Tree-by-tree comparison of two boosters, shared by the kernel-path tests
(test_wide_path.py, test_q8_kernels.py), and the child tables of a tree grown
level by level (test_predict_surfaces.py, _tpu_kernel_check.py)."""
import numpy as np


def level_order_children(num_leaves):
    """(left, right) child tables, >= 0 a node and ~leaf below, of a tree
    grown level by level as the depthwise grower numbers it: split t takes
    the oldest leaf, keeps its id on the left and makes leaf t + 1 on the
    right. 255 leaves: depth 8, the last level one short."""
    m = num_leaves - 1
    left, right = [0] * m, [0] * m
    queue = [(0, None)]               # (leaf, (parent node, its child table))
    for t in range(m):
        leaf, parent = queue.pop(0)
        if parent is not None:
            parent[1][parent[0]] = t
        left[t], right[t] = ~leaf, ~(t + 1)
        queue += [(leaf, (t, left)), (t + 1, (t, right))]
    return left, right


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
