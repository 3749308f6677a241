"""``benchmark/check.py`` for a configuration whose rows come from a
generator of its own and whose width needs the tiled reference
(``benchmark/reference_wide.py``): the same numbers beside the same kind of
limits (``check.py``'s docstring lists them), computed by ``check.py``'s own
functions from the tiled walk, and one number more:

  hessian_shortfall  the most that a child of a split taken lies under
                     ``min_sum_hessian_in_leaf`` by the reference's exact
                     float32 hessians, as a share of that minimum. The
                     program holds the minimum to its quantised sums
                     (``precision``), so a hair is sound and a split it let
                     through keeps its gain in ``split_regret``
                     (``reference_wide._taken``); ``check.py``'s reference
                     calls the same split's gain nan, which at HIGGS's leaf
                     sizes never comes up.

Training traffic without a validation set only: no cell asks for more yet.
"""
from benchmark import check, reference_wide as reference


def tree_numbers(walk, tree):
    return dict(check.tree_numbers(walk, tree),
                hessian_shortfall=walk["hessian_shortfall"])


def combine(per_tree):
    return dict(check.combine(per_tree), hessian_shortfall=max(
        n["hessian_shortfall"] for n in per_tree))


def follow_trees(seed, cfg, n_train, bounds, trees, which, block_rows):
    """Follows ``trees[i]`` for i in ``which`` and applies the others up to
    the last of them."""
    rows = reference.Rows(seed, cfg, n_train, bounds, block_rows)
    numbers, bins = [], None
    for i in range(max(which) + 1):
        if i in which:
            walk = reference.walk_tree(rows, cfg, bounds, tree=trees[i])
            numbers.append(tree_numbers(walk, trees[i]))
            bins = bins or check.bin_numbers(walk, bounds, cfg, n_train)
        else:
            reference.apply_tree(rows, trees[i], bounds)
    return dict(combine(numbers), **bins)


def check_cell(cell, seed, produced, n_train, n_valid, block_rows):
    if n_valid:
        raise ValueError("check_wide judges training without a validation "
                         f"set; {cell['name']} has {n_valid} validation rows")
    cfg, traffic = cell["cfg"], cell["traffic"]
    trees = reference.parse_model(produced["model_text"])
    done = int(produced["iterations"])
    which = sorted({i if i >= 0 else done + i
                    for i in traffic["checked_trees"]})
    fm = produced["feature_map"]
    numbers = {}
    if fm is not None and list(fm) != list(range(int(cfg["num_features"]))):
        numbers["off_grid"] = float("nan")       # a feature was dropped
    elif which[0] < 0 or which[-1] >= len(trees):
        numbers["off_grid"] = float("nan")       # too few trees to judge
    elif max(len(b) for b in produced["bounds"]) > reference.NBINS:
        numbers["bin_count_gap"] = float("nan")  # more bins than can be read
    else:
        numbers.update(follow_trees(seed, cfg, n_train, produced["bounds"],
                                    trees, which, block_rows))
    return check.judge(numbers, cell["limits"])
