"""``benchmark/check.py`` for K class trees an iteration, over the plain
reference ``benchmark/reference_multiclass.py`` and ``check.judge``'s
comparison. The traffic file's ``checked_trees`` name ITERATIONS here (the
second, and the last the window made); of each the reference judges

  every class tree   by one routing of its rows: ``leaf_count_gap``,
                     ``leaf_value_gap`` (``check.py``'s numbers) and
  class_order_gap    the class trees whose leaf values lie nearer to the
                     values the reference gives the leaves under ANOTHER
                     class's gradients than under the gradients of class
                     ``i mod K`` (exact: 0; a tree applied to the wrong
                     class's score, or two class trees swapped, reads so);
  three class trees  followed level by level with exact histograms, the
                     commonest class's, the rarest's and one drawn from
                     ``--seed``: ``off_grid`` (thresholds off the run's
                     bounds, and splits below ``max_depth``) of all three;
                     ``split_regret`` (second iteration) and
                     ``split_regret_last`` of the COMMONEST class's tree,
                     the one a coarser gradient lattice shows in; and
  split_regret_rare  the worst regret of the other two trees in either
                     iteration, under a limit of its own: a coarse net. A
                     rare class's tree splits nodes whose gain over the
                     parent is some ten-thousandth of the parent's own gain
                     (nearly all rows pull one way), and a float32 gain,
                     the difference of two such terms, resolves that to a
                     part in a thousand: its regret reads a hundred times
                     the commonest class's and does not fall with the rows
                     as int8's noise does (PERF.md section 2).

Every gradient is of the scores as the iteration found them; the other
iterations' trees are applied to the reference's scores. ``bin_count_gap``
and ``bin_occupancy_excess`` are ``check.py``'s over the columns that can
fill ``max_bin`` bins (the ten numeric ones; a 0/1 column has to come with
two bins, or three with an empty one).
"""
import sys

import numpy as np

from benchmark import check, data_covertype, reference_multiclass as R


def bin_numbers(root_bin_count, bounds, cfg, n_rows):
    max_bin = int(cfg["params"]["max_bin"])
    numeric = data_covertype.NUMERIC
    # a 0/1 column has two bins, or three where the program gives zero a
    # bin of its own and leaves one empty beside it (LightGBM's rule)
    gaps = [abs(len(b) - max_bin) for b in bounds[:numeric]] \
        + [max(2 - len(b), len(b) - 3, 0) for b in bounds[numeric:]]
    return {
        "bin_count_gap": float(sum(gaps)),
        "bin_occupancy_excess": float(
            root_bin_count[:numeric].max() * max_bin / n_rows - 1.0),
    }


def leaf_numbers(judged, tree, cls):
    """One routed class tree's numbers: ``check.tree_numbers``'s leaf gap
    under its own class, and whether another class fits its values better."""
    got = np.asarray(tree["leaf_value"], np.float64)
    ref = judged["leaf_value"]                                  # [K, L]
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref), axis=1,
                                              keepdims=True))
    gaps = np.max(np.abs(got[None, :] - ref) / scale, axis=1)
    return {
        "leaf_count_gap": float(np.abs(
            np.asarray(tree["leaf_count"], np.float64)
            - judged["leaf_count"]).sum()),
        "leaf_value_gap": float(gaps[cls]),
        "class_order_gap": float(int(np.nanargmin(gaps)) != cls),
    }


def followed_classes(rows, seed):
    """(the commonest class, the rarest, one of the others by the seed)."""
    count = sum(np.bincount(np.asarray(y)[np.asarray(l)], minlength=rows.k)
                for y, l in zip(rows.y, rows.live))
    common, rare = int(np.argmax(count)), int(np.argmin(count))
    rest = [c for c in range(rows.k) if c not in (common, rare)]
    return common, rare, rest[int(seed) % len(rest)]


def regret_numbers(per_iteration, common):
    """The followed trees' regrets ({class: regret} an iteration judged, in
    order) as the run's numbers."""
    def worst(values):
        return max(values, key=lambda v: np.inf if np.isnan(v) else v)
    out = {"split_regret": per_iteration[0][common]}
    if len(per_iteration) > 1:
        out["split_regret_last"] = per_iteration[-1][common]
    rest = [v for it in per_iteration for c, v in it.items() if c != common]
    if rest:
        out["split_regret_rare"] = worst(rest)
    return out


def follow_iterations(seed, cfg, n_train, bounds, trees, which, block_rows,
                      log=None):
    """Judges the iterations ``which`` of ``trees`` (K an iteration, class
    order) and applies the others up to the last of them."""
    rows = R.Rows(seed, cfg, n_train, bounds, block_rows)
    k = rows.k
    followed = followed_classes(rows, seed)
    leaves, regrets, off_grid, bins = [], [], 0, None
    for it in range(max(which) + 1):
        rows.begin_iteration()
        regret = {}
        for cls in range(k):
            tree = trees[it * k + cls]
            leaf = R.route_tree(rows, cfg, tree, bounds)
            if it in which:
                leaves.append(leaf_numbers(
                    R.judge_leaves(rows, cfg, tree, leaf), tree, cls))
            if it not in which or cls not in followed:
                rows.add_values(cls, leaf, tree["leaf_value"],
                                tree["num_leaves"])
                continue
            walk = R.walk_tree(rows, cfg, bounds, cls, tree=tree)
            numbers = check.tree_numbers(walk, tree)
            off_grid += numbers["off_grid"]
            regret[cls] = numbers["split_regret"]
            if bins is None and walk["root_bin_count"] is not None:
                bins = bin_numbers(walk["root_bin_count"], bounds, cfg,
                                   n_train)
            if log is not None:
                print(f"check_multiclass: iteration {it} class {cls}: "
                      f"{numbers}", file=log)
        if it in which:
            regrets.append(regret)

    def worst_of(key):
        return max((n[key] for n in leaves),
                   key=lambda v: np.inf if np.isnan(v) else v)
    out = {"off_grid": off_grid,
           "leaf_count_gap": sum(n["leaf_count_gap"] for n in leaves),
           "leaf_value_gap": worst_of("leaf_value_gap"),
           "class_order_gap": sum(n["class_order_gap"] for n in leaves)}
    out.update(regret_numbers(regrets, followed[0]))
    return dict(out, **(bins or bin_numbers(R.root_bin_count(rows), bounds,
                                            cfg, n_train)))


def check_cell(cell, seed, produced, n_train, n_valid, block_rows):
    if n_valid:
        raise ValueError("check_multiclass judges training without a "
                         f"validation set; {cell['name']} has {n_valid} "
                         "validation rows")
    cfg, traffic = cell["cfg"], cell["traffic"]
    k = int(cfg["params"]["num_class"])
    trees = R.parse_model(produced["model_text"])
    done = int(produced["iterations"])
    which = sorted({i if i >= 0 else done + i
                    for i in traffic["checked_trees"]})
    fm = produced["feature_map"]
    numbers = {}
    if fm is not None and list(fm) != list(range(int(cfg["num_features"]))):
        numbers["off_grid"] = float("nan")       # a feature was dropped
    elif which[0] < 0 or (which[-1] + 1) * k > len(trees):
        numbers["off_grid"] = float("nan")       # too few trees to judge
    elif max(len(b) for b in produced["bounds"]) > R.NBINS:
        numbers["bin_count_gap"] = float("nan")  # more bins than can be read
    else:
        numbers.update(follow_iterations(
            seed, cfg, n_train, produced["bounds"], trees, which, block_rows,
            log=sys.stderr))
    return check.judge(numbers, cell["limits"])
