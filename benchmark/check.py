"""What decides ``correct``: the bin bounds the run made, some of its trees
and its AUCs, against the plain reference, each number beside a limit of its
own.

The trees followed are the traffic file's ``checked_trees`` (indices into
the run's trees, negative ones counted from the last iteration of the
window): the second, and the last the window made; the reference applies
the others to its scores, so the last one's gradients rest on every score
update of the run.

The numbers (see PERF.md for the readings each limit was set from):

  bin_count_gap    sum over features of |bins the run made - ``max_bin``|
                   (exact: 0)
  bin_occupancy_excess  the fullest bin's rows over rows / ``max_bin``, less
                   one, over every feature: how far the run's bounds are
                   from equal frequency (the reference counts its own rows
                   against them)
  off_grid         thresholds of the followed trees that are not one of the
                   run's bin bounds (exact: 0)
  leaf_count_gap   sum over the followed trees' leaves of |model leaf_count -
                   rows the reference routes there| (exact: 0)
  leaf_value_gap   worst leaf: |model value - reference value| over the
                   larger of that leaf's and the median leaf's |reference
                   value| (init score taken off the first tree)
  split_regret     the first tree followed: mean over all its split nodes,
                   the root too, of (best gain the reference finds at the
                   node - gain of the split the program chose) over the
                   larger of that node's and the median node's best gain
  split_regret_last  the same of the last tree followed, under a limit that
                   leaves room for its growth with the tree's index
  hessian_shortfall  the most that a child of a split taken lies under
                   ``min_sum_hessian_in_leaf`` by the reference's exact
                   hessians, as a share of that minimum, over the followed
                   trees. The program holds the minimum to its quantised
                   sums (``precision``), so a hair is sound, and such a split
                   keeps its gain in the regrets (``reference._taken``)
  auc_gap          worst iteration: |AUC the run reported - float64 rank AUC
                   of the reference's prediction with the run's trees|
"""
import numpy as np

from benchmark import data, reference


def tree_numbers(walk, tree):
    """The per-tree numbers from a followed walk and the tree it followed
    (``hessian_shortfall`` where the walk reports one)."""
    bias = walk["bias"]
    ref = walk["leaf_value"] - bias
    got = np.asarray(tree["leaf_value"], np.float64) - bias
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    best = walk["best"]
    regret = (best - walk["chosen"]) / np.maximum(best, np.median(best))
    out = {
        "off_grid": walk["off_grid"],
        "leaf_count_gap": float(np.abs(
            np.asarray(tree["leaf_count"], np.float64)
            - walk["leaf_count"]).sum()),
        "leaf_value_gap": float(np.max(np.abs(got - ref) / scale)),
        "split_regret": float(regret.mean()) if len(regret) else 0.0,
    }
    if "hessian_shortfall" in walk:
        out["hessian_shortfall"] = walk["hessian_shortfall"]
    return out


def bin_numbers(walk, bounds, cfg, n_rows):
    """The run's bounds judged by the rows the reference counts in each bin
    at the root of the first tree it follows."""
    max_bin = int(cfg["params"]["max_bin"])
    return {
        "bin_count_gap": float(sum(abs(len(b) - max_bin) for b in bounds)),
        "bin_occupancy_excess": float(
            walk["root_bin_count"].max() * max_bin / n_rows - 1.0),
    }


def combine(per_tree):
    """The followed trees' numbers as the run's: counts summed, the leaf gap
    by the worst tree, the regret of the first tree followed and of the last
    apart (it grows with a tree's index, so the last one's has a limit that
    holds however many trees a faster program fits into the window)."""
    def worst(key):
        return max((n[key] for n in per_tree),
                   key=lambda v: np.inf if np.isnan(v) else v)
    out = {"off_grid": sum(n["off_grid"] for n in per_tree),
           "leaf_count_gap": sum(n["leaf_count_gap"] for n in per_tree),
           "leaf_value_gap": worst("leaf_value_gap"),
           "split_regret": per_tree[0]["split_regret"]}
    if len(per_tree) > 1:
        out["split_regret_last"] = per_tree[-1]["split_regret"]
    if all("hessian_shortfall" in n for n in per_tree):
        out["hessian_shortfall"] = worst("hessian_shortfall")
    return out


def follow_trees(seed, cfg, n_train, bounds, trees, which, block_rows,
                 ref=reference):
    """Follows ``trees[i]`` for i in ``which`` and applies the others up to
    the last of them, by the reference module ``ref`` (``reference_wide``
    for a matrix of thousands of columns)."""
    rows = ref.Rows(seed, cfg, n_train, bounds, block_rows)
    numbers, bins = [], None
    for i in range(max(which) + 1):
        if i in which:
            walk = ref.walk_tree(rows, cfg, bounds, tree=trees[i])
            numbers.append(tree_numbers(walk, trees[i]))
            bins = bins or bin_numbers(walk, bounds, cfg, n_train)
        else:
            ref.apply_tree(rows, trees[i], bounds)
    return dict(combine(numbers), **bins)


def auc_gap(seed, cfg, n_valid, trees, reported, block_rows):
    xv, yv = data.to_host(data.seed_key(seed), cfg, n_valid,
                          data.VALID_STREAM, rows=block_rows)
    score = np.zeros(n_valid, np.float64)
    gaps = []
    for t, rep in zip(trees, reported):
        score += reference.predict_tree(t, xv)
        gaps.append(abs(float(rep) - reference.auc(yv, score)))
    return max(gaps), len(gaps)


def judge(numbers, limits):
    """Each number beside its limit; a limit with no number and a nan fail
    (a nan is printed as null: the result line stays plain JSON)."""
    checks = {}
    for name in list(numbers) + [k for k in limits if k not in numbers]:
        value, limit = numbers.get(name), limits.get(name)
        if value is not None and np.isnan(value):
            value = None
        ok = value is not None and (limit is None or bool(value <= limit))
        checks[name] = {"value": None if value is None else float(value),
                        "limit": limit, "ok": ok}
    return checks


def check_cell(cell, seed, produced, n_train, n_valid, block_rows,
               ref=reference):
    cfg, traffic = cell["cfg"], cell["traffic"]
    trees = reference.parse_model(produced["model_text"])
    done = int(produced["iterations"])
    which = sorted({i if i >= 0 else done + i
                    for i in traffic["checked_trees"]})
    fm = produced["feature_map"]
    max_bins = max(len(b) for b in produced["bounds"])
    numbers = {}
    if fm is not None and list(fm) != list(range(int(cfg["num_features"]))):
        numbers["off_grid"] = float("nan")       # a feature was dropped
    elif which[0] < 0 or which[-1] >= len(trees):
        numbers["off_grid"] = float("nan")       # too few trees to judge
    elif max_bins > reference.NBINS:
        numbers["bin_count_gap"] = float("nan")  # more bins than can be read
    else:
        numbers.update(follow_trees(seed, cfg, n_train, produced["bounds"],
                                    trees, which, block_rows, ref))
    if n_valid:
        aucs = produced["auc"][:done]
        if len(aucs) != done or len(trees) < len(aucs):
            numbers["auc_gap"] = float("nan")
        else:
            numbers["auc_gap"], _ = auc_gap(seed, cfg, n_valid, trees, aucs,
                                            block_rows)
    return judge(numbers, cell["limits"])
