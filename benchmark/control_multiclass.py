"""``benchmark/control.py`` for a cell of K class trees an iteration, judged
by ``benchmark/check_multiclass.py`` over ``reference_multiclass.py``. The
same control (the reference in the program's place at int4 for int8), the
same faults (``coarse_bins``, ``thin_sample``, ``half``: the commonest
class's tree grown from every other row), the same sound readings (``int8``,
``exact``), held to the cell's limits by the run's own comparison; and two
faults of this configuration's own:

  regrad        an iteration whose class trees are each grown from the
                gradients of the scores as the class trees before it left
                them, not as the iteration found them
  bundle_order  an iteration's trees with the forty soil columns read in the
                wrong order (column 14 + s for 14 + 39 - s): what a bundle's
                positions decoded against the wrong member list come to

    python3 -m benchmark.control_multiclass --workload <name> --seeds 1,2,3

exits non-zero unless every control and fault read came out not correct and
every sound reading correct.
"""
import argparse
import importlib
import json
import sys
import time

import numpy as np

from benchmark import control

HAS_TO_FAIL = ("int4", "coarse_bins", "thin_sample", "half", "regrad",
               "bundle_order")
SOUND = control.SOUND


def wrong_soil_order(tree, first, count):
    """``tree`` with the split features of the ``count`` columns from
    ``first`` on mirrored inside their group."""
    feat = np.asarray(tree["split_feature"]).copy()
    inside = (feat >= first) & (feat < first + count)
    feat[inside] = first + (count - 1) - (feat[inside] - first)
    return dict(tree, split_feature=feat)


def read_seed(cell, seed, n_train, block_rows, iters=2,
              modes=HAS_TO_FAIL + SOUND):
    """{mode: numbers} for one seed."""
    from benchmark import check, check_multiclass as C, data_covertype as D
    from benchmark import reference_multiclass as R
    cfg = cell["cfg"]
    gen = importlib.import_module("benchmark." + cfg["generator"])
    max_bin = int(cfg["params"]["max_bin"])
    sample, _ = gen.to_host(gen.seed_key(seed), cfg,
                            min(n_train, control.BOUNDS_ROWS),
                            rows=block_rows)
    bounds = R.sample_bounds(sample, max_bin)
    k = int(cfg["params"]["num_class"])
    # of the iterations grown, the ones a run of this cell would follow
    which = sorted({i if i >= 0 else iters + i
                    for i in cell["traffic"]["checked_trees"]}
                   & set(range(iters))) or [iters - 1]

    def rows_of(bounds):
        return R.Rows(seed, cfg, n_train, bounds, block_rows)

    def judged(trees):
        return C.follow_iterations(seed, cfg, n_train, bounds, trees, [0],
                                   block_rows)

    out, first = {}, None
    for mode, bits in (("int4", 4), ("int8", 8), ("exact", None)):
        if mode not in modes:
            continue
        rows = rows_of(bounds)
        followed = C.followed_classes(rows, seed)
        walks, trees = R.grow_iterations(rows, cfg, bounds, iters,
                                         quant_bits=bits, quant_seed=seed)
        per = [{c: check.tree_numbers(walks[i * k + c], trees[i * k + c])
                for c in followed} for i in which]
        numbers = {"off_grid": 0, "leaf_count_gap": 0.0, "class_order_gap": 0,
                   "leaf_value_gap": max(n["leaf_value_gap"]
                                         for it in per for n in it.values())}
        print(f"control_multiclass: {mode}: " + "; ".join(
            f"iteration {i} " + ", ".join(
                f"class {c} {n['split_regret']:.3g}" for c, n in it.items())
            for i, it in zip(which, per)), file=sys.stderr)
        numbers.update(C.regret_numbers(
            [{c: n["split_regret"] for c, n in it.items()} for it in per],
            followed[0]))
        out[mode] = dict(numbers, **C.bin_numbers(
            walks[0]["root_bin_count"], bounds, cfg, n_train))
        if mode == "exact" or first is None:
            first = trees[:k]
    stump = dict(cfg, params=dict(cfg["params"], max_depth=1))
    for mode, made in (
            ("coarse_bins", lambda: R.sample_bounds(sample, max_bin // 4)),
            ("thin_sample", lambda: R.sample_bounds(
                sample[:control.THIN_ROWS], max_bin))):
        if mode in modes:
            b = made()
            walk = R.walk_tree(rows_of(b), stump, b, 0)
            out[mode] = C.bin_numbers(walk["root_bin_count"], b, cfg, n_train)
    if first is None and {"half", "bundle_order"} & set(modes):
        first = R.grow_iterations(rows_of(bounds), cfg, bounds, 1)[1]
    if "half" in modes:
        rows = rows_of(bounds)
        cls = C.followed_classes(rows, seed)[0]
        walk = R.walk_tree(rows, cfg, bounds, cls, half=True)
        trees = list(first)
        trees[cls] = R.grown_to_tree(walk, bounds)
        out["half"] = judged(trees)
    if "regrad" in modes:
        out["regrad"] = judged(R.grow_iterations(
            rows_of(bounds), cfg, bounds, 1, stale=False)[1])
    if "bundle_order" in modes:
        soil = D.NUMERIC + D.WILDERNESS
        out["bundle_order"] = judged(
            [wrong_soil_order(t, soil, D.SOIL) for t in first])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default=",".join(HAS_TO_FAIL + SOUND))
    ap.add_argument("--iters", type=int, default=2)
    args = ap.parse_args(argv)
    from benchmark import harness
    cell = harness.load_cell(args.workload)
    harness.prepare_environment(native_in_checkout=True)
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("benchmark.control_multiclass reads at the cell's own size: "
                 "it needs the chip (the small-size control is in "
                 "benchmark/tests)")
    cfg = cell["cfg"]
    gen = importlib.import_module("benchmark." + cfg["generator"])
    wrong = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = read_seed(cell, seed, int(cfg["train_rows"]), gen.BLOCK_ROWS,
                        args.iters, tuple(args.modes.split(",")))
        failed = control.verdicts(cell, out)
        wrong += [(seed, m) for m, f in failed.items()
                  if bool(f) != (m in HAS_TO_FAIL)]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": round(time.perf_counter() - t, 1),
                          "limits": cell["limits"], "readings": out,
                          "failed": failed}), flush=True)
    for seed, mode in wrong:
        print(f"control: seed {seed}: {mode} came out "
              f"{'correct' if mode in HAS_TO_FAIL else 'not correct'}",
              file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
