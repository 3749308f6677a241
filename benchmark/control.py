"""The control and the planted faults, read at a cell's own size and held to
the cell's limits by the run's own comparison (``check.judge``).

The control is the reference put in the program's place with its gradients
quantised one step below what the configuration states (int4 for int8); it
has to fail one of the cell's numbers, and so has each fault: bin bounds
with a quarter of the bins (``coarse_bins``), bounds taken from a thousand
rows (``thin_sample``), half of the rows left out of a step (``half``), a
validation score that misses a tree (``stale_valid``). Beside them the same
reference at the stated precision (``int8``) and exact, which have to pass
what they read. One fault only on request, ``lax_minimum``: ``--trees``
trees grown under half of ``min_sum_hessian_in_leaf`` and followed under the
whole, for ``hessian_shortfall``; at HIGGS's rows the minimum binds only from
about the twelfth tree, so it needs ``--trees`` as many as the window makes.
The benchmark's own runs never run this; ``benchmark/tests`` keeps it at a
small size.

    python3 -m benchmark.control --workload <name> --seeds 1,2,3 \
        [--trees 15 --modes lax_minimum,int8,exact]

exits non-zero unless every control and fault read came out not correct and
every sound reading correct.
"""
import argparse
import json
import sys
import time


ON_REQUEST = ("lax_minimum",)
HAS_TO_FAIL = ("int4", "coarse_bins", "thin_sample", "half",
               "stale_valid") + ON_REQUEST
SOUND = ("int8", "exact")
DEFAULT_MODES = tuple(m for m in HAS_TO_FAIL if m not in ON_REQUEST) + SOUND
BOUNDS_ROWS = 200_000      # rows the sound bounds are taken from
THIN_ROWS = 1_000


def read_seed(cell, seed, n_train, n_valid, block_rows, trees=2,
              modes=DEFAULT_MODES):
    """{mode: numbers} for one seed."""
    from benchmark import check, data, reference as R
    cfg = cell["cfg"]
    max_bin = int(cfg["params"]["max_bin"])
    key = data.seed_key(seed)
    sample, _ = data.to_host(key, cfg, min(n_train, BOUNDS_ROWS),
                             rows=block_rows)
    bounds = R.quantile_bounds(sample, max_bin)

    def grow(bounds, count=1, cfg=cfg, **how):
        rows = R.Rows(seed, cfg, n_train, bounds, block_rows)
        walks = [R.walk_tree(rows, cfg, bounds, **how) for _ in range(count)]
        return walks, [R.grown_to_tree(w, bounds) for w in walks]

    # of the trees grown, the ones a run of this cell would follow
    which = sorted({i if i >= 0 else trees + i
                    for i in cell["traffic"]["checked_trees"]})
    out = {}
    grown = None
    for mode, bits in (("int4", 4), ("int8", 8), ("exact", None)):
        if mode not in modes:
            continue
        walks, ts = grow(bounds, trees, quant_bits=bits, quant_seed=seed)
        out[mode] = dict(
            check.combine([check.tree_numbers(walks[i], ts[i])
                           for i in which]),
            **check.bin_numbers(walks[0], bounds, cfg, n_train))
        if mode == "exact" or grown is None:
            grown = ts
    for mode, made in (
            ("coarse_bins", lambda: R.quantile_bounds(sample, max_bin // 4)),
            ("thin_sample", lambda: R.quantile_bounds(sample[:THIN_ROWS],
                                                      max_bin))):
        if mode in modes:
            b = made()
            walks, _ = grow(b)
            out[mode] = check.bin_numbers(walks[0], b, cfg, n_train)
    if "half" in modes:
        _, ts = grow(bounds, half=True)
        out["half"] = check.follow_trees(seed, cfg, n_train, bounds, ts, [0],
                                         block_rows)
    if "lax_minimum" in modes:
        lax = dict(cfg, params=dict(cfg["params"], min_sum_hessian_in_leaf=(
            float(cfg["params"]["min_sum_hessian_in_leaf"]) / 2)))
        _, ts = grow(bounds, trees, cfg=lax)
        out["lax_minimum"] = check.follow_trees(seed, cfg, n_train, bounds,
                                                ts, which, block_rows)
    if "stale_valid" in modes and n_valid and grown:
        xv, yv = data.to_host(key, cfg, n_valid, data.VALID_STREAM,
                              rows=block_rows)
        score = sum(R.predict_tree(t, xv) for t in grown)
        stale = score - R.predict_tree(grown[-1], xv)
        out["stale_valid"] = {"auc_gap": abs(R.auc(yv, score)
                                             - R.auc(yv, stale))}
    return out


def verdicts(cell, readings):
    """{mode: names of the numbers it failed}, by the run's own comparison
    over the numbers the mode read."""
    from benchmark import check
    return {mode: sorted(
        k for k, c in check.judge(numbers, cell["limits"]).items()
        if k in numbers and not c["ok"])
        for mode, numbers in readings.items()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default=",".join(DEFAULT_MODES))
    ap.add_argument("--trees", type=int, default=2)
    args = ap.parse_args(argv)
    from benchmark import data, harness
    cell = harness.load_cell(args.workload)
    harness.prepare_environment(native_in_checkout=True)
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("benchmark.control reads at the cell's own size: it needs "
                 "the chip (the small-size control is in benchmark/tests)")
    cfg, traffic = cell["cfg"], cell["traffic"]
    wrong = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = read_seed(cell, seed, int(cfg["train_rows"]),
                        int(traffic["valid_rows"]), data.BLOCK_ROWS,
                        args.trees, tuple(args.modes.split(",")))
        failed = verdicts(cell, out)
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
