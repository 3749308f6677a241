"""``benchmark/control.py`` for a cell whose configuration names a generator
of its own and is judged by the tiled reference (``reference_wide.py``):
``control.py`` imports ``data`` and ``reference`` by name, whose blocks of
2**20 rows do not fit at thousands of columns. The same control (int4 for
int8), the same faults (``coarse_bins``, ``thin_sample``, ``half``), the same
sound readings (``int8``, ``exact``), held to the cell's limits by the run's
own comparison; no validation set, so no ``stale_valid``. One fault more,
for the number ``hessian_shortfall``: ``lax_minimum``, a tree grown under half
the configuration's ``min_sum_hessian_in_leaf`` and followed under the whole.

    python3 -m benchmark.control_wide --workload <name> --seeds 1,2,3

exits non-zero unless every control and fault read came out not correct and
every sound reading correct.
"""
import argparse
import importlib
import json
import sys
import time

from benchmark import control

HAS_TO_FAIL = ("int4", "coarse_bins", "thin_sample", "half", "lax_minimum")
SOUND = control.SOUND


def read_seed(cell, seed, n_train, block_rows, trees=2,
              modes=HAS_TO_FAIL + SOUND):
    """{mode: numbers} for one seed."""
    from benchmark import check, reference_wide as R
    cfg = cell["cfg"]
    gen = importlib.import_module("benchmark." + cfg["generator"])
    max_bin = int(cfg["params"]["max_bin"])
    sample, _ = gen.to_host(gen.seed_key(seed), cfg,
                            min(n_train, control.BOUNDS_ROWS),
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
    for mode, bits in (("int4", 4), ("int8", 8), ("exact", None)):
        if mode in modes:
            walks, ts = grow(bounds, trees, quant_bits=bits, quant_seed=seed)
            out[mode] = dict(
                check.combine([check.tree_numbers(walks[i], ts[i])
                               for i in which]),
                **check.bin_numbers(walks[0], bounds, cfg, n_train))
    for mode, made in (
            ("coarse_bins", lambda: R.quantile_bounds(sample, max_bin // 4)),
            ("thin_sample", lambda: R.quantile_bounds(
                sample[:control.THIN_ROWS], max_bin))):
        if mode in modes:
            b = made()
            walks, _ = grow(b)
            out[mode] = check.bin_numbers(walks[0], b, cfg, n_train)
    lax = dict(cfg, params=dict(cfg["params"], min_sum_hessian_in_leaf=float(
        cfg["params"]["min_sum_hessian_in_leaf"]) / 2))
    for mode, how in (("half", {"half": True}), ("lax_minimum", {"cfg": lax})):
        if mode in modes:
            _, ts = grow(bounds, **how)
            out[mode] = check.follow_trees(seed, cfg, n_train, bounds, ts,
                                           [0], block_rows, ref=R)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default=",".join(HAS_TO_FAIL + SOUND))
    ap.add_argument("--trees", type=int, default=2)
    args = ap.parse_args(argv)
    from benchmark import harness
    cell = harness.load_cell(args.workload)
    harness.prepare_environment(native_in_checkout=True)
    import jax
    if jax.default_backend() != "tpu":
        sys.exit("benchmark.control_wide reads at the cell's own size: it "
                 "needs the chip (the small-size control is in "
                 "benchmark/tests)")
    cfg = cell["cfg"]
    gen = importlib.import_module("benchmark." + cfg["generator"])
    wrong = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = read_seed(cell, seed, int(cfg["train_rows"]), gen.BLOCK_ROWS,
                        args.trees, tuple(args.modes.split(",")))
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
