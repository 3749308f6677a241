"""CPU rehearsal: the run's own code path at a tiny size, Pallas kernels in
interpret mode. Prints "rehearsal, not a result" in place of the metrics
line: nothing it times is a measurement.

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse --workload <name>
"""
import time
T0 = time.perf_counter()

import argparse                   # noqa: E402
import json                       # noqa: E402
import sys                        # noqa: E402

# the leaf minimums are loosened with the size: at 40k rows the source's 100
# of hessian would bind, and a split allowed by quantised sums and refused by
# exact ones is no fault of either side
TINY = {"train_rows": 40_000, "valid_rows": 5_000, "block_rows": 16_384,
        "params": {"histogram_impl": "pallas", "num_leaves": 15,
                   "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3}}


def rehearse(workload, seed=1, seconds=0.5, tiny=None):
    """Runs the cell's code path at the tiny size and returns the result
    dict (its timings mean nothing)."""
    from benchmark import harness
    cell = harness.load_cell(workload)
    tiny = dict(TINY if tiny is None else tiny)
    if not cell["traffic"]["valid_rows"]:
        tiny["valid_rows"] = 0
    cfg = cell["cfg"]
    cell["cfg"] = dict(cfg, params=dict(cfg["params"], **{
        k: v for k, v in tiny["params"].items() if k in cfg["params"]}))
    harness.prepare_environment()
    return harness.run_cell(cell, seed, seconds, False, t0=T0, rehearse=tiny)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    result = rehearse(args.workload, args.seed)
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps({"rehearsal, not a result": {
        "correct": result["correct"], "iterations": result["attempted"],
        "device": result["device"]["platform"]}}))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
