"""One cell, once, in one process, on the chip.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the contract's one-line JSON last on standard output. Exits non-zero,
printing no result, when the backend is not ``tpu`` or holds fewer chips
than the cell asks for. ``python3 -m benchmark.rehearse`` is the CPU
rehearsal; this command never runs off the chip.
"""
import time
T0 = time.perf_counter()          # set-up starts here, before any import

import argparse                   # noqa: E402
import sys                        # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark import harness
    cell = harness.load_cell(args.workload)
    harness.prepare_environment(native_in_checkout=True)
    cfg = cell["cfg"]
    buffer = harness.prefault((int(cfg["train_rows"]),
                               int(cfg["num_features"])))
    spans = {}
    t = time.perf_counter()
    import jax
    spans["harness.import_jax_s"] = time.perf_counter() - t
    t = time.perf_counter()
    devs = jax.devices()
    spans["harness.device_start_s"] = time.perf_counter() - t
    if jax.default_backend() != "tpu" or len(devs) < cell["chips"]:
        sys.exit(f"benchmark.run: backend {jax.default_backend()!r} with "
                 f"{len(devs)} device(s); the cell needs {cell['chips']} "
                 "TPU chip(s)")
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              t0=T0, spans=spans, train_buffer=buffer)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
