"""Time the fused route+histogram q8 level pass across slot widths.

``--json`` emits one machine-readable line (per-width ms + workload meta)
instead of the human table; ``--rows`` shrinks the workload for CI smoke
runs. Timings are taken on a TPU backend only: anywhere else the script
reports the channel/MAC accounting and leaves every ``ms`` as null (an
interpret-mode time is not a device time).
"""
import argparse
import json
import sys

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import pallas_hist as PH
from lightgbm_tpu.utils.timer import time_op_in_jit


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line instead of the human table")
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=64)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--widths", type=int, nargs="*",
                    default=(1, 2, 8, 32, 64, 127))
    ap.add_argument("--const-hess", action="store_true",
                    help="profile the const-hessian elided kernels (the low "
                         "channel is the 0/1 count; h reconstructed on "
                         "dequant)")
    ap.add_argument("--packed", action="store_true",
                    help="pack g+low into one int32 lattice word when the "
                         "guard-bit budget fits --rows (else reports "
                         "packed=false and runs unpacked)")
    args = ap.parse_args()

    n, f, b, L = args.rows, args.features, args.max_bin, args.leaves
    on_chip = jax.default_backend() == "tpu"
    pack_k = H.pack_guard_bits(n, args.const_hess) if args.packed else 0
    nch = PH._q8_nch(args.const_hess, pack_k)
    rng = np.random.RandomState(0)
    bins_T = jnp.asarray(rng.randint(0, b, size=(f, n), dtype=np.uint8))
    gq = jnp.asarray(rng.randint(-127, 128, n, dtype=np.int8))
    cq = jnp.ones(n, jnp.int8)
    # const-hess: the kernels read the count channel in place of hq
    hq = cq if args.const_hess else jnp.asarray(
        rng.randint(0, 128, n, dtype=np.int8))
    lid = jnp.asarray(rng.randint(0, L, n, dtype=np.int32))

    results = []
    for s in args.widths:
        tables = H.RouteTables(
            feat=jnp.zeros(L, jnp.int32), thr=jnp.full(L, b // 2, jnp.int32),
            dleft=jnp.zeros(L, jnp.int32),
            new_leaf=jnp.arange(L, dtype=jnp.int32),
            slot_left=jnp.zeros(L, jnp.int32),
            slot_right=jnp.minimum(jnp.ones(L, jnp.int32), s - 1))
        ms = time_op_in_jit(
            lambda i, bt, ll: PH.hist_routed_fused_q8(
                bt, gq, hq, cq, jnp.minimum(ll + i, L - 1), tables,
                jnp.full(f, b + 1, jnp.int32), s, b,
                jnp.float32(1.0), jnp.float32(1.0), L,
                const_hess=args.const_hess, pack_k=pack_k)[0].sum(),
            bins_T, lid, K=4, reps=2) if on_chip else None
        # analytic MXU work of the level pass: the [F*B, chunk] one-hot
        # contracts against [S*nch, chunk] row weights over all N rows
        results.append({"slot_width": s,
                        "ms": None if ms is None else round(ms, 3),
                        "channels": nch, "packed": pack_k > 0,
                        "macs": n * f * b * s * nch})
        if not args.json:
            print(f"fused S={s:4d} nch={nch}{' packed' if pack_k else '':7s}: "
                  + (f"{ms:7.2f} ms" if on_chip else
                     f"not measured (backend={jax.default_backend()})"))
    if args.json:
        print(json.dumps({
            "rows": n, "features": f, "max_bin": b, "num_leaves": L,
            "backend": jax.default_backend(),
            "channels": nch, "packed": pack_k > 0, "pack_guard_bits": pack_k,
            "const_hess": args.const_hess,
            "master_slot_widths": list(PH.MASTER_SLOT_WIDTHS),
            "fused_level_pass": results}))


if __name__ == "__main__":
    main()
