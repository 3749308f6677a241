"""Isolate the N-independent glue in one boosting iteration (PERF_NOTES lever
#3): time the production fused step via an in-jit fori_loop at several N and
fit time = a*N + b. The intercept b is the fixed per-tree cost (per-level
bookkeeping, split search, tree-array scatters) that does not shrink with
rows. Then break b down: grower alone vs grower+gradients+score, and glue
scaling with num_leaves (level count).
"""
# profiling harness: building jit wrappers per invocation is the POINT
# (each run measures a fresh compile/dispatch pair)
# tpu-lint: disable-file=retrace-hazard
import sys, time
sys.path.insert(0, "/root/repo")
import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.grow import GrowParams
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.grow_depthwise import grow_tree_depthwise

F, B = 28, 64


def make_gp(L):
    return GrowParams(num_leaves=L, max_bin=B,
                      split=SplitParams(min_data_in_leaf=20),
                      hist_impl="auto", quant=True, const_hess=False)


def step_time_ms(n, L, K=8, grow_only=False):
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B, size=(n, F), dtype=np.uint8))
    num_bins = jnp.full(F, B, jnp.int32)
    na_bin = jnp.full(F, B + 1, jnp.int32)
    label = jnp.asarray(rng.randint(0, 2, n).astype(np.float32))
    fmask = jnp.ones(F, bool)
    gp = make_gp(L)
    ones = jnp.ones(n, jnp.float32)

    def body(i, s):
        if grow_only:
            g = s * 1e-9 + 0.25
            h = ones * 0.25
        else:
            p = 1.0 / (1.0 + jnp.exp(-s))
            g = p - label
            h = jnp.maximum(p * (1.0 - p), 1e-15)
        tree, leaf_id = grow_tree_depthwise(bins, g, h, ones, num_bins,
                                            na_bin, fmask, gp, qseed=i)
        return s + 0.1 * tree.leaf_value[leaf_id]

    f1 = jax.jit(lambda s: jax.lax.fori_loop(0, 1, body, s))
    fK = jax.jit(lambda s: jax.lax.fori_loop(0, K, body, s))
    s0 = jnp.zeros(n, jnp.float32)
    jax.block_until_ready(f1(s0))
    jax.block_until_ready(fK(s0))
    best = 1e9
    for _ in range(3):
        t0 = time.time(); jax.block_until_ready(f1(s0)); t1 = time.time() - t0
        t0 = time.time(); jax.block_until_ready(fK(s0)); tK = time.time() - t0
        best = min(best, (tK - t1) / (K - 1))
    return best * 1000.0


if __name__ == "__main__":
    L = int(sys.argv[1]) if len(sys.argv) > 1 else 255
    print(f"L={L} (production-like quant path)")
    times = {}
    for n in (131_072, 1_048_576, 4_194_304):
        ms = step_time_ms(n, L)
        times[n] = ms
        print(f"  N={n:>9,}: {ms:8.2f} ms/step")
    ns = sorted(times)
    a = (times[ns[-1]] - times[ns[0]]) / (ns[-1] - ns[0])
    b = times[ns[0]] - a * ns[0]
    print(f"  fit: {a*1e6:.2f} ms/M rows, intercept (glue) = {b:.1f} ms")
    g = step_time_ms(ns[0], L, grow_only=True)
    print(f"  grower-only at N={ns[0]:,}: {g:.2f} ms "
          f"(step-minus-grow = {times[ns[0]] - g:.2f} ms of gradient+score)")
    for Ls in (7, 31):
        ms = step_time_ms(ns[0], Ls)
        print(f"  N={ns[0]:,} L={Ls}: {ms:.2f} ms")
