"""Marginal device cost per depthwise level: grow at max_depth k for several k."""
# profiling harness: building jit wrappers per invocation is the POINT
# (each run measures a fresh compile/dispatch pair)
# tpu-lint: disable-file=retrace-hazard
import sys
sys.path.insert(0, "/root/repo")
import time
from functools import partial
import numpy as np
import jax
import jax.numpy as jnp

from bench import synth_higgs
import lightgbm_tpu as lgb
from lightgbm_tpu.ops.grow import GrowParams
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.grow_depthwise import grow_tree_depthwise

N = 1_000_000
X, y = synth_higgs(N)
params = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
          "verbosity": -1}
ds = lgb.Dataset(X, label=y, params=params)
ds.construct()
bins, num_bins, na_bin = ds.bins, ds.num_bins_dev, ds.na_bin_dev
label = jnp.asarray(y)
fmask = jnp.ones(ds.num_features, bool)
score0 = jnp.zeros(N, jnp.float32)


def step(score, gp):
    p = 1.0 / (1.0 + jnp.exp(-score))
    g = p - label
    h = jnp.maximum(p * (1.0 - p), 1e-15)
    tree, leaf_id = grow_tree_depthwise(bins, g, h, jnp.ones_like(g),
                                        num_bins, na_bin, fmask, gp)
    return score + 0.1 * tree.leaf_value[leaf_id]


def t_of(gp, K=4, reps=3):
    def loop(k, s):
        return jax.lax.fori_loop(0, k, lambda i, ss: step(ss + i * 0.0, gp), s)
    f1 = jax.jit(partial(loop, 1))
    fK = jax.jit(partial(loop, K))
    jax.block_until_ready(f1(score0)); jax.block_until_ready(fK(score0))
    def t(f):
        best = 1e9
        for _ in range(reps):
            t0 = time.time(); jax.block_until_ready(f(score0))
            best = min(best, time.time() - t0)
        return best
    return (t(fK) - t(f1)) / (K - 1)


prev = 0.0
for k in (1, 3, 5, 7, 9, 11):
    gp = GrowParams(num_leaves=255, max_depth=k, max_bin=64,
                    split=SplitParams(min_data_in_leaf=20), hist_impl="onehot")
    dt = t_of(gp)
    print(f"max_depth={k:2d}: {dt*1000:8.1f} ms/step  (marginal {1000*(dt-prev):+.1f})")
    prev = dt
