"""Measure the full fused training-step device time via in-jit repetition,
and the per-dispatch host overhead."""
import sys
sys.path.insert(0, "/root/repo")
import time
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
          "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1}
ds = lgb.Dataset(X, label=y, params=params)
ds.construct()

bins = ds.bins
num_bins = ds.num_bins_dev
na_bin = ds.na_bin_dev
label = jnp.asarray(y)
gp = GrowParams(num_leaves=255, max_bin=64,
                split=SplitParams(min_data_in_leaf=20), hist_impl="onehot")
fmask = jnp.ones(ds.num_features, bool)


def train_step(score, i):
    p = 1.0 / (1.0 + jnp.exp(-score))
    g = p - label
    h = jnp.maximum(p * (1.0 - p), 1e-15)
    tree, leaf_id = grow_tree_depthwise(bins, g, h, jnp.ones_like(g),
                                        num_bins, na_bin, fmask, gp)
    return score + 0.1 * tree.leaf_value[leaf_id]


def loop(k, score):
    def body(i, s):
        return train_step(s, i)
    return jax.lax.fori_loop(0, k, body, score)


score0 = jnp.zeros(N, jnp.float32)
f1 = jax.jit(lambda s: loop(1, s))
f8 = jax.jit(lambda s: loop(8, s))
t0 = time.time(); jax.block_until_ready(f1(score0)); print(f"compile f1: {time.time()-t0:.1f}s")
t0 = time.time(); jax.block_until_ready(f8(score0)); print(f"compile f8: {time.time()-t0:.1f}s")


def t(f, reps=3):
    best = 1e9
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(f(score0))
        best = min(best, time.time() - t0)
    return best


t1, t8 = t(f1), t(f8)
print(f"t1={t1*1000:.1f}ms t8={t8*1000:.1f}ms -> device per-step "
      f"{(t8-t1)/7*1000:.1f}ms, overhead {t1*1000 - (t8-t1)/7*1000:.1f}ms")

# dispatch overhead: tiny op, sequential dependent dispatches without sync
tiny = jax.jit(lambda x: x + 1.0)
x = jnp.zeros(8, jnp.float32)
jax.block_until_ready(tiny(x))
t0 = time.time()
for _ in range(50):
    x = tiny(x)
jax.block_until_ready(x)
print(f"tiny chained x50: {(time.time()-t0)/50*1000:.2f} ms/dispatch")

# big-arg dispatch: does passing the 28MB bins array per call cost?
big = jax.jit(lambda b, s: s + b[:, 0].astype(jnp.float32).sum() * 0.0)
s = jnp.zeros((), jnp.float32)
jax.block_until_ready(big(bins, s))
t0 = time.time()
for _ in range(20):
    s = big(bins, s)
jax.block_until_ready(s)
print(f"big-arg chained x20: {(time.time()-t0)/20*1000:.2f} ms/dispatch")
