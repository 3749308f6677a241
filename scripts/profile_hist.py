"""Profile histogram / grower components at bench shapes on the real TPU."""
import time
import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.grow import GrowParams
from lightgbm_tpu.ops.split import SplitParams, best_split
from lightgbm_tpu.ops.grow_depthwise import grow_tree_depthwise

N, F, B, L = 1_000_000, 28, 64, 255
rng = np.random.RandomState(0)
bins = jnp.asarray(rng.randint(0, 63, size=(N, F)).astype(np.uint8))
g = jnp.asarray(rng.randn(N).astype(np.float32))
h = jnp.asarray(rng.rand(N).astype(np.float32))
c = jnp.ones(N, jnp.float32)
leaf_id = jnp.asarray(rng.randint(0, L, size=N).astype(np.int32))
num_bins = jnp.full(F, 63, jnp.int32)
na_bin = jnp.full(F, 256, jnp.int32)
fmask = jnp.ones(F, bool)


def bench(name, fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.time() - t0) / iters
    print(f"{name:40s} {dt*1000:9.2f} ms")
    return dt


f_hist = jax.jit(lambda: H.hist_leaf_onehot(bins, g, h, c, B))
bench("hist_leaf_onehot (root pass)", f_hist)

for S in (2, 8, 32, 128):
    tables = H.RouteTables(
        feat=jnp.zeros(L, jnp.int32), thr=jnp.full(L, 31, jnp.int32),
        dleft=jnp.zeros(L, jnp.int32), new_leaf=jnp.arange(L, dtype=jnp.int32),
        slot_left=jnp.zeros(L, jnp.int32) % S,
        slot_right=jnp.ones(L, jnp.int32) % S)
    f_r = jax.jit(lambda t=tables, s=S: H.hist_routed_onehot(
        bins, g, h, c, leaf_id, t, na_bin, s, B))
    bench(f"hist_routed_onehot S={S}", f_r)

hist = jnp.asarray(rng.randn(L, F, B, 3).astype(np.float32))
sp = SplitParams(min_data_in_leaf=20)
f_bs = jax.jit(lambda: jax.vmap(lambda hh, g_, h_, c_: best_split(
    hh, num_bins, na_bin, g_, h_, c_, fmask, sp, True))(
    hist, hist[:, 0, :, 0].sum(1), jnp.abs(hist[:, 0, :, 1].sum(1)) + 1,
    jnp.abs(hist[:, 0, :, 2].sum(1)) + 40))
bench("best_split vmap L=255", f_bs)

gp = GrowParams(num_leaves=L, max_bin=B, split=sp, hist_impl="onehot")
f_grow = jax.jit(lambda: grow_tree_depthwise(bins, g, h, c, num_bins, na_bin,
                                             fmask, gp))
t0 = time.time()
out = f_grow()
jax.block_until_ready(out)
print(f"grow compile+first: {time.time()-t0:.1f}s")
bench("grow_tree_depthwise full", f_grow, iters=3)
