"""Device-time profiling with in-jit repetition (subtracts host dispatch latency).

Times op(x) repeated K times inside one jitted fori_loop; device time per op =
(t_K - t_1) / (K - 1).
"""
# profiling harness: building jit wrappers per invocation is the POINT
# (each run measures a fresh compile/dispatch pair)
# tpu-lint: disable-file=retrace-hazard
import sys
sys.path.insert(0, "/root/repo")
import time
import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.split import SplitParams, best_split

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
sp = SplitParams(min_data_in_leaf=20)


def timed_loop(name, op, K=8, reps=3):
    """op: fn(perturb_scalar) -> array; perturb defeats CSE across iterations."""
    def loop(k_static, x0):
        def body(i, acc):
            out = op(acc * 0.0 + 1.0 + i.astype(jnp.float32) * 1e-9)
            return acc + out
        return jax.lax.fori_loop(0, k_static, body, x0)

    f1 = jax.jit(lambda x0: loop(1, x0))
    fK = jax.jit(lambda x0: loop(K, x0))
    x0 = jnp.zeros((), jnp.float32)
    jax.block_until_ready(f1(x0)); jax.block_until_ready(fK(x0))
    t1 = min(
        [-(time.time() - (lambda: (jax.block_until_ready(f1(x0)), time.time())[1])())
         for _ in range(reps)])
    # simpler: measure each
    def t(f):
        best = 1e9
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(f(x0))
            best = min(best, time.time() - t0)
        return best
    t1, tK = t(f1), t(fK)
    per_op = (tK - t1) / (K - 1)
    print(f"{name:42s} {per_op*1000:9.2f} ms/op   (t1={t1*1000:.1f} tK={tK*1000:.1f})")
    return per_op


# root histogram pass
timed_loop("hist_leaf_onehot", lambda s: H.hist_leaf_onehot(
    bins, g * s, h, c, B).sum())

# routed level pass at various S
for S in (2, 8, 32, 128):
    tables = H.RouteTables(
        feat=jnp.zeros(L, jnp.int32), thr=jnp.full(L, 31, jnp.int32),
        dleft=jnp.zeros(L, jnp.int32), new_leaf=jnp.arange(L, dtype=jnp.int32),
        slot_left=jnp.zeros(L, jnp.int32) % S,
        slot_right=jnp.ones(L, jnp.int32) % S)
    timed_loop(f"hist_routed_onehot S={S}",
               lambda s, t=tables, S_=S: H.hist_routed_onehot(
                   bins, g * s, h, c, leaf_id, t, na_bin, S_, B)[0].sum())

# best_split over L leaves
hist = jnp.asarray(rng.rand(L, F, B, 3).astype(np.float32))
pg = hist[:, 0, :, 0].sum(1)
ph = jnp.abs(hist[:, 0, :, 1].sum(1)) + 1
pc = jnp.abs(hist[:, 0, :, 2].sum(1)) + 40
allow = jnp.ones(L, bool)
timed_loop("best_split vmap L=255", lambda s: jax.vmap(
    lambda hh_, g_, h_, c_, a: best_split(hh_, num_bins, na_bin, g_, h_, c_,
                                          fmask, sp, a))(
    hist * s, pg, ph, pc, allow).gain.sum())

# gradient computation (binary objective shape)
score = jnp.zeros(N, jnp.float32)
label = (g > 0).astype(jnp.float32)
def grad_op(s):
    p = 1 / (1 + jnp.exp(-(score + s)))
    return ((p - label) * (p * (1 - p))).sum()
timed_loop("binary gradients 1M", grad_op)

# leaf gather score update
lv = jnp.asarray(rng.randn(L).astype(np.float32))
timed_loop("leaf-gather score update 1M", lambda s: (lv * s)[leaf_id].sum())
