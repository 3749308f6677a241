"""Micro-benchmark: best_split over [L,F,B,3] (vmap) vs channel-separated layout."""
import sys
sys.path.insert(0, "/root/repo")
import time
import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.split import SplitParams, best_split, leaf_split_gain, NEG_INF

L, F, B = 255, 28, 64
rng = np.random.RandomState(0)
hist = jnp.asarray(rng.rand(L, F, B, 3).astype(np.float32))
hg = jnp.asarray(np.ascontiguousarray(np.asarray(hist)[..., 0]))
hh = jnp.asarray(np.ascontiguousarray(np.asarray(hist)[..., 1]))
hc = jnp.asarray(np.ascontiguousarray(np.asarray(hist)[..., 2]))
num_bins = jnp.full(F, 63, jnp.int32)
na_bin = jnp.full(F, 256, jnp.int32)
fmask = jnp.ones(F, bool)
pg = jnp.asarray(np.asarray(hist)[:, 0, :, 0].sum(1))
ph = jnp.asarray(np.abs(np.asarray(hist)[:, 0, :, 1].sum(1)) + 1)
pc = jnp.asarray(np.abs(np.asarray(hist)[:, 0, :, 2].sum(1)) + 40)
allow = jnp.ones(L, bool)
p = SplitParams(min_data_in_leaf=20)


def bench(name, fn, iters=20):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    print(f"{name:45s} {(time.time()-t0)/iters*1000:9.2f} ms")


f_old = jax.jit(lambda hist, pg, ph, pc: jax.vmap(
    lambda h, g_, h_, c_, a: best_split(h, num_bins, na_bin, g_, h_, c_,
                                        fmask, p, a))(hist, pg, ph, pc, allow))
bench("vmap best_split [L,F,B,3]", lambda: f_old(hist, pg, ph, pc))


def best_split_chan(hg, hh, hc, pg, ph, pc):
    """Batched over leading L, channel-separated [L, F, B] layout."""
    iota = jnp.arange(B, dtype=jnp.int32)[None, None, :]          # [1, 1, B]
    na = na_bin[None, :, None]                                    # [1, F, 1]
    na_sel = iota == na                                           # [1, F, B]
    nag = jnp.sum(jnp.where(na_sel, hg, 0.0), axis=2)             # [L, F]
    nah = jnp.sum(jnp.where(na_sel, hh, 0.0), axis=2)
    nac = jnp.sum(jnp.where(na_sel, hc, 0.0), axis=2)
    cg = jnp.cumsum(jnp.where(na_sel, 0.0, hg), axis=2)           # [L, F, B]
    ch = jnp.cumsum(jnp.where(na_sel, 0.0, hh), axis=2)
    cc = jnp.cumsum(jnp.where(na_sel, 0.0, hc), axis=2)

    tg, th, tc = pg[:, None, None], ph[:, None, None], pc[:, None, None]

    def variant(lg, lh, lc):
        rg, rh, rc = tg - lg, th - lh, tc - lc
        ok = ((lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
              & (lh >= p.min_sum_hessian_in_leaf) & (rh >= p.min_sum_hessian_in_leaf))
        gain = leaf_split_gain(lg, lh, p) + leaf_split_gain(rg, rh, p)
        return jnp.where(ok, gain, NEG_INF)

    gain_r = variant(cg, ch, cc)
    gain_l = variant(cg + nag[:, :, None], ch + nah[:, :, None], cc + nac[:, :, None])
    valid_t = (iota < num_bins[None, :, None] - 1) & (~na_sel) & fmask[None, :, None]
    has_na = na >= 0
    gain_r = jnp.where(valid_t, gain_r, NEG_INF)
    gain_l = jnp.where(valid_t & has_na, gain_l, NEG_INF)
    gains = jnp.concatenate([gain_r.reshape(L, -1), gain_l.reshape(L, -1)], axis=1)
    flat = jnp.argmax(gains, axis=1)
    best_gain = jnp.take_along_axis(gains, flat[:, None], axis=1)[:, 0]
    d = flat // (F * B)
    rem = flat % (F * B)
    feat, tbin = rem // B, rem % B
    lidx = jnp.arange(L)
    lg_sel = cg[lidx, feat, tbin] + jnp.where(d == 1, nag[lidx, feat], 0.0)
    parent_gain = leaf_split_gain(pg, ph, p)
    improvement = best_gain - parent_gain
    found = allow & (best_gain > NEG_INF / 2) & (improvement > 0.0)
    return jnp.where(found, improvement, NEG_INF), feat, tbin, d == 1, lg_sel


f_new = jax.jit(best_split_chan)
bench("channel-separated batched", lambda: f_new(hg, hh, hc, pg, ph, pc))

# equivalence check
old = f_old(hist, pg, ph, pc)
new = f_new(hg, hh, hc, pg, ph, pc)
np.testing.assert_allclose(np.asarray(old.gain), np.asarray(new[0]), rtol=1e-4)
np.testing.assert_array_equal(np.asarray(old.feature), np.asarray(new[1]))
np.testing.assert_array_equal(np.asarray(old.bin), np.asarray(new[2]))
print("equivalent results ok")
