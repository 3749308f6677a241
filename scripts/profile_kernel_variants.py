"""Micro-profiles of the Pallas histogram kernel at bench scale (real TPU).

Timing methodology: K repetitions inside ONE jit (fori_loop), cost =
(t_K - t_1) / (K - 1) — host dispatch latency cancels out (same
subtraction bench.py's phase breakdown uses).
"""
# profiling harness: building jit wrappers per invocation is the POINT
# (each run measures a fresh compile/dispatch pair)
# tpu-lint: disable-file=retrace-hazard
import sys
sys.path.insert(0, "/root/repo")
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

N, F, B = 10_000_000, 28, 64
rng = np.random.RandomState(0)
bins_T = jax.device_put(rng.randint(0, B, size=(F, N)).astype(np.uint8))
gq = jax.device_put(rng.randint(-127, 128, size=N).astype(np.int8))
hq = jax.device_put(rng.randint(0, 128, size=N).astype(np.int8))
cq = jax.device_put(np.ones(N, np.int8))
gf = jax.device_put(rng.randn(N).astype(np.float32))

from lightgbm_tpu.ops.pallas_hist import hist_pallas_q8, hist_pallas


def t_loop(name, op, *big, K=6):
    def loop(k, x0, *a):
        return jax.lax.fori_loop(
            0, k, lambda i, acc: acc + op(acc * 0 + 1 + i, *a), x0)
    f1 = jax.jit(functools.partial(loop, 1))
    fK = jax.jit(functools.partial(loop, K))
    x0 = jnp.zeros((), jnp.float32)
    jax.block_until_ready(f1(x0, *big)); jax.block_until_ready(fK(x0, *big))
    t0 = time.time(); jax.block_until_ready(f1(x0, *big)); t1 = time.time() - t0
    t0 = time.time(); jax.block_until_ready(fK(x0, *big)); tK = time.time() - t0
    print(f"{name}: {(tK - t1) / (K - 1) * 1000:.2f} ms")


sc = jnp.float32(127.0)
for chunk in (1024, 2048, 4096):
    for S in (1, 8, 32, 128):
        slot = jax.device_put(rng.randint(0, S, size=N).astype(np.int32))
        # s scales gq via int cast to defeat loop-invariant hoisting
        # slot depends on the (traced) loop value via a non-foldable min
        t_loop(f"q8 S={S} chunk={chunk}",
               lambda s, bt, a, b2, c, sl, _S=S, _ck=chunk:
               hist_pallas_q8(bt, a, b2, c,
                              jnp.minimum(sl, s.astype(jnp.int32) + (1 << 30)),
                              _S, B, sc, sc, chunk=_ck)[0].sum(),
               bins_T, gq, hq, cq, slot)

slot0 = jax.device_put(np.zeros(N, np.int32))
t_loop("bf16 S=1 chunk=1024",
       lambda s, bt, g, sl: hist_pallas(bt, g * s, g, g, sl, 1, B)[0].sum(),
       bins_T, gf, slot0)
