"""Synthetic HIGGS-shaped data from ``--seed``, made on the device.

One jitted program makes one block of rows from ``fold_in(key, block)``;
the harness copies blocks to the host for ``lgb.Dataset`` and the reference
makes the same blocks again on the device, so neither needs the other's
copy. The same seed gives the same rows whatever the block is used for.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 20
VALID_STREAM = 1 << 24     # block ids of the validation rows start here


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@partial(jax.jit, static_argnames=("rows", "features", "signal"))
def _block(key, block, rows, features, signal):
    w, lin, ab, sq, off = signal
    kx, ky = jax.random.split(jax.random.fold_in(key, block))
    x = jax.random.normal(kx, (rows, features), jnp.float32)
    logit = off + ab * jnp.abs(x[:, 8]) * x[:, 9] + sq * x[:, 10] ** 2
    for j, wj in enumerate(w):
        logit = logit + (lin * wj) * x[:, j]
    y = (jax.random.uniform(ky, (rows,), jnp.float32)
         < jax.nn.sigmoid(logit)).astype(jnp.float32)
    return x, y


def signal_tuple(cfg):
    s = cfg["signal"]
    return (tuple(float(v) for v in s["w"]), float(s["linear_scale"]),
            float(s["abs_cross"]), float(s["square"]), float(s["offset"]))


def device_block(key, block, cfg, rows=BLOCK_ROWS):
    """(x [rows, F] f32, y [rows] f32) on the device for one block id."""
    return _block(key, block, rows, int(cfg["num_features"]),
                  signal_tuple(cfg))


def n_blocks(n_rows, rows=BLOCK_ROWS):
    return -(-n_rows // rows)


def to_host(key, cfg, n_rows, first_block=0, rows=BLOCK_ROWS, threads=6,
            out=None):
    """The first ``n_rows`` rows of the stream starting at ``first_block``
    as C-contiguous host arrays. Each block is made on the device and copied
    straight into its slice of one preallocated buffer (``out`` where the
    caller has one whose pages are touched already; no second copy of the
    matrix; at most ``threads`` blocks alive on the device). The copies run
    on a few threads because the host's first touch of a fresh buffer's
    pages, not the transfer, is what takes the time."""
    from concurrent.futures import ThreadPoolExecutor
    f = int(cfg["num_features"])
    X = np.empty((n_rows, f), np.float32) if out is None else out
    if X.shape != (n_rows, f) or X.dtype != np.float32:
        raise ValueError(f"out is {X.dtype}{X.shape}, not float32{(n_rows, f)}")
    y = np.empty((n_rows,), np.float32)

    def one(b):
        xb, yb = device_block(key, first_block + b, cfg, rows)
        lo = b * rows
        hi = min(n_rows, lo + rows)
        X[lo:hi] = np.asarray(xb)[: hi - lo]
        y[lo:hi] = np.asarray(yb)[: hi - lo]

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(n_blocks(n_rows, rows))))
    return X, y
