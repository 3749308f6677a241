"""Synthetic wide dense data from ``--seed``, made on the device.

``benchmark/data.py`` for a matrix of thousands of columns: the same
stream of blocks from ``fold_in(key, block)``, the same interaction terms on
columns 8 to 10, but the linear part is ``x . w`` over a ``w`` as wide as the
matrix, and a block is 32,768 rows (262 MB of float32 at 2,000 columns; a
block of ``data.BLOCK_ROWS`` rows would be 8.4 GB).

``w`` is written by a rule, not listed: every ``every``-th column from
``first`` on is informative, with the weight ``weights[i % len(weights)]``
for the i-th of them, so that each 32-column feature group of the grouped
histogram kernel (and the tail group, columns 1,984 to 1,999 at 2,000) holds
two informative columns of different strength.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.data import VALID_STREAM, n_blocks, seed_key   # noqa: F401

BLOCK_ROWS = 1 << 15


def weights(cfg):
    """The signal's ``w`` [F] float32 from the configuration's rule."""
    s = cfg["signal"]
    w = np.zeros(int(cfg["num_features"]), np.float32)
    cols = np.arange(int(s["first"]), len(w), int(s["every"]))
    w[cols] = np.resize(np.asarray(s["weights"], np.float32), len(cols))
    return w


@partial(jax.jit, static_argnames=("rows", "features", "signal"))
def _block(key, block, w, rows, features, signal):
    lin, ab, sq, off = signal
    kx, ky = jax.random.split(jax.random.fold_in(key, block))
    x = jax.random.normal(kx, (rows, features), jnp.float32)
    # elementwise and a row sum, not a matmul: float32 on every backend
    logit = (off + lin * jnp.sum(x * w[None, :], axis=1)
             + ab * jnp.abs(x[:, 8]) * x[:, 9] + sq * x[:, 10] ** 2)
    y = (jax.random.uniform(ky, (rows,), jnp.float32)
         < jax.nn.sigmoid(logit)).astype(jnp.float32)
    return x, y


def device_block(key, block, cfg, rows=BLOCK_ROWS):
    """(x [rows, F] f32, y [rows] f32) on the device for one block id."""
    s = cfg["signal"]
    return _block(key, block, jnp.asarray(weights(cfg)), rows,
                  int(cfg["num_features"]),
                  (float(s["linear_scale"]), float(s["abs_cross"]),
                   float(s["square"]), float(s["offset"])))


def to_host(key, cfg, n_rows, first_block=0, rows=BLOCK_ROWS, threads=6,
            out=None):
    """As ``data.to_host``: the first ``n_rows`` rows of the stream as
    C-contiguous host arrays, each block copied straight into its slice."""
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
