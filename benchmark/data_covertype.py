"""Synthetic Covertype-shaped data from ``--seed``, made on the device.

``benchmark/data.py`` for a seven-class table with one-hot groups: the same
stream of blocks from ``fold_in(key, block)``, ten float32 numeric columns,
then one wilderness area of four and one soil type of forty, each written as
exclusive 0/1 columns (UCI Covertype's layout: 10 + 4 + 40 = 54), and a
label drawn from a softmax over seven class logits.

The configuration's ``signal`` holds every constant; the tables are written
by a rule, not listed (k the class, j a numeric column, w the wilderness
area, s the soil type; all angles in radians):

    x[:, j]  ~ N(0, 1)                                  j = 0 .. 9
    P(w)     = wilderness_share[w]
    P(s)     proportional to 1 / (s + soil_shift)
    logit_k  = offset[k]
             + linear_scale * sum_j sin(1 + 1.3 k + 0.7 j + 0.37 k j) x[:, j]
             + cross_scale * cos(0.9 k) * |x[:, 0]| * x[:, 1]
             + wilderness_scale * sin(2 + 0.9 k + 1.9 w)
             + soil_scale * sin(3 + 0.5 k + 0.61 s + 0.11 k s)
    y        ~ Categorical(softmax(logit))

(w, s and y each by the inverse CDF of one uniform a row).

``offset`` was solved once so that the seven classes come out at the
published shares (36.5 / 48.8 / 6.2 / 0.5 / 1.6 / 3.0 / 3.5 %); for another
``num_class`` (the tests') the list is cycled to that length.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.data import VALID_STREAM, n_blocks, seed_key   # noqa: F401

BLOCK_ROWS = 1 << 20
NUMERIC, WILDERNESS, SOIL = 10, 4, 40


def tables(cfg):
    """The signal's tables as float32 numpy arrays: linear [K, 10], cross
    [K], wilderness [K, 4], soil [K, 40], offset [K], and the two groups'
    log shares."""
    s = cfg["signal"]
    classes = int(cfg["params"]["num_class"])
    k = np.arange(classes, dtype=np.float64)[:, None]
    j = np.arange(NUMERIC, dtype=np.float64)[None, :]
    w = np.arange(WILDERNESS, dtype=np.float64)[None, :]
    t = np.arange(SOIL, dtype=np.float64)[None, :]
    soil_p = 1.0 / (t[0] + float(s["soil_shift"]))
    out = {
        "linear": float(s["linear_scale"])
        * np.sin(1.0 + 1.3 * k + 0.7 * j + 0.37 * k * j),
        "cross": float(s["cross_scale"]) * np.cos(0.9 * k[:, 0]),
        "wilderness": float(s["wilderness_scale"])
        * np.sin(2.0 + 0.9 * k + 1.9 * w),
        "soil": float(s["soil_scale"])
        * np.sin(3.0 + 0.5 * k + 0.61 * t + 0.11 * k * t),
        "offset": np.resize(np.asarray(s["offset"], np.float64), classes),
        "log_wilderness": np.log(np.asarray(s["wilderness_share"])),
        "log_soil": np.log(soil_p / soil_p.sum()),
    }
    return {name: np.asarray(v, np.float32) for name, v in out.items()}


def _draw(u, p):
    """Inverse-CDF draw from the shares ``p`` [rows or 1, n] with one uniform
    a row: the number of cumulative shares below it."""
    cdf = jnp.cumsum(p, axis=-1)[..., :-1]
    return jnp.sum(u[:, None] > cdf, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("rows",))
def _block(key, block, tab, rows):
    kx, ku = jax.random.split(jax.random.fold_in(key, block))
    x = jax.random.normal(kx, (rows, NUMERIC), jnp.float32)
    u = jax.random.uniform(ku, (3, rows), jnp.float32)
    w = _draw(u[0], jnp.exp(tab["log_wilderness"])[None, :])
    s = _draw(u[1], jnp.exp(tab["log_soil"])[None, :])
    # elementwise and row sums, no matmul: float32 on every backend
    logit = (tab["offset"][None, :]
             + jnp.sum(x[:, None, :] * tab["linear"][None, :, :], axis=2)
             + tab["cross"][None, :] * (jnp.abs(x[:, 0]) * x[:, 1])[:, None]
             + tab["wilderness"].T[w] + tab["soil"].T[s])
    y = _draw(u[2], jax.nn.softmax(logit, axis=1)).astype(jnp.float32)
    wild = (w[:, None] == jnp.arange(WILDERNESS)[None, :])
    soil = (s[:, None] == jnp.arange(SOIL)[None, :])
    cols = jnp.concatenate([x, wild.astype(jnp.float32),
                            soil.astype(jnp.float32)], axis=1)
    return cols, y


def device_block(key, block, cfg, rows=BLOCK_ROWS):
    """(x [rows, 54] f32, y [rows] f32: the class as a float, as
    ``lgb.Dataset`` takes labels) on the device for one block id."""
    if int(cfg["num_features"]) != NUMERIC + WILDERNESS + SOIL:
        raise ValueError("data_covertype makes 54 columns")
    tab = {k: jnp.asarray(v) for k, v in tables(cfg).items()}
    return _block(key, block, tab, rows)


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
