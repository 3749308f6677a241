"""Small-table gather dispatch.

``table[idx]`` with idx [N] and a small [L] table is the score-update hot op
(reference: ScoreUpdater::AddScore's leaf-value add, score_updater.hpp:58).
XLA's TPU lowering is a per-element dynamic-slice (~7ms per 1M rows measured
on v5e); the Pallas one-hot contraction (pallas_hist.take_small_pallas) is
sub-ms. CPU keeps the native gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def take_small(table: jnp.ndarray, idx: jnp.ndarray,
               scope: str = None) -> jnp.ndarray:
    """table [L] f32, idx [N] i32 -> [N] f32 (out-of-range -> 0). ``scope``
    names the device scope of an eager call (see take_small_pallas)."""
    if jax.default_backend() == "tpu" and table.ndim == 1 \
            and table.shape[0] <= 4096:
        from .pallas_hist import take_small_pallas
        return take_small_pallas(table, idx,
                                 scope=scope).astype(table.dtype)
    return jnp.take(table, idx, mode="fill", fill_value=0)
