"""The work one boosting iteration needs, from shapes only.

The counts are the same whatever kernel or grower does the work: they are
what a histogram-based GBDT iteration has to read and add up, not the
multiply-accumulates a one-hot MXU contraction happens to issue.

Per iteration, with N rows, F features, ``channels`` gradient channels of
the objective (the configuration's ``grad_channels``: 2 for a per-row
hessian, 1 where it is constant) and
``sweeps = ceil(log2(num_leaves))`` level sweeps:

    hist_ops   = sweeps * N * F * (channels + 1)        adds (+1: the count)
    hist_bytes = sweeps * N * (F + 4 * channels + 4)    u8 bins, f32 gradient
                                                        channels, i32 leaf id
    score_bytes = 8 * N                                 f32 read-modify-write
    valid_bytes = N_valid * (F + 8)                     bins, score RMW
    valid_ops   = sweeps * N_valid                      node visits

Least time is max(ops / int8 peak, bytes / HBM peak).
"""
import math


def sweeps(num_leaves):
    return max(1, math.ceil(math.log2(num_leaves)))


def hist_work(n_rows, n_features, num_leaves, channels):
    s = sweeps(num_leaves)
    return {"ops": s * n_rows * n_features * (channels + 1),
            "bytes": s * n_rows * (n_features + 4 * channels + 4)}


def iteration_work(n_rows, n_features, num_leaves, channels, n_valid=0):
    h = hist_work(n_rows, n_features, num_leaves, channels)
    return {"ops": h["ops"] + sweeps(num_leaves) * n_valid,
            "bytes": h["bytes"] + 8 * n_rows + n_valid * (n_features + 8)}


def least_seconds(work, peaks):
    """(seconds, which bound binds) for a work dict on a chip's peaks."""
    t_ops = work["ops"] / peaks["int8_ops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops > t_bytes else (t_bytes, "bytes")
