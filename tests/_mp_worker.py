"""Worker for test_multiprocess.py — runs as one of two jax.distributed
processes. See that file for what is being asserted."""
import hashlib
import os
import sys

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# force the CPU backend (as tests/conftest.py does) and pick gloo so the CPU
# client federates across the two processes
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, "/root/repo")

from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.binning import bin_data  # noqa: E402
from lightgbm_tpu.io.parser import load_file  # noqa: E402
from lightgbm_tpu.ops.grow import GrowParams  # noqa: E402
from lightgbm_tpu.ops.split import SplitParams  # noqa: E402
from lightgbm_tpu.parallel.data_parallel import grow_tree_dp  # noqa: E402
from lightgbm_tpu.parallel.dist_data import (_encode_mapper,  # noqa: E402
                                             find_bin_mappers_distributed,
                                             round_robin_rows)
from lightgbm_tpu.parallel.mesh import init_distributed  # noqa: E402


def _digest(arrs) -> np.ndarray:
    h = hashlib.sha256()
    for a in arrs:
        h.update(np.ascontiguousarray(a).tobytes())
    return np.frombuffer(h.digest()[:8], dtype=np.int64).astype(np.float64)


def main():
    port, data_path = sys.argv[1], sys.argv[2]
    conf = Config({"num_machines": 2,
                   "machines": f"127.0.0.1:{port},127.0.0.1:0"})
    init_distributed(conf)
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()
    from jax.experimental import multihost_utils

    # ---- distributed load: round-robin row share of the same file ----
    pf = load_file(data_path)
    keep = round_robin_rows(pf.X.shape[0], rank, 2)
    Xl = pf.X[keep]
    yl = pf.label[keep]

    # ---- distributed bin finding + mapper equality across ranks ----
    MAXB = 16
    mappers = find_bin_mappers_distributed(Xl, max_bin=MAXB, sample_cnt=50000)
    enc = np.stack([_encode_mapper(m, MAXB + 12) for m in mappers])
    digests = np.asarray(multihost_utils.process_allgather(_digest([enc])))
    assert digests.shape[0] >= 2 and np.all(digests == digests[0]), \
        f"mappers diverge: {digests}"

    # ---- distributed EFB: identical bundle plans from GLOBAL counts ----
    # 3 groups of 3 mutually-exclusive sparse features; each rank holds a
    # different row shard, so rank-local conflict counts WOULD diverge —
    # the reduce_fn path must still produce identical BundleMeta
    rngE = np.random.RandomState(7)
    nE, gE = 4000, 3
    XE_full = np.zeros((nE, 3 * gE))
    for gset in range(gE):
        pick = rngE.randint(0, 3, nE)
        XE_full[np.arange(nE), gset * 3 + pick] = rngE.rand(nE) + 0.5
    XE = XE_full[round_robin_rows(nE, rank, 2)]
    mappersE = find_bin_mappers_distributed(XE, max_bin=16, sample_cnt=50000)
    binnedE = bin_data(XE, mappersE)
    from lightgbm_tpu.efb import plan_bundles

    def _reduce(arr):
        return np.asarray(multihost_utils.process_allgather(
            jnp.asarray(arr))).sum(axis=0)

    meta = plan_bundles(binnedE.bins, binnedE.mappers,
                        max_conflict_rate=0.0, sparse_threshold=0.5,
                        reduce_fn=_reduce)
    assert meta is not None, "exclusive sparse features should bundle"
    md = _digest([meta.num_bins, meta.range_start, meta.range_end,
                  np.asarray([len(m) for m in meta.members]),
                  np.asarray([j for m in meta.members for j, _, _ in m])])
    mds = np.asarray(multihost_utils.process_allgather(md))
    assert np.all(mds == mds[0]), f"bundle plans diverge across ranks: {mds}"

    # ---- one data-parallel training step over the global 2-process mesh ----
    binned = bin_data(Xl, mappers)
    n_all = np.asarray(multihost_utils.process_allgather(
        np.asarray([binned.bins.shape[0]], np.int64)))
    n_eq = int(n_all.max())
    pad = n_eq - binned.bins.shape[0]
    bins_l = np.pad(binned.bins, ((0, pad), (0, 0)))
    y_l = np.pad(np.asarray(yl), (0, pad))
    mask_l = np.pad(np.ones(binned.bins.shape[0], np.float32), (0, pad))

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("data",))
    row = NamedSharding(mesh, P("data"))
    mat = NamedSharding(mesh, P("data", None))
    bins_g = jax.make_array_from_process_local_data(mat, bins_l)
    # binary-objective gradients at score 0 (p = 0.5): g = 0.5 - y, h = 0.25
    g_g = jax.make_array_from_process_local_data(
        row, ((0.5 - y_l) * mask_l).astype(np.float32))
    h_g = jax.make_array_from_process_local_data(
        row, (0.25 * mask_l).astype(np.float32))
    c_g = jax.make_array_from_process_local_data(row, mask_l)

    f = bins_l.shape[1]
    num_bins = jnp.asarray([m.num_bins for m in binned.mappers],
                           dtype=jnp.int32)
    na = np.asarray([m.na_bin for m in binned.mappers], np.int32)
    na_bin = jnp.asarray(np.where(na < 0, 256, na).astype(np.int32))
    fmask = jnp.ones(f, dtype=bool)
    gp = GrowParams(num_leaves=8, max_bin=MAXB,
                    split=SplitParams(min_data_in_leaf=5),
                    hist_impl="scatter")
    tree, leaf_id = grow_tree_dp(bins_g, g_g, h_g, c_g, num_bins, na_bin,
                                 fmask, gp, mesh)
    nl = int(np.asarray(tree.num_leaves))
    assert nl > 1, "tree did not split"
    td = _digest([np.asarray(tree.split_feature),
                  np.asarray(tree.threshold_bin),
                  np.asarray(tree.leaf_value)])
    tds = np.asarray(multihost_utils.process_allgather(td))
    assert np.all(tds == tds[0]), f"trees diverge across ranks: {tds}"

    # ---- three FULL boosting iterations: grads -> dp tree -> score update,
    # all on global cross-process arrays; every rank must hold the same
    # replicated trees and the training loss must fall ----
    from lightgbm_tpu.ops.gather import take_small
    y_g = jax.make_array_from_process_local_data(
        row, (y_l * mask_l).astype(np.float32))
    m_g = c_g
    shrink = 0.5

    @jax.jit
    def boost_iter(score, yv, mv, bg):
        # global arrays must be ARGUMENTS (closing over non-addressable
        # cross-process arrays is rejected by jax)
        p = jax.nn.sigmoid(score)
        g = (p - yv) * mv
        h = jnp.maximum(p * (1 - p), 1e-6) * mv
        tree, leaf_id = grow_tree_dp(bg, g, h, mv, num_bins, na_bin,
                                     fmask, gp, mesh)
        delta = take_small(tree.leaf_value * shrink, leaf_id)
        ll = -jnp.sum(mv * (yv * jnp.log(p + 1e-9)
                            + (1 - yv) * jnp.log(1 - p + 1e-9)))
        return score + delta, tree, ll

    score = jax.jit(
        lambda m: m * 0.0,
        out_shardings=row)(m_g)
    lls = []
    tree_digests = []
    for _ in range(3):
        score, tr, ll = boost_iter(score, y_g, m_g, bins_g)
        lls.append(float(np.asarray(
            multihost_utils.process_allgather(ll, tiled=True)).ravel()[0]))
        tree_digests.append(_digest([
            np.asarray(multihost_utils.process_allgather(
                tr.split_feature, tiled=True))[: gp.num_leaves - 1],
            np.asarray(multihost_utils.process_allgather(
                tr.leaf_value, tiled=True))[: gp.num_leaves]]))
    assert lls[-1] < lls[0], f"training loss did not fall: {lls}"
    all_td = np.asarray(multihost_utils.process_allgather(
        np.concatenate(tree_digests)))
    assert np.all(all_td == all_td[0]), "iteration trees diverge across ranks"

    print(f"MP_WORKER_OK rank={rank} num_leaves={nl} lls={lls}")


if __name__ == "__main__":
    main()
