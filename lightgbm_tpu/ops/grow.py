"""Jitted tree growing.

TPU-native re-design of the reference's SerialTreeLearner
(src/treelearner/serial_tree_learner.cpp:147-194): leaf-wise (best-first) growth as a
``lax.scan`` over the ``num_leaves - 1`` split steps, entirely on device — zero host
round-trips per tree.

Key departures from the reference (SURVEY.md §7 design stance):
- no DataPartition index reordering (data_partition.hpp:113): a per-row ``leaf_id``
  vector is updated with a vectorized ``where`` on each split;
- the smaller-child histogram is built with a masked full-width pass and the sibling
  recovered by subtraction (the reference's subtraction trick,
  serial_tree_learner.cpp:315-355, kept because it halves histogram work);
- split selection is the vectorized argmax of ops/split.py, not a host-side scan;
- histograms for all live leaves stay resident in HBM ([L, F, B, 3]) — the analog of
  the reference's HistogramPool (feature_histogram.hpp:687) with capacity = num_leaves.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import histogram as H
from .split import NEG_INF, SplitParams, SplitResult, best_split, leaf_output


@dataclass(frozen=True)
class GrowParams:
    num_leaves: int = 31
    max_depth: int = -1
    max_bin: int = 255            # padded bin axis length B
    split: SplitParams = SplitParams()
    hist_impl: str = "auto"
    # int8 quantized-gradient histograms (LightGBM 4.x technique; applies to
    # the depthwise/pallas path — leaf values are renewed from exact sums)
    quant: bool = False
    # constant-hessian channel elision (reference: CONST_HESSIAN OpenCL
    # kernel variants, ocl/histogram256.cl:18-60): rows carry
    # h = h_const * bag01, so the q8 kernels drop the hessian channel and
    # reconstruct it from the count channel — set only by the fused
    # auto-gradient step for IsConstantHessian objectives (never for custom
    # gradients / GOSS-amplified channels, where h varies per row)
    const_hess: bool = False
    # voting-parallel: top-k features elected per level for histogram exchange
    # (reference: VotingParallelTreeLearner, top_k config); 0 = off
    voting_top_k: int = 0
    # per-node feature sampling (reference: feature_fraction_bynode,
    # serial_tree_learner.cpp:397+) — per-LEVEL per-leaf resampling in the
    # depthwise grower; 1.0 = off
    ff_bynode: float = 1.0
    # HistogramPool analog (reference: histogram_pool_size MB bounding the
    # per-leaf histogram cache, feature_histogram.hpp:687): number of cached
    # leaf histograms in the lossguide grower; 0 = unbounded ([L] resident).
    # Evicted parents are rebuilt with one extra masked histogram pass —
    # the reference's pool-miss ConstructHistograms, traded exactly the same
    # way (memory for recompute)
    hist_pool: int = 0
    # lean depthwise mode (histogram_pool_size for the DEPTHWISE grower,
    # VERDICT r3 weak #6): feature-tile width for the pass/search so live
    # histogram memory stays within the pool budget — the [L, 3, F, B]
    # frontier state is replaced by cached per-leaf split records and
    # both-children measurement. 0 = off (whole-frontier state)
    lean_ft: int = 0
    # Data-parallel axis (reference: DataParallelTreeLearner,
    # data_parallel_tree_learner.cpp:149-240). When set, rows are sharded over this
    # mesh axis under shard_map and every histogram / root-sum is psum-ed — the
    # reference's entire ReduceScatter+Allgather machinery (network.cpp) becomes
    # these two collectives; split selection is computed replicated on all shards.
    axis_name: str = ""
    # Optional second mesh axis of a 2-D (data, feature) mesh (reference:
    # VotingParallelTreeLearner's column partition). Rows stay replicated over
    # it; _hist_allreduce slices every histogram psum by feature block so each
    # device's data-axis collective volume drops by feature_shards — the
    # reference's ReduceScatter+Allgather (network.cpp) along the feature dim.
    feature_axis_name: str = ""
    feature_shards: int = 1
    # static spec of a built-in objective whose gradients the depthwise
    # grower recomputes in-register (ObjectiveFunction.fused_grad_spec):
    # ("l2",) or ("logloss", sigmoid, lw_pos, lw_neg). When set, the grower
    # takes fused=(score, aux, bag) row inputs and runs the fused
    # grad+quant+hist0 front instead of reading materialized g/h/c —
    # two fewer full-N HBM round-trips per iteration. None = unfused.
    fused_obj: tuple = None


# device scope of every cross-chip reduction of the growers (the histogram
# of a level, the root's, the exact leaf sums): what a chip spends starting
# and awaiting them reads under this name in a trace
ALLREDUCE_SCOPE = "hist_allreduce"


def _psum(x, gp: "GrowParams"):
    if gp.axis_name:
        with jax.named_scope(ALLREDUCE_SCOPE):
            return jax.lax.psum(x, gp.axis_name)
    return x


def _rows(x):
    """A float32 count channel (of a histogram, of the leaf sums) as the
    int32 row count a tree keeps: float32 holds odd numbers only up to 2^24,
    and a leaf of a table of a hundred million rows can hold more rows than
    that (the model text's ``leaf_count`` was then off by one)."""
    return jnp.round(x).astype(jnp.int32)


def _leaf_sums_allreduce(local, gp: "GrowParams"):
    """A shard's exact per-leaf sums ``[3, L]`` (grad, hess, rows) over all
    shards: (G, H) float32 and the rows int32. The rows cross the chips as
    integers: summed as float32, the shards' counts round where a leaf's
    total is odd and above 2^24. (Within a shard the kernels still count in
    float32: exact while one shard's part of a leaf is under 2^24 rows.)"""
    gh = _psum(local[:2], gp)
    return gh[0], gh[1], _psum(_rows(local[2]), gp)


def _hist_allreduce(hist, gp: "GrowParams", f_dim: int):
    """Allreduce a histogram-shaped array over the data axis.

    On a 1-D mesh this is a plain ``psum``. On a 2-D (data, feature) mesh each
    device first slices its own feature block (``axis_index`` along the
    feature axis), psums ONLY that block over the data axis, then rebuilds the
    full histogram with a tiled ``all_gather`` over the feature axis — the
    per-device data-axis collective shrinks by ``feature_shards`` while the
    result stays bit-identical (psum is elementwise, so psum-of-slice
    concatenated equals the full psum).
    """
    if not gp.axis_name:
        return hist
    fa, k = gp.feature_axis_name, gp.feature_shards
    F = hist.shape[f_dim]
    if not fa or k <= 1 or F % k != 0:
        return _psum(hist, gp)
    blk = F // k
    with jax.named_scope(ALLREDUCE_SCOPE):
        j = jax.lax.axis_index(fa)
        sub = jax.lax.dynamic_slice_in_dim(hist, j * blk, blk, axis=f_dim)
        sub = jax.lax.psum(sub, gp.axis_name)
        return jax.lax.all_gather(sub, fa, axis=f_dim, tiled=True)


class TreeArrays(NamedTuple):
    """Flat-array tree, device-side (reference analog: Tree, tree.h:25).

    Internal node ``i`` is created by split step ``i``; child pointers use the
    reference's encoding: >= 0 -> internal node index, < 0 -> ~leaf_index.
    """
    split_feature: jnp.ndarray   # [L-1] i32
    threshold_bin: jnp.ndarray   # [L-1] i32
    default_left: jnp.ndarray    # [L-1] bool
    left_child: jnp.ndarray      # [L-1] i32
    right_child: jnp.ndarray     # [L-1] i32
    split_gain: jnp.ndarray      # [L-1] f32
    leaf_value: jnp.ndarray      # [L] f32
    leaf_weight: jnp.ndarray     # [L] f32 (sum_hess)
    leaf_count: jnp.ndarray      # [L] i32 (rows; see _rows)
    internal_value: jnp.ndarray  # [L-1] f32
    internal_weight: jnp.ndarray # [L-1] f32
    internal_count: jnp.ndarray  # [L-1] f32
    num_leaves: jnp.ndarray      # scalar i32
    is_cat: jnp.ndarray          # [L-1] bool: categorical subset split
    cat_mask: jnp.ndarray        # [L-1, B] bool: bins routed LEFT (cat nodes)


class _GrowState(NamedTuple):
    leaf_id: jnp.ndarray         # [N] i32
    hist: jnp.ndarray            # [P, 3, F, B] (P = L unless gp.hist_pool)
    slot_of_leaf: jnp.ndarray    # [L] i32 pool slot per leaf (-1 evicted);
                                 # [1] dummy when unpooled
    leaf_of_slot: jnp.ndarray    # [P] i32 (or [1] dummy)
    slot_age: jnp.ndarray        # [P] i32 last-write step (LRU; [1] dummy)
    leaf_g: jnp.ndarray          # [L]
    leaf_h: jnp.ndarray
    leaf_cnt: jnp.ndarray
    leaf_depth: jnp.ndarray      # [L] i32
    parent_node: jnp.ndarray     # [L] i32: node whose child slot points at leaf
    parent_right: jnp.ndarray    # [L] bool
    leaf_min: jnp.ndarray        # [L] monotone output bounds
    leaf_max: jnp.ndarray
    forced_ptr: jnp.ndarray      # [L] i32: forced node to apply (-1 none)
    best: SplitResult            # arrays [L]
    tree: TreeArrays
    done: jnp.ndarray            # scalar bool


def _empty_tree(L: int, B: int = 256) -> TreeArrays:
    zi = jnp.zeros(max(L - 1, 1), dtype=jnp.int32)
    zf = jnp.zeros(max(L - 1, 1), dtype=jnp.float32)
    return TreeArrays(
        split_feature=zi, threshold_bin=zi, default_left=jnp.zeros_like(zi, dtype=bool),
        left_child=zi, right_child=zi, split_gain=zf,
        leaf_value=jnp.zeros(L, jnp.float32), leaf_weight=jnp.zeros(L, jnp.float32),
        leaf_count=jnp.zeros(L, jnp.int32),
        internal_value=zf, internal_weight=zf, internal_count=zf,
        num_leaves=jnp.int32(1),
        is_cat=jnp.zeros(max(L - 1, 1), dtype=bool),
        cat_mask=jnp.zeros((max(L - 1, 1), B), dtype=bool),
    )


def _allow_depth(depth, gp: GrowParams):
    if gp.max_depth > 0:
        return depth < gp.max_depth
    return jnp.ones_like(depth, dtype=bool) if hasattr(depth, "shape") else True


@partial(jax.jit, static_argnames=("gp",))
def grow_tree(bins: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray, c: jnp.ndarray,
              num_bins: jnp.ndarray, na_bin: jnp.ndarray,
              feature_mask: jnp.ndarray, gp: GrowParams, bundle=None,
              forced=None, qseed=None, bins_T=None
              ) -> Tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree.

    bins: [N, F] uint8; g/h/c: [N] f32 grad/hess/in-bag-count channels (already
    bag-masked) — bagging is mask-based (reference uses index subsets,
    gbdt.cpp:160-276; masks keep shapes static on TPU), and the channels are
    separate 1-D arrays because an [N, 3] array tiles with 42x lane padding on
    TPU; feature_mask: [F] bool (per-tree feature_fraction sample).

    Returns (TreeArrays, leaf_id [N] i32). leaf_id routes *all* rows (including
    out-of-bag) so the caller can update train scores by a single gather.

    ``forced`` (a grow_depthwise.ForcedSplits) applies the forced-splits
    tree leaf-wise: a leaf holding a forced-node pointer splits on that
    (feature, bin) with gain overridden high, mirroring the reference's
    ForceSplits-before-normal-growth (serial_tree_learner.cpp:456-618).
    Forced mode keeps the full [L] histogram state (the pool's evicted
    parents could not provide the forced split's cumsum). ``qseed`` drives
    per-node feature sampling when gp.ff_bynode < 1. ``bins_T`` and the
    length of the returned ``leaf_id``: as grow_tree_depthwise.
    """
    n, f = bins.shape
    L, B = gp.num_leaves, gp.max_bin
    sp = gp.split

    def _node_mask(tag, base_mask):
        """feature_fraction_bynode: Bernoulli keep within the usable set,
        best-u always kept so no node searches nothing (same scheme as the
        depthwise grower, keyed on (tree seed, split index))."""
        if gp.ff_bynode >= 1.0:
            return base_mask
        seed_base = qseed if qseed is not None else jnp.int32(0)
        key = jax.random.fold_in(jax.random.PRNGKey(seed_base), tag)
        u = jax.random.uniform(key, base_mask.shape)
        u_allowed = jnp.where(base_mask, u, -1.0)
        best_u = u_allowed >= u_allowed.max(axis=-1, keepdims=True)
        return base_mask & ((u < gp.ff_bynode) | best_u)

    def _et_key(tag):
        """extra_trees rand-threshold key per split search (reference:
        per-search rand_threshold, feature_histogram.hpp:99-102)."""
        if not sp.extra_trees:
            return None
        base = qseed if qseed is not None else jnp.int32(0)
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(sp.extra_seed), base), tag)

    # pallas kernels read a transposed bin matrix: use the Dataset's cached
    # device-resident copy when the caller passes one (no per-tree N*F HBM
    # transpose), else build it once per tree (XLA CSEs it across all
    # histogram passes inside this jit)
    if H.pick_impl(gp.hist_impl) != "pallas":
        bins_T = None
    elif bins_T is None:
        with jax.named_scope("bins_T"):
            bins_T = bins.T
    g, h, c, _, leaf_id = H.resident_rows(bins_T, n, L, g, h, c)
    hist0 = _psum(H.hist_leaf(bins, g, h, c, B, gp.hist_impl, bins_T=bins_T),
                  gp)                                                  # [3, F, B]
    g0, h0, c0 = hist0[0, 0].sum(), hist0[1, 0].sum(), hist0[2, 0].sum()

    best0 = best_split(hist0, num_bins, na_bin, g0, h0, c0,
                       _node_mask(L, feature_mask), sp,   # tag L: root (child
                       # tags are the split steps 0..L-2; fold_in rejects -1)
                       allow_split=_allow_depth(jnp.int32(0), gp) if gp.max_depth > 0 else True,
                       bundle=bundle, rand_key=_et_key(L))

    def tile(x, fill):
        return jnp.full((L,), fill, dtype=x.dtype).at[0].set(x)

    best = SplitResult(
        gain=tile(best0.gain, NEG_INF), feature=tile(best0.feature, 0),
        bin=tile(best0.bin, 0), default_left=tile(best0.default_left, False),
        left_g=tile(best0.left_g, 0.0), left_h=tile(best0.left_h, 0.0),
        left_cnt=tile(best0.left_cnt, 0.0),
        is_cat=tile(best0.is_cat, False),
        cat_member=jnp.zeros((L, B), dtype=bool).at[0].set(best0.cat_member))

    # HistogramPool (reference: feature_histogram.hpp:687): cap the cached
    # leaf histograms at P slots; evicted parents rebuild with a masked pass.
    # Forced mode keeps everything resident (see docstring)
    P = gp.hist_pool if 0 < gp.hist_pool < L and forced is None else L
    pooled = P < L
    hist = jnp.zeros((P, 3, f, B), dtype=jnp.float32).at[0].set(hist0)
    if pooled:
        slot_of_leaf = jnp.full(L, -1, jnp.int32).at[0].set(0)
        leaf_of_slot = jnp.full(P, -1, jnp.int32).at[0].set(0)
        slot_age = jnp.zeros(P, jnp.int32)
    else:
        slot_of_leaf = jnp.zeros(1, jnp.int32)
        leaf_of_slot = jnp.zeros(1, jnp.int32)
        slot_age = jnp.zeros(1, jnp.int32)
    state = _GrowState(
        leaf_id=leaf_id, hist=hist,
        slot_of_leaf=slot_of_leaf, leaf_of_slot=leaf_of_slot,
        slot_age=slot_age,
        leaf_g=jnp.zeros(L).at[0].set(g0),
        leaf_h=jnp.zeros(L).at[0].set(h0),
        leaf_cnt=jnp.zeros(L).at[0].set(c0),
        leaf_depth=jnp.zeros(L, jnp.int32),
        parent_node=jnp.full(L, -1, jnp.int32),
        parent_right=jnp.zeros(L, dtype=bool),
        leaf_min=jnp.full(L, -jnp.inf),
        leaf_max=jnp.full(L, jnp.inf),
        forced_ptr=jnp.full(L, -1, jnp.int32).at[0].set(
            0 if forced is not None else -1),
        best=best, tree=_empty_tree(L, B), done=jnp.bool_(L < 2),
    )

    def step(st: _GrowState, t):
        best_eff = st.best
        if forced is not None:
            # leaf-wise ForceSplits: leaves holding a forced-node pointer get
            # their gain overridden high so argmax picks the lowest such leaf
            # first; left stats come from the leaf histogram's cumsum at the
            # forced bin (na bin excluded), exactly like the depthwise grower
            fp = jnp.maximum(st.forced_ptr, 0)
            has_f = st.forced_ptr >= 0
            ffeat = forced.feat[fp]                          # [L]
            fbin = forced.bin[fp]
            iota_bf = jnp.arange(B, dtype=jnp.int32)[None, None, :]
            na_self = iota_bf == na_bin[None, :, None]       # [1, F, B]
            cumf = jnp.cumsum(jnp.where(na_self[:, None], 0.0, st.hist),
                              axis=-1)                       # [L, 3, F, B]
            lidx2 = jnp.arange(L)
            flg = cumf[lidx2, 0, ffeat, fbin]
            flh = cumf[lidx2, 1, ffeat, fbin]
            flc = cumf[lidx2, 2, ffeat, fbin]
            okf = has_f & (flc >= 1) & (st.leaf_cnt - flc >= 1)
            big = jnp.float32(1e30)
            best_eff = st.best._replace(
                gain=jnp.where(okf, big, st.best.gain),
                feature=jnp.where(okf, ffeat, st.best.feature),
                bin=jnp.where(okf, fbin, st.best.bin),
                default_left=jnp.where(okf, False, st.best.default_left),
                left_g=jnp.where(okf, flg, st.best.left_g),
                left_h=jnp.where(okf, flh, st.best.left_h),
                left_cnt=jnp.where(okf, flc, st.best.left_cnt),
                is_cat=jnp.where(okf, False, st.best.is_cat),
                cat_member=jnp.where(okf[:, None], False,
                                     st.best.cat_member))
            # degenerate forced splits stop forcing at that leaf
            st = st._replace(forced_ptr=jnp.where(has_f & ~okf, -1,
                                                  st.forced_ptr))
        l = jnp.argmax(best_eff.gain).astype(jnp.int32)
        ok = (best_eff.gain[l] > NEG_INF / 2) & (~st.done)

        def do_split(st: _GrowState) -> _GrowState:
            new_leaf = t + 1
            feat = best_eff.feature[l]
            thr = best_eff.bin[l]
            dleft = best_eff.default_left[l]

            # ---- partition rows (reference: DataPartition::Split,
            # data_partition.hpp:113 — here a vectorized where on leaf_id) ----
            # a row of the transposed matrix where there is one: as long as
            # leaf_id, and read in place
            col = (bins[:, feat] if bins_T is None
                   else bins_T[feat]).astype(jnp.int32)
            is_na = col == na_bin[feat]
            go_right = jnp.where(is_na, ~dleft, col > thr)
            if sp.cat_features or sp.has_bundles:
                from .gather import take_small
                iscat = best_eff.is_cat[l]
                memrow = best_eff.cat_member[l].astype(jnp.float32)
                mem = take_small(memrow, col) > 0.5
                go_right = jnp.where(iscat, ~mem, go_right)
            in_leaf = st.leaf_id == l
            leaf_id2 = jnp.where(in_leaf & go_right, new_leaf, st.leaf_id)

            # ---- child stats ----
            lg, lh, lc = (best_eff.left_g[l], best_eff.left_h[l],
                          best_eff.left_cnt[l])
            pg, ph, pc = st.leaf_g[l], st.leaf_h[l], st.leaf_cnt[l]
            rg, rh, rc = pg - lg, ph - lh, pc - lc
            lmin_p, lmax_p = st.leaf_min[l], st.leaf_max[l]

            # ---- smaller-child histogram + sibling by subtraction ----
            small_is_left = lc <= rc
            small_leaf = jnp.where(small_is_left, l, new_leaf)
            mask = (leaf_id2 == small_leaf).astype(g.dtype)
            hist_small = _psum(
                H.hist_leaf(bins, g * mask, h * mask, c * mask, B, gp.hist_impl,
                            bins_T=bins_T),
                gp)
            if pooled:
                # pool lookup; on miss rebuild the parent with one masked
                # pass over the PRE-split membership (reference: HistogramPool
                # miss -> ConstructHistograms)
                slot_p = st.slot_of_leaf[l]
                present = slot_p >= 0

                def _read(_):
                    return st.hist[jnp.maximum(slot_p, 0)]

                def _rebuild(_):
                    m2 = (st.leaf_id == l).astype(g.dtype)
                    return _psum(H.hist_leaf(bins, g * m2, h * m2, c * m2, B,
                                             gp.hist_impl, bins_T=bins_T), gp)

                hist_parent = jax.lax.cond(present, _read, _rebuild, None)
            else:
                hist_parent = st.hist[l]
            hist_large = hist_parent - hist_small
            hist_left = jnp.where(small_is_left, hist_small, hist_large)
            hist_right = jnp.where(small_is_left, hist_large, hist_small)
            if pooled:
                # LRU slot allocation: left child reuses the parent's slot
                # when present; victims are the oldest-written slots
                big = jnp.int32(1 << 30)
                iota_p = jnp.arange(P)
                age1 = jnp.where(iota_p == slot_p, big, st.slot_age)
                vA = jnp.argmin(age1).astype(jnp.int32)
                vB = jnp.argmin(age1.at[vA].set(big)).astype(jnp.int32)
                slot_l = jnp.where(present, slot_p, vA)
                slot_r = jnp.where(present, vA, vB)
                old_l = st.leaf_of_slot[slot_l]
                old_r = st.leaf_of_slot[slot_r]
                iota_L = jnp.arange(L)
                sol = jnp.where((iota_L == old_l) | (iota_L == old_r), -1,
                                st.slot_of_leaf)
                sol = sol.at[l].set(slot_l).at[new_leaf].set(slot_r)
                hist2 = st.hist.at[slot_l].set(hist_left) \
                               .at[slot_r].set(hist_right)
                los = st.leaf_of_slot.at[slot_l].set(l) \
                                     .at[slot_r].set(new_leaf)
                ages = st.slot_age.at[slot_l].set(t + 1).at[slot_r].set(t + 1)
            else:
                hist2 = st.hist.at[l].set(hist_left).at[new_leaf].set(hist_right)
                sol, los, ages = (st.slot_of_leaf, st.leaf_of_slot,
                                  st.slot_age)

            # ---- tree arrays (node t) ----
            tr = st.tree
            parent = st.parent_node[l]
            has_parent = parent >= 0
            pidx = jnp.maximum(parent, 0)
            lc_arr = tr.left_child.at[pidx].set(
                jnp.where(has_parent & ~st.parent_right[l], t, tr.left_child[pidx]))
            rc_arr = tr.right_child.at[pidx].set(
                jnp.where(has_parent & st.parent_right[l], t, tr.right_child[pidx]))
            w_l = leaf_output(lg, lh, sp)
            w_r = leaf_output(rg, rh, sp)
            w_p = leaf_output(pg, ph, sp)
            if sp.has_monotone:
                w_l = jnp.clip(w_l, lmin_p, lmax_p)
                w_r = jnp.clip(w_r, lmin_p, lmax_p)
                w_p = jnp.clip(w_p, lmin_p, lmax_p)
            tr = TreeArrays(
                split_feature=tr.split_feature.at[t].set(feat),
                threshold_bin=tr.threshold_bin.at[t].set(thr),
                default_left=tr.default_left.at[t].set(dleft),
                left_child=lc_arr.at[t].set(~l),
                right_child=rc_arr.at[t].set(~new_leaf),
                split_gain=tr.split_gain.at[t].set(best_eff.gain[l]),
                leaf_value=tr.leaf_value.at[l].set(w_l).at[new_leaf].set(w_r),
                leaf_weight=tr.leaf_weight.at[l].set(lh).at[new_leaf].set(rh),
                leaf_count=tr.leaf_count.at[l].set(_rows(lc))
                .at[new_leaf].set(_rows(rc)),
                internal_value=tr.internal_value.at[t].set(w_p),
                internal_weight=tr.internal_weight.at[t].set(ph),
                internal_count=tr.internal_count.at[t].set(pc),
                num_leaves=tr.num_leaves + 1,
                is_cat=tr.is_cat.at[t].set(best_eff.is_cat[l]),
                cat_mask=tr.cat_mask.at[t].set(best_eff.cat_member[l]),
            )

            # ---- monotone bound propagation for the two children ----
            if sp.has_monotone:
                mono_tab = jnp.zeros(f, jnp.int32).at[
                    jnp.arange(len(sp.monotone_constraints[:f]))].set(
                    jnp.asarray(sp.monotone_constraints[:f], jnp.int32))
                mf = jnp.where(best_eff.is_cat[l], 0, mono_tab[feat])
                mid = (w_l + w_r) / 2.0
                lmin_l = jnp.where(mf < 0, jnp.maximum(lmin_p, mid), lmin_p)
                lmax_l = jnp.where(mf > 0, jnp.minimum(lmax_p, mid), lmax_p)
                lmin_r = jnp.where(mf > 0, jnp.maximum(lmin_p, mid), lmin_p)
                lmax_r = jnp.where(mf < 0, jnp.minimum(lmax_p, mid), lmax_p)
                ch_min = jnp.stack([lmin_l, lmin_r])
                ch_max = jnp.stack([lmax_l, lmax_r])
                leaf_min2 = st.leaf_min.at[l].set(lmin_l).at[new_leaf].set(lmin_r)
                leaf_max2 = st.leaf_max.at[l].set(lmax_l).at[new_leaf].set(lmax_r)
            else:
                ch_min = ch_max = None
                leaf_min2, leaf_max2 = st.leaf_min, st.leaf_max

            # ---- forced-pointer propagation to the two children ----
            if forced is not None:
                applied = st.forced_ptr[l] >= 0
                fnode = jnp.maximum(st.forced_ptr[l], 0)
                fl_next = jnp.where(applied, forced.left[fnode], -1)
                fr_next = jnp.where(applied, forced.right[fnode], -1)
                fptr2 = st.forced_ptr.at[l].set(fl_next) \
                                     .at[new_leaf].set(fr_next)
            else:
                fptr2 = st.forced_ptr

            # ---- best splits for the two children (batched, not vmapped) ----
            depth = st.leaf_depth[l] + 1
            allow = _allow_depth(depth, gp) if gp.max_depth > 0 else jnp.bool_(True)
            ch_hist = jnp.stack([hist_left, hist_right])      # [2, 3, F, B]
            ch_g = jnp.stack([lg, rg])
            ch_h = jnp.stack([lh, rh])
            ch_c = jnp.stack([lc, rc])
            ch_mask = _node_mask(
                t, jnp.broadcast_to(feature_mask, (2, f)))
            bs = best_split(ch_hist, num_bins, na_bin, ch_g, ch_h, ch_c,
                            ch_mask, sp, allow,
                            leaf_min=ch_min, leaf_max=ch_max, bundle=bundle,
                            rand_key=_et_key(t))

            def upd(arr, vals):
                return arr.at[l].set(vals[0]).at[new_leaf].set(vals[1])

            best2 = SplitResult(*[upd(a, v) for a, v in zip(st.best, bs)])

            return _GrowState(
                leaf_id=leaf_id2, hist=hist2,
                slot_of_leaf=sol, leaf_of_slot=los, slot_age=ages,
                leaf_g=st.leaf_g.at[l].set(lg).at[new_leaf].set(rg),
                leaf_h=st.leaf_h.at[l].set(lh).at[new_leaf].set(rh),
                leaf_cnt=st.leaf_cnt.at[l].set(lc).at[new_leaf].set(rc),
                leaf_depth=st.leaf_depth.at[l].set(depth).at[new_leaf].set(depth),
                parent_node=st.parent_node.at[l].set(t).at[new_leaf].set(t),
                parent_right=st.parent_right.at[l].set(False).at[new_leaf].set(True),
                leaf_min=leaf_min2, leaf_max=leaf_max2,
                forced_ptr=fptr2, tree=tr, done=st.done,
                best=best2,
            )

        st2 = jax.lax.cond(ok, do_split, lambda s: s, st)
        st2 = st2._replace(done=st2.done | ~ok)
        return st2, None

    if L >= 2:
        state, _ = jax.lax.scan(step, state, jnp.arange(L - 1, dtype=jnp.int32))

    tree = state.tree
    # single-leaf tree: constant output
    root_w = leaf_output(g0, h0, sp)
    tree = tree._replace(
        leaf_value=jnp.where(tree.num_leaves > 1, tree.leaf_value,
                             tree.leaf_value.at[0].set(root_w)),
        leaf_weight=jnp.where(tree.num_leaves > 1, tree.leaf_weight,
                              tree.leaf_weight.at[0].set(h0)),
        leaf_count=jnp.where(tree.num_leaves > 1, tree.leaf_count,
                             tree.leaf_count.at[0].set(_rows(c0))),
    )
    return tree, state.leaf_id
