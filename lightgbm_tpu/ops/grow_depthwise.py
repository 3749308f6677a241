"""Depthwise (level-wise) tree growing — the high-throughput TPU path.

The leaf-wise grower (ops/grow.py) matches the reference's SerialTreeLearner
semantics exactly but pays one full-data histogram pass per split: O(num_leaves)
passes per tree. This grower does one pass per *level*: routing and histogram
accumulation for every node of a level happen in a single fused scan over the
data (ops/histogram.py hist_routed), whose MXU contraction width is the
(slot x channel) axis. The sibling-subtraction trick (reference:
serial_tree_learner.cpp:315-355) measures only the smaller child of each split.

Cost per tree: O(max_depth) data passes instead of O(num_leaves) — the same
asymptotic win the reference gets from partition-ordered gradients, with no row
reordering. Early levels are Python-unrolled with growing static slot counts
(level k splits at most 2^k leaves) so they don't pay the deepest level's
histogram width; a while_loop tail covers unbalanced growth past the unroll.

The whole tree builds inside ONE jitted program — zero host round-trips per
tree (every round-trip stalls the device behind host dispatch latency). All
level bookkeeping (budgeted split selection, node numbering, child pointers) is
vectorized as masked [num_leaves]-sized scatters.

Tree layout matches ops/grow.py: node t = t-th split (nodes within a level are
numbered in leaf order), child pointers >= 0 internal / < 0 = ~leaf (reference
encoding, tree.h:25).
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import histogram as H
from .grow import (GrowParams, TreeArrays, _empty_tree, _hist_allreduce,
                   _leaf_sums_allreduce, _psum, _rows)
from .split import (NEG_INF, SplitParams, SplitResult, best_split,
                    leaf_output, per_feature_gains)

_OOB = 1 << 20  # out-of-bounds scatter index (dropped with mode="drop")
# minimum static slot width for unrolled levels on the PALLAS path: the
# fused pass is latency-bound below S=32 (flat 17-22 ms, PERF_NOTES cost
# table), so levels 0..4 share one padded kernel variant instead of
# compiling five (S=1,2,4,8,16) that run no faster. Widths above the floor
# snap to pallas_hist.MASTER_SLOT_WIDTHS — at L=255 the per-grower variants
# are {32, 127} (the 64-wide level joins the 127 group). The XLA fallback
# impl pays real per-slot FLOPs, so it is not floored.
_SLOT_FLOOR = 32


class CEGBState(NamedTuple):
    """Persistent CEGB bookkeeping (reference: CostEfficientGradientBoosting,
    cost_effective_gradient_boosting.hpp). Threads ACROSS trees/iterations:
    ``feature_used`` is model-lifetime 'was feature ever split on' (coupled
    penalty); ``data_used`` is the per-(row, feature) on-demand bitset (lazy
    penalty; shape [N, F] when lazy is on, [1, 1] dummy otherwise).
    Penalty vectors are in grower feature space."""
    feature_used: jnp.ndarray   # [F] bool
    data_used: jnp.ndarray      # [N, F] bool (or [1, 1] dummy)
    coupled_pen: jnp.ndarray    # [F] f32 (zeros when coupled off)
    lazy_pen: jnp.ndarray       # [F] f32 (zeros when lazy off)


class ForcedSplits(NamedTuple):
    """Flattened forcedsplits_filename tree (reference: ForceSplits,
    serial_tree_learner.cpp:456-618): per forced node, the (already
    bin-mapped) split and child pointers (-1 = stop forcing)."""
    feat: jnp.ndarray    # [M] i32 (grower feature space)
    bin: jnp.ndarray     # [M] i32
    left: jnp.ndarray    # [M] i32 forced-node index of the left child
    right: jnp.ndarray   # [M] i32


class _DWState(NamedTuple):
    leaf_id: jnp.ndarray      # [N]
    forced_ptr: jnp.ndarray   # [L] i32: forced-node to apply next (-1 none)
    vote_mask: jnp.ndarray    # [L, F] bool: per-leaf features whose columns the
                              # stored frontier histogram actually holds (voting
                              # zeroes non-elected columns; a budget-deferred
                              # leaf must not search features its stored rows
                              # don't cover — ADVICE r2: starvation). All-True
                              # when voting is off.
    hist: jnp.ndarray         # [L, 3, F, B] per-leaf histograms (frontier leaves)
    leaf_g: jnp.ndarray       # [L]
    leaf_h: jnp.ndarray
    leaf_c: jnp.ndarray
    active: jnp.ndarray       # [L] bool: frontier (may still split)
    parent_node: jnp.ndarray  # [L] i32
    parent_right: jnp.ndarray # [L] bool
    leaf_min: jnp.ndarray     # [L] monotone output bounds (ConstraintEntry)
    leaf_max: jnp.ndarray
    cegb: CEGBState           # CEGB bookkeeping (dummy arrays when off)
    tree: TreeArrays


def _scatter_set(arr, idx, val, mask):
    """arr[idx] = val where mask (vectorized, dropped where ~mask)."""
    safe = jnp.where(mask, idx, _OOB)
    return arr.at[safe].set(val, mode="drop")


def _apply_level_to_tree(tr: TreeArrays, parent_node, parent_right, res,
                         sel, node_id, new_leaf, leaves_iota,
                         lg, lh, lc, rg, rh, rc, w_l, w_r, w_p,
                         num_sel) -> TreeArrays:
    """Masked-scatter application of one level's selected splits to the tree
    arrays (shared by the default and lean depthwise growers)."""
    feat, thr, dleft = res.feature, res.bin, res.default_left
    has_par = sel & (parent_node >= 0)
    lc_arr = _scatter_set(tr.left_child, parent_node,
                          node_id, has_par & ~parent_right)
    rc_arr = _scatter_set(tr.right_child, parent_node,
                          node_id, has_par & parent_right)
    return TreeArrays(
        split_feature=_scatter_set(tr.split_feature, node_id, feat, sel),
        threshold_bin=_scatter_set(tr.threshold_bin, node_id, thr, sel),
        default_left=_scatter_set(tr.default_left, node_id, dleft, sel),
        left_child=_scatter_set(lc_arr, node_id, ~leaves_iota, sel),
        right_child=_scatter_set(rc_arr, node_id, ~new_leaf, sel),
        split_gain=_scatter_set(tr.split_gain, node_id,
                                res.gain.astype(jnp.float32), sel),
        leaf_value=_scatter_set(
            _scatter_set(tr.leaf_value, leaves_iota, w_l, sel),
            new_leaf, w_r, sel),
        leaf_weight=_scatter_set(
            _scatter_set(tr.leaf_weight, leaves_iota, lh, sel),
            new_leaf, rh, sel),
        leaf_count=_scatter_set(
            _scatter_set(tr.leaf_count, leaves_iota, _rows(lc), sel),
            new_leaf, _rows(rc), sel),
        internal_value=_scatter_set(tr.internal_value, node_id, w_p, sel),
        internal_weight=_scatter_set(tr.internal_weight, node_id,
                                     lh + rh, sel),
        internal_count=_scatter_set(tr.internal_count, node_id,
                                    lc + rc, sel),
        num_leaves=tr.num_leaves + num_sel,
        is_cat=_scatter_set(tr.is_cat, node_id, res.is_cat, sel),
        cat_mask=_scatter_set(tr.cat_mask, node_id, res.cat_member, sel),
    )


def _monotone_child_bounds(sp: SplitParams, f: int, res, feat, sel,
                           w_l, w_r, leaf_min, leaf_max, leaves_iota,
                           new_leaf):
    """Monotone output-bound propagation to the two children of each selected
    split (LeafConstraints::UpdateConstraints, monotone_constraints.hpp:44);
    shared by the default and lean depthwise growers."""
    mono_tab = jnp.zeros(f, jnp.int32)
    mc = jnp.asarray(sp.monotone_constraints[:f], jnp.int32)
    mono_tab = mono_tab.at[jnp.arange(mc.shape[0])].set(mc)
    mf = jnp.where(res.is_cat, 0, mono_tab[feat])   # cat splits: none
    mid = (w_l + w_r) / 2.0
    lmin_l = jnp.where(sel & (mf < 0), jnp.maximum(leaf_min, mid), leaf_min)
    lmax_l = jnp.where(sel & (mf > 0), jnp.minimum(leaf_max, mid), leaf_max)
    lmin_r = jnp.where(sel & (mf > 0), jnp.maximum(leaf_min, mid), leaf_min)
    lmax_r = jnp.where(sel & (mf < 0), jnp.minimum(leaf_max, mid), leaf_max)
    leaf_min2 = _scatter_set(
        _scatter_set(leaf_min, leaves_iota, lmin_l, sel),
        new_leaf, lmin_r, sel)
    leaf_max2 = _scatter_set(
        _scatter_set(leaf_max, leaves_iota, lmax_l, sel),
        new_leaf, lmax_r, sel)
    return leaf_min2, leaf_max2


def level_groups(num_leaves: int, max_depth: int, use_pallas: bool):
    """The bucketed level schedule of both depthwise growers: a list of
    (width, first level, one-past-last level, decode leaves) groups.

    Level k has at most min(2^k, L // 2) splittable leaves, so the first
    ~log2(L) levels run at small static slot widths — the histogram pass cost
    scales with the slot axis, and one fixed width made every level pay for
    the deepest one (measured ~2x whole-tree cost at L=255). The tail past
    them (unbalanced growth, up to max_depth or L - 1 levels) runs at the
    full width L // 2: the most splits a level can SELECT, min(frontier 2^d,
    budget L - 2^d). The dropped-row slot id equals the slot count (no
    weight row in the kernel), so the pass width is exactly the split cap —
    at L=255 the deepest pass is S=127 -> 381 lanes -> 384 MXU-lane pad.

    On the pallas path every width is floored at _SLOT_FLOOR and snapped to
    pallas_hist.MASTER_SLOT_WIDTHS (the fused pass is latency-bound and flat
    below S=32, but every distinct S compiles its own Mosaic kernel); the
    XLA fallback pays real FLOPs per slot and keeps exact 2^k widths.
    Over-wide S never changes selection: level k has <= 2^k candidate leaves
    <= the un-floored width, so `rank < min(budget, SLOTS)` binds identically
    and the grown tree is bit-identical.

    Consecutive levels of one width form one group, traced once. ``decode
    leaves`` is the most leaves that can exist before the group's last
    level, min(L, 2^(k1-1)): leaf ids are dense in creation order, so the
    level's RouteTables and the kernels' per-row decode span that many
    entries and not num_leaves (32 and 255 for the two groups at L=255)."""
    L = num_leaves
    max_levels = max_depth if max_depth > 0 else max(1, L - 1)
    max_slots = max(1, L // 2)
    n_unroll = min(max_levels, max(1, math.ceil(math.log2(max(L - 1, 2)))) + 1)
    if use_pallas:
        from .pallas_hist import floor_slot_width
        widths = [floor_slot_width(max(min(2 ** k, max_slots), _SLOT_FLOOR),
                                   max_slots)
                  for k in range(n_unroll)]
    else:
        widths = [min(max_slots, 2 ** k) for k in range(n_unroll)]
    groups = []   # [width, first level, one-past-last level]
    for k, w in enumerate(widths):
        if groups and groups[-1][0] == w:
            groups[-1][2] = k + 1
        else:
            groups.append([w, k, k + 1])
    if max_levels > n_unroll:
        # unbalanced-growth tail: full width, merged with the last unrolled
        # group when that group already runs at max_slots
        if groups and groups[-1][0] == max_slots:
            groups[-1][2] = max_levels
        else:
            groups.append([max_slots, n_unroll, max_levels])
    return [(w, k0, k1, min(L, 2 ** (k1 - 1))) for w, k0, k1 in groups]


def allreduce_bytes_per_tree(num_leaves: int, max_depth: int,
                             num_features: int, max_bin: int,
                             use_pallas: bool) -> int:
    """Bytes one tree of the default depthwise data-parallel grower hands to
    the cross-chip reduction (``_hist_allreduce`` / ``_psum``), from shapes:
    the root's ``[3, F, B]`` float32 histogram, one ``[S, 3, F, B]`` a level
    at the level's slot width over the ceil(log2(num_leaves)) levels a
    balanced tree takes, less its last (the level that fills the leaf budget
    or meets the depth cap builds no histograms: ``_ends_tree``; a deeper
    tree adds one full-width reduction for each level of the tail), and the
    ``[3, L]`` exact leaf sums. Every chip sends and receives that much
    whatever the number of chips. The voting learner exchanges less and is
    not counted here."""
    cell = 3 * num_features * max_bin
    levels = max(1, math.ceil(math.log2(max(num_leaves, 2))))
    if max_depth > 0:
        levels = min(levels, max_depth)
    reduced = levels - 1
    slots = sum((min(reduced, k1) - k0) * w
                for w, k0, k1, _ in level_groups(num_leaves, max_depth,
                                                 use_pallas)
                if k0 < reduced)
    return 4 * (cell + slots * cell + 3 * num_leaves)


def _ends_tree(num_leaves, num_sel, lvl, L: int, max_levels: int):
    """Whether no level can run after level ``lvl``: its selected splits fill
    the leaf budget, or it is the last level the depth cap allows. Nothing
    then reads the children's histograms that the level would build (leaf
    values come from the level's own search and the exact leaf sums), so the
    level routes its rows and builds none."""
    return (num_leaves + num_sel >= L) | (lvl + 1 >= max_levels)


def _run_level_schedule(state, level, L, groups):
    """Run ``level(state, lvl, group)`` over ``level_groups``' schedule, each
    group one ``lax.while_loop`` (one ``lax.cond`` where it holds a single
    level), so the level body is traced and compiled once per group instead
    of once per depth. ``group`` is the static (width, first level,
    one-past-last level, decode leaves) tuple the body is traced for.

    Early exit is the loop guard: once a level selects no splits OR the leaf
    budget is exhausted, the tree is finished, ``last`` stays 0 and every
    later group runs zero iterations. The budget check matters for balanced
    growth: a tree that fills num_leaves=255 exactly at level 8 would
    otherwise pay one more full-width pass just to select nothing (~25% of
    whole-tree cost, measured at 10M rows); the level that fills it routes
    its rows and builds no histograms (``_ends_tree``). The level index
    reaches the body as a traced i32 (it feeds ``jax.random.fold_in`` and
    ``_ends_tree``).
    """
    last_sel = jnp.int32(1)
    for group in groups:
        w, k0, k1, _ = group
        # the scope holds the loop construct too, so a trace reads the
        # group's whole device time (guard, carry copies) under its width
        with jax.named_scope(f"level_s{w}"):
            if k1 - k0 == 1:
                # single level at this width: cond and while_loop both trace
                # the body exactly once; cond skips the carry plumbing
                state, last_sel = jax.lax.cond(
                    (last_sel > 0) & (state.tree.num_leaves < L),
                    lambda st, _k=k0, _g=group: level(st, jnp.int32(_k), _g),
                    lambda st: (st, jnp.int32(0)),
                    state)
                continue

            def cond(carry, _k1=k1):
                st, lvl, last = carry
                return (lvl < _k1) & (last > 0) & (st.tree.num_leaves < L)

            def body(carry, _g=group):
                st, lvl, _ = carry
                st2, num_sel = level(st, lvl, _g)
                return st2, lvl + 1, num_sel

            state, _, last_sel = jax.lax.while_loop(
                cond, body, (state, jnp.int32(k0), last_sel))
    return state


def _level_tables(l_dec: int, sel, res, new_leaf, slot_left, slot_right,
                  sp: SplitParams):
    """One level's RouteTables over the ``l_dec`` leaves it can hold (every
    live leaf id is below it: level_groups)."""
    cat = bool(sp.cat_features or sp.has_bundles)
    return jax.tree.map(lambda a: a[:l_dec], H.RouteTables(
        feat=jnp.where(sel, res.feature, -1),
        thr=res.bin,
        dleft=res.default_left.astype(jnp.int32),
        new_leaf=new_leaf,
        slot_left=slot_left,
        slot_right=slot_right,
        is_cat=(res.is_cat & sel).astype(jnp.int32) if cat else None,
        member=(res.cat_member & sel[:, None]).astype(jnp.float32)
        if cat else None))


@partial(jax.jit, static_argnames=("gp",))
def grow_tree_depthwise(bins: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray,
                        c: jnp.ndarray, num_bins: jnp.ndarray,
                        na_bin: jnp.ndarray, feature_mask: jnp.ndarray,
                        gp: GrowParams, bundle=None, forced=None, qseed=None,
                        cegb=None, bins_T=None, fused=None):
    """Grow one tree level-wise.

    bins: [N, F] uint8; g/h/c: [N] f32 grad/hess/in-bag count channels (already
    masked). Under shard_map with gp.axis_name set, histograms are psum-reduced
    (data-parallel). ``qseed`` (traced i32, e.g. the iteration index) varies
    the stochastic-rounding dither when gp.quant is on. Returns
    (TreeArrays, leaf_id [N] i32), plus the updated ``cegb`` CEGBState when one
    is passed (gp.split.has_cegb; penalties recomputed fresh each level, so the
    reference's stale-cache fixups in UpdateLeafBestSplits are unnecessary).

    ``bins_T``: optional cached transpose (Dataset.bins_T) — skips the
    per-tree transpose on the pallas path. Shaped for the kernels,
    [F_pad, N_pad], it makes every row vector of the tree N_pad long
    (histogram.resident_rows) and ``leaf_id`` is returned so: entries past N
    hold num_leaves, no leaf. ``fused``: (score, aux, bag) row
    inputs for the fused grad+quant+hist0 front, valid only with
    gp.fused_obj set and gp.quant on; the g/h/c arguments are then unused
    placeholders (the quantized channels and all histogram passes derive
    from the fused front, bit-identical to the unfused chain).
    """
    n, f = bins.shape
    L, B = gp.num_leaves, gp.max_bin
    sp = gp.split
    # pallas kernels read a transposed bin matrix: prefer the Dataset's
    # cached device-resident copy, else build it once per tree (XLA CSEs it
    # across all level passes inside this jit)
    use_pallas = H.pick_impl(gp.hist_impl) == "pallas"
    if not use_pallas:
        bins_T = None
    elif bins_T is None:
        with jax.named_scope("bins_T"):
            bins_T = bins.T
    c_rows = c                      # [N]: CEGB's per-row bookkeeping
    with jax.named_scope("front"):
        g, h, c, fused, leaf_id0 = H.resident_rows(bins_T, n, L, g, h, c,
                                                   fused)
        if fused is not None:
            # fused grad+quant+hist0 front: gradients recomputed in-register
            # from (score, aux, bag), never materialized as [N] rows — the
            # gradient write, two quantize reads and the root-histogram read
            # collapse into one pass. Per-shard scales remain fine under
            # data-parallel (histograms dequantize to f32 before the psum),
            # exactly as with make_quant below.
            assert gp.fused_obj is not None and gp.quant and cegb is None
            f_score, f_aux, f_bag = fused
            quant, hist0 = H.grad_quant_hist0(
                bins, f_score, f_aux, f_bag, qseed, gp.fused_obj, B,
                const_hess=gp.const_hess, impl=gp.hist_impl, bins_T=bins_T,
                col_axes=gp.col_axes)
            hist0 = _hist_allreduce(hist0, gp, f_dim=1)
        else:
            # int8 quantized channels, built once per tree; per-shard scales are
            # fine under data-parallel because every histogram is dequantized to
            # f32 before the psum (each shard contributes real-valued mass)
            with jax.named_scope("quant"):
                quant = (H.make_quant(g, h, c, qseed,
                                      const_hess=gp.const_hess)
                         if gp.quant else None)
            with jax.named_scope("hist0"):
                hist0 = _hist_allreduce(
                    H.hist_leaf(bins, g, h, c, B, gp.hist_impl, bins_T=bins_T,
                                quant=quant, col_axes=gp.col_axes),
                    gp, f_dim=1)                                         # [3, F, B]
        g0 = hist0[0, 0].sum()
        h0 = hist0[1, 0].sum()
        c0 = hist0[2, 0].sum()

    if cegb is None:
        dummy_b = jnp.zeros(1, bool)
        cegb = CEGBState(feature_used=dummy_b,
                         data_used=jnp.zeros((1, 1), bool),
                         coupled_pen=jnp.zeros(1, jnp.float32),
                         lazy_pen=jnp.zeros(1, jnp.float32))
        cegb_on = False
    else:
        cegb_on = sp.has_cegb

    state = _DWState(
        leaf_id=leaf_id0,
        forced_ptr=jnp.full(L, -1, jnp.int32).at[0].set(
            0 if forced is not None else -1),
        vote_mask=jnp.ones((L, f), dtype=bool),
        hist=jnp.zeros((L, 3, f, B), jnp.float32).at[0].set(hist0),
        leaf_g=jnp.zeros(L).at[0].set(g0),
        leaf_h=jnp.zeros(L).at[0].set(h0),
        leaf_c=jnp.zeros(L).at[0].set(c0),
        active=jnp.zeros(L, bool).at[0].set(True),
        parent_node=jnp.full(L, -1, jnp.int32),
        parent_right=jnp.zeros(L, bool),
        leaf_min=jnp.full(L, -jnp.inf),
        leaf_max=jnp.full(L, jnp.inf),
        cegb=cegb,
        tree=_empty_tree(L, B),
    )
    # root leaf value (kept if nothing splits)
    root_w = leaf_output(g0, h0, sp)
    state = state._replace(tree=state.tree._replace(
        leaf_value=state.tree.leaf_value.at[0].set(root_w),
        leaf_weight=state.tree.leaf_weight.at[0].set(h0),
        leaf_count=state.tree.leaf_count.at[0].set(_rows(c0))))

    leaves_iota = jnp.arange(L, dtype=jnp.int32)
    groups = level_groups(L, gp.max_depth, use_pallas)
    max_levels = groups[-1][2]

    def level(st: _DWState, lvl, group):
        SLOTS, _, end, l_dec = group
        with jax.named_scope("split_search"):
            # ---- per-node feature sampling (feature_fraction_bynode;
            # reference samples per node, serial_tree_learner.cpp:397+ — here
            # each frontier LEAF draws its own feature subset per level, keyed on
            # (tree seed, level) so trees and levels decorrelate) ----
            search_mask = feature_mask & st.vote_mask
            if gp.ff_bynode < 1.0:
                # Bernoulli(ff_bynode) keep within the CURRENTLY-USABLE set (the
                # reference samples exactly k of the per-tree used features,
                # serial_tree_learner.cpp:397+; a global top-k over all F columns
                # would compound with feature_fraction and can zero out a leaf's
                # search set). The best-u usable feature is always kept so no
                # leaf ever searches nothing.
                seed_base = qseed if qseed is not None else jnp.int32(0)
                key = jax.random.fold_in(jax.random.PRNGKey(seed_base), lvl)
                u = jax.random.uniform(key, (L, f))
                u_allowed = jnp.where(search_mask, u, -1.0)
                best = u_allowed >= u_allowed.max(axis=1, keepdims=True)
                search_mask = search_mask & ((u < gp.ff_bynode) | best)

            # ---- CEGB penalty plane (DetlaGain, cegb hpp:51-62): recomputed
            # fresh each level from current bookkeeping, so a feature that became
            # used at the previous level is already penalty-free here ----
            pen = None
            if cegb_on:
                pen = jnp.broadcast_to(
                    jnp.float32(sp.cegb_tradeoff * sp.cegb_penalty_split)
                    * st.leaf_c[:, None], (L, f))
                if sp.cegb_coupled:
                    pen = pen + sp.cegb_tradeoff * jnp.where(
                        st.cegb.feature_used, 0.0, st.cegb.coupled_pen)[None, :]
                if sp.cegb_lazy:
                    # on-demand cost: IN-BAG rows in the leaf that haven't paid
                    # for the feature yet (CalculateOndemandCosts iterates only
                    # the bagged partition — c is the in-bag channel)
                    fresh = jnp.where(st.cegb.data_used, 0.0,
                                      st.cegb.lazy_pen[None, :])      # [N, F]
                    fresh = fresh * (c_rows > 0)[:, None]
                    lazy_cost = _psum(
                        jax.ops.segment_sum(fresh, st.leaf_id[:n],
                                            num_segments=L), gp)
                    pen = pen + sp.cegb_tradeoff * lazy_cost

            # ---- best split for every frontier leaf (one batched kernel) ----
            if sp.extra_trees:
                # one random threshold per (leaf, feature) per level, keyed on
                # (extra_seed, tree seed, level) like the reference's per-search
                # rand_threshold (feature_histogram.hpp:99-102)
                et_base = qseed if qseed is not None else jnp.int32(0)
                et_key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(sp.extra_seed),
                                       et_base), lvl)
            else:
                et_key = None
            res = best_split(st.hist, num_bins, na_bin, st.leaf_g, st.leaf_h,
                             st.leaf_c, search_mask, sp, st.active,
                             leaf_min=st.leaf_min, leaf_max=st.leaf_max,
                             bundle=bundle, gain_penalty=pen, rand_key=et_key)
            if forced is not None:
                # ---- forced splits override the gain search (ForceSplits,
                # serial_tree_learner.cpp:456-618): leaves holding a forced-node
                # pointer split on that (feature, bin) unconditionally; left
                # stats come from the leaf histogram's cumsum at the forced bin
                fp = jnp.maximum(st.forced_ptr, 0)
                has_f = (st.forced_ptr >= 0) & st.active
                ffeat = forced.feat[fp]                         # [L]
                fbin = forced.bin[fp]
                iota_bf = jnp.arange(B, dtype=jnp.int32)[None, None, :]
                na_self = iota_bf == na_bin[None, :, None]      # [1, F, B]
                cumf = jnp.cumsum(jnp.where(na_self[:, None], 0.0, st.hist),
                                  axis=-1)                      # [L, 3, F, B]
                lidx2 = jnp.arange(L)
                flg = cumf[lidx2, 0, ffeat, fbin]
                flh = cumf[lidx2, 1, ffeat, fbin]
                flc = cumf[lidx2, 2, ffeat, fbin]
                # validity: both sides non-empty, else stop forcing at this leaf
                okf = has_f & (flc >= 1) & (st.leaf_c - flc >= 1)
                big = jnp.float32(1e30)
                res = res._replace(
                    gain=jnp.where(okf, big, res.gain),
                    feature=jnp.where(okf, ffeat, res.feature),
                    bin=jnp.where(okf, fbin, res.bin),
                    default_left=jnp.where(okf, False, res.default_left),
                    left_g=jnp.where(okf, flg, res.left_g),
                    left_h=jnp.where(okf, flh, res.left_h),
                    left_cnt=jnp.where(okf, flc, res.left_cnt),
                    is_cat=jnp.where(okf, False, res.is_cat),
                    cat_member=jnp.where(okf[:, None], False, res.cat_member))

            # ---- budgeted selection (num_leaves cap): top-gain candidates win.
            # rank by pairwise comparison count instead of argsort — an [L] sort
            # on TPU costs milliseconds; the [L, L] compare matrix is microseconds
            # in feature_contri mode res.gain is already the PENALIZED improvement
            # with min_gain_to_split subtracted (split.py best_split) — gating it
            # against min_gain again would apply the threshold twice
            gain_gate = 0.0 if sp.has_contri \
                else float(max(sp.min_gain_to_split, 0.0))
            cand = st.active & (res.gain > gain_gate) & (res.gain > NEG_INF / 2)
            budget = L - st.tree.num_leaves
            key = jnp.where(cand, res.gain, -jnp.inf)
            kj, ki = key[None, :], key[:, None]
            better = (kj > ki) | ((kj == ki) & (leaves_iota[None, :] < leaves_iota[:, None]))
            rank = jnp.sum(better, axis=1).astype(jnp.int32)   # stable desc rank
            sel = cand & (rank < jnp.minimum(budget, SLOTS))
            num_sel = sel.sum().astype(jnp.int32)

        with jax.named_scope("apply_level"):
            # assignment order within the level: by leaf index
            idx_in_lvl = (jnp.cumsum(sel.astype(jnp.int32)) - 1).astype(jnp.int32)
            node_id = st.tree.num_leaves - 1 + idx_in_lvl      # node_cnt == n_leaves-1
            new_leaf = st.tree.num_leaves + idx_in_lvl

            feat = res.feature
            lg, lh, lc = res.left_g, res.left_h, res.left_cnt
            rg, rh, rc = st.leaf_g - lg, st.leaf_h - lh, st.leaf_c - lc

            # ---- tree arrays (masked scatters over node/leaf ids); outputs
            # clamped by monotone bounds (CalculateSplittedLeafOutput with
            # ConstraintEntry, feature_histogram.hpp:498) ----
            w_l = leaf_output(lg, lh, sp)
            w_r = leaf_output(rg, rh, sp)
            w_p = leaf_output(st.leaf_g, st.leaf_h, sp)
            if sp.has_monotone:
                w_l = jnp.clip(w_l, st.leaf_min, st.leaf_max)
                w_r = jnp.clip(w_r, st.leaf_min, st.leaf_max)
                w_p = jnp.clip(w_p, st.leaf_min, st.leaf_max)
            tr = _apply_level_to_tree(st.tree, st.parent_node, st.parent_right,
                                      res, sel, node_id, new_leaf, leaves_iota,
                                      lg, lh, lc, rg, rh, rc, w_l, w_r, w_p,
                                      num_sel)

            # ---- CEGB bookkeeping (UpdateLeafBestSplits, cegb hpp:63-86):
            # selected splits mark their feature model-used (coupled) and mark
            # (row, feature) paid for every row in the split leaf (lazy) ----
            cegb2 = st.cegb
            if cegb_on and sp.cegb_coupled:
                cegb2 = cegb2._replace(feature_used=_scatter_set(
                    cegb2.feature_used, feat, jnp.ones(L, bool), sel))
            if cegb_on and sp.cegb_lazy:
                feat_of_leaf = jnp.where(sel, feat, _OOB)
                f_row = feat_of_leaf[st.leaf_id[:n]]                 # [N]
                f_row = jnp.where(c_rows > 0, f_row, _OOB)  # OOB rows never pay
                cegb2 = cegb2._replace(data_used=cegb2.data_used.at[
                    jnp.arange(n), f_row].set(True, mode="drop"))

        def route_hist():
            with jax.named_scope("route_hist"):
                # ---- fused route + child histogram pass ----
                voting = bool(gp.axis_name) and gp.voting_top_k > 0
                small_is_left = lc <= rc
                leaf_of_slot = _scatter_set(jnp.full(SLOTS, _OOB, jnp.int32),
                                            idx_in_lvl, leaves_iota, sel)
                slot_used = leaf_of_slot < L
                if voting:
                    # voting mode measures BOTH children fresh (no sibling
                    # subtraction): the next level's vote needs full local
                    # histograms of the whole frontier, and parent-derived
                    # entries would mix earlier elected sets (shard-divergent
                    # -> collective deadlock)
                    S_pass = 2 * SLOTS
                    slot_l_tab = jnp.where(sel, idx_in_lvl * 2, S_pass)
                    slot_r_tab = jnp.where(sel, idx_in_lvl * 2 + 1, S_pass)
                else:
                    S_pass = SLOTS
                    # slot only for the smaller child; larger sibling = parent
                    # minus smaller
                    slot_l_tab = jnp.where(sel & small_is_left, idx_in_lvl,
                                           SLOTS)
                    slot_r_tab = jnp.where(sel & ~small_is_left, idx_in_lvl,
                                           SLOTS)
                tables = _level_tables(l_dec, sel, res, new_leaf, slot_l_tab,
                                       slot_r_tab, sp)
                hist_pass, leaf_id2 = H.hist_routed(
                    bins, g, h, c, st.leaf_id, tables, na_bin, S_pass, B,
                    gp.hist_impl, bins_T=bins_T, quant=quant,
                    col_axes=gp.col_axes)
                if voting:
                    # ---- voting-parallel histogram exchange (PV-Tree;
                    # reference: VotingParallelTreeLearner GlobalVoting +
                    # CopyLocalHistogram, voting_parallel_tree_learner.cpp:
                    # 170-366). Per-LEVEL election (the depthwise analog of
                    # the reference's per-leaf vote): each shard votes its
                    # local top-2k features by best local frontier gains, the
                    # tally is all-reduced, and only the top-k elected
                    # features' histograms are exchanged — compressing the
                    # per-level collective from F*B to k*B columns.
                    k = min(gp.voting_top_k, f)
                    k2 = min(2 * k, f)
                    lg_local = per_feature_gains(
                        hist_pass, num_bins, na_bin,
                        hist_pass[:, 0, 0].sum(-1), hist_pass[:, 1, 0].sum(-1),
                        hist_pass[:, 2, 0].sum(-1), sp)        # [S_pass, F]
                    score = jnp.where(lg_local > NEG_INF / 2, lg_local,
                                      0.0).sum(0)
                    # local top-2k one-hot vote, tallied across shards
                    thresh2 = jax.lax.top_k(score, k2)[0][-1]
                    votes = (score >= thresh2).astype(jnp.float32)
                    votes = _psum(votes, gp)
                    # deterministic global election: top-k by (votes,
                    # score-sum)
                    global_score = _psum(score, gp)
                    elect_key = votes * 1e12 + global_score
                    elected = jax.lax.top_k(elect_key, k)[1]   # [k] features
                    sub = jnp.take(hist_pass, elected, axis=2)  # [S, 3, k, B]
                    sub = _psum(sub, gp)
                    elected_mask = jnp.zeros(f, bool).at[elected].set(True)
                    # non-elected entries must NOT keep local
                    # (shard-divergent) values: state feeds the replicated
                    # split selection and the loop predicates — divergence
                    # deadlocks the collectives. Zero them.
                    hist_pass = jnp.where(
                        elected_mask[None, None, :, None],
                        hist_pass.at[:, :, elected, :].set(sub), 0.0)
                    # per-leaf coverage: only leaves whose stored histograms
                    # are REPLACED this level (split leaves + their new
                    # siblings) narrow to the new elected set; budget-deferred
                    # leaves keep the mask of the election their stored rows
                    # were measured under
                    em_rows = jnp.broadcast_to(elected_mask[None, :], (L, f))
                    vote_mask = _scatter_set(st.vote_mask, leaves_iota,
                                             em_rows, sel)
                    vote_mask = _scatter_set(vote_mask, new_leaf, em_rows, sel)
                else:
                    hist_pass = _hist_allreduce(hist_pass, gp, f_dim=2)
                    vote_mask = st.vote_mask

                if voting:
                    hist_left = hist_pass[0::2][:SLOTS]
                    hist_right = hist_pass[1::2][:SLOTS]
                else:
                    safe_slot = jnp.minimum(leaf_of_slot, L - 1)
                    parent_hist = st.hist[safe_slot]            # [SLOTS, ...]
                    hist_sib = parent_hist - hist_pass
                    sl = small_is_left[safe_slot][:, None, None, None]
                    hist_left = jnp.where(sl, hist_pass, hist_sib)
                    hist_right = jnp.where(sl, hist_sib, hist_pass)
                new_leaf_of_slot = _scatter_set(
                    jnp.full(SLOTS, _OOB, jnp.int32), idx_in_lvl, new_leaf,
                    sel)
                hist2 = st.hist.at[
                    jnp.where(slot_used, leaf_of_slot, _OOB)].set(
                        hist_left, mode="drop")
                hist2 = hist2.at[
                    jnp.where(slot_used, new_leaf_of_slot, _OOB)].set(
                        hist_right, mode="drop")
            return hist2, leaf_id2, vote_mask

        def route_only():
            # the last level: its children's histograms are never read
            with jax.named_scope("route_only"):
                unmeasured = jnp.full(L, SLOTS, jnp.int32)
                leaf_id2 = H.route_only(
                    bins, st.leaf_id,
                    _level_tables(l_dec, sel, res, new_leaf, unmeasured,
                                  unmeasured, sp),
                    na_bin, SLOTS, B, gp.hist_impl, bins_T=bins_T,
                    quant=quant)
            return st.hist, leaf_id2, st.vote_mask

        # a level k ends with at most 2^(k+1) leaves: only a group that
        # reaches the leaf budget or the depth cap holds a tree's last level
        if 2 ** end >= L or end >= max_levels:
            hist2, leaf_id2, vote_mask = jax.lax.cond(
                _ends_tree(st.tree.num_leaves, num_sel, lvl, L, max_levels),
                route_only, route_hist)
        else:
            hist2, leaf_id2, vote_mask = route_hist()

        with jax.named_scope("apply_level"):
            # ---- monotone bound propagation (LeafConstraints::UpdateConstraints,
            # monotone_constraints.hpp:44-58): children inherit the parent entry;
            # a split on a monotone feature pins the midpoint between them ----
            if sp.has_monotone:
                leaf_min2, leaf_max2 = _monotone_child_bounds(
                    sp, f, res, feat, sel, w_l, w_r, st.leaf_min, st.leaf_max,
                    leaves_iota, new_leaf)
            else:
                leaf_min2, leaf_max2 = st.leaf_min, st.leaf_max

            # ---- per-leaf stats / frontier update ----
            leaf_g2 = _scatter_set(_scatter_set(st.leaf_g, leaves_iota, lg, sel),
                                   new_leaf, rg, sel)
            leaf_h2 = _scatter_set(_scatter_set(st.leaf_h, leaves_iota, lh, sel),
                                   new_leaf, rh, sel)
            leaf_c2 = _scatter_set(_scatter_set(st.leaf_c, leaves_iota, lc, sel),
                                   new_leaf, rc, sel)
            active2 = _scatter_set(sel, new_leaf, jnp.ones(L, bool), sel)
            pn2 = _scatter_set(_scatter_set(st.parent_node, leaves_iota, node_id, sel),
                               new_leaf, node_id, sel)
            pr2 = _scatter_set(
                _scatter_set(st.parent_right, leaves_iota, jnp.zeros(L, bool), sel),
                new_leaf, jnp.ones(L, bool), sel)

            if forced is not None:
                fl = forced.left[fp]
                fr = forced.right[fp]
                fp_next = jnp.where(okf, fl, -1)
                fptr2 = _scatter_set(
                    _scatter_set(st.forced_ptr, leaves_iota,
                                 jnp.where(sel, fp_next, st.forced_ptr), sel),
                    new_leaf, jnp.where(okf, fr, -1), sel)
            else:
                fptr2 = st.forced_ptr
        return _DWState(
            leaf_id=leaf_id2,
            forced_ptr=fptr2,
            vote_mask=vote_mask,
            hist=hist2, leaf_g=leaf_g2, leaf_h=leaf_h2,
            leaf_c=leaf_c2, active=active2, parent_node=pn2, parent_right=pr2,
            leaf_min=leaf_min2, leaf_max=leaf_max2,
            cegb=cegb2,
            tree=tr,
        ), num_sel

    state = _run_level_schedule(state, level, L, groups)

    if gp.quant:
        # leaf renewal from EXACT sums (quantized-training paper: splits
        # tolerate int8 gains, leaf outputs should not; reference analog:
        # exact LeafSplits aggregates, leaf_splits.hpp:20)
        with jax.named_scope("leaf_renew"):
            from .pallas_hist import leaf_sums_grad_pallas, leaf_sums_pallas
            # interpret only where Mosaic can't compile (CPU backend) — keying on
            # hist_impl would run the interpreter inside the jitted tree on TPU
            interp = jax.default_backend() == "cpu"
            if fused is not None and use_pallas:
                sums = leaf_sums_grad_pallas(f_score, f_aux, f_bag,
                                             state.leaf_id, gp.fused_obj,
                                             L, interpret=interp)
            elif fused is not None:
                # XLA fallback: rebuild the exact rows the unfused path would
                # have passed in (bit-identical f32 ops, see _grad_rows)
                from .pallas_hist import _grad_rows
                fg_, fh_ = _grad_rows(gp.fused_obj, f_score, f_aux)
                sums = leaf_sums_pallas(fg_ * f_bag, fh_ * f_bag,
                                        (f_bag > 0).astype(jnp.float32),
                                        state.leaf_id, L, interpret=interp)
            else:
                sums = leaf_sums_pallas(g, h, c, state.leaf_id, L,
                                        interpret=interp)
            eg, eh, ec = _leaf_sums_allreduce(sums, gp)
            w = leaf_output(eg, eh, sp)
            if sp.has_monotone:
                w = jnp.clip(w, state.leaf_min, state.leaf_max)
            tr = state.tree
            live = jnp.arange(L) < tr.num_leaves
            state = state._replace(tree=tr._replace(
                leaf_value=jnp.where(live, w, tr.leaf_value),
                leaf_weight=jnp.where(live, eh, tr.leaf_weight),
                leaf_count=jnp.where(live, ec, tr.leaf_count)))
    if cegb_on:
        return state.tree, state.leaf_id, state.cegb
    return state.tree, state.leaf_id


# ---------------------------------------------------------------------------
# lean depthwise grower: histogram_pool_size for the level-wise path
# ---------------------------------------------------------------------------

class _LeanState(NamedTuple):
    leaf_id: jnp.ndarray      # [N]
    rec: "object"             # SplitResult of [L]-shaped cached candidates
    leaf_g: jnp.ndarray       # [L]
    leaf_h: jnp.ndarray
    leaf_c: jnp.ndarray
    active: jnp.ndarray       # [L] bool
    parent_node: jnp.ndarray
    parent_right: jnp.ndarray
    leaf_min: jnp.ndarray
    leaf_max: jnp.ndarray
    tree: TreeArrays


def _tile_split_params(sp: SplitParams, lo: int, hi: int) -> SplitParams:
    """Re-index per-feature STATIC config to a [lo, hi) feature tile.

    The mode flags must stay UNIFORM across tiles even when a tile's slice
    is trivial: leaf output bounds apply to any split of a constrained leaf
    (not just splits on constrained features), and contri mode rescales
    gains to penalized improvement — folding a raw-gain tile against a
    penalized tile would compare incompatible scales. Hence the
    monotone_clamp/contri_active force-flags."""
    import dataclasses
    kw = {}
    if sp.cat_features:
        kw["cat_features"] = tuple(c - lo for c in sp.cat_features
                                   if lo <= c < hi)
    if sp.monotone_constraints:
        mc = list(sp.monotone_constraints)
        kw["monotone_constraints"] = tuple((mc + [0] * hi)[lo:hi])
        kw["monotone_clamp"] = sp.has_monotone
    if sp.feature_contri:
        fc = list(sp.feature_contri)
        kw["feature_contri"] = tuple((fc + [1.0] * hi)[lo:hi])
        kw["contri_active"] = sp.has_contri
    return dataclasses.replace(sp, **kw) if kw else sp


def _fold_best(a, b):
    """Keep the higher-gain candidate per leaf (earlier tile wins ties —
    matching the monolithic argmax's first-max preference in feature order)."""
    take = b.gain > a.gain
    out = []
    for va, vb in zip(a, b):
        t = take.reshape(take.shape + (1,) * (va.ndim - take.ndim))
        out.append(jnp.where(t, vb, va))
    return SplitResult(*out)


def _slice_bundle(bundle, lo, hi):
    if bundle is None:
        return None
    return type(bundle)(*[v[lo:hi] for v in bundle])


@partial(jax.jit, static_argnames=("gp",))
def grow_tree_depthwise_lean(bins: jnp.ndarray, g, h, c, num_bins, na_bin,
                             feature_mask, gp: GrowParams, bundle=None,
                             forced=None, qseed=None, cegb=None, bins_T=None):
    """Depthwise growth under a histogram-memory budget (reference analog:
    HistogramPool, feature_histogram.hpp:687 + serial_tree_learner.cpp:39-52
    sizing — here the budget bounds LIVE histogram tiles instead of caching
    per-leaf histograms).

    Design: the default grower keeps [L, 3, F, B] per-leaf histograms for
    sibling subtraction and deferred-leaf search — ~830 MB at Allstate width
    (F=4228, L=255, B=64). This mode keeps NO per-leaf histograms:

    - each active leaf caches its best SPLIT RECORD (a SplitResult row —
      gain/feature/bin/left stats/cat mask), valid until the leaf splits
      because its row set never changes while deferred;
    - each level measures BOTH children of every selected split (2S slots;
      no parent histogram needed for subtraction);
    - the histogram pass + best-split search run per FEATURE TILE of width
      ``gp.lean_ft`` (a Python-unrolled loop inside the jit), folding the
      per-tile winners — live histogram memory is [2S, 3, ft, B] for one
      tile, chosen by GBDT to fit histogram_pool_size.

    ``bins_T`` and the length of the returned ``leaf_id``: as
    grow_tree_depthwise. Not combined with voting/CEGB/forced-splits/ff_bynode
    (GBDT keeps the default grower and warns). Ties across
    missing-direction planes of different tiles may break differently from the monolithic search (both
    prefer the lower feature id within a plane).
    """
    n, f = bins.shape
    L, B = gp.num_leaves, gp.max_bin
    sp = gp.split
    ft = max(1, min(gp.lean_ft or f, f))
    n_tiles = -(-f // ft)

    use_pallas = H.pick_impl(gp.hist_impl) == "pallas"
    if not use_pallas:
        bins_T = None
    elif bins_T is None:
        with jax.named_scope("bins_T"):
            bins_T = bins.T
    # quantization mirrors hist_routed exactly (histogram.py:433-436): the
    # q8 kernel on the pallas path, per-row dequantized channels elsewhere —
    # so lean and default growers see the SAME histogram numbers per impl
    with jax.named_scope("front"):
        g, h, c, _, leaf_id0 = H.resident_rows(bins_T, n, L, g, h, c)
        quant = (H.make_quant(g, h, c, qseed, const_hess=gp.const_hess)
                 if gp.quant else None)
        if quant is not None and not use_pallas:
            gm, hm, cm = H.dequant_rows(quant)
        else:
            gm, hm, cm = g, h, c
    interp = jax.default_backend() == "cpu"

    def measure_tile(slot, n_slots, lo, hi):
        """[n_slots, 3, hi-lo, B] histograms of one feature tile, psum'd."""
        if quant is not None and use_pallas:
            from .pallas_hist import hist_pallas_q8
            hq, ch = H._q8_h_arg(quant)
            with jax.named_scope("hist"):
                ht = hist_pallas_q8(bins_T[lo:hi], quant.gq, hq, quant.cq,
                                    slot, n_slots, B, quant.scale_g,
                                    quant.scale_h, const_hess=ch,
                                    interpret=interp)
        else:
            ht = H.hist_per_leaf(bins[:, lo:hi], gm, hm, cm, slot, n_slots, B,
                                 gp.hist_impl,
                                 bins_T=bins_T[lo:hi] if bins_T is not None
                                 else None)
        return _psum(ht, gp)

    def tiled_search(slot, n_slots, sg, sh, sc, allow, lmin, lmax):
        """Best split per slot from feature-tiled passes."""
        best = None
        for t in range(n_tiles):
            lo, hi = t * ft, min(f, (t + 1) * ft)
            with jax.named_scope("route_hist"):
                hist_t = measure_tile(slot, n_slots, lo, hi)
            with jax.named_scope("split_search"):
                res_t = best_split(hist_t, num_bins[lo:hi], na_bin[lo:hi],
                                   sg, sh, sc, feature_mask[lo:hi],
                                   _tile_split_params(sp, lo, hi), allow,
                                   leaf_min=lmin, leaf_max=lmax,
                                   bundle=_slice_bundle(bundle, lo, hi))
                res_t = res_t._replace(
                    feature=res_t.feature + jnp.int32(lo))
                best = res_t if best is None else _fold_best(best, res_t)
        return best

    # ---- root ----
    with jax.named_scope("front"):
        zeros_slot = jnp.zeros_like(leaf_id0)
        # root stats from one tiny exact pass (leaf renewal needs them anyway)
        from .pallas_hist import leaf_sums_pallas
        if use_pallas:
            sums0 = _psum(leaf_sums_pallas(g, h, c, zeros_slot, 1,
                                           interpret=interp), gp)
            g0, h0, c0 = sums0[0, 0], sums0[1, 0], sums0[2, 0]
        else:
            g0, h0, c0 = (_psum(g.sum(), gp), _psum(h.sum(), gp),
                          _psum(c.sum(), gp))
        rec0 = tiled_search(zeros_slot, 1, g0[None], h0[None], c0[None],
                            jnp.ones(1, bool), jnp.full(1, -jnp.inf),
                            jnp.full(1, jnp.inf))

    def pad_rec(r1):
        """[1]-shaped root record -> [L] record arrays."""
        out = []
        for v in r1:
            shape = (L,) + v.shape[1:]
            base = jnp.full(shape, NEG_INF, v.dtype) \
                if v.dtype in (jnp.float32, jnp.float64) \
                else jnp.zeros(shape, v.dtype)
            out.append(base.at[0].set(v[0]))
        return SplitResult(*out)

    state = _LeanState(
        leaf_id=leaf_id0,
        rec=pad_rec(rec0),
        leaf_g=jnp.zeros(L).at[0].set(g0),
        leaf_h=jnp.zeros(L).at[0].set(h0),
        leaf_c=jnp.zeros(L).at[0].set(c0),
        active=jnp.zeros(L, bool).at[0].set(True),
        parent_node=jnp.full(L, -1, jnp.int32),
        parent_right=jnp.zeros(L, bool),
        leaf_min=jnp.full(L, -jnp.inf),
        leaf_max=jnp.full(L, jnp.inf),
        tree=_empty_tree(L, B),
    )
    root_w = leaf_output(g0, h0, sp)
    state = state._replace(tree=state.tree._replace(
        leaf_value=state.tree.leaf_value.at[0].set(root_w),
        leaf_weight=state.tree.leaf_weight.at[0].set(h0),
        leaf_count=state.tree.leaf_count.at[0].set(_rows(c0))))
    leaves_iota = jnp.arange(L, dtype=jnp.int32)

    def level(st: _LeanState, lvl, group):
        SLOTS, _, _, l_dec = group
        with jax.named_scope("split_search"):
            res = st.rec
            gain_gate = 0.0 if sp.has_contri \
                else float(max(sp.min_gain_to_split, 0.0))
            cand = st.active & (res.gain > gain_gate) & (res.gain > NEG_INF / 2)
            budget = L - st.tree.num_leaves
            key = jnp.where(cand, res.gain, -jnp.inf)
            kj, ki = key[None, :], key[:, None]
            better = (kj > ki) | ((kj == ki)
                                  & (leaves_iota[None, :] < leaves_iota[:, None]))
            rank = jnp.sum(better, axis=1).astype(jnp.int32)
            sel = cand & (rank < jnp.minimum(budget, SLOTS))
            num_sel = sel.sum().astype(jnp.int32)

        with jax.named_scope("apply_level"):
            idx_in_lvl = (jnp.cumsum(sel.astype(jnp.int32)) - 1).astype(jnp.int32)
            node_id = st.tree.num_leaves - 1 + idx_in_lvl
            new_leaf = st.tree.num_leaves + idx_in_lvl

            feat = res.feature
            lg, lh, lc = res.left_g, res.left_h, res.left_cnt
            rg, rh, rc = st.leaf_g - lg, st.leaf_h - lh, st.leaf_c - lc

            # ---- tree arrays (shared scatter helper) ----
            w_l = leaf_output(lg, lh, sp)
            w_r = leaf_output(rg, rh, sp)
            w_p = leaf_output(st.leaf_g, st.leaf_h, sp)
            if sp.has_monotone:
                w_l = jnp.clip(w_l, st.leaf_min, st.leaf_max)
                w_r = jnp.clip(w_r, st.leaf_min, st.leaf_max)
                w_p = jnp.clip(w_p, st.leaf_min, st.leaf_max)
            tr = _apply_level_to_tree(st.tree, st.parent_node, st.parent_right,
                                      res, sel, node_id, new_leaf, leaves_iota,
                                      lg, lh, lc, rg, rh, rc, w_l, w_r, w_p,
                                      num_sel)

        with jax.named_scope("route_hist"):
            # ---- route: BOTH children measured (slots 2i / 2i+1) ----
            S_pass = 2 * SLOTS
            tables = _level_tables(
                l_dec, sel, res, new_leaf,
                jnp.where(sel, idx_in_lvl * 2, S_pass),
                jnp.where(sel, idx_in_lvl * 2 + 1, S_pass), sp)
            slot, leaf_id2 = H.route_rows(bins, bins_T, st.leaf_id, tables,
                                          na_bin, S_pass, gp.hist_impl)

        with jax.named_scope("apply_level"):
            # ---- monotone bound propagation (shared helper) ----
            if sp.has_monotone:
                leaf_min2, leaf_max2 = _monotone_child_bounds(
                    sp, f, res, feat, sel, w_l, w_r, st.leaf_min, st.leaf_max,
                    leaves_iota, new_leaf)
            else:
                leaf_min2, leaf_max2 = st.leaf_min, st.leaf_max

            # ---- per-leaf stats / frontier update ----
            leaf_g2 = _scatter_set(_scatter_set(st.leaf_g, leaves_iota, lg, sel),
                                   new_leaf, rg, sel)
            leaf_h2 = _scatter_set(_scatter_set(st.leaf_h, leaves_iota, lh, sel),
                                   new_leaf, rh, sel)
            leaf_c2 = _scatter_set(_scatter_set(st.leaf_c, leaves_iota, lc, sel),
                                   new_leaf, rc, sel)
            active2 = _scatter_set(sel, new_leaf, jnp.ones(L, bool), sel)
            pn2 = _scatter_set(
                _scatter_set(st.parent_node, leaves_iota, node_id, sel),
                new_leaf, node_id, sel)
            pr2 = _scatter_set(
                _scatter_set(st.parent_right, leaves_iota,
                             jnp.zeros(L, bool), sel),
                new_leaf, jnp.ones(L, bool), sel)

        # ---- fresh records for the 2S children (feature-tiled search) ----
        # per-slot stats: slot 2i = left child of split i, 2i+1 = right
        leaf_of_slot_l = _scatter_set(jnp.full(SLOTS, _OOB, jnp.int32),
                                      idx_in_lvl, leaves_iota, sel)
        slot_leaf = jnp.stack(
            [leaf_of_slot_l,
             _scatter_set(jnp.full(SLOTS, _OOB, jnp.int32), idx_in_lvl,
                          new_leaf, sel)], axis=1).reshape(S_pass)
        slot_ok = slot_leaf < L
        safe_leaf = jnp.minimum(slot_leaf, L - 1)
        sgv = leaf_g2[safe_leaf]
        shv = leaf_h2[safe_leaf]
        scv = leaf_c2[safe_leaf]
        lminv = leaf_min2[safe_leaf]
        lmaxv = leaf_max2[safe_leaf]
        child_rec = tiled_search(slot, S_pass, sgv, shv, scv, slot_ok,
                                 lminv, lmaxv)

        rec2 = SplitResult(*[
            _scatter_set(rv, jnp.where(slot_ok, slot_leaf, _OOB), cv, slot_ok)
            for rv, cv in zip(st.rec, child_rec)])

        return _LeanState(
            leaf_id=leaf_id2, rec=rec2,
            leaf_g=leaf_g2, leaf_h=leaf_h2, leaf_c=leaf_c2,
            active=active2, parent_node=pn2, parent_right=pr2,
            leaf_min=leaf_min2, leaf_max=leaf_max2,
            tree=tr,
        ), num_sel

    state = _run_level_schedule(
        state, level, L, level_groups(L, gp.max_depth, use_pallas))

    if gp.quant:
        with jax.named_scope("leaf_renew"):
            # leaf renewal from EXACT sums (same epilogue as the default grower)
            eg, eh, ec = _leaf_sums_allreduce(
                leaf_sums_pallas(g, h, c, state.leaf_id, L,
                                 interpret=interp), gp)
            w = leaf_output(eg, eh, sp)
            if sp.has_monotone:
                w = jnp.clip(w, state.leaf_min, state.leaf_max)
            tr = state.tree
            live = jnp.arange(L) < tr.num_leaves
            state = state._replace(tree=tr._replace(
                leaf_value=jnp.where(live, w, tr.leaf_value),
                leaf_weight=jnp.where(live, eh, tr.leaf_weight),
                leaf_count=jnp.where(live, ec, tr.leaf_count)))
    return state.tree, state.leaf_id
