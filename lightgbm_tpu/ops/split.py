"""Vectorized best-split search over histograms.

TPU-native replacement for the reference's per-feature threshold scans
(FeatureHistogram::FindBestThresholdNumerical / FindBestThresholdSequence,
feature_histogram.hpp:92,527) and gain math (GetLeafSplitGain /
CalculateSplittedLeafOutput, feature_histogram.hpp:468-524).

Instead of a sequential scan per feature, the whole ``[F, B]`` gain surface is
computed at once: cumulative sums over the bin axis give left-side stats for every
threshold, both missing-direction variants are evaluated as two stacked planes, and a
single masked argmax picks the best (feature, bin, default_left) triple — so split
selection runs entirely on device (the reference's GPU learner ships histograms back
to the host for this step; we don't).

The search is natively BATCHED over a leading leaf axis ([L, 3, F, B] histograms
-> [L] split results, all ops whole-array) rather than vmapped per leaf: one
fused kernel over the whole frontier replaces L small latency-bound kernels.
Histograms are channel-major [3, F, B] (see ops/histogram.py layout rules).
"""
from __future__ import annotations

from dataclasses import dataclass, replace as dataclass_replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
# relative half-width of the split-gain tie band (~8 f32 ulps): candidates
# closer than this are "exactly equal" for election purposes and the lowest
# (feature, bin) index wins — see the tie-break note in best_split
TIE_RTOL = 1e-6


@dataclass(frozen=True)
class SplitParams:
    """Static split hyperparameters (subset of reference Config, config.h)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    max_delta_step: float = 0.0
    # categorical k-subset search (reference: FindBestThresholdCategorical,
    # feature_histogram.hpp:136-310). cat_features is the STATIC tuple of
    # categorical feature indices — empty tuple compiles the numerical-only
    # fast path with zero extra work
    cat_features: tuple = ()
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    # per-feature monotone constraints (-1/0/+1), STATIC tuple; empty = off
    # (reference: monotone_constraints.hpp ConstraintEntry + the direction
    # filter in FindBestThresholdSequence)
    monotone_constraints: tuple = ()
    # per-USED-COLUMN split-gain multipliers (reference: feature_contri,
    # dataset.cpp:394-400 feature_penalty_, applied to each feature's best
    # gain in FindBestThreshold, feature_histogram.hpp:89). STATIC tuple in
    # GROWER-column space (GBDT._contri_tuple maps original->used->bundle
    # columns and clamps at 0); empty = off.
    feature_contri: tuple = ()
    # EFB: bundled columns present (static flag; the BundleArrays data rides
    # along as a traced argument)
    has_bundles: bool = False
    # extremely-randomized trees (reference: extra_trees config.h:319 +
    # feature_histogram.hpp:99-102,253): each (leaf, feature) search
    # considers ONE random threshold — numerical unbundled candidates only,
    # like the reference (categorical keeps its full subset search). Needs
    # a ``rand_key`` operand at best_split call sites.
    extra_trees: bool = False
    extra_seed: int = 6
    # CEGB (reference: CostEfficientGradientBoosting,
    # cost_effective_gradient_boosting.hpp:26-45): per-candidate gain penalty
    # tradeoff*(penalty_split*n_leaf + coupled[f]*unused(f) + lazy on-demand
    # cost). The penalty VECTORS are traced (CEGBState); these static fields
    # gate compilation of the penalty planes.
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_coupled: bool = False
    cegb_lazy: bool = False

    # force-flags for feature-TILED search (grow_depthwise lean mode): a tile
    # whose monotone/contri slice is trivial must still apply the leaf-bound
    # clamp and the penalized-gain scale so candidates fold consistently
    # across tiles
    monotone_clamp: bool = False
    contri_active: bool = False

    @property
    def has_monotone(self) -> bool:
        return (any(m != 0 for m in self.monotone_constraints)
                or self.monotone_clamp)

    @property
    def has_contri(self) -> bool:
        return (any(c != 1.0 for c in self.feature_contri)
                or self.contri_active)

    def contri_array(self, f: int) -> np.ndarray:
        """[F] f32 gain multipliers in grower-column space: the registered
        tuple clamped at 0 (feature_penalty_, dataset.cpp:400) and padded
        with 1.0 to width f."""
        out = np.ones(f, dtype=np.float32)
        cvals = np.maximum(np.asarray(self.feature_contri, np.float32), 0.0)
        out[: len(cvals)] = cvals[:f]
        return out

    @property
    def has_cegb(self) -> bool:
        return (self.cegb_penalty_split > 0.0 or self.cegb_coupled
                or self.cegb_lazy)


class BundleArrays(NamedTuple):
    """Traced EFB decode arrays (built from efb.BundleMeta), all [F, B] except
    is_bundle [F]. See efb.py for the candidate identity."""
    range_start: jnp.ndarray
    range_end: jnp.ndarray
    prefix_end: jnp.ndarray
    incl_default: jnp.ndarray
    valid: jnp.ndarray
    is_bundle: jnp.ndarray


class SplitResult(NamedTuple):
    """Best split for one leaf (reference analog: SplitInfo, split_info.hpp:22).

    All fields are scalars (or share the batched leading dims of the input).
    For categorical subset splits (``is_cat``), ``cat_member`` [.., B] marks the
    bins routed LEFT (the reference's cat_threshold bitset, split_info.hpp:28)
    and ``bin`` holds the subset size - 1 (the reference's threshold index)."""
    gain: jnp.ndarray          # improvement: gain_l + gain_r - gain_parent; NEG_INF if none
    feature: jnp.ndarray       # i32
    bin: jnp.ndarray           # i32 threshold bin (go left if bin <= threshold)
    default_left: jnp.ndarray  # bool: missing values go left
    left_g: jnp.ndarray
    left_h: jnp.ndarray
    left_cnt: jnp.ndarray
    is_cat: jnp.ndarray        # bool
    cat_member: jnp.ndarray    # [.., B] bool (False rows for numerical splits)


def threshold_l1(s, l1):
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_g, sum_h, p: SplitParams):
    """Optimal leaf value (reference: CalculateSplittedLeafOutput,
    feature_histogram.hpp:468)."""
    w = -threshold_l1(sum_g, p.lambda_l1) / (sum_h + p.lambda_l2 + 1e-38)
    if p.max_delta_step > 0.0:
        w = jnp.clip(w, -p.max_delta_step, p.max_delta_step)
    return w


def leaf_gain_given_output(sum_g, sum_h, output, p: SplitParams):
    """Gain when the leaf output is fixed (clamped by monotone bounds) —
    reference: GetLeafSplitGainGivenOutput, feature_histogram.hpp:508."""
    sg = threshold_l1(sum_g, p.lambda_l1)
    return -(2.0 * sg * output + (sum_h + p.lambda_l2) * output * output)


def leaf_split_gain(sum_g, sum_h, p: SplitParams):
    """Gain contribution of a leaf (reference: GetLeafSplitGain,
    feature_histogram.hpp:485). No 1/2 factor, matching the reference so that
    ``min_gain_to_split`` has identical semantics."""
    sg = threshold_l1(sum_g, p.lambda_l1)
    if p.max_delta_step <= 0.0:
        return sg * sg / (sum_h + p.lambda_l2 + 1e-38)
    w = leaf_output(sum_g, sum_h, p)
    return -(2.0 * sg * w + (sum_h + p.lambda_l2) * w * w)


def per_feature_gains(hist: jnp.ndarray, num_bins: jnp.ndarray,
                      na_bin: jnp.ndarray, parent_g, parent_h, parent_cnt,
                      p: SplitParams) -> jnp.ndarray:
    """Per-feature best numerical gain [.., F] — the voting score for the
    voting-parallel learner (reference: LightSplitInfo gains fed to
    GlobalVoting, voting_parallel_tree_learner.cpp:170). Numerical planes
    only: votes are a heuristic pre-filter, not the final split search."""
    batch_shape = hist.shape[:-3]
    _, f, b = hist.shape[-3:]
    L = 1
    for d in batch_shape:
        L *= d
    h3 = hist.reshape(L, 3, f, b)
    pg = jnp.broadcast_to(jnp.asarray(parent_g, jnp.float32), batch_shape).reshape(L)
    ph = jnp.broadcast_to(jnp.asarray(parent_h, jnp.float32), batch_shape).reshape(L)
    pc = jnp.broadcast_to(jnp.asarray(parent_cnt, jnp.float32), batch_shape).reshape(L)
    iota = jnp.arange(b, dtype=jnp.int32)[None, None, :]
    na = na_bin[None, :, None]
    na_sel = iota == na
    cum = jnp.cumsum(jnp.where(na_sel[:, None, :, :], 0.0, h3), axis=3)
    lg, lh, lc = cum[:, 0], cum[:, 1], cum[:, 2]
    rg = pg[:, None, None] - lg
    rh = ph[:, None, None] - lh
    rc = pc[:, None, None] - lc
    ok = ((lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
          & (lh >= p.min_sum_hessian_in_leaf)
          & (rh >= p.min_sum_hessian_in_leaf)
          & (iota < num_bins[None, :, None] - 1) & (~na_sel))
    gain = leaf_split_gain(lg, lh, p) + leaf_split_gain(rg, rh, p)
    gain = jnp.where(ok, gain, NEG_INF)
    best = gain.max(axis=-1)                                     # [L, F]
    if p.has_contri:
        # keep the vote ranking consistent with the penalized final search
        parent = leaf_split_gain(pg, ph, p)                      # [L]
        best = p.contri_array(f)[None, :] * (best - parent[:, None]
                                             - p.min_gain_to_split)
    return best.reshape(batch_shape + (f,))


def best_split(hist: jnp.ndarray, num_bins: jnp.ndarray, na_bin: jnp.ndarray,
               parent_g, parent_h, parent_cnt,
               feature_mask: jnp.ndarray, p: SplitParams,
               allow_split=True, leaf_min=None, leaf_max=None,
               bundle=None, gain_penalty=None, rand_key=None) -> SplitResult:
    """Find the best split for one leaf or a whole frontier of leaves.

    hist: [..., 3, F, B] channel-major (grad, hess, count); num_bins: [F] i32
    actual bins per feature; na_bin: [F] i32 missing-bin index (or >= B if
    none).  The hess channel is ALWAYS materialized here even when the q8
    kernels elide it (const-hessian): the histogram epilogue reconstructs h
    as ``hess_scale * count`` before this function sees the array, so split
    evaluation is variant-agnostic (ops/pallas_hist._dequant_stack).
    feature_mask: [F] bool, or per-leaf [*batch, F] bool (voting mode:
    each frontier leaf may only search features its stored histogram holds);
    parent_g/h/cnt and allow_split broadcast over the leading batch dims.
    ``gain_penalty``: optional [*batch, F] f32 subtracted from every candidate
    gain of that (leaf, feature) — the CEGB delta (DetlaGain,
    cost_effective_gradient_boosting.hpp:51-62).
    """
    batch_shape = hist.shape[:-3]
    _, f, b = hist.shape[-3:]
    L = 1
    for d in batch_shape:
        L *= d
    h3 = hist.reshape(L, 3, f, b)
    # normalize the feature mask to per-leaf [L, F, 1]
    fm_lf = (jnp.broadcast_to(feature_mask, batch_shape + (f,)).reshape(L, f)
             if feature_mask.ndim > 1 else
             jnp.broadcast_to(feature_mask[None, :], (L, f)))
    fm3 = fm_lf[:, :, None]                                       # [L, F, 1]
    pg = jnp.broadcast_to(jnp.asarray(parent_g, jnp.float32), batch_shape).reshape(L)
    ph = jnp.broadcast_to(jnp.asarray(parent_h, jnp.float32), batch_shape).reshape(L)
    pc = jnp.broadcast_to(jnp.asarray(parent_cnt, jnp.float32), batch_shape).reshape(L)
    allow = jnp.broadcast_to(jnp.asarray(allow_split, bool), batch_shape).reshape(L)
    if p.has_monotone:
        lmin = (jnp.broadcast_to(jnp.asarray(leaf_min, jnp.float32), batch_shape)
                .reshape(L, 1, 1) if leaf_min is not None
                else jnp.full((L, 1, 1), -jnp.inf))
        lmax = (jnp.broadcast_to(jnp.asarray(leaf_max, jnp.float32), batch_shape)
                .reshape(L, 1, 1) if leaf_max is not None
                else jnp.full((L, 1, 1), jnp.inf))
        mono = np.zeros(f, dtype=np.int32)
        mono[: len(p.monotone_constraints)] = p.monotone_constraints[:f]
        mono_dev = jnp.asarray(mono)[None, :, None]

    iota = jnp.arange(b, dtype=jnp.int32)[None, None, :]          # [1, 1, B]
    na = na_bin[None, :, None]                                    # [1, F, 1]

    # stats of the missing bin, excluded from the ordered scan and attached
    # wholly to one side (reference scans both directions for the same effect,
    # feature_histogram.hpp:527+)
    na_sel = (iota == na)                                         # [1, F, B]
    na_stats = jnp.sum(jnp.where(na_sel[:, None, :, :], h3, 0.0), axis=3)  # [L,3,F]
    cum = jnp.cumsum(jnp.where(na_sel[:, None, :, :], 0.0, h3), axis=3)    # [L,3,F,B]

    def gains_of(left_shift):
        """left_shift: [L,3,F,1] added to cum (the missing-left variant)."""
        lg = cum[:, 0] + left_shift[:, 0]
        lh = cum[:, 1] + left_shift[:, 1]
        lc = cum[:, 2] + left_shift[:, 2]
        rg = pg[:, None, None] - lg
        rh = ph[:, None, None] - lh
        rc = pc[:, None, None] - lc
        ok = ((lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
              & (lh >= p.min_sum_hessian_in_leaf)
              & (rh >= p.min_sum_hessian_in_leaf))
        if p.has_monotone:
            # clamped-output gains + direction filter (reference:
            # GetSplitGains w/ ConstraintEntry, feature_histogram.hpp:435-466)
            wl = jnp.clip(leaf_output(lg, lh, p), lmin, lmax)
            wr = jnp.clip(leaf_output(rg, rh, p), lmin, lmax)
            gain = (leaf_gain_given_output(lg, lh, wl, p)
                    + leaf_gain_given_output(rg, rh, wr, p))
            viol = (((mono_dev > 0) & (wl > wr))
                    | ((mono_dev < 0) & (wl < wr)))
            ok = ok & ~viol
        else:
            gain = leaf_split_gain(lg, lh, p) + leaf_split_gain(rg, rh, p)
        return jnp.where(ok, gain, NEG_INF)

    zeros = jnp.zeros((L, 3, f, 1), jnp.float32)
    gain_r = gains_of(zeros)                                     # missing -> right
    gain_l = gains_of(na_stats[..., None])                       # missing -> left

    cat_mask_f = np.zeros(f, dtype=bool)
    for ci in p.cat_features:
        if 0 <= ci < f:
            cat_mask_f[ci] = True
    cat_mask_dev = jnp.asarray(cat_mask_f)

    valid_t = (iota < num_bins[None, :, None] - 1) & (~na_sel) \
        & fm3 & (~cat_mask_dev)[None, :, None]
    if p.has_bundles and bundle is not None:
        valid_t = valid_t & (~bundle.is_bundle)[None, :, None]
    if p.extra_trees and rand_key is not None:
        # extra_trees: only one random threshold per (leaf, feature)
        # competes (reference draws rand_threshold per search and skips
        # every other i, feature_histogram.hpp:253). A draw landing on the
        # missing bin leaves that (leaf, feature) without a candidate this
        # search — same effect as the reference's rand index falling on a
        # skipped position.
        u = jax.random.uniform(rand_key, (L, f))
        rnd = jnp.floor(u * jnp.maximum(num_bins[None, :] - 1, 1)) \
            .astype(jnp.int32)
        rnd = jnp.minimum(rnd, num_bins[None, :] - 2)
        valid_t = valid_t & (iota == rnd[:, :, None])
    has_na = na < b
    gain_r = jnp.where(valid_t, gain_r, NEG_INF)
    gain_l = jnp.where(valid_t & has_na, gain_l, NEG_INF)

    # feature_contri: the reference multiplies each feature's best gain —
    # which is stored as (improvement - min_gain_shift) — by the per-feature
    # penalty BEFORE the cross-feature comparison (feature_histogram.hpp:89
    # output->gain *= meta_->penalty, with gain = best - min_gain_shift from
    # FindBestThresholdSequence). So in contri mode every candidate plane is
    # rewritten to penalized improvement: contri_f * (gain - parent - min_gain)
    # and the final argmax/threshold operate on that directly.
    parent_gain = leaf_split_gain(pg, ph, p)                      # [L]
    contri_dev = None
    if p.has_contri:
        contri_dev = jnp.asarray(p.contri_array(f))
        shift = (parent_gain + p.min_gain_to_split)[:, None, None]  # [L,1,1]
        gain_r = contri_dev[None, :, None] * (gain_r - shift)
        gain_l = contri_dev[None, :, None] * (gain_l - shift)

    pen_lf = None
    if gain_penalty is not None:
        pen_lf = (jnp.broadcast_to(gain_penalty, batch_shape + (f,))
                  .reshape(L, f).astype(jnp.float32))
        gain_r = gain_r - pen_lf[:, :, None]
        gain_l = gain_l - pen_lf[:, :, None]

    sections = [gain_r.reshape(L, f * b), gain_l.reshape(L, f * b)]

    # ---- categorical subset planes (reference: FindBestThresholdCategorical,
    # feature_histogram.hpp:136-310) ----
    if p.cat_features:
        cat_idx = np.asarray(sorted(set(ci for ci in p.cat_features
                                        if 0 <= ci < f)), dtype=np.int32)
        fc = len(cat_idx)
        hcat = h3[:, :, cat_idx, :]                              # [L, 3, Fc, B]
        gch, hch, cch = hcat[:, 0], hcat[:, 1], hcat[:, 2]       # [L, Fc, B]
        nb_c = num_bins[cat_idx][None, :, None]                  # [1, Fc, 1]
        iota_c = jnp.arange(b, dtype=jnp.int32)[None, None, :]
        fm_c = fm_lf[:, cat_idx][:, :, None]                     # [L, Fc, 1]
        # bin 0 is the other/missing bin (binning.py): always routed RIGHT so
        # exported bitsets stay exact (reference: NaN/unseen -> right,
        # tree.h CategoricalDecision)
        in_range = (iota_c >= 1) & (iota_c < nb_c)

        # --- one-hot scan (num_bins <= max_cat_to_onehot; l2 unchanged) ---
        oh_allowed = (nb_c <= p.max_cat_to_onehot) & fm_c & in_range
        rg_oh, rh_oh, rc_oh = (pg[:, None, None] - gch,
                               ph[:, None, None] - hch,
                               pc[:, None, None] - cch)
        ok_oh = ((cch >= p.min_data_in_leaf) & (rc_oh >= p.min_data_in_leaf)
                 & (hch >= p.min_sum_hessian_in_leaf)
                 & (rh_oh >= p.min_sum_hessian_in_leaf))
        gain_oh = leaf_split_gain(gch, hch, p) + leaf_split_gain(rg_oh, rh_oh, p)
        gain_oh = jnp.where(ok_oh & oh_allowed, gain_oh, NEG_INF)

        # --- sorted k-subset scan ---
        pc2 = dataclass_replace(p, lambda_l2=p.lambda_l2 + p.cat_l2)
        subset_allowed = (nb_c > p.max_cat_to_onehot) & fm_c
        svalid = in_range & (cch >= p.cat_smooth)                # [L, Fc, B]
        mean = jnp.where(svalid, gch / (hch + p.cat_smooth), jnp.inf)
        # stable ascending rank without sort (invalid bins rank last)
        mi = mean[..., :, None]                                  # [L,Fc,B,1]
        mj = mean[..., None, :]                                  # [L,Fc,1,B]
        ii = jnp.arange(b)[:, None]
        jj = jnp.arange(b)[None, :]
        less = (mj < mi) | ((mj == mi) & (jj < ii))              # [L,Fc,B,B]
        rank = jnp.sum(jnp.where(less, 1, 0), axis=-1)           # [L,Fc,B]
        rank = jnp.where(svalid, rank, b + 1)
        used = jnp.sum(svalid, axis=-1)                          # [L, Fc]
        # sort by scattering each bin's stats to its rank position (a [B, B]
        # rank one-hot contraction — no [B(k), B(i)] prefix matrices, which
        # would be 2GB at B=256), then prefix sums along the sorted axis
        pos = jnp.arange(b)[None, None, None, :]
        oh_rank = (rank[..., :, None] == pos).astype(jnp.float32)  # [L,Fc,B,B]
        sg = jnp.einsum("lfip,lfi->lfp", oh_rank, jnp.where(svalid, gch, 0.0))
        sh = jnp.einsum("lfip,lfi->lfp", oh_rank, jnp.where(svalid, hch, 0.0))
        sc = jnp.einsum("lfip,lfi->lfp", oh_rank, jnp.where(svalid, cch, 0.0))
        cum_g = jnp.cumsum(sg, axis=-1)   # index k = ascending prefix len k+1
        cum_h = jnp.cumsum(sh, axis=-1)
        cum_c = jnp.cumsum(sc, axis=-1)
        tot_g = cum_g[..., -1:]
        tot_h = cum_h[..., -1:]
        tot_c = cum_c[..., -1:]

        def desc_prefix(cum, tot):
            # descending prefix len k+1 = total(valid) - asc prefix(used-k-2)
            kidx = jnp.arange(b)[None, None, :]
            j = used[..., None] - kidx - 2
            gathered = jnp.take_along_axis(cum, jnp.clip(j, 0, b - 1), axis=-1)
            return tot - jnp.where(j >= 0, gathered, 0.0)

        def subset_gains(lg, lh, lc):
            rg_, rh_, rc_ = (pg[:, None, None] - lg, ph[:, None, None] - lh,
                             pc[:, None, None] - lc)
            max_num_cat = jnp.minimum(p.max_cat_threshold,
                                      (used[..., None] + 1) // 2)
            kidx = jnp.arange(b)[None, None, :]
            ok = ((kidx < jnp.minimum(max_num_cat, used[..., None]))
                  & (lc >= p.min_data_in_leaf) & (rc_ >= p.min_data_in_leaf)
                  & (rc_ >= p.min_data_per_group)
                  & (lh >= p.min_sum_hessian_in_leaf)
                  & (rh_ >= p.min_sum_hessian_in_leaf) & subset_allowed)
            gain = leaf_split_gain(lg, lh, pc2) + leaf_split_gain(rg_, rh_, pc2)
            return jnp.where(ok, gain, NEG_INF)

        asc = (cum_g, cum_h, cum_c)
        desc = (desc_prefix(cum_g, tot_g), desc_prefix(cum_h, tot_h),
                desc_prefix(cum_c, tot_c))
        gain_asc = subset_gains(*asc)
        gain_desc = subset_gains(*desc)
        left_asc, left_desc = asc, desc
        if contri_dev is not None:
            cc = contri_dev[jnp.asarray(cat_idx)][None, :, None]
            gain_oh = cc * (gain_oh - shift)
            gain_asc = cc * (gain_asc - shift)
            gain_desc = cc * (gain_desc - shift)
        if pen_lf is not None:
            pen_c = pen_lf[:, cat_idx][:, :, None]
            gain_oh = gain_oh - pen_c
            gain_asc = gain_asc - pen_c
            gain_desc = gain_desc - pen_c
        sections += [gain_oh.reshape(L, fc * b), gain_asc.reshape(L, fc * b),
                     gain_desc.reshape(L, fc * b)]

    # ---- EFB virtual-feature plane (efb.py candidate identity) ----
    if p.has_bundles and bundle is not None:
        pe1 = bundle.prefix_end[None, None, :, :]              # [1,1,F,B]

        def at(pos):
            """cum[l, c, f, pos[f, j]]: the position tables are per column,
            not per leaf, so the lookup is a one-hot contraction over the
            bin axis (exact: one term of each sum is not zero), where a
            gather over [L, 3, F, B] walks the frontier element by element
            (64 to 129 ms a level pass on the v5e: PERF.md section 6, PR 34)."""
            sel = (pos[:, :, None] == jnp.arange(b, dtype=pos.dtype))
            return jnp.einsum("lcfb,fjb->lcfj", cum, sel.astype(cum.dtype),
                              precision=jax.lax.Precision.HIGHEST)
        cum_start = at(jnp.maximum(bundle.range_start - 1, 0))
        cum_end = at(bundle.range_end)
        cum_pe = at(jnp.maximum(bundle.prefix_end, 0))
        # prefix_end == range_start-1 encodes the empty prefix (t == default
        # with default bin 0): gather clamps to a valid index, mask to zero
        prefix = jnp.where((pe1 >= bundle.range_start[None, None, :, :]),
                           cum_pe - cum_start, 0.0)
        rng_tot = cum_end - cum_start
        incl = bundle.incl_default[None, :, :].astype(jnp.float32)
        par = jnp.stack([pg, ph, pc], axis=1)[:, :, None, None]  # [L,3,1,1]
        lB = prefix + incl[:, None, :, :] * (par - rng_tot)
        lgB, lhB, lcB = lB[:, 0], lB[:, 1], lB[:, 2]
        rgB = pg[:, None, None] - lgB
        rhB = ph[:, None, None] - lhB
        rcB = pc[:, None, None] - lcB
        okB = ((lcB >= p.min_data_in_leaf) & (rcB >= p.min_data_in_leaf)
               & (lhB >= p.min_sum_hessian_in_leaf)
               & (rhB >= p.min_sum_hessian_in_leaf)
               & bundle.valid[None, :, :] & bundle.is_bundle[None, :, None]
               & fm3)
        if p.has_monotone:
            # bundled features are never themselves monotone-constrained
            # (Dataset excludes them from bundling), but the LEAF's output
            # bounds still apply to any split of a constrained leaf
            wlB = jnp.clip(leaf_output(lgB, lhB, p), lmin, lmax)
            wrB = jnp.clip(leaf_output(rgB, rhB, p), lmin, lmax)
            gainB = (leaf_gain_given_output(lgB, lhB, wlB, p)
                     + leaf_gain_given_output(rgB, rhB, wrB, p))
        else:
            gainB = leaf_split_gain(lgB, lhB, p) + leaf_split_gain(rgB, rhB, p)
        gainB = jnp.where(okB, gainB, NEG_INF)
        if contri_dev is not None:
            # bundle columns carry their mapped contri (single-member columns:
            # the member's value; merged: 1.0 — see GBDT._contri_tuple)
            gainB = contri_dev[None, :, None] * (gainB - shift)
        if pen_lf is not None:
            gainB = gainB - pen_lf[:, :, None]
        sections.append(gainB.reshape(L, f * b))

    gains = jnp.concatenate(sections, axis=1)
    # deterministic tie-break: the winner is the LOWEST flat index whose gain
    # is within a few-ulp band of the max, not argmax of the raw surface.
    # Serial row-order accumulation and the data-parallel psum reduce the
    # same histogram partial sums in different orders, so two mathematically
    # tied candidates land 1-2 f32 ulps apart with the sign of the gap
    # depending on the reduction tree — a raw argmax then elects the
    # neighboring bin on one side and not the other. The band is relative to
    # the larger of |best| and |parent gain| (penalized planes like CEGB are
    # small differences of parent-scale quantities, so noise scales with the
    # parent, not the residual gain).
    best_raw = gains.max(axis=1)                                  # [L]
    tie_scale = jnp.maximum(jnp.maximum(jnp.abs(best_raw),
                                        jnp.abs(parent_gain)), 1.0)
    near = gains >= (best_raw - TIE_RTOL * tie_scale)[:, None]
    kidx_flat = jnp.arange(gains.shape[1], dtype=jnp.int32)[None, :]
    flat = jnp.min(jnp.where(near, kidx_flat, gains.shape[1]), axis=1)
    flat = jnp.minimum(flat, gains.shape[1] - 1)
    best_gain = jnp.take_along_axis(gains, flat[:, None], axis=1)[:, 0]
    d = flat // (f * b)                # 0/1 numerical planes; >= 2 categorical
    rem = flat % (f * b)
    feat = (rem // b).astype(jnp.int32)
    tbin = (rem % b).astype(jnp.int32)

    lidx = jnp.arange(L)

    def pick(chan):
        base = cum[lidx, chan, feat, tbin]
        return base + jnp.where(d == 1, na_stats[lidx, chan, feat], 0.0)

    left_g_, left_h_, left_c_ = pick(0), pick(1), pick(2)
    is_cat_res = jnp.zeros(L, dtype=bool)
    cat_member = jnp.zeros((L, b), dtype=bool)

    n_num = 2 * f * b
    n_cat = 3 * fc * b if p.cat_features else 0
    if p.cat_features:
        num_flat = n_num
        cflat = jnp.maximum(flat - num_flat, 0)      # index into the cat planes
        plane = jnp.clip(cflat // (fc * b), 0, 2)
        crem = cflat % (fc * b)
        cf = (crem // b).astype(jnp.int32)           # winning cat-feature index
        ck = (crem % b).astype(jnp.int32)            # bin (onehot) / prefix k
        is_cat_res = (flat >= num_flat) & (flat < num_flat + n_cat)
        feat = jnp.where(is_cat_res, jnp.asarray(cat_idx)[cf], feat)
        tbin = jnp.where(is_cat_res, ck, tbin)

        rank_w = rank[lidx, cf]                      # [L, B]
        used_w = used[lidx, cf][:, None]
        iota_b2 = jnp.arange(b)[None, :]
        mem_oh = iota_b2 == ck[:, None]
        mem_asc = rank_w <= ck[:, None]
        mem_desc = (rank_w >= used_w - ck[:, None] - 1) & (rank_w <= b)
        cat_member = jnp.where(
            is_cat_res[:, None],
            jnp.where((plane == 0)[:, None], mem_oh,
                      jnp.where((plane == 1)[:, None], mem_asc, mem_desc)),
            cat_member)

        def cpick(tbl_asc, tbl_desc, oh_src):
            asc = tbl_asc[lidx, cf, ck]
            desc = tbl_desc[lidx, cf, ck]
            ohv = oh_src[lidx, cf, ck]
            return jnp.where(plane == 0, ohv, jnp.where(plane == 1, asc, desc))

        left_g_ = jnp.where(is_cat_res,
                            cpick(left_asc[0], left_desc[0], gch), left_g_)
        left_h_ = jnp.where(is_cat_res,
                            cpick(left_asc[1], left_desc[1], hch), left_h_)
        left_c_ = jnp.where(is_cat_res,
                            cpick(left_asc[2], left_desc[2], cch), left_c_)

    if p.has_bundles and bundle is not None:
        # ---- EFB winner decode: routes as a bin-subset mask over the bundle
        # column (decoded to the original feature at tree finalization) ----
        bundle_base = n_num + n_cat
        bflat = jnp.maximum(flat - bundle_base, 0)
        bf = (bflat // b).astype(jnp.int32)
        bp = (bflat % b).astype(jnp.int32)
        is_bun = flat >= bundle_base
        feat = jnp.where(is_bun, bf, feat)
        tbin = jnp.where(is_bun, bp, tbin)
        start_w = bundle.range_start[bf, bp]
        end_w = bundle.range_end[bf, bp]
        pe_w = bundle.prefix_end[bf, bp]
        incl_w = bundle.incl_default[bf, bp]
        iota_b3 = jnp.arange(b)[None, :]
        mem_b = ((iota_b3 >= start_w[:, None]) & (iota_b3 <= pe_w[:, None])) \
            | (incl_w[:, None] & ((iota_b3 < start_w[:, None])
                                  | (iota_b3 > end_w[:, None])))
        is_cat_res = is_cat_res | is_bun
        cat_member = jnp.where(is_bun[:, None], mem_b, cat_member)
        left_g_ = jnp.where(is_bun, lgB[lidx, bf, bp], left_g_)
        left_h_ = jnp.where(is_bun, lhB[lidx, bf, bp], left_h_)
        left_c_ = jnp.where(is_bun, lcB[lidx, bf, bp], left_c_)

    if p.has_contri:
        # planes already hold contri * (improvement - min_gain); a masked
        # candidate can never win (it is <= 0 after the transform) so the
        # positivity check alone gates splitting (serial_tree_learner.cpp:184
        # best_split_info.gain <= 0 stop, on penalized gains)
        improvement = best_gain
        found = allow & (improvement > 0.0)
    else:
        improvement = best_gain - parent_gain
        found = allow & (best_gain > NEG_INF / 2) \
            & (improvement > p.min_gain_to_split) & (improvement > 0.0)

    res = SplitResult(
        gain=jnp.where(found, improvement, NEG_INF),
        feature=feat,
        bin=tbin,
        default_left=(d == 1) & ~is_cat_res,
        left_g=left_g_, left_h=left_h_, left_cnt=left_c_,
        is_cat=is_cat_res,
        cat_member=cat_member & is_cat_res[:, None],
    )
    return SplitResult(*[
        v.reshape(batch_shape + v.shape[1:] if v.ndim > 1 else batch_shape)
        for v in res])
