"""Histogram construction kernels.

TPU-native replacement for the reference's histogram machinery: the CPU hot loop
``DenseBin::ConstructHistogramInner`` (dense_bin.hpp:77-105), the row-wise multi-val
path (multi_val_dense_bin.hpp:17) and the three OpenCL kernels
(src/treelearner/ocl/histogram{16,64,256}.cl) all collapse into a small set of
XLA/Pallas formulations over a dense ``[N, F]`` uint8 bin matrix:

- ``onehot``: tiled one-hot expansion contracted against the (grad, hess, count)
  channels on the MXU — no atomics needed (TPU has none), bandwidth-friendly tiles.
- ``pallas``: hand-written Pallas kernel (pallas_hist.py) building the one-hot
  directly in [F*B, T] lane layout from a transposed bin matrix — no expansion
  matmul, accumulators resident in VMEM.
- ``scatter``: XLA scatter-add (fast on CPU backends, used for tests / small data).

Layout rules (learned the hard way):
- histograms are CHANNEL-MAJOR ``[..., 3, F, B]`` — a channels-minor [..., F, B, 3]
  array tiles its 3-lane minor dim to 128 lanes, a 42x HBM blowup that dominated
  whole-tree cost in round 1/2 profiling;
- gradient/hessian/count row channels are SEPARATE 1-D [N] arrays, never [N, C];
- all per-row intermediates live inside the row-tile scan body (fused, VMEM-sized);
- the only full-size arrays ever materialized are the uint8 bin matrices.

All histograms carry 3 channels: sum_grad, sum_hess, count (the reference packs
(grad, hess) f64 pairs, bin.h:32-34; count is carried explicitly here because
bagging is mask-based on TPU instead of index-subset based).

The choice between implementations mirrors the reference's empirical col-wise vs
row-wise auto-tune (``Dataset::TestMultiThreadingMethod``, dataset.cpp:640-715): see
``pick_impl``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_DEF_TILE = 4096


def _pad_1d(x: jnp.ndarray, mult: int, value=0):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=value)
    return x


def bin_axis(max_num_bins: int) -> int:
    """The growers' bin axis B (GrowParams.max_bin) for a dataset's widest
    feature: padded to a lane-friendly width, since a non-aligned
    [T, F, B] -> [T, F*B] reshape forces a relayout copy every histogram
    tile (measured 2.2x slower at B=63 vs B=64 on v5e)."""
    return 64 if max_num_bins <= 64 else (128 if max_num_bins <= 128 else 256)


def resident_rows(bins_T, n: int, num_leaves: int, g, h, c, fused=None):
    """Bring a grower's [N] row vectors, once a tree, to the columns of the
    transposed matrix its kernels will read in every pass: (g, h, c, fused,
    leaf_id), each n_res = bins_T.shape[1] long.

    Dataset.bins_T is [F_pad, N_pad] (pallas_hist.resident_shape): next to
    it every kernel wrapper's _pad_rows and [:n] is a no-op only if each row
    vector has N_pad entries too, from the first pass to the last, so all
    three growers take theirs from here. Rows past N carry what the wrappers
    pad with: zero in every channel (score 0, label 0, bag 0 in ``fused``,
    the fused front's (score, aux, bag)) and, in the tree's first
    ``leaf_id``, ``num_leaves`` where a row of the table starts at 0: no
    leaf to the split-table decode, the leaf sums and take_small. They
    reach no histogram, count or sum, and the growers return their leaf ids
    n_res long. With an [F, N] matrix (bins.T built in the grower, a
    caller's own) or none (not the Pallas path) nothing is padded."""
    n_res = n if bins_T is None else bins_T.shape[1]
    pad = n_res - n

    def rows(x):
        return jnp.pad(x, (0, pad)) if pad else x

    return (rows(g), rows(h), rows(c),
            None if fused is None else tuple(rows(x) for x in fused),
            jnp.where(jnp.arange(n_res) < n, 0, num_leaves).astype(jnp.int32))


def _split_hi_lo_tile(g: jnp.ndarray, h: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Stack f32 [T] channels into a [T, 6] bf16 (hi, lo) tile.

    The MXU runs bf16 natively; multiplying a bf16 value by an exact {0,1}
    one-hot and accumulating in f32 loses nothing, so hi+lo recovers ~f32
    accuracy (the reference accumulates f64 pairs, bin.h:32-34; GPU docs show
    f32 suffices, docs/GPU-Performance.rst:129-137 — bf16 alone does not)."""
    ghc = jnp.stack([g, h, c], axis=1)
    hi = ghc.astype(jnp.bfloat16)
    lo = (ghc - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, lo], axis=-1)          # [T, 6]


def _expand_onehot_2d(bins_t: jnp.ndarray, f: int, b: int) -> jnp.ndarray:
    """One-hot bin expansion built entirely in 2D lane layout: [T, F] -> [T, F*B].

    A naive ``(bins[:, :, None] == iota).reshape(T, F*B)`` makes XLA tile the
    intermediate as a [.., F, B] array (lane dim B, padded to 128) and then pay a
    relayout copy for the reshape. Instead the feature value is broadcast to its
    B-lane group with a constant selector matmul (exact: bin ids <= 255 are
    integers, exactly representable in bf16) and compared against a lane-indexed
    bin id, so no minor-dim reshape ever happens."""
    lane = jnp.arange(f * b, dtype=jnp.int32)
    sel = (lane[None, :] // b == jnp.arange(f, dtype=jnp.int32)[:, None])
    sel = sel.astype(jnp.bfloat16)                       # [F, F*B] constant
    bin_of_lane = (lane % b).astype(jnp.float32)         # [F*B]
    bv = jax.lax.dot_general(
        bins_t.astype(jnp.bfloat16), sel,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [T, F*B]
    return (bv == bin_of_lane[None, :]).astype(jnp.bfloat16)


def _hi_lo_combine(hist: jnp.ndarray, f: int, b: int, l: int) -> jnp.ndarray:
    """[F*B, L*6] accumulator -> [L, 3, F, B] f32 (hi+lo recombined,
    channel-major output layout)."""
    hist = hist.reshape(f, b, l, 2, 3).sum(axis=3).transpose(2, 3, 0, 1)
    return hist.astype(jnp.float32)


class RouteTables(NamedTuple):
    """Per-leaf split routing tables for one depthwise level, all [L] i32.

    ``feat < 0`` means the leaf does not split this level. ``slot_left/right``
    give the histogram slot the row lands in after routing (or the out-of-range
    sentinel when that child is the larger sibling, reconstructed by
    subtraction)."""
    feat: jnp.ndarray
    thr: jnp.ndarray
    dleft: jnp.ndarray       # 1 if missing goes left
    new_leaf: jnp.ndarray    # leaf id of the right child
    slot_left: jnp.ndarray
    slot_right: jnp.ndarray
    # categorical subset decisions (reference: CategoricalDecision, tree.h:279):
    # is_cat [L] i32 flags, member [L, B] f32 0/1 bin membership (member -> LEFT)
    is_cat: Optional[jnp.ndarray] = None
    member: Optional[jnp.ndarray] = None


# ---------------------------------------------------------------------------
# onehot (MXU) implementations
# ---------------------------------------------------------------------------

def hist_leaf_onehot(bins, g, h, c, num_bins: int, tile: int = _DEF_TILE,
                     acc_dtype=jnp.float32) -> jnp.ndarray:
    """Histogram of one row-subset: ``bins`` [N, F] uint8; g/h/c [N] f32
    (grad, hess, count — already masked: excluded rows have all-zero channels).

    Returns [3, F, B] float32.
    """
    n, f = bins.shape
    b = num_bins
    bins = _pad_1d(bins, tile)
    g, h, c = (_pad_1d(x, tile) for x in (g, h, c))
    n_tiles = bins.shape[0] // tile
    bins_t = bins.reshape(n_tiles, tile, f)
    g_t = g.reshape(n_tiles, tile)
    h_t = h.reshape(n_tiles, tile)
    c_t = c.reshape(n_tiles, tile)

    def step(carry, xs):
        bt, gt, ht, ct = xs
        onehot = _expand_onehot_2d(bt, f, b)
        ghc = _split_hi_lo_tile(gt, ht, ct)
        part = jax.lax.dot_general(
            onehot, ghc,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)
        return carry + part, None

    init = jnp.zeros((f * b, 6), dtype=acc_dtype)
    hist, _ = jax.lax.scan(step, init, (bins_t, g_t, h_t, c_t))
    return _hi_lo_combine(hist, f, b, 1)[0]             # [3, F, B]


def _leaf_weight_2d(lt: jnp.ndarray, ghc6: jnp.ndarray, l: int) -> jnp.ndarray:
    """Build w[t, s*6+c] = (lt[t]==s) * ghc6[t, c] without a [T, L, 6] reshape."""
    lane = jnp.arange(l * 6, dtype=jnp.int32)
    selc = (lane[None, :] % 6 == jnp.arange(6, dtype=jnp.int32)[:, None])
    selc = selc.astype(jnp.bfloat16)                     # [6, L*6] constant
    leaf_of_lane = lane // 6                             # [L*6]
    gexp = jax.lax.dot_general(
        ghc6, selc, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [T, L*6]
    return jnp.where(lt[:, None] == leaf_of_lane[None, :],
                     gexp, 0.0).astype(jnp.bfloat16)     # exact


def hist_per_leaf_onehot(bins, g, h, c, leaf_id, num_leaves: int, num_bins: int,
                         tile: int = _DEF_TILE, acc_dtype=jnp.float32) -> jnp.ndarray:
    """Per-leaf histograms in one data pass. Returns [L, 3, F, B] f32."""
    n, f = bins.shape
    b, l = num_bins, num_leaves
    bins = _pad_1d(bins, tile)
    g, h, c = (_pad_1d(x, tile) for x in (g, h, c))
    # padded rows get leaf_id = L (out of range -> zero one-hot row)
    leaf_id = _pad_1d(leaf_id, tile, value=l)
    n_tiles = bins.shape[0] // tile
    bins_t = bins.reshape(n_tiles, tile, f)
    g_t = g.reshape(n_tiles, tile)
    h_t = h.reshape(n_tiles, tile)
    c_t = c.reshape(n_tiles, tile)
    lid_t = leaf_id.reshape(n_tiles, tile)

    def step(carry, xs):
        bt, gt, ht, ct, lt = xs
        onehot_b = _expand_onehot_2d(bt, f, b)                           # [T, F*B]
        w = _leaf_weight_2d(lt, _split_hi_lo_tile(gt, ht, ct), l)        # [T, L*6]
        part = jax.lax.dot_general(
            onehot_b, w,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)                            # [F*B, L*6]
        return carry + part, None

    init = jnp.zeros((f * b, l * 6), dtype=acc_dtype)
    hist, _ = jax.lax.scan(step, init, (bins_t, g_t, h_t, c_t, lid_t))
    return _hi_lo_combine(hist, f, b, l)


def route_level(bins, leaf_id, tables: RouteTables, na_bin, num_slots: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized per-row routing through one depthwise level's splits.

    Replaces the reference's DataPartition::Split (data_partition.hpp:113): per
    row, look up its leaf's split (if any), compare the row's bin against the
    threshold, produce the new leaf id and the histogram slot (num_slots =
    sentinel for rows whose child is reconstructed by subtraction).

    Returns (slot [N] i32, new_leaf_id [N] i32).
    """
    n, f = bins.shape
    feat = jnp.take(tables.feat, leaf_id)
    has = feat >= 0
    fsafe = jnp.maximum(feat, 0)
    # gather the row's uint8 bin, then widen: widening first would make an
    # [N, F] int32 copy of the whole matrix (6.4 GB at 800k x 2000)
    colv = jnp.take_along_axis(bins, fsafe[:, None],
                               axis=1)[:, 0].astype(jnp.int32)
    nav = jnp.take(na_bin, fsafe)
    is_na = colv == nav
    go_right = jnp.where(is_na, jnp.take(tables.dleft, leaf_id) == 0,
                         colv > jnp.take(tables.thr, leaf_id))
    if tables.is_cat is not None:
        bm = tables.member.shape[1]
        mem = jnp.take(tables.member.reshape(-1), leaf_id * bm + colv) > 0.5
        iscat = jnp.take(tables.is_cat, leaf_id) > 0
        go_right = jnp.where(iscat, ~mem, go_right)
    lid2 = jnp.where(has & go_right, jnp.take(tables.new_leaf, leaf_id), leaf_id)
    slot = jnp.where(has,
                     jnp.where(go_right, jnp.take(tables.slot_right, leaf_id),
                               jnp.take(tables.slot_left, leaf_id)),
                     num_slots)
    return slot, lid2


def hist_routed_onehot(bins, g, h, c, leaf_id, tables: RouteTables, na_bin,
                       num_slots: int, num_bins: int, tile: int = _DEF_TILE,
                       acc_dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused depthwise-level pass: route every row through its leaf's split (if
    any) AND accumulate the smaller-child histograms, in one scan over the data.

    This replaces the reference's DataPartition::Split + ConstructHistograms
    pair (data_partition.hpp:113, dataset.cpp:1189) with a single fused pass.
    Fusing matters beyond the extra data pass: routing as a standalone op
    materializes [N, F]-shaped i32 temps whose TPU tilings waste 20-40x HBM
    (OOM at 10M rows); inside the scan body every intermediate is tile-sized.

    Returns (hist [S, 3, F, B] f32, new_leaf_id [N] i32).
    """
    n, f = bins.shape
    b, s = num_bins, num_slots
    bins_p = _pad_1d(bins, tile)
    g, h, c = (_pad_1d(x, tile) for x in (g, h, c))
    lid = _pad_1d(leaf_id, tile)   # padded rows route as leaf 0 but carry zero ghc
    n_tiles = bins_p.shape[0] // tile

    # per-leaf -> per-row lookups as full-size 1-D gathers (1-D layouts don't pad)
    feat_r = jnp.take(tables.feat, lid).reshape(n_tiles, tile)
    thr_r = jnp.take(tables.thr, lid).reshape(n_tiles, tile)
    dleft_r = jnp.take(tables.dleft, lid).reshape(n_tiles, tile)
    newl_r = jnp.take(tables.new_leaf, lid).reshape(n_tiles, tile)
    sl_r = jnp.take(tables.slot_left, lid).reshape(n_tiles, tile)
    sr_r = jnp.take(tables.slot_right, lid).reshape(n_tiles, tile)
    iscat_r = (jnp.take(tables.is_cat, lid).reshape(n_tiles, tile)
               if tables.is_cat is not None else jnp.zeros_like(thr_r))

    bins_t = bins_p.reshape(n_tiles, tile, f)
    g_t = g.reshape(n_tiles, tile)
    h_t = h.reshape(n_tiles, tile)
    c_t = c.reshape(n_tiles, tile)
    lid_t = lid.reshape(n_tiles, tile)
    iota_f = jnp.arange(f, dtype=jnp.int32)

    def step(carry, xs):
        bt, gt, ht, ct, lt, ft, tt, dt, nt, slt, srt, ict = xs
        # ---- route (vectorized NumericalDecision, tree.h:240) ----
        fm = ft[:, None] == iota_f[None, :]                        # [T, F] in-fusion
        colv = jnp.sum(jnp.where(fm, bt.astype(jnp.int32), 0), axis=1)
        nav = jnp.sum(jnp.where(fm, na_bin[None, :], 0), axis=1)
        has = ft >= 0
        is_na = colv == nav
        go_right = jnp.where(is_na, dt == 0, colv > tt)
        if tables.is_cat is not None:
            bm = tables.member.shape[1]
            mem = jnp.take(tables.member.reshape(-1), lt * bm + colv) > 0.5
            go_right = jnp.where(ict > 0, ~mem, go_right)
        lt2 = jnp.where(has & go_right, nt, lt)
        slot = jnp.where(has, jnp.where(go_right, srt, slt), s)    # s = sentinel

        # ---- accumulate smaller-child histograms by slot ----
        onehot_b = _expand_onehot_2d(bt, f, b)
        w = _leaf_weight_2d(slot, _split_hi_lo_tile(gt, ht, ct), s)
        part = jax.lax.dot_general(
            onehot_b, w,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)
        return carry + part, lt2

    init = jnp.zeros((f * b, s * 6), dtype=acc_dtype)
    hist, lid2 = jax.lax.scan(
        step, init,
        (bins_t, g_t, h_t, c_t, lid_t, feat_r, thr_r, dleft_r, newl_r, sl_r,
         sr_r, iscat_r))
    return _hi_lo_combine(hist, f, b, s), lid2.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# scatter implementations (CPU backend / tests)
# ---------------------------------------------------------------------------

def hist_leaf_scatter(bins, g, h, c, num_bins: int) -> jnp.ndarray:
    """Scatter-add histogram — XLA lowers to sorted-scatter; best on CPU backend.
    Returns [3, F, B]."""
    n, f = bins.shape
    b = num_bins
    idx = bins.astype(jnp.int32) + jnp.arange(f, dtype=jnp.int32)[None, :] * b  # [N,F]
    hist = jnp.zeros((f * b, 3), dtype=jnp.float32)
    ghc = jnp.stack([g, h, c], axis=1)
    vals = jnp.broadcast_to(ghc[:, None, :], (n, f, 3))
    hist = hist.at[idx.reshape(-1)].add(vals.reshape(-1, 3))
    return hist.reshape(f, b, 3).transpose(2, 0, 1)


def hist_per_leaf_scatter(bins, g, h, c, leaf_id, num_leaves: int,
                          num_bins: int) -> jnp.ndarray:
    """Returns [L, 3, F, B]. Out-of-range leaf ids are dropped."""
    n, f = bins.shape
    b, l = num_bins, num_leaves
    idx = (leaf_id[:, None] * f + jnp.arange(f, dtype=jnp.int32)[None, :]) * b \
        + bins.astype(jnp.int32)
    oob = (leaf_id < 0) | (leaf_id >= l)
    idx = jnp.where(oob[:, None], l * f * b, idx)
    hist = jnp.zeros((l * f * b, 3), dtype=jnp.float32)
    ghc = jnp.stack([g, h, c], axis=1)
    vals = jnp.broadcast_to(ghc[:, None, :], (n, f, 3))
    hist = hist.at[idx.reshape(-1)].add(vals.reshape(-1, 3), mode="drop")
    return hist.reshape(l, f, b, 3).transpose(0, 3, 1, 2)


def hist_routed_scatter(bins, g, h, c, leaf_id, tables: RouteTables, na_bin,
                        num_slots: int, num_bins: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    slot, lid2 = route_level(bins, leaf_id, tables, na_bin, num_slots)
    keep = (slot < num_slots).astype(g.dtype)
    hist = hist_per_leaf_scatter(bins, g * keep, h * keep, c * keep,
                                 jnp.where(slot < num_slots, slot, num_slots),
                                 num_slots, num_bins)
    return hist, lid2


# ---------------------------------------------------------------------------
# int8 gradient quantization (LightGBM 4.x "quantized training" analog)
# ---------------------------------------------------------------------------

def quantize_sr(x: jnp.ndarray, seed, salt: int):
    """Stochastic-rounding int8 quantization: returns (q [N] int8, scale f32).

    E[q] = x * 127 / scale (unbiased — round-to-nearest systematically biases
    split gains at low bit widths; the quantized-training paper uses
    stochastic rounding for the same reason). The dither is a counter-based
    hash of (row index, seed, salt) — no threaded PRNG key, so the jitted
    tree build stays a pure function of its operands."""
    n = x.shape[0]
    i = jnp.arange(n, dtype=jnp.uint32) + jnp.uint32((salt * 0x632BE59B) & 0xFFFFFFFF)
    k = jnp.uint32(0) if seed is None else jnp.asarray(seed).astype(jnp.uint32)
    z = (i ^ (k * jnp.uint32(0x9E3779B9))) * jnp.uint32(2654435761)
    z = (z ^ (z >> 15)) * jnp.uint32(2246822519)
    z = z ^ (z >> 13)
    u = (z >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-20).astype(jnp.float32)
    q = jnp.floor(x * (127.0 / scale) + u)
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


class QuantChannels(NamedTuple):
    """Per-tree quantized row channels + scales (built once per tree).

    ``hq is None`` signals constant-hessian elision (reference analog: the
    CONST_HESSIAN OpenCL kernel variants, ocl/histogram256.cl:18-60): rows
    carry h = h_const * bag01, so the histogram hessian channel is exactly
    ``count * scale_h / 127`` and the kernels skip it — the MXU contraction
    shrinks from 3 to 2 int8 channels. Bit-identical to the quantized path:
    quantize_sr on a {0, h_const} vector yields hq = 127 * cq exactly."""
    gq: jnp.ndarray      # [N] int8
    hq: Optional[jnp.ndarray]   # [N] int8, or None when hessian is constant
    cq: jnp.ndarray      # [N] int8 0/1
    scale_g: jnp.ndarray  # f32 scalar
    scale_h: jnp.ndarray  # f32 scalar


def make_quant(g, h, c, seed, const_hess: bool = False) -> QuantChannels:
    gq, sg = quantize_sr(g, seed, salt=1)
    if const_hess:
        # scale_h = 127 * h_const so every dequant site's out * scale_h/127
        # reconstructs h_const * count without a dedicated scalar
        return QuantChannels(gq, None, c.astype(jnp.int8), sg,
                             127.0 * jnp.max(h).astype(jnp.float32))
    hq, sh = quantize_sr(h, seed, salt=2)
    return QuantChannels(gq, hq, c.astype(jnp.int8), sg, sh)


def _q8_h_arg(quant: QuantChannels):
    """(hq array to pass, const_hess flag) for the q8 kernels."""
    return (quant.cq, True) if quant.hq is None else (quant.hq, False)


def dequant_rows(quant: QuantChannels):
    """Per-row f32 (g, h, c) for non-pallas backends — the same numbers the
    int32 accumulator would produce, up to f32 summation order. With elided
    hessians (hq None) the count channel stands in: hq would be 127*cq."""
    g = quant.gq.astype(jnp.float32) * (quant.scale_g / 127.0)
    h = (quant.hq if quant.hq is not None else quant.cq).astype(
        jnp.float32) * (quant.scale_h / 127.0)
    c = quant.cq.astype(jnp.float32)
    return g, h, c


def one_kernel_front(num_features: int, num_bins: int,
                     impl: str = "auto") -> bool:
    """Whether grad_quant_hist0 is ONE kernel at this width (the
    ``hist_path`` event's ``front``), or the grad -> quant -> hist0 chain."""
    from .pallas_hist import one_group
    return pick_impl(impl) == "pallas" and one_group(num_features, num_bins)


def grad_quant_hist0(bins, score, aux, bag, seed, spec, num_bins,
                     const_hess: bool = False, impl: str = "auto",
                     bins_T=None, col_axes=()):
    """Fused per-iteration front: objective gradients + SR quantization +
    root histogram in one pass.

    ``spec`` is an objective's static ``fused_grad_spec()`` tuple (("l2",) or
    ("logloss", sigmoid, lw_pos, lw_neg)); ``aux`` its per-row constant
    (label for L2, label_pos for logloss). Returns (QuantChannels, hist0
    [3, F, B] f32) — bit-identical to get_gradients -> mask-by-bag ->
    make_quant -> hist_leaf on every backend: the Pallas kernel replays the
    same f32 ops and dither hash, and the non-Pallas fallback below IS that
    unfused chain."""
    impl = pick_impl(impl)
    from .pallas_hist import _grad_rows, grad_quant_hist0_pallas
    if one_kernel_front(bins.shape[1], num_bins, impl):
        interp = jax.default_backend() == "cpu"
        bt = bins_T if bins_T is not None else bins.T
        gq, hq, cq, sg, sh, hist0 = grad_quant_hist0_pallas(
            bt, score, aux, bag, seed, spec, num_bins,
            const_hess=const_hess, interpret=interp)
        return QuantChannels(gq, hq, cq, sg, sh), hist0
    with jax.named_scope("grad"):
        grad, hess = _grad_rows(spec, score, aux)
        g = grad * bag
        h = hess * bag
        c = (bag > 0).astype(jnp.float32)
    with jax.named_scope("quant"):
        quant = make_quant(g, h, c, seed, const_hess=const_hess)
    with jax.named_scope("hist0"):
        hist0 = hist_leaf(bins, g, h, c, num_bins, impl=impl, bins_T=bins_T,
                          quant=quant, col_axes=col_axes)
    return quant, hist0


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def pick_impl(requested: str, backend: Optional[str] = None) -> str:
    """Empirical default (reference analog: dataset.cpp:640 runtime timing test):
    scatter on CPU (XLA CPU scatter is fast, one-hot matmul is not), the Pallas
    kernel on TPU (measured 1.5-1.7x the XLA onehot path at every slot width)."""
    if requested and requested != "auto":
        return requested
    backend = backend or jax.default_backend()
    return "scatter" if backend == "cpu" else "pallas"


def _own_axes(col_axes, num_features: int):
    """col_axes where it names every column of this matrix (a grower's
    GrowParams.col_axes), else () for one axis: a feature-sharded slice
    has other columns."""
    return col_axes if len(col_axes) == num_features else ()


def hist_leaf(bins, g, h, c, num_bins, impl="auto", bins_T=None, quant=None,
              col_axes=()):
    impl = pick_impl(impl)
    interp = jax.default_backend() == "cpu"   # tests force impl=pallas on CPU
    if quant is not None and impl == "pallas":
        from .pallas_hist import hist_pallas_q8
        bt = bins_T if bins_T is not None else bins.T
        slot = jnp.zeros(bt.shape[1], jnp.int32)
        hq, ch = _q8_h_arg(quant)
        return hist_pallas_q8(bt, quant.gq, hq, quant.cq, slot, 1,
                              num_bins, quant.scale_g, quant.scale_h,
                              const_hess=ch, interpret=interp,
                              col_axes=_own_axes(col_axes, bins.shape[1])
                              )[0, :, :bins.shape[1]]
    if quant is not None:
        g, h, c = dequant_rows(quant)
    if impl == "scatter":
        return hist_leaf_scatter(bins, g, h, c, num_bins)
    if impl == "pallas":
        from .pallas_hist import hist_leaf_pallas
        bt = bins_T if bins_T is not None else bins.T
        return hist_leaf_pallas(bt, g, h, c, num_bins,
                                interpret=interp)[:, :bins.shape[1]]
    return hist_leaf_onehot(bins, g, h, c, num_bins)


def hist_per_leaf(bins, g, h, c, leaf_id, num_leaves, num_bins, impl="auto",
                  bins_T=None):
    impl = pick_impl(impl)
    if impl == "scatter":
        return hist_per_leaf_scatter(bins, g, h, c, leaf_id, num_leaves, num_bins)
    if impl == "pallas":
        from .pallas_hist import hist_pallas
        bt = bins_T if bins_T is not None else bins.T
        return hist_pallas(bt, g, h, c, leaf_id, num_leaves,
                           num_bins)[:, :, :bins.shape[1]]
    return hist_per_leaf_onehot(bins, g, h, c, leaf_id, num_leaves, num_bins)


def hist_path(num_features: int, num_bins: int, impl: str = "auto",
              quant: bool = True, col_bins=None) -> dict:
    """The path a depthwise level pass takes at this width: what
    ``hist_routed`` selects, as the ``hist_path`` event reports it.

    level_kernel: the kernel that builds a level's histograms
    (``hist_level_q8`` routes in the same launch; ``hist_leaf_q8`` /
    ``hist_leaf`` run on a (feature group, row chunk) grid after a route pass
    of its own; off the Pallas path the impl's name); feature_groups: that
    grid's first axis; route: "fused", or the stand-alone router's
    implementation ("pallas": the ``route_level`` kernel, at every width on
    the Pallas path | "xla": ``route_level``'s gathers, off it).
    onehot_rows: the one-hot rows a level pass issues, summed over its
    groups (F x B in one block; off the Pallas path the F x B table the
    pass fills); bin_rows: the bins that exist, the sum of ``col_bins``
    (each column's bin count; B each where not given)."""
    impl = pick_impl(impl)
    bins = ([num_bins] * num_features if col_bins is None
            else [int(n) for n in col_bins])
    rows = {"onehot_rows": num_features * num_bins, "bin_rows": sum(bins)}
    if impl != "pallas":
        return {"level_kernel": impl, "feature_groups": 1,
                "route": "xla" if impl == "scatter" else "fused", **rows}
    from .pallas_hist import feature_grouping, one_group, plan_rows
    if quant and one_group(num_features, num_bins):
        return {"level_kernel": "hist_level_q8", "feature_groups": 1,
                "route": "fused", **rows}
    if quant:
        groups, rows["onehot_rows"] = plan_rows(
            tuple(bin_axis(n) for n in bins) if col_bins is not None
            else (num_bins,) * num_features)
    else:
        fg, groups = feature_grouping(num_features, num_bins)
        rows["onehot_rows"] = fg * groups * num_bins
    return {"level_kernel": "hist_leaf_q8" if quant else "hist_leaf",
            "feature_groups": groups, "route": "pallas", **rows}


def _split_columns(bins_T, tables: RouteTables, na_bin, num_slots: int):
    """The columns one level's splits read, as rows of ``bins_T``: a level
    never needs the F columns of the matrix, only those of the leaves that
    split in it, at most ``num_slots`` (one slot a split, two in the lean
    grower). Returns (cols [K_pad, N] uint8, their na_bin [K_pad], rank [L]:
    a splitting leaf's row of ``cols``, -1 where ``tables.feat`` is). Two
    leaves on one feature get a row each; ``cols`` is a transient of
    K_pad x N bytes (154 MB at 1.2 M rows and 127 slots)."""
    k_pad = -(-num_slots // 32) * 32                # uint8 sublane tile
    has = tables.feat >= 0
    rank = jnp.where(has, jnp.cumsum(has) - 1, -1)
    col_feat = jnp.zeros(k_pad, jnp.int32).at[
        jnp.where(has, rank, k_pad)].set(tables.feat, mode="drop")
    # the rows by a one-hot contraction (no hardware gather: XLA's row gather
    # copies the whole matrix first and takes twice the time). int8 x int8 ->
    # int32 is exact: the uint8 bins ride as their int8 bit patterns and the
    # mask undoes the wrap
    pick = col_feat[:, None] == jnp.arange(bins_T.shape[0])[None, :]
    cols = jax.lax.dot_general(
        pick.astype(jnp.int8), jax.lax.bitcast_convert_type(bins_T, jnp.int8),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (cols & 0xFF).astype(jnp.uint8), jnp.take(na_bin, col_feat), rank


def hist_routed(bins, g, h, c, leaf_id, tables, na_bin, num_slots, num_bins,
                impl="auto", bins_T=None, quant=None, col_axes=()):
    impl = pick_impl(impl)
    if quant is not None and impl != "pallas":
        g, h, c = dequant_rows(quant)
    if impl == "scatter":
        return hist_routed_scatter(bins, g, h, c, leaf_id, tables, na_bin,
                                   num_slots, num_bins)
    if impl == "pallas":
        from .pallas_hist import (hist_pallas, hist_pallas_q8,
                                  hist_routed_fused_q8)
        interp = jax.default_backend() == "cpu"
        bt, fused = _pallas_route(bins, bins_T, num_bins, quant)
        if fused:
            # single-feature-group data: route + histogram in ONE kernel
            # (one bins read per level instead of two, no [N] slot
            # round-trip; measured 8.3 ms/level for the separate route pass
            # at 10M rows)
            hq, ch = _q8_h_arg(quant)
            return hist_routed_fused_q8(
                bt, quant.gq, hq, quant.cq, leaf_id, tables, na_bin,
                num_slots, num_bins, quant.scale_g, quant.scale_h,
                const_hess=ch, interpret=interp)
        slot, lid2 = route_rows(bins, bt, leaf_id, tables, na_bin, num_slots,
                                impl)
        # the grouped kernel with its dequantise and transposes; a resident
        # matrix has the feature groups' rows (pallas_hist.resident_shape)
        with jax.named_scope("hist"):
            if quant is not None:
                hq, ch = _q8_h_arg(quant)
                hist = hist_pallas_q8(bt, quant.gq, hq, quant.cq, slot,
                                      num_slots, num_bins, quant.scale_g,
                                      quant.scale_h, const_hess=ch,
                                      interpret=interp,
                                      col_axes=_own_axes(col_axes,
                                                         bins.shape[1]))
            else:
                hist = hist_pallas(bt, g, h, c, slot, num_slots, num_bins,
                                   interpret=interp)
            return hist[:, :, :bins.shape[1]], lid2
    return hist_routed_onehot(bins, g, h, c, leaf_id, tables, na_bin,
                              num_slots, num_bins)


def _pallas_route(bins, bins_T, num_bins: int, quant):
    """How a Pallas level pass routes its rows, for ``hist_routed`` and
    ``route_only`` alike: over which matrix (the resident ``bins_T``, else
    ``bins.T``), and whether inside its one histogram kernel (``hist_path``
    route "fused") or in ``route_rows`` ahead of the grouped kernel.
    Returns (bt, fused)."""
    bt = bins_T if bins_T is not None else bins.T
    path = hist_path(bins.shape[1], num_bins, "pallas", quant is not None)
    return bt, path["route"] == "fused"


def route_only(bins, leaf_id, tables: RouteTables, na_bin, num_slots: int,
               num_bins: int, impl="auto", bins_T=None, quant=None):
    """``hist_routed``'s new leaf ids without its histograms, for a level
    whose children's histograms nothing reads (device scope ``route``),
    routed as that pass routes (``_pallas_route``): where it routes inside
    its one kernel, the ``route_level`` kernel over the same matrix, every
    column as that kernel reads them, through the same ``_route_chunk``;
    elsewhere ``route_rows``."""
    impl = pick_impl(impl)
    if impl != "pallas":
        return route_rows(bins, None, leaf_id, tables, na_bin, num_slots,
                          impl)[1]
    bt, fused = _pallas_route(bins, bins_T, num_bins, quant)
    if not fused:
        return route_rows(bins, bt, leaf_id, tables, na_bin, num_slots,
                          impl)[1]
    from .pallas_hist import route_level_pallas
    with jax.named_scope("route"):
        return route_level_pallas(
            bt, leaf_id, tables, na_bin, num_slots,
            interpret=jax.default_backend() == "cpu")[1]


def route_rows(bins, bins_T, leaf_id, tables: RouteTables, na_bin,
               num_slots: int, impl: str = "auto"):
    """The row router as a pass of its own (device scope ``route``). On the
    Pallas path the ``route_level`` kernel at every width, its bin block the
    level's split columns and a leaf's ``feat`` entry its column's row there;
    off it ``route_level``. Returns (slot, new_leaf_id) as ``route_level``."""
    with jax.named_scope("route"):
        if pick_impl(impl) != "pallas":
            return route_level(bins, leaf_id, tables, na_bin, num_slots)
        from .pallas_hist import route_level_pallas
        cols, na_cols, rank = _split_columns(bins_T, tables, na_bin,
                                             num_slots)
        return route_level_pallas(
            cols, leaf_id, tables._replace(feat=rank), na_cols, num_slots,
            interpret=jax.default_backend() == "cpu")
