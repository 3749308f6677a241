"""Pallas TPU histogram kernel.

Hand-written replacement for the XLA ``onehot`` formulation in ops/histogram.py
(reference hot loop: DenseBin::ConstructHistogramInner, dense_bin.hpp:77-105;
GPU ports: src/treelearner/ocl/histogram256.cl). Design (SURVEY §7):

- grid over (feature-group, row-chunk); the f32 accumulator block
  ``[Fg*B, S*6]`` stays resident in VMEM across the row-chunk axis;
- the bin one-hot is built DIRECTLY in ``[F*B, C]`` lane layout from a
  pre-transposed ``[F, N]`` bin matrix: a sublane-broadcast plus a
  ``broadcasted_iota`` compare — pure VPU work, no expansion matmul and no
  minor-dim reshape (the two relayout hazards of the XLA path);
- the per-row channel weights are built in ``[S*6, C]`` lane layout (rows =
  slot x channel, columns = rows-of-data) so the MXU contraction
  ``onehot [F*B, C] x w [S*6, C]^T`` contracts the lane axis of both operands
  — no transposes anywhere;
- grad/hess are split hi/lo into two bf16 channels each (f32-accurate MXU
  accumulation, see ops/histogram.py _split_hi_lo_tile).

The kernel serves both the root pass (S=1, all rows in slot 0) and the
depthwise level pass (S slots routed by ops/histogram.py route_level).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.timer import scoped_jit

_CHUNK = 1024          # rows per grid step (onehot block [F*B, C] bf16 ~3.7MB)
# int8 kernel takes bigger chunks: the onehot block is half the bytes of the
# bf16 one, and the SWAR one-hot (r5) freed enough VMEM that 4096 fits even
# at S=127 (onehot 7.3MB + acc 2.7MB + weights 1.5MB); fewer grid steps cut
# the per-chunk fixed cost that dominates shallow passes. The bf16 kernel
# stays at 1024 (hi/lo doubles its weight rows)
_CHUNK_Q8 = 4096
_ACC_ROWS_MAX = 2048   # Fg*B cap: keeps the f32 accumulator block <= ~6.3MB
# every row chunk a wrapper below can choose divides this (_CHUNK, the 2048
# fallbacks, _CHUNK_Q8, the 8192 of leaf_sums and take_small): a matrix and
# row vectors padded to its multiple once (resident_shape) are tiled by every
# kernel as they lie, and the wrappers' _pad_rows / [:n] do nothing
_ROW_ALIGN = 8192

# Master slot-width set: every Pallas level pass floors its slot count to one
# of these widths, so the depthwise default grower and the lean grower reuse
# the same traced kernel programs — fewer distinct widths = fewer lowerings.
# Over-wide S is free for correctness: extra slots accumulate nothing (no row
# routes into them) and split selection binds on the per-level budget, not
# the kernel width.
MASTER_SLOT_WIDTHS = (32, 128, 512)


def floor_slot_width(needed: int, max_slots: int) -> int:
    """Smallest master width >= needed, capped at max_slots."""
    for w in MASTER_SLOT_WIDTHS:
        if w >= needed:
            return min(w, max_slots)
    return max_slots


def feature_grouping(f: int, b: int):
    """(features per group, groups): the first grid axis of the grouped
    kernels (hist_leaf, hist_leaf_q8), each group's fg*b accumulator rows
    within _ACC_ROWS_MAX. What ops/histogram.hist_path reports."""
    fg = max(1, min(f, _ACC_ROWS_MAX // b))
    return fg, -(-f // fg)


def one_group(f: int, b: int) -> bool:
    """Every feature fits one accumulator block: the condition of the
    kernels that must see all columns (hist_level_q8, grad_quant_hist0)."""
    return f * b <= _ACC_ROWS_MAX


def resident_shape(n: int, f: int, b: int) -> Tuple[int, int]:
    """(F_pad, N_pad) of an [F, N] transposed bin matrix in the shape the
    kernels tile: rows up to the feature groups' fg * n_groups (F itself where
    one group holds every feature), columns up to the next multiple of
    _ROW_ALIGN. What Dataset.bins_T is built to, once; a matrix of any other
    shape is padded by the wrappers in every pass (_pad_rows)."""
    fg, n_fg = feature_grouping(f, b)
    return fg * n_fg, -(-n // _ROW_ALIGN) * _ROW_ALIGN


@functools.partial(jax.jit, static_argnames=("shape",))
def resident_bins_T(bins: jnp.ndarray, shape: Tuple[int, int]) -> jnp.ndarray:
    """[N, F] uint8 bins -> the transposed matrix at ``shape`` = (F_pad,
    N_pad) of resident_shape, the padding zero: bin 0 of rows that carry no
    weight in any channel, and of features no split table names."""
    n, f = bins.shape
    return jnp.pad(bins.T, ((0, shape[0] - f), (0, shape[1] - n)))


def _kernel(bins_ref, g_ref, h_ref, c_ref, slot_ref, out_ref, *,
            fg: int, b: int, s: int, chunk: int):
    """One (feature-group j, row-chunk i) grid step.

    bins_ref: [Fg, C] uint8 (transposed bins); g/h/c_ref: [C] f32;
    slot_ref: [C] i32; out_ref: [Fg*B, S*6] f32 accumulated across i.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # ---- one-hot in [Fg*B, C] lane layout: VPU only (int32 compares —
    # Mosaic on v5e rejects sub-word vector cmpi: "Target does not support
    # this comparison" on vector<...xi8>) ----
    bins_i = bins_ref[:].astype(jnp.int32)                      # [Fg, C]
    bb = jax.lax.broadcast_in_dim(bins_i, (fg, b, chunk), (0, 2))
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (fg, b, chunk), 1)
    onehot = (bb == iota_b).astype(jnp.bfloat16).reshape(fg * b, chunk)

    # ---- weights in [S*5, C] lane layout: (g_hi, h_hi, count, g_lo, h_lo).
    # The count channel is a 0/1 bag mask (bagging is mask-based here, see
    # ops/histogram.py) — exact in bf16, so it needs no lo component; one
    # channel fewer cuts the dominant MXU contraction by 1/6 ----
    g = g_ref[:].reshape(1, chunk)
    h = h_ref[:].reshape(1, chunk)
    c = c_ref[:].reshape(1, chunk)
    gh = jnp.concatenate([g, h], axis=0)                        # [2, C] f32
    hi = gh.astype(jnp.bfloat16)
    lo = (gh - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    ghc5 = jnp.concatenate([hi, c.astype(jnp.bfloat16), lo], axis=0)  # [5, C]
    w = jax.lax.broadcast_in_dim(ghc5, (s, 5, chunk), (1, 2)) \
        .reshape(s * 5, chunk)                                  # [S*5, C]
    slot = slot_ref[:].reshape(1, chunk)
    slot_of_row = jax.lax.broadcasted_iota(jnp.int32, (s * 5, chunk), 0) // 5
    w = jnp.where(slot == slot_of_row, w, jnp.bfloat16(0.0))

    # ---- MXU: contract the lane (row) axis of both operands ----
    part = jax.lax.dot_general(
        onehot, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                     # [Fg*B, S*6]
    out_ref[:] += part


def _pad_rows(x, mult, value=0):
    n = x.shape[-1] if x.ndim == 2 else x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    if x.ndim == 2:
        return jnp.pad(x, ((0, 0), (0, pad)), constant_values=value)
    return jnp.pad(x, (0, pad), constant_values=value)


def hist_pallas(bins_T: jnp.ndarray, g: jnp.ndarray, h: jnp.ndarray,
                c: jnp.ndarray, slot: jnp.ndarray, num_slots: int,
                num_bins: int, chunk: int = _CHUNK,
                interpret: bool = False) -> jnp.ndarray:
    """Slot-routed histogram: returns [S, 3, F, B] f32 (channel-major).

    bins_T: [F, N] uint8 (bins transposed — dataset-resident, built once);
    g/h/c: [N] f32 channels (zero for out-of-bag rows);
    slot: [N] i32 in [0, num_slots); rows with slot >= num_slots are dropped.
    """
    f = bins_T.shape[0]
    b, s = num_bins, num_slots

    fg, n_fg = feature_grouping(f, b)
    f_pad = n_fg * fg
    if f_pad != f:
        bins_T = jnp.pad(bins_T, ((0, f_pad - f), (0, 0)))

    bins_T = _pad_rows(bins_T, chunk)
    g = _pad_rows(g, chunk)
    h = _pad_rows(h, chunk)
    c = _pad_rows(c, chunk)
    # padded rows carry zero channels; droppped slots (>= s) become s below
    slot = _pad_rows(slot, chunk, value=s)
    slot = jnp.minimum(slot, s)  # anything out of range masks to zero weight
    n_p = bins_T.shape[1]           # the columns the kernel reads
    n_chunks = n_p // chunk

    kern = functools.partial(_kernel, fg=fg, b=b, s=s, chunk=chunk)
    out = pl.pallas_call(
        kern,
        name="hist_leaf",
        grid=(n_fg, n_chunks),
        in_specs=[
            pl.BlockSpec((fg, chunk), lambda j, i: (j, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((fg * b, s * 5), lambda j, i: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, s * 5), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_p * f_pad * b * s * 5,
            bytes_accessed=n_p * (f_pad + 16) + f_pad * b * s * 20,
            transcendentals=0),
        interpret=interpret,
    )(bins_T, g, h, c, slot)

    # [F_pad*B, S*5] -> [S, 3, F, B] (g/h hi+lo recombined), drop padding
    out = out.reshape(f_pad, b, s, 5)
    out = jnp.stack([out[..., 0] + out[..., 3], out[..., 1] + out[..., 4],
                     out[..., 2]], axis=-1).transpose(2, 3, 0, 1)
    return out[:, :, :f, :]


def hist_leaf_pallas(bins_T, g, h, c, num_bins: int,
                     interpret: bool = False) -> jnp.ndarray:
    """Root histogram pass: [3, F, B] f32."""
    slot = jnp.zeros(bins_T.shape[1], jnp.int32)
    return hist_pallas(bins_T, g, h, c, slot, 1, num_bins,
                       interpret=interpret)[0]


# ---------------------------------------------------------------------------
# int8 quantized-gradient histogram kernel
#
# LightGBM 4.x technique ("Quantized Training of Gradient Boosting Decision
# Trees", Shi et al.): gradients/hessians are quantized to int8 with
# stochastic rounding once per tree, histograms accumulate exactly in int32,
# and leaf values are renewed from exact f32 sums at tree end. On the MXU
# this turns the dominant contraction from 5 bf16 channels into 3 int8
# channels at 2x int8 throughput — ~3.3x fewer effective flops. The int32
# accumulator is exact up to ~16M rows/shard per (slot, feature, bin) cell
# (127 * 16.9M = 2^31), far beyond any real per-cell mass.
#
# Two weight layouts, chosen by nch alone: (gq, hq, count) int8, and
# (gq, count) int8 under a constant hessian (the hessian histogram is then
# count * scale_h/127, see _dequant_stack).
# ---------------------------------------------------------------------------

def _onehot_i8(bins_i, fg: int, b: int, chunk: int, swar: bool):
    """int8 bin one-hot in [Fg*B, C] lane layout from int32 bins [Fg, C].

    swar=False: B int32 broadcast-compares (Mosaic on v5e rejects sub-word
    vector cmpi, so the compare width is fixed at 32 bits).

    swar=True: build FOUR bin rows per int32 lane-op (VERDICT r4 next #5;
    reference analog: 4-features-per-DWORD packing,
    gpu_tree_learner.h:200-207 — packed along the BIN axis here). Each bin
    byte is splatted once (v * 0x01010101, hoisted out of the bin loop),
    XORed against the packed 4-bin constant (4k | 4k+1<<8 | 4k+2<<16 |
    4k+3<<24), and zero bytes are detected with the carry-free +0x7F7F7F7F
    test — exact because v, b < 128 keeps every x byte < 0x80, so the
    per-byte add can never carry. A logical >>7 turns the 0x80 match bits
    into 0x01 bytes (logical, NOT arithmetic: a byte-3 match sets bit 31 and
    an arithmetic shift would smear the sign across the byte), and
    pltpu.bitcast unpacks the 4 result bytes onto sublanes in little-endian
    order — row 4k+j of the one-hot = byte j of packed row k, i.e. bin
    b = 4k + j, exactly the [Fg, B, C] row order. Net: the [Fg, B/4, C]
    intermediate has 1/4 the int32 lanes of the compare path's [Fg, B, C]
    at ~4 ops per lane vs 2 — half the VPU work on the kernel's dominant
    non-MXU cost."""
    if swar:
        vs = bins_i * jnp.int32(0x01010101)                     # [Fg, C]
        vb = jax.lax.broadcast_in_dim(vs, (fg, b // 4, chunk), (0, 2))
        k4 = jax.lax.broadcasted_iota(jnp.int32, (fg, b // 4, chunk), 1)
        bconst = k4 * jnp.int32(4 * 0x01010101) + jnp.int32(0x03020100)
        x = vb ^ bconst
        t = x + jnp.int32(0x7F7F7F7F)                 # byte bit7 set iff != 0
        hit = ~t & jnp.int32(0x80808080 - (1 << 32))  # i32-range constant
        oh4 = jax.lax.shift_right_logical(hit, jax.lax.full_like(hit, 7))
        return pltpu.bitcast(oh4.reshape(fg * (b // 4), chunk), jnp.int8)
    bb = jax.lax.broadcast_in_dim(bins_i, (fg, b, chunk), (0, 2))
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (fg, b, chunk), 1)
    return (bb == iota_b).astype(jnp.int8).reshape(fg * b, chunk)


def _swar_ok(b: int, interpret: bool) -> bool:
    """SWAR one-hot requires bins/bin ids < 128 (carry-free byte test), a
    bin axis divisible by 4, and compiled Mosaic (pltpu.bitcast semantics
    are target-defined; the interpreter keeps the reference compare path)."""
    return (not interpret) and b % 4 == 0 and b <= 128


def _kernel_q8(bins_ref, gq_ref, hq_ref, c_ref, slot_ref, out_ref, *,
               fg: int, b: int, s: int, chunk: int, nch: int = 3,
               swar: bool = False):
    """One (feature-group j, row-chunk i) grid step, int8 x int8 -> int32.

    bins_ref: [Fg, C] uint8; gq/hq/c_ref: [C] int8; slot_ref: [C] i32;
    out_ref: [Fg*B, S*nch] i32 accumulated across i. nch=2 is the
    constant-hessian variant (channels (gq, count); hq_ref unused — the
    hessian histogram is count * scale_h/127, reconstructed by the caller)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins_i = bins_ref[:].astype(jnp.int32)                      # [Fg, C]
    onehot = _onehot_i8(bins_i, fg, b, chunk, swar)

    # weights [S*nch, C] int8: (gq[, hq], count) broadcast to slot groups,
    # masked by the row's slot (mask arithmetic in int32 — Mosaic's
    # narrow-bitwidth select support is spotty; the final cast to int8 is
    # exact)
    g = gq_ref[:].reshape(1, chunk).astype(jnp.int32)
    c = c_ref[:].reshape(1, chunk).astype(jnp.int32)
    if nch == 3:
        h = hq_ref[:].reshape(1, chunk).astype(jnp.int32)
        ghc = jnp.concatenate([g, h, c], axis=0)                # [3, C] i32
    else:
        ghc = jnp.concatenate([g, c], axis=0)                   # [2, C] i32
    w = jax.lax.broadcast_in_dim(ghc, (s, nch, chunk), (1, 2)) \
        .reshape(s * nch, chunk)                                # [S*nch, C]
    slot = slot_ref[:].reshape(1, chunk)
    slot_of_row = jax.lax.broadcasted_iota(
        jnp.int32, (s * nch, chunk), 0) // nch
    w = jnp.where(slot == slot_of_row, w, 0).astype(jnp.int8)
    part = jax.lax.dot_general(
        onehot, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                       # [Fg*B, S*nch]
    out_ref[:] += part


def _q8_nch(const_hess: bool) -> int:
    """MXU channel count of the q8 kernels: (g, h, count), or (g, count)
    under a constant hessian."""
    return 2 if const_hess else 3


def _dequant_stack(out, const_hess: bool, sg, sh):
    """[..., nch] int32 accumulator -> [..., 3] f32 (g, h, count) channels.

    const_hess reconstructs the hessian channel as count * sh (sh =
    scale_h/127 with scale_h = 127 * h_const, see ops/histogram.py
    make_quant)."""
    out = out.astype(jnp.float32)
    if const_hess:
        cnt = out[..., 1]
        return jnp.stack([out[..., 0] * sg, cnt * sh, cnt], axis=-1)
    return jnp.stack([out[..., 0] * sg, out[..., 1] * sh, out[..., 2]],
                     axis=-1)


def hist_pallas_q8(bins_T: jnp.ndarray, gq: jnp.ndarray, hq: jnp.ndarray,
                   cq: jnp.ndarray, slot: jnp.ndarray, num_slots: int,
                   num_bins: int, scale_g, scale_h, chunk: int = _CHUNK_Q8,
                   const_hess: bool = False,
                   interpret: bool = False) -> jnp.ndarray:
    """Slot-routed histogram from int8-quantized channels.

    gq/hq: [N] int8 (stochastic-rounded, see ops/histogram.py quantize_sr);
    cq: [N] int8 0/1 bag mask; scale_g/scale_h: the quantization scales
    (traced f32 scalars). Returns [S, 3, F, B] f32 with grad/hess channels
    dequantized (count channel is exact). const_hess drops the in-kernel
    hessian channel (2-channel MXU contraction) and reconstructs it as
    count * scale_h/127 — exact for h = h_const * bag01 rows."""
    f = bins_T.shape[0]
    b, s = num_bins, num_slots
    nch = _q8_nch(const_hess)
    fg, n_fg = feature_grouping(f, b)
    if chunk == _CHUNK_Q8:
        # the 4096 default is budgeted for the SWAR one-hot at the bench
        # shape (fg*b = 1792 rows measured fitting VMEM at S=127); wider
        # feature groups (fg*b = 2048 at 700 features: measured 16.75MB,
        # 764KB over the scoped-vmem limit) or the compare path's int32
        # broadcast intermediates keep the old 2048 chunk
        if not _swar_ok(b, interpret) or fg * b > 1792 or s * nch > 384:
            chunk = 2048
    f_pad = n_fg * fg
    if f_pad != f:
        bins_T = jnp.pad(bins_T, ((0, f_pad - f), (0, 0)))

    bins_T = _pad_rows(bins_T, chunk)
    gq = _pad_rows(gq, chunk)
    hq = _pad_rows(hq, chunk)
    cq = _pad_rows(cq, chunk)
    slot = _pad_rows(slot, chunk, value=s)
    slot = jnp.minimum(slot, s)
    n_p = bins_T.shape[1]
    n_chunks = n_p // chunk

    kern = functools.partial(_kernel_q8, fg=fg, b=b, s=s, chunk=chunk,
                             nch=nch, swar=_swar_ok(b, interpret))
    out = pl.pallas_call(
        kern,
        name="hist_leaf_q8",
        grid=(n_fg, n_chunks),
        in_specs=[
            pl.BlockSpec((fg, chunk), lambda j, i: (j, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda j, i: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((fg * b, s * nch), lambda j, i: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, s * nch), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_p * f_pad * b * s * nch,
            bytes_accessed=n_p * (f_pad + 7) + f_pad * b * s * 4 * nch,
            transcendentals=0),
        interpret=interpret,
    )(bins_T, gq, hq, cq, slot)

    out = out.reshape(f_pad, b, s, nch)
    sg = scale_g * jnp.float32(1.0 / 127.0)
    sh = scale_h * jnp.float32(1.0 / 127.0)
    hist = _dequant_stack(out, const_hess, sg, sh).transpose(2, 3, 0, 1)
    return hist[:, :, :f, :]


# ---------------------------------------------------------------------------
# per-row routing inside a kernel
#
# A plain XLA gather of an [N] index vector from a small [L] table costs ~7ms
# per million rows on v5e (no hardware gather; XLA lowers to per-element
# dynamic-slice). One depthwise level needs eight such lookups. The level
# kernels express them as ONE one-hot [L, C] contraction on the MXU, over the
# leaves the level can hold (the tables' length), not num_leaves.
# ---------------------------------------------------------------------------

_TAB_ROWS = 8    # feat, thr, dleft, new_leaf, slot_left, slot_right, is_cat, na
_TAB_LANES = 32  # the tables' length is padded to a multiple of this


def _route_tabs(tables, na_bin) -> jnp.ndarray:
    """One level's RouteTables as the kernels' decode operand: [16, L_pad]
    bf16, the eight rows (feat, thr, dleft, new_leaf, slot_left, slot_right,
    is_cat, the split feature's missing bin) each as ``value + 1`` in a
    low-byte row (0-7) and a high-byte row (8-15). A byte is exact in bf16,
    so entries in [-1, 65534] are (num_leaves > 256, feature ids > 255,
    na_bin 256 = none); the +1 makes an all-zero column, and so an id past
    the tables, decode to feat = -1: does not split. ``na_bin`` is indexed
    by ``tables.feat``."""
    iscat = (tables.is_cat if tables.is_cat is not None
             else jnp.zeros_like(tables.feat))
    na = jnp.take(na_bin, jnp.maximum(tables.feat, 0))
    v = jnp.stack([tables.feat, tables.thr, tables.dleft, tables.new_leaf,
                   tables.slot_left, tables.slot_right, iscat,
                   na]).astype(jnp.int32) + 1                      # [8, L]
    v = _pad_rows(v, _TAB_LANES)
    return jnp.concatenate([v & 0xFF, (v >> 8) & 0xFF]).astype(jnp.bfloat16)


def _member_tab(tables) -> jnp.ndarray:
    """Categorical membership as the decode's second operand: [B, L_pad]
    bf16 0/1, a column a leaf."""
    return _pad_rows(tables.member.astype(jnp.bfloat16).T, _TAB_LANES)


def _decode_leaf(lid, tabs_ref, chunk: int):
    """Per-row lookup of the level's split tables: lid [1, C] i32 against
    tabs_ref [16, L] bf16 (see _route_tabs) -> (tv [8, C] f32, the table
    rows at each row's leaf; oh [L, C] bf16, the leaf one-hot, for the
    membership decode). One bf16 x bf16 -> f32 MXU pass, exact by its
    operand types: bytes and 0/1 are exact in bf16 and a column of the
    one-hot holds at most one 1 (int8 x int8 -> int32 read 0.5-4 ms a pass
    slower on the v5e: PERF.md, PR 30). An id outside [0, L) decodes to
    all -1."""
    l = tabs_ref.shape[1]
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (l, chunk), 0)
    oh = (lid == iota_l).astype(jnp.bfloat16)                     # [L, C]
    by = jax.lax.dot_general(
        tabs_ref[:], oh, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # [16, C]
    return by[0:_TAB_ROWS] + 256.0 * by[_TAB_ROWS:2 * _TAB_ROWS] - 1.0, oh


def _route_chunk(lid, bins_i, tabs_ref, memT_ref, *, f: int, s: int,
                 chunk: int):
    """Route one row-chunk through its leaves' splits (DataPartition::Split;
    NumericalDecision tree.h:240, CategoricalDecision tree.h:279).

    lid [1, C] i32; bins_i [F, C] i32, the rows ``feat`` indexes; memT_ref
    [B, L] bf16 or None. Returns (slot [1, C] i32, s where the row's child
    is not measured; new leaf id [1, C] i32)."""
    tv, oh = _decode_leaf(lid, tabs_ref, chunk)
    feat, thr, dleft = tv[0:1], tv[1:2], tv[2:3]
    new_leaf, slot_l, slot_r = tv[3:4], tv[4:5], tv[5:6]
    nav = tv[7:8]
    # Mosaic has no direct uint8 -> f32 cast: the caller hops through int32
    bins_f = bins_i.astype(jnp.float32)                           # [F, C]
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (f, chunk), 0) \
        .astype(jnp.float32)
    colv = jnp.sum(jnp.where(iota_f == feat, bins_f, 0.0), axis=0,
                   keepdims=True)
    # all-f32 mask arithmetic: a bool-valued jnp.where lowers to an i1 select
    # Mosaic cannot truncate to ("Unsupported target bitwidth for truncation")
    has = jnp.where(feat >= 0, 1.0, 0.0)
    is_na = jnp.where(colv == nav, 1.0, 0.0)
    gr_na = jnp.where(dleft == 0, 1.0, 0.0)
    gr_num = jnp.where(colv > thr, 1.0, 0.0)
    go_right = is_na * gr_na + (1.0 - is_na) * gr_num
    if memT_ref is not None:
        # decode the leaf's [B] bin-membership row, pick the row's bin ->
        # member -> LEFT
        b = memT_ref.shape[0]
        mem_bc = jax.lax.dot_general(
            memT_ref[:], oh, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # [B, C] 0/1
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (b, chunk), 0) \
            .astype(jnp.float32)
        member = jnp.sum(jnp.where(iota_b == colv, mem_bc, 0.0),
                         axis=0, keepdims=True)
        iscat = tv[6:7]
        go_right = iscat * (1.0 - member) + (1.0 - iscat) * go_right
    lid2 = jnp.where(has * go_right > 0, new_leaf, lid)
    slot = has * (go_right * slot_r + (1.0 - go_right) * slot_l) \
        + (1.0 - has) * float(s)
    return slot.astype(jnp.int32), lid2.astype(jnp.int32)


def _kernel_q8_fused(*refs, f: int, b: int, s: int, chunk: int,
                     has_cat: bool, nch: int = 3, swar: bool = False):
    """Fused route + int8 histogram for ONE feature group (F*B <= block cap).

    Per level the two-pass scheme reads the bin matrix twice (route kernel,
    then histogram kernel) and round-trips the [N] slot vector through HBM;
    at 10M rows the route pass alone measured 8.3 ms against the small-S
    histogram floor of ~15 ms. This kernel routes the chunk in-register and
    feeds the slot straight into the weight mask — one bins read, one launch,
    one level.

    refs: bins [F, C] u8; gq/hq/cq [C] i8; lid [C] i32; tabs [16, L] bf16
    (_route_tabs); [memT [B, L] bf16 when has_cat]; outputs: out
    [F*B, S*nch] i32 accumulated, lid_out [C] i32.
    """
    if has_cat:
        (bins_ref, gq_ref, hq_ref, cq_ref, lid_ref, tabs_ref,
         memT_ref, out_ref, lid_out) = refs
    else:
        (bins_ref, gq_ref, hq_ref, cq_ref, lid_ref, tabs_ref,
         out_ref, lid_out) = refs
        memT_ref = None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins_i = bins_ref[:].astype(jnp.int32)                       # [F, C]
    onehot = _onehot_i8(bins_i, f, b, chunk, swar)
    g = gq_ref[:].reshape(1, chunk).astype(jnp.int32)
    c = cq_ref[:].reshape(1, chunk).astype(jnp.int32)
    if nch == 3:
        h = hq_ref[:].reshape(1, chunk).astype(jnp.int32)
        ghc = jnp.concatenate([g, h, c], axis=0)
    else:   # constant hessian: (gq, count) only
        ghc = jnp.concatenate([g, c], axis=0)
    wv = jax.lax.broadcast_in_dim(ghc, (s, nch, chunk), (1, 2)) \
        .reshape(s * nch, chunk)
    slot_of_row = jax.lax.broadcasted_iota(
        jnp.int32, (s * nch, chunk), 0) // nch

    slot, lid2 = _route_chunk(lid_ref[:].reshape(1, chunk), bins_i, tabs_ref,
                              memT_ref, f=f, s=s, chunk=chunk)
    slot = jnp.minimum(slot, s)                                  # [1, C]

    # ---- int8 histogram (see _kernel_q8 / _onehot_i8) ----
    w = jnp.where(slot == slot_of_row, wv, 0).astype(jnp.int8)
    part = jax.lax.dot_general(
        onehot, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    out_ref[:] += part
    lid_out[:] = lid2.reshape(chunk)


def hist_routed_fused_q8(bins_T, gq, hq, cq, leaf_id, tables, na_bin,
                         num_slots: int, num_bins: int, scale_g, scale_h,
                         chunk: int = 0, const_hess: bool = False,
                         interpret: bool = False):
    """Fused route+histogram level pass: ONE kernel launch routes every row
    through the level's splits and accumulates the slot histograms. Returns
    (hist [S, 3, F, B] f32, lid2 [N] i32), bit-identical to route_level
    followed by hist_pallas_q8 (int32 accumulation is order-independent;
    the routing is the same integers).

    ``tables`` hold the leaves the level can have (their length is the
    decode's width, at most num_leaves): every ``leaf_id`` is below it.
    Only valid when every feature fits one accumulator block
    (F * num_bins <= _ACC_ROWS_MAX) — the router must see ALL columns.
    const_hess: see hist_pallas_q8."""
    f, n = bins_T.shape
    b, s = num_bins, num_slots
    nch = _q8_nch(const_hess)
    assert one_group(f, b)
    if chunk == 0:
        # doubled chunk halves per-chunk fixed costs; the SWAR int8
        # one-hot keeps 4096 under the 16MB VMEM ceiling through S=127
        # (measured 35 -> 31.7 ms at S=127). Without SWAR (B > 128 or
        # interpret) the compare path's wider intermediates keep the old
        # 192-row threshold
        wide_ok = 384 if (_swar_ok(b, interpret) and f * b <= 1792) else 192
        chunk = 4096 if s * nch <= wide_ok else 2048

    has_cat = tables.is_cat is not None
    tabs = _route_tabs(tables, na_bin)
    l = tabs.shape[1]

    bins_Tp = _pad_rows(bins_T, chunk)
    gq = _pad_rows(gq, chunk)
    hq = _pad_rows(hq, chunk)
    cq = _pad_rows(cq, chunk)
    lid_p = _pad_rows(leaf_id, chunk, value=l)  # padded rows: no leaf -> the
    n_p = bins_Tp.shape[1]                      # decode yields feat=-1 -> drop
    n_chunks = n_p // chunk

    in_specs = [
        pl.BlockSpec((f, chunk), lambda i: (0, i), memory_space=pltpu.VMEM),
        pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        pl.BlockSpec(tabs.shape, lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    args = [bins_Tp, gq, hq, cq, lid_p, tabs]
    if has_cat:
        memT = _member_tab(tables)
        in_specs.append(pl.BlockSpec(memT.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        args.append(memT)

    kern = functools.partial(_kernel_q8_fused, f=f, b=b, s=s, chunk=chunk,
                             has_cat=has_cat, nch=nch,
                             swar=_swar_ok(b, interpret))
    out, lid2 = pl.pallas_call(
        kern,
        name="hist_level_q8",
        grid=(n_chunks,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((f * b, s * nch), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((f * b, s * nch), jnp.int32),
            jax.ShapeDtypeStruct((n_p,), jnp.int32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_p * f * b * s * nch + 2 * n_p * l * 16,
            bytes_accessed=n_p * (f + 11) + f * b * s * 4 * nch,
            transcendentals=0),
        # the leaf ids are renewed in place, a chunk read before it is
        # written: a level loop that carries them then holds one buffer,
        # where the compiler copied the carry ahead of every pass
        input_output_aliases={4: 1},
        interpret=interpret,
    )(*args)

    out = out.reshape(f, b, s, nch)
    sg = scale_g * jnp.float32(1.0 / 127.0)
    sh = scale_h * jnp.float32(1.0 / 127.0)
    hist = _dequant_stack(out, const_hess, sg, sh).transpose(2, 3, 0, 1)
    return hist, lid2[:n]


def _leaf_sums_kernel(g_ref, h_ref, c_ref, lid_ref, out_ref, *,
                      l: int, chunk: int):
    """Exact per-leaf (grad, hess, count) sums: [5, L] f32 (hi/lo split)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    g = g_ref[:].reshape(1, chunk)
    h = h_ref[:].reshape(1, chunk)
    c = c_ref[:].reshape(1, chunk)
    gh = jnp.concatenate([g, h], axis=0)                         # [2, C] f32
    hi = gh.astype(jnp.bfloat16)
    lo = (gh - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    w = jnp.concatenate([hi, c.astype(jnp.bfloat16), lo], axis=0)  # [5, C]
    lid = lid_ref[:].reshape(1, chunk)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (l, chunk), 0)
    oh = (lid == iota_l).astype(jnp.bfloat16)                    # [L, C]
    part = jax.lax.dot_general(
        w, oh, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                      # [5, L]
    out_ref[:] += part


def leaf_sums_pallas(g, h, c, leaf_id, num_leaves: int, chunk: int = 8192,
                     interpret: bool = False) -> jnp.ndarray:
    """Per-leaf exact sums [3, L] f32 (the quantized path's leaf renewal:
    LightGBM 4.x renews leaf values from unquantized sums; reference analog
    is the exact leaf aggregation in LeafSplits, leaf_splits.hpp:20)."""
    l = num_leaves
    n = g.shape[0]
    g = _pad_rows(g, chunk)
    h = _pad_rows(h, chunk)
    c = _pad_rows(c, chunk)
    lid = _pad_rows(leaf_id, chunk, value=l)   # padded rows -> no leaf
    n_chunks = g.shape[0] // chunk
    kern = functools.partial(_leaf_sums_kernel, l=l, chunk=chunk)
    out = pl.pallas_call(
        kern,
        name="leaf_sums",
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((5, l), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((5, l), jnp.float32),
        interpret=interpret,
    )(g, h, c, lid)
    return jnp.stack([out[0] + out[3], out[1] + out[4], out[2]], axis=0)


# ---------------------------------------------------------------------------
# fused gradient + quantization front (tentpole (b))
#
# The per-iteration front of the quantized depthwise path used to cost four
# separate full-N HBM round-trips before the first level pass: the objective
# gradient/hessian write, two quantize_sr reads, and the root-histogram read.
# The two kernels below compute g/h IN-REGISTER from (score, aux, bag) — aux
# is the objective's per-row constant (label for L2, label_pos for logloss) —
# so the gradient rows are never materialized: one kernel emits the int8
# channels, the scales and the root histogram; the other renews leaf sums at
# tree end. Bit-identity with the unfused path is by construction: identical
# f32 ops in identical order (jnp.exp included — the interpreter runs the
# same XLA expf; compiled Mosaic exp can differ in the last ulp, which is
# why the parity tests pin the CPU interpreter, see PERF_NOTES Round 9).
# ---------------------------------------------------------------------------

def _i32c(v: int) -> jnp.ndarray:
    """uint32 constant as its two's-complement int32 bit pattern."""
    v &= 0xFFFFFFFF
    return jnp.int32(v - (1 << 32) if v >= (1 << 31) else v)


def _lsr(x, k: int):
    return jax.lax.shift_right_logical(x, jax.lax.full_like(x, k))


def _sr_dither(idx, seed, salt: int):
    """quantize_sr's counter-hash dither (ops/histogram.py) in int32 —
    Mosaic has no uint32 vectors, but wrapping two's-complement add/mul is
    bit-equal to uint32 arithmetic mod 2^32 and the shifts are explicitly
    logical, so u matches the XLA uint32 version bit-for-bit."""
    i = idx + _i32c(salt * 0x632BE59B)
    z = (i ^ (seed * _i32c(0x9E3779B9))) * _i32c(2654435761)
    z = (z ^ _lsr(z, 15)) * _i32c(2246822519)
    z = z ^ _lsr(z, 13)
    return _lsr(z, 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _grad_rows(spec, score, aux):
    """In-register replica of the built-in objectives' get_gradients for the
    fused front (see objectives.py fused_grad_spec). ``spec`` is static:
    ("l2",) for unweighted RegressionL2 (grad = score - label, hess = 1) or
    ("logloss", sigmoid, lw_pos, lw_neg) for unweighted Binary. Ops and
    association order match the objective code exactly so the f32 results
    are bit-identical. ("softmax", K) is the step's alone, not a kernel's
    (objectives.MulticlassSoftmax.grad_rows_spec)."""
    kind = spec[0]
    if kind == "l2":
        return score - aux, jnp.ones_like(score)
    if kind == "logloss":
        sigmoid, lw_pos, lw_neg = spec[1], spec[2], spec[3]
        t = 2.0 * aux - 1.0
        lw = jnp.where(aux > 0, lw_pos, lw_neg)
        resp = 1.0 / (1.0 + jnp.exp(t * sigmoid * score))
        grad = -t * resp * sigmoid * lw
        hess = sigmoid * sigmoid * resp * (1.0 - resp) * lw
        return grad, hess
    if kind == "softmax":
        # MulticlassSoftmax.get_gradients class-major: score [K, N], aux the
        # [N] i32 labels -> grad/hess [K, N], a class's rows contiguous
        k = spec[1]
        prob = jax.nn.softmax(score, axis=0)
        onehot = (jnp.arange(k, dtype=aux.dtype)[:, None] == aux[None, :])
        grad = prob - onehot.astype(jnp.float32)
        hess = (k / (k - 1.0)) * prob * (1.0 - prob)
        return grad, hess
    raise ValueError(f"unsupported fused gradient spec: {spec!r}")


def _grad_quant_kernel(bins_ref, score_ref, aux_ref, bag_ref, seed_ref,
                       gq_ref, hq_ref, cq_ref, sc_ref, out_ref, mx_ref, *,
                       f: int, b: int, chunk: int, spec,
                       const_hess: bool, swar: bool):
    """Two-phase fused gradient + SR-quantization + root histogram.

    grid (2, n_chunks) — the TPU grid runs the trailing axis innermost, so
    every phase-0 step (global max|g| / max h reduction into the mx scratch)
    completes before the first phase-1 step reads the final scales. Each
    phase recomputes g/h in-register from (score, aux, bag): two reads of
    three [N] f32 rows replace the unfused path's separate gradient
    write + quantize reads + histogram read.

    bins [F, C] u8; score/aux/bag [C] f32; seed (1, 1) i32 SMEM; outputs
    gq/hq/cq [C] i8, sc (8, 128) f32 (row 0 lane 0 = scale_g, row 1 lane 0 =
    scale_h), out [F*B, nch] i32; scratch mx (2, 128) f32 lane-max partials.
    """
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _():
        mx_ref[:] = jnp.zeros_like(mx_ref)
        out_ref[:] = jnp.zeros_like(out_ref)
        sc_ref[:] = jnp.zeros_like(sc_ref)

    score = score_ref[:].reshape(1, chunk)
    aux = aux_ref[:].reshape(1, chunk)
    bag = bag_ref[:].reshape(1, chunk)
    grad, hess = _grad_rows(spec, score, aux)
    g = grad * bag
    h = hess * bag

    @pl.when(p == 0)
    def _():
        # lane-parallel partial max; channels are 0 on padded rows, so the
        # zero init is neutral (|g| >= 0, and h >= 0 on both spec families)
        pg = jnp.max(jnp.abs(g).reshape(chunk // 128, 128), axis=0,
                     keepdims=True)
        hv = h if const_hess else jnp.abs(h)
        ph = jnp.max(hv.reshape(chunk // 128, 128), axis=0, keepdims=True)
        mx_ref[:] = jnp.maximum(mx_ref[:], jnp.concatenate([pg, ph], axis=0))
        # the row-blocks are flushed once per phase; phase 0's visit writes
        # zeros, phase 1 overwrites with the real values
        gq_ref[:] = jnp.zeros_like(gq_ref)
        hq_ref[:] = jnp.zeros_like(hq_ref)
        cq_ref[:] = jnp.zeros_like(cq_ref)

    @pl.when(p == 1)
    def _():
        mg = jnp.max(mx_ref[0:1, :], axis=1, keepdims=True)        # (1, 1)
        mh = jnp.max(mx_ref[1:2, :], axis=1, keepdims=True)
        # exact make_quant / quantize_sr scale semantics (histogram.py):
        # scale_g floored at 1e-20; const-hess scale_h = 127 * max(h)
        # (reconstructs h_const * count at dequant), unfloored
        scale_g = jnp.maximum(mg, jnp.float32(1e-20))
        scale_h = (jnp.float32(127.0) * mh if const_hess
                   else jnp.maximum(mh, jnp.float32(1e-20)))

        @pl.when(i == 0)
        def _():
            r = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
            sc_ref[:] = jnp.where(r == 0, scale_g, 0.0) \
                + jnp.where(r == 1, scale_h, 0.0)

        idx = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) + i * chunk
        seed = seed_ref[0, 0]
        ug = _sr_dither(idx, seed, 1)
        gq = jnp.clip(jnp.floor(g * (127.0 / scale_g) + ug), -127, 127)
        gq_ref[:] = gq.astype(jnp.int8).reshape(chunk)
        cw = jnp.where(bag > 0, 1.0, 0.0)
        cq_ref[:] = cw.astype(jnp.int8).reshape(chunk)
        if const_hess:
            hq_ref[:] = jnp.zeros_like(hq_ref)
            w3 = jnp.concatenate([gq.astype(jnp.int32),
                                  cw.astype(jnp.int32)], axis=0)
        else:
            uh = _sr_dither(idx, seed, 2)
            hq = jnp.clip(jnp.floor(h * (127.0 / scale_h) + uh), -127, 127)
            hq_ref[:] = hq.astype(jnp.int8).reshape(chunk)
            w3 = jnp.concatenate([gq.astype(jnp.int32),
                                  hq.astype(jnp.int32),
                                  cw.astype(jnp.int32)], axis=0)
        bins_i = bins_ref[:].astype(jnp.int32)
        onehot = _onehot_i8(bins_i, f, b, chunk, swar)
        part = jax.lax.dot_general(
            onehot, w3.astype(jnp.int8),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)                     # [F*B, nch]
        out_ref[:] += part


def grad_quant_hist0_pallas(bins_T, score, aux, bag, seed, spec,
                            num_bins: int, const_hess: bool = False,
                            chunk: int = 0, interpret: bool = False):
    """Fused objective gradient + int8 quantization + root histogram.

    Returns (gq [N] i8, hq [N] i8 | None, cq [N] i8, scale_g f32 scalar,
    scale_h f32 scalar, hist0 [3, F, B] f32) — bit-identical to the unfused
    objective.get_gradients -> make_quant -> hist_leaf chain on the Pallas
    path (f32 max is order-independent, the dither hash is replayed exactly,
    and the int32 histogram accumulation is order-independent).

    Only valid when every feature fits one accumulator block
    (F * num_bins <= _ACC_ROWS_MAX)."""
    f, n = bins_T.shape
    b = num_bins
    nch = _q8_nch(const_hess)
    assert one_group(f, b)
    if chunk == 0:
        chunk = 4096 if (_swar_ok(b, interpret) and f * b <= 1792) else 2048
    bins_Tp = _pad_rows(bins_T, chunk)
    score_p = _pad_rows(score, chunk)
    aux_p = _pad_rows(aux, chunk)
    bag_p = _pad_rows(bag, chunk)   # padded rows: bag 0 -> zero channels
    n_p = bins_Tp.shape[1]
    n_chunks = n_p // chunk
    seed_arr = jnp.asarray(seed).astype(jnp.int32).reshape(1, 1)

    kern = functools.partial(_grad_quant_kernel, f=f, b=b, chunk=chunk,
                             spec=spec, const_hess=const_hess,
                             swar=_swar_ok(b, interpret))
    gq, hq, cq, sc, out = pl.pallas_call(
        kern,
        name="grad_quant_hist0",
        grid=(2, n_chunks),
        in_specs=[
            pl.BlockSpec((f, chunk), lambda p, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda p, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda p, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda p, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda p, i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((chunk,), lambda p, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda p, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda p, i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 128), lambda p, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((f * b, nch), lambda p, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_p,), jnp.int8),
            jax.ShapeDtypeStruct((n_p,), jnp.int8),
            jax.ShapeDtypeStruct((n_p,), jnp.int8),
            jax.ShapeDtypeStruct((8, 128), jnp.float32),
            jax.ShapeDtypeStruct((f * b, nch), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((2, 128), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * n_p * f * b * nch + 40 * n_p,
            bytes_accessed=n_p * (f + 12) * 2 + 3 * n_p + f * b * nch * 4,
            transcendentals=2 * n_p if spec[0] == "logloss" else 0),
        interpret=interpret,
    )(bins_Tp, score_p, aux_p, bag_p, seed_arr)

    scale_g = sc[0, 0]
    scale_h = sc[1, 0]
    out = out.reshape(f, b, nch)
    sg = scale_g * jnp.float32(1.0 / 127.0)
    sh = scale_h * jnp.float32(1.0 / 127.0)
    hist0 = _dequant_stack(out, const_hess, sg, sh).transpose(2, 0, 1)
    return (gq[:n], None if const_hess else hq[:n], cq[:n],
            scale_g, scale_h, hist0)


def _leaf_sums_grad_kernel(score_ref, aux_ref, bag_ref, lid_ref, out_ref, *,
                           l: int, chunk: int, spec):
    """_leaf_sums_kernel with g/h/c computed in-register from
    (score, aux, bag) — the fused-objective path's leaf renewal reads no
    materialized gradient rows."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    score = score_ref[:].reshape(1, chunk)
    aux = aux_ref[:].reshape(1, chunk)
    bag = bag_ref[:].reshape(1, chunk)
    grad, hess = _grad_rows(spec, score, aux)
    g = grad * bag
    h = hess * bag
    c = jnp.where(bag > 0, 1.0, 0.0)
    gh = jnp.concatenate([g, h], axis=0)                         # [2, C] f32
    hi = gh.astype(jnp.bfloat16)
    lo = (gh - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    w = jnp.concatenate([hi, c.astype(jnp.bfloat16), lo], axis=0)  # [5, C]
    lid = lid_ref[:].reshape(1, chunk)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (l, chunk), 0)
    oh = (lid == iota_l).astype(jnp.bfloat16)                    # [L, C]
    part = jax.lax.dot_general(
        w, oh, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                      # [5, L]
    out_ref[:] += part


def leaf_sums_grad_pallas(score, aux, bag, leaf_id, spec, num_leaves: int,
                          chunk: int = 8192,
                          interpret: bool = False) -> jnp.ndarray:
    """leaf_sums_pallas for the fused-objective path: [3, L] f32,
    bit-identical to leaf_sums_pallas(g, h, c, ...) on the same rows (same
    chunking, same hi/lo bf16 contraction; g/h/c recomputed in-register)."""
    l = num_leaves
    n = score.shape[0]
    score = _pad_rows(score, chunk)
    aux = _pad_rows(aux, chunk)
    bag = _pad_rows(bag, chunk)
    lid = _pad_rows(leaf_id, chunk, value=l)   # padded rows -> no leaf
    n_chunks = score.shape[0] // chunk
    kern = functools.partial(_leaf_sums_grad_kernel, l=l, chunk=chunk,
                             spec=spec)
    out = pl.pallas_call(
        kern,
        name="leaf_sums_grad",
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((5, l), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((5, l), jnp.float32),
        interpret=interpret,
    )(score, aux, bag, lid)
    return jnp.stack([out[0] + out[3], out[1] + out[4], out[2]], axis=0)


def _route_kernel(*refs, f: int, s: int, chunk: int, has_cat: bool):
    """Route one row-chunk through its leaf's split (_route_chunk).

    refs: bins [F, C] uint8; lid [C] i32; tabs [16, L] bf16 (_route_tabs);
    [memT [B, L] bf16 when has_cat]; outputs slot [C] i32, new leaf id [C] i32.
    """
    if has_cat:
        bins_ref, lid_ref, tabs_ref, memT_ref, slot_out, lid_out = refs
    else:
        bins_ref, lid_ref, tabs_ref, slot_out, lid_out = refs
        memT_ref = None
    slot, lid2 = _route_chunk(lid_ref[:].reshape(1, chunk),
                              bins_ref[:].astype(jnp.int32), tabs_ref,
                              memT_ref, f=f, s=s, chunk=chunk)
    slot_out[:] = slot.reshape(chunk)
    lid_out[:] = lid2.reshape(chunk)


def route_level_pallas(bins_T, leaf_id, tables, na_bin, num_slots: int,
                       chunk: int = 0,
                       interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas DataPartition::Split analog. Returns (slot [N] i32, lid2 [N] i32).

    ``bins_T``: the [F, N] rows that ``tables.feat`` indexes — the level's
    split columns from histogram.py route_rows, at most 128 of them, and
    ``na_bin`` their missing bins. ``tables``: as hist_routed_fused_q8.
    chunk=0 picks automatically: _CHUNK_Q8 up to 256 rows (+4% end-to-end at
    10M measured with the q8 kernel at the same chunk), _CHUNK above — the
    f32 [F, chunk] per-chunk intermediates double with the chunk."""
    if chunk == 0:
        chunk = _CHUNK_Q8 if bins_T.shape[0] <= 256 else _CHUNK
    f, n = bins_T.shape
    has_cat = tables.is_cat is not None
    tabs = _route_tabs(tables, na_bin)

    bins_Tp = _pad_rows(bins_T, chunk)
    lid_p = _pad_rows(leaf_id, chunk)
    n_chunks = bins_Tp.shape[1] // chunk

    in_specs = [
        pl.BlockSpec((f, chunk), lambda i: (0, i), memory_space=pltpu.VMEM),
        pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        pl.BlockSpec(tabs.shape, lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    args = [bins_Tp, lid_p, tabs]
    if has_cat:
        memT = _member_tab(tables)
        in_specs.append(pl.BlockSpec(memT.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        args.append(memT)

    kern = functools.partial(_route_kernel, f=f, s=num_slots, chunk=chunk,
                             has_cat=has_cat)
    slot, lid2 = pl.pallas_call(
        kern,
        name="route_level",
        grid=(n_chunks,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bins_Tp.shape[1],), jnp.int32),
            jax.ShapeDtypeStruct((bins_Tp.shape[1],), jnp.int32),
        ),
        input_output_aliases={1: 1},    # as hist_routed_fused_q8
        interpret=interpret,
    )(*args)
    return slot[:n], lid2[:n]


# ---------------------------------------------------------------------------
# small-table gather: table[idx] as a one-hot [L, C] mask contraction, as the
# level kernels' decode above, over an f32 table (leaf values)
# ---------------------------------------------------------------------------

def _take_kernel(tab_ref, idx_ref, out_ref, *, l: int, chunk: int):
    idx = idx_ref[:].reshape(1, chunk)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (l, chunk), 0)
    oh = (idx == iota_l).astype(jnp.float32)                     # [L, C]
    # HIGHEST precision: default MXU bf16 truncation would round every leaf
    # value to ~8 mantissa bits and bias all score updates
    out = jax.lax.dot_general(
        tab_ref[:].reshape(1, l), oh,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)                     # [1, C]
    out_ref[:] = out.reshape(chunk)


def _take_call(l: int, n_pad: int, chunk: int, interpret: bool):
    kern = functools.partial(_take_kernel, l=l, chunk=chunk)
    return pl.pallas_call(
        kern,
        name="take_small",
        grid=(n_pad // chunk,),
        in_specs=[
            pl.BlockSpec((l,), lambda i: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((chunk,), lambda i: (i,),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _scoped_take(scope: str, l: int, n_pad: int, chunk: int, interpret: bool):
    return scoped_jit(_take_call(l, n_pad, chunk, interpret), scope)


def take_small_pallas(table: jnp.ndarray, idx: jnp.ndarray,
                      chunk: int = 8192, interpret: bool = False,
                      scope: str = None) -> jnp.ndarray:
    """table[idx] for a small f32 table (out-of-range idx -> 0.0).

    The MXU one-hot contraction replaces XLA's per-element gather (~7ms per
    1M rows); measured sub-ms at 1M rows. ``scope``: for a call dispatched
    on its own, the device scope the kernel runs under (``scoped_jit``).
    The scoped program is kept per shape, so a caller that repeats a shape
    (validation scoring, every iteration) loads it once; a bare
    ``pallas_call`` is a fresh jit, traced and loaded again, on every call."""
    l = table.shape[0]
    n = idx.shape[0]
    idx_p = _pad_rows(idx, chunk, value=l)
    n_pad = idx_p.shape[0]
    call = (_take_call(l, n_pad, chunk, interpret) if scope is None else
            _scoped_take(scope, l, n_pad, chunk, interpret))
    return call(table.astype(jnp.float32), idx_p)[:n]


# ---------------------------------------------------------------------------
# whole-tree walk: a chunk of rows through every level of a FINISHED tree in
# one kernel, its bins read once. The decisions of ops/predict.route_bins
# (NumericalDecision, tree.h:240) made as _route_chunk makes a level's: the
# node one-hot against the tree's node tables on the MXU, an F-way select for
# the bin column, where the XLA walk pays six per-row gathers a step
# ---------------------------------------------------------------------------

# the widths the walk was compiled and timed at (PERF.md, PR 35): the select
# over 128 feature rows (route_level's width) and a node one-hot of
# num_leaves = 1,024 both fit VMEM at the chunk below
WALK_MAX_FEATURES = 128
WALK_MAX_NODES = 1023


def _walk_tabs(split_feature, threshold_bin, default_left, left_child,
               right_child, na_bin) -> jnp.ndarray:
    """A finished tree's node tables as _decode_leaf's operand: [16, M_pad]
    bf16, a column a node, in _route_tabs's rows and byte split (feat, thr,
    dleft, left child, right child, -, -, the split feature's missing bin).
    A child pointer is a node (>= 0) or ``~leaf`` (down to -(M + 1)): stored
    plus M + 1, so that it fits the decode's [-1, 65534] and the kernel takes
    the offset off again."""
    m = split_feature.shape[0]
    unused = jnp.full((m,), -1, jnp.int32)
    na = jnp.take(na_bin, jnp.maximum(split_feature, 0))
    v = jnp.stack([split_feature, threshold_bin, default_left,
                   left_child + (m + 1), right_child + (m + 1), unused,
                   unused, na]).astype(jnp.int32) + 1              # [8, M]
    v = _pad_rows(v, _TAB_LANES)
    return jnp.concatenate([v & 0xFF, (v >> 8) & 0xFF]).astype(jnp.bfloat16)


def _walk_tree_kernel(start_ref, bins_ref, tabs_ref, leaf_out, steps_out, *,
                      f: int, n: int, off: int, chunk: int, max_steps: int):
    """One row-chunk from the root to its leaves.

    start_ref: [1] i32 in SMEM, the pointer a row starts on (0, or -1 for a
    tree of one leaf); bins_ref [F, C] uint8; tabs_ref [16, M_pad] bf16
    (_walk_tabs); leaf_out [C] i32; steps_out [1] i32 in SMEM, the most steps
    a chunk has taken. The pointer is ops/predict._walk's: a node, or ~leaf
    once the row is parked; rows past ``n`` start parked, so the padding
    neither walks nor counts."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        steps_out[0] = 0

    # Mosaic has no direct uint8 -> f32 cast: hop through int32, once a chunk
    bins_f = bins_ref[:].astype(jnp.int32).astype(jnp.float32)    # [F, C]
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (f, chunk), 0) \
        .astype(jnp.float32)
    row = i * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    ptr0 = jnp.where(row < n, start_ref[0], -1)                   # [1, C]

    def rows_still_moving(carry):
        ptr, step = carry
        return (step < max_steps) & (jnp.max(ptr) >= 0)

    def body(carry):
        ptr, step = carry
        tv, _ = _decode_leaf(ptr, tabs_ref, chunk)   # a parked row: all -1
        feat, thr, dleft = tv[0:1], tv[1:2], tv[2:3]
        left, right, nav = tv[3:4], tv[4:5], tv[7:8]
        colv = jnp.sum(jnp.where(iota_f == feat, bins_f, 0.0), axis=0,
                       keepdims=True)
        # all-f32 mask arithmetic, as _route_chunk (Mosaic's i1 selects)
        is_na = jnp.where(colv == nav, 1.0, 0.0)
        gl_num = jnp.where(colv <= thr, 1.0, 0.0)
        go_left = is_na * dleft + (1.0 - is_na) * gl_num
        nxt = go_left * left + (1.0 - go_left) * right - float(off)
        return jnp.where(ptr >= 0, nxt.astype(jnp.int32), ptr), step + 1

    ptr, steps = jax.lax.while_loop(rows_still_moving, body,
                                    (ptr0, jnp.int32(0)))
    leaf_out[:] = (-1 - jnp.minimum(ptr, -1)).reshape(chunk)  # ~ptr, leaves
    steps_out[0] = jnp.maximum(steps_out[0], steps)


def walk_tree(split_feature, threshold_bin, default_left, left_child,
              right_child, num_leaves, bins_T, na_bin, n: int, max_steps: int,
              chunk: int = _CHUNK_Q8,
              interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ops/predict.route_bins's walk of a numerical tree in one kernel:
    (leaf [n] i32, steps i32 scalar), both equal to the XLA walk's to the
    bit. ``bins_T``: [F_pad, N_pad] uint8, the first ``n`` columns the rows
    (Dataset.bins_T; any N_pad >= n), at most WALK_MAX_FEATURES rows; the
    tree arrays [M], M <= WALK_MAX_NODES. Every chunk stops when none of its
    rows is on an internal node, and ``steps`` is the most any chunk took:
    what the XLA walk counts. ``chunk``: 500 k x 28 rows through 255
    leaves read 1.99 / 1.27 / 0.94 / 0.80 ms at 1024 / 2048 / 4096 / 8192
    rows a chunk on the v5e (197.5 ms in XLA); 4096 is the widest that was
    also compiled at both caps."""
    f = bins_T.shape[0]
    m = split_feature.shape[0]
    assert f <= WALK_MAX_FEATURES and m <= WALK_MAX_NODES
    tabs = _walk_tabs(split_feature, threshold_bin, default_left, left_child,
                      right_child, na_bin)
    bins_Tp = _pad_rows(bins_T, chunk)
    n_p = bins_Tp.shape[1]
    start = jnp.where(num_leaves > 1, 0, -1).astype(jnp.int32).reshape(1)
    kern = functools.partial(_walk_tree_kernel, f=f, n=n, off=m + 1,
                             chunk=chunk, max_steps=max_steps)
    leaf, steps = pl.pallas_call(
        kern,
        name="walk_tree",
        grid=(n_p // chunk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((f, chunk), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec(tabs.shape, lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((chunk,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_p,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ),
        interpret=interpret,
    )(start, bins_Tp, tabs)
    return leaf[:n], steps[0]
