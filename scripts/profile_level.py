"""Isolate one depthwise level() call (with bookkeeping) vs its hist_routed core
on the [L,3,F,B] channel-major state layout the grower uses.

``--json`` emits one machine-readable line instead of the human table,
including the shallow-level launch accounting: levels 0..D of one tree on
the fused pallas path cost exactly TWO kernel launches — the
grad+quant+hist0 front (ops/pallas_hist.grad_quant_hist0_pallas) and ONE
multi-level replay megapass (hist_routed_fused_multi_q8, all D tables
stacked) — verified bit-identical against D sequential level passes.
``--rows``/``--leaves`` shrink the workload for CI smoke runs. Timings are
taken on a TPU backend only: anywhere else the script still checks the
megapass against the sequential passes (interpret mode) and reports the
launch/channel accounting, with every ``*_ms`` left null.
"""
# profiling harness: building jit wrappers per invocation is the POINT
# (each run measures a fresh compile/dispatch pair)
# tpu-lint: disable-file=retrace-hazard
import argparse
import json
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import pallas_hist as PH
from lightgbm_tpu.ops.grow import GrowParams
from lightgbm_tpu.ops.grow_depthwise import (_OOB, _scatter_set,
                                             grow_tree_depthwise)
from lightgbm_tpu.ops.split import NEG_INF, SplitParams, best_split


ON_CHIP = jax.default_backend() == "tpu"


def t_loop(op, K=6, reps=3):
    if not ON_CHIP:
        return None

    def loop(k):
        def body(i, acc):
            return acc + op(1.0 + i.astype(jnp.float32) * 1e-9)
        return jax.lax.fori_loop(0, k, body, jnp.zeros((), jnp.float32))
    f1 = jax.jit(partial(loop, 1))
    fK = jax.jit(partial(loop, K))
    jax.block_until_ready(f1()); jax.block_until_ready(fK())
    def t(f):
        best = 1e9
        for _ in range(reps):
            t0 = time.time(); jax.block_until_ready(f()); best = min(best, time.time() - t0)
        return best
    return (t(fK) - t(f1)) / (K - 1)


def shallow_megapass(bins_T, N, F, B, L, emit_json: bool,
                     const_hess: bool = False, packed: bool = False):
    """Levels 0..D of one tree in two pallas launches.

    Launch 1 (grad+quant+hist0) is structural — grow_tree_depthwise's fused
    front (gp.fused_obj) derives the quantized channels and the root
    histogram from (score, aux, bag) in one kernel. Here we account for it
    and measure launch 2: the D-level replay megapass vs D sequential
    single-level passes over the SAME stacked split tables, asserting
    bit-identical histograms and final row routing.

    ``const_hess`` profiles the hessian-elided kernels; ``packed`` requests
    the packed g/h lattice (engages only when the guard-bit budget fits N)."""
    rng = np.random.RandomState(1)
    interp = not ON_CHIP
    pack_k = H.pack_guard_bits(N, const_hess) if packed else 0
    nch = PH._q8_nch(const_hess, pack_k)
    gq = jnp.asarray(rng.randint(-127, 128, N, dtype=np.int8))
    cq = jnp.ones(N, jnp.int8)
    hq = cq if const_hess else jnp.asarray(
        rng.randint(0, 128, N, dtype=np.int8))
    lid0 = jnp.zeros(N, jnp.int32)
    na_bin = jnp.full(F, B + 1, jnp.int32)
    # levels 1..D: frontier of 2^lvl leaves, every frontier leaf splits on a
    # random feature — the width every level floors to is the smallest
    # master width >= the frontier, i.e. 32 for all of levels 1..5
    D = 5
    S = PH.floor_slot_width(2 ** D, max(1, L // 2))
    tables_seq = []
    for lvl in range(1, D + 1):
        width = 2 ** (lvl - 1)       # leaves entering this level
        feat = np.full(L, -1, np.int32)
        feat[:width] = rng.randint(0, F, width)
        thr = np.zeros(L, np.int32)
        thr[:width] = rng.randint(1, B - 1, width)
        new_leaf = np.arange(L, dtype=np.int32)
        new_leaf[:width] = width + np.arange(width)
        slot_left = np.full(L, S, np.int32)
        slot_left[:width] = np.arange(width)
        tables_seq.append(H.RouteTables(
            feat=jnp.asarray(feat), thr=jnp.asarray(thr),
            dleft=jnp.zeros(L, jnp.int32), new_leaf=jnp.asarray(new_leaf),
            slot_left=jnp.asarray(slot_left),
            slot_right=jnp.full(L, S, jnp.int32)))
    one = jnp.float32(1.0)

    mega = jax.jit(lambda bt, ll: PH.hist_routed_fused_multi_q8(
        bt, gq, hq, cq, ll, tuple(tables_seq), na_bin, S, B, one, one, L,
        const_hess=const_hess, pack_k=pack_k, interpret=interp))

    def seq(bt, ll):
        hists = []
        for t in tables_seq:
            h_, ll = PH.hist_routed_fused_q8(
                bt, gq, hq, cq, ll, t, na_bin, S, B, one, one, L,
                const_hess=const_hess, pack_k=pack_k, interpret=interp)
            hists.append(h_)
        return jnp.stack(hists), ll
    seq = jax.jit(seq)

    hm, lm = jax.block_until_ready(mega(bins_T, lid0))
    hs, ls = jax.block_until_ready(seq(bins_T, lid0))
    identical = bool(jnp.array_equal(hm, hs)) and bool(jnp.array_equal(lm, ls))

    def t(f):
        if not ON_CHIP:
            return None
        best = 1e9
        for _ in range(3):
            t0 = time.time()
            jax.block_until_ready(f(bins_T, lid0))
            best = min(best, time.time() - t0)
        return round(best * 1000, 3)
    mega_ms, seq_ms = t(mega), t(seq)
    out = {
        "levels": list(range(0, D + 1)),
        "slot_width": S,
        "channels": nch,
        "packed": pack_k > 0,
        "pack_guard_bits": pack_k,
        # analytic MXU work of one level pass: [F*B, chunk] one-hot x
        # [S*nch, chunk] row weights over all N rows
        "macs_per_level": N * F * B * S * nch,
        "pallas_launches": 2,
        "launch_breakdown": [
            "grad_quant_hist0_pallas (gradients + int8 quantize + level-0 "
            "root histogram, one kernel)",
            f"hist_routed_fused_multi_q8 d={D} (levels 1-{D} replay, one "
            "kernel)"],
        "megapass_ms": mega_ms,
        "sequential_levels_ms": seq_ms,
        "bit_identical_vs_sequential": identical,
    }
    if not emit_json:
        print(f"shallow megapass levels 1-{D} (S={S}): {mega_ms} ms "
              f"(sequential {seq_ms} ms, bit_identical={identical})")
    assert identical, "megapass diverged from sequential level passes"
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line instead of the human table")
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--max-bin", type=int, default=64)
    ap.add_argument("--const-hess", action="store_true",
                    help="profile the const-hessian elided q8 megapass")
    ap.add_argument("--packed", action="store_true",
                    help="request the packed g/h lattice for the megapass "
                         "(engages only when the guard budget fits --rows)")
    args = ap.parse_args()

    N, F, B, L = args.rows, args.features, args.max_bin, args.leaves
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B - 1, size=(N, F)).astype(np.uint8))
    bins_T = jnp.asarray(np.ascontiguousarray(np.asarray(bins).T))
    g = jnp.asarray(rng.randn(N).astype(np.float32))
    h = jnp.asarray(rng.rand(N).astype(np.float32))
    c = jnp.ones(N, jnp.float32)
    num_bins = jnp.full(F, B - 1, jnp.int32)
    na_bin = jnp.full(F, 256, jnp.int32)
    fmask = jnp.ones(F, bool)
    sp = SplitParams(min_data_in_leaf=20)
    gp = GrowParams(num_leaves=L, max_bin=B, split=sp, hist_impl="onehot")

    SLOTS = max(2, (L + 1) // 2)
    leaf_id0 = jnp.asarray(rng.randint(0, SLOTS, size=N).astype(np.int32))
    hist_state = jnp.asarray(rng.rand(L, 3, F, B).astype(np.float32))
    leaf_g = jnp.asarray(rng.randn(L).astype(np.float32))
    leaf_h = jnp.abs(jnp.asarray(rng.randn(L).astype(np.float32))) + 1
    leaf_c = jnp.full(L, 4000.0)
    active = jnp.ones(L, bool)
    leaves_iota = jnp.arange(L, dtype=jnp.int32)

    # full level() including bookkeeping — replicate by re-creating level here
    def one_level(s):
        st_hist = hist_state * s
        res = jax.vmap(lambda hh, g_, h_, c_, a_: best_split(
            hh, num_bins, na_bin, g_, h_, c_, fmask, sp, a_)
        )(st_hist, leaf_g, leaf_h, leaf_c, active)
        cand = active & (res.gain > 0.0) & (res.gain > NEG_INF / 2)
        key = jnp.where(cand, res.gain, -jnp.inf)
        order = jnp.argsort(-key)
        rank = jnp.zeros(L, jnp.int32).at[order].set(leaves_iota)
        sel = cand & (rank < SLOTS - 1)
        idx_in_lvl = (jnp.cumsum(sel.astype(jnp.int32)) - 1).astype(jnp.int32)
        new_leaf = (SLOTS - 1) + idx_in_lvl
        lg, lh, lc = res.left_g, res.left_h, res.left_cnt
        rg, rh, rc = leaf_g - lg, leaf_h - lh, leaf_c - lc
        small_is_left = lc <= rc
        tables = H.RouteTables(
            feat=jnp.where(sel, res.feature, -1), thr=res.bin,
            dleft=res.default_left.astype(jnp.int32), new_leaf=new_leaf,
            slot_left=jnp.where(sel & small_is_left, idx_in_lvl, SLOTS),
            slot_right=jnp.where(sel & ~small_is_left, idx_in_lvl, SLOTS))
        hist_small, leaf_id2 = H.hist_routed(
            bins, g, h, c, leaf_id0, tables, na_bin, SLOTS, B, "onehot")
        leaf_of_slot = _scatter_set(jnp.full(SLOTS, _OOB, jnp.int32),
                                    idx_in_lvl, leaves_iota, sel)
        slot_used = leaf_of_slot < L
        parent_hist = st_hist[jnp.minimum(leaf_of_slot, L - 1)]
        hist_sib = parent_hist - hist_small
        sl = small_is_left[jnp.minimum(leaf_of_slot, L - 1)][:, None, None, None]
        hist_left = jnp.where(sl, hist_small, hist_sib)
        hist_right = jnp.where(sl, hist_sib, hist_small)
        new_leaf_of_slot = _scatter_set(jnp.full(SLOTS, _OOB, jnp.int32),
                                        idx_in_lvl, new_leaf, sel)
        hist2 = st_hist.at[jnp.where(slot_used, leaf_of_slot, _OOB)].set(
            hist_left, mode="drop")
        hist2 = hist2.at[jnp.where(slot_used, new_leaf_of_slot, _OOB)].set(
            hist_right, mode="drop")
        return hist2.sum() + leaf_id2.sum().astype(jnp.float32)

    def hist_only(s):
        tables = H.RouteTables(
            feat=jnp.zeros(L, jnp.int32),
            thr=jnp.full(L, B // 2, jnp.int32),
            dleft=jnp.zeros(L, jnp.int32),
            new_leaf=jnp.arange(L, dtype=jnp.int32),
            slot_left=jnp.zeros(L, jnp.int32),
            slot_right=jnp.ones(L, jnp.int32))
        hs, lid2 = H.hist_routed(bins, g * s, h, c, leaf_id0, tables, na_bin,
                                 SLOTS, B, "onehot")
        return hs.sum() + lid2.sum().astype(jnp.float32)

    def bookkeeping_only(s):
        st_hist = hist_state * s
        res = jax.vmap(lambda hh, g_, h_, c_, a_: best_split(
            hh, num_bins, na_bin, g_, h_, c_, fmask, sp, a_)
        )(st_hist, leaf_g, leaf_h, leaf_c, active)
        cand = active & (res.gain > 0.0)
        key = jnp.where(cand, res.gain, -jnp.inf)
        order = jnp.argsort(-key)
        rank = jnp.zeros(L, jnp.int32).at[order].set(leaves_iota)
        sel = cand & (rank < SLOTS - 1)
        idx_in_lvl = (jnp.cumsum(sel.astype(jnp.int32)) - 1).astype(jnp.int32)
        leaf_of_slot = _scatter_set(jnp.full(SLOTS, _OOB, jnp.int32),
                                    idx_in_lvl, leaves_iota, sel)
        parent_hist = st_hist[jnp.minimum(leaf_of_slot, L - 1)]
        hist_sib = parent_hist - hist_state[:SLOTS]
        hist2 = st_hist.at[jnp.where(leaf_of_slot < L, leaf_of_slot, _OOB)].set(
            hist_sib, mode="drop")
        return hist2.sum()

    phases = {}
    for name, key, op, K in (
            ("level() complete (S=%d)" % SLOTS, "level_complete", one_level, 6),
            ("hist_routed only (S=%d)" % SLOTS, "hist_routed", hist_only, 6),
            ("bookkeeping only (best_split+state)", "bookkeeping",
             bookkeeping_only, 6)):
        per = t_loop(op, K=K)
        phases[key] = None if per is None else round(per * 1000, 3)
        if not args.json:
            print(f"{name:50s} {phases[key]} ms")

    # whole grower for reference
    f_grow = jax.jit(lambda s: grow_tree_depthwise(
        bins, g * s, h, c, num_bins, na_bin, fmask, gp)[0].leaf_value.sum())
    per = t_loop(f_grow, K=3)
    phases["grow_tree_depthwise"] = None if per is None else round(per * 1000, 3)
    if not args.json:
        print(f"{'grow_tree_depthwise whole':50s} "
              f"{phases['grow_tree_depthwise']} ms")

    shallow = shallow_megapass(bins_T, N, F, B, L, args.json,
                               const_hess=args.const_hess,
                               packed=args.packed)
    if args.json:
        print(json.dumps({
            "rows": N, "features": F, "max_bin": B, "num_leaves": L,
            "backend": jax.default_backend(),
            "channels": shallow["channels"], "packed": shallow["packed"],
            "phases_ms": phases, "shallow": shallow}))


if __name__ == "__main__":
    main()
