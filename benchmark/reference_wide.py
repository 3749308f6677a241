"""The plain reference for a matrix of thousands of columns.

``benchmark/reference.py``'s walk with two things changed for the width,
the formulas and the numbers judged being the same (its docstring has
them): (1) a level's histogram is accumulated per tile of ``TILE`` features,
so the one-hot operand of the contraction is ``[SUB, TILE * NBINS]`` (0.5 GB)
where the whole width would be 4.2 GB at 2,000 features, and routing is a
pass of its own over the whole row; (2) the float64 split table of a level is
made per tile on a few host threads (at 2,000 x 64 bins x 128 slots x 7
pieces it is 115 M numbers a level). The rows come from the generator the
configuration names (``cfg["generator"]``). What does not depend on the
width is taken from ``benchmark/reference.py`` as it stands: the model
text's parser, binning, gradients, the control's quantiser, the bfloat16
pieces, the routing table's lookup, the leaf-value lookup, the gain and
hessian shortfall of a split taken (``_taken``). Nothing of ``lightgbm_tpu``
is imported.
"""
import importlib
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as R
from benchmark.reference import (NBINS, SUB, _taken,  # noqa: F401
                                 grown_to_tree, parse_model, quantile_bounds)

TILE = 256           # features per histogram tile
PAD_BIN = 255        # bin of the columns that pad the last tile: in no bin
HOST_THREADS = 8


def n_tiles(f):
    return -(-f // TILE)


# ---------------------------------------------------------------- level pass
@jax.jit
def _route_rows(bins, slot, leaf, table):
    return R._route(bins, slot, leaf, table)


@partial(jax.jit, static_argnames=("n_slots",), donate_argnums=(4, 5))
def _hist_tile(bins, chan, slot, tile, acc, comp, n_slots):
    """Adds one block's histogram of feature tile ``tile`` (traced) to
    acc/comp [TILE * NBINS, n_slots * P], P = 3 C + 1 pieces: as
    ``reference._level_block``, the contraction over ``SUB`` rows at a time
    and a compensated sum over the steps."""
    r = bins.shape[0]
    c = chan.shape[1]
    steps = r // SUB
    bf = jnp.bfloat16
    bt = jax.lax.dynamic_slice_in_dim(bins, tile * TILE, TILE, axis=1)

    def body(carry, xs):
        acc, comp = carry
        b, ch, sl = xs
        cols = []
        for j in range(c):
            cols.extend(R._pieces(ch[:, j]))
        cols.append(jnp.ones((SUB,), jnp.float32))
        w = jnp.stack(cols, axis=1)                              # [SUB, P]
        ohs = (sl[:, None] == jnp.arange(n_slots)[None, :])
        w = (ohs[:, :, None] * w[:, None, :]).reshape(SUB, -1).astype(bf)
        ohb = (b[:, :, None] == jnp.arange(NBINS, dtype=jnp.uint8)
               ).reshape(SUB, TILE * NBINS).astype(bf)
        part = jax.lax.dot_general(
            ohb, w, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        yk = part - comp
        t = acc + yk
        return (t, (t - acc) - yk), None

    xs = (bt.reshape(steps, SUB, TILE), chan.reshape(steps, SUB, c),
          slot.reshape(steps, SUB))
    (acc, comp), _ = jax.lax.scan(body, (acc, comp), xs)
    return acc, comp


# ------------------------------------------------------------------ the rows
class Rows(R.Rows):
    """``reference.Rows`` over the configuration's own generator, the bins
    padded to whole tiles with a bin no histogram holds."""

    def __init__(self, seed, cfg, n, bounds, block_rows=None):
        gen = importlib.import_module("benchmark." + cfg["generator"])
        block_rows = block_rows or gen.BLOCK_ROWS
        if block_rows % SUB:
            raise ValueError(f"block_rows must be a multiple of {SUB}")
        self.cfg, self.n, self.block_rows = cfg, n, block_rows
        self.objective = cfg["params"]["objective"]
        self.f = int(cfg["num_features"])
        self.f_pad = n_tiles(self.f) * TILE
        key = gen.seed_key(seed)
        thr = jnp.asarray(R.bounds_f32(bounds, self.f))
        self.bins, self.y, self.live = [], [], []
        pos = 0.0
        for b in range(gen.n_blocks(n, block_rows)):
            x, y = gen.device_block(key, b, cfg, block_rows)
            live = (jnp.arange(block_rows) + b * block_rows) < n
            self.bins.append(jnp.pad(
                R._bin_block(x, thr), ((0, 0), (0, self.f_pad - self.f)),
                constant_values=PAD_BIN))
            self.y.append(y)
            self.live.append(live)
            pos += float(jnp.sum(jnp.where(live, y, 0.0)))
        mean = pos / n
        self.init = (float(np.log(mean / (1.0 - mean)))
                     if self.objective == "binary" else float(mean))
        self.score = [jnp.full((block_rows,), self.init, jnp.float32)
                      for _ in self.bins]
        self.trees_done = 0


# --------------------------------------------------------------- split search
def _sums(h, n_chan, const_hess):
    """h [f, NBINS, S, P] float64 -> (g, hess, count) [S, f, NBINS]."""
    chans = [h[..., 3 * j] + h[..., 3 * j + 1] + h[..., 3 * j + 2]
             for j in range(n_chan)]
    cnt = h[..., -1]
    hs = cnt if const_hess else chans[1]
    return tuple(np.ascontiguousarray(np.moveaxis(a, 2, 0))
                 for a in (chans[0], hs, cnt))


def _tile_table(hist, totals, n_slots, n_chan, const_hess, num_bins,
                min_data, min_hess):
    """``reference._split_table`` for one tile: hist [TILE*NBINS, S_pad*P]
    -> gains [S, f, NBINS-1] (nan where not allowed) of the tile's real
    features, the cumulative sums and the rows of every bin. ``totals``:
    the slots' (G, H, count), read off the matrix's first feature, so that
    every tile subtracts from the same parent."""
    f = len(num_bins)
    p = 3 * n_chan + 1
    h = np.asarray(hist, np.float64).reshape(TILE, NBINS, -1, p)
    g, hs, cnt = _sums(h[:f, :, :n_slots], n_chan, const_hess)
    if totals is None:
        totals = tuple(a[:, 0, :].sum(axis=1) for a in (g, hs, cnt))
    gt, ht, ct = totals
    gl, hl, cl = (np.cumsum(a, axis=2)[:, :, :-1] for a in (g, hs, cnt))
    gr, hr, cr = (t[:, None, None] - a for t, a in
                  ((gt, gl), (ht, hl), (ct, cl)))
    ok = ((cl >= min_data) & (cr >= min_data) & (hl >= min_hess)
          & (hr >= min_hess))
    ok &= (np.arange(NBINS - 1)[None, None, :]
           < (np.asarray(num_bins) - 1)[None, :, None])
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / hl + gr * gr / hr - (gt * gt / ht)[:, None, None]
    return {"gain": np.where(ok, gain, np.nan), "gl": gl, "hl": hl, "cl": cl,
            "gt": gt, "ht": ht, "ct": ct, "cnt": cnt}


def _split_table(tiles, n_slots, n_chan, const_hess, num_bins, min_data,
                 min_hess):
    """The level's table from its per-tile histograms (host arrays): the
    tiles side by side along the feature axis."""
    how = (n_slots, n_chan, const_hess)
    first = _tile_table(tiles[0], None, *how, num_bins[:TILE], min_data,
                        min_hess)
    totals = (first["gt"], first["ht"], first["ct"])
    with ThreadPoolExecutor(HOST_THREADS) as pool:
        rest = list(pool.map(
            lambda t: _tile_table(tiles[t], totals, *how,
                                  num_bins[t * TILE:(t + 1) * TILE],
                                  min_data, min_hess),
            range(1, len(tiles))))
    out = {k: np.concatenate([t[k] for t in [first] + rest], axis=1)
           for k in ("gain", "gl", "hl", "cl", "cnt")}
    out.update(gt=totals[0], ht=totals[1], ct=totals[2])
    return out


# -------------------------------------------------------------------- a walk
def walk_tree(rows, cfg, bounds, tree=None, quant_bits=None, quant_seed=0,
              half=False):
    """``reference.walk_tree`` (its docstring says what is returned) with the
    level's histogram kept per feature tile, and ``hessian_shortfall``: the
    largest shortfall (``_taken``) over the splits taken."""
    f, fp = rows.f, rows.f_pad
    par = cfg["params"]
    lr = float(par["learning_rate"])
    num_leaves = int(par["num_leaves"])
    leaf_min = (int(par["min_data_in_leaf"]),
                float(par["min_sum_hessian_in_leaf"]))
    const_hess = par["objective"] == "regression"
    num_bins = [len(b) for b in bounds]
    chan = rows.gradients()
    n_exact = chan[0].shape[1]
    if quant_bits is not None:
        qmax = (1 << (quant_bits - 1)) - 1
        scale = jnp.max(jnp.stack([jnp.max(jnp.abs(
            jnp.where(l[:, None], c, 0.0)), axis=0)
            for c, l in zip(chan, rows.live)]), axis=0)
        qkey = jax.random.fold_in(jax.random.PRNGKey(quant_seed), quant_bits)
        chan = [jnp.concatenate(
            [c, R._quantise_block(c, scale, jax.random.fold_in(qkey, i),
                                  qmax)],
            axis=1) for i, c in enumerate(chan)]
    n_chan = chan[0].shape[1]
    p = 3 * n_chan + 1
    tiles = range(n_tiles(f))

    slot, leaf = rows.start(half)

    follow = tree is not None
    out = {"best": [], "chosen": [], "level": [], "short": [], "off_grid": 0,
           "leaf_g": {}, "leaf_h": {}, "leaf_c": {}}
    frontier = [None]
    if follow:
        thr_bin = R._threshold_bins(tree, bounds)
        out["off_grid"] = int(np.sum(thr_bin < 0))
        frontier = [0] if tree["num_leaves"] > 1 else []
    grown = {"split_feature": [], "threshold_bin": [], "left_child": [],
             "right_child": []}
    n_leaves = 1
    next_leaf = 0
    table = jnp.zeros((1, fp + 5), jnp.float32)
    level = 0
    while frontier:
        n_slots = len(frontier)
        s_pad = R._pow2(n_slots)
        acc = [jnp.zeros((TILE * NBINS, s_pad * p), jnp.float32)
               for _ in tiles]
        comp = [jnp.zeros_like(a) for a in acc]
        for i in range(len(slot)):
            if level > 0:
                slot[i], leaf[i] = _route_rows(rows.bins[i], slot[i],
                                               leaf[i], table)
            for t in tiles:
                acc[t], comp[t] = _hist_tile(rows.bins[i], chan[i], slot[i],
                                             t, acc[t], comp[t],
                                             n_slots=s_pad)
        del comp
        host = [np.asarray(a) for a in acc]
        del acc
        if quant_bits is None:
            exact = tab = _split_table(host, n_slots, n_chan, const_hess,
                                       num_bins, *leaf_min)
        else:
            # channels: exact first, quantised after; the count is shared
            def part(sl):
                return [np.concatenate(
                    [h.reshape(TILE * NBINS, s_pad, p)[:, :, sl],
                     h.reshape(TILE * NBINS, s_pad, p)[:, :, -1:]], 2
                ).reshape(TILE * NBINS, -1) for h in host]
            exact, tab = (_split_table(part(sl), n_slots, n_exact,
                                       const_hess, num_bins, *leaf_min)
                          for sl in (slice(0, 3 * n_exact),
                                     slice(3 * n_exact, -1)))
        del host
        best_gain, _, _ = R._best(exact["gain"])
        if level == 0:
            out["root_bin_count"] = exact["cnt"][0]
        if follow:
            feat = tree["split_feature"][frontier]
            tbin = np.maximum(thr_bin[frontier], 0)
            do_split = np.ones(n_slots, bool)
        else:
            own_gain, feat, tbin = R._best(tab["gain"])
            budget = num_leaves - n_leaves
            order = np.argsort(-own_gain, kind="stable")
            do_split = np.zeros(n_slots, bool)
            do_split[order[:budget]] = True
            do_split &= own_gain > 0
        chosen_gain, short = _taken(exact, feat, tbin, num_bins, *leaf_min)
        final = (not follow) and n_leaves + int(do_split.sum()) >= num_leaves
        rows_tab = np.zeros((s_pad, fp + 5), np.float32)
        nxt = []                 # next level's slots

        def close(lid, sums, s, side):
            out["leaf_g"][lid], out["leaf_h"][lid], out["leaf_c"][lid] = sums
            rows_tab[s, fp + 3 + side] = lid + 1

        for s in range(n_slots):
            gl, hl, cl = (exact[k][s, feat[s], tbin[s]]
                          for k in ("gl", "hl", "cl"))
            total = (exact["gt"][s], exact["ht"][s], exact["ct"][s])
            sums = ((gl, hl, cl), tuple(t - a for t, a in
                                        zip(total, (gl, hl, cl))))
            if not follow and not do_split[s]:
                # stays a leaf: every row of the slot goes "left" into it
                lid, next_leaf = next_leaf, next_leaf + 1
                if frontier[s] is not None:
                    par, side_name = frontier[s]
                    grown[side_name][par] = ~lid
                rows_tab[s, fp] = NBINS
                close(lid, total, s, 0)
                continue
            # a split taken a hair under the minimum can beat every split
            # the exact sums allow: no regret, and none below zero
            out["best"].append(np.fmax(best_gain[s], chosen_gain[s]))
            out["chosen"].append(chosen_gain[s])
            out["short"].append(short[s])
            out["level"].append(level)
            rows_tab[s, feat[s]] = 1.0
            rows_tab[s, fp] = tbin[s]
            if follow:
                kids = (int(tree["left_child"][frontier[s]]),
                        int(tree["right_child"][frontier[s]]))
            else:
                node_id = len(grown["split_feature"])
                grown["split_feature"].append(int(feat[s]))
                grown["threshold_bin"].append(int(tbin[s]))
                grown["left_child"].append(None)
                grown["right_child"].append(None)
                if frontier[s] is not None:
                    par, side_name = frontier[s]
                    grown[side_name][par] = node_id
                n_leaves += 1
                kids = []
                for side_name in ("left_child", "right_child"):
                    if final:
                        lid, next_leaf = next_leaf, next_leaf + 1
                        grown[side_name][node_id] = ~lid
                        kids.append(~lid)
                    else:
                        kids.append((node_id, side_name))
            for side, kid in enumerate(kids):
                if isinstance(kid, int) and kid < 0:
                    close(int(~kid), sums[side], s, side)
                else:
                    rows_tab[s, fp + 1 + side] = len(nxt) + 1
                    nxt.append(kid)
        table = jnp.asarray(rows_tab)
        level += 1
        frontier = nxt
    # last routing: rows into their leaves
    for i in range(len(slot)):
        slot[i], leaf[i] = _route_rows(rows.bins[i], slot[i], leaf[i], table)
    n_l = len(out["leaf_c"])
    ids = sorted(out["leaf_c"])
    g = np.array([out["leaf_g"][i] for i in ids])
    h = np.array([out["leaf_h"][i] for i in ids])
    c = np.array([out["leaf_c"][i] for i in ids])
    bias = rows.init if not rows.trees_done else 0.0
    ref_value = -lr * g / h + bias
    values = (np.asarray(tree["leaf_value"], np.float64) if follow
              else ref_value)
    rows.add_values(leaf, values, n_l)
    return {
        "best": np.array(out["best"]), "chosen": np.array(out["chosen"]),
        "level": np.array(out["level"]), "off_grid": out["off_grid"],
        "leaf_count": c, "leaf_value": ref_value, "bias": bias,
        "num_leaves": n_l, "tree": tree if follow else grown,
        "root_bin_count": out.get("root_bin_count"),
        "hessian_shortfall": float(max(out["short"], default=0.0)),
    }


def apply_tree(rows, tree, bounds):
    """``reference.apply_tree`` over the padded width: adds ``tree``'s leaf
    values to the scores, nothing judged."""
    fp = rows.f_pad
    thr_bin = np.maximum(R._threshold_bins(tree, bounds), 0)
    tables = []
    frontier = [0] if tree["num_leaves"] > 1 else []
    while frontier:
        # as wide as a level can be, so that trees of one depth share a program
        tab = np.zeros((1 << len(tables), fp + 5), np.float32)
        nxt = []
        for s, node in enumerate(frontier):
            tab[s, tree["split_feature"][node]] = 1.0
            tab[s, fp] = thr_bin[node]
            for side, kid in enumerate((int(tree["left_child"][node]),
                                        int(tree["right_child"][node]))):
                if kid < 0:
                    tab[s, fp + 3 + side] = ~kid + 1
                else:
                    tab[s, fp + 1 + side] = len(nxt) + 1
                    nxt.append(kid)
        tables.append(jnp.asarray(tab))
        frontier = nxt
    leaf = [R._route_block(b, l, tuple(tables))
            for b, l in zip(rows.bins, rows.live)]
    rows.add_values(leaf, np.asarray(tree["leaf_value"], np.float64),
                    tree["num_leaves"])
