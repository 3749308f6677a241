"""The plain reference: histogram GBDT in straightforward jax.numpy.

It imports nothing of ``lightgbm_tpu``. It makes the rows again from the
seed (``benchmark/data.py``), bins them itself against the bin bounds the
run produced, and walks a tree level by level with exact float32
histograms (one-hot contractions whose float operand is split into three
bfloat16 pieces, so every product is exact and sums are compensated). At
each node it finds the best split over every (feature, bin) by LightGBM's
gain, and then either follows a given tree (the program's, to judge it) or
its own best split (the reference put in the program's place, optionally
with gradients quantised to ``quant_bits``: the control).

Formulas (LightGBM, lambda_l1 = lambda_l2 = 0):
  binary:      g = p - y, h = p (1 - p), p = sigmoid(score); init = logit(mean y)
  regression:  g = score - y, h = 1; init = mean y
  leaf value = -lr * G / H  (+ init folded into the first tree)
  gain(split) = GL^2/HL + GR^2/HR - G^2/H, both children holding at least
  ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf`` of hessian; a
  level splits its best-gain leaves while the tree stays within
  ``num_leaves``. The parameters are the configuration's ``params``.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data

SUB = 16384          # rows per contraction
NBINS = 64           # bins per feature, padded


# ---------------------------------------------------------------- model text
def parse_model(text, max_trees=None):
    """Trees of a LightGBM model text as dicts of numpy arrays."""
    trees, cur = [], None
    for line in text.splitlines():
        if line.startswith("Tree="):
            if max_trees is not None and len(trees) >= max_trees:
                cur = None
                break
            cur = {}
            trees.append(cur)
        elif cur is not None:
            if not line.strip():
                cur = None
                continue
            k, _, v = line.partition("=")
            cur[k] = v
    out = []
    for t in trees:
        def arr(k, dt):
            return np.array(t[k].split(), dtype=dt) if t.get(k) else \
                np.zeros(0, dt)
        out.append({
            "num_leaves": int(t["num_leaves"]),
            "split_feature": arr("split_feature", np.int64),
            "threshold": arr("threshold", np.float64),
            "left_child": arr("left_child", np.int64),
            "right_child": arr("right_child", np.int64),
            "leaf_value": arr("leaf_value", np.float64),
            "leaf_count": arr("leaf_count", np.int64),
        })
    return out


# ------------------------------------------------------------------- binning
def bounds_f32(bounds, n_features):
    """[F, NBINS-1] float32 thresholds t with (x <= bound) == (x <= t) for
    float32 x: each float64 upper bound rounded down to float32; unused
    slots are +inf. The last bound of a feature (inf) is dropped."""
    out = np.full((n_features, NBINS - 1), np.inf, np.float32)
    for f, b in enumerate(bounds):
        b = np.asarray(b, np.float64)[:-1]
        if len(b) > NBINS - 1:
            raise ValueError(f"feature {f} has {len(b) + 1} bins > {NBINS}")
        r = b.astype(np.float32)
        r = np.where(r.astype(np.float64) > b,
                     np.nextafter(r, np.float32(-np.inf)), r)
        out[f, : len(b)] = r
    return out


@jax.jit
def _bin_block(x, thr):
    """bin = number of bounds below x  (x <= bound[b] <=> bin <= b)."""
    return jnp.sum(x[:, :, None] > thr[None, :, :], axis=-1).astype(jnp.uint8)


# ----------------------------------------------------------------- gradients
@partial(jax.jit, static_argnames=("objective",))
def _grad_block(score, y, objective):
    if objective == "binary":
        p = jax.nn.sigmoid(score)
        return jnp.stack([p - y, p * (1.0 - p)], axis=1)
    if objective == "regression":
        return (score - y)[:, None]
    raise ValueError(objective)


@partial(jax.jit, static_argnames=("qmax",))
def _quantise_block(chan, scale, key, qmax):
    """Stochastic rounding to integers in [-qmax, qmax], back in float."""
    u = jax.random.uniform(key, chan.shape, jnp.float32)
    q = jnp.clip(jnp.floor(chan / scale * qmax + u), -qmax, qmax)
    return q * (scale / qmax)


def _pieces(x):
    """x (f32) as three bfloat16-exact f32 pieces that sum to x exactly
    (mantissa truncation by bit mask: nothing the compiler may fold away)."""
    out = []
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        top = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                           jnp.float32)
        out.append(top)
        x = x - top
    return out


# ---------------------------------------------------------------- level pass
def _route(bins, slot, leaf, table):
    """Rows through one level's splits. table [S, F + 5]: one-hot feature,
    threshold bin, left/right next slot + 1, left/right leaf + 1 (0 = none);
    all small integers, so the one-hot lookup in bfloat16 is exact."""
    f = bins.shape[1]
    bf = jnp.bfloat16
    oh = (slot[:, None] == jnp.arange(table.shape[0])[None, :])
    sel = jnp.dot(oh.astype(bf), table.astype(bf),
                  preferred_element_type=jnp.float32)
    picked = jnp.sum(bins.astype(jnp.float32) * sel[:, :f], axis=1)
    left = picked <= sel[:, f]
    nxt = jnp.where(left, sel[:, f + 1], sel[:, f + 2]).astype(jnp.int32) - 1
    nl = jnp.where(left, sel[:, f + 3], sel[:, f + 4]).astype(jnp.int32)
    return nxt, jnp.where(nl > 0, nl - 1, leaf)


@jax.jit
def _route_block(bins, live, tables):
    """Leaf of every live row of a block under a tree's routing tables, one
    per level, level l at most 2**l slots wide."""
    slot = jnp.where(live, 0, -1).astype(jnp.int32)
    leaf = jnp.full(slot.shape, -1, jnp.int32)
    for table in tables:
        slot, leaf = _route(bins, slot, leaf, table)
    return leaf


@partial(jax.jit, static_argnames=("n_slots", "route"), donate_argnums=(5, 6))
def _level_block(bins, chan, slot, leaf, table, acc, comp, n_slots, route):
    """Routes one block of rows through the previous level's splits
    (``table``, see ``_route``) and adds the block's histogram of the new
    level's slots.

    acc/comp [F * NBINS, n_slots * P]: compensated sum, P = 3 C + 1 pieces
    (three per channel, then the count)."""
    r, f = bins.shape
    c = chan.shape[1]
    steps = r // SUB
    bf = jnp.bfloat16

    def body(carry, xs):
        acc, comp = carry
        b, ch, sl, lf = xs
        if route:
            sl, lf = _route(b, sl, lf, table)
        if n_slots:
            cols = []
            for j in range(c):
                cols.extend(_pieces(ch[:, j]))
            cols.append(jnp.ones((SUB,), jnp.float32))
            w = jnp.stack(cols, axis=1)                      # [SUB, P]
            ohs = (sl[:, None] == jnp.arange(n_slots)[None, :])
            w = (ohs[:, :, None] * w[:, None, :]).reshape(SUB, -1).astype(bf)
            ohb = (b[:, :, None] == jnp.arange(NBINS, dtype=jnp.uint8)
                   ).reshape(SUB, f * NBINS).astype(bf)
            part = jax.lax.dot_general(
                ohb, w, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            yk = part - comp
            t = acc + yk
            comp = (t - acc) - yk
            acc = t
        return (acc, comp), (sl, lf)

    xs = (bins.reshape(steps, SUB, f), chan.reshape(steps, SUB, c),
          slot.reshape(steps, SUB), leaf.reshape(steps, SUB))
    (acc, comp), (sl, lf) = jax.lax.scan(body, (acc, comp), xs)
    return sl.reshape(r), lf.reshape(r), acc, comp


@jax.jit
def _add_leaf_values(score, leaf, values):
    """score + values[leaf], the lookup as an exact one-hot contraction."""
    oh = (leaf[:, None] == jnp.arange(values.shape[0])[None, :]
          ).astype(jnp.bfloat16)
    add = sum(jnp.dot(oh, p.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
              for p in _pieces(values))
    return score + add


# ------------------------------------------------------------------ the rows
class Rows:
    """The training rows on the device, block by block: reference bins,
    labels, scores. Rows past ``n`` in the last block never enter a node."""

    def __init__(self, seed, cfg, n, bounds, block_rows=data.BLOCK_ROWS):
        if block_rows % SUB:
            raise ValueError(f"block_rows must be a multiple of {SUB}")
        self.cfg, self.n, self.block_rows = cfg, n, block_rows
        self.objective = cfg["params"]["objective"]
        key = data.seed_key(seed)
        thr = jnp.asarray(bounds_f32(bounds, int(cfg["num_features"])))
        self.bins, self.y, self.live = [], [], []
        pos = 0.0
        for b in range(data.n_blocks(n, block_rows)):
            x, y = data.device_block(key, b, cfg, block_rows)
            live = (jnp.arange(block_rows) + b * block_rows) < n
            self.bins.append(_bin_block(x, thr))
            self.y.append(y)
            self.live.append(live)
            pos += float(jnp.sum(jnp.where(live, y, 0.0)))
        mean = pos / n
        self.init = (float(np.log(mean / (1.0 - mean)))
                     if self.objective == "binary" else float(mean))
        self.score = [jnp.full((block_rows,), self.init, jnp.float32)
                      for _ in self.bins]
        self.trees_done = 0

    def gradients(self):
        return [_grad_block(s, y, self.objective)
                for s, y in zip(self.score, self.y)]

    def start(self, half=False):
        """(slot, leaf) per block for the top of a tree: live rows in slot
        0, in no leaf yet. ``half`` leaves every other row out."""
        def slot0(live):
            if half:
                live = live & ((jnp.arange(live.shape[0]) % 2) == 0)
            return jnp.where(live, 0, -1).astype(jnp.int32)
        return ([slot0(l) for l in self.live],
                [jnp.full((self.block_rows,), -1, jnp.int32)
                 for _ in self.live])

    def add_values(self, leaf, values, n_leaves):
        """score += values[leaf] for the rows that reached a leaf. The first
        tree carries the init score: there the score becomes the value."""
        pad = np.zeros(_pow2(max(n_leaves, 1)), np.float32)
        pad[: len(values)] = values
        vals = jnp.asarray(pad)
        shift = np.float32(self.init if not self.trees_done else 0.0)
        for i, lf in enumerate(leaf):
            self.score[i] = jnp.where(
                lf >= 0, _add_leaf_values(self.score[i] - shift, lf, vals),
                self.score[i])
        self.trees_done += 1


# --------------------------------------------------------------- split search
def _split_table(hist, n_slots, n_chan, const_hess, num_bins, min_data,
                 min_hess):
    """hist [F*NBINS, n_slots*P] -> per slot: totals, gain of every split
    [S, F, NBINS-1] (nan where not allowed), the cumulative sums, and the
    rows of every bin."""
    f = len(num_bins)
    p = 3 * n_chan + 1
    h = np.asarray(hist, np.float64).reshape(f, NBINS, n_slots, p)
    chans = [h[..., 3 * j] + h[..., 3 * j + 1] + h[..., 3 * j + 2]
             for j in range(n_chan)]
    cnt = h[..., -1]
    g = chans[0]
    hs = cnt if const_hess else chans[1]
    # -> [S, F, NBINS]
    g, hs, cnt = (np.moveaxis(a, 2, 0) for a in (g, hs, cnt))
    gl, hl, cl = (np.cumsum(a, axis=2)[:, :, :-1] for a in (g, hs, cnt))
    gt, ht, ct = (a[:, 0, :].sum(axis=1) for a in (g, hs, cnt))
    gr, hr, cr = (t[:, None, None] - a for t, a in
                  ((gt, gl), (ht, hl), (ct, cl)))
    ok = ((cl >= min_data) & (cr >= min_data) & (hl >= min_hess)
          & (hr >= min_hess))
    ok &= (np.arange(NBINS - 1)[None, None, :]
           < (np.asarray(num_bins) - 1)[None, :, None])
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / hl + gr * gr / hr \
            - (gt * gt / ht)[:, None, None]
    gain = np.where(ok, gain, np.nan)
    return {"gain": gain, "gl": gl, "hl": hl, "cl": cl,
            "gt": gt, "ht": ht, "ct": ct, "cnt": cnt}


def _best(gain):
    """(best gain, feature, bin) per slot; gain -inf where nothing allowed."""
    s = gain.shape[0]
    flat = np.where(np.isnan(gain), -np.inf, gain).reshape(s, -1)
    idx = flat.argmax(axis=1)
    return flat[np.arange(s), idx], idx // gain.shape[2], idx % gain.shape[2]


def _taken(exact, feat, tbin, num_bins, min_data, min_hess):
    """(gain, shortfall) per slot of the splits taken at (feat, tbin), from
    the exact sums. The program holds ``min_sum_hessian_in_leaf`` to the sums
    of its quantised hessians, which lie a rounding's noise beside the exact
    ones, so a child it let through at the minimum can read a hair under it
    here (at 1.2 M rows x 255 leaves the minimum of 100 binds at leaves of
    ~500 rows; at 52.5 M rows from about the twelfth tree on). Such a split
    keeps its gain and its shortfall, (minimum - the smaller child's
    hessian) / minimum, is a number of its own under a limit of its own; the
    gain is nan, as in ``_split_table``, only where the rows' count or the
    bin forbids the split, which no rounding moves."""
    sl = np.arange(len(feat))
    gl, hl, cl = (exact[k][sl, feat, tbin] for k in ("gl", "hl", "cl"))
    gt, ht, ct = (exact[k][sl] for k in ("gt", "ht", "ct"))
    gr, hr, cr = gt - gl, ht - hl, ct - cl
    ok = ((cl >= min_data) & (cr >= min_data)
          & (tbin < np.asarray(num_bins)[feat] - 1))
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / hl + gr * gr / hr - gt * gt / ht
    short = np.maximum(0.0, min_hess - np.minimum(hl, hr)) / max(min_hess,
                                                                 1e-300)
    return np.where(ok, gain, np.nan), short


def _pow2(n):
    return 1 << max(0, int(n - 1).bit_length())


# -------------------------------------------------------------------- a walk
def walk_tree(rows, cfg, bounds, tree=None, quant_bits=None, quant_seed=0,
              half=False):
    """One boosting step of the reference on ``rows`` (scores updated).

    ``tree`` given: follow its splits and use its leaf values for the score
    (the program's tree, judged). ``tree`` None: grow by the reference's own
    best splits; with ``quant_bits`` the splits are chosen from histograms of
    gradients quantised to that many bits (the control). ``half`` leaves
    every other row out of the step (a planted fault).
    Returns per-node gains (best, chosen, both from exact histograms; the
    chosen split's gain from the unmasked sums, ``_taken``), per-leaf
    reference sums, the tree walked, and ``hessian_shortfall``: the largest
    shortfall over the splits taken."""
    f = int(cfg["num_features"])
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
            [c, _quantise_block(c, scale, jax.random.fold_in(qkey, i), qmax)],
            axis=1) for i, c in enumerate(chan)]
    n_chan = chan[0].shape[1]
    p = 3 * n_chan + 1

    slot, leaf = rows.start(half)

    follow = tree is not None
    out = {"best": [], "chosen": [], "level": [], "short": [], "off_grid": 0,
           "leaf_g": {}, "leaf_h": {}, "leaf_c": {}}
    # the slots of the level at hand: the tree's node ids when following,
    # (parent node, side) links when growing (None for the root)
    frontier = [None]
    if follow:
        thr_bin = _threshold_bins(tree, bounds)
        out["off_grid"] = int(np.sum(thr_bin < 0))
        frontier = [0] if tree["num_leaves"] > 1 else []
    grown = {"split_feature": [], "threshold_bin": [], "left_child": [],
             "right_child": []}
    n_leaves = 1
    next_leaf = 0
    table = jnp.zeros((1, f + 5), jnp.float32)
    level = 0
    while frontier:
        n_slots = len(frontier)
        s_pad = _pow2(n_slots)
        acc = jnp.zeros((f * NBINS, s_pad * p), jnp.float32)
        comp = jnp.zeros_like(acc)
        for i in range(len(slot)):
            slot[i], leaf[i], acc, comp = _level_block(
                rows.bins[i], chan[i], slot[i], leaf[i], table, acc, comp,
                n_slots=s_pad, route=level > 0)
        if quant_bits is None:
            exact = tab = _split_table(acc, s_pad, n_chan, const_hess,
                                       num_bins, *leaf_min)
        else:
            # channels: exact first, quantised after; the count is shared
            h = np.asarray(acc).reshape(f * NBINS, s_pad, p)
            exact, tab = (_split_table(
                np.concatenate([h[:, :, part], h[:, :, -1:]], 2
                               ).reshape(f * NBINS, -1),
                s_pad, n_exact, const_hess, num_bins, *leaf_min)
                for part in (slice(0, 3 * n_exact),
                             slice(3 * n_exact, -1)))
        best_gain, _, _ = _best(exact["gain"][:n_slots])
        if level == 0:
            out["root_bin_count"] = exact["cnt"][0]
        if follow:
            feat = tree["split_feature"][frontier]
            tbin = np.maximum(thr_bin[frontier], 0)
            do_split = np.ones(n_slots, bool)
        else:
            own_gain, feat, tbin = _best(tab["gain"][:n_slots])
            budget = num_leaves - n_leaves
            order = np.argsort(-own_gain, kind="stable")
            do_split = np.zeros(n_slots, bool)
            do_split[order[:budget]] = True
            do_split &= own_gain > 0
        chosen_gain, short = _taken(exact, feat, tbin, num_bins, *leaf_min)
        final = (not follow) and n_leaves + int(do_split.sum()) >= num_leaves
        rows_tab = np.zeros((s_pad, f + 5), np.float32)
        nxt = []                 # next level's slots

        def close(lid, sums, s, side):
            out["leaf_g"][lid], out["leaf_h"][lid], out["leaf_c"][lid] = sums
            rows_tab[s, f + 3 + side] = lid + 1

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
                rows_tab[s, f] = NBINS
                close(lid, total, s, 0)
                continue
            # a split taken a hair under the minimum can beat every split
            # the exact sums allow: no regret, and none below zero
            out["best"].append(np.fmax(best_gain[s], chosen_gain[s]))
            out["chosen"].append(chosen_gain[s])
            out["short"].append(short[s])
            out["level"].append(level)
            rows_tab[s, feat[s]] = 1.0
            rows_tab[s, f] = tbin[s]
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
                    rows_tab[s, f + 1 + side] = len(nxt) + 1
                    nxt.append(kid)
        table = jnp.asarray(rows_tab)
        level += 1
        frontier = nxt
    # last routing: rows into their leaves, no histogram
    d_acc = jnp.zeros((1, 1), jnp.float32)
    d_comp = jnp.zeros((1, 1), jnp.float32)
    for i in range(len(slot)):
        slot[i], leaf[i], d_acc, d_comp = _level_block(
            rows.bins[i], chan[i], slot[i], leaf[i], table, d_acc, d_comp,
            n_slots=0, route=True)
    n_l = len(out["leaf_c"])
    ids = sorted(out["leaf_c"])
    g = np.array([out["leaf_g"][i] for i in ids])
    h = np.array([out["leaf_h"][i] for i in ids])
    c = np.array([out["leaf_c"][i] for i in ids])
    bias = rows.init if not rows.trees_done else 0.0
    ref_value = -lr * g / h + bias
    if follow:
        values = np.asarray(tree["leaf_value"], np.float64)
    else:
        values = ref_value
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
    """Adds ``tree``'s leaf values to the scores, routing the reference's own
    bins: no histogram, nothing judged. It carries the scores from one
    followed tree to a later one."""
    f = int(rows.cfg["num_features"])
    thr_bin = np.maximum(_threshold_bins(tree, bounds), 0)
    tables = []
    frontier = [0] if tree["num_leaves"] > 1 else []
    while frontier:
        # as wide as a level can be, so that trees of one depth share a program
        tab = np.zeros((1 << len(tables), f + 5), np.float32)
        nxt = []
        for s, node in enumerate(frontier):
            tab[s, tree["split_feature"][node]] = 1.0
            tab[s, f] = thr_bin[node]
            for side, kid in enumerate((int(tree["left_child"][node]),
                                        int(tree["right_child"][node]))):
                if kid < 0:
                    tab[s, f + 3 + side] = ~kid + 1
                else:
                    tab[s, f + 1 + side] = len(nxt) + 1
                    nxt.append(kid)
        tables.append(jnp.asarray(tab))
        frontier = nxt
    leaf = [_route_block(b, l, tuple(tables))
            for b, l in zip(rows.bins, rows.live)]
    rows.add_values(leaf, np.asarray(tree["leaf_value"], np.float64),
                    tree["num_leaves"])


def _threshold_bins(tree, bounds):
    """Bin index of each node's threshold on its feature's bounds, -1 where
    the threshold is not one of them."""
    out = np.full(len(tree["threshold"]), -1, np.int64)
    for i, (f, t) in enumerate(zip(tree["split_feature"], tree["threshold"])):
        b = np.asarray(bounds[f], np.float64)
        j = int(np.searchsorted(b, t))
        if j < len(b) and b[j] == t:
            out[i] = j
    return out


# ----------------------------------------------------------------------- AUC
def auc(y, score):
    """ROC AUC from average ranks, float64 on the host."""
    order = np.argsort(score, kind="stable")
    ps = score[order]
    start = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1]])
    end = np.r_[start[1:], len(ps)]
    rank = np.repeat((start + end + 1) / 2.0, end - start)
    pos = y[order] > 0
    n_pos = float(pos.sum())
    n_neg = float(len(y) - n_pos)
    return (rank[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)


def predict_tree(tree, x):
    """Leaf value of each row of x (float32) by the raw thresholds."""
    n = len(x)
    if tree["num_leaves"] <= 1:
        return np.full(n, tree["leaf_value"][0])
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    active = np.ones(n, bool)
    while active.any():
        idx = rows[active]
        nd = node[idx]
        go_left = x[idx, tree["split_feature"][nd]].astype(np.float64) \
            <= tree["threshold"][nd]
        nxt = np.where(go_left, tree["left_child"][nd],
                       tree["right_child"][nd])
        node[idx] = nxt
        active[idx] = nxt >= 0
    return tree["leaf_value"][~node]


# ------------------------------------------- the reference in the program's place
def quantile_bounds(x, max_bin):
    """Equal-frequency bin upper bounds from a sample [n, F]: midpoints
    between neighbouring order statistics, the last bound +inf."""
    x = np.sort(np.asarray(x, np.float64), axis=0)
    n = len(x)
    cuts = (np.arange(1, max_bin) * n) // max_bin
    out = []
    for f in range(x.shape[1]):
        mid = np.unique((x[cuts - 1, f] + x[cuts, f]) / 2.0)
        out.append(np.r_[mid, np.inf])
    return out


def grown_to_tree(walk, bounds):
    """A grown walk as a tree dict of the model-text form."""
    g = walk["tree"]
    return {
        "num_leaves": walk["num_leaves"],
        "split_feature": np.array(g["split_feature"], np.int64),
        "threshold": np.array([bounds[f][b] for f, b in
                               zip(g["split_feature"], g["threshold_bin"])]),
        "left_child": np.array(g["left_child"], np.int64),
        "right_child": np.array(g["right_child"], np.int64),
        "leaf_value": np.asarray(walk["leaf_value"], np.float64),
        "leaf_count": np.asarray(walk["leaf_count"]).round().astype(np.int64),
    }
