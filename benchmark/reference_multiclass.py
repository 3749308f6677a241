"""The plain reference for K class trees an iteration: softmax GBDT in
straightforward jax.numpy.

``benchmark/reference.py`` for ``objective=multiclass``. It imports nothing
of ``lightgbm_tpu``. It makes the rows again from the seed
(``benchmark/data_covertype.py``), bins the raw columns itself, ALL of them,
against the bin bounds the run produced (it knows no bundle: a program that
packs exclusive columns into one has to come out with the trees of the
columns as they are), and walks a class tree level by level with exact
float32 histograms (``reference.py``'s one-hot contractions over bfloat16
pieces, compensated sums). Differences from ``reference.py``:

  * K scores a row. An iteration's K class trees are ALL grown from the
    gradients of the scores as the iteration found them
    (``Rows.begin_iteration``), class 0 first; tree ``i`` of a model belongs
    to class ``i mod K``.
  * up to 256 bins a column, and columns of very different bin counts (255
    and 2): the histogram's rows are the bins that exist, feature after
    feature (``Layout``), not ``F * NBINS``.
  * rows are kept feature-major, ``[F, rows]``.

Formulas (LightGBM; float32 on the device, float64 on the host):
  p = softmax(score);  g_k = p_k - [y == k];  h_k = K/(K-1) p_k (1 - p_k)
  init score 0 for every class
  leaf value = -lr * G / (H + lambda_l2)
  gain(split) = GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2), both children
  holding at least ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf``
  of hessian; a level splits its best-gain leaves while the tree stays within
  ``num_leaves`` and ``max_depth``. The parameters are the configuration's
  ``params``.
"""
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (_add_leaf_values, _best, _pieces, _pow2,  # noqa: F401
                                 _quantise_block, _threshold_bins,
                                 grown_to_tree, parse_model, quantile_bounds)

SUB = 8192           # rows per contraction
NBINS = 256          # most bins a column can have (uint8 bins)
LANES = 128


# ------------------------------------------------------------------- binning
def bounds_f32(bounds, n_features):
    """[F, NBINS-1] float32 thresholds t with (x <= bound) == (x <= t) for
    float32 x (``reference.bounds_f32`` at 256 bins)."""
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
    """[rows, F] floats -> [F, rows] uint8 bins: the number of bounds below
    x (x <= bound[b] <=> bin <= b)."""
    return jnp.sum(x.T[:, :, None] > thr[:, None, :], axis=-1).astype(
        jnp.uint8)


class Layout:
    """The histogram's rows: one for every bin of every feature that the
    bounds give, feature after feature, padded to a lane multiple."""

    def __init__(self, bounds):
        self.num_bins = np.array([len(b) for b in bounds])
        f = len(bounds)
        feat = np.repeat(np.arange(f), self.num_bins)
        bins = np.concatenate([np.arange(n) for n in self.num_bins])
        self.used = len(feat)
        w = -(-self.used // LANES) * LANES
        self.feat, self.bin = feat, bins
        expand = np.zeros((w, f), np.float32)
        expand[np.arange(self.used), feat] = 1.0
        col_bin = np.full(w, -1.0, np.float32)      # a pad row matches no bin
        col_bin[: self.used] = bins
        self.expand, self.col_bin = jnp.asarray(expand), jnp.asarray(col_bin)
        self.width = w

    def dense(self, hist, lead):
        """hist [W, *lead] -> float64 [*lead, F, NBINS], zero where a feature
        has no such bin."""
        h = np.asarray(hist, np.float64)[: self.used]
        out = np.zeros(tuple(lead) + (len(self.num_bins), NBINS))
        out[..., self.feat, self.bin] = np.moveaxis(
            h.reshape((self.used,) + tuple(lead)), 0, -1)
        return out


# ----------------------------------------------------------------- gradients
@jax.jit
def _grad_block(score, y, cls):
    """[2, R]: class ``cls``'s gradient and hessian from the K scores."""
    k = score.shape[0]
    p = jax.nn.softmax(score, axis=0)[cls]
    g = p - (y == cls).astype(jnp.float32)
    h = (k / (k - 1.0)) * p * (1.0 - p)
    return jnp.stack([g, h])


# ---------------------------------------------------------------- level pass
def _route(bins, slot, leaf, table):
    """Rows through one level's splits. bins [F, R]; table [S, F + 5] as
    ``reference._route``'s: one-hot feature, threshold bin, left/right next
    slot + 1, left/right leaf + 1 (0 = none); integers up to 256, so the
    one-hot lookup in bfloat16 is exact."""
    f = bins.shape[0]
    bf = jnp.bfloat16
    oh = (jnp.arange(table.shape[0])[:, None] == slot[None, :])
    sel = jnp.dot(table.T.astype(bf), oh.astype(bf),
                  preferred_element_type=jnp.float32)          # [F + 5, R]
    picked = jnp.sum(bins.astype(jnp.float32) * sel[:f], axis=0)
    left = picked <= sel[f]
    nxt = jnp.where(left, sel[f + 1], sel[f + 2]).astype(jnp.int32) - 1
    nl = jnp.where(left, sel[f + 3], sel[f + 4]).astype(jnp.int32)
    return nxt, jnp.where(nl > 0, nl - 1, leaf)


@jax.jit
def _route_block(bins, live, tables):
    """Leaf of every live row of a block under a tree's routing tables."""
    slot = jnp.where(live, 0, -1).astype(jnp.int32)
    leaf = jnp.full(slot.shape, -1, jnp.int32)
    for table in tables:
        slot, leaf = _route(bins, slot, leaf, table)
    return leaf


def _weights(chan, n):
    """[P, n] float32: three bfloat16-exact pieces a channel, then ones."""
    rows = []
    for j in range(chan.shape[0]):
        rows.extend(_pieces(chan[j]))
    rows.append(jnp.ones((n,), jnp.float32))
    return jnp.stack(rows)


def _kahan(acc, comp, part):
    yk = part - comp
    t = acc + yk
    return t, (t - acc) - yk


@partial(jax.jit, static_argnames=("n_slots", "route"), donate_argnums=(7, 8))
def _level_block(bins, chan, slot, leaf, table, expand, col_bin, acc, comp,
                 n_slots, route):
    """Routes one block of rows through the previous level's splits and adds
    the block's histogram of the new level's slots.

    bins [F, R] u8, chan [C, R]; acc/comp [W, n_slots * P]: compensated sum,
    P = 3 C + 1 pieces (three a channel, then the count), W the layout's
    rows."""
    f, r = bins.shape
    c = chan.shape[0]
    steps = r // SUB
    bf = jnp.bfloat16

    def body(carry, xs):
        acc, comp = carry
        b, ch, sl, lf = xs
        if route:
            sl, lf = _route(b, sl, lf, table)
        if n_slots:
            w = _weights(ch, SUB)                              # [P, SUB]
            ohs = (jnp.arange(n_slots)[:, None] == sl[None, :])
            w = (ohs[:, None, :] * w[None, :, :]).reshape(-1, SUB).astype(bf)
            # the bin of each histogram row's feature, then the one-hot
            val = jnp.dot(expand.astype(bf), b.astype(bf),
                          preferred_element_type=jnp.float32)  # [W, SUB]
            ohb = (val == col_bin[:, None]).astype(bf)
            part = jax.lax.dot_general(
                ohb, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc, comp = _kahan(acc, comp, part)
        return (acc, comp), (sl, lf)

    xs = (jnp.moveaxis(bins.reshape(f, steps, SUB), 1, 0),
          jnp.moveaxis(chan.reshape(c, steps, SUB), 1, 0),
          slot.reshape(steps, SUB), leaf.reshape(steps, SUB))
    (acc, comp), (sl, lf) = jax.lax.scan(body, (acc, comp), xs)
    return sl.reshape(r), lf.reshape(r), acc, comp


@partial(jax.jit, donate_argnums=(2, 3))
def _leaf_sums_block(leaf, chan, acc, comp):
    """Adds a block's exact per-leaf sums: acc/comp [L, P]."""
    r = leaf.shape[0]
    steps = r // SUB
    n_leaves = acc.shape[0]
    bf = jnp.bfloat16

    def body(carry, xs):
        lf, ch = xs
        oh = (jnp.arange(n_leaves)[:, None] == lf[None, :]).astype(bf)
        part = jax.lax.dot_general(
            oh, _weights(ch, SUB).astype(bf), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return _kahan(*carry, part), None

    xs = (leaf.reshape(steps, SUB),
          jnp.moveaxis(chan.reshape(chan.shape[0], steps, SUB), 1, 0))
    return jax.lax.scan(body, (acc, comp), xs)[0]


# ------------------------------------------------------------------ the rows
class Rows:
    """The training rows on the device, block by block: reference bins
    [F, R], labels, the K scores [K, R]. Rows past ``n`` in the last block
    never enter a node."""

    def __init__(self, seed, cfg, n, bounds, block_rows=None):
        data = importlib.import_module("benchmark." + cfg["generator"])
        block_rows = block_rows or data.BLOCK_ROWS
        if block_rows % SUB:
            raise ValueError(f"block_rows must be a multiple of {SUB}")
        self.cfg, self.n, self.block_rows = cfg, n, block_rows
        self.k = int(cfg["params"]["num_class"])
        self.layout = Layout(bounds)
        key = data.seed_key(seed)
        thr = jnp.asarray(bounds_f32(bounds, int(cfg["num_features"])))
        self.bins, self.y, self.live = [], [], []
        for b in range(data.n_blocks(n, block_rows)):
            x, y = data.device_block(key, b, cfg, block_rows)
            self.bins.append(_bin_block(x, thr))
            self.y.append(y.astype(jnp.int32))
            self.live.append((jnp.arange(block_rows) + b * block_rows) < n)
        self.score = [jnp.zeros((self.k, block_rows), jnp.float32)
                      for _ in self.bins]
        self.begin_iteration()

    def begin_iteration(self):
        """The scores every class tree of the coming iteration takes its
        gradients from (arrays are values: the list is the snapshot)."""
        self.at_start = list(self.score)

    def gradients(self, cls, stale=True):
        """[2, R] a block. ``stale`` False is the planted fault: gradients
        from the scores as the class trees before this one left them."""
        cls = jnp.int32(cls)
        return [_grad_block(s, y, cls)
                for s, y in zip(self.at_start if stale else self.score,
                                self.y)]

    def start(self, half=False):
        def slot0(live):
            if half:
                live = live & ((jnp.arange(live.shape[0]) % 2) == 0)
            return jnp.where(live, 0, -1).astype(jnp.int32)
        return ([slot0(l) for l in self.live],
                [jnp.full((self.block_rows,), -1, jnp.int32)
                 for _ in self.live])

    def add_values(self, cls, leaf, values, n_leaves):
        """score[cls] += values[leaf] for the rows that reached a leaf."""
        pad = np.zeros(_pow2(max(n_leaves, 1)), np.float32)
        pad[: len(values)] = values
        vals = jnp.asarray(pad)
        for i, lf in enumerate(leaf):
            row = self.score[i][cls]
            row = jnp.where(lf >= 0, _add_leaf_values(row, lf, vals), row)
            self.score[i] = self.score[i].at[cls].set(row)


# --------------------------------------------------------------- split search
def _split_table(dense, n_chan, num_bins, min_data, min_hess, l2):
    """dense [S, P, F, NBINS] float64 -> per slot: totals, the gain of every
    split [S, F, NBINS-1] (nan where not allowed), the cumulative sums, the
    rows of every bin."""
    chans = [dense[:, 3 * j] + dense[:, 3 * j + 1] + dense[:, 3 * j + 2]
             for j in range(n_chan)]
    cnt = dense[:, -1]
    g, hs = chans[0], chans[1]
    gl, hl, cl = (np.cumsum(a, axis=2)[:, :, :-1] for a in (g, hs, cnt))
    gt, ht, ct = (a[:, 0, :].sum(axis=1) for a in (g, hs, cnt))
    gr, hr, cr = (t[:, None, None] - a for t, a in
                  ((gt, gl), (ht, hl), (ct, cl)))
    inside = (np.arange(NBINS - 1)[None, None, :]
              < (np.asarray(num_bins) - 1)[None, :, None])
    counted = (cl >= min_data) & (cr >= min_data) & inside
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) \
            - (gt * gt / (ht + l2))[:, None, None]
    ok = counted & (hl >= min_hess) & (hr >= min_hess)
    return {"gain": np.where(ok, gain, np.nan),
            # the gain of a split the rows' counts and the bins allow: what a
            # split TAKEN is worth even where a child's exact hessian lies a
            # rounding under the minimum the program holds to its int8 sums
            "taken": np.where(counted, gain, np.nan),
            "gl": gl, "hl": hl, "cl": cl, "gt": gt, "ht": ht, "ct": ct,
            "cnt": cnt}


# -------------------------------------------------------------------- a walk
def walk_tree(rows, cfg, bounds, cls, tree=None, quant_bits=None,
              quant_seed=0, half=False, stale=True):
    """One class tree of the reference on ``rows`` (class ``cls``'s score
    updated), from the gradients of the iteration's first scores.

    ``tree`` given: follow its splits and use its leaf values for the score
    (the program's tree, judged). ``tree`` None: grow by the reference's own
    best splits; with ``quant_bits`` the splits are chosen from histograms of
    gradients quantised to that many bits (the control). ``half`` leaves
    every other row out, ``stale`` False takes the gradients from the scores
    as they are now (planted faults). Returns what ``reference.walk_tree``
    returns."""
    layout = rows.layout
    f = int(cfg["num_features"])
    par = cfg["params"]
    lr = float(par["learning_rate"])
    l2 = float(par.get("lambda_l2", 0.0))
    num_leaves = int(par["num_leaves"])
    max_depth = int(par.get("max_depth", -1))
    leaf_min = (int(par["min_data_in_leaf"]),
                float(par["min_sum_hessian_in_leaf"]))
    num_bins = layout.num_bins
    chan = rows.gradients(cls, stale)
    n_exact = 2
    if quant_bits is not None:
        qmax = (1 << (quant_bits - 1)) - 1
        scale = jnp.max(jnp.stack([jnp.max(jnp.abs(
            jnp.where(l[None, :], c, 0.0)), axis=1)
            for c, l in zip(chan, rows.live)]), axis=0)
        qkey = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(quant_seed), quant_bits), cls)
        chan = [jnp.concatenate(
            [c, _quantise_block(c.T, scale, jax.random.fold_in(qkey, i),
                                qmax).T], axis=0)
            for i, c in enumerate(chan)]
    n_chan = chan[0].shape[0]
    p = 3 * n_chan + 1

    slot, leaf = rows.start(half)
    follow = tree is not None
    out = {"best": [], "chosen": [], "level": [], "off_grid": 0,
           "leaf_g": {}, "leaf_h": {}, "leaf_c": {}}
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
        acc = jnp.zeros((layout.width, s_pad * p), jnp.float32)
        comp = jnp.zeros_like(acc)
        for i in range(len(slot)):
            slot[i], leaf[i], acc, comp = _level_block(
                rows.bins[i], chan[i], slot[i], leaf[i], table,
                layout.expand, layout.col_bin, acc, comp,
                n_slots=s_pad, route=level > 0)
        dense = layout.dense(acc, (s_pad, p))
        pieces = slice(0, 3 * n_exact)
        exact = tab = _split_table(
            np.concatenate([dense[:, pieces], dense[:, -1:]], 1), n_exact,
            num_bins, *leaf_min, l2)
        if quant_bits is not None:
            # channels: exact first, quantised after; the count is shared
            tab = _split_table(dense[:, 3 * n_exact:], n_exact, num_bins,
                               *leaf_min, l2)
        best_gain, _, _ = _best(exact["gain"][:n_slots])
        if level == 0:
            out["root_bin_count"] = exact["cnt"][0]
        if follow:
            feat = tree["split_feature"][frontier]
            tbin = np.maximum(thr_bin[frontier], 0)
            do_split = np.ones(n_slots, bool)
            if 0 < max_depth <= level:
                out["off_grid"] += n_slots       # splits below the depth cap
        else:
            own_gain, feat, tbin = _best(tab["gain"][:n_slots])
            budget = num_leaves - n_leaves
            order = np.argsort(-own_gain, kind="stable")
            do_split = np.zeros(n_slots, bool)
            do_split[order[:budget]] = True
            do_split &= own_gain > 0
        sl = np.arange(n_slots)
        chosen_gain = exact["taken"][sl, feat, tbin]
        final = (not follow) and (
            n_leaves + int(do_split.sum()) >= num_leaves
            or level + 1 == max_depth)
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
                    parent, side_name = frontier[s]
                    grown[side_name][parent] = ~lid
                rows_tab[s, f] = NBINS
                close(lid, total, s, 0)
                continue
            out["best"].append(best_gain[s])
            out["chosen"].append(chosen_gain[s])
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
                    parent, side_name = frontier[s]
                    grown[side_name][parent] = node_id
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
            rows.bins[i], chan[i], slot[i], leaf[i], table, layout.expand,
            layout.col_bin, d_acc, d_comp, n_slots=0, route=True)
    ids = sorted(out["leaf_c"])
    g = np.array([out["leaf_g"][i] for i in ids])
    h = np.array([out["leaf_h"][i] for i in ids])
    c = np.array([out["leaf_c"][i] for i in ids])
    if not ids:                  # a stump: one leaf holding every row
        g, h, c = (np.array([v]) for v in _totals(rows, chan, half))
    ref_value = -lr * g / (h + l2)
    values = (np.asarray(tree["leaf_value"], np.float64) if follow
              else ref_value)
    rows.add_values(cls, leaf if ids else _stump_leaf(rows, half), values,
                    len(c))
    best = np.array(out["best"])
    return {
        "best": best,
        # a split the int8 minimum let through can be worth more than the
        # best the exact minimum allows: no regret, not a negative one
        "chosen": np.minimum(np.array(out["chosen"]), best),
        "level": np.array(out["level"]), "off_grid": out["off_grid"],
        "leaf_count": c, "leaf_value": ref_value, "bias": 0.0,
        "num_leaves": len(c), "tree": tree if follow else grown,
        "root_bin_count": out.get("root_bin_count"),
    }


def root_bin_count(rows):
    """Rows in every bin of every feature [F, NBINS], for a run whose
    followed trees are all stumps (no walk passed the root)."""
    layout = rows.layout
    slot, leaf = rows.start()
    acc = jnp.zeros((layout.width, 1), jnp.float32)
    comp = jnp.zeros_like(acc)
    none = jnp.zeros((0, rows.block_rows), jnp.float32)
    for i in range(len(slot)):
        _, _, acc, comp = _level_block(
            rows.bins[i], none, slot[i], leaf[i], jnp.zeros((1, 1)),
            layout.expand, layout.col_bin, acc, comp, n_slots=1, route=False)
    return layout.dense(acc, (1,))[0]


def _stump_leaf(rows, half):
    slot, _ = rows.start(half)
    return [jnp.where(s >= 0, 0, -1) for s in slot]


def _totals(rows, chan, half):
    a = _leaf_sums(_stump_leaf(rows, half), chan, 1)[0]
    return a[0:3].sum(), a[3:6].sum(), a[-1]


def _leaf_sums(leaf, chan, n_leaves):
    """Exact per-leaf sums [n_leaves, P] (float64): three pieces a channel
    of ``chan``, then the rows."""
    acc = jnp.zeros((_pow2(max(n_leaves, 1)), 3 * chan[0].shape[0] + 1),
                    jnp.float32)
    comp = jnp.zeros_like(acc)
    for lf, ch in zip(leaf, chan):
        acc, comp = _leaf_sums_block(lf, ch, acc, comp)
    return np.asarray(acc, np.float64)[:n_leaves]


def _tables(tree, bounds, f):
    """A tree's routing tables, one a level (``reference.apply_tree``'s)."""
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
    return tuple(tables)


@jax.jit
def _grad_all_block(score, y):
    """[2K, R]: every class's gradient and hessian, g_0 h_0 g_1 h_1 ..."""
    k = score.shape[0]
    p = jax.nn.softmax(score, axis=0)
    g = p - (jnp.arange(k)[:, None] == y[None, :]).astype(jnp.float32)
    h = (k / (k - 1.0)) * p * (1.0 - p)
    return jnp.stack([g, h], axis=1).reshape(2 * k, -1)


def route_tree(rows, cfg, tree, bounds):
    """Leaf of every row under ``tree``, by the reference's own bins (one
    routing of the rows, no histogram), a block each."""
    if tree["num_leaves"] <= 1:
        return _stump_leaf(rows, False)
    tables = _tables(tree, bounds, int(cfg["num_features"]))
    return [_route_block(b, l, tables) for b, l in zip(rows.bins, rows.live)]


def judge_leaves(rows, cfg, tree, leaf):
    """What one routing says of a tree: every leaf's rows, and the value the
    reference gives the leaf from its exact sums under EVERY class's
    gradients of the iteration's first scores, [K, leaves]: the tree belongs
    to the class whose values it carries."""
    par = cfg["params"]
    n_leaves = tree["num_leaves"]
    a = _leaf_sums(leaf, [_grad_all_block(s, y)
                          for s, y in zip(rows.at_start, rows.y)], n_leaves)
    sums = a[:, :-1].reshape(n_leaves, rows.k, 2, 3).sum(-1)   # [L, K, g|h]
    value = -float(par["learning_rate"]) * sums[:, :, 0] \
        / (sums[:, :, 1] + float(par.get("lambda_l2", 0.0)))
    return {"leaf_count": a[:, -1], "leaf_value": value.T}


# ------------------------------------------- the reference in the program's place
def sample_bounds(x, max_bin):
    """Bin upper bounds from a sample [n, F]: ``reference.quantile_bounds``
    for a column of more than ``max_bin`` distinct values, the midpoints
    between neighbouring values for one of fewer (a 0/1 column gets its two
    bins)."""
    out = quantile_bounds(x, max_bin)
    for f in range(x.shape[1]):
        values = np.unique(x[:, f])
        if len(values) <= max_bin:
            out[f] = np.r_[(values[:-1] + values[1:]) / 2.0, np.inf]
    return out


def grow_iterations(rows, cfg, bounds, count, **how):
    """``count`` iterations of K class trees grown by the reference's own
    best splits: (walks, trees), K an iteration in class order."""
    walks = []
    for _ in range(count):
        rows.begin_iteration()
        walks += [walk_tree(rows, cfg, bounds, cls, **how)
                  for cls in range(rows.k)]
    return walks, [grown_to_tree(w, bounds) for w in walks]
