"""Device-side prediction: route rows through trees.

Reference analog: Tree::Predict / NumericalDecision node walk (tree.h:126,240) and
the batch Predictor (predictor.hpp:29). On TPU the node walk is a ``while_loop``
of vectorized gathers over the flat tree arrays — every row advances one level
per iteration; finished rows park on their leaf (pointer < 0 is a leaf, encoded
~leaf_index, matching the reference's child encoding). The binned walk ends
when no row is left on an internal node, so it takes as many steps as the
deepest leaf a row reached; ``max_steps`` is only its ceiling. A caller that
also holds the rows feature-major (validation scoring of a trainer on the
Pallas kernels) gets the same walk as one Mosaic kernel, where the shapes
allow (``walk_path``).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from ..utils.timer import scoped_jit


def route_bins(split_feature, threshold_bin, default_left, left_child, right_child,
               num_leaves, bins, na_bin, max_steps: int,
               is_cat=None, cat_mask=None, scope: str = None,
               steps_out: list = None, bins_T=None):
    """Leaf index for each row of a *binned* matrix. bins: [N, F] uint8/int32.

    The walk stops as soon as every row is on a leaf (the predicate is
    computed on the device inside the loop; under ``vmap`` over trees, when
    the deepest tree of the batch is done) and at ``max_steps`` at the
    latest: a caller that knows no better passes ``num_leaves - 1``, the
    depth of a chain, and pays for the tree's real depth. ``steps_out``, a
    list, receives the number of steps taken as an i32 scalar on the device
    (it is an argument and not a second return value so that the leaf array
    stays the one thing a caller, or a test that wraps this entry, handles).

    is_cat [n_nodes] bool + cat_mask [n_nodes, B] bool extend the walk with
    categorical subset decisions (bin member -> LEFT; reference: tree.h:279).

    Jitted with the tree arrays as traced ARGUMENTS: the eager form baked
    them into the loop body's jaxpr as constants, so every call with a
    new tree lowered a fresh program (DART's per-iteration drop/re-add
    walked 6+ lowerings per iteration). Inside an outer jit the wrapper
    just inlines.

    ``scope`` names the device scope of a call that is dispatched on its own
    (validation scoring during training passes ``valid_score``): the walk is
    then the program ``jit_route_bins_<scope>``, every op under the scope
    (``utils.timer.scoped_jit``); what ``Booster.predict`` runs stays bare.

    ``bins_T``: the same rows feature-major, [F_pad, N_pad] uint8
    (``Dataset.bins_T``), from a caller that holds them and trains on the
    Pallas kernels. Where ``walk_path`` then says ``"kernel"``, the whole
    walk is one Mosaic kernel over ``bins_T`` (ops/pallas_hist.walk_tree;
    interpreted on the CPU, where only a forced ``histogram_impl=pallas``
    brings a caller here): the same leaves and the same step count, to the
    bit, under the same program name. Everywhere else the XLA walk below."""
    if walk_path(bins_T, split_feature, is_cat) == "kernel":
        leaf, steps = _kernel_walk(scope)(
            split_feature, threshold_bin, default_left, left_child,
            right_child, num_leaves, bins_T, na_bin, n=bins.shape[0],
            max_steps=max_steps, interpret=jax.default_backend() == "cpu")
    else:
        walk = _WALK if scope is None else _scoped_walk(scope)
        leaf, steps = walk(split_feature, threshold_bin, default_left,
                           left_child, right_child, num_leaves, bins, na_bin,
                           max_steps=max_steps, is_cat=is_cat,
                           cat_mask=cat_mask)
    if steps_out is not None:
        steps_out.append(steps)
    return leaf


def walk_path(bins_T, split_feature, is_cat=None) -> str:
    """Which program ``route_bins`` runs, read off its arguments' shapes:
    ``"kernel"`` (ops/pallas_hist.walk_tree) when the caller handed the rows
    feature-major, they are few enough for the kernel's select
    (WALK_MAX_FEATURES, 128: Epsilon's 2,000 columns are not), the tree's
    node tables fit VMEM beside a chunk (WALK_MAX_NODES, 1,023: num_leaves
    up to 1,024) and no node is categorical (the kernel decodes no bin
    membership: a tree with ``is_cat`` walks in XLA, never numerically);
    ``"xla"`` otherwise."""
    from .pallas_hist import WALK_MAX_FEATURES, WALK_MAX_NODES
    if (bins_T is not None and is_cat is None
            and bins_T.shape[0] <= WALK_MAX_FEATURES
            and split_feature.shape[0] <= WALK_MAX_NODES):
        return "kernel"
    return "xla"


def _walk(split_feature, threshold_bin, default_left, left_child, right_child,
          num_leaves, bins, na_bin, max_steps: int, is_cat=None,
          cat_mask=None):
    n = bins.shape[0]
    # pointer: >=0 internal node, <0 leaf (~leaf)
    start = jnp.where(num_leaves > 1, 0, -1)
    ptr = jnp.full((n,), start, dtype=jnp.int32)
    mem_flat = (cat_mask.reshape(-1).astype(jnp.float32)
                if cat_mask is not None else None)

    def body(carry):
        ptr, step = carry
        node = jnp.maximum(ptr, 0)
        feat = split_feature[node]
        thr = threshold_bin[node]
        col = jnp.take_along_axis(bins, feat[:, None].astype(jnp.int32), axis=1)[:, 0]
        col = col.astype(jnp.int32)
        is_na = col == na_bin[feat]
        go_left = jnp.where(is_na, default_left[node], col <= thr)
        if is_cat is not None:
            bm = cat_mask.shape[1]
            mem = jnp.take(mem_flat, node * bm + jnp.clip(col, 0, bm - 1),
                           mode="fill", fill_value=0.0) > 0.5
            mem = mem & (col < bm)
            go_left = jnp.where(is_cat[node], mem, go_left)
        nxt = jnp.where(go_left, left_child[node], right_child[node])
        return jnp.where(ptr >= 0, nxt, ptr), step + 1

    def rows_still_moving(carry):
        ptr, step = carry
        return (step < max_steps) & jnp.any(ptr >= 0)

    ptr, steps = jax.lax.while_loop(rows_still_moving, body,
                                    (ptr, jnp.int32(0)))
    return jnp.invert(jnp.minimum(ptr, -1)), steps  # ~ptr, leaves only


# the programs keep the public name: jit_route_bins, jit_route_bins_<scope>
_walk.__name__ = _walk.__qualname__ = "route_bins"
_WALK = jax.jit(_walk, static_argnames=("max_steps",))


@lru_cache(maxsize=None)
def _scoped_walk(scope: str):
    return scoped_jit(_walk, scope, static_argnames=("max_steps",))


def _walk_kernel(*tree_and_rows, n: int, max_steps: int, interpret: bool):
    from .pallas_hist import walk_tree
    return walk_tree(*tree_and_rows, n=n, max_steps=max_steps,
                     interpret=interpret)


# the kernel walk's programs carry the XLA walk's names: the trace's readers
# sum ``jit_route_bins*`` modules whichever walk ran
_walk_kernel.__name__ = _walk_kernel.__qualname__ = "route_bins"
_KERNEL_STATIC = ("n", "max_steps", "interpret")
_KERNEL_WALK = jax.jit(_walk_kernel, static_argnames=_KERNEL_STATIC)


@lru_cache(maxsize=None)
def _scoped_kernel_walk(scope: str):
    return scoped_jit(_walk_kernel, scope, static_argnames=_KERNEL_STATIC)


def _kernel_walk(scope: str = None):
    return _KERNEL_WALK if scope is None else _scoped_kernel_walk(scope)


def route_raw(split_feature, threshold_real, default_left, left_child, right_child,
              num_leaves, x, missing_type, zero_as_missing_eps, max_steps: int):
    """Leaf index for raw (unbinned) float rows x: [N, F] f64/f32.

    missing_type: [F] i32 (0 none / 1 zero / 2 nan), mirroring the reference's
    per-feature missing handling at predict time (tree.h:240 NumericalDecision).
    """
    n = x.shape[0]
    start = jnp.where(num_leaves > 1, 0, -1)
    ptr = jnp.full((n,), start, dtype=jnp.int32)

    def body(_, ptr):
        node = jnp.maximum(ptr, 0)
        feat = split_feature[node]
        thr = threshold_real[node]
        v = jnp.take_along_axis(x, feat[:, None].astype(jnp.int32), axis=1)[:, 0]
        mt = missing_type[feat]
        isnan = jnp.isnan(v)
        # missing_type None: NaN treated as 0 (reference converts NaN->0)
        v0 = jnp.where(isnan & (mt == 0), 0.0, v)
        is_missing = jnp.where(
            mt == 2, isnan,
            jnp.where(mt == 1, (jnp.abs(v0) < zero_as_missing_eps) | isnan,
                      jnp.zeros_like(isnan)))
        # non-missing NaN can only occur under missing_type None, where v0 == 0
        go_left = jnp.where(is_missing, default_left[node], v0 <= thr)
        nxt = jnp.where(go_left, left_child[node], right_child[node])
        return jnp.where(ptr >= 0, nxt, ptr)

    ptr = jax.lax.fori_loop(0, max_steps, body, ptr)
    return jnp.invert(jnp.minimum(ptr, -1))


def ensemble_raw_scores(dense, stack, bins_dev, na_dev, k: int, n_trees: int,
                        avg: bool, exact_f32: bool = False,
                        max_steps: int = 1):
    """Dense-or-walk ensemble dispatch shared by Booster.predict and the
    warm-start predictor (engine._predict_via_trees): dense path-matrix
    predictor when ``dense`` tables exist (no categorical nodes), the
    depth-bounded walk otherwise; per-class [cls::k] slicing for multiclass;
    ``avg`` divides by trees-per-class (RF average_output)."""
    import numpy as _np

    def one(tset, fn):
        if k == 1:
            # prediction OUTPUTS are host f64 by API contract (the reference
            # returns double scores); this is a device->host readback, not an
            # upload, so no precision is lost on device
            raw = _np.asarray(fn(tset),   # tpu-lint: disable=dtype-drift
                              dtype=_np.float64)
            return raw / n_trees if avg else raw
        out = _np.zeros((bins_dev.shape[0], k))
        for cls in range(k):
            sub = {kk: v[cls::k] for kk, v in tset.items()}
            out[:, cls] = _np.asarray(fn(sub))
        return out / (n_trees // k) if avg else out

    if dense is not None:
        dense_dev = {kk: jnp.asarray(v) for kk, v in dense.items()}
        return one(dense_dev, lambda tset: predict_bins_ensemble_dense(
            tset, bins_dev, exact_f32=exact_f32))
    stack_dev = {kk: jnp.asarray(v) for kk, v in stack.items()}
    return one(stack_dev, lambda tset: predict_bins_ensemble(
        tset, bins_dev, na_dev, max_steps))


@partial(jax.jit, static_argnames=("group", "row_chunk", "exact_f32"))
def predict_bins_ensemble_dense(tables, bins, group: int = 8,
                                row_chunk: int = 4096,
                                exact_f32: bool = False):
    """Gather-free ensemble prediction: [N] f32 raw scores.

    TPU-native replacement for the per-row pointer walk (reference:
    PredictRaw -> Tree::Predict node chase, gbdt_prediction.cpp:13 +
    tree.h:240): every node of a tree GROUP is decided at once via a one-hot
    feature contraction, and each row's leaf is resolved by the signed path
    matrix built in models/tree.py ensemble_path_tables — three batched MXU
    einsums per (tree-group, row-chunk), no sequential dependency, no
    gathers. The walk-based predict of a 500-tree model is a long chain of
    dependent gathers; this runs the same query as dense matmuls.

    tables: dict from ensemble_path_tables (device-put by the caller);
    bins: [N, F] uint8/int32 binned rows. ``exact_f32`` must be True when
    bin values can exceed 256 (pseudo-bins) — bf16 one-hot contraction is
    only exact below that.
    """
    n, f = bins.shape
    t, m = tables["feat"].shape
    l = tables["lv"].shape[1]
    cdt = jnp.float32 if exact_f32 else jnp.bfloat16
    prec = (jax.lax.Precision.HIGHEST if exact_f32
            else jax.lax.Precision.DEFAULT)

    t_pad = -(-t // group) * group
    n_pad = -(-n // row_chunk) * row_chunk

    def padt(x):
        return jnp.pad(x, ((0, t_pad - t),) + ((0, 0),) * (x.ndim - 1))

    feat_p = padt(tables["feat"]).reshape(-1, group, m)
    thr_p = padt(tables["thr"]).reshape(-1, group, m)
    dl_p = padt(tables["dleft"]).reshape(-1, group, m)
    nav_p = padt(tables["nav"]).reshape(-1, group, m)
    a_p = padt(tables["A"].astype(cdt)).reshape(-1, group, l, m)
    # padded trees: plen stays -1 (impossible count) so no leaf matches
    plen_p = jnp.pad(tables["plen"], ((0, t_pad - t), (0, 0)),
                     constant_values=-1.0).reshape(-1, group, l)
    lv_p = padt(tables["lv"]).reshape(-1, group, l)
    # one-hot of each node's feature, built once (chunk-independent)
    fo = (feat_p[..., None] == jnp.arange(f)[None, None, None, :]) \
        .astype(cdt)                                      # [Gs, G, M, F]

    bins_p = jnp.pad(bins, ((0, n_pad - n), (0, 0)))
    chunks = bins_p.reshape(-1, row_chunk, f)

    def per_chunk(_, bins_c):
        binc = bins_c.T.astype(cdt)                       # [F, C]

        def per_group(score, args):
            fo_g, thr_g, dl_g, nav_g, a_g, plen_g, lv_g = args
            colv = jnp.einsum("gmf,fc->gmc", fo_g, binc,
                              preferred_element_type=jnp.float32,
                              precision=prec)             # exact int values
            dec = jnp.where(colv == nav_g[:, :, None], dl_g[:, :, None],
                            (colv <= thr_g[:, :, None]).astype(jnp.float32))
            sgn = (2.0 * dec - 1.0).astype(jnp.bfloat16)  # +-1, exact
            cnt = jnp.einsum("glm,gmc->glc", a_g.astype(jnp.bfloat16), sgn,
                             preferred_element_type=jnp.float32)
            memb = (cnt == plen_g[:, :, None]).astype(jnp.float32)
            score = score + jnp.einsum("gl,glc->c", lv_g, memb)
            return score, None

        score, _ = jax.lax.scan(
            per_group, jnp.zeros(bins_c.shape[0], jnp.float32),
            (fo, thr_p, dl_p, nav_p, a_p, plen_p, lv_p))
        return None, score

    _, out = jax.lax.scan(per_chunk, None, chunks)
    return out.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("max_steps",))
def predict_bins_ensemble(tree_stack, bins, na_bin, max_steps: int):
    """Sum of leaf values over a stacked ensemble, on binned data.

    tree_stack: dict of arrays with leading tree axis [T, ...] (from
    models.tree.stack_trees). Returns [N] f32 raw scores (no init score).
    """
    has_cat = "is_cat" in tree_stack

    def one(sf, tb, dl, lc, rc, nl, lv, ic=None, cm=None):
        leaf = route_bins(sf, tb, dl, lc, rc, nl, bins, na_bin, max_steps,
                          is_cat=ic, cat_mask=cm)
        return lv[leaf]

    if has_cat:
        per_tree = jax.vmap(one)(
            tree_stack["split_feature"], tree_stack["threshold_bin"],
            tree_stack["default_left"], tree_stack["left_child"],
            tree_stack["right_child"], tree_stack["num_leaves"],
            tree_stack["leaf_value"], tree_stack["is_cat"],
            tree_stack["cat_mask"])
    else:
        per_tree = jax.vmap(one)(
            tree_stack["split_feature"], tree_stack["threshold_bin"],
            tree_stack["default_left"], tree_stack["left_child"],
            tree_stack["right_child"], tree_stack["num_leaves"],
            tree_stack["leaf_value"])
    return per_tree.sum(axis=0)


@partial(jax.jit, static_argnames=("max_steps",))
def leaf_bins_ensemble(tree_stack, bins, na_bin, max_steps: int):
    """Per-tree leaf indices on binned/pseudo-binned data: [N, T]."""
    has_cat = "is_cat" in tree_stack

    def one(sf, tb, dl, lc, rc, nl, ic=None, cm=None):
        return route_bins(sf, tb, dl, lc, rc, nl, bins, na_bin, max_steps,
                          is_cat=ic, cat_mask=cm)

    if has_cat:
        out = jax.vmap(one)(
            tree_stack["split_feature"], tree_stack["threshold_bin"],
            tree_stack["default_left"], tree_stack["left_child"],
            tree_stack["right_child"], tree_stack["num_leaves"],
            tree_stack["is_cat"], tree_stack["cat_mask"])
    else:
        out = jax.vmap(one)(
            tree_stack["split_feature"], tree_stack["threshold_bin"],
            tree_stack["default_left"], tree_stack["left_child"],
            tree_stack["right_child"], tree_stack["num_leaves"])
    return out.T


@partial(jax.jit, static_argnames=("max_steps",))
def predict_raw_ensemble(tree_stack, x, missing_type, max_steps: int):
    """Sum of leaf values over a stacked ensemble, on raw features."""
    def one(sf, tr, dl, lc, rc, nl, lv):
        leaf = route_raw(sf, tr, dl, lc, rc, nl, x, missing_type, 1e-35, max_steps)
        return lv[leaf]

    per_tree = jax.vmap(one)(
        tree_stack["split_feature"], tree_stack["threshold_real"],
        tree_stack["default_left"], tree_stack["left_child"],
        tree_stack["right_child"], tree_stack["num_leaves"],
        tree_stack["leaf_value"])
    return per_tree.sum(axis=0)


@partial(jax.jit, static_argnames=("max_steps",))
def predict_leaf_ensemble(tree_stack, x, missing_type, max_steps: int):
    """Per-tree leaf indices (reference: predict_leaf_index, boosting.h:159)."""
    def one(sf, tr, dl, lc, rc, nl):
        return route_raw(sf, tr, dl, lc, rc, nl, x, missing_type, 1e-35, max_steps)

    return jax.vmap(one)(
        tree_stack["split_feature"], tree_stack["threshold_real"],
        tree_stack["default_left"], tree_stack["left_child"],
        tree_stack["right_child"], tree_stack["num_leaves"]).T  # [N, T]
