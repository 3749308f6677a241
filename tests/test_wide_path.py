"""The path data wider than one accumulator block takes (F x B > 2,048,
``ops/pallas_hist._ACC_ROWS_MAX``): a route pass of its own, the grouped
histogram kernel ``hist_leaf_q8`` on a (feature group, row chunk) grid, the
unfused front. Two widths: 520 features x 15 bins and 40 features x 63 bins.
The router is the Pallas route kernel at every width, fed the level's split
columns (``H.route_rows``). Pallas kernels run interpreted."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import binning, obs
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import pallas_hist as ph

from _trees import same_trees

# (features, max_bin): both pad to 64 bins a feature, 32 features a group;
# neither width is a multiple of 32, so the last group is part padding
WIDTHS = [(520, 15), (40, 63)]
B = 64


def _data(f, n=3000, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = np.zeros(f)
    w[::7] = rng.randn(len(w[::7]))
    w[f - 3] = 1.5                       # a strong column in the tail group
    y = (X @ w + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _train(X, y, max_bin, impl, **extra):
    p = {"objective": "binary", "num_leaves": 15, "max_bin": max_bin,
         "min_data_in_leaf": 20, "verbosity": -1, "histogram_impl": impl,
         "use_quantized_grad": True, **extra}
    return lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)


# ---- (a) the whole path through lgb.train against scatter ------------------
@pytest.mark.parametrize("f,max_bin", WIDTHS)
def test_train_matches_scatter(f, max_bin):
    """Pallas (route pass + grouped kernel) and the scatter histograms grow
    the same trees from the same quantised gradients."""
    X, y = _data(f)
    a = _train(X, y, max_bin, "pallas")
    b = _train(X, y, max_bin, "scatter")
    assert f * a._gbdt.gp.max_bin > ph._ACC_ROWS_MAX
    ta = same_trees(a, b)
    used = {int(v) // 32 for t in ta
            for v in np.asarray(t.split_feature)[: t.num_leaves - 1]}
    assert (f - 1) // 32 in used, "no split in the tail feature group"


def test_lean_grower_routes_through_the_kernel():
    """``route_rows``' second caller: the lean depthwise grower (a histogram
    pool under the frontier's size; two slots a split) grows the same trees
    on the Pallas path, routed by the kernel, as on scatter."""
    X, y = _data(40)
    a, b = (_train(X, y, 63, impl, histogram_pool_size=0.05)
            for impl in ("pallas", "scatter"))
    assert a._gbdt.gp.lean_ft > 0 and b._gbdt.gp.lean_ft > 0
    same_trees(a, b)


@pytest.mark.parametrize("extra", [{}, {"histogram_pool_size": 0.05}],
                         ids=["default", "lean"])
def test_sharded_growers_route_through_the_kernel(extra):
    """Under ``shard_map`` ``bins_T`` is the shard's block and the pick-up
    of the split columns is local: two row shards put every row on a leaf
    and split the root where one shard does."""
    X, y = _data(40)
    one = _train(X, y, 63, "pallas", **extra)
    two = _train(X, y, 63, "pallas", num_shards=2, **extra)
    assert (two._gbdt.gp.lean_ft > 0) == bool(extra)
    for t1, t2 in zip(one._ensure_host_trees(), two._ensure_host_trees()):
        k = t2.num_leaves
        assert k > 1 and int(np.asarray(t2.leaf_count)[:k].sum()) == len(X)
        assert t1.split_feature[0] == t2.split_feature[0]
        assert t1.threshold_bin[0] == t2.threshold_bin[0]


# ---- (b) the grouped kernel, column by column ------------------------------
@pytest.mark.parametrize("f,const_hess", [(70, False), (70, True), (33, False)])
def test_grouped_kernel_matches_scatter(f, const_hess):
    """``hist_pallas_q8`` with several feature groups and a part-padded last
    one against the scatter histogram of the dequantised rows, every column
    (the padded tail group's real columns too)."""
    rng = np.random.RandomState(3)
    n, s = 5000, 5
    bins = rng.randint(0, 63, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = (np.ones(n) if const_hess else rng.rand(n)).astype(np.float32)
    c = (rng.rand(n) < 0.9).astype(np.float32)
    g, h = g * c, h * c
    slot = rng.randint(0, s + 2, size=n).astype(np.int32)   # some dropped
    quant = H.make_quant(jnp.asarray(g), jnp.asarray(h), jnp.asarray(c), 11,
                         const_hess=const_hess)
    hq, ch = H._q8_h_arg(quant)
    fg, n_fg = ph.feature_grouping(f, B)
    assert n_fg > 1 and f % fg                     # a padded tail group
    out = np.asarray(ph.hist_pallas_q8(
        jnp.asarray(bins.T.copy()), quant.gq, hq, quant.cq,
        jnp.asarray(slot), s, B, quant.scale_g, quant.scale_h,
        const_hess=ch, interpret=True))
    gd, hd, cd = H.dequant_rows(quant)
    keep = jnp.asarray(slot < s)
    ref = np.asarray(H.hist_per_leaf_scatter(
        jnp.asarray(bins), gd * keep, hd * keep, cd * keep,
        jnp.asarray(np.where(slot < s, slot, s)), s, B))
    assert out.shape == ref.shape == (s, 3, f, B)
    for j in range(f):
        np.testing.assert_allclose(out[:, :, j], ref[:, :, j], rtol=1e-5,
                                   atol=1e-4, err_msg=f"column {j}")
    np.testing.assert_array_equal(out[:, 2], ref[:, 2])     # counts exact


# ---- (c) the three routers agree -------------------------------------------
def _tables(rng, f, L, S):
    feat = rng.randint(-1, f, size=L).astype(np.int32)
    feat[0], feat[1] = f - 1, -1
    return H.RouteTables(
        feat=jnp.asarray(feat),
        thr=jnp.asarray(rng.randint(0, 63, size=L).astype(np.int32)),
        dleft=jnp.asarray(rng.randint(0, 2, size=L).astype(np.int32)),
        new_leaf=jnp.asarray((np.arange(L) + L).astype(np.int32)),
        slot_left=jnp.asarray(rng.randint(0, S + 1, size=L).astype(np.int32)),
        slot_right=jnp.asarray(rng.randint(0, S + 1, size=L).astype(np.int32)))


def _route_by_hand(bins, leaf_id, t, na_bin, S):
    """The routing rule written out row by row (numerical splits)."""
    feat, thr, dleft, new_leaf, sl, sr = (np.asarray(a) for a in (
        t.feat, t.thr, t.dleft, t.new_leaf, t.slot_left, t.slot_right))
    slot, lid = np.full(len(bins), S, np.int32), leaf_id.copy()
    for i, leaf in enumerate(leaf_id):
        fe = feat[leaf]
        if fe < 0:
            continue
        v = int(bins[i, fe])
        right = (dleft[leaf] == 0) if v == na_bin[fe] else v > thr[leaf]
        slot[i] = sr[leaf] if right else sl[leaf]
        if right:
            lid[i] = new_leaf[leaf]
    return slot, lid


@pytest.mark.parametrize("f", [40, 520, 2000])
def test_routers_agree(f):
    rng = np.random.RandomState(9)
    n, L, S = 2500, 8, 4
    bins = rng.randint(0, 63, size=(n, f)).astype(np.uint8)
    leaf_id = rng.randint(0, L, size=n).astype(np.int32)
    na_bin = np.where(rng.rand(f) < 0.3, 5, 256).astype(np.int32)
    t = _tables(rng, f, L, S)
    want_slot, want_lid = _route_by_hand(bins, leaf_id, t, na_bin, S)
    args = (jnp.asarray(leaf_id), t, jnp.asarray(na_bin), S)
    xs, xl = H.route_level(jnp.asarray(bins), *args)
    ps, pl_ = ph.route_level_pallas(jnp.asarray(bins.T.copy()), *args,
                                    interpret=True)
    # what the growers call: the kernel over the level's split columns
    rs, rl = H.route_rows(jnp.asarray(bins), jnp.asarray(bins.T.copy()),
                          *args, impl="pallas")
    for slot, lid in ((xs, xl), (ps, pl_), (rs, rl)):
        np.testing.assert_array_equal(np.asarray(lid), want_lid)
        np.testing.assert_array_equal(np.minimum(np.asarray(slot), S),
                                      want_slot)


def _case_tables(case, rng, f, L, S):
    """(tables, num_slots) of one level as the growers make them: leaf
    ``idx``'s split in slot ``idx`` (lean grower: both children, slots
    2 idx and 2 idx + 1), the out-of-range slot where no row is measured."""
    t = dict(feat=np.full(L, -1, np.int32),
             thr=rng.randint(0, 62, size=L).astype(np.int32),
             dleft=rng.randint(0, 2, size=L).astype(np.int32),
             new_leaf=(np.arange(L) + L).astype(np.int32))
    k = {"no_split_leaves": 2, "k_is_num_slots": S}.get(case, min(L, S) // 2)
    # live_leaves: every splitting leaf among the first S, where the rows are
    among = S if case.startswith("live_leaves") else L
    split = np.sort(rng.choice(among, size=k, replace=False))
    t["feat"][split] = rng.randint(0, f, size=k)
    t["feat"][split[0]] = f - 1                     # the matrix's last row
    if case == "same_feature":
        t["feat"][split[1:3]] = 7
    idx = np.zeros(L, np.int32)
    idx[split] = np.arange(k)
    if case == "lean_two_slots":
        S = 2 * S
        t["slot_left"], t["slot_right"] = 2 * idx, 2 * idx + 1
    else:                                # the smaller child is measured
        left = rng.rand(L) < 0.5
        t["slot_left"] = np.where(left, idx, S)
        t["slot_right"] = np.where(left, S, idx)
    for name in ("slot_left", "slot_right"):
        t[name] = np.where(t["feat"] >= 0, t[name], S).astype(np.int32)
    if case.endswith("categorical"):
        is_cat = np.zeros(L, np.int32)
        is_cat[split[::2]] = 1
        t["is_cat"] = is_cat
        t["member"] = (rng.rand(L, 256) < 0.4).astype(np.float32)
    return H.RouteTables(**{n: jnp.asarray(v) for n, v in t.items()}), S


@pytest.mark.parametrize("case,f,L,S,n", [
    ("categorical", 40, 16, 8, 4096 + 17),
    ("categorical", 2000, 255, 32, 3000),
    ("missing_both_defaults", 520, 16, 8, 4096 + 17),
    ("no_split_leaves", 2000, 255, 127, 3000),
    ("same_feature", 520, 16, 8, 2500),
    ("lean_two_slots", 40, 16, 8, 2500),
    ("lean_two_slots", 2000, 64, 32, 2500),
    ("k_is_num_slots", 520, 64, 32, 4096 + 17),
    ("k_is_num_slots", 2000, 255, 127, 2 * 4096 + 5),
    ("live_leaves", 2000, 255, 32, 3000),
    ("live_leaves_categorical", 520, 64, 8, 4096 + 17),
    ("thr_255", 2000, 255, 32, 3000),
    ("thr_255", 40, 16, 8, 2500),
])
def test_route_rows_equals_route_level(case, f, L, S, n):
    """``route_rows`` on the Pallas path (the route kernel over the level's
    split columns) gives the integers of ``route_level``, the XLA reference:
    with categorical splits, missing bins under both defaults, leaves that
    do not split, two leaves on one feature, the lean grower's two slots a
    split, as many splits as slots, N off the kernel's chunk, tables cut to
    the leaves that hold rows (the reference reads the whole ones),
    thresholds of 255."""
    rng = np.random.RandomState(len(case) * 1000 + f)
    bins = rng.randint(0, 63, size=(n, f)).astype(np.uint8)
    bins[:, f - 1] = rng.randint(200, 256, size=n)  # past int8: the pick-up
    live = S if case.startswith("live_leaves") else L
    leaf_id = rng.randint(0, live, size=n).astype(np.int32)
    t, S = _case_tables(case, rng, f, L, S)
    na_bin = np.where(rng.rand(f) < 0.3, 5, 256).astype(np.int32)
    if case == "thr_255":       # on the column that holds bins up to 255
        thr = np.asarray(t.thr).copy()
        thr[np.asarray(t.feat) == f - 1] = 255
        t = t._replace(thr=jnp.asarray(thr))
    if case == "missing_both_defaults":
        feat, dleft = np.asarray(t.feat), np.asarray(t.dleft)
        na_bin[feat[feat >= 0]] = 5
        assert set(dleft[feat >= 0]) == {0, 1}
        assert (bins[:, feat[feat >= 0]] == 5).any()
    args = (jnp.asarray(leaf_id), t, jnp.asarray(na_bin), S)
    want_slot, want_lid = H.route_level(jnp.asarray(bins), *args)
    cut = jax.tree.map(lambda a: a[:live], t)
    slot, lid = H.route_rows(jnp.asarray(bins), jnp.asarray(bins.T.copy()),
                             jnp.asarray(leaf_id), cut, jnp.asarray(na_bin),
                             S, impl="pallas")
    np.testing.assert_array_equal(np.asarray(lid), np.asarray(want_lid))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(want_slot))
    moved = np.asarray(lid) != leaf_id
    assert moved.any() and not moved.all()
    if case == "k_is_num_slots":
        assert int((np.asarray(t.feat) >= 0).sum()) == S


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations call."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_wide_level_pass_gathers_no_row_vector():
    """The wide level pass (F = 520: route pass + grouped kernel) looks
    nothing up row by row in XLA: no gather in it has an operand or a result
    with a dimension of N (the tables are decoded in the route kernel, the
    level's split columns picked by one [K_pad, F] x [F, N] contraction)."""
    n, f, L, S = 3000, 520, 8, 4
    t = _tables(np.random.RandomState(0), f, L, S)

    def level(bins, bins_T, g, h, c, lid, na):
        quant = H.make_quant(g, h, c, jnp.uint32(1))
        return H.hist_routed(bins, g, h, c, lid, t, na, S, 16, impl="pallas",
                             bins_T=bins_T, quant=quant)
    rows = jnp.zeros(n, jnp.float32)
    jaxpr = jax.make_jaxpr(level)(
        jnp.zeros((n, f), jnp.uint8), jnp.zeros((f, n), jnp.uint8), rows,
        rows, rows, jnp.zeros(n, jnp.int32), jnp.zeros(f, jnp.int32))
    eqns = list(_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 2          # route_level, hist_leaf_q8
    bad = [(e.primitive.name, [v.aval.shape for v in e.invars + e.outvars])
           for e in eqns if "gather" in e.primitive.name
           and any(n in v.aval.shape for v in e.invars + e.outvars)]
    assert not bad, bad
    picks = [e for e in eqns if e.primitive.name == "dot_general"
             and e.outvars[0].aval.shape == (32, n)]
    assert len(picks) == 1 and picks[0].invars[1].aval.shape == (f, n)


def test_route_level_never_widens_the_matrix():
    """The XLA router gathers each row's uint8 bin and widens that: no
    [N, F] int32 copy of the bin matrix (6.4 GB at 800k x 2,000)."""
    n, f, L = 64, 520, 8
    t = _tables(np.random.RandomState(0), f, L, 4)
    jaxpr = jax.make_jaxpr(lambda b, l, na: H.route_level(b, l, t, na, 4))(
        jnp.zeros((n, f), jnp.uint8), jnp.zeros(n, jnp.int32),
        jnp.zeros(f, jnp.int32))
    wide = [v.aval for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
            if v.aval.shape == (n, f) and v.aval.dtype != jnp.uint8]
    assert not wide, wide


# ---- (d) the hist_path event on both sides of the gate ---------------------
@pytest.mark.parametrize("f,max_bin,impl,want", [
    (520, 15, "pallas", {"level_kernel": "hist_leaf_q8", "feature_groups": 17,
                         "route": "pallas", "front": "unfused",
                         "bins_T_cached": True, "decode_leaves": [4]}),
    (40, 63, "pallas", {"level_kernel": "hist_leaf_q8", "feature_groups": 2,
                        "route": "pallas", "front": "unfused",
                        "bins_T_cached": True, "decode_leaves": [4]}),
    (28, 63, "pallas", {"level_kernel": "hist_level_q8", "feature_groups": 1,
                        "route": "fused", "front": "fused",
                        "bins_T_cached": True, "decode_leaves": [4]}),
    (28, 63, "scatter", {"level_kernel": "scatter", "feature_groups": 1,
                         "route": "xla", "front": "unfused",
                         "bins_T_cached": False, "decode_leaves": [1, 4]}),
    # the published shape: a level's leaves in the S = 32 group, num_leaves
    # in the group that holds the tail
    (28, 63, "pallas", {"level_kernel": "hist_level_q8", "route": "fused",
                        "num_leaves": 255, "decode_leaves": [32, 255]}),
])
def test_hist_path_event(f, max_bin, impl, want):
    X, y = _data(f, n=400)
    want = dict(want)
    obs.reset()
    obs.configure(enabled=True)
    try:
        p = {"objective": "binary", "num_leaves": want.pop("num_leaves", 4),
             "max_bin": max_bin,
             "verbosity": -1, "histogram_impl": impl, "telemetry": True}
        lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=1)
        events = [e for e in obs.EVENTS.snapshot() if e["type"] == "hist_path"]
    finally:
        obs.configure(enabled=False)
        obs.reset()
    assert len(events) == 1
    assert {k: events[0][k] for k in want} == want


def test_hist_path_at_the_published_widths():
    """Epsilon (2,000 x 64 padded bins) and HIGGS (28 x 64), from shapes."""
    assert H.hist_path(2000, 64, "pallas") == {
        "level_kernel": "hist_leaf_q8", "feature_groups": 63,
        "route": "pallas"}
    assert H.hist_path(28, 64, "pallas") == {
        "level_kernel": "hist_level_q8", "feature_groups": 1,
        "route": "fused"}
    # the front's gate, as grad_quant_hist0 and the event read it
    assert H.one_kernel_front(28, 64, "pallas")
    assert not H.one_kernel_front(2000, 64, "pallas")
    assert not H.one_kernel_front(28, 64, "scatter")


def test_wide_step_holds_no_row_literal():
    """The objective's per-row constant reaches the wide step as an argument
    (``grad_quant_hist0``'s unfused chain from (score, aux)): closed over by
    ``obj.get_gradients`` it was a literal of the program, and the persistent
    compile cache keyed on the labels (a compile in every run of the cell)."""
    from lightgbm_tpu import prewarm
    X, y = _data(40, n=1500)
    p = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
         "verbosity": -1, "histogram_impl": "pallas"}
    g = lgb.Booster(p, lgb.Dataset(X, label=y, params=p))._gbdt
    assert g._fused_front()[0] is not None
    text = g._build_fused_step(False).lower(*prewarm.step_avals(g)).as_text()
    assert "tensor<1500xf32>" in text              # the rows are there ...
    literals = [l for l in text.splitlines()
                if "constant dense<\"0x" in l and "1500x" in l]
    assert not literals, literals[0][:200]         # ... and none is a literal


# ---- (e) bin bounds: the column-major sample against the old loop ----------
def _same_mapper(a, b):
    assert a.__dict__.keys() == b.__dict__.keys()
    for k, v in a.__dict__.items():
        w = b.__dict__[k]
        if isinstance(v, np.ndarray):
            assert v.dtype == w.dtype, k
            np.testing.assert_array_equal(v, w, err_msg=k)   # nan == nan
        else:
            assert v == w or (v != v and w != w), k


def _find_bins_row_major(data, max_bin, sample_cnt, categorical, seed):
    """``find_bin_mappers`` as it stood before PR 27: each column sliced out
    of the row-major sample."""
    n, f = data.shape
    rng = np.random.RandomState(seed)
    sample = (data[rng.choice(n, sample_cnt, replace=False)]
              if n > sample_cnt else data)
    return [binning.BinMapper.from_sample(
        sample[:, j], len(sample), max_bin, min_data_in_bin=3,
        bin_type=(binning.BIN_CATEGORICAL if j in categorical
                  else binning.BIN_NUMERICAL),
        use_missing=True, zero_as_missing=False, forced_bounds=None)
        for j in range(f)]


@pytest.mark.parametrize("f", [28, 101])
@pytest.mark.parametrize("n", [900, 5000])
def test_find_bins_is_the_row_major_loop(monkeypatch, n, f):
    """The sample is taken column-major, copied in row blocks on threads;
    every mapper is the row-major loop's, field by field: ties, NaN, a
    constant column, a categorical one, a sparse one; sampled (n over
    ``sample_cnt``) and whole; at HIGGS's width and a wider one."""
    rng = np.random.RandomState(1)
    X = rng.randn(n, f).astype(np.float32)
    X[::7, 1] = np.nan
    X[:, 2] = 1.0
    X[:, 3] = np.round(X[:, 3])
    X[:, 4] = np.abs(np.round(X[:, 4] * 3))
    X[rng.rand(n) < 0.9, 5] = 0.0
    X[:, f - 1] = np.round(X[:, f - 1] * 2) / 2
    kw = dict(max_bin=63, categorical=[4], sample_cnt=2000, seed=3)
    monkeypatch.setattr(binning, "_FIND_BINS_ROWS", 256)    # several blocks
    new = binning.find_bin_mappers(X, **kw)
    old = _find_bins_row_major(X, **kw)
    assert len(new) == len(old) == f
    for a, b in zip(new, old):
        _same_mapper(a, b)
