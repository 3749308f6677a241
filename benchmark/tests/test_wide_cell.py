"""``epsilon-binary.train``: the cell's code path at a tiny size with all
2,000 columns (the grouped histogram kernel interpreted, the tiled reference
on the CPU), one fault planted on the wide path, and the readers of the
cell's seven per-layer metrics against a hand-made trace."""
import types

import pytest

from benchmark import harness, peaks, rehearse as rh, scopes, work

CELL = "epsilon-binary.train"
# fewer rows and leaves than rehearse.TINY: 63 feature groups interpreted
TINY = dict(rh.TINY, train_rows=8192, valid_rows=0,
            params=dict(rh.TINY["params"], num_leaves=7))


def failed(result):
    return sorted(k for k, c in result["checks"].items() if not c["ok"])


@pytest.fixture
def signal_in_the_tail_group(monkeypatch):
    """The cell with its signal moved into the last feature group's 16 real
    columns (1,984 to 1,999 of a group padded to 2,016), so that every split
    worth making is one the tail group's histogram has to show."""
    import jax
    real = harness.load_cell

    def moved(name, bench=None):
        cell = real(name, bench)
        cfg = cell["cfg"]
        cell["cfg"] = dict(cfg, signal=dict(
            cfg["signal"], first=1987, every=4, linear_scale=1.5))
        return cell
    monkeypatch.setattr(harness, "load_cell", moved)
    jax.clear_caches()      # the grower is traced anew, fault and all
    yield
    jax.clear_caches()


def test_sound_run_is_correct(signal_in_the_tail_group):
    r = rh.rehearse(CELL, tiny=TINY)
    assert r["correct"], r["checks"]


def test_tail_group_left_out_of_the_histogram(monkeypatch,
                                              signal_in_the_tail_group):
    """The grouped kernel's last feature group comes back empty: the program
    never sees the columns that carry the signal, splits on noise, and the
    reference's best split at the root is far better than the chosen one."""
    from lightgbm_tpu.ops import pallas_hist as ph
    real = ph.hist_pallas_q8

    def tail_dropped(bins_T, *a, **kw):
        hist = real(bins_T, *a, **kw)
        f = hist.shape[2]
        return hist.at[:, :, (f - 1) // 32 * 32:].set(0.0)
    monkeypatch.setattr(ph, "hist_pallas_q8", tail_dropped)
    r = rh.rehearse(CELL, tiny=TINY)
    assert not r["correct"]
    assert "split_regret" in failed(r)


# ---- the seven readers -----------------------------------------------------
US = 1000
GROW = "jit(step)/jit(grow_tree_depthwise)/"
PATHS = [
    "jit(step)/front/grad/sub:sub",
    GROW + "front/quant/floor:floor",
    GROW + "front/hist0/hist_leaf_q8:custom-call",
    GROW + "level_s32/while/body/split_search/cumsum:cumsum",
    GROW + "level_s32/while/body/route_hist/route/gather:gather",
    GROW + "level_s32/while/body/route_hist/hist/jit(_pad)/pad:pad",
    GROW + "level_s32/while/body/route_hist/hist/hist_leaf_q8:custom-call",
    GROW + "level_s127/while/body/route_hist/hist/hist_leaf_q8:custom-call",
    "jit(step)/score_update/take_small:custom-call",
]
KERNEL = "%hist_leaf_q8.{} = (s32[129024,96]{{1,0}}) custom-call(%pad.1)"
# (name, start, duration) in us, one iteration of 1,000 us; path by position
OPS = [("%fusion.1 = f32[8] fusion()", 0, 10),
       ("%fusion.2 = s8[8] fusion()", 10, 20),
       (KERNEL.format(17), 30, 100),
       ("%fusion.3 = f32[8] fusion()", 130, 40),
       ("%gather_fusion = u8[8] fusion()", 170, 50),
       ("%pad.1 = u8[2016,8] pad()", 220, 30),
       (KERNEL.format(18), 250, 300),
       (KERNEL.format(19), 550, 400),
       ("%take_small.1 = f32[8] custom-call()", 950, 50)]


def _ctx(paths=PATHS, ops=OPS, events=()):
    compact = {"chips": [{"modules": [["jit_step(1)", 0, 1000 * US]],
                          "ops": [[n, s * US, d * US] for n, s, d in ops],
                          "op_paths": list(range(len(ops)))}],
               "paths": list(paths), "host": []}
    view = scopes.ScopeView(compact, 1, 1e-3)
    cell = harness.load_cell(CELL)
    return types.SimpleNamespace(
        trace=view, scope_view=view, obs_events=list(events), cell=cell,
        n_train=int(cell["cfg"]["train_rows"]), work=work,
        channels=int(cell["cfg"]["grad_channels"]),
        peaks=peaks.peaks_for("TPU v5 lite"))


HIST_PATH = {"type": "hist_path", "level_kernel": "hist_leaf_q8",
             "feature_groups": 63, "route": "xla", "front": "unfused",
             "bins_T_cached": True}


@pytest.mark.parametrize("name,want_ms", [
    ("route.device_ms_per_iter", 0.050),
    ("kernels.hist_grouped_ms_per_iter", 0.800),
    ("grower.levels_ms_per_iter", 0.820),     # 130 to 950 us
    ("front.device_ms_per_iter", 0.130),
    ("split.search_ms_per_iter", 0.040),
])
def test_trace_readers(name, want_ms):
    assert harness.read_metric(name, _ctx()) == pytest.approx(want_ms)


def test_grouped_roofline_is_least_time_over_kernel_time():
    ctx = _ctx()
    cfg = ctx.cell["cfg"]
    least, bound = work.least_seconds(
        work.hist_work(ctx.n_train, cfg["num_features"],
                       cfg["params"]["num_leaves"], ctx.channels), ctx.peaks)
    assert bound == "bytes"
    got = harness.read_metric("kernels.hist_grouped_roofline", ctx)
    assert got == pytest.approx(100.0 * least / 0.8e-3)


def test_feature_groups_reads_the_programs_event():
    assert harness.read_metric("kernels.feature_groups",
                               _ctx(events=[HIST_PATH])) == 63
    assert harness.read_metric("kernels.feature_groups", _ctx()) is None


def test_readers_find_nothing_on_the_fused_path():
    """A HIGGS-width step: one fused kernel a level, no route pass, no
    grouped kernel; a program without the scopes reads the same."""
    paths = [p.replace("/route_hist/route/", "/route_hist/")
              .replace("/route_hist/hist/", "/route_hist/") for p in PATHS]
    ops = [(n.replace("hist_leaf_q8", "hist_level_q8"), s, d)
           for n, s, d in OPS]
    ctx = _ctx(paths, ops)
    for name in ("route.device_ms_per_iter",
                 "kernels.hist_grouped_ms_per_iter",
                 "kernels.hist_grouped_roofline", "kernels.feature_groups"):
        assert harness.read_metric(name, ctx) is None


def test_the_cell_lists_its_metrics():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    new = {"route.device_ms_per_iter", "kernels.hist_grouped_ms_per_iter",
           "kernels.hist_grouped_roofline", "kernels.feature_groups",
           "grower.levels_ms_per_iter", "front.device_ms_per_iter",
           "split.search_ms_per_iter"}
    assert new <= names
    assert not names & {"grower.narrow_ms_per_iter", "split.device_ms_per_iter",
                        "valid_score.device_ms_per_iter"}
    other = harness.load_cell("higgs-binary.train")
    assert not new & {m["name"] for m in other["per_layer"]}


# ---- the tiled reference is the reference ----------------------------------
def test_tiled_walk_is_the_reference_walk():
    """At a width both fit (300 features: two tiles, the second part
    padding), ``reference_wide`` grows the tree ``reference`` grows, from the
    same gains, sums and leaf values: same formulas, same numbers."""
    import numpy as np
    from benchmark import reference as R, reference_wide as W
    cfg = harness.load_cell("higgs-binary.train")["cfg"]
    cfg = dict(cfg, num_features=300, params=dict(
        cfg["params"], num_leaves=15, min_data_in_leaf=20,
        min_sum_hessian_in_leaf=1e-3))
    n, block = 30_000, 16_384
    from benchmark import data
    sample, _ = data.to_host(data.seed_key(5), cfg, n, rows=block)
    bounds = R.quantile_bounds(sample, 63)
    walks = [mod.walk_tree(mod.Rows(5, cfg, n, bounds, block), cfg, bounds,
                           quant_bits=bits, quant_seed=5)
             for mod in (R, W) for bits in (None, 8)]
    for a, b in ((walks[0], walks[2]), (walks[1], walks[3])):
        assert a["tree"] == b["tree"] and a["num_leaves"] == 15
        for k in ("best", "chosen", "leaf_count", "leaf_value"):
            np.testing.assert_allclose(a[k], b[k], rtol=1e-9, err_msg=k)
        np.testing.assert_array_equal(a["root_bin_count"],
                                      b["root_bin_count"])
    assert (walks[1]["best"] - walks[1]["chosen"]).max() >= 0


# ---- a split taken a hair under min_sum_hessian_in_leaf --------------------
def test_a_hair_under_the_hessian_minimum_keeps_its_gain():
    """One slot, one feature, three thresholds; the left child's exact
    hessian is 99.98 under a minimum of 100 (the program's quantised sum read
    100 or more), the rows' count forbids the third threshold."""
    import numpy as np
    from benchmark import reference as R
    exact = {"gl": np.array([[[-30.0, -20.0, 5.0]]]),
             "hl": np.array([[[99.98, 150.0, 399.0]]]),
             "cl": np.array([[[500.0, 800.0, 2000.0]]]),
             "gt": np.array([10.0]), "ht": np.array([400.0]),
             "ct": np.array([2000.0])}
    at = lambda b: R._taken(exact, np.array([0]), np.array([b]), [4],
                            1, 100.0)
    gain, short = at(0)
    assert gain[0] == pytest.approx(900 / 99.98 + 1600 / 300.02 - 0.25)
    assert short[0] == pytest.approx(2e-4)
    gain, short = at(1)
    assert np.isfinite(gain[0]) and short[0] == 0.0
    gain, _ = at(2)                      # no row on the right
    assert np.isnan(gain[0])


def test_a_tree_grown_under_a_laxer_minimum_fails_the_shortfall():
    """Followed under a minimum that its leaves lie far below, a tree's
    splits keep their gains (no nan in the regret) and the shortfall says by
    how much; followed under its own minimum it reads 0."""
    import numpy as np
    from benchmark import check, reference_wide as W
    cell = harness.load_cell(CELL)
    cfg = dict(cell["cfg"], num_features=40, signal=dict(
        cell["cfg"]["signal"], first=3, every=8))
    lax = dict(cfg, params=dict(cfg["params"], num_leaves=7,
                                min_sum_hessian_in_leaf=50))
    strict = dict(cfg, params=dict(lax["params"],
                                   min_sum_hessian_in_leaf=5000))
    n, block = 16_384, 16_384
    gen = __import__("benchmark.data_wide", fromlist=["x"])
    sample, _ = gen.to_host(gen.seed_key(7), cfg, n, rows=block)
    bounds = W.quantile_bounds(sample, 63)
    walk = W.walk_tree(W.Rows(7, lax, n, bounds, block), lax, bounds)
    tree = W.grown_to_tree(walk, bounds)
    assert walk["hessian_shortfall"] == 0.0 and tree["num_leaves"] == 7
    for c, want in ((lax, True), (strict, False)):
        numbers = check.follow_trees(7, c, n, bounds, [tree], [0], block,
                                     ref=W)
        assert np.isfinite(numbers["split_regret"])
        verdict = check.judge(numbers, cell["limits"])
        assert verdict["hessian_shortfall"]["ok"] is want
        if not want:
            assert numbers["hessian_shortfall"] > 0.5
