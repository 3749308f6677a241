"""``covertype-multiclass-d8.train``: the cell's code path at 8,192 rows with
all 54 columns (the grouped kernels interpreted, the K-class reference on the
CPU), four faults planted in the program, the control's own faults at a small
size, the reference's histogram against a bincount, and the readers of the
cell's ten per-layer metrics against a hand-made trace: what they read, and
their silence in the other cells and on a program that emits none of the new
events."""
import types

import numpy as np
import pytest

from benchmark import harness, peaks, rehearse as rh, scopes, work

CELL = "covertype-multiclass-d8.train"
TINY = dict(rh.TINY, train_rows=8192, valid_rows=0, block_rows=8192,
            params={"histogram_impl": "pallas", "max_bin": 63})
# the cell's limits are calibrated at 29 M rows; at 8,192 one flipped
# near-tie of the int8 lattice is a larger share of a tree's regret
SMALL_SIZE_REGRET = {"split_regret": 5e-3, "split_regret_last": 5e-3,
                     "split_regret_rare": 2e-2}


def failed(result):
    return sorted(k for k, c in result["checks"].items() if not c["ok"])


@pytest.fixture
def small_size_limits(monkeypatch):
    import jax
    real = harness.load_cell

    def patched(name, bench=None):
        cell = real(name, bench)
        cell["limits"] = dict(cell["limits"], **SMALL_SIZE_REGRET)
        return cell
    monkeypatch.setattr(harness, "load_cell", patched)
    jax.clear_caches()      # a planted fault is traced anew, and gone after
    yield
    jax.clear_caches()


def test_sound_run_is_correct(small_size_limits):
    r = rh.rehearse(CELL, tiny=TINY)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1


# ---- faults planted in the program -----------------------------------------
def test_class_trees_swapped(monkeypatch, small_size_limits):
    """The step hands back class 1's tree as class 0's and the other way
    round: the model text holds them in that order, the scores do not."""
    from lightgbm_tpu.models.gbdt import GBDT
    real = GBDT._dispatch_step

    def swapped(self, *a, **kw):
        trees, score, cegb, ok = real(self, *a, **kw)
        trees = list(trees)
        trees[0], trees[1] = trees[1], trees[0]
        return trees, score, cegb, ok
    monkeypatch.setattr(GBDT, "_dispatch_step", swapped)
    r = rh.rehearse(CELL, tiny=TINY)
    assert not r["correct"]
    assert {"class_order_gap", "leaf_value_gap"} <= set(failed(r))
    assert r["checks"]["class_order_gap"]["value"] >= 2


def test_gradients_recomputed_inside_the_class_loop(monkeypatch,
                                                    small_size_limits):
    """Every class tree takes its gradients from the scores as the class
    trees before it left them."""
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.ops.pallas_hist import _grad_rows
    real = GBDT._make_one_class

    def regrad(self, custom):
        one = real(self, custom)
        k = self.num_tree_per_iteration

        def one_class(new_score, cegb_st, grad, hess, cls, *rest):
            grad, hess = _grad_rows(("softmax", k), new_score, rest[-1])
            return one(new_score, cegb_st, grad, hess, cls, *rest)
        return one_class
    monkeypatch.setattr(GBDT, "_make_one_class", regrad)
    r = rh.rehearse(CELL, tiny=TINY)
    assert not r["correct"]
    assert "leaf_value_gap" in failed(r)
    assert r["checks"]["leaf_count_gap"]["ok"]


def test_a_bundle_decoded_against_the_wrong_members(monkeypatch,
                                                    small_size_limits):
    """The widest bundle's positions name its members in the wrong order:
    the trees come out on other soil columns than the rows were split by."""
    import lightgbm_tpu as lgb
    real = lgb.train

    def wrong(params, ds, *a, **kw):
        meta = ds.bundle_meta
        c = int(np.argmax([len(m) for m in meta.members]))
        n = int(meta.num_bins[c])
        meta.pos_feat[c, 1:n] = meta.pos_feat[c, 1:n][::-1].copy()
        return real(params, ds, *a, **kw)
    monkeypatch.setattr(lgb, "train", wrong)
    r = rh.rehearse(CELL, tiny=TINY)
    assert not r["correct"]
    assert "leaf_count_gap" in failed(r)


def test_a_class_score_left_unchanged(monkeypatch, small_size_limits):
    """Class 2's trees are grown and never added to its score."""
    import jax.numpy as jnp
    from lightgbm_tpu.models.gbdt import GBDT
    real = GBDT._apply_tree_delta

    def stuck(self, score, delta, cls, titer, axis=1):
        return jnp.where(cls == 2, score,
                         real(self, score, delta, cls, titer, axis))
    monkeypatch.setattr(GBDT, "_apply_tree_delta", stuck)
    r = rh.rehearse(CELL, tiny=TINY)
    assert not r["correct"]
    assert "leaf_value_gap" in failed(r)


# ---- the control's faults at a small size ----------------------------------
def test_control_faults_come_out_not_correct():
    from benchmark import control, control_multiclass as cm
    cell = harness.load_cell(CELL)
    cell["cfg"] = dict(cell["cfg"], params=dict(cell["cfg"]["params"],
                                                max_bin=63))
    cell["limits"] = dict(cell["limits"], **SMALL_SIZE_REGRET)
    r = cm.read_seed(cell, 2147483900, 16_384, 8192, iters=1, modes=(
        "exact", "coarse_bins", "thin_sample", "half", "regrad",
        "bundle_order"))
    bad = control.verdicts(cell, r)
    assert bad["exact"] == [] and r["exact"]["split_regret"] == 0.0
    assert bad["coarse_bins"] == ["bin_count_gap", "bin_occupancy_excess"]
    assert bad["thin_sample"] == ["bin_occupancy_excess"]
    assert {"leaf_count_gap", "leaf_value_gap"} <= set(bad["half"])
    assert "leaf_value_gap" in bad["regrad"]
    assert r["regrad"]["leaf_count_gap"] == 0.0
    assert {"leaf_count_gap", "class_order_gap"} <= set(bad["bundle_order"])


def test_histogram_is_exact_over_columns_of_unlike_bin_counts():
    """The reference's one-hot contraction over a layout of 255-, 3- and
    2-bin columns against a float64 bincount."""
    import jax.numpy as jnp
    from benchmark import reference_multiclass as R
    rng = np.random.default_rng(0)
    nb = [255, 3, 2, 200, 2]
    bounds = [list(range(n - 1)) + [np.inf] for n in nb]
    layout = R.Layout(bounds)
    assert layout.used == sum(nb) and layout.width % R.LANES == 0
    n, f, s, p = R.SUB * 2, len(nb), 4, 7
    bins = np.stack([rng.integers(0, k, n) for k in nb]).astype(np.uint8)
    g = rng.standard_normal((2, n)).astype(np.float32)
    slot = rng.integers(-1, s, n).astype(np.int32)
    acc = jnp.zeros((layout.width, s * p), jnp.float32)
    _, _, acc, _ = R._level_block(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(slot),
        jnp.full((n,), -1, jnp.int32), jnp.zeros((1, f + 5), jnp.float32),
        layout.expand, layout.col_bin, acc, jnp.zeros_like(acc),
        n_slots=s, route=False)
    h = layout.dense(acc, (s, p))                     # [S, P, F, NBINS]
    got = h[:, 0] + h[:, 1] + h[:, 2]
    want = np.zeros((s, f, R.NBINS))
    cnt = np.zeros((s, f, R.NBINS))
    live = slot >= 0
    for j in range(f):
        np.add.at(want, (slot[live], j, bins[j, live]),
                  g[0, live].astype(np.float64))
        np.add.at(cnt, (slot[live], j, bins[j, live]), 1.0)
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))
    assert np.array_equal(h[:, -1], cnt)


# ---- the ten readers -------------------------------------------------------
US = 1000
TREE = "jit(step)/while/body/class_tree/jit(grow_tree_depthwise)/"
PATHS = [
    "jit(step)/front/grad/reduce_max:reduce",
    TREE + "front/quant/floor:floor",
    TREE + "front/hist0/hist_leaf_q8:custom-call",
    TREE + "level_s32/while/body/split_search/cumsum:cumsum",
    TREE + "level_s32/while/body/route_hist/route/route_level:custom-call",
    TREE + "level_s32/while/body/route_hist/hist/hist_leaf_q8:custom-call",
    TREE + "level_s128/while/body/route_hist/hist/hist_leaf_q8:custom-call",
    "jit(step)/while/body/score_update/take_small:custom-call",
]
KERNEL = "%hist_leaf_q8.{} = (s32[4096,96]{{1,0}}) custom-call(%p.1)"
# (name, start, duration) in us, one iteration of 1,000 us; path by position
OPS = [("%fusion.1 = f32[7,8] fusion()", 0, 10),
       ("%fusion.2 = s8[8] fusion()", 10, 20),
       (KERNEL.format(30), 30, 100),
       ("%fusion.3 = f32[8] fusion()", 130, 40),
       ("%route_level.22 = s32[8] custom-call()", 170, 50),
       (KERNEL.format(31), 220, 330),
       (KERNEL.format(32), 550, 400),
       ("%take_small.8 = f32[8] custom-call()", 950, 50)]
HIST_PATH = {"type": "hist_path", "level_kernel": "hist_leaf_q8",
             "feature_groups": 2, "route": "pallas", "front": "unfused",
             "bins_T_cached": True}
MULTICLASS = {"type": "multiclass", "num_class": 7, "trees_per_iter": 7,
              "class_loop": "scan", "score_layout": "class_major_in_step",
              "labels_arg": True}
EFB_PLAN = {"type": "efb_plan", "columns_in": 54, "columns_out": 14,
            "bundles": 2, "largest_bundle_bins": 81}
EVENTS = [HIST_PATH, MULTICLASS, EFB_PLAN]
NEW = ["multiclass.trees_per_iter", "multiclass.class_tree_ms",
       "multiclass.level_passes_per_iter",
       "multiclass.softmax_grad_ms_per_iter",
       "multiclass.split_search_ms_per_iter", "multiclass.route_ms_per_iter",
       "multiclass.score_update_ms_per_iter", "multiclass.hist_roofline",
       "efb.columns_out", "efb.plan_s"]


def _ctx(cell=CELL, paths=PATHS, ops=OPS, events=EVENTS):
    compact = {"chips": [{"modules": [["jit_step(1)", 0, 1000 * US]],
                          "ops": [[n, s * US, d * US] for n, s, d in ops],
                          "op_paths": list(range(len(ops)))}],
               "paths": list(paths), "host": []}
    view = scopes.ScopeView(compact, 1, 1e-3)
    cell = harness.load_cell(cell)
    return types.SimpleNamespace(
        trace=view, scope_view=view, obs_events=list(events), cell=cell,
        n_train=int(cell["cfg"]["train_rows"]), work=work,
        channels=int(cell["cfg"]["grad_channels"]),
        construct_phases={"efb_plan_s": 3.25},
        peaks=peaks.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("name,want", [
    ("multiclass.trees_per_iter", 7),
    ("multiclass.class_tree_ms", 0.940 / 7),      # 10 to 950 us, over K
    ("multiclass.level_passes_per_iter", 2),      # the root pass not counted
    ("multiclass.softmax_grad_ms_per_iter", 0.010),
    ("multiclass.split_search_ms_per_iter", 0.040),
    ("multiclass.route_ms_per_iter", 0.050),
    ("multiclass.score_update_ms_per_iter", 0.050),
    ("efb.columns_out", 14),
    ("efb.plan_s", 3.25),
])
def test_readers(name, want):
    assert harness.read_metric(name, _ctx()) == pytest.approx(want)


def test_roofline_is_k_times_the_least_time_over_kernel_time():
    ctx = _ctx()
    cfg = ctx.cell["cfg"]
    wk = work.hist_work(ctx.n_train, cfg["num_features"],
                        cfg["params"]["num_leaves"], ctx.channels)
    least, bound = work.least_seconds(wk, ctx.peaks)
    assert bound == "bytes"
    got = harness.read_metric("multiclass.hist_roofline", ctx)
    # the five Mosaic calls: 100 + 50 + 330 + 400 + 50 us
    assert got == pytest.approx(100.0 * 7 * least / 0.930e-3)


@pytest.mark.parametrize("name", NEW)
def test_readers_are_silent_in_the_other_cells(name):
    """The same trace and events under a configuration of one tree an
    iteration whose plan bundled nothing."""
    events = [HIST_PATH, dict(EFB_PLAN, columns_out=28, bundles=0,
                              largest_bundle_bins=0)]
    for other in ("higgs-binary.train", "epsilon-binary.train"):
        assert harness.read_metric(name, _ctx(other, events=events)) is None


def test_a_program_without_the_new_names_reads_what_it_has():
    """The parent of PR 34: no ``multiclass`` or ``efb_plan`` event, no
    ``class_tree`` scope, the class trees unrolled in the step. The level
    passes and the scopes it always had are read; the rest is silent."""
    paths = [p.replace("while/body/class_tree/", "") for p in PATHS]
    ctx = _ctx(paths=paths, events=[HIST_PATH])
    read = {n: harness.read_metric(n, ctx) for n in NEW}
    assert read["multiclass.level_passes_per_iter"] == 2
    assert read["multiclass.split_search_ms_per_iter"] == pytest.approx(0.04)
    assert read["multiclass.hist_roofline"] is not None
    for name in ("multiclass.trees_per_iter", "multiclass.class_tree_ms",
                 "efb.columns_out", "efb.plan_s"):
        assert read[name] is None


def test_the_cell_lists_its_metrics():
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"kernels.hist_roofline", "step.mfu", "device.peak_hbm_gb"} <= names
    assert not names & {"grower.narrow_ms_per_iter", "route.device_ms_per_iter",
                        "split.device_ms_per_iter"}
    for other in ("higgs-binary.train", "epsilon-binary.train",
                  "higgs-binary-dp4.train"):
        assert not set(NEW) & {m["name"] for m in
                               harness.load_cell(other)["per_layer"]}
