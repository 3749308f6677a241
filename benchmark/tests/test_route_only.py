"""``grower.route_only_passes_per_iter`` against hand-made traces: launches
of ``route_level`` under the scope ``route_only`` an iteration, not those
under ``route_hist`` (the router ahead of the grouped kernel at every other
level), and nothing from a program whose last level still builds its
histograms (the parent of the route-only level)."""
import types

import pytest

from benchmark import harness, scopes

NAME = "grower.route_only_passes_per_iter"
US = 1000
GROW = "jit(step)/jit(grow_tree_depthwise)/"
LAST = GROW + "level_s127/while/body/cond/"
FUSED = "%hist_level_q8.{} = (s32[1792,96]{{1,0}}) custom-call(%p.1)"
GROUPED = "%hist_leaf_q8.{} = (s32[4096,96]{{1,0}}) custom-call(%p.1)"
ROUTE = "%route_level.{} = (s32[8], s32[8]) custom-call(%p.2)"


def _ctx(ops, n_iters):
    """ops: (name, path) in time order, 10 us each."""
    paths = sorted({p for _, p in ops})
    compact = {"chips": [{"modules": [["jit_step(1)", 0,
                                       10 * US * len(ops) + US]],
                          "ops": [[n, (10 * i + 1) * US, 10 * US]
                                  for i, (n, _) in enumerate(ops)],
                          "op_paths": [paths.index(p) for _, p in ops]}],
               "paths": paths, "host": []}
    view = scopes.ScopeView(compact, n_iters, 1e-3)
    return types.SimpleNamespace(trace=view, scope_view=view, obs_events=[])


def _fused_tree(k, last_level=True):
    """One tree on the fused path: seven levels that build histograms, and
    the last one route only (or, before it, an eighth fused pass)."""
    ops = [(FUSED.format(k * 10 + j),
            GROW + f"level_s{32 if j < 6 else 127}/while/body/"
            + ("" if j < 6 else "cond/branch_0_fun/")
            + "route_hist/hist_level_q8:custom-call") for j in range(7)]
    if last_level:
        ops.append((ROUTE.format(k), LAST + "branch_1_fun/route_only/route/"
                    "route_level/pallas_call:custom-call"))
    else:
        ops.append((FUSED.format(k * 10 + 7), GROW + "level_s127/while/body/"
                    "route_hist/hist_level_q8:custom-call"))
    return ops


def _grouped_tree(k):
    """One class tree on the grouped path: a route pass of its own ahead of
    each level's kernel, the last level's router alone."""
    ops = []
    for j in range(7):
        body = GROW + f"level_s{32 if j < 6 else 128}/while/body/" + (
            "" if j < 6 else "cond/branch_0_fun/") + "route_hist/"
        ops += [(ROUTE.format(k * 10 + j),
                 body + "route/route_level/pallas_call:custom-call"),
                (GROUPED.format(k * 10 + j),
                 body + "hist/hist_leaf_q8:custom-call")]
    return ops + [(ROUTE.format(k * 10 + 7), LAST + "branch_1_fun/route_only/"
                   "route/route_level/pallas_call:custom-call")]


@pytest.mark.parametrize("ops,n_iters,want", [
    (_fused_tree(0) + _fused_tree(1), 2, 1.0),
    ([op for k in range(7) for op in _grouped_tree(k)], 1, 7.0),
], ids=["one-tree-an-iteration", "seven-class-trees"])
def test_reads_one_route_only_pass_a_tree(ops, n_iters, want):
    assert harness.read_metric(NAME, _ctx(ops, n_iters)) == pytest.approx(
        want)


def test_silent_where_every_level_builds_histograms():
    """The parent: the eighth level is one more fused pass, and the grouped
    path's routers all stand under ``route_hist``."""
    grouped = [op for op in _grouped_tree(0)
               if "route_only" not in op[1]]
    for ops in (_fused_tree(0, last_level=False), grouped):
        assert harness.read_metric(NAME, _ctx(ops, 1)) is None


def test_silent_without_a_trace():
    ctx = types.SimpleNamespace(trace=None, obs_events=[])
    assert harness.read_metric(NAME, ctx) is None


def test_every_cell_lists_it():
    for name in ("higgs-binary.train-valid", "higgs-binary.train",
                 "higgs-l2.train", "epsilon-binary.train",
                 "higgs-binary-dp4.train", "covertype-multiclass-d8.train"):
        assert NAME in {m["name"] for m in
                        harness.load_cell(name)["per_layer"]}
