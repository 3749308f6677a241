"""The four readers ``higgs-binary-dp4.train`` brings, against a hand-made
trace of four device planes and planted events: each reads what the program
names (scopes ``hist_allreduce`` and ``bins_T``, events ``hist_path`` and
``shard_plan``), averages device time over the chips, and says nothing on a
one-chip trace or of a program that names none of them (the parent commit).
The cell's own run at a small size over four virtual devices is in
``tests/test_dp4_cell.py``, whose ``conftest.py`` gives the CPU the devices.
"""
import types

import pytest

from benchmark import harness, scopes

CELL = "higgs-binary-dp4.train"
US = 1_000          # ns
GROW = "jit(step_dp)/shard_map/jit(grow_tree_depthwise)/"
PATHS = [GROW + "bins_T/transpose:copy",
         GROW + "front/hist0/pallas_call:custom-call",
         GROW + "front/hist0/hist_allreduce/psum:all-reduce",
         GROW + "level_s32/while/body/route_hist/pallas_call:custom-call",
         GROW + "level_s32/while/body/route_hist/hist_allreduce/psum"
                ":all-reduce",
         GROW + "leaf_renew/hist_allreduce/psum:all-reduce",
         "jit(step_dp)/score_update/take_small:custom-call"]
NAMELESS = [p.replace("hist_allreduce/", "").replace("bins_T/", "")
            for p in PATHS]


def _chip(lag):
    """One iteration's ops on a chip that waits ``lag`` us in each of its
    three reductions for the slowest shard."""
    t, ops = 0, []
    for path, dur in [(0, 40), (1, 100), (2, 5 + lag), (3, 300),
                      (4, 20 + lag), (5, 5 + lag), (6, 30)]:
        ops.append((path, t, dur))
        t += dur
    return ops, t


def _ctx(chips=4, paths=PATHS, events=(), iters=2):
    planes = []
    for k in range(chips):
        ops, per_iter = _chip(lag=10 * k)
        rows = [(p, (s + i * per_iter) * US, d * US)
                for i in range(iters) for p, s, d in ops]
        planes.append({
            "modules": [["jit_step_dp(1)", 0, iters * per_iter * US]],
            "ops": [[f"%op.{j} = f32[8] op()", s, d]
                    for j, (_, s, d) in enumerate(rows)],
            "op_paths": [p for p, _, _ in rows]})
    view = scopes.ScopeView({"chips": planes, "paths": list(paths),
                             "host": []}, iters, 1e-3)
    return types.SimpleNamespace(trace=view, scope_view=view,
                                 obs_events=list(events),
                                 cell=harness.load_cell(CELL))


HIST_PATH = {"type": "hist_path", "level_kernel": "hist_level_q8",
             "feature_groups": 1, "route": "fused", "front": "unfused",
             "bins_T_cached": False, "decode_leaves": [32, 255]}
EVENTS = [
    dict(HIST_PATH, allreduce_bytes_per_iter=9_615_348),
    {"type": "shard_plan", "num_shards": 4, "rows_per_shard": 36_750_000,
     "pad_rows": 0, "feature_shards": 1},
]


def test_the_cell_lists_the_four_readers_and_four_chips():
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 4 and cell["cfg"]["params"]["num_shards"] == 4
    assert cell["cfg"]["train_rows"] % 4 == 0
    names = {m["name"] for m in cell["per_layer"]}
    assert {"collective.exposed_ms_per_iter", "collective.reduced_mb_per_iter",
            "shard.transpose_ms_per_iter", "shard.rows_per_chip",
            "step.device_ms_per_iter", "device.idle_share"} <= names
    assert not any(n.startswith(("collective.", "shard."))
                   for n in {m["name"] for m in harness.load_cell(
                       "higgs-binary.train")["per_layer"]})


@pytest.mark.parametrize("name,want", [
    # three reductions a tree of 30 us and three lags of 0, 10, 20, 30 us:
    # the mean over the chips of 30 + 3 * lag
    ("collective.exposed_ms_per_iter", (30 + 3 * 15) / 1e3),
    ("collective.reduced_mb_per_iter", 9.615348),
    ("shard.transpose_ms_per_iter", 40 / 1e3),
    ("shard.rows_per_chip", 36_750_000),
])
def test_readers_on_four_device_planes(name, want):
    assert harness.read_metric(name, _ctx(events=EVENTS)) == \
        pytest.approx(want)


def test_a_transpose_the_compiler_took_away_reads_zero():
    """The step says it is handed no cached ``bins_T`` and no op ran under
    the scope (a bitcast on the v5e): 0 ms, not silence."""
    paths = [p.replace("bins_T/", "") for p in PATHS]
    assert harness.read_metric("shard.transpose_ms_per_iter",
                               _ctx(paths=paths, events=EVENTS)) == 0.0
    cached = [dict(EVENTS[0], bins_T_cached=True)]
    assert harness.read_metric("shard.transpose_ms_per_iter",
                               _ctx(paths=paths, events=cached)) is None


def test_rows_per_chip_shows_a_run_that_fell_back_to_one_chip():
    events = EVENTS + [{"type": "shard_plan", "num_shards": 1,
                        "rows_per_shard": 147_000_000, "pad_rows": 0,
                        "feature_shards": 1}]
    assert harness.read_metric("shard.rows_per_chip",
                               _ctx(events=events)) == 147_000_000


@pytest.mark.parametrize("name", [
    "collective.exposed_ms_per_iter", "collective.reduced_mb_per_iter",
    "shard.transpose_ms_per_iter", "shard.rows_per_chip"])
@pytest.mark.parametrize("ctx", [
    # one chip: no reduction and no transpose is traced, the step's event
    # has no bytes to count
    lambda: _ctx(chips=1, paths=NAMELESS, events=[HIST_PATH]),
    # the parent's program on four chips: the same ops under no such scope,
    # no such event
    lambda: _ctx(paths=NAMELESS, events=[HIST_PATH]),
    # an untraced run
    lambda: types.SimpleNamespace(trace=None, obs_events=[]),
], ids=["one_chip", "parent", "untraced"])
def test_silence_where_nothing_is_named(name, ctx):
    assert harness.read_metric(name, ctx()) is None
