"""The readers of PR 25 against a recorded trace.

``fixtures/scopes_train_valid.json.gz`` is ``higgs-binary.train-valid``'s
code path on the v5e at a small size (40 k rows, 5 k validation rows, 127
leaves, four iterations) in ``scopes.load``'s form, and
``scopes_train_valid.expected.json`` what ``scopes.report`` read from it when
it was recorded (``python3 -m benchmark.scopes --record`` after a traced
run). Each reader has to give that number again, nothing on a trace whose
program names nothing (what the parent commit gives), and the scopes have to
tile the step.
"""
import copy
import json
import re
import types

import pytest

from benchmark import harness, scopes, trace

TRACE_READERS = {
    "grower.level_passes_per_iter": "level_passes_per_iter",
    "grower.narrow_ms_per_iter": "narrow_ms",
    "grower.full_ms_per_iter": "full_ms",
    "kernels.front_ms_per_iter": "front_kernel_ms",
    "split.device_ms_per_iter": "split_search_ms",
    "step.self_ms_per_iter": "step_self_ms",
    "valid_score.scoped_ms_per_iter": "valid_score_scoped_ms",
    "valid_score.host_ms_per_iter": "valid_score_host_ms",
}
EVENTS = [
    {"type": "aot_prewarm", "phase": "started"},
    {"type": "aot_prewarm", "phase": "adopted", "duration_s": 1.25},
    {"type": "span", "name": "dataset_construct", "duration_s": 9.0},
    {"type": "span", "name": "train_setup", "duration_s": 2.5},
    # warm-up iterations 1 and 2, window iterations 3 to 5
    {"type": "program_load", "span": "valid_score", "iteration": 2,
     "duration_s": 0.03},
    {"type": "program_load", "span": "valid_score", "iteration": 3,
     "duration_s": 0.03},
    {"type": "program_load", "span": "metric", "iteration": 3,
     "duration_s": 0.01},
    {"type": "program_load", "span": "valid_score", "iteration": 5,
     "duration_s": 0.03},
    {"type": "program_load", "span": "none", "duration_s": 2.0},
]


@pytest.fixture(scope="module")
def recorded():
    with open(scopes.EXPECTED) as fh:
        return trace.load_compact(scopes.FIXTURE), json.load(fh)


def _ctx(compact, events=EVENTS):
    view = scopes.ScopeView(compact, compact["n_iters"], compact["window_s"])
    return types.SimpleNamespace(
        trace=view, scope_view=view, obs_events=events,
        cell={"cfg": {"params": {"num_leaves": 127}}},
        window=types.SimpleNamespace(warmup=2, window_iters=3),
        spans={"ingest.construct_s": 11.0},
        construct_phases={"init_s": 0.5, "find_bins_s": 4.25, "efb_plan_s": 4.0,
                          "stream_busy": {"encode_s": 3.9}, "encoder": "native",
                          "overlap_efficiency": 0.7, "stream_s": 2.0,
                          "device_put_s": 0.0})


def _unnamed(compact):
    """The same trace as a program without PR 25 would leave it: kernels
    named after the enclosing function, no scope in any path, no span of the
    package on the host."""
    c = copy.deepcopy(compact)
    c["paths"] = [re.sub(r"/(front|level_s\d+|split_search|apply_level|"
                         r"route_hist|leaf_renew|score_update|valid_score|"
                         r"hist_level_q8|grad_quant_hist0)(?=[/:])", "", p)
                  for p in c["paths"]]
    for chip in c["chips"]:
        for op in chip["ops"]:
            op[0] = re.sub(r"^%(hist_level_q8|grad_quant_hist0)\.", "%body.",
                           op[0])
    known = scopes.PARENT_SPANS + scopes.LEAF_SPANS
    c["host"] = [h for h in c["host"] if h[0] not in known]
    return c


@pytest.mark.parametrize("name", sorted(TRACE_READERS))
def test_reader_reads_what_was_recorded(recorded, name):
    compact, want = recorded
    got = harness.read_metric(name, _ctx(compact))
    assert got is not None
    assert got == pytest.approx(want[TRACE_READERS[name]], rel=1e-12)


@pytest.mark.parametrize("name", sorted(TRACE_READERS) + [
    "loop.gap_in_dispatch_ms_per_iter", "loop.gap_unnamed_ms_per_iter"])
def test_reader_finds_nothing_where_nothing_is_named(recorded, name):
    compact, _ = recorded
    assert harness.read_metric(name, _ctx(_unnamed(compact))) is None


def test_scopes_tile_the_step(recorded):
    compact, want = recorded
    view = _ctx(compact).scope_view
    ms = view.per_iter_ms
    parts = [view.named_s(s) for s in scopes.STEP_SCOPES]
    parts += [view.level_s(lambda w, w0=w0: w == w0) for w0 in view.widths()]
    assert view.widths() == [32, 63]
    assert all(p is not None and p > 0 for p in parts)
    assert ms(view.step_self_s()) + sum(map(ms, parts)) == pytest.approx(
        ms(view.module_s(trace.STEP_MODULE)), rel=1e-9)
    assert want["step_self_ms"] < 0.08 * want["step_ms"]
    # the groups hold their stages, and the passes are the kernel's events
    assert want["route_hist_ms"] <= want["narrow_ms"] + want["full_ms"]
    assert want["level_passes_per_iter"] >= 6


def test_gaps_split_by_self_time(recorded):
    compact, want = recorded
    ctx = _ctx(compact)
    gaps = ctx.scope_view.gap_split()
    assert set(gaps) <= set(scopes.LEAF_SPANS) | {scopes.UNNAMED}
    for name, key in (("loop.gap_in_dispatch_ms_per_iter", "step_dispatch"),
                      ("loop.gap_unnamed_ms_per_iter", scopes.UNNAMED)):
        assert harness.read_metric(name, ctx) == pytest.approx(
            want["gaps_ms"][key], rel=1e-12)
    # no more idle time is given out than the device was idle
    idle = ctx.trace.window_s - ctx.trace.busy_s
    assert 0 < sum(gaps.values()) <= idle


def test_gap_goes_to_the_innermost_span():
    """A hand-made trace: the device idles from 100 to 200 us while the host
    is in boosting (50-400), of which step_dispatch covers 120-180."""
    us = 1000
    compact = {"chips": [{"modules": [["jit_step(1)", 0, 400 * us]],
                          "ops": [["%a = x", 0, 100 * us],
                                  ["%b = x", 200 * us, 200 * us]],
                          "op_paths": [0, 0]}],
               "paths": [""],
               "host": [["train_iter", 40 * us, 400 * us],
                        ["boosting", 50 * us, 350 * us],
                        ["step_dispatch", 120 * us, 60 * us]]}
    gaps = scopes.ScopeView(compact, 1, 400e-6).gap_split()
    assert gaps == {"step_dispatch": pytest.approx(60e-6),
                    scopes.UNNAMED: pytest.approx(40e-6)}


def test_event_readers(recorded):
    compact, _ = recorded
    ctx = _ctx(compact)
    assert harness.read_metric("valid_score.programs_loaded_per_iter",
                               ctx) == pytest.approx(2 / 3)
    assert harness.read_metric("prewarm.barrier_s", ctx) == 1.25
    assert harness.read_metric("loop.train_setup_s", ctx) == 2.5
    assert harness.read_metric("ingest.self_s", ctx) == pytest.approx(0.25)
    assert harness.read_metric("ingest.init_s", ctx) == 0.5
    assert harness.read_metric("ingest.efb_plan_s", ctx) == 4.0
    bare = _ctx(compact, events=[])
    bare.construct_phases = {}
    for name in ("valid_score.programs_loaded_per_iter", "prewarm.barrier_s",
                 "loop.train_setup_s", "ingest.self_s", "ingest.init_s",
                 "ingest.efb_plan_s"):
        assert harness.read_metric(name, bare) is None


def test_every_new_metric_has_a_reader_and_an_entry():
    import os
    bench = harness._json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = {m["name"] for m in bench["per_layer"]}
    new = set(TRACE_READERS) | {
        "loop.gap_in_dispatch_ms_per_iter", "loop.gap_unnamed_ms_per_iter",
        "valid_score.programs_loaded_per_iter", "prewarm.barrier_s",
        "loop.train_setup_s", "ingest.self_s", "ingest.init_s",
        "ingest.efb_plan_s"}
    assert new <= names
    for name in new:
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           name + ".py"))


def test_instruction_names():
    assert scopes.instruction(
        "%hist_level_q8.16 = (s32[1792,96]{1,0:T(8,128)S(1)}) "
        "custom-call(%x)") == "hist_level_q8"
    assert scopes.instruction("%copy-done.175 = pred[255] copy-done(%c)") \
        == "copy-done"
    assert scopes.instruction("%broadcast_multiply_fusion = f32[2] fusion("
                              ")") == "broadcast_multiply_fusion"
