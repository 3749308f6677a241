"""Pipelined cold-start (ingest.py + prewarm.py): bit-determinism of the
chunked threaded encode/upload pipeline, AOT-prewarm adoption (zero extra
lowerings at first dispatch), phase accounting, and the telemetry surface."""
import numpy as np
import pytest

import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu import ingest, obs, prewarm

RNG = np.random.RandomState(7)
N, F = 2000, 9
X = RNG.rand(N, F).astype(np.float32)
# a categorical-ish low-cardinality column + some NaNs exercise the mapper
# paths inside the threaded encoders (label derived BEFORE the NaN injection)
X[:, 3] = RNG.randint(0, 5, N)
Y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * RNG.randn(N)).astype(np.float32)
X[RNG.rand(N, F) < 0.02] = np.nan

BASE = {"objective": "regression", "num_leaves": 15, "verbose": -1,
        "min_data_in_leaf": 5}


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    obs.reset()
    obs.configure(enabled=False, metrics_out="")
    # the row gate exists to spare real construct-only datasets a wasted
    # background compile; these tests exercise the machinery at toy scale
    monkeypatch.setattr(prewarm, "MIN_PREWARM_ROWS", 0)
    yield
    obs.reset()
    obs.configure(enabled=False, metrics_out="")


def _dataset(**extra):
    return lgb.Dataset(X.copy(), label=Y.copy(), params={**BASE, **extra})


def _train(rounds=3, **extra):
    params = {**BASE, **extra}
    return lgb.train(params, _dataset(**extra), num_boost_round=rounds)


def _tree_sig(bst):
    """Model text minus the [param: value] dump (prewarm/encode_threads are
    reporting knobs; the trees themselves must be bit-identical)."""
    return "\n".join(l for l in bst.model_to_string().splitlines()
                     if not l.startswith("["))


# ---- bit-determinism of the binned matrix -----------------------------------

def test_bins_identical_across_encode_threads():
    # prewarm=0: construct-only datasets must not each burn a compile thread
    ref = np.asarray(_dataset(ingest_chunk_rows=512, encode_threads=1,
                              prewarm=0).construct().bins)
    for threads in (2, 4):
        got = np.asarray(_dataset(ingest_chunk_rows=512, prewarm=0,
                                  encode_threads=threads).construct().bins)
        assert np.array_equal(ref, got), f"encode_threads={threads} changed bins"


def test_bins_identical_chunked_vs_one_shot():
    one = np.asarray(_dataset(ingest_chunk_rows=10**9,
                              prewarm=0).construct().bins)
    for rows in (256, 1000, N):
        got = np.asarray(_dataset(ingest_chunk_rows=rows, prewarm=0,
                                  encode_threads=4).construct().bins)
        assert np.array_equal(one, got), f"chunk_rows={rows} changed bins"


def test_trees_identical_threads_chunks_prewarm():
    ref = _tree_sig(_train(prewarm=0, ingest_chunk_rows=10**9))
    for extra in ({"prewarm": 1, "ingest_chunk_rows": 10**9},
                  {"prewarm": 0, "ingest_chunk_rows": 700,
                   "encode_threads": 4},
                  {"prewarm": 1, "ingest_chunk_rows": 700,
                   "encode_threads": 4}):
        assert _tree_sig(_train(**extra)) == ref, \
            f"{extra} changed the grown trees"


# ---- AOT prewarm adoption ----------------------------------------------------

def test_prewarm_adopted_and_wrapper_never_built():
    bst = _train(prewarm=1)
    g = bst._gbdt
    assert g._aot_dispatches >= 1, "prewarmed executable was never dispatched"
    # the jit wrapper would only exist if some dispatch fell back to it —
    # its absence IS the zero-extra-compile proof for the whole run
    assert getattr(g, "_step_auto", None) is None
    assert g._prewarm_handle is None   # consumed at first dispatch


def test_prewarm_off_uses_jit_wrapper():
    bst = _train(prewarm=0)
    g = bst._gbdt
    assert g._aot_dispatches == 0
    fn = getattr(g, "_step_auto", None)
    assert fn is not None and int(fn._cache_size()) == 1


def test_prewarm_zero_extra_lowerings():
    """The prewarm MOVES the fused-step lowering off the critical path: the
    training thread must lower exactly one program fewer (the step, which
    the background thread lowers instead — jax counts lowerings per thread)
    and nothing else may change — zero retraces added."""
    _train(rounds=2, prewarm=0)   # warm shared module-level jits (_set_rows)
    with jtu.count_jit_and_pmap_lowerings() as off:
        _train(rounds=2, prewarm=0)
    with jtu.count_jit_and_pmap_lowerings() as on:
        _train(rounds=2, prewarm=1)
    assert on() == off() - 1, (f"prewarm changed the training thread's "
                               f"lowering count: {off()} -> {on()}")


def test_prewarm_spec_mismatch_falls_back():
    """A dataset constructed with DIFFERENT params than the trainer prewarms
    the wrong program; adoption must miss cleanly and training still work."""
    obs.configure(enabled=True)
    ds = lgb.Dataset(X.copy(), label=Y.copy(),
                     params={**BASE, "prewarm": 1})
    ds.construct()   # prewarm compiles for objective=regression
    # telemetry=1: engine.train re-applies the config's telemetry knob and
    # would otherwise switch off the events this test asserts on
    params = {**BASE, "objective": "regression_l1", "prewarm": 1,
              "telemetry": 1}
    bst = lgb.train(params, ds, num_boost_round=2)
    g = bst._gbdt
    assert g._aot_dispatches == 0
    assert getattr(g, "_step_auto", None) is not None
    assert any(e["type"] == "aot_prewarm" and e.get("phase") == "miss"
               for e in obs.EVENTS.snapshot())


# ---- phase accounting --------------------------------------------------------

def test_construct_phases_are_disjoint_with_busy_breakdown():
    ds = _dataset(ingest_chunk_rows=512, encode_threads=2,
                  prewarm=0).construct()
    ph = ds.construct_phases
    for key in ("find_bins_s", "efb_plan_s", "stream_s", "device_put_s",
                "stream_busy", "overlap_efficiency"):
        assert key in ph, f"missing phase key {key}: {ph}"
    busy = ph["stream_busy"]
    assert set(busy) >= {"encode_s", "h2d_s", "commit_s", "encode_threads",
                         "chunks"}
    assert busy["chunks"] == -(-N // 512)
    assert 0.0 <= ph["overlap_efficiency"] <= 1.0
    # the old double-count bug: per-stage busy times are NOT wall segments
    # and must no longer appear as top-level phase keys
    assert "encode_s" not in ph and "upload_s" not in ph
    stats = ingest.last_stats()
    assert stats["chunks"] == busy["chunks"]
    assert stats["encode_threads"] == busy["encode_threads"]


def test_overlap_efficiency_math():
    assert ingest.overlap_efficiency((2.0, 1.0, 1.0), 4.0) == 0.0  # serial
    assert ingest.overlap_efficiency((2.0, 1.0, 1.0), 2.0) == 1.0  # perfect
    assert ingest.overlap_efficiency((2.0, 1.0, 1.0), 3.0) == 0.5
    assert ingest.overlap_efficiency((5.0,), 5.0) == 1.0   # nothing to hide
    assert ingest.overlap_efficiency((1.0, 1.0), 9.0) == 0.0   # clamped


# ---- telemetry surface -------------------------------------------------------

def test_ingest_and_prewarm_events_emitted():
    # telemetry as a param: engine.train applies the config's telemetry knob
    _train(prewarm=1, ingest_chunk_rows=512, rounds=2, telemetry=1)
    ev = obs.EVENTS.snapshot()
    chunks = [e for e in ev if e["type"] == "ingest_chunk"]
    assert len(chunks) == -(-N // 512)
    for e in chunks:
        assert e["rows"] > 0 and e["encode_s"] >= 0 and e["depth"] >= 0
    phases = [e.get("phase") for e in ev if e["type"] == "aot_prewarm"]
    assert "started" in phases and "compiled" in phases \
        and "adopted" in phases, phases
    cold = [e for e in ev if e["type"] == "compile"
            and e.get("what") == "fused_step_aot"]
    assert len(cold) == 1 and cold[0]["key"] == "cold"
    depth = obs.METRICS.to_json().get("ingest_pipeline_depth")
    assert depth is not None


def test_ingest_chunk_says_bytes_and_thread_and_no_shard_unsharded():
    obs.configure(enabled=True)
    ds = _dataset(ingest_chunk_rows=512, prewarm=0).construct()
    chunks = [e for e in obs.EVENTS.snapshot() if e["type"] == "ingest_chunk"]
    assert len(chunks) == -(-N // 512)
    width = int(ds.bins.shape[1])
    for e in chunks:
        assert e["bytes"] == e["rows"] * width
        assert e["thread"] == "ingest-commit" and "shard" not in e
    assert not any(e["type"] == "mesh_shard_commit"
                   for e in obs.EVENTS.snapshot())


WORKER_SPANS = ("prewarm_trainer", "prewarm_build", "prewarm_lower",
                "prewarm_compile")


def test_prewarm_workers_life_is_spans():
    """The worker's four stages nest under prewarm_worker on its own thread,
    in order, cover it, and the step's program_load falls into
    prewarm_compile; prewarm_worker's parent is the span that was open where
    the thread was started."""
    _train(prewarm=1, rounds=2, telemetry=1)
    ev = obs.EVENTS.snapshot()
    spans = {e["name"]: e for e in ev if e["type"] == "span"
             and e["name"].startswith("prewarm_")}
    assert set(spans) == set(WORKER_SPANS) | {"prewarm_worker"}
    worker = spans["prewarm_worker"]
    assert worker["parent"] == "dataset_construct"
    assert all(e["thread"] == "aot-prewarm" for e in spans.values())
    stages = [spans[n] for n in WORKER_SPANS]
    assert all(e["parent"] == "prewarm_worker" for e in stages)
    starts = [e["start_ts"] for e in stages]
    assert starts == sorted(starts) and starts[0] >= worker["start_ts"]
    assert all(e["ts"] <= worker["ts"] for e in stages)
    assert sum(e["duration_s"] for e in stages) <= worker["duration_s"]
    step = [e for e in ev if e["type"] == "program_load"
            and e["thread"] == "aot-prewarm" and e["program"] == "step"]
    assert len(step) == 1 and step[0]["span"] == "prewarm_compile"
    assert step[0]["trace_s"] > 0 and step[0]["lower_s"] > 0
    assert not any(e["type"] == "program_load" and e["span"] == "none"
                   and e["thread"] == "aot-prewarm" for e in ev)
    # the accepted events are as they were
    cold = [e for e in ev if e["type"] == "compile"
            and e.get("what") == "fused_step_aot"]
    assert len(cold) == 1 and cold[0]["duration_s"] >= (
        spans["prewarm_lower"]["duration_s"]
        + spans["prewarm_compile"]["duration_s"]) * 0.99


@pytest.mark.parametrize("telemetry", [1, 0])
def test_step_memory_once_per_aot_compile_never_when_off(telemetry):
    bst = _train(prewarm=1, rounds=2, telemetry=telemetry)
    mem = [e for e in obs.EVENTS.snapshot() if e["type"] == "step_memory"]
    if not telemetry:
        assert len(obs.EVENTS) == 0
        return
    assert len(mem) == 1
    m = mem[0]
    assert m["what"] == "fused_step_aot" and m["devices"] == 1
    assert m["argument_bytes"] >= N * F and m["temp_bytes"] > 0
    assert m["output_bytes"] > 0 and m["alias_bytes"] >= 0
    # a second AOT compile of the same step (bench.py's warm one) is a
    # second event; the step dispatched by jit emits none
    prewarm.aot_compile_step(bst._gbdt, tag="warm")
    _train(prewarm=0, rounds=1, telemetry=1)
    assert sum(e["type"] == "step_memory"
               for e in obs.EVENTS.snapshot()) == 2


def test_pipeline_error_propagates():
    bad = X.copy()
    ds = lgb.Dataset(bad, label=Y.copy(),
                     params={**BASE, "ingest_chunk_rows": 512, "prewarm": 0})
    # sabotage the mapper list after find_bins would have produced it: the
    # encode stage must surface its failure on the caller's thread
    import lightgbm_tpu.ingest as ing
    with pytest.raises(ValueError, match="boom"):
        def explode(*a, **k):
            raise ValueError("boom")
        orig = ing.bin_data
        ing.bin_data = explode
        try:
            ds.construct()
        finally:
            ing.bin_data = orig
