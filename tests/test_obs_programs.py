"""Set-up named from inside (PR 36): ``program_load`` says which program, on
which thread, hit or miss, and what its tracing and lowering cost; ``span``
events say when, where and under what; a generation-2 collection is an
event; and none of it exists with telemetry off."""
import gc
import os
import subprocess
import sys
import threading

import pytest

from lightgbm_tpu import obs

LOAD = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
HITS = "/jax/compilation_cache/cache_hits"
MISSES = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"


@pytest.fixture(autouse=True)
def _telemetry_on():
    obs.reset()
    obs.configure(enabled=True, metrics_out="")
    yield
    obs.reset()
    obs.configure(enabled=False, metrics_out="")


def _loads():
    return [e for e in obs.EVENTS.snapshot() if e["type"] == "program_load"]


# ---- the listeners, fed what jax feeds them ---------------------------------

@pytest.mark.parametrize("said,word", [(HITS, "hit"), (MISSES, "miss"),
                                       (None, "off")])
def test_cache_event_before_the_load_names_its_outcome(said, word):
    if said:
        obs._on_jax_event(said)
    if said == HITS:
        obs._on_jax_duration(SAVED, 12.5)
        obs._on_jax_duration(RETRIEVAL, 0.25)
    obs._on_jax_duration(LOAD, 0.5, fun_name="jit(step)")
    obs._on_jax_duration(LOAD, 0.1, fun_name="jit(argsort)")
    first, second = _loads()
    assert (first["program"], first["cache"]) == ("step", word)
    assert first["thread"] == threading.current_thread().name
    assert (first.get("retrieval_s"), first.get("saved_s")) == (
        (0.25, 12.5) if word == "hit" else (None, None))
    # consumed: the next load on this thread has said nothing yet
    assert second["cache"] == "off" and "retrieval_s" not in second
    assert obs.METRICS.counter("programs_loaded", "", span="none",
                               cache=word).value >= 1


def test_cache_word_stays_on_the_thread_that_heard_it():
    heard = threading.Event()
    go_on = threading.Event()

    def worker():
        obs._on_jax_event(HITS)
        heard.set()
        assert go_on.wait(30)
        obs._on_jax_duration(LOAD, 0.2, fun_name="jit(theirs)")
    th = threading.Thread(target=worker, name="other-compiler")
    th.start()
    assert heard.wait(30)
    obs._on_jax_duration(LOAD, 0.1, fun_name="jit(mine)")
    go_on.set()
    th.join(30)
    assert not th.is_alive()
    by_program = {e["program"]: e for e in _loads()}
    assert by_program["mine"]["cache"] == "off"
    assert by_program["theirs"]["cache"] == "hit"
    assert by_program["theirs"]["thread"] == "other-compiler"


def test_trace_and_lower_attach_to_the_load_of_the_same_name():
    obs._on_jax_duration(TRACE, 0.25, fun_name="grow_tree")   # a jit inside
    obs._on_jax_duration(TRACE, 2.0, fun_name="step")
    obs._on_jax_duration(LOWER, 0.5, fun_name="jit(step)")
    obs._on_jax_duration(LOAD, 4.0, fun_name="jit(step)")
    obs._on_jax_duration(LOWER, 0.125, fun_name="jit(<lambda>)")
    obs._on_jax_duration(LOAD, 0.0625, fun_name="jit(<lambda>)")
    step, lam = _loads()
    assert (step["trace_s"], step["lower_s"], step["duration_s"]) == (
        2.0, 0.5, 4.0)
    # the inner jit's tracing went with the load it was part of; a program
    # whose tracing nobody reported says nothing of it
    assert lam["program"] == "_lambda_" and lam["lower_s"] == 0.125
    assert "trace_s" not in lam


@pytest.mark.parametrize("how", ["dispatch", "aot"])
def test_program_is_the_functions_name(how):
    import jax
    import jax.numpy as jnp

    def scaled_by_pr36(x):
        return x * 36 + 1
    if how == "dispatch":
        jax.jit(scaled_by_pr36)(jnp.arange(7)).block_until_ready()
    else:
        jax.jit(scaled_by_pr36).lower(
            jax.ShapeDtypeStruct((7,), jnp.int32)).compile()
    mine = [e for e in _loads() if e["program"] == "scaled_by_pr36"]
    assert len(mine) == 1
    assert mine[0]["thread"] == "MainThread"
    assert mine[0]["cache"] in ("hit", "miss", "off")
    assert mine[0]["trace_s"] > 0 and mine[0]["lower_s"] > 0


# ---- span events ------------------------------------------------------------

def test_span_event_says_when_where_and_under_what():
    with obs.span("outer", parent="ignored_where_the_thread_has_one"):
        with obs.span("inner"):
            pass

    def worker():
        with obs.span("workers_outermost", parent="outer"):
            pass
    th = threading.Thread(target=worker, name="a-worker")
    th.start()
    th.join(30)
    spans = {e["name"]: e for e in obs.EVENTS.snapshot()
             if e["type"] == "span"}
    assert spans["inner"]["parent"] == "outer"
    assert spans["outer"]["parent"] == "ignored_where_the_thread_has_one"
    assert spans["workers_outermost"]["parent"] == "outer"
    assert spans["workers_outermost"]["thread"] == "a-worker"
    for e in spans.values():
        assert e["start_ts"] <= e["ts"]
        assert e["ts"] - e["start_ts"] == pytest.approx(e["duration_s"],
                                                        abs=0.05)
    with obs.span("no_parent_at_all"):
        pass
    last = obs.EVENTS.snapshot()[-1]
    assert last["name"] == "no_parent_at_all" and "parent" not in last


# ---- the collector ----------------------------------------------------------

@pytest.mark.parametrize("generation", [0, 1, 2])
def test_only_a_generation_2_collection_is_an_event(generation):
    gc.collect(generation)
    obs.emit("aot_prewarm", phase="started")     # the next emit writes it
    pauses = [e for e in obs.EVENTS.snapshot() if e["type"] == "gc_pause"]
    if generation < 2:
        assert pauses == []
        return
    assert len(pauses) >= 1
    p = pauses[-1]
    assert p["generation"] == 2 and p["duration_s"] > 0
    assert p["collected"] >= 0 and p["start_ts"] <= p["ts"]
    # written before the event whose emit found it
    assert obs.EVENTS.snapshot()[-1]["type"] == "aot_prewarm"


def test_gc_hook_is_silent_when_disabled():
    obs.configure(enabled=False)
    gc.collect()
    obs.configure(enabled=True)
    obs.emit("aot_prewarm", phase="started")
    assert [e["type"] for e in obs.EVENTS.snapshot()] == ["aot_prewarm"]


# ---- telemetry off ----------------------------------------------------------

_OFF_SCRIPT = """
import gc, sys
import numpy as np
from jax._src import monitoring
import lightgbm_tpu as lgb
from lightgbm_tpu import obs, prewarm
prewarm.MIN_PREWARM_ROWS = 0
rng = np.random.RandomState(3)
X = rng.rand(400, 5).astype(np.float32)
y = (X[:, 0] > 0.5).astype(np.float32)
p = {"objective": "binary", "num_leaves": 7, "verbose": -1, "metric": "auc",
     "min_data_in_leaf": 5}
ds = lgb.Dataset(X[:300], label=y[:300], params=p)
lgb.train(p, ds, num_boost_round=2, verbose_eval=False,
          valid_sets=[ds.create_valid(X[300:], label=y[300:])])
gc.collect()
ours = [f for f in (monitoring.get_event_listeners()
                    + monitoring.get_event_duration_listeners()
                    + list(gc.callbacks))
        if getattr(f, "__module__", "").startswith("lightgbm_tpu")]
assert not ours, ours
assert len(obs.EVENTS) == 0, obs.EVENTS.snapshot()[:3]
assert obs.METRICS.to_json() == {}
obs.configure(enabled=True)
ours = [f.__name__ for f in (monitoring.get_event_listeners()
                             + monitoring.get_event_duration_listeners()
                             + list(gc.callbacks))
        if getattr(f, "__module__", "").startswith("lightgbm_tpu")]
assert sorted(ours) == ["_on_gc", "_on_jax_duration", "_on_jax_event"], ours
obs.configure(enabled=False)
obs.configure(enabled=True)           # registered once, not once a call
assert len(gc.callbacks) - len([f for f in gc.callbacks
                                if f.__name__ != "_on_gc"]) == 1
print("OFF-OK")
"""


def test_telemetry_off_registers_no_listener_and_records_nothing():
    """A fresh process (this one has had telemetry on): ``lgb.train`` with a
    prewarm thread and a validation set, telemetry off, leaves no
    ``jax.monitoring`` listener and no ``gc`` callback of the package and an
    empty event log; the first ``configure(enabled=True)`` installs the
    three, once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", LGBMTPU_TELEMETRY="")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _OFF_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "OFF-OK" in out.stdout, out.stderr[-3000:]
