"""Observability subsystem (lightgbm_tpu/obs/): event schema validation,
Prometheus exposition format, histogram bucket math, concurrent-predict
counter integrity, and the zero-retrace guarantee with telemetry enabled."""
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import events as obs_events
from lightgbm_tpu.obs import memory as obs_memory
from lightgbm_tpu.obs.metrics import Histogram, MetricsRegistry
from lightgbm_tpu.utils.timer import TIMER, TimerRegistry, timed

RNG = np.random.RandomState(11)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Telemetry state is process-global: isolate every test."""
    obs.reset()
    obs.configure(enabled=False, metrics_out="")
    yield
    obs.reset()
    obs.configure(enabled=False, metrics_out="")


def _train(rounds=8, **extra):
    X = RNG.rand(300, 6)
    y = X[:, 0] * 2 + np.sin(X[:, 1] * 5) + RNG.randn(300) * 0.05
    params = {"objective": "regression", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, **extra}
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds), X


@pytest.fixture(scope="module")
def booster():
    """One shared trained model for the predict-side tests (training again
    per test would triple the module's wall time for no extra coverage)."""
    return _train()


# ---- event schema -----------------------------------------------------------

def test_emit_validates_schema():
    obs.configure(enabled=True)
    with pytest.raises(ValueError, match="unregistered event type"):
        obs.emit("no_such_event", x=1)
    with pytest.raises(ValueError, match="missing required field"):
        obs.emit("train_iter", iteration=1)
    with pytest.raises(ValueError, match="unregistered field"):
        obs.emit("resume", iteration=1, path="p", bogus=2)
    with pytest.raises(ValueError, match="expected int"):
        obs.emit("train_iter", iteration="one", duration_s=0.1,
                 rows_per_s=1.0)
    with pytest.raises(ValueError, match="got bool"):
        obs.emit("train_iter", iteration=True, duration_s=0.1,
                 rows_per_s=1.0)
    obs.emit("train_iter", iteration=1, duration_s=0.1, rows_per_s=1.0)
    assert len(obs.EVENTS) == 1


def test_emit_is_noop_when_disabled():
    obs.emit("train_iter", iteration=1, duration_s=0.1, rows_per_s=1.0)
    assert len(obs.EVENTS) == 0
    # even invalid events pass silently when disabled: the hot path must not
    # pay validation cost for disabled telemetry
    obs.emit("not_validated_when_off")
    assert len(obs.EVENTS) == 0


def test_event_log_bounded_drops_oldest():
    log = obs_events.EventLog(capacity=4)
    for i in range(7):
        log.emit("resume", iteration=i, path=f"p{i}")
    assert len(log) == 4
    assert log.dropped == 3
    kept = [r["iteration"] for r in log.snapshot()]
    assert kept == [3, 4, 5, 6]


def test_training_emits_schema_valid_jsonl(tmp_path):
    _train(telemetry=1, metrics_out=str(tmp_path), rounds=12)
    ev_path = tmp_path / "events.jsonl"
    assert ev_path.exists()
    records = [json.loads(line) for line in ev_path.read_text().splitlines()]
    assert records, "training with telemetry=1 must emit events"
    types = {r["type"] for r in records}
    assert "train_iter" in types and "compile" in types
    for rec in records:
        body = {k: v for k, v in rec.items() if k not in ("ts", "type")}
        # every exported record must re-validate against its registered schema
        obs_events._validate(rec["type"], body)
    iters = [r for r in records if r["type"] == "train_iter"]
    assert len(iters) == 12
    assert all(r["rows_per_s"] > 0 for r in iters)
    # the lagged queue (depth 8) has aged out entries by iteration 12, so the
    # late train_iter events carry leaf_count/best_gain from ≤8 iters back
    late = iters[-1]
    assert late["leaf_count"] >= 1
    assert late["lagged_iteration"] <= late["iteration"] - 8


# ---- metrics / exporters ----------------------------------------------------

def test_prometheus_golden_format():
    reg = MetricsRegistry()
    reg.counter("requests", "served requests").inc(3)
    reg.gauge("queue_depth", "rows waiting", shard="0").set(7)
    h = reg.histogram("latency_seconds", "request latency", base=1.0,
                      n_buckets=2)
    h.observe(0.5)
    h.observe(1.5)
    h.observe(9.25)
    golden = (
        "# HELP lgbmtpu_latency_seconds request latency\n"
        "# TYPE lgbmtpu_latency_seconds histogram\n"
        'lgbmtpu_latency_seconds_bucket{le="1"} 1\n'
        'lgbmtpu_latency_seconds_bucket{le="2"} 2\n'
        'lgbmtpu_latency_seconds_bucket{le="+Inf"} 3\n'
        "lgbmtpu_latency_seconds_sum 11.25\n"
        "lgbmtpu_latency_seconds_count 3\n"
        "# HELP lgbmtpu_queue_depth rows waiting\n"
        "# TYPE lgbmtpu_queue_depth gauge\n"
        'lgbmtpu_queue_depth{shard="0"} 7\n'
        "# HELP lgbmtpu_requests_total served requests\n"
        "# TYPE lgbmtpu_requests_total counter\n"
        "lgbmtpu_requests_total 3\n")
    assert reg.to_prometheus() == golden


def test_histogram_log2_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("latency_seconds", base=1e-6, n_buckets=27)
    # bound i is base * 2^i, le-inclusive
    assert h.bucket_index(1e-6) == 0          # at the first bound
    assert h.bucket_index(1e-9) == 0          # below base
    assert h.bucket_index(2e-6) == 1          # exactly at bound 1
    assert h.bucket_index(2.1e-6) == 2        # just above bound 1
    assert h.bucket_index(1e9) == 27          # +Inf slot
    for v in (1e-6, 3e-6, 0.5, 1e9):
        h.observe(v)
    snap = h.snapshot()
    assert sum(snap["counts"]) == 4 == h.count
    assert snap["sum"] == pytest.approx(1e9 + 0.5 + 4e-6)
    # prometheus rendering must be cumulative and monotone
    lines = [l for l in reg.to_prometheus().splitlines() if "_bucket" in l]
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines]
    assert counts == sorted(counts)
    assert counts[-1] == 4


def test_counters_reject_negative_and_gauge_watermark():
    reg = MetricsRegistry()
    c = reg.counter("n")
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("peak")
    g.set_max(5)
    g.set_max(3)
    assert g.value == 5
    with pytest.raises(ValueError):
        reg.gauge("n")   # kind conflict on the same name


def test_metrics_json_and_files_roundtrip(tmp_path):
    obs.configure(enabled=True, metrics_out=str(tmp_path))
    obs.METRICS.counter("writes").inc()
    obs.METRICS.histogram("lat", base=1.0, n_buckets=2).observe(0.5)
    obs.emit("resume", iteration=3, path="snap")
    assert obs.export_all() == str(tmp_path)
    mj = json.loads((tmp_path / "metrics.json").read_text())
    assert mj["writes"]["kind"] == "counter"
    assert mj["lat"]["series"]["{}"]["count"] == 1
    assert (tmp_path / "metrics.prom").read_text().startswith("# HELP")


def test_memory_sampling_none_safe():
    # CPU devices report memory_stats() == None: everything degrades cleanly
    readings = obs_memory.sample()
    assert isinstance(readings, list)
    reg = MetricsRegistry()
    obs_memory.update_gauges(reg)
    wm = obs_memory.watermark([])
    assert wm == {}
    wm2 = obs_memory.watermark([{"device": "0", "peak_bytes_in_use": 42},
                                {"device": "1"}])
    assert wm2 == {"peak_bytes_in_use_max": 42, "devices_reporting": 1}


def test_env_var_overrides_config(monkeypatch):
    class FakeConf:
        telemetry = False
        metrics_out = ""
    monkeypatch.setenv("LGBMTPU_TELEMETRY", "1")
    obs.configure_from_config(FakeConf())
    assert obs.enabled()
    monkeypatch.setenv("LGBMTPU_TELEMETRY", "0")
    FakeConf.telemetry = True
    obs.configure_from_config(FakeConf())
    assert not obs.enabled()


# ---- serving ----------------------------------------------------------------

def test_predict_per_bucket_latency_histograms(booster):
    bst, X = booster
    obs.configure(enabled=True)
    bst.predict(X[:1])
    for _ in range(3):
        bst.predict(X[:100])
    series = obs.METRICS.to_json()["predict_latency_seconds"]["series"]
    assert '{bucket="1"}' in series
    assert '{bucket="128"}' in series
    assert series['{bucket="1"}']["count"] == 1
    assert series['{bucket="128"}']["count"] == 3
    ev = [r for r in obs.EVENTS.snapshot() if r["type"] == "predict_batch"]
    assert [e["rows"] for e in ev] == [1, 100, 100, 100]
    assert all(e["bucket"] in (1, 128) for e in ev)


def test_concurrent_predict_counter_integrity(booster):
    bst, X = booster
    obs.configure(enabled=True)
    eng = bst._predict_engine_for(bst._ensure_host_trees(), X.shape[1], 1)
    eng.warmup(sizes=(1, 64))
    base_calls = eng.stats["calls"]
    counter = obs.METRICS.counter("predict_calls", "predict() calls")
    base_metric = counter.value
    errors = []

    def worker():
        try:
            for i in range(25):
                n = 1 + (i % 40)
                out = eng.predict(X[:n])
                assert out.shape[0] == n
        except Exception as e:   # surfaced below; thread loses the raise
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert eng.stats["calls"] - base_calls == 8 * 25
    assert counter.value - base_metric == 8 * 25
    hseries = obs.METRICS.to_json()["predict_latency_seconds"]["series"]
    assert sum(s["count"] for s in hseries.values()) >= 8 * 25


def test_zero_retrace_predict_with_telemetry(booster):
    """Telemetry must add ZERO device code: after per-bucket warmup with
    telemetry OFF, turning it ON triggers no new jit lowerings — the same
    counters the serving tests use to prove the engine itself is retrace-free."""
    bst, X = booster
    for n in (1, 30, 100):
        bst.predict(X[:n])
        bst.predict(X[:n], raw_score=True)
    obs.configure(enabled=True)
    with jtu.count_jit_and_pmap_lowerings() as count:
        for n in (1, 30, 100):
            bst.predict(X[:n])
            bst.predict(X[:n], raw_score=True)
    assert count() == 0, f"telemetry caused {count()} new lowerings"
    assert obs.METRICS.counter("predict_calls", "predict() calls").value == 6


def test_training_lowering_count_unchanged_by_telemetry(tmp_path):
    """Identical training runs must lower the same number of programs with
    telemetry on and off (host-side observation only, no new jit boundaries)."""
    state = RNG.get_state()

    def same(**extra):      # the same rows each time: bin counts are shapes
        RNG.set_state(state)
        return _train(**extra)
    same()          # untimed: pays the compiles, so both counted runs are warm
    # the counter goes on counting after its block: read it inside
    with jtu.count_jit_and_pmap_lowerings() as count:
        same()
        off = count()
    obs.reset()
    with jtu.count_jit_and_pmap_lowerings() as count:
        same(telemetry=1, metrics_out=str(tmp_path))
        on = count()
    assert on == off, f"telemetry changed lowering count: {off} -> {on}"


# ---- names, spans and the program_load counter (PR 25) ----------------------

# every named_scope the step's traced body opens, and the Pallas kernels of
# the forced-Pallas path on the CPU
STEP_SCOPES = {"front", "split_search", "apply_level", "route_hist",
               "route_only", "leaf_renew", "score_update"}
# route_level: the router of a level that ends the tree (scope route_only)
STEP_KERNELS = {"grad_quant_hist0", "hist_level_q8", "leaf_sums_grad",
                "route_level"}
# children of train_iter, by name (docs/OBSERVABILITY.md)
ITER_SPANS = {"callbacks_before", "boosting", "prewarm_adopt", "step_dispatch",
              "valid_score", "finished_check", "eval", "metric", "callbacks",
              "snapshot"}


def _train_valid(rounds=3, **extra):
    X = RNG.rand(300, 6)
    y = (X[:, 0] + 0.2 * RNG.randn(300) > 0.5).astype(np.float32)
    params = {"objective": "binary", "metric": "auc", "num_leaves": 7,
              "verbose": -1, "min_data_in_leaf": 5, **extra}
    ds = lgb.Dataset(X[:200], label=y[:200], params=params)
    return lgb.train(params, ds, num_boost_round=rounds,
                     valid_sets=[ds.create_valid(X[200:], label=y[200:])],
                     verbose_eval=False)


def test_step_jaxpr_names_kernels_and_scopes(monkeypatch):
    """The traced step holds a pallas_call under every kernel name of the
    path and every stage's scope in its name stacks, and the q8 kernels
    contract int8 x int8 as on the chip (interpret mode, the small shape of
    test_q8_kernels)."""
    import jax
    from jax._src import core
    from lightgbm_tpu.models import gbdt
    seen = {}
    real = gbdt.GBDT._build_fused_step

    def build(self, custom):
        fn = real(self, custom)

        def call(*args):
            seen.setdefault("jaxpr", jax.make_jaxpr(fn)(*args))
            return fn(*args)
        return call
    monkeypatch.setattr(gbdt.GBDT, "_build_fused_step", build)
    rng = np.random.RandomState(0)
    X = rng.rand(220, 7).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.rand(220) > 0.65).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1, "prewarm": 0,
              "histogram_impl": "pallas", "use_quantized_grad": "true"}
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=1)
    kernels, scopes, dots = set(), set(), {}

    def walk(jaxpr, kernel=None):
        for eqn in jaxpr.eqns:
            scopes.update(str(eqn.source_info.name_stack).split("/"))
            inside = kernel
            if eqn.primitive.name == "pallas_call":
                inside = eqn.params["name"]
                kernels.add(inside)
            if eqn.primitive.name == "dot_general" and kernel:
                dots.setdefault(kernel, []).append(
                    tuple(str(v.aval.dtype) for v in eqn.invars))
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub, inside)
    walk(seen["jaxpr"].jaxpr)
    assert kernels == STEP_KERNELS
    for name in ("hist_level_q8", "grad_quant_hist0"):
        assert ("int8", "int8") in dots[name]
        assert not any("int32" in d for d in dots[name]), dots[name]
    assert STEP_SCOPES <= scopes
    assert any(s.startswith("level_s") for s in scopes)


def test_train_iter_spans_nest_and_add_up(monkeypatch):
    """Three iterations with telemetry on: three train_iter events whose
    spans are the documented children, add up to no more than the iteration,
    and were opened and closed in stack order on the training thread."""
    from lightgbm_tpu.utils import timer
    log, real = [], timer.TIMER.scope

    def scope(name, **kw):
        cm = real(name, **kw)
        log.append(("open", name, threading.get_ident()))

        class _Logged:
            def __enter__(self):
                return cm.__enter__()

            def __exit__(self, *exc):
                log.append(("close", name, threading.get_ident()))
                return cm.__exit__(*exc)
        return _Logged()
    monkeypatch.setattr(timer.TIMER, "scope", scope)
    _train_valid(telemetry=1)
    iters = [e for e in obs.EVENTS.snapshot() if e["type"] == "train_iter"]
    assert [e["iteration"] for e in iters] == [1, 2, 3]
    for e in iters:
        assert set(e["spans"]) <= ITER_SPANS
        assert {"boosting", "step_dispatch", "valid_score", "finished_check",
                "eval", "metric"} <= set(e["spans"])
        assert all(v >= 0 for v in e["spans"].values())
        assert sum(e["spans"].values()) <= e["duration_s"]
        assert e["programs_loaded"] >= 0
    stack = []
    for what, name, tid in log:
        assert tid == threading.get_ident()
        if what == "open":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    assert [n for w, n, _ in log if w == "open"].count("train_iter") == 3
    outside = {e["name"] for e in obs.EVENTS.snapshot()
               if e["type"] == "span"}
    assert {"dataset_construct", "train_setup", "finalize"} <= outside


def test_program_load_names_the_span_it_fell_into():
    """An eager jit dispatched inside valid_score yields a program_load event
    with that span, and the events add up to what a plain listener counts."""
    import jax
    import jax.numpy as jnp
    plain = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: plain.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    obs.configure(enabled=True)
    with obs.span("train_iter", step_num=7) as rec:
        with obs.span("valid_score"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    jax.jit(lambda x: x * 5 - 1)(jnp.arange(5)).block_until_ready()
    loads = [e for e in obs.EVENTS.snapshot() if e["type"] == "program_load"]
    assert len(loads) == len(plain) >= 2
    inside = [e for e in loads if e["span"] == "valid_score"]
    assert inside and all(e["iteration"] == 7 for e in inside)
    assert rec.programs_loaded == len(inside)
    assert loads[-1]["span"] == "none" and "iteration" not in loads[-1]
    by_span = sum(
        obs.METRICS.counter("programs_loaded", "", span=s, cache=c).value
        for s, c in {(e["span"], e["cache"]) for e in loads})
    assert by_span == len(plain)


def test_spans_and_program_load_silent_when_disabled():
    """Telemetry off: no program_load event, no spans field, and obs.span
    leaves the metrics registry as it found it."""
    import jax
    import jax.numpy as jnp
    obs.configure(enabled=True)      # the listener is in place ...
    obs.configure(enabled=False)     # ... and silent
    obs.reset()
    with obs.span("valid_score"):
        jax.jit(lambda x: x * 7 + 2)(jnp.arange(5)).block_until_ready()
    _train_valid(rounds=2)
    assert len(obs.EVENTS) == 0
    assert obs.METRICS.to_json() == {}
    _train_valid(rounds=2, telemetry=1)
    iters = [e for e in obs.EVENTS.snapshot() if e["type"] == "train_iter"]
    assert iters and all("spans" in e for e in iters)


def test_valid_walk_event_counts_the_steps_of_each_walk(monkeypatch):
    """Telemetry on: one valid_walk event per iteration and validation set,
    the last one emitted when training ends, its steps at most the tree's
    depth. Telemetry off: the walk is asked for no step count, so no device
    scalar is kept or read."""
    from lightgbm_tpu.ops import predict as P
    asked, real = [], P.route_bins

    def route_bins(*a, steps_out=None, **kw):
        asked.append(steps_out is not None)
        return real(*a, steps_out=steps_out, **kw)
    monkeypatch.setattr(P, "route_bins", route_bins)
    bst = _train_valid(telemetry=1)
    walks = [e for e in obs.EVENTS.snapshot() if e["type"] == "valid_walk"]
    assert [(e["iteration"], e["valid_set"]) for e in walks] == [
        (1, 0), (2, 0), (3, 0)]
    depths = [t.max_depth for t in bst.trees]
    assert len(depths) == 3 and max(depths) <= 6
    for e, depth in zip(walks, depths):
        assert 1 <= e["steps"] <= depth
        assert e["path"] == "xla"   # the CPU's default trainer: no kernel
    assert asked == [True] * 3 and bst._gbdt._valid_walks == []
    del asked[:]
    obs.reset()
    bst = _train_valid()
    assert asked == [False] * 3
    assert not hasattr(bst._gbdt, "_valid_walks")
    assert len(obs.EVENTS) == 0


# ---- timer satellites -------------------------------------------------------

def test_timed_uses_functools_wraps():
    @timed("t_scope")
    def documented(a, b=2):
        """docstring survives"""
        return a + b
    assert documented.__name__ == "documented"
    assert documented.__doc__ == "docstring survives"
    assert documented.__wrapped__.__name__ == "documented"
    assert documented(1) == 3


def test_timer_registry_thread_safe():
    reg = TimerRegistry()

    def worker():
        for _ in range(500):
            reg.add("x", 0.001)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.snapshot()["x"]["count"] == 8 * 500
    assert reg.get("x") == pytest.approx(8 * 500 * 0.001)


def test_timer_begin_run_archives_and_resets():
    reg = TimerRegistry()
    reg.add("boosting", 1.5)
    reg.begin_run()
    assert reg.get("boosting") == 0.0
    assert reg.last_run["boosting"] == (1.5, 1)
    reg.add("boosting", 0.5)
    assert reg.get("boosting") == 0.5


def test_train_resets_global_timer_per_run():
    _train(rounds=3)
    first = TIMER.get("boosting")
    assert first > 0.0
    _train(rounds=3)
    # accumulations must not bleed across train() calls: the first run's
    # totals were archived to last_run, and the live accumulator restarted
    assert TIMER.last_run["boosting"][0] == pytest.approx(first)
    assert TIMER.get("boosting") > 0.0


# ---- tooling ----------------------------------------------------------------

def test_schema_checker_passes_on_tree():
    """scripts/check_telemetry_schema.py is the static complement of runtime
    validation; it must pass on the shipped tree (fast: pure AST walk)."""
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("check_telemetry_schema",
                                                  script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0
