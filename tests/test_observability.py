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
    with jtu.count_jit_and_pmap_lowerings() as off:
        _train()
    obs.reset()
    with jtu.count_jit_and_pmap_lowerings() as on:
        _train(telemetry=1, metrics_out=str(tmp_path))
    assert on() == off(), (f"telemetry changed lowering count: "
                             f"{off()} -> {on()}")


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
