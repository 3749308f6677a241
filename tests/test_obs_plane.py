"""Live observability plane (ISSUE 11): request tracing through the serve
stack, the ObsServer /metrics endpoint, the SLO tracker, and the crash
flight recorder. Acceptance: trace ids propagate ingress -> response with
bit-exact outputs and ZERO new XLA programs on a warmed engine; a live
/metrics scrape during serve load parses as Prometheus exposition including
SLO attainment and request-latency histograms; an injected device fault
leaves a flight dump containing the faulting request's span chain."""
import glob
import json
import os
import re
import time
import urllib.request

import numpy as np
import pytest

import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import flight as obs_flight
from lightgbm_tpu.obs import http_server as obs_http
from lightgbm_tpu.obs import slo as obs_slo
from lightgbm_tpu.obs import tracing as obs_tracing
from lightgbm_tpu.obs.slo import SLOTracker
from lightgbm_tpu.server import PredictServer, handle_line
from lightgbm_tpu.utils import faults

RNG = np.random.RandomState(23)


@pytest.fixture(scope="module", autouse=True)
def _lockwatch_zero_inversions():
    from lightgbm_tpu.analysis import lockwatch
    yield
    lockwatch.WATCH.assert_clean("tests/test_obs_plane.py")
N_FEAT = 6


@pytest.fixture(autouse=True)
def _clean_obs():
    """Telemetry/SLO/trace/flight state is process-global: isolate every
    test, and disarm any fault spec a failing test left behind."""
    obs.reset()
    obs.configure(enabled=False, metrics_out="")
    faults.reset()
    yield
    obs.reset()
    obs.configure(enabled=False, metrics_out="")
    faults.reset()


@pytest.fixture(scope="module")
def booster():
    X = RNG.rand(400, N_FEAT)
    y = (X[:, 0] + X[:, 1] > 1).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5)


@pytest.fixture(scope="module")
def queries():
    return RNG.rand(64, N_FEAT)


def _mk_server(b, **conf):
    conf.setdefault("verbose", -1)
    conf.setdefault("serve_max_batch_rows", 64)
    return PredictServer(conf, model=b)


# ---- SLO tracker math -------------------------------------------------------

def test_slo_attainment_math_synthetic_stream():
    tr = SLOTracker()
    tr.configure(slo_ms=10.0, target=0.9, window=8)
    assert tr.active
    obs.configure(enabled=True)
    for _ in range(6):
        tr.observe("m", 0.005)          # in SLO
    for _ in range(2):
        tr.observe("m", 0.050)          # violations
    snap = tr.snapshot()["m"]
    assert snap["attainment"] == pytest.approx(6 / 8)
    assert snap["burn_rate"] == pytest.approx((1 - 6 / 8) / (1 - 0.9))
    assert snap["breached"] is True
    assert snap["requests"] == 8 and snap["violations"] == 2
    # rolling window: 8 fast requests push the violations out -> recovery
    for _ in range(8):
        tr.observe("m", 0.001)
    snap = tr.snapshot()["m"]
    assert snap["attainment"] == 1.0
    assert snap["burn_rate"] == pytest.approx(0.0)
    assert snap["breached"] is False
    # breach transitions emitted in both directions
    breaches = [e for e in obs.EVENTS.snapshot() if e["type"] == "slo_breach"]
    assert [e["recovered"] for e in breaches] == [False, True]
    # derived gauges are live in the global registry
    kind, children = obs.METRICS.get_family("slo_attainment")
    assert kind == "gauge"
    assert {dict(k)["model"]: c.value for k, c in children.items()}["m"] == 1.0


def test_slo_inactive_by_default_records_nothing():
    tr = SLOTracker()
    assert not tr.active
    tr.observe("m", 99.0)
    assert tr.snapshot() == {}


# ---- request tracing --------------------------------------------------------

def test_trace_id_propagates_and_outputs_bit_exact(booster, queries):
    """Traced server == untraced server == direct Booster.predict, bit for
    bit; every request's minted trace id surfaces in the sampled exemplars
    (sample=1 keeps all)."""
    obs.configure(enabled=True)
    traced = _mk_server(booster, serve_trace=True, serve_trace_sample=1)
    plain = _mk_server(booster)
    try:
        want = booster.predict(queries)
        ids = []
        for n in (1, 3, 17):
            req = traced.submit(queries[:n])
            out = req.result(timeout=30)
            assert req.trace_id is not None and req.trace_id.startswith("req-")
            ids.append(req.trace_id)
            np.testing.assert_array_equal(out, want[:n])
            np.testing.assert_array_equal(plain.predict(queries[:n]),
                                          want[:n])
        assert len(set(ids)) == len(ids)        # process-unique ids
        exemplars = obs_tracing.TRACES.snapshot()
        by_id = {t["trace_id"]: t for t in exemplars}
        for tid in ids:
            t = by_id[tid]
            for k in ("queue_wait_s", "bin_s", "device_dispatch_s",
                      "readback_s", "total_s", "model", "version", "rows",
                      "bucket"):
                assert k in t, k
            assert t["total_s"] >= 0.0 and t["queue_wait_s"] >= 0.0
        # span breakdown landed in the span_seconds histogram family
        kind, children = obs.METRICS.get_family("span_seconds")
        spans = {dict(k)["span"] for k in children}
        assert {"serve.queue_wait", "serve.bin", "serve.device_dispatch",
                "serve.readback"} <= spans
    finally:
        traced.close()
        plain.close()


def test_untraced_requests_have_no_trace_id(booster, queries):
    srv = _mk_server(booster)
    try:
        req = srv.submit(queries[:2])
        req.result(timeout=30)
        assert req.trace_id is None
    finally:
        srv.close()


def test_tracing_adds_zero_lowerings_on_warmed_engine(booster, queries):
    """Tracing is pure host-side clock reads: with the engine warmed, a
    traced request storm lowers ZERO new XLA programs."""
    obs.configure(enabled=True)
    srv = _mk_server(booster, serve_trace=True, serve_trace_sample=1)
    try:
        sizes = (1, 5, 64)
        for n in sizes:                 # serve-path warmup per bucket
            srv.predict(queries[:n])
        with jtu.count_jit_and_pmap_lowerings() as count:
            for _ in range(3):
                for n in sizes:
                    np.testing.assert_array_equal(
                        srv.predict(queries[:n]),
                        booster.predict(queries[:n]))
        assert count() == 0, f"tracing lowered {count()} new programs"
        assert obs_tracing.TRACES.snapshot()    # and it actually traced
    finally:
        srv.close()


def test_trace_sampling_keeps_one_in_n():
    buf = obs_tracing.TraceBuffer(capacity=32)
    kept = [buf.maybe_record({"i": i}, sample=4) for i in range(8)]
    assert kept == [True, False, False, False, True, False, False, False]


# ---- /metrics endpoint ------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.+eE]+|\+Inf|NaN)$")


def _check_prom_shape(text):
    """Exposition-format shape check: HELP/TYPE precede their samples,
    histogram buckets are cumulative and +Inf == _count."""
    typed = {}
    buckets = {}        # (family, labels-sans-le) -> [cumulative counts]
    counts = {}         # (family, labels) -> _count value
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) >= 4, line
            if parts[1] == "TYPE":
                typed[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and \
                    name[: -len(suffix)] in typed and \
                    typed[name[: -len(suffix)]] == "histogram":
                fam = name[: -len(suffix)]
        assert fam in typed, f"sample {name!r} precedes its # TYPE"
        pairs = tuple(p for p in re.findall(r'(\w+)="([^"]*)"', labels)
                      if p[0] != "le")
        if name.endswith("_bucket") and typed.get(fam) == "histogram":
            le = re.search(r'le="([^"]*)"', labels).group(1)
            buckets.setdefault((fam, pairs), []).append((le, float(value)))
        elif name.endswith("_count") and typed.get(fam) == "histogram":
            counts[(fam, pairs)] = float(value)
    assert typed, "no # TYPE lines at all"
    for (fam, rest), series in buckets.items():
        vals = [v for _, v in series]
        assert vals == sorted(vals), f"{fam}{rest} buckets not cumulative"
        assert series[-1][0] == "+Inf", f"{fam}{rest} missing +Inf"
        assert series[-1][1] == counts[(fam, rest)], \
            f"{fam}{rest} +Inf != _count"
    return typed


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), \
            resp.read().decode("utf-8")


def test_obs_server_live_scrape_under_load(booster, queries):
    obs.configure(enabled=True)
    srv = _mk_server(booster, serve_slo_ms=250.0, serve_slo_target=0.9,
                     serve_trace=True, serve_trace_sample=4)
    http = obs_http.ObsServer(port=0).start()
    try:
        for n in (1, 2, 9, 33):
            srv.predict(queries[:n])
        status, ctype, body = _get(http.port, "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain") and "version=0.0.4" in ctype
        typed = _check_prom_shape(body)
        assert typed.get("lgbmtpu_slo_attainment") == "gauge"
        assert typed.get("lgbmtpu_slo_burn_rate") == "gauge"
        assert typed.get("lgbmtpu_request_latency_seconds") == "histogram"
        assert typed.get("lgbmtpu_model_age_seconds") == "gauge"
        assert typed.get("lgbmtpu_events_buffered") == "gauge"
        assert "lgbmtpu_request_latency_seconds_bucket" in body
        assert 'lgbmtpu_slo_attainment{model="default"}' in body
        # healthz / statusz
        status, _, body = _get(http.port, "/healthz")
        assert status == 200 and body == "ok\n"
        status, ctype, body = _get(http.port, "/statusz")
        assert status == 200 and ctype.startswith("application/json")
        doc = json.loads(body)
        assert doc["telemetry"]["enabled"] is True
        serving = doc["serving"]
        assert serving["models"]["default"]["version"] == 1
        assert serving["models"]["default"]["age_s"] >= 0.0
        assert serving["queue"]["requests"] >= 4
        assert serving["slo"]["default"]["slo_ms"] == pytest.approx(250.0)
        # 404 on unknown paths
        with pytest.raises(urllib.error.HTTPError):
            _get(http.port, "/nope")
    finally:
        http.close()
        srv.close()


def test_maybe_start_disabled_by_default():
    class FakeConf:
        obs_port = 0
    assert obs_http.maybe_start(FakeConf()) is None
    assert obs_http.stop(None) is None          # no-op


# ---- flight recorder --------------------------------------------------------

def test_flight_dump_on_injected_device_fault(booster, queries, tmp_path):
    """An armed device_put_oom on the serve path fails the request, trips
    the recorder, and the dump holds the faulting request's span chain."""
    obs.configure(enabled=True)
    obs_flight.FLIGHT.configure(out_dir=str(tmp_path), capacity=128)
    srv = _mk_server(booster, serve_trace=True, serve_trace_sample=1)
    try:
        srv.predict(queries[:2])                # healthy first
        faults.configure("device_put_oom:1")
        req = srv.submit(queries[:3])
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            req.result(timeout=30)
        assert req.trace_id is not None
        faults.reset()
        # the server survives: next request serves normally
        np.testing.assert_array_equal(srv.predict(queries[:2]),
                                      booster.predict(queries[:2]))
        dumps = sorted(glob.glob(os.path.join(str(tmp_path), "flight_*.json")))
        assert dumps, "no flight dump written"
        doc = json.loads(open(dumps[0]).read())
        assert doc["reason"] == "device_fault"
        assert doc["events"] >= 1
        spans = [r for r in doc["records"] if r.get("kind") == "span"]
        chain = [s for s in spans if s.get("trace_id") == req.trace_id]
        assert chain, "faulting request's span chain missing from dump"
        assert chain[0]["error"].startswith("RESOURCE_EXHAUSTED")
        assert chain[0]["rows"] == 3
        evs = [r for r in doc["records"] if r.get("kind") == "event"
               and r.get("type") == "device_fault"]
        assert evs and evs[0]["point"] == "device_put_oom"
        assert evs[0]["action"] == "fail_request"
    finally:
        faults.reset()
        srv.close()


def test_flight_explicit_dump_and_ring_bound(tmp_path):
    obs.configure(enabled=True)
    rec = obs_flight.FlightRecorder(capacity=4)
    rec.configure(out_dir=str(tmp_path), capacity=4)
    for i in range(7):
        rec.note_event("resume", {"iteration": i, "path": f"p{i}"})
    assert len(rec) == 4                         # bounded ring
    path = rec.dump("operator_request")
    doc = json.loads(open(path).read())
    assert doc["reason"] == "operator_request"
    assert [r["iteration"] for r in doc["records"]] == [3, 4, 5, 6]


def test_flight_disabled_without_dir():
    rec = obs_flight.FlightRecorder()
    assert not rec.enabled() and not rec.active
    assert rec.dump("nope") is None


# ---- satellites: periodic flush, reset, stats surface -----------------------

def test_periodic_flush_writes_metrics(tmp_path):
    obs.configure(enabled=True, metrics_out=str(tmp_path))
    obs.METRICS.counter("predict_calls", "x").inc()
    owner = obs.start_periodic_flush(0.05)
    assert owner is True
    assert obs.start_periodic_flush(0.05) is False   # already running
    try:
        prom = os.path.join(str(tmp_path), "metrics.prom")
        deadline = time.time() + 5.0
        while not os.path.exists(prom) and time.time() < deadline:
            time.sleep(0.02)
        assert os.path.exists(prom), "flusher never exported"
        assert "lgbmtpu_predict_calls_total" in open(prom).read()
    finally:
        obs.stop_periodic_flush(owner)
    # a non-owner stop is a no-op; the owner stop actually joined the thread
    assert obs.start_periodic_flush(0) is False      # interval 0 = disabled


def test_event_gauges_exported(tmp_path):
    obs.configure(enabled=True, metrics_out=str(tmp_path))
    obs.emit("resume", iteration=1, path="p")
    obs.emit("resume", iteration=2, path="q")
    assert obs.export_all() == str(tmp_path)
    text = open(os.path.join(str(tmp_path), "metrics.prom")).read()
    assert "lgbmtpu_events_buffered 2" in text
    assert 'lgbmtpu_events_by_type{type="resume"} 2' in text
    assert "lgbmtpu_events_dropped 0" in text


def test_reset_clears_slo_traces_and_flight(tmp_path):
    obs.configure(enabled=True)
    obs_slo.TRACKER.configure(slo_ms=5.0)
    obs_slo.TRACKER.observe("m", 1.0)
    obs_tracing.TRACES.record({"trace_id": "req-x"})
    obs_flight.FLIGHT.configure(out_dir=str(tmp_path), capacity=8)
    obs.emit("resume", iteration=1, path="p")
    assert obs_slo.TRACKER.snapshot() and obs_tracing.TRACES.snapshot()
    assert len(obs_flight.FLIGHT) == 1
    obs.reset()
    assert obs_slo.TRACKER.snapshot() == {} and not obs_slo.TRACKER.active
    assert obs_tracing.TRACES.snapshot() == []
    assert len(obs_flight.FLIGHT) == 0 and not obs_flight.FLIGHT.active
    assert len(obs.EVENTS) == 0


def test_stats_and_protocol_include_slo_latency_age(booster, queries):
    obs.configure(enabled=True)
    srv = _mk_server(booster, serve_slo_ms=250.0)
    try:
        for n in (1, 4, 8):
            srv.predict(queries[:n])
        # the flusher completes requests BEFORE the SLO/latency bookkeeping
        # (responses never wait on metrics), so the last flush's observe may
        # still be in flight when predict() returns — poll for it to land
        deadline = time.time() + 5.0
        while time.time() < deadline:
            st = srv.stats()
            if (st.get("slo", {}).get("default", {}).get("requests", 0) >= 3
                    and st.get("latency", {}).get("default", {})
                                             .get("count", 0) >= 3):
                break
            time.sleep(0.01)
        assert st["models"]["default"]["age_s"] >= 0.0
        slo = st["slo"]["default"]
        assert slo["requests"] >= 3 and 0.0 <= slo["attainment"] <= 1.0
        lat = st["latency"]["default"]
        assert lat["count"] >= 3
        assert 0.0 <= lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
        # the !stats protocol line and the C API surface the same document
        doc = json.loads(handle_line(srv, "!stats"))
        assert "slo" in doc and "latency" in doc
        from lightgbm_tpu import capi_impl
        cdoc = json.loads(capi_impl.server_stats_json(srv))
        assert set(cdoc) == set(st)
        assert cdoc["slo"]["default"]["requests"] == slo["requests"]
        assert cdoc["latency"]["default"]["count"] == lat["count"]
    finally:
        srv.close()
