"""Serving fleet (fleet/): multi-replica scale-out, SLO admission control,
canary/shadow rollout. Acceptance (ISSUE 18): a 2-replica fleet sustains
>= 1.7x the single-replica throughput under a closed-loop client load with
bit-exact responses; a perturbed canary trips the PSI comparator and
auto-rolls-back with zero dropped in-flight requests while the incumbent
keeps serving; a clean candidate auto-promotes after the drift-free window
via engine handoff (no rebuild, zero new lowerings on warmed replicas); a
rollback can never free an engine under an in-flight request."""
import json
import threading
import time

import numpy as np
import pytest

import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu.fleet.admission import (ADMIT, DEGRADE, SHED,
                                          AdmissionController)
from lightgbm_tpu.fleet.drift import (CANDIDATE, INCUMBENT,
                                      StreamingComparator)
from lightgbm_tpu.fleet.rollout import canary_name
from lightgbm_tpu.fleet.service import FleetServer
from lightgbm_tpu.fleet.store import ArtifactStore
from lightgbm_tpu.server import PredictServer, ServeOverload, handle_line
from lightgbm_tpu.utils.log import LightGBMError

N_FEAT = 8


@pytest.fixture(scope="module", autouse=True)
def _lockwatch_zero_inversions():
    """fleet/ joins the lock-order static scope; the runtime watchdog must
    agree after this suite's real balancer/rollout/admission concurrency."""
    from lightgbm_tpu.analysis import lockwatch
    yield
    lockwatch.WATCH.assert_clean("tests/test_fleet.py")


def _train(rounds=5, seed=11, target_col=1):
    """Deterministic booster: same args -> bit-identical model (each call
    uses its own RandomState, unlike test_server's shared-RNG helper)."""
    rng = np.random.RandomState(seed)
    X = rng.rand(500, N_FEAT)
    y = (X[:, 0] + X[:, target_col] > 1).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds)


@pytest.fixture(scope="module")
def boosters():
    live = _train()
    divergent = _train(seed=29, target_col=5)   # different concept -> drift
    clean = _train()                            # bit-identical to live
    return live, divergent, clean


@pytest.fixture(scope="module")
def queries():
    return np.random.RandomState(7).rand(64, N_FEAT)


def _mk_server(b, **conf):
    conf.setdefault("verbose", -1)
    conf.setdefault("serve_max_batch_rows", 64)
    return PredictServer(conf, model=b)


_CANARY_CONF = dict(canary_fraction=0.5, canary_min_samples=40,
                    canary_cmp_window=256, canary_psi_max=0.25,
                    canary_window_s=30.0)


# ---- drift comparator ----

def test_comparator_stable_vs_shifted():
    rng = np.random.RandomState(3)
    same = StreamingComparator(window=256)
    a = rng.rand(256)
    same.observe(INCUMBENT, a)
    same.observe(CANDIDATE, a + rng.rand(256) * 1e-3)
    assert same.psi() < 0.05
    assert same.ks() < 0.1
    shifted = StreamingComparator(window=256)
    shifted.observe(INCUMBENT, rng.rand(256))
    shifted.observe(CANDIDATE, rng.rand(256) + 0.5)
    assert shifted.psi() > 0.25
    assert shifted.ks() > 0.25
    snap = shifted.snapshot()
    assert snap["n_incumbent"] == snap["n_candidate"] == 256


def test_comparator_needs_min_samples():
    c = StreamingComparator(window=64, bins=10)
    c.observe(INCUMBENT, np.arange(9))
    c.observe(CANDIDATE, np.arange(9) + 10.0)
    assert c.psi() == 0.0   # below bins on both sides: no verdict yet


# ---- artifact store ----

def test_artifact_store_versioning(tmp_path, boosters):
    live, div, _ = boosters
    store = ArtifactStore(str(tmp_path))
    v1, p1 = store.put("m", live)
    v2, p2 = store.put("m", div)
    assert (v1, v2) == (1, 2) and p1 != p2
    assert store.latest_version("m") == 2
    assert store.current_path("m") == p2
    assert store.versions("m") == [1, 2]
    # the artifact round-trips: a Booster built from it predicts identically
    q = np.random.RandomState(1).rand(4, N_FEAT)
    assert np.array_equal(lgb.Booster(model_file=p1).predict(q),
                          live.predict(q))
    # path and raw-text forms are accepted too
    v3, _ = store.put("m", p1)
    v4, _ = store.put("m", open(p1).read())
    assert (v3, v4) == (3, 4)


# ---- admission control ----

class _FakeTracker:
    """slo.TRACKER stand-in: fixed burn rate, always active."""

    def __init__(self, burn=0.0):
        self.burn = burn
        self.active = True

    def snapshot(self):
        return {"default": {"burn_rate": self.burn, "attainment": 0.9}}


def test_admission_states_track_burn_rate():
    tr = _FakeTracker(0.5)
    ac = AdmissionController(burn_degrade=1.5, burn_shed=3.0, batch_cap=4,
                             ttl_s=0.0, tracker=tr)
    assert ac.decide("default") == ADMIT
    assert ac.batch_cap("default") is None
    tr.burn = 2.0
    assert ac.decide("default") == DEGRADE
    assert ac.batch_cap("default") == 4
    tr.burn = 5.0
    assert ac.decide("default") == SHED
    assert ac.note_shed("default") == 5.0
    tr.burn = 0.1
    assert ac.decide("default") == ADMIT
    snap = ac.snapshot()
    assert snap["stats"]["sheds"] == 1
    assert snap["stats"]["refreshes"] >= 4


def test_admission_shed_probes_and_recovers():
    """Shed must not latch: the tracker window only refreshes from completed
    requests, so while shed one in every N decide() calls is admitted as a
    probe — once probes measure good latencies the burn falls and the model
    recovers without operator intervention."""
    from lightgbm_tpu.fleet.admission import _PROBE_EVERY
    tr = _FakeTracker(9.0)
    ac = AdmissionController(ttl_s=0.0, tracker=tr)
    decisions = [ac.decide("default") for _ in range(3 * _PROBE_EVERY)]
    assert decisions.count(ADMIT) == 3          # exactly one probe per N
    assert decisions.count(SHED) == 3 * _PROBE_EVERY - 3
    assert ac.snapshot()["stats"]["probes"] == 3
    # probes complete with good latencies -> burn drops -> full admission
    tr.burn = 0.2
    assert ac.decide("default") == ADMIT
    assert all(ac.decide("default") == ADMIT for _ in range(_PROBE_EVERY))


def test_admission_from_config_gate():
    from lightgbm_tpu.config import params_to_config
    assert AdmissionController.from_config(
        params_to_config({"serve_admission": 0})) is None
    ac = AdmissionController.from_config(
        params_to_config({"admission_burn_degrade": 2.0,
                          "admission_burn_shed": 4.0,
                          "serve_degraded_batch_rows": 16}))
    assert (ac.burn_degrade, ac.burn_shed) == (2.0, 4.0)


def test_admission_shed_and_degrade_on_serve_path(boosters, queries):
    """shed rejects at ingress with ServeOverload before anything queues;
    degrade keeps serving (bit-exact) while capping coalesced flushes."""
    live, _, _ = boosters
    srv = _mk_server(live)
    tr = _FakeTracker(9.0)
    ac = AdmissionController(batch_cap=2, ttl_s=0.0, tracker=tr)
    try:
        srv.admission = srv.batcher._admission = ac
        with pytest.raises(ServeOverload):
            srv.predict(queries[0])
        assert srv.batcher.stats["admission_shed"] == 1
        tr.burn = 2.0   # degrade: admitted, flushes capped at 2 rows
        want = live.predict(queries)
        errs = []

        def client(i):
            try:
                got = srv.predict(queries[i])
                if got[0] != want[i]:
                    raise AssertionError(f"row {i}: {got[0]} != {want[i]}")
            except Exception as e:              # pragma: no cover
                errs.append(e)

        ths = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        [t.start() for t in ths]
        [t.join() for t in ths]
        assert not errs, errs
        assert ac.snapshot()["stats"]["degraded_flushes"] > 0
        tr.burn = 0.0   # budget recovered: full service
        assert np.array_equal(srv.predict(queries[:8]), want[:8])
    finally:
        srv.close()


# ---- fleet server: balanced replicas ----

def test_fleet_predicts_bit_exact_across_replicas(boosters, queries):
    live, _, _ = boosters
    fs = FleetServer({"verbose": -1, "fleet_replicas": 2,
                      "serve_max_batch_rows": 64}, model=live)
    try:
        assert len(fs.pool) == 2
        want = live.predict(queries)
        for n in (1, 2, 7, 33):
            assert np.array_equal(fs.predict(queries[:n]), want[:n]), n
        out, ver = fs.predict_versioned(queries[0])
        assert ver == 1 and out[0] == want[0]
        # both replicas hold the published model at the same version
        for r in fs.pool.replicas:
            assert r.registry.models()["default"]["version"] == 1
        snap = fs.fleet_stats()
        assert snap["mode"] == "inproc" and snap["replicas"] == 2
        assert snap["pool"]["routed"] >= 5
        assert fs.pool.check_health() == 2
    finally:
        fs.close()


def test_balancer_prefers_least_outstanding(boosters):
    live, _, _ = boosters
    fs = FleetServer({"verbose": -1, "fleet_replicas": 2,
                      "fleet_health_s": 0}, model=live)
    try:
        r0, r1 = fs.pool.replicas
        r0.outstanding = 5
        assert fs.pool.pick() is r1              # fewest outstanding wins
        fs.pool._done(r1)
        r1.healthy = False                       # red replica routed around
        assert fs.pool.pick() is r0
        fs.pool._done(r0)
        r0.healthy = False                       # all red: fail open
        assert fs.pool.pick() in (r0, r1)
    finally:
        fs.close()


def _closed_loop(fs, queries, want, seconds=1.2, n_threads=16):
    """n closed-loop clients for ``seconds``; every response is checked
    bit-exact against the booster. Returns total completed requests."""
    t_end = time.monotonic() + seconds
    done = [0] * n_threads
    errs = []

    def client(t):
        i = t
        try:
            while time.monotonic() < t_end:
                q = i % len(queries)
                got = fs.predict(queries[q])
                if got[0] != want[q]:
                    raise AssertionError(f"row {q}: {got[0]} != {want[q]}")
                done[t] += 1
                i += 1
        except Exception as e:              # pragma: no cover
            errs.append(e)

    ths = [threading.Thread(target=client, args=(t,))
           for t in range(n_threads)]
    [t.start() for t in ths]
    [t.join() for t in ths]
    assert not errs, errs
    return sum(done)


@pytest.mark.slow
def test_two_replicas_scale_throughput(boosters, queries):
    """2 paced replicas sustain >= 1.7x one replica's throughput, bit-exact.

    On a single-core host real parallel speedup is unmeasurable, so the
    capacity model is made explicit: serve_flush_interval_us paces each
    replica's scheduler to one bounded flush per interval (as on a real
    fleet where each replica's device bounds its flush rate), and adding a
    replica adds that much flush capacity. 16 closed-loop clients saturate
    both configurations."""
    live, _, _ = boosters
    conf = {"verbose": -1, "serve_flush_interval_us": 10000,
            "serve_max_batch_rows": 4, "serve_batch_window_us": 0,
            "fleet_health_s": 0.5}
    want = live.predict(queries)
    rates = {}
    for n in (1, 2):
        fs = FleetServer(dict(conf, fleet_replicas=n), model=live)
        try:
            _closed_loop(fs, queries, want, seconds=0.3)   # settle/warm
            rates[n] = _closed_loop(fs, queries, want, seconds=1.2)
            assert fs.pool.check_health() == n
        finally:
            fs.close()
    ratio = rates[2] / max(rates[1], 1)
    assert ratio >= 1.7, f"2-replica scaling only {ratio:.2f}x ({rates})"


def test_zero_new_lowerings_on_warmed_fleet(boosters, queries):
    """Publish-time warmup + shared module-level executables: once the
    fleet is warm, a request storm AND a re-publish lower zero new XLA
    programs (replicas share the per-bucket jits)."""
    live, _, _ = boosters
    fs = FleetServer({"verbose": -1, "fleet_replicas": 2,
                      "serve_max_batch_rows": 8}, model=live)
    try:
        for n in (1, 2, 4, 8):                # serve-path warmup per bucket
            fs.predict(queries[:n])
        with jtu.count_jit_and_pmap_lowerings() as count:
            def worker(t):
                for n in (1, 2, 4, 8):
                    fs.predict(queries[:n])
            ths = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
            [t.start() for t in ths]
            [t.join() for t in ths]
            fs.publish(live)                  # v2 fan-out: same buckets
            fs.predict(queries[:4])
        assert count() == 0, f"{count()} new lowerings on a warmed fleet"
    finally:
        fs.close()


# ---- canary / shadow rollout ----

def _drain_traffic(srv, ro, queries, want_live, n=400, deadline_s=30.0):
    """Single-row traffic until the rollout leaves its active state (or n
    requests, whichever is later); every response must be the incumbent's
    in shadow mode. Returns the number of requests served."""
    t_end = time.monotonic() + deadline_s
    i = 0
    while i < n or (ro.active and time.monotonic() < t_end):
        q = i % len(queries)
        out, ver = srv.predict_versioned(queries[q])
        assert ver == 1 and out[0] == want_live[q], (i, ver)
        i += 1
        if i % 64 == 0:
            ro.tick()
        if not ro.active and i >= n:
            break
    return i


def test_shadow_divergent_candidate_auto_rolls_back(boosters, queries):
    """Shadow rollout of a drifted candidate: zero user exposure (every
    response is the incumbent's, bit-exact), the PSI comparator trips, the
    candidate auto-rolls-back and drains, the incumbent keeps serving."""
    live, divergent, _ = boosters
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        want_live = live.predict(queries)
        ro = srv.ensure_rollout()
        v = ro.start(divergent, shadow=True)
        assert v == 1 and ro.state == "shadow"
        cname = canary_name("default")
        cand_engine = srv.registry.current(cname).engine
        served = _drain_traffic(srv, ro, queries, want_live)
        assert ro.state == "idle", ro.statusz()
        assert ro.stats["rolled_back"] == 1 and ro.stats["promoted"] == 0
        assert ro.history[-1]["event"] == "rollback"
        assert ro.history[-1]["psi"] > 0.25
        assert served >= 400                      # zero dropped in-flight
        with pytest.raises(KeyError):
            srv.registry.current(cname)           # candidate is gone...
        _wait_released(cand_engine)               # ...and drained+freed
        out, ver = srv.predict_versioned(queries[0])
        assert ver == 1 and out[0] == want_live[0]    # incumbent unharmed
    finally:
        srv.close()


def _wait_released(engine, timeout=10.0):
    t_end = time.monotonic() + timeout
    while not engine.released and time.monotonic() < t_end:
        time.sleep(0.01)
    assert engine.released, "retired engine never freed after drain"


def test_clean_candidate_auto_promotes_via_engine_handoff(boosters, queries):
    """A drift-free candidate promotes after the clean window: the warmed
    canary engine is re-homed as the live version — same engine object, no
    rebuild, zero new lowerings, and it keeps serving bit-exact."""
    live, _, clean = boosters
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        want = live.predict(queries)
        ro = srv.ensure_rollout()
        t = [1000.0]
        ro.clock = lambda: t[0]                   # injected, test-stable
        ro.start(clean)                           # canary mode, fraction .5
        cand_engine = srv.registry.current(canary_name("default")).engine
        i = 0
        while min(*ro.comparator.counts()) < ro.min_samples:
            out = srv.predict(queries[i % len(queries)])
            assert out[0] == want[i % len(queries)]   # clean: bit-identical
            i += 1
            assert i < 5000
        time.sleep(0.05)                          # let the last taps land
        with jtu.count_jit_and_pmap_lowerings() as count:
            assert ro.tick() == "canary"          # clean tick opens window
            t[0] += ro.window_s + 1.0
            assert ro.tick() == "idle"            # window elapsed: promote
            srv.predict(queries[:1])
        assert count() == 0, "promote must not rebuild or re-lower"
        assert ro.stats["promoted"] == 1 and ro.stats["rolled_back"] == 0
        live_sm = srv.registry.current("default")
        assert live_sm.version == 2
        assert live_sm.engine is cand_engine      # handoff, not a rebuild
        assert not cand_engine.released
        with pytest.raises(KeyError):
            srv.registry.current(canary_name("default"))
        out, ver = srv.predict_versioned(queries[3])
        assert ver == 2 and out[0] == want[3]
    finally:
        srv.close()


def test_superseding_canary_rolls_back_the_old_one(boosters):
    live, divergent, clean = boosters
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        ro = srv.ensure_rollout()
        ro.start(divergent, shadow=True)
        ro.start(clean)                           # supersedes: old rolls back
        assert ro.stats["started"] == 2
        assert ro.stats["rolled_back"] == 1
        assert ro.history[0]["reason"] == "superseded"
        assert ro.state == "canary"
        ro.rollback()
        assert not ro.active
        with pytest.raises(LightGBMError):
            ro.promote()                          # nothing active
    finally:
        srv.close()


def test_candidate_route_falls_back_to_incumbent_after_rollback(boosters,
                                                                queries):
    """A request staged for the candidate can lose the race with a
    concurrent rollback (cname unpublished between the routing decision and
    the flush). It must be served by the incumbent, bit-exact — a rollback
    never surfaces as a client error."""
    live, divergent, _ = boosters
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        want = live.predict(queries)
        ro = srv.ensure_rollout()
        ro.start(divergent, fraction=1.0)          # every request -> canary
        # simulate the race: the candidate vanishes behind the router's back
        srv.registry.unpublish(ro.cname)
        for i in range(4):
            out = srv.predict(queries[i])
            assert out[0] == want[i]
        assert srv.batcher.stats["canary_fallback"] == 4
        assert ro.stats["routed_candidate"] == 4   # routing still chose it
        # a model with no base entry at all still fails loudly
        with pytest.raises(KeyError):
            srv.predict(queries[0], model="nosuch@canary")
    finally:
        srv.close()


# ---- rollback vs in-flight refcount (satellite: registry drain) ----

def test_rollback_never_frees_engine_under_inflight(boosters, queries):
    """Registry-level drain contract: an acquired canary version survives
    rollback until its refcount drops; the free happens at release, never
    under the in-flight holder."""
    live, divergent, _ = boosters
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        ro = srv.ensure_rollout()
        ro.start(divergent, shadow=True)
        cname = canary_name("default")
        sm = srv.registry.acquire(cname)          # simulated in-flight flush
        eng = sm.engine
        ro.rollback()
        assert sm.retired and not eng.released
        srv.registry.release(sm)                  # last holder drops out
        assert eng.released
    finally:
        srv.close()


def test_rollback_from_completion_callback_mid_flight(boosters, queries):
    """End-to-end drain: a request is in flight ON the candidate when its
    own completion callback trips the rollback (the on_done tap runs on the
    scheduler thread before the flush releases its refcount). The response
    still arrives bit-exact and the engine is freed only after the flush
    drains."""
    live, divergent, _ = boosters
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        ro = srv.ensure_rollout()
        ro.start(divergent, shadow=True)
        cname = canary_name("default")
        eng = srv.registry.current(cname).engine
        released_in_cb = []

        def cb(req):
            ro.rollback()                         # fires under the flush
            released_in_cb.append(eng.released)

        req = srv.batcher.submit_async(queries[0], model=cname, on_done=cb)
        out = req.result(30.0)
        assert out[0] == divergent.predict(queries[:1])[0]
        assert released_in_cb == [False], \
            "engine freed while its flush was still in flight"
        assert not ro.active
        _wait_released(eng)                       # freed after the drain
    finally:
        srv.close()


# ---- pool-level rollout (fleet backend) ----

def test_fleet_canary_promote_fans_across_replicas(boosters, queries):
    live, _, clean = boosters
    fs = FleetServer(dict(_CANARY_CONF, verbose=-1, fleet_replicas=2),
                     model=live)
    try:
        ro = fs.ensure_rollout()
        ro.start(clean)
        cname = canary_name("default")
        cand_engines = [r.registry.current(cname).engine
                        for r in fs.pool.replicas]
        ro.promote(reason="manual")
        for r, eng in zip(fs.pool.replicas, cand_engines):
            sm = r.registry.current("default")
            assert sm.version == 2 and sm.engine is eng
            with pytest.raises(KeyError):
                r.registry.current(cname)
        want = clean.predict(queries)
        out, ver = fs.predict_versioned(queries[0])
        assert ver == 2 and out[0] == want[0]
    finally:
        fs.close()


def test_fleet_canary_rollback_drops_candidate_everywhere(boosters):
    live, divergent, _ = boosters
    fs = FleetServer(dict(_CANARY_CONF, verbose=-1, fleet_replicas=2),
                     model=live)
    try:
        ro = fs.ensure_rollout()
        ro.start(divergent, shadow=True)
        cname = canary_name("default")
        ro.rollback()
        for r in fs.pool.replicas:
            with pytest.raises(KeyError):
                r.registry.current(cname)
            assert r.registry.models()["default"]["version"] == 1
    finally:
        fs.close()


def test_fleet_store_shared_artifacts(tmp_path, boosters):
    live, _, _ = boosters
    fs = FleetServer({"verbose": -1, "fleet_replicas": 2,
                      "fleet_store": str(tmp_path)}, model=live)
    try:
        assert fs.store.latest_version("default") == 1
        fs.publish(live)
        assert fs.store.latest_version("default") == 2
        snap = fs.fleet_stats()
        assert snap["store"]["default"]["versions"] == [1, 2]
    finally:
        fs.close()


# ---- line protocol + C surface ----

def test_protocol_canary_promote_rollback_fleet_stats(tmp_path, boosters,
                                                      queries):
    live, divergent, clean = boosters
    cand_path = str(tmp_path / "cand.txt")
    divergent.save_model(cand_path)
    clean_path = str(tmp_path / "clean.txt")
    clean.save_model(clean_path)
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        resp = handle_line(srv, f"!canary {cand_path} 0.5 shadow")
        assert resp == "ok version=1 mode=shadow"
        stats = json.loads(handle_line(srv, "!fleet_stats"))
        assert stats["mode"] == "single"
        assert stats["rollout"]["state"] == "shadow"
        assert handle_line(srv, "!rollback") == "ok version=1"
        resp = handle_line(srv, f"!canary {clean_path}")
        assert resp == "ok version=1 mode=canary"
        assert handle_line(srv, "!promote") == "ok version=2"
        # data line serves off the promoted version
        line = ",".join("%.17g" % v for v in queries[0])
        ver, vals = handle_line(srv, line).split("\t")
        assert int(ver) == 2
        assert float(vals) == clean.predict(queries[:1])[0]
        assert handle_line(srv, "!rollback").startswith("error:")
    finally:
        srv.close()


def test_capi_fleet_surface(tmp_path, boosters):
    from lightgbm_tpu import capi_impl
    live, divergent, _ = boosters
    path = str(tmp_path / "cand.txt")
    divergent.save_model(path)
    srv = _mk_server(live, **_CANARY_CONF)
    try:
        assert capi_impl.server_promote(srv) == -1      # nothing active
        assert capi_impl.server_canary(srv, path, 0.5, 1) == 1
        stats = json.loads(capi_impl.server_fleet_stats_json(srv))
        assert stats["rollout"]["state"] == "shadow"
        assert capi_impl.server_rollback(srv) == 1
        assert capi_impl.server_canary(srv, path, 0.0, 0) == 1
        assert capi_impl.server_promote(srv) == 2
    finally:
        srv.close()


# ---- worker processes (SO_REUSEPORT fleet) ----

@pytest.mark.slow
def test_process_mode_workers_round_trip(tmp_path, boosters, queries):
    """Two worker processes behind the routed balancer: bit-exact versioned
    predictions, fan-out publish, health probes green, pool-level rollout
    is explicitly refused (workers own their rollout)."""
    live, divergent, _ = boosters
    p1 = str(tmp_path / "v1.txt")
    live.save_model(p1)
    p2 = str(tmp_path / "v2.txt")
    divergent.save_model(p2)
    fs = FleetServer({"verbose": -1, "fleet_replicas": 2,
                      "fleet_mode": "process", "fleet_health_s": 0.5,
                      "serve_max_batch_rows": 16}, model=p1)
    try:
        want1 = live.predict(queries)
        for i in (0, 1, 2, 3):
            out, ver = fs.predict_versioned(queries[i])
            assert ver == 1 and out[0] == want1[i], i
        assert fs.pool.check_health() == 2
        # the routed control connections must address workers individually
        # (the shared SO_REUSEPORT data port is kernel-balanced and cannot):
        # distinct ctl ports, and the fan-out publish lands exactly once on
        # EVERY worker — no double-publish, no stale replica
        assert len({r.ctl_port for r in fs.pool.replicas}) == 2
        assert fs.publish(p2) == 2
        for r in fs.pool.replicas:
            models = json.loads(r.request("!stats"))["models"]
            assert models["default"]["version"] == 2, r.rid
        want2 = divergent.predict(queries)
        out, ver = fs.predict_versioned(queries[5])
        assert ver == 2 and out[0] == want2[5]
        with pytest.raises(LightGBMError):
            fs.ensure_rollout()
        snap = fs.fleet_stats()
        assert snap["mode"] == "process" and snap["replicas"] == 2
    finally:
        fs.close()
