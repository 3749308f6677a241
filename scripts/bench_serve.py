"""Closed-loop serving bench: SERVE_BENCH.json.

Measures the request-coalescing microbatcher (server.py) against the
uncoalesced baseline it exists to beat — one device dispatch per single-row
request (an earlier runtime recorded that baseline at ~31 rows/s on a v5e:
~30ms of dispatch+transfer amortized over one row).

Three sections:

- ``uncoalesced``: sequential single-row ``PredictEngine.predict`` calls —
  the per-dispatch floor on THIS backend (the honest denominator for the
  coalescing win; the recorded TPU 31 rows/s is kept as a reference point).
- ``load_points``: closed-loop sweep — N client threads, each submitting
  single-row requests back-to-back for a fixed wall window. Per point:
  achieved QPS, latency percentiles (p50/p99/p999), and the coalesce factor
  (rows per device dispatch) from the scheduler's own telemetry.
- ``overload``: graceful degradation — a tiny bounded queue is flooded with
  async submits; the JSON records how many were shed (ServeOverload) vs
  served, and that every ADMITTED request completed. Bounded queue =>
  bounded latency; load beyond capacity fails fast instead of stretching
  tails.

Two fleet sections (fleet/):

- ``fleet``: replica-scaling sweep — 1/2/4 paced replicas x 8/64/128
  closed-loop clients through the least-outstanding balancer. Pacing
  (``serve_flush_interval_us``) makes per-replica capacity explicit, so the
  sweep measures the scale-out law and p99 SLO attainment under overload
  rather than single-core scheduling noise.
- ``canary_drill``: mid-load rollout — under sustained 2-replica load, a
  perturbed candidate enters in shadow mode and must auto-roll-back on PSI
  divergence with zero client errors; then a clean candidate enters in
  canary mode and must auto-promote after the drift-free window.

Usage: python scripts/bench_serve.py [--quick] [out.json]
Env: LGBM_TPU_SERVE_BENCH_SECONDS / _CLIENTS / _REPLICAS / _FLEET_CLIENTS
     (comma lists) / _ROWS / _ITERS
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CLIENT_SWEEP = [int(c) for c in os.environ.get(
    "LGBM_TPU_SERVE_BENCH_CLIENTS", "1,8,64").split(",")]
REPLICA_SWEEP = [int(r) for r in os.environ.get(
    "LGBM_TPU_SERVE_BENCH_REPLICAS", "1,2,4").split(",")]
FLEET_CLIENTS = [int(c) for c in os.environ.get(
    "LGBM_TPU_SERVE_BENCH_FLEET_CLIENTS", "8,64,128").split(",")]
SECONDS = float(os.environ.get("LGBM_TPU_SERVE_BENCH_SECONDS", 2.0))
TRAIN_ROWS = int(os.environ.get("LGBM_TPU_SERVE_BENCH_ROWS", 20_000))
TRAIN_ITERS = int(os.environ.get("LGBM_TPU_SERVE_BENCH_ITERS", 20))


def _percentiles(lat):
    import numpy as np
    if not lat:
        return {"p50_ms": None, "p99_ms": None, "p999_ms": None,
                "max_ms": None}
    a = np.asarray(sorted(lat))
    return {
        "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 4),
        "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 4),
        "p999_ms": round(float(np.percentile(a, 99.9)) * 1e3, 4),
        "max_ms": round(float(a[-1]) * 1e3, 4),
    }


def run(out_path=None, quick=False):
    import numpy as np
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.server import PredictServer, ServeOverload

    seconds = 0.5 if quick else SECONDS
    rows = min(TRAIN_ROWS, 5_000) if quick else TRAIN_ROWS
    iters = min(TRAIN_ITERS, 5) if quick else TRAIN_ITERS

    from bench import synth_higgs
    X, y = synth_higgs(rows)
    params = {"objective": "binary", "num_leaves": 63, "max_bin": 63,
              "learning_rate": 0.1, "verbose": -1, "prewarm": 0}
    print(f"# training {rows} rows x {iters} iters...", file=sys.stderr)
    booster = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        num_boost_round=iters)
    queries = X[:4096]

    # ---- uncoalesced baseline: one dispatch per single-row request ----
    # the sweep server runs with the live observability plane on: request
    # tracing (span breakdown) + a latency SLO, so the bench records
    # attainment and where the time goes, not just the percentiles
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import slo as obs_slo
    from lightgbm_tpu.obs.metrics import histogram_quantiles
    obs.configure(enabled=True)
    srv = PredictServer({"verbose": -1, "serve_trace": True,
                         "serve_trace_sample": 64, "serve_slo_ms": 50.0,
                         "serve_slo_target": 0.99}, model=booster)
    eng = srv.registry.current().engine
    for _ in range(5):
        eng.predict(queries[:1])               # warm the n=1 bucket
    t0 = time.perf_counter()
    n_base = 0
    while time.perf_counter() - t0 < min(seconds, 1.0):
        eng.predict(queries[n_base % 1024: n_base % 1024 + 1])
        n_base += 1
    uncoalesced_rps = n_base / (time.perf_counter() - t0)
    print(f"# uncoalesced single-row: {uncoalesced_rps:,.0f} rows/s",
          file=sys.stderr)

    def _drive(predict_one, n_clients, secs):
        """n closed-loop single-row clients for secs. A shed request
        (ServeOverload — queue or SLO admission control) backs the client
        off 5ms and retries: the well-behaved client the shed contract
        assumes. Returns (lat, sheds, errs, wall)."""
        lat, errs = [], []
        sheds = [0]
        lat_lock = threading.Lock()
        stop = threading.Event()
        barrier = threading.Barrier(n_clients + 1)

        def client(t):
            my = []
            my_sheds = 0
            try:
                barrier.wait()
                i = t
                while not stop.is_set():
                    q0 = time.perf_counter()
                    try:
                        predict_one(queries[i % len(queries)])
                        my.append(time.perf_counter() - q0)
                    except ServeOverload:
                        my_sheds += 1
                        time.sleep(0.005)
                    i += n_clients
            except Exception as e:             # pragma: no cover
                errs.append(repr(e))
            with lat_lock:
                lat.extend(my)
                sheds[0] += my_sheds

        ths = [threading.Thread(target=client, args=(t,))
               for t in range(n_clients)]
        [t.start() for t in ths]
        barrier.wait()
        t0 = time.perf_counter()
        time.sleep(secs)
        stop.set()
        [t.join() for t in ths]
        return lat, sheds[0], errs, time.perf_counter() - t0

    # ---- closed-loop sweep ----
    load_points = []
    for n_clients in CLIENT_SWEEP:
        st0 = srv.batcher.snapshot()
        lat, sheds, errs, wall = _drive(
            lambda r: srv.predict(r, timeout=60), n_clients, seconds)
        st1 = srv.batcher.snapshot()
        flushes = st1["flushes"] - st0["flushes"]
        flushed = st1["flushed_rows"] - st0["flushed_rows"]
        point = {
            "clients": n_clients,
            "requests": len(lat),
            "sheds": sheds,
            "wall_s": round(wall, 3),
            "qps": round(len(lat) / wall, 1),
            "coalesce_factor": round(flushed / flushes, 2) if flushes else 0.0,
            "flushes": flushes,
            "errors": errs[:3],
            **_percentiles(lat),
        }
        slo_snap = obs_slo.TRACKER.snapshot().get("default")
        if slo_snap:
            point["slo_attainment"] = round(slo_snap["attainment"], 4)
            point["slo_burn_rate"] = round(slo_snap["burn_rate"], 3)
        load_points.append(point)
        print(f"# {n_clients:3d} clients: {point['qps']:>9,.0f} qps  "
              f"p50 {point['p50_ms']}ms  p99 {point['p99_ms']}ms  "
              f"coalesce {point['coalesce_factor']}", file=sys.stderr)

    # span breakdown: p50 per serve-path span across the whole sweep
    span_breakdown = {}
    fam = obs.METRICS.get_family("span_seconds")
    if fam is not None:
        for key, hist in fam[1].items():
            name = dict(key).get("span", "")
            if name.startswith("serve."):
                q = histogram_quantiles(hist.snapshot(), (0.5,))
                span_breakdown[name] = {
                    "p50_ms": round(q[0.5] * 1e3, 4),
                    "count": hist.snapshot()["count"]}
    srv.close()

    # ---- overload: bounded queue sheds, admitted requests all complete ----
    osrv = PredictServer({"verbose": -1, "serve_queue_max": 64,
                          "serve_batch_window_us": 2000}, model=booster)
    shed = admitted = 0
    reqs = []
    for i in range(2000):
        try:
            reqs.append(osrv.batcher.submit_async(queries[i % 1024]))
            admitted += 1
        except ServeOverload:
            shed += 1
    served = sum(1 for r in reqs if r.result(timeout=60) is not None)
    odepth = osrv.batcher.snapshot()["max_queue_depth"]
    osrv.close()
    overload = {
        "offered": 2000, "queue_max": 64, "admitted": admitted,
        "shed": shed, "served_of_admitted": served,
        "max_queue_depth": odepth,
        "all_admitted_served": served == admitted,
    }
    print(f"# overload: {shed}/2000 shed, {served}/{admitted} admitted "
          f"served, max depth {odepth}", file=sys.stderr)

    # ---- fleet sweep: replicas x clients through the balancer ----
    from lightgbm_tpu.fleet.service import FleetServer

    # pacing makes per-replica capacity explicit (one bounded flush per
    # interval, as each replica's device would on a real fleet). The
    # interval must clear the per-dispatch cost on this host (~20-25ms on
    # CPU) or replicas just contend for the core: 16 rows per 50ms flush =
    # 320 rows/s per replica at ~half a core, so added replicas raise the
    # ceiling and the sweep measures the scale-out law rather than
    # single-core scheduling noise. The SLO budget matches the pacing (a
    # request waits up to one interval plus the dispatch by design).
    fleet_conf = {"verbose": -1, "serve_flush_interval_us": 50000,
                  "serve_max_batch_rows": 16, "serve_batch_window_us": 0,
                  "serve_slo_ms": 250.0, "serve_slo_target": 0.99,
                  "fleet_health_s": 1.0}
    # the SLO tracker is process-global: reset between configurations (and
    # between points) so one overloaded point's breach window can't latch
    # admission shed into the next measurement
    def _slo_reset():
        obs_slo.TRACKER.reset()
        obs_slo.TRACKER.configure(slo_ms=fleet_conf["serve_slo_ms"],
                                  target=fleet_conf["serve_slo_target"])

    fleet_points = []
    for n_rep in REPLICA_SWEEP:
        obs_slo.TRACKER.reset()      # FleetServer.__init__ re-configures
        fs = FleetServer(dict(fleet_conf, fleet_replicas=n_rep),
                         model=booster)
        try:
            _drive(fs.predict, 4, 0.3)             # settle the pacing clock
            for n_clients in FLEET_CLIENTS:
                _slo_reset()
                lat, sheds, errs, wall = _drive(fs.predict, n_clients,
                                                seconds)
                point = {"replicas": n_rep, "clients": n_clients,
                         "requests": len(lat), "sheds": sheds,
                         "wall_s": round(wall, 3),
                         "qps": round(len(lat) / wall, 1),
                         "errors": errs[:3], **_percentiles(lat)}
                slo_snap = obs_slo.TRACKER.snapshot().get("default")
                if slo_snap:
                    point["slo_attainment"] = round(slo_snap["attainment"], 4)
                    point["slo_burn_rate"] = round(slo_snap["burn_rate"], 3)
                fleet_points.append(point)
                print(f"# fleet {n_rep}r x {n_clients:3d}c: "
                      f"{point['qps']:>8,.0f} qps  p99 "
                      f"{point['p99_ms']}ms  slo "
                      f"{point.get('slo_attainment', '-')}", file=sys.stderr)
        finally:
            fs.close()

    def _fleet_best(n_rep):
        pts = [p["qps"] for p in fleet_points if p["replicas"] == n_rep]
        return max(pts) if pts else None

    fleet = {
        "pacing_us": fleet_conf["serve_flush_interval_us"],
        "max_batch_rows": fleet_conf["serve_max_batch_rows"],
        "points": fleet_points,
        "best_qps_by_replicas": {str(r): _fleet_best(r)
                                 for r in REPLICA_SWEEP},
    }
    if _fleet_best(1) and _fleet_best(2):
        fleet["scaling_2x"] = round(_fleet_best(2) / _fleet_best(1), 2)
    if _fleet_best(1) and _fleet_best(4):
        fleet["scaling_4x"] = round(_fleet_best(4) / _fleet_best(1), 2)

    # ---- canary drill: rollout transitions under sustained load ----
    # a perturbed candidate (trained on near-constant random labels, so its
    # score mass sits far from the live model's) must trip PSI and
    # auto-roll-back with zero client errors; a clean (bit-identical
    # retrain) candidate must auto-promote
    print("# canary drill: training perturbed + clean candidates...",
          file=sys.stderr)
    y_pert = (np.random.RandomState(0).rand(len(y)) < 0.05).astype(float)
    perturbed = lgb.train(params,
                          lgb.Dataset(X, label=y_pert, params=params),
                          num_boost_round=max(2, iters // 4))
    clean = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                      num_boost_round=iters)
    # admission stays off for the drill: candidate build+warm compiles on
    # the same cores that serve, and that stall would breach the SLO and
    # shed the very traffic the comparator needs (the sweep above already
    # exercises admission under overload)
    obs_slo.TRACKER.reset()
    fs = FleetServer(dict(fleet_conf, fleet_replicas=2, serve_admission=0,
                          canary_fraction=0.5,
                          canary_min_samples=200, canary_cmp_window=512,
                          canary_psi_max=0.25, canary_window_s=1.0),
                     model=booster)
    drill = {"requests": 0, "client_errors": []}
    try:
        ro = fs.ensure_rollout()
        lat, errs = [], []
        sheds = [0]
        lat_lock = threading.Lock()
        stop = threading.Event()

        def client(t):
            # random query choice per request: deterministic cycling would
            # correlate with the router's deterministic canary sampling and
            # feed the two comparator sides biased query subsets
            rs_c = np.random.RandomState(1000 + t)
            my = []
            my_sheds = 0
            try:
                while not stop.is_set():
                    q0 = time.perf_counter()
                    try:
                        fs.predict(queries[rs_c.randint(len(queries))])
                        my.append(time.perf_counter() - q0)
                    except ServeOverload:
                        my_sheds += 1
                        time.sleep(0.005)
            except Exception as e:             # pragma: no cover
                errs.append(repr(e))
            with lat_lock:
                lat.extend(my)
                sheds[0] += my_sheds

        ths = [threading.Thread(target=client, args=(t,)) for t in range(8)]
        [t.start() for t in ths]
        time.sleep(0.3)                        # load established
        t0 = time.perf_counter()
        ro.start(perturbed, shadow=True)
        while ro.active and time.perf_counter() - t0 < 30.0:
            time.sleep(0.05)
            ro.tick()
        drill["rollback_s"] = round(time.perf_counter() - t0, 3)
        drill["rolled_back"] = ro.stats["rolled_back"] == 1
        t0 = time.perf_counter()
        ro.start(clean)
        while ro.active and time.perf_counter() - t0 < 30.0:
            time.sleep(0.05)
            ro.tick()
        drill["promote_s"] = round(time.perf_counter() - t0, 3)
        drill["promoted"] = ro.stats["promoted"] == 1
        stop.set()
        [t.join() for t in ths]
        drill["requests"] = len(lat)
        drill["sheds"] = sheds[0]
        drill["client_errors"] = errs[:3]
        drill["zero_client_errors"] = not errs
        drill["final_version"] = \
            fs.pool.replicas[0].registry.current("default").version
        drill["rollout_stats"] = dict(ro.stats)
        drill["rollout_history"] = list(ro.history)
        print(f"# canary drill: rollback in {drill['rollback_s']}s, "
              f"promote in {drill['promote_s']}s, {len(lat)} requests, "
              f"{len(errs)} errors", file=sys.stderr)
    finally:
        fs.close()

    best_qps = max(p["qps"] for p in load_points)
    p64 = next((p for p in load_points if p["clients"] == 64), None)
    result = {
        "bench": "serve_microbatch",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "cores": os.cpu_count() or 1,
        "quick": bool(quick),
        "model": {"rows": rows, "iters": iters, "num_leaves": 63,
                  "max_bin": 63, "features": int(X.shape[1])},
        "seconds_per_point": seconds,
        "uncoalesced_single_row_rps": round(uncoalesced_rps, 1),
        "recorded_tpu_uncoalesced_rps": 31.0,
        "load_points": load_points,
        "span_breakdown": span_breakdown,
        "overload": overload,
        "fleet": fleet,
        "canary_drill": drill,
        "best_qps": best_qps,
        "speedup_vs_uncoalesced": round(best_qps / uncoalesced_rps, 2),
        "speedup_vs_recorded_31rps": round(best_qps / 31.0, 1),
        "qps_64_clients": p64["qps"] if p64 else None,
    }
    doc = json.dumps(result, indent=2)
    if out_path:
        from lightgbm_tpu.utils.atomic_io import atomic_write_text
        atomic_write_text(out_path, doc + "\n")
    print(doc)
    return result


if __name__ == "__main__":
    argv = [a for a in sys.argv[1:] if a != "--quick"]
    run(argv[0] if argv else None, quick=len(argv) < len(sys.argv) - 1)
