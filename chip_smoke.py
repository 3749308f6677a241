"""Chip smoke: the HIGGS-width training path, end to end, on one TPU.

Drives ``lgb.Dataset`` -> ``lgb.train`` (validation set, AUC every
iteration) -> ``Booster.predict`` -> ``save_model`` / reload / predict, in
ONE process, at the full width the product trains at (28 features,
255 leaves, 63 bins); only the row count is cut. It then checks that the
device was used the way the product intends (Pallas kernels, quantised
gradients, fused front, prewarm adopted, native encoder, nothing placed on
the host backend) and runs the compiled-kernel equivalence checks of
tests/_tpu_kernel_check.py in-process. With four or more devices it repeats
the training row-sharded over four of them.

The last two stdout lines are JSON objects: first the observations of this
run (ending ``"claim": null`` -- they are not benchmark results), then, as
the very last line, the verdict and nothing else:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Exit code 0 only when every phase passed; a failed phase prints the verdict
with ``"ok": false`` and re-raises. Without a TPU backend, or without the
package beside it, it exits non-zero and prints no verdict at all.

    python chip_smoke.py
"""
import importlib.util
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

N_FEATURES = 28
N_TRAIN = 4_000_000     # two chunks at the default ingest_chunk_rows, above
N_VALID = 500_000       # prewarm's 200k-row gate
ROUNDS = 20
STEADY_ROUNDS = 15      # iters/s is taken over the last 15 iterations
MIN_VALID_AUC = 0.78
PARAMS = {
    "objective": "binary",
    "num_leaves": 255,
    "max_bin": 63,
    "learning_rate": 0.1,
    "min_data_in_leaf": 20,
    "metric": "auc",
    "verbosity": -1,
    "telemetry": True,   # the obs events below are the smoke's evidence
    # stated, not defaulted: 0 means "all local devices", which on a
    # four-chip host is a different (row-sharded) path
    "num_shards": 1,
}
HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)
    print(f"[smoke] ok: {what}", flush=True)


def synth_higgs(n_rows, seed):
    """bench.py's synth_higgs signal (a few nonlinear informative
    combinations, the rest noise) from a float32 generator: seconds per
    10M rows where RandomState.randn().astype took 39 s."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n_rows, N_FEATURES), dtype=np.float32)
    w = np.random.default_rng(0).standard_normal(8).astype(np.float32)
    logits = (X[:, :8] @ w) * 0.7 + 0.5 * np.abs(X[:, 8]) * X[:, 9] \
        - 0.4 * (X[:, 10] ** 2) + 0.3
    p = 1.0 / (1.0 + np.exp(-logits))
    y = (rng.random(n_rows, dtype=np.float32) < p).astype(np.float32)
    return X, y


def auc(y, p):
    """ROC AUC from average ranks, in float64 on the host."""
    order = np.argsort(p, kind="stable")
    ps = p[order]
    start = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1]])
    end = np.r_[start[1:], len(ps)]
    rank_sorted = np.repeat((start + end + 1) / 2.0, end - start)
    pos = y[order] > 0
    n_pos = float(pos.sum())
    n_neg = float(len(y) - n_pos)
    return (rank_sorted[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)


class IterClock:
    """train() callback: wall time at the end of each iteration (the metric
    evaluation before it reads the device, so the time is not an enqueue)."""

    def __init__(self):
        self.t = []

    def __call__(self, env):
        self.t.append(time.perf_counter())


def events(obs, etype, **match):
    return [e for e in obs.EVENTS.snapshot() if e["type"] == etype
            and all(e.get(k) == v for k, v in match.items())]


def train_once(lgb, obs, data, num_shards):
    """Dataset -> train -> the facts every phase shares."""
    X, y, Xv, yv = data
    params = {**PARAMS, "num_shards": num_shards}
    obs.reset()
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params).construct()
    bin_s = time.perf_counter() - t0
    valid = ds.create_valid(Xv, label=yv)
    clock = IterClock()
    evals = {}
    t_train = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=ROUNDS, valid_sets=[valid],
                    evals_result=evals, verbose_eval=False,
                    callbacks=[clock])
    aucs = evals["valid_0"]["auc"]
    check(len(aucs) == ROUNDS and len(clock.t) == ROUNDS,
          f"{ROUNDS} iterations ran with the metric evaluated on each")
    faults_seen = events(obs, "device_fault")
    check(not faults_seen, f"zero device_fault events ({faults_seen})")
    cold = events(obs, "compile", what="fused_step_aot", key="cold")
    return {
        "ds": ds, "bst": bst, "valid_auc": float(aucs[-1]),
        "bin_s": round(bin_s, 2),
        "compile_s": round(cold[0]["duration_s"], 2) if cold else None,
        "first_iter_s": round(clock.t[0] - t_train, 2),
        "steady_iters_per_s": round(
            STEADY_ROUNDS / (clock.t[-1] - clock.t[-1 - STEADY_ROUNDS]), 3),
        "prewarm_hit": bool(events(obs, "aot_prewarm", phase="adopted")),
        "dispatch_compiles": len(events(obs, "compile", what="fused_step")),
    }


def device_facts(obs, run):
    """The device was used the way the product intends."""
    from lightgbm_tpu import native
    from lightgbm_tpu.ops.histogram import pick_impl
    ds, gbdt = run["ds"], run["bst"]._gbdt
    check(pick_impl("auto") == "pallas", "pick_impl('auto') == 'pallas'")
    check(gbdt.gp.quant, "int8 quantised gradients are on (gp.quant)")
    check(gbdt._fused_front()[0] is not None,
          f"fused grad+quant+hist0 front engaged: {gbdt._fused_front()[0]}")
    check(gbdt._use_bt(), "cached transposed bin matrix in use (_use_bt)")
    if not run["prewarm_hit"]:
        why = [e.get("reason") for e in events(obs, "aot_prewarm")
               if e.get("phase") in ("miss", "skipped", "error")]
        raise SmokeFailure(f"prewarm not adopted: {why}")
    check(gbdt._step_aot is not None and gbdt._aot_dispatches == ROUNDS
          and run["dispatch_compiles"] == 0,
          f"prewarmed executable served all {ROUNDS} dispatches "
          f"({gbdt._aot_dispatches}), none compiled at dispatch")
    n_mosaic = gbdt._step_aot.as_text().count("tpu_custom_call")
    check(n_mosaic > 0, f"compiled fused step holds {n_mosaic} Mosaic "
                        "custom calls (tpu_custom_call)")
    for name, arr in (("ds.bins", ds.bins), ("train score", gbdt.train_score)):
        plats = sorted({d.platform for d in arr.devices()})
        check(plats == ["tpu"], f"{name} lives on {plats}")
    check(native.get_lib() is not None, "native fastio library built and loaded")
    check(ds.construct_phases.get("encoder") == "native",
          f"encoder = {ds.construct_phases.get('encoder')}")
    return n_mosaic


def predict_roundtrip(lgb, run, data):
    _, _, Xv, yv = data
    bst = run["bst"]
    pred = bst.predict(Xv)          # cold: compiles the predict programs
    t0 = time.perf_counter()
    pred2 = bst.predict(Xv)
    predict_s = time.perf_counter() - t0
    check(pred.shape == (N_VALID,) and np.isfinite(pred).all(),
          "predictions finite, one per validation row")
    check(np.array_equal(pred, pred2), "repeat predict is identical")
    host_auc = auc(yv, pred)
    check(host_auc >= MIN_VALID_AUC and run["valid_auc"] >= MIN_VALID_AUC,
          f"valid AUC {run['valid_auc']:.6f} >= {MIN_VALID_AUC}")
    # two routes to the same leaves: binned rows inside training, raw
    # thresholds through the PredictEngine
    check(abs(host_auc - run["valid_auc"]) <= 1e-4,
          f"AUC from Booster.predict {host_auc:.6f} agrees with the last "
          f"in-training value {run['valid_auc']:.6f} to 1e-4")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.txt")
        bst.save_model(path)
        reloaded = lgb.Booster(model_file=path)
    check(np.array_equal(reloaded.predict(Xv), pred),
          "save_model -> Booster(model_file=) predictions equal the originals")
    return round(N_VALID / predict_s)


def kernel_checks():
    spec = importlib.util.spec_from_file_location(
        "_tpu_kernel_check", os.path.join(HERE, "tests", "_tpu_kernel_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run_all()


def multichip(jax, lgb, obs, data, one_shard):
    """Same widths, row-sharded over four chips, in the same process."""
    if jax.device_count() < 4:
        print(f"multichip: not run ({jax.device_count()} devices)", flush=True)
        return f"not run ({jax.device_count()} devices)"
    run = train_once(lgb, obs, data, num_shards=4)
    shards = run["ds"].bins.addressable_shards
    rows = [int(s.data.shape[0]) for s in shards]
    check(len(shards) == 4 and len({s.device for s in shards}) == 4
          and all(abs(r - N_TRAIN / 4) <= 8 for r in rows),
          f"binned matrix: {len(shards)} shards of {rows} rows on "
          f"{sorted(str(s.device) for s in shards)}")
    check(abs(run["valid_auc"] - one_shard["valid_auc"]) <= 1e-3,
          f"4-shard valid AUC {run['valid_auc']:.6f} within 1e-3 of 1-shard "
          f"{one_shard['valid_auc']:.6f}")
    same = (run["bst"].model_to_string()
            == one_shard["bst"].model_to_string())
    print(f"[smoke] 4-shard model string identical to 1-shard: {same}",
          flush=True)
    return {k: run[k] for k in ("valid_auc", "bin_s", "compile_s",
                                "first_iter_s", "steady_iters_per_s",
                                "prewarm_hit")} | {"model_identical": same}


def main():
    t_start = time.perf_counter()
    import jax
    backend = jax.default_backend()
    print(f"[smoke] jax {jax.__version__} backend={backend} "
          f"devices={jax.devices()}", flush=True)
    if backend != "tpu":
        sys.exit(f"chip_smoke: backend is {backend!r}, not 'tpu'")
    dev = jax.devices()[0]
    print(f"[smoke] device_kind={dev.device_kind}", flush=True)

    # persistent-cache traffic as jax reports it: a "miss" is a program
    # compiled and written (>= 1 s of compile), a "hit" one read back. The
    # totals say little (a cold run reads back what it wrote itself), so the
    # fused step's own compile is told apart by the thread it runs on
    cache = {"hits": 0, "misses": 0, "step_hits": 0, "step_misses": 0}

    def on_event(name, **_):
        kind = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}.get(name)
        if kind:
            cache[kind] += 1
            if threading.current_thread().name == "aot-prewarm":
                cache["step_" + kind] += 1
    jax.monitoring.register_event_listener(on_event)

    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    obs.configure(enabled=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        summary = phases(jax, lgb, obs, cache)
    except BaseException:
        print(json.dumps({"ok": False, "device": device}), flush=True)
        raise
    summary.update(backend=backend, jax=jax.__version__,
                   wall_s=round(time.perf_counter() - t_start, 1), claim=None)
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def phases(jax, lgb, obs, cache):
    """Every phase in order; returns the run's observations."""
    t0 = time.perf_counter()
    data = synth_higgs(N_TRAIN, seed=1) + synth_higgs(N_VALID, seed=2)
    print(f"[smoke] generated {N_TRAIN}+{N_VALID} x {N_FEATURES} rows in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    run = train_once(lgb, obs, data, num_shards=1)
    n_mosaic = device_facts(obs, run)
    predict_rows_per_s = predict_roundtrip(lgb, run, data)
    checks = kernel_checks()
    multi = multichip(jax, lgb, obs, data, run)
    # the package's placement rule, checked from outside it
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(HERE, ".jax_cache"))
    check(os.path.isdir(cache_dir) and os.listdir(cache_dir),
          f"compile cache written to {cache_dir}")

    return {
        "rows": N_TRAIN, "valid_rows": N_VALID, "iterations": ROUNDS,
        "bin_s": run["bin_s"],
        "compile_s": run["compile_s"],
        "first_iter_s": run["first_iter_s"],
        "prewarm_hit": run["prewarm_hit"],
        "steady_iters_per_s": run["steady_iters_per_s"],
        "valid_auc": round(run["valid_auc"], 6),
        "predict_rows_per_s": predict_rows_per_s,
        "mosaic_custom_calls": n_mosaic,
        "kernel_checks": len(checks),
        # hit = every fused train step was read back, none compiled
        "compile_cache": {"dir": cache_dir,
                          "hit": cache["step_hits"] > 0
                          and cache["step_misses"] == 0, **cache},
        "multichip": multi,
    }


if __name__ == "__main__":
    main()
