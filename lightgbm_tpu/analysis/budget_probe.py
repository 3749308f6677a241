"""Measure distinct jit lowerings per warmed entry point.

Run as ``python -m lightgbm_tpu.analysis.budget_probe`` in a FRESH process
(the compile-budget rule and ``--update-budget`` both launch it via
subprocess): jit caches are process-global, so in-process measurement would
credit earlier work against later entries. Prints a single JSON line
``{"counts": {...}}`` on stdout.

Workload is fixed and tiny (512x16, 7 leaves, 3 iters, binary objective,
prewarm off) so the counts are exact, deterministic, and CPU-cheap. The
``predict_warm_repeat`` entry re-runs predict on the same shapes and MUST
measure 0 — it is the per-call-jit canary: any lowering there means a jit
wrapper is being rebuilt per call instead of reused.

Beyond the plain-gbdt quartet the probe guards the rest of the optimized
surface:

- ``train_3_iters_lossguide``: the leaf-wise grower's step program (the
  default quartet trains depthwise);
- ``train_warm_extra2_{dart,goss,rf}``: two EXTRA iterations on an
  already-warmed booster of each non-gbdt flavour, budgeted at 0 — DART's
  drop/normalize reweighting, GOSS's gradient-dependent bagging and RF's
  averaging custom step must all reuse their warmed wrappers;
- ``predict_engine_warm``: serving predicts at row counts whose buckets
  ``PredictEngine.warmup`` pre-compiled, budgeted at 0.

``--multihost`` runs the pod-surface probe instead: the 2-D
``("data","feature")`` mesh and voting-parallel step programs on a
4-virtual-device backend. It is a separate invocation because
``--xla_force_host_platform_device_count`` must be set before jax imports;
the compile-budget rule launches both and merges the counts.
"""
from __future__ import annotations

import json
import os
import sys


def measure() -> dict:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the persistent compile cache skips lowering-count measurement neither
    # way (counters hook lowering, not compilation), but keep the run
    # hermetic: no telemetry, no lint-only mode
    os.environ.pop("LGBMTPU_LINT_ONLY", None)

    import numpy as np
    import jax  # noqa: F401  (force backend init before counting)
    import jax._src.test_util as jtu

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.rand(512, 16).astype(np.float32)
    y = (rng.rand(512) > 0.5).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1, "prewarm": 0}

    counts = {}

    # warm the trivial-jit plumbing (device placement, singleton helpers) so
    # entry-point counts measure the entry point, not backend bring-up
    # one-shot by construction (runs once per probe process)
    jax.jit(lambda a: a + 1)(np.float32(0)).block_until_ready()  # tpu-lint: disable=retrace-hazard

    with jtu.count_jit_and_pmap_lowerings() as n:
        train_set = lgb.Dataset(X, label=y, params=params)
        train_set.construct()
    counts["dataset_construct"] = int(n())

    with jtu.count_jit_and_pmap_lowerings() as n:
        booster = lgb.train(params, train_set, num_boost_round=3)
    counts["train_3_iters"] = int(n())

    with jtu.count_jit_and_pmap_lowerings() as n:
        booster.predict(X)
    counts["predict_cold"] = int(n())

    with jtu.count_jit_and_pmap_lowerings() as n:
        for _ in range(3):
            booster.predict(X)
    counts["predict_warm_repeat"] = int(n())

    # leaf-wise grower: a different step program than the depthwise default
    with jtu.count_jit_and_pmap_lowerings() as n:
        lgb.train({**params, "grow_policy": "lossguide"}, train_set,
                  num_boost_round=3)
    counts["train_3_iters_lossguide"] = int(n())

    # warmed non-gbdt boosters: 3 warmup iterations, then two extra
    # update() calls must lower NOTHING (budget 0). skip_drop=0 makes every
    # DART iteration take the drop/normalize path, so the warmup sees it.
    for boosting, extra in (("dart", {"skip_drop": 0.0, "drop_rate": 0.5}),
                            ("goss", {}),
                            ("rf", {"bagging_freq": 1,
                                    "bagging_fraction": 0.8})):
        bst = lgb.train({**params, "boosting": boosting, **extra},
                        train_set, num_boost_round=3)
        with jtu.count_jit_and_pmap_lowerings() as n:
            bst.update()
            bst.update()
        counts[f"train_warm_extra2_{boosting}"] = int(n())

    # serving path: predicts at row counts whose buckets warmup()
    # pre-compiled must reuse the warmed executables (budget 0)
    booster.predict(X[:4])              # materialize the cached engine
    engine = booster._predict_engine
    engine.warmup(sizes=(1, 100))
    with jtu.count_jit_and_pmap_lowerings() as n:
        engine.predict(X[:1])
        engine.predict(X[:100])
    counts["predict_engine_warm"] = int(n())

    # q8 surface: forced-pallas quantized training on a regression
    # (const-hessian, 2-channel) workload — a separate step program from
    # the scatter-path train_3_iters above
    yreg = (X[:, 0] * 2.0 + rng.rand(512)).astype(np.float32)
    q8 = {**params, "objective": "regression", "histogram_impl": "pallas",
          "use_quantized_grad": "true"}
    dsq = lgb.Dataset(X, label=yreg, params=q8)
    dsq.construct()
    with jtu.count_jit_and_pmap_lowerings() as n:
        bstq = lgb.train(q8, dsq, num_boost_round=3)
    counts["train_3_iters_q8_2ch"] = int(n())
    with jtu.count_jit_and_pmap_lowerings() as n:
        bstq.update()
        bstq.update()
    counts["train_warm_extra2_q8_2ch"] = int(n())

    return counts


def measure_multihost() -> dict:
    """Pod-surface lowerings: the 2-D ("data","feature") sliced-histogram
    step and the voting-parallel top-k election step, on 4 virtual CPU
    devices. Runs in its own probe process: the device-count flag only
    takes effect if exported before jax ever imports."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                               + os.environ.get("XLA_FLAGS", "")).strip()
    os.environ.pop("LGBMTPU_LINT_ONLY", None)

    import numpy as np
    import jax
    import jax._src.test_util as jtu

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.rand(512, 16).astype(np.float32)
    y = (rng.rand(512) > 0.5).astype(np.float32)
    base = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
            "min_data_in_leaf": 5, "verbosity": -1, "prewarm": 0}

    counts = {}
    # same backend bring-up warmer as the plain probe
    # one-shot by construction (runs once per probe process)
    jax.jit(lambda a: a + 1)(np.float32(0)).block_until_ready()  # tpu-lint: disable=retrace-hazard

    # 2-D mesh: per-level histogram = sliced psum over "data" + tiled
    # all_gather over "feature" — a different step program than 1-D
    params2d = {**base, "num_shards": 2, "feature_shards": 2}
    ds2d = lgb.Dataset(X, label=y, params=params2d)
    ds2d.construct()
    with jtu.count_jit_and_pmap_lowerings() as n:
        bst2d = lgb.train(params2d, ds2d, num_boost_round=3)
    counts["train_3_iters_pod2d"] = int(n())
    with jtu.count_jit_and_pmap_lowerings() as n:
        bst2d.update()
        bst2d.update()
    counts["train_warm_extra2_pod2d"] = int(n())

    # voting-parallel: local top-k election + elected-column psum
    paramsv = {**base, "num_shards": 4, "voting_parallel": 1, "top_k": 3}
    dsv = lgb.Dataset(X, label=y, params=paramsv)
    dsv.construct()
    with jtu.count_jit_and_pmap_lowerings() as n:
        bstv = lgb.train(paramsv, dsv, num_boost_round=3)
    counts["train_3_iters_voting"] = int(n())
    with jtu.count_jit_and_pmap_lowerings() as n:
        bstv.update()
        bstv.update()
    counts["train_warm_extra2_voting"] = int(n())

    return counts


def main() -> int:
    if "--multihost" in sys.argv[1:]:
        counts = measure_multihost()
    else:
        counts = measure()
    json.dump({"counts": counts}, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
