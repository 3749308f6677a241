"""Background AOT compilation of the fused train step (cold-start overlap).

``BENCH_r05.json`` spends compile_s=49.45 before the first boosting
iteration — an order of magnitude more than the 20-iteration training loop
itself. All of that tracing/lowering/XLA work needs only the *shapes* of the
training arguments, and ``Dataset.construct`` fixes every one of them (N,
F_b, B, L, k) the moment bin mappers + the EFB plan exist — minutes of bulk
encode/upload before the first dispatch at the 10M bench scale. So: as soon
as the dataset publishes its metadata, ``maybe_start`` builds the same
trainer the Booster will build, lowers the fused step against
``ShapeDtypeStruct``s, and compiles it on a daemon thread concurrent with
the ingest pipeline.

Adoption is by *executable*, not by jit cache: on this jax version a
``lower().compile()`` does NOT populate the jit wrapper's dispatch cache
(measured: ``fn._cache_size()`` stays 0 and the first wrapper call compiles
again), so the trainer dispatches the returned ``Compiled`` object directly.
That requires the argument avals to match the lowering EXACTLY —
``step_avals`` mirrors ``GBDT._fused_step``'s argument construction
(``jnp.float32``/``jnp.int32`` scalars included, which have different cache
identities than numpy or weak-typed python scalars) and ``adopt`` verifies a
structural spec of everything that shapes the traced program, falling back
to plain jit dispatch on any mismatch. The join in ``adopt`` is the barrier
before first dispatch the pipeline design calls for.

Scope: the serial single-process tree learner with a built-in objective,
for ALL FOUR boosters — gbdt and dart share the auto-gradient step program;
goss and rf feed explicit gradients, so their prewarm lowers the
custom-gradient step instead (``handle.result["custom"]`` records which one
was built and ``adopt`` rejects a mismatch). The gbdt form includes the
mesh-native row-sharded trainer (the lowering then runs against sharded
avals — a dataset-published RowShardPlan fixes the padded shapes and the
NamedSharding before ingest starts). Everything else — explicit
data/voting/feature learners, multi-machine — skips the prewarm and
compiles at first dispatch exactly as before. ``prewarm=0`` is the kill
switch.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from . import obs
from .utils import faults, log

# config fields that shape the traced step program beyond what the
# structural fields (gp, k, n, f, flags) already capture — objective family
# and its hyperparameters, grower selection, and histogram variants
_SPEC_KEYS = (
    "objective", "num_class", "boosting", "sigmoid", "alpha", "fair_c",
    "poisson_max_delta_step", "tweedie_variance_power", "is_unbalance",
    "scale_pos_weight", "reg_sqrt", "boost_from_average", "grow_policy",
    "histogram_impl", "use_quantized_grad", "hist_dtype",
    "nonfinite_policy",
    "tree_learner", "top_k", "label_gain", "lambdarank_truncation_level",
    "lambdarank_norm", "histogram_pool_size", "forcedsplits_filename",
    "feature_fraction_bynode", "learning_rate",
)


class PrewarmHandle:
    """One background compile: join() is the pre-dispatch barrier; ``spec``
    and ``result`` are written by the worker before the thread exits, so
    they are safely visible to any thread that joined."""

    def __init__(self) -> None:
        self.spec: Optional[Dict[str, Any]] = None
        self.result: Dict[str, Any] = {}
        self._thread: Optional[threading.Thread] = None

    def join(self, timeout: Optional[float] = None) -> "PrewarmHandle":
        if self._thread is not None:
            self._thread.join(timeout)
        return self

    def done(self) -> bool:
        return self._thread is None or not self._thread.is_alive()


def step_spec(gbdt) -> Dict[str, Any]:
    """Everything that determines the traced fused-step program (beyond the
    argument avals): compared between the prewarmed trainer and the real one
    before the executable is adopted."""
    ts = gbdt.train_set
    conf = gbdt.config
    return {
        "class": type(gbdt).__name__,
        "k": int(gbdt.num_tree_per_iteration),
        "gp": gbdt.gp,
        "nf": gbdt._nf_policy,
        "avg": bool(gbdt.average_output),
        "obj": type(gbdt.objective).__name__ if gbdt.objective else None,
        "n": int(ts.num_data),
        "f": int(ts.num_features),
        "bundle": getattr(ts, "bundle_meta", None) is not None,
        "cegb": gbdt._cegb_dev is not None,
        "forced": gbdt._forced_dev is not None,
        "dp": bool(gbdt._dp),
        "fp": bool(gbdt._fp),
        # mesh-native row sharding shapes the program (shard_map + psum over
        # the plan's mesh); shard count 0 = unsharded
        "shards": (int(gbdt._plan.num_shards)
                   if getattr(gbdt, "_plan", None) is not None else 0),
        # the fused grad+quant+hist0 front and the cached transposed bin
        # matrix both change the traced program (and the argument avals);
        # neither is fully derivable from the conf fields alone
        "fused": gbdt._fused_front()[0],
        "rows": gbdt._grad_rows_spec()[0],
        "bt": gbdt._use_bt(),
        "conf": {k: getattr(conf, k, None) for k in _SPEC_KEYS},
    }


def step_avals(gbdt, custom: bool = False):
    """ShapeDtypeStructs matching GBDT._fused_step's argument construction
    exactly (order and dtypes included). ``custom=True`` mirrors the
    explicit-gradient dispatch (GOSS/RF): grad/hess are score-shaped row
    arrays instead of scalar dummies, and the fused front is off.

    With a mesh-native RowShardPlan the bins aval is [n_padded, f] and
    carries the plan's NamedSharding — lowering against the sharded aval is
    what makes the AOT executable match the row-sharded dispatch arguments,
    so cold-start still hides behind the (sharded) ingest. CEGB's row-wise
    lazy bitset is likewise already sharded on the trainer and its aval
    copies the live array's sharding."""
    import jax
    ts = gbdt.train_set
    n, f = int(ts.num_data), int(ts.num_features)
    k = gbdt.num_tree_per_iteration
    plan = getattr(gbdt, "_plan", None)
    S = jax.ShapeDtypeStruct
    # [N] row arguments the trainer lays over the plan's mesh itself
    rows = gbdt._row_sharding()
    score = S((n,) if k == 1 else (n, k), np.float32)
    sc_f = S((), np.float32)

    def _arr_aval(a):
        if plan is not None and getattr(a, "sharding", None) is not None:
            return S(a.shape, a.dtype, sharding=a.sharding)
        return S(a.shape, a.dtype)

    cegb = (jax.tree_util.tree_map(_arr_aval, gbdt._cegb_dev)
            if gbdt._cegb_dev is not None else sc_f)
    if plan is not None:
        bins_aval = S((plan.n_padded, f), np.uint8,
                      sharding=plan.sharding(2))
    else:
        bins_aval = S((n, f), np.uint8)
    gh = score if custom else sc_f      # explicit gradients are score-shaped
    # the cached transposed bin matrix, [F_pad, N_pad] in the kernels'
    # shape, rides along on serial Pallas trainers; an objective whose
    # gradients the step computes from
    # (score, aux) adds its aux rows (auto path only). Both fall back to the
    # scalar dummy aval the dispatch passes when the corresponding gate is
    # off.
    bt = S(ts.bins_T_shape, np.uint8) if gbdt._use_bt() else sc_f
    rows_spec, rows_aux = (None, None) if custom else gbdt._grad_rows_spec()
    if rows_spec is not None:
        aux = jax.tree_util.tree_map(
            lambda a: S(a.shape, a.dtype, sharding=rows), rows_aux)
    else:
        aux = sc_f
    return (bins_aval,                  # bins
            S((f,), np.int32),          # num_bins
            S((f,), np.int32),          # na_bin
            score,                      # train score
            S((f,), np.bool_),          # feature mask
            S((n,), np.float32, sharding=rows),   # bag weights
            gh, gh,                     # grad/hess (dummies on auto path)
            sc_f,                       # shrink
            S((), np.int32),            # qseed
            sc_f,                       # titer
            cegb,                       # CEGB state (dummy when off)
            bt,                         # transposed bins (dummy when off)
            aux)                        # fused-front aux rows (dummy when off)


def aot_compile_step(gbdt, fn=None, tag: str = "cold",
                     custom: bool = False):
    """Lower + XLA-compile the fused step out of band (auto-gradient by
    default; ``custom=True`` builds the explicit-gradient step GOSS/RF
    dispatch). Returns (jit wrapper, Compiled executable, seconds). ``tag``
    labels the compile event cold/warm so the bench can split the two
    without guessing. The spans ``prewarm_build`` / ``prewarm_lower`` /
    ``prewarm_compile`` name the three stages on whichever thread runs them
    (``program_load.span`` of the step reads ``prewarm_compile``)."""
    if fn is None:
        with obs.span("prewarm_build"):
            fn = gbdt._build_fused_step(custom=custom)
    t0 = time.perf_counter()
    with obs.span("prewarm_lower"):
        lowered = fn.lower(*step_avals(gbdt, custom=custom))
    with obs.span("prewarm_compile"):
        compiled = lowered.compile()
    dt = time.perf_counter() - t0
    if obs.enabled():
        # cache_size 0: AOT compilation does not enter the wrapper's
        # dispatch cache (the whole reason adoption hands over `compiled`)
        obs.emit("compile", what="fused_step_aot", cache_size=0,
                 duration_s=float(dt), key=tag)
        _emit_step_memory(compiled)
    return fn, compiled, dt


def _emit_step_memory(compiled) -> None:
    """The ``step_memory`` event of an executable already in hand (one
    device's bytes); nothing where the backend gives no analysis."""
    mem = compiled.memory_analysis()
    if mem is None:
        return
    obs.emit("step_memory", what="fused_step_aot",
             argument_bytes=int(mem.argument_size_in_bytes),
             output_bytes=int(mem.output_size_in_bytes),
             temp_bytes=int(mem.temp_size_in_bytes),
             alias_bytes=int(mem.alias_size_in_bytes),
             generated_code_bytes=int(mem.generated_code_size_in_bytes),
             devices=len(compiled.runtime_executable().local_devices()))


# below this the encode/upload window is far shorter than the compile it
# would hide, and Datasets that are constructed but never trained (valid
# sets, serialization round-trips) would burn a whole wasted XLA compile —
# at bench scale (10M rows) the ingest takes long enough to hide all of it
MIN_PREWARM_ROWS = 200_000


def _skip_reason(conf, dataset) -> Optional[str]:
    if not conf.prewarm:
        return "prewarm=0"
    n = int(dataset.num_data or 0)
    if n < MIN_PREWARM_ROWS:
        return f"num_data={n} < {MIN_PREWARM_ROWS} (nothing to hide behind)"
    if conf.boosting not in ("gbdt", "gbrt", "dart", "goss", "rf",
                             "random_forest"):
        return f"boosting={conf.boosting} (unknown booster)"
    if conf.tree_learner not in ("serial",):
        return f"tree_learner={conf.tree_learner} (sharded args differ)"
    if conf.num_machines > 1:
        return "num_machines>1"
    if dataset.label is None:
        return "no label (nothing to train)"
    return None


def maybe_start(conf, dataset) -> Optional[PrewarmHandle]:
    """Kick the background compile if the configuration is in scope.
    Called by Dataset.construct right after metadata publication — i.e.
    before the bulk encode/upload the compile is meant to hide behind."""
    reason = _skip_reason(conf, dataset)
    tele = obs.enabled()
    if reason is not None:
        if tele:
            obs.emit("aot_prewarm", phase="skipped", reason=reason)
        log.debug("AOT prewarm skipped: %s", reason)
        return None
    handle = PrewarmHandle()
    # the span that causes the worker's: open on THIS thread (the worker's
    # own stack starts empty), dataset_construct when construct() calls
    started_under = obs.tracing.current_span()

    def _worker():
        with obs.span("prewarm_worker", parent=started_under):
            t0 = time.perf_counter()
            try:
                # chaos point: a failed background compile must degrade to
                # compile-at-dispatch (adoption miss), never break training
                faults.fault_point("prewarm_compile")
                with obs.span("prewarm_trainer"):
                    # lazy import: basic imports this module lazily from
                    # construct, so there is no cycle at import time
                    from .basic import booster_class
                    from .objectives import create_objective
                    cls = booster_class(conf.boosting)
                    # GOSS (grad-dependent bagging) and RF (constant
                    # explicit gradients) dispatch the custom-gradient step;
                    # gbdt/dart the auto one. The flag travels with the
                    # handle so adopt() can refuse to hand a custom
                    # executable to an auto dispatch.
                    custom = bool(getattr(cls, "_needs_grad_for_bag", False)
                                  or getattr(cls, "average_output", False))
                    objective = create_objective(conf.objective, conf)
                    g = cls(conf, dataset, objective, metrics=[], quiet=True)
                    handle.spec = step_spec(g)
                fn, compiled, _ = aot_compile_step(g, tag="cold",
                                                   custom=custom)
                handle.result.update(fn=fn, compiled=compiled, custom=custom,
                                     duration_s=time.perf_counter() - t0)
                if tele:
                    obs.emit("aot_prewarm", phase="compiled",
                             duration_s=float(handle.result["duration_s"]))
            except BaseException as e:   # surfaced as a miss at adoption
                handle.result["error"] = e
                if tele:
                    obs.emit("aot_prewarm", phase="error",
                             reason=str(e)[:200],
                             duration_s=time.perf_counter() - t0)

    th = threading.Thread(target=_worker, daemon=True, name="aot-prewarm")
    handle._thread = th
    if tele:
        obs.emit("aot_prewarm", phase="started")
    th.start()
    return handle


def adopt(handle: PrewarmHandle, gbdt, custom: bool = False):
    """Join the background compile (the before-first-dispatch barrier) and
    return its Compiled executable iff it was built for exactly this
    trainer's step program AND the same custom/auto gradient flavour;
    None means compile at dispatch as usual."""
    t0 = time.perf_counter()
    handle.join()
    wait = time.perf_counter() - t0
    tele = obs.enabled()
    err = handle.result.get("error")
    if err is not None:
        if faults.is_compile_oom(err) and handle.spec == step_spec(gbdt):
            # the identical program would fail identically at dispatch:
            # surface it now instead of compiling it a second time
            raise err
        if tele:
            obs.emit("aot_prewarm", phase="miss",
                     reason=f"background compile failed: {str(err)[:160]}")
        log.debug("AOT prewarm unusable (%r); compiling at dispatch", err)
        return None
    if bool(handle.result.get("custom", False)) != bool(custom):
        if tele:
            obs.emit("aot_prewarm", phase="miss",
                     reason="custom/auto step mismatch")
        log.info("prewarmed step was compiled for the %s-gradient path; "
                 "compiling at dispatch",
                 "custom" if handle.result.get("custom") else "auto")
        return None
    if handle.spec != step_spec(gbdt):
        if tele:
            obs.emit("aot_prewarm", phase="miss", reason="spec mismatch")
        log.info("prewarmed step does not match the trainer configuration; "
                 "compiling at dispatch")
        return None
    if tele:
        obs.emit("aot_prewarm", phase="adopted", duration_s=float(wait))
        obs.METRICS.counter("aot_prewarm_hits",
                            "prewarmed step executables adopted").inc()
    log.debug("adopted prewarmed fused step (barrier wait %.3fs)", wait)
    return handle.result["compiled"]
