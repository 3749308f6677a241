"""Named-scope timing registry.

Analog of the reference's ``Timer``/``FunctionTimer`` profiling registry
(src/utils/common.h:1032-1093, enabled with USE_TIMER): named accumulating
wall-clock scopes, printed as a sorted table. TPU addition: scopes also emit
``jax.profiler.TraceAnnotation`` ranges so the same names line up in XLA
profiler traces, and a scope can optionally block on device results so
asynchronous dispatch doesn't attribute device time to the wrong scope.

The registry is thread-safe (the PredictEngine drives scopes from its chunk
producer thread and from concurrent callers) and namespaced per training run:
``engine.train`` calls :meth:`TimerRegistry.begin_run` so accumulations don't
bleed across successive ``train()`` calls in one process — the previous run's
table stays readable via ``last_run``.

Usage::

    from lightgbm_tpu.utils.timer import TIMER, timed

    with TIMER.scope("hist"):
        ...
    @timed("construct_bins")
    def f(...): ...

    TIMER.summary_string()  # -> table; printed at end of training at verbosity>=1
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Dict, Optional, Tuple

import jax


class TimerRegistry:
    def __init__(self) -> None:
        self._acc: Dict[str, float] = {}
        self._cnt: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.last_run: Dict[str, Tuple[float, int]] = {}
        self.enabled = True

    def reset(self) -> None:
        with self._lock:
            self._acc.clear()
            self._cnt.clear()

    def begin_run(self) -> None:
        """Start a fresh accumulation namespace (one per train() call):
        archives the current table into ``last_run`` and clears."""
        with self._lock:
            self.last_run = {k: (self._acc[k], self._cnt.get(k, 0))
                             for k in self._acc}
            self._acc.clear()
            self._cnt.clear()

    @contextlib.contextmanager
    def scope(self, name: str, block_on=None, step_num=None):
        """Accumulate wall time under ``name``. If ``block_on`` is a callable,
        its result is block_until_ready'd before the clock stops (so the scope
        covers device execution, not just async dispatch). With ``step_num``
        the range is a ``StepTraceAnnotation``: the profiler's step view then
        groups what ran under it by that number."""
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        ann = (jax.profiler.TraceAnnotation(name) if step_num is None else
               jax.profiler.StepTraceAnnotation(name, step_num=step_num))
        with ann:
            yield
            if block_on is not None:
                jax.block_until_ready(block_on() if callable(block_on) else block_on)
        self.add(name, time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._acc[name] = self._acc.get(name, 0.0) + seconds
            self._cnt[name] = self._cnt.get(name, 0) + 1

    def get(self, name: str) -> float:
        with self._lock:
            return self._acc.get(name, 0.0)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """{name: {"seconds", "count"}} — bench.py attaches this to its
        telemetry block; obs.export_all folds it into metrics.json."""
        with self._lock:
            return {k: {"seconds": self._acc[k], "count": self._cnt.get(k, 0)}
                    for k in self._acc}

    def summary_string(self) -> str:
        """Sorted table (reference prints the same at program exit,
        common.h:1056 Timer::~Timer)."""
        with self._lock:
            acc = dict(self._acc)
            cnt = dict(self._cnt)
        if not acc:
            return "No timing scopes recorded"
        lines = ["LightGBM-TPU timing summary:"]
        width = max(len(k) for k in acc)
        for name, sec in sorted(acc.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {name:<{width}s} {sec:10.3f} s  "
                         f"(x{cnt[name]})")
        return "\n".join(lines)


TIMER = TimerRegistry()


def timed(name: str, block: bool = False):
    """Decorator form (reference: FunctionTimer, common.h:1076)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with TIMER.scope(name):
                out = fn(*args, **kwargs)
                if block:
                    jax.block_until_ready(out)
            return out
        return inner
    return wrap


def scoped_jit(fn, scope: str, **jit_kwargs):
    """``jax.jit(fn)`` with the body under ``jax.named_scope(scope)``, for a
    program that is dispatched on its own. jit drops the caller's name stack
    at its boundary, so a scope opened around the call never reaches the
    device trace; opened inside, it is in every op's name. The scope is in
    the function's name as well (module ``jit_<fn>_<scope>``): the persistent
    compilation cache leaves metadata out of its key and would otherwise hand
    back the executable built for the unscoped program, its op names with it.
    """
    def body(*args, **kwargs):
        with jax.named_scope(scope):
            return fn(*args, **kwargs)
    body.__name__ = body.__qualname__ = f"{fn.__name__}_{scope}"
    # a factory: the caller keeps the wrapper (ops/predict.py caches per
    # scope, ops/pallas_hist.py per scope and shape)
    return jax.jit(body, **jit_kwargs)   # tpu-lint: disable=retrace-hazard


def time_op_in_jit(op, *big, K: int = 6, reps: int = 1):
    """Device time of ``op(s, *big)`` measured INSIDE one jit: cost =
    (t_K - t_1) / (K - 1) over a fori_loop, so host dispatch
    latency cancels. ``op`` must make its output genuinely depend on the
    traced loop value ``s`` (e.g. scale a float operand by it, or fold it
    into an index with a non-constant-foldable min/remainder) — otherwise
    XLA hoists the op out of the loop and the measurement reads ~0. The
    large arrays MUST be passed via ``*big`` (closure constants are embedded
    in the compiled program).
    Returns milliseconds per op. Used by bench.py's phase breakdown."""
    import time as _time
    from functools import partial as _partial
    import jax
    import jax.numpy as jnp

    def loop(k, x0, *a):
        return jax.lax.fori_loop(
            0, k, lambda i, acc: acc + op(acc * 0 + 1 + i, *a), x0)

    # fresh wrappers per call by design: each timing must include exactly
    # one compile so (t_K - t_1)/(K - 1) cancels dispatch latency; caching
    # them would poison the methodology
    f1 = jax.jit(_partial(loop, 1))  # tpu-lint: disable=retrace-hazard
    fK = jax.jit(_partial(loop, K))  # tpu-lint: disable=retrace-hazard
    x0 = jnp.zeros((), jnp.float32)
    jax.block_until_ready(f1(x0, *big))
    jax.block_until_ready(fK(x0, *big))
    best = None
    for _ in range(reps):
        t0 = _time.time(); jax.block_until_ready(f1(x0, *big))
        t1 = _time.time() - t0
        t0 = _time.time(); jax.block_until_ready(fK(x0, *big))
        tK = _time.time() - t0
        ms = (tK - t1) / (K - 1) * 1000.0
        best = ms if best is None else min(best, ms)
    return best
