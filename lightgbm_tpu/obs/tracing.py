"""Trace spans: one name, three sinks.

A :func:`span` scope feeds the same name to (1) the ``TIMER`` wall-clock
registry (whose scopes already emit ``jax.profiler.TraceAnnotation`` ranges,
so the name lines up in XLA profiler timelines), and (2) — when telemetry is
enabled — a log2 latency histogram ``span_seconds{span=<name>}`` in the
metrics registry, and (3) the in-memory span record: each thread keeps the
stack of its open spans, a span closed inside a ``train_iter`` adds its self
time (its own seconds less its children's) to that iteration's ``spans``,
and one closed outside any iteration emits a ``span`` event that says when
it started, on which thread and under which span.
:func:`current_span` is what a listener on the same thread (the
``program_load`` counter in ``obs/__init__``) asks for the innermost name.

Request tracing (serve path): :func:`mint_trace_id` stamps a process-unique
id on each request at serve ingress; the MicroBatcher flush records the span
breakdown (queue_wait / bin / device_dispatch / readback) through
:func:`record_span` into the same ``span_seconds`` histogram family, and
keeps 1-in-N complete traces as exemplars in :data:`TRACES` — all host-side
clock reads, zero new jit boundaries.

:func:`maybe_start_xla_trace` / :func:`stop_xla_trace` drive an on-demand XLA
profiler capture (``jax.profiler.start_trace``) gated by the ``xla_trace_out``
config knob — a full device trace is far too heavy to leave on, so it only
runs when an operator names an output directory.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from ..utils import log
from ..utils.timer import TIMER

# _xla_trace_dir is check-then-acted on from whichever thread calls
# maybe_start/stop (training loop, serving admin); the lock makes the
# "already capturing?" test and the rebind one atomic step
_xla_trace_lock = threading.Lock()
_xla_trace_dir: Optional[str] = None


_TL = threading.local()     # .stack: open spans, outermost first;
                            # .iteration: the open train_iter's record


class IterationSpans:
    """What one ``train_iter`` span gathers for its event."""
    __slots__ = ("step", "spans", "programs_loaded")

    def __init__(self, step: int) -> None:
        self.step = step
        self.spans: Dict[str, float] = {}      # name -> self seconds
        self.programs_loaded = 0


def _stack() -> list:
    st = getattr(_TL, "stack", None)
    if st is None:
        st = _TL.stack = []
    return st


def current_span() -> Optional[str]:
    """Name of the innermost span open on this thread."""
    st = getattr(_TL, "stack", None)
    return st[-1][0] if st else None


def current_iteration() -> Optional[IterationSpans]:
    """The record of the ``train_iter`` span open on this thread."""
    return getattr(_TL, "iteration", None)


@contextlib.contextmanager
def span(name: str, block_on=None, step_num=None, parent=None):
    """Timed scope: TIMER accumulation + TraceAnnotation + the thread's span
    stack, and with telemetry on the latency histogram and the span record
    (module docstring). ``step_num`` makes the scope one iteration of a loop:
    a ``StepTraceAnnotation`` that yields the :class:`IterationSpans` its
    children fill. ``parent`` names the span that caused this one where the
    thread has none open: a worker's outermost span is handed what
    :func:`current_span` said on the thread that started it. The disabled
    path adds a clock read and two list operations over a bare
    ``TIMER.scope``."""
    from . import METRICS, emit, enabled
    stack = _stack()
    frame = [name, 0.0]                 # name, seconds of closed children
    stack.append(frame)
    outer = current_iteration()
    if step_num is not None:
        _TL.iteration = IterationSpans(step_num)
    start_ts = time.time() if enabled() else None
    t0 = time.perf_counter()
    try:
        with TIMER.scope(name, block_on=block_on, step_num=step_num):
            yield _TL.iteration if step_num is not None else None
    finally:
        dt = time.perf_counter() - t0
        del stack[stack.index(frame):]  # and whatever leaked above it
        if stack:
            stack[-1][1] += dt
            parent = stack[-1][0]
        if step_num is not None:
            _TL.iteration = outer
        if enabled():
            METRICS.histogram("span_seconds", "span wall time by name",
                              span=name).observe(dt)
            if step_num is None and outer is not None:
                outer.spans[name] = outer.spans.get(name, 0.0) + dt - frame[1]
            elif step_num is None:      # an iteration's event is the loop's
                fields = {"thread": threading.current_thread().name}
                if start_ts is not None:
                    fields["start_ts"] = start_ts
                if parent is not None:
                    fields["parent"] = parent
                emit("span", name=name, duration_s=dt, **fields)


def record_span(name: str, seconds: float) -> None:
    """Observe an externally-timed duration into ``span_seconds{span=name}``
    (the flush path measures with bare perf_counter reads instead of nesting
    ``span`` contextmanagers per request)."""
    from . import METRICS, enabled
    if enabled():
        METRICS.histogram("span_seconds", "span wall time by name",
                          span=name).observe(seconds)


class TraceBuffer:
    """Bounded ring of sampled request-trace exemplars (thread-safe)."""

    def __init__(self, capacity: int = 256) -> None:
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self._sampled = 0

    def mint_trace_id(self) -> str:
        return f"req-{next(self._ids):08x}"  # itertools.count is atomic

    def maybe_record(self, trace: Dict[str, Any], sample: int = 1) -> bool:
        """Keep this trace as an exemplar with 1-in-``sample`` probability
        (deterministic round-robin, so sample=1 keeps everything)."""
        with self._lock:
            self._sampled += 1
            if sample > 1 and (self._sampled % sample) != 1:
                return False
            self._ring.append(dict(trace))
            return True

    def record(self, trace: Dict[str, Any]) -> None:
        with self._lock:
            self._ring.append(dict(trace))

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._sampled = 0


TRACES = TraceBuffer()


def mint_trace_id() -> str:
    return TRACES.mint_trace_id()


def maybe_start_xla_trace(out_dir: str) -> bool:
    """Start an XLA profiler capture into ``out_dir`` (no-op on empty dir or
    if a capture is already running). Returns whether a trace was started."""
    global _xla_trace_dir
    with _xla_trace_lock:
        if not out_dir or _xla_trace_dir is not None:
            return False
        try:
            import jax
            jax.profiler.start_trace(out_dir)
        except Exception as e:  # profiler backends vary; never break training
            log.warning(f"could not start XLA trace into {out_dir!r} "
                        f"({type(e).__name__}: {e})")
            return False
        _xla_trace_dir = out_dir
    log.info("XLA profiler trace started (xla_trace_out=%s)", out_dir)
    return True


def stop_xla_trace() -> Optional[str]:
    """Stop the running capture (if any); returns its output dir."""
    global _xla_trace_dir
    with _xla_trace_lock:
        if _xla_trace_dir is None:
            return None
        out, _xla_trace_dir = _xla_trace_dir, None
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception as e:  # pragma: no cover - symmetric guard
        log.warning(f"could not stop XLA trace ({type(e).__name__}: {e})")
        return None
    log.info("XLA profiler trace written to %s", out)
    return out
