"""Observability subsystem: telemetry events, metrics, trace spans, memory.

Off by default and designed so the disabled fast path is one attribute read
(`obs.enabled()` / the `_STATE.enabled` check at the top of `emit`) — the
training loop and the PredictEngine call into here on every iteration /
batch, and the <2% overhead budget only holds if "off" costs nothing.

Enable with the ``telemetry=1`` config param or the ``LGBMTPU_TELEMETRY=1``
environment variable (env wins, so an operator can switch telemetry on for
one run without touching params).  ``metrics_out=<dir>`` names a directory
that :func:`export_all` fills with three crash-safe files::

    events.jsonl    one JSON object per event (schema: obs/events.py)
    metrics.json    nested metric snapshot
    metrics.prom    Prometheus textfile exposition format

Everything is host-side bookkeeping around the existing jitted programs:
enabling telemetry changes **zero device code** — no new jit boundaries, no
new retraces (tests/test_observability.py asserts this with the same lowering
counters the serving tests use).
"""
from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

from ..utils import log
from . import flight, memory, slo, tracing
from .events import EVENT_SCHEMAS, EventLog, register_event
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .tracing import maybe_start_xla_trace, span, stop_xla_trace

EVENTS = EventLog()
METRICS = MetricsRegistry()


def _env_enabled() -> Optional[bool]:
    v = os.environ.get("LGBMTPU_TELEMETRY")
    if v is None or v == "":
        return None
    return v.strip().lower() not in ("0", "false", "no", "off")


class _State:
    def __init__(self) -> None:
        # env-only workflows (LGBMTPU_TELEMETRY=1 + predict without any
        # configure call) start enabled; configure_from_config re-reads the
        # env anyway, so this is just the pre-configure default
        self.enabled = bool(_env_enabled())
        self.listening = False      # jax's listeners and the gc hook are in
        self.metrics_out = ""
        self.lock = threading.Lock()


_STATE = _State()


def enabled() -> bool:
    return _STATE.enabled


def configure(enabled: Optional[bool] = None,
              metrics_out: Optional[str] = None) -> None:
    with _STATE.lock:
        if enabled is not None:
            _STATE.enabled = bool(enabled)
        if metrics_out is not None:
            _STATE.metrics_out = str(metrics_out)
        if _STATE.enabled and not _STATE.listening:
            # once per process (jax keeps listeners for good, gc its
            # callbacks); all of them silent while telemetry is off
            import gc

            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            jax.monitoring.register_event_listener(_on_jax_event)
            gc.callbacks.append(_on_gc)
            _STATE.listening = True


# ---- what jax says while it builds or loads a program -----------------------
# All of it arrives on the thread that asked for the program, in this order:
# jaxpr_trace_duration (fun_name "step"; a jit traced inside it first),
# jaxpr_to_mlir_module_duration ("jit(step)"), then inside the
# backend_compile_duration interval cache_hits with its two durations, or
# cache_misses where the executable was built and written, or neither where
# the program has no cache key (cache off, or built under the cache's
# thresholds), and last backend_compile_duration itself ("jit(step)"), which
# is the program_load. A program that jit serves from memory says nothing.

_PROGRAM_LOAD_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s"}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_TRANSFORMED = re.compile(r"\w+\((.*)\)")


def program_name(fun_name: str) -> str:
    """jax's ``fun_name`` as the device trace names the module, less its
    ``jit_`` prefix: ``jit(step)`` and ``step`` -> ``step``, ``jit(<lambda>)``
    -> ``_lambda_`` (mlir.sanitize_name's characters)."""
    m = _TRANSFORMED.fullmatch(fun_name)
    return re.sub(r"[^\w.-]", "_", m.group(1) if m else fun_name)


class _Compiling(threading.local):
    """What jax has said on this thread since its last program_load."""

    def __init__(self) -> None:
        self.clear()

    def clear(self) -> None:
        self.cache = "off"
        self.cache_s: Dict[str, float] = {}     # retrieval_s, saved_s
        # trace_s / lower_s -> program -> seconds
        self.stage_s: Dict[str, Dict[str, float]] = {
            field: {} for field in _STAGE_EVENTS.values()}


_COMPILING = _Compiling()


def _on_jax_event(event: str, **_kw: Any) -> None:
    word = _CACHE_EVENTS.get(event)
    if word is not None and _STATE.enabled:
        _COMPILING.cache = word


def _on_jax_duration(event: str, duration: float, fun_name: str = "",
                     **_kw: Any) -> None:
    if not _STATE.enabled:
        return
    said = _COMPILING
    if event == _PROGRAM_LOAD_EVENT:
        _program_load(program_name(fun_name), float(duration), said)
        said.clear()
    elif event in _STAGE_EVENTS:
        by_name = said.stage_s[_STAGE_EVENTS[event]]
        name = program_name(fun_name)
        by_name[name] = by_name.get(name, 0.0) + float(duration)
    elif event in _CACHE_DURATIONS:
        said.cache_s[_CACHE_DURATIONS[event]] = float(duration)


def _program_load(program: str, duration: float, said: _Compiling) -> None:
    """One executable built, or read from the persistent cache: jax reports
    both under one name, on the thread that asked for the program, so the
    innermost span open there is the host code that paid for it."""
    where = tracing.current_span() or "none"
    fields: Dict[str, Any] = {
        "span": where, "duration_s": duration, "program": program,
        "cache": said.cache, "thread": threading.current_thread().name}
    fields.update(said.cache_s)
    for field, by_name in said.stage_s.items():
        if program in by_name:
            fields[field] = by_name[program]
    it = tracing.current_iteration()
    if it is not None:
        it.programs_loaded += 1
        fields["iteration"] = int(it.step)
    emit("program_load", **fields)
    METRICS.counter("programs_loaded",
                    "executables built or read from the compile cache",
                    span=where, cache=said.cache).inc()


# ---- the collector's long passes --------------------------------------------
# gc runs its callbacks wherever the interpreter stood, perhaps inside
# EventLog.emit with its lock held: the hook takes no lock. It leaves the
# record in _GC_PAUSES (deque.append is atomic) and the next emit() writes it.

_GC_OPEN = threading.local()        # .gen2: (start ts, clock, annotation)
_GC_PAUSES: "collections.deque" = collections.deque()


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """A generation-2 collection as a ``gc_gen2`` range on the profiler's
    clock and a ``gc_pause`` event; younger generations are not looked at."""
    if info.get("generation") != 2 or not _STATE.enabled:
        return
    if phase == "start":
        import jax
        note = jax.profiler.TraceAnnotation("gc_gen2")
        note.__enter__()
        _GC_OPEN.gen2 = (time.time(), time.perf_counter(), note)
        return
    opened = getattr(_GC_OPEN, "gen2", None)
    if opened is None:              # telemetry came on inside the pass
        return
    _GC_OPEN.gen2 = None
    start_ts, t0, note = opened
    note.__exit__(None, None, None)
    _GC_PAUSES.append({"generation": 2, "start_ts": start_ts,
                       "duration_s": time.perf_counter() - t0,
                       "collected": int(info.get("collected", 0))})


def configure_from_config(conf) -> None:
    """Apply a Config's telemetry knobs (engine.train / CLI entry).
    ``LGBMTPU_TELEMETRY`` overrides the param in either direction."""
    env = _env_enabled()
    on = bool(getattr(conf, "telemetry", False)) if env is None else env
    configure(enabled=on, metrics_out=getattr(conf, "metrics_out", ""))
    slo.TRACKER.configure(slo_ms=getattr(conf, "serve_slo_ms", None),
                          target=getattr(conf, "serve_slo_target", None),
                          window=getattr(conf, "serve_slo_window", None))
    slo.FRESHNESS.configure(
        slo_s=getattr(conf, "online_freshness_slo_s", None))
    flight_dir = (getattr(conf, "flight_dir", "")
                  or getattr(conf, "metrics_out", ""))
    flight.FLIGHT.configure(out_dir=flight_dir,
                            capacity=getattr(conf, "flight_events", None))


def emit(etype: str, **fields: Any) -> None:
    """Record one telemetry event (no-op unless telemetry is enabled).
    Event types and fields must be registered in ``obs.events`` — an
    unregistered type or field raises (see scripts/check_telemetry_schema.py
    for the static check over call sites)."""
    if not _STATE.enabled:
        return
    while _GC_PAUSES:
        try:
            EVENTS.emit("gc_pause", **_GC_PAUSES.popleft())
        except IndexError:          # another thread wrote it
            break
    EVENTS.emit(etype, **fields)
    if flight.FLIGHT.active:
        flight.FLIGHT.note_event(etype, fields)


def reset() -> None:
    """Clear accumulated events, metrics, SLO windows, trace exemplars and
    flight-recorder state (per-run isolation in tests) under one lock, so a
    concurrent configure can't observe a half-reset plane."""
    with _STATE.lock:
        EVENTS.clear()
        _GC_PAUSES.clear()
        METRICS.clear()
        slo.TRACKER.reset()
        slo.FRESHNESS.reset()
        tracing.TRACES.clear()
        flight.FLIGHT.reset()


# ---- derived-gauge collectors ----------------------------------------------
# Run just before a scrape (/metrics) or an export so point-in-time gauges
# (event drops, buffered counts per family, device memory, model age) are
# fresh; nothing here runs on the hot paths.

_collectors_lock = threading.Lock()
_COLLECTORS: Dict[str, Any] = {}


def add_collector(name: str, fn) -> None:
    """Register ``fn(METRICS)`` to run before scrapes/exports (latest wins)."""
    with _collectors_lock:
        _COLLECTORS[name] = fn


def remove_collector(name: str) -> None:
    with _collectors_lock:
        _COLLECTORS.pop(name, None)


def run_collectors() -> None:
    with _collectors_lock:
        fns = list(_COLLECTORS.items())
    for name, fn in fns:
        try:
            fn(METRICS)
        except Exception as e:  # a broken collector must not break a scrape
            log.warning(f"metrics collector {name!r} failed "
                        f"({type(e).__name__}: {e})")


def _events_collector(reg: MetricsRegistry) -> None:
    reg.gauge("events_buffered",
              "telemetry events currently buffered").set(len(EVENTS))
    reg.gauge("events_dropped",
              "telemetry events dropped from the bounded log").set(EVENTS.dropped)
    for etype, n in EVENTS.family_counts().items():
        reg.gauge("events_by_type", "buffered telemetry events by type",
                  type=etype).set(n)


def export_all(out_dir: Optional[str] = None) -> Optional[str]:
    """Write events.jsonl + metrics.json + metrics.prom into ``out_dir``
    (default: the configured ``metrics_out``). Returns the directory written,
    or None when no directory is configured or telemetry is off."""
    out_dir = out_dir if out_dir is not None else _STATE.metrics_out
    if not out_dir or not _STATE.enabled:
        return None
    try:
        run_collectors()
        EVENTS.write_jsonl(os.path.join(out_dir, "events.jsonl"))
        METRICS.write_json(os.path.join(out_dir, "metrics.json"))
        METRICS.write_prometheus(os.path.join(out_dir, "metrics.prom"))
    except OSError as e:
        log.warning(f"telemetry export to {out_dir!r} failed "
                    f"({type(e).__name__}: {e})")
        return None
    return out_dir


# ---- periodic metrics flush -------------------------------------------------

_flush_lock = threading.Lock()
_flush_thread: Optional[threading.Thread] = None
_flush_stop: Optional[threading.Event] = None


def _flush_loop(interval_s: float, stop: "threading.Event") -> None:
    while not stop.wait(interval_s):
        export_all()


def start_periodic_flush(interval_s: float) -> bool:
    """Start the background re-export loop (``metrics_flush_secs`` knob).
    Returns True only to the caller that now owns it — pass that back to
    :func:`stop_periodic_flush` so a nested ``engine.train`` (an online refit
    cycle) can't tear down the outer run's flusher."""
    global _flush_thread, _flush_stop
    if interval_s is None or interval_s <= 0:
        return False
    if not _STATE.enabled or not _STATE.metrics_out:
        return False
    with _flush_lock:
        if _flush_thread is not None and _flush_thread.is_alive():
            return False
        stop = threading.Event()
        th = threading.Thread(target=_flush_loop, args=(float(interval_s), stop),
                              name="lgbm-obs-flush", daemon=True)
        _flush_stop = stop
        _flush_thread = th
        th.start()
    return True


def stop_periodic_flush(owned: bool) -> None:
    """Stop the flusher if ``owned`` (the start_periodic_flush return)."""
    global _flush_thread, _flush_stop
    if not owned:
        return
    with _flush_lock:
        th, stop = _flush_thread, _flush_stop
        _flush_thread = None
        _flush_stop = None
    if stop is not None:
        stop.set()
    if th is not None and th.is_alive():
        th.join(timeout=5.0)


add_collector("events", _events_collector)
add_collector("memory", memory.update_gauges)


__all__ = ["EVENTS", "METRICS", "EVENT_SCHEMAS", "EventLog", "MetricsRegistry",
           "Counter", "Gauge", "Histogram", "register_event",
           "configure", "configure_from_config", "enabled", "emit", "reset",
           "export_all", "span", "maybe_start_xla_trace", "stop_xla_trace",
           "memory", "tracing", "slo", "flight",
           "add_collector", "remove_collector", "run_collectors",
           "start_periodic_flush", "stop_periodic_flush"]
