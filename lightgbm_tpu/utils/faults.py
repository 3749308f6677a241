"""Fault-injection harness.

Named fault points are compiled into the hot paths of this package and are
inert unless armed. Arming happens via the ``LGBMTPU_FAULTS`` env var or the
``faults`` parameter, with the spec syntax::

    LGBMTPU_FAULTS="snapshot_write:2,mapper_allgather:1"

meaning: the first 2 hits of ``snapshot_write`` raise :class:`FaultInjected`,
then it succeeds; ``mapper_allgather`` fails once.  A count of ``-1`` (or
``*``) fails forever — that is how the kill-and-resume tests simulate a
process crash at a chosen iteration (``tree_update:0`` arms nothing;
``tree_update@5`` skips 5 hits then fails forever, i.e. "crash at the 6th
boosting iteration").  Unknown point names REJECT at arm time with the list
of known points — a typo'd spec that silently arms nothing would make a
chaos test pass without injecting anything.

Fault-point registry (every name accepted in a spec):

========================  ===================================================
point                     fires in
========================  ===================================================
``snapshot_write``        utils/atomic_io.py — between the temp-file write
                          and the atomic rename (the crash window the atomic
                          protocol exists for); snapshot.py retries through it
``mapper_allgather``      parallel/dist_data.py — the bin-mapper allgather
                          during distributed bin finding
``dist_init``             parallel/mesh.init_distributed — the
                          jax.distributed bootstrap (retried with backoff)
``tree_update``           engine.train — top of each boosting iteration
                          (kill-and-resume crash simulation)
``shard_commit``          ingest.py commit stage — before a chunk folds into
                          its owning shard's donated accumulator
``device_put_oom``        ingest.py H2D stage — before the chunk transfer —
                          and serving.py run_binned — before the serve-path
                          batch upload (a faulted flush fails its requests
                          and trips the flight recorder, obs/flight.py);
                          raises the REAL XLA ``RESOURCE_EXHAUSTED`` error
                          type (simulated device OOM), so product catch
                          paths match on the exception they see in prod
``hist_allreduce``        models/gbdt.py — host side of the fused-step
                          dispatch on the data mesh (the in-step histogram
                          psum's dispatch site)
``prewarm_compile``       prewarm.py — inside the background AOT compile
                          worker (a failed prewarm must degrade to
                          compile-at-dispatch, never break training)
``wal_append``            wal.py — right AFTER a feed batch is fsync'd into
                          the write-ahead feed log, before it buffers (the
                          post-WAL-append crash window of the kill-and-
                          replay drill: the batch is durable but untrained)
``dataset_append``        basic.py Dataset.append — mid-append, after the
                          fresh rows are encoded + on device but before any
                          in-place mutation of the dataset (crash here
                          leaves it exactly pre-append, so both a restart's
                          WAL replay and an in-process retry are safe)
``online_train``          online.py refit cycle — after the Dataset append,
                          before the model update (mid-train crash: rows
                          durable + appended, model never produced)
``online_publish``        online.py refit cycle — after the new model was
                          built, before artifact save + publish + WAL
                          commit (pre-publish crash: replay retrains the
                          same batches deterministically)
``join_capture``          wal.py append_feature — right AFTER a served
                          feature row-set is fsync'd as a pending FEAT
                          record (crash here: the pending join is durable,
                          the in-memory entry may not be — recovery
                          rebuilds it, the label still joins)
``join_label``            join.py label() — label in hand, pending entry
                          popped, join NOT yet durable (crash here: the
                          feature record survives, the producer re-sends
                          the label)
``join_commit``           join.py label() — right AFTER the joined batch
                          was fed (the WAL batch record seals the join)
                          but before the producer sees the ack (crash
                          here: the re-sent label must dedup, not
                          double-train)
========================  ===================================================

The last four are the DEVICE-level chaos points (:data:`DEVICE_FAULT_POINTS`)
driving the mesh fault-tolerance layer: :func:`is_device_fault` classifies
both their injected errors and real XLA ``RESOURCE_EXHAUSTED`` failures, and
the ``on_device_fault`` policy (config.py) decides the recovery.

The harness exists so the retry / atomic-write / resume machinery can be
*proven* under failure in CPU-fast tests instead of trusted on faith; the
reference has no analog (its fault story is "CHECK and die").
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from . import log

ENV_VAR = "LGBMTPU_FAULTS"

KNOWN_POINTS = ("snapshot_write", "mapper_allgather", "dist_init",
                "tree_update", "shard_commit", "hist_allreduce",
                "device_put_oom", "prewarm_compile",
                # continuous-training crash windows (kill-and-replay drill,
                # tests/test_online_wal.py): feed -> append -> train ->
                # publish, one point per window
                "wal_append", "dataset_append", "online_train",
                "online_publish",
                # delayed-label join crash windows (tests/test_online_join.py):
                # feature capture -> label arrival -> join-commit
                "join_capture", "join_label", "join_commit")

# chaos points that simulate DEVICE failures (OOM, lost chip, dead
# collective): their injected errors classify as device faults and route
# through the on_device_fault recovery policy instead of plain propagation
DEVICE_FAULT_POINTS = ("shard_commit", "hist_allreduce", "device_put_oom",
                       "prewarm_compile")

# points whose injector raises the real XLA RESOURCE_EXHAUSTED error type
# instead of FaultInjected (see _oom_error)
_OOM_POINTS = ("device_put_oom",)

_lock = threading.Lock()
# name -> [skip_remaining, fail_remaining]; fail_remaining < 0 = fail forever
_armed: Dict[str, list] = {}
_hits: Dict[str, int] = {}
_env_loaded = False


class FaultInjected(RuntimeError):
    """Raised by an armed fault point (simulated crash/transport error)."""

    def __init__(self, point: str, hit: int):
        super().__init__(f"injected fault at '{point}' (hit #{hit})")
        self.point = point
        self.hit = hit


class SimulatedOomError(RuntimeError):
    """Fallback OOM injector error when the jaxlib runtime error type cannot
    be constructed (jax not importable / exotic jaxlib). The message still
    carries RESOURCE_EXHAUSTED so :func:`is_resource_exhausted` matches."""


def _xla_runtime_error_type():
    """The runtime's error class (``jax.errors.JaxRuntimeError``, the
    former ``XlaRuntimeError``); None only where jax is not importable."""
    try:
        from jax.errors import JaxRuntimeError
        return JaxRuntimeError
    except ImportError:
        return None


def _oom_error(point: str, hit: int) -> BaseException:
    """Simulated device OOM: the REAL XLA error type with the REAL status
    prefix, so product recovery paths (which catch XlaRuntimeError and match
    RESOURCE_EXHAUSTED) exercise the exact branch a production OOM takes."""
    msg = (f"RESOURCE_EXHAUSTED: injected device OOM at '{point}' "
           f"(hit #{hit})")
    err_t = _xla_runtime_error_type()
    if err_t is not None:
        try:
            return err_t(msg)
        except Exception:
            pass
    return SimulatedOomError(msg)


def is_resource_exhausted(exc: BaseException) -> bool:
    """True for XLA allocation failures: the runtime surfaces device OOM as
    an ``XlaRuntimeError`` whose message starts with the canonical absl
    status name ``RESOURCE_EXHAUSTED`` (same for the injected form)."""
    if isinstance(exc, SimulatedOomError):
        return True
    err_t = _xla_runtime_error_type()
    if err_t is not None and not isinstance(exc, err_t):
        return False
    return "RESOURCE_EXHAUSTED" in str(exc)


def is_compile_oom(exc: BaseException) -> bool:
    """True for a RESOURCE_EXHAUSTED raised by the COMPILER rather than the
    allocator: the program as compiled cannot fit — a Mosaic kernel over the
    scoped-VMEM limit, or buffers beyond HBM ("Ran out of memory in memory
    space vmem|hbm ..."; the allocator's runtime failures read "Error
    allocating device buffer"). It is deterministic: the same program fails
    the same way on every retry, so it is not a device fault."""
    return (is_resource_exhausted(exc)
            and "Ran out of memory in memory space" in str(exc))


def is_device_fault(exc: BaseException) -> bool:
    """Classify an exception as a device-level fault: a real (or injected)
    XLA RESOURCE_EXHAUSTED from the allocator, or a :class:`FaultInjected`
    from one of the device chaos points. This is the predicate the
    ``on_device_fault`` recovery policies key on (ingest.py,
    models/gbdt.py); a compile-time out-of-memory (:func:`is_compile_oom`)
    is excluded because no retry, smaller chunk or re-plan of the same
    program can cure it."""
    if isinstance(exc, FaultInjected):
        return exc.point in DEVICE_FAULT_POINTS
    return is_resource_exhausted(exc) and not is_compile_oom(exc)


def classify_point(exc: BaseException, default: str = "device") -> str:
    """Best-effort fault-point name for telemetry: the point attribute for
    :class:`FaultInjected`, a registry name embedded in the message for the
    simulated-OOM injectors, else ``default`` (real faults carry no point)."""
    if isinstance(exc, FaultInjected):
        return exc.point
    msg = str(exc)
    for p in DEVICE_FAULT_POINTS:
        if p in msg:
            return p
    return default


def _parse_spec(spec: str) -> Dict[str, list]:
    out: Dict[str, list] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        skip = 0
        name = part
        count = "1"
        if ":" in part:
            name, count = part.split(":", 1)
        if "@" in name:
            # name@K -> skip the first K hits, then fail (count times)
            name, skip_s = name.split("@", 1)
            skip = int(skip_s)
            if ":" not in part:
                count = "-1"
        name = name.strip()
        n = -1 if count.strip() in ("-1", "*", "inf") else int(count)
        if name not in KNOWN_POINTS:
            # reject, don't warn-and-arm: a typo'd point would never fire,
            # so the chaos test it belongs to would pass without injecting
            # anything — a fault harness that can silently do nothing is
            # worse than none
            raise ValueError(
                f"unknown fault point '{name}' in spec {spec!r}; known "
                f"points: {', '.join(KNOWN_POINTS)} (see the registry in "
                "lightgbm_tpu/utils/faults.py)")
        out[name] = [skip, n]
    return out


def configure(spec: Optional[str]) -> None:
    """Arm fault points from a spec string (empty/None disarms everything).
    Raises ValueError on an unknown point name."""
    global _env_loaded
    armed = _parse_spec(spec) if spec else {}
    with _lock:
        _armed.clear()
        _hits.clear()
        _env_loaded = True   # explicit configure overrides the env var
        _armed.update(armed)


def reset() -> None:
    """Disarm all fault points and forget hit counts (test teardown)."""
    global _env_loaded
    with _lock:
        _armed.clear()
        _hits.clear()
        _env_loaded = False


def _ensure_env_loaded() -> None:
    global _env_loaded
    if _env_loaded:
        return
    _env_loaded = True
    spec = os.environ.get(ENV_VAR, "")
    if spec:
        _armed.update(_parse_spec(spec))
        log.info(f"fault injection armed from {ENV_VAR}: {spec}")


def fault_point(name: str) -> None:
    """Hot-path hook: no-op unless ``name`` is armed, else raise — a
    :class:`FaultInjected`, or for the simulated-OOM points the real XLA
    ``RESOURCE_EXHAUSTED`` error type — while the armed count lasts."""
    with _lock:
        _ensure_env_loaded()
        state = _armed.get(name)
        _hits[name] = _hits.get(name, 0) + 1
        if state is None:
            return
        if state[0] > 0:        # still skipping
            state[0] -= 1
            return
        if state[1] == 0:       # exhausted: succeed from now on
            return
        if state[1] > 0:
            state[1] -= 1
        hit = _hits[name]
    from .. import obs   # lazy: obs -> atomic_io -> this module
    obs.emit("fault_injected", point=name, hit=hit)
    if name in _OOM_POINTS:
        raise _oom_error(name, hit)
    raise FaultInjected(name, hit)


def hits(name: str) -> int:
    """How many times a fault point was reached (armed or not)."""
    with _lock:
        return _hits.get(name, 0)


def is_armed(name: str) -> bool:
    with _lock:
        _ensure_env_loaded()
        s = _armed.get(name)
        return bool(s and (s[0] > 0 or s[1] != 0))
