"""Chunked three-stage ingest pipeline: encode -> H2D -> commit.

Reference analog: ``PipelineReader`` (utils/pipeline_reader.h), which
prefetches+parses the next block on a background thread while the consumer
works on the current one, and the OpenCL learner's async feature-matrix
transfer (gpu_tree_learner.cpp). Here the same shape feeds the TPU:

- **encode** — a pool of ``encode_threads`` host workers bins row chunks
  (``binning.bin_data`` + EFB ``apply_bundles``; the native encoder releases
  the GIL, so chunks genuinely encode in parallel),
- **H2D** — one uploader thread ``jax.device_put``s each encoded chunk;
  the bounded queue in front of it keeps at most two chunks in flight
  (double buffering), so chunk i+1 transfers while chunk i commits,
- **commit** — one thread folds each uploaded chunk into a donated
  device accumulator (``_set_rows``) and blocks for completion, which is
  what backpressures the whole pipeline to device speed.

Mesh-native sharding: with a ``RowShardPlan`` (parallel/mesh.py) each chunk
is routed to its OWNING shard — chunk boundaries are aligned to the shard
grid (a chunk never spans two shards), the uploader device_puts straight to
the shard's device, and the commit stage keeps one donated accumulator PER
shard, so the full matrix never materializes on any single device. The
per-shard buffers are stitched into one global row-sharded array with
``jax.make_array_from_single_device_arrays`` at the end — zero copies,
zero relayout, because the plan's contiguous row blocks are exactly the
layout of ``NamedSharding(mesh, P(axis, None))``. Padding rows (shard grid
round-up) stay zero; the trainer masks them with zero gradients/hessians.

Every stage communicates over bounded queues: a full queue blocks the
producer (backpressure), a ``None`` sentinel terminates each consumer, and
the first exception from any stage is stashed and re-raised on the caller's
thread after join — the same protocol as serving.py's chunked predictor.

Determinism: chunk boundaries depend only on ``chunk_rows``; each chunk is
encoded by a pure per-row function; commits write DISJOINT row ranges of the
accumulator, so neither the number of encode threads nor the completion
order can change a single bit of the result (asserted by
tests/test_ingest_pipeline.py).

Thread-safety: the module-level last-run stats are guarded by
``_STATS_LOCK`` — this module is in the ``unlocked-shared-state`` tpu-lint
scope, same as serving.py and obs/.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .binning import bin_data
from .utils import faults, log

# accumulate rows into ONE preallocated device buffer via a donated
# dynamic-update (peak device memory 1x + in-flight chunks; a concatenate of
# all chunks would transiently hold 2x). Module-level so the jit wrapper (and
# its trace cache) is shared across Dataset constructions instead of being
# rebuilt — and retraced — per call.
_set_rows = jax.jit(
    lambda acc, chunk, s0: jax.lax.dynamic_update_slice(acc, chunk, (s0, 0)),
    donate_argnums=0)


@functools.lru_cache(maxsize=64)
def _device_zeros_maker(shape, dtype, device):
    """Cached jit wrapper producing zeros directly ON ``device`` — the cache
    keeps one wrapper (and one trace) per (shape, dtype, device) across
    Dataset constructions instead of rebuilding it per shard."""
    from jax.sharding import SingleDeviceSharding
    # the enclosing lru_cache IS the hoist: one wrapper per distinct
    # (shape, dtype, device) key  # tpu-lint: disable=retrace-hazard
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=SingleDeviceSharding(device))


def _device_zeros(shape, dtype, device):
    """Allocate a zero buffer directly ON ``device`` — no host-side zeros
    materialization and no transfer (a host np.zeros + device_put would cost
    a full-buffer H2D per shard just to ship zeros)."""
    return _device_zeros_maker(tuple(shape), jnp.dtype(dtype), device)()

# stats of the most recent pipeline run (profiling surface for the bench);
# guarded: construct can run from
# a worker thread while a profiler thread reads
_STATS_LOCK = threading.Lock()
LAST_INGEST_STATS: Dict[str, Any] = {}


def resolve_encode_threads(requested: int) -> int:
    """0 = auto: enough threads to keep encode off the critical path without
    oversubscribing the host (the native encoder may also use num_threads
    internally per call)."""
    if requested and requested > 0:
        return int(requested)
    import os
    return max(1, min(4, os.cpu_count() or 1))


def overlap_efficiency(stage_spans, wall_s: float) -> float:
    """How much of the *possible* stage overlap was realized, in [0, 1].

    ``stage_spans`` are per-stage ideal busy spans (seconds). With no overlap
    the wall is their sum; with perfect overlap it is their max. The ratio is
    (sum - wall) / (sum - max), clamped — 1.0 when one stage dominates so
    completely that there is nothing to hide."""
    total = float(sum(stage_spans))
    longest = float(max(stage_spans)) if stage_spans else 0.0
    max_savable = total - longest
    if max_savable <= 1e-9:
        return 1.0
    saved = total - float(wall_s)
    return max(0.0, min(1.0, saved / max_savable))


def stream_encode_upload(raw, mappers, meta, *, width: int,
                         chunk_rows: int, encode_threads: int = 0,
                         phases: Optional[Dict[str, Any]] = None,
                         shard_plan=None, encode_fn=None, row0: int = 0):
    """Run the three-stage pipeline over ``raw`` [N, F_raw] and return the
    device bin matrix: [N, width] uint8 on one device, or — with a
    ``shard_plan`` (parallel/mesh.RowShardPlan) — a global
    [n_padded, width] array row-sharded over the plan's mesh.

    ``meta`` is the (already planned) EFB bundle meta or None; bundling is
    applied per chunk inside the encode stage so the unbundled matrix never
    exists on device. ``phases`` (optional dict) receives the disjoint
    per-stage busy breakdown + ``overlap_efficiency``.

    ``encode_fn`` (optional) replaces the default encode stage body: it is
    called as ``encode_fn(raw[g0:g1])`` and must return the FINAL
    [rows, width] uint8 chunk (any EFB bundling already applied). The
    continuous-training append path uses it to re-bin fresh rows against a
    constructed Dataset's frozen mappers (``binning.rebin_frozen``) instead
    of re-deriving used columns from scratch; the function must be pure and
    thread-safe — it runs concurrently on the encode pool.
    """
    from .efb import apply_bundles

    n = int(raw.shape[0])
    if n == 0 and shard_plan is None:
        return jnp.zeros((0, width), jnp.uint8)
    chunk_rows = max(1, int(chunk_rows))
    proc = jax.process_index()
    if shard_plan is not None:
        # chunk grid aligned to the shard grid: every chunk lies inside ONE
        # shard's row block, so the uploader can target the owning device
        # and commits stay single-device dynamic-update-slices. In pod mode
        # (a plan whose mesh spans processes) each host only builds tasks for
        # the shards IT owns; ``row0`` translates the plan's global row
        # coordinates into indices of this host's local ``raw`` slice.
        chunk_rows = min(chunk_rows, shard_plan.rows_per_shard)
        tasks = []
        for s in range(shard_plan.num_shards):
            if shard_plan.devices[s].process_index != proc:
                continue
            lo, hi = shard_plan.shard_rows_range(s)
            tasks.extend((s, g0, min(g0 + chunk_rows, hi))
                         for g0 in range(lo, hi, chunk_rows))
    else:
        tasks = [(None, g0, min(g0 + chunk_rows, n))
                 for g0 in range(0, n, chunk_rows)]
    threads = min(resolve_encode_threads(encode_threads), max(len(tasks), 1))
    tele = obs.enabled()

    work_q: "queue.Queue" = queue.Queue()
    for ci, (shard, g0, g1) in enumerate(tasks):
        work_q.put((ci, shard, g0, g1))
    # encoded chunks awaiting H2D: one being transferred + one ready is the
    # double buffer; a deeper queue would only raise host memory pressure
    enc_q: "queue.Queue" = queue.Queue(maxsize=2)
    # uploaded chunks awaiting commit
    dev_q: "queue.Queue" = queue.Queue(maxsize=2)
    state: Dict[str, Any] = {"acc": None, "accs": {}, "exc": None,
                             "encode_s": 0.0, "h2d_s": 0.0, "commit_s": 0.0}
    lock = threading.Lock()

    def _fail(e: BaseException) -> None:
        with lock:
            if state["exc"] is None:
                state["exc"] = e

    def _encode_loop():
        while True:
            try:
                ci, shard, g0, g1 = work_q.get_nowait()
            except queue.Empty:
                return
            with lock:
                if state["exc"] is not None:
                    continue   # drain remaining work items without encoding
            try:
                t0 = time.perf_counter()
                if encode_fn is not None:
                    cb = encode_fn(raw[g0 - row0:g1 - row0])
                else:
                    cb = bin_data(raw[g0 - row0:g1 - row0], mappers).bins
                    if meta is not None:
                        cb = apply_bundles(cb, meta)
                cb = np.ascontiguousarray(cb)
                dt = time.perf_counter() - t0
                with lock:
                    state["encode_s"] += dt
                enc_q.put((ci, shard, g0, cb, dt))
            except BaseException as e:   # surfaced after join
                _fail(e)

    def _h2d_loop():
        while True:
            item = enc_q.get()
            if item is None:
                dev_q.put(None)
                return
            with lock:
                if state["exc"] is not None:
                    continue   # keep draining so encoder puts never block
            try:
                ci, shard, g0, cb, enc_dt = item
                t0 = time.perf_counter()
                # chaos point: simulated device OOM on the H2D transfer
                # (raises the real XLA RESOURCE_EXHAUSTED error type)
                faults.fault_point("device_put_oom")
                if shard is not None:
                    # straight to the owning shard's device — the global
                    # matrix never exists on any single chip
                    dev = jax.device_put(cb, shard_plan.devices[shard])
                else:
                    # single-accumulator path: follows the ambient default
                    # device on purpose (the plan-less contract predates the
                    # mesh)  # tpu-lint: disable=unsharded-transfer
                    dev = jax.device_put(cb)
                # block for transfer completion: h2d_s must measure the copy,
                # not the async enqueue — this thread exists so the wait
                # overlaps encode(i+1) and commit(i-1)
                dev.block_until_ready()   # tpu-lint: disable=host-sync-in-jit
                dt = time.perf_counter() - t0
                with lock:
                    state["h2d_s"] += dt
                dev_q.put((ci, shard, g0, dev, cb.shape[0], enc_dt, dt))
            except BaseException as e:
                _fail(e)

    def _commit_loop():
        while True:
            item = dev_q.get()
            if item is None:
                return
            with lock:
                if state["exc"] is not None:
                    continue
            try:
                ci, shard, g0, dev, rows, enc_dt, h2d_dt = item
                t0 = time.perf_counter()
                if shard is not None:
                    # chaos point: a chunk's fold into its owning shard's
                    # donated accumulator failed (lost chip / dead buffer)
                    faults.fault_point("shard_commit")
                    with lock:
                        acc = state["accs"].get(shard)
                    if acc is None:
                        # donated per-shard accumulator, allocated lazily ON
                        # its device (zero rows beyond the shard's real rows
                        # are the padding the trainer masks)
                        acc = _device_zeros(
                            (shard_plan.rows_per_shard, width), dev.dtype,
                            shard_plan.devices[shard])
                    local0 = g0 - shard * shard_plan.rows_per_shard
                    acc = _set_rows(acc, dev, jnp.int32(local0))
                    # single-writer: only this commit thread ever folds into
                    # accs; the lock publishes the slot to concurrent readers
                    with lock:  # tpu-lint: disable=lock-order
                        state["accs"][shard] = acc
                else:
                    if state["acc"] is None:
                        with lock:
                            state["acc"] = jnp.zeros((n, width), dev.dtype)
                    with lock:
                        acc = _set_rows(state["acc"], dev, jnp.int32(g0))
                        state["acc"] = acc
                # block: the donated accumulate must finish before the next
                # donation, and the wait here is the pipeline's backpressure
                acc.block_until_ready()   # tpu-lint: disable=host-sync-in-jit
                dt = time.perf_counter() - t0
                with lock:
                    state["commit_s"] += dt
                if tele:
                    depth = enc_q.qsize() + dev_q.qsize()
                    obs.METRICS.gauge(
                        "ingest_pipeline_depth",
                        "high-water chunks queued between ingest stages"
                    ).set_max(depth + 1)
                    obs.METRICS.counter("ingest_chunks",
                                        "chunks through the pipeline").inc()
                    owner = {} if shard is None else {"shard": int(shard)}
                    obs.emit("ingest_chunk", chunk=int(ci), rows=int(rows),
                             encode_s=float(enc_dt), h2d_s=float(h2d_dt),
                             commit_s=float(dt), depth=int(depth),
                             bytes=int(rows * width),
                             thread=threading.current_thread().name, **owner)
            except BaseException as e:
                _fail(e)

    t_wall = time.perf_counter()
    encoders = [threading.Thread(target=_encode_loop, daemon=True,
                                 name=f"ingest-encode-{i}")
                for i in range(threads)]
    up = threading.Thread(target=_h2d_loop, daemon=True, name="ingest-h2d")
    cm = threading.Thread(target=_commit_loop, daemon=True,
                          name="ingest-commit")
    for th in encoders:
        th.start()
    up.start()
    cm.start()
    try:
        for th in encoders:
            th.join()
    finally:
        enc_q.put(None)   # _h2d_loop forwards the sentinel to _commit_loop
        up.join()
        cm.join()
    if state["exc"] is not None:
        raise state["exc"]
    wall = time.perf_counter() - t_wall
    # per-stage ideal spans: encode busy is summed across workers, so divide
    # by the pool size for the ideally-parallel span the wall is compared to
    spans = (state["encode_s"] / max(threads, 1), state["h2d_s"],
             state["commit_s"])
    eff = overlap_efficiency(spans, wall)
    stats = {"encode_s": round(state["encode_s"], 3),
             "h2d_s": round(state["h2d_s"], 3),
             "commit_s": round(state["commit_s"], 3),
             "encode_threads": threads, "chunks": len(tasks),
             "chunk_rows": chunk_rows, "wall_s": round(wall, 3),
             "overlap_efficiency": round(eff, 3),
             "shards": (shard_plan.num_shards if shard_plan is not None
                        else 1)}
    with _STATS_LOCK:
        LAST_INGEST_STATS.clear()
        LAST_INGEST_STATS.update(stats)
    if phases is not None:
        phases["stream_busy"] = {k: stats[k] for k in
                                 ("encode_s", "h2d_s", "commit_s",
                                  "encode_threads", "chunks")}
        phases["overlap_efficiency"] = stats["overlap_efficiency"]
    log.debug("ingest pipeline: %s", stats)
    if shard_plan is None:
        return state["acc"]
    # stitch the per-shard buffers into ONE global row-sharded array — no
    # copy: every buffer already lives on its owning device and the plan's
    # contiguous blocks are the NamedSharding layout. In pod mode each host
    # contributes only the buffers for ITS shards (legal: multiprocess
    # make_array_from_single_device_arrays takes addressable buffers only).
    # With a 2-D (data, feature) mesh the row block is replicated across the
    # shard's feature-axis devices — all local, so the replication copies
    # never cross hosts.
    arrays = []
    for s in range(shard_plan.num_shards):
        if shard_plan.devices[s].process_index != proc:
            continue
        a = state["accs"].get(s)
        if a is None:   # shard holds only padding rows (n < num_shards * rps)
            a = _device_zeros((shard_plan.rows_per_shard, width), jnp.uint8,
                              shard_plan.devices[s])
        arrays.append(a)
        row_devs = (shard_plan.row_devices(s)
                    if hasattr(shard_plan, "row_devices") else [])
        for d in row_devs[1:]:
            arrays.append(jax.device_put(a, d))
    return jax.make_array_from_single_device_arrays(
        (shard_plan.n_padded, width), shard_plan.sharding(2), arrays)


def last_stats() -> Dict[str, Any]:
    """Copy of the most recent pipeline run's stage breakdown."""
    with _STATS_LOCK:
        return dict(LAST_INGEST_STATS)


# OOM-adaptive degradation bounds (stream_with_recovery): at most this many
# chunk halvings before escalating to the policy action, and a hard cap on
# total recovery attempts so a persistent fault can never loop forever
MAX_CHUNK_HALVINGS = 3
MAX_RECOVERY_ATTEMPTS = 8


def _grow_plan(plan):
    """Re-plan the row sharding over more devices (double, clamped to the
    device count); None when the plan cannot grow."""
    if plan is None:
        return None
    fs = int(getattr(plan, "feature_shards", 1) or 1)
    nd = jax.device_count() // fs
    if plan.num_shards >= nd:
        return None
    from .parallel.mesh import plan_row_sharding
    return plan_row_sharding(plan.n_rows, min(nd, plan.num_shards * 2),
                             axis_name=plan.axis_name, feature_shards=fs)


def stream_with_recovery(raw, mappers, meta, *, width: int, chunk_rows: int,
                         encode_threads: int = 0,
                         phases: Optional[Dict[str, Any]] = None,
                         shard_plan=None, policy: str = "reshard",
                         sleep=time.sleep, encode_fn=None, row0: int = 0):
    """:func:`stream_encode_upload` with OOM-adaptive degradation.

    A device-level fault during the pipeline (XLA ``RESOURCE_EXHAUSTED`` on
    the H2D transfer or commit, or an injected device chaos point — see
    ``utils.faults.is_device_fault``) is recovered per the ``on_device_fault``
    policy instead of propagating:

    1. **halve the chunk** — up to :data:`MAX_CHUNK_HALVINGS` times; smaller
       chunks shrink both the host staging buffer and the in-flight transfer,
       the usual cure for a transient allocator squeeze,
    2. then policy ``reshard`` — re-plan the row sharding over MORE devices
       (each shard's resident slice shrinks proportionally),
       or policy ``fallback_single`` — drop the plan and drain through the
       single-device path with a warning,
    3. policy ``fatal`` (or a non-device fault) re-raises immediately —
       reference CHECK semantics.

    Each recovery emits a schema-registered ``device_fault`` event and sleeps
    a deterministic backoff. Returns ``(bins_dev, plan, chunk_rows)`` — the
    plan/chunk size actually used, which the caller must adopt (the published
    Dataset plan and the prewarm spec both key on them).
    """
    from .utils.retry import backoff_delays

    plan = shard_plan
    # a plan whose mesh spans processes (pod mode) must keep the SAME shard
    # grid on every host — re-planning or dropping to single-device here would
    # diverge the global sharding this host commits into. Chunk halving stays
    # available (it is grid-preserving); the plan-changing rungs are disabled.
    multiproc = plan is not None and any(
        d.process_index != jax.process_index() for d in plan.mesh.devices.flat)
    rows = max(1, int(chunk_rows))
    halvings = 0
    attempt = 0
    delays = list(backoff_delays(MAX_RECOVERY_ATTEMPTS + 1,
                                 base_delay=0.05, max_delay=1.0))
    while True:
        try:
            bins = stream_encode_upload(
                raw, mappers, meta, width=width, chunk_rows=rows,
                encode_threads=encode_threads, phases=phases,
                shard_plan=plan, encode_fn=encode_fn, row0=row0)
            return bins, plan, rows
        except BaseException as e:
            if policy == "fatal" or not faults.is_device_fault(e):
                raise
            attempt += 1
            if attempt > MAX_RECOVERY_ATTEMPTS:
                raise
            point = faults.classify_point(e)
            before = plan.num_shards if plan is not None else 1
            after = before
            if halvings < MAX_CHUNK_HALVINGS and rows > 1:
                rows = max(1, rows // 2)
                halvings += 1
                action = "halve_chunk"
                log.warning(
                    f"device fault during ingest ({type(e).__name__}: {e}); "
                    f"halving chunk to {rows} rows and retrying "
                    f"({halvings}/{MAX_CHUNK_HALVINGS})")
            elif (policy == "reshard" and not multiproc
                  and (grown := _grow_plan(plan)) is not None):
                plan = grown
                after = plan.num_shards
                action = "reshard"
                log.warning(
                    f"device fault persists after chunk halving; re-planning "
                    f"row sharding {before} -> {after} shards")
            elif policy == "fallback_single" and not multiproc and plan is not None:
                plan = None
                after = 1
                action = "fallback_single"
                log.warning(
                    "device fault persists after chunk halving; draining to "
                    "the single-device ingest path (mesh training disabled "
                    "for this dataset)")
            else:
                raise
            obs.emit("device_fault", point=point, policy=policy,
                     action=action, error=f"{type(e).__name__}: {e}",
                     attempt=attempt, chunk_rows=int(rows),
                     shards_before=int(before), shards_after=int(after))
            sleep(delays[min(attempt - 1, len(delays) - 1)])
