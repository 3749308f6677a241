"""Pod-scale multi-host training: sharded ingest + merged-sketch global bins.

Reference analogs:
- per-machine data loading with ``pre_partition=true``: every machine reads
  only ITS slice of the input (dataset_loader.cpp:505-541) — here
  :func:`load_file_shard` mmaps the row range a host's devices own under the
  global :class:`~lightgbm_tpu.parallel.mesh.RowShardPlan`;
- distributed bin finding via synced samples (dataset_loader.cpp:957-1040 +
  Network::Allgather): here each host sketches its OWN rows of the shared
  global sample (:class:`~lightgbm_tpu.binning.FeatureSketch`), one
  ``process_allgather`` exchanges the sketches, and every host merges them in
  rank order — ``BinMapper.from_sketch`` on the merge is bit-identical to
  single-host ``find_bin_mappers`` on the concatenated data, so global bins
  never need a broadcast-and-trust step.

Why bins come out byte-identical to single-host construction:

1. every host draws the SAME global sample indices (same seed, same
   ``n_global``) and keeps only the indices inside its row range — the union
   across hosts is exactly the single-host sample multiset;
2. sketches are exact (sorted distinct values + integer multiplicities), and
   :func:`~lightgbm_tpu.binning.merge_sketches` is order-invariant and
   associative, so the merge equals the sketch of the concatenated sample;
3. ``from_sketch`` replays ``from_sample``'s own code path, which itself
   starts from ``np.unique`` — sketching loses nothing.

Topology contract (checked by :func:`verify_pod_plan`): the global mesh
enumerates devices process-contiguously, so host ``h`` owns a CONTIGUOUS
block of row shards — its file shard is one contiguous row range. On a 2-D
``(data, feature)`` mesh every mesh row (one row shard replicated across
feature blocks) must sit on a single host, so ingest replication never
crosses DCN.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..binning import (BIN_CATEGORICAL, BIN_NUMERICAL, BinMapper,
                       FeatureSketch, _check_max_bin_by_feature,
                       merge_sketches, sketch_feature)
from ..utils import faults, log
from ..utils.retry import call_with_backoff


@dataclasses.dataclass(frozen=True)
class HostTopology:
    """Process-level view of the pod (reference analog: the machine list)."""
    process_index: int
    process_count: int
    local_devices: int
    total_devices: int

    @property
    def is_pod(self) -> bool:
        return self.process_count > 1


def detect_topology() -> HostTopology:
    import jax
    return HostTopology(process_index=jax.process_index(),
                        process_count=jax.process_count(),
                        local_devices=jax.local_device_count(),
                        total_devices=jax.device_count())


def plan_spans_processes(plan) -> bool:
    """True when the plan's mesh includes devices of another process — the
    marker every pod-mode branch keys on."""
    if plan is None:
        return False
    import jax
    proc = jax.process_index()
    return any(d.process_index != proc for d in plan.mesh.devices.flat)


def replicate_global(x: np.ndarray, mesh) -> "object":
    """Turn a host array (identical on every process by construction) into a
    fully-replicated global ``jax.Array`` over ``mesh`` — the only legal way
    to feed a host vector into a computation spanning processes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = np.ascontiguousarray(x)
    sharding = NamedSharding(mesh, P())
    return jax.make_array_from_process_local_data(sharding, x)


def verify_pod_plan(plan) -> None:
    """Fatal unless the global plan satisfies the pod topology contract:

    - row shards are assigned to processes in non-decreasing, contiguous
      blocks (host h's rows form ONE contiguous range -> file sharding works);
    - on a 2-D mesh, all feature-axis replicas of a row shard live on the
      SAME process (ingest replication stays host-local).
    """
    last = -1
    for s in range(plan.num_shards):
        procs = {d.process_index for d in plan.row_devices(s)}
        if len(procs) > 1:
            log.fatal(f"pod plan invalid: row shard {s} spans processes "
                      f"{sorted(procs)} across the feature axis")
        p = procs.pop()
        if p < last:
            log.fatal("pod plan invalid: device enumeration is not "
                      "process-contiguous; shard->host assignment would "
                      "fragment the per-host row ranges")
        last = p


def host_row_range(plan, process_index: Optional[int] = None
                   ) -> Tuple[int, int]:
    """Global ``[row0, row1)`` of REAL rows owned by ``process_index`` under
    the global plan (``row1 == row0`` for a host holding only padding)."""
    import jax
    proc = jax.process_index() if process_index is None else int(process_index)
    lo, hi = None, None
    for s in range(plan.num_shards):
        if plan.devices[s].process_index != proc:
            continue
        slo, shi = plan.shard_rows_range(s)
        lo = slo if lo is None else min(lo, slo)
        hi = shi if hi is None else max(hi, shi)
    if lo is None:
        return 0, 0
    return lo, hi


def load_file_shard(path: str, row0: int, row1: int) -> np.ndarray:
    """Read ONLY rows ``[row0, row1)`` of an ``.npy`` matrix via mmap — no
    host ever materializes the full matrix (reference: pre_partition=true
    loading, dataset_loader.cpp:505)."""
    mm = np.load(path, mmap_mode="r")
    return np.array(mm[row0:row1])


# ---- sketch wire codec (the bin-sync Allgather payload) ----
# per-feature record: [bin_type, n_distinct, zero_cnt, na_cnt, total_cnt,
#                      distinct..., counts...]; all f64 (counts are exact in
#                      f64 up to 2^53 — far beyond any sample size)
_SK_HDR = 5


def encode_sketches(sketches: Sequence[FeatureSketch]) -> np.ndarray:
    parts = []
    for s in sketches:
        nd = len(s.distinct)
        hdr = np.array([s.bin_type, nd, s.zero_cnt, s.na_cnt, s.total_cnt],
                       dtype=np.float64)
        parts.append(hdr)
        if nd:
            parts.append(np.asarray(s.distinct, dtype=np.float64))
            parts.append(np.asarray(s.counts, dtype=np.float64))
    # f64 on the wire on purpose: distinct values ARE doubles and integer
    # tallies are exact in f64  # tpu-lint: disable=dtype-drift
    return np.concatenate(parts) if parts else np.zeros(0, np.float64)


def decode_sketches(vec: np.ndarray, num_features: int
                    ) -> List[FeatureSketch]:
    out, pos = [], 0
    for _ in range(num_features):
        bt, nd, zc, na, tot = vec[pos:pos + _SK_HDR]
        nd = int(nd)
        pos += _SK_HDR
        distinct = np.asarray(vec[pos:pos + nd], dtype=np.float64).copy()
        pos += nd
        counts = np.asarray(vec[pos:pos + nd], dtype=np.float64)
        counts = counts.astype(np.int64)
        pos += nd
        out.append(FeatureSketch(bin_type=int(bt), distinct=distinct,
                                 counts=counts, zero_cnt=int(zc),
                                 na_cnt=int(na), total_cnt=int(tot)))
    return out


def _gather_np(x: np.ndarray) -> np.ndarray:
    """``process_allgather`` with a guaranteed leading rank axis — the
    single-process shortcut returns the bare payload without one.

    This is the ONE blessed raw ``process_allgather`` call site
    (tpu-lint ``wire-dtype``): every other cross-process payload goes
    through :func:`wire_allgather`, which feeds only int32/uint8 arrays
    here — dtypes that cannot drift under ``jax_enable_x64=False``.
    """
    import jax
    from jax.experimental import multihost_utils
    out = np.asarray(multihost_utils.process_allgather(x))
    return out.reshape((jax.process_count(),) + x.shape)


# ---- raw-uint8 wire codec (the ONLY dtypes allowed on the wire) ----
# jax runs with x64 disabled, so a collective over an f64/i64 jnp array
# silently rounds the payload through f32/i32 — the bin-mapper
# byte-divergence class. Every cross-process payload therefore crosses as
# raw bytes and is reinterpreted on arrival: wire_encode -> gather ->
# wire_decode. tpu-lint's wire-dtype rule pins process_allgather to this
# file's _gather_np; new payloads MUST route through wire_allgather.


def wire_encode(arr: np.ndarray) -> np.ndarray:
    """Contiguous raw-byte (uint8) image of a host array — the only payload
    representation allowed on the cross-process wire."""
    return np.frombuffer(np.ascontiguousarray(arr).tobytes(), dtype=np.uint8)


def wire_decode(wire: np.ndarray, dtype,
                trailing_shape: Tuple[int, ...] = ()) -> np.ndarray:
    """Inverse of :func:`wire_encode`: reinterpret raw bytes as ``dtype``
    with an inferred leading dimension over ``trailing_shape``."""
    flat = np.frombuffer(np.ascontiguousarray(wire).tobytes(), dtype=dtype)
    return flat.reshape((-1,) + tuple(int(t) for t in trailing_shape))


def wire_allgather(local: np.ndarray, *, uniform: bool = False
                   ) -> List[np.ndarray]:
    """Allgather an arbitrary-dtype host payload as raw bytes.

    Returns one array per rank, each with ``local``'s dtype and trailing
    shape; leading dimensions may differ across ranks. With
    ``uniform=True`` the caller asserts every rank contributes an
    identically-shaped payload, which skips the width-negotiation
    collective (one gather on the wire instead of two) — use it for
    fixed-shape payloads like fence digests and (count, offset) metadata.
    """
    local = np.ascontiguousarray(local)
    wire = wire_encode(local)
    trailing = local.shape[1:] if local.ndim else ()
    if uniform:
        gathered = _gather_np(wire if wire.size
                              else np.zeros(1, dtype=np.uint8))
        widths = np.full(gathered.shape[0], len(wire), dtype=np.int64)
    else:
        widths = _gather_np(np.array([len(wire)],
                                     dtype=np.int32)).reshape(-1)
        wmax = max(1, int(widths.max()))
        padded = np.zeros(wmax, dtype=np.uint8)
        padded[:len(wire)] = wire
        gathered = _gather_np(padded)
    return [wire_decode(gathered[r, :int(widths[r])], local.dtype, trailing)
            for r in range(gathered.shape[0])]


def allgather_sketches(sketches: Sequence[FeatureSketch], retries: int = 3
                       ) -> List[FeatureSketch]:
    """Exchange per-host sketches and return the rank-order merge — identical
    on every host (merge_sketches is order-invariant, and every host merges
    in the SAME rank order anyway).

    Two collectives: a tiny width negotiation (per-rank payload lengths, so
    the variable-width sketch vectors can pad to one allgather-able shape)
    and ONE payload allgather. Transient failures retry with backoff; every
    rank re-enters the same pair, so a retried round stays
    collective-consistent.
    """
    f = len(sketches)
    enc = encode_sketches(sketches)

    def _sync():
        faults.fault_point("sketch_allgather")
        # f64 sketch vectors cross as raw bytes (see the wire codec note):
        # the variable per-rank widths make this the non-uniform path
        return wire_allgather(enc)

    per_rank_vecs = call_with_backoff(
        _sync, attempts=max(1, retries), base_delay=0.2,
        name="bin-sketch allgather")
    per_rank = [decode_sketches(vec, f) for vec in per_rank_vecs]
    return [merge_sketches([pr[j] for pr in per_rank]) for j in range(f)]


def find_bin_mappers_pod(
    raw_local: np.ndarray,
    n_global: int,
    row0: int,
    max_bin: int,
    min_data_in_bin: int = 3,
    sample_cnt: int = 200000,
    categorical: Optional[Sequence[int]] = None,
    use_missing: bool = True,
    zero_as_missing: bool = False,
    seed: int = 1,
    forced_bins=None,
    max_bin_by_feature=None,
    retries: int = 3,
) -> List[BinMapper]:
    """Merged-sketch global bin finding: byte-identical on every host AND to
    single-host ``find_bin_mappers`` over the concatenated rows.

    Every host draws the same global sample indices (same seed ->
    ``rng.choice(n_global, sample_cnt)`` is deterministic), keeps the ones in
    its own row range, sketches those rows, and merges the allgathered
    sketches — see the module docstring for why this is exact.
    """
    n_local, f = raw_local.shape
    rng = np.random.RandomState(seed)
    if n_global > sample_cnt:
        idx = rng.choice(n_global, sample_cnt, replace=False)
        mask = (idx >= row0) & (idx < row0 + n_local)
        sample = raw_local[idx[mask] - row0]
    else:
        sample = raw_local
    cats = set(categorical or ())
    sketches = [
        sketch_feature(sample[:, j], len(sample),
                       BIN_CATEGORICAL if j in cats else BIN_NUMERICAL)
        for j in range(f)]
    merged = allgather_sketches(sketches, retries=retries)
    per_feat_bin = _check_max_bin_by_feature(max_bin_by_feature, f, max_bin)
    return [
        BinMapper.from_sketch(
            merged[j], per_feat_bin[j], min_data_in_bin=min_data_in_bin,
            use_missing=use_missing, zero_as_missing=zero_as_missing,
            forced_bounds=(forced_bins or {}).get(j))
        for j in range(f)]


def allgather_rows(local: np.ndarray, n_global: int, row0: int,
                   retries: int = 3, name: str = "row allgather"
                   ) -> np.ndarray:
    """Assemble per-host row slices into the FULL host array on every host.

    Used for labels/weights/init scores: host-side training bookkeeping
    (objective init, boost_from_average, metric denominators) needs the
    global vectors, and they are tiny next to the feature matrix (which never
    leaves its shards). Hosts may own unequal row counts, so the payload pads
    to the max and a tiny (count, offset) allgather drives reassembly.
    """
    local = np.ascontiguousarray(local)
    n_local = int(local.shape[0])

    def _sync():
        faults.fault_point("rows_allgather")
        # the (count, offset) meta doubles as width negotiation: every rank
        # pads its slice to the max count, so the payload gather is uniform
        meta = np.stack(wire_allgather(
            np.array([n_local, row0], dtype=np.int32), uniform=True))
        nmax = max(1, int(meta[:, 0].max()))
        padded = np.zeros((nmax,) + local.shape[1:], dtype=local.dtype)
        padded[:n_local] = local
        return meta, wire_allgather(padded, uniform=True)

    meta, per_rank = call_with_backoff(_sync, attempts=max(1, retries),
                                       base_delay=0.2, name=name)
    out = np.zeros((n_global,) + local.shape[1:], dtype=local.dtype)
    for r, chunk in enumerate(per_rank):
        cnt, off = int(meta[r, 0]), int(meta[r, 1])
        if cnt:
            out[off:off + cnt] = chunk[:cnt]
    return out


def level_collective_bytes(num_features: int, max_bin: int, *,
                           num_shards: int, feature_shards: int = 1,
                           voting_top_k: int = 0, hist_slots: int = 1,
                           stat_width: int = 3, dtype_bytes: int = 4) -> dict:
    """Analytic per-device collective volume for ONE depthwise level.

    Models a ring allreduce (2*(S-1)/S of the payload crosses each link) over
    the data axis of size ``num_shards``:

    - ``full``: plain psum of the [slots, 3, F, B] histogram — O(F*B);
    - ``sliced``: the 2-D mesh path — psum of the F/feature_shards block this
      device owns plus the tiled all_gather that restores the full F axis;
    - ``voting``: PV-Tree election — two O(F) vote/score psums plus the psum
      of the k elected columns — O(k*B), independent of F.

    The bench (scripts/bench_pod.py) records these next to measured iters/s;
    the voting row drops below ``full`` once F*B outgrows 2F + k*B, i.e. for
    any realistic F >= 64 with k << F.
    """
    F, B = int(num_features), int(max_bin)
    S = max(1, int(num_shards))
    fs = max(1, int(feature_shards))
    ring = 2.0 * (S - 1) / S
    cell = hist_slots * stat_width * dtype_bytes
    full = ring * F * B * cell
    # sliced: psum moves only the owned F/fs block; the tiled all_gather then
    # delivers the (fs-1)/fs of the axis this device does not own
    sliced = ring * (F // fs) * B * cell + ((fs - 1) / fs) * F * B * cell
    k = min(int(voting_top_k), F) if voting_top_k else 0
    voting = (ring * (2 * F * dtype_bytes * hist_slots)  # votes + score psums
              + ring * k * B * cell) if k else full
    return {"full_bytes": int(full), "sliced_bytes": int(sliced),
            "voting_bytes": int(voting), "num_shards": S,
            "feature_shards": fs, "voting_top_k": k}
