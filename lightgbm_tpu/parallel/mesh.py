"""Device mesh utilities.

TPU-native replacement for the reference's machine-list/network bootstrap
(src/network/linkers_socket.cpp:80-224, Network::Init network.cpp:30): there are no
sockets or machine files — a ``jax.sharding.Mesh`` over the local (or
jax.distributed multi-host) device set plays the role of the linker topology, and
XLA collectives ride ICI/DCN automatically.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import log

DATA_AXIS = "data"
# second mesh axis for the optional 2-D ("data","feature") mesh (reference
# analog: FeatureParallelTreeLearner / VotingParallelTreeLearner column
# partitions, feature_parallel_tree_learner.cpp) — histogram allreduce volume
# per device drops by the feature-shard count (sliced psum + tiled all_gather)
FEATURE_AXIS = "feature"


@dataclasses.dataclass(frozen=True)
class RowShardPlan:
    """Row partition of an [N, ...] matrix over a 1-D (or 2-D) device mesh.

    The plan is pure metadata (mesh + row arithmetic) so it can be derived
    BEFORE the binned matrix exists — Dataset.construct publishes it ahead of
    the streamed ingest so chunk routing, the background AOT prewarm and the
    trainer's shard_map all agree on one grid. Rows are blocked contiguously:
    shard ``s`` owns global rows ``[s * rows_per_shard, (s+1) * rows_per_shard)``
    which is exactly how ``NamedSharding(mesh, P(axis, None))`` lays out the
    leading axis, so per-shard buffers assemble into the global array with
    ``jax.make_array_from_single_device_arrays`` and zero relayout.

    With ``feature_shards > 1`` the mesh is 2-D ``(data, feature)``: rows stay
    blocked over the data axis and REPLICATED over the feature axis (the bins
    spec is still ``P(data, None)``); the feature axis exists purely so the
    grower's histogram allreduce can slice by feature block.
    """
    mesh: Mesh
    axis_name: str
    num_shards: int
    n_rows: int            # true (unpadded) row count
    rows_per_shard: int    # ceil(n_rows / num_shards)
    feature_shards: int = 1
    feature_axis: str = FEATURE_AXIS

    @property
    def n_padded(self) -> int:
        return self.num_shards * self.rows_per_shard

    @property
    def pad_rows(self) -> int:
        return self.n_padded - self.n_rows

    @property
    def devices(self) -> List:
        """One OWNING device per row shard (the feature-axis leader when the
        mesh is 2-D) — the ingest pipeline commits each row block here."""
        if self.feature_shards > 1:
            return [self.mesh.devices[s, 0] for s in range(self.num_shards)]
        return list(self.mesh.devices.flat)

    def row_devices(self, s: int) -> List:
        """Every device holding a copy of row shard ``s`` (one on a 1-D mesh;
        the whole mesh row on a 2-D mesh, since bins replicate over feature)."""
        if self.feature_shards > 1:
            return list(self.mesh.devices[s, :])
        return [self.mesh.devices.flat[s]]

    def sharding(self, ndim: int = 2) -> NamedSharding:
        """Leading-axis row sharding for an ndim-dimensional array."""
        return NamedSharding(
            self.mesh, P(self.axis_name, *([None] * (ndim - 1))))

    def shard_rows_range(self, s: int):
        """Global [lo, hi) of REAL rows owned by shard ``s`` (hi <= n_rows;
        hi == lo for shards that hold only padding)."""
        lo = min(s * self.rows_per_shard, self.n_rows)
        hi = min((s + 1) * self.rows_per_shard, self.n_rows)
        return lo, hi


def resolve_num_shards(requested: int) -> int:
    """Resolve the ``num_shards`` knob (0 = auto) to a concrete shard count.

    Auto shards across every local device on accelerator backends — the
    mesh-native data-parallel path is the DEFAULT whenever
    ``jax.device_count() > 1`` on real chips. On the ``cpu`` backend extra
    devices are virtual (``--xla_force_host_platform_device_count``, used by
    the test suite to emulate a mesh on one host), so auto stays single-shard
    there and CPU sharding must be requested explicitly.
    """
    nd = jax.device_count()
    if requested and requested > 0:
        if requested > nd:
            log.warning(f"num_shards={requested} exceeds the {nd} available "
                        "devices; clamping")
        return max(1, min(int(requested), nd))
    try:
        platform = jax.devices()[0].platform
    except Exception:
        return 1
    return nd if (nd > 1 and platform != "cpu") else 1


def resolve_feature_shards(requested: int, num_features: int,
                           num_shards: int) -> int:
    """Resolve the ``feature_shards`` knob (0/1 = off) for a 2-D mesh.

    The sliced histogram allreduce needs the padded feature axis to divide
    evenly, so a non-divisor request clamps DOWN to the largest divisor of
    ``num_features``; the total ``num_shards * feature_shards`` devices must
    exist."""
    fs = int(requested or 0)
    if fs <= 1 or num_shards <= 1:
        return 1
    nd = jax.device_count()
    max_fs = max(1, nd // max(1, num_shards))
    if fs > max_fs:
        log.warning(f"feature_shards={fs} needs {num_shards}x{fs} devices but "
                    f"only {nd} exist; clamping to {max_fs}")
        fs = max_fs
    if num_features > 0 and num_features % fs != 0:
        d = fs
        while d > 1 and num_features % d != 0:
            d -= 1
        log.warning(f"feature_shards={fs} does not divide {num_features} "
                    f"features; clamping to divisor {d}")
        fs = d
    return max(1, fs)


def plan_row_sharding(n_rows: int, num_shards: int,
                      axis_name: str = DATA_AXIS,
                      feature_shards: int = 1) -> Optional[RowShardPlan]:
    """Build the row-shard plan, or None when one shard (single-chip path)."""
    if num_shards <= 1 or n_rows <= 0:
        return None
    feature_shards = max(1, int(feature_shards))
    mesh = make_mesh(num_shards * feature_shards, axis_name=axis_name,
                     feature_shards=feature_shards)
    rps = -(-n_rows // num_shards)   # ceil
    return RowShardPlan(mesh=mesh, axis_name=axis_name,
                        num_shards=num_shards, n_rows=int(n_rows),
                        rows_per_shard=int(rps),
                        feature_shards=feature_shards)


def make_mesh(num_devices: Optional[int] = None, axis_name: str = DATA_AXIS,
              devices: Optional[Sequence] = None,
              feature_shards: int = 1,
              feature_axis: str = FEATURE_AXIS) -> Mesh:
    """1-D data-parallel mesh, or 2-D (data, feature) when feature_shards > 1."""
    devs = list(devices) if devices is not None else jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    if feature_shards > 1:
        d = len(devs) // feature_shards
        arr = np.array(devs[: d * feature_shards]).reshape(d, feature_shards)
        return Mesh(arr, (axis_name, feature_axis))
    return Mesh(np.array(devs), (axis_name,))


def shard_rows(x, mesh: Mesh, axis_name: str = DATA_AXIS):
    """Place an array sharded along its leading (row) axis."""
    spec = P(axis_name, *([None] * (x.ndim - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def replicate(x, mesh: Mesh):
    return jax.device_put(x, NamedSharding(mesh, P()))


def pad_rows_to_devices(x: np.ndarray, n_dev: int):
    """Pad row count to a multiple of the mesh size; returns (padded, orig_n)."""
    n = x.shape[0]
    pad = (-n) % n_dev
    if pad:
        pad_width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        x = np.pad(x, pad_width)
    return x, n


_DISTRIBUTED_INITIALIZED = False


def init_distributed(config) -> bool:
    """Multi-host bootstrap (reference analog: Network::Init, network.cpp:30 +
    the machine-list linkers, linkers_socket.cpp:80-224).

    Reference conventions mapped to jax.distributed:
    - ``machines`` = comma-separated host:port list (reference 'machines'
      param); the FIRST entry is the coordinator (every process must pass the
      same list)
    - ``num_machines`` = process count
    - the process id comes from ``machine_list_file`` position in the
      reference; here it must be provided via the standard jax env
      (JAX_PROCESS_ID) or cluster auto-detection.

    Called automatically by the GBDT trainer when num_machines > 1. Idempotent.
    Returns True when running multi-process.
    """
    global _DISTRIBUTED_INITIALIZED
    if config.num_machines <= 1:
        return False
    if _DISTRIBUTED_INITIALIZED:
        return True
    machines = config.machines
    if not machines and config.machine_list_filename:
        # reference: machine_list_filename — one host[:port] per line
        # (linkers_socket.cpp:80 ParseMachineList)
        with open(config.machine_list_filename) as fh:
            entries = [ln.split("#", 1)[0].strip() for ln in fh]
            # 'host port' lines (any whitespace) -> 'host:port'
            machines = ",".join(":".join(e.split()) for e in entries if e)
    coords = None
    if machines:
        coords = machines.split(",")[0].strip()
        if ":" not in coords:
            # entries without a port listen on local_listen_port (reference:
            # config.h local_listen_port default 12400)
            coords = f"{coords}:{config.local_listen_port}"
    import os
    pid = os.environ.get("JAX_PROCESS_ID")
    kwargs = {"num_processes": config.num_machines}
    if coords:
        kwargs["coordinator_address"] = coords
    if pid is not None:
        kwargs["process_id"] = int(pid)
    if config.time_out and config.time_out > 0:
        # reference time_out is in minutes (config.h:306); jax takes seconds.
        # Applied unconditionally so the 120-minute default is honored too
        # (jax's own default is only ~5 minutes)
        kwargs["initialization_timeout"] = int(config.time_out) * 60
    # transient bootstrap failures (coordinator not yet listening, DNS
    # hiccup) retry with backoff — the reference's socket linkers likewise
    # retry Connect inside a timeout loop (linkers_socket.cpp:171-224)
    from ..utils import faults
    from ..utils.retry import call_with_backoff

    def _init_once():
        faults.fault_point("dist_init")
        jax.distributed.initialize(**kwargs)

    call_with_backoff(_init_once,
                      attempts=max(1, int(getattr(config, "network_retries",
                                                  3))),
                      base_delay=0.5, name="jax.distributed.initialize")
    _DISTRIBUTED_INITIALIZED = True
    log.info(f"jax.distributed initialized: process {jax.process_index()} "
             f"of {jax.process_count()} ({jax.device_count()} devices)")
    return True
