"""User-facing Dataset and Booster.

Mirrors the reference python package's core objects (python-package/lightgbm/
basic.py:712 Dataset, :1666 Booster) — but there is no ctypes/C-API hop: the Python
layer talks directly to the JAX device runtime. Binning happens lazily at
``construct()`` time like the reference's lazy Dataset, and validation sets are
aligned to the training set's bin mappers (reference: Dataset::CreateValid,
dataset.cpp:742).
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

try:
    import pandas as pd
    _PANDAS = True
except Exception:  # pragma: no cover
    _PANDAS = False

import jax
import jax.numpy as jnp

from .binning import BinMapper, BinnedDataset, bin_data, find_bin_mappers
from .config import Config, canonical_name, params_to_config
from .metrics import create_metrics, default_metric_for_objective
from .models.gbdt import GBDT
from .models.tree import Tree, stack_trees
from .objectives import create_objective
from .ops import predict as P
from .utils import faults, log
from .io import model_text


def _is_scipy_sparse(data) -> bool:
    try:
        import scipy.sparse as sps
    except Exception:  # pragma: no cover
        return False
    return sps.issparse(data)


def _data_from_pandas(df, pandas_categorical: Optional[List] = None):
    """Encode a DataFrame to float64, mapping CategoricalDtype columns to their
    integer codes (reference: _data_from_pandas, python-package
    basic.py:313-400). At train time (``pandas_categorical=None``) the category
    lists are captured from the frame; at predict time they REORDER the input's
    categories so string categoricals map to the same codes as training.
    Returns (array, pandas_categorical)."""
    cat_cols = [c for c, dt in zip(df.columns, df.dtypes)
                if isinstance(dt, pd.CategoricalDtype)]
    bad = [str(c) for c, dt in zip(df.columns, df.dtypes)
           if dt == object and c not in cat_cols]
    if bad:
        log.fatal("DataFrame.dtypes must be int, float or bool; did you mean "
                  f"astype('category') for columns {', '.join(bad)}?")
    if pandas_categorical is None:
        pandas_categorical = [list(df[c].cat.categories) for c in cat_cols]
    elif len(cat_cols) != len(pandas_categorical):
        log.fatal("train and valid/predict DataFrames have different numbers "
                  "of categorical columns")
    if cat_cols:
        df = df.copy(deep=False)
        for c, cats in zip(cat_cols, pandas_categorical):
            codes = (df[c].cat.set_categories(cats).cat.codes
                     .to_numpy(dtype=np.float64))
            df[c] = np.where(codes < 0, np.nan, codes)  # -1 = NaN/unseen
    return df.to_numpy(dtype=np.float64, na_value=np.nan), pandas_categorical


def _to_numpy_2d(data, pandas_categorical: Optional[List] = None) -> np.ndarray:
    if _PANDAS and isinstance(data, pd.DataFrame):
        return _data_from_pandas(data, pandas_categorical)[0]
    # f32 input stays f32: the native binner upcasts per value in-register
    # (exact), sparing the 2x host copy at 10M-row scale
    arr = np.asarray(data)
    if arr.dtype != np.float32:
        arr = np.asarray(arr, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


def _to_numpy_1d(data) -> Optional[np.ndarray]:
    if data is None:
        return None
    if _PANDAS and isinstance(data, (pd.Series,)):
        data = data.to_numpy()
    return np.asarray(data, dtype=np.float64).reshape(-1)


class Dataset:
    """Training dataset (reference: lightgbm.Dataset, basic.py:712).

    Lazily constructed: raw data is kept host-side until ``construct()`` bins it and
    ships the uint8 bin matrix to device HBM.
    """

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        t0 = time.time()
        self.params = dict(params or {})
        self.raw_data = data
        self.label = _to_numpy_1d(label)
        self.weight = _to_numpy_1d(weight)
        self.group = None if group is None else np.asarray(group, dtype=np.int64)
        self.init_score = _to_numpy_1d(init_score)
        # the row vectors as float64: the first of construct_phases
        self._init_s = round(time.time() - t0, 3)
        self.reference = reference
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self._constructed = False
        self.construct_phases: Dict[str, Any] = {}
        self.bundle_meta = None   # set by construct() when EFB bundles
        self.pandas_categorical = None  # per-cat-column category lists
        # filled by construct():
        self.mappers: List[BinMapper] = []
        self.feature_map: Optional[np.ndarray] = None
        self.bins = None            # jnp uint8 [N, F_used] ([N_pad, F_used]
        #                             row-sharded when shard_plan is set)
        self.shard_plan = None      # parallel.mesh.RowShardPlan or None
        self.num_bins_dev = None    # jnp i32 [F_used]
        self.na_bin_dev = None      # jnp i32 [F_used]
        self.missing_type_dev = None
        self._names: List[str] = []
        self._num_data = None
        self._num_features_raw = None
        self._num_features_used = None  # F_b, known once metadata publishes
        self._prewarm = None            # background AOT compile handle
        if data is not None:
            arr_shape = (data.shape if hasattr(data, "shape")
                         else np.asarray(data).shape)
            self._num_data = arr_shape[0]
            self._num_features_raw = arr_shape[1] if len(arr_shape) > 1 else 1

    # ---- device bin matrix + cached transpose ----
    @property
    def bins(self):
        """Device uint8 bin matrix [N, F_used] (row-sharded: [N_pad, F_used])."""
        return self._bins_dev

    @bins.setter
    def bins(self, value):
        # every assignment (construct / append / subset / add_features_from)
        # drops the transposed cache with it — the two can never disagree
        self._bins_dev = value
        self._bins_T = None

    @property
    def bins_T_shape(self) -> Tuple[int, int]:
        """(F_pad, N_pad): the shape ``bins_T`` has, or will be built to.
        Known once the metadata is published, while the matrix still streams
        (the window in which the background prewarm lowers its step)."""
        from .ops.histogram import bin_axis
        from .ops.pallas_hist import resident_shape
        n = self.num_data if self.bins is None else self.bins.shape[0]
        return resident_shape(n, self.num_features,
                              bin_axis(self.max_num_bins))

    @property
    def bins_T(self):
        """Device-resident transposed bin matrix [F_pad, N_pad], built lazily
        on first use and cached. The Pallas histogram kernels consume
        feature-major rows, in row chunks and feature groups: built here in
        that shape once (ops/pallas_hist.resident_shape: columns padded with
        zeros to a multiple of 8,192, rows to the feature groups'
        fg * n_groups) the matrix is read in place by every level pass,
        where an [F_used, N] one was copied by a pad in each. The serial
        Pallas trainer alone asks for it (GBDT._use_bt) and its growers
        bring their row vectors to N_pad to match
        (ops/histogram.resident_rows). ``bins`` stays [N, F_used]: every other
        consumer (prediction, validation, subset, the scatter and one-hot
        histograms, the sharded trainers) reads N rows as before, and none
        of them pays for rows that carry no weight. Invalidated by the
        ``bins`` setter whenever the matrix changes."""
        if self._bins_T is None:
            from .ops.pallas_hist import resident_bins_T
            self._bins_T = resident_bins_T(self.bins, self.bins_T_shape)
        return self._bins_T

    # ---- construction ----
    def _resolve_categorical(self, ncols: int, columns) -> List[int]:
        cf = self.categorical_feature
        if cf == "auto" or cf is None:
            if _PANDAS and isinstance(self.raw_data, pd.DataFrame):
                return [i for i, dt in enumerate(self.raw_data.dtypes)
                        if isinstance(dt, pd.CategoricalDtype)]
            return []
        out = []
        for c in (cf if isinstance(cf, (list, tuple)) else [cf]):
            if isinstance(c, int):
                out.append(c)
            elif isinstance(c, str) and columns is not None and c in columns:
                out.append(list(columns).index(c))
        return sorted(set(out))

    def construct(self) -> "Dataset":
        if self._constructed:
            return self
        from . import obs
        with obs.span("dataset_construct"):
            return self._construct_inner()

    def _construct_inner(self) -> "Dataset":
        # construct_phases: consecutive marks from here to the return, so the
        # named seconds add up to the call (find_bins_s, the first, holds the
        # raw matrix's way to numpy)
        phases = self.construct_phases = {"init_s": self._init_s}
        t_last = time.time()

        def _mark(name):
            nonlocal t_last
            now = time.time()
            phases[name] = round(now - t_last, 3)
            t_last = now

        conf = params_to_config(self.params)
        if conf.num_threads and conf.num_threads > 0:
            from .native import set_num_threads
            set_num_threads(conf.num_threads)
        if self.reference is not None:
            ref = self.reference.construct()
            self.mappers = ref.mappers
            self.feature_map = ref.feature_map
            self._names = ref._names
            self.pandas_categorical = getattr(ref, "pandas_categorical", None)
            if _is_scipy_sparse(self.raw_data):
                from .binning import bin_sparse_column
                csc = self.raw_data.tocsc()
                fm = (ref.feature_map if ref.feature_map is not None
                      else np.arange(csc.shape[1]))
                bins = np.empty((csc.shape[0], len(fm)), dtype=np.uint8)
                for k, j in enumerate(fm):
                    bin_sparse_column(ref.mappers[k], csc, int(j), bins[:, k])
            else:
                raw = _to_numpy_2d(self.raw_data, self.pandas_categorical)
                used = raw[:, ref.feature_map] if ref.feature_map is not None \
                    else raw
                bins = np.zeros(used.shape, dtype=np.uint8)
                for k in range(used.shape[1]):
                    bins[:, k] = ref.mappers[k].values_to_bins(
                        used[:, k]).astype(np.uint8)
            self.bundle_meta = getattr(ref, "bundle_meta", None)
            if self.bundle_meta is not None:
                from .efb import apply_bundles
                bins = apply_bundles(bins, self.bundle_meta)
            self._finish_device(bins, ref._num_bins_np, ref._na_bin_raw,
                                ref._mtypes_np, ref.max_num_bins)
            return self

        sparse_in = _is_scipy_sparse(self.raw_data)
        if sparse_in:
            raw = self.raw_data.tocsc()   # binned column-by-column, no dense
            columns = None                # f64 intermediate (CSR path,
        elif _PANDAS and isinstance(self.raw_data, pd.DataFrame):  # c_api.h:146)
            raw, self.pandas_categorical = _data_from_pandas(self.raw_data)
            columns = list(self.raw_data.columns)
        else:
            raw = _to_numpy_2d(self.raw_data)
            columns = None
        cats = self._resolve_categorical(raw.shape[1], columns)
        forced_bins = None
        if conf.forcedbins_filename:
            # reference: forcedbins_filename JSON (bin_serializer usage,
            # dataset_loader.cpp DatasetLoader::CheckDataset forced bins)
            with open(conf.forcedbins_filename) as fh:
                forced_bins = {int(e["feature"]): e["bin_upper_bound"]
                               for e in json.load(fh)}
        bin_kw = dict(
            max_bin=conf.max_bin, min_data_in_bin=conf.min_data_in_bin,
            sample_cnt=conf.bin_construct_sample_cnt, categorical=cats,
            use_missing=conf.use_missing, zero_as_missing=conf.zero_as_missing,
            seed=conf.data_random_seed, forced_bins=forced_bins,
            max_bin_by_feature=conf.max_bin_by_feature)
        distributed = False
        if sparse_in:
            if conf.num_machines > 1:
                from .parallel.mesh import init_distributed
                init_distributed(conf)
                if jax.process_count() > 1:
                    # rank-local mappers would diverge and silently corrupt
                    # the multi-host histogram psum; refuse loudly
                    log.fatal("scipy-sparse input is not supported with "
                              "distributed bin finding (num_machines > 1); "
                              "densify or use text-file loading")
            from .binning import bin_data_sparse, find_bin_mappers_sparse
            mappers = find_bin_mappers_sparse(raw, **bin_kw)
            _mark("find_bins_s")
            binned = bin_data_sparse(raw, mappers)
            _mark("encode_s")
            self.mappers = binned.mappers
            self.feature_map = binned.feature_map
            self.bundle_meta = None
            # sparse path: full host matrix exists; plan from its own
            # internal 50k sample (pre-stream behavior)
            meta = self._plan_efb(conf, binned.bins, self.mappers,
                                  binned.feature_map, distributed,
                                  presampled=False)
            if meta is not None:
                from .efb import apply_bundles
                self.bundle_meta = meta
                binned.bins = apply_bundles(binned.bins, meta)
            self._derive_names(columns, raw.shape[1])
            num_bins, na_bin, mtypes, maxb = self._derive_meta()
            _mark("efb_s")
            self._finish_device(binned.bins, num_bins, na_bin, mtypes, maxb)
            _mark("device_put_s")
            log.info("Dataset.construct phases: %s", phases)
            return self

        # ---- dense path: metadata-first, then the streamed ingest pipeline
        if conf.num_machines > 1:
            from .parallel.mesh import init_distributed
            init_distributed(conf)
            distributed = jax.process_count() > 1
        row0 = 0
        n_local = int(raw.shape[0])
        n_rows = n_local
        if distributed:
            # pod-scale construct: every host holds ONLY its contiguous row
            # block. Global bins come from merged per-host sketches so every
            # host derives byte-identical mappers — identical to single-host
            # find_bin_mappers over the concatenated rows, not merely
            # identical across ranks (parallel/multihost.py docstring)
            from .parallel import multihost
            counts = multihost.allgather_rows(
                np.array([n_local], np.int64), jax.process_count(),
                jax.process_index(), retries=conf.network_retries,
                name="row-count allgather").reshape(-1)
            n_rows = int(counts.sum())
            row0 = int(counts[: jax.process_index()].sum())
            mappers = multihost.find_bin_mappers_pod(
                raw, n_rows, row0, retries=conf.network_retries, **bin_kw)
        else:
            mappers = find_bin_mappers(raw, **bin_kw)
        _mark("find_bins_s")
        # EFB plan from the pre-drawn sample — the identical 50k-row sample
        # plan_bundles would draw from the full matrix, so the plan is
        # bit-identical to planning post-encode — which makes the FULL
        # dataset metadata (widths, bin counts, padded shapes) known before
        # a single bulk chunk is encoded
        rng = np.random.RandomState(conf.data_random_seed)
        sample_idx = (None if n_rows <= self._EFB_PLAN_SAMPLE
                      else rng.choice(n_rows, self._EFB_PLAN_SAMPLE,
                                      replace=False))
        plan_sample_cnt = None
        if distributed:
            # the SAME global draw on every host, filtered to the local row
            # block — summing the per-rank conflict counts (reduce_fn below)
            # then reproduces the single-host plan sample exactly
            plan_sample_cnt = (n_rows if sample_idx is None
                              else int(len(sample_idx)))
            if sample_idx is not None:
                m = (sample_idx >= row0) & (sample_idx < row0 + n_local)
                sample_idx = sample_idx[m] - row0
        sample = bin_data(raw if sample_idx is None else raw[sample_idx],
                          mappers)
        self.mappers = sample.mappers
        self.feature_map = sample.feature_map
        self.bundle_meta = self._plan_efb(conf, sample.bins, sample.mappers,
                                          sample.feature_map, distributed,
                                          presampled=True,
                                          plan_sample_cnt=plan_sample_cnt)
        sample.bins = None   # host sample no longer needed
        _mark("efb_plan_s")
        self._derive_names(columns, raw.shape[1])
        num_bins, na_bin, mtypes, maxb = self._derive_meta()
        # mesh-native row sharding: the plan (pure metadata) is published
        # BEFORE ingest so chunk routing, the background prewarm's sharded
        # avals and the trainer's shard_map all agree on one shard grid.
        # Derived before _publish_meta so pod mode can replicate the label
        # over the plan's global mesh.
        from .parallel.mesh import (plan_row_sharding,
                                    resolve_feature_shards,
                                    resolve_num_shards)
        ns = resolve_num_shards(conf.num_shards)
        fs_req = int(getattr(conf, "feature_shards", 0) or 0)
        if distributed and int(conf.num_shards or 0) <= 0:
            # pod auto: one row shard per device (feature axis carved out
            # first when a 2-D mesh is requested) — auto single-shard would
            # leave the other hosts' devices outside the mesh entirely
            ns = max(1, jax.device_count() // max(1, fs_req))
        fs = resolve_feature_shards(fs_req, int(len(num_bins)), ns)
        self.shard_plan = plan_row_sharding(
            n_rows, ns, axis_name=conf.mesh_axis, feature_shards=fs)
        if self.shard_plan is not None:
            log.info(f"row-sharded ingest: {self.shard_plan.num_shards} "
                     f"shards x {self.shard_plan.rows_per_shard} rows "
                     f"(pad {self.shard_plan.pad_rows}, "
                     f"feature_shards {self.shard_plan.feature_shards})")
        if distributed:
            if self.shard_plan is None:
                log.fatal("multi-host construct requires a row-shard plan; "
                          "set num_shards > 1 (or leave it 0 for auto)")
            multihost.verify_pod_plan(self.shard_plan)
            plo, phi = multihost.host_row_range(self.shard_plan)
            if (plo, phi) != (row0, row0 + n_local):
                log.fatal(
                    f"multi-host row split mismatch: this host holds global "
                    f"rows [{row0}, {row0 + n_local}) but the shard plan "
                    f"assigns [{plo}, {phi}); load each host's slice with "
                    f"parallel.multihost.host_row_range/load_file_shard")
            # host-side bookkeeping (objective init, boost_from_average,
            # metrics) needs the GLOBAL label/weight/init_score vectors —
            # tiny next to the feature matrix, which never leaves its shards
            for attr in ("label", "weight", "init_score"):
                v = getattr(self, attr)
                if v is not None:
                    setattr(self, attr, multihost.allgather_rows(
                        np.asarray(v, np.float32), n_rows, row0,
                        retries=conf.network_retries,
                        name=f"{attr} allgather"))
        self._publish_meta(num_bins, na_bin, mtypes, maxb)
        # shapes are now final: compile the fused train step in the
        # background while the pipeline below encodes/uploads the bulk rows
        # (skipped in pod mode: every host must reach the collective compile
        # in the SAME order, and a background race against the first step
        # dispatch would be rank-dependent)
        from . import prewarm as _prewarm
        self._prewarm = None if distributed else _prewarm.maybe_start(
            conf, self)
        from .ingest import stream_with_recovery
        bins_dev, plan_used, _rows_used = stream_with_recovery(
            raw, mappers, self.bundle_meta, width=int(len(num_bins)),
            chunk_rows=conf.ingest_chunk_rows,
            encode_threads=conf.encode_threads, phases=phases,
            shard_plan=self.shard_plan, policy=conf.on_device_fault,
            row0=row0)
        if plan_used is not self.shard_plan:
            # OOM-adaptive degradation changed the shard grid mid-ingest; the
            # published plan must match the matrix the trainer will adopt
            # (a now-stale prewarm spec simply misses adoption and the step
            # compiles at first dispatch)
            self.shard_plan = plan_used
        from . import binning as _binning
        phases["encoder"] = _binning.LAST_ENCODE_PATH
        _mark("stream_s")   # wall time of the overlapped pipeline
        self._finish_device(bins_dev, num_bins, na_bin, mtypes, maxb)
        _mark("device_put_s")
        log.info("Dataset.construct phases: %s", phases)
        return self

    def _derive_names(self, columns, ncols: int) -> None:
        if self.feature_name != "auto" and isinstance(self.feature_name,
                                                      (list, tuple)):
            self._names = list(self.feature_name)
        elif columns is not None:
            self._names = [str(c) for c in columns]
        else:
            self._names = [f"Column_{i}" for i in range(ncols)]

    def _derive_meta(self):
        """Per-column (num_bins, na_bin, missing_type, max bins) from the
        mappers + EFB plan — pure metadata, independent of the bulk encode."""
        if self.bundle_meta is not None:
            meta = self.bundle_meta
            num_bins = meta.num_bins.astype(np.int32)
            na_bin = np.array(
                [self.mappers[mem[0][0]].na_bin if len(mem) == 1 else -1
                 for mem in meta.members], dtype=np.int32)
            mtypes = np.array(
                [self.mappers[mem[0][0]].missing_type if len(mem) == 1 else 0
                 for mem in meta.members], dtype=np.int32)
        else:
            num_bins = np.array([m.num_bins for m in self.mappers],
                                dtype=np.int32)
            na_bin = np.array([m.na_bin for m in self.mappers],
                              dtype=np.int32)
            mtypes = np.array([m.missing_type for m in self.mappers],
                              dtype=np.int32)
        maxb = int(num_bins.max()) if len(num_bins) else 1
        return num_bins, na_bin, mtypes, maxb

    def _plan_efb(self, conf, sample_bins, mappers, feature_map, distributed,
                  presampled, plan_sample_cnt=None):
        """EFB plan decision shared by both construct paths.

        ``presampled=True`` means ``sample_bins`` rows ARE the plan sample
        (the streamed dense path pre-draws the identical 50k-row sample
        ``plan_bundles`` would have drawn from the full matrix, so the plan
        is bit-identical to the pre-streaming behavior); ``False`` hands the
        full matrix over and lets ``plan_bundles`` sample internally."""
        if not (conf.enable_bundle and sample_bins.shape[1] >= 3):
            return None
        if any(float(v) != 1.0 for v in (conf.feature_contri or [])):
            # a bundle column's split candidates span several member features;
            # one gain multiplier per column cannot represent per-member
            # contris, so bundling is turned off rather than mis-penalizing
            log.warning("EFB bundling is disabled because feature_contri is "
                        "set (per-feature gain multipliers cannot apply to "
                        "merged bundle columns)")
            return None
        from . import obs
        from .efb import plan_bundles
        # monotone-constrained features must keep their own columns: the
        # bundle candidate plane does not implement direction filtering
        mc = list(conf.monotone_constraints or [])
        excl = [u for u, orig in enumerate(feature_map)
                if int(orig) < len(mc) and mc[int(orig)] != 0] \
            if any(mc) else []
        reduce_fn = None
        if distributed:
            # cross-rank count aggregation: every rank derives the
            # IDENTICAL bundle plan from the globally-summed histograms
            # and pairwise-conflict counts (plan_bundles docstring;
            # divergent plans would corrupt the histogram psum). Counts
            # cross as raw bytes so i64 tallies arrive exact — the old
            # jnp round-trip silently truncated them through i32
            def reduce_fn(arr):
                return np.sum(multihost.wire_allgather(
                    np.ascontiguousarray(arr), uniform=True), axis=0)
        kw = {}
        if presampled:
            # pod mode: the plan thresholds (conflict rates) divide by the
            # GLOBAL sample size, not this host's slice of it
            kw["sample_cnt"] = (int(plan_sample_cnt) if plan_sample_cnt
                                else max(int(sample_bins.shape[0]), 1))
        meta = plan_bundles(sample_bins, mappers,
                            max_conflict_rate=conf.max_conflict_rate,
                            sparse_threshold=conf.sparse_threshold,
                            seed=conf.data_random_seed, exclude=excl,
                            reduce_fn=reduce_fn, **kw)
        merged = ([int(meta.num_bins[i]) for i in np.flatnonzero(
            meta.is_bundle)] if meta is not None else [])
        obs.emit("efb_plan", columns_in=int(len(mappers)),
                 columns_out=int(meta.num_columns if meta is not None
                                 else len(mappers)),
                 bundles=len(merged), largest_bundle_bins=max(merged, default=0))
        return meta

    _EFB_PLAN_SAMPLE = 50_000   # plan_bundles' own default sample size

    def _publish_meta(self, num_bins_np, na_bin_np, mtypes_np, maxb):
        """Upload the per-column metadata (and label/weight) to device.

        All metadata arguments are HOST numpy arrays — never device arrays:
        a host readback right after the async 280 MB bins upload serializes
        on the transfer queue. Called BEFORE the bulk ingest pipeline so everything the
        background AOT prewarm needs (padded shapes, device label for the
        objective's captured constants) exists while the bins stream —
        idempotent via the jax.Array guards."""
        self._num_bins_np = np.asarray(num_bins_np, np.int32)
        self._mtypes_np = np.asarray(mtypes_np, np.int32)
        self.num_bins_dev = jax.device_put(self._num_bins_np)
        # na_bin == -1 means none; remap to an out-of-range bin so device compares fail
        na = np.asarray(na_bin_np)
        self.na_bin_dev = jax.device_put(np.where(na < 0, 255 + 1, na).astype(np.int32))
        self._na_bin_raw = na
        self.missing_type_dev = jax.device_put(self._mtypes_np)
        self.max_num_bins = int(maxb)
        self._num_features_used = int(len(self._num_bins_np))
        from .parallel.multihost import plan_spans_processes, replicate_global
        pod = plan_spans_processes(self.shard_plan)
        for attr in ("label", "weight"):
            v = getattr(self, attr)
            if v is None or isinstance(v, jax.Array):
                continue
            if pod:
                # single-device arrays cannot feed a computation over the
                # global pod mesh; replicate (the vectors are tiny and every
                # host holds the identical allgathered copy by construction)
                setattr(self, attr, replicate_global(
                    np.asarray(v, np.float32), self.shard_plan.mesh))
            else:
                setattr(self, attr,
                        jax.device_put(np.asarray(v, np.float32)))

    def _finish_device(self, bins_np, num_bins_np, na_bin_np, mtypes_np, maxb):
        """Ship the binned dataset to device and mark construction done."""
        # device_put, NOT jnp.asarray: asarray on a large host uint8 matrix
        # takes a slow conversion path where device_put is a plain transfer
        # plus relayout-on-first-use
        if isinstance(bins_np, jax.Array):
            self.bins = bins_np   # streamed path: already uploaded in chunks
        else:
            self.bins = jax.device_put(np.ascontiguousarray(bins_np))
        self._publish_meta(num_bins_np, na_bin_np, mtypes_np, maxb)
        # row-sharded bins carry shard-grid padding rows; num_data is the
        # TRUE row count from the plan, never the padded device shape
        self._num_data = (self.shard_plan.n_rows
                          if self.shard_plan is not None
                          else bins_np.shape[0])
        self._constructed = True
        if self.free_raw_data:
            self.raw_data = None

    # ---- binary dataset cache (reference: Dataset::SaveBinaryFile,
    # dataset.h:424 + DatasetLoader::LoadFromBinFile) ----
    _BIN_MAGIC = "lgbm_tpu_dataset_v1"

    def save_binary(self, filename: str) -> "Dataset":
        """Persist the BINNED dataset so re-training skips bin finding
        (reference: is_save_binary_file / Dataset::SaveBinaryFile)."""
        self.construct()
        import pickle
        payload = {
            "magic": self._BIN_MAGIC,
            # slice off shard-grid padding rows: the cache holds TRUE rows
            # (reloads re-plan sharding for whatever mesh they run on)
            "bins": np.asarray(self.bins)[: self._num_data],
            "num_bins": np.asarray(self.num_bins_dev),
            "na_bin_raw": np.asarray(self._na_bin_raw),
            "missing_type": np.asarray(self.missing_type_dev),
            "max_num_bins": self.max_num_bins,
            "mappers": self.mappers,
            "feature_map": self.feature_map,
            "names": self._names,
            "label": None if self.label is None else np.asarray(self.label),
            "weight": None if self.weight is None else np.asarray(self.weight),
            "group": self.group,
            "init_score": self.init_score,
            "bundle_meta": self.bundle_meta,
            "params": self.params,
            "pandas_categorical": self.pandas_categorical,
        }
        from .io.vfs import open_file
        with open_file(filename, "wb") as fh:
            pickle.dump(payload, fh)
        log.info(f"Saved binned dataset to {filename}")
        return self

    @staticmethod
    def load_binary(filename: str, params=None) -> "Dataset":
        import pickle
        from .io.vfs import open_file
        with open_file(filename, "rb") as fh:
            payload = pickle.load(fh)
        if payload.get("magic") != Dataset._BIN_MAGIC:
            log.fatal(f"{filename} is not a lightgbm_tpu binary dataset")
        ds = Dataset(None, params={**payload["params"], **(params or {})})
        ds.mappers = payload["mappers"]
        ds.feature_map = payload["feature_map"]
        ds._names = payload["names"]
        ds.label = payload["label"]
        ds.weight = payload["weight"]
        ds.group = payload["group"]
        ds.init_score = payload["init_score"]
        ds.bundle_meta = payload["bundle_meta"]
        ds.pandas_categorical = payload.get("pandas_categorical")
        ds._num_features_raw = (int(ds.feature_map.max()) + 1
                                if ds.feature_map is not None
                                else payload["bins"].shape[1])
        ds._finish_device(payload["bins"], payload["num_bins"],
                          payload["na_bin_raw"], payload["missing_type"],
                          payload["max_num_bins"])
        return ds

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params)

    def subset(self, used_indices, params: Optional[Dict] = None) -> "Dataset":
        """Row subset of a CONSTRUCTED dataset sharing its bin mappers —
        binning happens once (reference: Dataset::CopySubrow via
        dataset.cpp:808 + python Dataset.subset). The rows are gathered on
        device from the binned matrix; no raw data needed."""
        self.construct()
        idx = np.asarray(used_indices, dtype=np.int64)
        ds = Dataset(None, params={**self.params, **(params or {})},
                     free_raw_data=self.free_raw_data)
        ds.mappers = self.mappers
        ds.feature_map = self.feature_map
        ds._names = self._names
        ds.bundle_meta = self.bundle_meta
        ds.pandas_categorical = self.pandas_categorical
        ds.reference = self            # aligned by construction
        idx_dev = jnp.asarray(idx)
        ds.bins = jnp.take(self.bins, idx_dev, axis=0)
        ds.num_bins_dev = self.num_bins_dev
        ds.na_bin_dev = self.na_bin_dev
        ds.missing_type_dev = self.missing_type_dev
        ds._num_bins_np = self._num_bins_np
        ds._na_bin_raw = self._na_bin_raw
        ds._mtypes_np = self._mtypes_np
        ds.max_num_bins = self.max_num_bins
        ds._num_data = int(len(idx))
        ds._num_features_raw = self._num_features_raw
        if self.label is not None:
            ds.label = jnp.take(jnp.asarray(self.label), idx_dev)
        if self.weight is not None:
            ds.weight = jnp.take(jnp.asarray(self.weight), idx_dev)
        if self.group is not None:
            # preserve query boundaries when idx selects WHOLE queries in
            # order (the reference's subset contract: sorted indices covering
            # complete groups, Metadata handling in Dataset::CopySubrow) —
            # this is what cv()'s group-aware ranking folds produce
            idx_np = np.asarray(idx)
            bounds = np.cumsum(self.group)
            qid = np.searchsorted(bounds, idx_np, side="right")
            counts = np.bincount(qid, minlength=len(self.group))
            whole = np.all((counts == 0) | (counts == self.group))
            ordered = np.all(np.diff(idx_np) > 0) if len(idx_np) > 1 else True
            if whole and ordered:
                ds.group = self.group[counts > 0].copy()
            else:
                log.warning("Dataset.subset on grouped (ranking) data drops "
                            "the group boundaries unless rows cover whole "
                            "queries in order; re-set group on the subset if "
                            "needed")
        if self.init_score is not None:
            isc = np.asarray(self.init_score)
            n = self._num_data
            if isc.ndim == 1 and isc.size != n and isc.size % n == 0:
                # multiclass init_score is stored flat [n*k]; row-index the
                # (n, k) view and re-flatten so subset rows keep all k scores
                k = isc.size // n
                ds.init_score = isc.reshape(n, k)[idx].reshape(-1)
            else:
                ds.init_score = isc[idx]
        ds._constructed = True
        return ds

    def append(self, data, label=None, weight=None, group=None,
               init_score=None, max_rows: Optional[int] = None) -> "Dataset":
        """Append fresh rows to a CONSTRUCTED dataset under FROZEN binning.

        The continuous-training growth path (reference analog: the refit /
        continued-training data flow around GBDT::RefitTree + train-from-
        init-model): new rows are re-binned against the bin boundaries, the
        used-feature map and the EFB bundle plan fixed at the original
        ``construct()`` — ``find_bins`` never reruns, so a model trained on
        the original rows keeps meaning the same thing on the grown matrix.
        Out-of-range values clip to the edge bins and unseen categories land
        in bin 0, exactly like a ``reference=``-aligned validation set.

        The fresh rows stream through the same three-stage ingest pipeline
        as construct (chunked host encode -> H2D -> donated device commit),
        with the encode stage swapped for the frozen re-encoder. Under a
        ``RowShardPlan`` the row grid is re-planned for the grown total over
        the same shard count and the matrix is redistributed onto it, so the
        trainer's shard_map keeps one contiguous-block layout.

        Trainers and Boosters created BEFORE an append hold the old device
        matrix (the fused step captures its padded shape); build a new one
        (or ``train(init_model=...)``) after appending — the online loop in
        ``lightgbm_tpu.online`` does exactly that.

        ``max_rows`` (default: the ``online_max_rows`` param) bounds the
        grown total as a FIFO sliding window: once ``old + new`` exceeds the
        cap, the oldest rows are evicted so exactly the newest ``max_rows``
        remain. Bins, EFB plan and feature map stay frozen — the window is a
        row slice of the matrix the model already understands — and under a
        RowShardPlan the window is re-planned and redistributed like any
        other append. Training on the evicted dataset is bit-identical to a
        ``reference=``-aligned construct of the same window (the sliding-
        window guarantee continuous training relies on, docs/ONLINE.md).
        Grouped (ranking) data refuses a cap: a FIFO row window would split
        query groups.
        """
        self.construct()
        if _is_scipy_sparse(data):
            log.fatal("Dataset.append does not support sparse input; "
                      "densify the appended rows")
        conf = params_to_config(self.params)
        raw = _to_numpy_2d(data, self.pandas_categorical)
        n_new = int(raw.shape[0])
        if n_new == 0:
            return self
        if self._num_features_raw is not None and \
                raw.shape[1] != self._num_features_raw:
            log.fatal(f"Dataset.append: appended rows have {raw.shape[1]} "
                      f"features, dataset was constructed with "
                      f"{self._num_features_raw}")
        label_new = _to_numpy_1d(label)
        weight_new = _to_numpy_1d(weight)
        isc_new = _to_numpy_1d(init_score)
        old_n = int(self._num_data)
        for name, have, got, want in (
                ("label", self.label is not None, label_new, n_new),
                ("weight", self.weight is not None, weight_new, n_new)):
            if have and got is None:
                log.fatal(f"Dataset.append: dataset has {name} but appended "
                          f"rows do not")
            if not have and got is not None:
                log.fatal(f"Dataset.append: appended rows carry {name} but "
                          f"the dataset has none")
            if got is not None and len(got) != want:
                log.fatal(f"Dataset.append: {name} has {len(got)} entries "
                          f"for {want} appended rows")
        if self.group is not None and group is None:
            log.fatal("Dataset.append: dataset has group boundaries; appended "
                      "rows must supply their own group")
        conf_cap = int(getattr(conf, "online_max_rows", 0))
        cap = int(max_rows) if max_rows is not None else conf_cap
        if cap > 0 and (self.group is not None or group is not None):
            log.fatal("Dataset.append: online_max_rows eviction is not "
                      "supported on grouped (ranking) data — a FIFO row "
                      "window would split query groups")

        from . import obs
        from .efb import apply_bundles
        from .binning import rebin_frozen
        from .ingest import last_stats, stream_encode_upload
        t0 = time.time()
        used = raw[:, self.feature_map] if self.feature_map is not None \
            else raw
        mappers, meta = self.mappers, self.bundle_meta

        def _frozen_encode(chunk):
            cb = rebin_frozen(chunk, mappers)
            return apply_bundles(cb, meta) if meta is not None else cb

        width = int(self._num_features_used)
        # the pipeline sees the already-column-selected matrix; mappers/meta
        # ride along only for the default encode path it will not take
        new_dev = stream_encode_upload(
            used, mappers, meta, width=width,
            chunk_rows=conf.ingest_chunk_rows,
            encode_threads=conf.encode_threads, encode_fn=_frozen_encode)
        chunks = int(last_stats().get("chunks", 0))
        n_total = old_n + n_new
        # FIFO sliding window: keep exactly the newest `cap` rows. The
        # window boundary is a single global row offset, so the kept slice
        # of the old matrix and the kept tail of the new rows stay in order.
        evicted = 0
        keep_old_from = 0
        new_from = 0
        if cap > 0 and n_total > cap:
            evicted = n_total - cap
            keep_old_from = min(evicted, old_n)
            new_from = evicted - keep_old_from
            n_total = cap
        old_plan = self.shard_plan
        resharded = False
        full = jnp.concatenate([self.bins[keep_old_from:old_n],
                                new_dev[new_from:]], axis=0)
        # the mid-append crash window (kill-and-replay drill): the rows are
        # encoded and on device but NOTHING in-place has mutated yet, so a
        # crash here leaves the dataset exactly pre-append — a restart
        # rebuilds it from the WAL, and an in-process retry of append() is
        # safe unconditionally (eviction included)
        faults.fault_point("dataset_append")
        if old_plan is not None:
            # same shard count, grown row total: every row's owner moves, so
            # redistribute onto the re-planned contiguous-block grid (the
            # trainer's shard_map and histogram psum key on this layout)
            from .parallel.mesh import plan_row_sharding
            plan = plan_row_sharding(n_total, old_plan.num_shards,
                                     axis_name=old_plan.axis_name)
            if plan is not None:
                pad = plan.n_padded - n_total
                if pad:
                    full = jnp.concatenate(
                        [full, jnp.zeros((pad, width), jnp.uint8)], axis=0)
                full = jax.device_put(full, plan.sharding(2))
                resharded = True
            self.shard_plan = plan
        self.bins = full
        if self.label is not None:
            self.label = jnp.concatenate(
                [jnp.asarray(self.label)[keep_old_from:old_n],
                 jax.device_put(np.asarray(label_new[new_from:],
                                           np.float32))])
        if self.weight is not None:
            self.weight = jnp.concatenate(
                [jnp.asarray(self.weight)[keep_old_from:old_n],
                 jax.device_put(np.asarray(weight_new[new_from:],
                                           np.float32))])
        if group is not None:
            g_new = np.asarray(group, dtype=np.int64)
            if int(g_new.sum()) != n_new:
                log.fatal(f"Dataset.append: group sums to {int(g_new.sum())} "
                          f"but {n_new} rows were appended")
            self.group = (np.concatenate([self.group, g_new])
                          if self.group is not None else g_new)
        if self.init_score is not None or isc_new is not None:
            old_isc = (np.asarray(self.init_score)
                       if self.init_score is not None else None)
            if old_isc is None or isc_new is None:
                log.fatal("Dataset.append: init_score must be supplied on "
                          "both the dataset and the appended rows, or "
                          "neither")
            # multiclass init_score is stored flat [n*k]
            k = old_isc.size // max(old_n, 1)
            if old_isc.size != old_n * k or isc_new.size != n_new * k:
                log.fatal(f"Dataset.append: init_score size {isc_new.size} "
                          f"does not match {n_new} rows x {k} classes")
            self.init_score = np.concatenate(
                [old_isc.reshape(old_n, k)[keep_old_from:],
                 isc_new.reshape(n_new, k)[new_from:]],
                axis=0).reshape(-1)
        self._num_data = n_total
        if obs.enabled():
            obs.emit("dataset_append", rows=int(n_new),
                     total_rows=int(n_total), chunks=chunks,
                     duration_s=time.time() - t0,
                     num_shards=(self.shard_plan.num_shards
                                 if self.shard_plan is not None else 1),
                     resharded=resharded, evicted=int(evicted))
        return self

    # ---- accessors (reference Dataset API surface) ----
    @property
    def num_data(self) -> int:
        return self._num_data

    @property
    def num_features(self) -> int:
        if self._constructed:
            return self.bins.shape[1]
        if self._num_features_used is not None:
            # metadata published but bins still streaming (the window where
            # the background AOT prewarm builds its trainer): F_b is final
            return self._num_features_used
        return self._num_features_raw

    def num_feature(self) -> int:
        return self._num_features_raw or self.num_features

    def get_label(self):
        return None if self.label is None else np.asarray(self.label)

    def get_weight(self):
        return None if self.weight is None else np.asarray(self.weight)

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def set_label(self, label):
        self.label = (jnp.asarray(_to_numpy_1d(label), dtype=jnp.float32)
                      if self._constructed else _to_numpy_1d(label))

    def set_weight(self, weight):
        self.weight = (jnp.asarray(_to_numpy_1d(weight), dtype=jnp.float32)
                       if self._constructed and weight is not None
                       else _to_numpy_1d(weight))

    def set_group(self, group):
        self.group = None if group is None else np.asarray(group, dtype=np.int64)

    def set_init_score(self, init_score):
        self.init_score = _to_numpy_1d(init_score)

    def feature_names(self) -> List[str]:
        return list(self._names)

    def get_feature_penalty(self):
        """Per-feature gain penalty, or None (reference:
        Dataset.get_feature_penalty, basic.py:1484 — the feature_contri /
        feature_penalty parameter)."""
        v = params_to_config(self.params).feature_contri
        return np.asarray(v, dtype=np.float64) if v else None

    def get_monotone_constraints(self):
        """Per-feature monotone constraints (-1/0/1), or None (reference:
        Dataset.get_monotone_constraints, basic.py:1496)."""
        v = params_to_config(self.params).monotone_constraints
        return np.asarray(v, dtype=np.int8) if v else None

    @staticmethod
    def _merge_per_feature_param(a, b, na: int, nb: int, default):
        """Concatenate two per-feature parameter vectors for
        add_features_from; a missing side takes the parameter's neutral
        default (reference: LGBM_DatasetAddFeaturesFrom merges
        feature_penalty with 1s and monotone_constraints with 0s)."""
        if a is None and b is None:
            return None
        av = list(a) if a is not None else [default] * na
        bv = list(b) if b is not None else [default] * nb
        return av + bv

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append ``other``'s features to this Dataset (reference:
        Dataset::AddFeaturesFrom, src/io/dataset.cpp:1385, exposed as
        Dataset.add_features_from, python-package basic.py:1625).

        Both Datasets must be constructed and hold the same number of rows;
        labels/weights/groups stay this Dataset's. The binned device matrices
        are concatenated column-wise and the bin/bundle metadata merged, so
        the result trains exactly like a dataset constructed from the
        horizontally-stacked raw data (modulo each side's own EFB plan)."""
        if not self._constructed or not other._constructed:
            log.fatal("Both source and target Datasets must be constructed "
                      "before adding features")
        if other._num_data != self._num_data:
            log.fatal("Cannot add features from other Dataset with a "
                      "different number of rows")
        if self.shard_plan is not None or \
                getattr(other, "shard_plan", None) is not None:
            log.fatal("add_features_from is not supported on row-sharded "
                      "Datasets (construct with num_shards=1 first)")
        if self.bundle_meta is not None or other.bundle_meta is not None:
            from .efb import identity_meta, merge_bundle_meta
            a = self.bundle_meta or identity_meta(self.mappers)
            b = other.bundle_meta or identity_meta(other.mappers)
            self.bundle_meta = merge_bundle_meta(a, b, len(self.mappers))
        fm_a = (self.feature_map if self.feature_map is not None
                else np.arange(len(self.mappers), dtype=np.int64))
        fm_b = (other.feature_map if other.feature_map is not None
                else np.arange(len(other.mappers), dtype=np.int64))
        self.feature_map = np.concatenate(
            [np.asarray(fm_a, dtype=np.int64),
             np.asarray(fm_b, dtype=np.int64) + int(self._num_features_raw)])
        self.mappers = list(self.mappers) + list(other.mappers)
        self.bins = jnp.concatenate([self.bins, other.bins], axis=1)
        self._num_bins_np = np.concatenate([self._num_bins_np,
                                            other._num_bins_np])
        self._na_bin_raw = np.concatenate([np.asarray(self._na_bin_raw),
                                           np.asarray(other._na_bin_raw)])
        self._mtypes_np = np.concatenate([self._mtypes_np, other._mtypes_np])
        self.num_bins_dev = jax.device_put(self._num_bins_np)
        self.na_bin_dev = jax.device_put(
            np.where(self._na_bin_raw < 0, 255 + 1,
                     self._na_bin_raw).astype(np.int32))
        self.missing_type_dev = jax.device_put(self._mtypes_np)
        self.max_num_bins = max(self.max_num_bins, other.max_num_bins)
        self._names = list(self._names) + list(other._names)
        na = int(self._num_features_raw or 0)
        nb = int(other._num_features_raw or 0)
        pen = self._merge_per_feature_param(
            self.get_feature_penalty(), other.get_feature_penalty(),
            na, nb, 1.0)
        if pen is not None:
            # drop alias spellings or the stale pre-merge value wins
            # alias resolution over the canonical key
            for alias in ("feature_contrib", "fc", "fp", "feature_penalty"):
                self.params.pop(alias, None)
            self.params["feature_contri"] = [float(v) for v in pen]
        mono = self._merge_per_feature_param(
            self.get_monotone_constraints(),
            other.get_monotone_constraints(), na, nb, 0)
        if mono is not None:
            for alias in ("mc", "monotone_constraint"):
                self.params.pop(alias, None)
            self.params["monotone_constraints"] = [int(v) for v in mono]
        self._num_features_raw = na + nb
        return self


def booster_class(boosting: str):
    """Boosting-variant trainer class for a config string (reference: the
    factory in boosting.cpp:35). Shared by Booster construction and the AOT
    prewarm worker (prewarm.py), which must build the SAME trainer class to
    produce an executable the real trainer can adopt."""
    b = str(boosting).lower()
    if b in ("gbdt", "gbrt"):
        return GBDT
    if b == "dart":
        from .models.dart import DART
        return DART
    if b == "goss":
        from .models.goss import GOSS
        return GOSS
    if b in ("rf", "random_forest"):
        from .models.rf import RF
        return RF
    log.fatal(f"unknown boosting type {boosting}")


class Booster:
    """Trained/training model handle (reference: lightgbm.Booster, basic.py:1666)."""

    def __init__(self, params: Optional[Dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = dict(params or {})
        self.config = params_to_config(self.params)
        # surface telemetry knobs passed at the Booster level (predict-only
        # workflows never go through engine.train); only an EXPLICIT param
        # reconfigures — a Booster built with defaults must not switch off
        # telemetry another entry point enabled
        if any(canonical_name(k) in ("telemetry", "metrics_out")
               for k in self.params):
            from . import obs
            obs.configure_from_config(self.config)
        self._gbdt: Optional[GBDT] = None
        self.trees: List[Tree] = []
        self._loaded_meta: Dict[str, Any] = {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self.train_set = None
        self.name_valid_sets: List[str] = []
        # free-form string attributes (reference: Booster.attr/set_attr,
        # python-package basic.py:2845 — a pure in-memory dict, copied on
        # refit, never serialized into the model file)
        self._attr: Dict[str, str] = {}

        if model_file is not None:
            from .io import vfs
            with vfs.open_text(model_file) as f:
                self._load_model_string(f.read())
            return
        if model_str is not None:
            self._load_model_string(model_str)
            return
        if train_set is not None:
            self._setup_train(train_set)

    # ---- training wiring ----
    def _setup_train(self, train_set: Dataset) -> None:
        if train_set._constructed:
            # binning params can no longer be applied (reference raises
            # "Cannot change max_bin after constructed Dataset"); warn on the
            # worst silent footgun: a mismatched max_bin widens every histogram
            # compare effective (alias-resolved, defaulted) values, not raw dicts
            mb_b = params_to_config(self.params or {}).max_bin
            mb_d = params_to_config(train_set.params or {}).max_bin
            if mb_d != mb_b:
                log.warning(
                    f"Dataset was constructed before max_bin={mb_b} could apply "
                    f"(effective max_bin={mb_d}); "
                    "pass params to Dataset() or let Booster construct it")
        train_set.params = {**self.params, **train_set.params} if train_set.params else dict(self.params)
        train_set.construct()
        self.train_set = train_set
        conf = self.config
        objective = create_objective(conf.objective, conf)
        metric_names = conf.metric or [default_metric_for_objective(conf.objective)]
        metrics = create_metrics(metric_names, conf, conf.objective)
        cls = booster_class(conf.boosting)
        self._gbdt = cls(conf, train_set, objective, metrics)
        self._objective = objective

    def add_valid(self, data: Dataset, name: str) -> None:
        data.construct()
        self._gbdt.add_valid(data, name)
        self.name_valid_sets.append(name)

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration (reference: Booster.update, basic.py:2048)."""
        if fobj is not None:
            score = self.raw_train_score()
            grad, hess = fobj(score, self._gbdt.train_set)
            grad = np.asarray(grad, dtype=np.float32)
            hess = np.asarray(hess, dtype=np.float32)
            grad, hess, skip = self._gbdt.guard_gradients(grad, hess)
            if skip:
                return self._gbdt.skip_one_iter()
            grad = jnp.asarray(grad)
            hess = jnp.asarray(hess)
            k = self._gbdt.num_tree_per_iteration
            if k > 1:
                grad = grad.reshape(-1, k) if grad.ndim == 1 else grad
                hess = hess.reshape(-1, k) if hess.ndim == 1 else hess
            return self._gbdt.train_one_iter(grad, hess)
        return self._gbdt.train_one_iter()

    def rollback_one_iter(self):
        self._gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self) -> int:
        return (self._gbdt.iter_ if self._gbdt
                else len(self.trees) // max(self.num_model_per_iteration(), 1))

    def num_model_per_iteration(self) -> int:
        if self._gbdt:
            return self._gbdt.num_tree_per_iteration
        return int(self._loaded_meta.get("num_tree_per_iteration", 1))

    def num_trees(self) -> int:
        return self._gbdt.num_trees() if self._gbdt else len(self.trees)

    def raw_train_score(self):
        score = self._gbdt.train_score
        try:
            fully = score.sharding.is_fully_addressable
        except Exception:
            fully = True
        if fully or getattr(score, "is_fully_replicated", False):
            return score
        # pod: the step leaves train_score row-sharded across processes;
        # user-facing fobj/eval code expects a host-fetchable full vector
        from .models.gbdt import _host_gather
        full = _host_gather(score)
        n = self._gbdt.train_set.num_data
        return full[:n] if full.shape[0] != n else full

    def eval_train(self):
        return self._gbdt.eval_train()

    def eval_valid(self):
        return self._gbdt.eval_valid()

    # ---- prediction ----
    def _ensure_host_trees(self) -> List[Tree]:
        if self._gbdt is not None:
            self.trees = self._gbdt.finalize()
        return self.trees

    @property
    def pandas_categorical(self):
        """Per-categorical-column category lists captured at train time
        (reference: Booster.pandas_categorical) — used to encode DataFrame
        inputs to the same codes at predict time."""
        if self.train_set is not None:
            return getattr(self.train_set, "pandas_categorical", None)
        return self._loaded_meta.get("pandas_categorical")

    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, data_has_header: bool = False,
                **kwargs):
        """Batch prediction on raw features (reference: Booster.predict ->
        Predictor, predictor.hpp:29).

        ``data`` may be a file path (reference: Predictor::Predict on a data
        file, c_api LGBM_BoosterPredictForFile): the file is parsed with the
        usual CSV/TSV/LibSVM sniffing, and — as in the reference parser
        factory — a leading label column is assumed present only when the
        column count exceeds the model's feature count.

        Returns an ndarray, EXCEPT for scipy-sparse input with
        ``pred_contrib=True`` which returns a scipy sparse matrix (reference
        parity: sparse in -> sparse contribs out, c_api.h:747)."""
        import os as _os
        if isinstance(data, (str, _os.PathLike)):
            from .io.parser import detect_format, load_file
            kind, _ = detect_format(str(data), skip_header=data_has_header)
            pf = load_file(str(data), header=data_has_header,
                           num_features_hint=self.num_feature())
            x = pf.X
            nf = self.num_feature()
            if (kind != "libsvm" and pf.label is not None and nf
                    and x.shape[1] < nf):
                # the parser stripped column 0 as a label by default, but the
                # column count does not EXCEED the model width, so no label
                # is assumed (reference parser-factory rule) — restore it.
                # A still-too-narrow file then fails the width check below
                # honestly instead of silently shifting features. (LibSVM
                # labels are never positional feature columns, so the restore
                # must not fire there even when trailing features are absent.)
                x = np.column_stack([pf.label, x])
            data = x
        if _is_scipy_sparse(data):
            # chunked densify: bounded [chunk, F] f64 intermediates instead of
            # the full dense matrix (reference predicts straight off CSR,
            # c_api.h:747; our router needs dense rows, so bound the chunk)
            csr = data.tocsr()
            chunk = max(1, (64 << 20) // max(1, 8 * csr.shape[1]))
            outs = [self.predict(np.asarray(csr[i: i + chunk].todense()),
                                 num_iteration=num_iteration,
                                 raw_score=raw_score, pred_leaf=pred_leaf,
                                 pred_contrib=pred_contrib, **kwargs)
                    for i in range(0, csr.shape[0], chunk)]
            if pred_contrib:
                # sparse in -> sparse out (reference returns a sparse matrix
                # for CSR pred_contrib, c_api.h:747): contribs of absent
                # features are mostly zero, and a dense [n, F+1] for wide
                # sparse data can exhaust host memory
                from scipy import sparse as _sp
                return _sp.vstack([_sp.csr_matrix(o) for o in outs])
            return np.concatenate(outs, axis=0)
        trees = self._ensure_host_trees()
        k = (self._gbdt.num_tree_per_iteration if self._gbdt
             else int(self._loaded_meta.get("num_tree_per_iteration", 1)))
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if num_iteration and num_iteration > 0:
            trees = trees[: num_iteration * k]
        x = _to_numpy_2d(data, self.pandas_categorical)
        n = x.shape[0]
        expected = self.num_feature()
        if expected and x.shape[1] != expected:
            log.fatal(f"The number of features in data ({x.shape[1]}) is not the "
                      f"same as it was in training data ({expected})")
        if not trees:
            base = np.zeros((n, k) if k > 1 else n)
            return base
        if pred_contrib:
            return self._predict_contrib(x, trees, k)
        # unified exact routing via the persistent serving engine
        # (serving.py PredictEngine): pseudo-bins the input on the host in
        # f64 and walks/matmuls the trees on device with integer compares +
        # categorical bitsets — identical for in-session and loaded models.
        # Tables live on device across calls; batches are padded to shape
        # buckets so repeated calls of any size reuse compiled executables.
        return self._predict_engine_for(trees, x.shape[1], k).predict(
            x, raw_score=raw_score, pred_leaf=pred_leaf)

    def _predict_engine_for(self, trees, n_features: int, k: int):
        """Cached PredictEngine for the current tree list; invalidated only
        on tree-count change (like the old per-Booster PseudoRouter cache —
        shuffle_models/refit reset it explicitly since they keep the count)."""
        from . import obs
        from .serving import PredictEngine
        engine = getattr(self, "_predict_engine", None)
        if engine is None or engine.n_trees != len(trees):
            reason = "new" if engine is None else "invalidated"
            engine = PredictEngine(trees, n_features, k, self._avg_output(),
                                   objective=self._objective_for_predict(),
                                   upload_reason=reason)
            self._predict_engine = engine
            self._pseudo_router = engine.router   # kept for introspection
            if obs.enabled():
                obs.METRICS.counter("predict_engine_cache",
                                    "engine cache lookups",
                                    outcome="miss").inc()
        elif obs.enabled():
            obs.METRICS.counter("predict_engine_cache",
                                "engine cache lookups", outcome="hit").inc()
        return engine

    def _avg_output(self) -> bool:
        if self._gbdt is not None:
            return self._gbdt.average_output
        return bool(self._loaded_meta.get("average_output", False))

    def _per_feature_missing(self, nf: int, trees: List[Tree]) -> np.ndarray:
        mt = np.zeros(nf, dtype=np.int32)
        for t in trees:
            for i in range(t.num_leaves - 1):
                f = t.split_feature[i]
                if f < nf:
                    mt[f] = max(mt[f], t.missing_type[i])
        return mt

    def _predict_contrib(self, x, trees, k):
        """SHAP-style contributions via per-tree path attribution (reference:
        PredictContrib, boosting.h:167). Exact TreeSHAP, host-side."""
        from .io.shap import tree_shap_ensemble
        return tree_shap_ensemble(x, trees, k, self._base_score(k))

    def _base_score(self, k):
        return np.zeros(k)

    def _objective_for_predict(self):
        if self._gbdt is not None:
            return self._objective
        name = self._loaded_meta.get("objective", "")
        if not name:
            return None
        conf = self.config.copy()
        parts = name.split(" ")
        for p in parts[1:]:
            if ":" in p:
                kk, vv = p.split(":", 1)
                conf.update({kk: vv})
        try:
            obj = create_objective(parts[0], conf)
        except Exception:
            return None
        return obj

    # ---- persistence (reference: gbdt_model_text.cpp) ----
    def refit(self, data, label, decay_rate: Optional[float] = None,
              weight=None, group=None, **kwargs) -> "Booster":
        """Refit the existing tree STRUCTURES to new data (reference:
        Booster.refit -> GBDT::RefitTree, gbdt.cpp:299 +
        SerialTreeLearner::FitByExistingTree, serial_tree_learner.cpp:196-226):
        per tree, route the new rows to leaves, recompute the regularized
        optimal outputs from the new gradients, and blend
        ``decay * old + (1 - decay) * new``."""
        conf = params_to_config(self.params)
        decay = conf.refit_decay_rate if decay_rate is None else decay_rate
        new_b = Booster(model_str=self.model_to_string(), params=self.params)
        trees = new_b._ensure_host_trees()
        if not trees:
            log.fatal("Cannot refit an empty model")
        x = _to_numpy_2d(data, self.pandas_categorical)
        y = _to_numpy_1d(label)
        obj = new_b._objective_for_predict()
        if obj is None:
            log.fatal("Cannot refit: model has no objective")
        obj.init(jnp.asarray(y, dtype=jnp.float32),
                 None if weight is None else jnp.asarray(_to_numpy_1d(weight),
                                                         dtype=jnp.float32),
                 None if group is None else np.asarray(group, dtype=np.int64))
        k = new_b.num_model_per_iteration()
        n = x.shape[0]
        leaf_mat = np.asarray(self.predict(x, pred_leaf=True))      # [N, T]
        score = (np.zeros(n) if k == 1 else np.zeros((n, k)))
        grad = hess = None
        from .ops.split import SplitParams, leaf_output
        sp = SplitParams(lambda_l1=conf.lambda_l1, lambda_l2=conf.lambda_l2,
                         max_delta_step=conf.max_delta_step)
        for ti, t in enumerate(trees):
            cls = ti % k
            if cls == 0:
                g_dev, h_dev = obj.get_gradients(jnp.asarray(score,
                                                             dtype=jnp.float32))
                grad, hess = np.asarray(g_dev), np.asarray(h_dev)
            g = grad if k == 1 else grad[:, cls]
            h = hess if k == 1 else hess[:, cls]
            leaf = leaf_mat[:, ti]
            sg = np.bincount(leaf, weights=g, minlength=t.num_leaves)
            sh = np.bincount(leaf, weights=h, minlength=t.num_leaves) + 1e-15
            new_out = np.asarray(leaf_output(jnp.asarray(sg), jnp.asarray(sh),
                                             sp)) * t.shrinkage
            t.leaf_value = decay * t.leaf_value + (1.0 - decay) * new_out
            delta = t.leaf_value[leaf]
            if k == 1:
                score = score + delta
            else:
                score[:, cls] += delta
        new_b._pseudo_router = None
        new_b._predict_engine = None     # leaf values changed in place
        new_b._attr = dict(self._attr)   # reference: refit copies __attr
        return new_b

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        # write-to-temp + fsync + atomic rename: a crash mid-save never
        # leaves a truncated model on disk (utils/atomic_io.py; the
        # reference's plain fwrite can, gbdt_model_text.cpp)
        from .utils import atomic_io
        atomic_io.atomic_write_text(
            filename, self.model_to_string(num_iteration, start_iteration))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        trees = self._ensure_host_trees()
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        return model_text.dump_model_text(self, trees, num_iteration, start_iteration)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict:
        trees = self._ensure_host_trees()
        k = self.num_model_per_iteration()
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if num_iteration and num_iteration > 0:
            trees = trees[: num_iteration * k]
        return model_text.dump_model_json(self, trees)

    def _load_model_string(self, s: str) -> None:
        meta, trees = model_text.parse_model_text(s)
        self._loaded_meta = meta
        self.trees = trees
        self.best_iteration = -1
        self._pseudo_router = None
        self._predict_engine = None  # loaded trees may keep the same count

    # ---- introspection ----
    def feature_name(self) -> List[str]:
        if self.train_set is not None:
            return self.train_set.feature_names()
        return list(self._loaded_meta.get("feature_names", []))

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """split/gain importances (reference: boosting.h:229 FeatureImportance)."""
        trees = self._ensure_host_trees()
        nf = (self.train_set.num_feature() if self.train_set is not None
              else int(self._loaded_meta.get("max_feature_idx", -1)) + 1)
        out = np.zeros(nf)
        for t in trees:
            for i in range(t.num_leaves - 1):
                f = int(t.split_feature[i])
                if f >= nf:
                    continue
                if importance_type == "split":
                    out[f] += 1
                else:
                    out[f] += t.split_gain[i]
        if importance_type == "split":
            return out.astype(np.int64 if importance_type == "split" else np.float64)
        return out

    def num_feature(self) -> int:
        if self.train_set is not None:
            return self.train_set.num_feature()
        return int(self._loaded_meta.get("max_feature_idx", -1)) + 1

    # ---- conveniences (reference python-package Booster surface) ----
    def attr(self, key: str) -> Optional[str]:
        """Get a string attribute, or None (reference: Booster.attr,
        basic.py:2845)."""
        return self._attr.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set string attributes; a value of None deletes the key
        (reference: Booster.set_attr, basic.py:2861)."""
        for key, value in kwargs.items():
            if value is None:
                self._attr.pop(key, None)
            else:
                if not isinstance(value, str):
                    raise ValueError("Only string values are accepted")
                self._attr[key] = value
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Output value of one leaf (reference: Booster.get_leaf_output ->
        LGBM_BoosterGetLeafValue, basic.py:2591 / c_api.cpp)."""
        trees = self._ensure_host_trees()
        if not 0 <= tree_id < len(trees):
            log.fatal(f"tree_id {tree_id} out of range [0, {len(trees)})")
        t = trees[tree_id]
        if not 0 <= leaf_id < t.num_leaves:
            log.fatal(f"leaf_id {leaf_id} out of range [0, {t.num_leaves})")
        return float(t.leaf_value[leaf_id])

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of the split thresholds used for one feature
        (reference: Booster.get_split_value_histogram, basic.py:2693).

        The reference recurses over the JSON dump; here the flat tree arrays
        are scanned directly. The bin-count selection rules (None -> number
        of unique thresholds; int + xgboost_style -> capped at that count)
        are the documented API contract and match the reference."""
        names = self.feature_name()
        if isinstance(feature, str):
            if feature not in names:
                log.fatal(f"Unknown feature name {feature!r}")
            fidx = names.index(feature)
        else:
            fidx = int(feature)
        values: List[float] = []
        for t in self._ensure_host_trees():
            for i in range(t.num_leaves - 1):
                if int(t.split_feature[i]) != fidx:
                    continue
                if bool(t.is_cat_node[i]):
                    log.fatal("Cannot compute split value histogram for the "
                              "categorical feature")
                values.append(float(t.threshold_real[i]))
        if bins is None or (isinstance(bins, (int, np.integer))
                            and xgboost_style):
            n_unique = len(np.unique(values))
            bins = max(min(n_unique, bins) if bins is not None else n_unique, 1)
        hist, edges = np.histogram(values, bins=bins)
        if xgboost_style:
            ret = np.column_stack((edges[1:], hist))
            ret = ret[ret[:, 1] > 0]
            if _PANDAS:
                return pd.DataFrame(ret, columns=["SplitValue", "Count"])
            return ret
        return hist, edges

    def trees_to_dataframe(self):
        """Parse the fitted model into a pandas DataFrame, one row per node
        (reference: Booster.trees_to_dataframe, basic.py:1865 — same
        columns and 'tree-S<i>' / 'tree-L<i>' node-index scheme)."""
        if not _PANDAS:
            log.fatal("This method cannot be run without pandas installed")
        if self.num_trees() == 0:
            log.fatal("There are no trees in this Booster and thus nothing "
                      "to parse")
        model = self.dump_model()
        feature_names = model.get("feature_names") or None
        rows: List[Dict[str, Any]] = []

        def node_index(tree_index, node):
            is_split = "split_index" in node
            tag = "S" if is_split else "L"
            num = node.get("split_index" if is_split else "leaf_index", 0)
            return f"{tree_index}-{tag}{num}"

        def rec(node, tree_index, depth, parent):
            is_split = "split_index" in node
            row = {
                "tree_index": tree_index,
                "node_depth": depth,
                "node_index": node_index(tree_index, node),
                "left_child": None, "right_child": None,
                "parent_index": parent,
                "split_feature": None, "split_gain": None,
                "threshold": None, "decision_type": None,
                "missing_direction": None, "missing_type": None,
                "value": None, "weight": None, "count": None,
            }
            if is_split:
                row["left_child"] = node_index(tree_index, node["left_child"])
                row["right_child"] = node_index(tree_index,
                                                node["right_child"])
                sf = node["split_feature"]
                row["split_feature"] = (feature_names[sf] if feature_names
                                        else sf)
                row["split_gain"] = node["split_gain"]
                row["threshold"] = node["threshold"]
                row["decision_type"] = node["decision_type"]
                row["missing_direction"] = ("left" if node["default_left"]
                                            else "right")
                row["missing_type"] = node["missing_type"]
                row["value"] = node["internal_value"]
                row["weight"] = node["internal_weight"]
                row["count"] = node["internal_count"]
                rows.append(row)
                rec(node["left_child"], tree_index, depth + 1,
                    row["node_index"])
                rec(node["right_child"], tree_index, depth + 1,
                    row["node_index"])
            else:
                row["value"] = node["leaf_value"]
                row["weight"] = node.get("leaf_weight")
                row["count"] = node.get("leaf_count")
                rows.append(row)

        for ti in model["tree_info"]:
            rec(ti["tree_structure"], ti["tree_index"], 1, None)
        return pd.DataFrame(rows)

    # ---- pickling / copying (reference: Booster.__getstate__, which
    # serializes the handle to a model string; needed for sklearn
    # ecosystem tools like joblib/GridSearchCV) ----
    def __getstate__(self):
        state = {
            "params": self.params,
            "best_iteration": self.best_iteration,
            "best_score": self.best_score,
            "attr": dict(self._attr),
            "name_valid_sets": list(self.name_valid_sets),
            "pandas_categorical": self.pandas_categorical,
        }
        # serialize ALL trees (num_iteration=-1), not just up to
        # best_iteration — the copy must predict identically at any
        # num_iteration (reference: Booster.__getstate__, basic.py:1793)
        state["model_str"] = (self.model_to_string(num_iteration=-1)
                              if self.num_trees() else None)
        return state

    def __setstate__(self, state):
        self.__init__(params=state.get("params"),
                      model_str=state.get("model_str"))
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._attr = dict(state.get("attr", {}))
        self.name_valid_sets = list(state.get("name_valid_sets", []))
        pc = state.get("pandas_categorical")
        if pc is not None:
            self._loaded_meta["pandas_categorical"] = pc

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _memodict):
        model_str = (self.model_to_string(num_iteration=-1)
                     if self.num_trees() else None)
        b = Booster(params=dict(self.params), model_str=model_str)
        b.best_iteration = self.best_iteration
        b.best_score = dict(self.best_score)
        b._attr = dict(self._attr)
        return b

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute the iteration order of the ensemble (reference:
        Booster.shuffle_models -> GBDT::ShuffleModels, gbdt.h:79: shuffles
        whole iterations — blocks of num_model_per_iteration trees — in
        [start_iteration, end_iteration), seeded deterministically)."""
        trees = self._ensure_host_trees()
        k = max(self.num_model_per_iteration(), 1)
        total_iter = len(trees) // k
        start = max(0, start_iteration)
        end = total_iter if end_iteration <= 0 else min(total_iter,
                                                        end_iteration)
        perm = np.arange(total_iter)
        if end > start:
            rng = np.random.RandomState(17)
            sub = perm[start:end].copy()
            rng.shuffle(sub)
            perm[start:end] = sub

        def _reorder(lst):
            return [lst[it * k + j] for it in perm for j in range(k)]

        if self._gbdt is not None:
            # keep the device-side model list consistent with the host list
            # so continued training / device prediction see the same order
            self._gbdt.models_host = _reorder(self._gbdt.models_host)
            self._gbdt.models_dev = _reorder(self._gbdt.models_dev)
            self.trees = self._gbdt.models_host
        else:
            self.trees = _reorder(trees)
        self._pseudo_router = None   # predict caches tree order
        self._predict_engine = None  # device tables cache tree order too
        return self
