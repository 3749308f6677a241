"""Mesh fault-tolerance suite (ISSUE 7 tentpole acceptance tests).

Three pillars, all asserted against the PR-6 bit-identity invariant
(sharded training == single-chip training, bit for bit, when gradients sit
on a dyadic lattice):

1. **Sharded kill-and-resume** — a run crashed mid-train on k shards and
   resumed from its (host-gathered, unsharded) snapshot onto k' shards
   produces the exact same model text as an uninterrupted single-chip run,
   for k=2, k=8 and the cross-topology resume k=8 -> k'=2.
2. **OOM-adaptive degradation** — an injected XLA ``RESOURCE_EXHAUSTED``
   during sharded ingest recovers through the ``on_device_fault`` ladder
   (chunk halving, then reshard / fallback_single), every rung emitting a
   ``device_fault`` telemetry event, while ``fatal`` still fails fast; a
   ``hist_allreduce`` fault in the fused step recovers via bounded retry.
3. **Mesh preflight** — a bad mesh (axis mismatch, dead device, stale row
   count) aborts with a per-field diff BEFORE step 0 instead of hanging
   the first collective.

Chaos-marked tests run under the conftest SIGALRM guard: a recovery path
that regresses into a hang fails the suite instead of eating the tier-1
budget. Named ``test_zz_*`` to sort after the fast suites.
"""
import os
from types import SimpleNamespace

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import ingest, obs
from lightgbm_tpu import snapshot as snap
from lightgbm_tpu.config import Config
from lightgbm_tpu.utils import faults, log
from lightgbm_tpu.utils.faults import FaultInjected

N, F = 1025, 5          # odd row count: every shard grid needs padding
ROUNDS = 4              # resume tests; chaos tests train 3 rounds

_P = {"objective": "none", "num_leaves": 7, "max_bin": 63,
      "min_data_in_leaf": 5, "verbose": -1, "seed": 7,
      "feature_fraction": 0.7, "prewarm": 0}


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.reset()
    yield
    faults.reset()


def _lattice_fobj(preds, train_data):
    # gradients on multiples of 2^-9, constant hessian: every f32 histogram
    # partial sum is exact, so ANY psum association gives the same bits
    labels = train_data.get_label()
    g = np.round((np.asarray(preds, np.float64) - labels) * 512.0) / 512.0
    return g.astype(np.float32), np.full(g.shape, 0.25, np.float32)


def _model_bytes(bst):
    # trees + feature importances only: the parameters echo legitimately
    # differs across runs (faults / on_device_fault / snapshot_dir)
    return bst.model_to_string().split("\nparameters:\n")[0]


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(5)
    return rng.rand(N, F).astype(np.float32), rng.rand(N).astype(np.float32)


def _train(data, num_shards, rounds, **extra):
    X, y = data
    params = {**_P, "num_shards": num_shards, **extra}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=rounds, fobj=_lattice_fobj)
    return bst, ds


@pytest.fixture(scope="module")
def ref_bytes(data):
    """Uninterrupted single-chip run — the byte-identity reference for every
    sharded/crashed/recovered run in this file."""
    return _model_bytes(_train(data, 1, ROUNDS)[0])


@pytest.fixture(scope="module")
def ref3_bytes(data):
    return _model_bytes(_train(data, 1, 3)[0])


# ---------------- sharded kill-and-resume ----------------

@pytest.mark.faults
@pytest.mark.parametrize("k_crash,k_resume", [(2, 2), (8, 8), (8, 2)])
def test_kill_and_resume_sharded_byte_identical(tmp_path, data, ref_bytes,
                                                k_crash, k_resume):
    """Crash a k_crash-shard run at iteration 3 via an armed tree_update
    fault, resume the newest snapshot onto k_resume shards, finish: the
    final model must equal the uninterrupted SINGLE-chip run byte for byte.
    feature_fraction is on, so the RNG streams must survive both the
    snapshot round trip and the topology change."""
    d = str(tmp_path / f"snaps_{k_crash}_{k_resume}")
    X, y = data
    with pytest.raises(FaultInjected):
        lgb.train({**_P, "num_shards": k_crash, "snapshot_freq": 1,
                   "snapshot_dir": d, "faults": "tree_update@3"},
                  lgb.Dataset(X, label=y,
                              params={**_P, "num_shards": k_crash}),
                  num_boost_round=ROUNDS, fobj=_lattice_fobj)
    faults.reset()

    payload = snap.load_latest_valid(d)
    assert payload is not None and payload.iteration == 3
    # sharded snapshots record their topology but store state UNSHARDED:
    # that is what makes the k' != k resume below legal
    assert int(payload.meta.get("num_shards", 0)) == k_crash

    bst = lgb.train({**_P, "num_shards": k_resume, "snapshot_freq": 1,
                     "snapshot_dir": d},
                    lgb.Dataset(X, label=y,
                                params={**_P, "num_shards": k_resume}),
                    num_boost_round=ROUNDS, fobj=_lattice_fobj,
                    resume_from_snapshot=d)
    assert bst.current_iteration == ROUNDS
    assert _model_bytes(bst) == ref_bytes


# ---------------- OOM-adaptive degradation (chaos) ----------------

def _device_fault_events():
    return [e for e in obs.EVENTS.snapshot() if e["type"] == "device_fault"]


@pytest.mark.chaos
@pytest.mark.faults
def test_device_put_oom_recovers_by_chunk_halving(data, ref3_bytes):
    """One injected RESOURCE_EXHAUSTED on the H2D upload: ingest halves the
    chunk, retries, trains to completion — bit-identical to single-chip —
    and the recovery is visible as a device_fault telemetry event."""
    obs.configure(enabled=True)
    obs.reset()
    try:
        bst, ds = _train(data, 2, 3, ingest_chunk_rows=400, telemetry=True,
                         faults="device_put_oom:1",
                         on_device_fault="reshard")
        ev = _device_fault_events()
        assert len(ev) == 1, ev
        assert ev[0]["point"] == "device_put_oom"
        assert ev[0]["policy"] == "reshard"
        assert ev[0]["action"] == "halve_chunk"
        assert ev[0]["chunk_rows"] == 200
        assert "RESOURCE_EXHAUSTED" in ev[0]["error"]
    finally:
        obs.configure(enabled=False)
        obs.reset()
    assert ingest.last_stats()["chunk_rows"] == 200
    assert ds.shard_plan is not None and ds.shard_plan.num_shards == 2
    assert _model_bytes(bst) == ref3_bytes


@pytest.mark.chaos
@pytest.mark.faults
def test_device_put_oom_fatal_fails_fast(data):
    """on_device_fault=fatal: the injected OOM propagates immediately —
    reference CHECK semantics, no silent degradation, no recovery events."""
    obs.configure(enabled=True)
    obs.reset()
    try:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            _train(data, 2, 3, telemetry=True, faults="device_put_oom:1",
                   on_device_fault="fatal")
        assert _device_fault_events() == []
    finally:
        obs.configure(enabled=False)
        obs.reset()


@pytest.mark.chaos
@pytest.mark.faults
def test_persistent_oom_reshards_to_more_devices(data, ref3_bytes):
    """Four consecutive injected OOMs exhaust the chunk-halving budget
    (3 rungs), so the reshard policy re-plans 2 -> 4 shards; the recovered
    run still matches single-chip bits."""
    obs.configure(enabled=True)
    obs.reset()
    try:
        bst, ds = _train(data, 2, 3, ingest_chunk_rows=400, telemetry=True,
                         faults="device_put_oom:4",
                         on_device_fault="reshard")
        actions = [e["action"] for e in _device_fault_events()]
        assert actions == ["halve_chunk"] * 3 + ["reshard"], actions
        last = _device_fault_events()[-1]
        assert last["shards_before"] == 2 and last["shards_after"] == 4
    finally:
        obs.configure(enabled=False)
        obs.reset()
    assert ds.shard_plan is not None and ds.shard_plan.num_shards == 4
    assert _model_bytes(bst) == ref3_bytes


@pytest.mark.chaos
@pytest.mark.faults
def test_persistent_oom_falls_back_to_single_device(data, ref3_bytes):
    """Same persistent OOM under on_device_fault=fallback_single: the plan
    is dropped and ingest drains through the single-device path — mesh
    training disabled, model bits unchanged."""
    bst, ds = _train(data, 2, 3, ingest_chunk_rows=400,
                     faults="device_put_oom:4",
                     on_device_fault="fallback_single")
    assert ds.shard_plan is None
    assert _model_bytes(bst) == ref3_bytes


@pytest.mark.chaos
@pytest.mark.faults
def test_hist_allreduce_fault_recovers_by_retry(data, ref3_bytes):
    """A device fault in the fused-step dispatch (the histogram psum) is
    retried with backoff instead of killing the run mid-boosting."""
    obs.configure(enabled=True)
    obs.reset()
    try:
        bst, _ds = _train(data, 2, 3, telemetry=True,
                          faults="hist_allreduce:1",
                          on_device_fault="reshard")
        ev = _device_fault_events()
        assert len(ev) == 1 and ev[0]["point"] == "hist_allreduce"
        assert ev[0]["action"] == "retry"
    finally:
        obs.configure(enabled=False)
        obs.reset()
    assert _model_bytes(bst) == ref3_bytes


@pytest.mark.chaos
@pytest.mark.faults
def test_hist_allreduce_fault_fatal_raises(data):
    with pytest.raises(FaultInjected):
        _train(data, 2, 3, faults="hist_allreduce:1",
               on_device_fault="fatal")


@pytest.mark.chaos
@pytest.mark.faults
def test_prewarm_compile_fault_is_adoption_miss(data, ref3_bytes,
                                                monkeypatch):
    """A fault inside the background prewarm worker must degrade to a cache
    miss (foreground compiles as usual), never to a failed run."""
    from lightgbm_tpu import prewarm
    monkeypatch.setattr(prewarm, "MIN_PREWARM_ROWS", 0)
    params = {k: v for k, v in _P.items() if k != "prewarm"}
    X, y = data
    bst = lgb.train({**params, "num_shards": 2,
                     "faults": "prewarm_compile:1"},
                    lgb.Dataset(X, label=y,
                                params={**params, "num_shards": 2}),
                    num_boost_round=3, fobj=_lattice_fobj)
    assert faults.hits("prewarm_compile") >= 1
    assert _model_bytes(bst) == ref3_bytes


# the text libtpu 0.0.34 gives a Mosaic kernel over the scoped-VMEM limit
_VMEM_OOM = ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem "
             "while allocating on stack for %custom-call. Scoped allocation "
             "with size 23.44M and limit 16.00M exceeded scoped vmem limit "
             "by 7.44M.")


@pytest.mark.faults
def test_compile_oom_is_not_a_device_fault(data, monkeypatch):
    """A compile-time out-of-memory is deterministic: it must surface from
    the first dispatch, once, and never enter the retry-with-backoff path
    (nor be logged as a transient device_fault)."""
    err = faults._xla_runtime_error_type()(_VMEM_OOM)
    assert faults.is_resource_exhausted(err) and faults.is_compile_oom(err)
    assert not faults.is_device_fault(err)
    runtime_oom = faults._oom_error("device_put_oom", 1)
    assert faults.is_device_fault(runtime_oom)
    assert not faults.is_compile_oom(runtime_oom)

    from lightgbm_tpu.models.gbdt import GBDT
    calls = []

    def _boom(self, custom):
        def step(*a):
            calls.append(1)
            raise err
        return step
    monkeypatch.setattr(GBDT, "_build_fused_step", _boom)
    obs.configure(enabled=True)
    obs.reset()
    try:
        with pytest.raises(type(err)) as ei:
            _train(data, 1, 3, telemetry=True, on_device_fault="reshard")
        assert len(calls) == 1
        assert not _device_fault_events()
        assert any("not retried" in n for n in ei.value.__notes__)
    finally:
        obs.configure(enabled=False)
        obs.reset()


# ---------------- mesh preflight fence ----------------

def _plan_shim(**over):
    import jax
    base = dict(axis_name="data", num_shards=2, n_rows=N,
                rows_per_shard=-(-N // 2), devices=jax.devices()[:2])
    base.update(over)
    return SimpleNamespace(**base)


def _ts_shim(n=N):
    return SimpleNamespace(num_data=n, mappers=None, feature_map=None,
                           num_features=F)


def test_mesh_preflight_passes_on_healthy_plan():
    from lightgbm_tpu.parallel.fence import mesh_preflight
    obs.configure(enabled=True)
    obs.reset()
    try:
        assert mesh_preflight(Config({}), _ts_shim(), _plan_shim()) is True
        ev = [e for e in obs.EVENTS.snapshot()
              if e["type"] == "mesh_preflight"]
        assert len(ev) == 1 and ev[0]["ok"] is True and ev[0]["shards"] == 2
    finally:
        obs.configure(enabled=False)
        obs.reset()
    # and trivially True with no plan: nothing to validate single-chip
    assert mesh_preflight(Config({}), _ts_shim(), None) is True


def test_mesh_preflight_names_axis_mismatch():
    from lightgbm_tpu.parallel.fence import mesh_preflight
    with pytest.raises(log.LightGBMError, match=r"plan\.axis_name"):
        mesh_preflight(Config({}), _ts_shim(),
                       _plan_shim(axis_name="rows"))


def test_mesh_preflight_names_stale_row_count():
    from lightgbm_tpu.parallel.fence import mesh_preflight
    with pytest.raises(log.LightGBMError, match=r"plan\.n_rows"):
        mesh_preflight(Config({}), _ts_shim(n=N - 100), _plan_shim())


def test_mesh_preflight_catches_dead_device():
    """A device that fails the liveness probe (here: not a device at all)
    is reported per-device instead of hanging the first collective."""
    from lightgbm_tpu.parallel.fence import mesh_preflight
    plan = _plan_shim(devices=["not-a-device"], num_shards=1,
                      rows_per_shard=N)
    captured = []
    log.set_callback(captured.append)
    try:
        ok = mesh_preflight(Config({}), _ts_shim(), plan,
                            raise_on_mismatch=False)
    finally:
        log.set_callback(None)
    assert ok is False
    blob = "".join(captured)
    assert "mesh preflight FAILED" in blob
    assert "not-a-device" in blob


# ---------------- fault registry hygiene ----------------

@pytest.mark.faults
def test_unknown_fault_point_rejected():
    """A typo'd fault spec must fail arming loudly (a chaos drill that
    silently tests nothing is worse than no drill), naming the registry."""
    with pytest.raises(ValueError) as ei:
        faults.configure("device_put_oops:1")
    msg = str(ei.value)
    assert "device_put_oops" in msg
    for known in ("device_put_oom", "tree_update", "shard_commit"):
        assert known in msg
    # and the same spec via params dies before any training starts
    with pytest.raises(ValueError):
        lgb.train({**_P, "faults": "device_put_oops:1"},
                  lgb.Dataset(np.zeros((8, 2), np.float32),
                              label=np.zeros(8, np.float32)),
                  num_boost_round=1)
