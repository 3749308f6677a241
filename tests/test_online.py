"""Continuous training (online.py): append-only Dataset growth + streaming
refit wired into hot-swap serving. Acceptance (ISSUE 10):

- appended rows bin bit-identically to a one-shot frozen (``reference=``)
  construct of the concatenated data;
- ``Booster.refit`` leaf outputs match a CPU reference computation;
- continued training from a snapshot on appended rows is byte-identical to
  uninterrupted continued training (same model text);
- publishing mid-load serves both versions bit-exactly with zero dropped
  requests, and the end-to-end drill (train first half, stream second half
  through append chunks, refit + publish into a live PredictServer under
  concurrent load) serves bit-exact vs the offline continued-training run
  with zero new lowerings across a warmed leaf-refit + publish + serve
  window.
"""
import threading
import time

import numpy as np
import pytest

import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu.basic import Booster, Dataset
from lightgbm_tpu.online import (OnlineTrainer, last_cycle_stats,
                                 merge_boosters, tail_source)
from lightgbm_tpu.server import PredictServer, handle_line
from lightgbm_tpu.utils.log import LightGBMError


@pytest.fixture(scope="module", autouse=True)
def _lockwatch_zero_inversions():
    from lightgbm_tpu.analysis import lockwatch
    yield
    lockwatch.WATCH.assert_clean("tests/test_online.py")

RNG = np.random.RandomState(23)
N_FEAT = 8


def _make_data(n=1000, f=N_FEAT, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    y = (X[:, 0] + X[:, 1] - 0.5 * X[:, 2] > 0.7).astype(float)
    return X, y


# ---- (a) appended bins == one-shot frozen construct ----

def test_append_bins_bit_identical():
    """Growing a dataset in uneven chunks must produce the exact binned
    matrix a reference=-aligned one-shot construct of the concatenation
    produces — including out-of-range values (clip to edge bins) and NaNs
    (na bin)."""
    X, y = _make_data(n=400, f=6)
    X = X.copy()
    X[350, 0] *= 100.0          # out of the frozen range: clips to edge bin
    X[351, 1] = np.nan          # missing: lands in the na bin
    X[352, 2] = -50.0           # below range: clips to the low edge
    a = 200
    params = {"verbose": -1, "max_bin": 63}
    ds = Dataset(X[:a], label=y[:a], params=params)
    ds.construct()
    n_bins_before = np.asarray(ds.bins[:a]).copy()
    # uneven chunks, including a single-row append
    for lo, hi in ((200, 340), (340, 341), (341, 400)):
        ds.append(X[lo:hi], label=y[lo:hi])
    assert ds.num_data == 400
    ref = Dataset(X, label=y, params=params, reference=ds)
    ref.construct()
    got = np.asarray(ds.bins[:400])
    want = np.asarray(ref.bins[:400])
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)
    # the original rows were not touched by the appends
    assert np.array_equal(got[:a], n_bins_before)
    # labels grew in step
    assert np.array_equal(ds.get_label(), y)


def test_append_validation():
    X, y = _make_data(n=100, f=4)
    ds = Dataset(X[:60], label=y[:60], params={"verbose": -1})
    ds.construct()
    with pytest.raises(LightGBMError, match="label"):
        ds.append(X[60:])                       # dataset labeled, rows not
    with pytest.raises(LightGBMError, match="features"):
        ds.append(X[60:, :3], label=y[60:])     # width mismatch
    with pytest.raises(LightGBMError, match="label"):
        ds.append(X[60:], label=y[60:70])       # length mismatch
    assert ds.num_data == 60                    # failed appends changed nothing


def test_append_resharded_under_mesh():
    """Appending to a row-sharded dataset re-plans the shard grid for the
    grown total and redistributes; the binned rows stay bit-identical to an
    unsharded grow of the same stream."""
    X, y = _make_data(n=600, f=6, seed=9)
    params = {"verbose": -1, "num_shards": 4}
    ds = Dataset(X[:401], label=y[:401], params=params)   # non-divisible
    ds.construct()
    assert ds.shard_plan is not None and ds.shard_plan.num_shards == 4
    ds.append(X[401:], label=y[401:])
    plan = ds.shard_plan
    assert plan is not None and plan.num_shards == 4
    assert plan.n_rows == 600 and ds.num_data == 600
    assert ds.bins.shape[0] == plan.n_padded
    assert len(set(ds.bins.sharding.device_set)) == 4
    flat = Dataset(X[:401], label=y[:401], params={"verbose": -1})
    flat.construct()
    flat.append(X[401:], label=y[401:])
    assert np.array_equal(np.asarray(ds.bins[:600]),
                          np.asarray(flat.bins[:600]))


# ---- (b) refit == CPU reference ----

def _refit_reference(booster, X, y, decay):
    """Host mirror of Booster.refit for unit-hessian L2 regression with
    lambda_l1 = lambda_l2 = max_delta_step = 0: per tree, route rows via
    pred_leaf, recompute -sum_g/sum_h in f32 (the jnp default dtype),
    blend with decay, and propagate the blended outputs into the score."""
    trees = booster._ensure_host_trees()
    leaf_mat = np.asarray(booster.predict(X, pred_leaf=True))
    yf = np.asarray(y, dtype=np.float32)
    score = np.zeros(X.shape[0], dtype=np.float64)
    expected = []
    for ti, t in enumerate(trees):
        g = score.astype(np.float32) - yf                 # f32 gradients
        leaf = leaf_mat[:, ti]
        sg = np.bincount(leaf, weights=g.astype(np.float64),
                         minlength=t.num_leaves)
        sh = np.bincount(leaf, weights=np.ones(len(g)),
                         minlength=t.num_leaves) + 1e-15
        w32 = -(sg.astype(np.float32)) / (sh.astype(np.float32)
                                          + np.float32(1e-38))
        new_out = w32.astype(np.float64) * t.shrinkage
        blended = decay * t.leaf_value + (1.0 - decay) * new_out
        expected.append(blended)
        score = score + blended[leaf]
    return expected


def test_refit_matches_cpu_reference():
    X, y = _make_data(n=500, f=6, seed=3)
    y = X[:, 0] * 2.0 + X[:, 1] + 0.1 * RNG.rand(500)
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    bst = lgb.train(params, Dataset(X, label=y, params=params),
                    num_boost_round=5)
    rng = np.random.RandomState(17)
    X2 = rng.rand(200, 6)
    y2 = X2[:, 0] * 2.0 + X2[:, 1] + 0.1 * rng.rand(200)
    decay = 0.7
    refit = bst.refit(X2, y2, decay_rate=decay)
    want = _refit_reference(bst, X2, y2, decay)
    got_trees = refit._ensure_host_trees()
    assert len(got_trees) == len(want)
    for t, w in zip(got_trees, want):
        np.testing.assert_allclose(t.leaf_value, w, rtol=1e-5, atol=1e-7)
    # the refit model predicts with the blended outputs, same structures
    leaves_before = bst.predict(X2, pred_leaf=True)
    leaves_after = refit.predict(X2, pred_leaf=True)
    assert np.array_equal(leaves_before, leaves_after)


# ---- merge_boosters: one servable artifact from init + delta ----

def test_merge_boosters_binary():
    X, y = _make_data(n=500)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    b1 = lgb.train(params, Dataset(X, label=y, params=params),
                   num_boost_round=5)
    delta = lgb.train(params, Dataset(X, label=y, params=params),
                      num_boost_round=3, init_model=b1)
    m = merge_boosters(b1, delta)
    assert m.num_trees() == b1.num_trees() + 3
    got = m.predict(X[:100], raw_score=True)
    want = b1.predict(X[:100], raw_score=True) + \
        delta.predict(X[:100], raw_score=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # text round-trip of the merged artifact is byte-idempotent
    s = m.model_to_string()
    assert Booster(model_str=s).model_to_string() == s


def test_merge_boosters_multiclass():
    rng = np.random.RandomState(2)
    X = rng.rand(400, 5)
    y = (X[:, 0] * 3).astype(int) % 3
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
              "verbose": -1, "min_data_in_leaf": 5}
    b1 = lgb.train(params, Dataset(X, label=y, params=params),
                   num_boost_round=2)
    delta = lgb.train(params, Dataset(X, label=y, params=params),
                      num_boost_round=2, init_model=b1)
    m = merge_boosters(b1, delta)
    assert m.num_model_per_iteration() == 3
    assert m.num_trees() == b1.num_trees() + delta.num_trees()
    got = m.predict(X[:50], raw_score=True)
    want = b1.predict(X[:50], raw_score=True) + \
        delta.predict(X[:50], raw_score=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


# ---- (c) snapshot-resumed continuation == uninterrupted continuation ----

def test_snapshot_continued_training_byte_identical(tmp_path):
    from lightgbm_tpu.snapshot import booster_from_latest, write_snapshot
    X, _ = _make_data(n=600, f=6, seed=11)
    y = X[:, 0] + 0.5 * X[:, 1] + 0.05 * RNG.rand(600)
    h = 300
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}

    def _continue(init):
        ds = Dataset(X[:h], label=y[:h], params=params)
        ds.construct()
        ds.append(X[h:], label=y[h:])
        delta = lgb.train(params, ds, num_boost_round=3, init_model=init)
        return merge_boosters(init, delta).model_to_string()

    b1 = lgb.train(params, Dataset(X[:h], label=y[:h], params=params),
                   num_boost_round=5)
    # uninterrupted: continue from the in-memory model
    text_mem = _continue(b1)
    # interrupted: snapshot, restore, continue from the restored model
    snap_dir = str(tmp_path / "snaps")
    write_snapshot(b1, snap_dir, iteration=5)
    loaded, it = booster_from_latest(snap_dir)
    assert loaded is not None and it == 5
    text_snap = _continue(loaded)
    assert text_mem == text_snap


# ---- sources + triggers ----

def test_tail_source_and_run_flush(tmp_path):
    feed = tmp_path / "feed.csv"
    feed.write_text("# comment line\n"
                    "1.5,0.1,0.2,0.3\n"
                    "2.5,0.4,0.5,0.6   # trailing comment\n"
                    "\n"
                    "3.5 0.7 0.8 0.9\n")   # whitespace-separated also ok
    batches = [b for b in tail_source(str(feed), follow=False)
               if b is not None]
    got_x = np.concatenate([b[0] for b in batches])
    got_y = np.concatenate([b[1] for b in batches])
    assert got_x.shape == (3, 3)
    np.testing.assert_array_equal(got_y, [1.5, 2.5, 3.5])

    X, _ = _make_data(n=120, f=3, seed=4)
    y = X[:, 0] + X[:, 1]
    params = {"objective": "regression", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, "num_iterations": 4,
              "online_refit_rows": 10 ** 6, "online_boost_rounds": 2}
    tr = OnlineTrainer(params, Dataset(X, label=y, params=params))
    n0 = tr.booster.num_trees()
    assert n0 == 4                     # trainer trained the initial model
    fed = tr.run(tail_source(str(feed), follow=False))
    assert fed == 3
    assert tr.cycles == 1 and tr.version == 1
    assert tr.dataset.num_data == 123
    assert tr.booster.num_trees() == n0 + 2     # merged delta rides along
    st = last_cycle_stats()
    assert st["trigger"] == "flush" and st["mode"] == "boost"
    assert st["rows"] == 3 and st["total_rows"] == 123


def test_drift_trigger_and_events():
    from lightgbm_tpu import obs
    X, _ = _make_data(n=300, f=4, seed=6)
    y = X[:, 0] + X[:, 1]
    # telemetry must ride in the params: the cycle's engine.train call
    # re-applies the config's telemetry knob (configure_from_config)
    params = {"objective": "regression", "metric": "l2", "num_leaves": 7,
              "verbose": -1, "min_data_in_leaf": 5, "num_iterations": 5,
              "telemetry": True, "online_refit_rows": 10 ** 6,
              "online_drift_metric_delta": 0.05, "online_boost_rounds": 1}
    obs.EVENTS.clear()
    try:
        tr = OnlineTrainer(params, Dataset(X, label=y, params=params))
        rng = np.random.RandomState(8)
        Xa = rng.rand(40, 4)
        # in-distribution batch: records the baseline, no trigger
        assert tr.feed(Xa, Xa[:, 0] + Xa[:, 1]) is None
        assert tr.cycles == 0 and tr.pending_rows == 40
        # drifted batch: l2 explodes past the delta -> cycle fires
        Xb = rng.rand(40, 4)
        ver = tr.feed(Xb, Xb[:, 0] + Xb[:, 1] + 10.0)
        assert ver == 1 and tr.cycles == 1
        assert tr.pending_rows == 0 and tr.dataset.num_data == 380
        assert last_cycle_stats()["trigger"] == "drift"
        events = obs.EVENTS.snapshot()
        drift = [e for e in events if e["type"] == "drift_trigger"]
        assert drift and drift[-1]["metric"] == "l2"
        assert drift[-1]["delta"] > 0.05
        assert any(e["type"] == "dataset_append" for e in events)
        refits = [e for e in events if e["type"] == "online_refit"]
        assert refits and refits[-1]["trigger"] == "drift"
        assert refits[-1]["mode"] == "boost" and refits[-1]["rows"] == 80
    finally:
        obs.configure(enabled=False)
        obs.EVENTS.clear()


# ---- the !learn serve-protocol command ----

def test_learn_protocol(tmp_path):
    X, y = _make_data(n=200, f=4, seed=12)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, "serve_max_batch_rows": 64,
              "online_refit_rows": 3, "online_boost_rounds": 0}
    b = lgb.train(params, Dataset(X, label=y, params=params),
                  num_boost_round=3)
    srv = PredictServer(params, model=b)
    try:
        row = ",".join("%.17g" % v for v in X[0])
        assert handle_line(srv, f"!learn 1,{row}") == \
            "error: no online trainer attached"
        ds = Dataset(X, label=y, params=params)
        tr = OnlineTrainer(params, ds, booster=b, server=srv)
        srv.attach_online(tr)
        assert tr.version == 1                  # server already published v1
        assert handle_line(srv, "!learn").startswith("error")
        assert handle_line(srv, "!learn 1.0").startswith("error")
        r1 = handle_line(srv, f"!learn 1,{row}")
        assert r1 == "ok pending=1"
        r2 = handle_line(srv, f"!learn 0,{row}")
        assert r2 == "ok pending=2"
        r3 = handle_line(srv, f"!learn 1,{row}")   # third row: cycle fires
        assert "version=2" in r3 and "pending=0" in r3
        assert tr.cycles == 1 and ds.num_data == 203
        # the hot-swapped version serves the refit model bit-exactly
        got = srv.predict(X[:5])
        np.testing.assert_array_equal(got, tr.booster.predict(X[:5]))
    finally:
        srv.close()


# ---- (d) + acceptance drill: stream second half, refit + publish under
# concurrent load, bit-exact vs offline, zero drops, zero new lowerings ----

def test_end_to_end_online_drill():
    X, y = _make_data(n=1000)
    h = 500
    queries = RNG.rand(64, N_FEAT)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "serve_max_batch_rows": 256,
              "online_refit_rows": 500, "online_boost_rounds": 4}

    # train on the first half; this booster seeds both runs
    ds = Dataset(X[:h], label=y[:h], params=params)
    b1 = lgb.train(params, ds, num_boost_round=6)

    # offline continued-training run: one-shot append + warm-started delta
    ds_off = Dataset(X[:h], label=y[:h], params=params)
    ds_off.construct()
    ds_off.append(X[h:], label=y[h:])
    delta_off = lgb.train(params, ds_off, num_boost_round=4, init_model=b1)
    b2_off = merge_boosters(b1, delta_off)

    srv = PredictServer(params, model=b1)
    tr = OnlineTrainer(params, ds, booster=b1, server=srv)
    srv.attach_online(tr)
    want = {1: b1.predict(queries), 2: b2_off.predict(queries)}
    errs, results = [], []
    res_lock = threading.Lock()
    stop = threading.Event()

    def worker_async(t):
        try:
            j = t
            while not stop.is_set():
                i = j % len(queries)
                r = srv.batcher.submit_async(queries[i])
                out = r.result(timeout=30)
                with res_lock:
                    results.append((i, r.version, out))
                j += 1
        except Exception as e:                    # pragma: no cover
            errs.append(e)

    try:
        ths = [threading.Thread(target=worker_async, args=(t,))
               for t in range(8)]
        [t.start() for t in ths]
        while len(results) < 40 and not errs:
            time.sleep(0.005)

        # stream the second half in four chunks; the last one crosses the
        # online_refit_rows threshold and runs a full cycle inline
        ver = None
        for lo in range(h, 1000, 125):
            v = tr.feed(X[lo:lo + 125], y[lo:lo + 125])
            ver = v if v is not None else ver
        assert ver == 2 and tr.cycles == 1
        assert tr.dataset.num_data == 1000
        st = last_cycle_stats()
        assert st["trigger"] == "rows" and st["mode"] == "boost"
        assert st["rows"] == 500 and st["version"] == 2
        # the online continuation IS the offline continuation, byte for byte
        assert tr.booster.model_to_string() == b2_off.model_to_string()

        n_at_swap = len(results)
        while len(results) < n_at_swap + 40 and not errs:
            time.sleep(0.005)

        # leaf-refit hot path: warm one refit + publish cycle (compiles the
        # pred_leaf route + the engine bucket set for this tree shape) ...
        r3 = tr.booster.refit(X[h:h + 125], y[h:h + 125])
        assert srv.publish(r3) == 3
        want[3] = r3.predict(queries)
        n_now = len(results)
        while len(results) < n_now + 20 and not errs:
            time.sleep(0.005)

        # ... then the measured window: a same-shape refit chunk, publish,
        # and concurrent serve traffic must lower ZERO new XLA programs
        # (leaf refit keeps every table shape; publish warmup hits the
        # module-level shape-keyed caches)
        with jtu.count_jit_and_pmap_lowerings() as count:
            r4 = tr.booster.refit(X[h + 125:h + 250], y[h + 125:h + 250])
            v4 = srv.publish(r4)
            n_now = len(results)
            while len(results) < n_now + 40 and not errs:
                time.sleep(0.005)
        assert count() == 0, \
            f"{count()} new lowerings in the refit+publish+serve window"
        assert v4 == 4
        want[4] = r4.predict(queries)

        stop.set()
        [t.join() for t in ths]
        assert not errs, errs
        # zero drops: every admitted request was answered, nothing shed
        assert srv.stats()["scheduler"]["shed"] == 0
        seen = set()
        for i, version, out in results:
            seen.add(version)
            assert out[0] == want[version][i], (i, version)
        assert {1, 2} <= seen, seen
        assert srv.registry.current().version == 4
    finally:
        stop.set()
        srv.close()
