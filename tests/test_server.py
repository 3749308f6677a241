"""Serving front-end (server.py): request-coalescing microbatcher + model
registry. Acceptance (ISSUE 8): scheduler outputs bit-exact vs direct
PredictEngine calls under concurrency, zero retraces after per-bucket
warmup, hot-swap mid-load drops zero requests and every response is
bit-exact for the version that served it, overload sheds instead of
queueing unboundedly."""
import io
import socket
import threading

import numpy as np
import pytest

import jax._src.test_util as jtu

import lightgbm_tpu as lgb
from lightgbm_tpu.server import (MicroBatcher, ModelRegistry, PredictServer,
                                 ServeOverload, handle_line, serve_stdio,
                                 serve_tcp)

RNG = np.random.RandomState(11)
N_FEAT = 8


@pytest.fixture(scope="module", autouse=True)
def _lockwatch_zero_inversions():
    """The static lock-order rule says the serve stack's lock graph is a
    DAG; the runtime watchdog (installed by conftest before any product
    lock exists) must agree after this suite's real concurrency."""
    from lightgbm_tpu.analysis import lockwatch
    yield
    lockwatch.WATCH.assert_clean("tests/test_server.py")


def _train(rounds=6, seed_shift=0.0):
    X = RNG.rand(500, N_FEAT)
    y = (X[:, 0] + X[:, 1] + seed_shift * X[:, 2] > 1).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=rounds)


@pytest.fixture(scope="module")
def boosters():
    return _train(rounds=5), _train(rounds=8, seed_shift=1.0)


@pytest.fixture(scope="module")
def queries():
    return RNG.rand(64, N_FEAT)


def _mk_server(b, **conf):
    conf.setdefault("verbose", -1)
    conf.setdefault("serve_max_batch_rows", 256)
    return PredictServer(conf, model=b)


# ---- bit-exactness + thread safety ----

def test_concurrent_bit_exact_vs_direct(boosters, queries):
    """N threads x M requests through the scheduler == per-row direct
    Booster.predict, bit for bit (row-independent kernels + pad slicing)."""
    b1, _ = boosters
    srv = _mk_server(b1)
    try:
        want = {False: b1.predict(queries),
                True: b1.predict(queries, raw_score=True)}
        n_threads, reps = 8, 3
        errs, results = [], {}

        def worker(t):
            try:
                out = []
                for rep in range(reps):
                    for i in range(t, len(queries), n_threads):
                        raw = (t + rep + i) % 2 == 1
                        got = srv.predict(queries[i], raw_score=raw)
                        out.append((i, raw, got))
                results[t] = out
            except Exception as e:            # pragma: no cover
                errs.append(e)

        ths = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
        [t.start() for t in ths]
        [t.join() for t in ths]
        assert not errs, errs
        checked = 0
        for out in results.values():
            for i, raw, got in out:
                assert got.shape == (1,)
                assert got[0] == want[raw][i], (i, raw)
                checked += 1
        assert checked == n_threads * reps * (len(queries) // n_threads)
        # concurrency actually coalesced at least some dispatches
        st = srv.stats()["scheduler"]
        assert st["requests"] >= checked
        assert st["flushes"] <= st["requests"]
    finally:
        srv.close()


def test_multirow_requests_bit_exact(boosters, queries):
    b1, _ = boosters
    srv = _mk_server(b1)
    try:
        for n in (1, 2, 7, 33):
            got = srv.predict(queries[:n])
            assert np.array_equal(got, b1.predict(queries[:n])), n
        got = srv.predict(queries[:5], pred_leaf=True)
        assert np.array_equal(got, b1.predict(queries[:5], pred_leaf=True))
    finally:
        srv.close()


def test_zero_retraces_after_warmup(boosters, queries):
    """After publish-time per-bucket warmup plus one serve-path call per
    bucket, a concurrent request storm lowers ZERO new XLA programs."""
    b1, _ = boosters
    srv = _mk_server(b1)
    try:
        sizes = (1, 2, 5, 8, 9, 30, 64)
        for n in sizes:                       # serve-path warmup per bucket
            srv.predict(queries[:n])
            srv.predict(queries[:n], raw_score=True)
        with jtu.count_jit_and_pmap_lowerings() as count:
            def worker(t):
                for n in sizes:
                    srv.predict(queries[:n], raw_score=(t % 2 == 0))
            ths = [threading.Thread(target=worker, args=(t,))
                   for t in range(6)]
            [t.start() for t in ths]
            [t.join() for t in ths]
        assert count() == 0, f"{count()} recompilations on the serve path"
    finally:
        srv.close()


# ---- hot swap ----

def test_hot_swap_mid_load_zero_drops(boosters, queries):
    """Publish v2 while 8 threads hammer v1: every request is answered (zero
    drops), every response matches the booster of the version that served
    it, and the retired v1 engine is freed once its flushes drain."""
    b1, b2 = boosters
    srv = _mk_server(b1)
    try:
        want = {1: b1.predict(queries), 2: b2.predict(queries)}
        eng_v1 = srv.registry.current().engine
        errs, seen_versions = [], set()
        results = []
        res_lock = threading.Lock()
        stop = threading.Event()

        # submit() returns only the ndarray; the swap test needs the serving
        # version too -> submit_async and read it off the request
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
            except Exception as e:            # pragma: no cover
                errs.append(e)

        ths = [threading.Thread(target=worker_async, args=(t,))
               for t in range(8)]
        [t.start() for t in ths]
        # let v1 serve some traffic, swap, let v2 serve some traffic
        import time
        while len(results) < 50 and not errs:
            time.sleep(0.005)
        v2 = srv.publish(b2)
        assert v2 == 2
        n_at_swap = len(results)
        while len(results) < n_at_swap + 50 and not errs:
            time.sleep(0.005)
        stop.set()
        [t.join() for t in ths]
        assert not errs, errs
        for i, version, out in results:
            seen_versions.add(version)
            assert out[0] == want[version][i], (i, version)
        assert seen_versions == {1, 2}, seen_versions
        # v1 drained -> its device tables were freed
        assert srv.registry.current().version == 2
        assert eng_v1.released
        with pytest.raises(RuntimeError, match="release"):
            eng_v1.run_binned(np.zeros((1, N_FEAT), np.int32), 1)
    finally:
        srv.close()


def test_registry_versioning_and_drain(boosters):
    b1, b2 = boosters
    reg = ModelRegistry()
    sm1 = reg.publish("m", b1)
    assert sm1.version == 1
    held = reg.acquire("m")                   # simulate an in-flight flush
    sm2 = reg.publish("m", b2)
    assert sm2.version == 2 and reg.current("m") is sm2
    assert sm1.retired and not sm1.engine.released   # still held
    reg.release(held, rows=3)
    assert sm1.engine.released                # freed at drain
    assert sm1.served_rows == 3
    with pytest.raises(KeyError):
        reg.acquire("nope")


# ---- scheduling behavior ----

def test_overload_sheds_bounded(boosters, queries):
    b1, _ = boosters
    reg = ModelRegistry()
    reg.publish("default", b1, warmup_sizes=())
    mb = MicroBatcher(reg, queue_max=4, start=False)
    for i in range(4):
        mb.submit_async(queries[i])
    with pytest.raises(ServeOverload):
        mb.submit_async(queries[4])
    assert mb.stats["shed"] == 1
    # draining close() still serves everything that WAS admitted
    mb.start()
    mb.close(drain=True)
    assert mb.stats["flushed_rows"] == 4


def test_coalesce_factor_above_one(boosters, queries):
    """A queued burst coalesces into far fewer dispatches than requests."""
    b1, _ = boosters
    reg = ModelRegistry()
    reg.publish("default", b1)
    mb = MicroBatcher(reg, batch_window_us=2000, max_batch_rows=256,
                      start=False)
    reqs = [mb.submit_async(queries[i % len(queries)]) for i in range(50)]
    mb.start()
    outs = [r.result(timeout=30) for r in reqs]
    assert all(o is not None for o in outs)
    assert mb.coalesce_factor() > 1.0
    assert mb.stats["flushes"] < 50
    mb.close()


def test_idle_fast_path(boosters, queries):
    """An unloaded server must NOT pay the coalescing window: a lone request
    with a deliberately huge window still returns quickly."""
    import time
    b1, _ = boosters
    srv = _mk_server(b1, serve_batch_window_us=300_000)   # 0.3s window
    try:
        srv.predict(queries[0])               # warm the n=1 serve path
        t0 = time.perf_counter()
        srv.predict(queries[1])
        dt = time.perf_counter() - t0
        assert dt < 0.25, f"idle single-row request took {dt:.3f}s (window tax)"
        assert srv.stats()["scheduler"]["fast_path"] >= 1
    finally:
        srv.close()


def test_request_validation(boosters, queries):
    b1, _ = boosters
    srv = _mk_server(b1, serve_max_batch_rows=16)
    try:
        with pytest.raises(ValueError, match="serve_max_batch_rows"):
            srv.predict(RNG.rand(17, N_FEAT))
        with pytest.raises(ValueError, match="features"):
            srv.predict(RNG.rand(2, 2, 2))
        with pytest.raises(KeyError, match="no model"):
            srv.predict(queries[0], model="ghost")
    finally:
        srv.close()
    with pytest.raises(RuntimeError, match="shut down"):
        srv.predict(queries[0])


# ---- transports ----

def test_line_protocol_and_stdio(boosters, queries, tmp_path):
    b1, b2 = boosters
    srv = _mk_server(b1)
    try:
        line = ",".join("%.17g" % v for v in queries[0])
        resp = handle_line(srv, line)
        ver, val = resp.split("\t")
        assert int(ver) == 1
        assert np.float64(val) == b1.predict(queries[:1])[0]

        p2 = str(tmp_path / "m2.txt")
        b2.save_model(p2)
        inp = io.StringIO(f"{line}\n!publish {p2}\n{line}\n!stats\n!quit\n")
        out = io.StringIO()
        served = serve_stdio(srv, inp, out)
        lines = out.getvalue().splitlines()
        assert served == 4
        assert lines[1] == "ok version=2"
        ver2, val2 = lines[2].split("\t")
        assert int(ver2) == 2
        assert np.float64(val2) == b2.predict(queries[:1])[0]
        assert '"flushes"' in lines[3]
        assert handle_line(srv, "!bogus").startswith("error:")
        assert handle_line(srv, "not,numbers,at,all").startswith("error:")
    finally:
        srv.close()


def test_tcp_transport(boosters, queries):
    b1, _ = boosters
    srv = _mk_server(b1)
    ready = threading.Event()
    th = threading.Thread(target=serve_tcp, args=(srv, "127.0.0.1", 0, ready),
                          daemon=True)
    th.start()
    assert ready.wait(10)
    host, port = ready.addr
    try:
        want = b1.predict(queries[:4])

        def client(i, out):
            with socket.create_connection((host, port), timeout=10) as s:
                f = s.makefile("rw")
                f.write(",".join("%.17g" % v for v in queries[i]) + "\n")
                f.flush()
                out[i] = f.readline().strip()

        outs = {}
        ths = [threading.Thread(target=client, args=(i, outs))
               for i in range(4)]
        [t.start() for t in ths]
        [t.join() for t in ths]
        for i in range(4):
            ver, val = outs[i].split("\t")
            assert int(ver) == 1 and np.float64(val) == want[i], i
    finally:
        with socket.create_connection((host, port), timeout=10) as s:
            s.sendall(b"!quit\n")
        th.join(10)
        srv.close()
        assert not th.is_alive()


# ---- C-API surface ----

def test_capi_server_roundtrip(boosters, queries, tmp_path):
    import ctypes
    from lightgbm_tpu import capi_impl as C
    b1, b2 = boosters
    p1, p2 = str(tmp_path / "v1.txt"), str(tmp_path / "v2.txt")
    b1.save_model(p1)
    b2.save_model(p2)
    srv = C.server_create(p1, "verbose=-1 serve_max_batch_rows=64")
    try:
        x = np.ascontiguousarray(queries[:3], dtype=np.float64)
        out = np.zeros(3, dtype=np.float64)
        n = C.server_predict(srv, x.ctypes.data, 3, N_FEAT, 0, 0,
                             out.ctypes.data, out.size)
        assert n == 3 and np.array_equal(out, b1.predict(queries[:3]))
        assert C.server_predict(srv, x.ctypes.data, 3, N_FEAT, 0, 0,
                                out.ctypes.data, 1) == -1   # cap too small
        assert C.server_publish(srv, p2) == 2
        n = C.server_predict(srv, x.ctypes.data, 3, N_FEAT, 0, 0,
                             out.ctypes.data, out.size)
        assert n == 3 and np.array_equal(out, b2.predict(queries[:3]))
        stats = C.server_stats_json(srv)
        assert '"version": 2' in stats
    finally:
        assert C.server_close(srv) == 0


# ---- every boosting type round-trips the serving path ----

_BOOSTING_PARAMS = {
    "gbdt": {},
    "dart": {"drop_rate": 0.5, "max_drop": 3},
    "goss": {"top_rate": 0.3, "other_rate": 0.2},
    "rf": {"bagging_freq": 1, "bagging_fraction": 0.7},
}


@pytest.mark.parametrize("boosting", sorted(_BOOSTING_PARAMS))
def test_boosting_types_round_trip_serving(boosting, queries, tmp_path):
    """GBDT/DART/GOSS/RF all serve bit-exact through the registry/engine:
    direct Booster.predict == served predictions (score AND raw_score), for
    both the in-session Booster and the saved->loaded artifact (DART's
    rescaled leaf values and RF's average_output must survive the publish
    path, not just in-session prediction)."""
    X = np.random.RandomState(5).rand(400, N_FEAT)
    y = (X[:, 0] + X[:, 1] > 1).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "boosting": boosting,
              **_BOOSTING_PARAMS[boosting]}
    b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
    want = {False: b.predict(queries), True: b.predict(queries, raw_score=True)}
    path = str(tmp_path / f"{boosting}.txt")
    b.save_model(path)
    loaded = lgb.Booster(model_file=path)
    for raw in (False, True):
        assert np.array_equal(loaded.predict(queries, raw_score=raw),
                              want[raw]), (boosting, "loaded", raw)
    srv = _mk_server(b)
    try:
        for raw in (False, True):
            assert np.array_equal(srv.predict(queries, raw_score=raw),
                                  want[raw]), (boosting, "served", raw)
        assert srv.publish(path) == 2       # loaded-artifact publish path
        for raw in (False, True):
            assert np.array_equal(srv.predict(queries, raw_score=raw),
                                  want[raw]), (boosting, "served-v2", raw)
        assert np.array_equal(srv.predict(queries[:5], pred_leaf=True),
                              b.predict(queries[:5], pred_leaf=True)), boosting
    finally:
        srv.close()
