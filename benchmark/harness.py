"""The run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` (a reader: ``read(ctx)`` returns the value or None),
``limits/<workload>.json`` (the limits ``correct`` is held to). Adding a
cell, a configuration or a metric adds files and entries and edits none.

A configuration's generator module (``generator`` in its file) gives
``seed_key``, ``to_host``, ``BLOCK_ROWS`` and ``VALID_STREAM``, and may give
``dataset_fields(key, cfg, n_rows, first_block=0, rows=BLOCK_ROWS)``: a dict
of ``lgb.Dataset``'s own keyword arguments among ``DATASET_FIELDS`` (query
groups, weights, init scores, categorical columns) for the same rows as
``to_host``; without it ``lgb.Dataset`` gets the matrix, labels and params
alone. The check gets every validation metric the run reported.
"""
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
STATE = os.path.join(ROOT, ".bench_state")   # native build, traces; ignored
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PREFAULT_THREADS = 6
DATASET_FIELDS = ("group", "weight", "init_score", "categorical_feature")


def _json(path):
    with open(path) as fh:
        return json.load(fh)


def load_cell(name, bench=None):
    bench = bench or _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"benchmark: no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])

    def applies(m):
        return name in m.get("workloads", [name])
    return {
        "name": name, "chips": int(w["chips"]),
        "cfg": _json(os.path.join(ROOT, conf["file"])),
        "traffic": _json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "limits": _json(os.path.join(HERE, "limits", name + ".json")),
    }


def prepare_environment(native_in_checkout=False):
    """Before jax or the package is imported: every compiled program goes to
    the persistent cache (the package's own knob; its default skips programs
    that compile in under a second, which would then compile in every run,
    and in cell 1 inside the window: PERF.md section 6 has the difference).
    On the chip the native encoder builds inside the checkout; a rehearsal
    leaves it under TMPDIR, since a library built for this CPU must not
    travel with the tree to another machine."""
    os.environ.setdefault("LGBM_TPU_JAX_CACHE_MIN_COMPILE_S", "0")
    if native_in_checkout:
        os.environ.setdefault("LGBM_TPU_NATIVE_CACHE",
                              os.path.join(STATE, "native"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def prefault(shape):
    """A float32 host buffer whose pages a few threads touch while the
    caller goes on (imports, device start): the host's first touch of 5.9 GB,
    not the copy from the device, is what generation waited for. Returns
    (buffer, wait)."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor
    buf = np.empty(shape, np.float32)
    pool = ThreadPoolExecutor(PREFAULT_THREADS)
    step = -(-shape[0] // (4 * PREFAULT_THREADS))
    jobs = [pool.submit(buf[lo:lo + step].fill, 0.0)
            for lo in range(0, shape[0], step)]

    def wait():
        for j in jobs:
            j.result()
        pool.shutdown()
    return buf, wait


def dataset_fields(gen, key, cfg, n_rows, first_block=0, rows=None):
    """The generator's ``lgb.Dataset`` keyword arguments for the rows
    ``to_host`` makes with the same arguments; {} where it defines none.
    A key outside ``DATASET_FIELDS``, or query groups that do not cover the
    rows, is an error before anything is constructed."""
    make = getattr(gen, "dataset_fields", None)
    if make is None:
        return {}
    fields = dict(make(key, cfg, n_rows, first_block,
                       rows=rows or gen.BLOCK_ROWS))
    unknown = sorted(set(fields) - set(DATASET_FIELDS))
    if unknown:
        raise ValueError(f"{gen.__name__}.dataset_fields gave {unknown}; "
                         f"lgb.Dataset takes {list(DATASET_FIELDS)}")
    if "group" in fields and int(sum(fields["group"])) != n_rows:
        raise ValueError(f"{gen.__name__}.dataset_fields: query groups sum "
                         f"to {int(sum(fields['group']))} rows, not {n_rows}")
    return fields


def train_params(cfg, traffic, trace, extra=None):
    """The configuration's ``params`` whole, plus what the traffic adds."""
    p = dict(cfg["params"])
    p["verbosity"] = -1
    if traffic.get("metric"):
        p["metric"] = traffic["metric"]
    if trace:
        p["telemetry"] = True     # the package's events, for the readers
    p.update(extra or {})
    return p


class Window:
    """train() callback: ends every iteration on ``block_until_ready``, opens
    the window after the last warm-up iteration and closes it at the end of
    the first iteration that ends at or after ``seconds``. Set-up ends on a
    full ``gc.collect()``: the garbage of imports, generation and ingest is
    set-up's to clear, and a generation-2 pass over it (64 ms) that falls
    into a 10 s window is 0.6% of it (PERF.md section 6)."""
    order = 1000                  # after record_evaluation and early stopping

    def __init__(self, jax, stop_exc, warmup, seconds, counters, trace_dir):
        self.jax, self.stop_exc = jax, stop_exc
        self.warmup, self.seconds = int(warmup), float(seconds)
        self.counters, self.trace_dir = counters, trace_dir
        self.iter_end = []
        self.t_open = self.t_close = None
        self.loads_at_open = 0

    def __call__(self, env):
        self.jax.block_until_ready(env.model._gbdt.train_score)
        now = time.perf_counter()
        self.iter_end.append(now)
        k = len(self.iter_end)
        if k == 1:
            self.first_iter_end = now
        if k == self.warmup:
            gc.collect()
            if self.trace_dir:
                self.jax.profiler.start_trace(self.trace_dir)
            self.loads_at_open = self.counters["programs_loaded"]
            self.t_open = self.iter_end[-1] = time.perf_counter()
        elif k > self.warmup and now - self.t_open >= self.seconds:
            self.t_close = now
            self.loads_in_window = (self.counters["programs_loaded"]
                                    - self.loads_at_open)
            if self.trace_dir:
                self.jax.profiler.stop_trace()
            raise self.stop_exc(env.iteration, None)

    @property
    def window_iters(self):
        return len(self.iter_end) - self.warmup


def run_cell(cell, seed, seconds, trace, t0, rehearse=None, spans=None,
             train_buffer=None):
    """Runs the cell and returns the result dict ``emit`` prints. ``spans``
    holds what the caller timed before (imports, device start) and
    ``train_buffer`` a ``prefault`` of the training matrix started then.
    ``rehearse`` (CPU only): {"train_rows", "valid_rows", "params"} overrides;
    the result is then no measurement and the caller must not print it as one.
    """
    import jax
    from benchmark import peaks, work
    spans, counters = dict(spans or {}), {"programs_loaded": 0}

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            counters["programs_loaded"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    cfg, traffic = cell["cfg"], cell["traffic"]
    data = importlib.import_module("benchmark." + cfg["generator"])
    check = importlib.import_module("benchmark." + cfg["check"])
    rehearse = rehearse or {}
    n_train = int(rehearse.get("train_rows", cfg["train_rows"]))
    n_valid = int(rehearse.get("valid_rows", traffic["valid_rows"]))
    block_rows = int(rehearse.get("block_rows", data.BLOCK_ROWS))
    devs = jax.devices()[: cell["chips"]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices())}

    # the package first: importing it places the persistent compile cache,
    # which then also holds the harness's own programs
    t = time.perf_counter()
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    spans["harness.import_pkg_s"] = time.perf_counter() - t
    t = time.perf_counter()
    key = data.seed_key(seed)
    jax.block_until_ready(key)
    spans["harness.first_array_s"] = time.perf_counter() - t
    # the TPU runtime's own start inside jax.devices() is no code of this
    # repo and drifts by seconds on one machine: timed apart, in no sum
    runtime_start = spans.get("harness.device_start_s", 0.0)
    spans["harness.import_init_s"] = time.perf_counter() - t0 - runtime_start

    t = time.perf_counter()
    out = None
    if train_buffer:
        out, wait = train_buffer
        wait()
        spans["harness.prefault_wait_s"] = time.perf_counter() - t
    X, y = data.to_host(key, cfg, n_train, rows=block_rows, out=out)
    del out, train_buffer
    fields = dataset_fields(data, key, cfg, n_train, rows=block_rows)
    Xv = yv = None
    vfields = {}
    if n_valid:
        Xv, yv = data.to_host(key, cfg, n_valid, data.VALID_STREAM,
                              rows=block_rows)
        # the validation set takes its categorical columns from its reference
        vfields = {k: v for k, v in dataset_fields(
            data, key, cfg, n_valid, data.VALID_STREAM,
            rows=block_rows).items() if k != "categorical_feature"}
    spans["harness.gen_s"] = time.perf_counter() - t

    if trace:
        obs.configure(enabled=True)
    params = train_params(cfg, traffic, trace, rehearse.get("params"))
    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params, **fields).construct()
    spans["ingest.construct_s"] = time.perf_counter() - t
    valid = ds.create_valid(Xv, label=yv, **vfields) if n_valid else None
    del X, y, Xv, yv, fields, vfields     # the raw data is not kept
    gc.collect()

    trace_dir = None
    if trace:
        trace_dir = os.path.join(STATE, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = Window(jax, lgb.EarlyStopException, traffic["warmup_iters"],
                 seconds, counters, trace_dir)
    evals = {}
    train_enter = time.perf_counter()
    bst = lgb.train(
        params, ds, num_boost_round=1_000_000,
        valid_sets=[valid] if valid is not None else None,
        evals_result=evals if valid is not None else None,
        early_stopping_rounds=traffic.get("early_stopping_rounds") or None,
        verbose_eval=False, callbacks=[win])
    if win.t_close is None:
        raise RuntimeError("training ended before the window closed")
    window_s = win.t_close - win.t_open
    spans["prewarm.first_iter_s"] = win.first_iter_end - train_enter
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devs)
    device["memory_peak_bytes"] = memory_peak

    # what the check needs of the program, then the program's state goes
    produced = {
        "model_text": bst.model_to_string(),
        "bounds": [[float(b) for b in m.upper_bounds] for m in ds.mappers],
        "feature_map": (None if ds.feature_map is None
                        else [int(i) for i in ds.feature_map]),
        "auc": list(evals.get("valid_0", {}).get("auc", [])),
        "valid_metrics": {k: [float(v) for v in vs] for k, vs in
                          evals.get("valid_0", {}).items()},
        "iterations": len(win.iter_end),
    }
    ctx = types.SimpleNamespace(
        cell=cell, spans=spans, counters=counters, window=win,
        window_s=window_s, n_train=n_train, n_valid=n_valid,
        construct_phases=dict(ds.construct_phases),
        obs_events=obs.EVENTS.snapshot() if trace else [],
        memory_peak_bytes=memory_peak, device=device, trace=None,
        work=work, peaks=None if rehearse else peaks.peaks_for(device["kind"]),
        channels=int(cfg["grad_channels"]))
    del bst, ds, valid, evals
    gc.collect()

    metrics = {}
    breakdown = None
    if trace:
        from benchmark import trace as tr
        ctx.trace = tr.TraceView.from_dir(trace_dir, win.window_iters,
                                          window_s)
        # the raw trace stays until the next traced run, for selfcheck --record
        with open(os.path.join(trace_dir, "window.json"), "w") as fh:
            json.dump({"workload": cell["name"], "n_iters": win.window_iters,
                       "window_s": window_s}, fh)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        for m in cell["per_layer"]:
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"train_iters_per_s": win.window_iters / window_s,
                  "setup_s": win.t_open - t0 - runtime_start}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    t = time.perf_counter()
    checks = check.check_cell(cell, seed, produced, n_train, n_valid,
                              block_rows=block_rows)
    spans["check_s"] = time.perf_counter() - t
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": win.window_iters, "failed": 0,
        "metrics": metrics, "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["spans"] = {k: round(v, 4) for k, v in spans.items()}
    ends = [train_enter, win.first_iter_end] + win.iter_end[1:]
    result["spans"]["iter_s"] = [round(b - a, 4)
                                 for a, b in zip(ends, ends[1:])][:8]
    result["checks"] = checks
    return result


def read_metric(name, ctx):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def emit(result):
    """The numbers compared beside their limits last on standard error, the
    result last on standard output."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
