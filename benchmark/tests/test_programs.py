"""The readers of PR 36 against planted events: ``program_load`` with its
program, thread and cache outcome, the prewarm thread's spans,
``step_memory`` and ``ingest_chunk``. Each gives its number on what the
package says since PR 36, and nothing where the run says nothing of the kind
(telemetry off; the parent's package for the fields it has not)."""
import json
import types

import pytest

from benchmark import harness, programs

T = 1_000.0


def load(ts, program, thread, span, cache, dur, trace=None, lower=None,
         iteration=None):
    e = {"type": "program_load", "ts": T + ts, "span": span,
         "duration_s": dur, "program": program, "thread": thread,
         "cache": cache}
    if trace is not None:
        e.update(trace_s=trace, lower_s=lower)
    if iteration is not None:
        e["iteration"] = iteration
    return e


def span(ts, name, dur, thread="MainThread", parent=None):
    e = {"type": "span", "ts": T + ts, "start_ts": T + ts - dur, "name": name,
         "duration_s": dur, "thread": thread}
    if parent:
        e["parent"] = parent
    return e


def chunk(ts, i, enc, h2d, com, shard=None):
    e = {"type": "ingest_chunk", "ts": T + ts, "chunk": i, "rows": 100,
         "encode_s": enc, "h2d_s": h2d, "commit_s": com, "depth": 1,
         "bytes": 2800, "thread": "ingest-commit"}
    if shard is not None:
        e["shard"] = shard
    return e


def train_iter(ts, i):
    return {"type": "train_iter", "ts": T + ts, "iteration": i,
            "duration_s": 1.0, "rows_per_s": 1.0}


# warm-up iterations 1 and 2; the window is iterations 3 and 4
EVENTS = [
    load(1.0, "_lambda_", "ingest-commit", "none", "hit", 0.25, 0.0, 0.25),
    chunk(1.5, 0, 0.5, 0.125, 0.25),
    chunk(2.0, 1, 0.5, 0.125, 0.5),
    span(2.5, "prewarm_trainer", 1.0, "aot-prewarm", "prewarm_worker"),
    span(2.5, "dataset_construct", 2.5),
    span(2.75, "prewarm_build", 0.125, "aot-prewarm", "prewarm_worker"),
    span(3.5, "prewarm_lower", 0.75, "aot-prewarm", "prewarm_worker"),
    load(5.5, "step", "aot-prewarm", "prewarm_compile", "miss", 2.0, 0.5,
         0.25),
    span(5.5, "prewarm_compile", 2.0, "aot-prewarm", "prewarm_worker"),
    {"type": "step_memory", "ts": T + 5.5, "what": "fused_step_aot",
     "argument_bytes": 3_000_000_000, "output_bytes": 210_000_000,
     "temp_bytes": 700_000_000, "alias_bytes": 210_000_000,
     "generated_code_bytes": 1_000_000, "devices": 1},
    span(5.5, "prewarm_worker", 4.0, "aot-prewarm", "dataset_construct"),
    chunk(5.75, 0, 8.0, 8.0, 8.0),           # the validation set's stream
    span(6.0, "train_setup", 0.5),
    load(6.5, "route_bins_valid_score", "MainThread", "valid_score", "hit",
         0.5, 0.125, 0.125, iteration=1),
    load(6.75, "argsort", "MainThread", "metric", "off", 0.125, 0.0, 0.125,
         iteration=1),
    train_iter(7.0, 1),
    load(7.5, "subtract", "MainThread", "valid_score", "miss", 0.0625, 0.0,
         0.0625, iteration=2),
    train_iter(8.0, 2),
    load(8.9, "late", "MainThread", "valid_score", "miss", 0.25, 0.0625,
         0.0625, iteration=3),
    train_iter(9.0, 3),
    train_iter(10.0, 4),
    load(10.5, "after", "MainThread", "none", "miss", 0.375),
    span(10.5, "finalize", 0.25),
]
# the package before PR 36: the same run, the new fields and events missing
OLD_FIELDS = {"program", "thread", "cache", "trace_s", "lower_s", "start_ts",
              "parent", "bytes", "shard", "retrieval_s", "saved_s"}
PARENT = [{k: v for k, v in e.items() if k not in OLD_FIELDS} for e in EVENTS
          if e["type"] != "step_memory"
          and not e.get("name", "").startswith("prewarm_")]

WANT = {
    "programs.loaded_in_setup": (5, 5),
    "programs.cache_misses_in_setup": (2, None),
    "programs.load_s_in_setup": (0.25 + 2.0 + 0.5 + 0.125 + 0.0625,) * 2,
    "programs.trace_lower_s_in_setup": (0.25 + 0.75 + 0.25 + 0.125 + 0.0625,
                                        None),
    "programs.main_thread_s_in_setup": (0.75 + 0.25 + 0.125, None),
    "programs.first_iter_load_s": (0.75 + 0.25, None),
    "prewarm.worker_s": (4.0, None),
    "prewarm.trainer_s": (1.0, None),
    "prewarm.lower_s": (0.75, None),
    "step.temp_hbm_gb": (0.7, None),
    "step.args_hbm_gb": (3.0, None),
    "ingest.encode_thread_s": (1.0, 1.0),
    "ingest.h2d_s": (0.25, 0.25),
    "ingest.commit_s": (0.75, 0.75),
}


def _ctx(events, **more):
    return types.SimpleNamespace(
        obs_events=events, cell={"name": "a-cell"},
        window=types.SimpleNamespace(warmup=2, window_iters=2), **more)


def test_every_new_metric_of_the_benchmark_has_its_case():
    bench = harness._json(harness.os.path.join(harness.ROOT,
                                               "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert sorted(set(WANT) - set(by_name)) == []
    assert all("workloads" not in by_name[name] for name in WANT)


@pytest.mark.parametrize("name", list(WANT))
def test_reader_on_the_packages_events(name):
    assert harness.read_metric(name, _ctx(EVENTS)) == pytest.approx(
        WANT[name][0])


@pytest.mark.parametrize("name", list(WANT))
def test_reader_on_the_parents_events(name):
    got = harness.read_metric(name, _ctx(PARENT))
    want = WANT[name][1]
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name", list(WANT))
def test_reader_with_telemetry_off(name):
    assert harness.read_metric(name, _ctx([])) is None


def test_setup_ends_where_the_window_opens_whatever_the_iteration_says():
    view = programs.SetupView(EVENTS, warmup=2)
    names = [e["program"] for e in view.loads()]
    assert names == ["_lambda_", "step", "route_bins_valid_score", "argsort",
                     "subtract"]
    # no train_iter event to cut at: the iteration rule alone
    no_iters = [e for e in EVENTS if e["type"] != "train_iter"]
    assert [e["program"] for e in programs.SetupView(no_iters, 2).loads()
            ] == names + ["after"]


def test_a_traced_run_keeps_its_events_and_the_table_reads_them(
        tmp_path, monkeypatch, capsys):
    kept = tmp_path / "state" / "programs.json"
    monkeypatch.setattr(programs, "KEPT_PATH", str(kept))
    programs.of(_ctx(EVENTS))                   # not traced: nothing kept
    assert not kept.exists()
    ctx = _ctx(EVENTS, trace=object())
    assert programs.of(ctx) is programs.of(ctx)
    held = json.loads(kept.read_text())
    assert held["workload"] == "a-cell" and held["warmup"] == 2
    assert {e["type"] for e in held["events"]} == set(programs.KEPT_TYPES)
    assert programs.main([]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("# a-cell:") and out[1].split() == list(
        programs.COLUMNS)
    body = out[2:]
    loads = [ln.split() for ln in body if ln.split()[1] == "load"]
    assert [ln[4] for ln in loads][:3] == ["_lambda_", "step",
                                           "route_bins_valid_score"]
    step = next(ln for ln in loads if ln[4] == "step")
    assert step[2:4] == ["aot-prewarm", "prewarm_compile"]
    assert step[5:9] == ["miss", "0.500", "0.250", "2.000"]
    assert sum("ingest" == ln.split()[1] for ln in body) == 2
    assert any("2 chunks: encode 1.00 h2d 0.25 commit 0.75 s" in ln
               for ln in body)
    assert any("temp 0.700 args 3.000" in ln for ln in body)
    # rows are in the order things started
    at = [float(ln.split()[0]) for ln in body]
    assert at == sorted(at) and at[0] == 0.0
    assert programs.main(["--by-seconds"]) == 0
    by_s = capsys.readouterr().out.splitlines()[2:]
    assert [ln.split()[4] for ln in by_s][:2] == ["step",
                                                  "route_bins_valid_score"]
    assert all(ln.split()[1] == "load" for ln in by_s)
    # the parent's events make a table too
    kept.write_text(json.dumps({"workload": "a-cell", "warmup": 2,
                                "events": [e for e in PARENT if e["type"]
                                           in programs.KEPT_TYPES]}))
    assert programs.main([]) == 0
