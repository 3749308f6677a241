"""Holds the trace reduction to a recorded trace.

``fixtures/trace_train_bare.json.gz`` is a few iterations of
``higgs-binary.train`` on the v5e in ``trace.load``'s compact form, and
``fixtures/trace_train_bare.expected.json`` what the reduction read from it
when it was recorded. A reduction that reads anything else has changed.

    python3 -m benchmark.selfcheck

``--record`` rewrites the numbers; where the last traced run's raw trace is
still there (``.bench_state/trace``: ``python3 -m benchmark.run --workload
higgs-binary.train --seed 1 --seconds 3 --trace 1`` leaves a small one) it
first records the fixture anew from it.
"""
import json
import os
import sys

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "trace_train_bare.json.gz")
EXPECTED = os.path.join(HERE, "fixtures", "trace_train_bare.expected.json")


def reduce_fixture():
    meta = trace.load_compact(FIXTURE)
    view = trace.TraceView(meta, meta["n_iters"], meta["window_s"])
    b = view.breakdown()
    return {
        "busy_s": view.busy_s,
        "step_s": view.module_s(trace.STEP_MODULE),
        "mosaic_s": view.op_s(trace.MOSAIC, trace.STEP_MODULE),
        "absent_module_s": view.module_s("jit_no_such_module"),
        "top_op": b["device_ops"][0],
        "idle_gaps": b["idle_gaps"][:3],
    }


def record_fixture(trace_dir):
    with open(os.path.join(trace_dir, "window.json")) as fh:
        window = json.load(fh)
    compact = trace.load(trace_dir)
    compact.update(n_iters=window["n_iters"], window_s=window["window_s"],
                   recorded=window["workload"])
    trace.save(compact, FIXTURE)


def main(argv=None):
    record = "--record" in (argv or sys.argv[1:])
    raw = os.path.join(os.path.dirname(HERE), ".bench_state", "trace")
    if record and os.path.isdir(raw):
        record_fixture(raw)
    got = reduce_fixture()
    if record:
        with open(EXPECTED, "w") as fh:
            json.dump(got, fh, indent=1)
        print("recorded", json.dumps(got))
        return 0
    with open(EXPECTED) as fh:
        want = json.load(fh)
    bad = {k: (got.get(k), want[k]) for k in want if got.get(k) != want[k]}
    for k, (g, w) in bad.items():
        print(f"selfcheck: {k}: reduction reads {g!r}, recorded {w!r}",
              file=sys.stderr)
    print("selfcheck", "FAILED" if bad else "ok", json.dumps(got))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
