"""Set-up by the program's own events: which programs a run traced, built or
read from the compile cache, what its prewarm thread did, what the compiled
step holds on the device, which stage of the stream was busy.

The package says all of it with telemetry on (PR 36; docs/OBSERVABILITY.md):
``program_load`` {program, thread, span, cache, trace_s, lower_s, duration_s},
``span`` {name, start_ts, thread, parent}, ``step_memory`` and
``ingest_chunk``. ``ctx.obs_events`` holds the whole run's events in the order
they were written; *set-up* here is everything up to the ``train_iter`` event
of the last warm-up iteration (the window opens there), less what carries a
later ``iteration``. The readers under ``metrics/`` take their numbers from
``of(ctx)``; a traced run also leaves those four kinds of event under
``.bench_state/programs.json`` (as the harness leaves the trace), and

    python3 -m benchmark.programs [--by-seconds]

prints them as one table: spans at their start, loads in the order they
happened (or the largest first), by thread, span, program and cache.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
KEPT_PATH = os.path.join(os.path.dirname(HERE), ".bench_state",
                         "programs.json")
KEPT_TYPES = ("program_load", "span", "step_memory", "ingest_chunk")
MAIN_THREAD = "MainThread"


def load_seconds(e):
    """What one ``program_load`` cost its thread: tracing and lowering, which
    no cache saves, and the build or the cache read."""
    return e.get("trace_s", 0.0) + e.get("lower_s", 0.0) + e["duration_s"]


class SetupView:
    def __init__(self, events, warmup):
        self.setup = []
        for e in events:
            it = e.get("iteration")
            if it is None or it <= warmup:
                self.setup.append(e)
            if e.get("type") == "train_iter" and it == warmup:
                break

    def _of_type(self, etype):
        return [e for e in self.setup if e.get("type") == etype]

    def loads(self, named=False):
        """Set-up's ``program_load`` events; None where the run has none or,
        with ``named``, where they do not say which program, thread and
        cache outcome (the package before PR 36)."""
        loads = self._of_type("program_load")
        if not loads or (named and not any("cache" in e for e in loads)):
            return None
        return loads

    def span_s(self, name):
        for e in self._of_type("span"):
            if e.get("name") == name:
                return e.get("duration_s")
        return None

    def step_memory_gb(self, field):
        first = next(iter(self._of_type("step_memory")), None)
        return None if first is None else first[field] / 1e9

    def chunk_s(self, field):
        """Sum of one stage's seconds over the chunks of the training set's
        stream: the first ``dataset_construct`` to close ends it (a
        validation set streams after that, inside ``train_setup``)."""
        total, seen = 0.0, False
        for e in self.setup:
            if e.get("type") == "ingest_chunk" and field in e:
                total, seen = total + e[field], True
            elif (e.get("type") == "span"
                  and e.get("name") == "dataset_construct"):
                break
        return total if seen else None


def of(ctx):
    """The run's SetupView, made once per run."""
    view = getattr(ctx, "setup_view", None)
    if view is None:
        view = ctx.setup_view = SetupView(ctx.obs_events, ctx.window.warmup)
        if getattr(ctx, "trace", None) is not None:
            keep(ctx, KEPT_PATH)
    return view


def keep(ctx, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"workload": ctx.cell["name"], "warmup": ctx.window.warmup,
                   "events": [e for e in ctx.obs_events
                              if e.get("type") in KEPT_TYPES]}, fh)


COLUMNS = ("at_s", "kind", "thread", "span", "name", "cache", "trace_s",
           "lower_s", "duration_s", "iter")


def rows(kept):
    """The kept events as the table's rows: a span at its start under its
    parent, a load at the start of what it cost, the chunks of a stream as
    one row of thread-seconds by stage, ``step_memory`` in GB."""
    out, chunks = [], None
    for e in kept["events"]:
        row = {"at_s": e.get("start_ts", e["ts"]), "kind": e["type"],
               "thread": e.get("thread", ""), "iter": e.get("iteration", "")}
        if e["type"] == "program_load":
            row.update(at_s=e["ts"] - load_seconds(e), kind="load",
                       cost=load_seconds(e),
                       span=e["span"], name=e.get("program", ""),
                       cache=e.get("cache", ""), trace_s=e.get("trace_s", ""),
                       lower_s=e.get("lower_s", ""),
                       duration_s=e["duration_s"])
        elif e["type"] == "span":
            row.update(span=e.get("parent", ""), name=e["name"],
                       duration_s=e["duration_s"])
            if e["name"] == "dataset_construct":
                chunks = None           # the next chunk is another stream's
        elif e["type"] == "step_memory":
            row.update(name="temp %.3f args %.3f out %.3f alias %.3f GB x%d"
                       % (e["temp_bytes"] / 1e9, e["argument_bytes"] / 1e9,
                          e["output_bytes"] / 1e9, e["alias_bytes"] / 1e9,
                          e["devices"]))
        else:
            if chunks is None:
                chunks = dict(row, kind="ingest", n=0, enc=0.0, h2d=0.0,
                              com=0.0)
                out.append(chunks)
            chunks["n"] += 1
            chunks["enc"] += e.get("encode_s", 0.0)
            chunks["h2d"] += e.get("h2d_s", 0.0)
            chunks["com"] += e.get("commit_s", 0.0)
            chunks["name"] = ("%d chunks: encode %.2f h2d %.2f commit %.2f s"
                              % (chunks["n"], chunks["enc"], chunks["h2d"],
                                 chunks["com"]))
            continue
        out.append(row)
    t0 = min((r["at_s"] for r in out), default=0.0)
    for r in out:
        r["at_s"] -= t0
    return sorted(out, key=lambda r: r["at_s"])


def table(kept, by_seconds=False):
    body = rows(kept)
    if by_seconds:
        body = sorted((r for r in body if r["kind"] == "load"),
                      key=lambda r: -r["cost"])

    def cell(v):
        return "%.3f" % v if isinstance(v, float) else str(v)
    lines = [[cell(r.get(c, "")) for c in COLUMNS] for r in body]
    widths = [max(len(c), *(len(ln[i]) for ln in lines)) if lines else len(c)
              for i, c in enumerate(COLUMNS)]
    return "\n".join("  ".join(v.ljust(w) for v, w in zip(ln, widths)).rstrip()
                     for ln in [list(COLUMNS)] + lines)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    with open(KEPT_PATH) as fh:
        kept = json.load(fh)
    print(f"# {kept['workload']}: {len(kept['events'])} events, warm-up "
          f"{kept['warmup']} iteration(s)")
    print(table(kept, by_seconds="--by-seconds" in argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
