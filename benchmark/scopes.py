"""Device time by the program's own scopes, and idle gaps by self time.

The program names what it runs (PR 25): every ``pl.pallas_call`` has a
``name=``, and ``jax.named_scope`` marks the stages of one tree (``front``,
``level_s<W>`` with ``split_search`` / ``apply_level`` / ``route_hist`` inside,
``leaf_renew``, ``score_update``) and validation scoring (``valid_score``).
On jax 0.9.0 / libtpu 0.0.34 the two land in different places of the
``.xplane.pb``: a kernel's name is the name of its HLO instruction, so it
starts the event's name on the ``XLA Ops`` line (``%hist_level_q8.3 = ...``);
a scope is a component of the instruction's ``op_name``
(``jit(step)/.../level_s32/while/body/route_hist/...``), which the profiler
keeps in a stat of the event's metadata (``tf_op``), not in its name.
``benchmark/trace.py`` keeps names only, so this module reads the raw trace
that the harness leaves under ``.bench_state/trace`` once more and keeps,
beside ``trace.load``'s compact form, each op's path:

    {"chips": [{"modules": [...], "ops": [...],      as trace.load
                "op_paths": [path id per op]}],
     "paths": [op_name, ...], "host": [...]}

``ScopeView`` reduces that. A scope's device time is the union of the
intervals of the ops whose path holds the scope as one of its components: a
``while`` under a scope covers the ops of its body and the gaps between them
once. Idle gaps are split among the innermost of the package's host spans
open at each instant (self time), where ``trace.TraceView.breakdown`` gives
each gap whole to the span that overlaps it most.

This is a second reader of the file ``trace.load`` has already read (a
tracing PR may not edit ``trace.py``): every traced run parses its trace
twice. The ``benchmark`` issue that retires the duplicate metrics folds
``op_paths`` into ``trace.load``'s compact form, so that one parser and one
``TraceView`` are left.

    python3 -m benchmark.scopes            what the last traced run holds
    python3 -m benchmark.scopes --record   and the fixture anew from it
"""
import glob
import json
import os
import re
import sys

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RAW = os.path.join(os.path.dirname(HERE), ".bench_state", "trace")
FIXTURE = os.path.join(HERE, "fixtures", "scopes_train_valid.json.gz")
EXPECTED = os.path.join(HERE, "fixtures", "scopes_train_valid.expected.json")

FIXTURE_NAME_CHARS = 96
# the stat of an XLA Ops event's metadata that holds the HLO op_name
PATH_STAT = "tf_op"
# scopes that stand directly in the step: they tile it, what they leave is
# the step's own (step.self_ms_per_iter)
STEP_SCOPES = ("front", "leaf_renew", "score_update")
LEVEL = re.compile(r"level_s(\d+)")
# the package's host spans (docs/OBSERVABILITY.md): a gap under a leaf has a
# name, a gap under a parent alone (or under none) has not
PARENT_SPANS = ("train_iter", "boosting", "eval")
LEAF_SPANS = ("step_dispatch", "prewarm_adopt", "finished_check",
              "valid_score", "metric", "callbacks", "callbacks_before",
              "snapshot")
UNNAMED = "unnamed"


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes for a length-delimited field; fixed-width fields are skipped.
    The installation has no xplane_pb2 that can be imported without
    TensorFlow, and ``jax.profiler.ProfileData`` hands out an event's own
    stats, not its metadata's, which is where the op_name is."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield no, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield no, buf[i:i + size]
            i += size
        else:
            i += {1: 8, 5: 4}[wire]


def _message(buf, *repeated):
    """One message as {field: value}, the fields named repeated as lists."""
    out = {no: [] for no in repeated}
    for no, val in _fields(buf):
        if no in out and isinstance(out[no], list):
            out[no].append(val)
        else:
            out[no] = val
    return out


def _text(b):
    return bytes(b).decode("utf-8", "replace")


def _plane(buf):
    """An XPlane (tsl/profiler/protobuf/xplane.proto): its name, its lines
    as (name, [(metadata id, start_ns, duration_ns)]) and, per event
    metadata id, the event's name and its metadata's string stats."""
    plane = _message(buf, 3, 4, 5)
    stat_names = {}
    for entry in plane[5]:                      # map<int64, XStatMetadata>
        meta = _message(_message(entry).get(2, b""))
        stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
    events = {}
    for entry in plane[4]:                      # map<int64, XEventMetadata>
        meta = _message(_message(entry).get(2, b""), 5)
        stats = {}
        for raw in meta[5]:                     # XStat
            st = _message(raw)
            if 5 in st:                         # str_value
                stats[stat_names.get(st.get(1))] = _text(st[5])
            elif 7 in st:                       # ref_value: a stat's name
                stats[stat_names.get(st.get(1))] = stat_names.get(st[7], "")
        events[meta.get(1, 0)] = (_text(meta.get(2, b"")), stats)
    lines = []
    for raw in plane[3]:                        # XLine
        line = _message(raw, 4)
        t0 = line.get(3, 0)                     # timestamp_ns
        rows = []
        for ev in line[4]:                      # XEvent
            e = _message(ev)
            rows.append((e.get(1, 0), int(t0 + e.get(2, 0) / 1000),
                         int(e.get(3, 0) / 1000)))
        lines.append((_text(line.get(2, b"")), rows))
    return _text(plane.get(2, b"")), lines, events


def load(trace_dir):
    """The raw trace in ``trace.load``'s compact form plus the op paths."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(max(paths, key=os.path.getmtime), "rb") as fh:
        space = memoryview(fh.read())
    chips, host, ids = [], [], {}
    for no, raw in _fields(space):
        if no != 1:                             # XSpace.planes
            continue
        name, lines, events = _plane(raw)
        if re.fullmatch(r"/device:TPU:\d+", name):
            chip = {"modules": [], "ops": [], "op_paths": []}
            for line, rows in lines:
                if line == "XLA Modules":
                    chip["modules"] = [[events[m][0], s, d]
                                       for m, s, d in rows]
                elif line == "XLA Ops":
                    for m, s, d in rows:
                        ev_name, stats = events[m]
                        path = stats.get(PATH_STAT, "")
                        chip["ops"].append([ev_name, s, d])
                        chip["op_paths"].append(
                            ids.setdefault(path, len(ids)))
            chips.append(chip)
        elif name.startswith("/host:"):
            for _, rows in lines:
                host.extend([events[m][0], s, d] for m, s, d in rows
                            if d >= trace.HOST_MIN_NS)
    return {"chips": chips, "host": host, "paths": list(ids)}


def _stands_in_step(component):
    return component in STEP_SCOPES or bool(LEVEL.fullmatch(component))


def of(ctx):
    """The run's ScopeView, read once per run; None where the run was not
    traced."""
    if ctx.trace is None:
        return None
    view = getattr(ctx, "scope_view", None)
    if view is None:
        view = ctx.scope_view = ScopeView(load(RAW), ctx.trace.n_iters,
                                          ctx.trace.window_s)
    return view


def instruction(name):
    """``%hist_level_q8.3 = (...) custom-call(...)`` -> ``hist_level_q8``."""
    m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)", name)
    return m.group(1) if m else ""


class ScopeView(trace.TraceView):
    """A TraceView that also knows each op's path."""

    def __init__(self, compact, n_iters, window_s):
        super().__init__(compact, n_iters, window_s)
        # tf_op is "<op_name>:<op type>"
        self._parts = [p.rsplit(":", 1)[0].split("/")
                       for p in compact.get("paths", [])]
        # per chip, (start, dur, name, path id) in time order
        self._rows = [sorted((s, d, name, pid) for (name, s, d), pid in zip(
            chip["ops"], chip.get("op_paths") or [None] * len(chip["ops"])))
            for chip in compact["chips"]]

    def _ops(self, k, module_prefixes):
        """(name, start, dur, path components) of chip k's ops that ran
        inside a module named by module_prefixes (every op where None)."""
        chip, rows = self.c["chips"][k], self._rows[k]
        if module_prefixes is None:
            spans = None
        else:
            spans = sorted((s, s + d) for name, s, d in chip["modules"]
                           if name.startswith(module_prefixes))
        i = 0
        for s, d, name, pid in rows:
            if spans is not None:
                while i < len(spans) and spans[i][1] <= s:
                    i += 1
                if not (i < len(spans) and spans[i][0] <= s):
                    continue
            yield name, s, d, (self._parts[pid] if pid is not None else ())

    def scope_s(self, match, module_prefixes=(trace.STEP_MODULE,)):
        """Seconds of device time under the scopes ``match`` accepts (a
        component of the op's path -> bool), averaged over chips; None where
        no op is under one."""
        tot, hit = 0, False
        for k in range(len(self.c["chips"])):
            iv = [[s, s + d] for _, s, d, parts
                  in self._ops(k, module_prefixes)
                  if any(match(p) for p in parts)]
            if iv:
                hit = True
                tot += sum(e - s for s, e in trace._union(iv))
        return tot / len(self.c["chips"]) / 1e9 if hit else None

    def named_s(self, *scopes, module_prefixes=(trace.STEP_MODULE,)):
        return self.scope_s(lambda p: p in scopes, module_prefixes)

    def level_s(self, keep):
        """Device time under ``level_s<W>`` for the widths ``keep`` accepts."""
        def match(p):
            m = LEVEL.fullmatch(p)
            return bool(m) and keep(int(m.group(1)))
        return self.scope_s(match)

    def widths(self):
        return sorted({int(m.group(1)) for parts in self._parts
                       for p in parts for m in [LEVEL.fullmatch(p)] if m})

    def step_self_s(self):
        """The step module's time less what runs under a scope that stands
        directly in the step; None where the step holds no such scope."""
        step = self.module_s(trace.STEP_MODULE)
        scoped = self.scope_s(_stands_in_step)
        return None if step is None or scoped is None else step - scoped

    def kernel(self, kernel, module_prefixes=(trace.STEP_MODULE,)):
        """(events, seconds) of the Mosaic kernel of that name, averaged over
        chips; (0, None) where it did not run."""
        n = tot = 0
        chips = len(self.c["chips"])
        for k in range(chips):
            for name, _, d, _ in self._ops(k, module_prefixes):
                if instruction(name) == kernel:
                    n, tot = n + 1, tot + d
        return (n / chips, tot / chips / 1e9) if n else (0, None)

    def host_span_s(self, name):
        """Seconds the host spent in the package's span of that name."""
        ds = [d for n, _, d in self.c["host"] if n == name]
        return sum(ds) / 1e9 if ds else None

    def gap_split(self):
        """Idle seconds of the device between the start of the first whole
        iteration the trace holds and the device's last op, by the innermost
        of the package's spans open at each instant (a parent span, or none,
        counts as ``unnamed``). None where the trace holds none of them."""
        known = PARENT_SPANS + LEAF_SPANS
        spans = sorted((s, s + d, n) for n, s, d in self.c["host"]
                       if n in known)
        starts = [s for s, _, n in spans if n == "train_iter"]
        if not starts:
            return None
        out = {}
        for busy in self._busy:
            t0 = starts[0]
            edges = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
            lead = [b for b in busy if b[1] > t0]
            if lead and lead[0][0] > t0:
                edges.append((t0, lead[0][0]))
            for a, b in edges:
                a = max(a, t0)
                if b <= a:
                    continue
                over = [sp for sp in spans if sp[0] < b and sp[1] > a]
                cuts = sorted({a, b} | {t for s, e, _ in over for t in (s, e)
                                        if a < t < b})
                for lo, hi in zip(cuts, cuts[1:]):
                    open_ = [sp for sp in over if sp[0] <= lo and sp[1] >= hi]
                    name = (max(open_, key=lambda sp: (sp[0], -sp[1]))[2]
                            if open_ else UNNAMED)
                    if name in PARENT_SPANS:
                        name = UNNAMED
                    out[name] = out.get(name, 0) + (hi - lo)
        k = len(self.c["chips"])
        return {n: v / k / 1e9 for n, v in out.items()}

    def unscoped_ops(self, top=12):
        """What the step runs under no scope of STEP_SCOPES / level_s<W>:
        [instruction, seconds per iteration, events per iteration], the
        largest first; containers left out as in trace.breakdown."""
        acc = {}
        for k in range(len(self.c["chips"])):
            for name, _, d, parts in self._ops(k, (trace.STEP_MODULE,)):
                if name.startswith(trace.CONTAINERS) or any(
                        map(_stands_in_step, parts)):
                    continue
                k = instruction(name)
                t, n = acc.get(k, (0, 0))
                acc[k] = (t + d, n + 1)
        k = len(self.c["chips"]) * self.n_iters
        return [[n, t / k / 1e9, c / k] for n, (t, c) in
                sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]]


def report(view):
    """Every number the readers take, and the step's tiling, for PERF.md."""
    ms = view.per_iter_ms
    half = max(view.widths(), default=0)
    levels = {f"level_s{w}": ms(view.level_s(lambda x, w=w: x == w))
              for w in view.widths()}
    passes, _ = view.kernel("hist_level_q8")
    return {
        "n_iters": view.n_iters,
        "step_ms": ms(view.module_s(trace.STEP_MODULE)),
        "step_self_ms": ms(view.step_self_s()),
        "front_ms": ms(view.named_s("front")),
        "levels_ms": levels,
        "narrow_ms": ms(view.level_s(lambda w: w < half)),
        "full_ms": ms(view.level_s(lambda w: w == half)),
        "leaf_renew_ms": ms(view.named_s("leaf_renew")),
        "score_update_ms": ms(view.named_s("score_update")),
        "split_search_ms": ms(view.named_s("split_search")),
        "apply_level_ms": ms(view.named_s("apply_level")),
        "route_hist_ms": ms(view.named_s("route_hist")),
        "level_passes_per_iter": passes / view.n_iters,
        "front_kernel_ms": ms(view.kernel("grad_quant_hist0")[1]),
        "valid_score_scoped_ms": ms(view.named_s(
            "valid_score", module_prefixes=None)),
        "valid_score_host_ms": ms(view.host_span_s("valid_score")),
        "gaps_ms": {k: ms(v) for k, v in (view.gap_split() or {}).items()},
        "unscoped_ops_ms": [[n, t * 1e3, c] for n, t, c
                            in view.unscoped_ops()],
    }


def record_fixture(trace_dir):
    with open(os.path.join(trace_dir, "window.json")) as fh:
        window = json.load(fh)
    compact = load(trace_dir)
    for chip in compact["chips"]:       # the instruction and its first shape
        for op in chip["ops"]:
            op[0] = op[0][:FIXTURE_NAME_CHARS]
    compact.update(n_iters=window["n_iters"], window_s=window["window_s"],
                   recorded=window["workload"])
    trace.save(compact, FIXTURE)
    with open(EXPECTED, "w") as fh:
        json.dump(report(ScopeView(compact, compact["n_iters"],
                                   compact["window_s"])), fh, indent=1)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if "--record" in argv:
        record_fixture(RAW)
    with open(os.path.join(RAW, "window.json")) as fh:
        window = json.load(fh)
    view = ScopeView(load(RAW), window["n_iters"], window["window_s"])
    print(json.dumps(dict(report(view), workload=window["workload"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
