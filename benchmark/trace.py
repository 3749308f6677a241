"""From a profiler trace to the numbers the per-layer readers take.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into a compact
dict (the form the fixture under ``fixtures/`` is kept in):

    {"chips": [{"modules": [[name, start_ns, dur_ns], ...],    XLA Modules
                "ops":     [[name, start_ns, dur_ns], ...]}],  XLA Ops
     "host":  [[name, start_ns, dur_ns], ...]}                 spans >= 20 us

``TraceView`` reduces that: device busy time is the union of the op
intervals (averaged over chips), a module's time the sum of its events on
the ``XLA Modules`` line, a kernel's time the sum of its op events inside
the modules named. Idle gaps are attributed to the host span that overlaps
each most. ``python3 -m benchmark.selfcheck`` holds this to the fixture.
"""
import glob
import gzip
import json
import os
import re

HOST_MIN_NS = 20_000
GAP_MIN_NS = 5_000
STEP_MODULE = "jit_step"
MOSAIC = "custom-call"
CONTAINERS = ("%while", "%conditional", "%call")


def load(trace_dir):
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    chips, host = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            chip = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    chip[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                 for e in line.events]
            chips.append(chip)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.duration_ns >= HOST_MIN_NS)
    return {"chips": chips, "host": host}


def save(compact, path):
    with gzip.open(path, "wt") as fh:
        json.dump(compact, fh)


def load_compact(path):
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def short(name, n=64):
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", name)[:n]


class TraceView:
    def __init__(self, compact, n_iters, window_s):
        self.c, self.n_iters, self.window_s = compact, n_iters, window_s
        if not compact["chips"] or not any(c["ops"] for c in compact["chips"]):
            raise ValueError("the trace holds no device operation")
        self._busy = [_union([s, s + d] for _, s, d in chip["ops"])
                      for chip in compact["chips"]]

    @classmethod
    def from_dir(cls, trace_dir, n_iters, window_s):
        return cls(load(trace_dir), n_iters, window_s)

    @property
    def busy_s(self):
        return sum(sum(e - s for s, e in u) for u in self._busy) \
            / len(self._busy) / 1e9

    def module_s(self, *prefixes):
        """Seconds of the modules whose name starts with one of prefixes,
        averaged over chips; None where none ran."""
        tot, hit = 0, False
        for chip in self.c["chips"]:
            for name, _, d in chip["modules"]:
                if name.startswith(prefixes):
                    tot, hit = tot + d, True
        return tot / len(self.c["chips"]) / 1e9 if hit else None

    def op_s(self, substr, *module_prefixes):
        """Seconds of the ops whose name holds substr and that ran inside a
        module named by module_prefixes; None where none ran."""
        tot, hit = 0, False
        for chip in self.c["chips"]:
            spans = [(s, s + d) for name, s, d in chip["modules"]
                     if name.startswith(module_prefixes)]
            spans.sort()
            i = 0
            for name, s, d in sorted(chip["ops"], key=lambda o: o[1]):
                if substr not in name:
                    continue
                while i < len(spans) and spans[i][1] <= s:
                    i += 1
                if i < len(spans) and spans[i][0] <= s:
                    tot, hit = tot + d, True
        return tot / len(self.c["chips"]) / 1e9 if hit else None

    def per_iter_ms(self, seconds):
        return None if seconds is None else seconds * 1e3 / self.n_iters

    def breakdown(self):
        ops = {}
        for chip in self.c["chips"]:
            for name, _, d in chip["ops"]:
                if name.startswith(CONTAINERS):
                    continue          # its body's ops are listed themselves
                k = short(name)
                ops[k] = ops.get(k, 0) + d
        gaps = {}
        host = sorted(self.c["host"], key=lambda h: h[1])
        for u in self._busy:
            for (_, e0), (s1, _) in zip(u, u[1:]):
                if s1 - e0 < GAP_MIN_NS:
                    continue
                best, best_ov, best_d = "none", 0, 0
                for name, s, d in host:
                    if s >= s1:
                        break
                    ov = min(s + d, s1) - max(s, e0)
                    if ov > best_ov or (ov == best_ov and ov > 0
                                        and d < best_d):
                        best, best_ov, best_d = name, ov, d
                k = short(best)
                gaps[k] = gaps.get(k, 0) + (s1 - e0)

        def top(d):
            return [[k, v / 1e9] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(ops), "idle_gaps": top(gaps)}
