"""``valid_score.kernel_walks_per_iter`` against planted ``valid_walk``
events: it counts the window's walks whose ``path`` is ``"kernel"``, and reads
nothing from a program whose events carry no ``path``."""
import types

import pytest

from benchmark import harness

# warm-up iterations 1 and 2, window iterations 3 to 5


def _walks(paths):
    return [dict({"type": "valid_walk", "steps": 8, "iteration": i,
                  "valid_set": 0}, **({} if p is None else {"path": p}))
            for i, p in enumerate(paths, start=1)]


OTHERS = [{"type": "program_load", "span": "valid_score", "iteration": 3,
           "duration_s": 0.03}]


@pytest.mark.parametrize("events,want", [
    (_walks(["kernel"] * 5) + OTHERS, 1.0),
    (_walks(["xla"] * 5) + OTHERS, 0.0),           # engaged nowhere: it says so
    (_walks(["kernel", "kernel", "xla", "kernel", "kernel"]), 2 / 3),
    (_walks(["kernel", "kernel", "xla", "xla", "xla"]), 0.0),  # warm-up only
    (_walks(["kernel"] * 5) + _walks(["kernel"] * 5), 2.0),    # two sets
    (_walks([None] * 5) + OTHERS, None),           # the parent: no ``path``
    (OTHERS, None),
    ([], None),                                    # telemetry off
])
def test_kernel_walks_per_iter(events, want):
    ctx = types.SimpleNamespace(
        obs_events=events,
        window=types.SimpleNamespace(warmup=2, window_iters=3))
    got = harness.read_metric("valid_score.kernel_walks_per_iter", ctx)
    assert got == want
