"""``valid_score.walk_steps_per_iter`` against planted ``valid_walk`` events:
it reads the window's iterations only, and nothing from a program that emits
no such event."""
import types

import pytest

from benchmark import harness

# warm-up iterations 1 and 2, window iterations 3 to 5
WALKS = [{"type": "valid_walk", "steps": s, "iteration": i, "valid_set": 0}
         for i, s in [(1, 8), (2, 8), (3, 8), (4, 9), (5, 10)]]
OTHERS = [{"type": "program_load", "span": "valid_score", "iteration": 3,
           "duration_s": 0.03}]


@pytest.mark.parametrize("events,want", [
    (WALKS + OTHERS, 9.0),
    (WALKS[:4] + OTHERS, 17 / 3),     # an event short: the reading shows it
    (OTHERS, None),                   # the parent: a fixed trip count
    ([], None),                       # telemetry off
])
def test_walk_steps_per_iter(events, want):
    ctx = types.SimpleNamespace(
        obs_events=events,
        window=types.SimpleNamespace(warmup=2, window_iters=3))
    got = harness.read_metric("valid_score.walk_steps_per_iter", ctx)
    assert got == want
