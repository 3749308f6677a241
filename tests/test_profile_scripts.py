"""Smoke the profiling harnesses' ``--json`` surface: each script must run
on the CPU backend at a tiny workload and emit one parseable JSON line with
the fields the perf tooling consumes — including profile_level's
shallow-level launch accounting (levels 0..D in exactly two pallas launches,
megapass bit-identical to the sequential level passes in interpret mode) —
and must NOT report a time there: off the chip every ``ms`` is null."""
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_json(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", script), "--json",
         *args],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_profile_fused_json():
    doc = _run_json("profile_fused.py", "--rows", "512", "--widths", "1", "8")
    assert doc["backend"] == "cpu"
    assert doc["master_slot_widths"] == [32, 128, 512]
    widths = [e["slot_width"] for e in doc["fused_level_pass"]]
    assert widths == [1, 8]
    assert all(e["ms"] is None for e in doc["fused_level_pass"])
    # channel accounting: plain q8 accumulates 3 channels, and the analytic
    # MAC count scales with them (N * F * B * S * nch)
    assert doc["channels"] == 3 and doc["packed"] is False
    e = doc["fused_level_pass"][1]
    assert e["channels"] == 3 and e["macs"] == 512 * 28 * 64 * 8 * 3


@pytest.mark.slow
def test_profile_fused_json_packed_const_hess():
    """--const-hess --packed at 512 rows fits the guard budget (k=10) and
    drops the level pass to ONE accumulated channel."""
    doc = _run_json("profile_fused.py", "--rows", "512", "--widths", "8",
                    "--const-hess", "--packed")
    assert doc["channels"] == 1 and doc["packed"] is True
    assert doc["pack_guard_bits"] == 10
    e = doc["fused_level_pass"][0]
    assert e["channels"] == 1 and e["packed"] is True
    assert e["macs"] == 512 * 28 * 64 * 8 * 1


@pytest.mark.slow
def test_profile_level_json_shallow_two_launches():
    doc = _run_json("profile_level.py", "--rows", "512", "--leaves", "31",
                    "--features", "4", "--max-bin", "16")
    assert set(doc["phases_ms"]) == {"level_complete", "hist_routed",
                                     "bookkeeping", "grow_tree_depthwise"}
    assert all(v is None for v in doc["phases_ms"].values())
    shallow = doc["shallow"]
    assert shallow["megapass_ms"] is None
    # the headline: levels 0..5 of one tree in exactly TWO pallas launches
    # (grad+quant+hist0 front + one multi-level replay megapass), and the
    # megapass must be bit-identical to running the levels one by one
    assert shallow["pallas_launches"] == 2
    assert len(shallow["launch_breakdown"]) == 2
    assert shallow["bit_identical_vs_sequential"] is True
    assert shallow["levels"] == [0, 1, 2, 3, 4, 5]
    assert doc["channels"] == 3 and doc["packed"] is False
    assert shallow["macs_per_level"] == 512 * 4 * 16 * shallow["slot_width"] * 3


@pytest.mark.slow
def test_profile_level_json_packed_reduces_channels():
    """The acceptance headline: profile_level --json reports the REDUCED
    channel count when const-hess elision + packing are active, and the
    packed megapass stays bit-identical to the sequential passes."""
    doc = _run_json("profile_level.py", "--rows", "512", "--leaves", "31",
                    "--features", "4", "--max-bin", "16",
                    "--const-hess", "--packed")
    shallow = doc["shallow"]
    assert doc["channels"] == 1 and doc["packed"] is True
    assert shallow["pack_guard_bits"] == 10
    assert shallow["bit_identical_vs_sequential"] is True
    assert shallow["macs_per_level"] == 512 * 4 * 16 * shallow["slot_width"] * 1
