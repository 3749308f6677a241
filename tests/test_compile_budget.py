"""compile-budget gate: the diff semantics (pure, no subprocess), the
budget-file roundtrip, the rule's failure modes, an in-process lowering-
counter canary proving a per-call jit moves the counters the probe reads,
and (slow) the real subprocess probe against the committed budget."""
import json
import os
import subprocess
import sys

import pytest

from lightgbm_tpu.analysis.rules import compile_budget as cb

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# diff_counts: the fixture trio, no jax involved

def test_diff_counts_clean_on_equal():
    assert cb.diff_counts({"a": 3, "b": 0}, {"a": 3, "b": 0}) == []


def test_diff_counts_growth_is_error():
    out = cb.diff_counts({"train": 17}, {"train": 16})
    assert len(out) == 1
    sev, msg = out[0]
    assert sev == "error"
    assert "regression" in msg and "+1" in msg


def test_diff_counts_shrinkage_is_warning_suggesting_update():
    out = cb.diff_counts({"train": 15}, {"train": 16})
    assert out[0][0] == "warning"
    assert "--update-budget" in out[0][1]


def test_diff_counts_drift_is_error_both_ways():
    missing_budget = cb.diff_counts({"new_entry": 2}, {})
    assert missing_budget[0][0] == "error"
    missing_measured = cb.diff_counts({}, {"gone_entry": 2})
    assert missing_measured[0][0] == "error"


def test_budget_file_roundtrip(tmp_path):
    path = str(tmp_path / "LOWERING_BUDGET.json")
    cb.write_budget({"train_3_iters": 16, "predict_warm_repeat": 0}, path)
    assert cb.load_budget(path) == {"train_3_iters": 16,
                                    "predict_warm_repeat": 0}
    doc = json.load(open(path))
    assert doc["version"] == 1 and "comment" in doc


def test_rule_missing_budget_is_error(monkeypatch, tmp_path):
    monkeypatch.setattr(cb, "BUDGET_PATH", str(tmp_path / "absent.json"))
    rule = cb.CompileBudget()
    findings = rule.run_dynamic()
    assert len(findings) == 1
    assert findings[0].severity == "error"
    assert "--update-budget" in findings[0].message


def test_rule_reports_diff_without_probe(monkeypatch):
    """Wire a fake measurement through the real rule path: regression and
    shrinkage come out with the right severities and the committed budget
    file is actually consulted."""
    committed = cb.load_budget()
    assert committed, "LOWERING_BUDGET.json must be committed and non-empty"
    assert committed.get("predict_warm_repeat") == 0, \
        "the warm-repeat canary must be budgeted at exactly 0 lowerings"
    bumped = dict(committed)
    bumped["predict_warm_repeat"] += 1          # a per-call jit appeared
    monkeypatch.setattr(cb, "measure", lambda **kw: bumped)
    findings = cb.CompileBudget().run_dynamic()
    assert [f.severity for f in findings] == ["error"]
    assert "predict_warm_repeat" in findings[0].message


PROBE_ENTRIES = {"dataset_construct", "train_3_iters", "predict_cold",
                 "predict_warm_repeat", "train_3_iters_lossguide",
                 "train_warm_extra2_dart", "train_warm_extra2_goss",
                 "train_warm_extra2_rf", "predict_engine_warm",
                 # forced-pallas quantised training (2-channel q8 kernels)
                 "train_3_iters_q8_2ch", "train_warm_extra2_q8_2ch",
                 # pod surface (the --multihost probe pass)
                 "train_3_iters_pod2d", "train_warm_extra2_pod2d",
                 "train_3_iters_voting", "train_warm_extra2_voting"}


def test_committed_budget_matches_probe_entry_names():
    committed = cb.load_budget()
    assert set(committed) == PROBE_ENTRIES


def test_warmed_entries_budgeted_at_zero():
    """The whole warmed surface — repeat predict, extra DART/GOSS/RF
    iterations, pre-warmed serving buckets — must stay at exactly 0
    lowerings; anything else is a per-call jit reaching a steady-state
    path."""
    committed = cb.load_budget()
    for name in ("predict_warm_repeat", "train_warm_extra2_dart",
                 "train_warm_extra2_goss", "train_warm_extra2_rf",
                 "predict_engine_warm", "train_warm_extra2_pod2d",
                 "train_warm_extra2_voting", "train_warm_extra2_q8_2ch"):
        assert committed.get(name) == 0, name


def test_flat_train_budget_preserved():
    """The q8 kernel variants must not grow the flat train budget."""
    committed = cb.load_budget()
    assert committed.get("train_3_iters") <= 11


# ---------------------------------------------------------------------------
# the counter the probe reads, exercised in-process: a per-call jit MUST
# move it, a reused wrapper must not

def test_lowering_counter_sees_per_call_jit():
    import numpy as np
    import jax
    import jax._src.test_util as jtu

    x = np.float32(1.0)
    reused = jax.jit(lambda a: a * 2 + 1)
    reused(x)                                   # warm
    with jtu.count_jit_and_pmap_lowerings() as n:
        for _ in range(3):
            reused(x)
    assert n() == 0, "a warmed wrapper must not lower again"
    with jtu.count_jit_and_pmap_lowerings() as n:
        for _ in range(3):
            # the canary pattern: fresh wrapper per call
            jax.jit(lambda a: a * 2 + 1)(x)  # tpu-lint: disable=retrace-hazard
    assert n() == 3, "per-call jit must lower per call"


# ---------------------------------------------------------------------------
# the real probe, fresh subprocess (slow: ~10 s of jax startup + training)

@pytest.mark.slow
def test_probe_subprocess_matches_committed_budget():
    measured = cb.measure()
    committed = cb.load_budget()
    diffs = cb.diff_counts(measured, committed)
    errors = [m for s, m in diffs if s == "error"]
    assert not errors, "compile-budget regression on an unchanged tree:\n" \
        + "\n".join(errors)
    assert measured["predict_warm_repeat"] == 0


@pytest.mark.slow
def test_update_budget_cli_writes_current_counts(tmp_path, monkeypatch):
    monkeypatch.setattr(cb, "BUDGET_PATH", str(tmp_path / "budget.json"))
    assert cb.update_budget_cli() == 0
    written = cb.load_budget(str(tmp_path / "budget.json"))
    assert written and set(written) == PROBE_ENTRIES
