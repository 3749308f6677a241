"""The rest of a run with the timed path broken underneath: ``correct`` has
to come out false for each fault the cells can have. (The look for a chip is
skipped: ``rehearse`` drives ``harness.run_cell`` on the CPU at a tiny size,
Pallas kernels in interpret mode.)"""
import numpy as np
import pytest

from benchmark import rehearse as rh


def failed(result):
    return sorted(k for k, c in result["checks"].items() if not c["ok"])


def test_sound_run_is_correct():
    r = rh.rehearse("higgs-binary.train-valid")
    assert r["correct"], r["checks"]


def test_state_left_unchanged(monkeypatch):
    """A step that returns its state unchanged: the scores never move, so
    every tree is fitted to the first gradients again."""
    from lightgbm_tpu.models import gbdt
    real = gbdt.GBDT._fused_step

    def unchanged(self, grad, hess):
        trees, _, cegb, ok = real(self, grad, hess)
        return trees, self.train_score, cegb, ok
    monkeypatch.setattr(gbdt.GBDT, "_fused_step", unchanged)
    r = rh.rehearse("higgs-binary.train")
    assert not r["correct"]
    assert "leaf_value_gap" in failed(r)


def test_half_of_the_rows_left_out(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    import lightgbm_tpu as lgb
    real = lgb.Dataset

    def half(data, label=None, **kw):
        return real(np.ascontiguousarray(data[::2]), label=label[::2], **kw)
    monkeypatch.setattr(lgb, "Dataset", half)
    r = rh.rehearse("higgs-l2.train")
    assert not r["correct"]
    assert "leaf_count_gap" in failed(r)


def test_leaf_value_altered_where_it_is_produced(monkeypatch):
    from lightgbm_tpu.models import gbdt
    real = gbdt.GBDT._fused_step

    def altered(self, grad, hess):
        trees, score, cegb, ok = real(self, grad, hess)
        trees = [(t._replace(leaf_value=t.leaf_value * 1.02), leaf)
                 for t, leaf in trees]
        return trees, score, cegb, ok
    monkeypatch.setattr(gbdt.GBDT, "_fused_step", altered)
    r = rh.rehearse("higgs-binary.train")
    assert not r["correct"]
    assert "leaf_value_gap" in failed(r)


def test_validation_score_misses_a_tree(monkeypatch):
    """An answer altered where it is produced: validation scoring skips the
    second tree, so every AUC after it is of a stale score."""
    from lightgbm_tpu.ops import predict as P
    real = P.route_bins
    calls = {"n": 0}

    def stale(*a, **kw):
        calls["n"] += 1
        leaf = real(*a, **kw)
        return leaf * 0 if calls["n"] == 2 else leaf
    monkeypatch.setattr(P, "route_bins", stale)
    r = rh.rehearse("higgs-binary.train-valid")
    assert calls["n"] >= 2
    assert not r["correct"]
    assert failed(r) == ["auc_gap"]


def test_state_left_unchanged_late(monkeypatch):
    """The same fault in the last warm-up iteration alone: the first tree
    followed is sound, and the last, which the window makes from the stale
    scores, is not."""
    from lightgbm_tpu.models import gbdt
    real = gbdt.GBDT._fused_step
    calls = {"n": 0}

    def unchanged(self, grad, hess):
        calls["n"] += 1
        trees, score, cegb, ok = real(self, grad, hess)
        return trees, (self.train_score if calls["n"] == 2 else score), \
            cegb, ok
    monkeypatch.setattr(gbdt.GBDT, "_fused_step", unchanged)
    r = rh.rehearse("higgs-binary.train")
    assert calls["n"] >= 3
    assert not r["correct"]
    assert "leaf_value_gap" in failed(r)


@pytest.mark.parametrize("weaker, numbers", [
    ({"max_bin": 15}, ["bin_count_gap", "bin_occupancy_excess"]),
    ({"bin_construct_sample_cnt": 1000}, ["bin_occupancy_excess"]),
])
def test_bin_bounds_made_cheaper(monkeypatch, weaker, numbers):
    """Ingest finds fewer bins, or finds them from a thousand rows: the
    trees still sit on the run's own bounds, and the bounds are judged."""
    import lightgbm_tpu as lgb
    real = lgb.Dataset

    def cheaper(data, label=None, params=None, **kw):
        return real(data, label=label, params=dict(params, **weaker), **kw)
    monkeypatch.setattr(lgb, "Dataset", cheaper)
    r = rh.rehearse("higgs-binary.train")
    assert not r["correct"]
    assert failed(r) == numbers
