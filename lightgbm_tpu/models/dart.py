"""DART — Dropouts meet Multiple Additive Regression Trees.

Reference: src/boosting/dart.hpp:23 — per iteration: randomly drop a subset of
existing trees from the score, fit the new tree to the residual, then normalize the
new and dropped trees' weights. Tree weights are tracked host-side; dropped-tree
score contributions are reconstructed by re-routing the binned matrix on device.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from ..ops import predict as P
from ..ops.gather import take_small
from ..utils import log
from .gbdt import GBDT


class DART(GBDT):
    name = "dart"

    def __init__(self, config, train_set, objective, metrics=None,
                 quiet: bool = False):
        super().__init__(config, train_set, objective, metrics, quiet=quiet)
        self.drop_rate = config.drop_rate
        self.max_drop = config.max_drop
        self.skip_drop = config.skip_drop
        self.uniform_drop = config.uniform_drop
        self.xgboost_dart_mode = config.xgboost_dart_mode
        self._drop_rng = np.random.RandomState(config.drop_seed)
        self.tree_weights: List[float] = []   # per stored tree (iteration-major)
        self._drop_idx: List[int] = []

    def train_one_iter(self, grad=None, hess=None) -> bool:
        self._select_and_drop()
        finished = super().train_one_iter(grad, hess)
        self._normalize()
        # DART rescales already-stored trees: the host-tree cache (GBDT.finalize)
        # would hold stale leaf values, so invalidate it every iteration
        self.models_host = []
        return finished

    # ---- dropping (dart.hpp:97-115 DroppingTrees) ----
    def _select_and_drop(self) -> None:
        self._drop_idx = []
        k = self.num_tree_per_iteration
        n_iters = len(self.models_dev) // max(k, 1)
        if n_iters == 0 or self._drop_rng.rand() < self.skip_drop:
            return
        if self.uniform_drop:
            mask = self._drop_rng.rand(n_iters) < self.drop_rate
            drop = list(np.nonzero(mask)[0])
        else:
            w = np.array([self.tree_weights[i * k] for i in range(n_iters)])
            p = (1.0 - w) if self.xgboost_dart_mode else np.ones(n_iters)
            p = p / max(p.sum(), 1e-12)
            n_drop = max(1, int(round(n_iters * self.drop_rate)))
            n_drop = min(n_drop, self.max_drop if self.max_drop > 0 else n_drop)
            drop = list(self._drop_rng.choice(n_iters, size=min(n_drop, n_iters),
                                              replace=False, p=p))
        if self.max_drop > 0:
            drop = drop[: self.max_drop]
        self._drop_idx = sorted(int(d) for d in drop)
        # subtract dropped trees from all scores
        for it in self._drop_idx:
            for cls in range(k):
                self._add_tree_score(it * k + cls, cls, -1.0)

    def _add_tree_score(self, tree_idx: int, cls: int, sign: float) -> None:
        """Add/remove a stored tree's (already weighted) contribution."""
        tree_dev = self.models_dev[tree_idx]
        ts = self.train_set
        max_steps = self.gp.num_leaves - 1 if self.gp.num_leaves > 1 else 1
        k = self.num_tree_per_iteration

        def upd(score, bins, na_bin):
            leaf = P.route_bins(
                tree_dev.split_feature, tree_dev.threshold_bin,
                tree_dev.default_left, tree_dev.left_child, tree_dev.right_child,
                tree_dev.num_leaves, bins, na_bin, max_steps,
                **self._subset_nodes(tree_dev))
            delta = take_small(tree_dev.leaf_value, leaf) * sign
            if delta.shape[0] != score.shape[0]:
                delta = delta[: score.shape[0]]   # row-shard padding rows
            if k == 1:
                return score + delta
            return score.at[:, cls].add(delta)

        self.train_score = upd(self.train_score, ts.bins, ts.na_bin_dev)
        for i, vs in enumerate(self.valid_sets):
            self.valid_scores[i] = upd(self.valid_scores[i], vs.bins, vs.na_bin_dev)

    # ---- normalization (dart.hpp:58 TrainOneIter tail) ----
    def _normalize(self) -> None:
        k = self.num_tree_per_iteration
        new_idx = list(range(len(self.models_dev) - k, len(self.models_dev)))
        n_drop = len(self._drop_idx)
        self.tree_weights.extend([1.0] * k)
        if n_drop == 0:
            return
        if self.xgboost_dart_mode:
            new_w = self.learning_rate / (n_drop + self.learning_rate)
            factor = n_drop / (n_drop + self.learning_rate)
        else:
            new_w = 1.0 / (n_drop + 1.0)
            factor = n_drop / (n_drop + 1.0)
        # rescale the new trees from weight 1 to new_w (scores track stored values)
        for ti in new_idx:
            self._scale_tree(ti, new_w, in_score=True)
            self.tree_weights[ti] = new_w
        # dropped trees (currently absent from scores): shrink by factor, add back
        for it in self._drop_idx:
            for cls in range(k):
                ti = it * k + cls
                self._scale_tree(ti, factor, in_score=False)
                self.tree_weights[ti] *= factor
                self._add_tree_score(ti, cls, +1.0)

    # ---- crash-safe resume (snapshot sidecar) ----
    def _extra_resume_state(self, arrays, meta) -> None:
        arrays["dart_tree_weights"] = np.asarray(self.tree_weights,
                                                 dtype=np.float64)

    def _apply_extra_resume_state(self, arrays, meta) -> None:
        self.tree_weights = [float(w) for w in
                             arrays.get("dart_tree_weights", [])]
        self._drop_idx = []

    def _scale_tree(self, tree_idx: int, scale: float, in_score: bool) -> None:
        """Multiply a stored tree's leaf values by ``scale``; if its contribution
        is currently in the scores, keep them consistent."""
        tree_dev = self.models_dev[tree_idx]
        cls = tree_idx % self.num_tree_per_iteration
        if in_score:
            self._add_tree_score(tree_idx, cls, -1.0)
        self.models_dev[tree_idx] = tree_dev._replace(
            leaf_value=tree_dev.leaf_value * scale,
            internal_value=tree_dev.internal_value * scale)
        if in_score:
            self._add_tree_score(tree_idx, cls, +1.0)
