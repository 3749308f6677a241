"""Host-side tree model.

Reference analog: Tree (include/LightGBM/tree.h:25, src/io/tree.cpp) — a
fixed-capacity flat-array decision tree. The device grower (ops/grow.py) emits the
same flat layout; this module finalizes it host-side (trims to the real leaf count,
maps bin thresholds to real-valued thresholds via the BinMappers) and provides
text/JSON serialization in the reference's model format plus if-else code generation
(tree.h:194-200 ToString/ToJSON/ToIfElse).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..binning import BinMapper, MISSING_NAN, MISSING_NONE, MISSING_ZERO


_MISSING_TYPE_MASK = {MISSING_NONE: 0, MISSING_ZERO: 4, MISSING_NAN: 8}


class Tree:
    """One decision tree, host-side numpy arrays (reference: tree.h:25)."""

    def __init__(self, num_leaves: int,
                 split_feature: np.ndarray, threshold_bin: np.ndarray,
                 default_left: np.ndarray, left_child: np.ndarray,
                 right_child: np.ndarray, split_gain: np.ndarray,
                 leaf_value: np.ndarray, leaf_weight: np.ndarray,
                 leaf_count: np.ndarray, internal_value: np.ndarray,
                 internal_weight: np.ndarray, internal_count: np.ndarray,
                 threshold_real: Optional[np.ndarray] = None,
                 missing_type: Optional[np.ndarray] = None,
                 shrinkage: float = 1.0,
                 is_cat_node: Optional[np.ndarray] = None,
                 cat_sets: Optional[List[np.ndarray]] = None,
                 cat_mask_bins: Optional[np.ndarray] = None):
        self.num_leaves = int(num_leaves)
        n_int = max(self.num_leaves - 1, 0)
        self.split_feature = np.asarray(split_feature[:n_int], dtype=np.int32)
        self.threshold_bin = np.asarray(threshold_bin[:n_int], dtype=np.int32)
        self.default_left = np.asarray(default_left[:n_int], dtype=bool)
        self.left_child = np.asarray(left_child[:n_int], dtype=np.int32)
        self.right_child = np.asarray(right_child[:n_int], dtype=np.int32)
        self.split_gain = np.asarray(split_gain[:n_int], dtype=np.float64)
        self.leaf_value = np.asarray(leaf_value[:self.num_leaves], dtype=np.float64)
        self.leaf_weight = np.asarray(leaf_weight[:self.num_leaves], dtype=np.float64)
        self.leaf_count = np.asarray(leaf_count[:self.num_leaves], dtype=np.int64)
        self.internal_value = np.asarray(internal_value[:n_int], dtype=np.float64)
        self.internal_weight = np.asarray(internal_weight[:n_int], dtype=np.float64)
        self.internal_count = np.asarray(internal_count[:n_int], dtype=np.int64)
        self.threshold_real = (np.asarray(threshold_real[:n_int], dtype=np.float64)
                               if threshold_real is not None
                               else np.zeros(n_int, dtype=np.float64))
        self.missing_type = (np.asarray(missing_type[:n_int], dtype=np.int32)
                             if missing_type is not None
                             else np.zeros(n_int, dtype=np.int32))
        self.shrinkage = shrinkage
        # categorical subset nodes (reference: tree.h:279 CategoricalDecision):
        # cat_sets[i] = raw category values routed LEFT at node i (empty for
        # numerical nodes); cat_mask_bins = [n_int, B] bin-space membership
        # (device-aligned, kept for bin-space routing of training data)
        self.is_cat_node = (np.asarray(is_cat_node[:n_int], dtype=bool)
                            if is_cat_node is not None
                            else np.zeros(n_int, dtype=bool))
        self.cat_sets = (list(cat_sets) if cat_sets is not None
                         else [np.empty(0, dtype=np.int64)] * n_int)
        self.cat_mask_bins = (np.asarray(cat_mask_bins[:n_int], dtype=bool)
                              if cat_mask_bins is not None else None)

    @property
    def num_cat(self) -> int:
        return int(self.is_cat_node.sum())

    @staticmethod
    def from_device(arrays, mappers: List[BinMapper],
                    feature_map: Optional[np.ndarray] = None,
                    bundle_meta=None) -> "Tree":
        """Build from ops.grow.TreeArrays; maps bin thresholds to real values.

        With EFB (``bundle_meta``), node features are bundle columns and
        bundle-subset splits carry is_cat + a bin mask; decode them back to
        (original feature, real threshold) numerical nodes (efb.py)."""
        nl = int(arrays.num_leaves)
        sf = np.asarray(arrays.split_feature).copy()
        tb = np.asarray(arrays.threshold_bin)
        is_cat = np.asarray(arrays.is_cat).copy()
        cat_mask = np.asarray(arrays.cat_mask)
        n_int = max(nl - 1, 0)
        if bundle_meta is not None:
            for i in range(n_int):
                c = int(sf[i])
                if bundle_meta.is_bundle[c] and is_cat[i]:
                    # bundle-subset node -> numerical on the original feature
                    p_pos = int(tb[i])
                    sf[i] = bundle_meta.pos_feat[c, p_pos]
                    is_cat[i] = False
                    tb = tb.copy()
                    tb[i] = bundle_meta.pos_bin[c, p_pos]
                else:
                    sf[i] = bundle_meta.members[c][0][0]
        thr_real = np.zeros(n_int)
        mtypes = np.zeros(n_int, dtype=np.int32)
        cat_sets: List[np.ndarray] = []
        for i in range(n_int):
            m = mappers[sf[i]]
            if is_cat[i]:
                # member bins -> raw categories (bin b holds cat_values[b-1];
                # bin 0 = other/missing, excluded from subsets by construction)
                member_bins = np.nonzero(cat_mask[i])[0]
                member_bins = member_bins[(member_bins >= 1)
                                          & (member_bins <= len(m.cat_values))]
                cat_sets.append(np.sort(m.cat_values[member_bins - 1])
                                .astype(np.int64))
                thr_real[i] = 0.0  # rewritten to the cat index at serialization
            else:
                cat_sets.append(np.empty(0, dtype=np.int64))
                thr_real[i] = m.bin_to_value(int(tb[i]))
            mtypes[i] = m.missing_type
        if feature_map is not None:
            sf_orig = feature_map[sf[:n_int]] if n_int else sf[:n_int]
        else:
            sf_orig = sf[:n_int]
        return Tree(
            num_leaves=nl,
            split_feature=sf_orig, threshold_bin=tb,
            default_left=np.asarray(arrays.default_left),
            left_child=np.asarray(arrays.left_child),
            right_child=np.asarray(arrays.right_child),
            split_gain=np.asarray(arrays.split_gain),
            leaf_value=np.asarray(arrays.leaf_value),
            leaf_weight=np.asarray(arrays.leaf_weight),
            leaf_count=np.asarray(arrays.leaf_count),
            internal_value=np.asarray(arrays.internal_value),
            internal_weight=np.asarray(arrays.internal_weight),
            internal_count=np.asarray(arrays.internal_count),
            threshold_real=thr_real, missing_type=mtypes,
            is_cat_node=is_cat, cat_sets=cat_sets,
            cat_mask_bins=cat_mask[:n_int] if n_int else None,
        )

    # ---- mutation (reference: Tree::Shrinkage tree.h:154, AddBias tree.h:172) ----
    def shrink(self, rate: float) -> None:
        self.leaf_value *= rate
        self.internal_value *= rate
        self.shrinkage *= rate

    def add_bias(self, val: float) -> None:
        self.leaf_value += val
        self.internal_value += val

    def set_leaf_values(self, values: np.ndarray) -> None:
        self.leaf_value = np.asarray(values[: self.num_leaves], dtype=np.float64)

    @property
    def max_depth(self) -> int:
        if self.num_leaves <= 1:
            return 0
        depth = np.zeros(self.num_leaves - 1, dtype=np.int32)
        md = 1
        # nodes are created in BFS-ish order but parent always precedes child
        for i in range(self.num_leaves - 1):
            for c in (self.left_child[i], self.right_child[i]):
                if c >= 0:
                    depth[c] = depth[i] + 1
                    md = max(md, depth[c] + 1)
        return md

    # ---- prediction (host reference path; device path in ops/predict.py) ----
    def predict(self, x: np.ndarray) -> np.ndarray:
        """x: [N, F] raw features -> leaf values [N]."""
        leaf = self.predict_leaf(x)
        return self.leaf_value[leaf]

    def predict_leaf(self, x: np.ndarray) -> np.ndarray:
        n = x.shape[0]
        out = np.zeros(n, dtype=np.int32)
        if self.num_leaves <= 1:
            return out
        node = np.zeros(n, dtype=np.int32)
        active = np.ones(n, dtype=bool)
        while active.any():
            idx = np.nonzero(active)[0]
            nd = node[idx]
            feat = self.split_feature[nd]
            v = x[idx, feat]
            thr = self.threshold_real[nd]
            mt = self.missing_type[nd]
            isnan = np.isnan(v)
            v0 = np.where(isnan & (mt == MISSING_NONE), 0.0, v)
            is_missing = np.where(mt == MISSING_NAN, isnan,
                                  np.where(mt == MISSING_ZERO,
                                           (np.abs(v0) < 1e-35) | isnan, False))
            go_left = np.where(is_missing, self.default_left[nd], v0 <= thr)
            if self.is_cat_node.any():
                cat_here = self.is_cat_node[nd]
                if cat_here.any():
                    gl_cat = np.zeros(len(nd), dtype=bool)
                    for j in np.nonzero(cat_here)[0]:
                        vv = v[j]
                        gl_cat[j] = (not np.isnan(vv) and vv >= 0 and
                                     int(vv) in self._cat_lookup(int(nd[j])))
                    go_left = np.where(cat_here, gl_cat, go_left)
            nxt = np.where(go_left, self.left_child[nd], self.right_child[nd])
            leaf_hit = nxt < 0
            out[idx[leaf_hit]] = ~nxt[leaf_hit]
            node[idx[~leaf_hit]] = nxt[~leaf_hit]
            active[idx[leaf_hit]] = False
        return out

    def _cat_lookup(self, node: int):
        key = getattr(self, "_cat_lut", None)
        if key is None:
            key = self._cat_lut = {
                i: frozenset(int(v) for v in self.cat_sets[i])
                for i in np.nonzero(self.is_cat_node)[0]}
        return key.get(node, frozenset())

    # ---- serialization (reference: gbdt_model_text.cpp:271 per-tree blocks) ----
    def to_string(self, tree_idx: int) -> str:
        def arr(a, fmt="%g"):
            return " ".join(fmt % v for v in a)

        n_int = self.num_leaves - 1
        decision_type = np.zeros(max(n_int, 0), dtype=np.int32)
        thr_out = self.threshold_real.copy()
        # categorical nodes: decision_type bit0, threshold = cat index, and
        # bitsets over raw category values (reference: Tree::ToString writes
        # cat_boundaries_/cat_threshold_, gbdt_model_text.cpp + tree.cpp;
        # bitsets via Common::ConstructBitset: bit v -> word v//32)
        cat_boundaries = [0]
        cat_words: List[int] = []
        cat_idx = 0
        for i in range(n_int):
            dt = 0  # bit0: categorical; bit1: default_left; bits2-3: missing type
            if self.is_cat_node[i]:
                dt |= 1
                thr_out[i] = cat_idx
                vals = self.cat_sets[i]
                n_words = (int(vals.max()) // 32 + 1) if len(vals) else 1
                words = [0] * n_words
                for v in vals:
                    words[int(v) // 32] |= 1 << (int(v) % 32)
                cat_words.extend(words)
                cat_boundaries.append(cat_boundaries[-1] + n_words)
                cat_idx += 1
            else:
                if self.default_left[i]:
                    dt |= 2
            dt |= _MISSING_TYPE_MASK.get(int(self.missing_type[i]), 0)
            decision_type[i] = dt
        lines = [f"Tree={tree_idx}",
                 f"num_leaves={self.num_leaves}",
                 f"num_cat={cat_idx}",
                 f"split_feature={arr(self.split_feature, '%d')}",
                 f"split_gain={arr(self.split_gain)}",
                 f"threshold={arr(thr_out, '%.17g')}",
                 f"decision_type={arr(decision_type, '%d')}",
                 f"left_child={arr(self.left_child, '%d')}",
                 f"right_child={arr(self.right_child, '%d')}",
                 f"leaf_value={arr(self.leaf_value, '%.17g')}",
                 f"leaf_weight={arr(self.leaf_weight, '%.17g')}",
                 f"leaf_count={arr(self.leaf_count, '%d')}",
                 f"internal_value={arr(self.internal_value, '%.17g')}",
                 f"internal_weight={arr(self.internal_weight, '%g')}",
                 f"internal_count={arr(self.internal_count, '%d')}",
                 f"shrinkage={self.shrinkage:g}",
                 "", ""]
        if cat_idx > 0:
            ins = [f"cat_boundaries={arr(cat_boundaries, '%d')}",
                   f"cat_threshold={arr(cat_words, '%d')}"]
            # after internal_count, before shrinkage (tree.cpp:238-243)
            pos = next(i for i, ln in enumerate(lines)
                       if ln.startswith("shrinkage="))
            lines[pos:pos] = ins
        return "\n".join(lines)

    @staticmethod
    def from_string(block: str) -> "Tree":
        kv: Dict[str, str] = {}
        for line in block.strip().splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v.strip()
        nl = int(kv["num_leaves"])

        def arr(key, dtype, size):
            s = kv.get(key, "")
            if not s:
                return np.zeros(size, dtype=dtype)
            return np.array(s.split(" "), dtype=dtype)

        n_int = max(nl - 1, 0)
        dt = arr("decision_type", np.int32, n_int)
        default_left = (dt & 2) > 0
        mt = np.where((dt & 12) == 8, MISSING_NAN,
                      np.where((dt & 12) == 4, MISSING_ZERO, MISSING_NONE))
        is_cat = (dt & 1) > 0
        thr = arr("threshold", np.float64, n_int)
        cat_sets: List[np.ndarray] = [np.empty(0, dtype=np.int64)] * n_int
        num_cat = int(kv.get("num_cat", 0))
        if num_cat > 0:
            bounds = arr("cat_boundaries", np.int64, num_cat + 1)
            words = arr("cat_threshold", np.uint64, int(bounds[-1])).astype(np.uint32)
            for i in np.nonzero(is_cat)[0]:
                ci = int(thr[i])
                vals = []
                for w_i in range(int(bounds[ci]), int(bounds[ci + 1])):
                    w = int(words[w_i])
                    base = (w_i - int(bounds[ci])) * 32
                    for bit in range(32):
                        if w & (1 << bit):
                            vals.append(base + bit)
                cat_sets[i] = np.asarray(vals, dtype=np.int64)
        t = Tree(
            num_leaves=nl,
            split_feature=arr("split_feature", np.int32, n_int),
            threshold_bin=np.zeros(n_int, dtype=np.int32),
            default_left=default_left,
            left_child=arr("left_child", np.int32, n_int),
            right_child=arr("right_child", np.int32, n_int),
            split_gain=arr("split_gain", np.float64, n_int),
            leaf_value=arr("leaf_value", np.float64, nl),
            leaf_weight=arr("leaf_weight", np.float64, nl),
            leaf_count=arr("leaf_count", np.int64, nl),
            internal_value=arr("internal_value", np.float64, n_int),
            internal_weight=arr("internal_weight", np.float64, n_int),
            internal_count=arr("internal_count", np.int64, n_int),
            threshold_real=thr,
            missing_type=mt,
            shrinkage=float(kv.get("shrinkage", 1.0)),
            is_cat_node=is_cat, cat_sets=cat_sets,
        )
        return t

    def to_json(self, tree_idx: int) -> Dict:
        def node_json(ptr: int) -> Dict:
            if ptr < 0:
                leaf = ~ptr
                return {"leaf_index": int(leaf),
                        "leaf_value": float(self.leaf_value[leaf]),
                        "leaf_weight": float(self.leaf_weight[leaf]),
                        "leaf_count": int(self.leaf_count[leaf])}
            if self.is_cat_node[ptr]:
                thr_str = "||".join(str(int(v)) for v in self.cat_sets[ptr])
                return {
                    "split_index": int(ptr),
                    "split_feature": int(self.split_feature[ptr]),
                    "split_gain": float(self.split_gain[ptr]),
                    "threshold": thr_str,
                    "decision_type": "==",
                    "default_left": False,
                    "missing_type": ["None", "Zero", "NaN"][int(self.missing_type[ptr])],
                    "internal_value": float(self.internal_value[ptr]),
                    "internal_weight": float(self.internal_weight[ptr]),
                    "internal_count": int(self.internal_count[ptr]),
                    "left_child": node_json(int(self.left_child[ptr])),
                    "right_child": node_json(int(self.right_child[ptr])),
                }
            return {
                "split_index": int(ptr),
                "split_feature": int(self.split_feature[ptr]),
                "split_gain": float(self.split_gain[ptr]),
                "threshold": float(self.threshold_real[ptr]),
                "decision_type": "<=",
                "default_left": bool(self.default_left[ptr]),
                "missing_type": ["None", "Zero", "NaN"][int(self.missing_type[ptr])],
                "internal_value": float(self.internal_value[ptr]),
                "internal_weight": float(self.internal_weight[ptr]),
                "internal_count": int(self.internal_count[ptr]),
                "left_child": node_json(int(self.left_child[ptr])),
                "right_child": node_json(int(self.right_child[ptr])),
            }
        root = 0 if self.num_leaves > 1 else ~0
        return {"tree_index": tree_idx, "num_leaves": self.num_leaves,
                "num_cat": self.num_cat, "shrinkage": self.shrinkage,
                "tree_structure": node_json(root)}

    def to_if_else(self, index: int) -> str:
        """C++ codegen of this tree (reference: Tree::ToIfElse, tree.h:200)."""
        def rec(ptr: int, indent: str) -> str:
            if ptr < 0:
                return f"{indent}return {float(self.leaf_value[~ptr]):.17g};\n"
            f_ = int(self.split_feature[ptr])
            if self.is_cat_node[ptr]:
                vals = ", ".join(str(int(v)) for v in self.cat_sets[ptr])
                s = f"{indent}if (IsCatLeft(arr[{f_}], {{{vals}}})) {{\n"
                s += rec(int(self.left_child[ptr]), indent + "  ")
                s += f"{indent}}} else {{\n"
                s += rec(int(self.right_child[ptr]), indent + "  ")
                s += f"{indent}}}\n"
                return s
            thr = float(self.threshold_real[ptr])
            dl = "true" if self.default_left[ptr] else "false"
            s = f"{indent}if (IsLeft(arr[{f_}], {thr:.17g}, {dl})) {{\n"
            s += rec(int(self.left_child[ptr]), indent + "  ")
            s += f"{indent}}} else {{\n"
            s += rec(int(self.right_child[ptr]), indent + "  ")
            s += f"{indent}}}\n"
            return s
        body = rec(0 if self.num_leaves > 1 else ~0, "  ")
        return (f"double PredictTree{index}(const double* arr) {{\n{body}}}\n")


def stack_trees(trees: List[Tree], num_features: int, max_num_bins: int,
                pad_leaves: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Stack per-tree flat arrays into [T, ...] device-ready arrays for the jitted
    ensemble predictors (ops/predict.py)."""
    t = len(trees)
    max_l = pad_leaves or max((tr.num_leaves for tr in trees), default=1)
    max_i = max(max_l - 1, 1)
    out = {
        "split_feature": np.zeros((t, max_i), dtype=np.int32),
        "threshold_bin": np.zeros((t, max_i), dtype=np.int32),
        "threshold_real": np.zeros((t, max_i), dtype=np.float32),
        "default_left": np.zeros((t, max_i), dtype=bool),
        "left_child": np.full((t, max_i), -1, dtype=np.int32),
        "right_child": np.full((t, max_i), -1, dtype=np.int32),
        "leaf_value": np.zeros((t, max_l), dtype=np.float32),
        "num_leaves": np.zeros((t,), dtype=np.int32),
        "missing_type": np.zeros((t, max_i), dtype=np.int32),
        "is_cat": np.zeros((t, max_i), dtype=bool),
        "cat_mask": np.zeros((t, max_i, max_num_bins), dtype=bool),
    }
    for i, tr in enumerate(trees):
        n_int = max(tr.num_leaves - 1, 0)
        out["split_feature"][i, :n_int] = tr.split_feature
        out["threshold_bin"][i, :n_int] = tr.threshold_bin
        out["threshold_real"][i, :n_int] = tr.threshold_real
        out["default_left"][i, :n_int] = tr.default_left
        out["left_child"][i, :n_int] = tr.left_child
        out["right_child"][i, :n_int] = tr.right_child
        out["leaf_value"][i, : tr.num_leaves] = tr.leaf_value
        out["num_leaves"][i] = tr.num_leaves
        out["missing_type"][i, :n_int] = tr.missing_type
        out["is_cat"][i, :n_int] = tr.is_cat_node
        if tr.cat_mask_bins is not None and n_int:
            bsz = min(tr.cat_mask_bins.shape[1], max_num_bins)
            out["cat_mask"][i, :n_int, :bsz] = tr.cat_mask_bins[:, :bsz]
    return out


def ensemble_path_tables(stack: Dict[str, np.ndarray],
                         na_of_feature: np.ndarray) -> Optional[Dict[str, np.ndarray]]:
    """Signed path matrices for the dense (gather-free) ensemble predictor
    (ops/predict.py predict_bins_ensemble_dense).

    The classic per-row tree WALK is a sequential chain of data-dependent
    gathers — the worst possible shape for the TPU (the reference walks
    pointers per row, tree.h:240; fine on CPU). Instead: decide EVERY node of
    a tree at once (one one-hot matmul per tree group), then resolve each
    row's leaf with a signed path matrix A [L, M] (+1 = path goes left at
    node m, -1 = right, 0 = node off-path): a row lands in leaf l iff
    A[l] . sign(decisions) == path_length[l]. Three batched MXU contractions
    replace depth x 4 sequential gathers.

    Returns None if any tree has categorical nodes (caller falls back to the
    walk; subset membership is not a threshold compare)."""
    if np.asarray(stack.get("is_cat", np.zeros(1, bool))).any():
        return None
    lc = np.asarray(stack["left_child"])
    rc = np.asarray(stack["right_child"])
    nl = np.asarray(stack["num_leaves"])
    feat = np.asarray(stack["split_feature"])
    t_cnt, m = lc.shape
    l_max = np.asarray(stack["leaf_value"]).shape[1]
    A = np.zeros((t_cnt, l_max, m), dtype=np.int8)
    plen = np.full((t_cnt, l_max), -1.0, dtype=np.float32)
    m_idx = np.arange(m)
    lrows = np.arange(l_max)
    for i in range(t_cnt):
        n_int = max(int(nl[i]) - 1, 0)
        if n_int == 0:
            plen[i, 0] = 0.0          # stump: every row is in leaf 0
            continue
        live = m_idx < n_int
        par = np.full(m, -1, dtype=np.int64)
        psign = np.zeros(m, dtype=np.int8)
        for ch_arr, s in ((lc[i], 1), (rc[i], -1)):
            mk = live & (ch_arr >= 0)
            par[ch_arr[mk]] = m_idx[mk]
            psign[ch_arr[mk]] = s
        leaf_par = np.full(l_max, -1, dtype=np.int64)
        leaf_sign = np.zeros(l_max, dtype=np.int8)
        for ch_arr, s in ((lc[i], 1), (rc[i], -1)):
            mk = live & (ch_arr < 0)
            leaves = ~ch_arr[mk]
            leaf_par[leaves] = m_idx[mk]
            leaf_sign[leaves] = s
        cur, sgn = leaf_par.copy(), leaf_sign.copy()
        while (cur >= 0).any():
            v = cur >= 0
            A[i][lrows[v], cur[v]] = sgn[v]
            safe = np.maximum(cur, 0)
            cur, sgn = np.where(v, par[safe], -1), np.where(v, psign[safe], 0)
        plen[i, : int(nl[i])] = np.abs(
            A[i][: int(nl[i])].astype(np.int32)).sum(axis=1)
    nav = np.asarray(na_of_feature, np.float32)[feat]     # [T, M]
    return {
        "feat": feat.astype(np.int32),
        "thr": np.asarray(stack["threshold_bin"], np.float32),
        "dleft": np.asarray(stack["default_left"], np.float32),
        "nav": nav,
        "A": A,
        "plen": plen,
        "lv": np.asarray(stack["leaf_value"], np.float32),
    }


def ensemble_max_depth(stack: Dict[str, np.ndarray]) -> int:
    """Longest root->leaf DECISION count across stacked trees (host-side).

    The ceiling of the jitted tree walks. The binned walk (ops/predict.py
    route_bins) stops by itself once every row is on a leaf, and under vmap
    once the deepest tree of the stack is done, so there this bound is only
    its guarantee of termination; the raw-float walk (route_raw) still runs
    a static-trip loop, where sizing it by num_leaves - 1 (254 at L=255)
    instead of the actual depth (~10 for depthwise trees) made batch
    prediction ~25x slower.
    Children always carry larger
    node ids than their parents (both growers assign ids split-/level-
    ordered), so one forward pass over nodes computes exact depths."""
    lc = np.asarray(stack["left_child"])
    rc = np.asarray(stack["right_child"])
    nl = np.asarray(stack["num_leaves"])
    t_cnt, m = lc.shape
    if t_cnt == 0:
        return 1
    node_iota = np.arange(m)[None, :]
    if (((lc >= 0) & (lc <= node_iota)) | ((rc >= 0) & (rc <= node_iota))).any():
        # non-monotone node ordering (foreign model file): conservative bound
        return int(max(1, nl.max() - 1))
    depth = np.zeros((t_cnt, m), dtype=np.int32)
    depth[:, 0] = (nl > 1).astype(np.int32)
    best = depth[:, 0].copy()
    rows = np.arange(t_cnt)
    for t in range(m):
        d = depth[:, t]
        active = d > 0
        if not active.any():
            continue
        best = np.maximum(best, d)
        for ch in (lc[:, t], rc[:, t]):
            valid = active & (ch > t) & (ch < m)
            idx = np.where(valid, ch, 0)
            nd = np.where(valid, d + 1, 0)
            np.maximum.at(depth, (rows, idx), nd)
    return int(max(1, best.max()))
