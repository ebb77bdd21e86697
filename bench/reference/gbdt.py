"""Plain reference of depth-wise second-order boosting, in float64 numpy.

The algorithm of the paper (arXiv 2005.09148, Alg. 1 with eq. 6-8, as in
XGBoost's ``hist`` method) written straight: per level, the gradient
histogram of every node over all of its rows, the gain of every (feature,
bin) split, leaf weights ``-G / (H + lambda)`` from the rows that end at each
leaf, and ``binary:logistic`` gradients from float64 margins that start at the
log-odds of the label mean. No histogram subtraction, no paging, no kernels.
It imports nothing of the program.

Rows arrive binned by the benchmark's generator (``bench/data.py``): level
``b`` of feature ``f`` is the ``b``-th smallest value, so a split "x <= t"
with ``t = LEVEL_VALUES[b]`` sends exactly the rows with level <= b left.

Two uses:

* `check_forest` follows a forest the program built (its splits, tree by
  tree, as a served model's tokens are followed) and measures, per node, how
  far the gain of the program's split lies below the best gain the reference
  finds there, and per leaf, how far the program's weight lies from the
  reference's weight over the same rows. The reference's own margins carry
  from tree to tree.
* `build_forest` grows trees itself; with ``precision="bfloat16"`` it rounds
  every gradient and hessian to bfloat16 first, the control that the check
  has to refuse.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

Tree = dict  # feature, split_value, is_leaf, leaf_value: (n_total,) arrays


@dataclasses.dataclass(frozen=True)
class Params:
    max_depth: int
    learning_rate: float
    reg_lambda: float
    gamma: float
    min_child_weight: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Params":
        return cls(*(float(cfg[k]) if k != "max_depth" else int(cfg[k])
                     for k in ("max_depth", "learning_rate", "reg_lambda", "gamma",
                               "min_child_weight")))


def base_margin(y: np.ndarray) -> float:
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def logistic_grad_hess(margin: np.ndarray, y: np.ndarray):
    p = 1.0 / (1.0 + np.exp(-margin))
    return p - y, p * (1.0 - p)


def to_precision(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x
    if precision == "bfloat16":
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


class _Histograms:
    """Per-level (node, feature, level) sums of g and h over all rows."""

    def __init__(self, levels_t: np.ndarray, n_levels: int):
        self.levels_t = levels_t  # (F, n) uint8
        self.n_levels = n_levels
        self.pool = ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1))

    def __call__(self, local: np.ndarray, count: int, g: np.ndarray, h: np.ndarray):
        """``local`` is each row's node within the level, or ``count`` for
        rows that are not at this level; returns G, H of (count, F, B)."""
        B = self.n_levels
        base = local.astype(np.int64) * B
        size = (count + 1) * B

        def one(f):
            idx = base + self.levels_t[f]
            return (np.bincount(idx, weights=g, minlength=size)[: count * B],
                    np.bincount(idx, weights=h, minlength=size)[: count * B])

        parts = list(self.pool.map(one, range(self.levels_t.shape[0])))
        G = np.stack([p[0] for p in parts]).reshape(-1, count, B).transpose(1, 0, 2)
        H = np.stack([p[1] for p in parts]).reshape(-1, count, B).transpose(1, 0, 2)
        return G, H

    def close(self):
        self.pool.shutdown()


def _split_gains(G, H, Gn, Hn, p: Params):
    """Gain of sending levels <= b of feature f left, for every node."""
    lam = p.reg_lambda
    GL, HL = np.cumsum(G, axis=2), np.cumsum(H, axis=2)
    GR, HR = Gn[:, None, None] - GL, Hn[:, None, None] - HL
    parent = (Gn * Gn / (Hn + lam))[:, None, None]
    raw = 0.5 * (GL * GL / (HL + lam) + GR * GR / (HR + lam) - parent) - p.gamma
    return raw, np.minimum(HL, HR)


def _grow(levels_t, g, h, p: Params, level_values, hist, forced: Tree | None):
    """One tree, level by level. With ``forced`` the splits are the given
    tree's and the gaps are measured; without, the best split is taken."""
    F, n = levels_t.shape
    D = p.max_depth
    n_total = 2 ** (D + 1) - 1
    mcw = p.min_child_weight
    node = np.zeros(n, np.int64)
    frozen = np.zeros(n, bool)
    feature = np.zeros(n_total, np.int64)
    level = np.zeros(n_total, np.int64)
    is_leaf = np.ones(n_total, bool)
    reachable = np.zeros(n_total, bool)
    reachable[0] = True
    split_gaps, split_best = [], []
    for d in range(D):
        off, cnt = 2**d - 1, 2**d
        local = np.where(frozen, cnt, node - off)
        G, H = hist(local, cnt, g, h)
        Gn = np.bincount(local, weights=g, minlength=cnt + 1)[:cnt]
        Hn = np.bincount(local, weights=h, minlength=cnt + 1)[:cnt]
        raw, hmin = _split_gains(G, H, Gn, Hn, p)
        gain = np.where(hmin >= mcw, raw, -np.inf).reshape(cnt, -1)
        best_i = np.argmax(gain, axis=1)
        best = gain[np.arange(cnt), best_i]
        for j in np.nonzero(reachable[off:off + cnt])[0]:
            nid = off + j
            if forced is None:
                split = bool(np.isfinite(best[j]) and best[j] > 0.0)
                f, b = divmod(int(best_i[j]), level_values.shape[0])
            else:
                split = not bool(forced["is_leaf"][nid])
                f = int(forced["feature"][nid])
                t = np.float32(forced["split_value"][nid])
                b = int(np.searchsorted(level_values, t))
                if split and not (0 <= f < F and b < len(level_values) and level_values[b] == t):
                    split_gaps.append(np.inf)  # a threshold that is no level
                    split_best.append(max(best[j], 0.0))
                    continue
                if split:
                    # the program's choice, valid to rounding of the child hessians
                    ok = hmin[j, f, b] >= mcw * (1.0 - 1e-6)
                    chosen = raw[j, f, b] if ok else -np.inf
                    gap = (max(best[j], chosen) - chosen) if np.isfinite(chosen) else np.inf
                else:
                    gap = max(best[j], 0.0)  # a split the program left out
                split_gaps.append(gap)
                split_best.append(max(best[j], 0.0))
            if split:
                feature[nid], level[nid], is_leaf[nid] = f, b, False
                reachable[2 * nid + 1] = reachable[2 * nid + 2] = True
        # route the rows of the nodes that split; rows at leaves stay
        act = np.nonzero(~frozen)[0]
        nd = node[act]
        splits = ~is_leaf[nd]
        go_left = levels_t[feature[nd], act] <= level[nd]
        node[act] = np.where(splits, 2 * nd + 1 + (~go_left), nd)
        frozen[act] = ~splits
    Gl = np.bincount(node, weights=g, minlength=n_total)
    Hl = np.bincount(node, weights=h, minlength=n_total)
    leaves = reachable & is_leaf
    weight = np.where(leaves, -Gl / (Hl + p.reg_lambda), 0.0)
    tree = {
        "feature": np.where(is_leaf, 0, feature).astype(np.int32),
        "split_value": np.where(is_leaf, 0.0, level_values[level]).astype(np.float32),
        "is_leaf": is_leaf,
        "leaf_value": weight,
    }
    return tree, node, leaves, np.asarray(split_gaps), np.asarray(split_best)


def _relative(gaps: np.ndarray, refs: np.ndarray) -> float:
    """Worst gap against the larger of its own reference and the median one."""
    if gaps.size == 0:
        return 0.0
    med = float(np.median(refs))
    scale = np.maximum(refs, med)
    rel = np.where(scale > 0, gaps / np.where(scale > 0, scale, 1.0), np.where(gaps > 0, np.inf, 0.0))
    return float(np.max(rel))


def check_forest(trees: list[Tree], levels: np.ndarray, y: np.ndarray, p: Params,
                 level_values: np.ndarray) -> dict:
    """Follow ``trees`` (the program's, in order) from the base margin and
    return the worst relative ``split_gap`` and ``leaf_gap`` over them."""
    levels_t = np.ascontiguousarray(levels.T)
    y = y.astype(np.float64)
    hist = _Histograms(levels_t, len(level_values))
    margin = np.full(y.shape[0], base_margin(y))
    split, leaf, per_tree = 0.0, 0.0, []
    try:
        for tree in trees:
            g, h = logistic_grad_hess(margin, y)
            mine, node, leaves, gaps, best = _grow(levels_t, g, h, p, level_values, hist, tree)
            ref = mine["leaf_value"]
            prog = np.asarray(tree["leaf_value"], np.float64)
            ids = np.nonzero(leaves)[0]
            s = _relative(gaps, best)
            lf = _relative(np.abs(prog[ids] - ref[ids]), np.abs(ref[ids]))
            per_tree.append({"split_gap": s, "leaf_gap": lf})
            split, leaf = max(split, s), max(leaf, lf)
            margin = margin + p.learning_rate * ref[node]
    finally:
        hist.close()
    return {"split_gap": split, "leaf_gap": leaf, "per_tree": per_tree}


def build_forest(levels: np.ndarray, y: np.ndarray, p: Params, level_values: np.ndarray,
                 n_trees: int, precision: str = "float64", leaves_exact: bool = False,
                 row_weight: np.ndarray | None = None) -> list[Tree]:
    """Grow ``n_trees`` trees with g and h held in ``precision``.
    ``row_weight`` scales each row's g and h (0 leaves a row out). With
    ``leaves_exact`` only the split search sees them so, and the leaf weights
    come from the float64 sums over every row that reaches the leaf."""
    levels_t = np.ascontiguousarray(levels.T)
    y = y.astype(np.float64)
    hist = _Histograms(levels_t, len(level_values))
    margin = np.full(y.shape[0], base_margin(y))
    trees = []
    try:
        for _ in range(n_trees):
            g, h = logistic_grad_hess(margin, y)
            gs, hs = (g, h) if row_weight is None else (g * row_weight, h * row_weight)
            if not leaves_exact:
                g, h = gs, hs
            gl, hl = to_precision(gs, precision), to_precision(hs, precision)
            tree, node, leaves, _, _ = _grow(levels_t, gl, hl, p, level_values, hist, None)
            if leaves_exact:
                n_total = leaves.shape[0]
                G = np.bincount(node, weights=g, minlength=n_total)
                H = np.bincount(node, weights=h, minlength=n_total)
                tree["leaf_value"] = np.where(leaves, -G / (H + p.reg_lambda), 0.0)
            tree["leaf_value"] = tree["leaf_value"].astype(np.float32)
            trees.append(tree)
            margin = margin + p.learning_rate * tree["leaf_value"].astype(np.float64)[node]
    finally:
        hist.close()
    return trees
