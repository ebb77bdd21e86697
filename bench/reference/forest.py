"""Plain reference of forest scoring, in float64 numpy.

A row walks each tree from the root: at an internal node it goes left iff its
value of the node's feature is at most the node's threshold (no value is
missing in this traffic), and it stops at a leaf. Its margin is the base
margin plus the learning rate times the sum of the leaves it reaches. It reads
raw feature values and thresholds, so it bins nothing, and it imports nothing
of the program.
"""
from __future__ import annotations

import numpy as np


def margins(X: np.ndarray, forest: dict, max_depth: int, learning_rate: float,
            base_margin: float, leaf_precision: str = "float64") -> np.ndarray:
    """(n,) float64 margins of rows ``X`` under ``forest`` (host arrays of
    shape (T, n_total)). ``leaf_precision="bfloat16"`` rounds every leaf
    weight first: the control that the check has to refuse."""
    feature = np.asarray(forest["feature"], np.int64)
    thr = np.asarray(forest["split_value"], np.float64)
    is_leaf = np.asarray(forest["is_leaf"], bool)
    leaf = np.asarray(forest["leaf_value"], np.float32)
    if leaf_precision == "bfloat16":
        import ml_dtypes

        leaf = leaf.astype(ml_dtypes.bfloat16)
    elif leaf_precision != "float64":
        raise ValueError(f"unknown precision {leaf_precision!r}")
    leaf = leaf.astype(np.float64)
    n_trees = feature.shape[0]
    X = np.asarray(X, np.float64)
    rows = np.arange(X.shape[0])[:, None]
    trees = np.arange(n_trees)[None, :]
    pos = np.zeros((X.shape[0], n_trees), np.int64)
    for _ in range(max_depth):
        x = X[rows, feature[trees, pos]]
        child = 2 * pos + 1 + (x > thr[trees, pos])
        pos = np.where(is_leaf[trees, pos], pos, child)
    return base_margin + learning_rate * leaf[trees, pos].sum(axis=1)
