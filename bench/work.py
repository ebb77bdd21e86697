"""Work the algorithm needs for one tree, counted from the tree and the rows.

The program's own counters are not read: the rows a level must read follow
from the paper's algorithm (histogram subtraction, arXiv 2005.09148 §2.1),
whatever implements it. Rows arrive as the generator's level indices, so a
split "x <= t" at ``t = LEVEL_VALUES[b]`` sends the rows with level <= b left.
"""
from __future__ import annotations

import numpy as np

from bench.data import LEVEL_VALUES


def level_work(tree: dict, levels: np.ndarray, max_depth: int) -> dict:
    """Per level of one tree: ``live`` rows (at a node that splits there),
    ``built`` rows (all rows at the root; below it, those of the smaller
    child of each split pair) and ``built_nodes`` (1 at the root, then one
    per split pair)."""
    n = levels.shape[0]
    feature = np.asarray(tree["feature"], np.int64)
    thr = np.searchsorted(LEVEL_VALUES, np.asarray(tree["split_value"], np.float32))
    is_leaf = np.asarray(tree["is_leaf"], bool)
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    live, built, built_nodes = [], [n], [1]
    for d in range(max_depth):
        split = ~is_leaf[node]
        live.append(int(split.sum()))
        go_left = levels[rows, feature[node]] <= thr[node]
        node = np.where(split, 2 * node + 1 + (~go_left), node)
        if d + 1 == max_depth:
            break
        off, cnt = 2 ** (d + 1) - 1, 2 ** (d + 1)
        at = (node >= off) & (node < off + cnt)
        counts = np.bincount(node[at] - off, minlength=cnt)
        pairs = np.nonzero(counts[0::2] + counts[1::2])[0]
        built.append(int(np.minimum(counts[0::2], counts[1::2]).sum()))
        built_nodes.append(int(pairs.size))
    return {"live": live, "built": built, "built_nodes": built_nodes}
