"""Work counts of the per-layer metrics, the peaks table, and the
smaller-child recount.

    python -m pytest bench/tests
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import peaks, work
from bench.data import LEVEL_VALUES
from bench.metrics import load


def _tree(depth: int, splits: dict[int, tuple[int, int]]) -> dict:
    n_total = 2 ** (depth + 1) - 1
    feature = np.zeros(n_total, np.int32)
    split_value = np.zeros(n_total, np.float32)
    is_leaf = np.ones(n_total, bool)
    for node, (f, b) in splits.items():
        feature[node], split_value[node], is_leaf[node] = f, LEVEL_VALUES[b], False
    return {"feature": feature, "split_value": split_value, "is_leaf": is_leaf}


def test_level_work_follows_the_smaller_child_rule():
    # 10 rows, 2 features. Root splits feature 0 at level 3: rows with
    # level <= 3 go left (4 rows), the rest right (6 rows). Node 1 splits
    # feature 1 at level 0 (1 left, 3 right); node 2 stays a leaf.
    levels = np.array([[0, 0], [1, 5], [2, 5], [3, 9],
                       [4, 0], [5, 0], [6, 0], [7, 0], [8, 0], [9, 0]], np.uint8)
    w = work.level_work(_tree(3, {0: (0, 3), 1: (1, 0)}), levels, 3)
    assert w["built"] == [10, 4, 1]  # root: all; min(4, 6); min(1, 3) + min(0, 0)
    assert w["built_nodes"] == [1, 1, 1]  # one split pair at each level below the root
    assert w["live"] == [10, 4, 0]  # rows at nodes that split, per level


def test_level_work_matches_a_plain_count_on_a_random_tree():
    rng = np.random.default_rng(0)
    levels = rng.integers(0, 255, (5000, 4)).astype(np.uint8)
    splits = {n: (int(rng.integers(4)), int(rng.integers(254))) for n in range(7)}
    tree = _tree(4, splits)
    w = work.level_work(tree, levels, 4)
    # plain count: route each row alone
    nodes = []
    for row in levels:
        n, path = 0, [0]
        while n in splits:
            f, b = splits[n]
            n = 2 * n + (1 if row[f] <= b else 2)
            path.append(n)
        nodes.append(path)
    for d in range(1, 4):
        per = {}
        for path in nodes:
            if len(path) > d:
                per[path[d]] = per.get(path[d], 0) + 1
        pairs = {(k - 1) // 2 for k in per}
        want = sum(min(per.get(2 * p + 1, 0), per.get(2 * p + 2, 0)) for p in pairs)
        assert w["built"][d] == want


def test_hist_work_at_a_known_shape():
    lw = [{"built": [2**21, 2**20], "built_nodes": [1, 1], "live": [2**21, 2**21]}]
    ops, nbytes = load("hist_kernel_roofline").work(lw, features=28, bins=255)
    rows = 2**21 + 2**20
    assert ops == 2 * rows * 28
    assert nbytes == rows * (28 + 12) + 2 * 28 * 255 * 2 * 4


def test_tree_work_adds_partition_and_row_state():
    lw = [{"built": [100], "built_nodes": [1], "live": [100, 60]}]
    _, hist_bytes = load("hist_kernel_roofline").work(lw, 28, 255)
    _, nbytes = load("tree_mfu").work(lw, rows=100, features=28, bins=255)
    assert nbytes == hist_bytes + 160 * 9 + 100 * 32


def test_forest_work_at_a_known_shape():
    ops, nbytes = load("forest_kernel_roofline").work(
        rows=65536, launches=1, trees=1000, depth=8, n_total=511, features=28)
    assert ops == 65536 * 1000 * 8
    assert nbytes == 65536 * 36 + 1000 * 511 * 14


def test_peaks_and_least_time():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    # 819 MB moves in 1 ms; 197 GFLOP takes 1 ms: the larger bound wins
    assert peaks.least_seconds("TPU v5 lite", 0.0, 819e6) == pytest.approx(1e-3)
    assert peaks.least_seconds("TPU v5 lite", 394e9, 819e6) == pytest.approx(2e-3)
    assert peaks.least_seconds("TPU v5 lite", 394e9, 0.0, chips=4) == pytest.approx(5e-4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.least_seconds("cpu", 1.0, 1.0)


def test_readers_find_nothing_to_read_outside_their_mode():
    ctx = {"work": {"mode": "score"}, "trace": {"devices": {}}, "kind": "TPU v5 lite", "chips": 1}
    for name in ("hist_kernel_roofline", "tree_mfu", "h2d_mb_per_tree", "device_idle_share.train"):
        assert load(name).read(ctx) is None
    ctx["work"] = {"mode": "train"}
    for name in ("forest_kernel_roofline", "score_mfu", "device_idle_share.score"):
        assert load(name).read(ctx) is None
