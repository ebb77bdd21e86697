"""The seeded forest of higgs-forest-1k: valid, repeatable, and scored alike
by `ForestServer` and the plain traversal."""
from __future__ import annotations

import numpy as np

from bench import data, forest
from bench.reference import forest as reference

CFG = {"trees": 12, "max_depth": 5, "bins": 255, "early_leaf_share": 0.15, "leaf_std": 0.5,
       "learning_rate": 0.1, "base_margin": 0.25, "objective": "binary:logistic"}


def _server(arrays):
    from repro.core.quantile import HistogramCuts
    from repro.serve import ForestServer
    from repro.serve.forest import PackedForest

    edges = forest.cut_edges(CFG["bins"])
    m, b = edges.shape
    cuts = HistogramCuts(values=edges.ravel(), ptrs=(np.arange(m + 1) * b).astype(np.int32),
                         min_vals=np.full(m, -np.inf, np.float32))
    return ForestServer(PackedForest(**arrays, max_depth=CFG["max_depth"],
                                     learning_rate=CFG["learning_rate"],
                                     base_margin=CFG["base_margin"],
                                     objective=CFG["objective"], cuts=cuts))


def test_forest_is_valid_and_repeatable():
    a = {k: np.asarray(v) for k, v in forest.make_forest(2**33 + 7, CFG).items()}
    b = {k: np.asarray(v) for k, v in forest.make_forest(2**33 + 7, CFG).items()}
    c = {k: np.asarray(v) for k, v in forest.make_forest(7, CFG).items()}
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["leaf_value"], c["leaf_value"])  # high bits count
    n_total = 2 ** (CFG["max_depth"] + 1) - 1
    assert a["feature"].shape == (CFG["trees"], n_total)
    assert a["is_leaf"][:, 2 ** CFG["max_depth"] - 1:].all()
    inner = ~a["is_leaf"]
    assert (a["split_bin"][inner] <= CFG["bins"] - 2).all()
    edges = forest.cut_edges(CFG["bins"])
    assert np.array_equal(a["split_value"][inner],
                          edges[a["feature"][inner], a["split_bin"][inner]])
    assert (a["leaf_value"][inner] == 0).all() and (a["leaf_value"][~inner] != 0).any()


def test_server_and_plain_traversal_agree():
    arrays = forest.make_forest(3, CFG)
    host = {k: np.asarray(v) for k, v in arrays.items()}
    X, _ = data.continuous_batch(3, 0, 3000)
    X[:5] = forest.cut_edges(CFG["bins"])[:, 100][None, :]  # values exactly at a cut
    got = _server(arrays).predict_margin(X)
    want = reference.margins(X, host, CFG["max_depth"], CFG["learning_rate"],
                             CFG["base_margin"])
    assert np.max(np.abs(got - want)) < 1e-5
