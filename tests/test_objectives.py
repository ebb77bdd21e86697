"""The eval AUC: the vectorized tied-rank pass is bit-equal to the rank loop."""
import math

import numpy as np
import pytest

from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
from repro.core import objectives as obj_lib
from repro.core.objectives import auc
from repro.data.dmatrix import ArrayDMatrix

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - bare env still collects
    HAVE_HYPOTHESIS = False


def _auc_loop(labels: np.ndarray, scores: np.ndarray) -> float:
    """ROC AUC via the rank statistic (ties handled by average rank)."""
    labels = np.asarray(labels).ravel()
    scores = np.asarray(scores).ravel()
    n_pos = int(np.sum(labels == 1))
    n_neg = labels.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(scores, kind="mergesort")
    sorted_scores = scores[order]
    ranks = np.empty(labels.size, dtype=np.float64)
    # average ranks for tied groups
    i = 0
    while i < labels.size:
        j = i
        while j + 1 < labels.size and sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        ranks[order[i : j + 1]] = 0.5 * (i + j) + 1.0
        i = j + 1
    sum_pos_ranks = float(np.sum(ranks[labels == 1]))
    return (sum_pos_ranks - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg)


def _assert_bit_equal(labels, scores):
    got, want = auc(labels, scores), _auc_loop(labels, scores)
    assert isinstance(got, float)
    if math.isnan(want):
        assert math.isnan(got)
    else:
        assert got == want, (got, want)


def _scores(case: str, n: int, rng: np.random.Generator) -> np.ndarray:
    if case == "no_ties":
        return rng.permutation(n) + rng.random(n) * 0.5
    if case == "all_equal":
        return np.full(n, 0.25)
    if case == "few_groups":  # a few large tie groups
        return rng.integers(0, 4, n) * 0.1
    if case == "nan":  # NaNs among tied and distinct scores
        s = rng.integers(0, 6, n) * 0.2 + np.where(rng.random(n) < 0.5, rng.random(n), 0.0)
        s[rng.random(n) < 0.1] = np.nan
        return s
    if case == "tree_margins":  # sums of a few trees' leaf values
        leaves = rng.normal(size=(3, 8))
        return leaves[np.arange(3)[:, None], rng.integers(0, 8, (3, n))].sum(axis=0)
    raise ValueError(case)


@pytest.mark.parametrize("label_dtype", [np.float32, np.int64])
@pytest.mark.parametrize("score_dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", ["no_ties", "all_equal", "few_groups", "nan", "tree_margins"])
def test_auc_bit_equal_to_rank_loop(case, score_dtype, label_dtype):
    rng = np.random.default_rng(7)
    n = 1037
    labels = (rng.random(n) < 0.53).astype(label_dtype)
    _assert_bit_equal(labels, _scores(case, n, rng).astype(score_dtype))


@pytest.mark.parametrize("label", [0, 1])
@pytest.mark.parametrize("n", [1, 64])
def test_auc_single_class_is_nan(label, n):
    scores = np.random.default_rng(n).random(n)
    assert math.isnan(auc(np.full(n, label), scores))
    _assert_bit_equal(np.full(n, label), scores)


if HAVE_HYPOTHESIS:

    @settings(max_examples=200, deadline=None)
    @given(
        pairs=st.lists(
            st.tuples(
                st.booleans(),
                st.sampled_from([-1.5, -0.0, 0.0, 0.1, 0.3, 2.0, float("nan")]),
            ),
            min_size=1,
            max_size=300,
        )
    )
    def test_auc_property_random_ties(pairs):
        labels = np.array([p[0] for p in pairs], np.float32)
        scores = np.array([p[1] for p in pairs], np.float32)
        _assert_bit_equal(labels, scores)


@pytest.mark.parametrize("engine", ["in_core", "out_of_core", "sampled", "fit_sharded"])
def test_fit_eval_history_matches_rank_loop(small_classification, engine, monkeypatch):
    """Every round's eval value is the rank loop's AUC of that round's
    probabilities, as the round loop handed them to the metric."""
    X, y = small_classification
    seen = []

    def recording_auc(labels, scores):
        seen.append((np.array(labels), np.array(scores)))
        return auc(labels, scores)

    monkeypatch.setitem(obj_lib.METRICS, "auc", recording_auc)
    params = BoosterParams(
        objective="binary:logistic", n_estimators=6, max_depth=3, max_bin=32, learning_rate=0.3
    )
    if engine == "fit_sharded":
        import jax

        from repro.distributed import fit_sharded

        b = fit_sharded(jax.make_mesh((1,), ("data",)), X, y, params=params, eval_set=(X, y))
    else:
        dm = ArrayDMatrix(X, y, max_bin=32, page_bytes=1024)
        b = GradientBooster(params, policy=ExecutionPolicy(mode=engine))
        b.fit(dm, eval_set=(X, y))
    assert len(seen) == len(b.eval_history) == params.n_estimators
    for rec, (labels, scores) in zip(b.eval_history, seen):
        assert rec.metric == "auc"
        assert rec.value == _auc_loop(labels, scores)
    # the first rounds' probabilities take a few values, so ties were ranked
    assert np.unique(seen[0][1]).size < y.size
