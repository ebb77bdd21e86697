"""A run with the timed path broken underneath has to come out not correct.

Each case drives a whole run of a cell at the small size of `small.py`, with
the device check off, and plants one fault in the program where its answer
is produced: a step that returns its state unchanged, half of the batch left
out, or an answer altered. The cells run on one chip, so there is no
exchange between chips to leave out.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from bench.tests.small import run_small


def _first_call_only(fn):
    """The step returns the state of its first call every time."""
    memo = []

    def wrapper(*a, **k):
        if not memo:
            memo.append(fn(*a, **k))
        return memo[0]
    return wrapper


def _half_rows(fn, g_at):
    """The step sees the gradients of the first half of the rows only."""
    def wrapper(*a, **k):
        a = list(a)
        for i in g_at:
            w = np.asarray(a[i])
            mask = np.arange(w.shape[0]) < w.shape[0] // 2
            a[i] = type(a[i])(w * mask) if isinstance(a[i], np.ndarray) else a[i] * mask
        return fn(*a, **k)
    return wrapper


def _one_leaf_moved(fn, tree_of):
    """One leaf weight of every tree is 1% off."""
    def wrapper(*a, **k):
        out = fn(*a, **k)
        tree = tree_of(out)
        leaf = np.array(tree.leaf_value)
        leaf[int(np.argmax(np.asarray(tree.is_leaf) & (leaf != 0)))] *= 1.01
        moved = tree._replace(leaf_value=jax.device_put(leaf, tree.leaf_value.sharding))
        return _rebuild(out, moved)
    return wrapper


def _rebuild(out, tree):
    if hasattr(out, "_replace") and hasattr(out, "tree"):
        return out._replace(tree=tree)
    return (tree,) + tuple(out[1:])


def _train_faults(monkeypatch, cell, fault):
    import repro.core.booster as booster
    import repro.core.outofcore as outofcore

    if cell == "higgs.train.in_core":
        target, name, g_at = booster, "grow_tree", (1, 2)
        tree_of = lambda out: out.tree  # noqa: E731
    else:
        target, name, g_at = outofcore, "build_tree_paged", (2, 3)
        tree_of = lambda out: out[0]  # noqa: E731
    fn = getattr(target, name)
    broken = {"state_unchanged": lambda: _first_call_only(fn),
              "half_batch": lambda: _half_rows(fn, g_at),
              "answer_altered": lambda: _one_leaf_moved(fn, tree_of)}[fault]()
    monkeypatch.setattr(target, name, broken)


def _score_faults(monkeypatch, fault):
    from repro.serve.engine import ForestServer

    fn = ForestServer.predict_margin
    if fault == "state_unchanged":
        memo = []

        def broken(self, X):
            # the work is done, so the closed loop keeps its pace
            out = fn(self, X)
            if not memo:
                memo.append(out)
            return memo[0]
    elif fault == "half_batch":
        def broken(self, X):
            out = np.array(fn(self, X))
            out[X.shape[0] // 2:] = self.forest.base_margin
            return out
    else:
        def broken(self, X):
            return fn(self, X) + np.float32(0.01)
    monkeypatch.setattr(ForestServer, "predict_margin", broken)


FAULTS = ["state_unchanged", "half_batch", "answer_altered"]
CELLS = ["higgs.train.in_core", "higgs.train.streaming", "forest.score.batch"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    if cell.startswith("forest."):
        _score_faults(monkeypatch, fault)
    else:
        _train_faults(monkeypatch, cell, fault)
    result = run_small(cell, seed=9)
    assert result["correct"] is False, result["checks"]
    assert result["failed"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = run_small(cell, seed=9)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"] and list(result)[-1] == "checks"
