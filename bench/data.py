"""HIGGS-shaped rows from a seed: the benchmark's own generator.

Rows have the 28 features of the UCI HIGGS set and a label drawn from a
nonlinear function of them, the shape of `repro.data.synthetic.make_higgs_like`
(copied here so that the yardstick does not move with the program).

Training rows are quantized: each feature takes one of ``LEVELS`` values, its
standard-normal rank cut into 255 equal-probability levels (read from a fine
grid, so that the cut costs a table lookup). The program's
quantile sketch is exact on such data (at most 255 distinct values per
feature, so its cuts are the values themselves), and the plain reference can
bin every row from the level index alone. Scoring rows stay continuous.
"""
from __future__ import annotations

import functools
import math

import numpy as np

FEATURES = 28
LEVELS = 255
# the value of each level: its index, centred and scaled to about the
# spread of a standard normal
LEVEL_VALUES = ((np.arange(LEVELS) - (LEVELS - 1) / 2) / 42.5).astype(np.float32)


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for (seed, stream); any non-negative seed."""
    return np.random.Generator(np.random.PCG64(np.random.SeedSequence([abs(int(seed)), stream])))


def _label_weights(seed: int) -> tuple[np.ndarray, np.ndarray]:
    r = rng(seed, 0)
    return r.normal(size=FEATURES), r.normal(size=FEATURES)


def continuous_batch(seed: int, batch: int, rows: int) -> tuple[np.ndarray, np.ndarray]:
    """(X float32 (rows, 28), y float32 (rows,)) for batch ``batch``."""
    r = rng(seed, batch + 1)
    X = r.standard_normal((rows, FEATURES), dtype=np.float32)
    w1, w2 = _label_weights(seed)
    logits = (
        X @ w1 * 0.5
        + np.sin(X @ w2)
        + 0.8 * X[:, 0] * X[:, 1]
        - 0.6 * X[:, 2] * X[:, 3] * np.tanh(X[:, 4])
    )
    logits = logits / np.std(logits)
    p = 1.0 / (1.0 + np.exp(-2.0 * logits))
    y = (r.random(rows) < p).astype(np.float32)
    return X, y


@functools.cache
def _level_table() -> np.ndarray:
    """Level of each cell of a 1/2048 grid over [-8, 8): its centre's normal
    rank, cut into ``LEVELS`` equal-probability levels."""
    centres = (np.arange(_GRID_CELLS) + 0.5) / _GRID_PER_UNIT - _GRID_HALF_WIDTH
    cdf = np.array([0.5 * (1.0 + math.erf(c / math.sqrt(2.0))) for c in centres])
    return np.minimum(np.floor(cdf * LEVELS), LEVELS - 1).astype(np.uint8)


_GRID_PER_UNIT, _GRID_HALF_WIDTH = 2048, 8
_GRID_CELLS = 2 * _GRID_HALF_WIDTH * _GRID_PER_UNIT


def levels_of(X: np.ndarray) -> np.ndarray:
    """Level index (uint8, 0..254) of each value, by its cell of the grid."""
    cell = X * np.float32(_GRID_PER_UNIT) + np.float32(_GRID_HALF_WIDTH * _GRID_PER_UNIT)
    np.clip(cell, 0, _GRID_CELLS - 1, out=cell)
    return _level_table()[cell.astype(np.int32)]


def quantized_batch(seed: int, batch: int, rows: int):
    """(X float32, y float32, levels uint8): X holds ``LEVEL_VALUES[levels]``."""
    X, y = continuous_batch(seed, batch, rows)
    lv = levels_of(X)
    return LEVEL_VALUES[lv], y, lv


def quantized_rows(seed: int, rows: int, batch_rows: int, first_batch: int = 0):
    """All batches of a quantized table: lists of X, y and level batches."""
    xs, ys, lvs = [], [], []
    for b, lo in enumerate(range(0, rows, batch_rows)):
        X, y, lv = quantized_batch(seed, first_batch + b, min(batch_rows, rows - lo))
        xs.append(X)
        ys.append(y)
        lvs.append(lv)
    return xs, ys, lvs
