"""The control of each cell's check: the plain reference, put in the
program's place and computed a precision lower than the configuration
states (bfloat16 gradients, hessians or leaf weights), has to read above
the cell's limits, and the reference itself far below them.

This is the small size a test run holds; ``bench/control.py`` reads the
same numbers on the chip at each cell's own size.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, data
from bench.reference import gbdt

ROOT = Path(__file__).resolve().parents[2]


def _limits(traffic: str) -> dict:
    return json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())["limits"]


@pytest.mark.parametrize("traffic", ["train.in_core", "train.streaming"])
def test_training_control_fails_and_reference_passes(traffic):
    cfg = json.loads((ROOT / "bench" / "configs" / "higgs-table2.json").read_text())
    lim = _limits(traffic)
    xs, ys, lvs = data.quantized_rows(21, 2**16, 2**15)
    levels, y = np.concatenate(lvs), np.concatenate(ys)
    p = gbdt.Params.from_config(cfg)
    for precision, fails in (("bfloat16", True), ("float64", False)):
        trees = gbdt.build_forest(levels, y, p, data.LEVEL_VALUES, 3, precision)
        got = gbdt.check_forest(trees, levels, y, p, data.LEVEL_VALUES)
        over = [k for k in lim if not got[k] <= lim[k]]
        assert bool(over) == fails, (precision, got)


def test_scoring_control_fails_and_reference_passes():
    cfg = json.loads((ROOT / "bench" / "configs" / "higgs-forest-1k.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "score.batch.json").read_text())
    cfg = {**cfg, "trees": 200}
    traffic = {**traffic, "request_rows": 4096, "pool_rows": 8192, "batch_rows": 4096,
               "check_rows_per_request": 128}
    got = control.scoring_readings(cfg, traffic, seed=4, requests=4)
    assert got["control"] > _limits("score.batch")["margin_gap"]
    assert got["reference_f32"] < _limits("score.batch")["margin_gap"]
