"""Run a cell end to end at a size the CPU holds, with the device check off.

Used by the tests of this directory; the sizes below are not the cells'.
"""
from __future__ import annotations

import argparse
import copy

from bench import run

SMALL = {
    "higgs-table2": {"rows": 8192, "eval_rows": 2048},
    "higgs-forest-1k": {"trees": 40},
    "train.in_core": {"batch_rows": 4096},
    "train.streaming": {"batch_rows": 4096, "page_bytes": 65536},
    "score.batch": {"request_rows": 1024, "pool_rows": 4096, "batch_rows": 4096,
                    "check_rows_per_request": 64},
}


_load_cell = run.load_cell


def small_cell(name: str):
    spec, cell, config, traffic = _load_cell(name)
    config = {**copy.deepcopy(config), **SMALL.get(cell["config"], {})}
    traffic = {**copy.deepcopy(traffic), **SMALL.get(cell["traffic"], {})}
    return spec, cell, config, traffic


def run_small(name: str, seed: int = 5, seconds: float = 0.2, trace: int = 0) -> dict:
    """One run of cell ``name`` at the small size."""
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace)
    run.load_cell = small_cell
    try:
        return run.run(args, require_device=False)
    finally:
        run.load_cell = _load_cell
