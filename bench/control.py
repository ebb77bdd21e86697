"""Readings of each cell's control at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--requests N]

The control is the plain reference put in the program's place and computed
in the precision below the one the configuration states: for training,
trees grown from bfloat16 gradients and hessians (and, for comparison, with
only the split search in bfloat16); for scoring, leaf weights rounded to
bfloat16. Each is compared exactly as a run compares the program's output,
and one JSON line per seed gives the numbers, with those of a fault planted
in the reference put in the program's place: half of the rows left out. The
benchmark's own runs do not run this; its small-size twin is
``bench/tests/test_control.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def training_readings(cfg: dict, traffic: dict, seed: int) -> dict:
    from bench import data
    from bench.reference import gbdt

    _, ys, lvs = data.quantized_rows(seed, cfg["rows"], traffic["batch_rows"])
    levels, y = np.concatenate(lvs), np.concatenate(ys)
    p = gbdt.Params.from_config(cfg)
    half = (np.arange(y.shape[0]) < y.shape[0] // 2).astype(np.float64)
    out = {}
    for tag, kw in (("control", {"precision": "bfloat16"}),
                    ("control_hist_only", {"precision": "bfloat16", "leaves_exact": True}),
                    ("fault_half_batch", {"row_weight": half})):
        trees = gbdt.build_forest(levels, y, p, data.LEVEL_VALUES, traffic["check_trees"], **kw)
        got = gbdt.check_forest(trees, levels, y, p, data.LEVEL_VALUES)
        out[tag] = {"split_gap": got["split_gap"], "leaf_gap": got["leaf_gap"]}
    return out


def scoring_readings(cfg: dict, traffic: dict, seed: int, requests: int) -> dict:
    """Margin gaps, against the float64 reference, of the rows a run's check
    draws from ``requests`` requests: bfloat16 leaves (the control) and a
    float32 sum in tree order (what a sound program reads)."""
    from bench import forest as forest_lib
    from bench.modes import score
    from bench.reference import forest as forest_ref

    host = {k: np.asarray(v) for k, v in forest_lib.make_forest(seed, cfg).items()}
    state = score.State({"seed": seed, "traffic": traffic, "config": cfg}, None, host,
                        score._pool(seed, traffic["pool_rows"], traffic["batch_rows"]))
    rows = traffic["request_rows"]
    state.answers = [(i, np.zeros(rows, np.float32)) for i in range(requests)]
    X, _ = score.sample(state)
    args = (X, host, cfg["max_depth"], cfg["learning_rate"], cfg["base_margin"])
    want = forest_ref.margins(*args)
    bf16 = forest_ref.margins(*args, leaf_precision="bfloat16")
    leaf = np.float32(cfg["learning_rate"]) * host["leaf_value"].astype(np.float32)
    f32 = np.full(X.shape[0], np.float32(cfg["base_margin"]), np.float32)
    feature, thr = host["feature"].astype(np.int64), host["split_value"].astype(np.float64)
    at = np.arange(X.shape[0])
    for t in range(feature.shape[0]):
        pos = np.zeros(X.shape[0], np.int64)
        for _ in range(cfg["max_depth"]):
            step = 2 * pos + 1 + (X[at, feature[t, pos]] > thr[t, pos])
            pos = np.where(host["is_leaf"][t, pos], pos, step)
        f32 = f32 + leaf[t, pos]
    # half of the rows left out: they keep the base margin
    left_out = slice(1, None, 2)
    return {"control": float(np.max(np.abs(bf16 - want))),
            "reference_f32": float(np.max(np.abs(f32.astype(np.float64) - want))),
            "fault_half_batch": float(np.max(np.abs(want[left_out] - cfg["base_margin"]))),
            "rows": int(X.shape[0])}


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.run import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--requests", type=int, default=30,
                    help="scoring: requests whose rows the check draws, as in a window")
    args = ap.parse_args(argv)
    _, cell, cfg, traffic = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["mode"] == "score":
            got = scoring_readings(cfg, traffic, seed, args.requests)
        else:
            got = training_readings(cfg, traffic, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed, **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
