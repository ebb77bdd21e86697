"""Training cells: one `GradientBooster.fit` call is the measured window.

Set-up makes the rows from the seed, builds the program's `IterDMatrix` (its
quantile sketch and quantization; pages on disk when the traffic asks), and
fits ``warm_trees`` trees, which compiles every shape a tree uses: the
levels' histogram build sets have static sizes (all nodes at the root, half
of them below), so two trees cover them. The window is one fresh fit of as
many trees as fill ``--seconds`` at the warm-up's seconds per tree; its
``tree_s`` is the last tree's `EvalRecord.elapsed_s` (stamped after a host
sync on the tree's eval margins) over the number of trees.

The check follows the window's first ``check_trees`` trees with the plain
reference (`bench.reference.gbdt.check_forest`).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import tempfile

import numpy as np

from bench import data, work
from bench.reference import gbdt

EVAL_FIRST_BATCH = 2**20  # batch ids of the eval split: disjoint from training


@dataclasses.dataclass
class State:
    ctx: dict
    dm: object
    eval_set: tuple
    levels: np.ndarray
    y: np.ndarray
    tmp: object
    warm_tree_s: float
    trees: list = dataclasses.field(default_factory=list)
    booster: object = None  # the traced fit's, until `counts` reads it
    h2d_before: int = 0
    attempted: int = 0
    failed: int = 0


def _booster_params(cfg: dict, n_trees: int, seed: int):
    from repro.core import BoosterParams

    return BoosterParams(
        n_estimators=n_trees, max_depth=cfg["max_depth"], learning_rate=cfg["learning_rate"],
        max_bin=cfg["max_bin"], objective=cfg["objective"], reg_lambda=cfg["reg_lambda"],
        gamma=cfg["gamma"], min_child_weight=cfg["min_child_weight"], seed=seed % 2**31,
    )


def setup(ctx: dict) -> State:
    from repro.data.dmatrix import IterDMatrix

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    xs, ys, lvs = data.quantized_rows(seed, cfg["rows"], tr["batch_rows"])
    ex, ey, _ = data.quantized_rows(seed, cfg["eval_rows"], tr["batch_rows"], EVAL_FIRST_BATCH)
    eval_set = (np.concatenate(ex), np.concatenate(ey))
    tmp = tempfile.TemporaryDirectory(prefix="bench_pages_") if tr["pages_on_disk"] else None
    dm = IterDMatrix(lambda: zip(xs, ys), max_bin=cfg["max_bin"],
                     cache_dir=tmp.name if tmp else None, page_bytes=tr["page_bytes"])
    levels, y = np.concatenate(lvs), np.concatenate(ys)
    del xs
    state = State(ctx, dm, eval_set, levels, y, tmp, 0.0)
    booster = _fit(state, tr["warm_trees"])
    hist = booster.eval_history
    state.warm_tree_s = hist[-1].elapsed_s - hist[-2].elapsed_s
    del booster
    gc.collect()
    return state


def _fit(state: State, n_trees: int):
    from repro.core import ExecutionPolicy, GradientBooster

    cfg, tr = state.ctx["config"], state.ctx["traffic"]
    booster = GradientBooster(_booster_params(cfg, n_trees, state.ctx["seed"]),
                              policy=ExecutionPolicy(mode=tr["execution"]))
    booster.fit(state.dm, eval_set=state.eval_set)
    if booster.decision_.mode != tr["execution"]:
        raise AssertionError(f"ran {booster.decision_.mode}, expected {tr['execution']}")
    return booster


def _keep(state: State, booster) -> None:
    """Host copies of the trees the check follows."""
    state.trees = [
        {k: np.asarray(getattr(t, k)) for k in ("feature", "split_value", "is_leaf", "leaf_value")}
        for t in booster.trees[: state.ctx["traffic"]["check_trees"]]
    ]


def window(state: State, seconds: float) -> dict:
    tr = state.ctx["traffic"]
    n = max(tr["min_trees"], tr["check_trees"], math.ceil(seconds / state.warm_tree_s))
    booster = _fit(state, n)
    state.attempted = len(booster.trees)
    _keep(state, booster)
    return {"tree_s": booster.eval_history[-1].elapsed_s / len(booster.trees), "trees": n}


def traced(state: State) -> None:
    """The traced fit; `counts` reads it once the traced window has closed."""
    tr = state.ctx["traffic"]
    state.h2d_before = state.dm.stats.host_to_device_bytes
    state.booster = _fit(state, max(tr["trace_trees"], tr["check_trees"]))


def counts(state: State) -> dict:
    """The traced fit's work: its trees recounted by `bench.work.level_work`,
    its seconds per tree and its host-to-device bytes."""
    booster, cfg = state.booster, state.ctx["config"]
    state.booster = None
    state.attempted = len(booster.trees)
    _keep(state, booster)
    host_trees = [{k: np.asarray(getattr(t, k)) for k in ("feature", "split_value", "is_leaf")}
                  for t in booster.trees]
    before = state.h2d_before if booster.stats is state.dm.stats else 0
    return {
        "mode": "train",
        "rows": int(state.levels.shape[0]),
        "features": int(state.levels.shape[1]),
        "bins": data.LEVELS,
        "tree_s": booster.eval_history[-1].elapsed_s / len(booster.trees),
        "h2d_bytes": booster.stats.host_to_device_bytes - before,
        "level_work": [work.level_work(t, state.levels, cfg["max_depth"]) for t in host_trees],
    }


def release(state: State) -> None:
    state.dm = None
    if state.tmp is not None:
        state.tmp.cleanup()
    gc.collect()


def check(state: State) -> list[dict]:
    tr, cfg = state.ctx["traffic"], state.ctx["config"]
    got = gbdt.check_forest(state.trees, state.levels, state.y, gbdt.Params.from_config(cfg),
                            data.LEVEL_VALUES)
    return [{"name": k, "value": got[k], "limit": tr["limits"][k]} for k in ("split_gap", "leaf_gap")]
