"""Training cells on a data-parallel mesh: one `repro.distributed.fit_sharded`
call is the measured window.

The configuration's ``mesh`` names the axes and their sizes (``{"data": 4}``);
the mesh is built over the devices the cell was given, and the rows shard over
its ``data`` axis. Everything else is `bench.modes.train`'s contract: the same
rows from the seed, the same `IterDMatrix`, ``warm_trees`` trees of warm-up in
a fit of their own, a window of one fresh fit of as many trees as fill
``--seconds``, ``tree_s`` the last tree's `EvalRecord.elapsed_s` over the
number of trees, and the same check of the window's first ``check_trees``
trees against the plain reference. The traced fit's counts add
``collective_bytes``, the program's `TransferStats.collective_bytes` over that
fit (None where the program does not count them).
"""
from __future__ import annotations

import dataclasses
import gc
import math

import numpy as np

from bench import data
from bench.modes.train import (  # noqa: F401 - check and release are this mode's too
    EVAL_FIRST_BATCH,
    State,
    _booster_params,
    _keep,
    check,
    counts as _train_counts,
    release,
)


@dataclasses.dataclass
class ShardedState(State):
    mesh: object = None


def _mesh(ctx: dict):
    import jax

    axes = ctx["config"]["mesh"]
    return jax.make_mesh(tuple(axes.values()), tuple(axes), devices=ctx["devices"])


def setup(ctx: dict) -> ShardedState:
    from repro.data.dmatrix import IterDMatrix

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    xs, ys, lvs = data.quantized_rows(seed, cfg["rows"], tr["batch_rows"])
    ex, ey, _ = data.quantized_rows(seed, cfg["eval_rows"], tr["batch_rows"], EVAL_FIRST_BATCH)
    eval_set = (np.concatenate(ex), np.concatenate(ey))
    dm = IterDMatrix(lambda: zip(xs, ys), max_bin=cfg["max_bin"])
    levels, y = np.concatenate(lvs), np.concatenate(ys)
    del xs
    state = ShardedState(ctx, dm, eval_set, levels, y, None, 0.0, mesh=_mesh(ctx))
    booster = _fit(state, tr["warm_trees"])
    hist = booster.eval_history
    state.warm_tree_s = hist[-1].elapsed_s - hist[-2].elapsed_s
    del booster
    gc.collect()
    return state


def _fit(state: ShardedState, n_trees: int):
    from repro.distributed import fit_sharded

    params = _booster_params(state.ctx["config"], n_trees, state.ctx["seed"])
    return fit_sharded(state.mesh, state.dm, params=params, eval_set=state.eval_set)


def window(state: ShardedState, seconds: float) -> dict:
    tr = state.ctx["traffic"]
    n = max(tr["min_trees"], tr["check_trees"], math.ceil(seconds / state.warm_tree_s))
    booster = _fit(state, n)
    state.attempted = len(booster.trees)
    _keep(state, booster)
    return {"tree_s": booster.eval_history[-1].elapsed_s / len(booster.trees), "trees": n}


def traced(state: ShardedState) -> None:
    """The traced fit; `counts` reads it once the traced window has closed."""
    tr = state.ctx["traffic"]
    state.booster = _fit(state, max(tr["trace_trees"], tr["check_trees"]))


def counts(state: ShardedState) -> dict:
    """`bench.modes.train.counts` of the traced fit, with the bytes each shard
    passed into the fit's collectives."""
    stats = state.booster.stats
    out = _train_counts(state)
    out["collective_bytes"] = getattr(stats, "collective_bytes", None)
    return out
