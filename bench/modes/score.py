"""Scoring cells: a closed loop of one client over `ForestServer.predict_margin`.

Set-up makes the configuration's forest on the device from the seed
(`bench.forest`), packs it behind a `ForestServer` with the forest's cuts,
makes a pool of raw HIGGS-shaped rows, and scores one request, which
compiles the only shape the window uses. In the window the client sends
request after request of ``request_rows`` rows (slices of the pool, in turn)
and waits for each answer: host binning, staging, one fused forest launch
and the copy back. ``score_rows_per_s`` is every row scored over the time
from the first send to the last answer.

The check draws ``check_rows_per_request`` rows of every answered request
from the seed and compares their margins with the plain reference
(`bench.reference.forest.margins`), which walks the raw values.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import data
from bench import forest as forest_lib
from bench.reference import forest as reference


@dataclasses.dataclass
class State:
    ctx: dict
    server: object
    host_forest: dict
    pool: np.ndarray
    answers: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0


def _pool(seed: int, rows: int, batch_rows: int) -> np.ndarray:
    return np.concatenate([data.continuous_batch(seed, b, min(batch_rows, rows - lo))[0]
                           for b, lo in enumerate(range(0, rows, batch_rows))])


def setup(ctx: dict) -> State:
    import jax

    from repro.core.quantile import HistogramCuts
    from repro.serve import ForestServer
    from repro.serve.forest import PackedForest

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    arrays = jax.block_until_ready(forest_lib.make_forest(seed, cfg))
    edges = forest_lib.cut_edges(cfg["bins"])
    m, b = edges.shape
    cuts = HistogramCuts(values=edges.ravel(), ptrs=(np.arange(m + 1) * b).astype(np.int32),
                         min_vals=np.full(m, -np.inf, np.float32))
    packed = PackedForest(**arrays, max_depth=cfg["max_depth"],
                          learning_rate=cfg["learning_rate"], base_margin=cfg["base_margin"],
                          objective=cfg["objective"], cuts=cuts)
    state = State(ctx, ForestServer(packed), {k: np.asarray(v) for k, v in arrays.items()},
                  _pool(seed, tr["pool_rows"], tr["batch_rows"]))
    for i in range(tr["warm_requests"]):
        state.server.predict_margin(_request(state, i))
    return state


def _request(state: State, i: int) -> np.ndarray:
    rows = state.ctx["traffic"]["request_rows"]
    n = state.pool.shape[0] // rows
    lo = (i % n) * rows
    return state.pool[lo:lo + rows]


def _loop(state: State, seconds: float | None, requests: int | None) -> float:
    t0 = time.perf_counter()
    i = 0
    while True:
        state.answers.append((i, state.server.predict_margin(_request(state, i))))
        i += 1
        elapsed = time.perf_counter() - t0
        if (requests is not None and i >= requests) or (seconds is not None and elapsed >= seconds):
            break
    state.attempted = i
    return elapsed


def window(state: State, seconds: float) -> dict:
    elapsed = _loop(state, seconds, None)
    rows = state.attempted * state.ctx["traffic"]["request_rows"]
    return {"score_rows_per_s": rows / elapsed, "requests": state.attempted}


def traced(state: State) -> None:
    _loop(state, None, state.ctx["traffic"]["trace_requests"])


def counts(state: State) -> dict:
    """The traced requests' work, read once the traced window has closed."""
    tr, cfg = state.ctx["traffic"], state.ctx["config"]
    f = state.host_forest["feature"]
    return {
        "mode": "score",
        "rows": state.attempted * tr["request_rows"],
        "requests": state.attempted,
        "trees": int(f.shape[0]),
        "n_total": int(f.shape[1]),
        "depth": int(cfg["max_depth"]),
        "features": int(state.pool.shape[1]),
    }


def release(state: State) -> None:
    state.server = None
    gc.collect()


def sample(state: State) -> tuple[np.ndarray, np.ndarray]:
    """(rows, served margins) of the rows the check draws from the seed."""
    k = state.ctx["traffic"]["check_rows_per_request"]
    r = data.rng(state.ctx["seed"], 2**31)
    xs, got = [], []
    for i, answer in state.answers:
        idx = r.choice(answer.shape[0], size=min(k, answer.shape[0]), replace=False)
        xs.append(_request(state, i)[idx])
        got.append(np.asarray(answer)[idx])
    return np.concatenate(xs), np.concatenate(got)


def check(state: State) -> list[dict]:
    cfg, tr = state.ctx["config"], state.ctx["traffic"]
    X, got = sample(state)
    want = reference.margins(X, state.host_forest, cfg["max_depth"], cfg["learning_rate"],
                             cfg["base_margin"])
    gap = float(np.max(np.abs(got.astype(np.float64) - want))) if X.size else float("inf")
    return [{"name": "margin_gap", "value": gap, "limit": tr["limits"]["margin_gap"]}]
