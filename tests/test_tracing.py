"""Program spans (`repro.tracing`) as the profiler records them.

Each case runs a tiny fit or request under `jax.profiler.trace` and reads the
host plane back with `jax.profiler.ProfileData`: span counts per round, level
and page pass, their nesting, the prefetch thread's own line, and the
attributes that carry the round, page and request. A profiler session must
not change what the program computes.
"""
from __future__ import annotations

import glob
import os
import tempfile

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
from repro.data.dmatrix import IterDMatrix
from repro.data.synthetic import make_higgs_like
from repro.serve import ForestServer

TREES, DEPTH, PAGE_BYTES = 2, 3, 2048
ROWS = 512
PREFIXES = ("gbdt.", "pipeline.", "serve.")


class Span:
    def __init__(self, event, line: int):
        self.name = event.name
        self.start = event.start_ns
        self.end = event.start_ns + event.duration_ns
        self.line = line
        self.stats = dict(event.stats)

    def inside(self, outer: "Span") -> bool:
        return self.line == outer.line and outer.start <= self.start and self.end <= outer.end


def traced(fn):
    """(fn's result, every program span the profiler recorded while it ran)."""
    with tempfile.TemporaryDirectory() as tdir:
        with jax.profiler.trace(tdir):
            out = fn()
        (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        spans = [
            Span(e, i)
            for plane in data.planes if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines)
            for e in line.events if e.name.startswith(PREFIXES)
        ]
    return out, spans


def named(spans, name):
    return [s for s in spans if s.name == name]


def each_inside(spans, inner: str, outer: str) -> bool:
    outers = named(spans, outer)
    return all(any(s.inside(o) for o in outers) for s in named(spans, inner))


@pytest.fixture(scope="module")
def data():
    X, y = make_higgs_like(ROWS, seed=5)
    Xe, ye = make_higgs_like(256, seed=5, batch=1000)
    return X, y, (Xe, ye)


def _params(**kw):
    return BoosterParams(n_estimators=TREES, max_depth=DEPTH, max_bin=32,
                         objective="binary:logistic", seed=0, **kw)


def _fit_in_core(data, **kw):
    X, y, ev = data
    return GradientBooster(_params(**kw), policy=ExecutionPolicy(mode="in_core")).fit(
        X, y, eval_set=ev)


@pytest.fixture(scope="module")
def paged(data, tmp_path_factory):
    X, y, _ = data
    dm = IterDMatrix([(X, y)], max_bin=32, cache_dir=str(tmp_path_factory.mktemp("pages")),
                     page_bytes=PAGE_BYTES)
    assert dm.page_set().store is not None and dm.n_pages > 1
    return dm


def _fit_streaming(data, dm):
    booster = GradientBooster(_params(), policy=ExecutionPolicy(mode="out_of_core"))
    return booster.fit(dm, eval_set=data[2])


@pytest.fixture(scope="module")
def in_core(data):
    return traced(lambda: _fit_in_core(data))


@pytest.fixture(scope="module")
def streaming(data, paged):
    return traced(lambda: _fit_streaming(data, paged))


def test_in_core_round_and_level_counts(in_core):
    booster, spans = in_core
    assert booster.decision_.mode == "in_core"
    assert len(named(spans, tracing.FIT)) == 1
    assert len(named(spans, tracing.PREPARE)) == 1
    rounds = named(spans, tracing.ROUND)
    assert sorted(s.stats["round"] for s in rounds) == list(range(TREES))
    for name in (tracing.GRAD, tracing.GROW, tracing.MARGINS, tracing.EVAL,
                 tracing.LEAF_SUMS):
        assert len(named(spans, name)) == TREES, name
    for name in (tracing.LEVEL, tracing.HIST, tracing.SPLIT, tracing.PARTITION):
        assert len(named(spans, name)) == TREES * DEPTH, name
    assert sorted(s.stats["depth"] for s in named(spans, tracing.LEVEL)) == \
        sorted(list(range(DEPTH)) * TREES)
    assert not named(spans, tracing.PAGE_WAIT)


@pytest.mark.parametrize("case", ["in_core", "streaming"])
def test_training_spans_nest(case, request):
    _, spans = request.getfixturevalue(case)
    assert each_inside(spans, tracing.PREPARE, tracing.FIT)
    assert each_inside(spans, tracing.ROUND, tracing.FIT)
    for part in (tracing.GRAD, tracing.GROW, tracing.MARGINS, tracing.EVAL):
        assert each_inside(spans, part, tracing.ROUND), part
    assert each_inside(spans, tracing.LEVEL, tracing.GROW)
    assert each_inside(spans, tracing.LEAF_SUMS, tracing.GROW)
    for part in (tracing.HIST, tracing.SPLIT, tracing.PARTITION):
        assert each_inside(spans, part, tracing.LEVEL), part
    # a round's parts carry its number
    for r in named(spans, tracing.ROUND):
        for part in (tracing.GRAD, tracing.GROW, tracing.MARGINS, tracing.EVAL):
            (p,) = [s for s in named(spans, part) if s.inside(r)]
            assert p.stats["round"] == r.stats["round"]


def test_streaming_page_spans(streaming, paged):
    booster, spans = streaming
    assert booster.decision_.mode == "out_of_core"
    pages = paged.n_pages
    passes = TREES * 2 * DEPTH  # a histogram pass and a partition pass per level
    waits, stages = named(spans, tracing.PAGE_WAIT), named(spans, tracing.PAGE_STAGE)
    fetches = named(spans, tracing.PAGE_FETCH)
    assert len(waits) == len(stages) == len(fetches) == passes * pages
    for group in (waits, stages, fetches):
        assert sorted(s.stats["page"] for s in group) == sorted(list(range(pages)) * passes)
    # the consumer waits and stages inside the level's histogram or partition
    for name in (tracing.PAGE_WAIT, tracing.PAGE_STAGE):
        assert all(any(s.inside(o) for o in named(spans, tracing.HIST) +
                       named(spans, tracing.PARTITION)) for s in named(spans, name))
    # the prefetch thread reads pages on a host line of its own
    assert {s.line for s in fetches}.isdisjoint({s.line for s in waits})
    assert {s.line for s in waits} == {s.line for s in named(spans, tracing.ROUND)}


def test_lossguide_spans(data):
    booster, spans = traced(lambda: _fit_in_core(data, grow_policy="lossguide", max_leaves=5))
    rounds = named(spans, tracing.ROUND)
    assert len(rounds) == TREES
    levels = named(spans, tracing.LEVEL)
    # the root's level, then one per pop: a budget of 5 leaves pops 4 times
    assert sorted(s.stats["pop"] for s in levels) == sorted(list(range(5)) * TREES)
    assert each_inside(spans, tracing.LEVEL, tracing.GROW)
    assert each_inside(spans, tracing.GROW, tracing.ROUND)
    # every pop repartitions; the root and pops with growable children build
    assert len(named(spans, tracing.PARTITION)) == len(levels) - TREES
    assert TREES <= len(named(spans, tracing.HIST)) < len(levels)
    for part in (tracing.HIST, tracing.SPLIT, tracing.PARTITION):
        assert each_inside(spans, part, tracing.LEVEL), part
    assert len(named(spans, tracing.LEAF_SUMS)) == TREES


def test_forest_server_request_spans(data, in_core):
    X = data[0]
    server = ForestServer(in_core[0])
    margins, spans = traced(lambda: server.predict_margin(X))
    (req,) = named(spans, tracing.REQUEST)
    for name in (tracing.BIN, tracing.LAUNCH, tracing.FETCH):
        (s,) = named(spans, name)
        assert s.inside(req), name
        assert s.stats["request"] == req.stats["request"]
    assert margins.shape == (ROWS,)


@pytest.mark.parametrize("case", ["in_core", "streaming"])
def test_profiler_session_changes_no_result(case, request, data, paged):
    traced_booster, _ = request.getfixturevalue(case)
    plain = _fit_in_core(data) if case == "in_core" else _fit_streaming(data, paged)
    assert len(plain.trees) == len(traced_booster.trees) == TREES
    for a, b in zip(plain.trees, traced_booster.trees):
        for field in a._fields:
            np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                          np.asarray(getattr(b, field)), err_msg=field)
    np.testing.assert_array_equal(plain.predict_margin(data[0]),
                                  traced_booster.predict_margin(data[0]))
    if case == "streaming":
        np.testing.assert_array_equal(plain.margins_, traced_booster.margins_)
