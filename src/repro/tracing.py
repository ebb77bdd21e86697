"""Named host spans on the profiler's clock.

Each layer marks where its work happens with `span`, a
`jax.profiler.TraceAnnotation`. Inside a profiler session the span lands in
the profiler's host trace, on the clock the device operations are placed on,
so a device idle gap can be put down to the span the host was in; outside a
session it costs about a microsecond and records nothing.

A span never waits for the device: its ends say what the host was doing, and
the device side comes from the trace. Spans are opened in host code only,
never inside a jitted function. Attributes become the event's stats: spans
of one boosting round carry ``round=<i>``, page spans ``page=<idx>``, spans
of one scoring request ``request=<n>``.

Spans on the main thread nest: ``gbdt.fit`` > ``gbdt.prepare`` and
``gbdt.round`` > ``gbdt.grad`` / ``gbdt.grow`` / ``gbdt.margins`` /
``gbdt.eval``; ``gbdt.grow`` > ``gbdt.level`` (one tree level, or one pop of
best-first growth) > ``gbdt.hist`` / ``gbdt.split`` / ``gbdt.partition``,
with ``gbdt.leaf_sums`` after the last level; page passes open
``pipeline.wait`` and ``pipeline.stage`` wherever they run.
``pipeline.fetch`` runs on the prefetch thread. ``serve.request`` >
``serve.bin`` / ``serve.launch`` / ``serve.fetch``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

# training front door and boosting rounds (core/booster.py, distributed/gbdt_shard.py)
FIT = "gbdt.fit"
PREPARE = "gbdt.prepare"
ROUND = "gbdt.round"
GRAD = "gbdt.grad"
GROW = "gbdt.grow"
MARGINS = "gbdt.margins"
EVAL = "gbdt.eval"
# tree driver (core/tree.py)
LEVEL = "gbdt.level"
HIST = "gbdt.hist"
SPLIT = "gbdt.split"
PARTITION = "gbdt.partition"
LEAF_SUMS = "gbdt.leaf_sums"
# page pipeline (data/pages.py, pipeline/stream.py, data/dmatrix.py)
PAGE_WAIT = "pipeline.wait"
PAGE_STAGE = "pipeline.stage"
PAGE_FETCH = "pipeline.fetch"
# serving (serve/forest.py, serve/engine.py)
REQUEST = "serve.request"
BIN = "serve.bin"
LAUNCH = "serve.launch"
FETCH = "serve.fetch"


def span(name: str, **attrs) -> TraceAnnotation:
    """A context manager that records ``name`` with ``attrs`` as one host
    event while a profiler session is active."""
    return TraceAnnotation(name, **attrs)
