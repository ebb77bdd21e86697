"""The program's own host spans in a reduced trace.

The program opens named spans (``jax.profiler.TraceAnnotation``) where each
layer works: ``gbdt.round`` around one boosting round, ``pipeline.wait``
around the wait for the next page, ``serve.bin`` around a request's host
binning, and so on. `bench.trace.reduce_trace` keeps every event of the
host's ``python*`` lines in ``host_spans`` as ``(start_ns, end_ns, name)``:
the program's spans among the Python tracer's frames, on the clock of the
device operations. A program that opens no spans leaves the readers nothing:
they return None.
"""
from __future__ import annotations

from collections import defaultdict

from bench.trace import _union

ROUND = "gbdt.round"
PAGE_WAIT = "pipeline.wait"
PAGE_FETCH = "pipeline.fetch"
REQUEST = "serve.request"
BIN = "serve.bin"
# spans of the main thread; they nest, so the innermost open one owns a gap.
# `pipeline.fetch` runs on the prefetch thread: it overlaps gaps, owns none.
MAIN_THREAD = (
    "gbdt.fit", "gbdt.prepare", ROUND, "gbdt.grad", "gbdt.grow", "gbdt.margins",
    "gbdt.eval", "gbdt.level", "gbdt.hist", "gbdt.split", "gbdt.partition",
    "gbdt.leaf_sums", PAGE_WAIT, "pipeline.stage", REQUEST, BIN, "serve.launch",
    "serve.fetch",
)
NO_SPAN = "no span"


def intervals(red: dict, name: str) -> list[tuple[float, float]]:
    """(start, end) in ns of every span ``name``, clipped to the window."""
    lo, hi = red["window"]
    return [(max(s, lo), min(e, hi)) for s, e, n in red["host_spans"]
            if n == name and e > lo and s < hi]


def length(ivs: list[tuple[float, float]]) -> float:
    """Seconds covered by the union of ``ivs``."""
    return sum(e - s for s, e in _union(ivs)) * 1e-9


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Seconds in both the union of ``a`` and the union of ``b``."""
    a, b = _union(a), _union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def idle_share_within(red: dict, name: str) -> float | None:
    """Share of the union of ``name`` spans in which no operation ran on the
    device, averaged over the devices; None without such spans."""
    spans = intervals(red, name)
    if not spans:
        return None
    busy = [overlap(info["busy"], spans) for info in red["devices"].values()]
    return 1.0 - sum(busy) / len(busy) / length(spans)


def _gaps(busy: list[tuple[float, float]], window: tuple[float, float]):
    lo, hi = window
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _covers(ivs: list[tuple[float, float]], t: float, start: int) -> tuple[bool, int]:
    """Whether sorted disjoint ``ivs`` cover ``t``, scanning from ``start``
    (times come in ascending order); and where the next scan starts."""
    while start < len(ivs) and ivs[start][1] < t:
        start += 1
    return start < len(ivs) and ivs[start][0] <= t, start


def idle_by_span(red: dict, within: str | None = None) -> list[list]:
    """Device-idle seconds inside the window, each gap put down to the
    innermost main-thread span open at its midpoint (``no span`` where none
    is), averaged over the devices. Rows ``[owner, idle_s, fetch_s]``,
    largest first: ``fetch_s`` is the part of those gaps in which the
    prefetch thread was reading a page (``pipeline.fetch``). With ``within``,
    only gaps whose midpoint lies inside a span of that name count."""
    main = sorted((s, -e, n) for s, e, n in red["host_spans"] if n in MAIN_THREAD)
    fetch = _union(intervals(red, PAGE_FETCH))
    inside = _union(intervals(red, within)) if within else None
    idle: dict[str, float] = defaultdict(float)
    fetched: dict[str, float] = defaultdict(float)
    n_dev = len(red["devices"])
    for info in red["devices"].values():
        stack: list[tuple[float, str]] = []  # (end, name) of the open spans
        j = k = w = 0
        for a, b in _gaps(info["busy"], red["window"]):
            mid = (a + b) / 2
            if inside is not None:
                covered, w = _covers(inside, mid, w)
                if not covered:
                    continue
            while j < len(main) and main[j][0] <= mid:
                start, neg_end, name = main[j]
                while stack and stack[-1][0] <= start:
                    stack.pop()
                stack.append((-neg_end, name))
                j += 1
            while stack and stack[-1][0] < mid:
                stack.pop()
            owner = stack[-1][1] if stack else NO_SPAN
            idle[owner] += (b - a) * 1e-9 / n_dev
            while k < len(fetch) and fetch[k][1] <= a:
                k += 1
            i = k
            while i < len(fetch) and fetch[i][0] < b:
                fetched[owner] += (min(fetch[i][1], b) - max(fetch[i][0], a)) * 1e-9 / n_dev
                i += 1
    return [[n, s, fetched[n]] for n, s in sorted(idle.items(), key=lambda kv: -kv[1])]
