"""page_wait_ms_per_tree: milliseconds a boosting round's main thread spends
waiting for the prefetch thread's next page, per tree.

The ``pipeline.wait`` spans (the blocking queue get of the page pipeline)
inside ``gbdt.round`` spans (`bench.spans`), summed, over the rounds. None
where the program opens no such span."""
from __future__ import annotations

from bench import spans


def read(ctx: dict) -> float | None:
    red = ctx["trace"]
    rounds, waits = spans.intervals(red, spans.ROUND), spans.intervals(red, spans.PAGE_WAIT)
    if not rounds or not waits:
        return None
    return 1e3 * spans.overlap(waits, rounds) / len(rounds)
