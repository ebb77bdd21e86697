"""bin_ms_per_request: milliseconds of host binning (raw rows to bins, the
``serve.bin`` span) per scoring request (``serve.request`` spans,
`bench.spans`). None where the program opens no such span."""
from __future__ import annotations

from bench import spans


def read(ctx: dict) -> float | None:
    red = ctx["trace"]
    requests, bins = spans.intervals(red, spans.REQUEST), spans.intervals(red, spans.BIN)
    if not requests or not bins:
        return None
    return 1e3 * spans.length(bins) / len(requests)
