"""hist_kernel_roofline: the histogram kernel's share of its roofline.

Least work of the histogram passes of the traced trees (the paper's count):
each row a level must read (`bench.work.level_work`: every row at the root,
the smaller child of each split below it) costs its ``F`` one-byte bins, 8 B
of g and h and 4 B of node id; each built node writes ``F * B`` bins of g
and h in float32. The operations are two adds per row and feature. The
one-hot MXU products and int32 copies of the implementation are not work.
Time: the device time of the histogram kernel (`_hist_kernel`, which the
trace shows as the ``tpu_custom_call`` named after its wrapper
``build_histogram_slab``) on the busiest device,
against the least time of that work spread over the cell's chips.
"""
from __future__ import annotations

import re

from bench import peaks, trace

KERNEL = re.compile(r"^%build_histogram_slab[\w.]* = .*tpu_custom_call")


def work(level_work: list[dict], features: int, bins: int) -> tuple[float, float]:
    """(ops, bytes) of the histogram passes of the given trees."""
    rows = sum(sum(w["built"]) for w in level_work)
    nodes = sum(sum(w["built_nodes"]) for w in level_work)
    return 2.0 * rows * features, rows * (features + 12.0) + nodes * features * bins * 2 * 4.0


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    seconds = trace.op_seconds(ctx["trace"], KERNEL)
    if w.get("mode") != "train" or seconds <= 0:
        return None
    ops, nbytes = work(w["level_work"], w["features"], w["bins"])
    return 100.0 * peaks.least_seconds(ctx["kind"], ops, nbytes, ctx["chips"]) / seconds
