"""forest_kernel_roofline: the forest kernel's share of its roofline.

Least work of scoring ``rows`` rows with a forest of ``trees`` trees of
``n_total`` nodes: one node step per row, tree and level for operations; for
bytes, each row's ``F`` one-byte bins once, the packed forest once per launch
(4 B feature, 4 B split bin, 1 B each of default direction and leaf flag,
4 B leaf weight per node) and the margins (4 B in, 4 B out per row).
Time: the device time of the forest kernel (`_forest_kernel`, which the
trace shows as the ``tpu_custom_call`` named after its wrapper
``predict_forest``) on the busiest device,
against the least time of that work spread over the cell's chips.
"""
from __future__ import annotations

import re

from bench import peaks, trace

KERNEL = re.compile(r"^%predict_forest[\w.]* = .*tpu_custom_call")
NODE_BYTES = 14.0


def work(rows: int, launches: int, trees: int, depth: int, n_total: int,
         features: int) -> tuple[float, float]:
    ops = float(rows) * trees * depth
    nbytes = rows * (features + 8.0) + launches * trees * n_total * NODE_BYTES
    return ops, nbytes


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    seconds = trace.op_seconds(ctx["trace"], KERNEL)
    if w.get("mode") != "score" or seconds <= 0:
        return None
    ops, nbytes = work(w["rows"], w["requests"], w["trees"], w["depth"], w["n_total"],
                       w["features"])
    return 100.0 * peaks.least_seconds(ctx["kind"], ops, nbytes, ctx["chips"]) / seconds
