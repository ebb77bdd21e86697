"""score_mfu: the whole scoring request's share of the chip's peak.

The least time of the forest's work for the traced requests (as
`forest_kernel_roofline` counts it) over the traced window, which holds the
requests end to end: host binning, staging, launch and the copy back."""
from __future__ import annotations

from bench import peaks
from bench.metrics import load


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    if w.get("mode") != "score":
        return None
    ops, nbytes = load("forest_kernel_roofline").work(
        w["rows"], w["requests"], w["trees"], w["depth"], w["n_total"], w["features"])
    return 100.0 * peaks.least_seconds(ctx["kind"], ops, nbytes, ctx["chips"]) / ctx["trace"]["window_s"]
