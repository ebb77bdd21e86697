"""h2d_mb_per_tree: host-to-device megabytes per tree of the traced fit.

The change of the program's exact `TransferStats.host_to_device_bytes` over
the fit, over its trees: the page pipeline's traffic (pages, margins,
positions back are not counted here)."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    if w.get("mode") != "train" or w.get("h2d_bytes") is None:
        return None
    return w["h2d_bytes"] / len(w["level_work"]) / 1e6
