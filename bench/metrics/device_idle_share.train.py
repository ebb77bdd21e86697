"""device_idle_share.train: share of the traced training window in which no
operation ran on the device (averaged over the chips used)."""
from __future__ import annotations

from bench import trace


def read(ctx: dict) -> float | None:
    if ctx["work"].get("mode") != "train":
        return None
    return 100.0 * trace.idle_share(ctx["trace"])
