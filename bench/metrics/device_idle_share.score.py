"""device_idle_share.score: share of the traced scoring window in which no
operation ran on the device."""
from __future__ import annotations

from bench import trace


def read(ctx: dict) -> float | None:
    if ctx["work"].get("mode") != "score":
        return None
    return 100.0 * trace.idle_share(ctx["trace"])
