"""Peak rates of each device kind (``peaks.json``), and least time from them."""
from __future__ import annotations

import functools
import json
from pathlib import Path


@functools.cache
def _table() -> dict:
    return json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return _table()["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json") from None


def least_seconds(device_kind: str, ops: float, nbytes: float, chips: int = 1) -> float:
    """The least time ``chips`` such devices need: the larger of the operations
    over peak operations per second and the bytes over peak HBM bandwidth."""
    p = peaks(device_kind)
    return max(ops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]) / chips
