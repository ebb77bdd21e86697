"""tree_mfu: the whole tree's share of the chip's peak.

Least work of a tree: its histogram passes (as `hist_kernel_roofline`
counts them), the partition (per level, each live row reads 1 B of its
split feature's bin and reads and writes 4 B of node id) and the per-tree
row state (each row reads and writes its 4 B margin, reads its 4 B label,
writes and reads 4 B each of g and h, and the leaf sums read g, h and node
id again: 32 B). The least time, the larger of ops over peak and bytes over
HBM bandwidth, over the traced run's seconds per tree.
"""
from __future__ import annotations

from bench import peaks
from bench.metrics import load

ROW_STATE_BYTES = 32.0


def work(level_work: list[dict], rows: int, features: int, bins: int) -> tuple[float, float]:
    ops, nbytes = load("hist_kernel_roofline").work(level_work, features, bins)
    for w in level_work:
        nbytes += sum(w["live"]) * 9.0 + rows * ROW_STATE_BYTES
    return ops, nbytes


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    if w.get("mode") != "train" or not w["level_work"]:
        return None
    ops, nbytes = work(w["level_work"], w["rows"], w["features"], w["bins"])
    least = peaks.least_seconds(ctx["kind"], ops, nbytes, ctx["chips"])
    return 100.0 * least / len(w["level_work"]) / w["tree_s"]
