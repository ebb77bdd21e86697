"""collective_mb_per_tree: megabytes each shard passes into the cross-shard
collectives per tree of the traced fit.

The program's own count, `TransferStats.collective_bytes` of the traced fit
(`repro.distributed.fit_sharded`: every psum and all-gather of the SPMD tree
program, counted from the operands' shapes), over its trees. None where the
program keeps no such counter."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    if w.get("mode") != "train" or w.get("collective_bytes") is None or not w["level_work"]:
        return None
    return w["collective_bytes"] / len(w["level_work"]) / 1e6
