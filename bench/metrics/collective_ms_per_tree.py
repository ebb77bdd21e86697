"""collective_ms_per_tree: device milliseconds of the cross-shard collectives
per tree of the traced fit, on the busiest device.

An operation counts when its HLO opcode is a collective: ``all-reduce`` or
``all-gather``, or their asynchronous ``-start`` and ``-done`` halves. On a
TPU v5e host of four chips, `fit_sharded`'s depthwise tree program shows 16
synchronous ``all-reduce`` operations a tree on each device: 14 named
``psum.<n>`` (a histogram or the next level's row counts) and 2 named
``all-reduce.<n>`` (the root's histogram combined with its g and h totals,
and the leaf sums); no ``-start``/``-done`` halves, and no ``all-gather``
without a feature axis (`bench/tests/data/higgs.train.sharded4.xplane.pb.gz`).
The names change with XLA's numbering, the opcode does not, so the opcode is
matched. None where the trace holds no such operation (one device, or a
program without collectives)."""
from __future__ import annotations

import re

from bench import trace

COLLECTIVE = re.compile(r"^%[\w.-]+ = .*? (all-reduce|all-gather)(-start|-done)?\(")


def read(ctx: dict) -> float | None:
    w = ctx["work"]
    seconds = trace.op_seconds(ctx["trace"], COLLECTIVE)
    if w.get("mode") != "train" or seconds <= 0 or not w["level_work"]:
        return None
    return 1e3 * seconds / len(w["level_work"])
