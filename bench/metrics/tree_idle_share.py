"""tree_idle_share: share of the boosting rounds in which no operation ran on
the device (averaged over the chips used).

The rounds are the program's ``gbdt.round`` spans (`bench.spans`): from the
gradients through the tree's eval record, so the fit's preparation (staging,
eval binning), which `device_idle_share.train` counts, is left out. None
where the program opens no such span."""
from __future__ import annotations

from bench import spans


def read(ctx: dict) -> float | None:
    share = spans.idle_share_within(ctx["trace"], spans.ROUND)
    return None if share is None else 100.0 * share
