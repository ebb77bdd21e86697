"""Out-of-core tree construction.

The out-of-core training engines (Alg. 6 streaming, Alg. 7 sampled) run in
`GradientBooster`'s round loop (`repro.core.booster`) and are selected by
`ExecutionPolicy`; the data side (sketch, paging, spill) lives on the DMatrix
sources in `repro.data.dmatrix`. This module keeps what is genuinely about
out-of-core *tree building*: `build_tree_paged`, one tree over streamed pages
(either growth policy), shared by the single-device streaming engine and the
sharded distributed build — including per-node page skipping for lossguide
passes (pages with no row in the popped node's window are never fetched or
staged; the skips are recorded in `TransferStats.pages_skipped`).

`PageSet` moved to `repro.data.dmatrix`; importing it from here still works.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp

from repro.core.histcache import (
    HistogramCache,
    LevelPlan,
    level_row_counts,
    node_grad_sums,
    node_row_counts,
)
from repro.core.tree import tree_growth_driver
from repro.kernels import ops

Array = jax.Array


def __getattr__(name: str):
    # compatibility re-export: PageSet's home is the DMatrix module now
    if name == "PageSet":
        from repro.data.dmatrix import PageSet

        return PageSet
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _accepts_indices(make_stream) -> bool:
    """Can ``make_stream`` start a subset pass (``indices=`` kwarg)?

    Older callers pass zero-arg closures; they still work, just without
    per-node page skipping.
    """
    try:
        sig = inspect.signature(make_stream)
    except (TypeError, ValueError):  # builtins / odd callables
        return False
    for prm in sig.parameters.values():
        if prm.kind is inspect.Parameter.VAR_KEYWORD or prm.name == "indices":
            return True
    return False


def build_tree_paged(
    make_stream,
    page_extents: list[tuple[int, int]],
    g,
    h,
    n_bins: int,
    bin_valid: Array,
    tp,
    cut_values=None,
    cut_ptrs=None,
    impl: str = "auto",
    hist_cache: HistogramCache | None = None,
    page_skipping: bool = True,
) -> tuple[object, dict[int, Array]]:
    """Tree build over streamed pages (Alg. 6 core), either growth policy.

    ``make_stream()`` starts one `PageStream` pass; the depthwise driver runs
    one pass per level for the histogram and one for the partition, while the
    lossguide driver (``tp.grow_policy == "lossguide"``) runs one pass per
    popped frontier leaf — a per-node histogram pass in which every row
    outside the popped node's 2-child window (including the whole derive set,
    via the `node_map` kernel path) hits no bin. Shared by the single-device
    streaming engine of `GradientBooster` and the sharded
    `distributed.grow_tree_distributed_paged` (which differ only in how the
    stream stages pages). Returns (tree, per-page positions keyed by stream
    index, in `page_extents` order).

    With histogram subtraction (the default) the stream pass only scatters
    rows belonging to *build* nodes — so each disk->host->device pass does
    roughly half the histogram work at depth >= 1.

    Per-node page skipping (``page_skipping``, lossguide only): before a
    popped node's histogram pass, pages none of whose rows sit inside the
    node's 2-child window are dropped from the pass entirely — no disk fetch,
    no host->device staging — and counted in ``stats.pages_skipped``. The
    repartition pass skips the same set: only the popped node's rows move, so
    pages whose rows all sit at leaves are proven immutable and never
    streamed. Needs a ``make_stream`` accepting ``indices=``; zero-arg
    closures always stream every page.
    """
    g_j, h_j = jnp.asarray(g), jnp.asarray(h)
    positions: dict[int, Array] = {
        i: jnp.zeros(nr, jnp.int32) for i, (_, nr) in enumerate(page_extents)
    }
    skip_enabled = (
        page_skipping and tp.grow_policy == "lossguide" and _accepts_indices(make_stream)
    )

    def subset_stream(active: list[int]):
        """Start a pass over ``active`` pages only, counting the skips; falls
        back to a full pass when nothing (or everything) is skippable."""
        if not active or len(active) == len(page_extents):
            return make_stream()
        stream = make_stream(indices=active)
        stats = getattr(stream, "stats", None)
        if stats is not None:
            stats.pages_skipped += len(page_extents) - len(active)
        return stream

    # the repartition pass's skip set, stashed for the histogram pass that
    # follows it in the same pop — the two sets are provably identical (only
    # the popped node's rows move, into the window the hist pass scans), so
    # the per-page device predicates run once per pop, not twice
    active_box: list[list[int] | None] = [None]

    def start_stream(offset: int, window: int):
        """One histogram pass, restricted to pages with rows in the node
        window when the caller supports subset passes (lossguide per-node
        passes)."""
        if not skip_enabled or offset == 0:
            return make_stream()
        active = active_box[0]
        active_box[0] = None
        if active is None:  # no repartition stashed a set (defensive)
            active = [
                i
                for i, (_, nr) in enumerate(page_extents)
                if nr
                and bool(jnp.any((positions[i] >= offset) & (positions[i] < offset + window)))
            ]
        return subset_stream(active)

    def hist_fn(offset: int, count: int, plan: LevelPlan) -> Array:
        # one double-buffered pass per level (or per pop batch); page k+1
        # stages while page k's histogram kernel runs. ``count`` is the
        # driver's window span — for batched pops it covers every popped
        # parent's children (a superset of the build set, so the page-skip
        # predicate stays conservative); plan.count would be too narrow then.
        stream = start_stream(offset, count)
        if plan.build_nodes is not None:
            # fused fast path: one launch per page, raw global positions
            return ops.build_histogram_paged(
                stream, g_j, h_j, positions, offset, plan.n_build, n_bins,
                impl=impl, build_nodes=plan.build_nodes,
            )
        return ops.build_histogram_paged(
            stream, g_j, h_j, positions, offset,
            plan.n_build, n_bins, node_map=plan.node_map, impl=impl,
        )

    def partition_fn(feature, split_bin, default_left, is_leaf, count_level):
        counts = None
        if skip_enabled:
            # per-node repartition only moves the popped node's rows — after
            # the split write it is the single non-leaf holding rows, so a
            # page whose rows all sit at leaves cannot change and is skipped.
            # This is exactly the histogram pass's skip set: the rows that
            # moved (into the 2-child window the next hist pass scans) came
            # from these same pages — stash it so the hist pass reuses it.
            active = [
                i
                for i, (_, nr) in enumerate(page_extents)
                if nr and bool(jnp.any(~is_leaf[positions[i]]))
            ]
            active_box[0] = active
            stream = subset_stream(active)
        else:
            stream = make_stream()
        for sp in stream:
            positions[sp.index] = ops.partition_rows(
                sp.device, positions[sp.index], feature, split_bin,
                default_left, is_leaf, impl=impl,
            )
            if count_level is not None:
                c = (
                    level_row_counts(positions[sp.index], *count_level)
                    if isinstance(count_level, tuple)
                    else node_row_counts(positions[sp.index], count_level)
                )
                counts = c if counts is None else counts + c
        return counts

    def leaf_sums_fn():
        # positions live on device; no page needs to be streamed again
        sums = [
            node_grad_sums(
                positions[i], g_j[ro:ro + nr], h_j[ro:ro + nr], tp.n_total_nodes
            )
            for i, (ro, nr) in enumerate(page_extents)
        ]
        return tuple(sum(parts[1:], parts[0]) for parts in zip(*sums))

    tree = tree_growth_driver(tp)(
        hist_fn, partition_fn, leaf_sums_fn, jnp.sum(g_j), jnp.sum(h_j), n_bins,
        bin_valid, tp, cut_values, cut_ptrs, hist_cache=hist_cache,
    )
    return tree, positions
