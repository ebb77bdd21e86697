"""GPU-style tree construction: depth-wise (paper Alg. 1) and best-first.

Trees use a complete-binary-tree array layout (node i -> children 2i+1, 2i+2,
n_total = 2^(effective_max_depth+1) - 1) so every step is static-shaped and
jit-able:

  level d:  histogram over *build* nodes  (kernels.ops.build_histogram)
            -> sibling derivation         (core.histcache: parent - built)
            -> EvaluateSplit              (core.split.evaluate_splits)
            -> RepartitionInstances       (kernels.ops.partition_rows)

`grow_tree_generic` drives the levels through three callbacks — histogram
accumulation, row repartition and the final per-node gradient sums — so the
same driver serves:
  * the in-core builder (`grow_tree`, one device-resident page, Alg. 1),
  * the out-of-core streaming builder (page loop per level, Alg. 6),
  * the distributed paged builder (sharded staging + per-page mesh reduce).

`grow_tree_lossguide_generic` is the best-first (LightGBM lossguide) sibling
over the same callbacks: a gain-ordered frontier pops one leaf at a time,
expands it via per-node 2-wide `LevelPlan`s, and repartitions only that
node's rows. Select with ``TreeParams(grow_policy="lossguide",
max_leaves=...)``; every builder dispatches through `tree_growth_driver`.

A `HistogramStore` sits between the driver and the callbacks: per level (or
per popped node) it plans which nodes must actually be built (the smaller
child of each split pair) and derives every sibling by subtraction from the
retained parent — see `core/histcache.py`. Each plan runs an explicit
fetch/derive/rebuild resolution step (recorded on ``LevelPlan.source``): the
parent histogram is used where it sits on device, staged back from the host
tier when the store's byte budget spilled it, reconstructed from a retained
ancestor chain (multi-level subtraction), or — when nothing resolves — the
window is rebuilt from rows. Disable per tree with
``TreeParams(hist_subtraction=False)`` to force the full build.

Rows carry a global node-id position; once their node becomes a leaf the
position freezes, so after the last level `leaf_value[pos]` is the tree's
prediction for every training row (a single gather for the margin update).

Leaf weights come from the gradient sums of the rows that end at each leaf
(`LeafSumsFn`), not from the split search's running sums. Those are
``node - cumsum(bins)`` differences of large f32 numbers, so a deep leaf's
sum inherits an error of about one ulp of its ancestors' sums, and two
builders that add the same rows in another order (pages, shards, the MXU
kernel and the XLA scatter) would disagree on small leaves beyond f32
tolerance. Splits still come from the histograms.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.histcache import (
    HistogramCache,
    LevelPlan,
    level_row_counts,
    node_grad_sums,
    node_row_counts,
)
from repro.core.split import LevelSplits, SplitParams, evaluate_splits, leaf_weight
from repro.kernels import ops
from repro.tracing import span

Array = jax.Array


class TreeArrays(NamedTuple):
    """One regression tree, complete-tree layout. All arrays length n_total."""

    feature: Array  # int32 split feature (0 for leaves)
    split_bin: Array  # int32 split bin (go left iff bin <= split_bin)
    split_value: Array  # f32 raw threshold (go left iff x <= split_value)
    default_left: Array  # bool missing-value direction
    is_leaf: Array  # bool
    leaf_value: Array  # f32 (0 for internal nodes)

    @property
    def n_total(self) -> int:
        return self.feature.shape[0]

    @property
    def max_depth(self) -> int:
        return int(np.log2(self.n_total + 1)) - 1


GROW_POLICIES = ("depthwise", "lossguide")


@dataclasses.dataclass(frozen=True)
class TreeParams:
    max_depth: int = 6
    split: SplitParams = SplitParams()
    # build only the smaller child of each split pair per level and derive the
    # sibling histogram as parent - built (exact up to f32 accumulation order)
    hist_subtraction: bool = True
    # "depthwise": expand every growable node level by level (paper Alg. 1);
    # "lossguide": best-first — a gain-ordered frontier pops the single best
    # candidate leaf, LightGBM-style (`grow_tree_lossguide_generic`)
    grow_policy: str = "depthwise"
    # lossguide leaf budget; 0 = unbounded (up to the 2^max_depth complete
    # tree). Ignored by depthwise (XGBoost semantics for grow_policy).
    max_leaves: int = 0
    # lossguide: pop up to this many frontier leaves per iteration so their
    # child windows share ONE HistFn pass and ONE PartitionFn pass (one
    # disk->host->device PageStream pass out-of-core instead of one per pop).
    # 1 (the default) is exactly strictly-best-first; >1 pops the current
    # top-k without re-ranking against the just-created children, which is
    # identical at a full leaf budget (every positive-gain candidate is
    # eventually popped; split decisions are per-node) but may keep different
    # leaves under a tight ``max_leaves``. Ignored by depthwise.
    pop_batch: int = 1

    def __post_init__(self) -> None:
        if self.grow_policy not in GROW_POLICIES:
            raise ValueError(
                f"grow_policy must be one of {GROW_POLICIES}, got {self.grow_policy!r}"
            )
        if self.max_leaves < 0:
            raise ValueError(f"max_leaves must be >= 0, got {self.max_leaves}")
        if self.pop_batch < 1:
            raise ValueError(f"pop_batch must be >= 1, got {self.pop_batch}")

    @property
    def effective_max_depth(self) -> int:
        """Deepest level any node can reach. A lossguide tree with L leaves
        makes L - 1 splits, so no node can sit deeper than min(max_depth,
        max_leaves - 1) — the node arrays shrink accordingly (a
        ``max_leaves=8`` tree never needs a depth-30 heap)."""
        if self.grow_policy == "lossguide" and self.max_leaves:
            return min(self.max_depth, max(self.max_leaves - 1, 0))
        return self.max_depth

    @property
    def n_total_nodes(self) -> int:
        """Heap-array capacity: complete tree over the *effective* depth."""
        return 2 ** (self.effective_max_depth + 1) - 1

    @property
    def leaf_budget(self) -> int:
        """Max leaves a built tree may have (both policies)."""
        full = 2**self.effective_max_depth
        if self.grow_policy == "lossguide" and self.max_leaves:
            return min(self.max_leaves, full)
        return full


class TreeBuildResult(NamedTuple):
    tree: TreeArrays
    positions: Array  # (n_rows,) final leaf node per training row


# HistFn(offset, count, plan) -> (plan.n_build, m, n_bins, 2)
#
# ``offset``/``count`` locate the node *window* in the complete-tree layout
# (global node ids [offset, offset + count)): a whole level for the depthwise
# driver, the popped node's 2-child window for the lossguide driver. Rows
# positioned outside the window contribute to no bin. ``plan`` is the window's
# `LevelPlan`:
# when ``plan.node_map`` is None the driver wants the full level histogram
# (all ``count`` nodes, plan.n_build == count); otherwise the driver receives
# only the *build subset* — implementations must route each row's level-local
# node id through ``plan.node_map`` (pass it to `ops.build_histogram` /
# `ops.build_histogram_paged`, which do the remap) so rows at derive-set nodes
# contribute to no bin and only ``plan.n_build`` node histograms are
# materialized. When ``plan.build_nodes`` is set (every store-produced plan),
# implementations should prefer the fused path instead: hand the *raw global*
# positions plus ``plan.build_nodes`` to `ops.build_histogram_nodes` — the
# window mask and node_map remap then happen inside one kernel launch, and
# the build set may be non-contiguous (batched lossguide pops, where
# ``count`` spans [offset, offset + count) over several popped parents'
# children). The driver reconstructs derive-set histograms by subtraction
# from the resolved parent before split evaluation; ``plan.source`` records
# how the store resolved that parent (device / fetched from the host tier /
# derived from an ancestor chain) — a "build" plan means nothing resolved and
# the window is rebuilt from rows. HistFn implementations never see the
# tiers: the resolution is entirely the store's concern.
HistFn = Callable[[int, int, LevelPlan], Array]

# PartitionFn(feature, split_bin, default_left, is_leaf, count_level)
#   -> (next_count,) int32 row counts per next-level node, or None
#
# Repartitions every live row to its child node (rows at leaves stay frozen,
# which is also what makes the lossguide driver's per-node repartition work:
# after one pop only the popped node is non-leaf). ``count_level`` is None
# when the driver has no use for row counts (subtraction off, or no histogram
# follows); otherwise it is the next window's ``(offset, count)`` node extent
# — the next level, or the freshly split node's 2-child window — and the
# implementation must return that window's per-node row counts (summed across
# pages/shards — use `core.histcache.level_row_counts`) so the cache can put
# the smaller child of each pair in the build set. Batched lossguide pops
# (``pop_batch > 1``) pass an int32 *array* of global node ids instead of the
# (offset, count) tuple — the popped parents' children are not contiguous —
# and the implementation must return per-node counts in that order (use
# `core.histcache.node_row_counts`).
PartitionFn = Callable[
    [Array, Array, Array, Array, "tuple[int, int] | Array | None"], Array | None
]

# LeafSumsFn() -> (node_g, node_h), each (n_total,) f32
#
# Called once, after the last repartition: the sums of g and h over the rows
# at each node (summed across pages/shards — use
# `core.histcache.node_grad_sums`). The drivers take every leaf weight from
# these.
LeafSumsFn = Callable[[], "tuple[Array, Array]"]


def leaf_values(is_leaf: Array, node_g: Array, node_h: Array, reg_lambda: float) -> Array:
    """Eq.-(6) weight at every reachable leaf (the root, or a child of a
    split node); 0 at internal nodes and unreachable heap slots."""
    parents = (jnp.arange(1, is_leaf.shape[0]) - 1) // 2
    reachable = jnp.concatenate([jnp.ones(1, bool), ~is_leaf[parents]])
    return jnp.where(is_leaf & reachable, leaf_weight(node_g, node_h, reg_lambda), 0.0)


def apply_level_splits(heap: tuple, depth: int, splits: LevelSplits) -> tuple:
    """Write one depthwise level's splits into the heap arrays.

    ``heap`` is ``(feature, split_bin, default_left, is_leaf, node_g,
    node_h)``, each (n_total,); the updated tuple is returned. A node of the
    level splits where ``splits.should_split`` holds and it is still growable
    (the root, or a child of a split node); its children start growable iff
    it split, carrying the split's g/h sums.
    """
    feature, split_bin, default_left, is_leaf, node_g, node_h = heap
    offset, count = 2**depth - 1, 2**depth
    growable = (
        ~jax.lax.dynamic_slice(is_leaf, (offset,), (count,)) if depth else jnp.ones(count, bool)
    )
    do_split = splits.should_split & growable

    idx = offset + jnp.arange(count)
    feature = feature.at[idx].set(jnp.where(do_split, splits.feature, 0))
    split_bin = split_bin.at[idx].set(jnp.where(do_split, splits.split_bin, 0))
    default_left = default_left.at[idx].set(splits.default_left & do_split)
    is_leaf = is_leaf.at[idx].set(~do_split)

    left_idx, right_idx = 2 * idx + 1, 2 * idx + 2
    node_g = node_g.at[left_idx].set(jnp.where(do_split, splits.left_g, 0.0))
    node_h = node_h.at[left_idx].set(jnp.where(do_split, splits.left_h, 0.0))
    node_g = node_g.at[right_idx].set(jnp.where(do_split, splits.right_g, 0.0))
    node_h = node_h.at[right_idx].set(jnp.where(do_split, splits.right_h, 0.0))
    is_leaf = is_leaf.at[left_idx].set(~do_split)
    is_leaf = is_leaf.at[right_idx].set(~do_split)
    return feature, split_bin, default_left, is_leaf, node_g, node_h


def grow_tree_generic(
    hist_fn: HistFn,
    partition_fn: PartitionFn,
    leaf_sums_fn: LeafSumsFn,
    total_g: Array,
    total_h: Array,
    n_bins: int,
    bin_valid: Array,  # (m, n_bins) bool
    params: TreeParams,
    cut_values: np.ndarray | None = None,
    cut_ptrs: np.ndarray | None = None,
    hist_cache: HistogramCache | None = None,
) -> TreeArrays:
    n_total = params.n_total_nodes
    max_depth = params.max_depth
    cache = hist_cache if hist_cache is not None else HistogramCache(
        enabled=params.hist_subtraction
    )
    cache.reset()
    level_counts: Array | None = None

    feature = jnp.zeros(n_total, jnp.int32)
    split_bin = jnp.zeros(n_total, jnp.int32)
    default_left = jnp.zeros(n_total, bool)
    is_leaf = jnp.ones(n_total, bool)
    node_g = jnp.zeros(n_total, jnp.float32).at[0].set(total_g)
    node_h = jnp.zeros(n_total, jnp.float32).at[0].set(total_h)

    for depth in range(max_depth):
        with span(tracing.LEVEL, depth=depth):
            offset = 2**depth - 1
            count = 2**depth
            with span(tracing.HIST):
                plan = cache.plan(count, level_counts)
                built = hist_fn(offset, count, plan)
                hist = cache.expand(plan, built)
            with span(tracing.SPLIT):
                lvl_g = jax.lax.dynamic_slice(node_g, (offset,), (count,))
                lvl_h = jax.lax.dynamic_slice(node_h, (offset,), (count,))
                splits: LevelSplits = evaluate_splits(hist, lvl_g, lvl_h, bin_valid, params.split)
                feature, split_bin, default_left, is_leaf, node_g, node_h = apply_level_splits(
                    (feature, split_bin, default_left, is_leaf, node_g, node_h), depth, splits
                )

            # counts feed the next level's build/derive plan; skip the bincount
            # when no histogram follows (last level) or subtraction is off
            count_level = (
                (2 ** (depth + 1) - 1, 2 ** (depth + 1))
                if cache.enabled and depth + 1 < max_depth
                else None
            )
            with span(tracing.PARTITION):
                level_counts = partition_fn(
                    feature, split_bin, default_left, is_leaf, count_level
                )

    # the last level's nodes are all leaves
    is_leaf = is_leaf.at[2**max_depth - 1:].set(True)
    with span(tracing.LEAF_SUMS):
        leaf_value = leaf_values(is_leaf, *leaf_sums_fn(), params.split.reg_lambda)
    split_value = _finalize_split_values(feature, split_bin, is_leaf, cut_values, cut_ptrs)

    return TreeArrays(
        feature=feature,
        split_bin=split_bin,
        split_value=split_value,
        default_left=default_left,
        is_leaf=is_leaf,
        leaf_value=leaf_value,
    )


class _SplitCandidate(NamedTuple):
    """Frontier entry: one growable leaf's best split, pulled to host scalars
    (best-first ordering is inherently host-driven control flow)."""

    feature: int
    split_bin: int
    default_left: bool
    left_g: float
    left_h: float
    right_g: float
    right_h: float


def _finalize_split_values(
    feature: Array,
    split_bin: Array,
    is_leaf: Array,
    cut_values: np.ndarray | None,
    cut_ptrs: np.ndarray | None,
) -> Array:
    """Raw thresholds for prediction on unquantized features (0 at leaves)."""
    if cut_values is not None and cut_ptrs is not None:
        cut_values_j = jnp.asarray(cut_values)
        cut_ptrs_j = jnp.asarray(cut_ptrs)
        split_value = cut_values_j[cut_ptrs_j[feature] + split_bin]
    else:
        split_value = jnp.zeros(feature.shape[0], jnp.float32)
    return jnp.where(is_leaf, 0.0, split_value)


def grow_tree_lossguide_generic(
    hist_fn: HistFn,
    partition_fn: PartitionFn,
    leaf_sums_fn: LeafSumsFn,
    total_g: Array,
    total_h: Array,
    n_bins: int,
    bin_valid: Array,  # (m, n_bins) bool
    params: TreeParams,
    cut_values: np.ndarray | None = None,
    cut_ptrs: np.ndarray | None = None,
    hist_cache: HistogramCache | None = None,
) -> TreeArrays:
    """Best-first (loss-guided, LightGBM-style) growth over the same
    HistFn/PartitionFn/LeafSumsFn contracts as `grow_tree_generic`.

    A gain-ordered frontier pops the single best candidate leaf and expands
    only it: the split is written into the heap-layout arrays, one
    PartitionFn call repartitions the popped node's rows (every other node is
    still a leaf, so its rows stay frozen — per-node repartition falls out of
    the existing kernel semantics), and one HistFn pass over the 2-node child
    window builds the children's histograms. With subtraction on, the pass
    builds only the smaller child (a per-node `LevelPlan` from
    `HistogramCache.plan_node`) and the sibling is derived from the cached
    parent histogram. Trees stay in the complete-heap array layout, so
    prediction and serialization are unchanged for the resulting non-complete
    trees.

    With ``max_leaves >= 2**effective_max_depth`` and untied gains this
    reproduces the depthwise tree exactly (every positive-gain candidate is
    eventually popped); smaller budgets keep only the highest-gain splits.
    """
    n_total = params.n_total_nodes
    eff_depth = params.effective_max_depth
    max_leaves = params.leaf_budget
    cache = hist_cache if hist_cache is not None else HistogramCache(
        enabled=params.hist_subtraction
    )
    cache.reset()

    feature = jnp.zeros(n_total, jnp.int32)
    split_bin = jnp.zeros(n_total, jnp.int32)
    default_left = jnp.zeros(n_total, bool)
    is_leaf = jnp.ones(n_total, bool)
    node_g = jnp.zeros(n_total, jnp.float32).at[0].set(total_g)
    node_h = jnp.zeros(n_total, jnp.float32).at[0].set(total_h)

    # heap entries (-gain, node, candidate): max-gain first, node id breaks
    # exact gain ties deterministically (heap order matching depthwise's
    # left-to-right sweep)
    frontier: list[tuple[float, int, _SplitCandidate]] = []

    def push_candidates(offset: int, hist: Array, ng: Array, nh: Array) -> None:
        splits: LevelSplits = evaluate_splits(hist, ng, nh, bin_valid, params.split)
        gain = np.asarray(splits.gain)
        should = np.asarray(splits.should_split)
        feat = np.asarray(splits.feature)
        sbin = np.asarray(splits.split_bin)
        dleft = np.asarray(splits.default_left)
        lg, lh = np.asarray(splits.left_g), np.asarray(splits.left_h)
        rg, rh = np.asarray(splits.right_g), np.asarray(splits.right_h)
        for j in range(hist.shape[0]):
            node = offset + j
            if bool(should[j]):
                cand = _SplitCandidate(
                    int(feat[j]), int(sbin[j]), bool(dleft[j]),
                    float(lg[j]), float(lh[j]), float(rg[j]), float(rh[j]),
                )
                heapq.heappush(frontier, (-float(gain[j]), node, cand))
                # the store spills coldest-first: frontier gain is the heat
                cache.note_gain(node, float(gain[j]))
            else:
                cache.discard_node(node)  # permanent leaf

    n_leaves = 1
    if eff_depth >= 1 and max_leaves >= 2:
        with span(tracing.LEVEL, pop=0):
            with span(tracing.HIST):
                root_hist = hist_fn(
                    0, 1,
                    LevelPlan(
                        node_map=None, n_build=1, count=1,
                        build_nodes=jnp.zeros(1, jnp.int32),
                    ),
                )
                cache.put_node(0, root_hist[0])
            with span(tracing.SPLIT):
                push_candidates(0, root_hist, node_g[:1], node_h[:1])

    pop_batch = max(1, params.pop_batch)
    pops = 0
    while frontier and n_leaves < max_leaves:
        pops += 1
        with span(tracing.LEVEL, pop=pops):
            # pop up to pop_batch frontier leaves; their splits are written
            # together so ONE repartition pass moves every popped node's rows and
            # (when any is expandable) ONE histogram pass covers all their child
            # windows — out-of-core, that is one PageStream pass per batch
            # instead of one per pop
            with span(tracing.SPLIT):
                batch: list[tuple[int, bool]] = []
                while frontier and len(batch) < pop_batch and n_leaves < max_leaves:
                    _, node, cand = heapq.heappop(frontier)
                    left, right = 2 * node + 1, 2 * node + 2
                    feature = feature.at[node].set(cand.feature)
                    split_bin = split_bin.at[node].set(cand.split_bin)
                    default_left = default_left.at[node].set(cand.default_left)
                    is_leaf = is_leaf.at[node].set(False)
                    node_g = node_g.at[left].set(cand.left_g)
                    node_h = node_h.at[left].set(cand.left_h)
                    node_g = node_g.at[right].set(cand.right_g)
                    node_h = node_h.at[right].set(cand.right_h)
                    n_leaves += 1
                    # children sit at depth(node) + 1 == (node+1).bit_length(); they
                    # can only split if their own children still fit under eff_depth
                    expandable = (node + 1).bit_length() < eff_depth and n_leaves < max_leaves
                    batch.append((node, expandable))

                # parents sorted ascending: the batch plan's slot order then follows
                # global node order, deterministically across builders
                parents = sorted(node for node, expandable in batch if expandable)
                for node, expandable in batch:
                    if not expandable:
                        cache.discard_node(node)

            # per-node repartition: only the popped nodes' rows move (all other
            # nodes are leaves, so their rows stay frozen); the child row counts
            # feed the build/derive choice
            if parents and cache.enabled:
                count_window = (
                    (2 * parents[0] + 1, 2)
                    if len(parents) == 1
                    else jnp.asarray(
                        [2 * p + 1 + c for p in parents for c in (0, 1)], jnp.int32
                    )
                )
            else:
                count_window = None
            with span(tracing.PARTITION):
                counts = partition_fn(feature, split_bin, default_left, is_leaf, count_window)

            if len(parents) == 1:
                # single pop: exactly the strictly-best-first per-node path
                node = parents[0]
                left = 2 * node + 1
                with span(tracing.HIST):
                    plan = cache.plan_node(node, counts)
                    built = hist_fn(left, 2, plan)
                    child_hist = cache.expand_node(node, plan, built)
                with span(tracing.SPLIT):
                    push_candidates(left, child_hist, node_g[left:left + 2], node_h[left:left + 2])
            elif parents:
                lo = 2 * parents[0] + 1
                span_nodes = 2 * parents[-1] + 2 - lo + 1
                with span(tracing.HIST):
                    plan = cache.plan_nodes(parents, counts)
                    built = hist_fn(lo, span_nodes, plan)
                    child_hist = cache.expand_nodes(parents, plan, built)
                with span(tracing.SPLIT):
                    for i, node in enumerate(parents):
                        left = 2 * node + 1
                        push_candidates(
                            left, child_hist[2 * i:2 * i + 2],
                            node_g[left:left + 2], node_h[left:left + 2],
                        )

    # budget exhausted: pending frontier nodes stay leaves
    for _, node, _ in frontier:
        cache.discard_node(node)

    with span(tracing.LEAF_SUMS):
        leaf_value = leaf_values(is_leaf, *leaf_sums_fn(), params.split.reg_lambda)
    split_value = _finalize_split_values(feature, split_bin, is_leaf, cut_values, cut_ptrs)

    return TreeArrays(
        feature=feature,
        split_bin=split_bin,
        split_value=split_value,
        default_left=default_left,
        is_leaf=is_leaf,
        leaf_value=leaf_value,
    )


def tree_growth_driver(params: TreeParams):
    """The generic driver for ``params.grow_policy`` — both drivers share the
    HistFn/PartitionFn/LeafSumsFn contracts, so every builder dispatches through here."""
    if params.grow_policy == "lossguide":
        return grow_tree_lossguide_generic
    return grow_tree_generic


def grow_tree(
    bins: Array,  # (n_rows, m) int32 quantized features
    g: Array,  # (n_rows,) f32 (already sample-weighted)
    h: Array,  # (n_rows,) f32
    n_bins: int,
    bin_valid: Array,
    params: TreeParams,
    cut_values: np.ndarray | None = None,
    cut_ptrs: np.ndarray | None = None,
    impl: str = "auto",
    hist_cache: HistogramCache | None = None,
) -> TreeBuildResult:
    """In-core builder (paper Alg. 1; best-first when
    ``params.grow_policy == "lossguide"``): one device-resident ELLPACK page."""
    n_rows = bins.shape[0]
    pos_box = [jnp.zeros(n_rows, jnp.int32)]
    # level-invariant precompute for the host contraction (None on kernel /
    # oracle paths or when too large — then each call computes it inline)
    bin_oh = ops.prepare_bin_onehot(bins, n_bins, impl=impl)

    def hist_fn(offset: int, count: int, plan: LevelPlan) -> Array:
        pos = pos_box[0]
        if plan.build_nodes is not None:
            # fused fast path: window mask + node_map remap happen inside the
            # kernel (one launch), raw global positions go straight in
            return ops.build_histogram_nodes(
                bins, g, h, pos, plan.build_nodes, n_bins, impl=impl,
                bin_onehot=bin_oh,
            )
        # rows outside [offset, offset + plan.count) — frozen at shallower
        # leaves, or live at other heap nodes during a per-node pass — hit no bin
        level_pos = jnp.where(
            (pos >= offset) & (pos < offset + plan.count), pos - offset, -1
        )
        return ops.build_histogram(
            bins, g, h, level_pos, plan.n_build, n_bins,
            node_map=plan.node_map, impl=impl,
        )

    def partition_fn(feature, split_bin, default_left, is_leaf, count_level):
        pos_box[0] = ops.partition_rows(
            bins, pos_box[0], feature, split_bin, default_left, is_leaf, impl=impl
        )
        if count_level is None:
            return None
        if isinstance(count_level, tuple):
            return level_row_counts(pos_box[0], *count_level)
        return node_row_counts(pos_box[0], count_level)  # batched pops

    tree = tree_growth_driver(params)(
        hist_fn,
        partition_fn,
        lambda: node_grad_sums(pos_box[0], g, h, params.n_total_nodes),
        jnp.sum(g),
        jnp.sum(h),
        n_bins,
        bin_valid,
        params,
        cut_values,
        cut_ptrs,
        hist_cache=hist_cache,
    )
    return TreeBuildResult(tree=tree, positions=pos_box[0])


def predict_tree_bins(tree: TreeArrays, bins: Array, max_depth: int) -> Array:
    """Predict one tree over quantized rows."""
    return ops.predict_bins(
        bins,
        tree.feature,
        tree.split_bin,
        tree.default_left,
        tree.is_leaf,
        tree.leaf_value,
        max_depth,
    )


def predict_tree_raw(tree: TreeArrays, X: Array, max_depth: int) -> Array:
    """Predict one tree over raw (unquantized) features using stored thresholds."""
    n_rows = X.shape[0]
    pos = jnp.zeros(n_rows, jnp.int32)

    def step(pos, _):
        f_idx = tree.feature[pos]
        x = jnp.take_along_axis(X, f_idx[:, None], axis=1)[:, 0]
        missing = jnp.isnan(x)
        go_left = jnp.where(missing, tree.default_left[pos], x <= tree.split_value[pos])
        child = 2 * pos + 1 + jnp.where(go_left, 0, 1)
        return jnp.where(tree.is_leaf[pos], pos, child), None

    pos, _ = jax.lax.scan(step, pos, None, length=max_depth)
    return tree.leaf_value[pos]


def stack_trees(trees: list[TreeArrays]) -> TreeArrays:
    """Stack a forest into one TreeArrays with a leading tree axis."""
    return TreeArrays(*[jnp.stack(x) for x in zip(*trees)])


def predict_forest_raw(
    forest: TreeArrays, X: Array, max_depth: int, learning_rate: float, base_margin: float
) -> Array:
    """Sum of per-tree predictions (eq. 1), vmapped over the forest axis."""
    per_tree = jax.vmap(lambda t: predict_tree_raw(t, X, max_depth))(forest)
    return base_margin + learning_rate * jnp.sum(per_tree, axis=0)
