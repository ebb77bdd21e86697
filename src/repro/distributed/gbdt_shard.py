"""Distributed GBDT tree construction (paper §2.2: histogram AllReduce).

Parallelism axes (all optional, compose):
  * rows sharded over the data axes ("pod", "data") — each device builds a
    local gradient histogram, summed with `lax.psum` (the paper's AllReduce);
  * features sharded over the "model" axis — feature-parallel split search:
    every model shard evaluates its own feature slice, candidates are
    all-gathered (a few hundred bytes per node) and arg-maxed globally; the
    owning shard broadcasts the per-row left/right decision via psum.

Distributed-optimization tricks:
  * histogram subtraction (default on, `DistConfig.hist_subtraction`): per
    level only the smaller child of each split pair is built locally and
    psum'd — HALF the dominant collective's payload — and every sibling is
    derived post-reduce as parent - built from the previous level's psum'd
    histogram (see `core/histcache.py`; build/derive choice uses exact psum'd
    row counts so all shards and the single-device builder agree);
  * histogram gradient compression: psum payload cast to bf16 (halves the
    dominant collective; beyond-paper, toggleable, default off, composes with
    subtraction for a 4x total reduction — note the composition compounds
    bf16 rounding through the level-by-level derivation chain, so split
    agreement with the f32 full build loosens with depth; the 8-device test
    pins >95% agreement at depth 4);
  * per-level single collective: the histogram psum is the only data-sized
    collective per level; split search and partition exchange O(nodes) and
    O(rows/shard) bytes respectively.

Everything here is shard_map-first: `make_gbdt_step_fn` returns a jit-able
function over a Mesh, used both for real execution and the multi-pod dry-run.

Compile once: `grow_tree_distributed` keeps one compiled depthwise tree
program per (mesh, resolved TreeParams, DistConfig, n_bins, input shapes and
dtypes), so every tree of a `fit_sharded` fit, and every later fit with the
same arguments, runs the same executable. The bytes each shard passes into
that program's collectives (histogram psums, row counts, leaf and root sums,
feature-parallel candidates and routing; the narrowed bytes under a bf16 or
f16 ``grad_transport``) are counted from the operands' static shapes when it
traces, and `TransferStats.collective_bytes` gains them once per tree.

Out-of-core + distributed (`grow_tree_distributed_paged`): ELLPACK pages
stream through `repro.pipeline.PageStream` with a *sharded* device put, so
each staged page lands row-sharded over the data axes and the per-page
histogram reduces across the mesh under jit — the paper's §2.2 AllReduce
composed with its §2.3 paging.

Growth policy: `DistConfig(grow_policy="lossguide", max_leaves=...)` (or the
same fields on `TreeParams`) switches `grow_tree_distributed` /
`grow_tree_distributed_paged` to host-driven best-first growth — see
`_grow_tree_distributed_lossguide`; `make_gbdt_step_fn` stays depthwise-only
because its whole boosting step is one closed SPMD program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import tracing
from repro.core.booster import BoosterParams, GradientBooster, _Engine
from repro.core.histcache import (
    HistogramStore,
    expand_level,
    level_row_counts,
    node_grad_sums,
    plan_level,
)
from repro.core.policy import ExecutionPolicy
from repro.core.sampling import sample
from repro.core.split import LevelSplits, evaluate_splits, worth_splitting
from repro.core.tree import (
    TreeArrays,
    TreeParams,
    apply_level_splits,
    grow_tree_lossguide_generic,
    leaf_values,
)
from repro.kernels import ops, ref
from repro.tracing import span

Array = jax.Array

_shard_map = functools.partial(jax.shard_map, check_vma=False)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    data_axes: tuple[str, ...] = ("data",)  # row sharding (+"pod" multi-pod)
    feature_axis: str | None = None  # "model" for feature-parallel split search
    hist_dtype: str = "float32"  # "bfloat16" -> compressed histogram psum
    kernel_impl: str = "auto"
    hist_subtraction: bool = True  # psum only the built half, derive siblings
    # growth-policy override: None inherits from TreeParams; "lossguide"
    # switches to the host-driven best-first build (see
    # `_grow_tree_distributed_lossguide`); max_leaves likewise overrides the
    # TreeParams leaf budget when set
    grow_policy: str | None = None
    max_leaves: int | None = None
    # tiered HistogramStore knobs for the host-driven builders (the paged
    # depthwise build and the best-first frontier): a device byte budget
    # spills cold post-psum histograms to host, K >= 2 retains ancestors for
    # multi-level derivation. The store lives on the driving host and only
    # ever sees psum'd histograms and psum'd row counts, so spill decisions
    # are made once from state every shard shares — the psum payload is still
    # only the built half of each level/window.
    hist_budget_bytes: int | None = None
    hist_retained_levels: int = 1
    # wire transport for the cross-shard histogram psum (repro.compress
    # GradQuantizer): "raw" (f32, bit-for-bit), "f16" or "bf16" (half the
    # all-reduce bytes). Supersedes the legacy hist_dtype="bfloat16" knob
    # (still honored when grad_transport is "raw"). "int8" is rejected here
    # — integer partial sums overflow across shards — use it on the
    # HistogramStore spill transport instead.
    grad_transport: str = "raw"
    # lossless page codec for sharded staging (repro.compress): "bitpack"
    # stages the packed wire payload to every shard and expands on device.
    # Device-decodable codecs require feature_axis=None (packed bytes can
    # only be row-sharded; a byte does not split across feature shards).
    page_codec: str = "raw"

    def __post_init__(self) -> None:
        from repro.compress import GradQuantizer, get_codec, make_transport

        get_codec(self.page_codec)
        GradQuantizer.resolve(self.grad_transport).psum_cast  # mode check
        if self.grad_transport not in ("raw", "f16", "bf16"):
            raise ValueError(
                f"DistConfig(grad_transport={self.grad_transport!r}) cannot "
                "back the histogram psum: int8 partial sums overflow across "
                "shards. Use 'f16'/'bf16' here, and point 'int8' at the "
                "spill transport (ExecutionPolicy(grad_transport='int8'))"
            )
        if make_transport(self.page_codec) is not None and self.feature_axis is not None:
            raise ValueError(
                f"DistConfig(page_codec={self.page_codec!r}) stages packed "
                "bytes, which only shard by rows; feature_axis="
                f"{self.feature_axis!r} would split symbols mid-byte. Drop "
                "feature_axis or use page_codec='raw'"
            )

    @property
    def grad_quantizer(self):
        """The psum transport, folding in the legacy hist_dtype knob."""
        from repro.compress import GradQuantizer

        if self.grad_transport == "raw" and self.hist_dtype == "bfloat16":
            return GradQuantizer("bf16")
        return GradQuantizer(self.grad_transport)

    @property
    def all_axes(self) -> tuple[str, ...]:
        return self.data_axes + ((self.feature_axis,) if self.feature_axis else ())

    def resolve_tree_params(self, tp: TreeParams) -> TreeParams:
        """TreeParams with this config's grow_policy/max_leaves overrides."""
        kw = {}
        if self.grow_policy is not None:
            kw["grow_policy"] = self.grow_policy
        if self.max_leaves is not None:
            kw["max_leaves"] = self.max_leaves
        return dataclasses.replace(tp, **kw) if kw else tp


def check_feature_parallel_lossguide(tp: TreeParams, cfg: DistConfig) -> None:
    """Feature-parallel + lossguide is an unimplemented combination; fail fast
    with an actionable message instead of a mid-build shard_map error."""
    if tp.grow_policy == "lossguide" and cfg.feature_axis is not None:
        raise NotImplementedError(
            f"feature-parallel lossguide growth is not implemented: DistConfig("
            f"feature_axis={cfg.feature_axis!r}, grow_policy='lossguide') would "
            "need the host-driven best-first frontier to all-gather per-node "
            "split candidates across feature shards on every pop. Either drop "
            "feature_axis (row-parallel lossguide is supported) or use "
            "grow_policy='depthwise' (feature-parallel split search is "
            "depthwise-only). Tracked as a ROADMAP open item."
        )


class _Collectives:
    """The cross-shard collectives of one traced program, and the bytes each
    shard passes into them, summed from the operands' static shapes."""

    def __init__(self) -> None:
        self.nbytes = 0

    def _count(self, x) -> None:
        self.nbytes += sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(x))

    def psum(self, x, axes):
        self._count(x)
        return jax.lax.psum(x, axes)

    def all_gather(self, x, axis):
        self._count(x)
        return jax.lax.all_gather(x, axis)


def _psum_hist(hist: Array, cfg: DistConfig, psum=jax.lax.psum) -> Array:
    q = cfg.grad_quantizer
    out = psum(q.psum_cast(hist), cfg.data_axes)
    return q.psum_restore(out)


def _global_best(splits, local_m: int, cfg: DistConfig, coll: _Collectives) -> LevelSplits:
    """All-gather per-shard best candidates over the feature axis and arg-max:
    each node's global best split."""
    ax = cfg.feature_axis
    shard = jax.lax.axis_index(ax)
    cand = jnp.stack(
        [
            splits.gain,
            (splits.feature + shard * local_m).astype(jnp.float32),
            splits.split_bin.astype(jnp.float32),
            splits.default_left.astype(jnp.float32),
            splits.left_g,
            splits.left_h,
            splits.right_g,
            splits.right_h,
        ],
        axis=0,
    )  # (8, n_nodes)
    allc = coll.all_gather(cand, ax)  # (n_shards, 8, n_nodes)
    best_shard = jnp.argmax(allc[:, 0, :], axis=0)  # (n_nodes,)
    picked = jnp.take_along_axis(allc, best_shard[None, None, :], axis=0)[0]  # (8, n_nodes)
    gain = picked[0]
    return LevelSplits(
        gain=gain,
        feature=picked[1].astype(jnp.int32),
        split_bin=picked[2].astype(jnp.int32),
        default_left=picked[3] > 0.5,
        left_g=picked[4],
        left_h=picked[5],
        right_g=picked[6],
        right_h=picked[7],
        should_split=worth_splitting(gain),
    )


def _grow_tree_local(
    bins: Array,  # (local_rows, local_m) int32 shard of the ELLPACK page
    g: Array,  # (local_rows,)
    h: Array,  # (local_rows,)
    n_bins: int,
    bin_valid: Array,  # (local_m, n_bins)
    tp: TreeParams,
    cfg: DistConfig,
    cut_values: Array | None,  # (total_cuts,) for raw thresholds (global)
    cut_ptrs: Array | None,
    coll: _Collectives,
) -> tuple[TreeArrays, Array]:
    """The shard-local body run under shard_map. Returns (tree, positions).
    Every cross-shard collective goes through ``coll``, which counts its
    bytes."""
    n_total = tp.n_total_nodes
    max_depth = tp.max_depth
    local_rows, local_m = bins.shape

    feature = jnp.zeros(n_total, jnp.int32)
    split_bin = jnp.zeros(n_total, jnp.int32)
    default_left = jnp.zeros(n_total, bool)
    is_leaf = jnp.ones(n_total, bool)
    total_g = coll.psum(jnp.sum(g), cfg.data_axes)
    total_h = coll.psum(jnp.sum(h), cfg.data_axes)
    node_g = jnp.zeros(n_total, jnp.float32).at[0].set(total_g)
    node_h = jnp.zeros(n_total, jnp.float32).at[0].set(total_h)
    positions = jnp.zeros(local_rows, jnp.int32)
    prev_hist = None  # previous level's full post-psum histogram
    level_counts = None  # psum'd per-node row counts for the current level

    for depth in range(max_depth):
        offset = 2**depth - 1
        count = 2**depth
        level_pos = jnp.where(positions >= offset, positions - offset, -1)
        subtract = (
            cfg.hist_subtraction
            and tp.hist_subtraction
            and prev_hist is not None
            and level_counts is not None
        )
        if subtract:
            # build + psum only the smaller child of each pair (half the
            # AllReduce payload); derive siblings from the cached parent level
            node_map, build_left = plan_level(count, level_counts)
            built_local = ops.build_histogram(
                bins, g, h, level_pos, count // 2, n_bins,
                node_map=node_map, impl=cfg.kernel_impl,
            )
            built = _psum_hist(built_local, cfg, coll.psum)  # the paper's AllReduce, halved
            hist = expand_level(prev_hist, built, build_left)
        else:
            hist_local = ops.build_histogram(
                bins, g, h, level_pos, count, n_bins, impl=cfg.kernel_impl
            )
            hist = _psum_hist(hist_local, cfg, coll.psum)  # the paper's AllReduce
        prev_hist = hist

        lvl_g = jax.lax.dynamic_slice(node_g, (offset,), (count,))
        lvl_h = jax.lax.dynamic_slice(node_h, (offset,), (count,))
        splits = evaluate_splits(hist, lvl_g, lvl_h, bin_valid, tp.split)

        if cfg.feature_axis is not None:
            splits = _global_best(splits, local_m, cfg, coll)
        feature, split_bin, default_left, is_leaf, node_g, node_h = apply_level_splits(
            (feature, split_bin, default_left, is_leaf, node_g, node_h), depth, splits
        )

        # ---- partition local rows ----
        if cfg.feature_axis is None:
            positions = ops.partition_rows(
                bins, positions, feature, split_bin, default_left, is_leaf,
                impl=cfg.kernel_impl,
            )
        else:
            # feature-parallel: the shard owning the split feature computes
            # the left/right decision; psum broadcasts it to every shard.
            shard = jax.lax.axis_index(cfg.feature_axis)
            active = positions >= 0
            safe = jnp.where(active, positions, 0)
            gf = feature[safe]  # global feature of my node
            lf = gf - shard * local_m
            owner = (lf >= 0) & (lf < local_m)
            bval = jnp.take_along_axis(bins, jnp.clip(lf, 0, local_m - 1)[:, None], axis=1)[:, 0]
            missing = bval == ref.MISSING_BIN
            go_left_local = jnp.where(missing, default_left[safe], bval <= split_bin[safe])
            go_left = coll.psum(
                jnp.where(owner, go_left_local.astype(jnp.int32), 0), cfg.feature_axis
            ) > 0
            child = 2 * positions + 1 + jnp.where(go_left, 0, 1)
            leaf_here = is_leaf[safe]
            positions = jnp.where(
                active, jnp.where(leaf_here, positions, child), -1
            ).astype(jnp.int32)

        # exact global row counts drive the next level's build/derive plan
        # (identical on every shard, and to the single-device builder's)
        if cfg.hist_subtraction and tp.hist_subtraction and depth + 1 < max_depth:
            noff, ncnt = 2 ** (depth + 1) - 1, 2 ** (depth + 1)
            level_counts = coll.psum(
                level_row_counts(positions, noff, ncnt), cfg.data_axes
            )

    # the last level's nodes are all leaves; their weights come from the
    # rows that end there, summed over the data shards
    is_leaf = is_leaf.at[2**max_depth - 1:].set(True)
    sums = coll.psum(node_grad_sums(positions, g, h, n_total), cfg.data_axes)
    leaf_value = leaf_values(is_leaf, *sums, tp.split.reg_lambda)

    if cut_values is not None and cut_ptrs is not None:
        split_value = cut_values[cut_ptrs[feature] + split_bin]
    else:
        split_value = jnp.zeros(n_total, jnp.float32)
    split_value = jnp.where(is_leaf, 0.0, split_value)

    tree = TreeArrays(feature, split_bin, split_value, default_left, is_leaf, leaf_value)
    return tree, positions


def _grow_tree_distributed_lossguide(
    mesh: Mesh,
    bins: Array,
    g: Array,
    h: Array,
    n_bins: int,
    bin_valid: Array,
    tp: TreeParams,
    cfg: DistConfig,
    cut_values=None,
    cut_ptrs=None,
    transfer_stats=None,
) -> tuple[TreeArrays, Array]:
    """Best-first distributed build: host-driven frontier over shard_map'd
    per-pass kernels.

    Best-first growth is inherently host-driven (the next node to expand
    depends on data), so unlike `_grow_tree_local` the frontier loop cannot
    live inside one shard_map program. Instead each per-node pass is its own
    jit'd SPMD step: every shard builds its local histogram for the popped
    node's 2-child window and the psum carries ONLY the built slots — one
    (1, m, n_bins, 2) payload per pop with subtraction on, half the depthwise
    per-pair payload — while the sibling is derived host-side from the cached
    parent. Row counts psum once per pop to keep the build/derive choice
    identical on every shard (and to the single-device builder's).
    """
    check_feature_parallel_lossguide(tp, cfg)
    if tp.pop_batch != 1:
        # the compiled per-pop SPMD step set covers contiguous 2-child
        # windows only ((window, n_build) in {(1,1),(2,1),(2,2)}); batched
        # non-contiguous pops would compile a fresh step per batch shape.
        # Pin single pops here — the paged distributed builder (which shares
        # `build_tree_paged`) does honor pop_batch.
        tp = dataclasses.replace(tp, pop_batch=1)
    bins_spec = P(cfg.data_axes, None)
    vec_spec = P(cfg.data_axes)
    rep = P()
    g_j, h_j = jnp.asarray(g), jnp.asarray(h)
    pos_box = [jnp.zeros(bins.shape[0], jnp.int32)]
    step_cache: dict[tuple[int, int], Callable] = {}

    def hist_step(window: int, n_build: int) -> Callable:
        # one compiled SPMD step per (window, n_build) in {(1,1),(2,1),(2,2)};
        # offset is traced so pops at different heap nodes share the program
        if (window, n_build) not in step_cache:

            def body(bins_l, g_l, h_l, pos_l, node_map, offset):
                lp = jnp.where(
                    (pos_l >= offset) & (pos_l < offset + window), pos_l - offset, -1
                )
                built = ops.build_histogram(
                    bins_l, g_l, h_l, lp, n_build, n_bins,
                    node_map=node_map, impl=cfg.kernel_impl,
                )
                return _psum_hist(built, cfg)  # AllReduce of built slots only

            fn = _shard_map(
                body, mesh=mesh,
                in_specs=(bins_spec, vec_spec, vec_spec, vec_spec, rep, rep),
                out_specs=rep,
            )
            step_cache[(window, n_build)] = jax.jit(fn)
        return step_cache[(window, n_build)]

    def part_body(bins_l, pos_l, feature, split_bin, default_left, is_leaf, offset):
        new_pos = ops.partition_rows(
            bins_l, pos_l, feature, split_bin, default_left, is_leaf,
            impl=cfg.kernel_impl,
        )
        counts = jax.lax.psum(level_row_counts(new_pos, offset, 2), cfg.data_axes)
        return new_pos, counts

    part_step = jax.jit(_shard_map(
        part_body, mesh=mesh,
        in_specs=(bins_spec, vec_spec, rep, rep, rep, rep, rep),
        out_specs=(vec_spec, rep),
    ))
    sums_step = jax.jit(_shard_map(
        lambda pos_l, g_l, h_l: jax.lax.psum(
            node_grad_sums(pos_l, g_l, h_l, tp.n_total_nodes), cfg.data_axes
        ),
        mesh=mesh, in_specs=(vec_spec, vec_spec, vec_spec), out_specs=rep,
    ))

    def hist_fn(offset, count, plan):
        node_map = (
            jnp.arange(plan.count, dtype=jnp.int32)  # full build: identity map
            if plan.node_map is None
            else plan.node_map
        )
        step = hist_step(plan.count, plan.n_build)
        return step(bins, g_j, h_j, pos_box[0], node_map, jnp.int32(offset))

    def partition_fn(feature, split_bin, default_left, is_leaf, count_level):
        offset = count_level[0] if count_level is not None else 0
        pos_box[0], counts = part_step(
            bins, pos_box[0], feature, split_bin, default_left, is_leaf,
            jnp.int32(offset),
        )
        return counts if count_level is not None else None

    cache = HistogramStore(
        enabled=cfg.hist_subtraction and tp.hist_subtraction,
        budget_bytes=cfg.hist_budget_bytes,
        retained_levels=cfg.hist_retained_levels,
        transfer_stats=transfer_stats,
        grad_transport=cfg.grad_transport,  # narrows spill/fetch wires too
    )
    tree = grow_tree_lossguide_generic(
        hist_fn, partition_fn, lambda: sums_step(pos_box[0], g_j, h_j),
        jnp.sum(g_j), jnp.sum(h_j), n_bins, bin_valid,
        tp, cut_values, cut_ptrs, hist_cache=cache,
    )
    return tree, pos_box[0]


def make_gbdt_step_fn(
    mesh: Mesh,
    tp: TreeParams,
    n_bins: int,
    cfg: DistConfig,
    learning_rate: float = 0.3,
    objective: str = "binary:logistic",
    sampling_f: float = 1.0,
):
    """One full boosting iteration as a single jit-able SPMD program.

    margin -> (g, h) -> MVS-style gradient masking -> distributed tree build
    -> margin update. Used by the distributed trainer and the multi-pod
    dry-run (this is the paper technique's "train_step").

    Depthwise only: best-first growth is host-driven control flow and cannot
    be closed over by one SPMD program — use `grow_tree_distributed` /
    `grow_tree_distributed_paged` with ``grow_policy="lossguide"`` instead.
    """
    from repro.core.objectives import get_objective
    from repro.core.sampling import SamplingConfig, sample

    tp = cfg.resolve_tree_params(tp)
    if tp.grow_policy == "lossguide":
        raise NotImplementedError(
            "make_gbdt_step_fn compiles the whole boosting step into one SPMD "
            "program; lossguide growth is host-driven — build trees with "
            "grow_tree_distributed or grow_tree_distributed_paged instead"
        )

    obj = get_objective(objective)
    row_spec = P(cfg.data_axes, cfg.feature_axis)
    vec_spec = P(cfg.data_axes)
    rep = P()

    samp = (
        SamplingConfig(method="mvs", f=sampling_f) if sampling_f < 1.0 else SamplingConfig()
    )

    def local_step(bins, margin, labels, bin_valid, cut_values, cut_ptrs, key):
        g, h = obj.grad_hess(margin, labels)
        if samp.method != "none":
            # per-shard MVS with a per-shard key fold: threshold from local
            # shard (size-proportional, unbiased in expectation)
            shard_key = key
            for ax in cfg.data_axes:
                shard_key = jax.random.fold_in(shard_key, jax.lax.axis_index(ax))
            mask, w = sample(shard_key, g, h, samp)
            scale = jnp.where(mask, w, 0.0)
            g, h = g * scale, h * scale
        tree, positions = _grow_tree_local(
            bins, g, h, n_bins, bin_valid, tp, cfg, cut_values, cut_ptrs, _Collectives()
        )
        new_margin = margin + learning_rate * tree.leaf_value[positions]
        return new_margin, tree

    bv_spec = P(cfg.feature_axis) if cfg.feature_axis else rep
    shard_fn = _shard_map(
        local_step,
        mesh=mesh,
        in_specs=(row_spec, vec_spec, vec_spec, bv_spec, rep, rep, rep),
        out_specs=(vec_spec, rep),
    )
    return jax.jit(shard_fn)


@dataclasses.dataclass
class _TreeProgram:
    """One compiled depthwise SPMD tree program and the bytes each shard
    passes into its collectives per call (set when it first traces)."""

    fn: Callable
    collective_bytes: int = 0


@functools.lru_cache(maxsize=32)
def _tree_program(
    mesh: Mesh, tp: TreeParams, cfg: DistConfig, n_bins: int, avals: tuple
) -> _TreeProgram:
    """The tree program for one (mesh, TreeParams, DistConfig, n_bins, input
    shapes and dtypes): built once and reused by every later call with the
    same arguments, so a fit compiles it once and later fits load nothing."""
    del avals  # part of the cache key only: one program, one shape
    row_spec = P(cfg.data_axes, cfg.feature_axis)
    vec_spec = P(cfg.data_axes)
    rep = P()
    program = _TreeProgram(fn=None)

    def body(bins, g, h, bin_valid, cut_values, cut_ptrs):
        coll = _Collectives()
        out = _grow_tree_local(
            bins, g, h, n_bins, bin_valid, tp, cfg, cut_values, cut_ptrs, coll
        )
        program.collective_bytes = coll.nbytes
        return out

    bv_spec = P(cfg.feature_axis) if cfg.feature_axis else rep
    program.fn = jax.jit(_shard_map(
        body,
        mesh=mesh,
        in_specs=(row_spec, vec_spec, vec_spec, bv_spec, rep, rep),
        out_specs=(rep, vec_spec),
    ))
    return program


def grow_tree_distributed(
    mesh: Mesh,
    bins: Array,
    g: Array,
    h: Array,
    n_bins: int,
    bin_valid: Array,
    tp: TreeParams,
    cfg: DistConfig,
    cut_values=None,
    cut_ptrs=None,
    transfer_stats=None,
):
    """Build one tree with rows/features sharded over the mesh.

    The depthwise build is one SPMD program (`_grow_tree_local` under
    shard_map), compiled once per (mesh, resolved TreeParams, DistConfig,
    n_bins, input shapes and dtypes) and reused by every later tree with the
    same arguments. Each call adds the bytes each shard passes into that
    program's collectives (histogram psums, row counts, leaf and root sums,
    feature-parallel candidates and routing) to
    ``transfer_stats.collective_bytes``; they are counted from the operands'
    static shapes when the program traces, so counting syncs nothing.

    ``transfer_stats`` is also the `TransferStats` sink for the host-driven
    lossguide build's histogram spill/fetch traffic (see
    ``DistConfig.hist_budget_bytes``); its per-pop collectives are not
    counted.
    """
    tp = cfg.resolve_tree_params(tp)
    check_feature_parallel_lossguide(tp, cfg)
    if tp.grow_policy == "lossguide":
        return _grow_tree_distributed_lossguide(
            mesh, bins, g, h, n_bins, bin_valid, tp, cfg, cut_values, cut_ptrs,
            transfer_stats=transfer_stats,
        )
    cut_values = jnp.zeros(1, jnp.float32) if cut_values is None else jnp.asarray(cut_values)
    cut_ptrs = jnp.zeros(1, jnp.int32) if cut_ptrs is None else jnp.asarray(cut_ptrs)
    args = (bins, g, h, bin_valid, cut_values, cut_ptrs)
    avals = tuple((a.shape, str(a.dtype)) for a in args)
    program = _tree_program(mesh, tp, cfg, n_bins, avals)
    out = program.fn(*args)
    if transfer_stats is not None:
        transfer_stats.collective_bytes += program.collective_bytes
    return out


def sharded_page_put(mesh: Mesh, cfg: DistConfig) -> Callable[[np.ndarray], Array]:
    """Device-put for `repro.pipeline.PageStream`: stage a page row-sharded
    over the data axes (uint8 over the wire, int32 on device).

    The page is placed on an Auto-typed view of ``mesh``: the paged builder
    mixes these pages with replicated per-page positions and node tables,
    and leaves the sharding of each result to the compiler."""
    auto = Mesh(mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * mesh.devices.ndim)
    sharding = NamedSharding(auto, P(cfg.data_axes))

    def put(arr: np.ndarray) -> Array:
        out = jax.device_put(arr, sharding)
        return out if arr.dtype == np.int32 else out.astype(jnp.int32)

    return put


def grow_tree_distributed_paged(
    mesh: Mesh,
    make_stream: Callable[[], "object"],
    page_extents: Sequence[tuple[int, int]],
    g: Array,
    h: Array,
    n_bins: int,
    bin_valid: Array,
    tp: TreeParams,
    cfg: DistConfig,
    cut_values=None,
    cut_ptrs=None,
    page_skipping: bool = True,
    transfer_stats=None,
) -> tuple[TreeArrays, Array]:
    """Out-of-core distributed build: one tree over pages that never all sit
    in device memory, rows of each staged page sharded over `cfg.data_axes`.

    ``make_stream()`` starts one `repro.pipeline.PageStream` pass (build it
    with ``put=sharded_page_put(mesh, cfg)`` so staging lands sharded; the
    double-buffered puts then overlap the sharded histogram kernels).
    ``page_extents`` is (row_offset, n_rows) per page in stream order — e.g.
    ``PageSet.page_extents``. Gradient vectors stay replicated; each per-page
    histogram reduces across the mesh under jit (the §2.2 AllReduce), so the
    level-wise split search is identical to the single-device one — it IS the
    single-device one: `core.outofcore.build_tree_paged`, with mesh placement
    supplied entirely by the stream's put. Histogram subtraction (on unless
    either `cfg` or `tp` disables it) shrinks every per-page histogram pass to
    the build half of the level. With ``grow_policy="lossguide"`` (from `cfg`
    or `tp`) the paged build runs best-first: one stream pass per popped leaf,
    each page's scatter covering only the popped node's built child — and when
    ``make_stream`` accepts an ``indices=`` kwarg (forward it to
    ``PageSet.stream`` / ``PageStream.from_host_pages``), pages with no row in
    the popped node's window are skipped outright (``page_skipping``; skips
    land in ``TransferStats.pages_skipped``). Build the stream with
    ``codec=cfg.page_codec`` (``PageSet.stream`` forwards it) to stage packed
    wire payloads — row-wise bitpacking keeps each staged page row-shardable. Pass the stream's
    `TransferStats` as ``transfer_stats`` so the tiered store's histogram
    spill/fetch traffic (``DistConfig.hist_budget_bytes``) lands in the same
    ledger as the page traffic.
    """
    from repro.core.outofcore import build_tree_paged

    tp = cfg.resolve_tree_params(tp)
    check_feature_parallel_lossguide(tp, cfg)
    cache = HistogramStore(
        enabled=cfg.hist_subtraction and tp.hist_subtraction,
        budget_bytes=cfg.hist_budget_bytes,
        retained_levels=cfg.hist_retained_levels,
        transfer_stats=transfer_stats,
        grad_transport=cfg.grad_transport,  # narrows spill/fetch wires too
    )
    tree, positions = build_tree_paged(
        make_stream, list(page_extents), g, h, n_bins, bin_valid, tp,
        cut_values, cut_ptrs, impl=cfg.kernel_impl, hist_cache=cache,
        page_skipping=page_skipping,
    )
    pos_full = jnp.concatenate([positions[i] for i in range(len(page_extents))])
    return tree, pos_full


def _held_by(device, x: Array) -> Array:
    """The copy of replicated ``x`` that ``device`` holds, as an array of that
    device alone (a `jax.device_put` would keep the mesh in its type)."""
    return next(s.data for s in x.addressable_shards if s.device == device)


def fit_sharded(
    mesh: Mesh,
    data,
    y=None,
    *,
    params=None,
    cfg: DistConfig | None = None,
    eval_set=None,
    eval_metric: str = "auto",
    verbose: bool = False,
    **kwargs,
):
    """Train a whole forest with rows (and optionally features) sharded over
    ``mesh`` — the distributed front door of the unified DMatrix surface.

    ``data`` is anything `GradientBooster.fit` accepts: a `DMatrix`
    (ArrayDMatrix / IterDMatrix / PagedDMatrix — its cuts/labels are used
    as-is, so a distributed fit of the same matrix matches the single-device
    forest up to f32 ties), raw ``(X, y)`` ndarrays, or a batch source.
    ``params`` is the same `BoosterParams` as everywhere else (extra
    ``**kwargs`` construct one); `BoosterParams.tree_params()` stays the
    single TreeParams derivation point, with `DistConfig` growth overrides
    applied on top. Returns a fitted `GradientBooster` (predict / save /
    get_params all work), trained by the booster's own round loop over a
    sharded engine.

    The quantized matrix is staged once, row-sharded over ``cfg.data_axes``
    (features over ``cfg.feature_axis`` when set), and so are the labels and
    the margin: the gradients, the sampling scale and the margin update of
    every round stay on the shards that hold their rows. Each round builds
    one tree with `grow_tree_distributed`, whose SPMD program (histogram
    psum = the paper's §2.2 AllReduce) is compiled once for the whole fit;
    the bytes each shard passes into its collectives add up in
    ``booster.stats.collective_bytes``. The eval rows and their margins live
    on the mesh's first device, where each tree is copied to score them.
    """
    from repro.data.dmatrix import as_dmatrix

    cfg = cfg or DistConfig()
    if params is None:
        params = BoosterParams(**kwargs)
    elif kwargs:
        params = dataclasses.replace(params, **kwargs)
    tp = cfg.resolve_tree_params(params.tree_params())
    check_feature_parallel_lossguide(tp, cfg)

    booster = GradientBooster(params, policy=ExecutionPolicy(mode="in_core"))
    with span(tracing.FIT):
        dm = as_dmatrix(data, y, max_bin=params.max_bin)
        n_shards = int(np.prod([mesh.shape[a] for a in cfg.data_axes]))
        if dm.n_rows % n_shards:
            raise ValueError(
                f"n_rows={dm.n_rows} must divide evenly over the data axes "
                f"{cfg.data_axes} ({n_shards} shards); pad or trim the DMatrix"
            )
        if cfg.feature_axis is not None and dm.num_features % mesh.shape[cfg.feature_axis]:
            raise ValueError(
                f"num_features={dm.num_features} must divide evenly over "
                f"feature_axis {cfg.feature_axis!r} ({mesh.shape[cfg.feature_axis]} shards)"
            )
        booster.cuts = dm.cuts
        engine = functools.partial(_ShardedEngine, booster, dm, mesh=mesh, cfg=cfg, tp=tp)
        return booster._boost(engine, dm, eval_set, eval_metric, verbose, 0)


class _ShardedEngine(_Engine):
    """`fit_sharded`'s engine: the quantized matrix, the labels and the
    margins row-sharded over ``cfg.data_axes``, one `grow_tree_distributed`
    program a tree, and the eval rows on the mesh's first device. A sharded
    fit starts from an empty forest, so nothing is replayed."""

    def __init__(self, booster, dm, labels, fresh, *, mesh: Mesh, cfg: DistConfig, tp):
        from repro.compress import make_transport
        from repro.data.pages import TransferStats

        super().__init__(booster, dm, tp)
        self.mesh, self.cfg = mesh, cfg
        # one ledger for the whole sharded fit: the host-driven lossguide
        # store's histogram spill/fetch traffic (DistConfig.hist_budget_bytes)
        # is observable on the returned booster, like every other engine
        stats = booster.stats = TransferStats()
        transport = make_transport(cfg.page_codec)
        host_bins = dm.single_page_bins()
        if transport is None:
            self.bins = jax.device_put(
                host_bins.astype(np.int32),
                NamedSharding(mesh, P(cfg.data_axes, cfg.feature_axis)),
            )
            wire_nbytes = host_bins.nbytes * 4  # the int32 upcast crosses as-is
        else:
            # row-wise bitpacking keeps each row's packed bytes self-contained,
            # so the wire payload row-shards exactly like the raw matrix
            # (feature_axis is rejected in DistConfig.__post_init__)
            wire, wire_meta = transport.encode(np.ascontiguousarray(host_bins))
            self.bins = transport.decode(
                jax.device_put(wire, NamedSharding(mesh, P(cfg.data_axes))), wire_meta
            )
            wire_nbytes = wire.nbytes
        stats.host_to_device_bytes += wire_nbytes
        stats.logical_bytes += host_bins.nbytes
        stats.wire_bytes += wire_nbytes
        self.rows = NamedSharding(mesh, P(cfg.data_axes))
        self.labels = jax.device_put(labels, self.rows)
        self._margin = jnp.full(
            labels.shape[0], booster.base_margin_, jnp.float32, device=self.rows
        )
        self.cut_values = jax.device_put(dm.cuts.values, NamedSharding(mesh, P()))
        self.cut_ptrs = jax.device_put(dm.cuts.ptrs, NamedSharding(mesh, P()))
        self.eval_device = mesh.devices.flat[0]

    def margin(self) -> Array:
        return self._margin

    def grow(self, key, g, h):
        mask, w = sample(key, g, h, self.params.sampling)
        scale = jnp.where(mask, w, 0.0)
        return grow_tree_distributed(
            self.mesh, self.bins, g * scale, h * scale, self.n_bins, self.bin_valid,
            self.tp, self.cfg, self.cut_values, self.cut_ptrs,
            transfer_stats=self.booster.stats,
        )

    def update(self, tree: TreeArrays, positions) -> None:
        # the leaf table is replicated and positions are row-sharded: the
        # gather keeps the rows' sharding
        leaf = tree.leaf_value.at[positions].get(out_sharding=self.rows)
        self._margin = self._margin + self.params.learning_rate * leaf

    def eval_tree(self, tree: TreeArrays) -> TreeArrays:
        return jax.tree.map(functools.partial(_held_by, self.eval_device), tree)


def distributed_train_step(*args, **kwargs):
    """Alias kept for the public API (see make_gbdt_step_fn)."""
    return make_gbdt_step_fn(*args, **kwargs)
