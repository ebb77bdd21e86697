"""Dispatch layer: Pallas kernel on TPU, pure-jnp oracle elsewhere.

Every op takes ``impl`` in {"auto", "pallas", "ref"}:
  - "auto": compiled Pallas on TPU backends, oracle on CPU/GPU hosts (the
    oracle is itself jit-compiled jnp and is the fast path off-TPU);
  - "pallas": force the kernel (interpret=True off-TPU, used by kernel tests);
  - "ref": force the oracle.
"""
from __future__ import annotations

from typing import Iterable, Mapping

import jax
import jax.numpy as jnp

from repro.kernels import ellpack_bin as _ellpack_bin
from repro.kernels import forest as _forest
from repro.kernels import histogram as _histogram
from repro.kernels import partition as _partition
from repro.kernels import ref as _ref
from repro.kernels._backend import on_tpu as _on_tpu

MISSING_BIN = _ref.MISSING_BIN


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    if impl not in ("pallas", "ref"):
        raise ValueError(f"impl must be auto|pallas|ref, got {impl!r}")
    return impl


_ref_build_histogram = jax.jit(_ref.build_histogram, static_argnames=("n_nodes", "n_bins"))
_ref_build_histogram_nodes = jax.jit(_ref.build_histogram_nodes, static_argnames=("n_bins",))
_ref_bin_values = jax.jit(_ref.bin_values)
_ref_partition_rows = jax.jit(_ref.partition_rows)
_ref_predict_bins = jax.jit(_ref.predict_bins, static_argnames=("max_depth",))
_ref_predict_forest = jax.jit(_ref.predict_forest_bins, static_argnames=("max_depth",))


def build_histogram(
    bins, g, h, positions, n_nodes: int, n_bins: int,
    node_map=None, impl: str = "auto",
):
    """``node_map`` (histogram subtraction, see `core.histcache`): level-local
    node -> compacted build slot (or -1 = derive-by-subtraction node); when
    given, ``n_nodes`` is the number of build slots and only those are
    materialized."""
    if _resolve(impl) == "pallas":
        return _histogram.build_histogram(
            bins, g, h, positions, n_nodes, n_bins, node_map=node_map
        )
    return _ref_build_histogram(
        bins, g, h, positions, n_nodes=n_nodes, n_bins=n_bins, node_map=node_map
    )


def build_histogram_nodes(
    bins, g, h, positions, build_nodes, n_bins: int, impl: str = "auto",
    bin_onehot=None,
):
    """Fused histogram over an explicit global build-node set (see
    `core.histcache.LevelPlan.build_nodes`): one launch replaces the
    window-mask + node_map-remap + scatter sequence of `build_histogram`.
    ``positions`` are raw global node ids; ``out[s]`` is the histogram of
    ``build_nodes[s]``. The build set may be non-contiguous (batched
    lossguide pops). ``bin_onehot`` (from `prepare_bin_onehot`) is a
    level-invariant precompute used only by the host contraction; kernel and
    oracle paths ignore it."""
    if _resolve(impl) == "pallas":
        return _histogram.build_histogram_nodes(bins, g, h, positions, build_nodes, n_bins)
    if impl == "auto":
        # off-TPU fast path: jnp mirror of the kernel's one-hot contraction.
        # Its cost scales with the build-set size, so subtraction pays off-TPU
        # too; the scatter oracle's cost is row-dominated and mode-independent.
        return _histogram.build_histogram_nodes_host(
            bins, g, h, positions, build_nodes, n_bins, bin_onehot
        )
    return _ref_build_histogram_nodes(bins, g, h, positions, build_nodes, n_bins=n_bins)


def prepare_bin_onehot(bins, n_bins: int, impl: str = "auto", cap_bytes: int = 256 * 2**20):
    """Per-tree precompute for `build_histogram_nodes`: the f32 bin one-hot
    the host contraction would otherwise rebuild every level (bins are
    level-invariant). Returns None — compute-on-the-fly — when the resolved
    impl is not the host contraction or the one-hot would exceed
    ``cap_bytes`` (it costs ``n_rows * m * n_bins * 4`` bytes). The
    precomputed path contracts in one dot, the on-the-fly path in row
    chunks; each is deterministic, but their f32 groupings differ in final
    ulps — use one consistently per fit (the in-core builder decides once
    per tree, before the level loop)."""
    if _resolve(impl) == "pallas" or impl != "auto":
        return None
    if bins.shape[0] * bins.shape[1] * n_bins * 4 > cap_bytes:
        return None
    return _histogram.bin_onehot(bins, n_bins)


def build_histogram_paged(
    stream: Iterable,
    g,
    h,
    positions: Mapping[int, jax.Array],
    offset: int,
    count: int,
    n_bins: int,
    node_map=None,
    impl: str = "auto",
    build_nodes=None,
):
    """Page-batched histogram: sum per-page level histograms over one stream pass.

    ``stream`` yields `repro.pipeline.StreamedPage`s whose host view exposes
    ``row_offset`` / ``n_rows`` and whose device buffer is the staged bins
    matrix (possibly sharded — the per-page histogram then reduces across the
    mesh under jit). ``positions[page.index]`` holds that page's global tree
    positions; rows not at this level contribute to no node (-1).

    With ``node_map``, ``count`` is the build-slot count and rows whose node is
    in the derive set contribute to no bin — every page's scatter/contraction
    only covers the smaller child of each split pair.

    The node window is ``[offset, offset + window)`` where ``window`` is the
    node_map length (or ``count`` for a full build). Rows outside it — frozen
    at shallower leaves, or live at *other* heap nodes during a best-first
    per-node pass — contribute to no bin.

    With ``build_nodes`` (the fused fast path) the window mask and node_map
    remap fold into the kernel itself: each page's raw global positions go
    straight to `build_histogram_nodes`, one launch per page instead of the
    lookup + scatter pair, and the build set may be non-contiguous (batched
    lossguide pops). ``offset``/``count``/``node_map`` are ignored then,
    except that ``count`` must equal ``build_nodes.shape[0]``.
    """
    window = node_map.shape[0] if node_map is not None else count
    hist = None
    for page in stream:
        ro, nr = page.host.row_offset, page.host.n_rows
        pos = positions[page.index]
        gp = jax.lax.dynamic_slice(g, (ro,), (nr,))
        hp_ = jax.lax.dynamic_slice(h, (ro,), (nr,))
        if build_nodes is not None:
            hp = build_histogram_nodes(
                page.device, gp, hp_, pos, build_nodes, n_bins, impl=impl
            )
        else:
            level_pos = jnp.where(
                (pos >= offset) & (pos < offset + window), pos - offset, -1
            )
            hp = build_histogram(
                page.device, gp, hp_, level_pos, count, n_bins,
                node_map=node_map, impl=impl,
            )
        hist = hp if hist is None else hist + hp
    return hist


def bin_values(x, padded_edges, n_bins_per_feature, impl: str = "auto"):
    if _resolve(impl) == "pallas":
        return _ellpack_bin.bin_values(x, padded_edges, n_bins_per_feature)
    return _ref_bin_values(x, padded_edges, n_bins_per_feature)


def partition_rows(
    bins, positions, feature, split_bin, default_left, is_leaf, impl: str = "auto"
):
    if _resolve(impl) == "pallas":
        return _partition.partition_rows(
            bins, positions, feature, split_bin, default_left, is_leaf
        )
    return _ref_partition_rows(bins, positions, feature, split_bin, default_left, is_leaf)


def predict_bins(bins, feature, split_bin, default_left, is_leaf, leaf_value, max_depth: int):
    return _ref_predict_bins(
        bins, feature, split_bin, default_left, is_leaf, leaf_value, max_depth=max_depth
    )


def predict_forest(
    bins,
    feature,  # (T, n_total) — stacked forest arrays, one launch for all T trees
    split_bin,
    default_left,
    is_leaf,
    leaf_value,
    max_depth: int,
    learning_rate: float,
    margin_in,
    impl: str = "auto",
):
    """Fused batched forest traversal (serving hot path).

    Accumulates ``margin_in + lr * leaf_t`` in tree order. The leaf table is
    scaled by the learning rate HERE, eagerly — inside a jit'd kernel XLA
    would contract the multiply-add into an FMA and round differently than
    the eager per-tree loop. Pre-scaling makes every accumulation a pure add
    (adds cannot fuse), so the fused kernel, the jnp oracle, and the chunked
    paged-forest path (which chains ``margin_in`` across chunks) are all
    bit-for-bit the per-tree reference.
    """
    if feature.shape[0] == 0:  # empty forest/chunk: margins pass through
        return jnp.asarray(margin_in, jnp.float32)
    scaled_leaf = jnp.float32(learning_rate) * jnp.asarray(leaf_value, jnp.float32)
    if _resolve(impl) == "pallas":
        return _forest.predict_forest(
            bins, feature, split_bin, default_left, is_leaf, scaled_leaf,
            max_depth, margin_in,
        )
    return _ref_predict_forest(
        bins, feature, split_bin, default_left, is_leaf, scaled_leaf,
        max_depth=max_depth, margin_in=margin_in,
    )
