"""Pure-jnp oracles for every Pallas kernel.

These are the semantics ground truth: each Pallas kernel in this package must
be allclose to the corresponding function here over shape/dtype sweeps (see
tests/test_kernels.py). They are also the fast dispatch target on CPU hosts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MISSING_BIN = 255


def scatter_sum(flat: jax.Array, w: jax.Array, size: int, max_terms: int) -> jax.Array:
    """``zeros(size).at[flat].add(w)`` whose result does not depend on the
    order in which the terms land, up to one rounding.

    A TPU scatter adds into a bin one term at a time, in f32: over the ~8k
    rows per bin of a 2^21-row level that loses 6.6e-5 of the bin's sum of
    |g| (TPU v5e), ten times the Pallas kernel's error. So each term splits
    into ``q / scale + lo``: ``q`` is an integer, summed exactly in int32,
    and ``|lo| <= 0.5 / scale`` is exact, with a sum small enough that its
    f32 rounding is negligible. ``scale`` is the power of two that keeps
    ``max_terms`` terms of ``|q|`` under 2^30.
    """
    _, exp = jnp.frexp(jnp.max(jnp.abs(w), initial=0.0) * max_terms)
    e = jnp.clip(29 - exp, -100, 100).astype(jnp.int32)
    scale = jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)  # 2^e
    q = jnp.round(w * scale)
    lo = w - q / scale
    q_sum = jnp.zeros(size, jnp.int32).at[flat].add(q.astype(jnp.int32))
    # the barrier keeps XLA from folding the sums below into this scatter,
    # which would add every small term onto a large running value
    lo_sum = jax.lax.optimization_barrier(jnp.zeros(size, jnp.float32).at[flat].add(lo))
    # |q_sum| < 2^30: its high part (a multiple of 64, toward zero) converts
    # to f32 exactly, and its low part, of the same sign, is small enough to
    # join lo_sum first at no cost in precision
    q_lo = jax.lax.rem(q_sum, 64)
    q_hi = q_sum - q_lo
    return q_hi.astype(jnp.float32) / scale + (q_lo.astype(jnp.float32) / scale + lo_sum)


def apply_node_map(positions: jax.Array, node_map: jax.Array) -> jax.Array:
    """Remap window-local node ids through ``node_map`` (histogram subtraction).

    ``node_map[j]`` is the compacted build slot of window-local node ``j``, or
    -1 for nodes whose histogram will be *derived* as ``parent - sibling``.
    Rows at derive nodes, already-inactive rows, and rows whose position falls
    outside the window entirely (best-first growth keeps live rows at heap
    nodes far from the pass's 2-node window) come out -1 and therefore
    contribute to no bin.
    """
    in_window = (positions >= 0) & (positions < node_map.shape[0])
    safe = jnp.clip(positions, 0, node_map.shape[0] - 1)
    return jnp.where(in_window, node_map[safe], -1).astype(jnp.int32)


def build_histogram(
    bins: jax.Array,  # (n_rows, m) int32 local bin indices (MISSING_BIN = missing)
    g: jax.Array,  # (n_rows,) f32
    h: jax.Array,  # (n_rows,) f32
    positions: jax.Array,  # (n_rows,) int32 level-local node index; < 0 = inactive
    n_nodes: int,
    n_bins: int,
    node_map: jax.Array | None = None,  # (level_nodes,) int32 -> build slot or -1
) -> jax.Array:
    """Gradient histogram: out[n, f, b] = (sum g, sum h) over rows in node n with bin b.

    Missing values contribute to no bin (XGBoost semantics: the missing mass of
    a node is node_total - feature_total and is routed by the learned default
    direction at split evaluation time).

    With ``node_map``, positions are first compacted through it and only the
    ``n_nodes`` *build* slots are materialized — the scatter target (and on
    TPU the VMEM out block) covers half the level at depth >= 1; siblings are
    reconstructed by subtraction in `core.histcache`.
    """
    n_rows, m = bins.shape
    pos = positions.astype(jnp.int32)
    if node_map is not None:
        pos = apply_node_map(pos, node_map)
    # rows past the scatter target (per-node passes see live rows at other
    # heap nodes) must be dropped explicitly, not left to OOB-scatter behavior
    active = (pos >= 0) & (pos < n_nodes)
    valid = (bins != MISSING_BIN) & active[:, None]
    # flat scatter index: node * m * n_bins + f * n_bins + bin
    feat = jax.lax.broadcasted_iota(jnp.int32, (n_rows, m), 1)
    flat = pos[:, None] * (m * n_bins) + feat * n_bins + bins.astype(jnp.int32)
    flat = jnp.where(valid, flat, 0)
    flat = flat.reshape(-1)
    wg = jnp.where(valid, g[:, None], 0.0).reshape(-1)
    wh = jnp.where(valid, h[:, None], 0.0).reshape(-1)
    size = n_nodes * m * n_bins
    hist_g = scatter_sum(flat, wg, size, n_rows)
    hist_h = scatter_sum(flat, wh, size, n_rows)
    return jnp.stack(
        [hist_g.reshape(n_nodes, m, n_bins), hist_h.reshape(n_nodes, m, n_bins)],
        axis=-1,
    )


def build_histogram_nodes(
    bins: jax.Array,  # (n_rows, m) int32 local bin indices (MISSING_BIN = missing)
    g: jax.Array,  # (n_rows,) f32
    h: jax.Array,  # (n_rows,) f32
    positions: jax.Array,  # (n_rows,) int32 GLOBAL node ids; < 0 = inactive
    build_nodes: jax.Array,  # (n_build,) int32 global build-node ids, all >= 0
    n_bins: int,
) -> jax.Array:
    """Fused-kernel oracle: ``out[s]`` is the histogram of global node
    ``build_nodes[s]``; rows at any other node contribute to no bin.

    This is the semantics ground truth for the fused Pallas kernel
    (`kernels.histogram.build_histogram_nodes`): the window masking and
    node_map compaction that `build_histogram` expects its caller to do are
    folded into a row -> build-slot match here, so the build set may be any
    node-id subset — contiguous level windows, a popped node's two children,
    or the non-contiguous union of several popped nodes' children.
    """
    hit = positions.astype(jnp.int32)[:, None] == build_nodes.astype(jnp.int32)[None, :]
    slot = jnp.argmax(hit, axis=1).astype(jnp.int32)
    pos = jnp.where(jnp.any(hit, axis=1), slot, -1)
    return build_histogram(bins, g, h, pos, build_nodes.shape[0], n_bins)


def bin_values(
    x: jax.Array,  # (n_rows, m) f32 raw features
    padded_edges: jax.Array,  # (m, max_bin) f32, +inf padded right edges
    n_bins_per_feature: jax.Array,  # (m,) int32
) -> jax.Array:
    """Quantize raw features to local bins; NaN -> MISSING_BIN. (Alg. 4 inner loop.)"""
    cnt = jnp.sum(x[:, :, None] > padded_edges[None, :, :], axis=-1).astype(jnp.int32)
    b = jnp.clip(cnt, 0, n_bins_per_feature[None, :] - 1)
    return jnp.where(jnp.isnan(x), MISSING_BIN, b).astype(jnp.int32)


def partition_rows(
    bins: jax.Array,  # (n_rows, m) int32
    positions: jax.Array,  # (n_rows,) int32 global node ids; < 0 = retired
    feature: jax.Array,  # (n_total_nodes,) int32 split feature per node
    split_bin: jax.Array,  # (n_total_nodes,) int32 split bin per node (go left if bin <= split_bin)
    default_left: jax.Array,  # (n_total_nodes,) bool missing direction
    is_leaf: jax.Array,  # (n_total_nodes,) bool
) -> jax.Array:
    """RepartitionInstances: rows move to child 2p+1 (left) or 2p+2 (right).

    Rows sitting at a leaf keep their position (so after the last level every
    row's position is its leaf node — the margin update is a single gather).
    """
    pos = positions.astype(jnp.int32)
    active = pos >= 0
    safe = jnp.where(active, pos, 0)
    f_idx = feature[safe]
    sbin = split_bin[safe]
    dleft = default_left[safe]
    leaf = is_leaf[safe]
    bval = jnp.take_along_axis(bins, f_idx[:, None], axis=1)[:, 0]
    missing = bval == MISSING_BIN
    go_left = jnp.where(missing, dleft, bval <= sbin)
    child = 2 * pos + 1 + jnp.where(go_left, 0, 1)
    return jnp.where(active, jnp.where(leaf, pos, child), -1).astype(jnp.int32)


def predict_bins(
    bins: jax.Array,  # (n_rows, m) int32
    feature: jax.Array,  # (n_nodes,) int32
    split_bin: jax.Array,  # (n_nodes,) int32
    default_left: jax.Array,  # (n_nodes,) bool
    is_leaf: jax.Array,  # (n_nodes,) bool
    leaf_value: jax.Array,  # (n_nodes,) f32
    max_depth: int,
) -> jax.Array:
    """Traverse one complete-layout tree over quantized rows -> leaf values."""
    n_rows = bins.shape[0]
    pos = jnp.zeros(n_rows, jnp.int32)

    def step(pos, _):
        f_idx = feature[pos]
        bval = jnp.take_along_axis(bins, f_idx[:, None], axis=1)[:, 0]
        missing = bval == MISSING_BIN
        go_left = jnp.where(missing, default_left[pos], bval <= split_bin[pos])
        child = 2 * pos + 1 + jnp.where(go_left, 0, 1)
        return jnp.where(is_leaf[pos], pos, child), None

    pos, _ = jax.lax.scan(step, pos, None, length=max_depth)
    return leaf_value[pos]


def predict_forest_bins(
    bins: jax.Array,  # (n_rows, m) int32
    feature: jax.Array,  # (T, n_nodes) int32
    split_bin: jax.Array,  # (T, n_nodes) int32
    default_left: jax.Array,  # (T, n_nodes) bool
    is_leaf: jax.Array,  # (T, n_nodes) bool
    leaf_value: jax.Array,  # (T, n_nodes) f32, PRE-SCALED by the learning rate
    max_depth: int,
    margin_in: jax.Array,  # (n_rows,) f32 running margin (base, or a prior chunk's)
) -> jax.Array:
    """Fused forest traversal: whole forest in one launch, margins accumulated
    tree-by-tree in forest order.

    ``leaf_value`` arrives pre-scaled by the learning rate (`kernels.ops`
    scales the table eagerly, outside jit) so the scan body is a pure add —
    XLA cannot re-fuse a multiply-add into an FMA and change the rounding.
    That makes this bit-for-bit identical to the eager per-tree Python loop,
    and lets the chunked paged-forest path chain ``margin_in`` across chunks
    without perturbing the accumulation order.
    """
    n_rows = bins.shape[0]

    def per_tree(margin, tree):
        feat, sbin, dleft, leaf, lval = tree
        pred = predict_bins(bins, feat, sbin, dleft, leaf, lval, max_depth)
        return margin + pred, None

    margin, _ = jax.lax.scan(
        per_tree, margin_in, (feature, split_bin, default_left, is_leaf, leaf_value)
    )
    return margin
