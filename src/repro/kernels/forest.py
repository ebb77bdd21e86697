r"""Pallas TPU kernel: fused batched forest traversal via one-hot MXU gathers.

Serving adaptation of the same scatter->matmul reformulation the histogram
kernel uses. CUDA serving kernels (the 1806.11248 fused predictor) walk one
tree per thread with gather loads; TPUs have no per-lane gathers from VMEM, so
each descent step reuses the partition kernel's pieces
(`kernels.partition.gather_nodes` / `route`): the four node attributes of
every row's current node come from one one-hot contraction, and the value of
its split feature from a masked sublane sum over feature-major bins.

The final leaf value is gathered on the VPU, as a masked sum over a node
column in which exactly one term is nonzero, so it is the exact f32 leaf.

The grid tiles (rows, trees); trees are the innermost (sequential) grid dim so
the ``(1, R)`` output margin block is revisited and accumulated in VMEM across
trees — one launch predicts the whole forest, and the accumulation order
(tree 0, 1, ...) matches the per-tree reference bit-for-bit.

VMEM per grid step (R=512, N_p=512 at depth 8, m<=512): node one-hot
(N_p, R) f32 = 1 MiB, bins (m, R) int32 <= 1 MiB, node tables (8, N_p) and
(N_p, 1) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._backend import LANES, resolve_interpret, round_up
from repro.kernels.partition import MISSING_BIN, gather_nodes, node_attr_rows, route


def _forest_kernel(bins_ref, attrs_ref, leaf_ref, margin_ref, out_ref, *, max_depth: int):
    bins = bins_ref[...]  # (m, R) int32
    table = attrs_ref[0]  # (8, N_p) f32: feature, split_bin, default_left, is_leaf
    leaf_col = leaf_ref[0]  # (N_p, 1) f32, pre-scaled by the learning rate
    pos = jnp.zeros((1, bins.shape[1]), jnp.int32)
    for _ in range(max_depth):
        pos = route(bins, pos, gather_nodes(table, pos))

    node_iota = jax.lax.broadcasted_iota(jnp.int32, (leaf_col.shape[0], pos.shape[1]), 0)
    leaf_val = jnp.sum(jnp.where(node_iota == pos, leaf_col, 0.0), axis=0, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = margin_ref[...]

    # leaf values arrive pre-scaled by the learning rate, so this is a pure
    # add — no multiply-add for the compiler to contract into an FMA, keeping
    # the accumulation bit-for-bit the per-tree reference's
    out_ref[...] += leaf_val


@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "row_tile", "interpret"),
)
def predict_forest(
    bins: jax.Array,  # (n_rows, m) int32 (uint8 ok; cast below)
    feature: jax.Array,  # (T, n_total) int32
    split_bin: jax.Array,  # (T, n_total) int32
    default_left: jax.Array,  # (T, n_total) bool
    is_leaf: jax.Array,  # (T, n_total) bool
    leaf_value: jax.Array,  # (T, n_total) f32, PRE-SCALED by the learning rate
    max_depth: int,
    margin_in: jax.Array,  # (n_rows,) f32
    *,
    row_tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused launch over the whole forest; returns the updated margins."""
    interpret = resolve_interpret(interpret)
    n_rows, m = bins.shape
    n_trees, n_total = feature.shape
    rt = min(row_tile, round_up(max(n_rows, 1), LANES))
    n_rows_p = round_up(max(n_rows, 1), rt)
    n_p = round_up(n_total, LANES)

    attrs = node_attr_rows(feature, split_bin, default_left, is_leaf)  # (T, 8, N_p)
    leaf_col = jnp.pad(
        leaf_value.astype(jnp.float32), ((0, 0), (0, n_p - n_total))
    )[:, :, None]
    # padding rows traverse on MISSING_BIN (default direction) — harmless,
    # sliced off below
    bins_t = jnp.pad(
        bins.astype(jnp.int32).T, ((0, 0), (0, n_rows_p - n_rows)),
        constant_values=MISSING_BIN,
    )
    margin_p = jnp.pad(margin_in.astype(jnp.float32), (0, n_rows_p - n_rows))[None, :]

    out = pl.pallas_call(
        functools.partial(_forest_kernel, max_depth=max_depth),
        grid=(n_rows_p // rt, n_trees),
        in_specs=[
            pl.BlockSpec((m, rt), lambda r, t: (0, r)),
            pl.BlockSpec((1, 8, n_p), lambda r, t: (t, 0, 0)),
            pl.BlockSpec((1, n_p, 1), lambda r, t: (t, 0, 0)),
            pl.BlockSpec((1, rt), lambda r, t: (0, r)),
        ],
        out_specs=pl.BlockSpec((1, rt), lambda r, t: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows_p), jnp.float32),
        interpret=interpret,
    )(bins_t, attrs, leaf_col, margin_p)
    return out[0, :n_rows]
