"""Pallas TPU kernel: row repartition (paper's RepartitionInstances).

CUDA implementations radix-partition row indices with warp ballots; on TPU we
keep an explicit per-row position array (complete-tree node ids) and update it
vectorially. Per-node attribute gathers (split feature/bin, default direction,
leaf flag) are one one-hot MXU contraction, and the per-row "value of my split
feature" gather is a masked sublane sum, instead of serialized dynamic
gathers. Rows lie along lanes: bins are read feature-major, ``(m, n_rows)``,
and positions as a ``(1, n_rows)`` row.

new_pos = 2*pos + 1 + go_right; rows at a leaf keep their position and
retired rows (pos < 0) stay -1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._backend import LANES, resolve_interpret, round_up

MISSING_BIN = 255


def node_attr_rows(*attrs: jax.Array) -> jax.Array:
    """Stack per-node attributes into an ``(8, N_p)`` f32 gather table.

    Row ``k`` is ``attrs[k]`` (small ints and 0/1 flags, exact in f32), the
    remaining rows are zero, and ``N_p`` is the node count rounded up to 128.
    Padding nodes are never reached: positions only move to real children.
    """
    n = attrs[0].shape[-1]
    rows = [a.astype(jnp.float32) for a in attrs]
    table = jnp.stack(rows + [jnp.zeros_like(rows[0])] * (8 - len(rows)), axis=-2)
    pad = [(0, 0)] * (table.ndim - 1) + [(0, round_up(n, LANES) - n)]
    return jnp.pad(table, pad)


def gather_nodes(table: jax.Array, pos: jax.Array) -> jax.Array:
    """``table[:, pos]`` as a one-hot MXU contraction: (8, N_p) x (1, R) -> (8, R).

    HIGHEST precision keeps the f32 operand unrounded, so ids up to 2^24
    come back exact; a position matching no node (-1) gathers zeros.
    """
    onehot = jax.lax.broadcasted_iota(jnp.int32, (table.shape[1], pos.shape[1]), 0) == pos
    return jax.lax.dot_general(
        table, onehot.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def route(bins: jax.Array, pos: jax.Array, attrs: jax.Array) -> jax.Array:
    """One descent step for a (1, R) row of positions over feature-major bins.

    ``attrs`` is ``gather_nodes`` output: feature, split_bin, default_left,
    is_leaf in rows 0-3. Rows at a leaf keep their position; others move to
    ``2p+1`` (left) or ``2p+2`` (right). Booleans combine with ``&``/``|``:
    Mosaic cannot select between two boolean vectors.
    """
    f_idx = attrs[0:1].astype(jnp.int32)
    s_bin = attrs[1:2].astype(jnp.int32)
    d_left = attrs[2:3] > 0.5
    leaf = attrs[3:4] > 0.5
    feat_iota = jax.lax.broadcasted_iota(jnp.int32, bins.shape, 0)
    bval = jnp.sum(jnp.where(feat_iota == f_idx, bins, 0), axis=0, keepdims=True)
    missing = bval == MISSING_BIN
    go_left = (missing & d_left) | (~missing & (bval <= s_bin))
    child = 2 * pos + 2 - go_left.astype(jnp.int32)
    return jnp.where(leaf, pos, child)


def _partition_kernel(bins_ref, pos_ref, attrs_ref, out_ref):
    bins = bins_ref[...]  # (m, R) int32
    pos = pos_ref[...]  # (1, R) int32
    new_pos = route(bins, pos, gather_nodes(attrs_ref[...], pos))
    # inactive (retired or padded) rows stay -1
    out_ref[...] = jnp.where(pos >= 0, new_pos, -1)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def partition_rows(
    bins: jax.Array,  # (n_rows, m) int32
    positions: jax.Array,  # (n_rows,) int32 global node ids
    feature: jax.Array,  # (n_nodes,) int32
    split_bin: jax.Array,  # (n_nodes,) int32
    default_left: jax.Array,  # (n_nodes,) bool
    is_leaf: jax.Array,  # (n_nodes,) bool
    *,
    row_tile: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    n_rows, m = bins.shape
    rt = min(row_tile, round_up(max(n_rows, 1), LANES))
    n_rows_p = round_up(max(n_rows, 1), rt)
    # feature-major so a (features, rows) block is sublanes x lanes
    bins_t = jnp.pad(
        bins.astype(jnp.int32).T, ((0, 0), (0, n_rows_p - n_rows)),
        constant_values=MISSING_BIN,
    )
    pos_p = jnp.pad(
        positions.astype(jnp.int32), (0, n_rows_p - n_rows), constant_values=-1
    )[None, :]
    attrs = node_attr_rows(feature, split_bin, default_left, is_leaf)

    out = pl.pallas_call(
        _partition_kernel,
        grid=(n_rows_p // rt,),
        in_specs=[
            pl.BlockSpec((m, rt), lambda r: (0, r)),
            pl.BlockSpec((1, rt), lambda r: (0, r)),
            pl.BlockSpec(attrs.shape, lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rt), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows_p), jnp.int32),
        interpret=interpret,
    )(bins_t, pos_p, attrs)
    return out[0, :n_rows]
