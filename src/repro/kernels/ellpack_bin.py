"""Pallas TPU kernel: ELLPACK quantization (paper Alg. 4 LookupBin hot spot).

Each grid step loads a (rows x features) tile of raw values plus that feature
tile's padded right-edge matrix and computes

    bin(x, f) = clip(sum_k [x > edges[f, k]], 0, n_bins_f - 1)

— a broadcast-compare-reduce on the VPU (edges are padded with +inf so the
count never includes padding). NaN maps to MISSING_BIN. Equivalent to a
per-feature searchsorted(..., side='left') but branch-free and layout-friendly.

VMEM per step: the (R, Ft, B) compare tensor, about 4 MiB (see `_tiles`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._backend import resolve_interpret

MISSING_BIN = 255


def _bin_kernel(x_ref, edges_ref, nbins_ref, out_ref):
    x = x_ref[...]  # (R, Ft) f32
    edges = edges_ref[...]  # (Ft, B) f32
    nb = nbins_ref[...]  # (1, Ft) int32
    cnt = jnp.sum(
        (x[:, :, None] > edges[None, :, :]).astype(jnp.int32), axis=-1
    )
    b = jnp.clip(cnt, 0, jnp.maximum(nb - 1, 0))
    out_ref[...] = jnp.where(jnp.isnan(x), MISSING_BIN, b).astype(jnp.int32)


def _tiles(m: int, max_bin: int, vmem_bytes: int = 4 * 2**20) -> tuple[int, int]:
    """(row_tile, feat_tile) for the TPU tiling rule: the feature tile is the
    whole width up to 128 features, else 128; the row tile is a multiple of 8
    that keeps the (R, Ft, B) compare tensor near ``vmem_bytes``."""
    ft = m if m <= 128 else 128
    rows = vmem_bytes // (4 * ft * max_bin)
    return max(8, min(128, rows // 8 * 8)), ft


@functools.partial(jax.jit, static_argnames=("interpret",))
def bin_values(
    x: jax.Array,  # (n_rows, m) f32
    padded_edges: jax.Array,  # (m, max_bin) f32 (+inf padded)
    n_bins_per_feature: jax.Array,  # (m,) int32
    *,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    n_rows, m = x.shape
    max_bin = padded_edges.shape[1]
    row_tile, feat_tile = _tiles(m, max_bin)
    r_pad = -n_rows % row_tile
    f_pad = -m % feat_tile
    x_p = jnp.pad(x.astype(jnp.float32), ((0, r_pad), (0, f_pad)))
    edges_p = jnp.pad(
        padded_edges.astype(jnp.float32), ((0, f_pad), (0, 0)), constant_values=jnp.inf
    )
    nb_p = jnp.pad(n_bins_per_feature.astype(jnp.int32), (0, f_pad), constant_values=1)[None, :]

    grid = ((m + f_pad) // feat_tile, (n_rows + r_pad) // row_tile)
    out = pl.pallas_call(
        _bin_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, feat_tile), lambda f, r: (r, f)),
            pl.BlockSpec((feat_tile, max_bin), lambda f, r: (f, 0)),
            pl.BlockSpec((1, feat_tile), lambda f, r: (0, f)),
        ],
        out_specs=pl.BlockSpec((row_tile, feat_tile), lambda f, r: (r, f)),
        out_shape=jax.ShapeDtypeStruct((n_rows + r_pad, m + f_pad), jnp.int32),
        interpret=interpret,
    )(x_p, edges_p, nb_p)
    return out[:n_rows, :m]
