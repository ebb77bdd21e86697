r"""Pallas TPU kernel: node-aware gradient histogram via one-hot MXU contractions.

TPU adaptation of the paper's BuildHistograms hot spot. CUDA builds gradient
histograms with atomic scatter-adds into shared memory; TPUs have no atomics,
so we reformulate the scatter as dense one-hot contractions that lower to
MXU matmuls, one per feature:

    slab[k, f*B + b] = sum_r  W[k, r]  *  [bin_{r,f} == b]
    W[k, r] = [pos_r == node_k] * g_r     for k <  S   (gradient rows)
    W[k, r] = [pos_r == node_k] * h_r     for k >= S   (hessian rows)

Each g and h is split exactly into an integer part ``q / scale`` and a small
remainder ``lo`` (`_fixed_point_split`). The integer parts sum exactly, in
f32 on the MXU within a row tile and in int32 across tiles, so a bin comes
out as its exact sum rounded about once, whatever the order of the rows, and
builders that add the rows in another order (pages, shards, the XLA scatter
oracle) see the same bins and choose the same splits.

The MXU multiplies bf16 exactly and sums in f32, so the kernel hands it bf16
terms that sum to each weight exactly (`_bf16_terms`): two for ``q``
(``|q| <= 2^16``), three for ``lo``. Per row tile it stacks them as the rows
of one bf16 ``W``, ten blocks of ``S_b`` slots (the build set rounded up to
8, so 10 S_b rows, a multiple of 16), and runs ONE product per feature:

    part = W @ onehot(bin_f)^T                  (10 S_b, B), f32 sums
    q_sum += int32(part[q term 1]) + int32(part[q term 2])
    lo_sum += part[lo term 1] + (part[lo term 2] + part[lo term 3])

A row tile's integer terms sum below 2^24, exact in f32. That is one MXU pass
per (feature, row tile); two f32 products at ``Precision.HIGHEST`` cost six
bf16 passes each, twelve in all, most of them multiplying the one-hot's zero
low parts.

The kernel reads bins feature-major, ``(m, n_rows)``, so that a (features,
rows) block is (8, R): sublanes by lanes, as the TPU tiling wants. It writes
``(2 S_b, m*B)`` slabs; `build_histogram_slab` drops the padding slots and
`build_histogram_nodes` reshapes to the ``(S, m, B, 2)`` layout every caller
reads. The grid tiles (features, rows); rows are the innermost (sequential)
grid dim, so the output blocks stay in VMEM and accumulate across row tiles.

VMEM per grid step (R=1024, Ft=8, B=256, S=128): bin one-hot (B, R) bf16 =
0.5 MiB; W (10S, R), formed in f32 (5 MiB) and cast to bf16 (2.5 MiB); one
product (10S, B) f32 = 1.25 MiB; output blocks 2 x (2S, Ft*B) = 4 MiB,
double-buffered: about 17 MiB, over the default scoped limit of 16 MiB, hence
`_VMEM_LIMIT`. Larger build sets run in chunks of `_MAX_SLOTS`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._backend import LANES, resolve_interpret, round_up
from repro.kernels.ref import apply_node_map

MISSING_BIN = 255
_MAX_SLOTS = 128  # build nodes per launch
_VMEM_LIMIT = 32 * 2**20  # of the v5e's 128 MiB VMEM


def _fixed_point_split(
    w: jax.Array, max_terms: int, q_bits: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split f32 ``w`` into ``q / scale + lo``, exactly.

    ``scale`` is a power of two and ``q`` holds integers with
    ``|q| <= 2^q_bits``, capped so that ``max_terms`` of them sum below 2^30:
    their sum is exact in int32, whatever the order. ``|lo| <= 0.5 / scale``.
    """
    k = min(q_bits, 30 - max(int(max_terms) - 1, 1).bit_length())
    _, exp = jnp.frexp(jnp.max(jnp.abs(w), initial=0.0))
    e = jnp.clip(k - exp, -100, 100).astype(jnp.int32)
    scale = jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)  # 2^e
    q = jnp.round(w * scale)
    return q, w - q / scale, scale


def _fixed_point_join(q_sum: jax.Array, lo_sum: jax.Array, scale: jax.Array) -> jax.Array:
    """``q_sum / scale + lo_sum`` for ``|q_sum| < 2^30``, rounded about once:
    the high part of ``q_sum`` (a multiple of 64, toward zero) converts to
    f32 exactly, and the low part, of the same sign and below 64, is small
    enough that adding it to ``lo_sum`` first costs no precision. A sum
    below 64 is rounded once, and no small negative sum cancels."""
    q_lo = jax.lax.rem(q_sum, 64)
    q_hi = q_sum - q_lo
    return q_hi.astype(jnp.float32) / scale + (q_lo.astype(jnp.float32) / scale + lo_sum)


def _bf16_terms(x: jax.Array, n_terms: int) -> list[jax.Array]:
    """Split f32 ``x`` into ``n_terms`` values that are each exact in bf16
    (kept as f32) and sum to ``x``: each term is the bf16 rounding of what
    the terms before it left. Two terms hold any integer ``|x| <= 2^16``
    exactly, three any normal f32 whose terms stay normal."""
    terms = []
    for _ in range(n_terms - 1):
        t = x.astype(jnp.bfloat16).astype(jnp.float32)
        terms.append(t)
        x = x - t
    return terms + [x.astype(jnp.bfloat16).astype(jnp.float32)]


def _hist_kernel(nodes_ref, bins_ref, w_ref, pos_ref, q_out, lo_out, *, n_bins: int):
    """One (feature tile, row tile) step: ``out += W @ onehot(bins)^T``, one
    bf16 product per feature whose row sums are recombined into the exact
    integer parts of g and h (int32) and the sums of their remainders."""
    bins = bins_ref[...]  # (Ft, R) int32; missing and padding are -1
    pos = pos_ref[...]  # (1, R) int32 global node ids; padding is -1
    nodes = nodes_ref[...]  # (S_b, 1) int32: the build set, padded with -1
    w = w_ref[...]  # (4, R) f32: q_g, q_h, lo_g, lo_h
    s_b = nodes.shape[0]
    ft, r = bins.shape

    # W's rows, in blocks of S_b slots: the two bf16 terms of q_g and of q_h,
    # then the three of lo_g and of lo_h. Padding rows weigh 0; padding
    # slots (-1) also match inactive rows, and their sums are dropped.
    hit = nodes == pos  # (S_b, R)
    q_terms = [_bf16_terms(w[c : c + 1], 2) for c in (0, 1)]
    lo_terms = [_bf16_terms(w[c : c + 1], 3) for c in (2, 3)]
    rows = [t[j] for j in range(2) for t in q_terms]
    rows += [t[j] for j in range(3) for t in lo_terms]
    lhs = jnp.concatenate([jnp.where(hit, t, 0.0) for t in rows]).astype(jnp.bfloat16)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        q_out[...] = jnp.zeros_like(q_out)
        lo_out[...] = jnp.zeros_like(lo_out)

    two = 2 * s_b  # rows of one term: g slots, then h slots
    bin_iota = jax.lax.broadcasted_iota(jnp.int32, (n_bins, r), 0)
    contract = (((1,), (1,)), ((), ()))  # rows
    for f in range(ft):
        onehot = (bins[f : f + 1, :] == bin_iota).astype(jnp.bfloat16)  # (B, R)
        # bf16 operands are exact here and the MXU sums them in f32: each
        # term of the integer parts sums to an integer below 2^24 over a row
        # tile, so its f32 sum is exact
        part = jax.lax.dot_general(
            lhs, onehot, contract, preferred_element_type=jnp.float32
        )  # (10 S_b, B)
        cols = slice(f * n_bins, (f + 1) * n_bins)
        q_out[:, cols] += part[:two].astype(jnp.int32) + part[two : 2 * two].astype(jnp.int32)
        lo_out[:, cols] += part[2 * two : 3 * two] + (
            part[3 * two : 4 * two] + part[4 * two :]
        )


def _histogram_sums(
    bins, g, h, positions, build_nodes, n_bins, row_tile, feat_tile, interpret
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The kernel's sums before the join: ``(q_sum, lo_sum, scale)``, each
    with ``2 S_b`` rows (``S_b``: the build set rounded up to 8 slots), the
    gradient slots then the hessian slots. ``q_sum`` is the exact int32 sum
    of the integer parts, ``lo_sum`` the f32 sum of the remainders."""
    interpret = resolve_interpret(interpret)
    n_rows, m = bins.shape
    s = build_nodes.shape[0]
    s_b = round_up(s, 8)  # f32 sublanes: W's term blocks stay tile-aligned
    b_p = round_up(n_bins, LANES)
    rt = min(row_tile, round_up(max(n_rows, 1), LANES))
    n_rows_p, m_p = round_up(max(n_rows, 1), rt), round_up(m, feat_tile)

    # feature-major bins; missing values and padding become -1, which
    # matches no bin, so the kernel needs no validity mask
    bins_i = bins.astype(jnp.int32)
    bins_t = jnp.pad(
        jnp.where(bins_i == MISSING_BIN, -1, bins_i).T,
        ((0, m_p - m), (0, n_rows_p - n_rows)),
        constant_values=-1,
    )
    # a row tile's integer parts stay exact in f32, and each splits into
    # two bf16 terms
    q_bits = min(24 - (rt - 1).bit_length(), 16)
    qg, lo_g, scale_g = _fixed_point_split(g.astype(jnp.float32), n_rows, q_bits)
    qh, lo_h, scale_h = _fixed_point_split(h.astype(jnp.float32), n_rows, q_bits)
    w = jnp.pad(jnp.stack([qg, qh, lo_g, lo_h]), ((0, 0), (0, n_rows_p - n_rows)))
    pos = jnp.pad(
        positions.astype(jnp.int32), (0, n_rows_p - n_rows), constant_values=-1
    )[None, :]
    # padding slots hold -1 and may gather inactive rows: their sums are dropped
    nodes = jnp.pad(build_nodes.astype(jnp.int32), (0, s_b - s), constant_values=-1)
    slab = jax.ShapeDtypeStruct((2 * s_b, m_p * b_p), jnp.float32)
    block = pl.BlockSpec((2 * s_b, feat_tile * b_p), lambda f, r: (0, f))
    q_sum, lo_sum = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=b_p),
        grid=(m_p // feat_tile, n_rows_p // rt),
        in_specs=[
            pl.BlockSpec((s_b, 1), lambda f, r: (0, 0)),
            pl.BlockSpec((feat_tile, rt), lambda f, r: (f, r)),
            pl.BlockSpec((4, rt), lambda f, r: (0, r)),
            pl.BlockSpec((1, rt), lambda f, r: (0, r)),
        ],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct(slab.shape, jnp.int32), slab],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(nodes[:, None], bins_t, w, pos)
    scale = jnp.repeat(jnp.stack([scale_g, scale_h]), s_b)[:, None]
    return q_sum, lo_sum, scale


@functools.partial(
    jax.jit, static_argnames=("n_bins", "row_tile", "feat_tile", "interpret")
)
def build_histogram_slab(
    bins: jax.Array,  # (n_rows, m) int32 (uint8 ok; cast below)
    g: jax.Array,
    h: jax.Array,
    positions: jax.Array,  # (n_rows,) int32 GLOBAL node ids; < 0 = inactive
    build_nodes: jax.Array,  # (S,) int32 global build-node ids, all >= 0
    n_bins: int,
    *,
    row_tile: int = 1024,
    feat_tile: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """The kernel's result: a ``(2S, m_p * B_p)`` f32 slab.

    Row ``s`` holds the gradient sums of ``build_nodes[s]`` and row ``S + s``
    its hessian sums; column ``f * B_p + b`` is feature ``f``, bin ``b``.
    ``m_p`` is ``m`` rounded up to ``feat_tile`` and ``B_p`` is ``n_bins``
    rounded up to 128; the padding columns are zero. Each bin is its exact
    sum rounded to f32 (to within an ulp), whatever the order of the rows.
    """
    s = build_nodes.shape[0]
    q_sum, lo_sum, scale = _histogram_sums(
        bins, g, h, positions, build_nodes, n_bins, row_tile, feat_tile, interpret
    )
    slab = _fixed_point_join(q_sum, lo_sum, scale)
    return slab.reshape(2, -1, slab.shape[1])[:, :s].reshape(2 * s, -1)


def slab_to_nodes(slab: jax.Array, n_build: int, m: int, n_bins: int) -> jax.Array:
    """``(2S, m_p * B_p)`` slab -> ``(S, m, n_bins, 2)`` node histograms."""
    m_p = slab.shape[1] // round_up(n_bins, LANES)
    hist = slab.reshape(2, n_build, m_p, -1)[:, :, :m, :n_bins]
    return hist.transpose(1, 2, 3, 0)


@functools.partial(
    jax.jit, static_argnames=("n_bins", "row_tile", "feat_tile", "interpret")
)
def build_histogram_nodes(
    bins: jax.Array,  # (n_rows, m) int32 (uint8 ok; cast below)
    g: jax.Array,
    h: jax.Array,
    positions: jax.Array,  # (n_rows,) int32 GLOBAL node ids; < 0 = inactive
    build_nodes: jax.Array,  # (n_build,) int32 global build-node ids, all >= 0
    n_bins: int,
    *,
    row_tile: int = 1024,
    feat_tile: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused histogram over an explicit build-node set (the fused fast path).

    ``out[s]`` is the (m, n_bins, 2) gradient histogram of global node
    ``build_nodes[s]``. Rows whose position is not in ``build_nodes`` — frozen
    leaves, derive-set siblings, rows at other heap nodes — contribute to no
    bin; the window masking and node_map compaction happen inside the kernel
    (a broadcast compare against the node-id vector), so one launch replaces
    lookup + scatter.
    """
    parts = []
    for i in range(0, build_nodes.shape[0], _MAX_SLOTS):
        nodes = build_nodes[i : i + _MAX_SLOTS]
        slab = build_histogram_slab(
            bins, g, h, positions, nodes, n_bins,
            row_tile=row_tile, feat_tile=feat_tile, interpret=interpret,
        )
        parts.append(slab_to_nodes(slab, nodes.shape[0], bins.shape[1], n_bins))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "row_tile", "feat_tile", "interpret"),
)
def build_histogram(
    bins: jax.Array,  # (n_rows, m) int32 (uint8 ok; cast below)
    g: jax.Array,
    h: jax.Array,
    positions: jax.Array,  # (n_rows,) int32 level-local node ids; < 0 = inactive
    n_nodes: int,
    n_bins: int,
    node_map: jax.Array | None = None,  # (level_nodes,) int32 -> build slot or -1
    *,
    row_tile: int = 1024,
    feat_tile: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Level-local histogram of ``n_nodes`` slots on the fused kernel.

    With ``node_map`` (histogram subtraction) positions are first compacted
    to build slots; rows at derive nodes drop to -1 and match no slot.
    """
    if node_map is not None:
        positions = apply_node_map(positions, node_map)
    return build_histogram_nodes(
        bins, g, h, positions, jnp.arange(n_nodes, dtype=jnp.int32), n_bins,
        row_tile=row_tile, feat_tile=feat_tile, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("n_bins",))
def bin_onehot(bins: jax.Array, n_bins: int) -> jax.Array:
    """(n_rows, m * n_bins) f32 bin one-hot for the host contraction. ``bins``
    is level-invariant, so callers that build many node sets over the same
    rows (the per-tree level loop) compute this once and pass it to
    `build_histogram_nodes_host` — per-level cost then reduces to the dot,
    which scales with the build-set size. MISSING_BIN rows one-hot to zero."""
    bin_iota = jnp.arange(n_bins, dtype=jnp.int32)
    oh = (bins.astype(jnp.int32)[..., None] == bin_iota).astype(jnp.float32)
    return oh.reshape(bins.shape[0], bins.shape[1] * n_bins)


@functools.partial(jax.jit, static_argnames=("n_bins", "row_chunk"))
def build_histogram_nodes_host(
    bins: jax.Array,
    g: jax.Array,
    h: jax.Array,
    positions: jax.Array,  # (n_rows,) int32 GLOBAL node ids; < 0 = inactive
    build_nodes: jax.Array,  # (n_build,) int32 global build-node ids, all >= 0
    n_bins: int,
    bin_oh: jax.Array | None = None,  # optional precomputed `bin_onehot(bins)`
    *,
    row_chunk: int = 4096,
) -> jax.Array:
    """jnp mirror of the fused kernel's one-hot contraction, for non-TPU
    backends. Unlike the scatter oracle — whose cost is per-row and therefore
    identical whether a level builds all nodes or only the smaller children —
    this dot's cost scales with the build-set size, so histogram subtraction
    halves the dominant term off-TPU exactly as it does on the MXU.

    With a precomputed ``bin_oh`` (see `bin_onehot`) the whole contraction is
    one BLAS dot. Without it, rows are processed in fixed ``row_chunk``
    blocks under `lax.scan`, bounding the one-hot working set to
    ``row_chunk * m * n_bins`` floats. Both paths are deterministic
    call-to-call. Like the kernel, they split g and h into exactly summed
    integer parts and small remainders, so each bin is its exact sum
    rounded about once, and both paths agree with the kernel and the oracle
    bit for bit on nearly every bin."""
    n_rows, m = bins.shape
    s = build_nodes.shape[0]
    nodes = build_nodes.astype(jnp.int32)
    # one dot sums this many rows; their integer parts must stay below 2^24
    dot_rows = n_rows if bin_oh is not None else row_chunk
    q_bits = 24 - max(dot_rows - 1, 1).bit_length()
    qg, lo_g, scale_g = _fixed_point_split(g.astype(jnp.float32), n_rows, q_bits)
    qh, lo_h, scale_h = _fixed_point_split(h.astype(jnp.float32), n_rows, q_bits)
    cols = jnp.stack([qg, qh, lo_g, lo_h], axis=1)  # (n_rows, 4)

    def contract(pos, cols, oh):
        """(4S, F*B): integer parts of g, h, then remainders of g, h."""
        slot_oh = (pos[:, None] == nodes[None, :]).astype(jnp.float32)  # (R, S)
        wm = (slot_oh[:, None, :] * cols[:, :, None]).reshape(pos.shape[0], 4 * s)
        hist = jax.lax.dot_general(
            wm, oh, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return hist[: 2 * s].astype(jnp.int32), hist[2 * s :]

    if bin_oh is not None:
        # precomputed one-hot: one full-height BLAS dot, no chunking (the
        # scan's slice/concat overhead would dominate the S-scaled dot)
        q_sum, lo_sum = contract(positions.astype(jnp.int32), cols, bin_oh)
    else:
        pad = -n_rows % row_chunk
        # pad rows match no node (pos -1 vs non-negative ids) and no bin
        bins_p = jnp.pad(
            bins.astype(jnp.int32), ((0, pad), (0, 0)), constant_values=MISSING_BIN
        )
        bin_iota = jnp.arange(n_bins, dtype=jnp.int32)
        oh_p = (bins_p[..., None] == bin_iota).astype(jnp.float32).reshape(
            n_rows + pad, m * n_bins
        )
        pos_p = jnp.pad(positions.astype(jnp.int32), (0, pad), constant_values=-1)
        n_chunks = (n_rows + pad) // row_chunk

        def body(acc, xs):
            q, lo = contract(*xs)
            return (acc[0] + q, acc[1] + lo), None

        xs = (
            pos_p.reshape(n_chunks, row_chunk),
            jnp.pad(cols, ((0, pad), (0, 0))).reshape(n_chunks, row_chunk, 4),
            oh_p.reshape(n_chunks, row_chunk, m * n_bins),
        )
        zeros = jnp.zeros((2 * s, m * n_bins), jnp.int32)
        (q_sum, lo_sum), _ = jax.lax.scan(body, (zeros, zeros.astype(jnp.float32)), xs)
    scale = jnp.repeat(jnp.stack([scale_g, scale_h]), s)[:, None]
    acc = _fixed_point_join(q_sum, lo_sum, scale)
    return acc.reshape(2, s, m, n_bins).transpose(1, 2, 3, 0)
