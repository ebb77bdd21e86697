"""Fused histogram path: one launch does bin lookup + multi-node scatter.

Covers the PR's three moving parts end to end:
  - `build_histogram_nodes` (Pallas interpret, host one-hot contraction, and
    the jnp oracle) agree across ragged shapes, non-contiguous build sets,
    MISSING bins, and inactive rows — and the fused path reproduces the old
    window-mask + node_map two-launch result bit-for-bit on the oracle.
  - tile-padding regression: padding rows/features contribute to NO
    (node, bin) cell for non-multiple-of-tile shapes.
  - batched lossguide pops (`TreeParams.pop_batch`): several frontier leaves
    share one partition pass and one histogram launch, and the grown tree is
    the strict best-first tree when the leaf budget is not binding.
  - async histogram spill: a fetch racing an in-flight device->host copy is
    bit-exact, `discard_node` cancels an in-flight spill, and spill
    wall-seconds never leak into the stream ledger that `overlap_ratio`
    reads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from oracle import assert_trees_equal

from repro.core.booster import BoosterParams, bin_valid_from_cuts
from repro.core.ellpack import create_ellpack_inmemory
from repro.core.histcache import HistogramStore, LevelPlan, level_row_counts, plan_level
from repro.core.tree import TreeParams, grow_tree
from repro.fault import inject as fault_inject
from repro.fault.inject import FaultPlan, FaultSpec
from repro.kernels import ops, ref
from repro.kernels.histogram import (
    bin_onehot,
    build_histogram_nodes as fused_pl,
    build_histogram_nodes_host,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - bare env still collects
    HAVE_HYPOTHESIS = False

MISSING = ref.MISSING_BIN


def _inputs(n, m, n_bins, n_nodes, seed, missing_rate=0.05, inactive_rate=0.2):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, (n, m)).astype(np.int32)
    bins[rng.random((n, m)) < missing_rate] = MISSING
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32)
    pos = rng.integers(0, n_nodes, n).astype(np.int32)
    pos[rng.random(n) < inactive_rate] = -1  # frozen / other-heap-node rows
    return (jnp.asarray(v) for v in (bins, g, h, pos))


# ------------------------------------------------- fused == oracle everywhere


def _check_fused_matches_oracle(n, m, n_bins, n_build, seed):
    """Pallas (interpret), host contraction (both with and without the
    precomputed bin one-hot), and the jnp oracle agree on a random
    non-contiguous build set."""
    bins, g, h, pos = _inputs(n, m, n_bins, n_nodes=2 * n_build + 3, seed=seed)
    rng = np.random.default_rng(seed + 1)
    nodes = jnp.asarray(
        np.sort(rng.choice(2 * n_build + 3, size=n_build, replace=False)).astype(
            np.int32
        )
    )
    want = np.asarray(ops.build_histogram_nodes(bins, g, h, pos, nodes, n_bins, impl="ref"))

    got_pl = np.asarray(fused_pl(bins, g, h, pos, nodes, n_bins, interpret=True))
    np.testing.assert_allclose(got_pl, want, rtol=1e-5, atol=1e-4)

    got_host = np.asarray(build_histogram_nodes_host(bins, g, h, pos, nodes, n_bins))
    np.testing.assert_allclose(got_host, want, rtol=1e-5, atol=1e-4)

    oh = bin_onehot(bins, n_bins)
    got_pre = np.asarray(build_histogram_nodes_host(bins, g, h, pos, nodes, n_bins, oh))
    np.testing.assert_allclose(got_pre, want, rtol=1e-5, atol=1e-4)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(1, 700),
        m=st.integers(1, 9),
        n_bins=st.sampled_from([4, 16, 32]),
        n_build=st.integers(1, 6),
        seed=st.integers(0, 10_000),
    )
    def test_fused_matches_oracle(n, m, n_bins, n_build, seed):
        _check_fused_matches_oracle(n, m, n_bins, n_build, seed)

else:  # bare env: deterministic slice of the property sweep

    @pytest.mark.parametrize(
        "n,m,n_bins,n_build,seed",
        [
            (1, 1, 4, 1, 0),  # single row, single feature
            (255, 3, 16, 2, 1),  # one short of the row tile
            (257, 9, 32, 5, 2),  # one past the row tile, ragged features
            (600, 7, 16, 6, 3),
        ],
    )
    def test_fused_matches_oracle(n, m, n_bins, n_build, seed):
        _check_fused_matches_oracle(n, m, n_bins, n_build, seed)


def test_fused_oracle_equals_windowed_node_map_path_bitwise():
    """On a contiguous window the fused build-node formulation IS the old
    window-mask + node_map two-launch path: same scatter indices in the same
    order, so the oracle results are bit-identical, not just close."""
    n, m, n_bins, count = 600, 5, 16, 8
    offset = count - 1
    bins, g, h, pos = _inputs(n, m, n_bins, n_nodes=count, seed=3, inactive_rate=0.1)
    pos_global = jnp.where(pos >= 0, pos + offset, -1)

    counts = level_row_counts(pos_global, offset, count)
    node_map, build_left = plan_level(count, counts)
    level_pos = jnp.where(
        (pos_global >= offset) & (pos_global < offset + count), pos_global - offset, -1
    )
    want = ref.build_histogram(
        bins, g, h, level_pos, count // 2, n_bins, node_map=node_map
    )

    pairs = count // 2
    build_nodes = (
        offset + 2 * jnp.arange(pairs, dtype=jnp.int32) + jnp.where(build_left, 0, 1)
    )
    got = ref.build_histogram_nodes(bins, g, h, pos_global, build_nodes, n_bins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- pad-leak regression


@pytest.mark.parametrize("n,m", [(1, 1), (255, 3), (257, 9), (300, 17)])
def test_tile_padding_contributes_to_no_bin(n, m):
    """Regression for tile-padding fills: with shapes that are NOT multiples of
    the (row, feature) tiles, the kernel pads rows and features. Pad rows
    carry pos=-1 (matches no build node) and bin -1 (matches no bin
    column), so a build node with zero real rows must come out exactly zero —
    any fill leak lands in (slot 0, bin 0) and breaks this."""
    n_bins = 8
    rng = np.random.default_rng(n + m)
    # every real row sits at node 1 with bins >= 1: node 0 and bin 0 are
    # observably empty in every slot of the output
    bins = jnp.asarray(rng.integers(1, n_bins, (n, m)).astype(np.int32))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    pos = jnp.ones(n, jnp.int32)
    nodes = jnp.asarray([0, 1], jnp.int32)

    got = np.asarray(fused_pl(bins, g, h, pos, nodes, n_bins, interpret=True))
    assert got[0].sum() == 0.0, "pad rows leaked into an empty build node"
    assert np.abs(got[:, :, 0, :]).sum() == 0.0, "pad bins leaked into bin 0"
    want = np.asarray(ref.build_histogram_nodes(bins, g, h, pos, nodes, n_bins))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)

    # the windowed path runs the same kernel behind a node_map remap
    from repro.kernels.histogram import build_histogram as windowed_pl

    got_w = np.asarray(windowed_pl(bins, g, h, pos, 2, n_bins, interpret=True))
    assert got_w[0].sum() == 0.0
    assert np.abs(got_w[:, :, 0, :]).sum() == 0.0


# ------------------------------------------------------------- batched pops


def _lossguide_inputs(seed=0, n=1500, m=6, max_bin=16):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(np.float32)
    X[rng.random((n, m)) < 0.05] = np.nan
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    ell = create_ellpack_inmemory(X, max_bin=max_bin)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    bv = bin_valid_from_cuts(ell.cuts, max_bin)
    return ell, bins, g, h, bv


@pytest.mark.parametrize("pop_batch", [2, 4])
def test_pop_batch_matches_strict_best_first_in_core(pop_batch):
    """With a non-binding leaf budget the expanded node set is order
    independent, so batched pops grow the strict best-first tree."""
    ell, bins, g, h, bv = _lossguide_inputs()
    base = dict(max_depth=5, grow_policy="lossguide", max_leaves=0)
    tp1 = TreeParams(pop_batch=1, **base)
    tpk = TreeParams(pop_batch=pop_batch, **base)
    t1 = grow_tree(bins, g, h, 16, bv, tp1, ell.cuts.values, ell.cuts.ptrs)
    tk = grow_tree(bins, g, h, 16, bv, tpk, ell.cuts.values, ell.cuts.ptrs)
    assert_trees_equal(
        tk.tree, t1.tree,
        got_positions=tk.positions, want_positions=t1.positions,
        exact=True,
    )


def test_pop_batch_matches_strict_best_first_paged():
    from repro.core.ellpack import EllpackPage
    from repro.core.outofcore import build_tree_paged
    from repro.pipeline import PageStream

    ell, bins, g, h, bv = _lossguide_inputs(seed=4)
    bins_u8 = ell.single_page().bins
    n = bins_u8.shape[0]
    cuts = np.linspace(0, n, 4).astype(int)
    extents = [(int(cuts[i]), int(cuts[i + 1] - cuts[i])) for i in range(3)]
    pages = [EllpackPage(bins=bins_u8[lo:lo + nr], row_offset=lo) for lo, nr in extents]

    def make_stream(indices=None):
        return PageStream.from_host_pages(
            pages, indices=indices,
            to_array=lambda p: np.ascontiguousarray(p.bins),
            put=lambda a: jax.device_put(a).astype(jnp.int32),
        )

    trees = {}
    for pb in (1, 3):
        tp = TreeParams(
            max_depth=5, grow_policy="lossguide", max_leaves=0, pop_batch=pb
        )
        trees[pb], _ = build_tree_paged(
            make_stream, extents, g, h, 16, bv, tp, ell.cuts.values, ell.cuts.ptrs
        )
    assert_trees_equal(trees[3], trees[1], exact=True)


def test_pop_batch_validation():
    with pytest.raises(ValueError, match="pop_batch"):
        TreeParams(max_depth=3, pop_batch=0)
    with pytest.raises(ValueError, match="pop_batch"):
        BoosterParams(pop_batch=0)
    assert BoosterParams(pop_batch=3).tree_params().pop_batch == 3


# --------------------------------------------------------- async spill races


def _fake_hist(depth, n_bins=4, m=2, scale=1.0):
    count = 2**depth
    base = np.arange(count * m * n_bins * 2, dtype=np.float32).reshape(
        count, m, n_bins, 2
    )
    return jnp.asarray(base * scale)


def test_fetch_racing_inflight_spill_is_bit_exact():
    """`_spill` flips the logical tier immediately but keeps the copy in
    flight; a fetch that lands inside that window must hit the completion
    barrier and read exactly what was spilled."""
    store = HistogramStore(enabled=True, budget_bytes=0)
    store.reset()
    arr = _fake_hist(2)
    store._put(("L", 2), arr, kind="level", priority=2.0)
    store._enforce_budget()  # budget 0: spills immediately
    assert store.tier_of(("L", 2)) == "host"
    assert ("L", 2) in store._inflight  # copy still in flight
    got = store._fetch(("L", 2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(arr))
    assert not store._inflight  # barrier completed the copy


def test_discard_node_cancels_inflight_spill():
    """discard_node racing an async spill must not resurrect the histogram:
    the in-flight device ref is dropped with the entry, and a later budget
    enforcement can never complete a cancelled copy into the host tier."""
    store = HistogramStore(enabled=True, budget_bytes=0)
    store.reset()
    store._put(("N", 7), _fake_hist(1), kind="node", priority=1.0)
    store._enforce_budget()
    assert ("N", 7) in store._inflight
    store.discard_node(7)
    assert ("N", 7) not in store._inflight
    assert ("N", 7) not in store._host
    assert store.tier_of(("N", 7)) is None


def test_inflight_depth_is_bounded():
    store = HistogramStore(enabled=True, budget_bytes=0)
    store.reset()
    for d in range(4):
        store._put(("L", d), _fake_hist(d), kind="level", priority=float(d))
        store._enforce_budget()
    assert len(store._inflight) <= store.max_inflight_spills
    # completed copies are real pinned host buffers, bit-equal to the source
    done = [k for k in store._host if store._host[k] is not None]
    assert done, "oldest spills should have been completed by the depth bound"
    for key in done:
        np.testing.assert_array_equal(store._host[key], np.asarray(_fake_hist(key[1])))


def test_delayed_fetch_crash_window_is_bit_exact_and_private():
    """Chaos probe for the async-spill crash window: a delay injected at the
    "hist_store.fetch" site widens the race between an in-flight spill and
    the fetch that needs its bytes. The tree must come out bit-identical to
    the undelayed build, and the spill/fetch wall-seconds must NOT appear in
    the stream ledger `overlap_ratio` reads (histogram traffic is byte-only
    by design)."""
    ell, bins, g, h, bv = _lossguide_inputs(seed=9, n=800, m=4)
    tp = TreeParams(max_depth=6, hist_subtraction=True)

    def build(with_fault):
        store = HistogramStore(enabled=True, budget_bytes=0)
        if with_fault:
            plan = FaultPlan.of(
                FaultSpec(site="hist_store.fetch", at=1, count=-1,
                          action="delay", delay_s=0.01)
            )
            with fault_inject.injected(plan):
                out = grow_tree(
                    bins, g, h, 16, bv, tp, ell.cuts.values, ell.cuts.ptrs,
                    hist_cache=store,
                )
        else:
            out = grow_tree(
                bins, g, h, 16, bv, tp, ell.cuts.values, ell.cuts.ptrs,
                hist_cache=store,
            )
        return out, store

    want, _ = build(with_fault=False)
    got, store = build(with_fault=True)
    for f in want.tree._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got.tree, f)), np.asarray(getattr(want.tree, f)),
            err_msg=f"TreeArrays.{f} differs under delayed fetch",
        )

    ts = store.transfer_stats
    assert ts.hist_spills > 0 and ts.hist_fetches > 0  # the race was exercised
    # spill/fetch seconds must not dilute the page pipeline's overlap ledger
    assert ts.stream_fetch_seconds == 0.0
    assert ts.stream_stage_seconds == 0.0
    assert ts.overlap_ratio == 0.0
