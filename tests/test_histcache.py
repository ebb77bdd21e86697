"""Histogram subtraction (core/histcache.py): invariant, plan/expand, builders.

The whole trick rests on one identity — a split partitions a parent's rows
into its children and the gradient histogram is additive over rows, so
``hist(parent) == hist(left) + hist(right)`` for every (node, feature, bin,
g/h) cell. Property-test that, then check the machinery end to end: the
node_map kernel path, plan/expand reconstruction, and subtraction-mode
`grow_tree` matching the full-build baseline across shape/missing sweeps.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from oracle import assert_trees_equal

from repro.core.booster import bin_valid_from_cuts
from repro.core.ellpack import create_ellpack_inmemory
from repro.core.histcache import (
    HistogramCache,
    expand_level,
    level_row_counts,
    plan_level,
)
from repro.core.tree import TreeParams, grow_tree
from repro.kernels import ref
from repro.kernels.histogram import build_histogram as hist_pl

MISSING = ref.MISSING_BIN


def _hist_inputs(n, m, n_bins, seed, missing_rate):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, (n, m)).astype(np.int32)
    bins[rng.random((n, m)) < missing_rate] = MISSING
    g = rng.normal(size=n).astype(np.float32)
    h = rng.random(n).astype(np.float32)
    return bins, g, h, rng


# ------------------------------------------------------ subtraction invariant

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - bare env still collects
    HAVE_HYPOTHESIS = False


def _check_parent_is_sum_of_children(n, m, n_bins, missing_rate, seed):
    """hist(parent) == hist(left) + hist(right) for ANY row partition."""
    bins, g, h, rng = _hist_inputs(n, m, n_bins, seed, missing_rate)
    go_left = rng.random(n) < rng.random()  # arbitrary split of the rows
    bins_j, g_j, h_j = jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h)

    all_at_0 = jnp.zeros(n, jnp.int32)
    parent = ref.build_histogram(bins_j, g_j, h_j, all_at_0, 1, n_bins)
    left = ref.build_histogram(
        bins_j, g_j, h_j, jnp.where(jnp.asarray(go_left), 0, -1), 1, n_bins
    )
    right = ref.build_histogram(
        bins_j, g_j, h_j, jnp.where(jnp.asarray(~go_left), 0, -1), 1, n_bins
    )
    np.testing.assert_allclose(
        np.asarray(parent), np.asarray(left + right), rtol=1e-5, atol=1e-5
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(16, 400),
        m=st.integers(1, 8),
        n_bins=st.sampled_from([4, 16, 32]),
        missing_rate=st.sampled_from([0.0, 0.05, 0.3]),
        seed=st.integers(0, 2**16),
    )
    def test_parent_hist_is_sum_of_children(n, m, n_bins, missing_rate, seed):
        _check_parent_is_sum_of_children(n, m, n_bins, missing_rate, seed)

else:  # bare env: keep a deterministic slice of the property sweep

    @pytest.mark.parametrize(
        "n,m,n_bins,missing_rate,seed",
        [(64, 2, 16, 0.0, 0), (211, 5, 32, 0.05, 1), (400, 8, 4, 0.3, 2)],
    )
    def test_parent_hist_is_sum_of_children(n, m, n_bins, missing_rate, seed):
        _check_parent_is_sum_of_children(n, m, n_bins, missing_rate, seed)


# ----------------------------------------------------------- node_map kernels

@pytest.mark.parametrize("n,m,n_bins,count", [(257, 3, 16, 4), (600, 7, 32, 8)])
def test_node_map_path_matches_full_build(n, m, n_bins, count):
    """ref and Pallas node_map paths == slicing the build nodes out of a full
    build; derive-set rows contribute nothing."""
    bins, g, h, rng = _hist_inputs(n, m, n_bins, seed=n, missing_rate=0.05)
    pos = rng.integers(-1, count, n).astype(np.int32)
    bins_j, g_j, h_j, pos_j = (jnp.asarray(v) for v in (bins, g, h, pos))

    full = ref.build_histogram(bins_j, g_j, h_j, pos_j, count, n_bins)
    counts = level_row_counts(pos_j, 0, count)
    node_map, build_left = plan_level(count, counts)
    built_ref = ref.build_histogram(
        bins_j, g_j, h_j, pos_j, count // 2, n_bins, node_map=node_map
    )
    built_pl = hist_pl(
        bins_j, g_j, h_j, pos_j, count // 2, n_bins, node_map=node_map,
        interpret=True,
    )
    build_ids = np.asarray(node_map)
    want = np.asarray(full)[np.where(build_ids >= 0)[0]]  # build nodes, slot order
    np.testing.assert_allclose(np.asarray(built_ref), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(built_pl), want, rtol=1e-5, atol=1e-5)


def test_plan_builds_smaller_child_and_expand_reconstructs():
    counts = jnp.asarray([10, 3, 0, 7, 5, 5], jnp.int32)  # 3 sibling pairs
    node_map, build_left = plan_level(6, counts)
    # pair 0: right smaller; pair 1: left smaller; pair 2: tie -> left
    np.testing.assert_array_equal(np.asarray(build_left), [False, True, True])
    np.testing.assert_array_equal(np.asarray(node_map), [-1, 0, 1, -1, 2, -1])

    rng = np.random.default_rng(0)
    left = rng.normal(size=(3, 2, 4, 2)).astype(np.float32)
    right = rng.normal(size=(3, 2, 4, 2)).astype(np.float32)
    parent = left + right
    built = np.where(np.asarray(build_left)[:, None, None, None], left, right)
    full = np.asarray(expand_level(jnp.asarray(parent), jnp.asarray(built), build_left))
    want = np.stack([left, right], axis=1).reshape(6, 2, 4, 2)
    np.testing.assert_allclose(full, want, rtol=1e-5, atol=1e-6)


def test_level_row_counts_ignores_frozen_rows():
    # offset 3, count 4: rows at nodes 3..6 counted; frozen (1) and -1 ignored
    pos = jnp.asarray([3, 3, 4, 6, 1, -1, 5], jnp.int32)
    got = np.asarray(level_row_counts(pos, 3, 4))
    np.testing.assert_array_equal(got, [2, 1, 1, 1])


# ------------------------------------------- grow_tree equivalence (the gate)

SWEEP = [
    # (n, m, max_bin, max_depth, missing_rate, seed)
    (400, 5, 8, 3, 0.0, 0),
    (777, 3, 16, 4, 0.1, 1),
    (1500, 10, 32, 6, 0.05, 2),
    (256, 8, 16, 5, 0.4, 3),
]


@pytest.mark.parametrize("n,m,max_bin,max_depth,missing_rate,seed", SWEEP)
def test_subtraction_grow_tree_matches_full_build(n, m, max_bin, max_depth, missing_rate, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(np.float32)
    if missing_rate:
        X[rng.random((n, m)) < missing_rate] = np.nan
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    ell = create_ellpack_inmemory(X, max_bin=max_bin)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    bv = bin_valid_from_cuts(ell.cuts, max_bin)

    cache = HistogramCache(enabled=True)
    sub = grow_tree(
        bins, g, h, max_bin, bv, TreeParams(max_depth=max_depth, hist_subtraction=True),
        ell.cuts.values, ell.cuts.ptrs, hist_cache=cache,
    )
    full = grow_tree(
        bins, g, h, max_bin, bv, TreeParams(max_depth=max_depth, hist_subtraction=False),
        ell.cuts.values, ell.cuts.ptrs,
    )
    # subtraction is exact only up to f32 accumulation order — the shared
    # oracle pins the semantic tree (structure, routing, ~all splits, leaves)
    assert_trees_equal(
        sub.tree, full.tree, got_positions=sub.positions, want_positions=full.positions
    )
    if max_depth >= 2:
        # the whole point: strictly fewer node-histograms built than a full build
        assert cache.stats.built_nodes < cache.stats.built_nodes + cache.stats.derived_nodes
        assert cache.stats.built_rows <= cache.stats.total_rows / 2 + 1e-6


def test_booster_paths_agree_with_subtraction_off():
    """End-to-end: the out-of-core streaming build, subtraction on vs off,
    same predictions within float tolerance (Table-2 AUC parity)."""
    from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
    from repro.data.dmatrix import IterDMatrix
    from repro.data.synthetic import SyntheticSource

    src = SyntheticSource(n_rows=900, num_features=10, batch_rows=256, task="higgs", seed=5)
    X, y = src.materialize()
    common = dict(n_estimators=4, max_depth=4, max_bin=16, objective="binary:logistic", seed=0)

    dm = IterDMatrix(src, max_bin=16, page_bytes=8 * 1024)
    out_of_core = ExecutionPolicy(mode="out_of_core")
    b_sub = GradientBooster(BoosterParams(hist_subtraction=True, **common), policy=out_of_core)
    b_sub.fit(dm)
    b_full = GradientBooster(BoosterParams(hist_subtraction=False, **common), policy=out_of_core)
    b_full.fit(dm)
    np.testing.assert_allclose(
        b_sub.predict_margin(X), b_full.predict_margin(X), rtol=1e-4, atol=1e-5
    )
    assert b_sub.hist_cache.stats.built_nodes > 0
    assert b_full.hist_cache.stats.built_nodes == 0  # disabled cache plans nothing
