"""Leaf-wise (lossguide) growth: cross-builder equivalence vs depthwise.

The pin: with ``max_leaves = 2**max_depth`` and untied gains, best-first
growth pops every positive-gain candidate, so it must reproduce the
depthwise tree bit-for-bit (up to f32 ties) — on the in-core, paged
out-of-core, and distributed builders alike. Truncated budgets must keep
exactly the highest-gain splits, and the shrunken heap capacity for
``max_leaves``-bounded trees must stay correct end to end (prediction,
serialization, margin caching).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from oracle import assert_positions_are_leaves, assert_trees_equal

from repro.core.booster import bin_valid_from_cuts
from repro.core.ellpack import EllpackPage, create_ellpack_inmemory
from repro.core.outofcore import build_tree_paged
from repro.core.tree import TreeParams, grow_tree, predict_tree_bins
from repro.data.pages import TransferStats
from repro.pipeline import PageStream

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - bare env still collects
    HAVE_HYPOTHESIS = False


def _tree_inputs(n, m, max_bin, missing_rate, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(np.float32)
    if missing_rate:
        X[rng.random((n, m)) < missing_rate] = np.nan
    # continuous random gradients make exact gain ties vanishingly unlikely
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    ell = create_ellpack_inmemory(X, max_bin=max_bin)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    bv = bin_valid_from_cuts(ell.cuts, max_bin)
    return ell, bins, g, h, bv


def _paged_build(ell, g, h, max_bin, bv, tp, n_pages=3):
    bins_u8 = ell.single_page().bins
    n = bins_u8.shape[0]
    cuts = np.linspace(0, n, n_pages + 1).astype(int)
    extents = [(int(cuts[i]), int(cuts[i + 1] - cuts[i])) for i in range(n_pages)]
    pages = [EllpackPage(bins=bins_u8[lo:lo + nr], row_offset=lo) for lo, nr in extents]
    stats = TransferStats()

    def make_stream():
        return PageStream.from_host_pages(
            pages,
            to_array=lambda p: np.ascontiguousarray(p.bins),
            put=lambda a: jax.device_put(a).astype(jnp.int32),
            stats=stats,
        )

    tree, positions = build_tree_paged(
        make_stream, extents, g, h, max_bin, bv, tp,
        ell.cuts.values, ell.cuts.ptrs,
    )
    pos_full = jnp.concatenate([positions[i] for i in range(len(extents))])
    return tree, pos_full


def _distributed_build(ell, bins, g, h, max_bin, bv, max_depth, max_leaves):
    from repro.distributed import DistConfig, grow_tree_distributed

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    cfg = DistConfig(
        data_axes=("data",), grow_policy="lossguide", max_leaves=max_leaves
    )
    return grow_tree_distributed(
        mesh, bins, g, h, max_bin, bv, TreeParams(max_depth=max_depth), cfg,
        ell.cuts.values, ell.cuts.ptrs,
    )


def _check_equivalence(n, m, max_bin, max_depth, missing_rate, seed):
    """lossguide @ full leaf budget == depthwise, on all three builders."""
    ell, bins, g, h, bv = _tree_inputs(n, m, max_bin, missing_rate, seed)
    tp_dw = TreeParams(max_depth=max_depth)
    tp_lg = TreeParams(
        max_depth=max_depth, grow_policy="lossguide", max_leaves=2**max_depth
    )

    dw = grow_tree(bins, g, h, max_bin, bv, tp_dw, ell.cuts.values, ell.cuts.ptrs)
    lg = grow_tree(bins, g, h, max_bin, bv, tp_lg, ell.cuts.values, ell.cuts.ptrs)
    assert_trees_equal(
        lg.tree, dw.tree, got_positions=lg.positions, want_positions=dw.positions
    )

    tree_p, pos_p = _paged_build(ell, g, h, max_bin, bv, tp_lg)
    assert_trees_equal(
        tree_p, dw.tree, got_positions=pos_p, want_positions=dw.positions
    )

    tree_d, pos_d = _distributed_build(
        ell, bins, g, h, max_bin, bv, max_depth, 2**max_depth
    )
    assert_trees_equal(
        tree_d, dw.tree, got_positions=pos_d, want_positions=dw.positions
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None)
    @given(
        n=st.integers(64, 500),
        m=st.integers(2, 8),
        max_bin=st.sampled_from([8, 16]),
        max_depth=st.integers(2, 4),
        missing_rate=st.sampled_from([0.0, 0.1]),
        seed=st.integers(0, 2**16),
    )
    def test_lossguide_full_budget_matches_depthwise(
        n, m, max_bin, max_depth, missing_rate, seed
    ):
        _check_equivalence(n, m, max_bin, max_depth, missing_rate, seed)

else:  # bare env: deterministic slice of the property sweep

    @pytest.mark.parametrize(
        "n,m,max_bin,max_depth,missing_rate,seed",
        [(400, 5, 8, 3, 0.0, 0), (300, 3, 16, 4, 0.1, 1), (150, 8, 16, 2, 0.0, 2)],
    )
    def test_lossguide_full_budget_matches_depthwise(
        n, m, max_bin, max_depth, missing_rate, seed
    ):
        _check_equivalence(n, m, max_bin, max_depth, missing_rate, seed)


def test_lossguide_respects_max_leaves_and_picks_best_gain_first():
    ell, bins, g, h, bv = _tree_inputs(500, 6, 16, 0.05, seed=7)
    full = grow_tree(
        bins, g, h, 16, bv,
        TreeParams(max_depth=4, grow_policy="lossguide", max_leaves=16),
        ell.cuts.values, ell.cuts.ptrs,
    )
    n_leaves_full = len(np.unique(np.asarray(full.positions)))

    for budget in (2, 3, 5):
        res = grow_tree(
            bins, g, h, 16, bv,
            TreeParams(max_depth=4, grow_policy="lossguide", max_leaves=budget),
            ell.cuts.values, ell.cuts.ptrs,
        )
        reached = np.unique(np.asarray(res.positions))
        assert len(reached) == min(budget, n_leaves_full)
        assert_positions_are_leaves(res.tree, res.positions)

    # max_leaves=2 is a stump whose single split is the depthwise root split
    stump = grow_tree(
        bins, g, h, 16, bv,
        TreeParams(max_depth=4, grow_policy="lossguide", max_leaves=2),
        ell.cuts.values, ell.cuts.ptrs,
    )
    dw = grow_tree(
        bins, g, h, 16, bv, TreeParams(max_depth=4), ell.cuts.values, ell.cuts.ptrs
    )
    assert int(stump.tree.feature[0]) == int(dw.tree.feature[0])
    assert int(stump.tree.split_bin[0]) == int(dw.tree.split_bin[0])
    assert not bool(stump.tree.is_leaf[0])
    assert bool(stump.tree.is_leaf[1]) and bool(stump.tree.is_leaf[2])


def test_n_total_nodes_capacity_for_leaf_bounded_trees():
    """Regression: node capacity must come from the *effective* depth — a
    max_leaves-bounded tree never needs the full max_depth heap (the old
    complete-tree accounting would allocate 2^31-1 nodes below)."""
    tp = TreeParams(max_depth=30, grow_policy="lossguide", max_leaves=8)
    assert tp.effective_max_depth == 7  # 8 leaves -> at most 7 splits deep
    assert tp.n_total_nodes == 2**8 - 1
    assert tp.leaf_budget == 8

    # depthwise accounting unchanged
    assert TreeParams(max_depth=6).n_total_nodes == 2**7 - 1
    # unbounded lossguide falls back to the complete tree over max_depth
    assert TreeParams(max_depth=5, grow_policy="lossguide").n_total_nodes == 2**6 - 1
    assert TreeParams(max_depth=5, grow_policy="lossguide").leaf_budget == 32

    # and the bounded tree actually builds + predicts with the small arrays
    ell, bins, g, h, bv = _tree_inputs(300, 4, 8, 0.0, seed=3)
    res = grow_tree(
        bins, g, h, 8, bv,
        TreeParams(max_depth=30, grow_policy="lossguide", max_leaves=8),
        ell.cuts.values, ell.cuts.ptrs,
    )
    assert res.tree.n_total == 255
    assert_positions_are_leaves(res.tree, res.positions)
    pred = predict_tree_bins(res.tree, bins, res.tree.max_depth)
    np.testing.assert_allclose(
        np.asarray(pred),
        np.asarray(res.tree.leaf_value)[np.asarray(res.positions)],
        rtol=1e-6,
    )


def test_grow_policy_validation():
    with pytest.raises(ValueError, match="grow_policy"):
        TreeParams(grow_policy="bestfirst")
    with pytest.raises(ValueError, match="max_leaves"):
        TreeParams(max_leaves=-1)


def test_lossguide_booster_end_to_end_and_serialization(tmp_path):
    """Non-complete trees survive the whole life cycle: boosting, margin
    cache, save/load, prediction parity."""
    from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
    from repro.core.objectives import auc
    from repro.data.dmatrix import IterDMatrix
    from repro.data.synthetic import SyntheticSource

    src = SyntheticSource(
        n_rows=900, num_features=10, batch_rows=256, task="higgs", seed=5
    )
    X, y = src.materialize()
    params = BoosterParams(
        n_estimators=4, max_depth=5, max_bin=16, objective="binary:logistic",
        seed=0, grow_policy="lossguide", max_leaves=12,
    )

    b = GradientBooster(params).fit(X, y)
    assert auc(y, b.predict(X)) > 0.75
    assert b.trees[0].n_total == params.tree_params().n_total_nodes

    b.save(str(tmp_path / "lg"))
    b2 = GradientBooster.load(str(tmp_path / "lg"))
    assert b2.params.grow_policy == "lossguide" and b2.params.max_leaves == 12
    np.testing.assert_allclose(
        b.predict_margin(X), b2.predict_margin(X), rtol=1e-6, atol=1e-7
    )

    eb = GradientBooster(params, policy=ExecutionPolicy(mode="out_of_core"))
    eb.fit(IterDMatrix(src, max_bin=16, page_bytes=8 * 1024))
    assert auc(y, eb.predict(X)) > 0.75
    # streaming margin cache stays consistent with full re-prediction
    np.testing.assert_allclose(
        eb.margins_, eb.predict_margin(X), rtol=1e-4, atol=1e-5
    )


def test_lossguide_subtraction_ledger_and_off_switch():
    """Per-node subtraction builds exactly one child per pop and halves the
    scanned rows; disabling it must not change the tree."""
    from repro.core.histcache import HistogramCache

    ell, bins, g, h, bv = _tree_inputs(400, 5, 16, 0.0, seed=11)
    cache = HistogramCache(enabled=True)
    sub = grow_tree(
        bins, g, h, 16, bv,
        TreeParams(max_depth=4, grow_policy="lossguide", max_leaves=16),
        ell.cuts.values, ell.cuts.ptrs, hist_cache=cache,
    )
    full = grow_tree(
        bins, g, h, 16, bv,
        TreeParams(
            max_depth=4, grow_policy="lossguide", max_leaves=16,
            hist_subtraction=False,
        ),
        ell.cuts.values, ell.cuts.ptrs,
    )
    assert_trees_equal(
        sub.tree, full.tree, got_positions=sub.positions, want_positions=full.positions
    )
    assert cache.stats.built_nodes > 0
    assert cache.stats.built_nodes == cache.stats.derived_nodes  # one per pop
    assert cache.stats.built_rows <= cache.stats.total_rows / 2 + 1e-6


def test_make_gbdt_step_fn_rejects_lossguide():
    from repro.distributed import DistConfig, make_gbdt_step_fn

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    with pytest.raises(NotImplementedError, match="lossguide"):
        make_gbdt_step_fn(
            mesh, TreeParams(max_depth=3, grow_policy="lossguide"), 16,
            DistConfig(data_axes=("data",)),
        )
