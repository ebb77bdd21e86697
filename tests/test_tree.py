"""Tree builder vs brute-force oracle; split-gain math (eq. 6/8)."""
import jax.numpy as jnp
import numpy as np
import pytest
from oracle import assert_positions_are_leaves, assert_trees_equal

from repro.core.booster import bin_valid_from_cuts
from repro.core.ellpack import bin_batch, create_ellpack_inmemory
from repro.core.split import SplitParams, evaluate_splits
from repro.core.tree import TreeParams, grow_tree, predict_tree_bins, predict_tree_raw
from repro.kernels import ref


def _brute_force_stump(bins, g, h, n_bins_per_feature, lam, gamma):
    """Exhaustive best (feature, bin, default_dir) for a single split."""
    n, m = bins.shape
    G, H = g.sum(), h.sum()
    parent = G * G / (H + lam)
    best = (-np.inf, None)
    for f in range(m):
        col = bins[:, f]
        miss = col == ref.MISSING_BIN
        for b in range(n_bins_per_feature[f]):
            base_left = (col <= b) & ~miss
            for dleft in (False, True):
                left = base_left | (miss & dleft)
                gl, hl = g[left].sum(), h[left].sum()
                gr, hr = G - gl, H - hl
                if hl < 1.0 or hr < 1.0:  # min_child_weight = 1
                    continue
                gain = 0.5 * (gl * gl / (hl + lam) + gr * gr / (hr + lam) - parent) - gamma
                if gain > best[0]:
                    best = (gain, (f, b, dleft))
    return best


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_root_split_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    n, m = 300, 5
    X = rng.normal(size=(n, m)).astype(np.float32)
    X[rng.random((n, m)) < 0.05] = np.nan
    g = rng.normal(size=n).astype(np.float32)
    h = (rng.random(n).astype(np.float32) + 0.1)
    ell = create_ellpack_inmemory(X, max_bin=8)
    bins = np.asarray(ell.single_page().bins, dtype=np.int32)
    nbf = ell.cuts.n_bins_per_feature
    n_bins = 8
    hist = ref.build_histogram(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
        jnp.zeros(n, jnp.int32), 1, n_bins,
    )
    bin_valid = bin_valid_from_cuts(ell.cuts, n_bins)
    splits = evaluate_splits(
        hist, jnp.asarray([g.sum()]), jnp.asarray([h.sum()]), bin_valid,
        SplitParams(reg_lambda=1.0, gamma=0.0, min_child_weight=1.0),
    )
    want_gain, (wf, wb, wd) = _brute_force_stump(bins, g, h, nbf, 1.0, 0.0)
    assert np.isclose(float(splits.gain[0]), want_gain, rtol=1e-4)
    got = (int(splits.feature[0]), int(splits.split_bin[0]))
    # gain ties can pick a different but equally good split; check gain primarily
    bf_left = None
    assert float(splits.gain[0]) >= want_gain - 1e-4


def test_deep_tree_overfits_training_data():
    rng = np.random.default_rng(7)
    n = 256
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32) * 2 - 1
    ell = create_ellpack_inmemory(X, max_bin=32)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    g = jnp.asarray(-y)  # squared error grad at margin 0: (0 - y)
    h = jnp.ones(n, jnp.float32)
    bv = bin_valid_from_cuts(ell.cuts, 32)
    tp = TreeParams(max_depth=8, split=SplitParams(reg_lambda=0.01, min_child_weight=0.001))
    res = grow_tree(bins, g, h, 32, bv, tp, ell.cuts.values, ell.cuts.ptrs)
    pred = np.asarray(res.tree.leaf_value)[np.asarray(res.positions)]
    # a depth-8 tree on 256 rows should fit the training signal nearly perfectly
    assert np.mean((pred > 0) == (y > 0)) > 0.97


def test_positions_are_leaves_and_match_predict():
    rng = np.random.default_rng(8)
    n = 200
    X = rng.normal(size=(n, 4)).astype(np.float32)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, np.float32)
    ell = create_ellpack_inmemory(X, max_bin=16)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    bv = bin_valid_from_cuts(ell.cuts, 16)
    tp = TreeParams(max_depth=4)
    res = grow_tree(bins, jnp.asarray(g), jnp.asarray(h), 16, bv, tp,
                    ell.cuts.values, ell.cuts.ptrs)
    assert_positions_are_leaves(res.tree, res.positions)
    via_traversal = np.asarray(predict_tree_bins(res.tree, bins, 4))
    via_positions = np.asarray(res.tree.leaf_value)[np.asarray(res.positions)]
    np.testing.assert_allclose(via_traversal, via_positions, rtol=1e-6)

    # the same build is oracle-equal to itself rerun (jit determinism pin)
    res2 = grow_tree(bins, jnp.asarray(g), jnp.asarray(h), 16, bv, tp,
                     ell.cuts.values, ell.cuts.ptrs)
    assert_trees_equal(
        res2.tree, res.tree, got_positions=res2.positions,
        want_positions=res.positions, exact=True,
    )


def test_raw_and_binned_prediction_agree():
    rng = np.random.default_rng(9)
    n = 150
    X = rng.normal(size=(n, 3)).astype(np.float32)
    g = rng.normal(size=n).astype(np.float32)
    ell = create_ellpack_inmemory(X, max_bin=16)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    bv = bin_valid_from_cuts(ell.cuts, 16)
    tp = TreeParams(max_depth=3)
    res = grow_tree(bins, jnp.asarray(g), jnp.ones(n, jnp.float32), 16, bv, tp,
                    ell.cuts.values, ell.cuts.ptrs)
    p_bins = np.asarray(predict_tree_bins(res.tree, bins, 3))
    p_raw = np.asarray(predict_tree_raw(res.tree, jnp.asarray(X), 3))
    np.testing.assert_allclose(p_bins, p_raw, rtol=1e-6)


def test_leaf_weight_formula():
    from repro.core.split import leaf_weight

    w = leaf_weight(jnp.asarray([6.0]), jnp.asarray([2.0]), reg_lambda=1.0)
    assert np.isclose(float(w[0]), -2.0)  # -6 / (2 + 1)


@pytest.mark.parametrize("grow_policy", ["depthwise", "lossguide"])
def test_leaf_weights_come_from_the_rows_at_each_leaf(grow_policy):
    """A leaf's weight is -G/(H+lambda) over the rows that end at it, to f32
    rounding. The split search's running sums (node - cumsum of bins) carry
    an ulp of the ancestors' larger sums into every leaf below them, which
    is far coarser for a small leaf."""
    rng = np.random.default_rng(3)
    n = 8192
    X = rng.normal(size=(n, 5)).astype(np.float32)
    prob = 1 / (1 + np.exp(-rng.normal(0, 0.5, n) - X[:, 0]))
    y = rng.random(n) < prob
    g = (prob - y).astype(np.float32)  # logistic gradients of a noisy model
    h = (prob * (1 - prob)).astype(np.float32)
    ell = create_ellpack_inmemory(X, max_bin=64)
    bins = jnp.asarray(ell.single_page().bins.astype(np.int32))
    bv = bin_valid_from_cuts(ell.cuts, 64)
    tp = TreeParams(max_depth=6, grow_policy=grow_policy)
    res = grow_tree(bins, jnp.asarray(g), jnp.asarray(h), 64, bv, tp,
                    ell.cuts.values, ell.cuts.ptrs)
    pos = np.asarray(res.positions)
    n_total = tp.n_total_nodes
    G = np.bincount(pos, g.astype(np.float64), n_total)
    H = np.bincount(pos, h.astype(np.float64), n_total)
    reached = np.bincount(pos, minlength=n_total) > 0
    got = np.asarray(res.tree.leaf_value, np.float64)
    np.testing.assert_allclose(got[reached], -G[reached] / (H[reached] + 1.0),
                               rtol=3e-7, atol=0)
    assert not got[~reached].any()
