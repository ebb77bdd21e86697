"""Out-of-core executor (Alg. 3/5/6/7): equivalence, sampling, restart, disk paging."""
import numpy as np
import pytest
from oracle import assert_forests_equal

from repro.core import BoosterParams, ExecutionPolicy, GradientBooster, SamplingConfig
from repro.core.objectives import auc
from repro.core.quantile import QuantileSketch
from repro.data.dmatrix import IterDMatrix
from repro.data.pages import TransferStats
from repro.data.synthetic import SyntheticSource

PARAMS = dict(n_estimators=6, max_depth=3, max_bin=32, objective="binary:logistic")


def external(params, mode="out_of_core"):
    """A booster forced out of core (``mode="sampled"``: Alg. 7)."""
    return GradientBooster(params, policy=ExecutionPolicy(mode=mode))


def paged(source, **kw):
    """``source`` sketched and paged by an `IterDMatrix` of 32 bins."""
    return IterDMatrix(source, max_bin=32, **kw)


@pytest.fixture(scope="module")
def source():
    return SyntheticSource(n_rows=1200, num_features=28, batch_rows=256, task="higgs", seed=3)


@pytest.fixture(scope="module")
def arrays(source):
    return source.materialize()


def test_streaming_equivalent_to_in_core(source, arrays):
    """Paper §4.2: with f = 1.0 out-of-core == in-core (up to float summation order)."""
    X, y = arrays
    sk = QuantileSketch(28, max_bin=32)  # must match preprocess(): min(max_bin, 255)
    for xb, _ in source.iter_batches():
        sk.update(xb)
    cuts = sk.finalize()

    b_in = GradientBooster(BoosterParams(seed=0, **PARAMS)).fit(X, y, cuts=cuts)
    b_ooc = external(BoosterParams(seed=0, **PARAMS))
    b_ooc.fit(paged(source, page_bytes=8 * 1024))
    assert b_ooc.pages.n_pages > 1  # actually paged
    # tree-by-tree structural equality (shared oracle), not just final margins
    assert_forests_equal(b_ooc.trees, b_in.trees)
    np.testing.assert_allclose(
        b_in.predict_margin(X), b_ooc.predict_margin(X), rtol=1e-4, atol=1e-5
    )


def test_sampled_path_learns(source, arrays):
    X, y = arrays
    cfg = SamplingConfig(method="mvs", f=0.3)
    b = external(BoosterParams(sampling=cfg, seed=0, **PARAMS), mode="sampled")
    b.fit(paged(source, page_bytes=8 * 1024))
    assert auc(y, b.predict(X)) > 0.75


def test_disk_pages_and_transfer_stats(tmp_path, source, arrays):
    X, y = arrays
    stats = TransferStats()
    b = external(BoosterParams(seed=0, **PARAMS))
    b.fit(paged(source, cache_dir=str(tmp_path / "cache"), page_bytes=8 * 1024, stats=stats))
    assert stats.disk_write_bytes > 0
    assert stats.disk_read_bytes > 0
    assert stats.host_to_device_bytes > 0
    # Alg. 6 re-streams every page per level: h2d traffic must exceed data size
    assert stats.host_to_device_bytes > 1200 * 28
    assert auc(y, b.predict(X)) > 0.75


def test_sampling_reduces_device_traffic(source):
    """The paper's core claim: compaction slashes per-iteration device traffic."""
    stats_full = TransferStats()
    b1 = external(BoosterParams(seed=0, **PARAMS))
    b1.fit(paged(source, page_bytes=8 * 1024, stats=stats_full))

    stats_mvs = TransferStats()
    cfg = SamplingConfig(method="mvs", f=0.2)
    b2 = external(BoosterParams(sampling=cfg, seed=0, **PARAMS), mode="sampled")
    b2.fit(paged(source, page_bytes=8 * 1024, stats=stats_mvs))
    assert stats_mvs.host_to_device_bytes < stats_full.host_to_device_bytes


def test_checkpoint_resume_identical(tmp_path, source, arrays):
    """Fault tolerance: kill after k trees, resume -> identical model."""
    X, y = arrays
    params = BoosterParams(seed=0, **PARAMS)

    full = external(params)
    full.fit(paged(source, page_bytes=8 * 1024))
    want = full.predict_margin(X)

    part = external(dict_replace(params, n_estimators=3))
    part.fit(paged(source, page_bytes=8 * 1024))
    part.save(str(tmp_path / "ckpt"))

    resumed = GradientBooster.resume(str(tmp_path / "ckpt"), source)
    resumed.params = params  # continue to the full horizon
    # the source re-paged with the checkpointed cuts (no re-sketch)
    resumed.fit(paged(source, cuts=resumed.cuts, page_bytes=8 * 1024), start_iteration=3)
    got = resumed.predict_margin(X)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def dict_replace(params, **kw):
    import dataclasses

    return dataclasses.replace(params, **kw)


def test_margin_cache_consistency(source, arrays):
    """Cached margins equal full re-prediction after training."""
    X, y = arrays
    b = external(BoosterParams(seed=0, **PARAMS))
    b.fit(paged(source, page_bytes=8 * 1024))
    np.testing.assert_allclose(b.margins_, b.predict_margin(X), rtol=1e-4, atol=1e-5)


# --------------------------------------- per-node page skipping: repartition pass

def test_partition_skip_set_matches_hist_skip_set():
    """The invariant the repartition skip rests on: pages whose rows all sit
    at leaves (the partition pass's skip set) are exactly the pages that end
    up with no row in the freshly split node's 2-child window (the histogram
    pass's skip set) — the popped node's rows are the only ones that move."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    n_pages, rows, m, n_bins = 4, 64, 3, 8
    live_pages = {1, 3}
    node = 5  # the popped leaf, just split; children 11, 12
    n_total = 2**5 - 1
    positions = {}
    bins = {}
    for i in range(n_pages):
        pos = np.full(rows, 3, np.int32)  # node 3: a frozen leaf elsewhere
        if i in live_pages:
            pos[: rows // 2] = node
        positions[i] = jnp.asarray(pos)
        bins[i] = jnp.asarray(rng.integers(0, n_bins, (rows, m)).astype(np.int32))
    feature = jnp.zeros(n_total, jnp.int32)
    split_bin = jnp.zeros(n_total, jnp.int32).at[node].set(3)
    default_left = jnp.zeros(n_total, bool)
    is_leaf = jnp.ones(n_total, bool).at[node].set(False)

    partition_active = {
        i for i in range(n_pages) if bool(jnp.any(~is_leaf[positions[i]]))
    }
    assert partition_active == live_pages
    # apply the repartition to every page (skipped or not) and check the
    # histogram pass's window predicate lands on the same set
    left = 2 * node + 1
    new_pos = {
        i: ops.partition_rows(
            bins[i], positions[i], feature, split_bin, default_left, is_leaf
        )
        for i in range(n_pages)
    }
    hist_active = {
        i
        for i in range(n_pages)
        if bool(jnp.any((new_pos[i] >= left) & (new_pos[i] < left + 2)))
    }
    assert hist_active == partition_active
    # skipped pages really were immutable under the repartition kernel
    for i in set(range(n_pages)) - partition_active:
        np.testing.assert_array_equal(np.asarray(new_pos[i]), np.asarray(positions[i]))


def test_partition_pass_skips_pages_and_preserves_tree():
    """End-to-end: lossguide paged builds skip repartition passes too (more
    subset passes than histogram passes alone can account for), count them in
    TransferStats.pages_skipped, and grow the identical tree."""
    import jax
    import jax.numpy as jnp
    from oracle import assert_trees_equal

    from repro.core.booster import bin_valid_from_cuts
    from repro.core.ellpack import EllpackPage, create_ellpack_inmemory
    from repro.core.outofcore import build_tree_paged
    from repro.core.tree import TreeParams
    from repro.pipeline import PageStream

    n, m, max_bin = 1024, 6, 32
    rng = np.random.default_rng(1)
    X = rng.normal(size=(n, m)).astype(np.float32)
    X[:, 0] = np.arange(n)  # splits on f0 give page-contiguous row ranges
    g = jnp.asarray((np.arange(n) / n - 0.5).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    ell = create_ellpack_inmemory(X, max_bin=max_bin)
    bins_u8 = ell.single_page().bins
    bv = bin_valid_from_cuts(ell.cuts, max_bin)
    extents = [(lo, 256) for lo in range(0, n, 256)]
    pages = [EllpackPage(bins=bins_u8[lo:lo + nr], row_offset=lo) for lo, nr in extents]
    tp = TreeParams(max_depth=5, grow_policy="lossguide", max_leaves=10)

    def run(page_skipping):
        stats = TransferStats()
        calls = []

        def make_stream(indices=None):
            calls.append(None if indices is None else tuple(indices))
            return PageStream.from_host_pages(
                pages, indices=indices,
                to_array=lambda p: np.ascontiguousarray(p.bins),
                put=lambda a: jax.device_put(a).astype(jnp.int32),
                stats=stats,
            )

        tree, positions = build_tree_paged(
            make_stream, extents, g, h, max_bin, bv, tp,
            ell.cuts.values, ell.cuts.ptrs, page_skipping=page_skipping,
        )
        return tree, positions, stats, calls

    tree, positions, stats, calls = run(page_skipping=True)
    n_pops = int(np.asarray(~tree.is_leaf).sum())  # one repartition per pop
    n_hist = len(calls) - n_pops  # root pass + one per expanded node
    subset_calls = [c for c in calls if c is not None]
    assert stats.pages_skipped > 0
    # more subset passes than histogram passes exist: repartition skipped too
    assert len(subset_calls) > n_hist
    # each skipping expansion runs repartition then histogram over the same
    # set: at least one adjacent identical subset pair must appear
    assert any(a == b for a, b in zip(calls, calls[1:]) if a is not None)

    tree_full, positions_full, stats_full, _ = run(page_skipping=False)
    assert stats_full.pages_skipped == 0
    assert stats_full.host_to_device_bytes > stats.host_to_device_bytes
    pos = jnp.concatenate([positions[i] for i in range(len(extents))])
    pos_full = jnp.concatenate([positions_full[i] for i in range(len(extents))])
    assert_trees_equal(tree, tree_full, got_positions=pos, want_positions=pos_full)
