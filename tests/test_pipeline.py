"""repro.pipeline: overlap of the double-buffered engine, LRU device cache,
stream passes over host/disk sources, and the paged consumers."""
import time

import jax
import numpy as np
import pytest
from oracle import assert_trees_equal

from repro.data.pages import PageStore, TransferStats
from repro.pipeline import DevicePageCache, PageStream

N_PAGES = 8
PAGE_SHAPE = (64, 8)


class SlowStore:
    """Fake disk: every fetch takes `delay` seconds."""

    def __init__(self, delay: float):
        self.delay = delay
        self.fetches: list[int] = []

    def fetch(self, idx: int) -> np.ndarray:
        time.sleep(self.delay)
        self.fetches.append(idx)
        return np.full(PAGE_SHAPE, idx % 251, np.uint8)


def test_double_buffering_hides_transfer_under_compute():
    """The tentpole property: with a slow store and equally slow consumer,
    wall time of a pass is well below serial transfer+compute time."""
    delay = 0.03
    # wall-clock assertion depends on thread scheduling: allow a few attempts
    # so one starved prefetcher thread on a loaded runner doesn't flake CI
    for attempt in range(3):
        stats = TransferStats()
        store = SlowStore(delay)
        stream = PageStream(
            store.fetch, range(N_PAGES), threaded=True,
            prefetch_depth=2, staging_depth=2, stats=stats,
        )
        t0 = time.perf_counter()
        seen = []
        for sp in stream:
            time.sleep(delay)  # "compute" on page k while page k+1 fetches
            seen.append(sp.index)
        wall = time.perf_counter() - t0

        assert seen == list(range(N_PAGES))
        # both sides of the pipe really did their work...
        assert stats.stream_fetch_seconds >= N_PAGES * delay * 0.9
        assert stats.stream_compute_seconds >= N_PAGES * delay * 0.9
        serial = stats.stream_serial_seconds
        if wall < 0.9 * serial and stats.overlap_ratio > 0.1:
            break
    # ...yet the pass finished in much less than their sum: overlap worked
    assert wall < 0.9 * serial, (wall, serial)
    assert stats.overlap_ratio > 0.1
    assert stats.stream_wall_seconds == pytest.approx(wall, rel=0.2)


def test_stream_counts_bytes_and_is_reiterable():
    pages = [np.full(PAGE_SHAPE, i, np.uint8) for i in range(3)]
    stats = TransferStats()
    stream = PageStream.from_host_pages(pages, stats=stats)
    out = [sp for sp in stream]
    assert [sp.index for sp in out] == [0, 1, 2]
    assert all(np.asarray(sp.device).dtype == np.uint8 for sp in out)
    one_pass = 3 * pages[0].nbytes
    assert stats.host_to_device_bytes == one_pass
    list(stream)  # second independent pass
    assert stats.host_to_device_bytes == 2 * one_pass


def test_iter_host_stages_nothing():
    pages = [np.zeros(PAGE_SHAPE, np.uint8) for _ in range(4)]
    stats = TransferStats()
    stream = PageStream.from_host_pages(pages, stats=stats)
    assert [idx for idx, _ in stream.iter_host()] == [0, 1, 2, 3]
    assert stats.host_to_device_bytes == 0


def test_from_store_roundtrip(tmp_path):
    stats = TransferStats()
    store = PageStore(str(tmp_path / "pages"), stats=stats)
    for i in range(3):
        store.write_page({"bins": np.full(PAGE_SHAPE, i, np.uint8)})
    stream = PageStream.from_store(
        store, wrap=lambda idx, arrays: arrays["bins"], stats=stats
    )
    for sp in stream:
        np.testing.assert_array_equal(np.asarray(sp.device), sp.host)
        assert int(sp.host[0, 0]) == sp.index
    assert stats.page_loads == 3
    assert stats.host_to_device_bytes == 3 * 64 * 8


def test_device_cache_lru_eviction():
    cache = DevicePageCache(max_pages=2)
    cache.put("a", 1, 10)
    cache.put("b", 2, 10)
    assert cache.get("a") == 1  # refresh a; b is now LRU
    cache.put("c", 3, 10)
    assert cache.get("b") is None
    assert cache.get("a") == 1 and cache.get("c") == 3
    assert cache.n_pages == 2 and cache.nbytes == 20


def test_device_cache_byte_bound():
    cache = DevicePageCache(max_pages=10, max_bytes=25)
    for key, nb in [("a", 10), ("b", 10), ("c", 10)]:
        cache.put(key, key.upper(), nb)
    assert cache.get("a") is None  # evicted to satisfy the byte bound
    assert cache.nbytes <= 25


def test_device_cache_clear_resets_counters():
    cache = DevicePageCache(max_pages=2)
    cache.put("a", 1, 10)
    cache.get("a")
    cache.get("missing")
    assert cache.hits == 1 and cache.misses == 1
    cache.clear()
    assert cache.hits == 0 and cache.misses == 0
    assert cache.n_pages == 0 and cache.nbytes == 0
    assert cache.pinned_pages == 0 and cache.pinned_bytes == 0
    assert cache.hit_rate == 0.0


def test_device_cache_oversize_put_rejected():
    cache = DevicePageCache(max_pages=4, max_bytes=25)
    cache.put("a", 1, 10)
    # a single value bigger than the whole budget: refused up front (the old
    # behavior admitted it then silently evicted it along with everything else)
    assert not cache.put("big", 2, 26)
    assert cache.get("big") is None
    assert cache.get("a") == 1  # resident entries survive the refusal
    assert cache.oversize_puts == 1
    assert cache.put("b", 3, 10)  # budget-respecting puts still land


def test_device_cache_pins_survive_pressure():
    cache = DevicePageCache(max_pages=10, max_bytes=30)
    assert cache.put("pin", "P", 10, pinned=True)
    assert cache.is_pinned("pin")
    # row-page pressure: unpinned entries churn, the pin never moves
    for i in range(6):
        cache.put(f"row{i}", i, 10)
    assert cache.get("pin") == "P"
    assert cache.nbytes <= 30
    assert cache.pinned_bytes == 10
    # the survivors are the most recent unpinned entries, not the oldest
    assert cache.get("row0") is None and cache.get("row5") == 5


def test_device_cache_unpin_releases_bytes():
    cache = DevicePageCache(max_pages=10, max_bytes=30)
    cache.put("a", 1, 15, pinned=True)
    cache.put("b", 2, 15, pinned=True)
    assert cache.pinned_bytes == 30
    assert not cache.can_pin(1)  # budget exhausted by pins
    cache.unpin("a")
    assert cache.pinned_bytes == 15 and cache.can_pin(15)
    # demoted entry is evictable again under pressure
    cache.put("c", 3, 15)
    assert cache.get("a") is None and cache.get("b") == 2


def test_device_cache_pin_refuses_over_budget():
    cache = DevicePageCache(max_pages=10, max_bytes=20)
    cache.put("a", 1, 15, pinned=True)
    cache.put("b", 2, 10)  # lands unpinned: pinning it would bust the budget
    assert not cache.is_pinned("b")
    assert not cache.pin("b")
    assert not cache.pin("absent")
    assert cache.pinned_bytes == 15


def test_device_cache_max_bytes_none_matches_page_lru():
    """max_bytes=None degenerates to the old page-count LRU bit-for-bit."""
    ref = DevicePageCache(max_pages=2)
    cache = DevicePageCache(max_pages=2, max_bytes=None)
    for c in (ref, cache):
        c.put("a", 1, 10)
        c.put("b", 2, 10)
        c.get("a")
        c.put("c", 3, 10)
    assert [k for k in ref._entries] == [k for k in cache._entries]
    assert (ref.hits, ref.misses) == (cache.hits, cache.misses)
    assert cache.get("b") is None
    assert cache.get("a") == 1 and cache.get("c") == 3


def test_device_cache_tag_counts():
    cache = DevicePageCache(max_pages=8)
    cache.put(("forest/2", 0), "f0", 10)
    cache.put(("rows/0", 0), "r0", 10)
    cache.lookup(("forest/2", 0))  # hit
    cache.lookup(("forest/2", 1))  # miss
    cache.lookup(("rows/0", 0))  # hit, different namespace
    assert cache.tag_counts("forest") == (1, 1)
    assert cache.tag_counts("rows") == (1, 0)
    assert (cache.hits, cache.misses) == (2, 1)


def test_cached_pass_skips_transfers():
    pages = [np.full(PAGE_SHAPE, i, np.uint8) for i in range(3)]
    stats = TransferStats()
    cache = DevicePageCache(max_pages=8)
    stream = PageStream.from_host_pages(pages, stats=stats, cache=cache)
    list(stream)
    first_pass_bytes = stats.host_to_device_bytes
    out = [np.asarray(sp.device) for sp in stream]  # second pass: all hits
    assert stats.host_to_device_bytes == first_pass_bytes
    assert stats.cache_hits == 3
    assert stats.cache_hit_bytes > 0
    for i, arr in enumerate(out):
        np.testing.assert_array_equal(arr, pages[i])


def test_prefetch_failure_surfaces_after_retries():
    def flaky(idx):
        raise OSError("disk gone")

    stream = PageStream(flaky, range(2), threaded=True)
    with pytest.raises(RuntimeError, match="failed to load"):
        list(stream)


def test_booster_sampled_path_uses_device_cache(source_small):
    """f<1 fast path: the auto device cache skips margin-update transfers
    after the first iteration without changing the model."""
    from repro.core import BoosterParams, ExecutionPolicy, GradientBooster, SamplingConfig
    from repro.data.dmatrix import IterDMatrix

    params = dict(
        n_estimators=4, max_depth=3, max_bin=32, objective="binary:logistic",
        sampling=SamplingConfig(method="mvs", f=0.4), seed=0,
    )
    stats_on = TransferStats()
    b_on = GradientBooster(BoosterParams(**params), policy=ExecutionPolicy(mode="sampled"))
    b_on.fit(IterDMatrix(source_small, max_bin=32, page_bytes=4 * 1024, stats=stats_on))
    assert stats_on.cache_hits > 0

    stats_off = TransferStats()
    b_off = GradientBooster(
        BoosterParams(**params), policy=ExecutionPolicy(mode="sampled", device_cache_pages=0)
    )
    b_off.fit(IterDMatrix(source_small, max_bin=32, page_bytes=4 * 1024, stats=stats_off))
    assert stats_off.cache_hits == 0
    assert stats_on.host_to_device_bytes < stats_off.host_to_device_bytes
    X, _ = source_small.materialize()
    np.testing.assert_allclose(
        b_on.predict_margin(X), b_off.predict_margin(X), rtol=1e-5, atol=1e-6
    )


@pytest.fixture(scope="module")
def source_small():
    from repro.data.synthetic import SyntheticSource

    return SyntheticSource(n_rows=600, num_features=12, batch_rows=128, task="higgs", seed=9)


# ------------------------- edge cases the per-node (lossguide) passes hit --

def _edge_case_fixture(n=257, m=4, max_bin=8, seed=13):
    import jax.numpy as jnp

    from repro.core.booster import bin_valid_from_cuts
    from repro.core.ellpack import create_ellpack_inmemory

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(np.float32)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.random(n).astype(np.float32) + 0.1)
    ell = create_ellpack_inmemory(X, max_bin=max_bin)
    bv = bin_valid_from_cuts(ell.cuts, max_bin)
    return ell, g, h, bv


def _host_page_stream(pages, stats):
    import jax.numpy as jnp

    return PageStream.from_host_pages(
        pages,
        to_array=lambda p: np.ascontiguousarray(p.bins),
        put=lambda a: jax.device_put(a).astype(jnp.int32),
        stats=stats,
    )


@pytest.mark.parametrize("grow_policy", ["depthwise", "lossguide"])
def test_single_page_dataset_matches_in_core(grow_policy):
    """A 1-page page set is the degenerate stream: every per-level and
    per-node pass stages exactly one page and must equal the in-core build."""
    import jax.numpy as jnp

    from repro.core.ellpack import EllpackPage
    from repro.core.outofcore import build_tree_paged
    from repro.core.tree import TreeParams, grow_tree

    ell, g, h, bv = _edge_case_fixture()
    bins_u8 = ell.single_page().bins
    n = bins_u8.shape[0]
    tp = TreeParams(max_depth=3, grow_policy=grow_policy, max_leaves=8)
    res = grow_tree(
        jnp.asarray(bins_u8.astype(np.int32)), g, h, 8, bv, tp,
        ell.cuts.values, ell.cuts.ptrs,
    )
    stats = TransferStats()
    pages = [EllpackPage(bins=bins_u8, row_offset=0)]
    tree, positions = build_tree_paged(
        lambda: _host_page_stream(pages, stats), [(0, n)], g, h, 8, bv, tp,
        ell.cuts.values, ell.cuts.ptrs,
    )
    assert_trees_equal(
        tree, res.tree, got_positions=positions[0], want_positions=res.positions
    )


@pytest.mark.parametrize("grow_policy", ["depthwise", "lossguide"])
def test_empty_last_page_is_harmless(grow_policy):
    """A 0-row trailing page (ragged page split) streams, stages, histograms,
    and partitions without perturbing the tree."""
    import jax.numpy as jnp

    from repro.core.ellpack import EllpackPage
    from repro.core.outofcore import build_tree_paged
    from repro.core.tree import TreeParams, grow_tree

    ell, g, h, bv = _edge_case_fixture()
    bins_u8 = ell.single_page().bins
    n = bins_u8.shape[0]
    tp = TreeParams(max_depth=3, grow_policy=grow_policy, max_leaves=8)
    res = grow_tree(
        jnp.asarray(bins_u8.astype(np.int32)), g, h, 8, bv, tp,
        ell.cuts.values, ell.cuts.ptrs,
    )
    extents = [(0, 128), (128, n - 128), (n, 0)]  # empty last page
    pages = [
        EllpackPage(bins=bins_u8[lo:lo + nr], row_offset=lo) for lo, nr in extents
    ]
    stats = TransferStats()
    tree, positions = build_tree_paged(
        lambda: _host_page_stream(pages, stats), extents, g, h, 8, bv, tp,
        ell.cuts.values, ell.cuts.ptrs,
    )
    assert positions[2].shape == (0,)
    pos_full = jnp.concatenate([positions[i] for i in range(3)])
    assert_trees_equal(
        tree, res.tree, got_positions=pos_full, want_positions=res.positions
    )


def test_histogram_pass_touching_zero_pages_is_all_zeros():
    """A per-node pass whose active row set lives on no page (all positions
    frozen elsewhere / outside the window) must stream cleanly and return an
    all-zero histogram — with and without a node_map."""
    import jax.numpy as jnp

    from repro.core.ellpack import EllpackPage
    from repro.kernels import ops

    ell, g, h, _ = _edge_case_fixture()
    bins_u8 = ell.single_page().bins
    n = bins_u8.shape[0]
    extents = [(0, 128), (128, n - 128)]
    pages = [
        EllpackPage(bins=bins_u8[lo:lo + nr], row_offset=lo) for lo, nr in extents
    ]
    # every row frozen at heap node 1: a pass over the window [3, 5) — node
    # 1's grandchildren — touches zero rows on every page
    positions = {i: jnp.full(nr, 1, jnp.int32) for i, (_, nr) in enumerate(extents)}

    stats = TransferStats()
    hist = ops.build_histogram_paged(
        _host_page_stream(pages, stats), g, h, positions, 3, 2, 8,
    )
    assert hist.shape == (2, bins_u8.shape[1], 8, 2)
    np.testing.assert_array_equal(np.asarray(hist), 0.0)

    node_map = jnp.asarray([0, -1], jnp.int32)  # build slot for node 3 only
    hist_sub = ops.build_histogram_paged(
        _host_page_stream(pages, stats), g, h, positions, 3, 1, 8,
        node_map=node_map,
    )
    assert hist_sub.shape == (1, bins_u8.shape[1], 8, 2)
    np.testing.assert_array_equal(np.asarray(hist_sub), 0.0)
    assert stats.host_to_device_bytes > 0  # the pages still streamed


def test_distributed_paged_matches_in_core(source_small):
    """grow_tree_distributed_paged over PageStream == single-device grow_tree."""
    import jax.numpy as jnp

    from repro.core.booster import bin_valid_from_cuts
    from repro.core.ellpack import create_ellpack_inmemory
    from repro.core.tree import TreeParams, grow_tree
    from repro.distributed import (
        DistConfig, grow_tree_distributed_paged, sharded_page_put,
    )

    X, _ = source_small.materialize()
    ell = create_ellpack_inmemory(X, max_bin=16)
    bins_np = ell.single_page().bins
    n = bins_np.shape[0]
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.ones(n, jnp.float32)
    bv = bin_valid_from_cuts(ell.cuts, 16)
    tp = TreeParams(max_depth=3)

    res = grow_tree(
        jnp.asarray(bins_np.astype(np.int32)), g, h, 16, bv, tp,
        ell.cuts.values, ell.cuts.ptrs,
    )

    from repro.core.ellpack import EllpackPage

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    cfg = DistConfig(data_axes=("data",))
    splits = [0, 150, 300, 450, n]
    extents = [(splits[i], splits[i + 1] - splits[i]) for i in range(4)]
    host_pages = [
        EllpackPage(bins=bins_np[lo : lo + nr], row_offset=lo) for lo, nr in extents
    ]
    stats = TransferStats()

    def make_stream():
        return PageStream.from_host_pages(
            host_pages,
            to_array=lambda p: np.ascontiguousarray(p.bins),
            put=sharded_page_put(mesh, cfg),
            stats=stats,
        )

    tree_d, pos_d = grow_tree_distributed_paged(
        mesh, make_stream, extents, g, h, 16, bv, tp, cfg,
        ell.cuts.values, ell.cuts.ptrs,
    )
    assert_trees_equal(
        tree_d, res.tree, got_positions=pos_d, want_positions=res.positions
    )
    assert stats.host_to_device_bytes > 0  # pages actually streamed
