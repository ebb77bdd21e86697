"""Unified DMatrix surface + ExecutionPolicy mode auto-selection.

The paper's transparency claim, as tests: one DMatrix-shaped object trains in
every mode from the same `GradientBooster.fit`, the `ExecutionPolicy` decision
procedure picks the mode the Table-1 byte model prescribes, and the forests
match across auto-selected vs explicitly-forced modes (shared oracle).
"""

import numpy as np
import pytest
from oracle import assert_forests_equal

from repro.core import (
    BoosterParams,
    ExecutionPolicy,
    GradientBooster,
    SamplingConfig,
)
from repro.core.objectives import auc
from repro.data.dmatrix import ArrayDMatrix, IterDMatrix, PagedDMatrix, as_dmatrix
from repro.data.pages import TransferStats
from repro.data.synthetic import SyntheticSource

PARAMS = dict(n_estimators=5, max_depth=3, max_bin=32, objective="binary:logistic")
PAGE_BYTES = 8 * 1024


@pytest.fixture(scope="module")
def source():
    return SyntheticSource(n_rows=1200, num_features=28, batch_rows=256, task="higgs", seed=3)


@pytest.fixture(scope="module")
def arrays(source):
    return source.materialize()


@pytest.fixture(scope="module")
def iter_dm(source):
    return IterDMatrix(source, max_bin=32, page_bytes=PAGE_BYTES)


def _booster(policy=None, **overrides):
    kw = dict(PARAMS)
    kw.update(overrides)
    return GradientBooster(BoosterParams(seed=0, **kw), policy=policy)


# --------------------------------------------------------------- mode decision
def test_auto_selects_in_core_with_room(iter_dm):
    b = _booster(ExecutionPolicy(mode="auto"))  # default 16 GiB budget
    b.fit(iter_dm)
    assert b.decision_.mode == "in_core"
    assert len(b.trees) == PARAMS["n_estimators"]


def test_auto_selects_out_of_core_and_matches_forced(iter_dm):
    """Acceptance: auto picks out-of-core when the matrix busts the in-core
    budget, and the auto-selected forest equals the explicitly-forced one."""
    # budget between the streaming floor (~97 KB: fixed working set incl. the
    # depth-honest histogram term + 2 pages + per-row state) and the in-core
    # threshold (~123 KB)
    policy = ExecutionPolicy(mode="auto", memory_budget_bytes=110_000)
    b_auto = _booster(policy)
    b_auto.fit(iter_dm)
    assert b_auto.decision_.mode == "out_of_core", b_auto.decision_.reason
    model = b_auto.decision_.model
    assert iter_dm.n_rows > model.max_rows_in_core()
    assert iter_dm.n_rows <= model.max_rows_out_of_core()

    b_forced = _booster(ExecutionPolicy(mode="out_of_core"))
    b_forced.fit(iter_dm)
    assert b_forced.decision_.mode == "out_of_core"
    assert_forests_equal(b_auto.trees, b_forced.trees)


def test_auto_selects_sampled_when_streaming_state_busts_budget(iter_dm):
    # below the streaming floor (~97 KB) but with room for the f=0.1
    # compacted page — only the smallest grid fraction fits
    policy = ExecutionPolicy(mode="auto", memory_budget_bytes=90_000)
    b = _booster(policy)
    b.fit(iter_dm)
    d = b.decision_
    assert d.mode == "sampled", d.reason
    assert d.sampling_f == 0.1  # only the smallest grid fraction fits
    assert iter_dm.n_rows > d.model.max_rows_out_of_core()
    assert iter_dm.n_rows <= d.model.max_rows_sampled(d.sampling_f)
    assert len(b.trees) == PARAMS["n_estimators"]


def test_nothing_fits_raises(iter_dm):
    with pytest.raises(ValueError, match="does not fit"):
        _booster(ExecutionPolicy(mode="auto", memory_budget_bytes=40_000)).fit(iter_dm)


def test_sampling_config_promotes_forced_out_of_core(iter_dm):
    cfg = SamplingConfig(method="mvs", f=0.3)
    b = _booster(ExecutionPolicy(mode="out_of_core"), sampling=cfg)
    b.fit(iter_dm)
    assert b.decision_.mode == "sampled"
    assert b.decision_.sampling_f == pytest.approx(0.3)


# ----------------------------------------------------------- the three sources
def test_same_dmatrix_trains_equal_in_all_exact_modes(iter_dm, arrays):
    """In-core and out-of-core on the SAME DMatrix (same cuts) grow the same
    forest — the cross-mode oracle behind the paper's transparency claim."""
    X, y = arrays
    b_in = _booster(ExecutionPolicy(mode="in_core"))
    b_in.fit(iter_dm)
    b_ooc = _booster(ExecutionPolicy(mode="out_of_core"))
    b_ooc.fit(iter_dm)
    assert iter_dm.n_pages > 1  # the streaming mode actually paged
    assert_forests_equal(b_in.trees, b_ooc.trees)
    np.testing.assert_allclose(
        b_in.predict_margin(X), b_ooc.predict_margin(X), rtol=1e-4, atol=1e-5
    )


def test_array_dmatrix_pages_cover_all_rows(arrays):
    X, y = arrays
    dm = ArrayDMatrix(X, y, max_bin=32, page_bytes=PAGE_BYTES)
    ps = dm.page_set()
    assert ps.n_pages > 1
    assert sum(nr for _, nr in ps.page_extents) == dm.n_rows
    np.testing.assert_array_equal(
        np.concatenate([p.bins for p in ps.host_pages]), dm.single_page_bins()
    )


def test_iter_dmatrix_spills_and_paged_dmatrix_reopens(tmp_path, source, arrays):
    X, y = arrays
    stats = TransferStats()
    dm = IterDMatrix(
        source, max_bin=32, cache_dir=str(tmp_path / "pages"),
        page_bytes=PAGE_BYTES, stats=stats,
    )
    assert stats.disk_write_bytes > 0
    b1 = _booster(ExecutionPolicy(mode="out_of_core"))
    b1.fit(dm)

    re_dm = PagedDMatrix(str(tmp_path / "pages"))
    assert re_dm.n_rows == dm.n_rows
    assert re_dm.n_pages == dm.n_pages
    np.testing.assert_array_equal(re_dm.cuts.values, dm.cuts.values)
    np.testing.assert_array_equal(re_dm.labels, dm.labels)
    b2 = _booster(ExecutionPolicy(mode="out_of_core"))
    b2.fit(re_dm)
    assert_forests_equal(b1.trees, b2.trees)
    assert auc(y, b2.predict(X)) > 0.7


def test_as_dmatrix_coercions(arrays, source):
    X, y = arrays
    assert isinstance(as_dmatrix(X, y, max_bin=32), ArrayDMatrix)
    assert isinstance(as_dmatrix((X, y), max_bin=32), ArrayDMatrix)
    assert isinstance(as_dmatrix(source, max_bin=32), IterDMatrix)
    dm = ArrayDMatrix(X, y, max_bin=32)
    assert as_dmatrix(dm) is dm
    with pytest.raises(ValueError, match="constructing the DMatrix"):
        as_dmatrix(dm, y)
    with pytest.raises(TypeError, match="re-iterable"):
        IterDMatrix(iter([(X, y)]))


def test_iter_dmatrix_accepts_dataiter_callback(arrays):
    """XGBoost DataIter shape: a zero-arg callable, one fresh pass per call."""
    X, y = arrays

    def batches():
        for lo in range(0, X.shape[0], 256):
            yield X[lo : lo + 256], y[lo : lo + 256]

    dm = IterDMatrix(batches, max_bin=32, page_bytes=PAGE_BYTES)
    assert dm.n_rows == X.shape[0]
    b = _booster(ExecutionPolicy(mode="in_core"))
    b.fit(dm)
    assert auc(y, b.predict(X)) > 0.7


# ----------------------------------------------------------------- page skipping
def test_lossguide_page_skipping_skips_and_preserves_forest():
    """Row-ordered data makes deep lossguide nodes page-local: per-node stream
    passes must skip the pages outside the popped node's window (fewer staged
    bytes) while growing the identical forest."""
    n, m = 1024, 8
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, m)).astype(np.float32)
    X[:, 0] = np.arange(n)  # splits on f0 give contiguous row ranges
    y = (np.arange(n) / n).astype(np.float32)
    dm = ArrayDMatrix(X, y, max_bin=64, page_bytes=2048)  # 4 x 256-row pages
    assert dm.n_pages == 4
    kw = dict(
        n_estimators=2, max_depth=4, max_bin=64, objective="reg:squarederror",
        grow_policy="lossguide", max_leaves=8,
    )
    b_skip = GradientBooster(
        BoosterParams(seed=0, **kw),
        policy=ExecutionPolicy(mode="out_of_core", page_skipping=True),
    )
    b_skip.fit(dm)
    skipped = b_skip.stats.pages_skipped
    assert skipped > 0
    h2d_skip = b_skip.stats.host_to_device_bytes

    b_full = GradientBooster(
        BoosterParams(seed=0, **kw),
        policy=ExecutionPolicy(mode="out_of_core", page_skipping=False),
    )
    b_full.fit(dm)  # same stats sink: the delta isolates the second fit
    assert b_full.stats.pages_skipped == skipped  # no new skips when disabled
    h2d_full = b_full.stats.host_to_device_bytes - h2d_skip
    assert h2d_skip < h2d_full  # skipping really cut the staged traffic
    assert_forests_equal(b_skip.trees, b_full.trees)


# ------------------------------------------------------------------ resume
def test_resume_from_paged_dmatrix_and_in_core_continuation(tmp_path, source, arrays):
    """Resume re-quantizes with the checkpointed cuts (or reopens the original
    pages) and continues in EITHER engine: the streaming continuation and the
    in-core continuation both rebuild the full-run forest."""
    import dataclasses

    cache = str(tmp_path / "pages")
    dm = IterDMatrix(source, max_bin=32, cache_dir=cache, page_bytes=PAGE_BYTES)
    full = _booster(ExecutionPolicy(mode="out_of_core"))
    full.fit(dm)

    part = _booster(ExecutionPolicy(mode="out_of_core"), n_estimators=2)
    part.fit(dm)
    part.save(str(tmp_path / "ckpt"))

    re_dm = PagedDMatrix(cache)
    horizon = dict(n_estimators=PARAMS["n_estimators"])
    resumed = GradientBooster.resume(str(tmp_path / "ckpt"), re_dm)
    resumed.params = dataclasses.replace(resumed.params, **horizon)
    resumed.fit(re_dm, start_iteration=2)
    assert_forests_equal(resumed.trees, full.trees)

    resumed_ic = GradientBooster.resume(
        str(tmp_path / "ckpt"), re_dm, policy=ExecutionPolicy(mode="in_core")
    )
    resumed_ic.params = dataclasses.replace(resumed_ic.params, **horizon)
    resumed_ic.fit(re_dm, start_iteration=2)
    assert_forests_equal(resumed_ic.trees, full.trees)
    with pytest.raises(ValueError, match="start_iteration"):
        _booster(ExecutionPolicy(mode="in_core")).fit(re_dm, start_iteration=2)


def test_resume_rejects_mismatched_dmatrix(tmp_path, arrays):
    X, y = arrays
    b = _booster(ExecutionPolicy(mode="out_of_core"), n_estimators=2)
    dm = ArrayDMatrix(X, y, max_bin=32, page_bytes=PAGE_BYTES)
    b.fit(dm)
    b.save(str(tmp_path / "ckpt"))
    other = ArrayDMatrix(X * 1.7 + 0.3, y, max_bin=32, page_bytes=PAGE_BYTES)
    with pytest.raises(ValueError, match="differs from the checkpoint"):
        GradientBooster.resume(str(tmp_path / "ckpt"), other)


# ----------------------------------------------------------------- sklearn compat
def test_get_set_params_roundtrip():
    b = _booster(ExecutionPolicy(mode="out_of_core"), sampling=SamplingConfig(method="mvs", f=0.5))
    shallow = b.get_params(deep=False)
    clone = GradientBooster(**shallow)  # sklearn clone() semantics
    assert clone.get_params(deep=False) == shallow
    assert clone.policy.mode == "out_of_core"

    deep = b.get_params(deep=True)
    assert deep["sampling__f"] == 0.5
    assert deep["policy__mode"] == "out_of_core"

    b.set_params(max_depth=4, sampling__f=0.25, policy__mode="in_core")
    assert b.params.max_depth == 4
    assert b.params.sampling.f == 0.25
    assert b.policy.mode == "in_core"
    with pytest.raises(ValueError, match="invalid parameter"):
        b.set_params(not_a_param=1)


def test_sklearn_clone_and_grid(arrays):
    """Real sklearn clone() + ParameterGrid over nested params, when available."""
    sk_base = pytest.importorskip("sklearn.base")
    from sklearn.model_selection import ParameterGrid

    X, y = arrays
    b = _booster(
        ExecutionPolicy(mode="in_core"), sampling=SamplingConfig(method="mvs", f=0.5)
    )
    c = sk_base.clone(b)
    assert c.params == b.params and c.policy == b.policy
    for cfg in ParameterGrid({"max_depth": [2], "sampling__f": [0.4]}):
        g = sk_base.clone(b).set_params(**cfg)
        assert g.params.max_depth == 2
        assert g.params.sampling.f == 0.4
        g.fit(X, y)
        assert auc(y, g.predict(X)) > 0.6


def test_set_params_keeps_training_consistent(arrays):
    X, y = arrays
    b = _booster().set_params(objective="binary:logistic", max_depth=2)
    b.fit(X, y)
    assert auc(y, b.predict(X)) > 0.6


def test_booster_params_validation():
    with pytest.raises(ValueError, match="grow_policy"):
        BoosterParams(grow_policy="bestfirst")
    with pytest.raises(ValueError, match="max_depth"):
        BoosterParams(max_depth=0)
    with pytest.raises(ValueError, match="mode"):
        ExecutionPolicy(mode="gpu")


# ------------------------------------------------------------------ distributed
def test_fit_sharded_accepts_dmatrix_and_matches_in_core(iter_dm, arrays):
    import jax

    from repro.distributed import DistConfig, fit_sharded

    X, y = arrays
    mesh = jax.make_mesh((1,), ("data",))
    b_dist = fit_sharded(
        mesh, iter_dm, params=BoosterParams(seed=0, **PARAMS), cfg=DistConfig()
    )
    b_in = _booster(ExecutionPolicy(mode="in_core"))
    b_in.fit(iter_dm)
    assert_forests_equal(b_dist.trees, b_in.trees)
    np.testing.assert_allclose(
        b_dist.predict_margin(X), b_in.predict_margin(X), rtol=1e-4, atol=1e-5
    )


def test_feature_parallel_lossguide_raises_clearly():
    import jax

    from repro.distributed import DistConfig, fit_sharded

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = DistConfig(feature_axis="model", grow_policy="lossguide", max_leaves=8)
    X = np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    with pytest.raises(NotImplementedError, match="feature-parallel lossguide"):
        fit_sharded(mesh, X, y, params=BoosterParams(seed=0, **PARAMS), cfg=cfg)

    # the tree-level entry point fails just as eagerly
    from repro.core import TreeParams
    from repro.distributed import check_feature_parallel_lossguide

    with pytest.raises(NotImplementedError, match="feature-parallel lossguide"):
        check_feature_parallel_lossguide(
            TreeParams(max_depth=3, grow_policy="lossguide", max_leaves=8), cfg
        )
