"""End-to-end system behaviour: the paper's claims on this implementation.

§4.2  "When not sampling the data, the out-of-core GPU algorithm is
       equivalent to the in-core version."            -> test_equivalence
§4.2  "Models with different sampling rates performed similarly"
                                                      -> test_sampling_auc_close
§3.4  compaction reduces device traffic               -> (tests/test_outofcore.py)
Table 1 ratios                                        -> test_memory_model_ratios
"""
import numpy as np
import pytest

from repro.core import (
    BoosterParams,
    DeviceMemoryModel,
    ExecutionPolicy,
    GradientBooster,
    SamplingConfig,
)
from repro.core.objectives import auc
from repro.data.dmatrix import IterDMatrix
from repro.data.synthetic import SyntheticSource

PARAMS = dict(n_estimators=10, max_depth=4, max_bin=32, learning_rate=0.1,
              objective="binary:logistic")


@pytest.fixture(scope="module")
def higgs():
    train = SyntheticSource(n_rows=3000, num_features=28, batch_rows=512,
                            task="higgs", seed=9)
    evals = SyntheticSource(n_rows=1200, num_features=28, task="higgs", seed=9,
                            batch_offset=5000)
    return train, train.materialize(), evals.materialize()


@pytest.mark.slow
def test_end_to_end_beats_baseline(higgs):
    train_src, (X, y), (Xe, ye) = higgs
    b = GradientBooster(BoosterParams(seed=0, **PARAMS),
                        policy=ExecutionPolicy(mode="out_of_core"))
    b.fit(IterDMatrix(train_src, max_bin=32, page_bytes=16 * 1024), eval_set=(Xe, ye))
    assert b.eval_history[-1].value > 0.72  # well above random on held-out data
    # boosting monotonically helps on average
    assert b.eval_history[-1].value > b.eval_history[0].value


@pytest.mark.slow
def test_sampling_auc_close(higgs):
    """Fig-1 claim: sampled AUC within a small margin of full-data AUC."""
    train_src, (X, y), (Xe, ye) = higgs
    dm = IterDMatrix(train_src, max_bin=32, page_bytes=16 * 1024)
    full = GradientBooster(BoosterParams(seed=0, **PARAMS),
                           policy=ExecutionPolicy(mode="out_of_core"))
    full.fit(dm)
    a_full = auc(ye, full.predict(Xe))

    mvs = GradientBooster(
        BoosterParams(seed=0, sampling=SamplingConfig(method="mvs", f=0.3), **PARAMS),
        policy=ExecutionPolicy(mode="sampled"),
    )
    mvs.fit(dm)
    a_mvs = auc(ye, mvs.predict(Xe))
    assert a_full - a_mvs < 0.03, (a_full, a_mvs)


def test_memory_model_ratios():
    """Table-1 shape: out-of-core > in-core; f=0.1 sampling ~an order of magnitude."""
    m = DeviceMemoryModel()  # 16 GiB, 500 features (paper §4.1)
    in_core = m.max_rows_in_core()
    ooc = m.max_rows_out_of_core()
    sampled = m.max_rows_sampled(0.1)
    assert ooc > in_core
    assert 5 <= sampled / in_core <= 20  # paper: 85M/9M ≈ 9.4x


def test_in_core_sampled_equals_masked(higgs):
    """In-core mask-based sampling is exactly Alg. 7 compact-and-build."""
    _, (X, y), _ = higgs
    cfg = SamplingConfig(method="mvs", f=0.5)
    b1 = GradientBooster(BoosterParams(seed=3, sampling=cfg, **PARAMS)).fit(X, y)
    b2 = GradientBooster(BoosterParams(seed=3, sampling=cfg, **PARAMS)).fit(X, y)
    np.testing.assert_array_equal(b1.predict_margin(X), b2.predict_margin(X))
