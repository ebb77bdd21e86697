"""In-core booster: learning, objectives, checkpointing, missing values."""
import numpy as np
import pytest

from repro.core import BoosterParams, ExecutionPolicy, GradientBooster, SamplingConfig
from repro.data.dmatrix import ArrayDMatrix
from repro.core.objectives import auc, rmse


PARAMS = dict(n_estimators=10, max_depth=3, max_bin=32, learning_rate=0.3)


def test_classification_learns(small_classification):
    X, y = small_classification
    b = GradientBooster(BoosterParams(objective="binary:logistic", **PARAMS))
    b.fit(X, y, eval_set=(X, y))
    assert b.eval_history[-1].value > 0.9  # train AUC
    # monotone-ish improvement over boosting
    assert b.eval_history[-1].value > b.eval_history[0].value


def test_regression_learns():
    from repro.data.synthetic import make_regression

    X, y = make_regression(512, 8, noise=0.05, seed=2)
    b = GradientBooster(BoosterParams(objective="reg:squarederror", **PARAMS))
    b.fit(X, y)
    pred = b.predict(X)
    assert rmse(y, pred) < rmse(y, np.full_like(y, y.mean())) * 0.6


def test_missing_values_learnable():
    rng = np.random.default_rng(0)
    n = 512
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    X[rng.random(n) < 0.3, 0] = np.nan  # feature 0 missing 30%
    b = GradientBooster(BoosterParams(objective="binary:logistic", **PARAMS))
    b.fit(X, y)
    assert auc(y, b.predict(X)) > 0.85


def test_sampling_modes_still_learn(small_classification):
    X, y = small_classification
    for method, kw in [("uniform", {"f": 0.6}), ("goss", {}), ("mvs", {"f": 0.4})]:
        cfg = SamplingConfig(method=method, **kw)
        b = GradientBooster(
            BoosterParams(objective="binary:logistic", sampling=cfg, seed=1, **PARAMS)
        )
        b.fit(X, y)
        assert auc(y, b.predict(X)) > 0.85, method


@pytest.mark.parametrize("engine", ["in_core", "out_of_core", "sampled", "fit_sharded"])
def test_early_stopping(small_classification, engine):
    """Every engine stops once the eval metric has not improved for
    ``early_stopping_rounds`` rounds and records the best round. The eval
    labels are flipped, so the eval AUC falls as the model learns."""
    X, y = small_classification
    params = BoosterParams(objective="binary:logistic", early_stopping_rounds=2, **PARAMS)
    eval_set = (X, 1.0 - y)
    if engine == "fit_sharded":
        import jax

        from repro.distributed import fit_sharded

        mesh = jax.make_mesh((1,), ("data",))
        b = fit_sharded(mesh, X, y, params=params, eval_set=eval_set)
    else:
        dm = ArrayDMatrix(X, y, max_bin=PARAMS["max_bin"], page_bytes=1024)
        b = GradientBooster(params, policy=ExecutionPolicy(mode=engine))
        b.fit(dm, eval_set=eval_set)
        assert b.decision_.mode == engine
    assert len(b.trees) < PARAMS["n_estimators"]
    assert len(b.eval_history) == len(b.trees)
    assert b.best_iteration_ == len(b.trees) - 1 - params.early_stopping_rounds


def test_checkpoint_every_saves_an_in_core_fit(tmp_path, small_classification):
    """``checkpoint_every`` saves the forest every k rounds in core too, and
    the checkpoint loads to the fit's first k, 2k, ... trees."""
    X, y = small_classification
    ckpt = str(tmp_path / "ckpt")
    policy = ExecutionPolicy(mode="in_core", checkpoint_every=4, checkpoint_dir=ckpt)
    b = GradientBooster(BoosterParams(objective="binary:logistic", **PARAMS), policy=policy)
    b.fit(X, y)
    # rounds 4 and 8 of 10 were saved; the older generation is kept as .prev
    restored = GradientBooster.load(ckpt)
    assert len(restored.trees) == 8
    assert len(GradientBooster.load(ckpt + ".prev").trees) == 4
    np.testing.assert_array_equal(
        restored.predict_margin(X), b.predict_margin(X, iteration_range=(0, 8))
    )


def test_save_load_roundtrip(tmp_path, small_classification):
    X, y = small_classification
    b = GradientBooster(BoosterParams(objective="binary:logistic", **PARAMS))
    b.fit(X, y)
    p1 = b.predict_margin(X)
    b.save(str(tmp_path / "ckpt"))
    b2 = GradientBooster.load(str(tmp_path / "ckpt"))
    p2 = b2.predict_margin(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-6)
    assert b2.params.objective == "binary:logistic"


def test_base_margin_default_is_log_odds(small_classification):
    X, y = small_classification
    b = GradientBooster(BoosterParams(objective="binary:logistic", n_estimators=1, max_depth=2, max_bin=16))
    b.fit(X, y)
    p = np.clip(np.mean(y), 1e-6, 1 - 1e-6)
    assert np.isclose(b.base_margin_, np.log(p / (1 - p)), rtol=1e-5)


def test_deterministic_given_seed(small_classification):
    X, y = small_classification
    cfg = SamplingConfig(method="mvs", f=0.5)
    preds = []
    for _ in range(2):
        b = GradientBooster(
            BoosterParams(objective="binary:logistic", sampling=cfg, seed=42, **PARAMS)
        )
        b.fit(X, y)
        preds.append(b.predict_margin(X))
    np.testing.assert_array_equal(preds[0], preds[1])
