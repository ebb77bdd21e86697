"""Fault tolerance plumbing: RetryPolicy, FaultInjector, durable pages,
atomic checksummed checkpoints, and the crash-window resume paths.

Chaos tests for the multi-worker ElasticTrainer live in test_elastic.py
(slow); everything here is fast and runs in tier-1.
"""
import os
import shutil

import numpy as np
import pytest

from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
from repro.core.booster import CheckpointCorruptError
from repro.data.dmatrix import IterDMatrix
from repro.data.pages import PageCorruptError, PageStore, Prefetcher, TransferStats
from repro.data.synthetic import SyntheticSource
from repro.fault import (
    FaultPlan,
    FaultSpec,
    RetryPolicy,
    get_injector,
    injected,
)
from repro.fault import inject as fault_inject

PARAMS = dict(n_estimators=3, max_depth=3, max_bin=32, objective="binary:logistic")
OUT_OF_CORE = ExecutionPolicy(mode="out_of_core")


# ------------------------------------------------------------------ RetryPolicy

def test_retry_policy_backoff_schedule_is_deterministic():
    p = RetryPolicy(max_attempts=4, base_delay=0.1, multiplier=2.0, jitter=0.0)
    assert p.delays() == [0.1, 0.2, 0.4]
    # the jitter stream is seeded: two calls agree, and stay within bounds
    q = RetryPolicy(max_attempts=4, base_delay=0.1, multiplier=2.0, jitter=0.5, seed=7)
    d1, d2 = q.delays(), q.delays()
    assert d1 == d2
    for raw, got in zip([0.1, 0.2, 0.4], d1):
        assert raw * 0.5 <= got <= raw


def test_retry_policy_max_delay_caps_backoff():
    p = RetryPolicy(max_attempts=5, base_delay=1.0, multiplier=10.0, max_delay=2.0, jitter=0.0)
    assert p.delays() == [1.0, 2.0, 2.0, 2.0]


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=1.5)


def test_retry_policy_retries_then_succeeds_counting_stats():
    stats = TransferStats()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    p = RetryPolicy(max_attempts=3, base_delay=0.0)
    assert p.call(flaky, stats=stats, sleep=lambda _t: None) == "ok"
    assert calls["n"] == 3
    assert stats.io_retries == 2
    assert stats.io_giveups == 0


def test_retry_policy_gives_up_after_budget():
    stats = TransferStats()
    calls = {"n": 0}

    def always_fails():
        calls["n"] += 1
        raise OSError("still broken")

    p = RetryPolicy(max_attempts=3, base_delay=0.0)
    with pytest.raises(OSError, match="still broken"):
        p.call(always_fails, stats=stats, sleep=lambda _t: None)
    assert calls["n"] == 3
    assert stats.io_retries == 2
    assert stats.io_giveups == 1


def test_retry_policy_nonretryable_raises_immediately():
    calls = {"n": 0}

    def corrupt():
        calls["n"] += 1
        raise PageCorruptError(0, "/nowhere", 1, 2)

    p = RetryPolicy(max_attempts=5, base_delay=0.0)
    # PageCorruptError IS an OSError, but nonretryable wins on the overlap
    with pytest.raises(PageCorruptError):
        p.call(
            corrupt,
            retryable=(OSError,),
            nonretryable=(PageCorruptError,),
            sleep=lambda _t: None,
        )
    assert calls["n"] == 1


def test_retry_policy_unlisted_exception_passes_through():
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise ValueError("deterministic bug, never retry")

    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=3, base_delay=0.0).call(bug, sleep=lambda _t: None)
    assert calls["n"] == 1


# ---------------------------------------------------------------- FaultInjector

def test_fault_injector_unset_is_noop():
    assert get_injector() is None
    fault_inject.fire("page_store.read_page", index=0)  # must not raise


def test_fault_spec_triggers_on_call_count_window():
    plan = FaultPlan.of(FaultSpec(site="s", at=2, count=2, exc="OSError"))
    with injected(plan) as inj:
        fault_inject.fire("s")  # call 1: before window
        with pytest.raises(OSError, match=r"\[site=s call=2\]"):
            fault_inject.fire("s")
        with pytest.raises(OSError):
            fault_inject.fire("s")
        fault_inject.fire("s")  # call 4: past window
        assert inj.call_count("s") == 4
        assert len(inj.fired) == 2
    assert get_injector() is None  # context manager uninstalls


def test_fault_spec_match_filters_context():
    plan = FaultPlan.of(
        FaultSpec(site="rpc", at=1, count=-1, match={"worker": "w1"}, exc="TimeoutError")
    )
    with injected(plan):
        fault_inject.fire("rpc", worker="w0")  # wrong worker: no fault
        with pytest.raises(TimeoutError):
            fault_inject.fire("rpc", worker="w1")


def test_fault_spec_delay_action_sleeps():
    import time

    plan = FaultPlan.of(FaultSpec(site="s", action="delay", delay_s=0.05))
    with injected(plan):
        t0 = time.perf_counter()
        fault_inject.fire("s")
        assert time.perf_counter() - t0 >= 0.04


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="action"):
        FaultSpec(site="s", action="explode")
    with pytest.raises(ValueError, match="exc"):
        FaultSpec(site="s", exc="KeyboardInterrupt")
    with pytest.raises(ValueError, match="1-based"):
        FaultSpec(site="s", at=0)
    with pytest.raises(ValueError, match="count"):
        FaultSpec(site="s", count=0)


def test_fault_plan_json_roundtrip():
    plan = FaultPlan.of(
        FaultSpec(site="a", at=3, action="delay", delay_s=0.5),
        FaultSpec(site="b", exc="ConnectionError", match={"op": "hist"}),
        seed=9,
    )
    assert FaultPlan.from_json(plan.to_json()) == plan


def test_install_from_env_arms_serialized_plan():
    """The coordinator→worker handoff: a plan serialized into the env var is
    installed by the subprocess entry point; empty/missing means no-op."""
    plan = FaultPlan.of(FaultSpec(site="s", exc="OSError"))
    try:
        assert fault_inject.install_from_env({}) is None
        assert fault_inject.install_from_env({fault_inject.ENV_VAR: ""}) is None
        inj = fault_inject.install_from_env({fault_inject.ENV_VAR: plan.to_json()})
        assert inj is get_injector()
        with pytest.raises(OSError):
            fault_inject.fire("s")
    finally:
        fault_inject.uninstall()


# ---------------------------------------------------- Prefetcher + PageStore IO

def test_prefetcher_flaky_load_retries_into_stats():
    stats = TransferStats()
    calls = {"n": 0}

    def load(idx):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("flaky read")
        return idx

    pf = Prefetcher(load, range(3), depth=1,
                    retry=RetryPolicy(max_attempts=2, base_delay=0.0), stats=stats)
    assert [item for _idx, item in pf] == [0, 1, 2]
    assert stats.io_retries == 1
    assert stats.io_giveups == 0


def test_prefetcher_gives_up_after_retry_budget():
    stats = TransferStats()

    def load(idx):
        raise OSError("disk gone")

    pf = Prefetcher(load, range(2), depth=1,
                    retry=RetryPolicy(max_attempts=3, base_delay=0.0), stats=stats)
    with pytest.raises(RuntimeError, match="failed to load"):
        list(pf)
    assert stats.io_giveups >= 1
    assert stats.io_retries >= 2


def test_prefetcher_corrupt_page_is_not_retried(tmp_path):
    store = PageStore(str(tmp_path / "pages"))
    idx = store.write_page({"bins": np.arange(12, dtype=np.uint8)})
    path = os.path.join(str(tmp_path / "pages"), f"page_{idx:06d}.bin")
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF
    open(path, "wb").write(bytes(blob))

    loads = {"n": 0}

    def load(i):
        loads["n"] += 1
        return store.read_page(i)

    pf = Prefetcher(load, [idx], depth=1, retry=RetryPolicy(max_attempts=5, base_delay=0.0))
    with pytest.raises(PageCorruptError):
        list(pf)
    assert loads["n"] == 1  # corruption is permanent: retrying is pointless


def test_page_store_crc_names_corrupt_page(tmp_path):
    store = PageStore(str(tmp_path / "pages"))
    store.write_page({"bins": np.zeros(64, np.uint8)})
    idx = store.write_page({"bins": np.ones(64, np.uint8)})
    path = os.path.join(str(tmp_path / "pages"), f"page_{idx:06d}.bin")
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0x5A
    open(path, "wb").write(bytes(blob))

    with pytest.raises(PageCorruptError, match=f"page {idx}") as ei:
        store.read_page(idx)
    assert ei.value.idx == idx
    assert "IterDMatrix" in str(ei.value)  # actionable: rebuild from raw source
    # undamaged neighbours still verify
    np.testing.assert_array_equal(store.read_page(0)["bins"], np.zeros(64, np.uint8))


def test_page_store_legacy_manifest_without_crc_still_reads(tmp_path):
    import json

    store = PageStore(str(tmp_path / "pages"))
    idx = store.write_page({"bins": np.arange(8, dtype=np.uint8)})
    mpath = os.path.join(str(tmp_path / "pages"), "manifest.json")
    meta = json.load(open(mpath))
    for entry in meta["pages"]:
        entry.pop("crc32", None)
    json.dump(meta, open(mpath, "w"))

    legacy = PageStore(str(tmp_path / "pages"))
    np.testing.assert_array_equal(legacy.read_page(idx)["bins"], np.arange(8, dtype=np.uint8))


def test_fault_injection_on_page_read_is_absorbed_by_retry(tmp_path):
    """End-to-end: one injected read fault mid-fit is retried transparently."""
    source = SyntheticSource(n_rows=600, num_features=8, batch_rows=200, task="higgs", seed=2)
    stats = TransferStats()
    plan = FaultPlan.of(
        FaultSpec(site="page_store.read_page", at=3, exc="OSError", message="yanked disk")
    )
    with injected(plan) as inj:
        dm = IterDMatrix(
            source, max_bin=32, cache_dir=str(tmp_path / "cache"), page_bytes=4 * 1024,
            stats=stats,
        )
        b = GradientBooster(BoosterParams(seed=0, **PARAMS), policy=OUT_OF_CORE)
        b.fit(dm)
    assert len(inj.fired) == 1
    assert stats.io_retries >= 1
    assert stats.io_giveups == 0
    assert len(b.trees) == PARAMS["n_estimators"]


# ------------------------------------------------- atomic checksummed checkpoints

@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    src = SyntheticSource(n_rows=600, num_features=8, batch_rows=200, task="higgs", seed=4)
    dm = IterDMatrix(
        src, max_bin=32, cache_dir=str(tmp_path_factory.mktemp("fitcache") / "cache"),
        page_bytes=4 * 1024,
    )
    return GradientBooster(BoosterParams(seed=0, **PARAMS), policy=OUT_OF_CORE).fit(dm)


def test_checkpoint_manifest_and_verify(tmp_path, fitted):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)
    assert sorted(os.listdir(ckpt)) == ["booster.json", "manifest.json", "model.npz"]
    GradientBooster.verify_checkpoint(ckpt)  # intact: no raise
    assert GradientBooster.last_good_checkpoint(ckpt) == ckpt


def test_checkpoint_truncated_model_raises_named_error(tmp_path, fitted):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)
    model = os.path.join(ckpt, "model.npz")
    with open(model, "r+b") as fh:
        fh.truncate(os.path.getsize(model) // 2)
    with pytest.raises(CheckpointCorruptError, match="model.npz") as ei:
        GradientBooster.load(ckpt)
    assert ei.value.bad_file == "model.npz"
    assert "CRC32" in str(ei.value)


def test_checkpoint_missing_booster_json_raises(tmp_path, fitted):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)
    os.remove(os.path.join(ckpt, "booster.json"))
    with pytest.raises(CheckpointCorruptError, match="booster.json"):
        GradientBooster.load(ckpt)


def test_checkpoint_rotation_keeps_last_good_generation(tmp_path, fitted):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)
    fitted.save(ckpt)  # second save rotates the first to .prev
    assert os.path.isdir(ckpt + ".prev")

    model = os.path.join(ckpt, "model.npz")
    with open(model, "r+b") as fh:
        fh.truncate(1)
    with pytest.raises(CheckpointCorruptError) as ei:
        GradientBooster.verify_checkpoint(ckpt)
    # the error points at the intact previous generation...
    assert ei.value.last_good == ckpt + ".prev"
    assert ckpt + ".prev" in str(ei.value)
    # ...and the fallback resolver agrees and loads bit-for-bit
    assert GradientBooster.last_good_checkpoint(ckpt) == ckpt + ".prev"
    prev = GradientBooster.load(ckpt + ".prev")
    assert len(prev.trees) == len(fitted.trees)
    for got, want in zip(prev.trees, fitted.trees):
        for field in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
            )


def test_checkpoint_both_generations_gone_reports_no_fallback(tmp_path, fitted):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)
    os.remove(os.path.join(ckpt, "model.npz"))
    with pytest.raises(CheckpointCorruptError, match="no intact previous checkpoint"):
        GradientBooster.load(ckpt)
    assert GradientBooster.last_good_checkpoint(ckpt) is None


def test_checkpoint_legacy_layout_without_manifest_loads(tmp_path, fitted):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)
    os.remove(os.path.join(ckpt, "manifest.json"))  # pre-manifest layout
    b = GradientBooster.load(ckpt)
    assert len(b.trees) == len(fitted.trees)


def test_save_failure_leaves_no_temp_litter(tmp_path, fitted, monkeypatch):
    ckpt = str(tmp_path / "ckpt")
    fitted.save(ckpt)

    real_replace = os.replace

    def exploding_replace(src, dst):
        if dst == ckpt and src.startswith(ckpt + ".tmp"):
            raise OSError("simulated crash at publish")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", exploding_replace)
    with pytest.raises(OSError, match="simulated crash"):
        fitted.save(ckpt)
    monkeypatch.undo()
    # the failed save cleaned its temp dir and never touched the live copy
    assert not any(name.startswith("ckpt.tmp") for name in os.listdir(tmp_path))
    GradientBooster.verify_checkpoint(ckpt)


def test_resume_from_previous_generation_reproduces_training(tmp_path):
    """The crash-window story end to end: the latest checkpoint dies, training
    resumes from .prev and still converges to the uninterrupted forest."""
    src = SyntheticSource(n_rows=600, num_features=8, batch_rows=200, task="higgs", seed=6)
    params = BoosterParams(seed=0, **PARAMS)
    cache = str(tmp_path / "cache")
    ckpt = str(tmp_path / "ckpt")

    full = GradientBooster(params, policy=OUT_OF_CORE)
    full.fit(IterDMatrix(src, max_bin=32, cache_dir=cache, page_bytes=4 * 1024))

    import dataclasses

    part = GradientBooster(dataclasses.replace(params, n_estimators=1), policy=OUT_OF_CORE)
    part_dm = IterDMatrix(src, max_bin=32, page_bytes=4 * 1024)
    part.fit(part_dm)
    part.save(ckpt)
    part.params = dataclasses.replace(params, n_estimators=2)
    part.fit(part_dm, start_iteration=1)
    part.save(ckpt)  # generation 2; generation 1 rotates to .prev

    shutil.rmtree(ckpt)  # the crash window claims the newest generation
    good = GradientBooster.last_good_checkpoint(ckpt)
    assert good == ckpt + ".prev"
    resumed = GradientBooster.resume(good, src)
    assert len(resumed.trees) == 1
    resumed.params = params
    # the source re-paged with the checkpointed cuts (no re-sketch)
    resumed.fit(
        IterDMatrix(src, max_bin=32, cuts=resumed.cuts, page_bytes=4 * 1024), start_iteration=1
    )
    X, _ = src.materialize()
    np.testing.assert_allclose(
        resumed.predict_margin(X), full.predict_margin(X), rtol=1e-4, atol=1e-5
    )
