"""Smoke run of the out-of-core GBDT trainer and forest server on one TPU.

Drives the paper's main path once through the entry points a user calls, at
the paper's Table 2 width: HIGGS-shaped data generated from ``--seed`` (28
features), ``max_bin=256`` (255 real bins), depth 8, learning rate 0.1,
``binary:logistic``, 2^21 training rows and a held-out eval set.

  (a) in-core    `GradientBooster.fit`, ``ExecutionPolicy(mode="in_core")``
  (b) streaming  the same `IterDMatrix` pages on disk, paper Alg. 6
  (c) sampled    the same pages with MVS f=0.1, paper Alg. 7
  (d) serving    `ForestServer` fused prediction of (a)'s forest over the
                 eval rows, ``array_equal`` to the per-tree path
  (e) reference  (a) with ``kernel_impl="ref"``: the XLA-compiled oracle,
                 independent of the Pallas kernels

(a) is held to (e) and (b) to (a) by the rules of
``tests/oracle.py::assert_forests_equal``, with an eval AUC difference of at
most 1e-3. ``--four-chips`` runs only `fit_sharded` on a 4-device
``("data",)`` mesh and a single-device in-core fit of the same data, held to
each other by the same rules.

Each phase prints one JSON line. Its seconds end in ``block_until_ready`` and
include compilation: they are a smoke run, not a benchmark. The last line of
a run that passed is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python3 chip_smoke.py                 # one chip, phases (a)-(e)
    python3 chip_smoke.py --four-chips    # four chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse --rows 8192

``--rehearse`` skips only the device check, and then never prints the ``ok``
line. The script runs in one process and starts none. The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
AUC_TOL = 1e-3
PAGE_BYTES = 4 * 2**20


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def phase(name: str, seconds: float, booster, auc: float, **extra) -> None:
    emit({
        "phase": name,
        "seconds": seconds,
        "trees": len(booster.trees),
        "auc": auc,
        **extra,
        "device_kind": jax.devices()[0].device_kind,
    })


def timed_fit(fit) -> tuple[object, float]:
    """Run ``fit()`` and return (booster, seconds to its last tree on device)."""
    t0 = time.perf_counter()
    booster = fit()
    jax.block_until_ready(booster.trees)
    return booster, time.perf_counter() - t0


def check_same_forest(got, want, what: str, failures: list[str]) -> None:
    """Hold two forests to the oracle's rules; record a failure and go on,
    so that one run reports every phase."""
    from oracle import assert_forests_equal

    d_auc = abs(got.eval_history[-1].value - want.eval_history[-1].value)
    # the share of the oracle's leaf tolerance (rtol 1e-4, atol 1e-5) used
    # by the worst leaf of each tree: below 1 passes
    tol_used = [
        float(np.max(np.abs(np.asarray(g.leaf_value) - np.asarray(w.leaf_value))
                     / (1e-5 + 1e-4 * np.abs(np.asarray(w.leaf_value)))))
        for g, w in zip(got.trees, want.trees)
    ]
    try:
        assert_forests_equal(got.trees, want.trees)
        if d_auc > AUC_TOL:
            raise AssertionError(f"eval AUC differs by {d_auc} > {AUC_TOL}")
    except AssertionError as e:
        failures.append(f"{what}: {e}")
        emit({"check": what, "forest": "DIFFERENT", "auc_delta": d_auc,
              "leaf_tol_used": tol_used, "error": str(e)[:400]})
        return
    emit({"check": what, "forest": "equal", "auc_delta": d_auc, "leaf_tol_used": tol_used})


def run_one_chip(dm, eval_set, params, impl: str, failures: list[str]) -> None:
    from repro.core import ExecutionPolicy, GradientBooster, SamplingConfig, bin_batch
    from repro.core.objectives import auc
    from repro.serve import ForestServer

    in_core = ExecutionPolicy(mode="in_core")

    a, sec = timed_fit(lambda: GradientBooster(params, policy=in_core).fit(dm, eval_set=eval_set))
    phase("a_in_core", sec, a, a.eval_history[-1].value, pages=1,
          h2d_bytes=a.stats.host_to_device_bytes, impl=impl)

    for name, f, mode in (("b_streaming", 1.0, "out_of_core"), ("c_sampled", 0.1, "sampled")):
        h2d0 = dm.stats.host_to_device_bytes
        b, sec = timed_fit(lambda: GradientBooster(
            params, policy=ExecutionPolicy(mode="out_of_core"),
            sampling=SamplingConfig(method="mvs", f=f),
        ).fit(dm, eval_set=eval_set))
        if b.decision_.mode != mode:
            raise AssertionError(f"{name}: ran {b.decision_.mode}, expected {mode}")
        phase(name, sec, b, b.eval_history[-1].value, pages=dm.n_pages,
              h2d_bytes=dm.stats.host_to_device_bytes - h2d0, impl=impl, mode=mode)
        if name == "b_streaming":
            check_same_forest(b, a, "b_streaming vs a_in_core", failures)
        elif not b.eval_history[-1].value > 0.5:
            raise AssertionError(f"{name}: eval AUC {b.eval_history[-1].value} <= 0.5")

    server = ForestServer(a)
    t0 = time.perf_counter()
    fused = server.predict_margin(eval_set[0])  # returns host numpy: synced
    sec = time.perf_counter() - t0
    bins = jax.numpy.asarray(bin_batch(eval_set[0], a.cuts).astype(np.int32))
    per_tree = np.asarray(server.forest.predict_margin_per_tree(bins))
    if not np.array_equal(fused, per_tree):
        raise AssertionError(
            f"d_serving: fused != per-tree on {int(np.sum(fused != per_tree))} rows"
        )
    phase("d_serving", sec, a, auc(eval_set[1], fused), pages=1,
          h2d_bytes=server.stats.host_to_device_bytes, impl=impl,
          rows=int(fused.shape[0]), equal_per_tree=True)

    e, sec = timed_fit(lambda: GradientBooster(
        params, policy=in_core, kernel_impl="ref"
    ).fit(dm, eval_set=eval_set))
    phase("e_reference", sec, e, e.eval_history[-1].value, pages=1,
          h2d_bytes=e.stats.host_to_device_bytes, impl="ref")
    check_same_forest(a, e, "a_in_core vs e_reference", failures)


def run_four_chips(dm, eval_set, params, impl: str, rehearse: bool, failures: list[str]) -> None:
    from repro.core import ExecutionPolicy, GradientBooster
    from repro.distributed import DistConfig, fit_sharded

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = jax.make_mesh((4,), ("data",))
    s, sec = timed_fit(lambda: fit_sharded(
        mesh, dm, params=params, cfg=DistConfig(data_axes=("data",)), eval_set=eval_set
    ))
    # rows must really shard: every device holds at least its int32 row shard
    shard_bytes = dm.n_rows * dm.num_features * 4 // len(devices)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    if not rehearse and not all(p is not None and p >= shard_bytes for p in peaks):
        raise AssertionError(f"rows did not shard: peaks {peaks} < {shard_bytes} B")
    phase("sharded_4", sec, s, s.eval_history[-1].value, pages=1,
          h2d_bytes=s.stats.host_to_device_bytes, impl=impl,
          peak_bytes_per_device=peaks, row_shard_bytes=shard_bytes)

    one, sec = timed_fit(lambda: GradientBooster(
        params, policy=ExecutionPolicy(mode="in_core")
    ).fit(dm, eval_set=eval_set))
    phase("single_device", sec, one, one.eval_history[-1].value, pages=1,
          h2d_bytes=one.stats.host_to_device_bytes, impl=impl)
    check_same_forest(s, one, "sharded_4 vs single_device", failures)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=2**21, help="training rows")
    ap.add_argument("--trees", type=int, default=4, help="trees per phase")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fit_sharded on 4 chips and its 1-device reference")
    ap.add_argument("--rehearse", action="store_true",
                    help="skip the device check (CPU rehearsal); never prints ok")
    args = ap.parse_args(argv)

    if "REPRO_KERNEL_IMPL" in os.environ:
        raise SystemExit("unset REPRO_KERNEL_IMPL: kernels must resolve from the device")
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))

    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    from repro.kernels import ops
    from repro.kernels._backend import resolve_interpret

    dev = jax.devices()[0]
    impl = ops._resolve("auto")
    if not args.rehearse:
        if dev.platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
        if impl != "pallas" or resolve_interpret(None) is not False:
            raise SystemExit(f"kernels resolved to {impl}, not compiled Pallas")

    from repro.core import BoosterParams
    from repro.data.dmatrix import IterDMatrix
    from repro.data.synthetic import SyntheticSource

    params = BoosterParams(
        n_estimators=args.trees, max_depth=8, learning_rate=0.1, max_bin=256,
        objective="binary:logistic", seed=args.seed,
    )
    source = SyntheticSource(n_rows=args.rows, num_features=28, task="higgs",
                             seed=args.seed, batch_rows=2**17)
    eval_rows = min(2**17, max(args.rows // 4, 1024))
    eval_set = SyntheticSource(n_rows=eval_rows, num_features=28, task="higgs",
                               seed=args.seed, batch_offset=10**6).materialize()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as cache_dir:
        t0 = time.perf_counter()
        dm = IterDMatrix(source, max_bin=params.max_bin, cache_dir=cache_dir,
                         page_bytes=min(PAGE_BYTES, args.rows * 28 // 8))
        emit({"phase": "data", "seconds": time.perf_counter() - t0, "rows": dm.n_rows,
              "features": dm.num_features, "bins": dm.n_bins, "pages": dm.n_pages,
              "eval_rows": eval_rows, "impl": impl, "device_kind": dev.device_kind})
        if dm.n_pages < 2:
            raise AssertionError(f"expected several pages, got {dm.n_pages}")
        failures: list[str] = []
        if args.four_chips:
            run_four_chips(dm, eval_set, params, impl, args.rehearse, failures)
        else:
            run_one_chip(dm, eval_set, params, impl, failures)
    if failures:
        raise SystemExit("checks failed:\n" + "\n".join(failures))

    if args.rehearse:
        emit({"rehearsal": "passed", "platform": dev.platform})
        return 0
    emit({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
