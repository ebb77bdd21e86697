"""Benchmark harness: one entry per paper table/figure (+ kernels).

Prints ``name,us_per_call,derived`` CSV. ``--quick`` shrinks sweeps.
``--json <path>`` additionally writes the collected rows to exactly that
path as a machine-readable perf record (one {name, us_per_call, derived,
timestamp} object per row). Checked-in baselines follow the
``BENCH_<suite>.json`` naming convention at the repo root (e.g.
``--only kernel_bench --json BENCH_kernels.json``) so the perf trajectory
is diffable across PRs.

The persistent compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def write_json_record(path: str, rows: list[str], quick: bool) -> None:
    ts = time.strftime("%Y-%m-%d %H:%M:%S")
    records = []
    for row in rows:
        name, us, derived = row.split(",", 2)
        records.append(
            {"name": name, "us_per_call": float(us), "derived": derived, "timestamp": ts}
        )
    with open(path, "w") as fh:
        json.dump({"schema": "bench-v1", "quick": quick, "records": records}, fh, indent=1)
        fh.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated benchmark names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a JSON perf record at PATH "
                         "(checked-in baselines: BENCH_<suite>.json)")
    args = ap.parse_args()

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax

        cache = Path(__file__).resolve().parents[1] / ".jax_cache"
        jax.config.update("jax_compilation_cache_dir", str(cache))

    from benchmarks import fault_tolerance, kernel_bench, max_data_size
    from benchmarks import sampling_methods, serving_latency, training_curves
    from benchmarks import training_time

    table = {
        "table1_max_data_size": max_data_size.main,
        "table2_training_time": training_time.main,
        "fig1_training_curves": training_curves.main,
        "sampling_methods": sampling_methods.main,
        "kernel_bench": kernel_bench.main,
        "serving_latency": serving_latency.main,
        "fault_tolerance": fault_tolerance.main,
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    all_rows: list[str] = []
    for name, fn in table.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for row in fn(quick=args.quick):
                print(row, flush=True)
                all_rows.append(row)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},-1,ERROR:{type(e).__name__}:{e}", flush=True)
        print(f"# {name} took {time.perf_counter()-t0:.1f}s", file=sys.stderr)
    if args.json:
        write_json_record(args.json, all_rows, args.quick)
    if failures:
        raise SystemExit(failures)


if __name__ == "__main__":
    main()
