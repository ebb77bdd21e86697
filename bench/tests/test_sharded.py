"""The four-chip data-parallel training cell (``higgs.train.sharded4``) and its
two readers of the distributed layer.

The cell runs end to end at a small size on four forced CPU host devices, in
a subprocess (JAX fixes the device count when it starts). The readers run on
a small four-chip fit recorded on a TPU v5e (``data/``), and on traces and
counts of programs that have no collectives or no counter."""
from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import trace
from bench.metrics import load

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "higgs.train.sharded4"

SCRIPT = r"""
import json
from bench.tests import small

small.SMALL["higgs-table2-dp4"] = dict(small.SMALL["higgs-table2"])
small.SMALL["train.sharded4"] = dict(small.SMALL["train.in_core"])
result = small.run_small("higgs.train.sharded4", seed=2147483659, seconds=0.2)
print("RESULT " + json.dumps(result))
"""


def test_small_cell_on_four_cpu_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")])}
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-8000:]
    lines = [json.loads(ln.split(" ", 1)[1] if ln.startswith("RESULT ") else ln)
             for ln in proc.stdout.splitlines() if ln.startswith(("RESULT ", "{"))]
    compiles = next(ln for ln in lines if "compiles_in_window" in ln)
    result = next(ln for ln in lines if "correct" in ln)
    assert compiles["compiles_in_window"] == 0, compiles["compiled"]
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) >= {"tree_s", "peak_device_mb"}
    for name, check in result["checks"].items():
        assert check["value"] <= check["limit"], name


MS = 1e6  # ns
ALL_REDUCE = "%psum.137 = f32[1,28,256,2]{2,1,3,0:T(8,128)S(1)} all-reduce(%slice_bitcast_fusion.2), channel_id=1"
ALL_REDUCE_TUPLE = ("%all-reduce.10 = (f32[1,28,256,2]{2,1,3,0}, f32[]{:T(128)}) "
                    "all-reduce(%slice_bitcast_fusion.3, %get-tuple-element.310), channel_id=1")
USES_ALL_REDUCE = "%add_fusion = f32[64]{0} fusion(f32[64]{0} %psum.137, f32[64]{0} %all-reduce.9), kind=kLoop"


def _reduced(events_per_device):
    """A reduced trace (`bench.trace.reduce_trace`'s shape) with the given
    (start_ns, end_ns, hlo) events on each device."""
    return {"window": (0, 1000 * MS), "window_s": 1.0, "host_spans": [],
            "devices": {d: {"events": [(s, e, [hlo]) for s, e, hlo in ev]}
                        for d, ev in enumerate(events_per_device)}}


def _work(trees=2, **extra):
    return {"mode": "train", "level_work": [{}] * trees, **extra}


def test_collective_ms_counts_collective_opcodes_on_the_busiest_device():
    red = _reduced([
        [(0, 3 * MS, ALL_REDUCE), (5 * MS, 6 * MS, ALL_REDUCE_TUPLE), (7 * MS, 9 * MS, USES_ALL_REDUCE)],
        [(0, 1 * MS, ALL_REDUCE)],
    ])
    got = load("collective_ms_per_tree").read({"trace": red, "work": _work(trees=2)})
    assert got == pytest.approx((3 + 1) / 2)


def test_collective_mb_is_the_programs_count_per_tree():
    ctx = {"trace": None, "work": _work(trees=3, collective_bytes=3 * 7_345_144)}
    assert load("collective_mb_per_tree").read(ctx) == pytest.approx(7.345144)


def test_readers_find_nothing_without_collectives_or_counter():
    # an older program: no `collective_bytes` in the counts
    assert load("collective_mb_per_tree").read({"trace": None, "work": _work()}) is None
    assert load("collective_mb_per_tree").read(
        {"trace": None, "work": _work(collective_bytes=None)}) is None
    # a one-device fit recorded on the chip holds no collective
    path = DATA / "higgs.train.in_core.xplane.pb.gz"
    red = _reduce_recorded(path)
    assert load("collective_ms_per_tree").read({"trace": red, "work": _work(trees=1)}) is None


def _reduce_recorded(path: Path) -> dict:
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        raw = Path(d) / "t.xplane.pb"
        raw.write_bytes(gzip.decompress(path.read_bytes()))
        return trace.reduce_trace(str(raw))


# the recording: `higgs.train.sharded4` at the small size above (8192 rows,
# 2048 eval rows), seed 5, its traced fit of 3 trees on a TPU v5e host of
# four chips, Python tracer off. Numbers read from the file when it was made.
RECORDED_TREES = 3
RECORDED_COLLECTIVES = {"psum": 14 * RECORDED_TREES, "all-reduce": 2 * RECORDED_TREES}


@pytest.fixture(scope="module")
def recorded():
    return _reduce_recorded(DATA / f"{CELL}.xplane.pb.gz")


def test_recorded_collectives_are_the_tree_programs_all_reduces(recorded):
    pattern = load("collective_ms_per_tree").COLLECTIVE
    assert sorted(recorded["devices"]) == [0, 1, 2, 3]
    for info in recorded["devices"].values():
        found: dict[str, int] = {}
        for _, _, names in info["events"]:
            if any(pattern.search(n) for n in names):
                found[trace.op_name(names[0])] = found.get(trace.op_name(names[0]), 0) + 1
        assert found == RECORDED_COLLECTIVES


def test_readers_on_the_recorded_fit(recorded):
    work = _work(trees=RECORDED_TREES, collective_bytes=21_949_416)
    ctx = {"trace": recorded, "work": work}
    assert load("collective_ms_per_tree").read(ctx) == pytest.approx(0.191601, abs=1e-9)
    # 128 built node histograms x 28 features x 255 bins x (g, h) x 4 B,
    # 254 int32 row counts, 2 x 511 leaf sums and 2 root sums, a tree
    assert load("collective_mb_per_tree").read(ctx) == pytest.approx(7.316472)
    assert 128 * 28 * 255 * 8 + 254 * 4 + 2 * 511 * 4 + 2 * 4 == 7_316_472
