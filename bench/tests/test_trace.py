"""The reduction from a device trace to busy time, kernel time and idle gaps,
on two small traces recorded on a TPU v5e (``data/``): a one-tree in-core fit
of 8192 rows and two scoring requests of 1024 rows to a 40-tree forest, each
inside the benchmark's ``bench.window`` span. The expected numbers were read
from the same files when they were recorded."""
from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from bench import trace
from bench.metrics import load

DATA = Path(__file__).resolve().parent / "data"

# cell: (busy s, window s, kernel op, kernel s, kernel reader)
RECORDED = {
    "higgs.train.in_core": (0.007165824, 0.44777639, "build_histogram_slab", 0.004816445,
                            "hist_kernel_roofline"),
    "forest.score.batch": (0.000714586, 0.011654919, "predict_forest", 0.000690157,
                           "forest_kernel_roofline"),
}


@pytest.fixture(scope="module", params=sorted(RECORDED))
def reduced(request, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / f"{request.param}.xplane.pb.gz").read_bytes()))
    assert trace.find_xplane(str(path.parent)) == str(path)
    return request.param, trace.reduce_trace(str(path))


def test_busy_window_and_kernel_time(reduced):
    cell, red = reduced
    busy, window, kernel, kernel_s, reader = RECORDED[cell]
    assert sorted(red["devices"]) == [0]
    assert trace.busy_s(red) == pytest.approx(busy, abs=1e-9)
    assert red["window_s"] == pytest.approx(window, abs=1e-9)
    assert trace.idle_share(red) == pytest.approx(1 - busy / window, abs=1e-9)
    top = trace.top_ops(red, 3)
    assert top[0][0] == kernel and top[0][1] == pytest.approx(kernel_s, abs=1e-9)
    assert trace.op_seconds(red, load(reader).KERNEL) == pytest.approx(kernel_s, abs=1e-9)


def test_readers_of_other_kernels_find_nothing(reduced):
    cell, red = reduced
    others = {"hist_kernel_roofline", "forest_kernel_roofline"} - {RECORDED[cell][4]}
    for name in others:
        assert trace.op_seconds(red, load(name).KERNEL) == 0.0


def test_idle_gaps_cover_the_idle_time(reduced):
    _, red = reduced
    gaps = trace.idle_gaps(red, k=10**6)
    idle = red["window_s"] - trace.busy_s(red)
    assert all(s > 0 for _, s in gaps)
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert len(trace.idle_gaps(red)) <= 10


def test_union_and_op_name():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert trace.op_name("%build_histogram_slab.12 = (s32[2,8]) custom-call(%a)") == \
        "build_histogram_slab"

