"""`bench.spans` and the readers of the program's spans, on reduced traces built
by hand with known intervals (nanoseconds), and on a streaming fit recorded on
a TPU v5e (``data/``)."""
from __future__ import annotations

import pytest

from bench import spans
from bench.metrics import load

MS = 1e6  # ns


def reduced(busy, host, window=(0, 1000 * MS)):
    """A reduced trace (`bench.trace.reduce_trace`'s shape) of one device per
    busy list; ``host`` holds (start, end, name) host events."""
    if busy and not isinstance(busy[0], list):
        busy = [busy]
    return {"window": window, "window_s": (window[1] - window[0]) * 1e-9,
            "devices": {d: {"busy": b} for d, b in enumerate(busy)},
            "host_spans": sorted(host)}


def read(metric, red):
    return load(metric).read({"trace": red, "work": {}})


def test_intervals_clip_to_the_window_and_union():
    red = reduced([], [(-5 * MS, 5 * MS, "gbdt.round"), (990 * MS, 1200 * MS, "gbdt.round"),
                       (2000 * MS, 2100 * MS, "gbdt.round"), (1 * MS, 2 * MS, "other")],
                  window=(0, 1000 * MS))
    assert spans.intervals(red, "gbdt.round") == [(0, 5 * MS), (990 * MS, 1000 * MS)]
    assert spans.length([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == pytest.approx(10e-9)
    assert spans.overlap([(0, 10)], []) == 0.0


ROUNDS = [(0, 500 * MS, "gbdt.round"), (600 * MS, 1000 * MS, "gbdt.round")]


def test_tree_idle_share_counts_the_rounds_only():
    # busy 100 + 100 ms inside round 0, 50 ms of 550-650 inside round 1;
    # the 100 ms between the rounds are no round's
    red = reduced([(100 * MS, 200 * MS), (300 * MS, 400 * MS), (550 * MS, 650 * MS)], ROUNDS)
    assert read("tree_idle_share", red) == pytest.approx(100 * (1 - 250 / 900))
    # averaged over devices: a second device busy throughout
    red = reduced([[(100 * MS, 200 * MS), (300 * MS, 400 * MS), (550 * MS, 650 * MS)],
                   [(0, 1000 * MS)]], ROUNDS)
    assert read("tree_idle_share", red) == pytest.approx(100 * (1 - (250 + 900) / 2 / 900))


def test_page_wait_ms_per_tree_sums_waits_inside_rounds():
    host = ROUNDS + [(10 * MS, 40 * MS, "pipeline.wait"), (700 * MS, 710 * MS, "pipeline.wait"),
                     (520 * MS, 580 * MS, "pipeline.wait"),  # between rounds
                     (10 * MS, 90 * MS, "pipeline.fetch")]
    red = reduced([(0, 1 * MS)], host)
    assert read("page_wait_ms_per_tree", red) == pytest.approx((30 + 10) / 2)


def test_bin_ms_per_request_is_the_mean_binning_time():
    host = [(0, 300 * MS, "serve.request"), (10 * MS, 40 * MS, "serve.bin"),
            (400 * MS, 700 * MS, "serve.request"), (410 * MS, 460 * MS, "serve.bin")]
    assert read("bin_ms_per_request", reduced([(0, 1 * MS)], host)) == pytest.approx(40.0)


def test_readers_find_nothing_in_a_program_without_spans():
    # the Python tracer's frames alone, as a program without spans leaves them
    red = reduced([(100 * MS, 200 * MS)], [(0, 900 * MS, "booster.py:300 fit"),
                                           (10 * MS, 20 * MS, "$builtins isinstance")])
    for metric in ("tree_idle_share", "page_wait_ms_per_tree", "bin_ms_per_request"):
        assert read(metric, red) is None, metric
    assert spans.idle_by_span(red) == [[spans.NO_SPAN, pytest.approx(0.9), 0.0]]


def test_idle_by_span_puts_each_gap_on_the_innermost_span():
    host = [
        (0, 1000 * MS, "gbdt.fit"),
        (100 * MS, 900 * MS, "gbdt.round"),
        (100 * MS, 400 * MS, "gbdt.grow"),
        (150 * MS, 350 * MS, "gbdt.hist"),
        (200 * MS, 300 * MS, "pipeline.wait"),
        (180 * MS, 320 * MS, "pipeline.fetch"),  # prefetch thread: owns no gap
        (205 * MS, 210 * MS, "read"),  # a Python frame: owns no gap
        (400 * MS, 900 * MS, "gbdt.eval"),
    ]
    busy = [(40 * MS, 60 * MS),  # gap 0-40 (fit), gap 60-160 (mid 110: grow)
            (160 * MS, 190 * MS),  # gap 190-310 (mid 250: wait, fetch over 190-310 -> 120)
            (310 * MS, 820 * MS)]  # gap 820-1000 (mid 910: after the round, so fit)
    red = reduced(busy, host)
    rows = {n: (s, f) for n, s, f in spans.idle_by_span(red)}
    assert rows == {
        "gbdt.fit": (pytest.approx(0.22), pytest.approx(0.0)),
        "pipeline.wait": (pytest.approx(0.12), pytest.approx(0.12)),
        "gbdt.grow": (pytest.approx(0.1), pytest.approx(0.0)),
    }
    assert sum(s for s, _ in rows.values()) == pytest.approx(1.0 - 0.02 - 0.03 - 0.51)
    # inside the round only: the gaps at mids 110 and 250
    within = {n: s for n, s, _ in spans.idle_by_span(red, within="gbdt.round")}
    assert within == {"pipeline.wait": pytest.approx(0.12), "gbdt.grow": pytest.approx(0.1)}


def test_idle_by_span_averages_over_devices():
    host = [(0, 1000 * MS, "serve.request"), (0, 500 * MS, "serve.bin")]
    red = reduced([[(500 * MS, 1000 * MS)], [(0, 1000 * MS)]], host)
    assert spans.idle_by_span(red) == [["serve.bin", pytest.approx(0.25), 0.0]]


def test_span_names_are_the_programs():
    from repro import tracing

    names = {v for k, v in vars(tracing).items() if k.isupper() and isinstance(v, str)}
    assert set(spans.MAIN_THREAD) == names - {tracing.PAGE_FETCH}
    assert (spans.ROUND, spans.PAGE_WAIT, spans.PAGE_FETCH, spans.REQUEST, spans.BIN) == (
        tracing.ROUND, tracing.PAGE_WAIT, tracing.PAGE_FETCH, tracing.REQUEST, tracing.BIN)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A one-tree streaming fit (8192 rows in 4 pages on disk, depth 8) inside
    the benchmark's window, recorded with the program's spans."""
    import gzip
    from pathlib import Path

    from bench import trace

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    src = Path(__file__).resolve().parent / "data" / "higgs.train.streaming.xplane.pb.gz"
    path.write_bytes(gzip.decompress(src.read_bytes()))
    return trace.reduce_trace(str(path))


def test_readers_on_a_recorded_streaming_fit(recorded):
    from bench import trace

    names = [n for _, _, n in recorded["host_spans"]]
    assert names.count("gbdt.round") == 1
    assert names.count("gbdt.level") == 8
    # a histogram and a partition pass per level, 4 pages each
    for name in ("pipeline.wait", "pipeline.stage", "pipeline.fetch"):
        assert names.count(name) == 2 * 8 * 4, name
    assert recorded["window_s"] == pytest.approx(0.658369863, abs=1e-9)
    assert read("tree_idle_share", recorded) == pytest.approx(98.5567810037945, abs=1e-9)
    assert read("tree_idle_share", recorded) <= 100 * trace.idle_share(recorded)
    assert read("page_wait_ms_per_tree", recorded) == pytest.approx(45.581347, abs=1e-6)
    assert read("bin_ms_per_request", recorded) is None
    rows = spans.idle_by_span(recorded)
    assert sum(s for _, s, _ in rows) == pytest.approx(
        recorded["window_s"] - trace.busy_s(recorded), rel=1e-9)
    owners = {n: (s, f) for n, s, f in rows}
    assert owners["pipeline.wait"] == (pytest.approx(0.096318206, abs=1e-9),
                                       pytest.approx(0.070823088, abs=1e-9))
    assert owners["gbdt.split"][0] == pytest.approx(0.336243847, abs=1e-9)
    # inside the round every idle second has an owner below it
    within = {n for n, _, _ in spans.idle_by_span(recorded, within="gbdt.round")}
    assert within.isdisjoint({"gbdt.round", "gbdt.fit", spans.NO_SPAN})
