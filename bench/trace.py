"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

* busy time per device: the union of the intervals in which an operation ran
  on it, inside the traced window;
* the window: the host span named ``WINDOW`` that the benchmark opens around
  the traced work (else the first to the last device operation);
* device time per operation name, and per kernel by name;
* idle gaps inside the window, each put down to what the host's main thread
  was doing at its midpoint (the innermost Python frame the profiler's
  Python tracer recorded there).

Device planes are those named ``/device:TPU:<n>``; their operations are the
events of the ``XLA Ops`` line.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _names(event) -> list[str]:
    """The event's name and the string values of its stats (an operation's
    HLO name, its long name, the kernel it runs)."""
    out = [event.name]
    for _, value in event.stats:
        if isinstance(value, str):
            out.append(value)
    return out


def op_name(hlo: str) -> str:
    """An operation's instruction name without its numbering
    (``%build_histogram_slab.1 = (...) custom-call(...)`` -> ``build_histogram_slab``)."""
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].lstrip("%"))


def reduce_trace(path: str) -> dict:
    """Everything the per-layer readers need from one trace, in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host_spans: list[tuple[float, float, str]] = []
    window = None
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [(e.start_ns, e.start_ns + e.duration_ns, _names(e))
                                        for ln in plane.lines if ln.name == OPS_LINE
                                        for e in ln.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif line.name.startswith("python"):
                        host_spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    devices = {d: ev for d, ev in devices.items() if ev}
    if not devices:
        raise ValueError(f"no device operations in {path}")
    if window is None:
        starts = [s for ev in devices.values() for s, _, _ in ev]
        ends = [e for ev in devices.values() for _, e, _ in ev]
        window = (min(starts), max(ends))
    lo, hi = window
    per_device = {}
    for d, events in sorted(devices.items()):
        inside = [(max(s, lo), min(e, hi), names) for s, e, names in events if e > lo and s < hi]
        busy = _union([(s, e) for s, e, _ in inside])
        ops: dict[str, float] = defaultdict(float)
        for s, e, names in inside:
            ops[op_name(names[0])] += (e - s) * 1e-9
        per_device[d] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "busy": busy,
            "events": inside,
            "ops": dict(ops),
        }
    return {
        "window_s": (hi - lo) * 1e-9,
        "window": window,
        "devices": per_device,
        "host_spans": sorted(host_spans),
    }


def busy_s(red: dict) -> float:
    """Busy seconds, averaged over the devices in the trace."""
    devs = red["devices"].values()
    return sum(d["busy_s"] for d in devs) / len(devs)


def idle_share(red: dict) -> float:
    return 1.0 - busy_s(red) / red["window_s"]


def op_seconds(red: dict, pattern: re.Pattern) -> float:
    """Device seconds of operations any of whose names match ``pattern``, on
    the busiest device."""
    return max((sum(e - s for s, e, names in info["events"]
                    if any(pattern.search(n) for n in names)) * 1e-9
                for info in red["devices"].values()), default=0.0)


def top_ops(red: dict, k: int = 10) -> list[list]:
    """The k operation names (`op_name`) that took most device time, summed
    over devices."""
    agg: dict[str, float] = defaultdict(float)
    for info in red["devices"].values():
        for name, s in info["ops"].items():
            agg[name] += s
    return [[n, s] for n, s in sorted(agg.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(red: dict, k: int = 10) -> list[list]:
    """Idle seconds inside the window (first device), summed by the innermost
    host Python frame at each gap's midpoint; the k largest."""
    lo, hi = red["window"]
    busy = next(iter(red["devices"].values()))["busy"]
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    spans = red["host_spans"]
    starts = [s for s, _, _ in spans]
    agg: dict[str, float] = defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        # innermost: of the spans that cover the midpoint, the one that started last
        i = bisect.bisect_right(starts, mid)
        name = next((n for s, e, n in reversed(spans[max(0, i - 4096):i]) if e >= mid),
                    "no host span")
        agg[name] += (b - a) * 1e-9
    return [[n, s] for n, s in sorted(agg.items(), key=lambda kv: -kv[1])[:k]]
