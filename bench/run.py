"""One run of one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file and a
traffic file (``bench/traffic/<traffic>.json``); the traffic names the mode
driver (``bench/modes/<mode>.py``). A run:

1. makes its data or forest from ``--seed`` and warms up every shape the
   window uses (set-up, timed from the start of the process);
2. measures for ``--seconds`` (``--trace 0``), or traces a few steps in a run
   of its own (``--trace 1``), counts their work once the traced window has
   closed, and reads each per-layer metric with its reader
   (``bench/metrics/<metric>.py``);
3. counts the compilations inside the window and prints the count;
4. reads the peak device memory, frees the program's state, and compares
   what the window produced with the plain reference (``bench/reference``);
5. prints each compared number beside its limit, on standard error and as
   the last key of the result, the JSON object that is the last line of
   standard output.

It exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for. JAX's persistent compilation cache is ``.jax_cache/`` at
the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration", "/jax/compilation_cache/cache_hits")


class NoDevice(SystemExit):
    pass


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, cell, configuration, traffic) for workload ``name``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


class CompileCounter:
    """Compilations (XLA compiles and persistent-cache loads) while active,
    with the names JAX logs for them."""

    def __init__(self):
        import jax

        self.count = 0
        self.active = False
        self.names: list[str] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        counter = self

        class _Names(logging.Handler):
            def emit(self, record):
                message = record.getMessage()
                if counter.active and "compil" in message:
                    counter.names.append(message[:200])

        self._handler = _Names(level=logging.WARNING)

    def _event(self, event, **_):
        if self.active and event in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, event, _secs, **_):
        self._event(event)

    def __enter__(self):
        import jax

        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self._handler)
        self.active = True
        return self

    def __exit__(self, *exc):
        import jax

        self.active = False
        logging.getLogger("jax").removeHandler(self._handler)
        jax.config.update("jax_log_compiles", False)


def _select(metrics: list[dict], cell: str, reported: set[str] | None = None) -> list[dict]:
    """The metrics this cell reports: those that list it, or, without a
    ``workloads`` key, every cell (per-layer: every cell that reports the
    end-to-end metric it moves)."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m["moves"] in reported:
            out.append(m)
    return out


def run(args, require_device: bool = True) -> dict:
    spec, cell, config, traffic = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("the program (src/repro) is not in this checkout")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_device and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        raise NoDevice(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
                       f"{len(devices)} {devices[0].platform} device(s)")
    used = devices[: cell["chips"]]
    kind = used[0].device_kind

    from bench import trace as trace_lib
    from bench.metrics import load as load_metric

    mode = importlib.import_module(f"bench.modes.{traffic['mode']}")
    ctx = {"seed": args.seed, "config": config, "traffic": traffic, "cell": cell,
           "devices": used, "kind": kind, "chips": len(used)}
    counter = CompileCounter()
    state = mode.setup(ctx)
    setup_s = time.perf_counter() - T_START

    breakdown = None
    device = {"platform": used[0].platform, "kind": kind, "count": len(used)}
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
            with counter, jax.profiler.trace(tdir):
                with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
                    mode.traced(state)
            red = trace_lib.reduce_trace(trace_lib.find_xplane(tdir))
        ctx.update(trace=red, work=mode.counts(state))
        e2e = {m["name"] for m in _select(spec["end_to_end"], cell["name"])}
        metrics = {}
        for m in _select(spec["per_layer"], cell["name"], e2e):
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=trace_lib.busy_s(red), window_s=red["window_s"])
        breakdown = {"device_ops": trace_lib.top_ops(red), "idle_gaps": trace_lib.idle_gaps(red)}
    else:
        with counter:
            measured = mode.window(state, args.seconds)
        measured["setup_s"] = setup_s
        metrics = {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    device["memory_peak_bytes"] = peak
    if not args.trace:
        measured["peak_device_mb"] = peak / 1e6
        for m in _select(spec["end_to_end"], cell["name"]):
            metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    print(json.dumps({"compiles_in_window": counter.count, "compiled": counter.names[:20],
                      "setup_s": setup_s}), flush=True)

    attempted = state.attempted
    mode.release(state)
    gc.collect()
    checks = mode.check(state)
    failed = sum(1 for c in checks if not c["value"] <= c["limit"])
    result = {
        "correct": failed == 0 and not state.failed,
        "attempted": attempted,
        "failed": state.failed + failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoDevice as e:
        print(e, file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
