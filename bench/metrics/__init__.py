"""Per-layer metric readers, one file each, found by the metric's name.

A reader exposes ``read(ctx) -> float | None``: None where the traced run
holds nothing for it to read, so the harness leaves the metric out. ``ctx``
holds ``trace`` (`bench.trace.reduce_trace`), ``work`` (what the mode's traced
run counted), ``kind`` (device kind) and ``chips``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

_DIR = Path(__file__).resolve().parent
_CACHE: dict = {}


def load(name: str):
    """The reader module of metric ``name`` (``bench/metrics/<name>.py``)."""
    if name not in _CACHE:
        path = _DIR / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
        spec = importlib.util.spec_from_file_location(f"bench.metrics._{name.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _CACHE[name] = module
    return _CACHE[name]
