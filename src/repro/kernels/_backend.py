"""Backend probing and TPU tiling helpers shared by the Pallas kernels.

The Pallas kernels take ``interpret: bool | None``. ``None`` (the default)
resolves at trace time via `resolve_interpret`: compiled on a real TPU,
interpreter everywhere else — so direct callers get correct behavior without
knowing the backend, mirroring how `ops._resolve` picks pallas-vs-ref.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True on a TPU backend. A failing probe raises: a chip that cannot be
    reached must not quietly turn into the CPU path."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Explicit value wins; None means "interpret unless on a real TPU"."""
    return (not on_tpu()) if interpret is None else interpret


LANES = 128  # a block's last dim is a multiple of this, or the full array dim


def round_up(x: int, k: int) -> int:
    return -(-x // k) * k
