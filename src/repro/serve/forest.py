"""PackedForest: a fitted forest flattened for one-launch batched prediction.

The training side keeps trees as a Python list of per-tree `TreeArrays` —
convenient to grow, terrible to serve: predicting T trees costs T kernel
dispatches plus T Python-loop iterations per batch. `PackedForest` stacks the
forest into flat (T, n_total) arrays once, stages them to the device once, and
predicts the whole forest per launch through `kernels.ops.predict_forest`
(Pallas one-hot traversal on TPU, the jit'd scan oracle elsewhere).

Accumulation is tree-ordered, so packed prediction is bit-for-bit the per-tree
reference — `predict_margin_per_tree` keeps that reference alive as the
serving oracle and the benchmark baseline.

`chunk(...)` splits the forest into tree-ranges for the paged-forest path
(models larger than the device budget; see `repro.serve.engine`), and
`pack_page`/`unpack_page` flatten a chunk into the single ndarray-per-page
shape `repro.pipeline.PageStream` stages.
"""
from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.quantile import HistogramCuts
from repro.core.tree import TreeArrays
from repro.kernels import ops
from repro.tracing import span

Array = jax.Array

# process-wide ids of scoring requests: the ``request`` of their serve.* spans
REQUEST_IDS = itertools.count()

# pack_page row layout: one f32 plane per tree-array field, in this order
_PAGE_FIELDS = ("feature", "split_bin", "split_value", "default_left", "is_leaf", "leaf_value")


@dataclasses.dataclass(frozen=True)
class PackedForest:
    """Flat-array forest: every field is (n_trees, n_total), device-resident.

    ``base_margin``/``learning_rate``/``max_depth`` travel with the arrays so
    a forest chunk is self-describing; ``cuts`` (optional) lets the forest
    quantize raw feature rows itself — the batch-serving front door.
    """

    feature: Array  # (T, n_total) int32
    split_bin: Array  # (T, n_total) int32
    split_value: Array  # (T, n_total) f32 (raw thresholds; kept for export)
    default_left: Array  # (T, n_total) bool
    is_leaf: Array  # (T, n_total) bool
    leaf_value: Array  # (T, n_total) f32
    max_depth: int
    learning_rate: float
    base_margin: float
    objective: str = "reg:squarederror"
    cuts: HistogramCuts | None = None

    # ------------------------------------------------------------ construction
    @classmethod
    def from_booster(
        cls, booster, iteration_range: tuple[int, int] | None = None
    ) -> "PackedForest":
        """Pack a fitted `GradientBooster` (or any object with ``trees``,
        ``params``, ``cuts``, ``base_margin_``) for serving."""
        if not booster.trees:
            raise ValueError("booster has no trees; fit before packing")
        lo, hi = iteration_range or (0, len(booster.trees))
        trees = booster.trees[lo:hi]
        if trees:
            stacked = {
                f: jnp.stack([getattr(t, f) for t in trees]) for f in TreeArrays._fields
            }
        else:  # empty range: a 0-tree forest predicts the base margin
            n_total = booster.trees[0].n_total
            stacked = {
                f: jnp.zeros((0, n_total), getattr(booster.trees[0], f).dtype)
                for f in TreeArrays._fields
            }
        return cls(
            max_depth=booster.params.max_depth,
            learning_rate=booster.params.learning_rate,
            base_margin=float(booster.base_margin_),
            objective=booster.params.objective,
            cuts=booster.cuts,
            **stacked,
        )

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def n_total(self) -> int:
        """Heap-layout node capacity per tree."""
        return self.feature.shape[1]

    @property
    def nbytes(self) -> int:
        """Device bytes of the packed arrays (f32/int32 staging layout)."""
        return sum(
            np.asarray(getattr(self, f)).nbytes for f in _PAGE_FIELDS
        )

    # ------------------------------------------------------------- prediction
    def predict_margin_bins(
        self, bins: Array, margin_in: Array | None = None, impl: str = "auto"
    ) -> Array:
        """Fused whole-forest margins over quantized rows (one launch)."""
        if margin_in is None:
            margin_in = jnp.full(bins.shape[0], self.base_margin, jnp.float32)
        return ops.predict_forest(
            bins, self.feature, self.split_bin, self.default_left, self.is_leaf,
            self.leaf_value, self.max_depth, self.learning_rate, margin_in, impl=impl,
        )

    def predict_margin(self, X: np.ndarray, impl: str = "auto") -> np.ndarray:
        """Raw-feature front door: quantize with the forest's cuts, then fuse."""
        request = next(REQUEST_IDS)
        with span(tracing.REQUEST, request=request):
            return self._predict_raw(X, impl, request)

    def _predict_raw(self, X: np.ndarray, impl: str, request: int) -> np.ndarray:
        """`predict_margin`'s body, inside a ``serve.request`` span the
        caller opened for ``request``: host binning, one launch, the copy back."""
        if self.cuts is None:
            raise ValueError("PackedForest has no cuts; predict from bins instead")
        from repro.core.ellpack import bin_batch

        with span(tracing.BIN, request=request):
            host_bins = bin_batch(np.asarray(X), self.cuts).astype(np.int32)
        with span(tracing.LAUNCH, request=request):
            margin = self.predict_margin_bins(jnp.asarray(host_bins), impl=impl)
        with span(tracing.FETCH, request=request):
            return np.asarray(margin)

    def predict_margin_per_tree(self, bins: Array) -> Array:
        """The per-tree reference loop the fused kernel must match bit-for-bit
        (also the benchmark's Python-dispatch baseline).

        Scales the leaf table up front (the same eager elementwise multiply
        `kernels.ops.predict_forest` performs) so the per-tree accumulation is
        a pure add — the identical f32 op sequence as the fused scan, hence
        exact equality rather than allclose.
        """
        margin = jnp.full(bins.shape[0], self.base_margin, jnp.float32)
        scaled_leaf = jnp.float32(self.learning_rate) * self.leaf_value
        for t in range(self.n_trees):
            margin = margin + ops.predict_bins(
                bins, self.feature[t], self.split_bin[t], self.default_left[t],
                self.is_leaf[t], scaled_leaf[t], self.max_depth,
            )
        return margin

    # ------------------------------------------------- paged-forest chunking
    def chunk(self, lo: int, hi: int) -> "PackedForest":
        """Trees [lo, hi) as a self-contained chunk (same metadata)."""
        sliced = {f: getattr(self, f)[lo:hi] for f in _PAGE_FIELDS}
        return dataclasses.replace(self, **sliced)

    def pack_page(self, lo: int, hi: int) -> np.ndarray:
        """Trees [lo, hi) as ONE (6, hi-lo, n_total) f32 host array — the
        single-ndarray page shape `PageStream` stages; ids/bools are exact in
        f32, so `unpack_page` round-trips bit-for-bit."""
        return np.stack(
            [np.asarray(getattr(self, f)[lo:hi], np.float32) for f in _PAGE_FIELDS]
        )

    @staticmethod
    def unpack_page(page: Array) -> dict[str, Array]:
        """Device-side inverse of `pack_page` (cheap casts under jit)."""
        return {
            "feature": page[0].astype(jnp.int32),
            "split_bin": page[1].astype(jnp.int32),
            "split_value": page[2],
            "default_left": page[3] > 0.5,
            "is_leaf": page[4] > 0.5,
            "leaf_value": page[5],
        }
