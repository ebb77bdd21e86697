"""GradientBooster: the single estimator surface over every training mode.

The paper's usability claim is that one estimator hides the out-of-core
machinery: the user calls ``fit`` with DMatrix-shaped data and the library
decides — via `ExecutionPolicy` and the Table-1 byte model — whether the data
trains in-core (whole ELLPACK matrix resident, Alg. 1 per round), out-of-core
(PageStream passes per tree level, Alg. 6), or out-of-core with gradient-based
sampling (compacted page, Alg. 7). All three engines live here behind one
``fit``; `repro.core.outofcore.ExternalGradientBooster` survives only as a
deprecated alias.

Sampling in-core is applied as a gradient mask — numerically identical to
compact-and-build (the histogram only sees sampled rows' gradients) while
keeping shapes static.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import objectives as obj_lib
from repro.core.histcache import HistogramStore
from repro.core.policy import ExecutionDecision, ExecutionPolicy, sampling_requested
from repro.core.quantile import HistogramCuts
from repro.core.sampling import SamplingConfig, sample
from repro.core.split import SplitParams
from repro.core.tree import (
    TreeArrays,
    TreeBuildResult,
    TreeParams,
    grow_tree,
    predict_tree_bins,
    stack_trees,
)
from repro.data.pages import TransferStats, fsync_dir
from repro.tracing import span

Array = jax.Array


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed its manifest validation — never load garbage.

    Names the damaged file and, when one survives, the last-good checkpoint
    (``<path>.prev``, kept by the atomic `GradientBooster.save` rename) so
    the operator can resume from it: ``GradientBooster.resume(err.last_good,
    data)``.
    """

    def __init__(self, path: str, bad_file: str, reason: str, last_good: str | None):
        self.path = path
        self.bad_file = bad_file
        self.last_good = last_good
        hint = (
            f"last-good checkpoint: {last_good!r} — resume from it"
            if last_good
            else "no intact previous checkpoint found"
        )
        super().__init__(
            f"checkpoint {path!r} is corrupt: {bad_file} {reason}. {hint}."
        )


@dataclasses.dataclass
class BoosterParams:
    """Model hyperparameters — the single validated config surface.

    Execution concerns (mode selection, memory budget, streaming depths,
    checkpoint cadence) live on `ExecutionPolicy`; data concerns (cuts,
    paging, cache_dir) live on the `DMatrix`. `tree_params()` is the one
    place a `TreeParams` is derived from booster config.
    """

    n_estimators: int = 100
    learning_rate: float = 0.3
    max_depth: int = 6
    reg_lambda: float = 1.0
    gamma: float = 0.0
    min_child_weight: float = 1.0
    max_bin: int = 256
    objective: str = "reg:squarederror"
    sampling: SamplingConfig = dataclasses.field(default_factory=SamplingConfig)
    base_score: float | None = None
    seed: int = 0
    kernel_impl: str = "auto"  # auto | pallas | ref
    early_stopping_rounds: int | None = None
    # histogram subtraction trick: per level, build only the smaller child of
    # each split pair and derive the sibling as parent - built (see
    # core/histcache.py); False forces the full per-node build
    hist_subtraction: bool = True
    # "depthwise" (paper Alg. 1) or "lossguide" (LightGBM-style best-first:
    # gain-ordered frontier, up to max_leaves leaves, still depth-capped by
    # max_depth); max_leaves=0 means up to the 2^max_depth complete tree
    grow_policy: str = "depthwise"
    max_leaves: int = 0
    # lossguide only: number of frontier leaves popped per histogram pass.
    # 1 reproduces strict best-first growth; >1 amortises one partition pass
    # and one (paged) data sweep over several splits, at the cost of not
    # re-ranking against children created inside the same batch (identical
    # trees when the leaf budget is not binding)
    pop_batch: int = 1

    def __post_init__(self) -> None:
        if self.n_estimators < 1:
            raise ValueError(f"n_estimators must be >= 1; got {self.n_estimators}")
        if self.max_depth < 1:
            raise ValueError(f"max_depth must be >= 1; got {self.max_depth}")
        if self.learning_rate <= 0.0:
            raise ValueError(f"learning_rate must be > 0; got {self.learning_rate}")
        if self.max_bin < 2:
            raise ValueError(f"max_bin must be >= 2; got {self.max_bin}")
        if self.grow_policy not in ("depthwise", "lossguide"):
            raise ValueError(
                f"grow_policy must be 'depthwise' or 'lossguide'; got {self.grow_policy!r}"
            )
        if self.max_leaves < 0:
            raise ValueError(f"max_leaves must be >= 0; got {self.max_leaves}")
        if self.pop_batch < 1:
            raise ValueError(f"pop_batch must be >= 1; got {self.pop_batch}")
        if self.kernel_impl not in ("auto", "pallas", "ref"):
            raise ValueError(
                f"kernel_impl must be 'auto', 'pallas', or 'ref'; got {self.kernel_impl!r}"
            )
        if self.early_stopping_rounds is not None and self.early_stopping_rounds < 1:
            raise ValueError(
                f"early_stopping_rounds must be >= 1 or None; got {self.early_stopping_rounds}"
            )

    def tree_params(self) -> TreeParams:
        return TreeParams(
            max_depth=self.max_depth,
            split=SplitParams(
                reg_lambda=self.reg_lambda,
                gamma=self.gamma,
                min_child_weight=self.min_child_weight,
            ),
            hist_subtraction=self.hist_subtraction,
            grow_policy=self.grow_policy,
            max_leaves=self.max_leaves,
            pop_batch=self.pop_batch,
        )


def bin_valid_from_cuts(cuts: HistogramCuts, n_bins: int) -> jnp.ndarray:
    nbf = cuts.n_bins_per_feature
    mask = np.zeros((cuts.num_features, n_bins), dtype=bool)
    for f, k in enumerate(nbf):
        mask[f, : int(k)] = True
    return jnp.asarray(mask)


@dataclasses.dataclass
class EvalRecord:
    iteration: int
    metric: str
    value: float
    elapsed_s: float


class GradientBooster:
    """XGBoost-like estimator over the JAX tree builder, every training mode.

    ``fit`` accepts a `DMatrix` (ArrayDMatrix / IterDMatrix / PagedDMatrix),
    raw ``(X, y)`` ndarrays, or a batch source; the `ExecutionPolicy` decides
    in-core vs out-of-core vs sampled against the memory budget. The chosen
    `ExecutionDecision` is recorded on ``self.decision_``.
    """

    def __init__(
        self,
        params: BoosterParams | None = None,
        *,
        policy: ExecutionPolicy | None = None,
        **kwargs,
    ):
        if params is None:
            params = BoosterParams(**kwargs)
        elif kwargs:
            params = dataclasses.replace(params, **kwargs)
        self.params = params
        self.policy = policy if policy is not None else ExecutionPolicy()
        self.objective = obj_lib.get_objective(params.objective)
        self.trees: list[TreeArrays] = []
        self.cuts: HistogramCuts | None = None
        self.base_margin_: float = 0.0
        self.eval_history: list[EvalRecord] = []
        # build-vs-derive ledger accumulated over every tree of the last fit;
        # the policy's hist_budget_bytes / hist_retained_levels knobs make
        # this the tiered store (cold histograms spill to host)
        self.hist_cache = self._make_hist_store()
        self._rng = jax.random.PRNGKey(params.seed)
        self.decision_: ExecutionDecision | None = None
        # external-mode state (filled when the decision routes off-device)
        self.pages = None  # PageSet of the last external fit
        self.stats = None  # its TransferStats
        self.labels_: np.ndarray | None = None
        self.margins_: np.ndarray | None = None
        self._device_cache = None
        self._packed_forest = None  # serving-tier cache (see packed_forest)

    def _make_hist_store(self, transfer_stats=None) -> HistogramStore:
        """Fresh tiered histogram store wired to this booster's policy knobs.
        ``transfer_stats`` shares the spill/fetch ledger with page traffic
        (external fits pass the page set's stats)."""
        return HistogramStore(
            enabled=self.params.hist_subtraction,
            budget_bytes=self.policy.hist_budget_bytes,
            retained_levels=self.policy.hist_retained_levels,
            transfer_stats=transfer_stats,
            retry=self.policy.retry,
            grad_transport=self.policy.grad_transport,
        )

    # ---------------------------------------------------------- sklearn compat
    def get_params(self, deep: bool = True) -> dict:
        """Flat `BoosterParams` fields + ``policy``, sklearn-style.

        ``deep=True`` additionally flattens the nested dataclasses with the
        double-underscore convention (``sampling__f``, ``policy__mode``) so
        grid search can address them; ``deep=False`` returns exactly the
        kwargs that reconstruct this estimator — ``clone()`` semantics.
        """
        out = {f.name: getattr(self.params, f.name) for f in dataclasses.fields(BoosterParams)}
        out["policy"] = self.policy
        if deep:
            for fld in dataclasses.fields(SamplingConfig):
                out[f"sampling__{fld.name}"] = getattr(self.params.sampling, fld.name)
            for fld in dataclasses.fields(ExecutionPolicy):
                out[f"policy__{fld.name}"] = getattr(self.policy, fld.name)
        return out

    def set_params(self, **updates) -> "GradientBooster":
        """sklearn-style parameter update; accepts the same keys `get_params`
        emits (flat fields, ``policy``, and ``sampling__*`` / ``policy__*``)."""
        field_names = {f.name for f in dataclasses.fields(BoosterParams)}
        flat: dict = {}
        nested: dict[str, dict] = {"sampling": {}, "policy": {}}
        for key, val in updates.items():
            if key == "policy":
                self.policy = val
            elif "__" in key:
                head, _, tail = key.partition("__")
                if head not in nested:
                    raise ValueError(
                        f"invalid nested parameter {key!r}; nestable prefixes are "
                        "'sampling__' and 'policy__'"
                    )
                nested[head][tail] = val
            elif key in field_names:
                flat[key] = val
            else:
                raise ValueError(
                    f"invalid parameter {key!r} for GradientBooster; valid "
                    f"parameters are {sorted(field_names | {'policy'})}"
                )
        if nested["sampling"]:
            flat["sampling"] = dataclasses.replace(
                flat.get("sampling", self.params.sampling), **nested["sampling"]
            )
        if flat:
            self.params = dataclasses.replace(self.params, **flat)
        if nested["policy"]:
            self.policy = dataclasses.replace(self.policy, **nested["policy"])
        self.objective = obj_lib.get_objective(self.params.objective)
        self.hist_cache = self._make_hist_store()
        self._rng = jax.random.PRNGKey(self.params.seed)
        return self

    # ------------------------------------------------------------------ fit
    def fit(
        self,
        data,
        y: np.ndarray | None = None,
        *,
        eval_set: tuple[np.ndarray, np.ndarray] | None = None,
        eval_metric: str = "auto",
        verbose: bool = False,
        cuts: HistogramCuts | None = None,
        start_iteration: int = 0,
    ) -> "GradientBooster":
        """Train on a DMatrix, raw arrays, or a batch source.

        The `ExecutionPolicy` picks the engine; the decision (mode, sampling
        fraction, byte model, reason) lands on ``self.decision_``.
        """
        from repro.data.dmatrix import as_dmatrix

        p = self.params
        self._packed_forest = None  # forest is about to change
        with span(tracing.FIT):
            dm = as_dmatrix(data, y, max_bin=p.max_bin, cuts=cuts)
            decision = self.policy.decide(dm, p)
            self.decision_ = decision
            self.cuts = dm.cuts
            if decision.mode == "in_core":
                return self._fit_in_core(dm, eval_set, eval_metric, verbose, start_iteration)
            return self._fit_external(
                dm, decision, eval_set, eval_metric, verbose, start_iteration
            )

    # ------------------------------------------------------- in-core engine
    def _fit_in_core(
        self, dm, eval_set, eval_metric, verbose, start_iteration=0
    ) -> "GradientBooster":
        p = self.params
        if start_iteration and len(self.trees) != start_iteration:
            raise ValueError(
                f"start_iteration={start_iteration} but the booster holds "
                f"{len(self.trees)} trees; resume with start_iteration == len(trees)"
            )
        with span(tracing.PREPARE):
            if start_iteration == 0:
                # fresh ledger: stats cover exactly this fit() call; in-core fits
                # get their own TransferStats so histogram spill/fetch traffic is
                # still observable (self.stats)
                self.stats = TransferStats()
                self.hist_cache = self._make_hist_store(self.stats)
            else:
                # resumed boosting keeps the store (and its accumulated ledger)
                # but must not record into a detached private sink
                if self.stats is None:
                    self.stats = TransferStats()
                self.hist_cache.transfer_stats = self.stats
            labels = dm.require_labels()
            n_bins = dm.n_bins
            bin_valid = bin_valid_from_cuts(dm.cuts, n_bins)
            bins = self._stage_in_core(dm.single_page_bins())
            labels_j = jnp.asarray(labels)

            if start_iteration == 0:
                self.base_margin_ = (
                    p.base_score if p.base_score is not None else self.objective.base_margin(labels)
                )
            margin = jnp.full(labels.shape[0], self.base_margin_, jnp.float32)
            for tree in self.trees:  # resumed run: replay the restored forest
                margin = margin + p.learning_rate * predict_tree_bins(tree, bins, p.max_depth)

            eval_bins = eval_labels = None
            eval_margin = None
            if eval_set is not None:
                from repro.core.ellpack import bin_batch

                eval_bins = jnp.asarray(bin_batch(eval_set[0], dm.cuts).astype(np.int32))
                eval_labels = np.asarray(eval_set[1], dtype=np.float32)
                eval_margin = jnp.full(eval_labels.shape[0], self.base_margin_, jnp.float32)
                for tree in self.trees:
                    eval_margin = eval_margin + p.learning_rate * predict_tree_bins(
                        tree, eval_bins, p.max_depth
                    )
            metric_name = self._metric_name(eval_metric)

        tp = p.tree_params()
        t0 = time.perf_counter()
        best_metric, best_iter = None, -1
        for it in range(start_iteration, p.n_estimators):
            with span(tracing.ROUND, round=it):
                with span(tracing.GRAD, round=it):
                    g, h = self.objective.grad_hess(margin, labels_j)
                    self._rng, k = jax.random.split(self._rng)
                    mask, w = sample(k, g, h, p.sampling)
                    scale = jnp.where(mask, w, 0.0)
                with span(tracing.GROW, round=it):
                    res = grow_tree(
                        bins,
                        g * scale,
                        h * scale,
                        n_bins,
                        bin_valid,
                        tp,
                        cut_values=dm.cuts.values,
                        cut_ptrs=dm.cuts.ptrs,
                        impl=p.kernel_impl,
                        hist_cache=self.hist_cache,
                    )
                self.trees.append(res.tree)
                with span(tracing.MARGINS, round=it):
                    margin = margin + p.learning_rate * res.tree.leaf_value[res.positions]
                if eval_bins is None:
                    continue
                with span(tracing.EVAL, round=it):
                    pred = predict_tree_bins(res.tree, eval_bins, tp.max_depth)
                    eval_margin = eval_margin + p.learning_rate * pred
                    val = self._eval(metric_name, eval_labels, eval_margin)
                    self.eval_history.append(
                        EvalRecord(it, metric_name, val, time.perf_counter() - t0)
                    )
            if verbose:
                print(f"[{it}] {metric_name}={val:.6f}")
            better = (
                best_metric is None
                or (metric_name in ("auc", "accuracy") and val > best_metric)
                or (metric_name not in ("auc", "accuracy") and val < best_metric)
            )
            if better:
                best_metric, best_iter = val, it
            elif (
                p.early_stopping_rounds
                and it - best_iter >= p.early_stopping_rounds
            ):
                break
        self.best_iteration_ = best_iter if best_iter >= 0 else len(self.trees) - 1
        return self

    def _stage_in_core(self, host_bins: np.ndarray):
        """Stage the whole quantized matrix, through the policy's page codec
        when it is device-decodable (only the packed wire payload crosses;
        the decoded int32 bins are identical either way)."""
        from repro.compress import make_transport

        transport = make_transport(self.policy.page_codec)
        if transport is None:
            bins = jnp.asarray(host_bins.astype(np.int32))
            if self.stats is not None:
                self.stats.logical_bytes += host_bins.nbytes
                self.stats.wire_bytes += host_bins.nbytes
                self.stats.host_to_device_bytes += host_bins.nbytes
            return bins
        wire, wire_meta = transport.encode(np.ascontiguousarray(host_bins))
        bins = transport.decode(jnp.asarray(wire), wire_meta)
        if self.stats is not None:
            self.stats.logical_bytes += host_bins.nbytes
            self.stats.wire_bytes += wire.nbytes
            self.stats.host_to_device_bytes += wire.nbytes
        return bins

    # ----------------------------------------------------- external engines
    def _stream(self, indices=None, staging_depth: int | None = None):
        """One `PageStream` pass over the last external fit's page set."""
        return self.pages.stream(
            prefetch_depth=self.policy.prefetch_depth,
            staging_depth=staging_depth or self.policy.staging_depth,
            cache=self._device_cache,
            indices=indices,
            retry=self.policy.retry,
            codec=self.policy.page_codec,
        )

    def _fit_external(
        self, dm, decision, eval_set, eval_metric, verbose, start_iteration
    ) -> "GradientBooster":
        from repro.core.ellpack import bin_batch
        from repro.pipeline import DevicePageCache

        p, pol = self.params, self.policy
        with span(tracing.PREPARE):
            labels = dm.require_labels()
            pages = dm.page_set()
            self.pages = pages
            self.stats = pages.stats
            # fresh ledger unless resuming mid-boosting (keep the run's totals);
            # histogram spills/fetches land in the page set's TransferStats so one
            # ledger carries all device-boundary traffic — resumed stores are
            # rewired to it (their __init__ sink is a detached placeholder)
            if start_iteration == 0:
                self.hist_cache = self._make_hist_store(pages.stats)
            else:
                self.hist_cache.transfer_stats = pages.stats
            self.labels_ = labels
            n_bins = dm.n_bins
            bin_valid = bin_valid_from_cuts(dm.cuts, n_bins)
            labels_j = jnp.asarray(labels)

            if self.margins_ is None:
                self.base_margin_ = (
                    p.base_score if p.base_score is not None else self.objective.base_margin(labels)
                )
                self.margins_ = np.full(pages.n_rows, self.base_margin_, np.float32)

            eval_bins = eval_labels = eval_margin = None
            if eval_set is not None:
                eval_bins = jnp.asarray(bin_batch(eval_set[0], dm.cuts).astype(np.int32))
                eval_labels = np.asarray(eval_set[1], np.float32)
                eval_margin = jnp.full(eval_labels.shape[0], self.base_margin_, jnp.float32)
                md = p.max_depth
                for t in self.trees:  # resumed run: rebuild eval margins
                    pred = predict_tree_bins(t, eval_bins, md)
                    eval_margin = eval_margin + p.learning_rate * pred
            metric_name = self._metric_name(eval_metric)

        tp = p.tree_params()
        use_sampling = decision.mode == "sampled"
        sampling_cfg = p.sampling
        if use_sampling and not sampling_requested(p.sampling):
            # policy-chosen fraction: the paper's MVS default at the largest
            # f whose compacted page fits the budget
            sampling_cfg = SamplingConfig(method="mvs", f=decision.sampling_f or 0.5)
        cache_pages = pol.device_cache_pages
        if cache_pages is None:
            # auto: cache only when the whole page set fits (a sequential LRU
            # scan over more pages than capacity evicts every page right
            # before its reuse — zero hits), and only on the f<1 fast path
            # where pages are revisited once per iteration.
            fits = pages.n_pages <= 8
            cache_pages = pages.n_pages if (use_sampling and fits) else 0
        self._device_cache = DevicePageCache(cache_pages) if cache_pages > 0 else None
        t0 = time.perf_counter()
        for it in range(start_iteration, p.n_estimators):
            with span(tracing.ROUND, round=it):
                with span(tracing.GRAD, round=it):
                    g, h = self.objective.grad_hess(jnp.asarray(self.margins_), labels_j)
                    self._rng, k = jax.random.split(self._rng)
                with span(tracing.GROW, round=it):
                    if use_sampling:
                        res = self._build_tree_sampled(
                            k, g, h, n_bins, bin_valid, tp, dm.cuts, sampling_cfg
                        )
                    else:
                        res = self._build_tree_streaming(g, h, n_bins, bin_valid, tp, dm.cuts)
                self.trees.append(res.tree)
                with span(tracing.MARGINS, round=it):
                    self._update_margins(res, tp)
                if eval_bins is not None:
                    with span(tracing.EVAL, round=it):
                        pred = predict_tree_bins(res.tree, eval_bins, tp.max_depth)
                        eval_margin = eval_margin + p.learning_rate * pred
                        val = self._eval(metric_name, eval_labels, eval_margin)
                        self.eval_history.append(
                            EvalRecord(it, metric_name, val, time.perf_counter() - t0)
                        )
            if verbose and eval_bins is not None:
                print(f"[{it}] {metric_name}={val:.6f}")
            if (
                pol.checkpoint_every
                and pol.checkpoint_dir
                and (it + 1) % pol.checkpoint_every == 0
            ):
                self.save(pol.checkpoint_dir)
        return self

    # -------------------------------------------------- Alg. 7 (sampled path)
    def _sampled_capacity(self, n_rows: int, sampling_cfg: SamplingConfig) -> int:
        """Static compacted-page capacity: keeps jit shapes stable across
        iterations (Bernoulli sampling varies the kept count slightly)."""
        f = sampling_cfg.f if sampling_cfg.method != "goss" else (
            sampling_cfg.goss_a + sampling_cfg.goss_b
        )
        cap = int(n_rows * min(1.0, f * 1.25)) + 256
        return min(n_rows, -(-cap // 1024) * 1024)

    def _build_tree_sampled(
        self, key, g, h, n_bins, bin_valid, tp, cuts, sampling_cfg
    ) -> TreeBuildResult:
        p = self.params
        mask, w = sample(key, g, h, sampling_cfg)
        mask_np = np.asarray(mask)
        sel = np.nonzero(mask_np)[0]
        capacity = self._sampled_capacity(self.pages.n_rows, sampling_cfg)
        if len(sel) > capacity:  # extreme tail: drop lowest-weight extras
            sel = sel[:capacity]
        gw = np.asarray(g * w)
        hw = np.asarray(h * w)

        # Compact: gather sampled rows from every page into one device page
        # (host-side pass: the prefetcher overlaps disk reads, nothing staged)
        chunks: list[np.ndarray] = []
        for _, page in self._stream().iter_host():
            lo = np.searchsorted(sel, page.row_offset, side="left")
            hi = np.searchsorted(sel, page.row_offset + page.n_rows, side="left")
            if hi > lo:
                local = sel[lo:hi] - page.row_offset
                chunks.append(page.bins[local])
        bins_np = np.concatenate(chunks, axis=0) if chunks else np.zeros(
            (0, self.pages.num_features), np.uint8
        )
        pad = capacity - bins_np.shape[0]
        g_np = np.zeros(capacity, np.float32)
        h_np = np.zeros(capacity, np.float32)
        g_np[: len(sel)] = gw[sel]
        h_np[: len(sel)] = hw[sel]
        if pad:  # zero-gradient padding rows: no histogram contribution
            bins_np = np.concatenate(
                [bins_np, np.zeros((pad, bins_np.shape[1]), np.uint8)], axis=0
            )
        from repro.core.ellpack import EllpackPage

        staged = EllpackPage(bins_np, 0)
        bins_c = self.pages.stage(staged, codec=self.policy.page_codec)
        res = grow_tree(
            bins_c, jnp.asarray(g_np), jnp.asarray(h_np), n_bins, bin_valid, tp,
            cut_values=cuts.values, cut_ptrs=cuts.ptrs,
            impl=p.kernel_impl, hist_cache=self.hist_cache,
        )
        # positions only cover sampled rows -> margin update must stream pages
        return TreeBuildResult(tree=res.tree, positions=None)

    # ----------------------------------------------- Alg. 6 (streaming path)
    def _build_tree_streaming(self, g, h, n_bins, bin_valid, tp, cuts) -> TreeBuildResult:
        from repro.core.outofcore import build_tree_paged

        extents = self.pages.page_extents
        tree, positions = build_tree_paged(
            self._stream, extents, g, h, n_bins, bin_valid, tp,
            cuts.values, cuts.ptrs, impl=self.params.kernel_impl,
            hist_cache=self.hist_cache, page_skipping=self.policy.page_skipping,
        )
        # final positions point at leaves: margin update without re-streaming
        # (pages cover the rows in order; `_update_margins` copies them back)
        return TreeBuildResult(
            tree=tree, positions=jnp.concatenate([positions[i] for i in range(len(extents))])
        )

    # -------------------------------------------------------- margin update
    def _update_margins(self, res: TreeBuildResult, tp) -> None:
        lr = self.params.learning_rate
        if res.positions is not None:  # streaming path: positions are leaves
            leaf = np.asarray(res.tree.leaf_value)
            self.margins_ += lr * leaf[np.asarray(res.positions)]
            return
        for sp in self._stream():
            pred = predict_tree_bins(res.tree, sp.device, tp.max_depth)
            sl = slice(sp.host.row_offset, sp.host.row_offset + sp.host.n_rows)
            self.margins_[sl] += lr * np.asarray(pred)

    # ------------------------------------------------------------------ misc
    def _metric_name(self, eval_metric: str) -> str:
        if eval_metric != "auto":
            return eval_metric
        return "auc" if self.objective.name == "binary:logistic" else "rmse"

    def _eval(self, metric: str, labels: np.ndarray, margin: Array) -> float:
        preds = np.asarray(self.objective.transform(margin))
        if metric == "rmse":
            return obj_lib.rmse(labels, preds)
        return obj_lib.METRICS[metric](labels, preds)

    # -------------------------------------------------------------- predict
    def packed_forest(self, iteration_range: tuple[int, int] | None = None):
        """The serving-tier view of this forest (`repro.serve.PackedForest`):
        flat (T, n_total) arrays predicted by one fused launch. Cached per
        forest length; explicit ``iteration_range`` packs fresh."""
        from repro.serve.forest import PackedForest

        if iteration_range is not None:
            return PackedForest.from_booster(self, iteration_range)
        if self._packed_forest is None or self._packed_forest.n_trees != len(self.trees):
            self._packed_forest = PackedForest.from_booster(self)
        return self._packed_forest

    def predict_margin(
        self, X, iteration_range: tuple[int, int] | None = None
    ) -> np.ndarray:
        """Margins via the fused serving tier — the front door mirrors ``fit``:
        raw ndarrays predict in one whole-forest launch; a DMatrix streams its
        ELLPACK pages through `PageStream` (out-of-core prediction). Both are
        bit-for-bit the per-tree reference loop
        (`PackedForest.predict_margin_per_tree`)."""
        from repro.core.ellpack import bin_batch

        assert self.cuts is not None, "not fitted"
        forest = self.packed_forest(iteration_range)
        impl = self.params.kernel_impl
        if hasattr(X, "page_set"):  # DMatrix: the streaming serving path
            from repro.serve.engine import predict_margin_dmatrix

            return predict_margin_dmatrix(
                forest, X, impl=impl, page_codec=self.policy.page_codec
            )
        bins = jnp.asarray(bin_batch(np.asarray(X), self.cuts).astype(np.int32))
        return np.asarray(forest.predict_margin_bins(bins, impl=impl))

    def predict(self, X, output_margin: bool = False) -> np.ndarray:
        """Predictions for raw feature rows or any DMatrix (mirrors ``fit``)."""
        margin = self.predict_margin(X)
        if output_margin:
            return margin
        return np.asarray(self.objective.transform(jnp.asarray(margin)))

    # ----------------------------------------------------------- checkpoint
    def save(self, path: str) -> None:
        """Checkpoint the forest + quantization state — atomically, durably.

        Files are written to a temp sibling directory, fsynced, and renamed
        into place; the previous checkpoint survives one generation as
        ``<path>.prev`` (the last-good fallback `CheckpointCorruptError`
        names). A ``manifest.json`` records each file's CRC32, validated by
        ``load`` — a crash at any point leaves either the old checkpoint or
        the new one, never a torn mix the next resume would trust.
        """
        assert self.cuts is not None
        forest = stack_trees(self.trees) if self.trees else None
        arrays = {}
        if forest is not None:
            arrays = {f: np.asarray(getattr(forest, f)) for f in forest._fields}
        meta = dataclasses.asdict(self.params)
        meta["sampling"] = dataclasses.asdict(self.params.sampling)
        meta["base_margin_"] = self.base_margin_
        meta["n_trees"] = len(self.trees)

        parent = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(parent, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        try:
            np.savez_compressed(
                os.path.join(tmp, "model.npz"),
                cut_values=self.cuts.values,
                cut_ptrs=self.cuts.ptrs,
                cut_min_vals=self.cuts.min_vals,
                rng=np.asarray(self._rng),
                **{f"tree_{k}": v for k, v in arrays.items()},
            )
            with open(os.path.join(tmp, "booster.json"), "w") as fh:
                json.dump(meta, fh, indent=2)
            manifest = {"format": 1, "files": {}}
            for name in ("model.npz", "booster.json"):
                with open(os.path.join(tmp, name), "rb") as fh:
                    blob = fh.read()
                manifest["files"][name] = {"crc32": zlib.crc32(blob), "bytes": len(blob)}
            with open(os.path.join(tmp, "manifest.json"), "w") as fh:
                json.dump(manifest, fh, indent=2)
            for name in ("model.npz", "booster.json", "manifest.json"):
                with open(os.path.join(tmp, name), "rb") as fh:
                    os.fsync(fh.fileno())
            fsync_dir(tmp)
            prev = f"{path}.prev"
            rotated = False
            if os.path.isdir(path):
                # keep exactly one last-good generation
                shutil.rmtree(prev, ignore_errors=True)
                os.replace(path, prev)
                rotated = True
            try:
                os.replace(tmp, path)
            except BaseException:
                if rotated:
                    # publish failed after rotation: put the live copy back so
                    # a crashed save never leaves `path` empty
                    os.replace(prev, path)
                raise
            fsync_dir(parent)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    @staticmethod
    def _checkpoint_damage(path: str) -> tuple[str, str] | None:
        """(bad_file, reason) if the checkpoint fails validation, else None.

        Pre-durability checkpoints without a ``manifest.json`` validate on
        file presence only (nothing to checksum against); missing files are
        damage either way.
        """
        manifest_path = os.path.join(path, "manifest.json")
        if not os.path.isdir(path):
            return (path, "does not exist")
        files: dict[str, dict] = {}
        if os.path.exists(manifest_path):
            try:
                with open(manifest_path) as fh:
                    files = json.load(fh)["files"]
            except (OSError, ValueError, KeyError) as err:
                return ("manifest.json", f"is unreadable ({err})")
        for name in ("booster.json", "model.npz"):
            fp = os.path.join(path, name)
            if not os.path.exists(fp):
                return (name, "is missing")
            want = files.get(name, {}).get("crc32")
            if want is None:
                continue
            with open(fp, "rb") as fh:
                got = zlib.crc32(fh.read())
            if got != want:
                return (
                    name,
                    f"failed its CRC32 check (manifest {want:#010x}, on disk {got:#010x})",
                )
        return None

    @classmethod
    def verify_checkpoint(cls, path: str) -> None:
        """Validate a checkpoint's manifest; raise `CheckpointCorruptError`
        (naming the bad file and the last-good fallback) on damage."""
        damage = cls._checkpoint_damage(path)
        if damage is None:
            return
        prev = f"{path}.prev"
        last_good = prev if cls._checkpoint_damage(prev) is None else None
        raise CheckpointCorruptError(path, damage[0], damage[1], last_good)

    @classmethod
    def last_good_checkpoint(cls, path: str) -> str | None:
        """The newest intact checkpoint among ``path`` and ``path.prev``."""
        for cand in (path, f"{path}.prev"):
            if cls._checkpoint_damage(cand) is None:
                return cand
        return None

    @classmethod
    def load(cls, path: str) -> "GradientBooster":
        cls.verify_checkpoint(path)
        with open(os.path.join(path, "booster.json")) as fh:
            meta = json.load(fh)
        base_margin = meta.pop("base_margin_")
        n_trees = meta.pop("n_trees")
        sampling = SamplingConfig(**meta.pop("sampling"))
        params = BoosterParams(sampling=sampling, **meta)
        self = cls(params)
        data = np.load(os.path.join(path, "model.npz"))
        self.cuts = HistogramCuts(
            values=data["cut_values"], ptrs=data["cut_ptrs"], min_vals=data["cut_min_vals"]
        )
        self.base_margin_ = float(base_margin)
        self._rng = jnp.asarray(data["rng"])
        if n_trees:
            fields = TreeArrays._fields
            stacked = [jnp.asarray(data[f"tree_{f}"]) for f in fields]
            self.trees = [
                TreeArrays(*[a[i] for a in stacked]) for i in range(n_trees)
            ]
        return self

    @classmethod
    def resume(
        cls,
        checkpoint_path: str,
        data,
        *,
        policy: ExecutionPolicy | None = None,
    ) -> "GradientBooster":
        """Restart external-mode training from a checkpoint.

        Reloads the forest + cuts, rebuilds the margin cache by streaming the
        data's pages (a `PagedDMatrix` reopening the original cache directory
        is the natural argument — no raw data needed), and returns a booster
        ready for ``fit(data, start_iteration=len(trees))``. The checkpointed
        cuts are authoritative: raw sources are (re)quantized WITH them, and a
        pre-built DMatrix must carry bit-identical cuts — resuming onto pages
        binned with different thresholds would silently corrupt the model, so
        that raises instead.
        """
        from repro.data.dmatrix import DMatrix, as_dmatrix

        base = cls.load(checkpoint_path)
        self = cls(base.params, policy=policy or ExecutionPolicy(mode="out_of_core"))
        self.trees = base.trees
        self.base_margin_ = base.base_margin_
        self._rng = base._rng
        if isinstance(data, DMatrix):
            dm = data
            if not (
                np.array_equal(dm.cuts.values, base.cuts.values)
                and np.array_equal(dm.cuts.ptrs, base.cuts.ptrs)
            ):
                raise ValueError(
                    "DMatrix quantization differs from the checkpoint's cuts; "
                    "its pages were binned with different thresholds than the "
                    "restored trees split on. Reopen the original page cache "
                    "(PagedDMatrix) or rebuild the DMatrix from the raw source "
                    "via resume(ckpt, source)."
                )
        else:
            # quantize the source with the checkpointed cuts (no re-sketch)
            dm = as_dmatrix(data, max_bin=base.params.max_bin, cuts=base.cuts)
        self.cuts = base.cuts
        self.pages = dm.page_set()
        self.stats = self.pages.stats
        self.margins_ = np.full(self.pages.n_rows, self.base_margin_, np.float32)
        md = self.params.max_depth
        for tree in self.trees:
            for sp in self._stream():
                pred = predict_tree_bins(tree, sp.device, md)
                sl = slice(sp.host.row_offset, sp.host.row_offset + sp.host.n_rows)
                self.margins_[sl] += self.params.learning_rate * np.asarray(pred)
        return self


def train_in_core(
    X: np.ndarray, y: np.ndarray, params: BoosterParams | None = None, **kw
) -> GradientBooster:
    return GradientBooster(params, policy=ExecutionPolicy(mode="in_core"), **kw).fit(X, y)
