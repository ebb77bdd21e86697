"""GradientBooster: the single estimator surface over every training mode.

The paper's usability claim is that one estimator hides the out-of-core
machinery: the user calls ``fit`` with DMatrix-shaped data and the library
decides — via `ExecutionPolicy` and the Table-1 byte model — whether the data
trains in-core (whole ELLPACK matrix resident, Alg. 1 per round), out-of-core
(PageStream passes per tree level, Alg. 6), or out-of-core with gradient-based
sampling (compacted page, Alg. 7). One round loop, `GradientBooster._boost`,
trains every mode; what differs lives in a small engine (`_InCoreEngine`,
`_PagedEngine`, and `repro.distributed.fit_sharded`'s sharded engine).
"""
from __future__ import annotations

import dataclasses
import functools
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
        self.stats = None  # the last fit's TransferStats
        # out-of-core state: the last fit's PageSet and its host margins
        self.pages = None
        self.margins_: np.ndarray | None = None
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
        fraction, byte model, reason) lands on ``self.decision_``. A fit with
        ``start_iteration == len(self.trees)`` continues the forest (see
        `resume`), quantizing raw data with the forest's cuts.
        """
        from repro.data.dmatrix import as_dmatrix

        p = self.params
        self._packed_forest = None  # forest is about to change
        if cuts is None and start_iteration:
            cuts = self.cuts
        with span(tracing.FIT):
            dm = as_dmatrix(data, y, max_bin=p.max_bin, cuts=cuts)
            decision = self.policy.decide(dm, p)
            self.decision_ = decision
            self.cuts = dm.cuts
            if decision.mode == "in_core":
                engine = functools.partial(_InCoreEngine, self, dm)
            else:
                sampling = None
                if decision.mode == "sampled":
                    # the params' fraction, else the policy's: the paper's MVS
                    # default at the largest f whose compacted page fits
                    sampling = p.sampling if sampling_requested(p.sampling) else (
                        SamplingConfig(method="mvs", f=decision.sampling_f or 0.5)
                    )
                engine = functools.partial(_PagedEngine, self, dm, sampling=sampling)
            return self._boost(engine, dm, eval_set, eval_metric, verbose, start_iteration)

    def _boost(
        self, make_engine, dm, eval_set, eval_metric, verbose, start_iteration
    ) -> "GradientBooster":
        """The boosting rounds, for every engine.

        ``make_engine(labels, fresh)`` prepares an `_Engine`; the loop owns
        the rest: the base margin, the replay of a restored forest, the eval
        set, each round's key and span nest, `EvalRecord`, ``verbose``, early
        stopping and checkpoints. ``t0`` is taken after all preparation, right
        before the first round.
        """
        p, pol = self.params, self.policy
        if start_iteration and len(self.trees) != start_iteration:
            raise ValueError(
                f"start_iteration={start_iteration} but the booster holds "
                f"{len(self.trees)} trees; resume with start_iteration == len(trees)"
            )
        fresh = start_iteration == 0
        with span(tracing.PREPARE):
            labels = dm.require_labels()
            if fresh:
                self.base_margin_ = (
                    p.base_score if p.base_score is not None else self.objective.base_margin(labels)
                )
            engine = make_engine(labels, fresh)
            engine.replay(self.trees)
            if eval_set is not None:
                from repro.core.ellpack import bin_batch

                device = engine.eval_device
                eval_bins = jax.device_put(bin_batch(eval_set[0], dm.cuts).astype(np.int32), device)
                eval_labels = np.asarray(eval_set[1], np.float32)
                eval_margin = jnp.full(
                    eval_labels.shape[0], self.base_margin_, jnp.float32, device=device
                )

                def add_tree(margin, tree):
                    pred = predict_tree_bins(engine.eval_tree(tree), eval_bins, p.max_depth)
                    return margin + p.learning_rate * pred

                for tree in self.trees:
                    eval_margin = add_tree(eval_margin, tree)
            metric_name = self._metric_name(eval_metric)

        t0 = time.perf_counter()
        best_metric, best_iter = None, -1
        for it in range(start_iteration, p.n_estimators):
            with span(tracing.ROUND, round=it):
                with span(tracing.GRAD, round=it):
                    g, h = self.objective.grad_hess(engine.margin(), engine.labels)
                    self._rng, key = jax.random.split(self._rng)
                with span(tracing.GROW, round=it):
                    tree, positions = engine.grow(key, g, h)
                self.trees.append(tree)
                with span(tracing.MARGINS, round=it):
                    engine.update(tree, positions)
                if eval_set is not None:
                    with span(tracing.EVAL, round=it):
                        eval_margin = add_tree(eval_margin, tree)
                        val = self._eval(metric_name, eval_labels, eval_margin)
                        self.eval_history.append(
                            EvalRecord(it, metric_name, val, time.perf_counter() - t0)
                        )
            if pol.checkpoint_every and pol.checkpoint_dir and (it + 1) % pol.checkpoint_every == 0:
                self.save(pol.checkpoint_dir)
            if eval_set is None:
                continue
            if verbose:
                print(f"[{it}] {metric_name}={val:.6f}")
            higher = metric_name in ("auc", "accuracy")
            if best_metric is None or (val > best_metric if higher else val < best_metric):
                best_metric, best_iter = val, it
            elif p.early_stopping_rounds and it - best_iter >= p.early_stopping_rounds:
                break
        self.best_iteration_ = best_iter if best_iter >= 0 else len(self.trees) - 1
        return self

    def _use_stats(self, stats: TransferStats, fresh: bool) -> None:
        """Make ``stats`` the fit's ledger. A fresh fit gets a histogram store
        on it; a resumed one keeps its store (and the store's accumulated
        ledger), rewired from the detached sink it was built with."""
        self.stats = stats
        if fresh:
            self.hist_cache = self._make_hist_store(stats)
        else:
            self.hist_cache.transfer_stats = stats

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
        """Restart training from a checkpoint.

        Reloads the forest, cuts and key, and returns a booster ready for
        ``fit(data, start_iteration=len(trees))``, whose preparation replays
        the restored forest onto the margins (out of core by streaming the
        data's pages: a `PagedDMatrix` reopening the original cache directory
        is the natural argument, no raw data needed). The checkpointed cuts
        are authoritative: that fit quantizes raw sources WITH them, and a
        pre-built DMatrix must carry bit-identical cuts — resuming onto pages
        binned with different thresholds would silently corrupt the model, so
        that raises here instead.
        """
        from repro.data.dmatrix import DMatrix

        base = cls.load(checkpoint_path)
        if isinstance(data, DMatrix) and not (
            np.array_equal(data.cuts.values, base.cuts.values)
            and np.array_equal(data.cuts.ptrs, base.cuts.ptrs)
        ):
            raise ValueError(
                "DMatrix quantization differs from the checkpoint's cuts; "
                "its pages were binned with different thresholds than the "
                "restored trees split on. Reopen the original page cache "
                "(PagedDMatrix) or resume from the raw source, which the "
                "continued fit quantizes with the checkpoint's cuts."
            )
        self = cls(base.params, policy=policy or ExecutionPolicy(mode="out_of_core"))
        self.trees = base.trees
        self.cuts = base.cuts
        self.base_margin_ = base.base_margin_
        self._rng = base._rng
        return self


class _Engine:
    """What one training engine supplies to `GradientBooster._boost`.

    The constructor is the preparation: it stages the data, picks the fit's
    `TransferStats` sink and histogram store, and sets the margins to the
    base margin. ``margin()`` is the margins the round's gradients read;
    ``grow(key, g, h)`` grows one tree and returns ``(tree, positions)``,
    ``positions`` being every row's leaf, or None where the engine does not
    route every row; ``update(tree, positions)`` adds the tree to the
    margins, from the bins when ``positions`` is None. The eval rows live on
    ``eval_device`` (None: the default device) and are scored with
    ``eval_tree(tree)``.
    """

    eval_device = None

    def __init__(self, booster: GradientBooster, dm, tp: TreeParams | None = None):
        self.booster = booster
        self.params = booster.params
        self.tp = tp or booster.params.tree_params()
        self.cuts = dm.cuts
        self.n_bins = dm.n_bins
        self.bin_valid = bin_valid_from_cuts(dm.cuts, dm.n_bins)

    def replay(self, trees: list[TreeArrays]) -> None:
        """Add a restored forest to the margins."""
        for tree in trees:
            self.update(tree, None)

    def eval_tree(self, tree: TreeArrays) -> TreeArrays:
        return tree


class _InCoreEngine(_Engine):
    """The whole quantized matrix resident on the default device, Alg. 1 each
    round; sampling is a gradient mask (numerically compact-and-build: the
    histogram only sees the sampled rows' gradients) at static shapes."""

    def __init__(self, booster: GradientBooster, dm, labels: np.ndarray, fresh: bool):
        super().__init__(booster, dm)
        # in-core fits keep their own TransferStats, so histogram spill/fetch
        # traffic is still observable (booster.stats)
        booster._use_stats(
            TransferStats() if fresh or booster.stats is None else booster.stats, fresh
        )
        self.bins = self._stage(dm.single_page_bins())
        self.labels = jnp.asarray(labels)
        self._margin = jnp.full(labels.shape[0], booster.base_margin_, jnp.float32)

    def _stage(self, host_bins: np.ndarray) -> Array:
        """Stage the whole quantized matrix, through the policy's page codec
        when it is device-decodable (only the packed wire payload crosses;
        the decoded int32 bins are identical either way)."""
        from repro.compress import make_transport

        stats = self.booster.stats
        transport = make_transport(self.booster.policy.page_codec)
        if transport is None:
            bins = jnp.asarray(host_bins.astype(np.int32))
            wire_nbytes = host_bins.nbytes
        else:
            wire, wire_meta = transport.encode(np.ascontiguousarray(host_bins))
            bins = transport.decode(jnp.asarray(wire), wire_meta)
            wire_nbytes = wire.nbytes
        stats.logical_bytes += host_bins.nbytes
        stats.wire_bytes += wire_nbytes
        stats.host_to_device_bytes += wire_nbytes
        return bins

    def margin(self) -> Array:
        return self._margin

    def grow(self, key, g, h) -> TreeBuildResult:
        p = self.params
        mask, w = sample(key, g, h, p.sampling)
        scale = jnp.where(mask, w, 0.0)
        return grow_tree(
            self.bins, g * scale, h * scale, self.n_bins, self.bin_valid, self.tp,
            cut_values=self.cuts.values, cut_ptrs=self.cuts.ptrs,
            impl=p.kernel_impl, hist_cache=self.booster.hist_cache,
        )

    def update(self, tree: TreeArrays, positions) -> None:
        if positions is None:
            leaf = predict_tree_bins(tree, self.bins, self.tp.max_depth)
        else:
            leaf = tree.leaf_value[positions]
        self._margin = self._margin + self.params.learning_rate * leaf


class _PagedEngine(_Engine):
    """The page set, streamed through `PageStream` pass after pass: Alg. 6
    grows every tree over all pages; with ``sampling`` set, Alg. 7 grows it
    over one compacted page of the sampled rows. The margins stay on the
    host (``booster.margins_``)."""

    def __init__(
        self,
        booster: GradientBooster,
        dm,
        labels: np.ndarray,
        fresh: bool,
        sampling: SamplingConfig | None = None,
    ):
        from repro.pipeline import DevicePageCache

        super().__init__(booster, dm)
        self.pages = booster.pages = dm.page_set()
        # one ledger carries all device-boundary traffic: histogram spills
        # and fetches land in the page set's TransferStats
        booster._use_stats(self.pages.stats, fresh)
        self.labels = jnp.asarray(labels)
        booster.margins_ = np.full(self.pages.n_rows, booster.base_margin_, np.float32)
        self.sampling = sampling
        cache_pages = booster.policy.device_cache_pages
        if cache_pages is None:
            # auto: cache only when the whole page set fits (a sequential LRU
            # scan over more pages than capacity evicts every page right
            # before its reuse — zero hits), and only on the sampled path
            # where pages are revisited once per iteration.
            fits = self.pages.n_pages <= 8
            cache_pages = self.pages.n_pages if (sampling is not None and fits) else 0
        self.cache = DevicePageCache(cache_pages) if cache_pages > 0 else None

    def stream(self, indices=None):
        """One `PageStream` pass over the page set, or its ``indices``."""
        pol = self.booster.policy
        return self.pages.stream(
            prefetch_depth=pol.prefetch_depth,
            staging_depth=pol.staging_depth,
            cache=self.cache,
            indices=indices,
            retry=pol.retry,
            codec=pol.page_codec,
        )

    def margin(self) -> Array:
        return jnp.asarray(self.booster.margins_)

    def grow(self, key, g, h) -> tuple[TreeArrays, Array | None]:
        from repro.core.outofcore import build_tree_paged

        if self.sampling is None:
            extents = self.pages.page_extents
            tree, positions = build_tree_paged(
                self.stream, extents, g, h, self.n_bins, self.bin_valid, self.tp,
                self.cuts.values, self.cuts.ptrs, impl=self.params.kernel_impl,
                hist_cache=self.booster.hist_cache,
                page_skipping=self.booster.policy.page_skipping,
            )
            # the pages cover the rows in order: every row's leaf
            return tree, jnp.concatenate([positions[i] for i in range(len(extents))])
        return self._grow_sampled(key, g, h), None

    def _grow_sampled(self, key, g, h) -> TreeArrays:
        """Alg. 7: gather the sampled rows of every page into one compacted
        device page and grow the tree over it. Its positions cover only the
        sampled rows, so the margin update re-streams the pages."""
        from repro.core.ellpack import EllpackPage

        cfg = self.sampling
        mask, w = sample(key, g, h, cfg)
        sel = np.nonzero(np.asarray(mask))[0]
        # a static capacity keeps jit shapes stable across rounds (Bernoulli
        # sampling varies the kept count slightly)
        f = cfg.goss_a + cfg.goss_b if cfg.method == "goss" else cfg.f
        n_rows = self.pages.n_rows
        cap = int(n_rows * min(1.0, f * 1.25)) + 256
        capacity = min(n_rows, -(-cap // 1024) * 1024)
        if len(sel) > capacity:  # extreme tail: drop lowest-weight extras
            sel = sel[:capacity]
        gw = np.asarray(g * w)
        hw = np.asarray(h * w)

        # host-side pass: the prefetcher overlaps disk reads, nothing staged
        chunks: list[np.ndarray] = []
        for _, page in self.stream().iter_host():
            lo = np.searchsorted(sel, page.row_offset, side="left")
            hi = np.searchsorted(sel, page.row_offset + page.n_rows, side="left")
            if hi > lo:
                chunks.append(page.bins[sel[lo:hi] - page.row_offset])
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
        bins_c = self.pages.stage(EllpackPage(bins_np, 0), codec=self.booster.policy.page_codec)
        return grow_tree(
            bins_c, jnp.asarray(g_np), jnp.asarray(h_np), self.n_bins, self.bin_valid,
            self.tp, cut_values=self.cuts.values, cut_ptrs=self.cuts.ptrs,
            impl=self.params.kernel_impl, hist_cache=self.booster.hist_cache,
        ).tree

    def update(self, tree: TreeArrays, positions) -> None:
        lr = self.params.learning_rate
        margins = self.booster.margins_
        if positions is not None:
            margins += lr * np.asarray(tree.leaf_value)[np.asarray(positions)]
            return
        for sp in self.stream():
            pred = predict_tree_bins(tree, sp.device, self.tp.max_depth)
            rows = slice(sp.host.row_offset, sp.host.row_offset + sp.host.n_rows)
            margins[rows] += lr * np.asarray(pred)
