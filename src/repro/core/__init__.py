"""Core GBDT library: the paper's contribution (out-of-core gradient boosting)."""
from repro.core.booster import BoosterParams, GradientBooster
from repro.core.ellpack import (
    DEFAULT_PAGE_BYTES,
    MISSING_BIN,
    EllpackMatrix,
    EllpackPage,
    bin_batch,
    compact,
    create_ellpack_inmemory,
    create_ellpack_pages,
)
from repro.core.histcache import HistCacheStats, HistogramCache, HistogramStore, LevelPlan
from repro.core.memory import DeviceMemoryModel
from repro.core.objectives import LOGISTIC, SQUARED_ERROR, get_objective
from repro.core.outofcore import build_tree_paged
from repro.core.policy import ExecutionDecision, ExecutionPolicy
from repro.core.quantile import HistogramCuts, QuantileSketch, sketch_dense
from repro.core.sampling import SamplingConfig, estimate_mvs_lambda, mvs_threshold, sample
from repro.core.split import SplitParams, evaluate_splits, leaf_weight
from repro.core.tree import (
    TreeArrays,
    TreeParams,
    grow_tree,
    grow_tree_generic,
    grow_tree_lossguide_generic,
    predict_forest_raw,
    predict_tree_bins,
    predict_tree_raw,
    stack_trees,
    tree_growth_driver,
)

__all__ = [
    "BoosterParams",
    "GradientBooster",
    "DEFAULT_PAGE_BYTES",
    "MISSING_BIN",
    "EllpackMatrix",
    "EllpackPage",
    "bin_batch",
    "compact",
    "create_ellpack_inmemory",
    "create_ellpack_pages",
    "DeviceMemoryModel",
    "ExecutionDecision",
    "ExecutionPolicy",
    "build_tree_paged",
    "HistCacheStats",
    "HistogramCache",
    "HistogramStore",
    "LevelPlan",
    "LOGISTIC",
    "SQUARED_ERROR",
    "get_objective",
    "HistogramCuts",
    "QuantileSketch",
    "sketch_dense",
    "SamplingConfig",
    "estimate_mvs_lambda",
    "mvs_threshold",
    "sample",
    "SplitParams",
    "evaluate_splits",
    "leaf_weight",
    "TreeArrays",
    "TreeParams",
    "grow_tree",
    "grow_tree_generic",
    "grow_tree_lossguide_generic",
    "tree_growth_driver",
    "predict_forest_raw",
    "predict_tree_bins",
    "predict_tree_raw",
    "stack_trees",
]
