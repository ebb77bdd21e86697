"""Split evaluation: paper eq. (6)/(7)/(8) with missing-value default directions.

Given per-node gradient histograms, enumerate every (feature, bin) split with
both missing-value routings and return the arg-max split per node. This is
EvaluateSplit of Alg. 1, vectorized over all nodes of a tree level.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -jnp.inf


@dataclasses.dataclass(frozen=True)
class SplitParams:
    reg_lambda: float = 1.0  # λ of eq. (3)
    gamma: float = 0.0  # γ of eq. (3); subtracted in eq. (8)
    min_child_weight: float = 1.0  # XGBoost default: min hessian per child


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LevelSplits:
    """Best split per node of one tree level (all arrays shaped (n_nodes,))."""

    gain: Array
    feature: Array  # int32
    split_bin: Array  # int32
    default_left: Array  # bool
    left_g: Array
    left_h: Array
    right_g: Array
    right_h: Array
    should_split: Array  # bool

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        return tuple(getattr(self, f.name) for f in fields), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def _leaf_objective(g: Array, h: Array, reg_lambda: float) -> Array:
    """-(Σg)² / (Σh + λ): twice the per-leaf term of eq. (7) (sign flipped)."""
    return (g * g) / (h + reg_lambda)


@functools.partial(jax.jit, static_argnames=("params",))
def evaluate_splits(
    hist: Array,  # (n_nodes, m, n_bins, 2) gradient histogram (g, h)
    node_g: Array,  # (n_nodes,) total gradient per node (incl. missing rows)
    node_h: Array,  # (n_nodes,)
    bin_valid: Array,  # (m, n_bins) bool: real (non-padding) bins per feature
    params: SplitParams,
) -> LevelSplits:
    n_nodes, m, n_bins, _ = hist.shape
    lam, gamma, mcw = params.reg_lambda, params.gamma, params.min_child_weight

    cum = jnp.cumsum(hist, axis=2)  # stats for bins <= b (left side, non-missing)
    cum_g, cum_h = cum[..., 0], cum[..., 1]
    tot_g, tot_h = cum_g[:, :, -1], cum_h[:, :, -1]  # per-feature non-missing totals
    miss_g = node_g[:, None] - tot_g  # (n_nodes, m)
    miss_h = node_h[:, None] - tot_h

    parent_obj = _leaf_objective(node_g, node_h, lam)[:, None, None]

    def gain_of(left_g, left_h):
        right_g = node_g[:, None, None] - left_g
        right_h = node_h[:, None, None] - left_h
        raw = 0.5 * (
            _leaf_objective(left_g, left_h, lam)
            + _leaf_objective(right_g, right_h, lam)
            - parent_obj
        ) - gamma
        ok = (left_h >= mcw) & (right_h >= mcw)
        return jnp.where(ok, raw, NEG_INF)

    # default-right: missing rows go right -> left stats are the cumulative sums
    gain_dr = gain_of(cum_g, cum_h)
    # default-left: missing rows go left
    gain_dl = gain_of(cum_g + miss_g[:, :, None], cum_h + miss_h[:, :, None])

    valid = bin_valid[None, :, :]
    # splitting at the LAST real bin sends all non-missing left; only useful
    # with default-right (missing-only split). Disallow for default-left
    # (degenerate: empty right child) — min_child_weight already guards h=0,
    # but make it explicit for h-free correctness.
    last_bin = jnp.cumsum(bin_valid.astype(jnp.int32), axis=1) == jnp.sum(
        bin_valid, axis=1, keepdims=True
    )
    gain_dr = jnp.where(valid, gain_dr, NEG_INF)
    gain_dl = jnp.where(valid & ~last_bin[None], gain_dl, NEG_INF)

    use_dl = gain_dl > gain_dr
    gain = jnp.maximum(gain_dl, gain_dr)  # (n_nodes, m, n_bins)

    flat = gain.reshape(n_nodes, m * n_bins)
    best_idx = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
    best_feature = (best_idx // n_bins).astype(jnp.int32)
    best_bin = (best_idx % n_bins).astype(jnp.int32)

    def pick(x):  # x: (n_nodes, m, n_bins)
        return jnp.take_along_axis(
            x.reshape(n_nodes, m * n_bins), best_idx[:, None], axis=1
        )[:, 0]

    best_dl = pick(use_dl)
    left_g = pick(jnp.where(use_dl, cum_g + miss_g[:, :, None], cum_g))
    left_h = pick(jnp.where(use_dl, cum_h + miss_h[:, :, None], cum_h))

    return LevelSplits(
        gain=best_gain,
        feature=best_feature,
        split_bin=best_bin,
        default_left=best_dl.astype(bool),
        left_g=left_g,
        left_h=left_h,
        right_g=node_g - left_g,
        right_h=node_h - left_h,
        should_split=worth_splitting(best_gain),
    )


def worth_splitting(gain: Array) -> Array:
    """Where a node's best split is made: its gain is finite and positive."""
    return jnp.isfinite(gain) & (gain > 0.0)


def leaf_weight(g: Array, h: Array, reg_lambda: float) -> Array:
    """Optimal leaf weight, eq. (6): w* = -Σg / (Σh + λ)."""
    return -g / (h + reg_lambda)
