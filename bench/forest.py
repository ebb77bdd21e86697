"""The seeded forest of ``higgs-forest-1k``: a forest's equivalent of random
weights, made on the device in one jitted call.

Like the forest of ``benchmarks/serving_latency.py``: complete-layout trees
with a split at every internal node over the real bins of a fixed set of
cuts, some early leaves, and random leaf weights. Each split's raw threshold
is the cut it names, so a row goes left iff ``x <= split_value``, which is
the program's ``bin(x) <= split_bin`` for any split short of the last bin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import FEATURES


def cut_edges(n_bins: int) -> np.ndarray:
    """(28, n_bins) float32 right edges: equal-probability cuts of the
    standard normal, the last one above any value a row can take."""
    from scipy.special import ndtri

    inner = ndtri(np.arange(1, n_bins) / n_bins)
    edges = np.append(inner, 64.0).astype(np.float32)
    return np.tile(edges, (FEATURES, 1))


def key_of(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = abs(int(seed))
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("n_trees", "max_depth", "early_leaf_share",
                                             "leaf_std"))
def _make(key, edges, *, n_trees, max_depth, early_leaf_share, leaf_std):
    n_total = 2 ** (max_depth + 1) - 1
    m, n_bins = edges.shape
    kf, kb, kl, kd, kv = jax.random.split(key, 5)
    shape = (n_trees, n_total)
    last_level = jnp.arange(n_total) >= 2**max_depth - 1
    is_leaf = (jax.random.uniform(kl, shape) < early_leaf_share) | last_level
    feature = jnp.where(is_leaf, 0, jax.random.randint(kf, shape, 0, m))
    # never the last bin: that split would send every row left
    split_bin = jnp.where(is_leaf, 0, jax.random.randint(kb, shape, 0, n_bins - 1))
    split_value = jnp.where(is_leaf, 0.0, edges[feature, split_bin])
    default_left = jax.random.bernoulli(kd, 0.5, shape) & ~is_leaf
    leaf_value = jnp.where(is_leaf, leaf_std * jax.random.normal(kv, shape), 0.0)
    return {
        "feature": feature.astype(jnp.int32),
        "split_bin": split_bin.astype(jnp.int32),
        "split_value": split_value.astype(jnp.float32),
        "default_left": default_left,
        "is_leaf": is_leaf,
        "leaf_value": leaf_value.astype(jnp.float32),
    }


def make_forest(seed: int, cfg: dict) -> dict:
    """The forest's arrays, (n_trees, n_total) each, on the default device."""
    edges = jnp.asarray(cut_edges(int(cfg["bins"])))
    return _make(key_of(seed), edges, n_trees=int(cfg["trees"]),
                 max_depth=int(cfg["max_depth"]),
                 early_leaf_share=float(cfg["early_leaf_share"]),
                 leaf_std=float(cfg["leaf_std"]))
