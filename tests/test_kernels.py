"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ellpack_bin import bin_values as bin_pl
from repro.kernels.histogram import build_histogram as hist_pl
from repro.kernels.histogram import build_histogram_nodes_host
from repro.kernels.partition import partition_rows as part_pl

MISSING = ref.MISSING_BIN


def _hist_inputs(n, m, n_bins, n_nodes, seed, missing_rate=0.05, gdtype=np.float32):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, (n, m)).astype(np.int32)
    bins[rng.random((n, m)) < missing_rate] = MISSING
    g = rng.normal(size=n).astype(gdtype)
    h = rng.random(n).astype(gdtype)
    pos = rng.integers(-1, n_nodes, n).astype(np.int32)
    return tuple(jnp.asarray(v) for v in (bins, g, h, pos))


HIST_SWEEP = [
    # (n_rows, m, n_bins, n_nodes) — off-tile sizes on purpose
    (64, 4, 16, 1),
    (257, 3, 32, 2),
    (513, 13, 32, 4),
    (1000, 7, 64, 8),
    (128, 1, 256, 16),
    (300, 20, 8, 3),
]


@pytest.mark.parametrize("n,m,n_bins,n_nodes", HIST_SWEEP)
def test_histogram_matches_oracle(n, m, n_bins, n_nodes):
    bins, g, h, pos = _hist_inputs(n, m, n_bins, n_nodes, seed=n + m)
    want = ref.build_histogram(bins, g, h, pos, n_nodes, n_bins)
    got = hist_pl(bins, g, h, pos, n_nodes, n_bins, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_histogram_block_shape_invariance():
    bins, g, h, pos = _hist_inputs(500, 6, 16, 4, seed=9)
    want = ref.build_histogram(bins, g, h, pos, 4, 16)
    for rt, ft in [(64, 2), (128, 3), (512, 6)]:
        got = hist_pl(bins, g, h, pos, 4, 16, row_tile=rt, feat_tile=ft, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas", "host"])
def test_histogram_rounds_each_bin_once(impl):
    """Each bin is its exact sum rounded once to f32, within an ulp, however
    many rows it holds, so every histogram path gives the split search the
    same bins. A sequential f32 sum of these 4096 rows per bin is off by
    tens of ulps."""
    n, m, n_bins = 1 << 16, 2, 16
    rng = np.random.default_rng(0)
    bins = rng.integers(0, n_bins, (n, m)).astype(np.int32)
    p = 1 / (1 + np.exp(-rng.normal(0, 0.5, n)))
    g = (p - (rng.random(n) < p)).astype(np.float32)
    h = (p * (1 - p)).astype(np.float32)
    args = [jnp.asarray(v) for v in (bins, g, h, np.zeros(n, np.int32))]
    if impl == "ref":
        got = jax.jit(ref.build_histogram, static_argnums=(4, 5))(*args, 1, n_bins)
    elif impl == "pallas":
        got = hist_pl(*args, 1, n_bins, interpret=True)
    else:
        got = build_histogram_nodes_host(*args, jnp.zeros(1, jnp.int32), n_bins)
    flat = (np.arange(m) * n_bins + bins).ravel()
    for k, w in enumerate((g, h)):
        want = np.bincount(flat, np.repeat(w.astype(np.float64), m), m * n_bins)
        err = np.abs(np.asarray(got, np.float64)[0, ..., k].ravel() - want)
        ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
        assert np.all(err <= ulp), f"channel {k}: {np.max(err / ulp):.1f} ulps"


def test_histogram_bf16_gradients():
    bins, g, h, pos = _hist_inputs(256, 4, 16, 2, seed=1)
    g16 = g.astype(jnp.bfloat16)
    h16 = h.astype(jnp.bfloat16)
    want = ref.build_histogram(bins, g16.astype(jnp.float32), h16.astype(jnp.float32), pos, 2, 16)
    got = hist_pl(bins, g16, h16, pos, 2, 16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-2, atol=1e-2)


BIN_SWEEP = [(17, 3, 8), (128, 9, 16), (77, 33, 64), (256, 5, 256)]


@pytest.mark.parametrize("n,m,max_bin", BIN_SWEEP)
def test_bin_values_matches_oracle(n, m, max_bin):
    rng = np.random.default_rng(n * m)
    x = rng.normal(size=(n, m)).astype(np.float32)
    x[rng.random((n, m)) < 0.05] = np.nan
    nbf = rng.integers(2, max_bin + 1, m).astype(np.int32)
    pe = np.full((m, max_bin), np.inf, np.float32)
    for f in range(m):
        pe[f, : nbf[f]] = np.sort(rng.normal(size=nbf[f]))
    args = (jnp.asarray(x), jnp.asarray(pe), jnp.asarray(nbf))
    want = ref.bin_values(*args)
    got = bin_pl(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bin_values_boundary_semantics():
    # edges are right-inclusive: x == edge -> that bin; x > last edge -> clipped
    edges = np.array([[0.0, 1.0, np.inf, np.inf]], np.float32)
    nbf = np.array([2], np.int32)
    x = np.array([[-1.0], [0.0], [0.5], [1.0], [5.0]], np.float32)
    got = np.asarray(bin_pl(jnp.asarray(x), jnp.asarray(edges), jnp.asarray(nbf), interpret=True))
    np.testing.assert_array_equal(got[:, 0], [0, 0, 1, 1, 1])


PART_SWEEP = [(33, 3, 8, 7), (257, 5, 16, 15), (512, 8, 32, 31)]


@pytest.mark.parametrize("n,m,n_bins,n_nodes", PART_SWEEP)
def test_partition_matches_oracle(n, m, n_bins, n_nodes):
    rng = np.random.default_rng(n)
    bins = rng.integers(0, n_bins, (n, m)).astype(np.int32)
    bins[rng.random((n, m)) < 0.07] = MISSING
    pos = rng.integers(-1, (n_nodes - 1) // 2, n).astype(np.int32)
    feat = rng.integers(0, m, n_nodes).astype(np.int32)
    sb = rng.integers(0, n_bins, n_nodes).astype(np.int32)
    dl = rng.random(n_nodes) < 0.5
    lf = rng.random(n_nodes) < 0.3
    args = tuple(jnp.asarray(v) for v in (bins, pos, feat, sb, dl, lf))
    want = ref.partition_rows(*args)
    got = part_pl(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_partition_leaf_rows_keep_position():
    bins = jnp.zeros((4, 2), jnp.int32)
    pos = jnp.asarray([0, 0, -1, 0], jnp.int32)
    feat = jnp.zeros(3, jnp.int32)
    sb = jnp.zeros(3, jnp.int32)
    dl = jnp.zeros(3, bool)
    lf = jnp.asarray([True, False, False])
    got = np.asarray(ref.partition_rows(bins, pos, feat, sb, dl, lf))
    np.testing.assert_array_equal(got, [0, 0, -1, 0])  # node 0 is leaf -> frozen


def test_predict_bins_known_tree():
    # depth-1 stump: feature 0, split at bin 2, left value -1, right +1
    feature = jnp.asarray([0, 0, 0], jnp.int32)
    split_bin = jnp.asarray([2, 0, 0], jnp.int32)
    default_left = jnp.asarray([True, False, False])
    is_leaf = jnp.asarray([False, True, True])
    leaf_value = jnp.asarray([0.0, -1.0, 1.0], jnp.float32)
    bins = jnp.asarray([[0], [2], [3], [MISSING]], jnp.int32)
    got = np.asarray(
        ref.predict_bins(bins, feature, split_bin, default_left, is_leaf, leaf_value, 1)
    )
    np.testing.assert_array_equal(got, [-1.0, -1.0, 1.0, -1.0])


def test_on_tpu_lets_a_probe_error_propagate(monkeypatch):
    """A backend probe that fails must raise, not answer "not a TPU": the
    dispatch layer would otherwise route a lost chip to the CPU path."""
    from repro.kernels import _backend

    def broken_probe():
        raise RuntimeError("backend probe failed")

    monkeypatch.setattr(_backend.jax, "default_backend", broken_probe)
    with pytest.raises(RuntimeError, match="backend probe failed"):
        _backend.on_tpu()
    with pytest.raises(RuntimeError, match="backend probe failed"):
        _backend.resolve_interpret(None)


@pytest.mark.parametrize("m,n_bins,n_build", [(5, 16, 3), (11, 130, 2)])
def test_histogram_slab_layout_matches_oracle(m, n_bins, n_build):
    """The kernel writes a (2S, m_p * B_p) slab: row s is the gradient sums
    of build node s, row S + s its hessian sums, column f * B_p + b is
    (feature f, bin b), and every padding column is zero. Reshaped, it is
    the (S, m, B, 2) histogram of `kernels/ref.py`."""
    from repro.kernels.histogram import build_histogram_nodes, build_histogram_slab

    bins, g, h, pos = _hist_inputs(300, m, n_bins, n_build + 2, seed=m)
    nodes = jnp.arange(1, n_build + 1, dtype=jnp.int32)
    want = np.asarray(ref.build_histogram_nodes(bins, g, h, pos, nodes, n_bins))
    slab = np.asarray(build_histogram_slab(bins, g, h, pos, nodes, n_bins, interpret=True))
    b_p = -(-n_bins // 128) * 128
    m_p = slab.shape[1] // b_p
    assert slab.shape == (2 * n_build, m_p * b_p) and m_p >= m
    cells = slab.reshape(2, n_build, m_p, b_p)
    np.testing.assert_allclose(cells[0, :, :m, :n_bins], want[..., 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cells[1, :, :m, :n_bins], want[..., 1], rtol=1e-5, atol=1e-5)
    assert not cells[:, :, m:, :].any() and not cells[:, :, :, n_bins:].any()
    got = np.asarray(build_histogram_nodes(bins, g, h, pos, nodes, n_bins, interpret=True))
    np.testing.assert_array_equal(got, cells[:, :, :m, :n_bins].transpose(1, 2, 3, 0))
