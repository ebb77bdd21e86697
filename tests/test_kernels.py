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


# ------------------------------------------- exact bf16 terms of the kernel


def test_bf16_terms_rebuild_every_input():
    """Two bf16 terms rebuild every integer part (``|q| <= 2^16``, so also
    ``|q| = 2^k``) and three every remainder: zero, negatives, tiny and huge
    magnitudes, full 24-bit significands."""
    from repro.kernels.histogram import _bf16_terms

    rng = np.random.default_rng(0)
    q = np.arange(-(2**16), 2**16 + 1, dtype=np.float32)
    mant = np.concatenate([rng.random(4000) + 1, [1.0, 2 - 2.0**-23, 1 + 2.0**-23]])
    mags = np.ldexp(mant, rng.integers(-100, 101, mant.size))
    lo = np.concatenate([[0.0], mags, -mags]).astype(np.float32)
    for x, n_terms in ((q, 2), (lo, 3)):
        terms = [np.asarray(t) for t in _bf16_terms(jnp.asarray(x), n_terms)]
        for t in terms:
            np.testing.assert_array_equal(t, t.astype(jnp.bfloat16).astype(np.float32))
        np.testing.assert_array_equal(np.sum(np.array(terms, np.float64), axis=0), x)


def _adversarial_tiles(n_build, seed, n_bins=16, m=2):
    """Four row tiles of 1024 rows, each tile's rows all in one bin of one
    build node. Tile 0 holds the largest |g| and |h| in every row, so each
    integer part is 2^k and the tile's sum reaches the 2^24 cap; the other
    tiles span 2^-20 to 2^20, g with both signs."""
    rng = np.random.default_rng(seed)
    n = 4 * 1024
    tile = np.arange(n) // 1024
    nodes = np.arange(5, 5 + 2 * n_build, 2, dtype=np.int32)  # not contiguous
    pos = nodes[(3 * tile) % n_build]
    bins = (tile[:, None] * 5 + np.arange(m)[None, :] * 3) % n_bins
    top = np.float32((1 - 2.0**-24) * 2.0**20)
    g = (2.0 ** rng.uniform(-20, 20, n) * rng.choice([-1, 1], n)).astype(np.float32)
    h = (2.0 ** rng.uniform(-20, 20, n)).astype(np.float32)
    g[tile == 0], h[tile == 0] = top, top
    return bins.astype(np.int32), g, h, pos, nodes, n_bins


@pytest.mark.parametrize("n_build", [1, 3, 64, 128])
def test_histogram_integer_parts_exact(n_build):
    """The kernel's integer-part sums are the exact int64 sums, bit for bit,
    with 10 S rows that are sometimes not a multiple of 16 (S = 1, 3)."""
    from repro.kernels.histogram import _fixed_point_split, _histogram_sums

    bins, g, h, pos, nodes, n_bins = _adversarial_tiles(n_build, seed=n_build)
    q_sum, _, _ = _histogram_sums(
        *(jnp.asarray(v) for v in (bins, g, h, pos, nodes)), n_bins, 1024, 8, True
    )
    q_sum = np.asarray(q_sum)
    s_b = q_sum.shape[0] // 2
    slot = np.searchsorted(nodes, pos)
    for k, w in enumerate((g, h)):
        q = np.asarray(_fixed_point_split(jnp.asarray(w), w.size, 14)[0]).astype(np.int64)
        assert np.abs(q).max() == 2**14
        for f in range(bins.shape[1]):
            want = np.zeros((n_build, n_bins), np.int64)
            np.add.at(want, (slot, bins[:, f]), q)
            got = q_sum[k * s_b : k * s_b + n_build, f * 128 : f * 128 + n_bins]
            np.testing.assert_array_equal(got, want)
    assert np.abs(q_sum).max() >= 2**24


@pytest.mark.parametrize("n_build", [1, 3, 64, 128])
def test_histogram_adversarial_bins_within_an_ulp(n_build):
    """After the join, every bin is within an f32 ulp of the float64 sum of
    its rows' g or h."""
    from repro.kernels.histogram import build_histogram_nodes

    bins, g, h, pos, nodes, n_bins = _adversarial_tiles(n_build, seed=n_build)
    got = np.asarray(build_histogram_nodes(
        *(jnp.asarray(v) for v in (bins, g, h, pos, nodes)), n_bins, interpret=True
    ), np.float64)
    slot = np.searchsorted(nodes, pos)
    for k, w in enumerate((g, h)):
        for f in range(bins.shape[1]):
            want = np.zeros((n_build, n_bins))
            np.add.at(want, (slot, bins[:, f]), w.astype(np.float64))
            ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
            assert np.all(np.abs(got[:, f, :, k] - want) <= ulp)


def test_fixed_point_join_rounds_small_sums_once():
    """An integer-part sum below 64, of either sign, joins its remainder sum
    in one rounding, so a bin of one row is that row's value; larger sums
    stay within an ulp. The kernel and the scatter oracle join alike."""
    from repro.kernels.histogram import _fixed_point_join

    rng = np.random.default_rng(3)
    scale = np.float32(2.0**-6)
    q = np.concatenate([np.arange(-200, 201), rng.integers(-(2**29), 2**29, 400)])
    lo = (rng.uniform(-0.5, 0.5, q.size) / scale).astype(np.float32)
    got = np.asarray(_fixed_point_join(
        jnp.asarray(q, jnp.int32), jnp.asarray(lo), jnp.float32(scale)
    ), np.float64)
    want = q / np.float64(scale) + lo
    err = np.abs(got - want)
    small = np.abs(q) < 64
    np.testing.assert_array_equal(got[small], want[small].astype(np.float32))
    assert np.all(err <= np.spacing(np.abs(want).astype(np.float32)))
    w = jnp.asarray(np.float32([-41.83049, 2.0**20]))
    one_row = ref.scatter_sum(jnp.arange(2), w, 2, 2)
    np.testing.assert_array_equal(np.asarray(one_row), np.asarray(w))


def _kernel_dots(jaxpr, in_kernel=False):
    """Every ``dot_general`` inside a ``pallas_call`` of ``jaxpr``."""
    for eqn in jaxpr.eqns:
        inside = in_kernel or eqn.primitive.name == "pallas_call"
        if in_kernel and eqn.primitive.name == "dot_general":
            yield eqn
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _kernel_dots(sub, inside)


@pytest.mark.parametrize("n_build", [1, 64])
def test_histogram_kernel_runs_one_bf16_product_per_feature(n_build):
    """No product in the kernel takes f32 operands or runs at
    ``Precision.HIGHEST`` (six bf16 MXU passes each): one bf16 product per
    feature and row tile."""
    from repro.kernels.histogram import build_histogram_slab

    bins, g, h, pos = _hist_inputs(2048, 11, 255, n_build, seed=4)
    jaxpr = jax.make_jaxpr(
        lambda *a: build_histogram_slab(*a, 255, interpret=False)
    )(bins, g, h, pos, jnp.arange(n_build, dtype=jnp.int32))
    dots = list(_kernel_dots(jaxpr.jaxpr))
    assert len(dots) == 8  # one per feature of a feature tile
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert jax.lax.Precision.HIGHEST not in (eqn.params["precision"] or ())
        assert eqn.params["preferred_element_type"] == jnp.float32
