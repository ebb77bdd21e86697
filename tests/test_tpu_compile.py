"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one chip
of a ``v5e:2x2`` topology that is described, not attached, and checks that
the compiled program holds the Mosaic kernel (``tpu_custom_call``). This
catches what interpret mode cannot: blocks that break the (8, 128) tiling
rule, operations Mosaic cannot lower, and kernels that overflow VMEM.

The topology is described inside a module fixture, so only the worker that
runs this file loads the TPU compiler. Widths are the paper's: 28 (HIGGS)
and 500 (synthetic) features, 255 bins, depth-8 trees.
"""
import pytest

import jax
import jax.numpy as jnp

ROWS = 2**20
WIDTHS = [28, 500]
N_BINS = 255
DEPTH = 8
N_NODES = 2 ** (DEPTH + 1) - 1  # 511


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep the cache out of these compiles
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"


@pytest.mark.parametrize("n_build", [1, 64, 128, 256])
@pytest.mark.parametrize("m", WIDTHS)
def test_build_histogram_nodes_compiles(one_chip, m, n_build):
    from repro.kernels.histogram import build_histogram_nodes

    _compile(
        lambda b, g, h, p, n: build_histogram_nodes(b, g, h, p, n, N_BINS, interpret=False),
        one_chip,
        ((ROWS, m), jnp.uint8), ((ROWS,), jnp.float32), ((ROWS,), jnp.float32),
        ((ROWS,), jnp.int32), ((n_build,), jnp.int32),
    )


@pytest.mark.parametrize("m", WIDTHS)
def test_partition_rows_compiles(one_chip, m):
    from repro.kernels.partition import partition_rows

    _compile(
        lambda *a: partition_rows(*a, interpret=False),
        one_chip,
        ((ROWS, m), jnp.uint8), ((ROWS,), jnp.int32),
        ((N_NODES,), jnp.int32), ((N_NODES,), jnp.int32),
        ((N_NODES,), jnp.bool_), ((N_NODES,), jnp.bool_),
    )


@pytest.mark.parametrize("m", WIDTHS)
def test_predict_forest_compiles(one_chip, m):
    from repro.kernels.forest import predict_forest

    trees = 64  # one serving chunk
    _compile(
        lambda b, f, s, d, leaf, v, mi: predict_forest(
            b, f, s, d, leaf, v, DEPTH, mi, interpret=False
        ),
        one_chip,
        ((ROWS, m), jnp.uint8), ((trees, N_NODES), jnp.int32),
        ((trees, N_NODES), jnp.int32), ((trees, N_NODES), jnp.bool_),
        ((trees, N_NODES), jnp.bool_), ((trees, N_NODES), jnp.float32),
        ((ROWS,), jnp.float32),
    )


@pytest.mark.parametrize("m", WIDTHS)
def test_bin_values_compiles(one_chip, m):
    from repro.kernels.ellpack_bin import bin_values

    _compile(
        lambda x, e, n: bin_values(x, e, n, interpret=False),
        one_chip,
        ((ROWS, m), jnp.float32), ((m, N_BINS + 1), jnp.float32), ((m,), jnp.int32),
    )


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The four chips of the described ``v5e:2x2`` host (the environment and
    the compile cache as ``one_chip`` leaves them)."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices


def test_sharded_tree_program_compiles(four_chips, monkeypatch):
    """`fit_sharded`'s depthwise tree program on a ("data",) mesh of the four
    chips, 2^21 HIGGS rows: the Mosaic kernels inside the SPMD program, one
    histogram all-reduce a level (the root's shares one with the root's g and
    h, the leaf sums take one, the row counts one after each level but the
    last: 16 in all), and the bytes `TransferStats.collective_bytes` adds a
    tree: 128 built node histograms x 28 x 256 bins x (g, h) x 4 B, 254 int32
    row counts, 2 x 511 leaf sums and the root's 2 sums."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core.tree import TreeParams
    from repro.distributed import DistConfig, gbdt_shard
    from repro.kernels import _backend

    monkeypatch.setattr(_backend, "on_tpu", lambda: True)  # compiled, not interpreted
    mesh = Mesh(np.array(four_chips), ("data",), axis_types=(AxisType.Explicit,))
    rows, m, n_bins = 2**21, 28, N_BINS + 1
    avals = (((rows, m), "int32"), ((rows,), "float32"), ((rows,), "float32"),
             ((m, n_bins), "bool"), ((m * n_bins,), "float32"), ((m + 1,), "int32"))
    program = gbdt_shard._tree_program(
        mesh, TreeParams(max_depth=DEPTH), DistConfig(kernel_impl="pallas"), n_bins, avals
    )
    specs = (P("data", None), P("data"), P("data"), P(), P(), P())
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=NamedSharding(mesh, spec))
            for (s, d), spec in zip(avals, specs)]
    text = program.fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    assert text.count(" all-reduce(") == 2 * DEPTH
    assert program.collective_bytes == 128 * m * n_bins * 2 * 4 + 254 * 4 + 2 * 511 * 4 + 2 * 4
