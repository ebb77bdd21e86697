"""`fit_sharded` on a ("data",) mesh of four devices: the same forest as the
one-device in-core fit, its tree program compiled once per fit, and the bytes
its collectives carry counted from their operands' shapes.

One subprocess with four forced CPU host devices (JAX fixes the device count
when it starts) runs every case and prints one JSON object; the tests read it.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROWS, FEATURES, MAX_BIN, DEPTH, TREES = 4096, 28, 32, 4, 4  # HIGGS width
LEARNING_RATE = 0.1

SCRIPT = r"""
import json, sys
import jax

sys.path.insert(0, sys.argv[2])
from oracle import assert_forests_equal

from repro import tracing
from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
from repro.data.dmatrix import IterDMatrix
from repro.data.synthetic import SyntheticSource
from repro.distributed import DistConfig, fit_sharded
import repro.core.booster as booster_module

ROWS, FEATURES, MAX_BIN, DEPTH, TREES, LR = json.loads(sys.argv[1])
assert len(jax.devices()) == 4, jax.devices()

compiles = [0]
def on_event(event, *_, **__):
    if event in ("/jax/core/compile/backend_compile_duration",
                 "/jax/compilation_cache/cache_hits"):
        compiles[0] += 1
jax.monitoring.register_event_listener(on_event)
jax.monitoring.register_event_duration_secs_listener(on_event)

# the compile count as each boosting round opens its span (the booster's
# round loop runs every engine, fit_sharded's too)
round_starts = []
span = booster_module.span
def counting_span(name, **kw):
    if name == tracing.ROUND:
        round_starts.append(compiles[0])
    return span(name, **kw)
booster_module.span = counting_span

source = SyntheticSource(n_rows=ROWS, num_features=FEATURES, task="higgs", seed=7,
                         batch_rows=1024)
eval_x, eval_y = SyntheticSource(n_rows=1024, num_features=FEATURES, task="higgs", seed=7,
                                 batch_offset=10**6).materialize()
dm = IterDMatrix(source, max_bin=MAX_BIN)
assert dm.num_features == FEATURES, dm.num_features
params = BoosterParams(n_estimators=TREES, max_depth=DEPTH, learning_rate=LR,
                       max_bin=MAX_BIN, objective="binary:logistic", seed=0)
mesh = jax.make_mesh((4,), ("data",))
out = {"n_bins": dm.n_bins}

sharded = fit_sharded(mesh, dm, params=params, eval_set=(eval_x, eval_y))
end = compiles[0]
out["compiles_after_round"] = [end - c for c in round_starts]
one = GradientBooster(params, policy=ExecutionPolicy(mode="in_core"))
one.fit(dm, eval_set=(eval_x, eval_y))
assert_forests_equal(sharded.trees, one.trees)
out["forests_equal"] = True
out["margins"] = [sharded.predict_margin(eval_x).tolist(), one.predict_margin(eval_x).tolist()]
out["auc"] = [sharded.eval_history[-1].value, one.eval_history[-1].value]

counted = lambda b: getattr(b.stats, "collective_bytes", None)
out["collective_bytes"] = {"raw": counted(sharded)}
bf16 = fit_sharded(mesh, dm, params=params, cfg=DistConfig(grad_transport="bf16"))
out["collective_bytes"]["bf16"] = counted(bf16)
again = fit_sharded(mesh, dm, params=params, cfg=DistConfig(grad_transport="bf16"))
out["collective_bytes"]["bf16_again"] = counted(again)
out["bf16_trees"] = len(bf16.trees)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    args = json.dumps([ROWS, FEATURES, MAX_BIN, DEPTH, TREES, LEARNING_RATE])
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, args, here], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-4000:] + "\n" + out.stderr[-8000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_same_forest_as_the_one_device_fit(result):
    assert result["forests_equal"]
    sharded, one = (np.asarray(m) for m in result["margins"])
    # Four shards sum each histogram and leaf sum in another order than one
    # device, so a leaf weight may differ by an f32 rounding (the oracle's
    # 1e-4 relative); each of the TREES trees adds LEARNING_RATE x its leaf.
    np.testing.assert_allclose(sharded, one, rtol=1e-4, atol=TREES * LEARNING_RATE * 1e-5)
    assert result["auc"][0] == pytest.approx(result["auc"][1], abs=1e-6)


def test_tree_program_compiles_in_the_first_round_only(result):
    after = result["compiles_after_round"]
    assert len(after) == TREES >= 4
    assert after[0] > 0  # the first round compiles the tree program
    assert after[1:] == [0] * (TREES - 1), after


def _expected_collective_bytes(n_bins: int, hist_itemsize: int) -> int:
    """Per tree, what each shard passes into the depthwise program's psums:
    the histograms of the built nodes (the root, then the smaller child of
    each pair: 2^(DEPTH-1) in all, F x B x (g, h) each), the next level's
    row counts (int32) after every level but the last, the leaf sums
    (g and h, f32, over all 2^(DEPTH+1) - 1 nodes) and the root's g and h."""
    built_nodes = 2 ** (DEPTH - 1)
    hist = built_nodes * FEATURES * n_bins * 2 * hist_itemsize
    counts = sum(2 ** (d + 1) for d in range(DEPTH - 1)) * 4
    sums = 2 * (2 ** (DEPTH + 1) - 1) * 4
    return hist + counts + sums + 2 * 4


@pytest.mark.parametrize("transport, itemsize", [("raw", 4), ("bf16", 2)])
def test_collective_bytes_count_the_operands(result, transport, itemsize):
    want = TREES * _expected_collective_bytes(result["n_bins"], itemsize)
    assert result["collective_bytes"][transport] == want
    if transport == "bf16":
        # a later fit with the same arguments reuses the program and counts alike
        assert result["collective_bytes"]["bf16_again"] == want
