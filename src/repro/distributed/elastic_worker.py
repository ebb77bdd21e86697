"""Subprocess entry point for `ElasticTrainer` workers.

``python -m repro.distributed.elastic_worker --name w0 --heartbeat <path>``

One worker owns a set of on-disk shards (`PagedDMatrix` page caches) and
serves the coordinator's per-tree RPCs over stdin/stdout:

  init            hyperparameters (BoosterParams dict)
  open_shard      reopen one shard's page cache
  shard_stats     per-shard (label_sum, label_count) for the base margin
  set_base_margin flat margins (fresh start)
  reset           reload margins from a checkpoint via GradientBooster.resume
                  (the recovery primitive: replayed margins are bit-for-bit
                  the incremental ones)
  begin_tree      gradients from current margins + zeroed positions;
                  returns per-shard (sum_g, sum_h)
  hist            one streamed histogram pass over a node window
  partition       re-route rows by the broadcast split arrays; optional
                  per-node row counts for the subtraction planner
  finish_tree     apply the finished tree's leaves to the margins
  ping/shutdown   liveness / clean exit

Protocol hygiene: the binary framing owns the *original* stdout fd (dup'd at
startup); fd 1 is then redirected to stderr so stray library prints can never
corrupt a frame. A heartbeat thread touches ``--heartbeat`` every
``--heartbeat-interval`` seconds — started before the handler loop so the
coordinator's staleness watchdog sees a live file even while an op runs long.

Fault injection: `repro.fault.install_from_env` arms any plan the coordinator
serialized into ``REPRO_FAULT_PLAN``; the worker fires "elastic.rpc"
(worker/op context) before each op and "elastic.worker.iteration"
(worker/iteration context) at each begin_tree — the latter is where the chaos
test's "kill worker w1 at iteration k" lands (``os._exit``, a real crash).
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time
import traceback

from repro.fault import inject as fault_inject


def _start_heartbeat(path: str, interval: float) -> None:
    def beat() -> None:
        while True:
            try:
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as fh:
                    fh.write(str(time.time()))
                os.replace(tmp, path)
            except OSError:  # pragma: no cover - transient fs hiccup
                pass
            time.sleep(interval)

    threading.Thread(target=beat, daemon=True, name="heartbeat").start()


class _Shard:
    """One opened shard: its page cache plus per-tree training state."""

    def __init__(self, dm):
        import jax.numpy as jnp
        import numpy as np

        self.dm = dm
        self.pages = dm.page_set()
        self.labels_np = np.asarray(dm.require_labels(), np.float32)
        self.labels = jnp.asarray(self.labels_np)
        self.margins: "np.ndarray | None" = None
        self.g = None
        self.h = None
        self.positions: dict = {}


class _WorkerState:
    def __init__(self, name: str):
        self.name = name
        self.params = None
        self.objective = None
        self.shards: dict[int, _Shard] = {}

    # ------------------------------------------------------------------ ops
    def handle(self, msg: dict) -> dict:
        op = msg["op"]
        fn = getattr(self, f"_op_{op}", None)
        if fn is None:
            raise ValueError(f"unknown op {op!r}")
        return fn(msg)

    def _op_init(self, msg: dict) -> dict:
        from repro.core import objectives as obj_lib
        from repro.core.booster import BoosterParams
        from repro.core.sampling import SamplingConfig

        meta = dict(msg["params"])
        sampling = SamplingConfig(**meta.pop("sampling"))
        self.params = BoosterParams(sampling=sampling, **meta)
        self.objective = obj_lib.get_objective(self.params.objective)
        return {}

    def _op_open_shard(self, msg: dict) -> dict:
        from repro.data.dmatrix import PagedDMatrix

        sid = int(msg["shard"])
        if sid not in self.shards:  # idempotent under RPC retry
            self.shards[sid] = _Shard(PagedDMatrix(msg["dir"]))
        return {"n_rows": int(self.shards[sid].dm.n_rows)}

    def _op_shard_stats(self, msg: dict) -> dict:
        import numpy as np

        sh = self.shards[int(msg["shard"])]
        return {
            # float64 accumulation: the per-shard sum must not depend on
            # shard size, so the coordinator's aggregated mean is stable
            "label_sum": float(np.sum(sh.labels_np, dtype=np.float64)),
            "label_count": int(sh.labels_np.shape[0]),
        }

    def _op_set_base_margin(self, msg: dict) -> dict:
        import numpy as np

        value = float(msg["value"])
        for sh in self.shards.values():
            sh.margins = np.full(sh.dm.n_rows, value, np.float32)
            sh.g = sh.h = None
            sh.positions = {}
        return {}

    def _op_reset(self, msg: dict) -> dict:
        from repro.core.booster import GradientBooster, _PagedEngine

        n_trees = 0
        for sh in self.shards.values():
            booster = GradientBooster.resume(msg["checkpoint"], sh.dm)
            # the out-of-core engine's preparation: base margins, then the
            # restored forest replayed over the shard's pages
            _PagedEngine(booster, sh.dm, sh.labels_np, fresh=True).replay(booster.trees)
            sh.margins = booster.margins_
            sh.g = sh.h = None
            sh.positions = {}
            n_trees = len(booster.trees)
        return {"n_trees": n_trees}

    def _op_begin_tree(self, msg: dict) -> dict:
        import jax.numpy as jnp

        fault_inject.fire(
            "elastic.worker.iteration",
            worker=self.name,
            iteration=int(msg["iteration"]),
        )
        sums: dict[int, tuple[float, float]] = {}
        for sid, sh in self.shards.items():
            if sh.margins is None:
                raise RuntimeError("begin_tree before set_base_margin/reset")
            sh.g, sh.h = self.objective.grad_hess(jnp.asarray(sh.margins), sh.labels)
            sh.positions = {
                i: jnp.zeros(nr, jnp.int32)
                for i, (_ro, nr) in enumerate(sh.pages.page_extents)
            }
            sums[sid] = (float(jnp.sum(sh.g)), float(jnp.sum(sh.h)))
        return {"sums": sums}

    def _op_hist(self, msg: dict) -> dict:
        import jax.numpy as jnp
        import numpy as np

        from repro.kernels import ops

        sh = self.shards[int(msg["shard"])]
        node_map = msg["node_map"]
        hist = ops.build_histogram_paged(
            sh.pages.stream(),
            sh.g,
            sh.h,
            sh.positions,
            int(msg["offset"]),
            int(msg["n_build"]),
            sh.dm.n_bins,
            node_map=None if node_map is None else jnp.asarray(node_map),
            impl=self.params.kernel_impl,
        )
        return {"hist": np.asarray(hist)}

    def _op_partition(self, msg: dict) -> dict:
        import jax.numpy as jnp
        import numpy as np

        from repro.core.histcache import level_row_counts
        from repro.kernels import ops

        sh = self.shards[int(msg["shard"])]
        feature = jnp.asarray(msg["feature"])
        split_bin = jnp.asarray(msg["split_bin"])
        default_left = jnp.asarray(msg["default_left"])
        is_leaf = jnp.asarray(msg["is_leaf"])
        window = msg["count_window"]
        counts = None
        for sp in sh.pages.stream():
            sh.positions[sp.index] = ops.partition_rows(
                sp.device,
                sh.positions[sp.index],
                feature,
                split_bin,
                default_left,
                is_leaf,
                impl=self.params.kernel_impl,
            )
            if window is not None:
                c = level_row_counts(
                    sh.positions[sp.index], int(window[0]), int(window[1])
                )
                counts = c if counts is None else counts + c
        return {"counts": None if counts is None else np.asarray(counts)}

    def _op_leaf_sums(self, msg: dict) -> dict:
        import numpy as np

        from repro.core.histcache import node_grad_sums

        sh = self.shards[int(msg["shard"])]
        n_nodes = int(msg["n_nodes"])
        total = np.zeros((2, n_nodes), np.float32)
        for i, (ro, nr) in enumerate(sh.pages.page_extents):
            total += np.asarray(node_grad_sums(
                sh.positions[i], sh.g[ro : ro + nr], sh.h[ro : ro + nr], n_nodes
            ))
        return {"sums": total}

    def _op_finish_tree(self, msg: dict) -> dict:
        import numpy as np

        leaf = np.asarray(msg["tree"]["leaf_value"])
        lr = float(msg["learning_rate"])
        for sh in self.shards.values():
            # identical arithmetic to the out-of-core engine's margin update
            # and replay (repro.core.booster._PagedEngine.update) — so a
            # checkpoint-reset worker reproduces these margins bit-for-bit
            for i, (ro, nr) in enumerate(sh.pages.page_extents):
                pos = np.asarray(sh.positions[i])
                sh.margins[ro : ro + nr] += lr * leaf[pos]
            sh.g = sh.h = None
            sh.positions = {}
        return {}

    def _op_ping(self, msg: dict) -> dict:
        return {"name": self.name}

    def _op_shutdown(self, msg: dict) -> dict:
        return {}


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--name", required=True)
    parser.add_argument("--heartbeat", required=True)
    parser.add_argument("--heartbeat-interval", type=float, default=0.5)
    args = parser.parse_args(argv)

    _start_heartbeat(args.heartbeat, args.heartbeat_interval)
    fault_inject.install_from_env()

    # the frame protocol owns the original stdout; stray prints go to stderr
    out_fd = os.dup(sys.stdout.fileno())
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    in_fh = os.fdopen(os.dup(sys.stdin.fileno()), "rb")

    from repro.distributed.elastic import recv_msg_blocking, send_msg

    state = _WorkerState(args.name)
    while True:
        msg = recv_msg_blocking(in_fh)
        if msg is None:  # coordinator closed the pipe
            break
        op = msg.get("op", "")
        try:
            fault_inject.fire("elastic.rpc", worker=args.name, op=op)
            reply = state.handle(msg)
        except Exception as err:
            reply = {
                "error": f"{type(err).__name__}: {err}",
                "transient": isinstance(err, (OSError, TimeoutError, ConnectionError)),
                "traceback": traceback.format_exc(),
            }
        reply["req_id"] = msg.get("req_id")
        send_msg(out_fd, reply)
        if op == "shutdown":
            break


if __name__ == "__main__":
    main()
