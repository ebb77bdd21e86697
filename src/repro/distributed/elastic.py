"""Elastic fault-tolerant GBDT training over subprocess workers.

`ElasticTrainer` is Ray-Trainer-shaped (a coordinator plus N data-parallel
workers, each streaming its own on-disk shard) but runs on plain
``subprocess`` + pipes so the failure surface is real: a worker that dies is
a dead OS process, not a mocked exception. The design leans on two existing
pillars instead of inventing new distributed state:

  the generic growth driver   the coordinator runs `core.tree
                              .tree_growth_driver` exactly like every other
                              builder; its HistFn sums per-shard histograms
                              returned over RPC (in shard-id order, so the
                              f32 total is independent of *which worker*
                              serves a shard), its PartitionFn broadcasts
                              the split arrays and sums the returned row
                              counts, and its LeafSumsFn sums the shards'
                              per-node gradient sums. All split evaluation, subtraction
                              planning, and tree layout stay centralized and
                              bit-identical to the single-process builders.

  resume as the recovery      the coordinator checkpoints per iteration
  primitive                   through the hardened atomic
                              `GradientBooster.save`; when a worker dies
                              (exit-code watch, pipe EOF, heartbeat staleness,
                              or RPC deadline) its shards are re-assigned to
                              the least-loaded survivor and *every* worker
                              reloads margins from the last durable
                              checkpoint via `GradientBooster.resume` — the
                              same replay path the single-process crash test
                              pins bit-for-bit. Because shard histograms do
                              not depend on worker assignment, the recovered
                              run grows the same forest the uninterrupted run
                              would (the chaos test's acceptance bar).

Worker death injected by `repro.fault` (the plan rides the
``REPRO_FAULT_PLAN`` env var into the worker subprocess) is how the chaos
tests script "kill worker w1 at iteration 3" deterministically.

RPC discipline: requests carry a ``req_id`` and replies echo it, so a
timed-out request's late reply is discarded rather than mismatched. Worker
errors marked transient (I/O class) are retried under ``ElasticConfig.retry``
— every op the coordinator retries is idempotent (``begin_tree`` resets
per-tree state; ``hist`` and ``leaf_sums`` are pure reads; ``partition`` re-routes rows to
freshly-split children whose rows are not yet re-partitioned anywhere else).
``finish_tree`` mutates margins cumulatively and is therefore *never*
retried: if it fails, the coordinator falls back to checkpoint recovery,
which rebuilds margins from scratch.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
import select
import shutil
import struct
import subprocess
import sys
import time
from typing import Any, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import objectives as obj_lib
from repro.core.booster import (
    BoosterParams,
    GradientBooster,
    bin_valid_from_cuts,
)
from repro.core.histcache import HistogramStore
from repro.core.policy import sampling_requested
from repro.core.quantile import HistogramCuts
from repro.core.tree import TreeArrays, tree_growth_driver
from repro.data.pages import TransferStats
from repro.fault import inject as fault_inject
from repro.fault.retry import RetryPolicy

_HDR = struct.Struct("!Q")


# ------------------------------------------------------------------- framing
def send_msg(fd: int, obj: Any) -> None:
    """Length-prefixed pickle frame onto a pipe fd (loops over short writes)."""
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    data = _HDR.pack(len(payload)) + payload
    view = memoryview(data)
    while view:
        n = os.write(fd, view)
        view = view[n:]


def recv_msg_blocking(fh) -> Any | None:
    """Read one frame from a buffered binary file; None on clean EOF."""
    hdr = fh.read(_HDR.size)
    if not hdr:
        return None
    if len(hdr) < _HDR.size:
        raise EOFError("truncated frame header")
    (size,) = _HDR.unpack(hdr)
    payload = fh.read(size)
    if len(payload) < size:
        raise EOFError("truncated frame payload")
    return pickle.loads(payload)


def _read_exact(fd: int, n: int, deadline: float) -> bytes:
    """Read exactly n bytes from fd before `deadline` (monotonic seconds)."""
    chunks: list[bytes] = []
    got = 0
    while got < n:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError(f"deadline exceeded after {got}/{n} bytes")
        ready, _, _ = select.select([fd], [], [], min(remaining, 0.5))
        if not ready:
            continue
        chunk = os.read(fd, n - got)
        if not chunk:
            raise EOFError("pipe closed")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def recv_msg_deadline(fd: int, deadline: float) -> Any:
    (size,) = _HDR.unpack(_read_exact(fd, _HDR.size, deadline))
    return pickle.loads(_read_exact(fd, size, deadline))


# ---------------------------------------------------------------- exceptions
class ElasticError(RuntimeError):
    """Unrecoverable elastic-training failure (budget exhausted, fatal op)."""


class WorkerFailure(ElasticError):
    """One worker is gone or unresponsive; recovery should handle it."""

    def __init__(self, worker: str, reason: str):
        self.worker = worker
        self.reason = reason
        super().__init__(f"worker {worker}: {reason}")


class TransientWorkerError(ElasticError):
    """The worker survived but an op hit a transient (I/O-class) error."""


class WorkerError(ElasticError):
    """The worker raised a deterministic application error; retrying or
    recovering cannot help — propagate with the worker's traceback."""


# -------------------------------------------------------------- worker handle
class WorkerHandle:
    """One subprocess worker: pipes, heartbeat file, request/reply framing."""

    def __init__(
        self,
        name: str,
        workdir: str,
        *,
        python: str | None = None,
        env_extra: dict[str, str] | None = None,
        heartbeat_interval: float = 0.5,
    ):
        self.name = name
        self.shards: list[int] = []
        self.broken = False
        self._req_id = 0
        self.heartbeat_path = os.path.join(workdir, f"heartbeat_{name}")
        env = dict(os.environ)
        env.update(env_extra or {})
        self.proc = subprocess.Popen(
            [
                python or sys.executable,
                "-m",
                "repro.distributed.elastic_worker",
                "--name",
                name,
                "--heartbeat",
                self.heartbeat_path,
                "--heartbeat-interval",
                str(heartbeat_interval),
            ],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            env=env,
        )

    def alive(self) -> bool:
        return not self.broken and self.proc.poll() is None

    def heartbeat_age(self) -> float:
        try:
            return time.time() - os.path.getmtime(self.heartbeat_path)
        except OSError:
            return float("inf")

    def request(self, msg: dict, timeout: float) -> dict:
        """One RPC round-trip; raises `WorkerFailure` on death/deadline,
        `TransientWorkerError`/`WorkerError` on in-worker exceptions."""
        if not self.alive():
            raise WorkerFailure(self.name, f"not alive (exit code {self.proc.poll()})")
        self._req_id += 1
        msg = dict(msg, req_id=self._req_id)
        try:
            send_msg(self.proc.stdin.fileno(), msg)
        except (BrokenPipeError, OSError) as err:
            self.broken = True
            raise WorkerFailure(self.name, f"request pipe broke ({err})") from err
        deadline = time.monotonic() + timeout
        while True:
            try:
                reply = recv_msg_deadline(self.proc.stdout.fileno(), deadline)
            except TimeoutError as err:
                # a hung worker holds no further promises: mark it broken so
                # recovery terminates and replaces it
                self.broken = True
                raise WorkerFailure(
                    self.name, f"rpc {msg.get('op')!r} timed out after {timeout}s"
                ) from err
            except (EOFError, OSError) as err:
                self.broken = True
                code = self.proc.poll()
                raise WorkerFailure(
                    self.name, f"died during rpc {msg.get('op')!r} (exit code {code})"
                ) from err
            if reply.get("req_id") == self._req_id:
                break
            # stale reply from an earlier timed-out request: discard
        if "error" in reply:
            if reply.get("transient"):
                raise TransientWorkerError(f"{self.name}: {reply['error']}")
            raise WorkerError(
                f"{self.name}: {reply['error']}\n{reply.get('traceback', '')}"
            )
        return reply

    def terminate(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:  # pragma: no cover
                self.proc.kill()
                self.proc.wait()
        for fh in (self.proc.stdin, self.proc.stdout):
            try:
                fh.close()
            except OSError:  # pragma: no cover
                pass


# Workers are CPU processes. A chip belongs to the one process that loaded
# it first, so a worker that inherited a TPU platform would fail or hang.
WORKER_ENV = {"JAX_PLATFORMS": "cpu"}


# -------------------------------------------------------------------- config
@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Knobs of the elastic orchestrator (everything time/failure related).

    ``rpc_timeout_s`` must cover a worker's first-call jit compiles; the
    chaos tests lower it only for the hang-detection scenario. ``respawn``
    False re-assigns a dead worker's shards to survivors (capacity shrinks,
    the run continues — "elastic"); True also spawns a replacement worker
    (without the fault plan: a planned crash must not loop forever).
    """

    n_workers: int = 2
    rpc_timeout_s: float = 300.0
    heartbeat_timeout_s: float = 120.0
    heartbeat_interval_s: float = 0.5
    max_recoveries: int = 3
    respawn: bool = False
    checkpoint_every: int = 1
    retry: RetryPolicy = RetryPolicy(max_attempts=3, base_delay=0.1)
    python: str | None = None  # interpreter for workers (None = sys.executable)
    env: dict[str, str] | None = None  # extra env for workers, over WORKER_ENV

    def __post_init__(self) -> None:
        if self.n_workers < 1:
            raise ValueError(f"n_workers must be >= 1; got {self.n_workers}")
        if self.checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1; got {self.checkpoint_every}")


# ----------------------------------------------------------------- shard prep
def prepare_shards(
    X: np.ndarray,
    y: np.ndarray,
    n_shards: int,
    root: str,
    *,
    max_bin: int = 256,
    page_bytes: int | None = None,
) -> list[str]:
    """Quantize once (shared cuts) and write one on-disk page cache per
    contiguous row shard; returns the shard cache dirs.

    Every shard is binned with the *same* `HistogramCuts` (sketched over the
    full matrix), so the elastic run's histograms sum to exactly what a
    single-process run over the concatenated rows builds — the chaos test's
    forest-equality oracle depends on this.
    """
    from repro.core.ellpack import DEFAULT_PAGE_BYTES
    from repro.data.dmatrix import ArrayDMatrix, IterDMatrix

    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    cuts = ArrayDMatrix(X, y, max_bin=max_bin).cuts
    bounds = np.linspace(0, X.shape[0], n_shards + 1).astype(int)
    dirs: list[str] = []
    for s in range(n_shards):
        d = os.path.join(root, f"shard_{s:04d}")
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        IterDMatrix(
            [(X[lo:hi], y[lo:hi])],
            max_bin=max_bin,
            cuts=cuts,
            cache_dir=d,
            page_bytes=page_bytes or DEFAULT_PAGE_BYTES,
        )
        dirs.append(d)
    return dirs


# ------------------------------------------------------------------- trainer
class ElasticTrainer:
    """Coordinator for elastic data-parallel training over shard dirs.

    Parameters
    ----------
    shard_dirs : on-disk page caches (one per shard, shared cuts — see
        `prepare_shards`); shard i starts on worker ``i % n_workers``.
    params : model hyperparameters. Gradient-based sampling is not supported
        elastically (the sampled fast path holds per-fit RNG state the
        recovery replay cannot reproduce across reassignment) and raises.
    checkpoint_dir : where per-iteration checkpoints land (atomic
        `GradientBooster.save`; ``<dir>.prev`` keeps the last-good
        generation).
    config : `ElasticConfig`.
    fault_plan : optional `repro.fault.FaultPlan` shipped to the *initial*
        workers via ``REPRO_FAULT_PLAN`` (chaos tests).
    """

    def __init__(
        self,
        shard_dirs: Sequence[str],
        params: BoosterParams,
        *,
        checkpoint_dir: str,
        config: ElasticConfig | None = None,
        fault_plan: fault_inject.FaultPlan | None = None,
        verbose: bool = False,
    ):
        if not shard_dirs:
            raise ValueError("need at least one shard dir")
        if sampling_requested(params.sampling):
            raise NotImplementedError(
                "ElasticTrainer does not support gradient-based sampling: the "
                "compacted-page fast path carries per-fit sampling state that "
                "checkpoint recovery cannot replay across shard reassignment. "
                "Use SamplingConfig(method='none') for elastic runs."
            )
        self.shard_dirs = list(shard_dirs)
        self.params = params
        self.cfg = config or ElasticConfig()
        self.checkpoint_dir = checkpoint_dir
        self.fault_plan = fault_plan
        self.verbose = verbose
        self.objective = obj_lib.get_objective(params.objective)
        self.stats = TransferStats()
        self.recoveries = 0
        self.events: list[str] = []
        self._workers: list[WorkerHandle] = []
        self._owner: dict[int, WorkerHandle] = {}
        self._spawned = 0
        self._saved = False  # a checkpoint from THIS run exists on disk
        self._workdir = f"{checkpoint_dir}.workers"
        self.base_margin_: float | None = None
        self._hist_store = HistogramStore(
            enabled=params.hist_subtraction,
            transfer_stats=self.stats,
            retry=self.cfg.retry,
        )

        # shard 0's sidecar is the authoritative quantization for the run
        # (prepare_shards wrote every shard with identical cuts)
        meta = np.load(os.path.join(self.shard_dirs[0], "dmatrix.npz"))
        self.cuts = HistogramCuts(
            values=meta["cut_values"],
            ptrs=meta["cut_ptrs"],
            min_vals=meta["cut_min_vals"],
        )
        self.n_bins = int(meta["n_bins"])
        self._bin_valid = bin_valid_from_cuts(self.cuts, self.n_bins)

    # ------------------------------------------------------------------ infra
    def _log(self, msg: str) -> None:
        self.events.append(msg)
        if self.verbose:
            print(f"[elastic] {msg}", file=sys.stderr)

    def _spawn_worker(self, *, with_faults: bool) -> WorkerHandle:
        env = {**WORKER_ENV, **(self.cfg.env or {})}
        if with_faults and self.fault_plan is not None:
            env[fault_inject.ENV_VAR] = self.fault_plan.to_json()
        else:
            # replacements must not inherit the plan: a scripted crash that
            # respawned into the same crash would loop forever
            env[fault_inject.ENV_VAR] = ""
        name = f"w{self._spawned}"
        self._spawned += 1
        handle = WorkerHandle(
            name,
            self._workdir,
            python=self.cfg.python,
            env_extra=env,
            heartbeat_interval=self.cfg.heartbeat_interval_s,
        )
        meta = dataclasses.asdict(self.params)
        meta["sampling"] = dataclasses.asdict(self.params.sampling)
        self._request(handle, {"op": "init", "params": meta})
        self._log(f"spawned {name} (pid {handle.proc.pid})")
        return handle

    def _request(self, worker: WorkerHandle, msg: dict, *, retryable: bool = True) -> dict:
        """RPC with transient-error retry (idempotent ops only)."""
        if not retryable:
            return worker.request(msg, self.cfg.rpc_timeout_s)
        return self.cfg.retry.call(
            lambda: worker.request(msg, self.cfg.rpc_timeout_s),
            retryable=(TransientWorkerError,),
            stats=self.stats,
            describe=f"rpc {msg.get('op')} -> {worker.name}",
        )

    def _assign(self, sid: int, worker: WorkerHandle) -> None:
        worker.shards.append(sid)
        self._owner[sid] = worker
        self._request(worker, {"op": "open_shard", "shard": sid, "dir": self.shard_dirs[sid]})

    def _check_workers(self) -> None:
        """Exit-code + heartbeat watchdog, run between iterations."""
        for w in self._workers:
            code = w.proc.poll()
            if code is not None:
                w.broken = True
                raise WorkerFailure(w.name, f"process exited with code {code}")
            age = w.heartbeat_age()
            if age > self.cfg.heartbeat_timeout_s:
                w.broken = True
                raise WorkerFailure(
                    w.name,
                    f"heartbeat stale for {age:.1f}s "
                    f"(timeout {self.cfg.heartbeat_timeout_s}s)",
                )

    # ------------------------------------------------------------------ setup
    def _start_workers(self) -> None:
        os.makedirs(self._workdir, exist_ok=True)
        self._workers = [
            self._spawn_worker(with_faults=True) for _ in range(self.cfg.n_workers)
        ]
        for sid in range(len(self.shard_dirs)):
            self._assign(sid, self._workers[sid % len(self._workers)])
        # base margin from aggregated per-shard label stats: both built-in
        # objectives' base scores are functions of the label mean (mean /
        # logit of clipped mean), so one synthetic-mean call is exact
        total, count = 0.0, 0
        for sid in sorted(self._owner):
            rep = self._request(self._owner[sid], {"op": "shard_stats", "shard": sid})
            total += rep["label_sum"]
            count += rep["label_count"]
        if self.params.base_score is not None:
            self.base_margin_ = float(self.params.base_score)
        else:
            mean = np.float32(total / max(count, 1))
            self.base_margin_ = float(
                self.objective.base_margin(np.full(1, mean, np.float32))
            )
        self._broadcast_margins(None)

    def _fresh_booster(self) -> GradientBooster:
        booster = GradientBooster(self.params)
        booster.cuts = self.cuts
        booster.base_margin_ = self.base_margin_
        booster.stats = self.stats
        return booster

    def _broadcast_margins(self, checkpoint: str | None) -> None:
        """Reset every worker's margins: from a checkpoint (resume replay) or
        to the flat base margin (fresh start)."""
        for w in self._workers:
            if checkpoint is None:
                self._request(w, {"op": "set_base_margin", "value": self.base_margin_})
            else:
                self._request(w, {"op": "reset", "checkpoint": checkpoint})

    # ------------------------------------------------------------------- fit
    def fit(self) -> GradientBooster:
        """Train to ``params.n_estimators`` trees, recovering worker deaths.

        Returns a fitted `GradientBooster` (trees + cuts + base margin); the
        final forest is also durably checkpointed at ``checkpoint_dir``.
        """
        p = self.params
        try:
            self._start_workers()  # computes base_margin_ before any booster
            booster = self._fresh_booster()
            while len(booster.trees) < p.n_estimators:
                it = len(booster.trees)
                try:
                    self._check_workers()
                    tree = self._build_tree(it)
                    booster.trees.append(tree)
                    self._finish_tree(tree)
                    if (it + 1) % self.cfg.checkpoint_every == 0 or (
                        it + 1 == p.n_estimators
                    ):
                        booster.save(self.checkpoint_dir)
                        self._saved = True
                except WorkerFailure as failure:
                    while True:
                        try:
                            booster = self._recover(failure)
                            break
                        except WorkerFailure as another:
                            failure = another
            return booster
        finally:
            self._shutdown()

    # ------------------------------------------------------------- tree build
    def _build_tree(self, iteration: int) -> TreeArrays:
        p = self.params
        tp = p.tree_params()

        # begin_tree on every worker: compute gradients from current margins,
        # zero the positions, return per-shard (sum_g, sum_h)
        shard_sums: dict[int, tuple[float, float]] = {}
        for w in self._workers:
            rep = self._request(w, {"op": "begin_tree", "iteration": iteration})
            for sid, (sg, sh) in rep["sums"].items():
                shard_sums[int(sid)] = (sg, sh)
        # f32 accumulation in shard-id order: the totals are independent of
        # which worker owns which shard, so recovery preserves them exactly
        total_g = np.float32(0.0)
        total_h = np.float32(0.0)
        for sid in sorted(shard_sums):
            total_g = np.float32(total_g + np.float32(shard_sums[sid][0]))
            total_h = np.float32(total_h + np.float32(shard_sums[sid][1]))

        def hist_fn(offset: int, count: int, plan) -> jnp.ndarray:
            node_map = None if plan.node_map is None else np.asarray(plan.node_map)
            total: np.ndarray | None = None
            for sid in sorted(self._owner):
                rep = self._request(
                    self._owner[sid],
                    {
                        "op": "hist",
                        "shard": sid,
                        "offset": offset,
                        "count": plan.count,
                        "n_build": plan.n_build,
                        "node_map": node_map,
                    },
                )
                part = rep["hist"]
                total = part if total is None else total + part
            return jnp.asarray(total)

        def partition_fn(feature, split_bin, default_left, is_leaf, count_window):
            msg = {
                "op": "partition",
                "feature": np.asarray(feature),
                "split_bin": np.asarray(split_bin),
                "default_left": np.asarray(default_left),
                "is_leaf": np.asarray(is_leaf),
                "count_window": count_window,
            }
            counts: np.ndarray | None = None
            for sid in sorted(self._owner):
                rep = self._request(self._owner[sid], dict(msg, shard=sid))
                c = rep["counts"]
                if c is not None:
                    counts = c if counts is None else counts + c
            return None if counts is None else jnp.asarray(counts)

        def leaf_sums_fn():
            total = np.zeros((2, tp.n_total_nodes), np.float32)
            for sid in sorted(self._owner):
                rep = self._request(
                    self._owner[sid],
                    {"op": "leaf_sums", "shard": sid, "n_nodes": tp.n_total_nodes},
                )
                total += rep["sums"]
            return jnp.asarray(total[0]), jnp.asarray(total[1])

        grow = tree_growth_driver(tp)
        return grow(
            hist_fn,
            partition_fn,
            leaf_sums_fn,
            jnp.float32(total_g),
            jnp.float32(total_h),
            self.n_bins,
            self._bin_valid,
            tp,
            cut_values=self.cuts.values,
            cut_ptrs=self.cuts.ptrs,
            hist_cache=self._hist_store,
        )

    def _finish_tree(self, tree: TreeArrays) -> None:
        arrays = {f: np.asarray(getattr(tree, f)) for f in TreeArrays._fields}
        for w in self._workers:
            # NOT retryable: margins += leaf is cumulative, a double-apply
            # would corrupt them. Failure here falls through to recovery,
            # which rebuilds margins from the checkpoint.
            self._request(
                w,
                {"op": "finish_tree", "tree": arrays, "learning_rate": self.params.learning_rate},
                retryable=False,
            )

    # --------------------------------------------------------------- recovery
    def _recover(self, failure: WorkerFailure) -> GradientBooster:
        self.recoveries += 1
        if self.recoveries > self.cfg.max_recoveries:
            raise ElasticError(
                f"giving up after {self.cfg.max_recoveries} recoveries "
                f"(last failure — {failure})"
            ) from failure
        self._log(f"recovering from failure: {failure}")

        dead = [w for w in self._workers if w.broken or w.proc.poll() is not None]
        for w in dead:
            self._log(f"terminating dead worker {w.name}")
            w.terminate()
            self._workers.remove(w)
        orphans = sorted(sid for sid, w in self._owner.items() if w not in self._workers)

        if self.cfg.respawn or not self._workers:
            for _ in range(max(len(dead), 1) if not self._workers else len(dead)):
                self._workers.append(self._spawn_worker(with_faults=False))
        for sid in orphans:
            target = min(self._workers, key=lambda w: len(w.shards))
            self._log(f"re-assigning shard {sid} -> {target.name}")
            self._assign(sid, target)

        # reload the forest from the last durable checkpoint (falling back to
        # <dir>.prev if the newest generation is damaged), then reset every
        # worker's margins from it — survivors included, so margins always
        # correspond exactly to the restored forest
        ckpt = (
            GradientBooster.last_good_checkpoint(self.checkpoint_dir)
            if self._saved
            else None
        )
        if ckpt is None:
            self._log("no durable checkpoint yet: restarting forest from scratch")
            booster = self._fresh_booster()
            self._broadcast_margins(None)
        else:
            booster = GradientBooster.load(ckpt)
            booster.stats = self.stats
            self._log(f"resumed {len(booster.trees)} trees from {ckpt}")
            self._broadcast_margins(ckpt)
        return booster

    # --------------------------------------------------------------- shutdown
    def _shutdown(self) -> None:
        for w in self._workers:
            try:
                if w.alive():
                    w.request({"op": "shutdown"}, timeout=5.0)
            except ElasticError:
                pass
            w.terminate()
        self._workers = []
        self._owner = {}
        shutil.rmtree(self._workdir, ignore_errors=True)
