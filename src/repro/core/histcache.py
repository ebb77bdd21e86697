"""Tiered histogram store (subtraction cache + budget-aware device/host tiers).

Every split partitions a parent node's rows into its two children, and the
gradient histogram is additive over rows, so

    hist(parent) == hist(left) + hist(right)        (exactly, per (g, h) bin)

holds at every level. Instead of building histograms for *all* 2^d nodes of
level d, the builder therefore only needs the smaller child of each split
pair — the sibling is derived as ``parent - built``. This roughly halves the
dominant BuildHistograms cost (and, in the out-of-core builder, the per-page
scatter work of every disk->host->device pass).

The retained histograms are themselves a device-memory liability: at depth d
the previous level holds ``2^(d-1) * m * n_bins * 2 * 4`` bytes, which at
depth >= 10 rivals the ELLPACK matrix the paper's Table-1 budget tracks.
`HistogramStore` therefore manages them as a *tiered*, byte-budgeted store:

  device tier   hot histograms, ready for subtraction (``budget_bytes`` caps
                this tier; None = unlimited — bit-for-bit the old cache);
  host tier     cold histograms spilled off-device. The spill is *async*:
                eviction issues ``copy_to_host_async`` and returns, so the
                device->host copy overlaps the next build pass; the pinned
                host buffer materializes at a completion barrier
                (`_host_buffer`) the moment anything needs it, which keeps a
                fetch racing an in-flight spill bit-exact. A plan that needs
                an entry back stages it through the same
                `repro.pipeline.PageStream` engine the ELLPACK pages use, so
                the fetch leg shares the pages' staging ledger (the round
                trip is accounted in `TransferStats.hist_spill_bytes` /
                ``hist_fetch_bytes`` next to the page traffic);
  ancestors     with ``retained_levels=K >= 2``, up to K-1 generations of
                expanded parents are retired on-device instead of evicted, so
                a popped node whose own histogram was spilled can be derived
                as ``ancestor - sum(built siblings along the path)`` without
                any transfer (multi-level subtraction) — and only rebuilt
                from rows when no tier can resolve it.

Every `plan`/`plan_node` therefore runs an explicit resolution step, recorded
on the returned ``LevelPlan.source``:

  "build"    full build from rows (root, store disabled, nothing resolvable)
  "device"   parent histogram device-resident: classic subtraction
  "fetched"  parent was spilled; staged back from the host tier (bit-exact)
  "derived"  parent reconstructed from a device-resident ancestor chain
             (exact up to f32 accumulation order)

Eviction order under budget pressure: depthwise holds exactly one level
entry (the next plan's parent — older levels have no read path and are
dropped free the moment the next level lands), so levels leave the device in
level order as the build descends past the budget; best-first growth spills
frontier-node entries lowest-gain-first (LRU by frontier gain — low-gain
leaves are popped last, if ever). Retired node ancestors are dropped (not
spilled) only after every spillable entry left the device: they exist to
save transfers, and are re-derivable.

`HistogramStore` owns that machinery for all three builders:

  plan(count, level_counts)  partition the level's nodes into a *build* set
                             (smaller child of each pair, by row count from
                             repartition) and a *derive* set; emits a
                             `LevelPlan` whose ``node_map`` compacts build
                             nodes to ``count // 2`` kernel slots (-1 for
                             derive nodes — their rows contribute to no bin)
  expand(plan, built)        reconstruct the full level histogram from the
                             compact build histogram and the cached previous
                             level (``derived = parent - built``), then store
                             it for the next level (spilling per the budget)

Best-first (lossguide) growth uses the per-node sibling API instead: the
frontier pops one leaf at a time, so histograms are stored per heap node id
rather than per level:

  put_node(node, hist)            retain one node's (m, n_bins, 2) histogram
                                  while it sits on the frontier
  plan_node(parent, child_counts) a 2-node `LevelPlan` for the popped
                                  parent's children: build only the smaller
                                  child (ties build left, same rule as the
                                  level plan) and derive the sibling from the
                                  resolved parent histogram
  expand_node(parent, plan, built)  reconstruct both children, store them as
                                  new frontier nodes, retire (K >= 2) or
                                  evict the parent
  note_gain(node, gain)           record the frontier gain that orders spills
  discard_node(node)              drop a node that left the frontier (became
                                  a permanent leaf)

At most one histogram per frontier leaf is retained (plus <= K-1 retired
ancestors per path), so the per-node store holds <= max_leaves hot entries.

The node choice uses exact row counts (`level_row_counts` over the positions
produced by RepartitionInstances), so every builder — in-core, paged
out-of-core, and distributed — makes identical build/derive decisions and the
resulting trees match the full-build baseline bit-for-bit up to f32
accumulation order. The distributed builders drive one host-side store over
psum'd histograms and row counts, so spill decisions are made once, from
state every shard shares.

Shapes stay static under jit: at depth >= 1 exactly ``count // 2`` slots are
built (dead pairs — parent did not split — waste a slot holding zeros; their
children are masked as non-growable by the driver, so the garbage sibling
derivation for them is never consumed).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pages import TransferStats
from repro.fault import inject as fault_inject
from repro.fault.retry import RetryPolicy
from repro.kernels import ref

Array = jax.Array

_HOT = float("inf")  # priority of entries whose frontier gain is not yet known


class LevelPlan(NamedTuple):
    """Build/derive split of one node window, plus how the parent resolved.

    ``node_map`` is None for a full build (root level, cache disabled, or no
    counts yet); otherwise ``node_map[j]`` maps level-local node j to its
    compacted build slot, or -1 if j's histogram is derived by subtraction.
    ``source`` records the resolution step: "build" (full rebuild from rows),
    "device" (parent hot), "fetched" (parent staged back from the host tier),
    or "derived" (parent reconstructed from a device ancestor chain).
    """

    node_map: Array | None  # (count,) int32, or None = build everything
    n_build: int  # static: number of histogram slots the kernel materializes
    count: int  # static: nodes at this level
    source: str = "build"
    # global node ids of the build slots, in slot order — the fused-kernel
    # fast path (`ops.build_histogram_nodes`): HistFns that honor it skip the
    # caller-side window mask and the node_map remap entirely (one launch
    # instead of lookup + scatter), and the set may be non-contiguous
    # (batched lossguide pops). None on hand-built plans; node_map consumers
    # (the distributed shard steps) ignore it.
    build_nodes: Array | None = None


@dataclasses.dataclass
class HistCacheStats:
    """Build-vs-derive ledger (levels >= 1; the root build is counted by the
    caller since the cache never sees the root row count).

    Row totals accumulate as device scalars — no host sync in the level loop —
    and convert to floats only when `built_rows` / `total_rows` are read.
    """

    levels: int = 0
    built_nodes: int = 0
    derived_nodes: int = 0
    # parent histograms reconstructed from an ancestor chain (multi-level
    # subtraction) instead of a host fetch or a row rebuild
    chain_derived_nodes: int = 0
    # per-node plans that fell back to a full rebuild because no tier could
    # resolve the parent histogram
    rebuilt_nodes: int = 0
    _built_rows_acc: Array | None = dataclasses.field(default=None, repr=False)
    _total_rows_acc: Array | None = dataclasses.field(default=None, repr=False)

    def _add_rows(self, built: Array, total: Array) -> None:
        # f32 accumulation: int32 would wrap past 2^31 rows over a long fit
        # (10M rows x deep trees x hundreds of rounds), and int64 needs x64
        built = built.astype(jnp.float32)
        total = total.astype(jnp.float32)
        if self._built_rows_acc is None:
            self._built_rows_acc, self._total_rows_acc = built, total
        else:
            self._built_rows_acc = self._built_rows_acc + built
            self._total_rows_acc = self._total_rows_acc + total

    @property
    def built_rows(self) -> float:
        """Rows scanned into built node histograms (subtraction mode)."""
        return float(self._built_rows_acc) if self._built_rows_acc is not None else 0.0

    @property
    def total_rows(self) -> float:
        """Rows a full per-node build would have scanned."""
        return float(self._total_rows_acc) if self._total_rows_acc is not None else 0.0

    @property
    def node_rows_ratio(self) -> float:
        """How many times fewer node-rows the subtraction build materializes
        (levels >= 1). >= 2 when children split evenly."""
        built = self.built_rows
        return self.total_rows / built if built else 1.0


@functools.partial(jax.jit, static_argnames=("count",))
def level_row_counts(positions: Array, offset: int, count: int) -> Array:
    """Rows per window-local node; frozen/out-of-window rows count nowhere.

    ``offset`` is traced (not static): best-first growth calls this with a
    fresh 2-node window per popped leaf, and a static offset would recompile
    on every pop.
    """
    lp = positions.astype(jnp.int32) - offset
    valid = (positions >= offset) & (lp < count)
    if count <= 64:
        # narrow window: a vectorized compare+sum beats XLA CPU's serialized
        # scatter (this runs once per level on the subtraction path only, so
        # its cost lands squarely in the sub-vs-full wall-clock gap)
        slots = jnp.arange(count, dtype=jnp.int32)
        hit = valid[:, None] & (lp[:, None] == slots[None, :])
        return jnp.sum(hit, axis=0).astype(jnp.int32)
    safe = jnp.where(valid, lp, count)  # overflow slot for non-window rows
    return jnp.zeros(count + 1, jnp.int32).at[safe].add(1)[:count]


@functools.partial(jax.jit, static_argnames=("n_nodes",))
def node_grad_sums(positions: Array, g: Array, h: Array, n_nodes: int) -> tuple[Array, Array]:
    """(sum g, sum h) of the rows at each global node id in ``[0, n_nodes)``;
    rows with a negative position count nowhere.

    Each sum is exact up to about one rounding, whatever the order of the
    rows (`kernels.ref.scatter_sum`), so builders that hold the same rows in
    other pages or shards agree to f32 precision once their partial sums
    are added."""
    valid = positions >= 0
    flat = jnp.where(valid, positions, 0).astype(jnp.int32)
    n = positions.shape[0]
    return tuple(
        ref.scatter_sum(flat, jnp.where(valid, w.astype(jnp.float32), 0.0), n_nodes, n)
        for w in (g, h)
    )


@jax.jit
def node_row_counts(positions: Array, nodes: Array) -> Array:
    """Rows per *global* node id in ``nodes`` (any subset, any order) — the
    non-contiguous counterpart of `level_row_counts`, used by batched
    lossguide pops where the popped parents' child windows do not form one
    contiguous range. ``nodes`` is small (2 per popped parent), so the
    broadcast compare is cheap."""
    hit = positions[None, :].astype(jnp.int32) == nodes[:, None].astype(jnp.int32)
    return jnp.sum(hit, axis=1).astype(jnp.int32)


def plan_level(count: int, level_counts: Array) -> tuple[Array, Array]:
    """(node_map, build_left) for one level: build the smaller child of each
    sibling pair (ties build left — deterministic, so every builder agrees)."""
    pairs = count // 2
    left = level_counts[0::2]
    right = level_counts[1::2]
    build_left = left <= right  # (pairs,)
    slots = jnp.arange(pairs, dtype=jnp.int32)
    node_map = jnp.stack(
        [jnp.where(build_left, slots, -1), jnp.where(build_left, -1, slots)],
        axis=1,
    ).reshape(count)
    return node_map, build_left


@functools.partial(jax.jit, static_argnames=("count",))
def _plan_level_fused(level_counts: Array, offset, count: int):
    """One jitted call for everything a subtraction plan derives from the
    level's row counts: (node_map, build_left, build_nodes, built_rows,
    total_rows). The eager per-level dispatch overhead of computing these
    one jnp op at a time was a measurable slice of the subtraction path's
    wall time (the BENCH_kernels speedup=0.90x regression)."""
    node_map, build_left = plan_level(count, level_counts)
    pairs = count // 2
    build_nodes = (
        offset + 2 * jnp.arange(pairs, dtype=jnp.int32) + jnp.where(build_left, 0, 1)
    ).astype(jnp.int32)
    built = jnp.sum(jnp.minimum(level_counts[0::2], level_counts[1::2]))
    total = jnp.sum(level_counts)
    return node_map, build_left, build_nodes, built, total


def expand_level(parent_hist: Array, built: Array, build_left: Array) -> Array:
    """Full level histogram from the compact build half: the built child keeps
    its histogram, the sibling is ``parent - built`` (exact up to f32 order)."""
    derived = parent_hist - built
    mask = build_left.reshape((-1,) + (1,) * (built.ndim - 1))
    left = jnp.where(mask, built, derived)
    right = jnp.where(mask, derived, built)
    pairs = built.shape[0]
    return jnp.stack([left, right], axis=1).reshape((2 * pairs,) + built.shape[1:])


# jitted alias for the eager level loops (elementwise: bit-identical jitted)
_expand_level_j = jax.jit(expand_level)


class HistogramStore:
    """Byte-budgeted, tiered retention of per-node histograms, and the
    build/derive planner for the next level or popped node.

    One instance per tree (or per forest — `reset` is called at the start of
    every driver run and clears the tiered state but keeps the accumulated
    `stats` and `transfer_stats`).

    Parameters
    ----------
    enabled : subtraction on/off (off = every plan is a full build).
    budget_bytes : device-tier byte budget. None = unlimited (the store
        degenerates bit-for-bit to the pre-tiered subtraction cache); 0 =
        everything spills to the host tier and every plan fetches.
    retained_levels : K >= 1. The best-first ancestor-chain depth: up to K-1
        generations of retired parents stay device-resident per path for
        transfer-free chain derivation. Depthwise always retains exactly the
        parent level (nothing reads older levels), so K only shapes per-node
        growth.
    transfer_stats : `TransferStats` sink for spill/fetch bytes (shares the
        page-traffic ledger when the caller passes the page set's stats).
    grad_transport : wire transport for the spill/fetch round trip
        (`repro.compress.GradQuantizer`): "raw" keeps today's f32 path bit
        for bit; "f16"/"bf16" halve and "int8" (per-array absmax scale)
        quarters the bytes each spilled histogram moves. Payloads are
        dequantized to f32 at fetch, before any accumulation, so only the
        stored values narrow — never the reconstruction order.
    """

    def __init__(
        self,
        enabled: bool = True,
        budget_bytes: int | None = None,
        retained_levels: int = 1,
        transfer_stats: TransferStats | None = None,
        retry: "RetryPolicy | None" = None,
        grad_transport: str = "raw",
    ):
        from repro.compress import GradQuantizer

        if budget_bytes is not None and budget_bytes < 0:
            raise ValueError(f"budget_bytes must be >= 0 or None, got {budget_bytes}")
        if retained_levels < 1:
            raise ValueError(f"retained_levels must be >= 1, got {retained_levels}")
        self.enabled = enabled
        self.budget_bytes = budget_bytes
        self.retained_levels = retained_levels
        self.transfer_stats = transfer_stats if transfer_stats is not None else TransferStats()
        self.retry = retry if retry is not None else RetryPolicy()
        self.quantizer = GradQuantizer.resolve(grad_transport)
        self.stats = HistCacheStats()
        self._device: dict[tuple, Array] = {}
        # host tier. A key whose copy is still in flight maps to None here
        # and holds its device array in ``_inflight`` until the completion
        # barrier (`_host_buffer`) materializes the pinned host buffer.
        self._host: dict[tuple, np.ndarray | None] = {}
        # in-flight async spills: key -> device array whose device->host copy
        # was issued but not yet awaited. Bounded by ``max_inflight_spills``
        # (the same double-buffering depth PageStream stages with): the
        # oldest copy is completed when a third spill would exceed it.
        self._inflight: dict[tuple, Array] = {}
        self.max_inflight_spills = 2
        self._nbytes: dict[tuple, int] = {}
        # int8 transport: per-key dequantization scale (device f32 scalar)
        self._qscale: dict[tuple, Array | None] = {}
        self._kind: dict[tuple, str] = {}  # "level" | "node" | "ancestor"
        self._priority: dict[tuple, float] = {}  # lower = colder = spills first
        self._stamp: dict[tuple, int] = {}  # insertion order tiebreak
        self._clock = 0
        self._dev_bytes = 0
        self._build_left: Array | None = None
        self._node_build_left: Array | None = None
        # per-parent build modes of the last `plan_nodes` batch (see there)
        self._batch_modes: list | None = None

    # ------------------------------------------------------------- tier plumbing
    def reset(self) -> None:
        self._device.clear()
        self._host.clear()
        self._inflight.clear()
        self._nbytes.clear()
        self._qscale.clear()
        self._kind.clear()
        self._priority.clear()
        self._stamp.clear()
        self._dev_bytes = 0
        self._build_left = None
        self._node_build_left = None
        self._batch_modes = None

    @property
    def device_bytes(self) -> int:
        """Bytes currently held in the device tier."""
        return self._dev_bytes

    def tier_of(self, key: tuple) -> str | None:
        """"device" | "host" | None — where one entry currently lives."""
        if key in self._device:
            return "device"
        if key in self._host:
            return "host"
        return None

    def _put(self, key: tuple, hist: Array, kind: str, priority: float) -> None:
        self._drop(key)
        self._device[key] = hist
        self._nbytes[key] = int(hist.nbytes)
        self._kind[key] = kind
        self._priority[key] = priority
        self._stamp[key] = self._clock
        self._clock += 1
        self._dev_bytes += self._nbytes[key]

    def _drop(self, key: tuple) -> None:
        if key in self._device:
            self._dev_bytes -= self._nbytes[key]
            del self._device[key]
        # dropping an entry whose spill is still in flight abandons the copy:
        # the host buffer is never read, so `discard_node` racing an async
        # spill can never resurrect or reorder against a stale histogram
        self._inflight.pop(key, None)
        self._host.pop(key, None)
        self._nbytes.pop(key, None)
        self._qscale.pop(key, None)
        self._kind.pop(key, None)
        self._priority.pop(key, None)
        self._stamp.pop(key, None)

    def _spill(self, key: tuple) -> None:
        """Device -> host, asynchronously: issue the device->host copy and
        return without waiting — the next build pass overlaps the transfer.

        The *logical* tier transition is immediate (``tier_of`` says "host",
        the spill ledger is booked, the device budget is credited) so spill
        policy and its tests are oblivious to the overlap; only the pinned
        host buffer materializes later, at the `_host_buffer` completion
        barrier. The device array stays referenced in ``_inflight`` until
        then — at most ``max_inflight_spills`` copies deep, after which the
        oldest is completed (double buffering, same depth PageStream uses).
        Spill wall-seconds are deliberately booked nowhere: the copy runs
        behind compute, and charging it to the stream ledger would dilute
        ``overlap_ratio``.
        """
        arr = self._device.pop(key)
        if not self.quantizer.is_raw:
            # narrow on device: only the wire payload crosses to the host
            arr, self._qscale[key] = self.quantizer.quantize(arr)
        try:
            arr.copy_to_host_async()
        except AttributeError:  # non-committed/np-backed arrays: copy is free
            pass
        self._inflight[key] = arr
        self._host[key] = None  # placeholder: logically host-tier as of now
        self._dev_bytes -= self._nbytes[key]
        wire_nbytes = int(arr.nbytes)  # == _nbytes under the raw transport
        ts = self.transfer_stats
        ts.hist_spills += 1
        ts.hist_spill_bytes += wire_nbytes
        ts.device_to_host_bytes += wire_nbytes
        while len(self._inflight) > self.max_inflight_spills:
            self._complete_spill(next(iter(self._inflight)))

    def _complete_spill(self, key: tuple) -> None:
        """Completion barrier for one in-flight spill: await the async copy
        and pin the host buffer (np.asarray reuses the buffer the issued
        copy landed in; it only blocks if the copy is still in flight)."""
        arr = self._inflight.pop(key, None)
        if arr is not None:
            self._host[key] = np.asarray(arr)

    def _host_buffer(self, key: tuple) -> np.ndarray:
        """The host-tier buffer for ``key``, completing its spill if the
        copy is still in flight — the barrier that keeps `_fetch` of an
        in-flight spill bit-exact."""
        self._complete_spill(key)
        return self._host[key]

    def _fetch(self, key: tuple) -> Array:
        """Host -> device: stage a spilled histogram back through the same
        `PageStream` engine the ELLPACK pages ride (no hand-rolled copy
        loop). The stream's time ledger is private — a single synchronous
        histogram put has nothing to overlap, and booking its wall==stage
        seconds into the page pipeline's shared ledger would dilute
        ``overlap_ratio`` — while the byte counters land in the shared
        `TransferStats` next to the page traffic. The staging put is retried
        under ``self.retry`` (a transient device-transfer fault should not
        kill a build whose host copy is intact); the fault-injection site
        "hist_store.fetch" fires once per fetch."""
        from repro.pipeline.stream import PageStream

        host = self._host_buffer(key)  # pop only after a successful stage

        def _stage() -> Array:
            fault_inject.fire("hist_store.fetch")
            stream = PageStream(
                lambda _i: host, [0], threaded=False,
                cache_tag="hist", stats=TransferStats(),
            )
            (page,) = list(stream)
            return page.device

        device = self.retry.call(
            _stage, stats=self.transfer_stats, describe="histogram fetch"
        )
        if not self.quantizer.is_raw:
            # widen back to f32 *before* any accumulation reads it, so the
            # reconstruction order matches the raw transport exactly
            device = self.quantizer.dequantize(device, self._qscale.pop(key, None))
        del self._host[key]
        self._device[key] = device
        self._dev_bytes += self._nbytes[key]
        ts = self.transfer_stats
        ts.hist_fetches += 1
        ts.hist_fetch_bytes += host.nbytes
        ts.host_to_device_bytes += host.nbytes
        ts.logical_bytes += self._nbytes[key]
        ts.wire_bytes += host.nbytes
        return device

    def _coldest(self, keys: list[tuple]) -> tuple:
        return min(keys, key=lambda k: (self._priority[k], self._stamp[k]))

    def _enforce_budget(self) -> None:
        if self.budget_bytes is None:
            return
        while self._dev_bytes > self.budget_bytes:
            # spill the coldest live entry: shallowest level first (depthwise
            # "level order"), lowest frontier gain first (lossguide "LRU by
            # gain"); insertion order breaks exact ties
            spillable = [
                k for k in self._device if self._kind[k] != "ancestor"
            ]
            if spillable:
                self._spill(self._coldest(spillable))
                continue
            # retired node ancestors last: they feed chain derivation while
            # they live, and are re-derivable, so drop rather than spill (a
            # host-tier ancestor saves no transfer)
            ancestors = list(self._device)
            if not ancestors:
                return
            self._drop(self._coldest(ancestors))

    # ------------------------------------------------------- depthwise (levels)
    def plan(self, count: int, level_counts: Array | None) -> LevelPlan:
        depth = count.bit_length() - 1  # count == 2**depth in the heap layout
        parent_key = ("L", depth - 1)
        subtract = (
            self.enabled
            and count > 1
            and level_counts is not None
            and self.tier_of(parent_key) is not None
        )
        if not subtract:
            self._build_left = None
            return LevelPlan(
                node_map=None, n_build=count, count=count, source="build",
                build_nodes=jnp.arange(count, dtype=jnp.int32) + (count - 1),
            )
        if parent_key in self._device:
            source = "device"
        else:
            # resolution step: stage the spilled parent level back now, so the
            # fetch overlaps the histogram pass that runs before expand()
            self._fetch(parent_key)
            source = "fetched"
        node_map, build_left, build_nodes, built, total = _plan_level_fused(
            level_counts, count - 1, count
        )
        self._build_left = build_left
        self.stats.levels += 1
        self.stats.built_nodes += count // 2
        self.stats.derived_nodes += count - count // 2
        # tracers would leak out of a jitted caller's trace; drop stats there
        if not isinstance(built, jax.core.Tracer):
            self.stats._add_rows(built, total)
        return LevelPlan(
            node_map=node_map, n_build=count // 2, count=count, source=source,
            build_nodes=build_nodes,
        )

    def expand(self, plan: LevelPlan, built: Array) -> Array:
        """Compact build histogram -> full (count, m, n_bins, 2) level
        histogram; stores the result as the next level's parent (within the
        budget — overflow spills to the host tier)."""
        depth = plan.count.bit_length() - 1
        if plan.node_map is None:
            full = built
        else:
            full = _expand_level_j(self._device[("L", depth - 1)], built, self._build_left)
        if self.enabled:
            self._put(("L", depth), full, kind="level", priority=float(depth))
            # depthwise retains exactly one level: the fresh one is the next
            # plan's parent and nothing ever reads older levels (there is no
            # whole-level derivation chain), so they are dropped free —
            # `retained_levels` is the *per-node* ancestor-chain knob
            for key in [k for k in self._nbytes if k[0] == "L" and k[1] < depth]:
                self._drop(key)
            self._enforce_budget()
        return full

    # ------------------------------------------------- per-node (best-first) API
    def put_node(self, node: int, hist: Array) -> None:
        """Retain one frontier node's (m, n_bins, 2) histogram."""
        if self.enabled:
            self._put(("N", node), hist, kind="node", priority=_HOT)
            self._enforce_budget()

    def note_gain(self, node: int, gain: float) -> None:
        """Record a frontier node's split gain: the spill order. Low-gain
        leaves are popped last (or never), so they go cold first."""
        key = ("N", node)
        if key in self._priority:
            self._priority[key] = float(gain)

    def discard_node(self, node: int) -> None:
        """Drop a node that left the frontier (became a permanent leaf)."""
        self._drop(("N", node))

    def _derive_from_chain(self, node: int) -> Array | None:
        """Multi-level subtraction: hist(node) from the nearest retired
        ancestor minus the device-resident siblings along the path (at most
        ``retained_levels - 1`` generations up). None if the chain breaks."""
        if self.retained_levels < 2:
            return None
        sibs: list[Array] = []
        cur = node
        for _ in range(self.retained_levels - 1):
            if cur == 0:
                return None
            parent = (cur - 1) // 2
            sibling = cur + 1 if cur % 2 == 1 else cur - 1
            sib_hist = self._device.get(("N", sibling))
            if sib_hist is None:
                return None
            sibs.append(sib_hist)
            anc = self._device.get(("N", parent))
            if anc is not None:
                for s in sibs:
                    anc = anc - s
                return anc
            cur = parent
        return None

    def plan_node(self, parent: int, child_counts: Array | None) -> LevelPlan:
        """Build/derive plan for the popped ``parent``'s 2-node child window.

        Resolution order for the parent histogram: device tier (classic
        subtraction) -> ancestor-chain derivation (``retained_levels >= 2``,
        no transfer) -> host-tier fetch (bit-exact, staged back through
        `PageStream`) -> full rebuild from rows. With a resolved parent, only
        the smaller child (exact row counts from the per-node repartition;
        ties build left, matching `plan_level`) occupies the single kernel
        slot and the sibling is derived in `expand_node`.
        """
        key = ("N", parent)
        children = jnp.arange(2, dtype=jnp.int32) + (2 * parent + 1)
        if not (self.enabled and child_counts is not None):
            self._node_build_left = None
            return LevelPlan(
                node_map=None, n_build=2, count=2, source="build",
                build_nodes=children,
            )
        if key in self._device:
            source = "device"
        else:
            chain = self._derive_from_chain(parent)
            if chain is not None:
                prio = self._priority.get(key, _HOT)
                self._put(key, chain, kind="node", priority=prio)
                self.stats.chain_derived_nodes += 1
                source = "derived"
            elif key in self._host:
                self._fetch(key)
                source = "fetched"
            else:
                self._node_build_left = None
                self.stats.rebuilt_nodes += 1
                return LevelPlan(
                    node_map=None, n_build=2, count=2, source="build",
                    build_nodes=children,
                )
        node_map, build_left, build_nodes, built, total = _plan_level_fused(
            child_counts, 2 * parent + 1, 2
        )
        self._node_build_left = build_left
        self.stats.levels += 1
        self.stats.built_nodes += 1
        self.stats.derived_nodes += 1
        if not isinstance(built, jax.core.Tracer):
            self.stats._add_rows(built, total)
        return LevelPlan(
            node_map=node_map, n_build=1, count=2, source=source,
            build_nodes=build_nodes,
        )

    def _store_pair(self, parent: int, pair: Array) -> None:
        """Store a popped parent's two child histograms as frontier nodes and
        retire (``retained_levels >= 2``) or evict the parent."""
        key = ("N", parent)
        self._put(("N", 2 * parent + 1), pair[0], kind="node", priority=_HOT)
        self._put(("N", 2 * parent + 2), pair[1], kind="node", priority=_HOT)
        if self.retained_levels > 1 and key in self._device:
            # retire the parent: its depth orders ancestor drops, and the
            # chain for its descendants may reach it without a transfer
            self._kind[key] = "ancestor"
            self._priority[key] = float((parent + 1).bit_length() - 1)
            self._inflight.pop(key, None)
            self._host.pop(key, None)
            # prune path ancestors the bounded chain can no longer reach
            cur, steps = parent, 0
            while cur > 0:
                cur = (cur - 1) // 2
                steps += 1
                akey = ("N", cur)
                if steps >= self.retained_levels - 1 and self._kind.get(akey) == "ancestor":
                    self._drop(akey)
        else:
            self._drop(key)
        self._enforce_budget()

    def expand_node(self, parent: int, plan: LevelPlan, built: Array) -> Array:
        """Compact build -> full (2, m, n_bins, 2) child histograms; stores
        both children as frontier nodes and retires (``retained_levels >= 2``)
        or evicts the parent."""
        key = ("N", parent)
        if plan.node_map is None:
            full = built
        else:
            full = _expand_level_j(self._device[key][None], built, self._node_build_left)
        if self.enabled:
            self._store_pair(parent, full)
        return full

    # ----------------------------------------------- batched pops (best-first)
    def plan_nodes(self, parents: list[int], child_counts: Array | None) -> LevelPlan:
        """Batched `plan_node`: one fused plan for several popped parents, so
        all their child histograms ride a single HistFn pass (one PageStream
        pass out-of-core instead of one per pop).

        ``parents`` must be sorted ascending (the drivers sort — array slots
        then follow global node order deterministically); ``child_counts`` is
        ``(2 * len(parents),)`` in [left_0, right_0, left_1, right_1, ...]
        order. Each parent resolves independently through the same order as
        `plan_node` (device -> ancestor chain -> host fetch -> rebuild):
        resolved parents contribute their *smaller* child to the build set
        (ties build left), unresolved parents contribute both children. The
        returned plan's ``build_nodes`` is the (possibly non-contiguous)
        union, in parent order; ``node_map`` is None — batched windows are
        not contiguous, only the fused kernel path serves them.
        """
        k = len(parents)
        count = 2 * k
        if not (self.enabled and child_counts is not None):
            self._batch_modes = [("full", None)] * k
            build_nodes = jnp.asarray(
                [2 * p + 1 + c for p in parents for c in (0, 1)], jnp.int32
            )
            return LevelPlan(
                node_map=None, n_build=count, count=count, source="build",
                build_nodes=build_nodes,
            )
        counts_np = np.asarray(child_counts)
        modes: list[tuple[str, bool | None]] = []
        nodes: list[int] = []
        sources: set[str] = set()
        built_rows = 0.0
        total_rows = 0.0
        for i, parent in enumerate(parents):
            key = ("N", parent)
            if key in self._device:
                resolved = True
                sources.add("device")
            else:
                chain = self._derive_from_chain(parent)
                if chain is not None:
                    prio = self._priority.get(key, _HOT)
                    self._put(key, chain, kind="node", priority=prio)
                    self.stats.chain_derived_nodes += 1
                    sources.add("derived")
                    resolved = True
                elif key in self._host:
                    self._fetch(key)
                    sources.add("fetched")
                    resolved = True
                else:
                    resolved = False
            left_n, right_n = int(counts_np[2 * i]), int(counts_np[2 * i + 1])
            if resolved:
                build_left = left_n <= right_n
                modes.append(("sub", build_left))
                nodes.append(2 * parent + 1 + (0 if build_left else 1))
                self.stats.levels += 1
                self.stats.built_nodes += 1
                self.stats.derived_nodes += 1
                built_rows += min(left_n, right_n)
                total_rows += left_n + right_n
            else:
                modes.append(("full", None))
                nodes.extend((2 * parent + 1, 2 * parent + 2))
                self.stats.rebuilt_nodes += 1
                sources.add("build")
        self._batch_modes = modes
        if total_rows:
            self.stats._add_rows(
                jnp.asarray(built_rows, jnp.float32), jnp.asarray(total_rows, jnp.float32)
            )
        # aggregate source label, most expensive resolution wins the name
        source = next(
            (s for s in ("fetched", "derived", "build", "device") if s in sources),
            "device",
        )
        return LevelPlan(
            node_map=None, n_build=len(nodes), count=count, source=source,
            build_nodes=jnp.asarray(nodes, jnp.int32),
        )

    def expand_nodes(self, parents: list[int], plan: LevelPlan, built: Array) -> Array:
        """Batched `expand_node`: reconstruct every popped parent's child pair
        from the fused build histogram and store/retire exactly as the
        per-node path does. Returns ``(2 * len(parents), m, n_bins, 2)`` in
        [left_0, right_0, left_1, right_1, ...] order."""
        modes = self._batch_modes
        self._batch_modes = None
        pairs: list[Array] = []
        slot = 0
        # derive every pair before storing any: storing triggers budget
        # enforcement, which could spill a later parent mid-batch
        for i, parent in enumerate(parents):
            mode, build_left = modes[i]
            if mode == "full":
                pair = built[slot:slot + 2]
                slot += 2
            else:
                b = built[slot]
                slot += 1
                # same elementwise math as expand_level on a 1-pair window
                derived = self._device[("N", parent)] - b
                pair = jnp.stack([b, derived] if build_left else [derived, b])
            pairs.append(pair)
        if self.enabled:
            for parent, pair in zip(parents, pairs):
                self._store_pair(parent, pair)
        return jnp.concatenate(pairs, axis=0)


class HistogramCache(HistogramStore):
    """Backward-compatible alias: the unlimited-budget single-tier store.

    ``HistogramCache(enabled=...)`` behaves bit-for-bit like the pre-tiered
    subtraction cache (nothing spills, no ancestor chains); the tiered knobs
    are still accepted for callers migrating to `HistogramStore`.
    """
