"""`PageStream`: the unified async page-streaming engine (paper §2.3 / §3.2).

One engine owns the whole disk -> host -> device pipeline that the paper's
out-of-core argument rests on:

  disk -> host    the threaded `Prefetcher` keeps `prefetch_depth` page loads
                  in flight ahead of the consumer (§2.3's multi-threaded
                  pre-fetcher, with retries for transient I/O faults);
  host -> device  double-buffered staging: the `jax.device_put` for page k+1
                  is issued while the consumer computes on page k
                  (`staging_depth` puts in flight; JAX device puts are async,
                  so the copy engine runs under the compute);
  device          an optional `DevicePageCache` LRU skips the host->device
                  copy entirely for pages still resident from a previous pass
                  (the f < 1 compacted-page fast path revisits every page once
                  per iteration).

Every boundary crossing is accounted in a `TransferStats`: bytes per edge plus
the overlap ledger (fetch/stage/compute attributed where they run, against the
end-to-end wall time), so callers can report how much of the serial
transfer+compute cost the pipeline actually hid — the paper's central claim is
precisely that this ratio can approach the ideal.

Consumers: `GradientBooster`'s out-of-core engine (Alg. 6 streaming build,
Alg. 7 compaction and margin update),
`distributed.gbdt_shard.grow_tree_distributed_paged` (sharded staging), and
the serving tier (`repro.serve.engine` streams both row pages and paged-forest
tree-chunks through this engine; see `examples/serve_paged.py`).
"""
from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Iterable, Iterator, NamedTuple, Sequence

import jax
import numpy as np

from repro import tracing
from repro.data.pages import GLOBAL_STATS, PageStore, Prefetcher, TransferStats
from repro.fault.retry import RetryPolicy
from repro.pipeline.cache import DevicePageCache
from repro.tracing import span


class StreamedPage(NamedTuple):
    """One page as it leaves the pipeline: host view + staged device buffer."""

    index: int
    host: Any  # whatever the fetch callable produced (e.g. an EllpackPage)
    device: jax.Array


def _default_to_array(page: Any) -> np.ndarray:
    return np.asarray(page)


class PageStream:
    """Double-buffered streaming of pages from a source to the device.

    Parameters
    ----------
    fetch : idx -> host page. Disk-backed sources should do their read here;
        it runs in a background thread when ``threaded=True``.
    indices : iteration order (one pass = one full iteration of ``indices``).
    to_array : host page -> np.ndarray staged to the device. Defaults to
        ``np.asarray``.
    put : np.ndarray -> jax.Array. Defaults to ``jax.device_put``; pass a
        sharded put (e.g. ``device_put(..., NamedSharding)``) to stage pages
        directly into a mesh layout.
    threaded : run ``fetch`` in the §2.3 prefetcher thread (True for disk,
        False for pages already in host RAM).
    prefetch_depth / staging_depth : fetches / device puts kept in flight.
    cache : optional `DevicePageCache`; hits skip the host->device copy.
    cache_tag : namespace for cache keys so distinct streams over the same
        indices don't collide.
    cache_pin : stage into the cache's pinned (never-evicted) tier — the
        serving tier's pin prologue stages hot forest tree-chunks this way so
        later row-page pressure on the shared byte budget cannot displace
        them. Entries the pin budget refuses land in the plain LRU tier.
    stats : `TransferStats` sink (defaults to the module-global one).
    retry : `RetryPolicy` for the threaded prefetcher's transient-fault
        retries (None = the policy's defaults); attempts/aborts land in
        ``stats.io_retries`` / ``io_giveups``.
    transport : optional `repro.compress.PageTransport` (or the forest wire
        packer). When set, ``to_array``'s output is encoded on host, only
        the wire payload crosses through ``put``, and the staged device
        buffer is decoded back on device — the consumer still sees the full
        logical page. The ledger books both sides: ``logical_bytes`` (what
        the device consumes) vs ``wire_bytes`` (what actually crossed).

    A `PageStream` is re-iterable: each ``iter()`` is an independent pass.
    """

    def __init__(
        self,
        fetch: Callable[[int], Any],
        indices: Iterable[int],
        *,
        to_array: Callable[[Any], np.ndarray] | None = None,
        put: Callable[[np.ndarray], jax.Array] | None = None,
        threaded: bool = False,
        prefetch_depth: int = 2,
        staging_depth: int = 2,
        cache: DevicePageCache | None = None,
        cache_tag: str = "page",
        cache_pin: bool = False,
        stats: TransferStats | None = None,
        retry: RetryPolicy | None = None,
        transport: Any | None = None,
    ):
        self._fetch = fetch
        self._indices = list(indices)
        self._to_array = to_array or _default_to_array
        self._put = put or jax.device_put
        self._threaded = threaded
        self.prefetch_depth = max(1, prefetch_depth)
        self.staging_depth = max(1, staging_depth)
        self.cache = cache
        self.cache_tag = cache_tag
        self.cache_pin = cache_pin
        self.stats = stats or GLOBAL_STATS
        self.retry = retry
        self.transport = transport

    # ------------------------------------------------------------ constructors
    @classmethod
    def from_host_pages(
        cls, pages: Sequence[Any], indices: Iterable[int] | None = None, **kw
    ) -> "PageStream":
        """Stream pages already resident in host RAM (no prefetch thread).

        ``indices`` restricts the pass to a subset while keeping each page's
        global index (page-skipping passes stay keyed consistently).
        """
        kw.setdefault("threaded", False)
        return cls(pages.__getitem__, indices if indices is not None else range(len(pages)), **kw)

    @classmethod
    def from_store(
        cls,
        store: PageStore,
        wrap: Callable[[int, dict], Any] | None = None,
        indices: Iterable[int] | None = None,
        **kw,
    ) -> "PageStream":
        """Stream a disk `PageStore`; ``wrap(idx, arrays)`` builds the host page."""

        def fetch(idx: int) -> Any:
            arrays = store.read_page(idx)
            return wrap(idx, arrays) if wrap is not None else arrays

        kw.setdefault("threaded", True)
        kw.setdefault("stats", store.stats)
        return cls(fetch, indices if indices is not None else range(store.n_pages), **kw)

    @property
    def n_pages(self) -> int:
        return len(self._indices)

    # --------------------------------------------------------------- host pass
    def _source(self) -> Iterator[tuple[int, Any]]:
        """Raw fetched pages, no ledger entries beyond fetch time itself."""
        timed = self._timed_fetch
        if self._threaded:
            yield from Prefetcher(
                timed,
                self._indices,
                depth=self.prefetch_depth,
                retry=self.retry,
                stats=self.stats,
            )
        else:
            for idx in self._indices:
                yield idx, timed(idx)

    def iter_host(self) -> Iterator[tuple[int, Any]]:
        """One pass over host pages with prefetch but *no* device staging.

        Used by host-side consumers (Alg. 7's Compact gathers sampled rows on
        the host before staging one compacted page). Keeps the same
        wall/compute ledger as a device pass so overlap_ratio stays honest:
        fetch time booked by this pass is matched by the wall time it took.
        """
        stats = self.stats
        t_wall0 = time.perf_counter()
        try:
            for idx, page in self._source():
                t_yield = time.perf_counter()
                yield idx, page
                stats.stream_compute_seconds += time.perf_counter() - t_yield
        finally:
            stats.stream_wall_seconds += time.perf_counter() - t_wall0

    def _timed_fetch(self, idx: int) -> Any:
        t0 = time.perf_counter()
        page = self._fetch(idx)
        self.stats.stream_fetch_seconds += time.perf_counter() - t0
        return page

    # -------------------------------------------------------------- device pass
    def _stage(self, idx: int, host: Any) -> StreamedPage:
        with span(tracing.PAGE_STAGE, page=idx):
            key = (self.cache_tag, idx)
            if self.cache is not None:
                entry = self.cache.lookup(key)
                if entry is not None:
                    dev, nbytes = entry
                    self.stats.cache_hits += 1
                    self.stats.cache_hit_bytes += nbytes  # host bytes the hit saved
                    return StreamedPage(idx, host, dev)
                self.stats.cache_misses += 1
            arr = self._to_array(host)
            t0 = time.perf_counter()
            if self.transport is not None:
                wire, wire_meta = self.transport.encode(arr)
                dev = self.transport.decode(self._put(wire), wire_meta)
                wire_nbytes = wire.nbytes
            else:
                dev = self._put(arr)
                wire_nbytes = arr.nbytes
            self.stats.stream_stage_seconds += time.perf_counter() - t0
            self.stats.host_to_device_bytes += wire_nbytes
            self.stats.logical_bytes += arr.nbytes
            self.stats.wire_bytes += wire_nbytes
            if self.cache is not None:
                self.cache.put(key, dev, wire_nbytes, pinned=self.cache_pin)
            return StreamedPage(idx, host, dev)

    def __iter__(self) -> Iterator[StreamedPage]:
        stats = self.stats
        t_wall0 = time.perf_counter()
        source = self._source()
        inflight: deque[StreamedPage] = deque()
        exhausted = False
        try:
            while True:
                # keep `staging_depth` device puts in flight ahead of compute:
                # the put for page k+1 is issued before page k is yielded, so
                # the copy engine overlaps the consumer's kernel on page k.
                while not exhausted and len(inflight) < self.staging_depth:
                    try:
                        idx, host = next(source)
                    except StopIteration:
                        exhausted = True
                        break
                    inflight.append(self._stage(idx, host))
                if not inflight:
                    return
                page = inflight.popleft()
                t_yield = time.perf_counter()
                yield page
                stats.stream_compute_seconds += time.perf_counter() - t_yield
        finally:
            stats.stream_wall_seconds += time.perf_counter() - t_wall0
