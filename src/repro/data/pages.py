"""External page store + threaded prefetcher (paper §2.3 / §3.2 substrate).

`PageStore` persists ELLPACK pages (and their labels/metadata) to disk with
optional zstd compression; `Prefetcher` is the "multi-threaded pre-fetcher" of
§2.3 — it loads page k+1..k+depth from disk while page k is being consumed, so
host I/O overlaps device compute. `TransferStats` counts the bytes that cross
each boundary (disk->host, host->device), which is the measured quantity behind
the paper's PCIe-bottleneck argument and our roofline paging model.

Durability: every page blob lands via tmp-file + fsync + ``os.replace`` and is
CRC32-checksummed in the manifest (itself replaced atomically), so a crash
mid-write never leaves a half-written page that a later `PagedDMatrix` reopen
would trust — the torn page is simply absent from the manifest. `read_page`
verifies the stored CRC and raises `PageCorruptError` naming the page index
instead of decoding garbage. Pages optionally pass through a lossless
`repro.compress` codec (``codec="bitpack"``/``"delta-rle"``/chains); the codec
name + meta are recorded per page in the manifest, so mixed and legacy
(pre-codec) caches decode correctly, and a garbled compressed payload
surfaces as `PageDecodeError` (a `PageCorruptError`) naming the codec and
page index via the ``page_store.decode`` fault site. Transient read faults
are retried with
exponential backoff through `repro.fault.RetryPolicy` (attempts/aborts in
``TransferStats.io_retries`` / ``io_giveups``), and both store and prefetcher
fire `repro.fault.inject` sites so chaos tests can plant deterministic I/O
failures.

Read path: one read and one CRC serve each page. `read_page` reads the file
with `readinto` into a fresh buffer of its own (no `bytes` copy; the syscall
releases the GIL) and runs `zlib.crc32` over it once. An uncompressed
(``RAW0``) blob with a manifest CRC is then decoded as views: each ``.npy``
member is found through the zip's central directory and local header and
returned as `np.frombuffer` over the checked buffer (``TransferStats.
direct_page_reads``). zipfile's per-member CRC-32 does not run there: the
manifest CRC already covers every byte of the blob. zstd (``ZST0``) blobs,
manifests without a CRC (where the member CRC is the only check), deflated
members and any zip layout the view parse does not handle go through
`np.load` as before.
"""
from __future__ import annotations

import dataclasses
import io
import json
import math
import os
import queue
import struct
import threading
import zlib
from typing import Callable, Iterable, Iterator

import numpy as np
from numpy.lib import format as npy_format

from repro import tracing
from repro.fault import inject as fault_inject
from repro.fault.retry import RetryPolicy
from repro.tracing import span

try:
    import zstandard as _zstd
except Exception:  # pragma: no cover
    _zstd = None


@dataclasses.dataclass
class TransferStats:
    disk_read_bytes: int = 0
    disk_write_bytes: int = 0
    host_to_device_bytes: int = 0
    device_to_host_bytes: int = 0
    page_loads: int = 0
    # --- streaming-overlap accounting (filled by repro.pipeline.PageStream) ---
    # fetch/stage/compute are attributed where the work happens (fetch in the
    # prefetcher thread, stage + compute in the consumer thread), so their sum
    # is the *serial* cost of a pass; wall is what actually elapsed. Overlap
    # hides serial work, so wall < serial when the pipeline is doing its job.
    stream_fetch_seconds: float = 0.0  # source fetch (disk/host) time
    stream_stage_seconds: float = 0.0  # host->device put time
    stream_compute_seconds: float = 0.0  # consumer time between pages
    stream_wall_seconds: float = 0.0  # end-to-end elapsed across passes
    cache_hits: int = 0  # device-page cache hits (transfers skipped)
    cache_hit_bytes: int = 0  # host->device bytes those hits saved
    # stages that consulted a DevicePageCache and found nothing resident; only
    # counted when a cache is attached, so cache_hit_rate reads 0/0 (not a
    # fake 0%) on cacheless streams
    cache_misses: int = 0
    # pages never fetched/staged because a per-node lossguide pass proved no
    # row of theirs sits in the popped node's window (see build_tree_paged)
    pages_skipped: int = 0
    # --- tiered histogram store ledger (filled by core.histcache.HistogramStore) ---
    # cold node/level histograms evicted from the device budget land in host
    # buffers (spill) and are staged back through PageStream when a plan
    # needs them again (fetch); fetch bytes are *also* counted in
    # host_to_device_bytes because the fetch goes through the same staging path
    hist_spill_bytes: int = 0
    hist_fetch_bytes: int = 0
    hist_spills: int = 0
    hist_fetches: int = 0
    # --- compression ledger (filled everywhere pages/histograms stage) ---
    # logical_bytes is what the device consumes after decode; wire_bytes is
    # what actually crossed host->device. With page_codec="raw" they are
    # equal; a codec's win is exactly logical_bytes - wire_bytes. Disk-side
    # savings show up in disk_read/write_bytes instead (the blob shrinks).
    logical_bytes: int = 0
    wire_bytes: int = 0
    # --- retry ledger (filled by repro.fault.RetryPolicy.call) ---
    # io_retries counts re-attempts that a transient fault cost us (page
    # reads, histogram staging, elastic RPCs); io_giveups counts operations
    # that exhausted their attempt budget and surfaced the error
    io_retries: int = 0
    io_giveups: int = 0
    # --- cross-shard collective ledger (filled by repro.distributed) ---
    # bytes each shard passes into the distributed tree program's collectives
    # (histogram AllReduce, row counts, leaf and root sums, split candidates),
    # counted from the operands' static shapes when the program traces and
    # added once per tree; with a narrowed grad_transport, the narrowed bytes
    collective_bytes: int = 0
    # --- page read path (filled by PageStore.read_page) ---
    # pages decoded as views over their CRC-checked read buffer; equals
    # page_loads on uncompressed CRC'd stores, 0 on zstd and CRC-less ones
    direct_page_reads: int = 0

    @property
    def cache_hit_rate(self) -> float:
        """Device-cache hit fraction of cached stages (0..1); 0.0 when no
        cache-backed stage ran. Sits next to overlap_ratio in benchmark
        records so residency wins are ledgered, not just byte counts."""
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def wire_ratio(self) -> float:
        """wire/logical staged bytes (1.0 = uncompressed, lower = better)."""
        return self.wire_bytes / self.logical_bytes if self.logical_bytes > 0 else 1.0

    @property
    def stream_serial_seconds(self) -> float:
        """What the streamed passes would cost with zero overlap."""
        return self.stream_fetch_seconds + self.stream_stage_seconds + self.stream_compute_seconds

    @property
    def overlap_saved_seconds(self) -> float:
        return max(0.0, self.stream_serial_seconds - self.stream_wall_seconds)

    @property
    def overlap_ratio(self) -> float:
        """Fraction of serial transfer+compute time hidden by pipelining (0..1)."""
        serial = self.stream_serial_seconds
        return self.overlap_saved_seconds / serial if serial > 0 else 0.0

    def reset(self) -> None:
        self.disk_read_bytes = 0
        self.disk_write_bytes = 0
        self.host_to_device_bytes = 0
        self.device_to_host_bytes = 0
        self.page_loads = 0
        self.stream_fetch_seconds = 0.0
        self.stream_stage_seconds = 0.0
        self.stream_compute_seconds = 0.0
        self.stream_wall_seconds = 0.0
        self.cache_hits = 0
        self.cache_hit_bytes = 0
        self.cache_misses = 0
        self.pages_skipped = 0
        self.hist_spill_bytes = 0
        self.hist_fetch_bytes = 0
        self.hist_spills = 0
        self.hist_fetches = 0
        self.logical_bytes = 0
        self.wire_bytes = 0
        self.io_retries = 0
        self.io_giveups = 0
        self.collective_bytes = 0
        self.direct_page_reads = 0


GLOBAL_STATS = TransferStats()


_RAW_TAG = b"RAW0"
_ZSTD_TAG = b"ZST0"


def _encode(arrays: dict[str, np.ndarray], compress: bool) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    raw = buf.getvalue()
    if compress and _zstd is not None:
        return _ZSTD_TAG + _zstd.ZstdCompressor(level=1).compress(raw)
    return _RAW_TAG + raw


def _decode(blob) -> dict[str, np.ndarray]:
    """Any blob through `np.load` (zipfile checks each member's CRC-32)."""
    blob = memoryview(blob)
    tag, body = bytes(blob[:4]), blob[4:]
    if tag == _ZSTD_TAG:
        if _zstd is None:
            raise RuntimeError("zstd page but zstandard not installed")
        body = _zstd.ZstdDecompressor().decompress(body)
    data = np.load(io.BytesIO(body))
    return {k: data[k] for k in data.files}


def _read_file(path: str) -> np.ndarray:
    """The whole file in one fresh buffer, filled by `readinto`: no `bytes`
    object, and the read syscall releases the GIL. Every read gets its own
    buffer, since the views `_decode_views` returns over it may still be
    staged to the device while the next page is read."""
    with open(path, "rb", buffering=0) as fh:
        buf = np.empty(os.fstat(fh.fileno()).st_size, np.uint8)
        view = memoryview(buf)
        n = 0
        while n < len(buf):
            got = fh.readinto(view[n:])
            if not got:
                break
            n += got
    return buf[:n]


# the zip records `np.savez` writes (PKWARE APPNOTE 4.3.7, 4.3.12, 4.3.16)
_LOCAL = struct.Struct("<4s5H3L2H")  # local file header
_CENTRAL = struct.Struct("<4s6H3L5H2L")  # central directory entry
_END = struct.Struct("<4s4H2LH")  # end of central directory
_ZIP_STORED = 0
_UTF8_NAME = 0x800
_ENCRYPTED = 0x1
_ZIP64_MARK = 0xFFFFFFFF


def _decode_views(buf: np.ndarray) -> dict[str, np.ndarray] | None:
    """The ``.npy`` members of a ``RAW0`` blob as views over ``buf``.

    Members are found through the zip's central directory and each one's
    local header, read straight from ``buf``: the body is never copied.
    Returns None for a layout left to `_decode` (zip64 end records, an
    archive comment, a deflated or encrypted member, a member that is not
    ``.npy``, an ``.npy`` format other than 1.0); raises ValueError where
    the directory contradicts itself or the blob.
    """
    mv = memoryview(buf)
    base = len(_RAW_TAG)
    end_at = len(mv) - _END.size
    if end_at < base:
        raise ValueError(f"blob of {len(mv)} bytes holds no zip directory")
    sig, disk, cd_disk, n_here, n_members, cd_size, cd_offset, comment = _END.unpack_from(mv, end_at)
    if sig != b"PK\x05\x06" or comment or _ZIP64_MARK in (cd_size, cd_offset) or n_members == 0xFFFF:
        return None
    if disk or cd_disk or n_here != n_members or base + cd_offset + cd_size != end_at:
        raise ValueError("zip end record disagrees with the blob")
    cd_start = base + cd_offset
    out: dict[str, np.ndarray] = {}
    at = cd_start
    for _ in range(n_members):
        (sig, _, _, flags, method, _, _, _, size, _, n_name, n_extra, n_comment,
         _, _, _, local) = _CENTRAL.unpack_from(mv, at)
        if sig != b"PK\x01\x02":
            raise ValueError(f"no central directory entry at zip offset {at - base}")
        name_bytes = bytes(mv[at + _CENTRAL.size:at + _CENTRAL.size + n_name])
        at += _CENTRAL.size + n_name + n_extra + n_comment
        if at > end_at:
            raise ValueError("central directory runs past its end")
        if method != _ZIP_STORED or flags & _ENCRYPTED or _ZIP64_MARK in (size, local):
            return None
        name = name_bytes.decode("utf-8" if flags & _UTF8_NAME else "cp437")
        if not name.endswith(".npy"):
            return None
        head = base + local
        if head + _LOCAL.size > cd_start:
            raise ValueError(f"member {name!r}: local header past the directory")
        sig, _, _, l_method, _, _, _, _, _, l_name, l_extra = _LOCAL.unpack_from(mv, head)
        name_at = head + _LOCAL.size
        if sig != b"PK\x03\x04" or l_method != method or bytes(mv[name_at:name_at + l_name]) != name_bytes:
            raise ValueError(f"member {name!r}: local header disagrees with the directory")
        # the local extra field holds the zip64 sizes np.savez writes
        # (force_zip64); the directory entry's sizes are the real ones
        start = name_at + l_name + l_extra
        if start + size > cd_start:
            raise ValueError(f"member {name!r} runs past the directory")
        arr = _npy_view(buf, start, start + size)
        if arr is None:
            return None
        out[name[:-4]] = arr
    return out


def _npy_view(buf: np.ndarray, start: int, stop: int) -> np.ndarray | None:
    """The array of the ``.npy`` file at ``buf[start:stop]``, as a view;
    None for a format version other than the 1.0 `np.save` writes."""
    magic = bytes(buf[start:min(stop, start + 10)])
    if len(magic) < 10 or magic[:6] != npy_format.MAGIC_PREFIX:
        raise ValueError("member is not an .npy file")
    if magic[6:8] != b"\x01\x00":
        return None
    data_at = start + 10 + int.from_bytes(magic[8:10], "little")
    header = io.BytesIO(bytes(buf[start + 8:data_at]))
    shape, fortran_order, dtype = npy_format.read_array_header_1_0(header)
    count = math.prod(shape)
    if data_at + count * dtype.itemsize != stop:
        raise ValueError(f".npy member holds {stop - data_at} bytes, its header says {shape} {dtype}")
    arr = np.frombuffer(buf, dtype=dtype, count=count, offset=data_at)
    return arr.reshape(shape[::-1]).T if fortran_order else arr.reshape(shape)


class PageCorruptError(OSError):
    """A page blob failed its manifest CRC32 check (torn write / bit rot).

    Raised by `PageStore.read_page` instead of decoding garbage; names the
    page index and file so the operator knows exactly what to rebuild.
    """

    def __init__(self, idx: int, path: str, expected: int, actual: int):
        self.idx = idx
        self.path = path
        super().__init__(
            f"page {idx} is corrupt: CRC32 mismatch on {path} "
            f"(manifest {expected:#010x}, on disk {actual:#010x}). The page "
            f"cache is damaged — rebuild it from the raw source (IterDMatrix)."
        )


class PageDecodeError(PageCorruptError):
    """A page blob passed CRC but failed codec decode (truncated/garbled
    payload, stale codec meta). Deterministic damage like a CRC mismatch —
    never retried — naming the codec and page index."""

    def __init__(self, idx: int, path: str, codec: str, cause: Exception):
        self.idx = idx
        self.path = path
        self.codec = codec
        OSError.__init__(
            self,
            f"page {idx} failed {codec!r} decode on {path}: {cause!r}. The "
            f"compressed payload is damaged — rebuild the page cache from "
            f"the raw source (IterDMatrix).",
        )


def _atomic_write(path: str, data: bytes) -> None:
    """Write bytes durably: tmp file in the same dir, fsync, `os.replace`.

    A crash at any point leaves either the old file or the new file — never a
    half-written one trusted by a later reopen.
    """
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as fh:
        fh.write(data)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


def fsync_dir(path: str) -> None:
    """Best-effort directory fsync so a rename itself survives power loss."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # pragma: no cover - platform without dir-open support
        return
    try:
        os.fsync(fd)
    except OSError:  # pragma: no cover
        pass
    finally:
        os.close(fd)


class PageStore:
    """Directory of numbered pages; thread-safe reads, durable writes.

    Every blob and the manifest land via `_atomic_write`; each manifest entry
    records the blob's CRC32, verified on `read_page`. A crash between blob
    and manifest writes leaves the new page invisible (the manifest still
    describes a fully consistent store).
    """

    def __init__(
        self,
        root: str,
        compress: bool = False,
        stats: TransferStats | None = None,
        codec: str = "raw",
    ):
        from repro.compress import get_codec

        self.root = root
        self.compress = compress
        self.stats = stats or GLOBAL_STATS
        self.codec = get_codec(codec)
        os.makedirs(root, exist_ok=True)
        self._meta: dict = {"pages": []}
        self._meta_path = os.path.join(root, "manifest.json")
        if os.path.exists(self._meta_path):
            with open(self._meta_path) as fh:
                self._meta = json.load(fh)

    @property
    def n_pages(self) -> int:
        return len(self._meta["pages"])

    def _path(self, idx: int) -> str:
        return os.path.join(self.root, f"page_{idx:06d}.bin")

    def write_page(self, arrays: dict[str, np.ndarray], meta: dict | None = None) -> int:
        idx = self.n_pages
        fault_inject.fire("page_store.write_page", index=idx)
        codec_meta: dict = {}
        if self.codec.name != "raw":
            # only uint8 payloads (ELLPACK bin pages) go through the codec;
            # labels/float sidecars pass through verbatim
            coded = {}
            for key, arr in arrays.items():
                if isinstance(arr, np.ndarray) and arr.dtype == np.uint8:
                    coded[key], codec_meta[key] = self.codec.encode(arr)
                else:
                    coded[key] = arr
            arrays = coded
        blob = _encode(arrays, self.compress)
        _atomic_write(self._path(idx), blob)
        self.stats.disk_write_bytes += len(blob)
        entry = {"idx": idx, "bytes": len(blob), "crc32": zlib.crc32(blob)}
        entry["codec"] = self.codec.name
        if codec_meta:
            entry["codec_meta"] = codec_meta
        entry.update(meta or {})
        self._meta["pages"].append(entry)
        # manifest last: a crash before this point leaves the fresh blob
        # unreferenced, never a referenced-but-torn page
        _atomic_write(self._meta_path, json.dumps(self._meta).encode())
        fsync_dir(self.root)
        return idx

    def read_page(self, idx: int) -> dict[str, np.ndarray]:
        """Page ``idx``'s arrays, after one read and one CRC32 check.

        The file is read once into a buffer of its own and checked once
        against the manifest's CRC32 (`PageCorruptError` on a mismatch).
        An uncompressed blob with a manifest CRC decodes as views over that
        buffer (`_decode_views`; ``stats.direct_page_reads``): zipfile's
        member CRC would only re-check bytes the manifest CRC has covered.
        A zstd blob, a manifest entry without a CRC (its member CRCs are
        then the only check), or a layout the view parse leaves alone goes
        through `np.load`. Codec decode follows as written in the entry; any
        decode failure raises `PageDecodeError` naming the page.
        """
        with span(tracing.PAGE_FETCH, page=idx):
            fault_inject.fire("page_store.read_page", index=idx)
            blob = _read_file(self._path(idx))
            entry = self._meta["pages"][idx] if idx < len(self._meta["pages"]) else {}
            want = entry.get("crc32")  # pre-durability manifests have no CRC
            if want is not None:
                got = zlib.crc32(blob)
                if got != want:
                    raise PageCorruptError(idx, self._path(idx), want, got)
            # decode with the codec the *entry* was written with — legacy
            # (pre-codec) manifests have no "codec" field and decode as raw, so
            # old caches reopen bit-for-bit
            codec_name = entry.get("codec", "raw")
            try:
                fault_inject.fire("page_store.decode", index=idx, codec=codec_name)
                out = None
                if want is not None and bytes(blob[:4]) == _RAW_TAG:
                    out = _decode_views(blob)
                direct = out is not None
                if out is None:
                    out = _decode(blob)
                codec_meta = entry.get("codec_meta") or {}
                if codec_meta:
                    from repro.compress import get_codec

                    codec = get_codec(codec_name)
                    for key, cmeta in codec_meta.items():
                        out[key] = codec.decode(out[key], cmeta)
            except PageCorruptError:
                raise
            except Exception as err:
                raise PageDecodeError(idx, self._path(idx), codec_name, err) from err
            self.stats.disk_read_bytes += len(blob)
            self.stats.page_loads += 1
            self.stats.direct_page_reads += direct
            return out

    def page_meta(self, idx: int) -> dict:
        return self._meta["pages"][idx]


class Prefetcher:
    """Background-thread page loader (the §2.3 multi-threaded pre-fetcher).

    Wraps any `load(idx)` callable; yields pages in order while keeping up to
    `depth` loads in flight ahead of the consumer. Failed loads are retried
    with exponential backoff + jitter under a `repro.fault.RetryPolicy`
    (``retry``; the legacy ``retries`` count maps to
    ``RetryPolicy(max_attempts=retries + 1)``) before surfacing — transient-
    I/O fault tolerance for long runs. Re-attempts land in
    ``stats.io_retries``, exhausted budgets in ``stats.io_giveups``.
    `PageCorruptError` is never retried: a failed checksum is deterministic
    damage, not a transient fault.
    """

    def __init__(
        self,
        load: Callable[[int], dict],
        indices: Iterable[int],
        depth: int = 2,
        retries: int = 2,
        retry: RetryPolicy | None = None,
        stats: TransferStats | None = None,
    ):
        self._load = load
        self._indices = list(indices)
        self._queue: "queue.Queue[tuple[int, object]]" = queue.Queue(maxsize=depth)
        self._retry = retry if retry is not None else RetryPolicy(max_attempts=retries + 1)
        self._stats = stats
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        for idx in self._indices:
            try:
                page = self._retry.call(
                    lambda idx=idx: self._load(idx),
                    # the old contract retried any exception; keep it, minus
                    # deterministic corruption
                    retryable=(Exception,),
                    nonretryable=(PageCorruptError,),
                    stats=self._stats,
                    describe=f"page {idx} load",
                )
            except Exception as e:
                self._queue.put((idx, e))
                continue
            self._queue.put((idx, page))

    def __iter__(self) -> Iterator[tuple[int, dict]]:
        # the worker puts exactly one result per index, in order
        for idx in self._indices:
            with span(tracing.PAGE_WAIT, page=idx):
                _, item = self._queue.get()
            if isinstance(item, PageCorruptError):
                raise item  # already the actionable error; don't bury it
            if isinstance(item, Exception):
                raise RuntimeError(f"page {idx} failed to load after retries") from item
            yield idx, item
