"""`PageStore.read_page`: one read, one CRC32, pages as views over the blob.

An uncompressed page with a manifest CRC decodes as `np.frombuffer` views
over its checked read buffer; everything else goes through `np.load`. The
contract, as tests: both paths return the same arrays, the view path is
taken exactly where it should be (``TransferStats.direct_page_reads``),
every damaged byte is still caught by the manifest CRC, and a directory
that contradicts itself surfaces as `PageDecodeError` naming the page.
"""
import json
import os
import zlib

import numpy as np
import pytest

from repro.core import BoosterParams, ExecutionPolicy, GradientBooster
from repro.data import pages
from repro.data.dmatrix import IterDMatrix
from repro.data.pages import PageCorruptError, PageDecodeError, PageStore, TransferStats
from repro.data.synthetic import SyntheticSource
from repro.fault import FaultSpec, injected

_RNG = np.random.default_rng(16)
_BINS = _RNG.integers(0, 32, size=(64, 8)).astype(np.uint8)

# (codec, arrays) of the pages both decode paths must agree on
CASES = {
    "raw": ("raw", {"bins": _BINS}),
    "bitpack": ("bitpack", {"bins": _BINS}),
    "bins_and_float_sidecar": ("raw", {"bins": _BINS, "labels": _RNG.normal(size=64).astype(np.float32)}),
    "zero_rows": ("raw", {"bins": np.zeros((0, 8), np.uint8)}),
    "fortran_order": ("raw", {"bins": np.asfortranarray(_BINS)}),
}


def _store(tmp_path, codec="raw", arrays=None, **kw):
    stats = TransferStats()
    store = PageStore(str(tmp_path / "pages"), stats=stats, codec=codec, **kw)
    idx = store.write_page(arrays if arrays is not None else {"bins": _BINS})
    return store, stats, idx


def _blob_path(store, idx):
    return os.path.join(store.root, f"page_{idx:06d}.bin")


def _reopen(store, idx, blob=None):
    """Reopen ``store`` with page ``idx``'s manifest CRC made to match
    ``blob`` (written over the page), or dropped as pre-durability manifests
    have it when ``blob`` is None."""
    mpath = os.path.join(store.root, "manifest.json")
    with open(mpath) as fh:
        meta = json.load(fh)
    if blob is None:
        meta["pages"][idx].pop("crc32")
    else:
        with open(_blob_path(store, idx), "wb") as fh:
            fh.write(bytes(blob))
        meta["pages"][idx]["crc32"] = zlib.crc32(bytes(blob))
    with open(mpath, "w") as fh:
        json.dump(meta, fh)
    return PageStore(store.root, stats=TransferStats())


@pytest.mark.parametrize("case", sorted(CASES))
def test_view_path_equals_zipfile_path(tmp_path, monkeypatch, case):
    codec, arrays = CASES[case]
    store, stats, idx = _store(tmp_path, codec, arrays)
    viewed = store.read_page(idx)
    assert stats.direct_page_reads == 1
    monkeypatch.setattr(pages, "_decode_views", lambda buf: None)
    loaded = store.read_page(idx)
    assert stats.direct_page_reads == 1 and stats.page_loads == 2
    assert sorted(viewed) == sorted(loaded) == sorted(arrays)
    for key, want in arrays.items():
        got, ref = viewed[key], loaded[key]
        assert got.dtype == ref.dtype == want.dtype
        assert got.shape == ref.shape == want.shape
        assert got.tobytes() == ref.tobytes() == want.tobytes()
        assert got.flags.f_contiguous == ref.flags.f_contiguous


def test_each_read_gets_its_own_writable_buffer(tmp_path):
    store, _, idx = _store(tmp_path)
    first, second = store.read_page(idx)["bins"], store.read_page(idx)["bins"]
    assert not np.shares_memory(first, second)
    assert first.flags.writeable  # as np.load's arrays are
    first[:] = 0
    np.testing.assert_array_equal(second, _BINS)


class _ZlibAsZstd:
    """Stands in for `zstandard` where it is not installed: the page store
    only calls these two methods."""

    class ZstdCompressor:
        def __init__(self, level):
            pass

        def compress(self, data):
            return zlib.compress(bytes(data))

    class ZstdDecompressor:
        def decompress(self, data):
            return zlib.decompress(bytes(data))


@pytest.mark.parametrize("kind", ["raw_with_crc", "zstd", "manifest_without_crc"])
def test_direct_page_reads_counts_view_decodes_only(tmp_path, monkeypatch, kind):
    if kind == "zstd" and pages._zstd is None:
        monkeypatch.setattr(pages, "_zstd", _ZlibAsZstd)
    store, stats, idx = _store(tmp_path, compress=kind == "zstd")
    with open(_blob_path(store, idx), "rb") as fh:
        blob = fh.read()
    assert blob[:4] == (b"ZST0" if kind == "zstd" else b"RAW0")
    if kind == "manifest_without_crc":
        store = _reopen(store, idx)
        stats = store.stats
    for _ in range(3):
        np.testing.assert_array_equal(store.read_page(idx)["bins"], _BINS)
    assert stats.page_loads == 3
    assert stats.direct_page_reads == (3 if kind == "raw_with_crc" else 0)
    stats.reset()
    assert stats.direct_page_reads == 0


def test_streaming_fit_reads_every_page_as_views(tmp_path):
    source = SyntheticSource(n_rows=900, num_features=8, batch_rows=300, task="higgs", seed=5)
    dm = IterDMatrix(source, max_bin=32, cache_dir=str(tmp_path / "cache"), page_bytes=2048)
    assert dm.page_set().store.n_pages > 1
    booster = GradientBooster(
        BoosterParams(n_estimators=2, max_depth=3, max_bin=32, seed=0),
        policy=ExecutionPolicy(mode="out_of_core"),
    )
    booster.fit(dm)
    assert dm.stats.page_loads > 0
    assert dm.stats.direct_page_reads == dm.stats.page_loads


def _regions(blob):
    """One byte offset inside each region of a one-member RAW0 blob."""
    central = blob.index(b"PK\x01\x02")
    return {
        "tag": 1,
        "local_header": 4 + 6,
        "npy_header": blob.index(b"\x93NUMPY") + 20,
        "data": central - 1,
        "central_directory": central + 20,
        "end_record": len(blob) - 10,
    }


@pytest.mark.parametrize("region", ["tag", "local_header", "npy_header", "data", "central_directory", "end_record"])
def test_one_flipped_byte_in_any_region_fails_the_crc(tmp_path, region):
    store, stats, idx = _store(tmp_path)
    path = _blob_path(store, idx)
    with open(path, "rb") as fh:
        blob = bytearray(fh.read())
    blob[_regions(bytes(blob))[region]] ^= 0x01
    with open(path, "wb") as fh:
        fh.write(bytes(blob))
    with pytest.raises(PageCorruptError, match=f"page {idx} is corrupt: CRC32 mismatch") as err:
        store.read_page(idx)
    assert not isinstance(err.value, PageDecodeError)
    assert stats.page_loads == 0 and stats.direct_page_reads == 0


def _garble(blob, how):
    central = blob.index(b"PK\x01\x02")
    if how == "central_signature":
        blob[central] ^= 0xFF
    elif how == "local_offset":  # the entry points into the tag's middle
        blob[central + 42:central + 46] = (1).to_bytes(4, "little")
    elif how == "member_size":
        size = int.from_bytes(blob[central + 20:central + 24], "little")
        blob[central + 20:central + 24] = (size - 1).to_bytes(4, "little")
    elif how == "local_name":
        blob[4 + 30] ^= 0x20
    elif how == "npy_shape":
        at = blob.index(b"(64, 8)")
        blob[at + 1:at + 3] = b"65"


@pytest.mark.parametrize("how", ["central_signature", "local_offset", "member_size", "local_name", "npy_shape"])
def test_garbled_directory_with_matching_crc_is_a_decode_error(tmp_path, monkeypatch, how):
    store, _, idx = _store(tmp_path)
    store.write_page({"bins": _BINS[::-1].copy()})
    with open(_blob_path(store, idx), "rb") as fh:
        blob = bytearray(fh.read())
    _garble(blob, how)
    store = _reopen(store, idx, blob)
    # the view parse itself must object: it is the only decoder that runs
    monkeypatch.setattr(pages, "_decode", lambda blob: pytest.fail("fell back to np.load"))
    with pytest.raises(PageDecodeError, match=f"page {idx} failed 'raw' decode"):
        store.read_page(idx)
    np.testing.assert_array_equal(store.read_page(1)["bins"], _BINS[::-1])  # neighbour intact


def test_decode_fault_site_fires_on_the_view_path(tmp_path):
    store, stats, idx = _store(tmp_path)
    with injected([FaultSpec(site="page_store.decode", at=2)]) as inj:
        np.testing.assert_array_equal(store.read_page(idx)["bins"], _BINS)
        with pytest.raises(PageDecodeError, match=f"page {idx} failed 'raw' decode"):
            store.read_page(idx)
        assert [(site, n) for site, n, _ in inj.fired] == [("page_store.decode", 2)]
    assert stats.direct_page_reads == 1
