"""Unit coverage of the parallel chunked hashing engine (``hashing.py``):
crc32_combine property tests against ``zlib.crc32``, tree-digest records,
the async chunk/serial hashers, and the verification helpers every sidecar
consumer shares."""

import asyncio
import hashlib
import random
import zlib
from concurrent.futures import ThreadPoolExecutor

import pytest

from torchsnapshot_tpu import hashing


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ------------------------------------------------------------ crc32_combine


def test_crc32_combine_random_splits() -> None:
    """Property test: combining the parts' crcs at ANY split point equals
    hashing the concatenation, bit for bit."""
    rng = random.Random(42)
    for _ in range(100):
        n = rng.randrange(0, 4096)
        data = rng.randbytes(n)
        k = rng.randrange(0, n + 1)
        got = hashing.crc32_combine(
            zlib.crc32(data[:k]), zlib.crc32(data[k:]), n - k
        )
        assert got == zlib.crc32(data)


def test_crc32_combine_empty_and_one_byte_chunks() -> None:
    data = b"torchsnapshot"
    # Empty right side: identity.
    assert hashing.crc32_combine(zlib.crc32(data), zlib.crc32(b""), 0) == zlib.crc32(data)
    # Empty left side.
    assert hashing.crc32_combine(zlib.crc32(b""), zlib.crc32(data), len(data)) == zlib.crc32(data)
    # Fold one byte at a time through combine only.
    crc = zlib.crc32(data[:1])
    for i in range(1, len(data)):
        crc = hashing.crc32_combine(crc, zlib.crc32(data[i : i + 1]), 1)
    assert crc == zlib.crc32(data)


def test_crc32_combine_associative() -> None:
    """combine(combine(a, b), c) == combine(a, combine(b, c)) == crc(abc):
    chunk crcs may merge in any grouping (completion order independence)."""
    rng = random.Random(7)
    for _ in range(25):
        a, b, c = (rng.randbytes(rng.randrange(0, 500)) for _ in range(3))
        ca, cb, cc = zlib.crc32(a), zlib.crc32(b), zlib.crc32(c)
        left = hashing.crc32_combine(
            hashing.crc32_combine(ca, cb, len(b)), cc, len(c)
        )
        right = hashing.crc32_combine(
            ca, hashing.crc32_combine(cb, cc, len(c)), len(b) + len(c)
        )
        assert left == right == zlib.crc32(a + b + c)


def test_chunk_extents() -> None:
    assert hashing.chunk_extents(0, 10) == []
    assert hashing.chunk_extents(10, 10) == [(0, 10)]
    assert hashing.chunk_extents(25, 10) == [(0, 10), (10, 20), (20, 25)]
    assert hashing.chunk_extents(5, 0) == [(0, 5)]  # grain 0: one extent


# ------------------------------------------------------------------ records


def test_digest_of_bytes_small_object_keeps_v1_record() -> None:
    data = b"x" * 100
    rec = hashing.digest_of_bytes(data, 1000)
    assert rec == [zlib.crc32(data), 100, hashlib.sha256(data).hexdigest()]
    assert not hashing.is_v2_record(rec)


def test_digest_of_bytes_v2_record_fields() -> None:
    data = random.Random(0).randbytes(2500)
    rec = hashing.digest_of_bytes(data, 1000)
    assert hashing.is_v2_record(rec)
    assert rec["crc"] == zlib.crc32(data)  # combined == serial fold
    assert rec["size"] == 2500
    assert rec["grain"] == 1000
    assert len(rec["chunks"]) == len(rec["crcs"]) == 3
    for (b, e), sha, crc in zip(
        hashing.chunk_extents(2500, 1000), rec["chunks"], rec["crcs"]
    ):
        assert sha == hashlib.sha256(data[b:e]).hexdigest()
        assert crc == zlib.crc32(data[b:e])
    assert rec["root"] == hashing.tree_root(rec["chunks"])
    assert rec["sha"] is None


def test_record_accessors_all_formats() -> None:
    data = b"y" * 3000
    v2 = hashing.digest_of_bytes(data, 1000)
    v1 = hashing.serial_digest(memoryview(data), True)
    legacy = zlib.crc32(data)
    for rec in (v1, v2, legacy):
        assert hashing.record_crc(rec) == zlib.crc32(data)
    assert hashing.record_size(v1) == hashing.record_size(v2) == 3000
    assert hashing.record_size(legacy) is None
    assert hashing.record_whole_sha(v1) == hashlib.sha256(data).hexdigest()
    assert hashing.record_whole_sha(v2) is None
    assert hashing.record_whole_sha(legacy) is None
    # Junk shapes never crash the accessors.
    for junk in (None, [], [1, 2], {"v": 3}, "x", [1, "a", None]):
        hashing.record_crc(junk)
        hashing.record_size(junk)
        hashing.record_content_keys(junk)
        assert hashing.record_chunk_info(junk) is None


def test_content_keys_bridge_v1_and_v2() -> None:
    """A v2 record carrying the compat whole-sha intersects a v1 record of
    the same bytes — the mixed-chain dedup identity."""
    data = b"z" * 5000
    v1 = hashing.serial_digest(memoryview(data), True)
    v2 = hashing.digest_of_bytes(data, 1024)
    assert not set(hashing.record_content_keys(v1)) & set(
        hashing.record_content_keys(v2)
    )  # tree root alone can't match a whole sha...
    v2_compat = _run(
        _hash_with_whole_sha(data, 1024)
    )
    assert set(hashing.record_content_keys(v1)) & set(
        hashing.record_content_keys(v2_compat)
    )  # ...but the compat shim's whole sha does
    # crc-only records carry no collision-resistant identity.
    assert hashing.record_content_keys([123, 10, None]) == ()
    assert hashing.record_content_keys(123) == ()


async def _hash_with_whole_sha(data, grain):
    ex = ThreadPoolExecutor(max_workers=2)
    try:
        return await hashing.hash_buffer(
            memoryview(data),
            grain,
            True,
            asyncio.get_running_loop(),
            ex,
            want_whole_sha=True,
        )
    finally:
        ex.shutdown(wait=True)


def test_record_cache_key_formats() -> None:
    data = b"q" * 4000
    v1 = hashing.serial_digest(memoryview(data), True)
    v2 = hashing.digest_of_bytes(data, 1000)
    assert hashing.record_cache_key(v1) == hashlib.sha256(data).hexdigest()
    assert hashing.record_cache_key(v2) == f"{v2['root']}-t1000"
    assert hashing.record_cache_key([1, 2, None]) is None
    assert hashing.record_cache_key(7) is None


# ------------------------------------------------------------------ engines


def test_hash_buffer_matches_sync_recompute() -> None:
    data = random.Random(3).randbytes(10_000)

    async def go():
        ex = ThreadPoolExecutor(max_workers=4)
        try:
            return await hashing.hash_buffer(
                memoryview(data), 1024, True, asyncio.get_running_loop(), ex
            )
        finally:
            ex.shutdown(wait=True)

    assert _run(go()) == hashing.digest_of_bytes(data, 1024)


@pytest.mark.parametrize("want_sha", [True, False], ids=["sha", "crc-only"])
@pytest.mark.parametrize("grain", [0, 512, 1024, 10**6])
def test_hash_buffer_equals_serial_and_sync_digests(grain, want_sha) -> None:
    """At every grain (0 = serial, inside, on and beyond the object) the
    pool's chunk-parallel record is the one the synchronous recompute
    gives, its crc32 is the serial fold's, and without shas it carries
    none."""
    data = random.Random(grain).randbytes(5000)

    async def go():
        ex = ThreadPoolExecutor(max_workers=3)
        try:
            return await hashing.hash_buffer(
                memoryview(data), grain, want_sha, asyncio.get_running_loop(), ex
            )
        finally:
            ex.shutdown(wait=True)

    rec = _run(go())
    assert rec == hashing.digest_of_bytes(data, grain, want_sha=want_sha)
    serial = hashing.serial_digest(memoryview(data), want_sha)
    assert hashing.record_crc(rec) == serial[0] == zlib.crc32(data)
    assert hashing.record_size(rec) == serial[1]
    if grain <= 0 or grain >= len(data):
        assert rec == serial
    else:
        assert hashing.is_v2_record(rec)
        assert len(rec["crcs"]) == -(-len(data) // grain)
        assert (rec["chunks"] is None and rec["root"] is None) != want_sha


# ------------------------------------------------------------- verification


def _corrupt(data: bytes, offset: int) -> bytes:
    out = bytearray(data)
    out[offset] ^= 0xFF
    return bytes(out)


def test_verify_buffer_and_find_bad_chunks() -> None:
    data = random.Random(9).randbytes(4096)
    rec = hashing.digest_of_bytes(data, 1024)
    assert hashing.verify_buffer(memoryview(data), rec) is None
    assert hashing.find_bad_chunks(memoryview(data), rec) == []
    bad = _corrupt(data, 2048 + 5)  # chunk 2
    problem = hashing.verify_buffer(memoryview(bad), rec)
    assert problem is not None and "[2]" in problem
    assert hashing.find_bad_chunks(memoryview(bad), rec) == [2]
    # Size mismatch reported before any hashing.
    assert "size" in hashing.verify_buffer(memoryview(data[:-1]), rec)
    # v1 records verify by whole sha; not chunk-attributable.
    v1 = hashing.serial_digest(memoryview(data), True)
    assert hashing.verify_buffer(memoryview(data), v1) is None
    assert "sha256" in hashing.verify_buffer(memoryview(bad), v1)
    assert hashing.find_bad_chunks(memoryview(bad), v1) is None


def test_verify_range_contained_chunks_only() -> None:
    data = random.Random(11).randbytes(4096 + 100)  # 5 chunks, short tail
    rec = hashing.digest_of_bytes(data, 1024)
    bad = _corrupt(data, 2100)  # chunk 2 = [2048, 3072)

    def rng_view(d, b, e):
        return memoryview(d)[b:e]

    # Range fully covering the corrupt chunk: detected.
    assert hashing.range_verifiable(rec, 1024, 3072)
    problem = hashing.verify_range(rng_view(bad, 1024, 3072), rec, 1024, 3072)
    assert problem is not None and "[2]" in problem
    # Clean range next to it: passes.
    assert hashing.verify_range(rng_view(bad, 0, 2048), rec, 0, 2048) is None
    # Range only PARTIALLY covering the corrupt chunk: edge chunks are
    # skipped (their digests cover unfetched bytes) — not verifiable.
    assert hashing.verify_range(rng_view(bad, 2100, 2500), rec, 2100, 2500) is None
    assert not hashing.range_verifiable(rec, 2100, 2500)
    # The short tail chunk verifies when the range reaches the object end.
    tail_bad = _corrupt(data, 4096 + 50)
    assert (
        hashing.verify_range(
            rng_view(tail_bad, 4096, len(data)), rec, 4096, len(data)
        )
        is not None
    )
    # v1 records can never verify a range.
    v1 = hashing.serial_digest(memoryview(data), True)
    assert not hashing.range_verifiable(v1, 0, 1024)
    assert hashing.verify_range(rng_view(bad, 0, 1024), v1, 0, 1024) is None


def test_verify_chunks_of_intersecting_range() -> None:
    """The cache-side helper verifies chunks INTERSECTING the range (it
    holds the full entry, so even partially-covered chunks check whole)."""
    data = random.Random(13).randbytes(4096)
    rec = hashing.digest_of_bytes(data, 1024)
    info = hashing.record_chunk_info(rec)
    bad = _corrupt(data, 2100)  # chunk 2
    assert hashing.verify_chunks_of(memoryview(data), info) is None
    assert hashing.verify_chunks_of(memoryview(bad), info) is not None
    # A range merely touching chunk 2 still verifies it (full bytes held).
    assert (
        hashing.verify_chunks_of(memoryview(bad), info, 2100, 2101)
        is not None
    )
    # A range entirely inside other chunks passes.
    assert hashing.verify_chunks_of(memoryview(bad), info, 0, 1024) is None
