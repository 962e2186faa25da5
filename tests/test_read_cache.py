"""Content-addressed read-through cache (storage_plugins/cache.py).

Covers the serving-path guarantees: repeat reads hit the local store (zero
origin bytes), concurrent readers of one digest share a single origin
fetch, eviction respects a tight byte budget LRU-wise, a corrupt cache
entry falls back to the origin and re-populates, ranged reads pass through
untouched, and fault injection through the cache wrapper (chaos surface)
behaves like any other plugin stack.
"""

import asyncio

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, telemetry
from torchsnapshot_tpu.io_types import ReadIO, WriteIO
from torchsnapshot_tpu.storage_plugins.cache import (
    CachedStoragePlugin,
    find_read_cache,
)
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs


class CountingPlugin(MemoryStoragePlugin):
    """Memory plugin that counts origin reads."""

    def __init__(self) -> None:
        super().__init__()
        self.reads = 0
        self.read_bytes = 0

    async def read(self, read_io: ReadIO) -> None:
        self.reads += 1
        await super().read(read_io)
        self.read_bytes += read_io.buf.getbuffer().nbytes


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def make_cache(tmp_path, inner=None, max_bytes=1 << 30):
    inner = inner or CountingPlugin()
    plugin = CachedStoragePlugin(
        inner, origin_id="memory://t", cache_dir=str(tmp_path), max_bytes=max_bytes
    )
    return plugin, inner


def seed(inner, path, data):
    run(inner.write(WriteIO(path=path, buf=data)))


def read(plugin, path, byte_range=None):
    io = ReadIO(path=path, byte_range=byte_range)
    run(plugin.read(io))
    return io.buf.getvalue()


def test_read_through_and_hit(tmp_path):
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", b"x" * 1000)
    assert read(plugin, "obj") == b"x" * 1000
    assert inner.reads == 1
    # Second read: cache hit, origin untouched.
    assert read(plugin, "obj") == b"x" * 1000
    assert inner.reads == 1
    run(plugin.close())


def test_digest_keyed_entries_shared_across_paths(tmp_path):
    """Two paths with the SAME content digest share one cache entry — the
    content-addressed property that makes incremental snapshot chains
    cache-efficient."""
    import hashlib

    data = b"y" * 2048
    sha = hashlib.sha256(data).hexdigest()
    plugin, inner = make_cache(tmp_path)
    seed(inner, "a/obj", data)
    seed(inner, "b/obj", data)
    plugin.attach_digest_index(
        {"a/obj": (len(data), sha, None), "b/obj": (len(data), sha, None)}
    )
    assert read(plugin, "a/obj") == data
    assert read(plugin, "b/obj") == data  # digest hit: no second origin read
    assert inner.reads == 1
    run(plugin.close())


class SlowCountingPlugin(CountingPlugin):
    """Origin whose reads suspend (like any network backend), opening the
    window in which concurrent readers must share one in-flight fetch."""

    async def read(self, read_io: ReadIO) -> None:
        await asyncio.sleep(0.01)
        await super().read(read_io)


def test_concurrent_readers_share_one_origin_fetch(tmp_path):
    plugin, inner = make_cache(tmp_path, inner=SlowCountingPlugin())
    seed(inner, "obj", b"z" * 4096)

    async def both():
        a = ReadIO(path="obj")
        b = ReadIO(path="obj")
        await asyncio.gather(plugin.read(a), plugin.read(b))
        return a.buf.getvalue(), b.buf.getvalue()

    got_a, got_b = run(both())
    assert got_a == got_b == b"z" * 4096
    assert inner.reads == 1, "concurrent readers must dedup the origin fetch"
    run(plugin.close())


def test_eviction_under_tight_budget(tmp_path):
    plugin, inner = make_cache(tmp_path, max_bytes=2500)
    for i in range(4):
        seed(inner, f"obj{i}", bytes([i]) * 1000)
    for i in range(4):
        read(plugin, f"obj{i}")
    # Budget fits 2 entries: the oldest were evicted.
    total = plugin._scan()
    assert sum(sz for _, sz, _ in total) <= 2500
    # Evicted entries re-fetch from origin and still serve correct bytes.
    reads_before = inner.reads
    assert read(plugin, "obj0") == b"\x00" * 1000
    assert inner.reads == reads_before + 1
    run(plugin.close())


def test_lru_touch_keeps_hot_entries(tmp_path):
    import time as _time

    plugin, inner = make_cache(tmp_path, max_bytes=2500)
    seed(inner, "hot", b"h" * 1000)
    seed(inner, "cold", b"c" * 1000)
    read(plugin, "hot")
    _time.sleep(0.02)
    read(plugin, "cold")
    _time.sleep(0.02)
    read(plugin, "hot")  # bump hot's recency above cold's
    _time.sleep(0.02)
    seed(inner, "new", b"n" * 1000)
    read(plugin, "new")  # overflows the budget -> evicts LRU (cold)
    reads_before = inner.reads
    read(plugin, "hot")
    assert inner.reads == reads_before, "hot entry should have survived"
    read(plugin, "cold")
    assert inner.reads == reads_before + 1, "cold entry should be evicted"
    run(plugin.close())


def test_corrupt_entry_falls_back_and_repopulates(tmp_path):
    import hashlib

    data = b"q" * 1500
    sha = hashlib.sha256(data).hexdigest()
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    plugin.attach_digest_index({"obj": (len(data), sha, None)})
    read(plugin, "obj")
    assert inner.reads == 1
    # Corrupt the cache entry in place (same size, different bytes).
    entry = plugin._digest_entry_path(sha)
    with open(entry, "wb") as f:
        f.write(b"!" * 1500)
    tm = telemetry.Telemetry()
    prev = telemetry.activate(tm)
    try:
        assert read(plugin, "obj") == data  # falls back to origin
    finally:
        telemetry.deactivate(tm, prev)
    assert inner.reads == 2
    assert tm.metrics.as_dict().get("cache.corrupt_entries") == 1
    # Re-populated: next read hits again.
    assert read(plugin, "obj") == data
    assert inner.reads == 2
    run(plugin.close())


def test_crc_validation_without_sha(tmp_path):
    """Sha-less sidecar records (dedup digests off) still validate hits by
    size+crc32 — a corrupt path-keyed entry never serves bad bytes."""
    import zlib

    data = b"r" * 900
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    plugin.attach_digest_index({"obj": (len(data), None, zlib.crc32(data))})
    read(plugin, "obj")
    entry = plugin._path_entry_path("obj")
    with open(entry, "wb") as f:
        f.write(b"#" * 900)
    assert read(plugin, "obj") == data
    assert inner.reads == 2
    run(plugin.close())


def test_ranged_reads_pass_through_and_serve_from_cached(tmp_path):
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", bytes(range(200)))
    # Ranged miss: passes through (lazy reads must not over-fetch).
    assert read(plugin, "obj", byte_range=(10, 20)) == bytes(range(10, 20))
    assert inner.reads == 1
    # Populate via a full read, then ranges serve locally.
    read(plugin, "obj")
    assert inner.reads == 2
    assert read(plugin, "obj", byte_range=(5, 9)) == bytes(range(5, 9))
    assert inner.reads == 2
    run(plugin.close())


def test_full_extent_range_populates(tmp_path):
    """The scheduler expresses raw full-object reads as (0, nbytes) ranges;
    with the size known from the digest index these populate the cache."""
    import zlib

    data = b"s" * 640
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    plugin.attach_digest_index({"obj": (len(data), None, zlib.crc32(data))})
    assert read(plugin, "obj", byte_range=(0, 640)) == data
    assert inner.reads == 1
    assert read(plugin, "obj", byte_range=(0, 640)) == data
    assert inner.reads == 1, "full-extent range should be served from cache"
    run(plugin.close())


def test_write_through_invalidates_path_entry(tmp_path):
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", b"old")
    read(plugin, "obj")
    run(plugin.write(WriteIO(path="obj", buf=b"newer")))
    assert read(plugin, "obj") == b"newer"
    run(plugin.close())


def test_snapshot_restore_zero_origin_bytes_on_repeat(tmp_path):
    """End-to-end: K=3 simulated replicas restore one snapshot through the
    knob-wrapped cache; every replica after the first reads 0 bytes from
    origin storage."""
    snap_path = str(tmp_path / "snap")
    cache_dir = str(tmp_path / "cache")
    state = StateDict(
        a=np.arange(512, dtype=np.float32),
        b=np.arange(512, 1024).astype(np.int64),
    )
    Snapshot.take(snap_path, {"app": state})
    origin_bytes = []
    with knobs.override_read_cache_dir(cache_dir):
        for _ in range(3):
            tm = telemetry.Telemetry()
            tgt = StateDict(
                a=np.zeros(512, dtype=np.float32),
                b=np.zeros(512, dtype=np.int64),
            )
            Snapshot(snap_path).restore({"app": tgt}, _telemetry=tm)
            assert np.array_equal(tgt["a"], state["a"])
            assert np.array_equal(tgt["b"], state["b"])
            m = tm.metrics.as_dict()
            origin_bytes.append(
                sum(
                    v
                    for k, v in m.items()
                    if k.startswith("storage.") and k.endswith(".read_bytes")
                )
            )
    assert origin_bytes[0] > 0
    assert origin_bytes[1] == 0 and origin_bytes[2] == 0, origin_bytes


def test_find_read_cache_through_fault_wrapper(tmp_path):
    from torchsnapshot_tpu.faults import FaultyStoragePlugin, parse_fault_spec

    plugin, _ = make_cache(tmp_path)
    wrapped = FaultyStoragePlugin(plugin, parse_fault_spec("seed=1"))
    assert find_read_cache(wrapped) is plugin
    assert find_read_cache(MemoryStoragePlugin()) is None
    run(plugin.close())


def test_chaos_faults_through_cache_wrapper(tmp_path):
    """Fault injection composes with the cache: transient read faults on
    the wrapped stack retry through the real cloud_retry machinery and the
    restore still lands bit-exact; a permanent metadata fault surfaces."""
    snap_path = str(tmp_path / "snap")
    cache_dir = str(tmp_path / "cache")
    state = StateDict(w=np.arange(256, dtype=np.float32))
    Snapshot.take(snap_path, {"app": state})

    with knobs.override_read_cache_dir(cache_dir):
        with knobs.override_faults("seed=3;backoff=0.01;op=read,kind=transient,times=2"):
            tm = telemetry.Telemetry()
            tgt = StateDict(w=np.zeros(256, dtype=np.float32))
            Snapshot(snap_path).restore({"app": tgt}, _telemetry=tm)
            assert np.array_equal(tgt["w"], state["w"])
            assert tm.metrics.as_dict().get("faults.transient", 0) >= 1

    with knobs.override_read_cache_dir(str(tmp_path / "cache2")):
        with knobs.override_faults("op=read,kind=fail,path=.snapshot_metadata"):
            with pytest.raises(Exception):
                tgt = StateDict(w=np.zeros(256, dtype=np.float32))
                Snapshot(snap_path).restore({"app": tgt})


def test_torn_commit_through_cache_leaves_no_snapshot(tmp_path):
    """A torn metadata write injected through the cache-wrapped stack
    aborts cleanly: no commit marker lands, and a retake through the same
    stack succeeds and restores bit-exact."""
    import os

    snap_path = str(tmp_path / "snap")
    cache_dir = str(tmp_path / "cache")
    state = StateDict(w=np.arange(128, dtype=np.float32))
    with knobs.override_read_cache_dir(cache_dir):
        with knobs.override_faults(
            "op=write,kind=torn,bytes=16,path=.snapshot_metadata"
        ):
            with pytest.raises(Exception):
                Snapshot.take(snap_path, {"app": state})
        assert not os.path.exists(
            os.path.join(snap_path, ".snapshot_metadata")
        ), "torn commit must leave no commit marker"
        Snapshot.take(snap_path, {"app": state})
        tgt = StateDict(w=np.zeros(128, dtype=np.float32))
        Snapshot(snap_path).restore({"app": tgt})
        assert np.array_equal(tgt["w"], state["w"])


def test_populate_failure_is_fail_open(tmp_path):
    """A cache store that cannot be written degrades to origin reads."""
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", b"k" * 100)

    def boom(entry, data):
        raise OSError("disk full")

    plugin._write_entry = boom
    assert read(plugin, "obj") == b"k" * 100
    assert read(plugin, "obj") == b"k" * 100  # origin again, still correct
    assert inner.reads == 2
    run(plugin.close())


def test_eviction_never_touches_pinned_entries(tmp_path):
    """Satellite: LRU eviction skips entries that are mid-populate or have
    an in-flight reader. With every resident entry pinned, a populate that
    overflows the byte budget evicts nothing (the store transiently
    exceeds the budget rather than tear a concurrent read); unpinned, the
    same populate evicts the LRU entry."""
    import os as _os
    import time as _time

    plugin, inner = make_cache(tmp_path, max_bytes=1500)
    seed(inner, "a", b"a" * 1000)
    seed(inner, "b", b"b" * 1000)
    read(plugin, "a")  # resident
    entry_a = plugin._path_entry_path("a")
    assert _os.path.exists(entry_a)

    plugin._pin(entry_a)
    try:
        _time.sleep(0.02)  # entry_a is strictly the LRU candidate
        read(plugin, "b")  # populate overflows the 1500-byte budget
        assert _os.path.exists(entry_a), "evicted a pinned (in-flight) entry"
    finally:
        plugin._unpin(entry_a)
    # Unpinned, the same overflow evicts it.
    plugin._maybe_evict()
    assert not _os.path.exists(entry_a)
    run(plugin.close())


def test_quarantine_path_removes_digest_and_path_entries(tmp_path):
    """The read pipeline's mismatch handler: quarantining a path unlinks
    BOTH the digest-keyed content entry and the path-keyed entry, so bytes
    that failed verification upstream are never served twice."""
    import hashlib as _hashlib
    import os as _os

    plugin, inner = make_cache(tmp_path)
    data = b"q" * 500
    sha = _hashlib.sha256(data).hexdigest()
    plugin.attach_digest_index({"obj": (len(data), sha, None)})
    seed(inner, "obj", data)
    read(plugin, "obj")  # populates the digest-keyed entry
    digest_entry = plugin._digest_entry_path(sha)
    assert _os.path.exists(digest_entry)

    removed = plugin.quarantine_path("obj")
    assert removed == 1, removed
    assert not _os.path.exists(digest_entry)
    # Next read misses and repopulates from origin.
    before = inner.reads
    assert read(plugin, "obj") == data
    assert inner.reads == before + 1
    assert _os.path.exists(digest_entry)
    run(plugin.close())


# ---------------------------------------------------------------------------
# Sparse (chunk-granular) entries — the reshard sub-range tier
# ---------------------------------------------------------------------------

def _chunked_index(data, grain):
    from torchsnapshot_tpu.hashing import digest_of_bytes, record_cache_key, record_chunk_info

    rec = digest_of_bytes(data, grain, want_sha=True)
    info = record_chunk_info(rec)
    assert info is not None, "payload must span several chunks"
    return (len(data), record_cache_key(rec), rec.get("crc"), info)


def test_ranged_miss_populates_and_serves_sub_ranges(tmp_path):
    grain = 4096
    data = bytes(np.random.default_rng(0).integers(0, 256, 20000, np.uint8))
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    plugin.attach_digest_index({"obj": _chunked_index(data, grain)})
    tm = telemetry.Telemetry()
    prev = telemetry.activate(tm)
    try:
        # Chunk-aligned miss: passes through AND populates chunks 0-1.
        assert read(plugin, "obj", (0, 2 * grain)) == data[: 2 * grain]
        assert inner.reads == 1
        # Repeat: served from the sparse entry, zero origin reads.
        assert read(plugin, "obj", (0, 2 * grain)) == data[: 2 * grain]
        assert inner.reads == 1
        # A sub-range inside the populated chunks also hits.
        assert read(plugin, "obj", (100, grain + 50)) == data[100 : grain + 50]
        assert inner.reads == 1
        # A range touching an unpopulated chunk misses (and populates it).
        assert (
            read(plugin, "obj", (2 * grain, 4 * grain))
            == data[2 * grain : 4 * grain]
        )
        assert inner.reads == 2
        # Unaligned fetch: only fully contained chunks populate — chunk 4
        # (partial in the fetched range) stays absent.
        assert (
            read(plugin, "obj", (4 * grain, 4 * grain + 100))
            == data[4 * grain : 4 * grain + 100]
        )
        n3 = inner.reads
        assert (
            read(plugin, "obj", (4 * grain, len(data)))
            == data[4 * grain :]
        )
        assert inner.reads == n3 + 1  # the partial chunk was NOT cached
    finally:
        telemetry.deactivate(tm, prev)
    m = tm.metrics.as_dict()
    assert m.get("cache.range_populates", 0) >= 2, m
    assert m.get("cache.range_misses", 0) >= 2, m
    assert m.get("cache.bypass_reads", 0) == 0, m
    run(plugin.close())


def test_sparse_entry_promotes_to_full_entry(tmp_path):
    grain = 4096
    data = bytes(np.random.default_rng(1).integers(0, 256, 3 * grain, np.uint8))
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    index = _chunked_index(data, grain)
    plugin.attach_digest_index({"obj": index})
    for k in range(3):
        read(plugin, "obj", (k * grain, (k + 1) * grain))
    # All chunks landed: the bitmap is gone and a FULL read hits locally.
    entry = plugin._digest_entry_path(index[1])
    import os as _os

    assert _os.path.exists(entry)
    assert not _os.path.exists(entry + ".chunks")
    n = inner.reads
    assert read(plugin, "obj") == data
    assert inner.reads == n
    run(plugin.close())


def test_sparse_entry_never_serves_as_full_object(tmp_path):
    grain = 4096
    data = bytes(np.random.default_rng(2).integers(0, 256, 3 * grain, np.uint8))
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    plugin.attach_digest_index({"obj": _chunked_index(data, grain)})
    read(plugin, "obj", (0, grain))  # one chunk resident
    # Full-object read: the sparse entry must NOT satisfy it.
    n = inner.reads
    assert read(plugin, "obj") == data
    assert inner.reads == n + 1
    run(plugin.close())


def test_corrupt_sparse_chunk_dropped_and_refetched(tmp_path):
    grain = 4096
    data = bytes(np.random.default_rng(3).integers(0, 256, 3 * grain, np.uint8))
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    index = _chunked_index(data, grain)
    plugin.attach_digest_index({"obj": index})
    read(plugin, "obj", (0, 2 * grain))
    entry = plugin._digest_entry_path(index[1])
    with open(entry, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff")
    tm = telemetry.Telemetry()
    prev = telemetry.activate(tm)
    try:
        assert read(plugin, "obj", (0, 2 * grain)) == data[: 2 * grain]
    finally:
        telemetry.deactivate(tm, prev)
    assert tm.metrics.as_dict().get("cache.corrupt_entries", 0) == 1
    import os as _os

    # The corrupt sparse entry was dropped whole (data + bitmap) and the
    # re-fetch re-populated it.
    assert read(plugin, "obj", (0, 2 * grain)) == data[: 2 * grain]
    run(plugin.close())


def test_try_read_range_and_populate_range_publics(tmp_path):
    grain = 4096
    data = bytes(np.random.default_rng(4).integers(0, 256, 4 * grain, np.uint8))
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    plugin.attach_digest_index({"obj": _chunked_index(data, grain)})
    # Nothing resident yet.
    assert run(plugin.try_read_range("obj", 0, grain)) is None
    # populate_range lands the two middle chunks (caller-verified bytes).
    run(plugin.populate_range("obj", grain, 3 * grain, data[grain : 3 * grain]))
    assert (
        run(plugin.try_read_range("obj", grain, 3 * grain))
        == data[grain : 3 * grain]
    )
    assert run(plugin.try_read_range("obj", 0, grain)) is None
    # Digest-unknown paths are refused outright.
    assert run(plugin.try_read_range("other", 0, 10)) is None
    run(plugin.populate_range("other", 0, grain, data[:grain]))
    assert run(plugin.try_read_range("other", 0, grain)) is None
    run(plugin.close())


def test_quarantine_and_eviction_remove_sparse_state(tmp_path):
    grain = 4096
    data = bytes(np.random.default_rng(5).integers(0, 256, 3 * grain, np.uint8))
    plugin, inner = make_cache(tmp_path)
    seed(inner, "obj", data)
    index = _chunked_index(data, grain)
    plugin.attach_digest_index({"obj": index})
    read(plugin, "obj", (0, grain))
    entry = plugin._digest_entry_path(index[1])
    import os as _os

    assert _os.path.exists(entry + ".chunks")
    assert plugin.quarantine_path("obj") >= 1
    assert not _os.path.exists(entry)
    assert not _os.path.exists(entry + ".chunks")
    run(plugin.close())


def test_bypass_vs_range_miss_metric_split(tmp_path):
    plugin, inner = make_cache(tmp_path)
    seed(inner, "known", b"a" * 10000)
    seed(inner, "unknown", b"b" * 10000)
    plugin.attach_digest_index({"known": _chunked_index(b"a" * 10000, 4096)})
    tm = telemetry.Telemetry()
    prev = telemetry.activate(tm)
    try:
        read(plugin, "unknown", (5, 55))  # digest-unknown -> bypass
        read(plugin, "known", (5, 55))  # digest-known -> range miss
    finally:
        telemetry.deactivate(tm, prev)
    m = tm.metrics.as_dict()
    assert m.get("cache.bypass_reads", 0) == 1, m
    assert m.get("cache.range_misses", 0) == 1, m
    run(plugin.close())


@pytest.mark.parametrize("byte_range", [None, (1 << 16, 3 << 16)], ids=["whole", "range"])
def test_cache_entry_outlives_the_fetched_buffer(tmp_path, byte_range):
    """A fetched buffer belongs to its read (the fs plugin's native read
    hands its array on by reference): the cache fills from a copy
    of its own, so after the consumer has scribbled over and released the
    buffer it was given, the entry still serves the bytes bit for bit, with
    no second origin read."""
    import os

    from torchsnapshot_tpu import native
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    data = os.urandom(1 << 18)
    want = data if byte_range is None else data[slice(*byte_range)]
    origin_reads = []

    class CountingFS(FSStoragePlugin):
        async def read(self, read_io: ReadIO) -> None:
            origin_reads.append(read_io.path)
            await super().read(read_io)

    (tmp_path / "origin").mkdir()
    with knobs.override_direct_io_threshold_bytes(1024):
        inner = CountingFS(str(tmp_path / "origin"))
        assert inner._native is not None
        plugin, _ = make_cache(tmp_path / "cache", inner=inner)
        seed(inner, "obj", data)
        if byte_range is not None:
            # A range is cacheable once the digest index knows the chunk grid.
            plugin.attach_digest_index({"obj": _chunked_index(data, 1 << 16)})
        first = ReadIO(path="obj", byte_range=byte_range)
        run(plugin.read(first))
        view = first.buf.getbuffer()
        assert type(view.obj) is np.ndarray and view == want
        view[:] = bytes(len(want))  # the consumer's buffer, to do with as it likes
        view.release()
        del first
        assert read(plugin, "obj", byte_range) == want
        assert origin_reads == ["obj"], "the second read went back to the origin"
        run(plugin.close())
