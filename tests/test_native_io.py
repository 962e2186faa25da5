"""Native O_DIRECT I/O engine tests (``torchsnapshot_tpu/native``).

Covers: build+load, write/read round-trips at aligned/unaligned sizes,
ranged reads at unaligned offsets, buffered fallback on filesystems without
O_DIRECT (tmpfs), the disable knob, and FS-plugin integration parity with the
pure-Python path.
"""

import os

import numpy as np
import pytest

from torchsnapshot_tpu import native
from torchsnapshot_tpu.io_types import ReadIO, WriteIO
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.utils import knobs


@pytest.fixture(scope="module")
def lib():
    lib = native.load_native()
    if lib is None:
        pytest.skip("native IO engine unavailable")
    return lib


def test_version(lib) -> None:
    assert lib.tss_io_version() >= 1


@pytest.mark.parametrize(
    "nbytes",
    [
        0,
        1,
        4095,
        4096,
        4097,
        1 << 20,
        (1 << 20) + 13,
        3 * 4096,
    ],
)
def test_write_read_roundtrip(lib, tmp_path, nbytes: int) -> None:
    rng = np.random.default_rng(nbytes)
    data = rng.integers(0, 256, size=nbytes, dtype=np.uint8)
    path = str(tmp_path / f"f{nbytes}")
    native.write_file(lib, path, data, direct=True, chunk_bytes=1 << 20)
    assert os.path.getsize(path) == nbytes
    assert native.file_size(lib, path) == nbytes

    out = bytearray(nbytes)
    native.read_into(lib, path, out, offset=0, direct=True, chunk_bytes=1 << 20)
    assert bytes(out) == data.tobytes()


def test_small_chunk_many_iterations(lib, tmp_path) -> None:
    """Chunk smaller than payload: exercises the bounce-buffer loop."""
    data = np.arange(64 * 1024, dtype=np.uint8).tobytes()
    path = str(tmp_path / "chunked")
    native.write_file(lib, path, data, direct=True, chunk_bytes=4096)
    out = bytearray(len(data))
    native.read_into(lib, path, out, direct=True, chunk_bytes=4096)
    assert bytes(out) == data


@pytest.mark.parametrize("offset,length", [(0, 100), (1, 4096), (4095, 2), (8192, 8192), (5000, 70001)])
def test_ranged_read_unaligned(lib, tmp_path, offset: int, length: int) -> None:
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
    path = str(tmp_path / "ranged")
    native.write_file(lib, path, data, direct=True, chunk_bytes=1 << 20)
    out = bytearray(length)
    native.read_into(lib, path, out, offset=offset, direct=True, chunk_bytes=16384)
    assert bytes(out) == data[offset : offset + length]


def test_read_past_eof_raises(lib, tmp_path) -> None:
    path = str(tmp_path / "short")
    native.write_file(lib, path, b"x" * 100, direct=True, chunk_bytes=4096)
    out = bytearray(200)
    with pytest.raises(OSError):
        native.read_into(lib, path, out, offset=0, direct=True)


def test_missing_file_raises(lib, tmp_path) -> None:
    out = bytearray(10)
    with pytest.raises(OSError):
        native.read_into(lib, str(tmp_path / "nope"), out)


def test_tmpfs_fallback(lib) -> None:
    """tmpfs rejects O_DIRECT; the engine must fall back to buffered I/O."""
    if not os.path.isdir("/dev/shm"):
        pytest.skip("no tmpfs mount")
    path = f"/dev/shm/tss_native_test_{os.getpid()}"
    try:
        data = os.urandom(123_456)
        native.write_file(lib, path, data, direct=True, chunk_bytes=1 << 20)
        out = bytearray(len(data))
        native.read_into(lib, path, out, direct=True)
        assert bytes(out) == data
    finally:
        if os.path.exists(path):
            os.remove(path)


def test_disable_knob(monkeypatch) -> None:
    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DISABLE_NATIVE_IO", "1")
    assert native.load_native() is None
    assert not knobs.is_native_io_enabled()


def _plugin_roundtrip(plugin: FSStoragePlugin, nbytes: int) -> None:
    data = os.urandom(nbytes)
    plugin.sync_write(WriteIO(path="obj", buf=data))
    read_io = ReadIO(path="obj")
    plugin.sync_read(read_io)
    assert read_io.buf.getvalue() == data
    # ranged read across the native threshold boundary
    read_io = ReadIO(path="obj", byte_range=(nbytes // 3, nbytes // 3 + nbytes // 2))
    plugin.sync_read(read_io)
    assert read_io.buf.getvalue() == data[nbytes // 3 : nbytes // 3 + nbytes // 2]
    plugin.sync_close()


def test_fs_plugin_native_path(tmp_path) -> None:
    # Build/load the engine BLOCKING so this test exercises the native path
    # even standalone (the plugin's own _native property is non-blocking and
    # would return None while a first-use background build is running).
    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    with knobs.override_direct_io_threshold_bytes(1024):
        plugin = FSStoragePlugin(str(tmp_path))
        assert plugin._native is not None
        _plugin_roundtrip(plugin, 1 << 20)


def test_take_telemetry_says_which_write_path_objects_used(tmp_path) -> None:
    """A run that starts before the engine is built writes buffered at
    first and O_DIRECT later; the take's metrics tell the two apart."""
    from torchsnapshot_tpu import Snapshot, StateDict

    assert native.load_native() is not None
    arr = np.arange(64 * 1024, dtype=np.float32)  # 256 KiB
    with knobs.override_direct_io_threshold_bytes(1024):
        Snapshot.take(str(tmp_path / "native"), {"s": StateDict(a=arr)})
        metrics = Snapshot.last_telemetry.metrics.as_dict()
        assert metrics["storage.fs.native_write_bytes"] >= arr.nbytes
        assert "storage.fs.native_fallback_bytes" not in metrics
        # The engine "not loaded yet": same knobs, nothing to write through.
        orig = native.load_native_nonblocking
        native.load_native_nonblocking = lambda: None
        try:
            Snapshot.take(str(tmp_path / "buffered"), {"s": StateDict(a=arr)})
        finally:
            native.load_native_nonblocking = orig
        metrics = Snapshot.last_telemetry.metrics.as_dict()
        assert metrics["storage.fs.native_fallback_bytes"] >= arr.nbytes
        assert "storage.fs.native_write_bytes" not in metrics


def test_fs_plugin_python_path_parity(tmp_path) -> None:
    with knobs.override_native_io_enabled(False):
        plugin = FSStoragePlugin(str(tmp_path))
        assert plugin._native is None
        _plugin_roundtrip(plugin, 1 << 20)


@pytest.mark.parametrize("nbytes", [0, 1, 4095, 4096, (1 << 20) + 123])
@pytest.mark.parametrize("direct", [True, False])
def test_write_file_digest_matches_zlib(lib, tmp_path, nbytes, direct) -> None:
    """The inline crc32 computed during the write loop must equal zlib's
    over the same bytes, for both IO paths and unaligned sizes; the sha
    slot stays None by design (hashlib's OpenSSL sha is the fast one —
    the scheduler fills it)."""
    import zlib

    rng = np.random.default_rng(nbytes)
    data = rng.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes()
    path = str(tmp_path / f"obj_{nbytes}_{direct}")
    digest = native.write_file_digest(
        lib, path, data, direct=direct, chunk_bytes=64 * 1024
    )
    assert digest == [zlib.crc32(data), nbytes, None]
    with open(path, "rb") as f:
        assert f.read() == data


def test_snapshot_sidecar_digests_match_recomputation(tmp_path) -> None:
    """End-to-end: sidecar digests of native-written objects (inline crc +
    scheduler-filled sha) must match an independent recomputation of the
    stored bytes."""
    import hashlib
    import json
    import zlib

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    from torchsnapshot_tpu import Snapshot, StateDict

    with knobs.override_direct_io_threshold_bytes(1024):
        path = str(tmp_path / "snap")
        arr = np.random.default_rng(0).standard_normal(64 * 1024).astype(np.float32)
        Snapshot.take(path, {"s": StateDict(a=arr)})
        with open(os.path.join(path, ".checksums.0")) as f:
            sidecar = json.load(f)
        stored = open(os.path.join(path, "0", "s", "a"), "rb").read()
        crc, size, sha = sidecar["0/s/a"]
        assert crc == zlib.crc32(stored)
        assert size == len(stored)
        assert sha == hashlib.sha256(stored).hexdigest()


# ------------------------------------------------------ streamed writes


@pytest.mark.parametrize(
    "chunk_sizes",
    [
        [4096, 8192, 4096],  # all aligned
        [5000, 3000, 77],  # unaligned everywhere: carry logic
        [100],  # never crosses an alignment boundary
        [],  # empty stream
        [65536, 1, 4095, 4096],  # mixed
    ],
)
def test_write_at_fs_stream_roundtrip(lib, tmp_path, chunk_sizes) -> None:
    """_FSWriteStream over the native positioned-write API: arbitrary
    append sizes land byte-exact through the aligned O_DIRECT path + the
    buffered tail flush at commit."""
    import asyncio

    from torchsnapshot_tpu.storage_plugins.fs import _FSWriteStream

    rng = np.random.default_rng(5)
    chunks = [rng.integers(0, 255, size=n, dtype=np.uint8) for n in chunk_sizes]
    expected = b"".join(c.tobytes() for c in chunks)
    plugin = FSStoragePlugin(str(tmp_path))

    async def go():
        stream = await plugin.write_stream("obj")
        assert isinstance(stream, _FSWriteStream)
        for c in chunks:
            await stream.append(c)
        await stream.commit()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
        with open(tmp_path / "obj", "rb") as f:
            assert f.read() == expected
        assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]
    finally:
        loop.close()


def test_fs_stream_abort_leaves_nothing(lib, tmp_path) -> None:
    import asyncio

    plugin = FSStoragePlugin(str(tmp_path))

    async def go():
        stream = await plugin.write_stream("obj")
        await stream.append(b"x" * 10000)
        await stream.abort()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
    assert not os.path.exists(tmp_path / "obj")
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


def test_write_at_direct_binding(lib, tmp_path) -> None:
    """The raw native binding: positioned aligned writes + truncate_to."""
    path = str(tmp_path / "f")
    rng = np.random.default_rng(9)
    a = rng.integers(0, 255, size=8192, dtype=np.uint8)
    b = rng.integers(0, 255, size=4096, dtype=np.uint8)
    tail = rng.integers(0, 255, size=100, dtype=np.uint8)
    native.write_at(lib, path, a, offset=0, direct=True, chunk_bytes=1 << 20)
    native.write_at(lib, path, b, offset=8192, direct=True, chunk_bytes=1 << 20)
    native.write_at(
        lib,
        path,
        tail,
        offset=12288,
        direct=False,
        chunk_bytes=1 << 20,
        truncate_to=12388,
    )
    with open(path, "rb") as f:
        data = f.read()
    assert data == a.tobytes() + b.tobytes() + tail.tobytes()


@pytest.mark.parametrize("byte_range", [None, (4096 + 7, 300_000)], ids=["whole", "range"])
@pytest.mark.parametrize("route", ["native", "buffered"])
def test_consumer_views_the_object_the_read_filled(tmp_path, route, byte_range) -> None:
    """No copy lies between the storage read and the consumer: the view the
    read pipeline hands to ``consume_buffer`` is backed by the very
    ``bytearray`` the native read filled (or the ``bytes`` ``aiofiles``
    returned): identity, not equality."""
    import asyncio

    from torchsnapshot_tpu.io_types import ReadReq
    from torchsnapshot_tpu.scheduler import execute_read_reqs

    if route == "native" and native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    data = os.urandom(1 << 20)
    filled, seen = [], []

    class Recording(FSStoragePlugin):
        async def _native_read(self, path, offset, nbytes):
            filled.append(await super()._native_read(path, offset, nbytes))
            return filled[-1]

        async def _buffered_read(self, path, offset, nbytes):
            filled.append(await super()._buffered_read(path, offset, nbytes))
            return filled[-1]

    class Consumer:
        def get_consuming_cost_bytes(self) -> int:
            return len(data)

        async def consume_buffer(self, buf, executor=None) -> None:
            seen.append(memoryview(buf))

    async def go() -> None:
        plugin = Recording(str(tmp_path))
        assert (plugin._native is not None) == (route == "native")
        await plugin.write(WriteIO(path="obj", buf=data))
        await execute_read_reqs(
            [ReadReq(path="obj", buffer_consumer=Consumer(), byte_range=byte_range)],
            plugin,
            memory_budget_bytes=1 << 30,
            rank=0,
        )
        await plugin.close()

    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_native_io_enabled(
        route == "native"
    ):
        asyncio.run(go())
    (obj,), (view,) = filled, seen
    assert type(obj) is (bytearray if route == "native" else bytes)
    assert view.obj is obj
    begin, end = byte_range or (0, len(data))
    assert view.nbytes == end - begin and view == data[begin:end]
