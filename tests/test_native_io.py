"""Native O_DIRECT I/O engine tests (``torchsnapshot_tpu/native``).

Covers: build+load, write/read round-trips at aligned/unaligned sizes,
ranged reads at unaligned offsets, buffered fallback on filesystems without
O_DIRECT (tmpfs), the disable knob, and FS-plugin integration parity with the
pure-Python path.
"""

import errno
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
        # Whole writes with the sizes a large object takes in pieces:
        # aligned, unaligned everywhere, under one sector, mixed.
        4096 + 8192 + 4096,
        5000 + 3000 + 77,
        100,
        65536 + 1 + 4095 + 4096,
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


@pytest.mark.parametrize("touchers", [1, 3])
def test_touchers_write_a_byte_a_page_as_far_as_is_wanted_and_nothing_once_stopped(lib, touchers) -> None:
    import threading
    import time

    page, pages = 4096, 40
    buf = np.full(pages * page + 16, 7, dtype=np.uint8)
    state, ends = native.TouchState(), []

    def toucher():
        ends.append(native.touch_stripes(lib, buf.ctypes.data, state, 4 * page, page))

    threads = [threading.Thread(target=toucher) for _ in range(touchers)]
    for t in threads:
        t.start()

    def wait_for(nbytes):
        deadline = time.monotonic() + 30
        while state.done < nbytes and time.monotonic() < deadline:
            time.sleep(0.001)
        assert state.done == nbytes == state.claimed

    time.sleep(0.01)
    assert np.all(buf == 7)  # nothing is wanted yet
    state.wanted = 10 * page  # two whole stripes and a short one
    wait_for(10 * page)
    assert np.flatnonzero(buf != 7).tolist() == [i * page for i in range(10)]
    state.wanted = 30 * page
    wait_for(30 * page)
    state.stop = 1
    for t in threads:
        t.join(30)
    state.wanted = pages * page  # too late
    assert ends == [native.TOUCHED_ALL] * touchers
    assert np.flatnonzero(buf != 7).tolist() == [i * page for i in range(30)]


def test_a_toucher_told_to_stop_beforehand_touches_nothing(lib) -> None:
    buf = np.full(8 * 4096, 7, dtype=np.uint8)
    state = native.TouchState(wanted=buf.nbytes, stop=1)
    assert native.touch_stripes(lib, buf.ctypes.data, state, 4096, 4096) == native.TOUCHED_ALL
    assert state.claimed == 0 == state.done and np.all(buf == 7)


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


@pytest.mark.parametrize(
    "nbytes",
    [0, 1, 4095, 4096, (1 << 20) + 123, 16384, 8077, 100, 73728],
)
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


@pytest.mark.parametrize("route", ["native", "buffered"])
def test_fs_failed_write_leaves_no_object_and_no_temp(
    lib, tmp_path, monkeypatch, route
) -> None:
    """A write that fails after its bytes reached the temp file (here: at
    the rename) leaves neither an object nor the temp file, on either
    path."""
    plugin = FSStoragePlugin(str(tmp_path))

    def refuse(src, dst):
        assert os.path.getsize(src) == 10000
        raise RuntimeError("rename refused")

    monkeypatch.setattr(os, "replace", refuse)
    threshold = 1024 if route == "native" else 1 << 30
    with knobs.override_direct_io_threshold_bytes(threshold):
        with pytest.raises(RuntimeError, match="rename refused"):
            plugin.sync_write(WriteIO(path="obj", buf=b"x" * 10000))
    plugin.sync_close()
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("byte_range", [None, (4096 + 7, 300_000)], ids=["whole", "range"])
@pytest.mark.parametrize("route", ["native", "buffered"])
def test_consumer_views_the_object_the_read_filled(tmp_path, route, byte_range) -> None:
    """No copy lies between the storage read and the consumer: the view the
    read pipeline hands to ``consume_buffer`` is backed by the very
    array the native read filled (or the ``bytes`` ``aiofiles``
    returned): identity, not equality."""
    import asyncio

    from torchsnapshot_tpu.io_types import ReadReq
    from torchsnapshot_tpu.scheduler import execute_read_reqs

    if route == "native" and native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    data = os.urandom(1 << 20)
    filled, seen = [], []

    class Recording(FSStoragePlugin):
        async def _native_read(self, path, offset, nbytes, into=None):
            filled.append(await super()._native_read(path, offset, nbytes, into))
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
    assert type(obj) is (np.ndarray if route == "native" else bytes)
    assert view.obj is obj
    begin, end = byte_range or (0, len(data))
    assert view.nbytes == end - begin and view == data[begin:end]


# ----------------------------------------------------------- the chunked read
#
# One object is read as positional chunk reads on the engine's reader pool,
# ``depth`` of them on the mount at once, whichever objects they belong to.


@pytest.fixture
def restore_depth(lib):
    """Leave the process-wide pool as the library sizes it."""
    yield
    native.set_read_depth(lib, knobs.get_direct_read_depth())


def _file_of(tmp_path, nbytes: int, seed: int = 0):
    data = np.random.default_rng(seed).integers(0, 256, size=nbytes, dtype=np.uint8)
    path = str(tmp_path / f"obj{seed}-{nbytes}")
    with open(path, "wb") as f:
        f.write(data.tobytes())
    return path, data


def _size(spec, chunk: int) -> int:
    return 3 * chunk + 17 if spec == "3c+17" else spec


@pytest.mark.parametrize("depth", [1, 2, 8])
@pytest.mark.parametrize("chunk", [4096, 16384, 1 << 20])
@pytest.mark.parametrize("offset", [0, 1, 4096, 12345])
@pytest.mark.parametrize("size", [0, 1, 4095, 4096, 4097, (1 << 20) + 3, "3c+17"])
def test_chunked_read_is_the_files_bytes(
    lib, tmp_path, restore_depth, size, offset, chunk, depth
) -> None:
    n = _size(size, chunk)
    path, data = _file_of(tmp_path, offset + n + 5)
    native.set_read_depth(lib, depth)
    out = np.full(n, 0xA5, dtype=np.uint8)
    chunk_reads = native.read_into(
        lib, path, out, offset=offset, direct=True, chunk_bytes=chunk, stamped=True
    )
    assert np.array_equal(out, data[offset : offset + n])
    # One chunk a row, on time.monotonic()'s clock: the pread inside it.
    assert len(chunk_reads) == -(-n // chunk)
    assert all(0.0 < t0 <= p0 <= p1 <= t1 + 1e-9 for t0, t1, p0, p1 in chunk_reads)
    stats = native.read_pool_stats(lib)
    assert stats["depth"] == depth and stats["in_flight"] == 0
    assert stats["high_water"] <= depth and stats["buffers"] <= depth
    assert stats["buffer_bytes"] <= depth * (chunk + 4096)


@pytest.mark.parametrize("chunk", [4096, 1 << 20])
@pytest.mark.parametrize("direct", [True, False])
def test_chunked_read_of_a_short_file_is_eio(lib, tmp_path, chunk, direct) -> None:
    """A file shorter than the manifest says fails, never a short buffer."""
    path, _ = _file_of(tmp_path, 3 * chunk)
    with pytest.raises(OSError) as e:
        native.read_into(
            lib, path, np.empty(3 * chunk + 1, np.uint8), direct=direct, chunk_bytes=chunk
        )
    assert e.value.errno == errno.EIO


@pytest.mark.parametrize("chunk", [4096, 1 << 20])
def test_chunked_read_without_o_direct_falls_back(lib, chunk) -> None:
    """tmpfs refuses O_DIRECT at open: every chunk is read buffered."""
    if not os.path.isdir("/dev/shm"):
        pytest.skip("no tmpfs mount")
    path = f"/dev/shm/tss_native_chunked_{os.getpid()}_{chunk}"
    data = np.random.default_rng(chunk).integers(0, 256, 3 * chunk + 17, dtype=np.uint8)
    try:
        with open(path, "wb") as f:
            f.write(data.tobytes())
        out = np.empty(data.size - 7, np.uint8)
        native.read_into(lib, path, out, offset=7, direct=True, chunk_bytes=chunk)
        assert np.array_equal(out, data[7:])
        assert native.read_pool_stats(lib)["in_flight"] == 0
    finally:
        os.remove(path)


@pytest.mark.parametrize("depth", [1, 2, 8])
def test_eight_objects_from_eight_threads_are_all_exact(
    lib, tmp_path, restore_depth, depth
) -> None:
    """Depth across objects: the cap counts chunks in flight for the
    process, never more than ``depth``, and the bounce pool never grows
    past ``depth`` buffers however many callers wait."""
    import threading

    chunk = 64 * 1024
    files = [_file_of(tmp_path, 5 * chunk + 4097 * i + 1, seed=i) for i in range(8)]
    outs = [np.empty(data.size, np.uint8) for _, data in files]
    native.set_read_depth(lib, depth)
    errors = []

    def read(i: int) -> None:
        try:
            native.read_into(lib, files[i][0], outs[i], direct=True, chunk_bytes=chunk)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=read, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for (_, data), out in zip(files, outs):
        assert np.array_equal(out, data)
    stats = native.read_pool_stats(lib)
    assert stats["chunks_read"] == sum(-(-d.size // chunk) for _, d in files)
    assert 1 <= stats["high_water"] <= depth
    assert stats["buffers"] <= depth and stats["in_flight"] == 0
    assert stats["buffer_bytes"] <= depth * (chunk + 4096) <= 256 << 20


def test_bounce_memory_is_bounded_whatever_chunk_is_asked(lib, tmp_path, restore_depth) -> None:
    """depth x chunk stays under 256 MiB: the engine clamps the chunk."""
    path, data = _file_of(tmp_path, (1 << 20) + 3)
    native.set_read_depth(lib, 8)
    out = np.empty(data.size, np.uint8)
    native.read_into(lib, path, out, direct=True, chunk_bytes=1 << 40)
    assert np.array_equal(out, data)
    assert native.read_pool_stats(lib)["buffer_bytes"] <= (256 << 20) + 8 * 4096


def test_a_failed_chunk_fails_the_read_and_the_others_land(lib, tmp_path) -> None:
    """The fault harness's torn read: chunk 1 of 4 fails with ESTALE (a
    transient errno), chunk 0 has landed in the attempt's destination."""
    chunk = 16384
    path, data = _file_of(tmp_path, 4 * chunk)
    out = np.zeros(data.size, np.uint8)
    with pytest.raises(OSError) as e:
        native.read_into(lib, path, out, direct=True, chunk_bytes=chunk, fail_chunk=1)
    assert e.value.errno == errno.ESTALE
    assert np.array_equal(out[:chunk], data[:chunk])
    assert not out[chunk : 2 * chunk].any()
    assert native.read_pool_stats(lib)["in_flight"] == 0


def test_native_read_destination_is_not_zero_filled_and_is_spanned(tmp_path) -> None:
    """``_native_read`` hands on one writable buffer it allocated
    uninitialised, inside its ``storage.read_work`` span."""
    import asyncio

    from torchsnapshot_tpu import telemetry
    from torchsnapshot_tpu.telemetry import core as telemetry_core

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    data = os.urandom((1 << 20) + 5)
    allocated = []
    real_empty = np.empty

    def spying_empty(*args, **kwargs):
        allocated.append(telemetry_core.current_span_id())
        return real_empty(*args, **kwargs)

    async def go():
        from torchsnapshot_tpu.storage_plugins import fs as fs_mod

        plugin = FSStoragePlugin(str(tmp_path))
        await plugin.write(WriteIO(path="obj", buf=data))
        read_io = ReadIO(path="obj")
        tm = telemetry.Telemetry()
        prev = telemetry.activate(tm)
        fs_mod.np.empty = spying_empty
        try:
            await plugin.read(read_io)
        finally:
            fs_mod.np.empty = real_empty
            telemetry.deactivate(tm, prev)
        await plugin.close()
        return read_io, tm

    with knobs.override_direct_io_threshold_bytes(1024):
        read_io, tm = asyncio.run(go())
    view = read_io.buf.getbuffer()
    assert not view.readonly and view == data
    (work_span,) = tm.spans(name="storage.read_work")
    assert allocated == [work_span.span_id]
    assert work_span.attrs["nbytes"] == len(data)


def test_a_forked_child_reads_through_a_pool_of_its_own(lib, tmp_path) -> None:
    """The reader threads do not survive ``fork``: the child's first read
    starts a pool of its own, sized as the parent's was."""
    import subprocess
    import sys
    import textwrap

    path, data = _file_of(tmp_path, (1 << 20) + 3)
    script = textwrap.dedent(
        f"""
        import os, sys
        import numpy as np
        from torchsnapshot_tpu import native

        lib = native.load_native()
        want = np.fromfile({path!r}, dtype=np.uint8)

        def read():
            out = np.empty(want.size, np.uint8)
            native.read_into(lib, {path!r}, out, direct=True, chunk_bytes=65536)
            return np.array_equal(out, want)

        native.set_read_depth(lib, 3)
        assert read()
        pid = os.fork()
        if pid == 0:
            ok = read() and native.read_pool_stats(lib)["depth"] == 3
            os._exit(0 if ok else 1)
        _, status = os.waitpid(pid, 0)
        assert read()
        sys.exit(os.waitstatus_to_exitcode(status))
        """
    )
    done = subprocess.run(
        [sys.executable, "-W", "ignore", "-c", script],
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
    )
    assert done.returncode == 0, done.stderr[-2000:]


# ------------------------------------------------------- the engine's stamps
#
# Asked to, the engine says what each thread did with each chunk, on
# ``time.monotonic()``'s clock: a write's copy into the bounce buffer, its
# ``pwrite`` and its crc; a read's whole chunk and the ``pread`` inside it.
# CPU runs: orderings, identities and byte counts, never a rate.

_MIB = 1 << 20
_WRITE_CASES = {
    # name: (bytes, chunk, O_DIRECT asked for)
    "direct_multi_chunk": (3 * _MIB, _MIB, True),
    "unaligned_tail": (2 * _MIB + 17, _MIB, True),
    "under_one_chunk": (300_000, _MIB, True),
    "buffered_fallback": (2 * _MIB + 17, _MIB, False),
}


class _SpyLib:
    """The engine's library with every call's arguments kept."""

    def __init__(self, lib) -> None:
        self._lib = lib
        self.calls = []

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self.calls.append((name, args))
            return fn(*args)

        return call


@pytest.mark.parametrize("digest", [True, False], ids=["digest", "plain"])
@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
def test_write_stamps_say_what_the_thread_did_with_each_chunk(lib, tmp_path, case, digest) -> None:
    import time
    import zlib

    nbytes, chunk, direct = _WRITE_CASES[case]
    data = np.random.default_rng(nbytes).integers(0, 256, size=nbytes, dtype=np.uint8)
    write = native.write_file_digest if digest else native.write_file
    stamped, plain = str(tmp_path / "stamped"), str(tmp_path / "plain")
    chunks = []
    before = time.monotonic()
    got = write(lib, stamped, data, direct=direct, chunk_bytes=chunk, stamps=chunks)
    after = time.monotonic()
    # The same bytes on disk and the same digest, stamped or not.
    assert write(lib, plain, data, direct=direct, chunk_bytes=chunk) == got
    assert got == ([zlib.crc32(data), nbytes, None] if digest else None)
    for path in (stamped, plain):
        with open(path, "rb") as f:
            assert f.read() == data.tobytes()
    # One thread, in series: every chunk is copy, then pwrite, then crc, and
    # the next chunk starts after it; all of it inside the call.
    assert chunks and sum(c[4] for c in chunks) == nbytes
    edges = [t for c in chunks for t in c[:4]]
    assert edges == sorted(edges) and before <= edges[0] and edges[-1] <= after
    copy_s = sum(c[1] - c[0] for c in chunks)
    mount_s = sum(c[2] - c[1] for c in chunks)
    crc_s = sum(c[3] - c[2] for c in chunks)
    assert mount_s > 0.0 and copy_s + mount_s + crc_s <= after - before
    if not digest:
        assert crc_s == 0.0  # nothing is hashed where no digest is asked for
    if not direct:
        assert copy_s == 0.0  # a buffered write has no bounce buffer
    assert len(chunks) <= -(-nbytes // chunk) + 1


@pytest.mark.parametrize("digest", [True, False], ids=["digest", "plain"])
def test_an_unstamped_write_hands_the_engine_no_stamps_out(lib, tmp_path, digest) -> None:
    spy = _SpyLib(lib)
    write = native.write_file_digest if digest else native.write_file
    write(spy, str(tmp_path / "obj"), os.urandom(70_000), direct=True, chunk_bytes=1 << 20)
    ((name, args),) = [c for c in spy.calls if c[0].startswith("tss_write_file")]
    assert args[-2:] == (None, None)
    assert not [c for c in spy.calls if c[0] == "tss_free" and c[1][0]]


@pytest.mark.parametrize(
    "case,direct,nbytes",
    [("direct", True, 4 * 16384), ("buffered_tail", False, 3 * 16384 + 5), ("one_short_chunk", True, 100)],
)
def test_read_stamps_hold_the_pread_inside_its_chunk(lib, tmp_path, restore_depth, case, direct, nbytes) -> None:
    import time

    chunk = 16384
    path, data = _file_of(tmp_path, nbytes)
    native.set_read_depth(lib, 1)  # one reader thread: its chunks are in series
    out = np.zeros(nbytes, np.uint8)
    before = time.monotonic()
    rows = native.read_into(lib, path, out, direct=direct, chunk_bytes=chunk, stamped=True)
    after = time.monotonic()
    assert np.array_equal(out, data)
    assert len(rows) == -(-nbytes // chunk)
    for t0, t1, p0, p1 in rows:
        assert before <= t0 <= p0 < p1 <= t1 + 1e-9 and t1 <= after
    ends = [t for t0, t1, _, _ in sorted(rows) for t in (t0, t1)]
    assert ends == sorted(ends)
    # Unstamped: the same bytes and no array.
    again = np.zeros(nbytes, np.uint8)
    assert native.read_into(lib, path, again, direct=direct, chunk_bytes=chunk) == []
    assert np.array_equal(again, data)


def test_a_failed_chunk_of_a_stamped_read_gives_no_stamps(lib, tmp_path) -> None:
    chunk = 16384
    path, data = _file_of(tmp_path, 4 * chunk)
    out = np.zeros(data.size, np.uint8)
    with pytest.raises(OSError) as e:
        native.read_into(lib, path, out, chunk_bytes=chunk, stamped=True, fail_chunk=2)
    assert e.value.errno == errno.ESTALE
    assert np.array_equal(out[:chunk], data[:chunk]) and not out[2 * chunk : 3 * chunk].any()
    assert native.read_pool_stats(lib)["in_flight"] == 0


_TAKE_KEYS = (
    "write_work_sum_s", "write_queue_sum_s",
    "mount_write_s", "mount_write_sum_s", "mount_write_bytes",
    "write_copy_sum_s", "write_crc_sum_s",
    "write_bounce_warm_bytes", "write_bounce_fresh_bytes",
    "stage_gather_s", "stage_gather_sum_s", "stage_d2h_sum_s",
)
_RESTORE_KEYS = ("pread_busy_s", "pread_sum_s", "reader_copy_sum_s")


@pytest.fixture(scope="module")
def taken(tmp_path_factory):
    """One asynchronous take of a pieced leaf, a whole leaf and a small one
    through the fs plugin, its artifact, and a restore of it."""
    import json

    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import snapshot as snapshot_mod

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    tree = dict(
        pieced=jax.random.normal(jax.random.PRNGKey(0), (4096, 4096), jnp.bfloat16),
        whole=jax.random.normal(jax.random.PRNGKey(1), (1024, 1024), jnp.float32),
        small=jnp.arange(8, dtype=jnp.float32),
    )
    path = str(tmp_path_factory.mktemp("stamps") / "snap")
    Snapshot.async_take(path, {"m": StateDict(**tree)}).wait()
    with open(os.path.join(path, ".telemetry", "rank_0.json")) as f:
        artifact = json.load(f)
    targets = StateDict(**{k: jnp.zeros_like(v) for k, v in tree.items()})
    Snapshot(path).restore({"m": targets})
    for k, v in tree.items():
        assert np.array_equal(np.asarray(targets[k]).view(np.uint8), np.asarray(v).view(np.uint8)), k
    return path, tree, artifact, dict(snapshot_mod.LAST_RESTORE_STATS)


@pytest.mark.parametrize("view", ["drain_stats_s", "pipeline_stats_s"])
def test_a_take_says_what_its_writers_and_lanes_seconds_are_made_of(taken, view) -> None:
    _, tree, artifact, _ = taken
    stats = artifact[view]
    assert set(_TAKE_KEYS) <= set(stats)
    eps = 1e-5  # the artifact rounds to microseconds
    native_bytes = artifact["metrics"]["storage.fs.native_write_bytes"]
    assert stats["mount_write_bytes"] == native_bytes == tree["pieced"].nbytes + tree["whole"].nbytes
    # Every byte a pwrite took had been copied into a bounce buffer, into
    # pages that were warm or fresh (neither where the mount refuses O_DIRECT).
    bounced = stats["write_bounce_warm_bytes"] + stats["write_bounce_fresh_bytes"]
    assert bounced in (0.0, stats["mount_write_bytes"])
    assert artifact["metrics"]["storage.fs.bounce_warm_bytes"] == stats["write_bounce_warm_bytes"]
    assert artifact["metrics"]["storage.fs.bounce_fresh_bytes"] == stats["write_bounce_fresh_bytes"]
    inside = stats["mount_write_sum_s"] + stats["write_copy_sum_s"] + stats["write_crc_sum_s"]
    assert 0.0 < inside <= stats["write_work_sum_s"] + eps
    assert stats["write_queue_sum_s"] >= 0.0
    assert 0.0 < stats["mount_write_s"] <= stats["io_busy_s"] + eps
    assert stats["mount_write_s"] <= stats["mount_write_sum_s"] + eps
    # The 32 MiB leaf hashes on the pool beside its write, the 4 MiB one in
    # the writer's loop.
    assert stats["write_crc_sum_s"] > 0.0
    assert 0.0 < stats["stage_gather_sum_s"] <= stats["stage_d2h_sum_s"] + eps
    assert stats["stage_gather_s"] <= stats["stage_d2h_s"] + eps <= stats["stage_d2h_sum_s"] + 2 * eps


def test_the_artifact_carries_the_engines_intervals_and_the_spans_that_anchor_them(taken) -> None:
    _, _, artifact, _ = taken
    intervals = artifact["intervals"]
    assert {"mount_write", "write_copy", "write_work", "stage_gather"} <= set(intervals)
    assert len(intervals["write_work"]) == 2  # one an object through the engine
    # Each pwrite and each copy lies inside a storage.write_work interval.
    for kind in ("mount_write", "write_copy"):
        for t0, t1 in intervals[kind]:
            assert any(w0 - 1e-5 <= t0 and t1 <= w1 + 1e-5 for w0, w1 in intervals["write_work"]), kind
    assert artifact["metrics"]["d2h.pieces"] == 2  # 32 MiB in pieces of 16


def test_a_restore_tells_a_readers_pread_from_its_copy(taken) -> None:
    _, _, _, stats = taken
    assert set(_RESTORE_KEYS) <= set(stats)
    assert 0.0 < stats["pread_busy_s"] <= stats["mount_busy_s"] + 1e-9
    assert stats["pread_busy_s"] <= stats["pread_sum_s"] + 1e-9
    assert stats["pread_sum_s"] + stats["reader_copy_sum_s"] == pytest.approx(stats["mount_sum_s"], abs=1e-6)
    assert stats["reader_copy_sum_s"] > 0.0


@pytest.mark.parametrize("op", ["read_object", "plugin_write"])
def test_outside_a_take_or_a_restore_the_engine_is_asked_for_no_stamps(taken, tmp_path, monkeypatch, op) -> None:
    from torchsnapshot_tpu import Snapshot

    path, tree, _, _ = taken
    asked = []
    real_read, real_write = native.read_into, native.write_file

    def read_into(*args, **kwargs):
        asked.append(kwargs.get("stamped", False))
        return real_read(*args, **kwargs)

    def write_file(*args, **kwargs):
        asked.append(kwargs.get("stamps") is not None)
        return real_write(*args, **kwargs)

    monkeypatch.setattr(native, "read_into", read_into)
    monkeypatch.setattr(native, "write_file", write_file)
    if op == "read_object":
        got = Snapshot(path).read_object("0/m/whole")
        assert np.array_equal(np.asarray(got), np.asarray(tree["whole"]))
    else:
        plugin = FSStoragePlugin(str(tmp_path))
        plugin.sync_write(WriteIO(path="obj", buf=os.urandom(5 << 20)))
        plugin.sync_close()
    assert asked and not any(asked)


def test_a_plugin_write_handed_a_sink_tells_it_what_the_engine_did(lib, tmp_path) -> None:
    from torchsnapshot_tpu.io_types import WriteTimes

    times, nbytes = WriteTimes(), (5 << 20) + 3
    plugin = FSStoragePlugin(str(tmp_path))
    plugin.sync_write(WriteIO(path="obj", buf=os.urandom(nbytes), times=times))
    plugin.sync_close()
    got = times.intervals()
    ((handed, held, n),), ((held_too, done, _),) = got["write_queue"], got["write_work"]
    assert handed <= held == held_too < done and n == nbytes
    assert sum(taken for _, _, taken in got["mount_write"]) == nbytes
    # The same pwrites again, with the bytes of each that were copied into
    # warm pages of the lent buffer, and into fresh ones.
    assert [iv[:2] for iv in got["bounce_warm"]] == [iv[:2] for iv in got["bounce_fresh"]] == [iv[:2] for iv in got["mount_write"]]
    bounced = [warm[2] + fresh[2] for warm, fresh in zip(got["bounce_warm"], got["bounce_fresh"])]
    assert bounced in ([taken for _, _, taken in got["mount_write"]], [0] * len(bounced))
    for kind in ("write_copy", "mount_write", "write_crc"):
        assert got[kind] and all(held <= t0 <= t1 <= done for t0, t1, _ in got[kind]), kind


# ------------------------------------------------- the write side's bounce
#
# A direct write copies its chunks into a bounce buffer the engine lends it
# and keeps between writes, so the copy lands in pages an earlier write
# touched. CPU runs: counts of buffers and bytes, never a rate.

_OVER_THE_CAP = (256 << 20) + 4096


@pytest.fixture
def nothing_kept(lib, tmp_path):
    """The engine's list of kept bounce buffers emptied, with what its gauges
    read then. A write whose chunk is over the engine's cap on kept memory
    drops the kept buffer it finds too small and, at its end, its own (of
    which it touched one page)."""
    while native.write_bounce_stats(lib)["kept"]:
        native.write_file(lib, str(tmp_path / "flush"), b"\0" * 4096, direct=True, chunk_bytes=_OVER_THE_CAP)
    stats = native.write_bounce_stats(lib)
    assert stats["lent"] == stats["kept"] == stats["kept_bytes"] == 0
    return stats


def _written(lib, path, data, chunk, write=native.write_file):
    """``data`` through the engine under O_DIRECT: its stamps."""
    chunks = []
    write(lib, path, data, direct=True, chunk_bytes=chunk, stamps=chunks)
    if not sum(c[5] + c[6] for c in chunks) and len(data) >= 4096:
        pytest.skip("this filesystem refuses O_DIRECT: nothing is bounced")
    return chunks


def test_a_second_and_a_tenth_write_allocate_nothing(lib, tmp_path, nothing_kept) -> None:
    chunk = 1 << 20
    data = np.random.default_rng(0).integers(0, 256, size=3 * chunk + 17, dtype=np.uint8)
    first = _written(lib, str(tmp_path / "w0"), data, chunk)
    # Its own buffer, new: the first chunk's copy is the pages' first touch,
    # the later chunks of the same object land in them.
    assert [(c[5], c[6]) for c in first] == [(0, chunk), (chunk, 0), (chunk, 0), (17, 0)]
    one = dict(nothing_kept, allocated=nothing_kept["allocated"] + 1, kept=1, kept_bytes=chunk)
    assert native.write_bounce_stats(lib) == one
    for k in range(1, 10):
        again = _written(lib, str(tmp_path / f"w{k}"), data, chunk)
        assert sum(c[6] for c in again) == 0 and sum(c[5] for c in again) == data.size
        assert native.write_bounce_stats(lib) == one
        with open(tmp_path / f"w{k}", "rb") as f:
            assert f.read() == data.tobytes()


_CHUNK = 64 * 1024


@pytest.mark.parametrize("digest", [True, False], ids=["digest", "plain"])
@pytest.mark.parametrize(
    "nbytes",
    [4095, 4096, 4097, _CHUNK - 1, _CHUNK, _CHUNK + 1, 2 * _CHUNK + 4097, 100],
)
def test_nothing_of_an_earlier_object_reaches_a_later_file(lib, tmp_path, nothing_kept, nbytes, digest) -> None:
    """A kept buffer holds the last object's bytes: the file written through
    it is its source byte for byte and ends where the source does."""
    import zlib

    _written(lib, str(tmp_path / "ff"), np.full(4 * _CHUNK, 0xFF, np.uint8), _CHUNK)
    before = native.write_bounce_stats(lib)
    assert before["kept"] == 1
    data = np.random.default_rng(nbytes).integers(0, 0xFF, size=nbytes, dtype=np.uint8)  # no 0xFF of its own
    path, got = str(tmp_path / "later"), []

    def write(*args, **kwargs):
        got.append((native.write_file_digest if digest else native.write_file)(*args, **kwargs))

    chunks = _written(lib, path, data, _CHUNK, write)
    assert native.write_bounce_stats(lib) == before  # the same buffer, back again
    if nbytes >= 4096:  # under a sector the engine writes buffered: no bounce
        assert sum(c[5] for c in chunks) == nbytes and sum(c[6] for c in chunks) == 0
    assert os.path.getsize(path) == nbytes
    with open(path, "rb") as f:
        assert f.read() == data.tobytes()
    assert got == [[zlib.crc32(data), nbytes, None] if digest else None]


def test_two_writes_at_once_hold_two_buffers_and_both_are_kept(lib, tmp_path, nothing_kept) -> None:
    import threading

    chunk, nbytes = 64 * 1024, 8 << 20
    datas = [np.random.default_rng(i).integers(0, 256, size=nbytes + i, dtype=np.uint8) for i in range(2)]
    for attempt in range(20):
        stamps, errors, gate = [[], []], [], threading.Barrier(2)

        def write(i: int) -> None:
            try:
                gate.wait(timeout=60)
                native.write_file(lib, str(tmp_path / f"at_once{i}"), datas[i], direct=True, chunk_bytes=chunk, stamps=stamps[i])
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)

        threads = [threading.Thread(target=write, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors and not any(t.is_alive() for t in threads)
        for i in range(2):
            with open(tmp_path / f"at_once{i}", "rb") as f:
                assert f.read() == datas[i].tobytes()
        if not sum(c[5] + c[6] for c in stamps[0]):
            pytest.skip("this filesystem refuses O_DIRECT: nothing is bounced")
        stats = native.write_bounce_stats(lib)
        assert stats["lent"] == 0 and stats["kept"] == stats["allocated"] - nothing_kept["allocated"] <= 2
        # Each held its buffer from its first pwrite to its last: where those
        # spans overlap, two buffers were out at once.
        held = [(s[0][1], s[-1][3]) for s in stamps]
        if max(h[0] for h in held) < min(h[1] for h in held):
            assert stats["kept"] == 2 and stats["kept_bytes"] == 2 * chunk
            return
    pytest.fail("two 8 MiB writes started together never overlapped in 20 attempts")


def test_six_writers_under_two_slots_never_need_a_third_buffer(lib, tmp_path, nothing_kept) -> None:
    """As ``fs.py`` drives the engine: more callers than writer slots, a
    shortened switch interval, and every file exact."""
    import sys
    import threading

    chunk = 64 * 1024
    slots = threading.Semaphore(2)
    datas = [np.random.default_rng(i).integers(0, 256, size=(1 << 20) + 4097 * i + 1, dtype=np.uint8) for i in range(6)]
    errors = []

    def write(i: int) -> None:
        try:
            for k in range(4):
                with slots:
                    native.write_file(lib, str(tmp_path / f"s{i}_{k}"), datas[i], direct=True, chunk_bytes=chunk)
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    for i in range(6):
        for k in range(4):
            with open(tmp_path / f"s{i}_{k}", "rb") as f:
                assert f.read() == datas[i].tobytes()
    stats = native.write_bounce_stats(lib)
    assert stats["lent"] == 0 and 1 <= stats["kept"] <= 2
    assert stats["allocated"] - nothing_kept["allocated"] == stats["kept"]
    assert stats["kept_bytes"] == stats["kept"] * chunk


def test_a_write_that_fails_mid_object_and_its_retry_return_what_they_borrowed(lib, tmp_path, nothing_kept) -> None:
    """The second chunk's ``pwrite`` is cut short by the file size limit and
    the next one refused (EFBIG; the interpreter ignores SIGXFSZ)."""
    import resource

    chunk = 64 * 1024
    data = np.random.default_rng(1).integers(0, 256, size=3 * chunk + 5, dtype=np.uint8)
    path = str(tmp_path / "limited")
    _written(lib, str(tmp_path / "probe"), data, chunk)  # skips where nothing is bounced
    primed = native.write_bounce_stats(lib)
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (chunk + 8192, hard))
    try:
        with pytest.raises(OSError) as e:
            native.write_file(lib, path, data, direct=True, chunk_bytes=chunk, stamps=[])
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    assert e.value.errno == errno.EFBIG
    assert os.path.getsize(path) == chunk + 8192  # it failed mid-object
    assert native.write_bounce_stats(lib) == primed
    again = _written(lib, path, data, chunk)
    assert sum(c[5] for c in again) == data.size
    assert native.write_bounce_stats(lib) == primed
    with open(path, "rb") as f:
        assert f.read() == data.tobytes()


def test_a_take_whose_writes_fail_and_retry_leaves_no_buffer_lent(lib, tmp_path) -> None:
    """The fault schedule's ``op=write``: three writes fail before they
    reach the engine and are retried; the take commits, restores bit for
    bit, and every buffer the engine lent is back."""
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import snapshot as snapshot_mod

    arrs = {f"a{i}": np.random.default_rng(i).standard_normal(70_000).astype(np.float32) for i in range(4)}
    path = str(tmp_path / "snap")
    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_faults(
        "backoff=0.01;op=write,kind=transient,times=3,path=0/s"
    ):
        Snapshot.take(path, {"s": StateDict(**arrs)})
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["faults.transient"] == 3
    # The leaves' writes are the take's own and stamped; the counters say
    # what the drain's stats say.
    stats = snapshot_mod.LAST_SYNC_DRAIN_STATS
    bounced = stats["write_bounce_warm_bytes"] + stats["write_bounce_fresh_bytes"]
    counted = metrics["storage.fs.bounce_warm_bytes"] + metrics["storage.fs.bounce_fresh_bytes"]
    assert counted in (0, sum(a.nbytes for a in arrs.values()))
    # (A synchronous take's stats leave out a pwrite that began between its
    # two accounting windows; the counters leave out none.)
    assert 0 < stats["mount_write_bytes"] <= sum(a.nbytes for a in arrs.values())
    assert bounced in (0, stats["mount_write_bytes"])  # 0: no O_DIRECT here
    stats = native.write_bounce_stats(lib)
    assert stats["lent"] == 0 and stats["kept"] <= knobs.get_direct_io_concurrency()
    back = StateDict(**{k: np.zeros_like(v) for k, v in arrs.items()})
    Snapshot(path).restore({"s": back})
    assert all(np.array_equal(back[k], v) for k, v in arrs.items())


def test_a_larger_chunk_reallocates_once_and_a_smaller_one_not_at_all(lib, tmp_path, nothing_kept) -> None:
    data = np.random.default_rng(2).integers(0, 256, size=(1 << 20) + 3, dtype=np.uint8)
    allocated = nothing_kept["allocated"]
    for chunk, grown in [(64 * 1024, 1), (256 * 1024, 2), (256 * 1024, 2), (64 * 1024, 2), (256 * 1024, 2)]:
        _written(lib, str(tmp_path / "obj"), data, chunk)
        want = dict(allocated=allocated + grown, lent=0, kept=1, kept_bytes=(64 * 1024, 256 * 1024)[grown - 1])
        assert native.write_bounce_stats(lib) == want, (chunk, grown)
        with open(tmp_path / "obj", "rb") as f:
            assert f.read() == data.tobytes()


def test_a_buffer_over_the_cap_is_not_kept(lib, tmp_path, nothing_kept) -> None:
    """No more than 256 MiB stays with the engine, whatever chunk is asked."""
    data = os.urandom(8192)
    native.write_file(lib, str(tmp_path / "big_chunk"), data, direct=True, chunk_bytes=_OVER_THE_CAP)
    stats = native.write_bounce_stats(lib)
    assert stats["kept"] == stats["kept_bytes"] == stats["lent"] == 0
    with open(tmp_path / "big_chunk", "rb") as f:
        assert f.read() == data


def test_a_forked_child_writes_through_a_list_of_its_own(lib, tmp_path) -> None:
    """A kept buffer's pages are the parent's: a child copying into them
    would fault each in anew, so it starts with none, and with no write of
    the parent's counted as its own."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        f"""
        import os, sys
        import numpy as np
        from torchsnapshot_tpu import native

        lib = native.load_native()
        data = np.random.default_rng(3).integers(0, 256, size=(1 << 20) + 3, dtype=np.uint8)

        def write(name):
            chunks = []
            native.write_file(lib, os.path.join({str(tmp_path)!r}, name), data, direct=True, chunk_bytes=65536, stamps=chunks)
            with open(os.path.join({str(tmp_path)!r}, name), "rb") as f:
                assert f.read() == data.tobytes()
            return sum(c[6] for c in chunks), native.write_bounce_stats(lib)

        fresh, stats = write("parent0")
        direct = fresh > 0  # else this filesystem refuses O_DIRECT
        assert stats == dict(allocated=direct, lent=0, kept=direct, kept_bytes=65536 * direct), stats
        pid = os.fork()
        if pid == 0:
            before = native.write_bounce_stats(lib)
            fresh, after = write("child")
            ok = (
                before == dict(allocated=0, lent=0, kept=0, kept_bytes=0)
                and fresh == 65536 * direct
                and after == dict(allocated=direct, lent=0, kept=direct, kept_bytes=65536 * direct)
            )
            os._exit(0 if ok else 1)
        _, status = os.waitpid(pid, 0)
        fresh, stats = write("parent1")
        assert fresh == 0 and stats["allocated"] == direct, stats
        sys.exit(os.waitstatus_to_exitcode(status))
        """
    )
    done = subprocess.run(
        [sys.executable, "-W", "ignore", "-c", script],
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
    )
    assert done.returncode == 0, done.stderr[-2000:]
