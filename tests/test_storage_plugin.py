"""URL -> StoragePlugin dispatch (reference ``storage_plugin.py:17-68`` tests:
``tests/test_fs_storage_plugin.py`` et al.), plus raw FS plugin behavior:
ranged reads, delete, and parent-dir creation."""

import asyncio
import errno
import io

import pytest

from torchsnapshot_tpu.io_types import ReadBuffer, ReadIO, WriteIO
from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin


def test_bare_path_dispatches_to_fs(tmp_path) -> None:
    plugin = url_to_storage_plugin(str(tmp_path))
    assert isinstance(plugin, FSStoragePlugin)


def test_fs_scheme(tmp_path) -> None:
    plugin = url_to_storage_plugin(f"fs://{tmp_path}")
    assert isinstance(plugin, FSStoragePlugin)


def test_memory_scheme_shares_roots() -> None:
    a = url_to_storage_plugin("memory://bucket1")
    b = url_to_storage_plugin("memory://bucket1")
    c = url_to_storage_plugin("memory://bucket2")
    assert isinstance(a, MemoryStoragePlugin)
    assert a is b  # same root -> same instance (snapshots visible across opens)
    assert a is not c


def test_unsupported_scheme_raises() -> None:
    with pytest.raises(RuntimeError, match="Unsupported protocol"):
        url_to_storage_plugin("carrierpigeon://coop")


def test_malformed_url_raises() -> None:
    with pytest.raises(RuntimeError):
        url_to_storage_plugin("://nothing")


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.mark.parametrize("plugin_kind", ["fs", "memory"])
def test_write_read_roundtrip(tmp_path, plugin_kind) -> None:
    plugin = (
        FSStoragePlugin(root=str(tmp_path))
        if plugin_kind == "fs"
        else MemoryStoragePlugin(root="test_rt")
    )
    payload = bytes(range(256)) * 16

    async def go():
        await plugin.write(WriteIO(path="deep/nested/blob", buf=payload))
        rio = ReadIO(path="deep/nested/blob")
        await plugin.read(rio)
        return rio.buf.getvalue()

    assert _run(go()) == payload
    _run(plugin.close())


@pytest.mark.parametrize("plugin_kind", ["fs", "memory"])
def test_ranged_read(tmp_path, plugin_kind) -> None:
    plugin = (
        FSStoragePlugin(root=str(tmp_path))
        if plugin_kind == "fs"
        else MemoryStoragePlugin(root="test_ranged")
    )
    payload = bytes(range(256)) * 4

    async def go():
        await plugin.write(WriteIO(path="blob", buf=payload))
        out = []
        # A spread of byte ranges, including slab-style interior ranges.
        for lo, hi in [(0, 10), (100, 356), (1000, 1024), (0, 1024)]:
            rio = ReadIO(path="blob", byte_range=(lo, hi))
            await plugin.read(rio)
            out.append((lo, hi, rio.buf.getvalue()))
        return out

    for lo, hi, got in _run(go()):
        assert got == payload[lo:hi], (lo, hi)
    _run(plugin.close())


def test_fs_delete(tmp_path) -> None:
    plugin = FSStoragePlugin(root=str(tmp_path))

    async def go():
        await plugin.write(WriteIO(path="doomed", buf=b"x"))
        await plugin.delete(path="doomed")

    _run(go())
    assert not (tmp_path / "doomed").exists()
    _run(plugin.close())


def test_memoryview_payload_accepted(tmp_path) -> None:
    # Plugins must accept memoryview payloads (zero-copy staged buffers).
    plugin = FSStoragePlugin(root=str(tmp_path))
    payload = memoryview(b"zero-copy payload")

    async def go():
        await plugin.write(WriteIO(path="mv", buf=payload))
        rio = ReadIO(path="mv")
        await plugin.read(rio)
        return rio.buf.getvalue()

    assert _run(go()) == bytes(payload)
    _run(plugin.close())


# ---------------------------------------------------------------------------
# ReadIO.buf: the read's bytes held by reference (io_types.ReadBuffer)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", [bytes, bytearray, memoryview])
def test_read_buffer_keeps_the_first_write_by_reference(kind) -> None:
    data = kind(b"0123456789")
    buf = ReadBuffer()
    assert buf.write(data) == 10
    backing = data.obj if kind is memoryview else data
    assert buf.getbuffer().obj is backing
    assert buf.getbuffer().format == "B" and buf.getbuffer().nbytes == 10
    assert buf.getvalue() == b"0123456789"
    # Only ``bytes`` can be handed on as ``bytes`` without a copy.
    assert (buf.getvalue() is data) == (kind is bytes)
    assert buf.copied_bytes == 0


def test_read_buffer_second_write_joins_and_counts_the_copy() -> None:
    """The reference's ``BytesIO`` contract for a plugin that delivers one
    read in pieces: the concatenation, through a counted copy that leaves
    the plugin's own objects alone."""
    first, second = bytearray(b"abc"), b"defg"
    buf = ReadBuffer()
    buf.write(first)
    buf.write(b"")  # nothing delivered, nothing joined
    assert buf.getbuffer().obj is first and buf.copied_bytes == 0
    buf.write(second)
    assert buf.getvalue() == b"abcdefg" and buf.copied_bytes == 7
    buf.write(memoryview(b"hi"))
    assert buf.getvalue() == b"abcdefghi" and buf.copied_bytes == 9
    assert first == b"abc", "joined into the plugin's own buffer"


def test_read_buffer_reset_drops_the_failed_attempts_bytes() -> None:
    buf = ReadBuffer()
    buf.write(b"torn")
    buf.seek(0)
    buf.truncate(0)
    assert buf.getvalue() == b"" and buf.getbuffer().nbytes == 0
    whole = b"whole"
    buf.write(whole)
    assert buf.getvalue() is whole and buf.copied_bytes == 0
    # What the read path never asks of it is refused, not half-emulated.
    with pytest.raises(io.UnsupportedOperation):
        buf.seek(2)
    with pytest.raises(io.UnsupportedOperation):
        buf.truncate(3)


class _TornOnce:
    """First attempt of a read: part of the bytes delivered, then a
    transient error, as a read torn by a stale handle."""

    def __init__(self) -> None:
        self.failures = 0

    def tear(self, read_io: ReadIO) -> None:
        if self.failures == 0:
            self.failures += 1
            read_io.buf.write(b"\xff" * 100)
            raise OSError(errno.ESTALE, "stale handle mid-read")


def _read_through_pipeline(plugin, path: str, nbytes: int) -> bytes:
    from torchsnapshot_tpu.io_types import ReadReq
    from torchsnapshot_tpu.scheduler import execute_read_reqs

    got = []

    class Consumer:
        def get_consuming_cost_bytes(self) -> int:
            return nbytes

        async def consume_buffer(self, buf, executor=None) -> None:
            got.append(bytes(buf))

    _run(
        execute_read_reqs(
            [ReadReq(path=path, buffer_consumer=Consumer())],
            plugin,
            memory_budget_bytes=1 << 20,
            rank=0,
        )
    )
    (data,) = got
    return data


@pytest.mark.parametrize("layer", ["fs_plugin", "read_pipeline"])
def test_retried_read_delivers_only_the_second_attempt(tmp_path, monkeypatch, layer) -> None:
    """Both retry layers (the fs plugin's own, and the read pipeline's for
    any plugin) start the second attempt from an empty buffer."""
    from torchsnapshot_tpu.storage_plugins import cloud_retry

    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    payload = bytes(range(256)) * 8
    torn = _TornOnce()

    if layer == "fs_plugin":

        class Flaky(FSStoragePlugin):
            async def _read_inner(self, read_io: ReadIO) -> None:
                torn.tear(read_io)
                await super()._read_inner(read_io)

    else:

        class Flaky(FSStoragePlugin):
            async def read(self, read_io: ReadIO) -> None:
                torn.tear(read_io)
                await super().read(read_io)

    plugin = Flaky(root=str(tmp_path))
    _run(plugin.write(WriteIO(path="obj", buf=payload)))
    assert _read_through_pipeline(plugin, "obj", len(payload)) == payload
    assert torn.failures == 1, "the transient fault never fired"
    _run(plugin.close())


@pytest.mark.parametrize("chunk", [0, 2], ids=["first_chunk", "third_chunk"])
def test_read_torn_at_chunk_grain_delivers_a_fresh_destination(
    tmp_path, monkeypatch, chunk
) -> None:
    """``op=read_chunk``: one chunk of one object's native read fails
    transiently, inside the engine, once. The plugin's retry reads the
    object again into a destination of its own; the consumer sees that one
    and never the array the failed attempt partly filled."""
    import numpy as np

    from torchsnapshot_tpu import native
    from torchsnapshot_tpu.io_types import ReadReq
    from torchsnapshot_tpu.scheduler import execute_read_reqs
    from torchsnapshot_tpu.storage_plugins import cloud_retry, fs as fs_mod
    from torchsnapshot_tpu.utils import knobs

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    monkeypatch.setattr(fs_mod, "_READ_CHUNK_BYTES", 4096)
    payload = np.random.default_rng(chunk).integers(0, 256, 5 * 4096 + 9, dtype=np.uint8)
    destinations, delivered, seen = [], [], []
    real_empty = np.empty

    def recording_empty(*args, **kwargs):
        destinations.append(real_empty(*args, **kwargs))
        return destinations[-1]

    class Recording(FSStoragePlugin):
        async def _native_read(self, path, offset, nbytes, into=None):
            delivered.append(await super()._native_read(path, offset, nbytes, into))
            return delivered[-1]

    class Consumer:
        def get_consuming_cost_bytes(self) -> int:
            return payload.size

        async def consume_buffer(self, buf, executor=None) -> None:
            seen.append(memoryview(buf))

    async def go() -> None:
        plugin = Recording(root=str(tmp_path))
        await plugin.write(WriteIO(path="obj", buf=payload.tobytes()))
        await plugin.write(WriteIO(path="other", buf=payload.tobytes()))
        monkeypatch.setattr(fs_mod.np, "empty", recording_empty)
        try:
            await execute_read_reqs(
                [
                    ReadReq(path="other", buffer_consumer=Consumer()),
                    ReadReq(path="obj", buffer_consumer=Consumer()),
                ],
                plugin,
                memory_budget_bytes=1 << 30,
                rank=0,
            )
        finally:
            monkeypatch.setattr(fs_mod.np, "empty", real_empty)
        await plugin.close()

    spec = f"op=read_chunk,kind=transient,path=obj,times=1,chunk={chunk}"
    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_faults(spec):
        _run(go())
    # Three attempts allocated, two delivered: the torn one raised.
    assert len(destinations) == 3 and len(delivered) == 2 and len(seen) == 2
    torn = [d for d in destinations if not any(d is ok for ok in delivered)]
    assert len(torn) == 1
    for view in seen:
        assert view == payload.tobytes()
        assert any(view.obj is ok for ok in delivered) and view.obj is not torn[0]
