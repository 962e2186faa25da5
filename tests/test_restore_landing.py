"""A big raw read lands in the leaf's own host target.

A consumer that would only copy its fetched buffer, byte for byte, into one
contiguous host buffer the restore allocated offers that buffer as the
read's destination (``BufferConsumer.destination`` -> ``ReadIO.into``); the
fs plugin's native read fills it in place of a buffer of its own, and the
consumer, handed its own memory, copies nothing (``landed_bytes``). Every
other read is consumed as ever. Both sides are held here: what lands, and
what must not. CPU runs: counts, addresses and bits only, never a rate.
"""

import contextlib

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, native
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.io_preparers.array import ArrayBufferConsumer
from torchsnapshot_tpu.io_preparers.sharded_array import ShardedArrayBufferConsumer
from torchsnapshot_tpu.scheduler import ReadVerificationError
from torchsnapshot_tpu.storage_plugins import cloud_retry, fs as fs_mod
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.utils import knobs


@pytest.fixture(autouse=True)
def native_reads_of_small_leaves(monkeypatch):
    """Test-sized leaves take the native route, several chunks a read."""
    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    monkeypatch.setattr(fs_mod, "_READ_CHUNK_BYTES", 16384)
    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    with knobs.override_direct_io_threshold_bytes(1024):
        yield


def _any_bits(dtype, shape, seed: int) -> np.ndarray:
    """Every kind of bit pattern of ``dtype``: denormals, infinities, NaNs
    with payloads."""
    dtype = np.dtype(dtype)
    raw = np.random.default_rng(seed).integers(
        0, 256, size=int(np.prod(shape)) * dtype.itemsize, dtype=np.uint8
    )
    return raw.view(dtype).reshape(shape)


def _bits(x) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)


def _addr(buf) -> int:
    return np.frombuffer(memoryview(buf), dtype=np.uint8).ctypes.data


@pytest.fixture
def handed(monkeypatch):
    """What each array consumer was handed: (where its destination starts,
    where the fetched buffer starts, whether it offered a destination)."""
    rec = []

    def where(consumer) -> int:
        if isinstance(consumer, ArrayBufferConsumer):
            return consumer.target.ctypes.data
        dst, _src, dst_slices = consumer.copy_specs[0]
        return (dst[dst_slices] if dst_slices else dst).ctypes.data

    for cls in (ArrayBufferConsumer, ShardedArrayBufferConsumer):
        real = cls.consume_buffer

        async def spy(self, buf, executor=None, _real=real):
            rec.append((where(self), _addr(buf), self.destination() is not None))
            await _real(self, buf, executor)

        monkeypatch.setattr(cls, "consume_buffer", spy)
    return rec


def _mesh(n: int, shape, names):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names)


def _put(host, mesh=None, spec=None):
    import jax
    from jax.sharding import NamedSharding

    if mesh is None:
        return jax.device_put(host)
    return jax.device_put(host, NamedSharding(mesh, spec))


def _zeros(host, mesh=None, spec=None):
    return _put(np.zeros_like(host), mesh, spec)


def _case_single(dtype):
    def build():
        import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

        host = _any_bits(dtype, (256, 192), seed=31)
        return {"w": _put(host)}, {"w": _zeros(host)}

    return build


def _case_stack_3d():
    host = _any_bits(np.float32, (4, 64, 48), seed=32)
    return {"w": _put(host)}, {"w": _zeros(host)}


def _case_host_leaf_without_a_live_array():
    host = _any_bits(np.float32, (128, 96), seed=33)
    return {"w": host}, {"w": None}


def _case_sharded_same_rectangles():
    from jax.sharding import Mesh, PartitionSpec as P

    host = _any_bits("bfloat16", (256, 256), seed=34)
    saved = _mesh(4, (2, 2), ("a", "b"))
    # The same axis names over the transposed device grid: the same set of
    # shard rectangles, handed to other devices.
    onto = Mesh(saved.devices.T, ("a", "b"))
    return (
        {"w": _put(host, saved, P("a", "b"))},
        {"w": _zeros(host, onto, P("a", "b"))},
    )


def _case_sharded_rows_4_to_2():
    from jax.sharding import PartitionSpec as P

    host = _any_bits(np.float32, (256, 64), seed=35)
    # Each saved piece goes whole into a run of rows of one target buffer.
    return (
        {"w": _put(host, _mesh(4, (4,), ("a",)), P("a"))},
        {"w": _zeros(host, _mesh(2, (2,), ("a",)), P("a"))},
    )


def _case_live_ndarray_in_place():
    host = _any_bits(np.float32, (128, 96), seed=36)
    return {"w": host}, {"w": np.zeros_like(host)}


def _case_reshard_cut_columns_4_to_2():
    from jax.sharding import PartitionSpec as P

    host = _any_bits(np.float32, (64, 256), seed=37)
    # A saved column block is a strided part of its target buffer.
    return (
        {"w": _put(host, _mesh(4, (4,), ("a",)), P(None, "a"))},
        {"w": _zeros(host, _mesh(2, (2,), ("a",)), P(None, "a"))},
    )


def _case_reshard_cut_rows_2_to_4():
    from jax.sharding import PartitionSpec as P

    host = _any_bits(np.float32, (256, 64), seed=38)
    # A saved piece is cut between two target buffers.
    return (
        {"w": _put(host, _mesh(2, (2,), ("a",)), P("a"))},
        {"w": _zeros(host, _mesh(4, (4,), ("a",)), P("a"))},
    )


def _case_small_leaves():
    tree = {f"w{i}": _put(_any_bits(np.float32, (32, 32), seed=40 + i)) for i in range(6)}
    return tree, {k: _zeros(np.asarray(v)) for k, v in tree.items()}


# id -> (builder of the saved tree and its targets, knobs around the take,
# knobs around the restore, the URL scheme). Under a ``not:`` id nothing may
# land; under any other every leaf must.
_CASES = {
    "single_bf16": (_case_single("bfloat16"), [], [], "fs"),
    "single_float32": (_case_single(np.float32), [], [], "fs"),
    "stack_3d_float32": (_case_stack_3d, [], [], "fs"),
    "host_leaf_without_a_live_array": (_case_host_leaf_without_a_live_array, [], [], "fs"),
    "sharded_same_rectangles": (_case_sharded_same_rectangles, [], [], "fs"),
    "sharded_rows_4_to_2": (_case_sharded_rows_4_to_2, [], [], "fs"),
    "not:live_ndarray_in_place": (_case_live_ndarray_in_place, [], [], "fs"),
    "not:compressed": (
        _case_single(np.float32), [lambda: knobs.override_compression("zlib")], [], "fs",
    ),
    "not:framed": (
        _case_single(np.float32),
        [
            lambda: knobs.override_compression("zlib"),
            lambda: knobs.override_compression_frame_bytes(32768),
        ],
        [],
        "fs",
    ),
    "not:budget_split": (
        _case_single(np.float32), [], [lambda: knobs.override_memory_budget_bytes(65536)], "fs",
    ),
    "not:reshard_cut_columns_4_to_2": (_case_reshard_cut_columns_4_to_2, [], [], "fs"),
    "not:reshard_cut_rows_2_to_4": (_case_reshard_cut_rows_2_to_4, [], [], "fs"),
    "not:slab_merged": (
        _case_small_leaves,
        [lambda: knobs.override_batching_enabled(True)],
        [lambda: knobs.override_batching_enabled(True)],
        "fs",
    ),
    "not:memory_plugin": (_case_single(np.float32), [], [], "memory"),
}


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "phases"])
@pytest.mark.parametrize("case", list(_CASES))
def test_what_lands_and_what_must_not(tmp_path, handed, case, overlap) -> None:
    """Every restore is bit for bit. Where the consumer would only have
    copied, the fetched buffer IS its target's memory and ``landed_bytes``
    counts it; everywhere else nothing lands and the copy runs as ever."""
    build, take_knobs, restore_knobs, scheme = _CASES[case]
    tree, targets = build()
    lands = [] if case.startswith("not:") else list(tree)
    url = str(tmp_path / "snap") if scheme == "fs" else f"memory://landing/{case}-{overlap}"
    with contextlib.ExitStack() as stack:
        for k in take_knobs:
            stack.enter_context(k())
        Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**targets)
    with contextlib.ExitStack() as stack:
        stack.enter_context(knobs.override_restore_overlap(overlap))
        for k in restore_knobs:
            stack.enter_context(k())
        Snapshot(url).restore({"s": tgt})
    for name, want in tree.items():
        assert np.array_equal(_bits(tgt[name]), _bits(want)), name
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["landed_bytes"] == sum(np.asarray(tree[k]).nbytes for k in lands)
    assert stats["fetch_copied_bytes"] == 0
    if lands:
        assert handed and all(target == got and offered for target, got, offered in handed)
    else:
        assert all(target != got for target, got, _ in handed)
    if case == "host_leaf_without_a_live_array":
        # The restore's own target is what the caller gets: the very
        # memory the engine filled.
        assert [tgt["w"].ctypes.data] == [got for _, got, _ in handed]
    if case == "not:live_ndarray_in_place":
        # Never offered: a caller's live array is overwritten only by
        # bytes that were fetched whole.
        assert tgt["w"] is targets["w"]
        assert handed == [(targets["w"].ctypes.data, handed[0][1], False)]
    if case == "not:budget_split":
        assert stats["requests"] > 1 and not handed  # ChunkedReadConsumer
    if case == "not:slab_merged":
        assert stats["requests"] == 1 and len(handed) == len(tree)
    if case == "not:memory_plugin":
        # Offered, and ignored by the plugin: the consumer copies.
        assert [offered for _, _, offered in handed] == [True]


def test_a_cache_miss_passes_the_offer_on_and_a_hit_is_copied(tmp_path, handed) -> None:
    """The read cache hands the ``ReadIO`` on untouched (a miss lands in
    the target, and the cache keeps a copy of its own); a hit delivers the
    cache's ``bytes``, which the consumer copies."""
    host = _any_bits(np.float32, (256, 192), seed=51)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(w=_put(host))})
    landed = []
    with knobs.override_read_cache_dir(str(tmp_path / "cache")):
        for _ in range(2):
            tgt = StateDict(w=_zeros(host))
            Snapshot(url).restore({"s": tgt})
            assert np.array_equal(_bits(tgt["w"]), _bits(host))
            landed.append(snapshot_mod.LAST_RESTORE_STATS["landed_bytes"])
    assert landed == [host.nbytes, 0]
    (miss, hit) = handed
    assert miss[0] == miss[1] and hit[0] != hit[1] and miss[2] and hit[2]


class _RottingFS(FSStoragePlugin):
    """Flips a byte of the leaf's completed read, in the buffer it was
    delivered in (the target's own memory, where it landed), ``rot`` times."""

    rot = 0
    delivered = []

    async def read(self, read_io) -> None:
        await super().read(read_io)
        if read_io.path == "0/s/w":
            view = read_io.buf.getbuffer()
            type(self).delivered.append(_addr(view))
            if type(self).rot > 0:
                type(self).rot -= 1
                view[len(view) // 2] ^= 0xFF


@pytest.mark.parametrize("rot", [1, 2])
def test_a_landed_read_is_verified_before_it_is_consumed(tmp_path, monkeypatch, handed, rot) -> None:
    """``VERIFY_READS=all`` checks the landed bytes: one corrupted fetch is
    refetched into the same destination and the restore is bit for bit; a
    second raises ``ReadVerificationError`` and nothing is consumed."""
    import torchsnapshot_tpu.storage_plugin as sp

    host = _any_bits(np.float32, (256, 192), seed=52)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(w=_put(host))})
    monkeypatch.setattr(sp, "url_to_storage_plugin", lambda u: _RottingFS(u))
    _RottingFS.rot, _RottingFS.delivered = rot, []
    tgt = StateDict(w=_zeros(host))
    with knobs.override_verify_reads("all"):
        if rot == 1:
            Snapshot(url).restore({"s": tgt})
        else:
            with pytest.raises(Exception) as exc_info:
                Snapshot(url).restore({"s": tgt})
    first, second = _RottingFS.delivered
    assert first == second, "the refetch did not overwrite the same destination"
    if rot == 1:
        assert np.array_equal(_bits(tgt["w"]), _bits(host))
        assert snapshot_mod.LAST_RESTORE_STATS["landed_bytes"] == host.nbytes
        assert handed == [(first, first, True)]
    else:
        chain, e = [], exc_info.value
        while e is not None:
            chain.append(type(e))
            e = e.__cause__
        assert ReadVerificationError in chain, chain
        assert handed == [] and not _bits(tgt["w"]).any()


@pytest.mark.parametrize("verify", ["off", "all"])
def test_a_torn_chunk_read_into_the_target_is_retried_to_exact_bytes(tmp_path, handed, verify) -> None:
    """``op=read_chunk``: chunk 3 of the leaf's native read fails inside
    the engine, once, the other chunks already in the target. The plugin's
    retry overwrites the target from its start."""
    from torchsnapshot_tpu import faults

    host = _any_bits("bfloat16", (256, 192), seed=53)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(w=_put(host))})
    tgt = StateDict(w=_zeros(host))
    spec = "op=read_chunk,kind=transient,path=0/s/w,times=1,chunk=3"
    with knobs.override_faults(spec), knobs.override_verify_reads(verify):
        Snapshot(url).restore({"s": tgt})
        (rule,) = faults._LOCAL_INJECTOR.plan.rules
    assert rule.injected == 1, "the torn chunk read never fired"
    assert np.array_equal(_bits(tgt["w"]), _bits(host))
    assert snapshot_mod.LAST_RESTORE_STATS["landed_bytes"] == host.nbytes
    assert [(t == got, offered) for t, got, offered in handed] == [(True, True)]


def test_the_offer_is_refused_unless_it_is_the_reads_size(tmp_path) -> None:
    """``_native_read`` fills ``ReadIO.into`` only where it is writable and
    exactly as long as the read; else it reads into an array of its own."""
    import asyncio

    from torchsnapshot_tpu.io_types import ReadIO, WriteIO

    data = np.random.default_rng(54).integers(0, 256, 100_000, dtype=np.uint8)

    async def go():
        plugin = FSStoragePlugin(str(tmp_path))
        await plugin.write(WriteIO(path="obj", buf=data.tobytes()))
        out = {}
        for name, size, rng in (("exact", 100_000, None), ("short", 99_999, None), ("range", 50_000, (10, 50_010))):
            dest = np.zeros(size, np.uint8)
            read_io = ReadIO(path="obj", byte_range=rng, into=memoryview(dest))
            await plugin.read(read_io)
            out[name] = (_addr(read_io.buf.getbuffer()) == dest.ctypes.data, bytes(read_io.buf.getbuffer()))
        frozen = np.zeros(100_000, np.uint8)
        frozen.flags.writeable = False
        read_io = ReadIO(path="obj", into=memoryview(frozen))
        await plugin.read(read_io)
        out["readonly"] = (_addr(read_io.buf.getbuffer()) == frozen.ctypes.data, bytes(read_io.buf.getbuffer()))
        await plugin.close()
        return out, frozen

    out, frozen = asyncio.run(go())
    whole = data.tobytes()
    assert out["exact"] == (True, whole)
    assert out["short"] == (False, whole)
    assert out["range"] == (True, whole[10:50_010])
    assert out["readonly"] == (False, whole) and not frozen.any()
