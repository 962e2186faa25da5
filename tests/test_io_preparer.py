"""Unit tests for the value-routing and shard/chunk math (reference
``tests/test_tensor_io_preparer.py``, ``tests/test_chunked_tensor_io_preparer.py``,
``tests/test_sharded_tensor_io_preparer.py``)."""

import numpy as np
import pytest

from torchsnapshot_tpu.device_programs import chunk_row_ranges
from torchsnapshot_tpu.io_preparer import classify, get_storage_path
from torchsnapshot_tpu.io_preparers.chunked_array import should_chunk
from torchsnapshot_tpu.io_preparers.sharded_array import (
    index_to_offsets_sizes,
    local_unique_shards,
    overlap,
    subdivide,
)
from torchsnapshot_tpu.utils import knobs


# ------------------------------------------------------------------- routing

def test_get_storage_path() -> None:
    assert get_storage_path("model/w", rank=3, replicated=False) == "3/model/w"
    assert get_storage_path("model/w", rank=3, replicated=True) == "replicated/model/w"


@pytest.mark.parametrize(
    "value, expected",
    [
        (1, "primitive"),
        (1.5, "primitive"),
        (True, "primitive"),
        ("s", "primitive"),
        (b"b", "primitive"),
        (None, "primitive"),
        (np.ones((2, 2)), "array"),
        ({"not": "stateful"}, "object"),
        ([1, 2, 3], "object"),
    ],
)
def test_classify_host_values(value, expected) -> None:
    assert classify(value, world_size=1) == expected


def test_classify_numpy_scalar_is_array_not_primitive() -> None:
    # np.generic must not be routed as a Python primitive: its repr would not
    # round-trip through the manifest.
    assert classify(np.float32(1.5), world_size=1) in ("array", "object")


def test_classify_jax_single_device_array() -> None:
    import jax.numpy as jnp

    assert classify(jnp.ones((2, 2)), world_size=1) == "array"


def test_classify_mesh_sharded_array() -> None:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("x",))
    arr = jax.device_put(
        np.arange(16, dtype=np.float32).reshape(4, 4),
        NamedSharding(mesh, P("x")),
    )
    assert classify(arr, world_size=1) == "sharded"


# ------------------------------------------------------------------ chunking

def test_should_chunk_respects_knob() -> None:
    arr = np.zeros((8, 1024), dtype=np.float32)  # 32 KB
    assert not should_chunk(arr)
    with knobs.override_max_chunk_size_bytes(4 * 1024):
        assert should_chunk(arr)
    # dim0 == 1 can't be row-chunked.
    single = np.zeros((1, 8 * 1024), dtype=np.float32)
    with knobs.override_max_chunk_size_bytes(4 * 1024):
        assert not should_chunk(single)


def test_chunk_row_ranges_cover_and_bound() -> None:
    shape = (100, 7)
    itemsize = 4
    max_bytes = 10 * 7 * 4  # 10 rows
    ranges = chunk_row_ranges(shape, itemsize, max_bytes)
    # Full disjoint cover of [0, 100).
    assert ranges[0][0] == 0
    assert ranges[-1][1] == 100
    for (a0, a1), (b0, b1) in zip(ranges, ranges[1:]):
        assert a1 == b0
    row_bytes = itemsize * 7
    for r0, r1 in ranges:
        assert (r1 - r0) * row_bytes <= max_bytes
    # Even spread: no tiny trailing chunk.
    sizes = [r1 - r0 for r0, r1 in ranges]
    assert max(sizes) - min(sizes) <= 1


def test_chunk_row_ranges_single_huge_row() -> None:
    # A row larger than max_chunk still yields 1-row chunks (can't split rows).
    ranges = chunk_row_ranges((4, 1000), itemsize=8, max_chunk_bytes=16)
    assert ranges == [(0, 1), (1, 2), (2, 3), (3, 4)]


# ---------------------------------------------------------------- shard math

def test_index_to_offsets_sizes() -> None:
    offs, szs = index_to_offsets_sizes(
        (slice(2, 6), slice(None)), global_shape=(8, 3)
    )
    assert offs == [2, 0]
    assert szs == [4, 3]
    # 0-d array: empty index.
    offs, szs = index_to_offsets_sizes((), global_shape=())
    assert offs == [] and szs == []
    with pytest.raises(ValueError):
        index_to_offsets_sizes((slice(0, 8, 2),), global_shape=(8,))


def test_subdivide_covers_without_overlap() -> None:
    pieces = subdivide([4, 0], [16, 8], itemsize=4, max_bytes=8 * 4 * 4)
    # Largest dim (0) split into 4-row pieces.
    assert [(o[0], s[0]) for o, s in pieces] == [(4, 4), (8, 4), (12, 4), (16, 4)]
    for o, s in pieces:
        assert o[1] == 0 and s[1] == 8
        assert int(np.prod(s)) * 4 <= 8 * 4 * 4


def test_subdivide_small_shard_untouched() -> None:
    assert subdivide([0], [4], itemsize=4, max_bytes=1024) == [([0], [4])]
    # 0-d shard.
    assert subdivide([], [], itemsize=4, max_bytes=1) == [([], [])]


@pytest.mark.parametrize(
    "src, dst, expected",
    [
        # Identical regions.
        (([0, 0], [4, 4]), ([0, 0], [4, 4]), ((slice(0, 4), slice(0, 4)), (slice(0, 4), slice(0, 4)))),
        # Partial overlap.
        (([0, 0], [4, 4]), ([2, 2], [4, 4]), ((slice(2, 4), slice(2, 4)), (slice(0, 2), slice(0, 2)))),
        # Disjoint.
        (([0, 0], [2, 2]), ([2, 2], [2, 2]), None),
        # Touching edges are disjoint (half-open ranges).
        (([0], [4]), ([4], [4]), None),
        # Containment.
        (([0], [8]), ([2], [2]), ((slice(2, 4),), (slice(0, 2),))),
    ],
)
def test_overlap(src, dst, expected) -> None:
    got = overlap(src[0], src[1], dst[0], dst[1])
    assert got == expected


def test_overlap_scatter_roundtrip() -> None:
    # Write a global array as 1 saved region; scatter into 3 uneven dst shards.
    rng = np.random.default_rng(0)
    src = rng.standard_normal((10, 6))
    dst_specs = [([0, 0], [3, 6]), ([3, 0], [4, 6]), ([7, 0], [3, 6])]
    out = np.zeros_like(src)
    for off, sz in dst_specs:
        ov = overlap([0, 0], [10, 6], off, sz)
        assert ov is not None
        src_sl, dst_sl = ov
        view = out[tuple(slice(o, o + s) for o, s in zip(off, sz))]
        view[dst_sl] = src[src_sl]
    assert np.array_equal(out, src)


def test_local_unique_shards_dedups_replicas() -> None:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # (2, 4) mesh, sharded on x only -> each row-block replicated 4x.
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("x", "y"))
    arr = jax.device_put(
        np.arange(32, dtype=np.float32).reshape(8, 4),
        NamedSharding(mesh, P("x", None)),
    )
    shards = local_unique_shards(arr)
    assert len(shards) == 2  # one per unique row-block, replicas deduped
    for _, offsets, sizes, replica_id in shards:
        assert replica_id == 0  # authoritative copies win the dedup
        assert sizes == [4, 4]
    assert sorted(off[0] for _, off, _, _ in shards) == [0, 4]


# ------------------------------------------- staging through the pipeline

def _write_all(reqs, storage):
    import asyncio

    from torchsnapshot_tpu.scheduler import execute_write_reqs

    async def go():
        pending = await execute_write_reqs(
            reqs, storage, memory_budget_bytes=10**9, rank=0
        )
        await pending.complete()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()


def test_chunked_compressed_roundtrip_bit_exact() -> None:
    """A dim-0-chunked, framed-zlib-compressed array staged through the
    write pipeline produces, per chunk, the codec's own framed payload and
    its .ftab frame table, and restores bit-exact."""
    import asyncio
    import json

    from torchsnapshot_tpu.serialization import Serializer, compress_framed

    from torchsnapshot_tpu.io_preparers.chunked_array import (
        ChunkedArrayIOPreparer,
    )
    from torchsnapshot_tpu.scheduler import execute_read_reqs
    from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

    rng = np.random.default_rng(7)
    arr = rng.standard_normal((64, 64)).astype(np.float32)  # 16 KB

    storage_on = MemoryStoragePlugin()
    with knobs.override_compression("zlib"), \
            knobs.override_compression_frame_bytes(1024), \
            knobs.override_max_chunk_size_bytes(8192):
        entry_on, reqs = ChunkedArrayIOPreparer.prepare_write("arr", arr)
        assert len(entry_on.chunks) > 1  # really chunked
        level = reqs[0].buffer_stager.compression_level
        _write_all(reqs, storage_on)
    # One payload + its .ftab per chunk object, each the codec's output of
    # that chunk's rows.
    for chunk in entry_on.chunks:
        r0 = chunk.offsets[0]
        rows = arr[r0 : r0 + chunk.sizes[0]]
        payload, sizes = compress_framed(
            rows.tobytes(), Serializer.RAW_ZLIB, level, 1024
        )
        location = chunk.tensor.location
        assert storage_on.objects[location] == bytes(payload)
        ftab = json.loads(storage_on.objects[location + ".ftab"])
        assert ftab["sizes"] == list(sizes)

    # Round-trip through the read pipeline, bit-exact.
    target = np.zeros_like(arr)
    read_reqs = ChunkedArrayIOPreparer.prepare_read(entry_on, target)

    async def read():
        await execute_read_reqs(
            read_reqs, storage_on, memory_budget_bytes=10**9, rank=0
        )

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(read())
    finally:
        loop.close()
    assert np.array_equal(
        target.view(np.uint8), arr.view(np.uint8)
    )


def test_raw_array_object_is_the_arrays_bytes() -> None:
    """RAW (uncompressed): one request, and the stored object is exactly
    the array's bytes."""
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
    from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

    rng = np.random.default_rng(11)
    arr = rng.integers(0, 255, size=(128, 32), dtype=np.uint8)  # 4 KB
    storage = MemoryStoragePlugin()
    _entry, reqs = ArrayIOPreparer.prepare_write("arr", arr)
    assert len(reqs) == 1
    _write_all(reqs, storage)
    assert storage.objects["arr"] == arr.tobytes()
