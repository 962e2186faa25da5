"""Opt-in array-payload compression (``TORCHSNAPSHOT_TPU_COMPRESSION``).

The incumbent TPU checkpointer compresses (orbax/TensorStore OCDBT writes
zstd'd chunks, measured 1.4x on bf16 noise); this is the equivalent
capability here: raw byte streams compressed whole per storage object, with
the serializer recorded per entry so restore auto-detects and mixed
snapshots coexist.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.serialization import Serializer
from torchsnapshot_tpu.test_utils import rand_array
from torchsnapshot_tpu.utils import knobs

def _app():
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("x",))
    sharded = jax.device_put(
        jnp.asarray(np.arange(64 * 32, dtype=np.float32).reshape(64, 32)),
        NamedSharding(mesh, P("x")),
    )
    return {
        "m": StateDict(
            f32=np.arange(4096, dtype=np.float32).reshape(64, 64),
            bf16=jnp.ones((128, 8), jnp.bfloat16) * 3,
            i64=np.arange(100),
            sharded=sharded,
            obj={1, "two"},  # sets stay opaque -> pickle ObjectEntry
            scalar=7,
        )
    }


def _assert_restored(path, app) -> None:
    src = app["m"]
    tgt = StateDict(
        f32=np.zeros((64, 64), np.float32),
        bf16=jnp.zeros((128, 8), jnp.bfloat16),
        i64=np.zeros(100, np.int64),
        sharded=jnp.zeros((64, 32), jnp.float32),
        obj=None,
        scalar=0,
    )
    Snapshot(path).restore({"m": tgt})
    assert np.array_equal(tgt["f32"], src["f32"])
    assert np.asarray(tgt["bf16"]).view(np.uint8).tobytes() == np.asarray(src["bf16"]).view(np.uint8).tobytes()
    assert np.array_equal(tgt["i64"], src["i64"])
    assert np.array_equal(np.asarray(tgt["sharded"]), np.asarray(src["sharded"]))
    assert tgt["obj"] == {1, "two"}
    assert tgt["scalar"] == 7


def _tree_bytes(root: str) -> int:
    total = 0
    for dirpath, _, files in os.walk(root):
        for f in files:
            total += os.path.getsize(os.path.join(dirpath, f))
    return total


@pytest.mark.parametrize(
    "codec,serializer",
    [
        ("zstd", Serializer.RAW_ZSTD),
        ("zlib", Serializer.RAW_ZLIB),
    ],
)
def test_compressed_roundtrip(tmp_path, codec, serializer) -> None:
    app = _app()
    path = str(tmp_path / codec)
    with knobs.override_compression(codec):
        Snapshot.take(path, app)
    manifest = Snapshot(path).get_manifest()
    assert manifest["0/m/f32"].serializer == serializer
    for shard in manifest["0/m/sharded"].shards:
        assert shard.tensor.serializer == serializer
    assert manifest["0/m/obj"].type == "object"  # pickle path unaffected
    # Restore without the knob: serializer is read from the entry.
    _assert_restored(path, app)
    assert Snapshot(path).verify() == {}


def test_compression_shrinks_storage(tmp_path) -> None:
    app = _app()  # arange/ones data: highly compressible
    plain = str(tmp_path / "plain")
    comp = str(tmp_path / "comp")
    Snapshot.take(plain, app)
    with knobs.override_compression("zstd"):
        Snapshot.take(comp, app)
    assert _tree_bytes(comp) < _tree_bytes(plain) * 0.7


def test_compressed_read_object_ignores_byte_budget_correctly(tmp_path) -> None:
    """Compressed entries are not byte-range addressable: read_object with a
    budget still returns exact data via whole-object reads."""
    app = _app()
    path = str(tmp_path / "c")
    with knobs.override_compression("zstd"):
        Snapshot.take(path, app)
    got = Snapshot(path).read_object("0/m/sharded", memory_budget_bytes=64)
    assert np.array_equal(got, np.asarray(app["m"]["sharded"]))
    got = Snapshot(path).read_object("0/m/f32", memory_budget_bytes=64)
    assert np.array_equal(got, app["m"]["f32"])


def test_compressed_chunked_roundtrip(tmp_path) -> None:
    with knobs.override_max_chunk_size_bytes(1024), knobs.override_compression("zstd"):
        arr = np.arange(64 * 32, dtype=np.float32).reshape(64, 32)
        path = str(tmp_path / "c")
        Snapshot.take(path, {"s": StateDict(a=arr)})
        entry = Snapshot(path).get_manifest()["0/s/a"]
        assert entry.type == "chunked_array" and len(entry.chunks) > 1
        assert entry.chunks[0].tensor.serializer == Serializer.RAW_ZSTD
    tgt = StateDict(a=np.zeros((64, 32), np.float32))
    Snapshot(path).restore({"s": tgt})
    assert np.array_equal(tgt["a"], arr)


def test_compression_composes_with_batching(tmp_path) -> None:
    """Small compressed entries coalesce into member-framed compressed
    slabs: the manifest records each member's RAW range within the packed
    slab (compressed sizes don't exist at planning time), the slab's
    ``.ftab`` maps raw ranges to compressed frames, and restore reads each
    member via its covering frames."""
    app = _app()
    path = str(tmp_path / "b")
    with knobs.override_batching_enabled(True), knobs.override_slab_size_threshold_bytes(1 << 20):
        with knobs.override_compression("zstd"):
            Snapshot.take(path, app)
        manifest = Snapshot(path).get_manifest()
        batched = [
            e
            for e in manifest.values()
            if getattr(e, "location", "").startswith("batched/")
        ]
        assert batched, "small compressed entries should join slabs now"
        assert all(
            e.serializer == Serializer.RAW_ZSTD and e.raw_range is not None
            for e in batched
        )
        # One frame table per slab, written by the same pipeline.
        for loc in {e.location for e in batched}:
            assert os.path.exists(os.path.join(path, loc + ".ftab"))
        _assert_restored(path, app)
        assert Snapshot(path).verify() == {}


def test_async_device_compressed_entries_batch_into_slabs(tmp_path) -> None:
    """Async takes get BOTH wins now: small compressed device entries join
    slabs (one storage object, one D2H via the device-batched packer) and
    compress at drain time — never inside the stall window — because the
    slab is compressed member-framed at staging."""
    dev = jax.devices()[0]
    dev_a = jax.device_put(jnp.asarray(np.arange(256, dtype=np.float32)), dev)
    dev_b = jax.device_put(jnp.asarray(np.arange(256, dtype=np.float32) + 1), dev)
    app = {"m": StateDict(a=dev_a, b=dev_b)}
    path = str(tmp_path / "a")
    with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
        pending = Snapshot.async_take(path, app)
        # Donation-safety composes: originals die right after return.
        dev_a.delete()
        dev_b.delete()
        pending.wait()
    manifest = Snapshot(path).get_manifest()
    batched = [
        e
        for e in manifest.values()
        if getattr(e, "location", "").startswith("batched/")
    ]
    assert len(batched) == 2, manifest
    assert len({e.location for e in batched}) == 1  # ONE slab object
    assert all(e.raw_range is not None for e in batched)
    slab_loc = batched[0].location
    assert os.path.exists(os.path.join(path, slab_loc + ".ftab"))
    # The slab object holds compressed frames: smaller than the raw bytes.
    assert os.path.getsize(os.path.join(path, slab_loc)) < 2 * 256 * 4
    assert Snapshot(path).verify() == {}
    tgt = StateDict(a=jnp.zeros(256, jnp.float32), b=jnp.zeros(256, jnp.float32))
    Snapshot(path).restore({"m": tgt})
    assert np.array_equal(np.asarray(tgt["a"]), np.arange(256, dtype=np.float32))
    assert np.array_equal(np.asarray(tgt["b"]), np.arange(256, dtype=np.float32) + 1)
    # Random access to one member fetches its frames via the table.
    got = Snapshot(path).read_object("0/m/a")
    assert np.array_equal(np.asarray(got), np.arange(256, dtype=np.float32))


def _worker_replicated_compressed_slab(rank, world_size, shared):
    """Replicated small compressed arrays across ranks: the partitioner
    assigns the writes to one rank, whose slab batching relocates the
    entries to a batched/ object via raw_range — consolidation must
    propagate that relocation (location + raw_range) to every rank's
    manifest copy, or non-writer ranks restore from a path that was never
    written."""
    import os

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    src = {
        f"t{i}": (np.arange(512, dtype=np.float32) + i) for i in range(6)
    }
    path = os.path.join(shared, "ckpt")
    with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
        Snapshot.take(
            path, {"m": StateDict(**src)}, replicated=["m/*"]
        )
    manifest = Snapshot(path).get_manifest()
    # Every rank's copy of each replicated entry points at the same slab.
    for i in range(6):
        per_rank = [manifest[f"{r}/m/t{i}"] for r in range(world_size)]
        locs = {e.location for e in per_rank}
        assert len(locs) == 1, locs
        assert all(e.raw_range is not None for e in per_rank), per_rank
        assert next(iter(locs)).startswith("batched/"), locs
    assert Snapshot(path).verify() == {}
    tgt = {"m": StateDict(**{f"t{i}": np.zeros(512, np.float32) for i in range(6)})}
    Snapshot(path).restore(tgt)
    for i in range(6):
        assert np.array_equal(tgt["m"][f"t{i}"], src[f"t{i}"])


@pytest.mark.multiprocess
def test_replicated_compressed_slab_consolidates_across_ranks(tmp_path) -> None:
    from torchsnapshot_tpu.test_utils import run_with_processes

    run_with_processes(
        _worker_replicated_compressed_slab, nproc=2, args=(str(tmp_path),)
    )


def _worker_take_replicated_slab(rank, world_size, shared):
    import os

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    src = {f"t{i}": (np.arange(256, dtype=np.float32) + i) for i in range(5)}
    with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
        Snapshot.take(
            os.path.join(shared, "ckpt"), {"m": StateDict(**src)}, replicated=["m/*"]
        )


@pytest.mark.multiprocess
def test_compressed_slab_snapshot_elastic_across_world_sizes(tmp_path) -> None:
    """Elasticity x compressed slabs: a replicated state taken at world 2
    (slab written by one rank, entries consolidated) restores in a world-1
    process that never participated in the take."""
    from torchsnapshot_tpu.test_utils import run_with_processes

    run_with_processes(
        _worker_take_replicated_slab, nproc=2, args=(str(tmp_path),)
    )
    path = str(tmp_path / "ckpt")
    # Guard the premise: the replicated entries really are compressed slab
    # members (else the restore below exercises nothing new).
    manifest = Snapshot(path).get_manifest()
    for i in range(5):
        e = manifest[f"0/m/t{i}"]
        assert e.location.startswith("batched/") and e.raw_range is not None, e
    tgt = {"m": StateDict(**{f"t{i}": np.zeros(256, np.float32) for i in range(5)})}
    Snapshot(path).restore(tgt)
    for i in range(5):
        assert np.array_equal(tgt["m"][f"t{i}"], np.arange(256, dtype=np.float32) + i)
    assert Snapshot(path).verify() == {}


def test_compressed_slab_ftab_lost_degrades_to_whole_slab_read(tmp_path, caplog) -> None:
    """A lost/corrupt slab frame table degrades to reading + decoding the
    whole slab and slicing members out — never a failed restore."""
    import logging

    app = {
        "m": StateDict(
            a=np.arange(512, dtype=np.float32),
            b=np.arange(512, dtype=np.float32) * 2,
        )
    }
    path = str(tmp_path / "d")
    with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
        Snapshot.take(path, app)
    manifest = Snapshot(path).get_manifest()
    slab_loc = manifest["0/m/a"].location
    assert slab_loc.startswith("batched/")
    os.remove(os.path.join(path, slab_loc + ".ftab"))
    tgt = StateDict(a=np.zeros(512, np.float32), b=np.zeros(512, np.float32))
    with caplog.at_level(logging.WARNING, logger="torchsnapshot_tpu.snapshot"):
        Snapshot(path).restore({"m": tgt})
    assert any("frame table" in r.getMessage() for r in caplog.records)
    assert np.array_equal(tgt["a"], app["m"]["a"])
    assert np.array_equal(tgt["b"], app["m"]["b"])


def test_compressed_slabs_shrink_small_param_storage(tmp_path) -> None:
    """The done-criterion composition: a small-param-heavy state (MoE/
    embedding shaped: many sub-threshold arrays) gets one-object-per-slab
    AND compression — measurably smaller than both the uncompressed-batched
    and the unbatched-compressed layouts of the same data."""
    rng = np.random.default_rng(0)
    # f16-quantized noise re-widened to f32: zero mantissa tails compress
    # like trained weights do, unlike white f32 noise.
    base = rng.standard_normal(1024).astype(np.float16).astype(np.float32)
    app = {
        "m": StateDict(**{f"e{i}": base + np.float32(i) for i in range(32)})
    }
    plain_batched = str(tmp_path / "pb")
    comp_unbatched = str(tmp_path / "cu")
    comp_batched = str(tmp_path / "cb")
    with knobs.override_batching_enabled(True):
        Snapshot.take(plain_batched, app)
        with knobs.override_compression("zstd"):
            Snapshot.take(comp_batched, app)
    with knobs.override_compression("zstd"):
        Snapshot.take(comp_unbatched, app)

    def data_objects(root):
        return [
            os.path.join(d, f)
            for d, _, fs in os.walk(root)
            for f in fs
            if not f.startswith(".")
        ]

    # Compression shrinks bytes vs the raw slab...
    assert _tree_bytes(comp_batched) < _tree_bytes(plain_batched) * 0.8
    # ...and batching collapses the object count vs unbatched compressed.
    assert len(data_objects(comp_batched)) < len(data_objects(comp_unbatched)) / 4
    tgt = StateDict(**{f"e{i}": np.zeros(1024, np.float32) for i in range(32)})
    Snapshot(comp_batched).restore({"m": tgt})
    for i in range(32):
        assert np.array_equal(tgt[f"e{i}"], base + np.float32(i))


def test_framed_budgeted_subreads_never_read_whole_object(tmp_path) -> None:
    """Large compressed arrays are framed: read_object with a memory budget
    fetches + decompresses only covering frames, never the whole payload."""
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    rng = np.random.default_rng(0)
    # ~1 MB array, 64 KiB frames -> 16 frames.
    arr = rng.standard_normal(128 * 1024).astype(np.float64)
    path = str(tmp_path / "f")
    with knobs.override_compression("zstd"), knobs.override_compression_frame_bytes(64 * 1024):
        Snapshot.take(path, {"s": StateDict(a=arr)})
    entry = Snapshot(path).get_manifest()["0/s/a"]
    assert entry.frame_bytes == 64 * 1024
    assert os.path.exists(os.path.join(path, "0", "s", "a.ftab"))

    # Spy on read sizes through the plugin.
    read_sizes = []
    orig_read = FSStoragePlugin.read

    async def spy_read(self, read_io):
        await orig_read(self, read_io)
        read_sizes.append(read_io.buf.getbuffer().nbytes)

    FSStoragePlugin.read = spy_read
    try:
        got = Snapshot(path).read_object("0/s/a", memory_budget_bytes=128 * 1024)
    finally:
        FSStoragePlugin.read = orig_read
    assert np.array_equal(got, arr)
    payload_bytes = os.path.getsize(os.path.join(path, "0", "s", "a"))
    # Every read (incl. metadata/ftab) is far smaller than the whole payload.
    data_reads = [s for s in read_sizes if s > 16 * 1024]
    assert data_reads, read_sizes
    assert max(data_reads) < payload_bytes * 0.5, (read_sizes, payload_bytes)


def test_framed_sharded_budgeted_restore(tmp_path) -> None:
    """Budgeted sub-reads work on compressed SHARDED arrays: no read ever
    fetches a whole shard payload, and the reshard stays bit-exact."""
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("a", "b"))
    rng = np.random.default_rng(5)
    host = rng.standard_normal((256, 128)).astype(np.float32)  # 128 KiB
    arr = jax.device_put(jnp.asarray(host), NamedSharding(mesh, P("a")))
    path = str(tmp_path / "fs")
    # 2 shards of 64 KiB; 8 KiB frames -> 8 frames per shard.
    with knobs.override_compression("zstd"), knobs.override_compression_frame_bytes(8 * 1024):
        Snapshot.take(path, {"s": StateDict(x=arr)})
    entry = Snapshot(path).get_manifest()["0/s/x"]
    assert all(s.tensor.frame_bytes == 8 * 1024 for s in entry.shards)

    read_sizes = []
    orig_read = FSStoragePlugin.read

    async def spy_read(self, read_io):
        await orig_read(self, read_io)
        read_sizes.append(read_io.buf.getbuffer().nbytes)

    FSStoragePlugin.read = spy_read
    try:
        got = Snapshot(path).read_object("0/s/x", memory_budget_bytes=16 * 1024)
    finally:
        FSStoragePlugin.read = orig_read
    assert np.array_equal(got, host)
    shard_files = [
        os.path.join(dirpath, f)
        for dirpath, _, files in os.walk(os.path.join(path, "sharded"))
        for f in files
        if not f.endswith(".ftab")
    ]
    shard_payload = min(os.path.getsize(f) for f in shard_files)
    data_reads = [s for s in read_sizes if s > 4 * 1024]
    assert data_reads and max(data_reads) < shard_payload, (
        read_sizes,
        shard_payload,
    )


def test_framed_whole_restore_no_table_needed(tmp_path) -> None:
    """Unbudgeted restores of framed entries decode the concatenated frames
    without touching the .ftab (it may even be lost)."""
    rng = np.random.default_rng(1)
    arr = rng.standard_normal(64 * 1024).astype(np.float32)
    path = str(tmp_path / "w")
    with knobs.override_compression("zstd"), knobs.override_compression_frame_bytes(32 * 1024):
        Snapshot.take(path, {"s": StateDict(a=arr)})
    os.remove(os.path.join(path, "0", "s", "a.ftab"))
    tgt = StateDict(a=np.zeros_like(arr))
    Snapshot(path).restore({"s": tgt})
    assert np.array_equal(tgt["a"], arr)


def test_framed_zlib_roundtrip(tmp_path) -> None:
    rng = np.random.default_rng(2)
    arr = rng.standard_normal(32 * 1024).astype(np.float32)
    path = str(tmp_path / "z")
    with knobs.override_compression("zlib"), knobs.override_compression_frame_bytes(16 * 1024):
        Snapshot.take(path, {"s": StateDict(a=arr)})
    got = Snapshot(path).read_object("0/s/a", memory_budget_bytes=16 * 1024)
    assert np.array_equal(got, arr)
    tgt = StateDict(a=np.zeros_like(arr))
    Snapshot(path).restore({"s": tgt})
    assert np.array_equal(tgt["a"], arr)


def test_codec_versions_recorded_in_metadata(tmp_path) -> None:
    path = str(tmp_path / "v")
    with knobs.override_compression("zstd"):
        Snapshot.take(path, {"s": StateDict(a=np.arange(8, dtype=np.float32))})
    versions = Snapshot(path).metadata.codec_versions
    assert versions and "zstd" in versions


def test_compression_composes_with_incremental_dedup(tmp_path) -> None:
    """Byte-identical compressed objects dedup against a base snapshot
    (zstd is deterministic for a fixed level/version)."""
    frozen = {f"b{i}": np.arange(2000, dtype=np.float32) + i for i in range(3)}

    def app(step):
        return {"m": StateDict(**frozen, head=np.full((10,), step, np.float32))}

    s0 = str(tmp_path / "s0")
    s1 = str(tmp_path / "s1")
    with knobs.override_compression("zstd"):
        Snapshot.take(s0, app(0))
        Snapshot.take(s1, app(1), base=s0)
    # Hard links: deduped objects share inodes with the base.
    import os as _os

    linked = 0
    for i in range(3):
        a = _os.path.join(s0, "0", "m", f"b{i}")
        b = _os.path.join(s1, "0", "m", f"b{i}")
        if _os.path.exists(a) and _os.path.exists(b) and _os.path.samefile(a, b):
            linked += 1
    assert linked == 3
    tgt = StateDict(**{k: np.zeros(2000, np.float32) for k in frozen}, head=np.zeros(10, np.float32))
    Snapshot(s1).restore({"m": tgt})
    assert np.array_equal(tgt["head"], np.full((10,), 1, np.float32))


def test_exotic_dtypes_compress(tmp_path) -> None:
    arrays = {d: rand_array((32, 8), d, seed=1) for d in ("bfloat16", "float8_e4m3fn", "int4", "uint16")}
    path = str(tmp_path / "d")
    with knobs.override_compression("zstd"):
        Snapshot.take(path, {"s": StateDict(**arrays)})
    tgt = StateDict(**{k: np.zeros_like(v) for k, v in arrays.items()})
    Snapshot(path).restore({"s": tgt})
    for k, v in arrays.items():
        assert tgt[k].view(np.uint8).tobytes() == v.view(np.uint8).tobytes(), k


def test_invalid_codec_rejected() -> None:
    with knobs._override_env(knobs._ENV_COMPRESSION, "lz77"):
        with pytest.raises(ValueError, match="lz77"):
            knobs.get_compression()


def test_missing_zstandard_fails_fast(monkeypatch) -> None:
    """A zstd knob without the zstandard package must fail at knob-read
    (take time), not ModuleNotFoundError in the background drain."""
    import builtins

    real_import = builtins.__import__

    def no_zstd(name, *args, **kwargs):
        if name == "zstandard":
            raise ImportError(name)
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_zstd)
    with knobs.override_compression("zstd"):
        with pytest.raises(RuntimeError, match="zstandard"):
            knobs.get_compression()


def test_compression_level_validated_per_codec() -> None:
    with knobs.override_compression("zlib"), knobs.override_compression_level(12):
        with pytest.raises(ValueError, match="out of range"):
            knobs.get_compression()
    with knobs.override_compression("zstd"), knobs.override_compression_level(12):
        assert knobs.get_compression() == "zstd"
        assert knobs.get_compression_level() == 12
    # Stale level env with compression off never raises — numeric or not.
    with knobs.override_compression("none"), knobs.override_compression_level(99):
        assert knobs.get_compression() == "none"
    with knobs.override_compression("none"), knobs._override_env(
        knobs._ENV_COMPRESSION_LEVEL, "fast"
    ):
        assert knobs.get_compression() == "none"
        assert knobs.get_compression_level() == 1


def test_compressed_staging_costs_account_double() -> None:
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer, entry_cost_bytes

    arr = np.zeros((256, 256), np.float32)  # 256 KiB raw
    with knobs.override_compression("zstd"):
        entry, reqs = ArrayIOPreparer.prepare_write("p", arr)
    assert entry.serializer == Serializer.RAW_ZSTD
    assert reqs[0].buffer_stager.get_staging_cost_bytes() == 2 * arr.nbytes
    assert entry_cost_bytes(entry) == 2 * arr.nbytes
    entry_plain, reqs_plain = ArrayIOPreparer.prepare_write("p", arr)
    assert reqs_plain[0].buffer_stager.get_staging_cost_bytes() == arr.nbytes


def test_stage_level_keyed_by_entry_not_env(tmp_path) -> None:
    """An entry recorded under one codec compresses correctly even if the
    env codec/level changed before its (deferred) staging ran."""
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer

    arr = np.arange(1024, dtype=np.float32)
    with knobs.override_compression("zstd"), knobs.override_compression_level(15):
        entry, reqs = ArrayIOPreparer.prepare_write("p", arr)
    assert entry.serializer == Serializer.RAW_ZSTD
    assert reqs[0].buffer_stager.compression_level == 15
    # Env now says zlib (level 15 would be invalid for it) — staging must
    # use the codec and level captured at prepare time.
    import asyncio

    with knobs.override_compression("zlib"), knobs.override_compression_level(15):
        buf = asyncio.new_event_loop().run_until_complete(
            reqs[0].buffer_stager.stage_buffer()
        )
    from torchsnapshot_tpu.serialization import decode_raw_payload

    raw = decode_raw_payload(buf, Serializer.RAW_ZSTD)
    assert np.array_equal(np.frombuffer(raw, np.float32), arr)


def test_async_host_arrays_safe_to_mutate_after_compressed_take(tmp_path) -> None:
    """The RAW path defensively copies mutable host arrays for async takes;
    compressed payloads are consumed inside staging, so mutating the live
    array after async_take returns must not corrupt the snapshot."""
    live = np.arange(4096, dtype=np.float32)
    want = live.copy()
    path = str(tmp_path / "c")
    with knobs.override_compression("zstd"):
        pending = Snapshot.async_take(path, {"s": StateDict(a=live)})
        live += 1000.0  # mutate immediately after return
        pending.wait()
    tgt = StateDict(a=np.zeros(4096, np.float32))
    Snapshot(path).restore({"s": tgt})
    assert np.array_equal(tgt["a"], want)


def test_divergent_codec_across_ranks_fails_loudly(tmp_path) -> None:
    """A replicated entry's manifest copy on a non-writer rank must never
    lie about the writer's bytes: codec divergence across ranks aborts the
    take with a clear error instead of corrupting the manifest."""
    from torchsnapshot_tpu.test_utils import run_with_processes

    run_with_processes(
        _divergent_codec_worker, nproc=2, args=(str(tmp_path),), timeout_s=120
    )


def _divergent_codec_worker(rank, world_size, shared):
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs as _knobs

    from torchsnapshot_tpu.snapshot import CheckpointAbortedError

    codec = "zstd" if rank == 0 else "none"
    state = StateDict(w=np.arange(512, dtype=np.float32))
    with _knobs.override_compression(codec):
        # The contract since structured aborts: EVERY rank gets a
        # CheckpointAbortedError (the detecting rank's ValueError is its
        # detail, and its cause on that rank); nothing is committed.
        try:
            Snapshot.take(
                os.path.join(shared, "ckpt"), {"m": state}, replicated=["m/*"]
            )
        except CheckpointAbortedError as e:
            assert "TORCHSNAPSHOT_TPU_COMPRESSION" in str(e)
        else:
            raise AssertionError("divergent codecs did not fail the take")
    assert not os.path.exists(
        os.path.join(shared, "ckpt", ".snapshot_metadata")
    )


def test_restore_without_zstandard_fails_fast_at_planning(tmp_path, monkeypatch) -> None:
    """Restoring a zstd snapshot on a host lacking zstandard must raise an
    actionable error at read planning, not ImportError mid-pipeline."""
    path = str(tmp_path / "c")
    with knobs.override_compression("zstd"):
        Snapshot.take(path, {"s": StateDict(a=np.arange(64, dtype=np.float32))})

    import builtins

    real_import = builtins.__import__

    def no_zstd(name, *args, **kwargs):
        if name == "zstandard":
            raise ImportError(name)
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_zstd)
    with pytest.raises(RuntimeError, match="zstandard"):
        Snapshot(path).restore({"s": StateDict(a=np.zeros(64, np.float32))})


def test_compressed_sharded_reshard(tmp_path) -> None:
    """Elasticity composes with compression: a compressed sharded snapshot
    restores into different layouts (the two flagship features together).
    Shard subdivision on save is forced so restore scatters many compressed
    pieces per target shard."""
    mesh42 = Mesh(np.array(jax.devices()).reshape(4, 2), ("a", "b"))
    mesh8 = Mesh(np.array(jax.devices()).reshape(8), ("x",))
    host = np.random.default_rng(3).standard_normal((16, 16)).astype(np.float32)
    arr = jax.device_put(jnp.asarray(host), NamedSharding(mesh42, P("a", "b")))
    path = str(tmp_path / "c")
    with knobs.override_compression("zstd"), knobs.override_max_shard_size_bytes(96):
        Snapshot.take(path, {"s": StateDict(x=arr)})
    entry = Snapshot(path).get_manifest()["0/s/x"]
    assert all(s.tensor.serializer == Serializer.RAW_ZSTD for s in entry.shards)
    assert len(entry.shards) > 8  # subdivision happened
    for spec, mesh in [(P(None, "x"), mesh8), (P("b", "a"), mesh42), (P(), mesh8)]:
        live = jax.device_put(
            jnp.zeros((16, 16), jnp.float32), NamedSharding(mesh, spec)
        )
        tgt = StateDict(x=live)
        Snapshot(path).restore({"s": tgt})
        got = np.asarray(tgt["x"])
        assert got.view(np.uint8).tobytes() == host.view(np.uint8).tobytes(), spec


def test_frame_table_stager_fails_fast_when_payload_staging_fails(monkeypatch) -> None:
    """A framed payload's staging failure must unblock the companion .ftab
    stager promptly (RuntimeError), not leave it polling forever as an
    orphaned task."""
    import asyncio

    from torchsnapshot_tpu.io_preparers import array as array_mod
    from torchsnapshot_tpu.io_preparers.array import (
        ArrayBufferStager,
        FrameTableStager,
    )
    from torchsnapshot_tpu.manifest import ArrayEntry

    entry = ArrayEntry(
        location="p",
        serializer=Serializer.RAW_ZSTD,
        dtype="float32",
        shape=[1024],
        frame_bytes=512,
    )
    with knobs.override_compression("zstd"):
        main = ArrayBufferStager(np.arange(1024, dtype=np.float32), entry)
    ftab = FrameTableStager(main)

    def boom(*args, **kwargs):
        raise MemoryError("compressor OOM")

    monkeypatch.setattr(array_mod, "compress_framed", boom)

    async def go():
        ftab_task = asyncio.ensure_future(ftab.stage_buffer())
        with pytest.raises(MemoryError):
            await main.stage_buffer()
        with pytest.raises(RuntimeError, match="payload staging failed"):
            await asyncio.wait_for(ftab_task, timeout=5)

    asyncio.new_event_loop().run_until_complete(go())
