"""A synchronous take's stage (PR 48): a big leaf the fork would cut is cut on
the device at its turn, by the fork's movers, under a bounded HBM window, and
its pieces are gathered into a view of the take's arena of host pages, given
back when hash and write are done with it. What is written is what the
whole-leaf path writes; ``async_take`` is untouched.
"""

import asyncio
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, d2h, device_programs, host_arena, prepare_cache
from torchsnapshot_tpu.host_arena import HostArena
from torchsnapshot_tpu.device_programs import piece_row_ranges
from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
from torchsnapshot_tpu.parallel.coordinator import get_coordinator
from torchsnapshot_tpu.scheduler import _WritePipeline
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs
from torchsnapshot_tpu.utils.lru import BoundedLRU

KIB = 1024
PIECE = 64 * KIB
WINDOW = 160 * KIB  # the drain's: two pieces and a half
SYNC_WINDOW = 256 * KIB  # a synchronous take's: four pieces
CUT_WINDOW = 1024 * KIB
ARENA = 768 * KIB


@pytest.fixture(autouse=True)
def _debug_ledger():
    with knobs.override_debug_ledger(True):
        yield


@pytest.fixture(autouse=True)
def _fresh_cache():
    prepare_cache.reset(get_coordinator())
    yield
    prepare_cache.reset(get_coordinator())


@pytest.fixture
def grain(monkeypatch):
    """Toy sizes in the proportions of the chip's: leaves of a few hundred
    KiB run the path the chip runs at tens and hundreds of MiB."""
    monkeypatch.setattr(d2h, "PIECE_BYTES", PIECE)
    monkeypatch.setattr(d2h, "PIECE_WINDOW_BYTES", WINDOW)
    monkeypatch.setattr(d2h, "SYNC_PIECE_WINDOW_BYTES", SYNC_WINDOW)
    monkeypatch.setattr(d2h, "CUT_WINDOW_BYTES", CUT_WINDOW)
    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", 4 * 1024 * KIB)
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", ARENA)


@pytest.fixture
def arenas(monkeypatch):
    """Every arena made, with the bytes it still lent when it was closed."""
    made = []
    real_init, real_close = HostArena.__init__, HostArena.close

    def init(self, *args, **kwargs):
        real_init(self, *args, **kwargs)
        self.lent_at_close = None
        made.append(self)

    def close(self):
        self.lent_at_close = self.in_use_bytes
        real_close(self)

    monkeypatch.setattr(HostArena, "__init__", init)
    monkeypatch.setattr(HostArena, "close", close)
    return made


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _bits(dtype: str, shape, seed: int = 0) -> np.ndarray:
    """Every pattern of the 8- and 16-bit types in turn, random words of
    float32: what a device program could rewrite."""
    import jax.numpy as jnp

    n = int(np.prod(shape))
    width = np.dtype(jnp.dtype(dtype)).itemsize * 8
    if width == 32:
        words = np.random.default_rng(seed).integers(0, 1 << 32, size=n, dtype=np.uint64).astype(np.uint32)
        words[:8] = [1, 0x007FFFFF, 0x7F800001, 0x7FC00001, 0xFFFFFFFF, 0x80000001, 0x7F800000, 0xFF800000]
    else:
        words = ((np.arange(n, dtype=np.uint64) + seed) % (1 << width)).astype(f"uint{width}")
    return words.view(jnp.dtype(dtype)).reshape(shape)


def _put(host: dict) -> dict:
    import jax

    return {k: jax.device_put(v) for k, v in host.items()}


def _metrics() -> dict:
    return Snapshot.last_telemetry.metrics.as_dict()


def _objects(path: str) -> dict:
    out = {}
    for root, _dirs, files in os.walk(path):
        for name in files:
            rel = os.path.relpath(os.path.join(root, name), path)
            if rel.startswith((".telemetry", ".journal")):
                continue
            with open(os.path.join(root, name), "rb") as f:
                out[rel] = f.read()
    return out


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.reshape(-1).view(np.uint8).tobytes() == b.reshape(-1).view(np.uint8).tobytes()


# on the tiling (the DMA cut), off it (the re-laying cut)
SHAPES = [(512, 256), (8, 64, 256), (16, 168, 116), (168, 704), (1001, 128)]
DTYPES = ["bfloat16", "float32", "int8", "uint16"]


# ------------------------------------------------- what is written, and counted


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_a_synchronous_take_writes_what_the_whole_leaf_path_writes(grain, tmp_path, monkeypatch, shape, dtype) -> None:
    """Object, checksum sidecar and manifest of a take whose big leaf was cut
    in the stage are byte for byte those of the take that moved it whole, and
    the counters read what the shape says."""
    host = {"w": _bits(dtype, shape), "small": np.arange(7, dtype=np.int32)}
    state = _put(host)
    cut = piece_row_ranges(shape, host["w"].dtype)
    assert cut is not None and cut.relaid == (shape in SHAPES[2:])
    cut_path, whole_path = str(tmp_path / "cut"), str(tmp_path / "whole")
    Snapshot.take(cut_path, {"m": StateDict(**state)})
    m = _metrics()
    nbytes = host["w"].nbytes
    assert m["stage.sync_cut_leaves"] == 1 and m["stage.sync_cut_bytes"] == nbytes
    assert m["stage.sync_cut_relaid_bytes"] == (nbytes if cut.relaid else 0)
    assert m["stage.sync_cut_refused"] == 0 and m["stage.host_relaid_bytes"] == 0
    assert m["d2h.pieces"] == len(cut.ranges) and m["d2h.pieced_bytes"] == nbytes
    assert m["stage.recycled_bytes"] + m["stage.fresh_bytes"] == nbytes
    assert m["d2h.bytes"] == sum(v.nbytes for v in host.values())  # once a byte
    assert "capture.forked_leaves" not in m

    monkeypatch.setattr(d2h, "PIECE_BYTES", 1 << 40)
    prepare_cache.reset(get_coordinator())
    Snapshot.take(whole_path, {"m": StateDict(**state)})
    whole = _metrics()
    assert whole["stage.sync_cut_leaves"] == whole["d2h.pieces"] == 0
    a, b = _objects(cut_path), _objects(whole_path)
    assert sorted(a) == sorted(b) and ".snapshot_metadata" in a
    assert any(rel.startswith(".checksums") for rel in a)
    for rel in a:
        assert a[rel] == b[rel], rel
    assert Snapshot(cut_path).verify() == {}
    assert _same_bits(Snapshot(cut_path).read_object("0/m/w"), host["w"])


@pytest.mark.parametrize(
    "shape, relaid",
    [((1024, 256), False), ((16, 64, 256), False), ((16, 128, 116), True), ((1408, 232), True)],
)
def test_every_bfloat16_pattern_survives_a_synchronous_take(grain, tmp_path, shape, relaid) -> None:
    """All 65,536 patterns (NaN payloads, denormals, -0), through the DMA cut
    and through the re-laying cut, taken and restored onto the device."""
    import jax
    import jax.numpy as jnp

    n = int(np.prod(shape))
    assert n % (1 << 16) == 0 or n > 1 << 16
    host = (np.arange(n, dtype=np.uint64) % (1 << 16)).astype(np.uint16).view(jnp.bfloat16).reshape(shape)
    assert len(np.unique(host.view(np.uint16))) == 1 << 16
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(w=jax.device_put(host))})
    m = _metrics()
    assert m["stage.sync_cut_leaves"] == 1
    assert (m["stage.sync_cut_relaid_bytes"] == host.nbytes) == relaid
    target = StateDict(w=jnp.zeros(shape, jnp.bfloat16))
    Snapshot(path).restore({"m": target})
    assert _same_bits(target["w"], host)
    assert _same_bits(Snapshot(path).read_object("0/m/w"), host)


# --------------------------------------------------------------- the lease's life


def _big_state(n: int = 6, seed: int = 0):
    """``n`` leaves of 256 KiB (four pieces each), half of them off the tiling."""
    host = {}
    for i in range(n):
        shape = (512, 256) if i % 2 == 0 else (16, 128, 64)
        host[f"w{i}"] = _bits("bfloat16", shape, seed=seed + i)
    return host


def test_a_take_larger_than_its_arena_recycles_and_lends_nothing_at_close(grain, tmp_path, arenas) -> None:
    host = _big_state(6)  # 1.5 MiB through 768 KiB
    state = _put(host)
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(**state)})
    (arena,) = arenas
    m = _metrics()
    total = sum(v.nbytes for v in host.values())
    assert m["stage.sync_cut_leaves"] == 6 and m["stage.sync_cut_bytes"] == total
    assert m["stage.recycled_bytes"] + m["stage.fresh_bytes"] == total
    # All but what the arena touched for the first time landed in used pages.
    assert m["stage.fresh_bytes"] == arena.touched_bytes <= arena.capacity == ARENA
    assert m["stage.recycled_bytes"] >= total - ARENA > 0
    assert arena.in_use_hwm_bytes <= ARENA
    assert arena.lent_at_close == 0 and not arena.allocated
    assert m["stage.sync_cut_hwm_bytes"] <= CUT_WINDOW
    for name, want in host.items():
        assert _same_bits(Snapshot(path).read_object(f"0/m/{name}"), want), name
    assert Snapshot(path).verify() == {}


def test_a_leaf_larger_than_the_arena_takes_fresh_pages(grain, tmp_path, arenas, monkeypatch) -> None:
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 128 * KIB)
    host = {"w": _bits("bfloat16", (512, 256)), "v": _bits("uint16", (16, 128, 64))}
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(**_put(host))})
    (arena,) = arenas
    m = _metrics()
    assert m["stage.sync_cut_leaves"] == 2 and m["stage.recycled_bytes"] == 0
    assert m["stage.fresh_bytes"] == sum(v.nbytes for v in host.values())
    assert not arena.touched_bytes and arena.lent_at_close == 0
    for name, want in host.items():
        assert _same_bits(Snapshot(path).read_object(f"0/m/{name}"), want), name


def test_a_take_of_small_leaves_makes_no_arena(grain, tmp_path, arenas) -> None:
    host = {"a": np.arange(100, dtype=np.float32), "b": _bits("bfloat16", (64, 256))}
    Snapshot.take(str(tmp_path / "ck"), {"m": StateDict(**_put(host))})
    assert arenas == []
    m = _metrics()
    assert m["stage.sync_cut_leaves"] == m["stage.recycled_bytes"] == m["stage.fresh_bytes"] == 0


class _Storage(MemoryStoragePlugin):
    """Writes that can be held, failed, and watched."""

    def __init__(self, fail_on=None, hold=None):
        super().__init__()
        self.fail_on, self.hold = fail_on, hold
        self.lent_during_write = []
        self.arena = None

    async def write(self, write_io):
        if self.arena is not None and self.arena():
            self.lent_during_write.append(self.arena().in_use_bytes)
        if self.hold is not None:
            await self.hold.wait()
        if self.fail_on is not None and self.fail_on in write_io.path:
            raise OSError(f"no space left for {write_io.path}")
        await super().write(write_io)


def _pipeline(host: dict, storage, synchronous: bool = True, budget: int = 1 << 30):
    reqs = []
    for name, arr in _put(host).items():
        _entry, r = ArrayIOPreparer.prepare_write(f"0/{name}", arr, whole_leaf=True)
        reqs.extend(r)
    return _WritePipeline(reqs, storage, budget, rank=0, synchronous=synchronous)


def test_a_view_is_held_until_hash_and_write_are_done_then_given_back(grain, arenas) -> None:
    host = _big_state(2)
    storage = _Storage()

    async def go():
        storage.hold = asyncio.Event()
        pipeline = _pipeline(host, storage)
        storage.arena = lambda: pipeline._arena
        await pipeline.run_until_staged()
        # Staged, the writes held: both leaves still hold their views.
        assert pipeline._arena.in_use_bytes == sum(v.nbytes for v in host.values())
        storage.hold.set()
        await pipeline.run_to_completion()
        return pipeline

    pipeline = _run(go())
    (arena,) = arenas
    assert storage.lent_during_write and min(storage.lent_during_write[:2]) >= 256 * KIB
    assert arena.lent_at_close == 0 and pipeline.budget_balanced
    for name, want in host.items():
        assert storage.objects[f"0/{name}"] == want.reshape(-1).view(np.uint8).tobytes()
    assert pipeline.pipeline_stats["stage_sync_cut_leaves"] == 2.0


def test_a_failing_write_gives_every_view_back(grain, arenas) -> None:
    host = _big_state(4)
    storage = _Storage(fail_on="w1")

    async def go():
        pipeline = _pipeline(host, storage)
        with pytest.raises(OSError, match="no space left"):
            await pipeline.run_until_staged()
            await pipeline.run_to_completion()
        return pipeline

    pipeline = _run(go())
    (arena,) = arenas
    assert arena.lent_at_close == 0 and pipeline.budget_balanced
    lanes = pipeline._staging_ctx.lanes
    assert all(w.ahead == 0 and not w.waiting for w in lanes._windows.values())
    assert all(w.ahead == 0 and not w.waiting for w in lanes._cut_windows.values())


def test_a_cancelled_take_gives_every_view_back(grain, arenas) -> None:
    """Cancelled with leaves gathered and held at their writes, and others
    waiting for room in the arena."""
    host = _big_state(6)
    storage = _Storage()

    async def go():
        storage.hold = asyncio.Event()
        pipeline = _pipeline(host, storage)
        task = asyncio.ensure_future(pipeline.run_until_staged())
        for _ in range(200):
            await asyncio.sleep(0.005)
            if pipeline._arena is not None and pipeline._arena._waiters:
                break
        assert pipeline._arena._waiters and pipeline._arena.in_use_bytes > 0
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        return pipeline

    pipeline = _run(go())
    (arena,) = arenas
    assert arena.lent_at_close == 0 and pipeline.budget_balanced
    lanes = pipeline._staging_ctx.lanes
    assert all(w.ahead == 0 and not w.waiting for w in lanes._cut_windows.values())


def test_a_failed_take_commits_nothing_and_the_next_is_whole(grain, tmp_path, arenas, monkeypatch) -> None:
    import torchsnapshot_tpu.storage_plugins.fs as fs_mod

    host = _big_state(4)
    state = _put(host)
    real = fs_mod.FSStoragePlugin.write
    calls = {"n": 0}

    async def failing(self, write_io):
        calls["n"] += 1
        if calls["n"] == 2:
            raise OSError("disk full")
        await real(self, write_io)

    monkeypatch.setattr(fs_mod.FSStoragePlugin, "write", failing)
    path = str(tmp_path / "ck")
    with pytest.raises(Exception):
        Snapshot.take(path, {"m": StateDict(**state)})
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))
    assert [a.lent_at_close for a in arenas] == [0]
    monkeypatch.setattr(fs_mod.FSStoragePlugin, "write", real)
    prepare_cache.reset(get_coordinator())
    again = str(tmp_path / "again")
    Snapshot.take(again, {"m": StateDict(**state)})
    assert [a.lent_at_close for a in arenas] == [0, 0]
    for name, want in host.items():
        assert _same_bits(Snapshot(again).read_object(f"0/m/{name}"), want), name


# ----------------------------------------------------- a device with no room


def test_a_cut_the_device_has_no_room_for_leaves_the_leaf_whole(grain, tmp_path, arenas, monkeypatch) -> None:
    """HBM nearly full (a preemption take): the cut's allocation fails, the
    leaf crosses whole as it did before, the take commits."""
    host = _big_state(3)
    real = device_programs.batch_copy_fn
    seen = []

    def no_room(shardings, cuts, cache=None):
        fn = real(shardings, cuts, cache)

        def call(xs):
            seen.append(xs[0].shape)
            if len(seen) != 2:  # the second leaf finds room
                raise RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer: out of memory")
            return fn(xs)

        return call

    monkeypatch.setattr(device_programs, "batch_copy_fn", no_room)
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(**_put(host))})
    m = _metrics()
    assert m["stage.sync_cut_refused"] == 2 and m["stage.sync_cut_leaves"] == 1
    assert m["stage.sync_cut_bytes"] == m["d2h.pieced_bytes"] == 256 * KIB
    assert m["d2h.bytes"] == sum(v.nbytes for v in host.values())
    assert [a.lent_at_close for a in arenas] == [0]
    assert not device_programs._dma_cut_refused and not device_programs._relay_cut_refused
    for name, want in host.items():
        assert _same_bits(Snapshot(path).read_object(f"0/m/{name}"), want), name
    assert Snapshot(path).verify() == {}


def test_a_program_that_runs_out_of_room_as_it_runs_leaves_the_leaf_whole(grain, tmp_path, arenas, monkeypatch) -> None:
    """The re-laying program's temporaries are allocated when it runs: the
    failure then surfaces at a piece's resolve, not at the dispatch."""
    host = {"w": _bits("bfloat16", (16, 128, 64)), "v": _bits("bfloat16", (512, 256), seed=3)}
    real = d2h.resolve_on_host
    failed = []

    def resolve(arr, into=None, times=None, path=""):
        if into is not None and path.endswith("/w") and not failed:
            failed.append(path)
            raise RuntimeError("RESOURCE_EXHAUSTED: ran out of memory in memory space hbm")
        return real(arr, into, times, path)

    monkeypatch.setattr(d2h, "resolve_on_host", resolve)
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(**_put(host))})
    m = _metrics()
    assert failed and m["stage.sync_cut_refused"] == 1 and m["stage.sync_cut_leaves"] == 1
    assert [a.lent_at_close for a in arenas] == [0]
    for name, want in host.items():
        assert _same_bits(Snapshot(path).read_object(f"0/m/{name}"), want), name


def test_a_refusal_by_the_kernel_compiler_in_the_stage_is_remembered(grain, tmp_path, monkeypatch, caplog) -> None:
    monkeypatch.setattr(device_programs, "_STAGE_CUTS", BoundedLRU())
    monkeypatch.setattr(device_programs, "_dma_cut_refused", False)
    monkeypatch.setattr(device_programs, "_relay_cut_refused", False)
    real = device_programs.batch_copy_fn

    def refusing(shardings, cuts, cache=None):
        if any(c is not None and c.relaid for c in cuts):
            raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: no such tiling")
        return real(shardings, cuts, cache)

    monkeypatch.setattr(device_programs, "batch_copy_fn", refusing)
    host = _big_state(4)
    path = str(tmp_path / "ck")
    with caplog.at_level("WARNING"):
        Snapshot.take(path, {"m": StateDict(**_put(host))})
    assert "re-laying cut was refused" in caplog.text
    assert device_programs._relay_cut_refused and not device_programs._dma_cut_refused
    m = _metrics()
    # The first odd leaf met the compiler; the second was never offered.
    assert m["stage.sync_cut_refused"] == 1 and m["stage.sync_cut_leaves"] == 2
    assert m["stage.sync_cut_relaid_bytes"] == 0
    for name, want in host.items():
        assert _same_bits(Snapshot(path).read_object(f"0/m/{name}"), want), name


# --------------------------------------------------------------- the windows


def test_the_cut_pieces_never_hold_more_hbm_than_the_window(grain, arenas, monkeypatch) -> None:
    """Leaves are cut while the uncrossed pieces of those before them stay
    under the window; one leaf bigger than the window goes alone."""
    monkeypatch.setattr(d2h, "CUT_WINDOW_BYTES", 600 * KIB)
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 8 * 1024 * KIB)
    host = _big_state(6)
    host["huge"] = _bits("float32", (1024, 256))  # 1 MiB: over the window
    storage = MemoryStoragePlugin()
    live = {"now": 0, "hwm": 0, "alone": None}
    real_cut = device_programs.cut_in_stage

    def cut(arr, c):
        live["now"] += arr.nbytes
        live["hwm"] = max(live["hwm"], live["now"])
        if arr.nbytes > 600 * KIB:
            live["alone"] = live["now"] == arr.nbytes
        return real_cut(arr, c)

    class Window(d2h._DeviceWindow):
        def done(self, nbytes):
            if self is pipeline._staging_ctx.lanes._cut_windows.get(0):
                live["now"] -= nbytes
            super().done(nbytes)

    monkeypatch.setattr(device_programs, "cut_in_stage", cut)
    monkeypatch.setattr(d2h, "_DeviceWindow", Window)
    pipeline = _pipeline(host, storage)

    async def go():
        await pipeline.run_until_staged()
        await pipeline.run_to_completion()

    _run(go())
    lanes = pipeline._staging_ctx.lanes
    assert live["alone"] is True and live["now"] == 0
    assert live["hwm"] == lanes.cut_hwm_bytes == 1024 * KIB  # the one that went alone
    window = lanes._cut_windows[0]
    assert window.waits > 0 and window.ahead == 0 and not window.waiting
    for name, want in host.items():
        assert storage.objects[f"0/{name}"] == want.reshape(-1).view(np.uint8).tobytes()


def test_a_synchronous_takes_pieces_cross_under_their_own_window(grain, tmp_path) -> None:
    host = _big_state(6)
    state = _put(host)
    Snapshot.take(str(tmp_path / "sync"), {"m": StateDict(**state)})
    sync = _metrics()
    assert WINDOW < sync["d2h.hinted_ahead_hwm_bytes"] <= SYNC_WINDOW
    Snapshot.async_take(str(tmp_path / "async"), {"m": StateDict(**state)}).wait()
    drain = _metrics()
    assert drain["d2h.hinted_ahead_hwm_bytes"] <= WINDOW
    assert drain["d2h.pieces"] == sync["d2h.pieces"] and drain["d2h.pieced_bytes"] == sync["d2h.pieced_bytes"]


# ----------------------------------------------------- who is left untouched


def test_async_take_never_touches_an_arena_and_forks_the_same_pieces(grain, tmp_path, arenas, monkeypatch) -> None:
    def no_cut(arr, cut):
        raise AssertionError("async_take cut a leaf in the stage")

    monkeypatch.setattr(device_programs, "cut_in_stage", no_cut)
    host = _big_state(6)
    state = _put(host)
    path = str(tmp_path / "ck")
    Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    m = _metrics()
    assert arenas == []
    total = sum(v.nbytes for v in host.values())
    assert m["d2h.pieced_bytes"] == total
    assert m["d2h.pieces"] == sum(len(piece_row_ranges(v.shape, v.dtype).ranges) for v in host.values())
    assert m["capture.fork_relaid_leaves"] == 3 and m["capture.forked_leaves"] == 6
    assert m["stage.sync_cut_leaves"] == m["stage.sync_cut_bytes"] == m["stage.recycled_bytes"] == 0
    assert m["stage.fresh_bytes"] == total and m["stage.target_wait_s"] == 0
    assert "stage.sync_cut_hwm_bytes" not in m or m["stage.sync_cut_hwm_bytes"] == 0
    for name, want in host.items():
        assert _same_bits(Snapshot(path).read_object(f"0/m/{name}"), want), name


@pytest.mark.parametrize("case", ["chunked", "compressed", "host", "sharded", "small_float", "bool"])
def test_leaves_a_synchronous_take_leaves_whole(grain, tmp_path, arenas, case) -> None:
    """A leaf chunked into several objects, a compressed entry, a host array,
    a sharded leaf, float16 and bool cross as they did."""
    import contextlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    stack = contextlib.ExitStack()
    host = _bits("float32", (512, 256))
    leaf = jax.device_put(host)
    if case == "chunked":
        stack.enter_context(knobs.override_max_chunk_size_bytes(128 * KIB))
    elif case == "compressed":
        stack.enter_context(knobs.override_compression("zlib"))
    elif case == "host":
        leaf = host
    elif case == "sharded":
        mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
        leaf = jax.device_put(host, NamedSharding(mesh, P("x")))
    elif case == "small_float":
        host = _bits("float16", (512, 256))
        leaf = jax.device_put(host)
    elif case == "bool":
        host = _bits("uint8", (1024, 256)) > 127
        leaf = jax.device_put(host)
    path = str(tmp_path / "ck")
    with stack:
        Snapshot.take(path, {"m": StateDict(w=leaf)})
        m = _metrics()
        assert arenas == []
        assert m["stage.sync_cut_leaves"] == m["stage.sync_cut_refused"] == m["d2h.pieces"] == 0
        target = StateDict(w=jnp.zeros(host.shape, host.dtype))
        Snapshot(path).restore({"m": target})
    assert _same_bits(target["w"], host)


def test_the_budget_high_water_is_no_higher_with_the_arena(grain, tmp_path, monkeypatch) -> None:
    host = _big_state(6)
    state = _put(host)
    with knobs.override_memory_budget_bytes(1024 * KIB):
        Snapshot.take(str(tmp_path / "cut"), {"m": StateDict(**state)})
        cut = _metrics()
        monkeypatch.setattr(d2h, "PIECE_BYTES", 1 << 40)
        prepare_cache.reset(get_coordinator())
        Snapshot.take(str(tmp_path / "whole"), {"m": StateDict(**state)})
        whole = _metrics()
    assert cut["stage.sync_cut_leaves"] == 6 and whole["stage.sync_cut_leaves"] == 0
    assert cut["scheduler.budget_hwm_bytes"] <= whole["scheduler.budget_hwm_bytes"] <= 1024 * KIB


def test_the_arena_is_never_more_than_the_memory_budget(grain, tmp_path, arenas) -> None:
    host = _big_state(4)
    with knobs.override_memory_budget_bytes(512 * KIB):
        Snapshot.take(str(tmp_path / "ck"), {"m": StateDict(**_put(host))})
    (arena,) = arenas
    assert arena.capacity == 512 * KIB and arena.in_use_hwm_bytes <= 512 * KIB
    assert arena.lent_at_close == 0


def test_a_second_take_through_the_prepared_cache_cuts_the_new_leaves(grain, tmp_path, arenas) -> None:
    first, second = _big_state(4, seed=0), _big_state(4, seed=11)
    Snapshot.take(str(tmp_path / "a"), {"m": StateDict(**_put(first))})
    Snapshot.take(str(tmp_path / "b"), {"m": StateDict(**_put(second))})
    assert _metrics()["stage.sync_cut_leaves"] == 4
    assert [a.lent_at_close for a in arenas] == [0, 0]
    for name, want in second.items():
        assert _same_bits(Snapshot(str(tmp_path / "b")).read_object(f"0/m/{name}"), want), name


# ------------------------------------------------------------ the arena itself


def test_a_lease_of_one_read_waits_for_any_view_that_is_out(grain) -> None:
    """The take's use of the arena: every lease is of one read, so every
    view that is out is worth waiting for, and a waiter gets the pages the
    moment they come back, counted as used before."""

    async def go():
        arena = HostArena(256 * KIB)
        a = arena.lease([192 * KIB], reads=1)
        assert (await a.acquire()) is not None and a.recycled_bytes == 0
        b = arena.lease([128 * KIB], reads=1)
        waiter = asyncio.ensure_future(b.acquire())
        await asyncio.sleep(0.01)
        assert not waiter.done()
        a.give_back()
        views = await asyncio.wait_for(waiter, 1.0)
        assert views is not None and b.recycled_bytes == 128 * KIB
        assert arena.take_wait_s() > 0
        too_big = arena.lease([512 * KIB], reads=1)
        assert (await too_big.acquire()) is None
        b.give_back()
        assert arena.in_use_bytes == 0
        arena.close()

    _run(go())


def test_a_waiter_that_gave_up_is_handed_no_block(grain) -> None:
    """A leaf cancelled while it waited for room gives its lease back; the
    pages that come back later go to the waiter behind it, not to it."""

    async def go():
        arena = HostArena(256 * KIB)
        a = arena.lease([256 * KIB], reads=1)
        await a.acquire()
        gone, behind = arena.lease([128 * KIB], reads=1), arena.lease([128 * KIB], reads=1)
        waiting = [asyncio.ensure_future(x.acquire()) for x in (gone, behind)]
        await asyncio.sleep(0.01)
        waiting[0].cancel()
        await asyncio.gather(waiting[0], return_exceptions=True)
        gone.give_back()
        a.give_back()
        assert (await asyncio.wait_for(waiting[1], 1.0)) is not None
        assert gone.views is None and arena.in_use_bytes == 128 * KIB
        behind.give_back()
        assert arena.in_use_bytes == 0 and not arena._waiters
        arena.close()

    _run(go())
