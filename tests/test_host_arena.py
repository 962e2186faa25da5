"""The restore's arena of host pages (``host_arena.py``).

A leaf bound for a device that copies on ``device_put`` is read into a view
of a bounded arena, taken when its first read is about to be fetched and
given back when its transfer has finished with the pages. Held here: the
arena alone (carving, reuse, the bound, who may be waited for, close), and
the restore through it. The CPU backend may alias a placed array with the
host pages it was put from, so there the arena must not engage; the tests
that want it engaged stand in a place that copies and say the devices copy.
CPU runs: counts, addresses and bits only, never a rate.
"""

import asyncio
import contextlib
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, host_arena, native
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.host_arena import PAGE_BYTES, HostArena
from torchsnapshot_tpu.storage_plugins import cloud_retry, fs as fs_mod
from torchsnapshot_tpu.utils import knobs

KIB = 1024


def _addr(arr) -> int:
    return np.frombuffer(memoryview(arr), dtype=np.uint8).ctypes.data


def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def _take(arena, *sizes, reads=1):
    """A lease whose reads have all begun, with its views (or None)."""
    lease = arena.lease(sizes, reads)

    async def begin():
        for _ in range(reads):
            await lease.acquire()

    _run(begin())
    return lease


# ---------------------------------------------------------------------------
# The arena alone
# ---------------------------------------------------------------------------


def test_nothing_is_allocated_before_a_lease_takes_room() -> None:
    arena = HostArena(64 * KIB)
    assert not arena.allocated and arena.in_use_bytes == 0
    arena.lease([4 * KIB], 1)
    assert not arena.allocated
    _take(arena, 4 * KIB)
    assert arena.allocated


@pytest.mark.parametrize("sizes", [[4 * KIB], [1], [5000, 3, 12 * KIB], [PAGE_BYTES] * 4])
def test_views_are_page_aligned_apart_and_of_the_sizes_asked(sizes) -> None:
    arena = HostArena(256 * KIB)
    lease = _take(arena, *sizes)
    assert [v.nbytes for v in lease.views] == sizes
    spans = sorted((_addr(v), _addr(v) + v.nbytes) for v in lease.views)
    assert all(a % PAGE_BYTES == 0 for a, _ in spans)
    assert all(b <= c for (_, b), (c, _) in zip(spans, spans[1:]))
    assert all(v.flags.writeable and v.dtype == np.uint8 for v in lease.views)
    assert arena.in_use_bytes == sum(-(-s // PAGE_BYTES) * PAGE_BYTES for s in sizes)


def test_a_block_given_back_is_carved_again_from_the_lowest_address() -> None:
    arena = HostArena(64 * KIB)
    a = _take(arena, 16 * KIB)
    b = _take(arena, 16 * KIB)
    base = _addr(a.views[0])
    assert _addr(b.views[0]) == base + 16 * KIB
    assert (a.recycled_bytes, b.recycled_bytes) == (0, 0)
    a.give_back()
    c = _take(arena, 8 * KIB)
    d = _take(arena, 8 * KIB)
    assert (_addr(c.views[0]), _addr(d.views[0])) == (base, base + 8 * KIB)
    assert (c.recycled_bytes, d.recycled_bytes) == (8 * KIB, 8 * KIB)
    assert arena.touched_bytes == 32 * KIB  # no fresh page was wanted


def test_recycled_bytes_count_only_pages_handed_out_before() -> None:
    arena = HostArena(64 * KIB)
    a = _take(arena, 8 * KIB)
    a.give_back()
    b = _take(arena, 20 * KIB)  # 8 KiB of it used before, 12 KiB new
    assert b.recycled_bytes == 8 * KIB and arena.touched_bytes == 20 * KIB
    b.give_back()
    c = _take(arena, 5000, 3000)  # second view starts on the third page
    assert c.recycled_bytes == 8000


def test_neighbours_given_back_merge_into_one_block() -> None:
    arena = HostArena(48 * KIB)
    leases = [_take(arena, 16 * KIB) for _ in range(3)]
    for lease in (leases[0], leases[2], leases[1]):
        lease.give_back()
    whole = _take(arena, 48 * KIB)
    assert whole.views is not None and whole.recycled_bytes == 48 * KIB


@pytest.mark.parametrize("seed", range(4))
def test_largest_first_never_strands_a_view(seed) -> None:
    """Entries come largest first and go back in any order: whatever is
    given back fits the next entry, so once the arena has filled no later
    entry takes fresh pages, and the bound holds throughout."""
    rng = np.random.default_rng(seed)
    sizes = sorted((int(s) * KIB for s in rng.integers(1, 64, size=60)), reverse=True)
    arena = HostArena(128 * KIB)
    out, fresh_after_full, filled = [], 0, False
    for size in sizes:
        lease = arena.lease([size], 1)
        views = lease.take_nowait()
        while views is None and out:
            filled = True
            out.pop(int(rng.integers(len(out)))).give_back()
            lease = arena.lease([size], 1)
            views = lease.take_nowait()
        fresh_after_full += filled and views is None
        assert arena.in_use_bytes <= arena.capacity
        out.append(lease)
    assert filled and fresh_after_full == 0
    assert arena.in_use_hwm_bytes <= arena.capacity
    assert arena.touched_bytes <= arena.capacity


@pytest.mark.parametrize("sizes", [[65 * KIB], [40 * KIB, 40 * KIB], [0]])
def test_an_entry_the_arena_cannot_hold_takes_fresh_pages(sizes) -> None:
    arena = HostArena(64 * KIB)
    held = _take(arena, 16 * KIB)  # a full lease is out: still nothing to wait for
    lease = _take(arena, *sizes)
    assert lease.views is None and lease.recycled_bytes == 0
    assert arena.in_use_bytes == 16 * KIB
    lease.give_back()  # giving back nothing is nothing
    held.give_back()
    assert arena.in_use_bytes == 0


def test_a_view_is_not_handed_out_again_before_its_transfer_is_done() -> None:
    """``give_back_when``: the pages stay the lease's until the readiness
    handle returns, on the arena's own thread."""
    arena = HostArena(32 * KIB)
    first = _take(arena, 32 * KIB)
    done, settled_on = threading.Event(), []

    def ready():
        done.wait(30)
        settled_on.append(threading.current_thread().name)

    first.give_back_when(ready)
    second = arena.lease([32 * KIB], 1)
    got = []

    async def wait_for_room():
        got.append("waiting")
        asyncio.get_running_loop().call_later(0.2, done.set)
        views = await second.acquire()
        got.append(views)

    assert arena.in_use_bytes == 32 * KIB
    _run(wait_for_room())
    assert got[0] == "waiting" and got[1] is not None
    assert settled_on == ["tss-host-arena"]
    assert second.recycled_bytes == 32 * KIB and arena.take_wait_s() >= 0.15
    arena.close()


def test_a_readiness_handle_that_raises_still_gives_the_view_back() -> None:
    arena = HostArena(32 * KIB)
    first = _take(arena, 32 * KIB)

    def ready():
        raise RuntimeError("transfer failed")

    first.give_back_when(ready)
    second = arena.lease([32 * KIB], 1)
    assert _run(asyncio.wait_for(second.acquire(), 30)) is not None
    arena.close()


def test_waiters_are_served_oldest_first() -> None:
    arena = HostArena(32 * KIB)
    held = _take(arena, 32 * KIB)
    order = []

    async def main():
        async def want(name, size):
            lease = arena.lease([size], 1)
            await lease.acquire()
            order.append(name)
            await asyncio.sleep(0.01)
            lease.give_back()

        tasks = [asyncio.ensure_future(want(n, s)) for n, s in (("a", 32 * KIB), ("b", 8 * KIB))]
        await asyncio.sleep(0.05)
        assert order == []  # both wait: the lease that is out needs nothing more
        held.give_back()
        await asyncio.wait_for(asyncio.gather(*tasks), 30)

    _run(main())
    assert order == ["a", "b"]
    assert arena.in_use_bytes == 0


def test_an_entry_whose_other_reads_have_not_begun_is_never_waited_for() -> None:
    """The deadlock rule: the only lease that is out has a read that may
    queue behind the one that asks, so the one that asks takes fresh pages
    now; once that read has begun, the next that asks may wait."""
    arena = HostArena(32 * KIB)
    partial = arena.lease([16 * KIB, 16 * KIB], reads=2)

    async def main():
        assert await partial.acquire() is not None  # its first read begins
        assert not partial.full
        blocked = arena.lease([8 * KIB], 1)
        assert await asyncio.wait_for(blocked.acquire(), 5) is None
        await partial.acquire()  # its second read begins
        assert partial.full
        waiter = arena.lease([8 * KIB], 1)
        task = asyncio.ensure_future(waiter.acquire())
        await asyncio.sleep(0.05)
        assert not task.done()
        partial.give_back()
        assert await asyncio.wait_for(task, 5) is not None

    _run(main())


def test_reads_of_one_entry_share_one_wait() -> None:
    arena = HostArena(32 * KIB)
    held = _take(arena, 32 * KIB)
    entry = arena.lease([8 * KIB, 8 * KIB], reads=2)

    async def main():
        reads = [asyncio.ensure_future(entry.acquire()) for _ in range(2)]
        await asyncio.sleep(0.05)
        assert not any(r.done() for r in reads)
        held.give_back()
        a, b = await asyncio.wait_for(asyncio.gather(*reads), 5)
        assert a is b and len(a) == 2

    _run(main())
    assert entry.full and arena.in_use_bytes == 16 * KIB


@pytest.mark.parametrize("how", ["close", "exception"])
def test_everything_is_dropped_on_close_and_on_an_exception(how) -> None:
    """A closed arena keeps no page of its own, wakes whoever waited (with
    fresh pages), serves nobody, and a view still out stays whole until its
    holder lets go."""
    arena = HostArena(32 * KIB)
    held = _take(arena, 32 * KIB)
    view = held.views[0]
    view[:] = 7
    waiter = arena.lease([8 * KIB], 1)

    async def main():
        task = asyncio.ensure_future(waiter.acquire())
        await asyncio.sleep(0.05)
        assert not task.done()
        if how == "close":
            arena.close()
        else:
            with pytest.raises(ZeroDivisionError), contextlib.closing(arena):
                1 / 0
        assert await asyncio.wait_for(task, 5) is None

    _run(main())
    assert not arena.allocated and arena.in_use_bytes == 0
    assert _take(arena, 4 * KIB).views is None
    assert view.min() == 7 == view.max()
    held.give_back()  # after close: nothing to give back to
    assert arena.in_use_bytes == 0


@pytest.mark.parametrize(
    "platforms, copies",
    [
        (["tpu"], True),
        (["tpu"] * 4, True),
        (["gpu"], True),
        (["cpu"], False),
        (["tpu", "cpu"], False),
        (["weird"], False),
        ([], False),
    ],
)
def test_only_accelerators_are_said_to_copy(platforms, copies) -> None:
    class Device:
        def __init__(self, platform):
            self.platform = platform

    assert host_arena.copies_on_put([Device(p) for p in platforms]) is copies


def test_the_cpu_backends_devices_are_not_said_to_copy() -> None:
    import jax

    assert not host_arena.copies_on_put(jax.devices())
    assert not host_arena.copies_on_put(jax.device_put(np.zeros(4)).sharding.device_set)


# ---------------------------------------------------------------------------
# Touched while the restore plans
# ---------------------------------------------------------------------------


def _touchers():
    return [t for t in threading.enumerate() if t.name.startswith("tss-host-arena-touch")]


def _let_the_touchers_finish(arena, timeout=30.0) -> None:
    """Until every byte wanted so far has been touched (they then wait for
    more to be wanted, or for the end)."""
    deadline = time.monotonic() + timeout
    while arena._touch.done < arena._touch.wanted:
        assert time.monotonic() < deadline, "the touchers never caught up"
        time.sleep(0.001)


@pytest.fixture
def held_touchers(monkeypatch):
    """Touchers that stand at the engine's door until they are told to
    stop, as ones that have not come round to their first stripe do; when
    each was let in, with the flag up, is on record."""
    real, calls = native.touch_stripes, []

    def touch(lib, address, state, stripe_bytes, page_bytes):
        while not state.stop:
            time.sleep(0.001)
        calls.append(time.monotonic())
        return real(lib, address, state, stripe_bytes, page_bytes)

    monkeypatch.setattr(native, "touch_stripes", touch)
    return calls


def test_pretouched_pages_count_as_used_and_are_handed_out_first() -> None:
    arena = HostArena(256 * KIB)
    arena.lease([16 * KIB], 1)
    arena.pretouch(64 * KIB)
    assert arena.allocated and len(_touchers()) == host_arena.PRETOUCH_THREADS
    _let_the_touchers_finish(arena)
    first = _take(arena, 16 * KIB)
    assert not _touchers()
    assert arena.pretouched_bytes == 64 * KIB == arena.touched_bytes
    assert _addr(first.views[0]) == arena._mem.ctypes.data and first.recycled_bytes == 16 * KIB
    second = _take(arena, 64 * KIB)  # 48 KiB of it touched beforehand
    assert _addr(second.views[0]) == _addr(first.views[0]) + 16 * KIB
    assert second.recycled_bytes == 48 * KIB and arena.touched_bytes == 80 * KIB
    assert arena.pretouch_s > 0 and 0 <= arena.pretouch_stop_wait_s <= arena.pretouch_s
    arena.close()


@pytest.mark.parametrize("asked, wanted", [([8 * KIB], 8 * KIB), ([8 * KIB, 12 * KIB], 20 * KIB), ([48 * KIB] * 3, 64 * KIB)])
def test_no_more_is_touched_than_planned_leases_will_use(asked, wanted) -> None:
    arena = HostArena(64 * KIB)
    for nbytes in asked:
        arena.pretouch(nbytes)
    arena.pretouch(65 * KIB)  # a lease the arena cannot hold takes pages of its own
    arena.pretouch(0)
    _let_the_touchers_finish(arena)
    assert arena._touch.wanted == wanted == arena._touch.claimed
    lease = _take(arena, 4 * KIB)
    assert arena.pretouched_bytes == wanted and lease.recycled_bytes == 4 * KIB
    arena.pretouch(32 * KIB)  # a lease holds a view: never again
    assert not _touchers() and arena._touch.wanted == wanted
    arena.close()


@pytest.mark.parametrize("mib", [1, 8, 32])
def test_a_lease_taken_while_the_touchers_run_is_never_written_by_one(mib) -> None:
    """The invariant, against the engine itself: the lease takes the whole
    of what is being touched the moment the touchers have begun, fills it,
    and nothing but the filling is found there afterwards."""
    arena = HostArena(mib * 1024 * KIB)
    arena.pretouch(arena.capacity)
    lease = _take(arena, arena.capacity)
    assert not _touchers()
    (view,) = lease.views
    view[:] = 0xA5
    time.sleep(0.05)
    assert arena.pretouched_bytes <= arena.capacity and lease.recycled_bytes == arena.pretouched_bytes
    assert view.min() == 0xA5 == view.max()
    arena.close()
    assert view.min() == 0xA5 == view.max()


def test_touchers_are_gone_before_the_first_view_and_never_come_back(held_touchers) -> None:
    arena = HostArena(64 * 1024 * KIB)
    arena.pretouch(arena.capacity)
    assert len(_touchers()) == host_arena.PRETOUCH_THREADS and not held_touchers
    lease = _take(arena, 40 * 1024 * KIB)
    taken_at = time.monotonic()
    assert not _touchers()
    lease.views[0][:] = 0x5A
    # Each was let into the engine only with the flag up, and touched nothing.
    assert len(held_touchers) == host_arena.PRETOUCH_THREADS
    assert all(at <= taken_at for at in held_touchers)
    assert arena.pretouched_bytes == 0 and lease.recycled_bytes == 0
    arena.pretouch(8 * 1024 * KIB)
    assert not _touchers()
    arena.close()
    assert lease.views[0].min() == 0x5A == lease.views[0].max()


def test_close_while_touching_joins_before_the_memory_goes(held_touchers) -> None:
    arena = HostArena(64 * 1024 * KIB)
    arena.pretouch(arena.capacity)
    assert _touchers() and arena.allocated
    arena.close()
    closed_at = time.monotonic()
    assert not _touchers() and not arena.allocated
    assert len(held_touchers) == host_arena.PRETOUCH_THREADS
    assert all(at <= closed_at for at in held_touchers)
    arena.pretouch(8 * KIB)  # closed: serves nobody
    assert not _touchers() and not arena.allocated


@pytest.mark.parametrize("mib", [16, 64])
def test_close_in_the_middle_of_the_touching_leaves_no_toucher_in_the_memory(mib) -> None:
    """Against the engine itself: closed as soon as the first stripes are
    out, the touchers inside them."""
    arena = HostArena(mib * 1024 * KIB)
    arena.pretouch(arena.capacity)
    deadline = time.monotonic() + 30
    while not arena._touch.claimed:
        assert time.monotonic() < deadline, "no toucher ever claimed a stripe"
        time.sleep(0)
    arena.close()
    assert not _touchers() and not arena.allocated
    assert arena.pretouched_bytes <= arena._touch.claimed <= arena.capacity
    assert arena.pretouched_bytes % PAGE_BYTES == 0


def test_without_the_engine_nothing_is_touched_beforehand(monkeypatch) -> None:
    monkeypatch.setattr(native, "load_native_nonblocking", lambda: None)
    arena = HostArena(64 * KIB)
    arena.pretouch(16 * KIB)
    assert not arena.allocated and not _touchers()
    lease = _take(arena, 16 * KIB)
    assert lease.recycled_bytes == 0 and arena.pretouched_bytes == 0 and arena.pretouch_s == 0
    arena.close()


# ---------------------------------------------------------------------------
# The restore through it
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def native_reads_of_small_leaves(monkeypatch):
    """Test-sized leaves take the native route, several chunks a read."""
    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    monkeypatch.setattr(fs_mod, "_READ_CHUNK_BYTES", 16384)
    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_restore_overlap(True):
        yield


@pytest.fixture
def arenas(monkeypatch):
    """Every arena a restore makes, whatever became of it."""
    made = []
    real = HostArena.__init__

    def spy(self, *args, **kwargs):
        real(self, *args, **kwargs)
        made.append(self)

    monkeypatch.setattr(HostArena, "__init__", spy)
    return made


@pytest.fixture
def devices_that_copy(monkeypatch, arenas):
    """The devices are said to copy on put, and the place does: a stand-in
    for an accelerator, whose placed array never shares the host pages.
    Each page a view is given back with is scribbled over first, so a leaf
    that did share them would not compare."""
    import jax

    real_put, real_assemble = jax.device_put, snapshot_mod.assemble_jax_array

    def put(x, *args, **kwargs):
        return real_put(np.array(x, copy=True) if isinstance(x, np.ndarray) else x, *args, **kwargs)

    def assemble(sharding, shape, buffers):
        copies = {k: (np.array(b, copy=True), o, s) for k, (b, o, s) in buffers.items()}
        return real_assemble(sharding, shape, copies)

    real_give_back = HostArena._give_back

    def give_back(self, lease):
        for view in lease.views or ():
            view[:] = 0xA5
        real_give_back(self, lease)

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(snapshot_mod, "assemble_jax_array", assemble)
    monkeypatch.setattr(host_arena, "copies_on_put", lambda devices: True)
    monkeypatch.setattr(HostArena, "_give_back", give_back)
    return arenas


def _any_bits(dtype, shape, seed: int) -> np.ndarray:
    dtype = np.dtype(dtype)
    raw = np.random.default_rng(seed).integers(
        0, 256, size=int(np.prod(shape)) * dtype.itemsize, dtype=np.uint8
    )
    return raw.view(dtype).reshape(shape)


def _bits(x) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)


def _mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))


def _put(host, spec=None):
    import jax
    from jax.sharding import NamedSharding

    if spec is None:
        return jax.device_put(host)
    return jax.device_put(host, NamedSharding(_mesh(), spec))


def _state(n: int = 10):
    """Replicated single leaves and leaves of four shards, largest first
    and not, float32 and bfloat16, and one host leaf."""
    import ml_dtypes  # noqa: F401
    from jax.sharding import PartitionSpec as P

    specs = [None, P("a", "b"), P("a", None), P(None, "b")]
    tree = {}
    for i in range(n):
        host = _any_bits(np.float32 if i % 3 else "bfloat16", (128, 512 - 32 * i), seed=100 + i)
        tree[f"w{i}"] = _put(host, specs[i % 4])
    tree["host"] = _any_bits(np.float32, (64, 64), seed=99)
    return tree


def _zero_targets(tree):
    import jax

    return {
        k: jax.device_put(np.zeros_like(np.asarray(v)), v.sharding) if isinstance(v, jax.Array) else None
        for k, v in tree.items()
    }


def _assert_restored(tgt, tree) -> None:
    for name, want in tree.items():
        assert np.array_equal(_bits(tgt[name]), _bits(want)), name


_KNOBS = {
    "as_is": [],
    # Every leaf over 64 KiB goes in byte-range pieces (and the arena, never
    # more than the budget, can hold none of those whole: fresh pages).
    "budget_split": [lambda: knobs.override_memory_budget_bytes(65536)],
    "verify_all": [lambda: knobs.override_verify_reads("all")],
    "slab_merged": [lambda: knobs.override_batching_enabled(True)],
    "phases": [lambda: knobs.override_restore_overlap(False)],
}


@pytest.mark.parametrize("capacity_kib", [96, 300, 4096])
@pytest.mark.parametrize("case", list(_KNOBS))
def test_a_restore_through_a_small_arena_is_exact_and_finishes(
    tmp_path, monkeypatch, devices_that_copy, case, capacity_kib
) -> None:
    """The capacity forced under two of the larger entries: multi-shard and
    budget-split entries, waits and fresh-page fallbacks, and every leaf
    bit for bit with the pages scribbled over as they go back."""
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", capacity_kib * KIB)
    tree = _state()
    url = str(tmp_path / "snap")
    with contextlib.ExitStack() as stack:
        if case == "slab_merged":
            stack.enter_context(knobs.override_batching_enabled(True))
        Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    with contextlib.ExitStack() as stack:
        for k in _KNOBS[case]:
            stack.enter_context(k())
        Snapshot(url).restore({"s": tgt})
    _assert_restored(tgt, tree)
    (arena,) = devices_that_copy
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert arena.in_use_hwm_bytes <= arena.capacity <= capacity_kib * KIB
    assert arena.touched_bytes <= arena.capacity
    device_bytes = sum(np.asarray(v).nbytes for k, v in tree.items() if k != "host")
    assert stats["recycled_bytes"] + stats["fresh_target_bytes"] == device_bytes + tree["host"].nbytes
    if case == "phases":
        # No finalizer runs before the pipeline's end, so no view would
        # come back: the arena is left alone.
        assert not arena.allocated and stats["recycled_bytes"] == 0
    elif case == "budget_split":
        # A piece a read, a leaf too large: no page comes back to be used
        # again, so nothing is recycled but what was touched beforehand.
        assert stats["recycled_bytes"] <= stats["pretouched_bytes"]
    elif case != "slab_merged" and capacity_kib < 4096:
        assert stats["recycled_bytes"] > 0
        # Every page the arena has used was some leaf's first touch or the
        # arena's own, while the restore planned.
        first_touched = stats["fresh_target_bytes"] + stats["pretouched_bytes"]
        assert first_touched >= arena.touched_bytes - 10 * PAGE_BYTES


def test_recycled_and_fresh_account_for_every_landed_byte(
    tmp_path, monkeypatch, devices_that_copy
) -> None:
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    tree = _state()
    del tree["host"]
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    Snapshot(url).restore({"s": tgt})
    _assert_restored(tgt, tree)
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["landed_bytes"] == stats["bytes_read"] == sum(np.asarray(v).nbytes for v in tree.values())
    assert stats["recycled_bytes"] + stats["fresh_target_bytes"] == stats["landed_bytes"]
    assert 0 < stats["recycled_bytes"] <= stats["landed_bytes"]
    # The arena's pages had their first touch from a leaf or, while the
    # restore planned, from the arena itself.
    assert stats["fresh_target_bytes"] + stats["pretouched_bytes"] > 0
    assert stats["target_wait_s"] >= 0.0


def test_a_restore_returns_with_no_lease_of_its_arena_waited_for(
    tmp_path, monkeypatch, devices_that_copy
) -> None:
    """After the restore the arena is closed and holds nothing; a second
    restore makes its own."""
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    tree = _state(6)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    for _ in range(2):
        tgt = StateDict(**_zero_targets(tree))
        Snapshot(url).restore({"s": tgt})
        _assert_restored(tgt, tree)
    first, second = devices_that_copy
    assert first is not second
    assert first._closed and second._closed and not first.allocated and not second.allocated


@pytest.mark.parametrize("verify", ["off", "all"])
def test_a_torn_chunk_read_into_a_view_is_retried_to_exact_bytes(
    tmp_path, monkeypatch, devices_that_copy, verify
) -> None:
    """``op=read_chunk``: a chunk of the largest leaf's native read fails
    inside the engine, once, the other chunks already in the view. The
    plugin's retry overwrites the view from its start."""
    from torchsnapshot_tpu import faults

    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    tree = _state(6)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    spec = "op=read_chunk,kind=transient,path=0/s/w0,times=1,chunk=3"
    with knobs.override_faults(spec), knobs.override_verify_reads(verify):
        Snapshot(url).restore({"s": tgt})
        (rule,) = faults._LOCAL_INJECTOR.plan.rules
    assert rule.injected == 1, "the torn chunk read never fired"
    _assert_restored(tgt, tree)
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["landed_bytes"] == stats["bytes_read"] and stats["recycled_bytes"] > 0


def test_a_failed_restore_leaves_no_arena_behind(tmp_path, monkeypatch, devices_that_copy) -> None:
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    tree = _state(6)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    real = snapshot_mod._place_over_target
    calls = []

    def failing(logical_path, live, place, times=None):
        calls.append(logical_path)
        if len(calls) == 3:
            raise RuntimeError("the third leaf cannot be placed")
        return real(logical_path, live, place, times)

    monkeypatch.setattr(snapshot_mod, "_place_over_target", failing)
    with pytest.raises(Exception, match="third leaf"):
        Snapshot(url).restore({"s": tgt})
    (arena,) = devices_that_copy
    assert arena._closed and not arena.allocated and arena.in_use_bytes == 0


def test_a_second_place_after_an_allocation_failure_uses_the_same_view(
    tmp_path, monkeypatch, devices_that_copy
) -> None:
    import jax

    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    host = _any_bits(np.float32, (128, 256), seed=7)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(w=_put(host))})
    tgt = StateDict(w=_put(np.zeros_like(host)))
    live = tgt["w"]
    put, seen = jax.device_put, []

    def oom_once(x, *args, **kwargs):
        if isinstance(x, np.ndarray) and x.nbytes == host.nbytes and args:
            seen.append(x.ctypes.data)
            if len(seen) == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", oom_once)
    Snapshot(url).restore({"s": tgt})
    assert np.array_equal(_bits(tgt["w"]), _bits(host))
    assert len(seen) == 2 and seen[0] == seen[1] and live.is_deleted()
    (arena,) = devices_that_copy
    assert snapshot_mod.LAST_RESTORE_STATS["targets_consumed"] == 1 and arena.touched_bytes > 0


@pytest.mark.parametrize("live", ["none", "ndarray"])
def test_a_numpy_target_is_memory_no_arena_owns(tmp_path, monkeypatch, devices_that_copy, live) -> None:
    """What the caller will see is never a view of the arena: no ``live``
    gets an array of its own, a live ``np.ndarray`` is filled in place."""
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    tree = {f"h{i}": _any_bits(np.float32, (128, 64 + i), seed=i) for i in range(4)}
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    given = {k: (np.zeros_like(v) if live == "ndarray" else None) for k, v in tree.items()}
    tgt = StateDict(**given)
    Snapshot(url).restore({"s": tgt})
    _assert_restored(tgt, tree)
    (arena,) = devices_that_copy
    assert not arena.allocated and arena.touched_bytes == 0
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["recycled_bytes"] == 0
    want_fresh = 0 if live == "ndarray" else sum(v.nbytes for v in tree.values())
    assert stats["fresh_target_bytes"] == want_fresh
    if live == "ndarray":
        assert all(tgt[k] is given[k] for k in tree)


@pytest.mark.parametrize("kind", ["device_leaf", "sharded_leaf", "host_leaf"])
def test_read_object_makes_no_arena(tmp_path, devices_that_copy, kind) -> None:
    from jax.sharding import PartitionSpec as P

    host = _any_bits(np.float32, (128, 96), seed=3)
    leaf = {"device_leaf": lambda: _put(host), "sharded_leaf": lambda: _put(host, P("a", "b")), "host_leaf": lambda: host}[kind]()
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(w=leaf)})
    got = Snapshot(url).read_object("0/s/w")
    assert np.array_equal(_bits(got), _bits(host))
    assert devices_that_copy == []


def test_on_the_cpu_platform_the_arena_does_not_engage(tmp_path, monkeypatch, arenas) -> None:
    """The CPU backend may hand back an array that shares the host pages it
    was put from: every target stays a fresh array of its own, and a leaf
    restored once is untouched by the restore after it."""
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 300 * KIB)
    tree = _state()
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    first = StateDict(**_zero_targets(tree))
    Snapshot(url).restore({"s": first})
    stats = dict(snapshot_mod.LAST_RESTORE_STATS)
    second = StateDict(**_zero_targets(tree))
    Snapshot(url).restore({"s": second})
    _assert_restored(first, tree)
    _assert_restored(second, tree)
    assert stats["recycled_bytes"] == 0 and stats["target_wait_s"] == 0
    assert stats["fresh_target_bytes"] == sum(np.asarray(v).nbytes for v in tree.values())
    assert len(arenas) == 2 and not any(a.allocated or a.touched_bytes for a in arenas)


def test_the_capacity_is_never_more_than_the_memory_budget(tmp_path, devices_that_copy) -> None:
    host = _any_bits(np.float32, (64, 64), seed=5)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(w=_put(host))})
    with knobs.override_memory_budget_bytes(32 * KIB):
        Snapshot(url).restore({"s": StateDict(w=_put(np.zeros_like(host)))})
    (arena,) = devices_that_copy
    assert arena.capacity == 32 * KIB
    assert host_arena.CAPACITY_BYTES <= 4 << 30


@pytest.fixture
def touchers_that_finish(monkeypatch):
    """A test-sized plan is over before a thread has started: the first
    lease lets the touchers finish what was wanted before it stops them."""
    real = HostArena._end_pretouch

    def end(self):
        if self._touchers:
            _let_the_touchers_finish(self)
        real(self)

    monkeypatch.setattr(HostArena, "_end_pretouch", end)


@pytest.mark.parametrize("capacity_kib", [300, 8192])
def test_a_restore_through_pretouched_pages_is_exact(
    tmp_path, monkeypatch, devices_that_copy, touchers_that_finish, capacity_kib
) -> None:
    """What the plan's leases will use is touched beforehand, no more, and
    counts as recycled; every leaf bit for bit."""
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", capacity_kib * KIB)
    real_lease, leases = HostArena.lease, []

    def lease(self, *args, **kwargs):
        leases.append(real_lease(self, *args, **kwargs))
        return leases[-1]

    monkeypatch.setattr(HostArena, "lease", lease)
    tree = _state()
    del tree["host"]
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    Snapshot(url).restore({"s": tgt})
    _assert_restored(tgt, tree)
    (arena,) = devices_that_copy
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert not _touchers() and arena._closed
    leased = sum(lease.nbytes for lease in leases)
    assert len(leases) == len(tree) and 0 < leased <= 8192 * KIB
    assert stats["pretouched_bytes"] == arena.pretouched_bytes == min(arena.capacity, leased)
    assert stats["recycled_bytes"] >= min(stats["pretouched_bytes"], stats["landed_bytes"]) - 40 * PAGE_BYTES
    assert stats["recycled_bytes"] + stats["fresh_target_bytes"] == stats["landed_bytes"] == stats["bytes_read"]
    assert arena.touched_bytes <= leased
    assert stats["pretouch_s"] > 0 and stats["pretouch_stop_wait_s"] >= 0


@pytest.mark.parametrize("fails_at", [1, 4])
def test_a_restore_that_fails_in_its_plan_leaves_no_toucher_behind(
    tmp_path, monkeypatch, devices_that_copy, held_touchers, fails_at
) -> None:
    monkeypatch.setattr(host_arena, "CAPACITY_BYTES", 8192 * KIB)
    tree = _state(6)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    real, planned = snapshot_mod._prepare_restore_one, []

    def failing(logical_path, *args, **kwargs):
        if len(planned) == fails_at:
            assert _touchers()  # leases were planned: the touchers are at it
            raise RuntimeError("the plan cannot go on")
        planned.append(logical_path)
        return real(logical_path, *args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "_prepare_restore_one", failing)
    with pytest.raises(Exception, match="plan cannot go on"):
        Snapshot(url).restore({"s": tgt})
    (arena,) = devices_that_copy
    assert arena._closed and not arena.allocated and not _touchers()
    assert arena.pretouched_bytes == 0 and arena.touched_bytes == 0


def test_on_the_cpu_platform_nothing_is_touched_beforehand(tmp_path, arenas) -> None:
    tree = _state(4)
    url = str(tmp_path / "snap")
    Snapshot.take(url, {"s": StateDict(**tree)})
    tgt = StateDict(**_zero_targets(tree))
    Snapshot(url).restore({"s": tgt})
    _assert_restored(tgt, tree)
    (arena,) = arenas
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert not arena.allocated and not _touchers()
    assert stats["pretouched_bytes"] == 0 == stats["pretouch_s"] == stats["pretouch_stop_wait_s"]
