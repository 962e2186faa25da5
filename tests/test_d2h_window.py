"""The lanes' hint window (``d2h.TransferLanes``): a transfer is hinted
(``copy_to_host_async``) only while the bytes hinted and not yet resolved on
its device stay under the window, in admission order, one bigger than the
window alone; nothing is hinted inside ``async_take``; an abort or a transfer
error with transfers waiting for room leaves the budget balanced and commits
nothing; the bits are untouched.
"""

import asyncio
import os
import threading

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, d2h
from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
from torchsnapshot_tpu.scheduler import _WritePipeline
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs


@pytest.fixture(autouse=True)
def _debug_ledger():
    with knobs.override_debug_ledger(True):
        yield


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class _Device:
    def __init__(self, id_: int) -> None:
        self.id = id_


class _World:
    """What the fake arrays saw: every hint in order, and the bytes hinted
    and not yet handed to the host, a device."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.hints = []
        self.ahead = {}
        self.ahead_at_hint = []

    def hwm(self) -> int:
        return max(after for _, _, after in self.ahead_at_hint)


class _FakeArray:
    """Counts ``copy_to_host_async`` and resolves (``np.asarray``) only once
    the test lets it."""

    def __init__(self, world: _World, name: str, nbytes: int, device: int = 0) -> None:
        self.world, self.name, self.nbytes = world, name, nbytes
        self._devices = {_Device(device)}
        self.device = device
        self.go = threading.Event()

    def devices(self):
        return self._devices

    def copy_to_host_async(self) -> None:
        w = self.world
        with w.lock:
            before = w.ahead.get(self.device, 0)
            w.ahead[self.device] = before + self.nbytes
            w.hints.append(self.name)
            w.ahead_at_hint.append((self.name, before, before + self.nbytes))

    def __array__(self, dtype=None, copy=None):
        assert self.name in self.world.hints, "resolved before it was hinted"
        assert self.go.wait(30), f"{self.name} was never let through"
        with self.world.lock:
            self.world.ahead[self.device] -= self.nbytes
        return np.zeros(1, dtype=np.uint8)


async def _settle() -> None:
    for _ in range(5):
        await asyncio.sleep(0)


@pytest.fixture
def window_100(monkeypatch):
    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", 100)


def test_window_bounds_bytes_ahead_in_admission_order_and_counts(window_100) -> None:
    """The fake never sees more than W hinted and unresolved, except the one
    leaf bigger than W, which goes alone; hints come in the order the
    transfers were started; the two counters read what the fake saw."""
    world = _World()
    sizes = [("a0", 60), ("a1", 60), ("a2", 30), ("big", 150), ("a4", 10), ("a5", 10)]
    arrs = {n: _FakeArray(world, n, b) for n, b in sizes}

    async def go():
        loop = asyncio.get_running_loop()
        lanes = d2h.TransferLanes(lanes=4)
        tasks = {
            n: asyncio.ensure_future(lanes.start(a, a.nbytes, loop))
            for n, a in arrs.items()
        }

        async def release(name):
            arrs[name].go.set()
            await asyncio.wait_for(tasks[name], 30)
            await _settle()

        await _settle()
        assert world.hints == ["a0"]  # a1 does not fit; a2 would, and waits its turn
        await release("a0")
        assert world.hints == ["a0", "a1", "a2"]
        assert lanes.hinted_ahead_hwm_bytes == 90
        await release("a1")
        assert world.hints == ["a0", "a1", "a2"]  # 30 ahead: big goes alone only
        await release("a2")
        assert world.hints == ["a0", "a1", "a2", "big"]
        await release("big")
        assert world.hints == [n for n, _ in sizes]
        await release("a4")
        await release("a5")
        lanes.shutdown()
        return lanes

    lanes = _run(go())
    for name, before, after in world.ahead_at_hint:
        assert after <= 100 or before == 0, (name, before, after)
    assert world.hwm() == 150 == lanes.hinted_ahead_hwm_bytes
    assert lanes.window_waits == 5  # all but a0
    assert all(w.ahead == 0 and not w.waiting for w in lanes._windows.values())


def test_each_device_has_its_own_window(window_100) -> None:
    """Transfers of two devices do not wait for each other: each device's
    first goes at once, its second waits for its own first."""
    world = _World()
    arrs = [
        _FakeArray(world, f"d{dev}.{i}", 60, device=dev) for i in range(2) for dev in range(2)
    ]

    async def go():
        loop = asyncio.get_running_loop()
        lanes = d2h.TransferLanes(lanes=4)
        tasks = [asyncio.ensure_future(lanes.start(a, a.nbytes, loop)) for a in arrs]
        await _settle()
        assert world.hints == ["d0.0", "d1.0"]
        arrs[1].go.set()  # device 1's first resolves: device 1's second goes
        await asyncio.wait_for(tasks[1], 30)
        await _settle()
        assert world.hints == ["d0.0", "d1.0", "d1.1"]
        for a in arrs:
            a.go.set()
        await asyncio.wait_for(asyncio.gather(*tasks), 30)
        lanes.shutdown()
        return lanes

    lanes = _run(go())
    assert world.hints == ["d0.0", "d1.0", "d1.1", "d0.1"]
    assert lanes.hinted_ahead_hwm_bytes == 60 and lanes.window_waits == 2
    assert sorted(lanes._windows) == [0, 1]


def test_cancelled_waiters_hand_their_room_on(window_100) -> None:
    """Transfers cancelled while they wait for room (an abort's sweep) leave
    the window empty, and one behind them still gets its turn."""
    world = _World()
    arrs = [_FakeArray(world, f"a{i}", 60) for i in range(4)]

    async def go():
        loop = asyncio.get_running_loop()
        lanes = d2h.TransferLanes(lanes=2)
        tasks = [asyncio.ensure_future(lanes.start(a, a.nbytes, loop)) for a in arrs]
        await _settle()
        tasks[1].cancel()
        tasks[2].cancel()
        await asyncio.gather(tasks[1], tasks[2], return_exceptions=True)
        arrs[0].go.set()
        arrs[3].go.set()
        await asyncio.wait_for(asyncio.gather(tasks[0], tasks[3]), 30)
        lanes.shutdown()
        return lanes

    lanes = _run(go())
    assert world.hints == ["a0", "a3"]
    assert all(w.ahead == 0 and not w.waiting for w in lanes._windows.values())


# ------------------------------------------------ through the write pipeline


def _leaves(count: int, rows: int = 256, cols: int = 256):
    import jax
    import jax.numpy as jnp

    arrs = [
        jax.random.normal(jax.random.PRNGKey(i), (rows, cols), jnp.float32)
        for i in range(count)
    ]
    jax.block_until_ready(arrs)
    return arrs


def _count_hints(monkeypatch, fail_at=None):
    """Every ``copy_to_host_async`` the library asks for, with the thread
    that asked; the ``fail_at``-th raises, as a transfer error would."""
    calls = []
    real = d2h.hint_copy_to_host

    def counting(arr):
        calls.append(threading.current_thread())
        if fail_at is not None and len(calls) == fail_at:
            raise RuntimeError("transfer exploded")
        real(arr)

    monkeypatch.setattr(d2h, "hint_copy_to_host", counting)
    return calls


def test_async_take_hints_nothing_before_it_returns(tmp_path, monkeypatch) -> None:
    """No transfer is asked for on the caller's thread: ``async_take``
    returns with none issued, and the drain's lanes issue them all under the
    window, which the take's telemetry says."""
    import jax.numpy as jnp

    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", 3 * 256 * 1024)
    calls = _count_hints(monkeypatch)
    leaves = _leaves(8)  # 256 KiB each
    state = {f"w{i}": a for i, a in enumerate(leaves)}
    path = str(tmp_path / "ck")
    pending = Snapshot.async_take(path, {"m": StateDict(**state)})
    pending.wait()
    assert len(calls) == len(leaves)
    assert threading.current_thread() not in calls
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["d2h.hinted_ahead_hwm_bytes"] == 3 * 256 * 1024
    assert metrics["d2h.window_waits"] == 5
    assert metrics["d2h.bytes"] == 8 * 256 * 1024
    target = StateDict(**{k: jnp.zeros_like(v) for k, v in state.items()})
    Snapshot(path).restore({"m": target})
    for k, v in state.items():
        assert np.asarray(target[k]).tobytes() == np.asarray(v).tobytes(), k


def test_round_trip_bit_exact_under_a_window_smaller_than_a_leaf(tmp_path, monkeypatch) -> None:
    """Leaves above and below the window, two dtypes, every bit pattern of
    bfloat16 among them: what comes back is what went in."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", 64 * 1024)
    bits = np.arange(1 << 16, dtype=np.uint16)
    state = {
        "every_bf16": jax.device_put(bits.view(jnp.bfloat16)),  # 128 KiB: above W
        "big": jax.random.normal(jax.random.PRNGKey(0), (512, 128), jnp.float32),
        "mid": jax.random.normal(jax.random.PRNGKey(1), (96, 64), jnp.bfloat16),
        "small": jnp.arange(7, dtype=jnp.int32),
    }
    path = str(tmp_path / "ck")
    Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["d2h.hinted_ahead_hwm_bytes"] == 512 * 128 * 4  # alone
    target = StateDict(**{k: jnp.zeros_like(v) for k, v in state.items()})
    Snapshot(path).restore({"m": target})
    for k, v in state.items():
        got, want = np.asarray(target[k]), np.asarray(v)
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), k
    assert Snapshot(path).verify() == {}


def test_sharded_leaf_keeps_one_window_a_device(tmp_path, monkeypatch) -> None:
    """A leaf sharded over four devices is four transfers, each under its
    own device's window: the most ahead on one device is one shard, though
    four shards are hinted at once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    shard_bytes = 64 * 256 * 4
    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", shard_bytes + 1)
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    state = {
        f"w{i}": jax.device_put(
            jax.random.normal(jax.random.PRNGKey(i), (256, 256), jnp.float32), sharding
        )
        for i in range(3)
    }
    path = str(tmp_path / "ck")
    Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["d2h.hinted_ahead_hwm_bytes"] == shard_bytes
    assert metrics["d2h.window_waits"] == 4 * 2  # a device: all but its first
    per_device = [v for k, v in metrics.items() if k.startswith("d2h.device_bytes.")]
    assert per_device == [3 * shard_bytes] * 4
    target = StateDict(**{k: jax.device_put(jnp.zeros_like(v), sharding) for k, v in state.items()})
    Snapshot(path).restore({"m": target})
    for k, v in state.items():
        assert np.asarray(target[k]).tobytes() == np.asarray(v).tobytes(), k


def _pipeline(storage, leaves):
    reqs = []
    for i, leaf in enumerate(leaves):
        _entry, leaf_reqs = ArrayIOPreparer.prepare_write(f"obj{i}", leaf)
        reqs.extend(leaf_reqs)
    return _WritePipeline(reqs, storage, memory_budget_bytes=10**9, rank=0)


def _drive(pipeline):
    async def go():
        await pipeline.run_until_staged()
        await asyncio.wait_for(pipeline.run_to_completion(), timeout=30)

    _run(go())


def test_abort_with_transfers_waiting_for_room_balances_the_budget(monkeypatch) -> None:
    """A storage write fails while six of eight admitted transfers still
    wait for room: the failure propagates, the waiters are cancelled, every
    debit is credited and the windows are empty."""

    class FailingWriteStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            raise OSError("write exploded")

    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", 2 * 256 * 1024)
    calls = _count_hints(monkeypatch)
    storage = FailingWriteStorage()
    pipeline = _pipeline(storage, _leaves(8))
    with pytest.raises(OSError, match="write exploded"):
        _drive(pipeline)
    assert len(calls) < 8  # the budget admitted all eight; the window did not
    assert not storage.objects
    assert pipeline.budget_balanced, (pipeline.budget.available, pipeline.budget.total)
    lanes = pipeline._staging_ctx.lanes
    assert all(w.ahead == 0 and not w.waiting for w in lanes._windows.values())


def test_transfer_error_with_transfers_waiting_for_room_commits_nothing(
    tmp_path, monkeypatch
) -> None:
    """The third transfer fails at its hint while five wait for room behind
    it: ``wait()`` raises, no metadata is committed, the budget balances."""
    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", 2 * 256 * 1024)
    calls = _count_hints(monkeypatch, fail_at=3)
    storage = MemoryStoragePlugin()
    pipeline = _pipeline(storage, _leaves(8))
    with pytest.raises(RuntimeError, match="transfer exploded"):
        _drive(pipeline)
    assert 3 <= len(calls) < 8
    assert ".checksums.0" not in storage.objects
    assert pipeline.budget_balanced, (pipeline.budget.available, pipeline.budget.total)

    calls.clear()
    state = {f"w{i}": a for i, a in enumerate(_leaves(8))}
    path = str(tmp_path / "ck")
    pending = Snapshot.async_take(path, {"m": StateDict(**state)})
    with pytest.raises(RuntimeError, match="transfer exploded"):
        pending.wait()
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))
