"""TCPStore / LocalStore / LinearBarrier unit tests
(reference model: ``tests/test_dist_store.py``)."""

import threading
import time

import pytest

from torchsnapshot_tpu.parallel.store import (
    BarrierError,
    LinearBarrier,
    LocalStore,
    TCPStore,
)


@pytest.fixture(params=["local", "tcp"])
def store(request):
    if request.param == "local":
        yield LocalStore()
    else:
        s = TCPStore("127.0.0.1", 0, is_server=True)
        yield s
        s.shutdown()


def test_set_get(store) -> None:
    store.set("k", b"v1")
    assert store.get("k", timeout_s=1) == b"v1"
    store.set("k", b"v2")
    assert store.get("k", timeout_s=1) == b"v2"
    assert store.try_get("nope") is None


def test_blocking_get(store) -> None:
    def delayed_set():
        time.sleep(0.2)
        store.set("later", b"x")

    threading.Thread(target=delayed_set).start()
    t0 = time.monotonic()
    assert store.get("later", timeout_s=5) == b"x"
    assert time.monotonic() - t0 >= 0.15


def test_get_timeout(store) -> None:
    with pytest.raises(TimeoutError):
        store.get("never", timeout_s=0.2)


def test_add(store) -> None:
    assert store.add("ctr", 1) == 1
    assert store.add("ctr", 2) == 3
    assert store.add("other", 5) == 5


def test_prefix(store) -> None:
    p1 = store.prefix("a")
    p2 = store.prefix("b")
    p1.set("k", b"1")
    p2.set("k", b"2")
    assert p1.get("k", timeout_s=1) == b"1"
    assert p2.get("k", timeout_s=1) == b"2"


def test_tcp_store_multiple_clients() -> None:
    server = TCPStore("127.0.0.1", 0, is_server=True)
    client = TCPStore("127.0.0.1", server.port, is_server=False)
    client.set("x", b"from-client")
    assert server.get("x", timeout_s=1) == b"from-client"
    server.shutdown()


def test_linear_barrier_happy_path() -> None:
    store = LocalStore()
    world = 3
    order = []

    def run(rank):
        b = LinearBarrier(store, "b1", rank, world)
        b.arrive(timeout_s=5)
        if rank == 0:
            order.append("critical")
        b.depart(timeout_s=5)
        order.append(f"done{rank}")

    threads = [threading.Thread(target=run, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert order[0] == "critical"
    assert len(order) == world + 1


def test_linear_barrier_error_propagation() -> None:
    store = LocalStore()
    world = 2
    results = {}

    def good(rank):
        b = LinearBarrier(store, "b2", rank, world)
        try:
            b.arrive(timeout_s=5)
            b.depart(timeout_s=5)
            results[rank] = "ok"
        except BarrierError as e:
            results[rank] = f"barrier-error: {e}"

    def bad(rank):
        b = LinearBarrier(store, "b2", rank, world)
        b.report_error(RuntimeError("boom"))
        results[rank] = "reported"

    t0 = threading.Thread(target=good, args=(0,))
    t1 = threading.Thread(target=bad, args=(1,))
    t0.start(), t1.start()
    t0.join(), t1.join()
    assert results[1] == "reported"
    assert "barrier-error" in results[0] and "boom" in results[0]


def test_linear_barrier_error_carries_rank_and_phase() -> None:
    """report_error(phase=...) reaches peers as a structured BarrierError:
    the failing rank and its take phase ride the store payload, so callers
    can raise a CheckpointAbortedError naming both."""
    store = LocalStore()
    world = 2
    caught = {}

    def good(rank):
        b = LinearBarrier(store, "b3", rank, world)
        try:
            b.arrive(timeout_s=5)
            b.depart(timeout_s=5)
        except BarrierError as e:
            caught[rank] = e

    def bad(rank):
        b = LinearBarrier(store, "b3", rank, world)
        b.report_error(RuntimeError("disk on fire"), phase="write")

    t0 = threading.Thread(target=good, args=(0,))
    t1 = threading.Thread(target=bad, args=(1,))
    t0.start(), t1.start()
    t0.join(), t1.join()
    e = caught[0]
    assert e.rank == 1 and e.phase == "write"
    assert "rank 1" in str(e) and "write" in str(e) and "disk on fire" in str(e)


def test_linear_barrier_legacy_error_payload_tolerated() -> None:
    """A (rank, msg) 2-tuple from a pre-phase-tagging writer still parses:
    mixed-version pods fail cleanly, not with an unpack crash."""
    import pickle

    store = LocalStore()
    b = LinearBarrier(store, "b-legacy", 0, 2)
    store.set("barrier/b-legacy/error", pickle.dumps((1, "old-style boom")))
    with pytest.raises(BarrierError, match="rank 1 failed: old-style boom"):
        b.arrive(timeout_s=5)


@pytest.mark.parametrize("death_point", ["before_arrive", "between_phases"])
def test_linear_barrier_rank_death_times_out_peers(death_point) -> None:
    """A rank that dies WITHOUT reporting — before arriving, or between
    arrive and depart (the preemption window: its data is durable but it
    never sees the commit) — must fail the surviving ranks with the barrier
    TimeoutError within the timeout, never hang them."""
    store = LocalStore()
    world = 2
    outcome = {}

    def survivor(rank):
        b = LinearBarrier(store, "b4", rank, world)
        t0 = time.monotonic()
        try:
            b.arrive(timeout_s=2)
            b.depart(timeout_s=2)
            outcome[rank] = "ok"
        except TimeoutError as e:
            outcome[rank] = ("timeout", time.monotonic() - t0, str(e))
        except BarrierError as e:
            outcome[rank] = ("barrier-error", time.monotonic() - t0, str(e))

    def doomed(rank):
        b = LinearBarrier(store, "b4", rank, world)
        if death_point == "between_phases":
            b.arrive(timeout_s=2)
        # ...and the thread simply exits: a SIGKILLed process writes
        # neither an error report nor its depart increment.

    t0 = threading.Thread(target=survivor, args=(0,))
    t1 = threading.Thread(target=doomed, args=(1,))
    t0.start(), t1.start()
    t0.join(), t1.join()
    kind, elapsed, msg = outcome[0]
    assert kind == "timeout", outcome
    assert "timed out" in msg
    # Prompt: bounded by (at most) the two phases' timeouts plus polling
    # slack, not a hang.
    assert elapsed < 10, elapsed


def _worker_jax_store_overwrites(rank, world_size, shared):
    import logging
    import os

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.parallel.coordinator import get_coordinator
    from torchsnapshot_tpu.parallel.store import JaxCoordinationStore
    from torchsnapshot_tpu.telemetry import fleet
    from torchsnapshot_tpu.utils import knobs

    store = JaxCoordinationStore(namespace="overwrite_test")
    key = f"k/{rank}"
    assert store.try_get(key) is None  # absent is None, not an error
    store.set(key, b"one")
    store.set(key, b"two")  # last writer wins, as on every other Store
    assert store.try_get(key) == b"two"
    assert store.get(key, 5.0) == b"two"

    # The fleet bus overwrites one beacon key per process on this store
    # (auto-on at world > 1): every publish after the first used to fail
    # with ALREADY_EXISTS and was swallowed fail-open.
    assert isinstance(get_coordinator().store, JaxCoordinationStore)
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logging.getLogger("torchsnapshot_tpu").addHandler(handler)
    with knobs.override_fleet_beacon_s(0.05):
        fleet.reset()
        for i in range(2):
            Snapshot.take(
                os.path.join(shared, f"ckpt{i}"),
                {"s": StateDict(w=np.full(1 << 16, rank, np.float32))},
            )
        bus = fleet.get_bus()
    assert bus is not None and bus.publishes >= 2, bus
    assert bus.publish_failures == 0
    assert not [
        r for r in records if "fleet beacon publish failed" in r.getMessage()
    ]


def test_jax_coordination_store_overwrites_and_beacons_keep_publishing(
    tmp_path,
) -> None:
    from torchsnapshot_tpu.test_utils import run_with_processes

    run_with_processes(
        _worker_jax_store_overwrites,
        nproc=2,
        init_jax_distributed=True,
        args=(str(tmp_path),),
    )
