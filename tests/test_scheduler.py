"""Scheduler pipeline semantics: budget, pipelining, PendingIOWork
(reference model: ``tests/test_scheduler.py`` + ``rss`` benchmarks)."""

import asyncio
import json
import zlib

import pytest

from torchsnapshot_tpu import hashing
from torchsnapshot_tpu.io_types import (
    BufferConsumer,
    BufferStager,
    ReadReq,
    WriteIO,
    WriteReq,
)
from torchsnapshot_tpu.scheduler import (
    _WritePipeline,
    execute_read_reqs,
    execute_write_reqs,
    get_process_memory_budget_bytes,
)
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs


@pytest.fixture(autouse=True)
def _debug_ledger():
    """The whole scheduler suite runs under the budget-ledger sanitizer:
    every pipeline asserts zero outstanding bytes at close/abort, naming
    leaking sites — the runtime cross-check of the TSA6xx static pass."""
    with knobs.override_debug_ledger(True):
        yield


class TrackingStager(BufferStager):
    live = 0
    peak = 0

    def __init__(self, nbytes: int, fail: bool = False):
        self.nbytes = nbytes
        self.fail = fail

    def payload(self) -> bytes:
        return bytes(i % 251 for i in range(self.nbytes))

    async def stage_buffer(self, executor=None):
        TrackingStager.live += self.nbytes
        TrackingStager.peak = max(TrackingStager.peak, TrackingStager.live)
        await asyncio.sleep(0.01)
        if self.fail:
            raise RuntimeError("staging failure")
        return bytearray(self.payload())

    def get_staging_cost_bytes(self) -> int:
        return self.nbytes


class ReleasingStorage(MemoryStoragePlugin):
    """Credits TrackingStager.live as buffers are written out."""

    async def write(self, write_io: WriteIO) -> None:
        await asyncio.sleep(0.01)
        await super().write(write_io)
        TrackingStager.live -= memoryview(write_io.buf).nbytes


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _run_write(reqs, storage, budget):
    # complete() must run on the same loop that created the I/O tasks.
    async def go():
        pending = await execute_write_reqs(
            reqs, storage, memory_budget_bytes=budget, rank=0
        )
        await pending.complete()

    _run(go())


@pytest.mark.parametrize(
    "sizes,budget",
    [
        ([100] * 50, 300),
        # One leaf far above the others (the sizes the chunk stream used to
        # take): admitted at its full size, alone or beside the small ones.
        ([50 * 1024] + [1024] * 8, 10**9),
        ([50 * 1024] * 3 + [1024] * 8, 60 * 1024),
    ],
    ids=["many-small", "one-large", "large-over-budget"],
)
def test_write_budget_bounds_staged_bytes(sizes, budget) -> None:
    TrackingStager.live = TrackingStager.peak = 0
    stagers = {f"p{i}": TrackingStager(n) for i, n in enumerate(sizes)}
    reqs = [WriteReq(path, stager) for path, stager in stagers.items()]
    storage = ReleasingStorage()

    async def go():
        pending = await execute_write_reqs(
            reqs, storage, memory_budget_bytes=budget, rank=0
        )
        await pending.complete()
        return pending

    with knobs.override_hash_chunk_bytes(16 * 1024):
        pipeline = _run(go())._pipeline
    # Peak staged bytes stays within budget + one over-admitted request,
    # the budget saw exactly that, and every debit came back.
    bound = max(budget, max(sizes)) if budget < sum(sizes) else sum(sizes)
    assert TrackingStager.peak <= min(budget, sum(sizes)) + max(sizes)
    assert pipeline.budget.high_water_bytes <= bound
    assert pipeline.budget.available == pipeline.budget.total
    # Bytes exact; the sidecar digest (v1 below the hash grain, a v2 tree
    # above it) equals an independent recompute.
    sidecar = json.loads(storage.objects.pop(".checksums.0"))
    assert storage.objects.keys() == stagers.keys() == sidecar.keys()
    for path, stager in stagers.items():
        expected = stager.payload()
        assert storage.objects[path] == expected
        rec = sidecar[path]
        assert hashing.is_v2_record(rec) == (len(expected) > 16 * 1024)
        assert hashing.record_crc(rec) == zlib.crc32(expected)
        assert rec == hashing.digest_of_bytes(
            expected, 16 * 1024, want_sha=bool(hashing.record_content_keys(rec))
        )


def test_budget_deadlock_avoided_single_huge_req() -> None:
    TrackingStager.live = TrackingStager.peak = 0
    reqs = [WriteReq("huge", TrackingStager(10_000))]
    storage = ReleasingStorage()
    _run_write(reqs, storage, budget=10)
    data_objects = [k for k in storage.objects if not k.startswith(".checksums")]
    assert len(data_objects) == 1  # over-budget req still admitted


def test_pending_io_work_defers_io() -> None:
    class SlowStorage(MemoryStoragePlugin):
        async def write(self, write_io: WriteIO) -> None:
            await asyncio.sleep(0.05)
            await super().write(write_io)

    reqs = [WriteReq(f"p{i}", TrackingStager(10)) for i in range(20)]
    storage = SlowStorage()

    async def staged_then_drain():
        pending = await execute_write_reqs(
            reqs, storage, memory_budget_bytes=10**6, rank=0
        )
        staged_but_unwritten = len(storage.objects) < 20
        await pending.complete()
        return staged_but_unwritten

    assert _run(staged_then_drain())
    data_objects = [k for k in storage.objects if not k.startswith(".checksums")]
    assert len(data_objects) == 20


class CountingConsumer(BufferConsumer):
    def __init__(self, expected: bytes, box: list):
        self.expected = expected
        self.box = box

    async def consume_buffer(self, buf, executor=None) -> None:
        assert bytes(buf) == self.expected
        self.box.append(1)

    def get_consuming_cost_bytes(self) -> int:
        return len(self.expected)


def test_read_pipeline_with_ranges() -> None:
    storage = MemoryStoragePlugin()
    storage.objects["obj"] = bytes(range(100))
    box: list = []
    reqs = [
        ReadReq("obj", CountingConsumer(bytes(range(100)), box)),
        ReadReq("obj", CountingConsumer(bytes(range(10, 20)), box), byte_range=(10, 20)),
    ]
    _run(execute_read_reqs(reqs, storage, memory_budget_bytes=10**6, rank=0))
    assert len(box) == 2


@pytest.mark.parametrize("sizes", [[10] * 4, [50 * 1024] + [1024] * 8],
                         ids=["small", "one-large"])
@pytest.mark.parametrize("fail_in", ["write", "stage"])
def test_write_failure_propagates(fail_in, sizes) -> None:
    """A failing write, or a failing staging of the largest leaf while the
    others are in flight: the failure propagates, the failed object is
    absent with no digest recorded, and the budget is fully credited."""

    class FailingStorage(MemoryStoragePlugin):
        async def write(self, write_io: WriteIO) -> None:
            if fail_in == "write" and write_io.path == "p0":
                raise OSError("disk full")
            await super().write(write_io)

    storage = FailingStorage()
    reqs = [
        WriteReq(f"p{i}", TrackingStager(n, fail=fail_in == "stage" and i == 0))
        for i, n in enumerate(sizes)
    ]
    pipeline = _WritePipeline(reqs, storage, memory_budget_bytes=10**6, rank=0)

    async def go():
        await pipeline.run_until_staged()
        await asyncio.wait_for(pipeline.run_to_completion(), timeout=30)

    error = OSError if fail_in == "write" else RuntimeError
    with pytest.raises(error, match="disk full|staging failure"):
        _run(go())
    assert "p0" not in storage.objects
    assert "p0" not in pipeline.checksums
    assert ".checksums.0" not in storage.objects
    assert pipeline.budget.available == pipeline.budget.total


def test_memory_budget_override_knob() -> None:
    with knobs.override_memory_budget_bytes(12345):
        assert get_process_memory_budget_bytes(None) == 12345


def test_stage_and_io_streams_overlap_across_requests() -> None:
    """Overlap stats: with several requests in flight, stagings land in
    the staging stream and writes in the io stream, and the two overlap."""

    class SlowStager(TrackingStager):
        def __init__(self, delay: float):
            super().__init__(1024)
            self.delay = delay

        async def stage_buffer(self, executor=None):
            await asyncio.sleep(self.delay)
            return bytearray(self.nbytes)

    async def go():
        # Staggered stagings under a budget of four requests: a write starts
        # as each staging ends, and the budget it frees admits the next.
        pending = await execute_write_reqs(
            [WriteReq(f"p{i}", SlowStager(0.005 * (i % 4 + 1))) for i in range(16)],
            ReleasingStorage(),
            memory_budget_bytes=4 * 1024,
            rank=0,
        )
        await pending.complete()
        return pending

    stats = _run(go()).pipeline_stats
    assert stats["stage_busy_s"] > 0
    assert stats["io_busy_s"] > 0
    shorter = min(stats["stage_busy_s"], stats["io_busy_s"])
    assert stats["overlap_s"] > 0.5 * shorter, stats


def test_progress_reporter_logs_occupancy(caplog) -> None:
    from torchsnapshot_tpu.scheduler import _Budget, _ProgressReporter

    rep = _ProgressReporter(rank=0, kind="write", interval_s=0.0)
    with caplog.at_level("INFO", logger="torchsnapshot_tpu.scheduler"):
        rep.maybe_report({"pending": 3, "io": 2}, 12_000_000, _Budget(10**9))
    (rec,) = [r for r in caplog.records if "pipeline" in r.message]
    msg = rec.getMessage()
    assert "pending=3" in msg and "io=2" in msg and "0.01 GB done" in msg
