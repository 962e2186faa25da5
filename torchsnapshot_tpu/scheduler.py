"""Memory-budgeted async execution pipelines, lowered onto the dataflow
engine.

Conceptual port of the reference's scheduler state machine
(``/root/reference/torchsnapshot/scheduler.py:220-461``) — not of its code.
Since the engine unification, this module is the *graph builder* layer:
``execute_write_reqs`` / ``execute_read_reqs`` translate write/read request
lists into task graphs (see ``engine/graph.py``) and the shared
:class:`~.engine.GraphExecutor` owns the machinery that used to live here
three times over — budget admission, slot caps, task tables, abort sweeps,
interval/span recording, occupancy reporting, the stall watchdog, and QoS
preemption. What remains here is the checkpoint domain logic: what staging
means, hashing/dedup, sidecar commit, and read verification.

Write pipeline graph (one chain per request)::

    stage ──(budget+data edge)──> io
    D2H + serialize               hash + dedup + storage.write
    (pool: staging)               (pool: io, cap MAX_CONCURRENT_IO)

The memory budget is debited by each request's estimated staging cost when
it is admitted, corrected to the actual buffer size when staging completes,
and credited back when its storage write completes — the reservation rides
the graph edge. One over-budget request is always admitted when the graph
is otherwise empty, so a single huge array can't deadlock the pipeline
(reference ``scheduler.py:268``).

``execute_write_reqs`` returns at the **capture point**: every request whose
source training could still invalidate (mutable host arrays, objects) has
been staged into private host buffers under the memory budget — the
reference's capture semantics (``scheduler.py:178-214``). Requests flagged
``defer_staging`` (device arrays: immutable, and defensively forked against
donation by ``io_preparer._defensive_device_copies``) enter the graph as
*deferred* nodes; the returned :class:`PendingIOWork` releases them and
drains device→host transfer plus all storage I/O in the background, still
under the same budget. For device-dominated snapshots — the TPU norm —
``async_take``'s stall is thus planning time only, independent of
checkpoint size.

The read pipeline is the mirrored graph: ``read_io`` (fetch + digest
verify) → ``consume`` (deserialize + scatter) chains admitted under a
consuming budget.

Every pipeline carries a QoS class (``engine.Priority``, inherited from the
ambient :func:`~.engine.qos.priority_scope` or passed explicitly): a
FOREGROUND restore preempts a BACKGROUND drain's next admission at chunk
granularity through the process-wide arbiter.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import os
import socket
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import psutil

from . import d2h, hashing, host_arena, restore_times, telemetry
from .engine import GraphExecutor, Node, Priority
from .engine.executor import Budget as _Budget  # noqa: F401 - test surface
from .engine.executor import ProgressReporter as _ProgressReporter  # noqa: F401
from .engine.intervals import (
    busy_in as _busy_in,
    merge_intervals as _merge_intervals,
    smaller_than as _smaller_than,
    stream_stats as _stream_stats,
    sum_in as _sum_in,
)
from .io_types import (
    SMALL_OBJECT_BYTES,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    WriteReq,
    WriteTimes,
    acquire_target_of,
    destination_of,
)
from .storage_plugins.cloud_retry import (
    CollectiveProgress,
    is_transient_os_error,
    retry_transient,
)
from .utils import knobs

logger = logging.getLogger(__name__)

_STAGE_POOLS = ("staging",)


class ReadVerificationError(RuntimeError):
    """A fetched object's bytes did not match the snapshot's recorded
    digest TWICE — the original fetch and one verified re-fetch (with any
    read-cache entry for the path quarantined in between). Persistent
    corruption at the origin, not a transient flake; the restore aborts
    rather than scatter bad bytes into live state. Raised only under
    ``TORCHSNAPSHOT_TPU_VERIFY_READS=all`` (cache hits carry their own
    default-on verification inside the cache plugin)."""


CHECKSUM_FILE_PREFIX = ".checksums."  # one JSON sidecar per rank

# Digesting lives in ``hashing.py``: objects larger than one hash chunk
# (``TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES``) are hashed chunk-PARALLEL on the
# hash pool and recorded as v2 tree-digest records (per-chunk sha256s +
# combined crc32, bit-identical to the serial fold); smaller ones keep the
# exact v1 ``[crc32, size, sha256|None]`` record. ``want_sha`` is resolved
# once per pipeline (``knobs.is_dedup_digests_enabled``: auto-gated on CPU
# headroom, forced on when the take passes ``base=``).

_MAX_PER_RANK_MEMORY_BUDGET_BYTES = 32 * 1024 * 1024 * 1024
_AVAILABLE_MEMORY_MULTIPLIER = 0.6


def derive_local_world_size(coordinator=None) -> int:
    """Ranks co-hosted with this process (sharing one disk/NIC).

    With a coordinator: derived from a hostname all-gather and cached into
    ``knobs.set_local_world_size`` so IO-concurrency defaults adapt — N
    co-hosted pipelines otherwise run N x 16 storage ops and N x 2 O_DIRECT
    streams against one device (measured to *lose* to a single process on
    TPU-VM NVMe). Without a coordinator: returns the cached value from the
    most recent coordinated call (1 if never coordinated).
    """
    if coordinator is None:
        return knobs.get_local_world_size()
    local_world_size = 1
    if coordinator.get_world_size() > 1:
        # Gather to rank 0 + broadcast the list back: constant store
        # round-trips per non-zero rank (an all_gather costs O(world) store
        # reads on EVERY rank, and this runs on the restore/restart path).
        # SPMD contract: every rank calls this at the same program point
        # (gated on world size only, never on rank/local state) — enforced
        # statically by the TSA9xx collective-discipline pass and at
        # runtime by the collective lockstep tracer
        # (TORCHSNAPSHOT_TPU_DEBUG_COLLECTIVES).
        gathered = coordinator.gather_object(socket.gethostname(), dst=0)
        hostnames = coordinator.broadcast_object(gathered, src=0)
        local_world_size = max(1, hostnames.count(socket.gethostname()))
    knobs.set_local_world_size(local_world_size)
    return local_world_size


def get_process_memory_budget_bytes(coordinator=None) -> int:
    """Per-process staging budget (reference ``scheduler.py:27-65``)."""
    # Derive (and cache) the local world size even when the budget itself is
    # overridden — IO-concurrency scaling depends on the cached value, and
    # skipping the gather here would silently disable it. All ranks call
    # this symmetrically, so the collective is safe either way.
    local_world_size = derive_local_world_size(coordinator)
    override = knobs.get_memory_budget_override_bytes()
    if override is not None:
        return override
    available = psutil.virtual_memory().available
    budget = int(available * _AVAILABLE_MEMORY_MULTIPLIER / local_world_size)
    return min(budget, _MAX_PER_RANK_MEMORY_BUDGET_BYTES)


class PipelinePools:
    """The thread pools one take/restore's pipelines share: a staging
    executor (D2H + serialize), a hash pool (checksums/dedup digests), and
    a consuming executor (deserialize + scatter on restore).

    One instance serves every pipeline of the same operation — a restore's
    per-stateful read pipelines, or a take's write pipeline plus any reads
    it issues — instead of each constructing (and tearing down) fresh pools.
    ``shutdown(cancel_queued=True)`` is the error path: queued thunks are
    cancelled so they don't run against a torn-down pipeline.
    """

    def __init__(self) -> None:
        self._staging: Optional[ThreadPoolExecutor] = None
        self._hash: Optional[ThreadPoolExecutor] = None
        self._consuming: Optional[ThreadPoolExecutor] = None
        self._lanes: Optional[d2h.TransferLanes] = None

    def staging_executor(self) -> ThreadPoolExecutor:
        if self._staging is None:
            self._staging = ThreadPoolExecutor(
                max_workers=knobs.get_staging_threads(),
                thread_name_prefix="tss-stage",
            )
        return self._staging

    def hash_executor(self) -> ThreadPoolExecutor:
        # Sized by TORCHSNAPSHOT_TPU_HASH_WORKERS (default: the staging
        # width): hashing (~1 GB/s/thread for crc+sha256) must not become
        # the drain's bottleneck now that chunk jobs of ONE object can
        # occupy every worker, and on incremental takes it replaces the
        # skipped storage write.
        if self._hash is None:
            self._hash = ThreadPoolExecutor(
                max_workers=knobs.get_hash_workers(),
                thread_name_prefix="tss-hash",
            )
        return self._hash

    def consuming_executor(self) -> ThreadPoolExecutor:
        if self._consuming is None:
            self._consuming = ThreadPoolExecutor(
                max_workers=knobs.get_consuming_threads(),
                thread_name_prefix="tss-consume",
            )
        return self._consuming

    def transfer_lanes(self) -> d2h.TransferLanes:
        """The operation's parallel D2H lanes (dedicated transfer executor
        behind a hint window a device; see ``d2h.TransferLanes``). Sized by
        the D2H_LANES knob at first use."""
        if self._lanes is None:
            self._lanes = d2h.TransferLanes()
        return self._lanes

    def shutdown(self, cancel_queued: bool = False) -> None:
        for ex in (self._staging, self._hash, self._consuming):
            if ex is not None:
                ex.shutdown(wait=False, cancel_futures=cancel_queued)
        if self._lanes is not None:
            self._lanes.shutdown(cancel_queued=cancel_queued)
        self._staging = self._hash = self._consuming = self._lanes = None


def _release_staged(stager) -> None:
    """``BufferStager.release_staged``, for a stager that is one (a
    caller's own may only quack like one)."""
    release = getattr(stager, "release_staged", None)
    if release is not None:
        release()


class _WritePipeline:
    """The write-side graph builder + domain node bodies. Builds one engine
    chain per request (``stage → io``) and keeps the checkpoint semantics —
    hashing, dedup link-in, sidecar commit, capture point — while the
    engine owns execution.
    Resumable so deferred staging (``WriteReq.defer_staging``) can finish
    on the async-commit background thread."""

    def __init__(
        self,
        write_reqs: List[WriteReq],
        storage: StoragePlugin,
        memory_budget_bytes: int,
        rank: int,
        base_loader: Optional[
            Callable[[], Optional[Tuple[str, Dict[str, list]]]]
        ] = None,
        pools: Optional[PipelinePools] = None,
        priority: Optional[Priority] = None,
        synchronous: bool = False,
    ) -> None:
        self.storage = storage
        # Thread pools: shared with the operation's other pipelines when the
        # caller passes them, private (and torn down at drain end) otherwise.
        self._owns_pools = pools is None
        self.pools = pools if pools is not None else PipelinePools()
        # Resolved lazily (on the background drain for async takes) so
        # reading the base snapshot's metadata/sidecars never extends
        # async_take's stall; after resolution base is
        # (root, {path: digest}, {(size, sha): path}) or None.
        self._base_loader = base_loader
        self._base_resolved = base_loader is None
        # Resolved once per pipeline: a deferred background drain must not
        # re-read a knob whose env changed since the take was planned.
        self._want_sha = knobs.is_dedup_digests_enabled(
            has_base=base_loader is not None
        )
        # The chunked-hashing grain, resolved once for the same reason
        # (0 = the serial v1 fold; objects <= one chunk keep v1 records).
        self._hash_grain = knobs.get_hash_chunk_bytes()
        # Set at base resolution: True when the base's sidecars carry v1
        # whole-object identities, so new objects must compute the whole
        # sha256 too (the compat shim) or dedup would spuriously re-upload.
        self._base_needs_whole_sha = False
        self._base_lock = asyncio.Lock()
        self.base = None
        self.bytes_deduped = 0
        self.rank = rank
        self.begin_ts = time.monotonic()
        # Live progress counters (PendingSnapshot.progress()): totals start
        # as staging-cost estimates and converge on actual bytes as staging
        # completes, so bytes_written ends equal to the payload total.
        self.progress = telemetry.ProgressTracker()
        # Fleet beacons carry this pipeline's rates/ETA; latest tracker wins
        # (one drain at a time per class, and a stale tracker just reads as
        # a finished drain). One is-None check when the bus is off.
        telemetry.fleet.set_progress(self.progress)
        self.progress.set_totals(
            requests=len(write_reqs),
            bytes_=sum(
                r.buffer_stager.get_staging_cost_bytes() for r in write_reqs
            ),
        )
        self.bytes_staged = 0
        self.staged_ts: Optional[float] = None
        self.executor: Optional[ThreadPoolExecutor] = None
        self.checksums: Dict[str, list] = {}
        self._crc_executor: Optional[ThreadPoolExecutor] = None
        self._tm = telemetry.get_active()
        # Parallel D2H lanes + stage-time attribution, exposed to stagers
        # via the d2h contextvar around node-task creation.
        # ``synchronous``: the pipeline of a ``Snapshot.take``, which no
        # step runs beside. Its big leaves are cut on the device at their
        # turn and gathered into views of an arena of host pages handed from
        # leaf to leaf (``host_arena.py``): inside what the budget admits
        # for those leaves, allocated at the first lease, closed with the
        # pipeline. A drain beside a step gets none: its gathers land in
        # fresh pages (``PERF.md``, PR 39).
        self._arena: Optional[host_arena.HostArena] = None
        self._arena_capacity = min(host_arena.CAPACITY_BYTES, memory_budget_bytes)
        self._stagers = [r.buffer_stager for r in write_reqs]
        self._staging_ctx = d2h.StagingContext(
            lanes=self.pools.transfer_lanes(),
            times=d2h.StageTimes(tm=self._tm),
            arena=self._host_arena if synchronous else None,
        )
        # Beside the io stream: what the plugin's native writes did, handed
        # to it with each object's ``WriteIO``.
        self._write_times = WriteTimes()

        def _max_io() -> int:
            return knobs.get_max_concurrent_io_for(self.storage)

        self._engine = GraphExecutor(
            budget_bytes=memory_budget_bytes,
            rank=rank,
            owner=f"write@rank{rank}",
            kind="write",
            span_prefix="scheduler",
            priority=priority,
            caps={"staging": None, "io": _max_io},
            ready_label="ready_for_io",
            progress=self.progress,
            bytes_done=lambda: self.bytes_staged,
            task_context=self._staging_scope,
            on_progress=self._after_reap,
        )
        self.budget = self._engine.budget
        # Populated by run_to_completion: how well the pipeline overlapped
        # its two streams (D2H+serialize staging vs storage writes). The
        # 7B-scale exposure is drain throughput, so the overlap efficiency
        # must be observable, not asserted. drain_stats covers the
        # run_to_completion call only; pipeline_stats the whole pipeline.
        # Both are derived views over the engine's recorded stream
        # intervals (the same data the telemetry trace exports as spans).
        self.drain_stats: Dict[str, float] = {}
        self.pipeline_stats: Dict[str, float] = {}
        # Lower every request onto the engine graph, big first: they
        # dominate the critical path and admit small ones into the leftover
        # budget.
        for req in sorted(
            write_reqs,
            key=lambda r: -r.buffer_stager.get_staging_cost_bytes(),
        ):
            self._add_request(req)

    # ----------------------------------------------------- engine plumbing

    def _host_arena(self) -> host_arena.HostArena:
        """Made when the first big leaf asks for pages: a take of small
        leaves has none."""
        if self._arena is None:
            self._arena = host_arena.HostArena(self._arena_capacity)
        return self._arena

    def _staging_scope(self):
        """Context manager applied around node-task creation so every
        stager (and the sub-tasks it spawns) sees the transfer lanes +
        interval sink via ``d2h.get_active()`` — no signature change to the
        stager protocol."""
        import contextlib

        @contextlib.contextmanager
        def scope():
            token = d2h.activate(self._staging_ctx)
            try:
                yield
            finally:
                d2h.deactivate(token)

        return scope()

    # Engine interval/window views — the telemetry artifact summary and the
    # stats derivation read these (one source of truth: the engine).
    @property
    def _windows(self) -> List[Tuple[float, float]]:
        return self._engine.windows

    @property
    def _stage_intervals(self) -> List[Tuple[float, float]]:
        return self._engine.stage_intervals

    @property
    def _io_intervals(self) -> List[Tuple[float, float, int]]:
        return self._engine.io_intervals

    def _after_reap(self) -> None:
        self._maybe_mark_staged()

    # ------------------------------------------------------- graph building

    def _add_request(self, req: WriteReq) -> None:
        cost = req.buffer_stager.get_staging_cost_bytes()
        io_node = Node(
            "io",
            self._make_io_body(req),
            pool="io",
            stream="io",
            path=req.path,
        )
        self._engine.add(
            Node(
                "stage",
                self._make_stage_body(req, cost),
                cost_bytes=cost,
                pool="staging",
                stream="stage",
                path=req.path,
                deferred=req.defer_staging,
                successor=io_node,
            )
        )

    def _make_stage_body(self, req: WriteReq, cost: int):
        async def stage(ctx, _payload):
            if self.executor is None:
                self.executor = self.pools.staging_executor()
            buf = await req.buffer_stager.stage_buffer(self.executor)
            nbytes = memoryview(buf).nbytes
            self.bytes_staged += nbytes
            self.progress.note_staged(nbytes, estimate=cost)
            # Correct the estimate to the real footprint; the corrected
            # reservation rides the edge to the io node.
            ctx.recost(nbytes)
            return buf

        return stage

    def _make_io_body(self, req: WriteReq):
        async def io(_ctx, buf):
            # The staged buffer's reservation is credited by the engine
            # whether the write lands or fails (edge-final semantics).
            try:
                await self._write_one(req.path, buf)
            finally:
                nbytes = memoryview(buf).nbytes
                self.progress.note_written(nbytes)
                # Hash and write are done with the bytes, whichever way
                # they ended: lent pages go to the next leaf.
                _release_staged(req.buffer_stager)
            self.progress.note_request_done()

        return io

    def _timed_hash(self, path: str, nbytes: int, fn):
        """Run one hashing thunk with its interval recorded in the ``hash``
        sub-stream (the thunk itself executes on the hash pool)."""
        times = self._staging_ctx.times

        def work():
            with d2h.timed(times, "hash", path=path, nbytes=nbytes):
                return fn()

        return work

    async def _write_one(self, path: str, buf) -> None:
        if knobs.is_checksums_enabled():
            # Hashing releases the GIL; it runs on its own pool (width =
            # staging threads) so a staging pool saturated with multi-second
            # D2H jobs can't head-of-line block storage writes behind queued
            # staging work.
            # Recorded per *storage object* (sidecar value
            # [crc32, size, sha256]) so ``Snapshot.verify()`` can audit
            # files without the manifest and incremental takes can dedup.
            loop = asyncio.get_running_loop()
            if self._crc_executor is None:
                # Hashing runs on the operation's shared hash pool so a
                # staging pool saturated with multi-second D2H jobs can't
                # head-of-line block storage writes behind queued staging
                # work (width: see PipelinePools.hash_executor).
                self._crc_executor = self.pools.hash_executor()
            if not self._base_resolved:
                async with self._base_lock:
                    if not self._base_resolved:
                        self.base = await loop.run_in_executor(
                            self._crc_executor, self._base_loader
                        )
                        if self.base is not None:
                            # Content-keyed inverted index: lets an object
                            # dedup against a base object at a DIFFERENT
                            # path — e.g. batched slabs, whose
                            # ``batched/<uuid>`` paths are fresh each take
                            # even when their bytes are identical. Keys are
                            # the records' content identities (v1 whole-sha
                            # AND/OR v2 tree-root — hashing.py owns both),
                            # so mixed v1-base + v2-delta chains dedup.
                            root, digests = self.base
                            by_content = {}
                            for k, v in digests.items():
                                sz = hashing.record_size(v)
                                for key in hashing.record_content_keys(v):
                                    by_content.setdefault((sz, key), k)
                            self.base = (root, digests, by_content)
                            # A base with v1 whole-object identities needs
                            # new objects to carry a whole sha256 too (the
                            # compat shim) or nothing would ever match.
                            self._base_needs_whole_sha = any(
                                isinstance(v, list)
                                for v in digests.values()
                            )
                        self._base_resolved = True
            mv = memoryview(buf)
            grain = self._hash_grain
            times = self._staging_ctx.times
            if self.base is None:
                if grain > 0 and mv.nbytes > grain:
                    # v2 path: chunk-PARALLEL digest on the hash pool,
                    # overlapping the storage write — neither waits on the
                    # other, and the hash itself scales with HASH_WORKERS
                    # instead of serializing one fold per object.
                    digest_task = asyncio.ensure_future(
                        hashing.hash_buffer(
                            mv,
                            grain,
                            self._want_sha,
                            loop,
                            self._crc_executor,
                            times=times,
                            path=path,
                        )
                    )
                    try:
                        await self.storage.write(
                            WriteIO(path=path, buf=buf, times=self._write_times)
                        )
                    except BaseException:
                        digest_task.cancel()
                        await asyncio.gather(
                            digest_task, return_exceptions=True
                        )
                        raise
                    self.checksums[path] = await digest_task
                    return
                # Small (<= one hash chunk) or serial-mode objects keep the
                # exact v1 record and the plugin fast path: the native FS
                # engine hashes chunk-hot in C++ inside its own write loop
                # (WriteIO.digest_out), and Python covers only what the
                # plugin didn't — everything (non-native backends), or just
                # the sha256 dedup digest.
                write_io = WriteIO(
                    path=path, buf=buf, want_digest=True, times=self._write_times
                )
                await self.storage.write(write_io)
                digest = write_io.digest_out
                if digest is None:
                    digest = await loop.run_in_executor(
                        self._crc_executor,
                        self._timed_hash(
                            path,
                            mv.nbytes,
                            lambda: hashing.serial_digest(mv, self._want_sha),
                        ),
                    )
                elif digest[2] is None and self._want_sha:

                    def sha_only(mv=mv):
                        h = hashlib.sha256()
                        h.update(mv)
                        return h.hexdigest()

                    digest = [
                        digest[0],
                        digest[1],
                        await loop.run_in_executor(
                            self._crc_executor,
                            self._timed_hash(path, mv.nbytes, sha_only),
                        ),
                    ]
                self.checksums[path] = digest
                return
            # Incremental take: the digest decides link-in vs write, so it
            # must land BEFORE the write — but it is still chunk-parallel
            # across the pool (plus the sequential whole-sha compat job
            # when the base recorded v1 identities).
            digest = await hashing.hash_buffer(
                mv,
                grain,
                self._want_sha,
                loop,
                self._crc_executor,
                times=times,
                path=path,
                want_whole_sha=self._base_needs_whole_sha,
            )
            self.checksums[path] = digest
            my_keys = hashing.record_content_keys(digest)
            my_size = hashing.record_size(digest)
            if my_keys:
                base_root, base_digests, by_content = self.base
                rec = base_digests.get(path)
                src_path = None
                if (
                    rec is not None
                    and hashing.record_size(rec) == my_size
                    and set(my_keys) & set(hashing.record_content_keys(rec))
                ):
                    src_path = path
                else:
                    for key in my_keys:
                        src_path = by_content.get((my_size, key))
                        if src_path is not None:
                            break
                if src_path is not None:
                    # Byte-identical to a base snapshot object (size +
                    # content-key match): hard-link / server-side copy
                    # instead of rewriting. Any failure (cross-device, base
                    # deleted, backend mismatch) falls back to a write.
                    src = os.path.join(base_root, src_path)
                    if await self.storage.link_in(src, path):
                        self.bytes_deduped += my_size
                        return
        await self.storage.write(
            WriteIO(path=path, buf=buf, times=self._write_times)
        )

    # ---------------------------------------------------------------- phases

    @property
    def budget_balanced(self) -> bool:
        """True when every debit has been credited back — the invariant an
        aborted take must restore (chaos-harness assertion surface)."""
        return self.budget.available == self.budget.total

    async def _abort_inflight(self) -> None:
        """Failure path: the engine's abort sweep (cancel, await, credit
        every outstanding reservation) — so an aborted take leaves the
        budget balanced and no staging/io coroutine running against a
        torn-down pipeline."""
        await self._engine.abort()
        # A leaf staged into lent pages whose io node never ran (it waited
        # for a slot, or rode the edge) still holds them.
        for stager in self._stagers:
            _release_staged(stager)
        # Debug-ledger cross-check: an aborted pipeline must leave zero
        # outstanding bytes; a leak here raises naming the debiting sites
        # (chained onto the failure that triggered the abort).
        self.budget.assert_balanced("write pipeline abort")

    def _maybe_mark_staged(self) -> None:
        if (
            self.staged_ts is None
            and not self._engine._deferred
            and self._engine.unfinished_in(_STAGE_POOLS) == 0
        ):
            self.staged_ts = time.monotonic()
            logger.info(
                "Rank %d staged %.2f GB in %.2fs",
                self.rank,
                self.bytes_staged / 1e9,
                self.staged_ts - self.begin_ts,
            )

    async def run_until_staged(self) -> None:
        """Drive the graph to the capture point: every *non-deferred*
        request's bytes are privately held in host RAM. Deferred requests
        (immutable device-backed data) then become admissible for the
        background drain."""
        try:
            await self._engine.run(
                until=lambda: self._engine.unfinished_in(_STAGE_POOLS) == 0
            )
        except BaseException:
            await self._abort_inflight()
            self._shutdown_executor(failed=True)
            raise
        self._engine.release_deferred()
        self._maybe_mark_staged()

    async def run_to_completion(self) -> None:
        """Drive the graph (staging and I/O) until everything is written."""
        # Window bookkeeping: drain_stats reports THIS call's window only
        # (for async takes, the background drain — any host-entry staging
        # billed during the stall must not deflate the apparent drain
        # rate), while pipeline_stats covers every window for sync takes.
        try:
            self._engine.release_deferred()
            await self._engine.run()
            # The sidecar write/delete below is real storage time: recorded
            # as an io interval so wall_s (and the drain rate derived from
            # it) doesn't silently exclude the post-loop tail.
            sidecar_t0 = time.monotonic()
            if self.checksums:
                # Pre-commit (the caller barriers before rank 0 writes the
                # metadata file), so a committed snapshot always carries its
                # checksum sidecars.
                payload = json.dumps(self.checksums, sort_keys=True).encode()
                self.checksums = {}
                sidecar_path = f"{CHECKSUM_FILE_PREFIX}{self.rank}"
                await self.storage.write(
                    WriteIO(path=sidecar_path, buf=payload)
                )
                self._engine.record_interval(
                    "io", sidecar_t0, sidecar_path, len(payload)
                )
            else:
                # No sidecar written this take (checksums off, or this rank
                # staged no storage objects): remove any stale sidecar a
                # previous take left at this path, or verify() would compare
                # the old digests against the new bytes and report a healthy
                # snapshot as corrupt.
                try:
                    await self.storage.delete(
                        f"{CHECKSUM_FILE_PREFIX}{self.rank}"
                    )
                except FileNotFoundError:
                    # Absent — the common case. Plugins normalize their
                    # backend's absence error to FileNotFoundError (the
                    # StoragePlugin contract), so no name/message sniffing
                    # is needed here.
                    pass
                except Exception:
                    logger.warning(
                        "Could not delete stale checksum sidecar %s%d; "
                        "a later verify() of this path may report "
                        "false corruption",
                        CHECKSUM_FILE_PREFIX,
                        self.rank,
                        exc_info=True,
                    )
        except BaseException:
            # Error path: the engine sweep cancels in-flight nodes
            # (crediting their reservations) and queued staging/hash thunks
            # so nothing runs against a torn-down pipeline.
            await self._abort_inflight()
            self._shutdown_executor(failed=True)
            raise
        self._shutdown_executor()
        # Debug-ledger cross-check: a completed drain has credited every
        # debit — zero outstanding bytes at pipeline close.
        self.budget.assert_balanced("write pipeline close")

        # Extend this run's accounting window over the sidecar tail, then
        # derive the stats views.
        windows = self._engine.windows
        if windows:
            windows[-1] = (windows[-1][0], time.monotonic())
            drain_window = windows[-1]
        else:  # pragma: no cover - run() always records a window
            drain_window = (self.begin_ts, time.monotonic())
        # drain_stats: this call's window only (the async background drain).
        self.drain_stats = _stream_stats(
            [drain_window], self._stage_intervals, self._io_intervals
        )
        # pipeline_stats: run_until_staged + drain — the whole pipeline, so
        # a SYNC take's staging (done before its drain loop) is attributed.
        self.pipeline_stats = _stream_stats(
            windows, self._stage_intervals, self._io_intervals
        )
        # Decompose stage_busy into its sub-streams (D2H resolve, serialize/
        # compress, hash fold, a piece's gather) from the StageTimes
        # intervals — same union/clip algebra, so the stats and the stage.*
        # trace spans can never disagree. With parallel lanes the
        # sub-streams overlap each other, so their sum may legitimately
        # EXCEED stage_busy_s (that overlap is the speedup); each value
        # reads "seconds this sub-stream was busy". Each view clips to its
        # own windows.
        times = self._staging_ctx.times
        sub = times.intervals()
        written = self._write_times.intervals()
        # What a synchronous take's stage did with its big leaves (all 0
        # beside a step but ``fresh_bytes``, the gathers of forked pieces):
        # leaves cut on the device at their turn, their bytes, of those the
        # bytes re-laid there, and leaves the device or the kernel compiler
        # refused; of the gathered bytes, those that landed in pages of the
        # arena an earlier leaf had used, and in fresh ones; and the seconds
        # in which some leaf waited for room in the arena (a union).
        staged = {
            "stage_sync_cut_leaves": times.sync_cut_leaves,
            "stage_sync_cut_bytes": times.sync_cut_bytes,
            "stage_sync_cut_relaid_bytes": times.sync_cut_relaid_bytes,
            "stage_sync_cut_refused": times.sync_cut_refused,
            "stage_recycled_bytes": times.recycled_bytes,
            "stage_fresh_bytes": times.fresh_bytes,
            "stage_target_wait_s": (
                self._arena.take_wait_s() if self._arena is not None else 0.0
            ),
        }
        for stats, wins in (
            (self.drain_stats, [drain_window]),
            (self.pipeline_stats, windows),
        ):
            for kind, ivs in sub.items():
                stats[f"stage_{kind}_s"] = _busy_in(_merge_intervals(ivs), wins)
            # The plain sums the unions lack: of a lane's seconds in
            # stage.d2h (sum), those in the gather's copy (sum).
            stats["stage_d2h_sum_s"] = _sum_in(sub["d2h"], wins)
            stats["stage_gather_sum_s"] = _sum_in(sub["gather"], wins)
            # Of the d2h sub-stream and of the io stream, the seconds spent
            # on transfers and writes under SMALL_OBJECT_BYTES: what a state
            # of many sizes pays in per-object fixed costs.
            for name, ivs in (
                ("stage_d2h_small_s", sub["d2h"]),
                ("io_busy_small_s", self._io_intervals),
            ):
                stats[name] = _busy_in(
                    _merge_intervals(_smaller_than(ivs, SMALL_OBJECT_BYTES)), wins
                )
            # Inside io_busy_s, what the fs plugin's native writes did
            # (``WriteTimes.record_native_write``; a write under the native
            # threshold goes through aiofiles on threads the library does
            # not own and is in io_busy_small_s only): the union and the sum
            # of the pwrites with their bytes, and the sums of the
            # storage.write_work intervals, of the copies into the bounce
            # buffer, of the crc and of the waits for a writer slot; and of
            # the pwrites' bytes, those copied into pages of a bounce buffer
            # that were warm from an earlier copy, and into fresh ones.
            stats["mount_write_s"] = _busy_in(
                _merge_intervals(written["mount_write"]), wins
            )
            for name, kind in (
                ("mount_write_bytes", "mount_write"),
                ("write_bounce_warm_bytes", "bounce_warm"),
                ("write_bounce_fresh_bytes", "bounce_fresh"),
            ):
                stats[name] = float(
                    sum(
                        nbytes
                        for t0, _, nbytes in written[kind]
                        if any(w0 <= t0 < w1 for w0, w1 in wins)
                    )
                )
            for name, kind in (
                ("write_work_sum_s", "write_work"),
                ("mount_write_sum_s", "mount_write"),
                ("write_copy_sum_s", "write_copy"),
                ("write_crc_sum_s", "write_crc"),
                ("write_queue_sum_s", "write_queue"),
            ):
                stats[name] = _sum_in(written[kind], wins)
            stats.update((name, float(value)) for name, value in staged.items())
        # Pipeline-level metrics (no-ops unless a telemetry session is on).
        telemetry.gauge_max(
            "scheduler.budget_hwm_bytes", self.budget.high_water_bytes
        )
        lanes = self._staging_ctx.lanes
        telemetry.gauge_max(
            "d2h.hinted_ahead_hwm_bytes", lanes.hinted_ahead_hwm_bytes
        )
        telemetry.counter_add("d2h.window_waits", lanes.window_waits)
        telemetry.counter_add("d2h.pieces", lanes.pieces)
        telemetry.counter_add("d2h.pieced_bytes", lanes.pieced_bytes)
        telemetry.counter_add("stage.host_relaid_bytes", times.host_relaid_bytes)
        for name, value in staged.items():
            telemetry.counter_add(name.replace("stage_", "stage.", 1), value)
        telemetry.gauge_max("stage.sync_cut_hwm_bytes", lanes.cut_hwm_bytes)
        telemetry.counter_add("scheduler.bytes_staged", self.bytes_staged)
        if self.bytes_deduped:
            telemetry.counter_add("scheduler.bytes_deduped", self.bytes_deduped)
        elapsed = time.monotonic() - self.begin_ts
        if self.bytes_staged:
            dedup = (
                f" ({self.bytes_deduped / 1e9:.2f} GB deduped from base)"
                if self.bytes_deduped
                else ""
            )
            # Overlap efficiency over the whole pipeline: how much of the
            # shorter stream's busy time ran concurrently with the other
            # stream. Low values mean D2H serialized against storage writes
            # — the tunable exposure at multi-GB scale.
            ps = self.pipeline_stats
            shorter = min(ps["stage_busy_s"], ps["io_busy_s"])
            efficiency = ps["overlap_s"] / shorter if shorter > 0 else 1.0
            logger.info(
                "Rank %d wrote %.2f GB in %.2fs (%.2f GB/s)%s | pipeline %.2fs: "
                "D2H/serialize busy %.2fs, storage busy %.2fs, overlapped "
                "%.2fs (%.0f%% of shorter stream), idle %.2fs",
                self.rank,
                self.bytes_staged / 1e9,
                elapsed,
                self.bytes_staged / 1e9 / max(elapsed, 1e-9),
                dedup,
                ps["wall_s"],
                ps["stage_busy_s"],
                ps["io_busy_s"],
                ps["overlap_s"],
                efficiency * 100,
                ps["idle_s"],
            )

    def _shutdown_executor(self, failed: bool = False) -> None:
        """Release the thread pools. On the error path, queued thunks are
        cancelled (``cancel_futures``) so no staging/hash work runs against
        a torn-down pipeline; shared pools (``_owns_pools`` False) are only
        torn down on failure — their owner closes them on success."""
        self.executor = None
        self._crc_executor = None
        if self._owns_pools or failed:
            self.pools.shutdown(cancel_queued=failed)
        if self._arena is not None:
            # Every leaf has given its view back by now (the io body, a
            # failing stage, the abort's sweep): the take keeps no page.
            lent = self._arena.in_use_bytes
            if lent:
                logger.error(
                    "write pipeline closed with %d bytes of its host arena "
                    "still lent", lent
                )
            self._arena.close()


class PendingIOWork:
    """Work still in flight after ``execute_write_reqs`` returned: remaining
    storage I/O, plus staging of any ``defer_staging`` requests."""

    def __init__(self, pipeline: _WritePipeline) -> None:
        self._pipeline = pipeline

    async def complete(self) -> None:
        await self._pipeline.run_to_completion()

    def sync_complete(self, event_loop: asyncio.AbstractEventLoop) -> None:
        event_loop.run_until_complete(self.complete())

    @property
    def budget_balanced(self) -> bool:
        """True when every memory-budget debit has been credited back.
        Holds after a successful drain AND after an aborted one — the
        chaos harness asserts it on every failure path."""
        return self._pipeline.budget_balanced

    @property
    def drain_stats(self) -> Dict[str, float]:
        """Stream-overlap accounting of the completed drain (empty until
        ``complete`` finishes): wall_s, stage_busy_s, io_busy_s, overlap_s,
        idle_s. Covers the drain only — staging billed during the take's
        stall (non-deferred host entries) is excluded, so bytes/wall_s is
        an honest drain rate."""
        return dict(self._pipeline.drain_stats)

    @property
    def pipeline_stats(self) -> Dict[str, float]:
        """Same keys, accumulated over the WHOLE pipeline (capture-point
        staging + drain) — what a sync take should report, since its
        staging completes before the drain loop ever runs."""
        return dict(self._pipeline.pipeline_stats)

    @property
    def progress(self) -> "telemetry.ProgressTracker":
        """The pipeline's live progress counters (monotonic; safe to read
        from any thread while the drain runs)."""
        return self._pipeline.progress

    def progress_snapshot(self) -> Dict[str, float]:
        """Counters + derived rates/ETA (see ProgressTracker.snapshot)."""
        return self._pipeline.progress.snapshot()

    def telemetry_io_summary(self) -> Dict[str, object]:
        """Everything the persisted telemetry artifact needs from this
        pipeline: overlap stats, merged stream intervals + accounting
        windows (monotonic seconds; the artifact builder rebases them to
        the unix epoch), and the byte/request totals. Meaningful once the
        pipeline has completed."""
        p = self._pipeline
        counters = p.progress.counters()
        written = p._write_times.intervals()
        return {
            "pipeline_stats_s": dict(p.pipeline_stats),
            "drain_stats_s": dict(p.drain_stats),
            "bytes": {
                "staged": p.bytes_staged,
                "written": counters["bytes_written"],
                "total": counters["bytes_total"],
                "deduped": p.bytes_deduped,
            },
            "requests": {
                "done": counters["requests_done"],
                "total": counters["requests_total"],
            },
            "windows": list(p._windows),
            "stage_intervals": _merge_intervals(p._stage_intervals),
            "io_intervals": _merge_intervals(p._io_intervals),
            # stage_busy decomposed: merged d2h/serialize/hash sub-stream
            # intervals (the artifact persists them beside stage/io).
            "stage_substreams": {
                kind: _merge_intervals(ivs)
                for kind, ivs in p._staging_ctx.times.intervals().items()
            },
            # Inside io: when a pwrite of the native engine was on the mount
            # and when a writer was copying into its bounce buffer (merged),
            # and every storage.write_work interval as it was (what a
            # reader of a profiler trace matches the tss.storage.write_work
            # events against, to put the other two on the trace's clock).
            "write_substreams": {
                "mount_write": _merge_intervals(written["mount_write"]),
                "write_copy": _merge_intervals(written["write_copy"]),
                "write_work": sorted(
                    (t0, t1) for t0, t1, _ in written["write_work"]
                ),
            },
            # Engine/QoS introspection totals + closed pause episodes, so
            # preemption waves survive into the persisted artifact instead
            # of existing only as live metrics.
            "engine": {
                "preemptions": p._engine.preemptions,
                "preempted_wait_s": round(p._engine.preempted_wait_s, 6),
                "pause_intervals": list(p._engine.pause_intervals),
            },
        }


async def execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    base_loader: Optional[
        Callable[[], Optional[Tuple[str, Dict[str, list]]]]
    ] = None,
    pools: Optional[PipelinePools] = None,
    priority: Optional[Priority] = None,
    synchronous: bool = False,
) -> PendingIOWork:
    """Runs to the capture point (all non-deferred requests staged) and
    returns a :class:`PendingIOWork` that drains the rest (deferred staging +
    all storage I/O). ``base_loader`` lazily yields (base snapshot root,
    merged digest map) for incremental takes: byte-identical objects are
    hard-linked, not rewritten. ``pools``: thread pools shared with the
    operation's other pipelines (owned, and torn down, by the caller).
    ``priority``: the pipeline's QoS class (default: the ambient
    ``engine.qos`` scope, NORMAL outside any scope). ``synchronous``: the
    caller waits for the whole pipeline and runs no step beside it
    (``Snapshot.take``), so its big leaves are cut on the device and land
    in recycled host pages (``_WritePipeline``)."""
    pipeline = _WritePipeline(
        write_reqs,
        storage,
        memory_budget_bytes,
        rank,
        base_loader=base_loader,
        pools=pools,
        priority=priority,
        synchronous=synchronous,
    )
    await pipeline.run_until_staged()
    return PendingIOWork(pipeline)


def sync_execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    base_loader: Optional[
        Callable[[], Optional[Tuple[str, Dict[str, list]]]]
    ] = None,
    pools: Optional[PipelinePools] = None,
    priority: Optional[Priority] = None,
    synchronous: bool = False,
) -> PendingIOWork:
    return event_loop.run_until_complete(
        execute_write_reqs(
            write_reqs,
            storage,
            memory_budget_bytes,
            rank,
            base_loader=base_loader,
            pools=pools,
            priority=priority,
            synchronous=synchronous,
        )
    )


def _read_digest_record(digests: Optional[Dict[str, object]], path: str):
    """The sidecar digest record for ``path`` — a v1 ``[crc32, size, sha]``
    list or a v2 tree-digest dict — or None when unknown / legacy-int
    format (no recorded size: a full-object read can't even be recognized,
    let alone verified). Interpretation belongs to ``hashing.py``'s record
    accessors."""
    if not digests:
        return None
    rec = digests.get(path)
    if hashing.record_size(rec) is None:
        return None
    return rec


async def fetch_read_io(
    storage: StoragePlugin,
    path: str,
    byte_range: Optional[Tuple[int, int]],
    progress: "CollectiveProgress",
    into: Optional[memoryview] = None,
) -> ReadIO:
    """One storage fetch of ``path`` (optionally ranged), retrying
    transient local OSErrors through the shared ``cloud_retry`` machinery
    under the caller's collective-progress window — the single fetch
    discipline of the read pipeline, shared with the broadcast and swarm
    restore paths so every origin read in the restore story retries
    identically. A retried read never appends to a partially-filled
    buffer. ``into``: the consumer's own destination, offered to the plugin
    (``ReadIO.into``); a retried read overwrites it from its start."""
    read_io = ReadIO(path=path, byte_range=byte_range, into=into)

    async def attempt() -> None:
        read_io.buf.seek(0)
        read_io.buf.truncate(0)
        await storage.read(read_io)

    await retry_transient(
        attempt, is_transient_os_error, progress, "read_pipeline"
    )
    return read_io


def _verify_checker(
    want, byte_range: Optional[Tuple[int, int]]
) -> Optional[Callable[[memoryview], Optional[str]]]:
    """The verification thunk (run on an executor thread) for one fetched
    request, or None when nothing is verifiable: full-object fetches check
    the whole record (tree or v1); RANGED fetches of v2 tree records check
    every chunk fully contained in the range — the capability the chunked
    sidecar exists for (v1 records can't verify a range at all)."""
    size = hashing.record_size(want)
    if byte_range is None or (
        size is not None and byte_range[0] == 0 and byte_range[1] == size
    ):
        return lambda mv, w=want: hashing.verify_buffer(mv, w)
    begin, end = byte_range
    if hashing.range_verifiable(want, begin, end):
        return lambda mv, w=want, b=begin, e=end: hashing.verify_range(
            mv, w, b, e
        )
    return None


def _timed_checker(times: "restore_times.RestoreTimes", checker, path: str):
    """``checker`` stamping its own ``verify`` interval, on the executor
    thread that runs it."""
    parent = telemetry.core.current_span_id()

    def timed(mv):
        with times.work("verify", path=path, nbytes=mv.nbytes, parent=parent):
            return checker(mv)

    return timed


async def execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    pools: Optional[PipelinePools] = None,
    digests: Optional[Dict[str, object]] = None,
    priority: Optional[Priority] = None,
) -> Dict[str, float]:
    """Drive the read graph to completion. Returns this pipeline's
    accounting — ``{"bytes_read", "wall_s", "requests"}`` — so restore
    callers can aggregate a restore-side record (bench regression gate,
    persisted artifacts) without a telemetry session.

    Each request lowers onto a ``read_io → consume`` engine chain: the
    fetch is admitted under the consuming budget (the reservation rides
    the edge until the consume completes), capped at the storage plugin's
    IO concurrency, and — at FOREGROUND priority — preempts any
    lower-class engine's next admission in this process.

    Fault tolerance: every request retries transient local OSErrors
    (stale NFS handles, timeouts — the same classification the fs plugin
    uses) through the shared ``cloud_retry`` machinery under one
    collective-progress window for the whole pipeline, on top of whatever
    retrying the plugin stack does internally. With ``digests`` (the
    snapshot's parsed checksum sidecars) and
    ``TORCHSNAPSHOT_TPU_VERIFY_READS=all``, every full-object fetch is
    verified against its recorded digest; a mismatch quarantines any
    read-cache entry for the path and re-fetches ONCE, and a second
    mismatch raises :class:`ReadVerificationError` — the restore aborts
    instead of consuming silently corrupt bytes."""
    begin_ts = time.monotonic()
    # One consuming pool per operation: restores with many statefuls reuse
    # the caller's pools instead of constructing one per read pipeline.
    owns_pools = pools is None
    if owns_pools:
        pools = PipelinePools()
    executor = pools.consuming_executor()
    # One window for the pipeline: any request starting or succeeding is
    # collective progress, so a transient storm retries while the backend
    # still moves bytes for peers and gives up ~window after a total stall.
    read_progress = CollectiveProgress()
    verify_reads = knobs.is_origin_read_verify_enabled() and bool(digests)
    quarantine_cache = None
    if verify_reads:
        from .storage_plugins.cache import find_read_cache

        quarantine_cache = find_read_cache(storage)
    totals = {"bytes_read": 0}
    eng = GraphExecutor(
        budget_bytes=memory_budget_bytes,
        rank=rank,
        owner=f"read@rank{rank}",
        kind="read",
        span_prefix="scheduler",
        priority=priority,
        caps={
            "io": lambda: knobs.get_max_concurrent_io_for(storage),
            "consume": None,
        },
        ready_label="consume_ready",
        bytes_done=lambda: totals["bytes_read"],
    )

    # The restore's interval sink (None outside a restore): each request's
    # fetch, verify and consume are stamped where they happen.
    times = restore_times.get_active()

    def make_bodies(req: ReadReq):
        fetched_at = 0.0  # when read_one handed its buffer on

        async def fetch(ctx) -> ReadIO:
            t0 = time.monotonic()
            read_io = await fetch_read_io(
                storage,
                req.path,
                req.byte_range,
                read_progress,
                into=destination_of(req.buffer_consumer),
            )
            if times is not None:
                times.record_fetch(
                    t0,
                    req.path,
                    read_io.buf.getbuffer().nbytes,
                    ctx.admitted_at,
                    read_io.buf.copied_bytes,
                )
            return read_io

        async def read_one(ctx, _payload):
            nonlocal fetched_at
            # A destination that has no memory yet gets it now, once a read
            # (the restore's arena of host pages), not at plan time.
            await acquire_target_of(req.buffer_consumer)
            read_io = await fetch(ctx)
            want = (
                _read_digest_record(digests, req.path) if verify_reads else None
            )
            checker = (
                _verify_checker(want, req.byte_range)
                if want is not None
                else None
            )
            if checker is not None:
                if times is not None:
                    checker = _timed_checker(times, checker, req.path)
                loop = asyncio.get_running_loop()
                problem = await loop.run_in_executor(
                    executor, checker, read_io.buf.getbuffer()
                )
                if problem is not None:
                    telemetry.counter_add("scheduler.read_verify_failures")
                    logger.warning(
                        "read of %s failed digest verification (%s); "
                        "quarantining cache entries and re-fetching once",
                        req.path,
                        problem,
                    )
                    if quarantine_cache is not None:
                        await loop.run_in_executor(
                            executor,
                            quarantine_cache.quarantine_path,
                            req.path,
                        )
                    read_io = await fetch(ctx)
                    problem = await loop.run_in_executor(
                        executor, checker, read_io.buf.getbuffer()
                    )
                    if problem is not None:
                        telemetry.counter_add("scheduler.read_verify_failures")
                        raise ReadVerificationError(
                            f"read of {req.path} failed digest verification "
                            f"twice ({problem}); persistent corruption at the "
                            "source — aborting instead of restoring bad bytes"
                        )
            buf = read_io.buf.getbuffer()
            nbytes = memoryview(buf).nbytes
            totals["bytes_read"] += nbytes
            ctx.note_bytes(nbytes)
            fetched_at = time.monotonic()
            return buf

        async def consume(_ctx, buf):
            if times is not None:
                restore_times.begin_consume(fetched_at)
            await req.buffer_consumer.consume_buffer(buf, executor)

        return read_one, consume

    for req in sorted(
        read_reqs, key=lambda r: -r.buffer_consumer.get_consuming_cost_bytes()
    ):
        read_one, consume = make_bodies(req)
        consume_node = Node("consume", consume, pool="consume", path=req.path)
        eng.add(
            Node(
                "read_io",
                read_one,
                cost_bytes=req.buffer_consumer.get_consuming_cost_bytes(),
                pool="io",
                path=req.path,
                successor=consume_node,
            )
        )

    try:
        await eng.run()
    except BaseException:
        # Error path: the engine sweep cancels in-flight reads/consumes
        # (crediting their reservations) and queued consumer thunks —
        # nothing may run against a torn-down pipeline.
        await eng.abort()
        pools.shutdown(cancel_queued=True)
        # Debug-ledger cross-check (chains onto the original failure).
        eng.assert_balanced("read pipeline abort")
        raise
    else:
        if owns_pools:
            pools.shutdown()
        eng.assert_balanced("read pipeline close")

    bytes_read = totals["bytes_read"]
    elapsed = time.monotonic() - begin_ts
    telemetry.gauge_max("scheduler.budget_hwm_bytes", eng.budget.high_water_bytes)
    if bytes_read:
        logger.info(
            "Rank %d read %.2f GB in %.2fs (%.2f GB/s)",
            rank,
            bytes_read / 1e9,
            elapsed,
            bytes_read / 1e9 / max(elapsed, 1e-9),
        )
    return {
        "bytes_read": float(bytes_read),
        "wall_s": elapsed,
        "requests": float(len(read_reqs)),
    }


def sync_execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    event_loop: asyncio.AbstractEventLoop,
    pools: Optional[PipelinePools] = None,
    digests: Optional[Dict[str, object]] = None,
    priority: Optional[Priority] = None,
) -> Dict[str, float]:
    return event_loop.run_until_complete(
        execute_read_reqs(
            read_reqs,
            storage,
            memory_budget_bytes,
            rank,
            pools=pools,
            digests=digests,
            priority=priority,
        )
    )
