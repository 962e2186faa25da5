"""Array write/read preparation: the D2H + serialization hot path.

TPU-native analogue of the reference's ``io_preparers/tensor.py:45-376``. The
reference's performance trick is overlapping CUDA D2H copies (run on a
GIL-dropping jit-scripted helper inside a thread pool) with storage I/O; the
XLA-native equivalent used here is:

1. ``jax.Array.copy_to_host_async()`` (the *hint*) — asks the device for the
   transfer without blocking the Python thread or the XLA stream. Inside a
   write pipeline the transfer lanes issue it, at the request's turn under
   their window a device (``d2h.TransferLanes``, ``d2h.HINT_WINDOW_BYTES``):
   never inside ``async_take``'s stall, and never the whole snapshot at
   once, which would make the job's next step wait behind all of it. A
   leaf an async take forked in pieces (:class:`PiecedArray`) is one
   transfer a piece, under the pieces' own smaller window, gathered into
   one host buffer of the leaf's size;
2. ``np.asarray(arr)`` on a lane's thread — resolves the (already
   in-flight) transfer off the event loop, so a few transfers and the storage
   writes interleave under the scheduler's memory budget.

Serialization is zero-copy for every dtype in ``SUPPORTED_DTYPES`` (including
bfloat16/fp8 via ml_dtypes); anything else falls back to pickle (the
reference's ``torch.save`` fallback, ``tensor.py:66-69``).
"""

from __future__ import annotations

import asyncio
import logging
import pickle
import time
from concurrent.futures import Executor
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from .. import d2h, device_programs, telemetry
from ..device_programs import PiecedArray
from ..io_types import BufferConsumer, BufferStager, BufferType, ReadReq, WriteReq
from ..manifest import ArrayEntry
from ..restore_times import consume_landed, run_consume_work
from ..serialization import (
    Serializer,
    array_as_bytes_view,
    array_from_bytes,
    array_nbytes,
    codec_for_raw_serializer,
    compress_framed,
    compress_payload,
    decode_framed_payload,
    decode_raw_payload,
    dtype_to_string,
    ensure_codec_available,
    is_raw_family,
    is_raw_serializable,
    raw_serializer_for_codec,
    string_to_dtype,
)
from ..utils import knobs

# Side-object suffix carrying a framed payload's compressed frame sizes
# (tiny JSON). Written by the same pipeline as the payload; read only by
# budgeted sub-reads (whole-object reads decode concatenated frames without
# a table).
FRAME_TABLE_SUFFIX = ".ftab"

logger = logging.getLogger(__name__)


def is_jax_array(obj: Any) -> bool:
    import jax

    return isinstance(obj, jax.Array)


def to_host(
    arr: Any, executor: Optional[Executor] = None, into: Optional[np.ndarray] = None
):
    """Kick off an async D2H transfer; return an awaitable resolver. The
    path of a stager driven outside a write pipeline (no lanes, no window).
    ``into``: as :func:`~..d2h.resolve_on_host` takes it."""
    if is_jax_array(arr):
        d2h.hint_copy_to_host(arr)

    async def resolve() -> np.ndarray:
        loop = asyncio.get_running_loop()
        if executor is not None:
            return await loop.run_in_executor(
                executor, d2h.resolve_on_host, arr, into
            )
        return d2h.resolve_on_host(arr, into)

    return resolve


async def _traced_to_host(
    arr: Any,
    executor: Optional[Executor],
    location: str,
    nbytes: int,
    into: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Resolve one device→host transfer, attributed as ``stage.d2h``.
    ``into``: ``arr`` is a piece of a leaf, to land in these bytes of the
    leaf's host buffer (``d2h.TransferLanes.start``), admitted under the
    pieces' window of the pipeline's kind.

    Inside a write pipeline (an active :class:`~..d2h.StagingContext`) the
    transfer waits for room in its device's hint window, is hinted, and
    resolves on the DEDICATED transfer-lane executor — never queued
    behind serialize/compress jobs on the staging pool — and the lane
    records the transfer interval for the stage-time decomposition. Outside
    a pipeline it falls back to :func:`to_host` on the given executor, with
    a ``stage.d2h`` span when a telemetry session is active (free
    None-checks otherwise)."""
    ctx = d2h.get_active()
    if ctx is not None:
        loop = asyncio.get_running_loop()
        return await ctx.lanes.start(
            arr,
            nbytes,
            loop,
            times=ctx.times,
            location=location,
            into=into,
            piece_window_bytes=ctx.piece_window_bytes,
        )
    tm = telemetry.get_active()
    if tm is None:
        return await to_host(arr, executor, into)()
    with tm.span("stage.d2h", "stage", path=location, nbytes=nbytes):
        host = await to_host(arr, executor, into)()
    tm.metrics.counter("d2h.bytes").add(nbytes)
    return host


async def _gather_pieces(
    arr: PiecedArray,
    executor: Optional[Executor],
    location: str,
    into: Optional[np.ndarray] = None,
) -> np.ndarray:
    """The pieced leaf whole in one host buffer: every piece is started
    through the lanes at once (each waits its turn under the pieces' window)
    and lands in its rows. The buffer is ``into`` (``arr.nbytes`` of lent
    ``uint8``, which is then what comes back: the C-order bytes of the leaf)
    or fresh pages of the leaf's own. Beyond the buffer, which the request's
    admission debited, the host holds at most one window of resolved pieces.
    A failing piece cancels the others and waits them out, so the window is
    balanced when the error leaves here."""
    host = into if into is not None else np.empty(arr.shape, dtype=arr.dtype)
    flat = host.reshape(-1).view(np.uint8)
    row_bytes = arr.nbytes // arr.shape[0]
    tasks = [
        asyncio.ensure_future(
            _traced_to_host(
                piece,
                executor,
                location,
                (r1 - r0) * row_bytes,
                into=flat[r0 * row_bytes : r1 * row_bytes],
            )
        )
        for piece, (r0, r1) in zip(arr.pieces, arr.ranges)
    ]
    try:
        await asyncio.gather(*tasks)
    except BaseException:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        raise
    return host


class ArrayBufferStager(BufferStager):
    def __init__(
        self,
        arr: Any,  # jax.Array | np.ndarray
        entry: ArrayEntry,
        is_async_snapshot: bool = False,
        whole_leaf: bool = False,
    ) -> None:
        self.arr = arr
        self.entry = entry
        self.is_async_snapshot = is_async_snapshot
        # ``arr`` is a leaf of the state as it stands, one storage object:
        # not a chunk's or a shard's slice. A synchronous take may cut such
        # a leaf on the device at its turn (``_stage_cut``).
        self.whole_leaf = whole_leaf
        # The staged buffer's pages, where the take's arena lent them.
        self._lease: Optional[Any] = None
        # Sole owner of level resolution, at construction (== prepare
        # time), never at stage time: a deferred background drain must not
        # re-read knobs whose env changed since (wrong level breaks the
        # fixed-level zstd determinism incremental dedup relies on; an
        # invalid ambient level would raise mid-drain).
        self.compression_level: Optional[int] = None
        if entry.serializer in (Serializer.RAW_ZSTD, Serializer.RAW_ZLIB):
            self.compression_level = knobs.get_compression_level(
                _codec=codec_for_raw_serializer(entry.serializer)
            )
        # Compressed frame sizes, published by stage_buffer for framed
        # entries; the companion FrameTableStager polls for it. A staging
        # failure publishes frame_error instead so the poller fails fast
        # rather than spinning as an orphaned task.
        self.frame_sizes: Optional[List[int]] = None
        self.frame_error: Optional[BaseException] = None
        # Set by the batcher when this request joins a member-framed
        # compressed slab: stage the RAW bytes (the slab compresses all
        # members together at the slab level); entry.serializer still
        # records the codec for the read side.
        self.stage_raw = False

    def rebind(self, arr: Any) -> None:
        """Point this stager at a new step's array and clear per-take state
        (frame publication) while keeping the structural
        plan — entry, compression level, slab membership (``stage_raw``) —
        exactly as prepared. The prepared-state cache's hit path: the new
        array must match the cached plan's shape/dtype (guaranteed by the
        cache's fingerprint key)."""
        self.arr = arr
        self.frame_sizes = None
        self.frame_error = None

    def unbind(self) -> None:
        """Drop the array reference between takes so a cached prepared
        state never pins device/host buffers past its pipeline's commit."""
        self.arr = None
        self.release_staged()

    def release_staged(self) -> None:
        lease, self._lease = self._lease, None
        if lease is not None:
            lease.give_back()

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        if not self.entry.frame_bytes:
            return await self._stage_inner(executor)
        try:
            return await self._stage_inner(executor)
        except BaseException as e:  # noqa: BLE001 - published, then re-raised
            # Any failure (D2H error, compressor OOM, cancellation) must
            # unblock the companion FrameTableStager's poll.
            self.frame_error = e
            raise

    async def _stage_inner(self, executor: Optional[Executor] = None) -> BufferType:
        # stage_raw (member of a compressed slab): the slab stager consumes
        # this buffer synchronously inside ITS staging call (copied into the
        # packed slab), so a zero-copy view is mutation-safe without the
        # async defensive copy below.
        serializer = Serializer.RAW if self.stage_raw else self.entry.serializer
        arr = self.arr
        ctx = d2h.get_active()
        times = ctx.times if ctx is not None else None
        location = self.entry.location
        if isinstance(arr, PiecedArray):
            host = await _gather_pieces(arr, executor, location)
            if times is not None:
                times.count_gather_pages(0, host.nbytes)
        elif is_jax_array(arr):
            host = None
            if (
                ctx is not None
                and ctx.arena is not None
                and self.whole_leaf
                and serializer == Serializer.RAW
                and not self.stage_raw
            ):
                host = await self._stage_cut(arr, ctx, executor, location)
            if host is None:
                host = await _traced_to_host(arr, executor, location, _nbytes_of(arr))
                if times is not None:
                    times.count_host_relaid(host)
        else:
            host = np.asarray(arr)
            if times is not None:
                times.count_host_relaid(host)
            if (
                self.is_async_snapshot
                and serializer == Serializer.RAW
                and not self.stage_raw
            ):
                # Host arrays stage *before* async_take returns, but the RAW
                # staged buffer is a zero-copy view that the background
                # write reads after training resumed — copy so training can
                # mutate the live array meanwhile (reference
                # ``tensor.py:254-264``). Compressed/pickled payloads are
                # consumed synchronously inside this staging call and the
                # output is independent bytes, so they skip the copy.
                host = host.copy()
            elif not host.flags["C_CONTIGUOUS"]:
                host = np.ascontiguousarray(host)
        if serializer == Serializer.RAW:
            # Zero-copy fast path: the staged buffer IS a memoryview of the
            # resolved host buffer — no serialization pass, no intermediate
            # bytes(). Downstream (plugin writes, the digest fold, slab
            # packing) all consume the buffer protocol
            # directly, so the only full sweeps over a RAW payload are the
            # transfer itself, the (optional) hash, and the storage write.
            t0 = time.monotonic()
            view = array_as_bytes_view(host)
            if times is not None:
                times.record(
                    "serialize", t0, time.monotonic(),
                    path=location, nbytes=view.nbytes,
                )
            return view
        if is_raw_family(self.entry.serializer):
            # Compress on the executor: seconds of zstd on a large shard
            # must not block the event loop that dispatches every other
            # request's transfers and writes.
            view = array_as_bytes_view(host)
            level = self.compression_level
            loop = asyncio.get_running_loop()
            if self.entry.frame_bytes:
                def framed():
                    with d2h.timed(times, "serialize", path=location) as work:
                        payload, sizes = compress_framed(
                            view,
                            self.entry.serializer,
                            level,
                            self.entry.frame_bytes,
                        )
                        work.sized(len(payload))
                    # Publish for the companion FrameTableStager (same
                    # pipeline, polls until this lands). Cross-thread by
                    # design: a single atomic reference store.
                    self.frame_sizes = sizes  # noqa: TSA701
                    return payload

                if executor is not None:
                    return await loop.run_in_executor(executor, framed)
                return framed()

            def compress():
                with d2h.timed(times, "serialize", path=location) as work:
                    payload = compress_payload(view, self.entry.serializer, level)
                    work.sized(len(payload))
                return payload

            if executor is not None:
                return await loop.run_in_executor(executor, compress)
            return compress()
        with d2h.timed(times, "serialize", path=location) as work:
            payload = pickle.dumps(host, protocol=pickle.HIGHEST_PROTOCOL)
            work.sized(len(payload))
        return payload

    async def _stage_cut(
        self, arr: Any, ctx: "d2h.StagingContext", executor: Optional[Executor], location: str
    ) -> Optional[np.ndarray]:
        """A synchronous take's big leaf (``ctx.arena``: no step runs beside
        this pipeline, and it owns an arena of host pages): where the fork
        would have cut it (``device_programs.leaf_cut``, the one predicate;
        ``whole_leaf`` says it stays one storage object), it
        is cut now, by the fork's movers, and its pieces cross under the
        pieces' window into a view of the arena that an earlier leaf of the
        take has used, or into fresh pages where the arena has no room worth
        waiting for. Returns the leaf's C-order bytes, or None where it is
        no such leaf, the device has no room for its pieces, or the kernel
        compiler refuses them: it then crosses whole, as before.

        The view is taken before the cut, so the pieces hold HBM only while
        they cross (``d2h.CUT_WINDOW_BYTES`` a device bounds them), and is
        given back by :meth:`release_staged` once hash and write are done
        with it, or here where the stage fails."""
        cut = device_programs.leaf_cut(arr)
        if cut is None:
            return None
        nbytes = _nbytes_of(arr)
        loop = asyncio.get_running_loop()
        lease = self._lease = ctx.arena().lease([nbytes], reads=1)
        pieced = views = None
        try:
            views = await lease.acquire()
            window = ctx.lanes.cut_window(next(iter(arr.devices())).id)
            await window.room(nbytes, d2h.CUT_WINDOW_BYTES, loop)
            try:
                pieced = device_programs.cut_in_stage(arr, cut)
                if pieced is not None:
                    host = await _gather_pieces(
                        pieced, executor, location, into=views[0] if views else None
                    )
            finally:
                window.done(nbytes)
        except BaseException as e:
            self.release_staged()
            # The program's own temporaries are allocated as it runs: a
            # device that ran out then says so at a piece's resolve.
            if not device_programs.is_oom_error(e):
                raise
            logger.info("no room on the device for the pieces of %s: %s", location, e)
            pieced = None
        if pieced is None:
            self.release_staged()
            ctx.times.count_sync_cut_refused()
            return None
        ctx.times.count_sync_cut(nbytes, cut.relaid)
        recycled = lease.recycled_bytes if views else 0
        ctx.times.count_gather_pages(recycled, nbytes - recycled)
        return host

    def get_staging_cost_bytes(self) -> int:
        # A pieced leaf costs its one host buffer, like a whole one; the
        # resolved pieces not yet copied into it are at most one window
        # (``d2h.PIECE_WINDOW_BYTES``) a device beyond every debit.
        if not is_raw_family(self.entry.serializer):
            return _nbytes_of(self.arr)
        nbytes = array_nbytes(self.entry.shape, self.entry.dtype)
        if self.entry.serializer != Serializer.RAW:
            # Peak transient footprint holds the raw host bytes AND the
            # compressed output simultaneously; incompressible data makes
            # that ~2x raw — the budget must see the true peak.
            return 2 * nbytes
        return nbytes


class PollingTableStager(BufferStager):
    """Base for ``.ftab`` side-object stagers: polls a main stager's
    published ``frame_sizes`` and encodes a JSON table.

    The sizes exist only after the main stager compressed the payload (which
    is why they can't live in the manifest — it is gathered before staging),
    so this stager polls the main stager's published result. Both requests
    run in the same pipeline; the poll holds no executor thread and the main
    request always runs (dedup link-in decisions happen after staging), so
    this terminates. The generous deadline guards that invariant: if a
    future change ever drops/filters the payload req from this rank's
    pipeline, fail loudly with the payload location instead of hanging the
    pipeline forever (ADVICE round 3, item 2).
    """

    POLL_TIMEOUT_S = 1800.0

    def __init__(self, main: Any, described: str) -> None:
        self.main = main  # must expose frame_sizes / frame_error
        self.described = described

    def _table(self) -> dict:
        raise NotImplementedError

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        import json
        import time

        deadline = time.monotonic() + self.POLL_TIMEOUT_S
        while self.main.frame_sizes is None:
            if self.main.frame_error is not None:
                raise RuntimeError(
                    f"frame table for {self.described} unavailable: "
                    "payload staging failed"
                ) from self.main.frame_error
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"frame table for {self.described} never materialized: "
                    "the payload write request did not stage within the "
                    "deadline — was it dropped from this rank's pipeline?"
                )
            await asyncio.sleep(0.005)
        return json.dumps(self._table()).encode()

    def get_staging_cost_bytes(self) -> int:
        # ~8 digits per frame size; a 4 GB payload at 8 MiB frames is ~4 KB.
        return 16384


class FrameTableStager(PollingTableStager):
    """``.ftab`` of a uniformly framed payload: ``{"frame_bytes", "sizes"}``."""

    def __init__(self, main: ArrayBufferStager) -> None:
        super().__init__(main, described=main.entry.location)

    def _table(self) -> dict:
        return {
            "frame_bytes": self.main.entry.frame_bytes,
            "sizes": self.main.frame_sizes,
        }


def plan_frame_groups(
    frame_sizes: Sequence[int],
    frame_bytes: int,
    raw_begin: int,
    raw_end: int,
    budget: Optional[int],
) -> List[Tuple[int, int, int, int]]:
    """Split the raw range [raw_begin, raw_end) into frame-aligned groups.

    Returns ``(comp_begin, comp_end, group_raw_begin, group_raw_end)`` per
    group, where the comp range indexes the concatenated framed payload and
    each group's raw coverage is <= max(budget, frame_bytes) (a single frame
    wider than the budget is admitted whole — the usual one-over-budget
    escape hatch).
    """
    prefix = [0]
    for s in frame_sizes:
        prefix.append(prefix[-1] + int(s))
    first = raw_begin // frame_bytes
    last = (raw_end + frame_bytes - 1) // frame_bytes  # exclusive
    per_group = max(1, (budget or raw_end) // frame_bytes)
    groups: List[Tuple[int, int, int, int]] = []
    i = first
    while i < last:
        j = min(i + per_group, last)
        groups.append(
            (prefix[i], prefix[j], i * frame_bytes, min(j * frame_bytes, raw_end))
        )
        i = j
    return groups


class FramedSliceConsumer(BufferConsumer):
    """Decompresses one group of frames and delivers the requested raw slice.

    ``deliver`` receives a memoryview of raw bytes covering
    [raw_begin, raw_end) of the entry's serialized layout; the group's
    frames may cover a superset (frame alignment), which is sliced off.
    """

    # Read-merging must never coalesce a BIG array's framed groups: their
    # COMPRESSED ranges are adjacent, so a compressed-span cap would
    # re-create the whole-object decode the budget split exists to avoid.
    # Checked (via any wrapper's proxy) by ``batcher.batch_read_requests``.
    # Member-framed SLAB reads opt out (``merge_exempt=False``): each
    # member decodes independently, so adjacent members' compressed ranges
    # merge into one ranged read safely.
    merge_exempt = True

    def __init__(
        self,
        serializer: str,
        group_raw_begin: int,
        raw_begin: int,
        raw_end: int,
        deliver: Callable[[memoryview], None],
        decoded_raw_bytes: Optional[int] = None,
        merge_exempt: bool = True,
    ) -> None:
        self.serializer = serializer
        self.group_raw_begin = group_raw_begin
        self.raw_begin = raw_begin
        self.raw_end = raw_end
        self.deliver = deliver
        # Frame alignment can force decoding more raw bytes than the
        # requested slice; the budget must see the true peak.
        self.decoded_raw_bytes = decoded_raw_bytes
        self.merge_exempt = merge_exempt

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        def work() -> None:
            raw = decode_framed_payload(buf, self.serializer)
            off = self.raw_begin - self.group_raw_begin
            self.deliver(
                memoryview(raw)[off : off + (self.raw_end - self.raw_begin)]
            )

        await run_consume_work(work, executor)

    def get_consuming_cost_bytes(self) -> int:
        # Compressed group + decompressed raw coexist during decode.
        return 2 * (self.decoded_raw_bytes or (self.raw_end - self.raw_begin))


def _flat_range_deliver(target: np.ndarray, begin: int, end: int):
    flat = target.view(np.uint8).reshape(-1)

    def deliver(mv: memoryview) -> None:
        flat[begin:end] = np.frombuffer(mv, dtype=np.uint8)

    return deliver


def _nbytes_of(arr: Any) -> int:
    nbytes = getattr(arr, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    return int(np.asarray(arr).nbytes)


def entry_np_dtype(dtype: str, serializer: str) -> np.dtype:
    """Numpy dtype for an entry: raw-family entries use the canonical table;
    pickle entries recorded ``str(np.dtype)`` (e.g. ``datetime64[D]``,
    ``object``)."""
    if is_raw_family(serializer):
        return string_to_dtype(dtype)
    return np.dtype(dtype)


def entry_cost_bytes(entry: ArrayEntry) -> int:
    """Best-effort host-memory cost of staging/consuming one array entry.

    Compressed entries cost ~2x on the consume side: the compressed buffer
    and the decoded raw bytes coexist during decompression.
    """
    try:
        n = 1
        for d in entry.shape:
            n *= int(d)
        n *= entry_np_dtype(entry.dtype, entry.serializer).itemsize
        if is_raw_family(entry.serializer) and entry.serializer != Serializer.RAW:
            n *= 2
        return n
    except Exception:
        return 1024 * 1024


def landing_view(entry: ArrayEntry, dst: np.ndarray) -> Optional[memoryview]:
    """``dst``'s bytes as the destination of ``entry``'s read
    (``BufferConsumer.destination``), where filling them is all a consumer
    would do: an unframed RAW payload of ``dst``'s own dtype and shape, and
    ``dst`` C-contiguous and writable. Else None."""
    if entry.serializer != Serializer.RAW or entry.frame_bytes:
        return None
    if not (dst.nbytes and dst.flags.c_contiguous and dst.flags.writeable):
        return None
    if dst.dtype != string_to_dtype(entry.dtype) or list(dst.shape) != list(
        entry.shape
    ):
        return None
    return memoryview(dst.reshape(-1).view(np.uint8))


async def consumed_by_landing(buf: BufferType, dest: Optional[memoryview]) -> bool:
    """Whether ``buf`` is ``dest``'s own memory (same address, same length):
    the read landed in the consumer's destination, which is then counted
    and stamped as consumed (``restore_times.consume_landed``) with nothing
    copied. False where no destination was offered or the plugin delivered
    a buffer of its own: the consumer copies as ever."""
    if dest is None:
        return False
    mv = memoryview(buf)
    if mv.nbytes != dest.nbytes or (
        np.frombuffer(mv, dtype=np.uint8).ctypes.data
        != np.frombuffer(dest, dtype=np.uint8).ctypes.data
    ):
        return False
    await consume_landed(dest.nbytes)
    return True


class ArrayBufferConsumer(BufferConsumer):
    """Deserializes one buffer and copies it into a host target buffer.

    ``fresh_target``: the restore allocated ``target`` itself and nobody
    sees it before the restore ends, so the read may land in it
    (:meth:`destination`); a caller's live array restored in place is not."""

    def __init__(
        self, target: np.ndarray, entry: ArrayEntry, fresh_target: bool = False
    ) -> None:
        self.target = target  # writable, C-contiguous host array
        self.entry = entry
        self.fresh_target = fresh_target

    def destination(self) -> Optional[memoryview]:
        return landing_view(self.entry, self.target) if self.fresh_target else None

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        if await consumed_by_landing(buf, self.destination()):
            return

        def work() -> None:
            if is_raw_family(self.entry.serializer):
                decode = (
                    decode_framed_payload
                    if self.entry.frame_bytes
                    else decode_raw_payload
                )
                raw = decode(buf, self.entry.serializer)
                src = array_from_bytes(raw, self.entry.dtype, self.entry.shape)
            else:
                src = pickle.loads(bytes(buf))
            np.copyto(self.target, src, casting="no")

        await run_consume_work(work, executor)

    def get_consuming_cost_bytes(self) -> int:
        return entry_cost_bytes(self.entry)


class ChunkedReadConsumer(BufferConsumer):
    """Consumes one byte-range of a raw-serialized array into the flat target.

    Enables budget-capped reads of arrays larger than host memory allows at
    once (reference ``tensor.py:120-166``; exercised by ``read_object`` with
    ``memory_budget_bytes``).
    """

    def __init__(self, target: np.ndarray, byte_range: Tuple[int, int]) -> None:
        self.target = target
        self.byte_range = byte_range

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        begin, end = self.byte_range
        flat = self.target.view(np.uint8).reshape(-1)

        def work() -> None:
            flat[begin:end] = np.frombuffer(memoryview(buf), dtype=np.uint8)

        await run_consume_work(work, executor)

    def get_consuming_cost_bytes(self) -> int:
        return self.byte_range[1] - self.byte_range[0]


def _member_deliver(target: np.ndarray, entry: ArrayEntry):
    """Deliver one slab member's raw bytes into the host target."""

    def deliver(mv: memoryview) -> None:
        src = array_from_bytes(mv, entry.dtype, entry.shape)
        np.copyto(target, src, casting="no")

    return deliver


def _member_framed_reads(
    entry: ArrayEntry, target: np.ndarray, frame_table
) -> List[ReadReq]:
    """Read one member of a member-framed compressed slab.

    With the slab's ``.ftab`` (``{"raw_sizes": [...], "sizes": [...]}``) the
    member's raw range resolves to its covering frames and a compressed
    byte-range read; without it (side object lost), degrade to reading and
    decoding the WHOLE slab and slicing the member out — slower, never a
    failed restore."""
    a, b = entry.raw_range
    if isinstance(frame_table, dict):
        raw_sizes = frame_table["raw_sizes"]
        comp_sizes = frame_table["sizes"]
        rprefix, cprefix = [0], [0]
        for r in raw_sizes:
            rprefix.append(rprefix[-1] + int(r))
        for c in comp_sizes:
            cprefix.append(cprefix[-1] + int(c))
        # Covering frame run [i, j): frames are member-aligned, so a lands
        # on a frame boundary for well-formed manifests; tolerate interior
        # starts anyway.
        i = max(0, next((k for k in range(len(raw_sizes)) if rprefix[k + 1] > a), 0))
        j = next(
            (k + 1 for k in range(i, len(raw_sizes)) if rprefix[k + 1] >= b),
            len(raw_sizes),
        )
        return [
            ReadReq(
                path=entry.location,
                buffer_consumer=FramedSliceConsumer(
                    entry.serializer,
                    group_raw_begin=rprefix[i],
                    raw_begin=a,
                    raw_end=b,
                    deliver=_member_deliver(target, entry),
                    decoded_raw_bytes=rprefix[j] - rprefix[i],
                    merge_exempt=False,
                ),
                byte_range=(cprefix[i], cprefix[j]),
            )
        ]
    return [
        ReadReq(
            path=entry.location,
            buffer_consumer=FramedSliceConsumer(
                entry.serializer,
                group_raw_begin=0,
                raw_begin=a,
                raw_end=b,
                deliver=_member_deliver(target, entry),
                # The whole slab decodes per member here; without the table
                # its raw extent is unknown, so bill the slab threshold
                # (slabs close at it) — over-billing serializes these
                # degraded reads through the budget instead of letting N
                # concurrent whole-slab decodes blow past it.
                decoded_raw_bytes=max(
                    knobs.get_slab_size_threshold_bytes(), b - a
                ),
            ),
        )
    ]


class ArrayIOPreparer:
    @staticmethod
    def prepare_write(
        storage_path: str,
        arr: Any,
        replicated: bool = False,
        is_async_snapshot: bool = False,
        whole_leaf: bool = False,
    ) -> Tuple[ArrayEntry, List[WriteReq]]:
        host_like = arr  # dtype/shape probes work on jax and numpy alike
        dtype = np.dtype(host_like.dtype)
        if is_raw_serializable(dtype):
            serializer = raw_serializer_for_codec(knobs.get_compression())
        else:
            serializer = Serializer.PICKLE
        frame_bytes = None
        if serializer in (Serializer.RAW_ZSTD, Serializer.RAW_ZLIB):
            f = knobs.get_compression_frame_bytes()
            raw_nbytes = array_nbytes(
                list(host_like.shape), dtype_to_string(dtype)
            )
            if f > 0 and raw_nbytes > f:
                frame_bytes = f
        entry = ArrayEntry(
            location=storage_path,
            serializer=serializer,
            dtype=dtype_to_string(dtype) if is_raw_family(serializer) else str(dtype),
            shape=list(host_like.shape),
            replicated=replicated,
            frame_bytes=frame_bytes,
        )
        stager = ArrayBufferStager(arr, entry, is_async_snapshot, whole_leaf)
        reqs = [WriteReq(path=storage_path, buffer_stager=stager)]
        if frame_bytes:
            reqs.append(
                WriteReq(
                    path=storage_path + FRAME_TABLE_SUFFIX,
                    buffer_stager=FrameTableStager(stager),
                )
            )
        return entry, reqs

    @staticmethod
    def prepare_read(  # spmd-pure
        entry: ArrayEntry,
        target: np.ndarray,
        buffer_size_limit_bytes: Optional[int] = None,
        frame_table: Optional[List[int]] = None,
        fresh_target: bool = False,
    ) -> List[ReadReq]:
        """Plan reads filling ``target`` (a writable host array).
        ``fresh_target``: the caller allocated it for this read and shows it
        to nobody before the read ends (``ArrayBufferConsumer``).

        ``frame_table`` (the compressed frame sizes from the entry's
        ``.ftab`` side object) enables budgeted sub-reads of framed
        compressed entries: each read fetches one group of frames and
        decompresses only those. For member-framed slab members
        (``entry.raw_range``) the table is a dict carrying per-frame raw AND
        compressed sizes; the member's raw range maps to exactly its own
        covering frames.
        """
        ensure_codec_available(entry.serializer)
        if getattr(entry, "raw_range", None) is not None:
            return _member_framed_reads(entry, target, frame_table)
        if (
            entry.frame_bytes
            and frame_table is not None
            and buffer_size_limit_bytes is not None
            and array_nbytes(entry.shape, entry.dtype) > buffer_size_limit_bytes
        ):
            base = entry.byte_range[0] if entry.byte_range else 0
            raw_total = array_nbytes(entry.shape, entry.dtype)
            reqs = []
            for cb, ce, grb, gre in plan_frame_groups(
                frame_table,
                entry.frame_bytes,
                0,
                raw_total,
                buffer_size_limit_bytes,
            ):
                reqs.append(
                    ReadReq(
                        path=entry.location,
                        buffer_consumer=FramedSliceConsumer(
                            entry.serializer,
                            group_raw_begin=grb,
                            raw_begin=grb,
                            raw_end=gre,
                            deliver=_flat_range_deliver(target, grb, gre),
                        ),
                        byte_range=(base + cb, base + ce),
                    )
                )
            return reqs
        if entry.serializer != Serializer.RAW:
            # Pickled and (unframed, or unbudgeted) compressed payloads:
            # read the whole object, ranged only to a slab-relocated span if
            # the entry records one.
            return [
                ReadReq(
                    path=entry.location,
                    buffer_consumer=ArrayBufferConsumer(target, entry),
                    byte_range=tuple(entry.byte_range)
                    if entry.byte_range
                    else None,
                )
            ]
        base_range = entry.byte_range or [0, array_nbytes(entry.shape, entry.dtype)]
        total = base_range[1] - base_range[0]
        if buffer_size_limit_bytes is None or total <= buffer_size_limit_bytes:
            return [
                ReadReq(
                    path=entry.location,
                    buffer_consumer=ArrayBufferConsumer(
                        target, entry, fresh_target
                    ),
                    byte_range=(base_range[0], base_range[1]),
                )
            ]
        # Budget-capped: split into byte-range reads landing directly in the
        # target's flat view. Ranges are itemsize-aligned by construction.
        itemsize = target.dtype.itemsize
        per_read = max(
            itemsize, buffer_size_limit_bytes - buffer_size_limit_bytes % itemsize
        )
        read_reqs = []
        for begin in range(0, total, per_read):
            end = min(begin + per_read, total)
            read_reqs.append(
                ReadReq(
                    path=entry.location,
                    buffer_consumer=ChunkedReadConsumer(target, (begin, end)),
                    byte_range=(base_range[0] + begin, base_range[0] + end),
                )
            )
        return read_reqs


