"""Parallel device→host transfer lanes + stage-time attribution.

A transfer has two halves: ``copy_to_host_async()`` asks the device for it
(the *hint*), and ``np.asarray`` waits for the host copy (the *resolve*).
Two cooperating pieces:

- :class:`TransferLanes` — N resolving lanes (a dedicated
  ``ThreadPoolExecutor``, knob ``TORCHSNAPSHOT_TPU_D2H_LANES``) behind a
  **window a device**: a transfer is hinted only while the bytes hinted and
  not yet resolved on its device stay under :data:`HINT_WINDOW_BYTES`; one
  bigger than the window goes alone; the others wait their turn in the order
  they were admitted and are hinted as resolves make room. Nothing is hinted
  inside ``async_take``'s stall, and a request is hinted at its turn, not at
  its admission: a take that hands the device the whole snapshot at once
  makes the job's next step (and its own 4-byte read of the loss) wait behind
  all of it — 0.9-1.3 s behind 3.1-3.2 GB on a v5e, against 0.00-0.04 s
  under the window (``PERF.md`` section 6, PR 33's probe). The resolved host bytes are
  debited by the request's own admission, so no host copy exists that the
  memory budget has not seen.
  **The grain.** A leaf that ``async_take`` forked and that is over
  :data:`PIECE_BYTES` reaches the lanes as row-range pieces the fork itself
  wrote, every bit kept, by one of two movers inside the one fork program:
  DMAs of whole HBM tiles (``device_programs._cut_rows``) or, where the leaf's
  shape is off the tiling (a width of 1856 or 10304, 1001 rows: a leaf the
  device may hold column first, whose host copy would come in that order
  and be re-laid by a strided copy on the drain's event loop), integer
  copies that re-lay each range row-major on the device
  (``device_programs._relay_rows``). Either way a piece's host bytes are the
  C-order bytes of its rows. A piece is admitted under
  :data:`PIECE_WINDOW_BYTES`, and the lane that resolved it copies it into
  its rows of the leaf's one host buffer and drops it, so a few tens of MiB
  are in flight where whole leaves put hundreds: the job's steps beside the
  drain lose a third to a half of what they lost (``PERF.md`` section 6,
  PR 39). **A synchronous take** has no fork and no step beside it: its
  stage cuts the same leaves (``device_programs.leaf_cut``, the one predicate)
  at their turn, a leaf at a time, by the same movers
  (``device_programs.cut_in_stage``), while the pieces cut and not yet
  gathered hold at most :data:`CUT_WINDOW_BYTES` of HBM a device; they
  cross under a wider window (:data:`SYNC_PIECE_WINDOW_BYTES`) and are
  gathered into a view of the take's arena of host pages, handed from
  leaf to leaf as hash and write finish with them (``host_arena.py``: a
  first touch costs several times a write into a used page, and such a
  take is nothing but its stage and its writes). A cut the device has no
  room for, or the kernel compiler refuses, leaves that leaf whole.
  **What still crosses whole**, under :data:`HINT_WINDOW_BYTES` as before,
  and is re-laid on the host where the device does not hold it row-major
  (``stage.host_relaid_bytes``): a sharded leaf, a chunk of a leaf split
  into several objects, a compressed entry, a leaf at or under the piece
  size, a leaf of one row or whose rows fill no whole number of 128 lanes,
  bool / float16 / float8 / 64-bit leaves, and a bfloat16 leaf off the
  tiling that the device holds in no whole tiles either
  (``device_programs.piece_row_ranges``, ``device_piece_cut``). The
  lanes tell the two apart by what they are handed. Beyond a leaf's buffer
  the host holds at most one window of resolved pieces.
- :class:`StageTimes` — a thread-safe sink for the staging stream's
  sub-phase intervals (``d2h`` / ``serialize`` / ``hash`` / ``gather``). The
  scheduler derives ``stage_d2h_s``/``stage_serialize_s``/``stage_hash_s``/
  ``stage_gather_s`` from these by the same interval-union algebra as the
  stage/io streams, so the monolithic ``stage_busy`` decomposes in drain
  stats, persisted telemetry artifacts, and bench output. The ``d2h``
  interval is a lane's resolve, not the wait for room in the window; for a
  piece it holds the ``gather`` interval, the copy into the leaf's buffer.
  With a telemetry session active the same intervals are exported as
  ``stage.d2h``/``stage.serialize``/``stage.hash``/``stage.gather`` spans.

The write pipeline activates a :class:`StagingContext` (lanes + times) via a
``contextvars.ContextVar`` around staging-task creation — the same pattern
telemetry uses — so stagers pick it up with one ``get_active()`` call and
degrade gracefully (no lanes, no recording) when driven outside a pipeline.
"""

from __future__ import annotations

import collections
import contextvars
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from .telemetry import core as telemetry_core
from .utils import knobs

logger = logging.getLogger(__name__)

# The most bytes hinted and not yet resolved on one device. From the probe of
# PR 33 on a v5e (``PERF.md`` section 6): beside a donated step that reads its
# loss, with 3.1-3.2 GB of whole leaves to move, 512 MiB adds 0.13-0.26 s to
# the job's steps per take where the whole snapshot hinted at once adds
# 1.2-1.4 s. What the steps lose grows with the window (in the benchmark's
# own cells 768 MiB and 1 GiB kept 2-6 points less of the step rate than
# 512 MiB), and the transfers' rate falls with it: one whole leaf in flight
# reads 0.8-1.0 GB/s, three or four 1.0-1.4, all of them 1.45-1.65, so
# 256 MiB or one leaf at a time would halve it. A value, not a knob: no
# caller wants the burst back.
HINT_WINDOW_BYTES = 512 * 1024 * 1024

# The grain. A forked leaf over PIECE_BYTES leaves the fork as row-range
# pieces of at most that size (``device_programs.piece_row_ranges``; one
# unit of rows where a unit is bigger), and
# pieces are admitted under PIECE_WINDOW_BYTES a device: four of them. From
# PR 39's runs on a v5e (``PERF.md`` section 6), 3.24 GB of params saved every
# 44 donated steps of 0.206 s beside the storage writes: whole leaves under
# 512 MiB cost the steps 0.64-0.92 s a take (``goodput_pct`` 89.2-91.1);
# pieces of 16 MiB under 64 MiB 0.21-0.42 s (95.7-96.2) with the drain at
# 4.6-4.9 s against 5.1-5.4; 16 under 128, 8 under 64 and 8 under 128 read
# 94.5-95.2; 32 under 128 92.1; 32 under 64 94.5-98.3 with the drain at
# 6.1-6.2 s, the transfers pacing it at 0.55 GB/s. What the steps lose
# falls with the bytes in flight, and so does the transfers' rate: under
# 64 MiB they keep the pace of the storage writes (0.77-0.79 GB/s), which
# pace the drain either way. The window does not buy the 3-4 GB/s that small
# transfers reach alone: each piece is copied into its leaf's buffer, and
# the first write into fresh pages runs at 1.0 GB/s a thread on that machine
# (recycled buffers moved 1.0-1.8 GB/s and cost the steps 0.33-2.3 s a take).
# Values, not knobs.
PIECE_BYTES = 16 * 1024 * 1024
PIECE_WINDOW_BYTES = 64 * 1024 * 1024

# A synchronous take has no fork, so its big leaves are cut at their turn in
# the stage, by the fork's own movers, a leaf at a time
# (``device_programs.cut_in_stage``), and the pieces are a second copy of the
# leaf in HBM until they have crossed. CUT_WINDOW_BYTES bounds the pieces of
# the leaves cut and not yet gathered on one device (one leaf bigger than
# the window goes alone): three 160 MB stacks, enough that the next leaf's
# pieces exist while the last of this one's cross. No step runs beside such
# a take, so its pieces are admitted under a wider window than the drain's:
# SYNC_PIECE_WINDOW_BYTES (chosen on a v5e, ``CHANGES.md`` PR 48). Values
# chosen by the take's kind, not knobs.
CUT_WINDOW_BYTES = 512 * 1024 * 1024
SYNC_PIECE_WINDOW_BYTES = 256 * 1024 * 1024


def resolve_on_host(
    arr: Any,
    into: Optional[np.ndarray] = None,
    times: Optional["StageTimes"] = None,
    path: str = "",
) -> np.ndarray:
    """Wait for ``arr``'s host copy. ``into`` (a writable ``uint8`` view of
    ``arr``'s size) makes ``arr`` a piece of a leaf: it is copied there and
    dropped, its device buffer and jax's cached host value alike, and
    ``into`` is returned. The copy is its own ``gather`` interval of
    ``times``: the first touch of the leaf's fresh pages, apart from the
    runtime's resolve before it."""
    host = np.asarray(arr)
    if into is None:
        return host
    if times is not None:
        times.count_host_relaid(host)  # ``reshape(-1)`` below would re-lay it
    # As bytes: one memcpy with the GIL released, whatever the dtype. Copied
    # before the piece is dropped, so a backend whose host value aliases the
    # device buffer is safe too.
    with timed(times, "gather", path=path, nbytes=into.nbytes):
        into[:] = host.reshape(-1).view(np.uint8)
    arr.delete()
    return into


def hint_copy_to_host(arr: Any) -> None:
    """Issue ``arr``'s D2H transfer now so a later ``np.asarray`` finds it
    in flight. Every backend of the installed jax implements it; any failure
    is a real transfer error and propagates — retrying it silently as a
    blocking ``np.asarray`` would lose the overlap and hide the device-side
    error until it resurfaces somewhere far less attributable."""
    arr.copy_to_host_async()


class StageTimes:
    """Thread-safe recorder of staging sub-phase intervals.

    ``record`` is called from the event loop (await-measured blocks) and
    from lane/staging/hash executor threads (thunk-measured blocks) alike;
    appends take a lock, matching the trace buffer's own discipline. The
    telemetry session is captured at construction because executor threads
    don't inherit the activation contextvar."""

    KINDS = ("d2h", "serialize", "hash", "gather")

    def __init__(self, tm: Optional[Any] = None) -> None:
        # ``tm``: the op's telemetry.Telemetry session (or None when off).
        self._tm = tm
        self._lock = threading.Lock()
        self._intervals: Dict[str, List[Tuple[float, float, int]]] = {
            k: [] for k in self.KINDS
        }
        self.host_relaid_bytes = 0
        # A synchronous take's leaves cut in the stage (``stage.sync_cut_*``)
        # and, of the bytes gathered into one host buffer a leaf, those that
        # landed in pages an earlier leaf had used and in fresh ones
        # (``stage.recycled_bytes`` / ``stage.fresh_bytes``).
        self.sync_cut_leaves = 0
        self.sync_cut_bytes = 0
        self.sync_cut_relaid_bytes = 0
        self.sync_cut_refused = 0
        self.recycled_bytes = 0
        self.fresh_bytes = 0

    def count_sync_cut(self, nbytes: int, relaid: bool) -> None:
        with self._lock:
            self.sync_cut_leaves += 1
            self.sync_cut_bytes += nbytes
            if relaid:
                self.sync_cut_relaid_bytes += nbytes

    def count_sync_cut_refused(self) -> None:
        with self._lock:
            self.sync_cut_refused += 1

    def count_gather_pages(self, recycled: int, fresh: int) -> None:
        with self._lock:
            self.recycled_bytes += recycled
            self.fresh_bytes += fresh

    def count_host_relaid(self, host: np.ndarray) -> None:
        """``stage.host_relaid_bytes``: a leaf the device holds in another
        order than row-major reaches the host in that order (as does a
        strided host array), and the stage makes it contiguous by a strided
        copy, a whole leaf's on the drain's event loop
        (``serialization.array_as_bytes_view``): what the fork's re-laying
        cut spares the big forked leaves (``device_programs._relay_rows``)."""
        if not host.flags["C_CONTIGUOUS"]:
            with self._lock:
                self.host_relaid_bytes += host.nbytes

    def record(
        self,
        kind: str,
        t0: float,
        t1: float,
        path: str = "",
        nbytes: int = 0,
        span: Optional[str] = None,
        device: Optional[int] = None,
    ) -> None:
        # ``device``: id of the single device a d2h transfer read from, when
        # there is one — ``d2h.device_bytes.<id>`` shows whether a
        # multi-device drain pulls from every device or queues on the first.
        # ``span`` overrides the exported span name while the interval still
        # joins ``kind``'s sub-stream — parallel chunk hashes export as
        # ``stage.hash_chunk`` spans but stay inside ``stage_hash_s``.
        with self._lock:
            self._intervals[kind].append((t0, t1, nbytes))
        tm = self._tm
        if tm is not None:
            tm.add_span(
                span or f"stage.{kind}",
                "stage",
                t0,
                t1 - t0,
                {"path": path, "nbytes": nbytes},
            )
            if kind == "d2h":
                tm.metrics.counter("d2h.bytes").add(nbytes)
                if device is not None:
                    tm.metrics.counter(f"d2h.device_bytes.{device}").add(nbytes)

    def intervals(self) -> Dict[str, List[Tuple[float, float, int]]]:
        """A snapshot copy per kind (safe to merge/clip while staging runs);
        each interval as ``(t0, t1, nbytes)``, 0 where no size was given."""
        with self._lock:
            return {k: list(v) for k, v in self._intervals.items()}


class timed:
    """``with d2h.timed(times, kind, ...)`` around one stretch of staging
    work that is synchronous on the calling thread (a lane's resolve, the
    copy of a resolved piece into its leaf, a compress, a whole-leaf hash):
    the interval is recorded as
    :meth:`StageTimes.record` would and, under a telemetry session, the
    stretch is also a ``tss.stage.<kind>`` event of a running profiler
    trace. ``sized(n)`` inside the body gives the bytes where only the
    work's result says them. ``times`` None: nothing is recorded. Per-chunk
    work (``stage.hash_chunk``) stays with ``record``: too many events for
    a trace."""

    __slots__ = ("_times", "_kind", "_path", "_nbytes", "_device", "_t0", "_ann")

    def __init__(
        self,
        times: Optional[StageTimes],
        kind: str,
        path: str = "",
        nbytes: int = 0,
        device: Optional[int] = None,
    ) -> None:
        self._times = times
        self._kind = kind
        self._path = path
        self._nbytes = nbytes
        self._device = device
        self._t0 = 0.0
        self._ann: Optional[Any] = None

    def sized(self, nbytes: int) -> None:
        self._nbytes = nbytes

    def __enter__(self) -> "timed":
        times = self._times
        if times is not None:
            if times._tm is not None:
                self._ann = telemetry_core.open_annotation(f"stage.{self._kind}")
            self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        times = self._times
        if times is not None:
            t1 = time.monotonic()
            if self._ann is not None:
                self._ann.__exit__(exc_type, exc, tb)
            if exc_type is None:
                times.record(
                    self._kind, self._t0, t1,
                    path=self._path, nbytes=self._nbytes, device=self._device,
                )
        return False


class _DeviceWindow:
    """One device's bytes admitted and not yet done (transfers hinted and
    unresolved; a synchronous take's leaves cut and not yet gathered), and
    those waiting for room: ``(future, nbytes, limit)`` in the order they
    asked, ``limit`` the window the request is admitted under (a piece's or
    a whole leaf's). Touched on the event loop only, so it needs no lock."""

    __slots__ = ("ahead", "waiting", "hwm", "waits")

    def __init__(self) -> None:
        self.ahead = 0
        self.waiting: Deque[Tuple[Any, int, int]] = collections.deque()
        self.hwm = 0  # the most ever admitted at once
        self.waits = 0  # requests that waited for room

    def _fits(self, nbytes: int, limit: int) -> bool:
        return self.ahead == 0 or self.ahead + nbytes <= limit

    def _admit(self, nbytes: int) -> None:
        self.ahead += nbytes
        self.hwm = max(self.hwm, self.ahead)

    async def room(self, nbytes: int, limit: int, loop) -> None:
        """Wait until ``nbytes`` fit under ``limit`` (one request bigger
        than the window goes alone), in the order asked."""
        if not self.waiting and self._fits(nbytes, limit):
            self._admit(nbytes)
            return
        self.waits += 1
        turn = loop.create_future()
        self.waiting.append((turn, nbytes, limit))
        try:
            await turn
        except BaseException:
            # Cancelled (an abort's sweep). Still in line: ``_pump`` drops
            # the cancelled turn when it comes up. Given room in the same
            # turn of the loop: hand it on.
            if not turn.cancelled():
                self.done(nbytes)
            raise

    def done(self, nbytes: int) -> None:
        self.ahead -= nbytes
        self._pump()

    def _pump(self) -> None:
        """Give room to the requests at the head of the line, in order."""
        while self.waiting:
            turn, nbytes, limit = self.waiting[0]
            if not turn.cancelled():
                if not self._fits(nbytes, limit):
                    break
                self._admit(nbytes)
                turn.set_result(None)
            self.waiting.popleft()


class TransferLanes:
    """N concurrent D2H resolution lanes (a dedicated transfer executor)
    behind a hint window a device (:data:`HINT_WINDOW_BYTES`).

    The windows are touched on the event loop only (``start`` runs there and
    the lanes only resolve), so they need no lock; the executor's threads
    never see them."""

    def __init__(self, lanes: Optional[int] = None) -> None:
        self.lane_count = lanes if lanes is not None else knobs.get_d2h_lanes()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._windows: Dict[Optional[int], _DeviceWindow] = {}
        # A synchronous take's cuts (:data:`CUT_WINDOW_BYTES`).
        self._cut_windows: Dict[Optional[int], _DeviceWindow] = {}
        # The transfers that were pieces of a leaf (cut by the fork, or by a
        # synchronous take's stage), and their bytes (``d2h.pieces``,
        # ``d2h.pieced_bytes``).
        self.pieces = 0
        self.pieced_bytes = 0

    # What the windows did, for the take's telemetry: the most bytes ever
    # hinted and unresolved on one device (``d2h.hinted_ahead_hwm_bytes``),
    # the transfers that waited for room (``d2h.window_waits``), and the
    # most HBM a synchronous take's cut pieces held on one device
    # (``stage.sync_cut_hwm_bytes``).

    @property
    def hinted_ahead_hwm_bytes(self) -> int:
        return max((w.hwm for w in self._windows.values()), default=0)

    @property
    def window_waits(self) -> int:
        return sum(w.waits for w in self._windows.values())

    @property
    def cut_hwm_bytes(self) -> int:
        return max((w.hwm for w in self._cut_windows.values()), default=0)

    def executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=self.lane_count,
                thread_name_prefix="tss-d2h",
            )
        return self._executor

    def cut_window(self, device: Optional[int]) -> _DeviceWindow:
        """``device``'s window of leaves cut and not yet gathered: ``await
        room(nbytes, CUT_WINDOW_BYTES, loop)`` before the cut, ``done``
        once the last piece has crossed (or the cut failed)."""
        return self._cut_windows.setdefault(device, _DeviceWindow())

    async def start(
        self,
        arr: Any,
        nbytes: int,
        loop,
        times: Optional[StageTimes] = None,
        location: str = "",
        into: Optional[np.ndarray] = None,
        piece_window_bytes: int = PIECE_WINDOW_BYTES,
    ) -> np.ndarray:
        """``arr`` on the host: wait for room in its device's window, hint
        the transfer, resolve it on a lane.

        ``into`` (a writable ``uint8`` view of ``nbytes``) marks ``arr`` as
        one piece of a leaf that was cut on the device: it is admitted under
        ``piece_window_bytes`` (:data:`PIECE_WINDOW_BYTES` beside a step,
        :data:`SYNC_PIECE_WINDOW_BYTES` in a synchronous take), and the lane
        that resolved it copies it into ``into`` (its rows of the leaf's
        host buffer) and drops it (:func:`resolve_on_host`).

        The resolve is timed inside the lane thread, so the recorded ``d2h``
        interval is transfer time only (a piece's copy into its leaf's
        buffer included, as the ``gather`` interval inside it: the bytes
        are not ready for hash and write before it) — neither the wait for
        room nor the time the result waited to be awaited (that wait is
        exactly the overlap the lanes exist to create)."""
        devices = arr.devices()
        device = next(iter(devices)).id if len(devices) == 1 else None

        def resolve() -> np.ndarray:
            with timed(times, "d2h", path=location, nbytes=nbytes, device=device):
                return resolve_on_host(arr, into, times, location)

        limit = HINT_WINDOW_BYTES
        if into is not None:
            limit = piece_window_bytes
            self.pieces += 1
            self.pieced_bytes += nbytes
        window = self._windows.setdefault(device, _DeviceWindow())
        await window.room(nbytes, limit, loop)
        try:
            hint_copy_to_host(arr)
            return await loop.run_in_executor(self.executor(), resolve)
        finally:
            window.done(nbytes)

    def shutdown(self, cancel_queued: bool = False) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=cancel_queued)
            self._executor = None


class StagingContext:
    """What one write pipeline exposes to its stagers: the transfer lanes,
    the sub-phase interval sink and, in a synchronous take's pipeline,
    ``arena``: a call that gives the arena its big leaves' host buffers are
    leased from (``host_arena.HostArena``, made at the first call; None
    beside a step, where a gather lands in fresh pages), with the window
    that take's pieces are admitted under."""

    __slots__ = ("lanes", "times", "arena", "piece_window_bytes")

    def __init__(
        self,
        lanes: TransferLanes,
        times: StageTimes,
        arena: Optional[Callable[[], Any]] = None,
    ) -> None:
        self.lanes = lanes
        self.times = times
        self.arena = arena
        self.piece_window_bytes = (
            PIECE_WINDOW_BYTES if arena is None else SYNC_PIECE_WINDOW_BYTES
        )


_ACTIVE: contextvars.ContextVar[Optional[StagingContext]] = (
    contextvars.ContextVar("torchsnapshot_tpu_staging_ctx", default=None)
)


def get_active() -> Optional[StagingContext]:
    return _ACTIVE.get()


def activate(ctx: Optional[StagingContext]) -> contextvars.Token:
    return _ACTIVE.set(ctx)


def deactivate(token: contextvars.Token) -> None:
    _ACTIVE.reset(token)
