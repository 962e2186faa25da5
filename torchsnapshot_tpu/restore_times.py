"""Where a restore's time goes: the read side's interval sink.

The write side has had :class:`~.d2h.StageTimes` and ``stream_stats`` since
the drain was decomposed; this is the same discipline for
``Snapshot.restore``. Each layer of the read path stamps an interval on the
thread that does the work, around the work and not around the ``await`` of
it:

    plan      metadata, digest index, key gather; per stateful: frame tables,
              flatten, every ``_prepare_restore_one``, read batching
    fetch     one storage read of the read pipeline, retries included
    mount     inside a fetch, one chunk read of the fs plugin's native
              engine for the time a reader thread had it: the ``pread``
              into the thread's bounce buffer AND the copy out of it into
              the target's pages, so ``mount_*`` is the readers' occupancy
              (no span: stamped in the engine, GIL-free)
    pread     inside a chunk, the ``pread`` itself: the mount proper; the
              rest of the chunk is the reader's copy (``reader_copy_*``)
    verify    the digest check of one fetched buffer
    consume   one consumer's decode + copy into its host target (nothing,
              where the read landed in that target: ``landed_bytes``)
    place     one finalizer: ``device_put`` / ``assemble_jax_array`` /
              ``make_array_from_callback``, a failed first attempt and the
              target's release included
    load      the stateful's ``load_state_dict``, the artifact write, the
              post-load barrier

and :meth:`RestoreTimes.summary` reduces them, once, with the interval
algebra of ``engine/intervals.py``: a busy time is the measure of a union, a
sum the plain sum of durations (``sum / busy`` is the depth the layer ran
at), a wait the time work sat ready before its layer took it up. With a
telemetry session the same intervals are its spans (``restore.plan``,
``scheduler.fetch``, ``scheduler.verify``, ``scheduler.consume_work``,
``restore.place``, ``restore.load_state_dict``), so the trace and the stats
cannot disagree; those that are synchronous on a thread are bridged onto a
running profiler trace as ``tss.*``.

One sink per restore, activated through a ``ContextVar`` as the telemetry
session and the write side's ``StagingContext`` are: every task of the
restore's event loop inherits it, and work handed to an executor thread
captures it on the loop side first. Outside a restore (``read_object``, a
bare ``execute_read_reqs``) nothing is active and every site costs one
``None`` check.
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import threading
import time
from concurrent.futures import Executor
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from .engine.intervals import (
    Interval,
    clip_merged,
    measure,
    merge_intervals,
)
from . import telemetry
from .telemetry import core as telemetry_core

# kind -> (span name, span category, bridged onto a profiler trace)
_SPANS: Dict[str, Tuple[str, str, bool]] = {
    "plan": ("restore.plan", "restore", True),
    "fetch": ("scheduler.fetch", "scheduler", False),  # spans an await
    "verify": ("scheduler.verify", "scheduler", True),
    "consume": ("scheduler.consume_work", "scheduler", True),
    # One per leaf: its own category keeps it out of the artifact's
    # ``phase_spans`` (``cat="restore"``), which stays a handful of lines.
    "place": ("restore.place", "restore.leaf", True),
    "load": ("restore.load_state_dict", "restore", True),
}
# What the pipeline's wall is made of; the rest of it is ``idle_s``.
_PIPELINE_KINDS = ("fetch", "verify", "consume", "place")
_SUMS = (
    "fetch_wait_s",
    "fetch_copied_bytes",
    "landed_bytes",
    "recycled_bytes",
    "fresh_target_bytes",
    "target_wait_s",
    "pretouched_bytes",
    "pretouch_s",
    "pretouch_stop_wait_s",
    "mount_bytes",
    "consume_wait_s",
    "place_wait_s",
    "place_retry_s",
    "place_bytes",
    "targets_consumed",
)


class RequestClock:
    """One read request between its layers: when its buffer was fetched,
    and when the last of its consumers finished on its thread."""

    __slots__ = ("fetched_at", "consumed_at")

    def __init__(self, fetched_at: float) -> None:
        self.fetched_at = fetched_at
        self.consumed_at = 0.0


class Stamp:
    """What ``with times.work(...) as w`` yields: the interval's ends."""

    __slots__ = ("t0", "t1")

    def __init__(self) -> None:
        self.t0 = self.t1 = 0.0


class RestoreTimes:
    """Thread-safe sink of one restore's intervals, waits and counts."""

    def __init__(self, tm: Optional[Any] = None) -> None:
        # ``tm``: the restore's telemetry.Telemetry session (None when off);
        # captured here because executor threads inherit no context.
        self.tm = tm
        self._lock = threading.Lock()
        self._intervals: Dict[str, List[Interval]] = {k: [] for k in _SPANS}
        self._pipeline: List[Interval] = []
        self._mount: List[Tuple[float, float, float, float]] = []
        self._sums: Dict[str, float] = {k: 0.0 for k in _SUMS}

    # ----------------------------------------------------------- recording

    @contextlib.contextmanager
    def work(
        self,
        kind: str,
        path: str = "",
        nbytes: int = 0,
        parent: Optional[int] = None,
        since: Optional[float] = None,
    ) -> Iterator[Stamp]:
        """One interval of ``kind``, stamped on the calling thread around
        the body, and its span. ``parent``: the span open where the work was
        handed over, for work that runs on an executor thread (which
        inherits no context). ``since``: an earlier start of the interval,
        on this thread."""
        name, cat, bridge = _SPANS[kind]
        w = Stamp()
        with (self.tm or telemetry_core).span(
            name, cat, bridge, path=path, nbytes=nbytes
        ) as sp:
            w.t0 = time.monotonic() if since is None else since
            if sp is not telemetry_core.NOOP_SPAN:
                if parent is not None:
                    sp.span.parent_id = parent
                if since is not None:
                    sp.span.ts = since
            try:
                yield w
            finally:
                w.t1 = time.monotonic()
                self.add_interval(kind, w.t0, w.t1)

    def add_interval(self, kind: str, t0: float, t1: float) -> None:
        with self._lock:
            self._intervals[kind].append((t0, t1))

    def record_fetch(
        self,
        t0: float,
        path: str,
        nbytes: int,
        admitted_at: float,
        copied_bytes: int,
    ) -> None:
        """One storage read that began at ``t0`` and ends now, measured
        around its ``await`` (the read itself runs in the plugin); it had
        waited for its turn since ``admitted_at``. ``copied_bytes``: what
        of it was copied in Python between the backend's delivery and the
        buffer the consumer gets (``io_types.ReadBuffer``'s join of a read
        delivered in several writes)."""
        t1 = time.monotonic()
        with self._lock:
            self._intervals["fetch"].append((t0, t1))
            self._sums["fetch_wait_s"] += max(0.0, t0 - admitted_at)
            self._sums["fetch_copied_bytes"] += copied_bytes
        if self.tm is not None:
            name, cat, _ = _SPANS["fetch"]
            self.tm.add_span(name, cat, t0, t1 - t0, {"path": path, "nbytes": nbytes})

    def add_mount_reads(
        self, chunk_reads: List[Tuple[float, float, float, float]], nbytes: int
    ) -> None:
        """One native read's chunk reads as the engine stamped them, on this
        clock (``native.ReadChunk``): each chunk's whole interval on its
        reader thread and the ``pread`` inside it, and the bytes they
        delivered. No span of their own: ``storage.read_work`` stays one
        span an object."""
        with self._lock:
            self._mount.extend(chunk_reads)
            self._sums["mount_bytes"] += nbytes

    def add(self, key: str, value: float) -> None:
        with self._lock:
            self._sums[key] += value

    def add_pipeline_window(self, t0: float, t1: float) -> None:
        """One stateful's pipeline, from its plan's end to its load's
        start: what ``idle_s`` is the remainder of."""
        with self._lock:
            self._pipeline.append((t0, t1))

    def timed_consume(
        self, work: Callable[[], None], clock: Optional[RequestClock]
    ) -> Callable[[], None]:
        """``work`` (a consumer's decode + copy) as a thunk that stamps its
        own interval on the thread that runs it, counts how long the
        fetched buffer waited for that thread, and tells the request's
        clock when it finished."""

        parent = telemetry_core.current_span_id()

        def timed() -> None:
            with self.work("consume", parent=parent) as w:
                work()
            if clock is not None:
                with self._lock:
                    self._sums["consume_wait_s"] += max(0.0, w.t0 - clock.fetched_at)
                    clock.consumed_at = max(clock.consumed_at, w.t1)

        return timed

    # ------------------------------------------------------------ reduction

    def summary(self) -> Dict[str, float]:
        """The restore's split, in seconds (and bytes, and counts). Unions
        are taken over the whole restore; ``idle_s`` is the pipelines' wall
        less the time in which any of fetch, verify, consume or place ran
        inside them."""
        with self._lock:
            ivs = {k: list(v) for k, v in self._intervals.items()}
            windows = merge_intervals(self._pipeline)
            chunks = list(self._mount)
            out = dict(self._sums)
        merged = {k: merge_intervals(v) for k, v in ivs.items()}
        busy_any = merge_intervals(
            [iv for k in _PIPELINE_KINDS for iv in merged[k]]
        )
        # A chunk, the pread inside it, and what its reader did outside the
        # pread: the copy out of the bounce buffer into the target's pages.
        mount = [(t0, t1) for t0, t1, _, _ in chunks]
        pread = [(p0, p1) for _, _, p0, p1 in chunks]
        reader_copy = [
            iv for t0, t1, p0, p1 in chunks for iv in ((t0, p0), (p1, t1))
        ]
        pipeline_s = measure(windows)
        busy_in_pipeline = sum(
            measure(clip_merged(busy_any, w0, w1)) for w0, w1 in windows
        )
        out.update(
            plan_s=measure(merged["plan"]),
            fetch_busy_s=measure(merged["fetch"]),
            fetch_sum_s=measure(ivs["fetch"]),
            mount_busy_s=measure(merge_intervals(mount)),
            mount_sum_s=measure(mount),
            pread_busy_s=measure(merge_intervals(pread)),
            pread_sum_s=measure(pread),
            reader_copy_sum_s=measure(reader_copy),
            verify_busy_s=measure(merged["verify"]),
            consume_busy_s=measure(merged["consume"]),
            consume_sum_s=measure(ivs["consume"]),
            place_busy_s=measure(merged["place"]),
            load_s=measure(merged["load"]),
            pipeline_s=pipeline_s,
            idle_s=max(0.0, pipeline_s - busy_in_pipeline),
        )
        # ``measure`` of nothing is the int 0: one type for every value.
        return {k: float(v) for k, v in out.items()}


_ACTIVE: contextvars.ContextVar[Optional[RestoreTimes]] = contextvars.ContextVar(
    "torchsnapshot_tpu_restore_times", default=None
)
_CLOCK: contextvars.ContextVar[Optional[RequestClock]] = contextvars.ContextVar(
    "torchsnapshot_tpu_restore_request_clock", default=None
)


def get_active() -> Optional[RestoreTimes]:
    return _ACTIVE.get()


def activate(times: Optional[RestoreTimes]) -> contextvars.Token:
    return _ACTIVE.set(times)


def deactivate(token: contextvars.Token) -> None:
    _ACTIVE.reset(token)


def begin_consume(fetched_at: float) -> None:
    """Called at the top of a request's consume task: its consumers (and
    the sub-tasks a merged read fans out to) see this clock."""
    _CLOCK.set(RequestClock(fetched_at))


def consumed_at() -> float:
    """When the calling task's request was last consumed on a thread; now,
    where no clock ran (no restore active, or a consumer that hands nothing
    to :func:`run_consume_work`)."""
    clock = _CLOCK.get()
    return (clock.consumed_at if clock is not None else 0.0) or time.monotonic()


async def consume_landed(nbytes: int) -> None:
    """A consumer was handed its own destination's memory
    (``io_types.ReadIO.into``): the read landed, nothing is left to decode
    or copy. Counted as ``landed_bytes``, and still one consume interval,
    stamped where it ran, so the consume layer's share falls by what it no
    longer does and the request's clock runs on."""
    times = _ACTIVE.get()
    if times is not None:
        times.add("landed_bytes", nbytes)
    telemetry.counter_add("restore.landed_bytes", nbytes)
    await run_consume_work(lambda: None, None)


async def run_consume_work(
    work: Callable[[], None], executor: Optional[Executor]
) -> None:
    """The tail every ``BufferConsumer`` shares: run ``work`` (decode + copy
    into the host target) on ``executor``, or inline where there is none.
    Under an active restore the work stamps its own interval, on the thread
    that runs it."""
    times = _ACTIVE.get()
    if times is not None:
        work = times.timed_consume(work, _CLOCK.get())
    if executor is not None:
        await asyncio.get_running_loop().run_in_executor(executor, work)
    else:
        work()
