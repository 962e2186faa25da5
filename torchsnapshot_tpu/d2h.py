"""Parallel device→host transfer lanes + stage-time attribution.

The background drain used to resolve device→host transfers one ``np.asarray``
at a time per request: the staging stream was a chain of
hint → resolve → serialize → hash → append steps in which the link sat idle
for every serialize/hash gap. BENCH rounds 2→5 measured the cost —
``stage_busy`` at 95-99% of drain wall while ``io_busy`` stayed under 10%,
and ``drain_vs_link`` stuck at ~0.66. This module closes the gap with two
cooperating pieces:

- :class:`TransferLanes` — N concurrent transfer lanes (a dedicated
  ``ThreadPoolExecutor``, knob ``TORCHSNAPSHOT_TPU_D2H_LANES``):
  ``copy_to_host_async()`` is issued when a request is admitted, and the
  (already in-flight) transfers resolve out of the lane executor
  concurrently — so the transfer engine runs back-to-back while
  serialize/hash/write work on earlier requests. The resolved host bytes
  are debited by the request's own admission.
- :class:`StageTimes` — a thread-safe sink for the staging stream's
  sub-phase intervals (``d2h`` / ``serialize`` / ``hash``). The scheduler
  derives ``stage_d2h_s``/``stage_serialize_s``/``stage_hash_s`` from these
  by the same interval-union algebra as the stage/io streams, so the
  monolithic ``stage_busy`` decomposes in drain stats, persisted telemetry
  artifacts, and bench output — the next staging regression is attributable
  instead of a single opaque number. With a telemetry session active the
  same intervals are exported as ``stage.d2h``/``stage.serialize``/
  ``stage.hash`` spans.

The write pipeline activates a :class:`StagingContext` (lanes + times) via a
``contextvars.ContextVar`` around staging-task creation — the same pattern
telemetry uses — so stagers pick it up with one ``get_active()`` call and
degrade gracefully (no lanes, no recording) when driven outside a pipeline.
"""

from __future__ import annotations

import contextvars
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .telemetry import core as telemetry_core
from .utils import knobs

logger = logging.getLogger(__name__)


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

    KINDS = ("d2h", "serialize", "hash")

    def __init__(self, tm: Optional[Any] = None) -> None:
        # ``tm``: the op's telemetry.Telemetry session (or None when off).
        self._tm = tm
        self._lock = threading.Lock()
        self._intervals: Dict[str, List[Tuple[float, float, int]]] = {
            k: [] for k in self.KINDS
        }

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
    work that is synchronous on the calling thread (a lane's resolve, a
    compress, a whole-leaf hash): the interval is recorded as
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


class TransferLanes:
    """N concurrent D2H resolution lanes: a dedicated transfer executor."""

    def __init__(self, lanes: Optional[int] = None) -> None:
        self.lane_count = lanes if lanes is not None else knobs.get_d2h_lanes()
        self._executor: Optional[ThreadPoolExecutor] = None

    def executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=self.lane_count,
                thread_name_prefix="tss-d2h",
            )
        return self._executor

    def start(
        self,
        arr: Any,
        nbytes: int,
        loop,
        times: Optional[StageTimes] = None,
        location: str = "",
    ):
        """Hint ``arr``'s transfer NOW and schedule its resolve on a lane.

        Returns an awaitable future of the host ``np.ndarray``. The resolve
        is timed inside the lane thread, so the recorded ``d2h`` interval is
        transfer time only — not the time the future waited to be awaited
        (that wait is exactly the overlap the lanes exist to create)."""
        hint_copy_to_host(arr)
        devices = arr.devices()
        device = next(iter(devices)).id if len(devices) == 1 else None

        def resolve() -> np.ndarray:
            with timed(times, "d2h", path=location, nbytes=nbytes, device=device):
                return np.asarray(arr)

        return loop.run_in_executor(self.executor(), resolve)

    def shutdown(self, cancel_queued: bool = False) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=cancel_queued)
            self._executor = None


class StagingContext:
    """What one write pipeline exposes to its stagers: the transfer lanes
    and the sub-phase interval sink."""

    __slots__ = ("lanes", "times")

    def __init__(self, lanes: TransferLanes, times: StageTimes) -> None:
        self.lanes = lanes
        self.times = times


_ACTIVE: contextvars.ContextVar[Optional[StagingContext]] = (
    contextvars.ContextVar("torchsnapshot_tpu_staging_ctx", default=None)
)


def get_active() -> Optional[StagingContext]:
    return _ACTIVE.get()


def activate(ctx: Optional[StagingContext]) -> contextvars.Token:
    return _ACTIVE.set(ctx)


def deactivate(token: contextvars.Token) -> None:
    _ACTIVE.reset(token)
