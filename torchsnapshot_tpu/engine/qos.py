"""Priority classes + the process-wide QoS arbiter.

Every operation the dataflow engine runs carries one of three priority
classes — ``FOREGROUND > NORMAL > BACKGROUND``. The arbiter is the one
process-wide rendezvous between them: an operation *registers demand* for
its class while it runs, and every engine (and every cooperating
chunk-granular loop: swarm/bcast origin fetches, cache
populates) asks ``preempted(my_class)`` before admitting its next unit of
work. While a strictly higher class has registered demand, lower-class
admission pauses — budget, io/hash/transfer-pool slots, and storage
bandwidth all yield at the next chunk boundary. Nothing in flight is
cancelled: preemption is admission-level, at chunk granularity, so a
foreground restore arriving mid-drain steals the *next* admission rather
than waiting for the drain to finish (and the drain resumes the moment the
restore's demand unregisters).

The arbiter is thread-safe (a take's background drain thread and a
restore's main-thread event loop consult the same instance) and
deliberately process-local: cross-process QoS is the cluster scheduler's
job; this arbiter owns exactly the resources one process multiplexes — its
memory budget, thread pools, and storage connections.

Starvation is bounded: a continuously-preempted engine admits one round of
work every ``TORCHSNAPSHOT_TPU_QOS_MAX_PAUSE_S`` seconds regardless of
demand, so a long-lived foreground class slows background work to a
trickle but can never wedge it. ``TORCHSNAPSHOT_TPU_QOS=0`` disables the
arbiter entirely (FIFO).

The ambient class travels via a ``contextvars.ContextVar`` (the same
pattern d2h/telemetry use): ``Snapshot.take/async_take/restore`` wrap the
operation in :func:`priority_scope`, and everything built inside — write
and read pipelines, swarm sessions, broadcast fetches — inherits it
without signature changes. Secondary consumers (scrub, gc, cache
populate) pin ``BACKGROUND`` explicitly.
"""

from __future__ import annotations

import contextlib
import contextvars
import enum
import threading
import time
from typing import Optional, Union

from .. import telemetry
from ..utils import knobs


class Priority(enum.IntEnum):
    """QoS class of one operation. Order is preemption order: a class
    preempts (pauses admission of) every strictly lower class."""

    BACKGROUND = 0
    NORMAL = 1
    FOREGROUND = 2


def parse_priority(value: Union["Priority", str, None]) -> Optional[Priority]:
    """``"foreground" | "normal" | "background"`` (any case) or a Priority
    member; None passes through (meaning "inherit the ambient class")."""
    if value is None or isinstance(value, Priority):
        return value
    try:
        return Priority[str(value).upper()]
    except KeyError:
        raise ValueError(
            f"unknown QoS class {value!r}: expected one of "
            f"{[p.name.lower() for p in Priority]}"
        ) from None


class QoSArbiter:
    """Process-wide demand registry. ``register``/``unregister`` bracket an
    operation; ``preempted(p)`` is the admission gate every engine and
    chunk loop consults. All methods are thread-safe and O(#classes)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._demand = {p: 0 for p in Priority}

    def register(self, priority: Priority) -> None:
        with self._lock:
            self._demand[priority] += 1

    def unregister(self, priority: Priority) -> None:
        with self._lock:
            self._demand[priority] -= 1

    def preempted(self, priority: Priority) -> bool:
        """True while some strictly higher class has registered demand (and
        the QoS knob is on)."""
        if not knobs.is_qos_enabled():
            return False
        with self._lock:
            return any(
                self._demand[p] > 0 for p in Priority if p > priority
            )

    def demand_snapshot(self) -> dict:
        with self._lock:
            return {p.name: n for p, n in self._demand.items()}

    def introspect(self) -> dict:
        """One flight-recorder sample of the arbiter: per-class demand plus
        which classes are currently preempted by it."""
        demand = self.demand_snapshot()
        return {
            "demand": demand,
            "qos_enabled": knobs.is_qos_enabled(),
            "preempted_classes": [
                p.name for p in Priority if self.preempted(p)
            ],
        }


_ARBITER = QoSArbiter()


def get_arbiter() -> QoSArbiter:
    return _ARBITER


@contextlib.contextmanager
def demand_scope(priority: Priority, arbiter: Optional[QoSArbiter] = None):
    """Register demand for ``priority`` for the duration of the block — the
    whole-operation bracket (a foreground restore keeps background drains
    paused across its planning/device_put gaps, not just while its read
    engine runs)."""
    arb = arbiter if arbiter is not None else _ARBITER
    arb.register(priority)
    try:
        yield arb
    finally:
        arb.unregister(priority)


# ------------------------------------------------------------ ambient class

_PRIORITY: contextvars.ContextVar[Priority] = contextvars.ContextVar(
    "torchsnapshot_tpu_qos_priority", default=Priority.NORMAL
)


def current_priority() -> Priority:
    return _PRIORITY.get()


@contextlib.contextmanager
def priority_scope(priority: Optional[Priority]):
    """Set the ambient QoS class for the block (None = leave as-is).
    Captured at pipeline/engine construction, so an async take's background
    drain keeps the class the take was planned under even though the drain
    thread never sees this contextvar."""
    if priority is None:
        yield
        return
    token = _PRIORITY.set(priority)
    try:
        yield
    finally:
        _PRIORITY.reset(token)


# --------------------------------------------------------- cooperative pause

async def pause_point(
    priority: Optional[Priority] = None,
    arbiter: Optional[QoSArbiter] = None,
) -> float:
    """One cooperative preemption point for chunk-granular loops outside an
    engine (swarm/bcast origin fetches, cache populates): awaits while a
    higher class has demand, bounded by the max-pause knob. Returns seconds
    paused (0.0 on the fast path — one arbiter check, no allocation)."""
    import asyncio

    p = priority if priority is not None else current_priority()
    arb = arbiter if arbiter is not None else _ARBITER
    if not arb.preempted(p):
        return 0.0
    t0 = time.monotonic()
    max_pause = knobs.get_qos_max_pause_s()
    poll = knobs.get_qos_poll_s()
    telemetry.counter_add("engine.preemptions")
    demand = arb.demand_snapshot()
    telemetry.recorder.record_event(
        "engine.pause",
        {"engine": "pause_point", "priority": p.name, "demand": demand},
    )
    # Fleet wait edge: name the class(es) holding demand above us, so a
    # peer reading this rank's beacon sees "paused for class:FOREGROUND"
    # rather than an unattributed stall. Cleared when the pause ends.
    holders = [
        f"class:{q.name}"
        for q in Priority
        if q > p and demand.get(q.name, 0) > 0
    ]
    telemetry.fleet.note_blocked("qos.pause", holders)
    try:
        while arb.preempted(p):
            if max_pause > 0 and time.monotonic() - t0 >= max_pause:
                break
            await asyncio.sleep(poll)
    finally:
        telemetry.fleet.clear_blocked("qos.pause")
    waited = time.monotonic() - t0
    telemetry.counter_add("engine.preempted_wait_s", waited)
    telemetry.histogram_observe("engine.pause_s", waited)
    telemetry.recorder.record_event(
        "engine.resume",
        {"engine": "pause_point", "priority": p.name,
         "paused_s": round(waited, 6)},
    )
    return waited
