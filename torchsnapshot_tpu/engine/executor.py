"""The memory-budgeted, priority-classed DAG executor.

One executor drives one operation's task graph (see ``graph.py``): it owns
the operation's byte budget, the per-pool slot caps, the task tables, the
interval/span recording, the occupancy reporter, the stall watchdog, and
the abort sweep — the machinery that used to exist three times over in
``scheduler.py``, each with its own budget accounting, abort semantics,
and telemetry shape.

Execution semantics (identical to the legacy pipelines, now stated once):

- **Admission** is head-of-line from a cost-descending pending queue: the
  head node is admitted when its pool has a free slot AND its cost fits
  the budget; one over-budget node is admitted when nothing is in flight,
  so a single huge request can never deadlock the graph.
- **Budget handoff**: a node's admission reservation (re-costed to the
  actual buffer size via ``ctx.recost``) travels along its ``successor``
  edge and is credited back when the edge's final node completes — or by
  the abort sweep, on every failure path.
- **Priority**: the executor registers demand for its class with the
  process-wide :class:`~.qos.QoSArbiter` while it runs, and pauses ALL new
  admissions (budget, slots — including successor dispatch, i.e. storage
  bandwidth) whenever a strictly higher class has demand, re-checking at
  chunk granularity. In-flight steps always finish; starvation is bounded
  by ``TORCHSNAPSHOT_TPU_QOS_MAX_PAUSE_S``.
- **Abort** cancels every in-flight task, awaits them, credits every
  outstanding reservation (task tables, handed-off edges), and leaves the
  budget balanced — the invariant the debug ledger
  (``TORCHSNAPSHOT_TPU_DEBUG_LEDGER``) asserts with site attribution.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import psutil

from .. import ledger, telemetry
from ..utils import knobs
from . import qos as qos_mod
from .graph import Node, Priority
from .intervals import Interval

logger = logging.getLogger(__name__)

# The occupancy reporter kept its historical log channel when it moved here
# from scheduler.py: operator tooling (and the scheduler test suite) filters
# pipeline-occupancy lines by that logger name.
_pipeline_logger = logging.getLogger("torchsnapshot_tpu.scheduler")


class Budget:
    """The operation's byte budget. Two adds on the hot path; under the
    debug-ledger knob every debit is journaled with its owner/call-site so
    ``assert_balanced`` can name leaking sites."""

    def __init__(self, total: int, owner: str = "pipeline") -> None:
        self.total = total
        self.available = total
        # Lowest availability seen — the budget high-water mark
        # (total - min_available) is a telemetry gauge at pipeline end.
        self.min_available = total
        self.ledger = ledger.maybe_ledger(owner)

    def debit(self, n: int) -> None:
        self.available -= n
        if self.available < self.min_available:
            self.min_available = self.available
        if self.ledger is not None:
            self.ledger.record_debit(n)

    def credit(self, n: int) -> None:
        self.available += n
        if self.ledger is not None:
            self.ledger.record_credit(n)

    def assert_balanced(self, context: str) -> None:
        """Ledger-mode assertion that every debit has been credited back —
        called at engine close and on every abort path. No-op (and no
        allocation) unless the debug-ledger knob is set."""
        if self.ledger is not None:
            self.ledger.assert_balanced(context)

    @property
    def high_water_bytes(self) -> int:
        return self.total - self.min_available

    @property
    def balanced(self) -> bool:
        return self.available == self.total


class ProgressReporter:
    """Periodic per-rank occupancy logging: how many nodes sit in each
    pool, bytes moved, budget headroom, and RSS delta since the engine
    began. Logged at most once per ``interval_s``, from the event-loop
    side (so a stall in any pool shows its last known occupancy)."""

    def __init__(self, rank: int, kind: str, interval_s: float = 10.0) -> None:
        self.rank = rank
        self.kind = kind
        self.interval_s = interval_s
        self._last_ts = time.monotonic()
        try:
            self._rss0 = psutil.Process(os.getpid()).memory_info().rss
        except Exception:  # pragma: no cover - psutil hiccup
            self._rss0 = 0

    def maybe_report(
        self, stages: Dict[str, int], bytes_done: int, budget: Budget
    ) -> None:
        now = time.monotonic()
        if now - self._last_ts < self.interval_s:
            return
        self._last_ts = now
        try:
            rss_delta = psutil.Process(os.getpid()).memory_info().rss - self._rss0
        except Exception:  # pragma: no cover
            rss_delta = 0
        occupancy = " ".join(f"{k}={v}" for k, v in stages.items())
        _pipeline_logger.info(
            "Rank %d %s pipeline: %s | %.2f GB done | budget %.2f/%.2f GB | "
            "RSS delta %+.2f GB",
            self.rank,
            self.kind,
            occupancy,
            bytes_done / 1e9,
            budget.available / 1e9,
            budget.total / 1e9,
            rss_delta / 1e9,
        )


class NodeContext:
    """What a node body sees of its engine: cost correction and span-byte
    attribution."""

    __slots__ = ("engine", "node")

    def __init__(self, engine: "GraphExecutor", node: Node) -> None:
        self.engine = engine
        self.node = node

    @property
    def admitted_at(self) -> float:
        """``time.monotonic()`` at which this node was admitted (its span's
        start): a body that waits for something before its real work
        measures that wait from here."""
        return self.engine._t0.get(self.node, 0.0)

    def recost(self, nbytes: int) -> None:
        """Correct this node's admission reservation to the actual bytes
        (estimate → real buffer footprint); the corrected reservation rides
        the successor edge."""
        self.engine._recost(self.node, nbytes)

    def note_bytes(self, nbytes: int) -> None:
        """Attribute ``nbytes`` to this node's span/interval without
        touching the budget (e.g. actual fetched bytes on a read whose
        reservation is the consuming cost)."""
        self.engine._nbytes[self.node] = nbytes

class GraphExecutor:
    """Drives one task graph to completion under one budget, one priority
    class, and one set of slot pools. See the module docstring."""

    def __init__(
        self,
        *,
        budget_bytes: int,
        rank: int = 0,
        owner: str = "engine",
        kind: str = "engine",
        span_prefix: str = "scheduler",
        priority: Optional[Priority] = None,
        caps: Optional[Dict[str, Optional[Callable[[], int]]]] = None,
        ready_label: str = "ready_for_io",
        progress: Optional[Any] = None,
        bytes_done: Optional[Callable[[], int]] = None,
        task_context: Optional[Callable[[], Any]] = None,
        on_progress: Optional[Callable[[], None]] = None,
        arbiter: Optional[qos_mod.QoSArbiter] = None,
    ) -> None:
        self.budget = Budget(budget_bytes, owner=owner)
        self.rank = rank
        self.kind = kind
        self.priority = (
            priority if priority is not None else qos_mod.current_priority()
        )
        self._caps = caps or {}
        self._ready_label = ready_label
        self._span_prefix = span_prefix
        self._pending: Deque[Node] = deque()
        self._deferred: List[Node] = []
        # Handed-off successor edges awaiting a slot: (node, payload,
        # carried reservation).
        self._ready: Deque[Tuple[Node, Any, int]] = deque()
        self._tasks: Dict[asyncio.Task, Node] = {}
        self._reservation: Dict[Node, int] = {}
        self._t0: Dict[Node, float] = {}
        # Span ids reserved when a node's body starts, so that what the
        # body records parents to the node's span (itself recorded at reap).
        self._span_id: Dict[Node, int] = {}
        self._nbytes: Dict[Node, int] = {}
        self._inflight: Dict[str, int] = {}
        self._pool_order: List[str] = []
        self.windows: List[Interval] = []
        self.stage_intervals: List[Interval] = []
        # (t0, t1, nbytes); nbytes 0 where the record carries no size.
        self.io_intervals: List[Tuple[float, float, int]] = []
        self._tm = telemetry.get_active()
        self.reporter = ProgressReporter(rank, kind)
        self._progress = progress
        self._bytes_done = bytes_done or (lambda: 0)
        self._task_context = task_context
        self._on_progress = on_progress
        self._arbiter = (
            arbiter if arbiter is not None else qos_mod.get_arbiter()
        )
        self._paused_since: Optional[float] = None
        # Preemption counters for this engine (also mirrored as telemetry
        # metrics) — the qos bench and the chaos harness read them.
        self.preemptions = 0
        self.preempted_wait_s = 0.0
        # Closed QoS pause episodes as monotonic intervals; persisted with
        # the per-op artifact so the fleet view can show pause waves.
        self.pause_intervals: List[Interval] = []
        # Nodes ever admitted (task-table handoffs) — an introspection
        # rate, not an accounting quantity.
        self.admitted = 0

    # ------------------------------------------------------------- building

    def add(self, node: Node) -> Node:
        """Add one node chain (``node`` and its successors). Only the head
        enters the admission queue; successors ride the handoff edges."""
        for n in node.chain():
            if n.pool not in self._inflight:
                self._inflight[n.pool] = 0
                self._pool_order.append(n.pool)
        if node.deferred:
            self._deferred.append(node)
        else:
            self._pending.append(node)
        return node

    def release_deferred(self) -> None:
        """Make deferred nodes admissible (the async take's capture point:
        device-array staging joins the queue for the background drain)."""
        if self._deferred:
            self._pending.extend(self._deferred)
            self._deferred = []

    # ------------------------------------------------------------ inspection

    def unfinished_in(self, pools: Tuple[str, ...]) -> int:
        """Pending + in-flight nodes in the given pools (deferred nodes
        excluded — they are not yet admissible). The capture-point
        predicate: phase 1 runs until no staging work remains."""
        n = sum(1 for node in self._pending if node.pool in pools)
        n += sum(self._inflight.get(p, 0) for p in pools)
        return n

    def all_done(self) -> bool:
        return not self._pending and not self._ready and not self._tasks

    def occupancy(self) -> Dict[str, int]:
        occ: Dict[str, int] = {
            "pending": len(self._pending),
            "deferred": len(self._deferred),
        }
        for pool in self._pool_order:
            occ[pool] = self._inflight.get(pool, 0)
        occ[self._ready_label] = len(self._ready)
        return occ

    def introspect(self) -> Dict[str, Any]:
        """One flight-recorder sample of this engine: identity, occupancy,
        budget state, admission/preemption counters, and the arbiter's
        per-class demand. Values only — safe to call from any thread at
        any point in the engine's life (the dict is freshly built)."""
        return {
            "engine": self.kind,
            "rank": self.rank,
            "priority": self.priority.name,
            "occupancy": self.occupancy(),
            "bytes_done": self._bytes_done(),
            "admitted": self.admitted,
            "budget_total": self.budget.total,
            "budget_available": self.budget.available,
            "budget_hwm": self.budget.high_water_bytes,
            "preemptions": self.preemptions,
            "preempted_wait_s": round(self.preempted_wait_s, 6),
            "paused": self._paused_since is not None,
            "demand": self._arbiter.demand_snapshot(),
        }

    # --------------------------------------------------------------- running

    async def run(
        self,
        until: Optional[Callable[[], bool]] = None,
    ) -> None:
        """Drive the graph until ``until()`` holds (default: everything
        admitted and completed). Failures propagate after the failing
        node's reservation is credited; the caller is expected to
        ``await engine.abort()`` to sweep the rest. Each call records one
        accounting window."""
        window_t0 = time.monotonic()
        watchdog = self._spawn_watchdog()
        self._arbiter.register(self.priority)
        try:
            while True:
                if until is not None and until():
                    break
                if self.all_done():
                    break
                self._dispatch()
                if until is not None and until():
                    break
                inflight = set(self._tasks)
                if not inflight:
                    if self.all_done():
                        break
                    # Work exists but is gated (preemption pause): poll for
                    # the higher class's demand to clear. Keep sampling —
                    # pause waves are exactly what the recorder is for.
                    telemetry.recorder.sample_engine(self)
                    telemetry.fleet.sample_engine(self)
                    await asyncio.sleep(knobs.get_qos_poll_s())
                    continue
                done, _ = await asyncio.wait(
                    inflight,
                    return_when=asyncio.FIRST_COMPLETED,
                    # Bounded so the reporter fires during a stall (when no
                    # task completes, wait returns with done == set()).
                    timeout=self.reporter.interval_s,
                )
                self._reap(done)
                if self._on_progress is not None:
                    self._on_progress()
                self.reporter.maybe_report(
                    self.occupancy(), self._bytes_done(), self.budget
                )
                telemetry.recorder.sample_engine(self)
                telemetry.fleet.sample_engine(self)
        finally:
            self._arbiter.unregister(self.priority)
            self._note_resumed()
            await self._reap_watchdog(watchdog)
            self.windows.append((window_t0, time.monotonic()))

    # ------------------------------------------------------------ dispatching

    def _cap(self, pool: str) -> Optional[int]:
        cap = self._caps.get(pool)
        return cap() if callable(cap) else cap

    def _qos_gated(self) -> bool:
        """True while admissions must pause for a higher class. Bounded by
        the max-pause knob: a continuously-preempted engine admits one
        round per bound and re-arms (starvation safety)."""
        if not self._arbiter.preempted(self.priority):
            self._note_resumed()
            return False
        now = time.monotonic()
        if self._paused_since is None:
            if not self._ready and not self._pending:
                return False  # nothing to admit: not a pause episode
            self._paused_since = now
            self.preemptions += 1
            telemetry.counter_add("engine.preemptions")
            telemetry.recorder.record_event(
                "engine.pause",
                {
                    "engine": self.kind,
                    "rank": self.rank,
                    "priority": self.priority.name,
                    "demand": self._arbiter.demand_snapshot(),
                },
            )
            return True
        max_pause = knobs.get_qos_max_pause_s()
        if max_pause > 0 and now - self._paused_since >= max_pause:
            self._note_resumed()
            self._paused_since = now  # re-arm: admit this one round
            return False
        return True

    def _note_resumed(self) -> None:
        if self._paused_since is not None:
            now = time.monotonic()
            waited = now - self._paused_since
            self.pause_intervals.append((self._paused_since, now))
            self.preempted_wait_s += waited
            telemetry.counter_add("engine.preempted_wait_s", waited)
            telemetry.histogram_observe("engine.pause_s", waited)
            telemetry.recorder.record_event(
                "engine.resume",
                {
                    "engine": self.kind,
                    "rank": self.rank,
                    "priority": self.priority.name,
                    "paused_s": round(waited, 6),
                },
            )
            self._paused_since = None

    def _dispatch(self) -> None:
        if self._qos_gated():
            return
        cm = (
            self._task_context()
            if self._task_context is not None
            else contextlib.nullcontext()
        )
        # Tasks are created under the caller's context (e.g. the write
        # pipeline's d2h StagingContext): ensure_future snapshots the
        # contextvars, so node bodies and their sub-tasks inherit it.
        with cm:
            self._dispatch_ready()
            self._dispatch_pending()

    def _dispatch_ready(self) -> None:
        while self._ready:
            node, payload, reservation = self._ready[0]
            cap = self._cap(node.pool)
            if cap is not None and self._inflight[node.pool] >= cap:
                break
            self._ready.popleft()
            task = asyncio.ensure_future(self._run_node(node, payload))
            self._reservation[node] = reservation
            self._register(task, node)

    def _dispatch_pending(self) -> None:
        # Head-of-line admission from the cost-descending queue: the head
        # blocks everything behind it (budget fairness for the big request
        # that dominates the critical path).
        while self._pending:
            node = self._pending[0]
            cap = self._cap(node.pool)
            if cap is not None and self._inflight[node.pool] >= cap:
                break
            cost = node.cost_bytes
            if cost > self.budget.available and self._tasks:
                break  # over budget; admitted only when nothing is in flight
            self._pending.popleft()
            # Debit only once the task object exists, immediately before
            # the task-table handoff: if coroutine construction raises, no
            # reservation has been made yet, so nothing can leak (the
            # reservation table is what _reap/abort sweep credits from).
            task = asyncio.ensure_future(self._run_node(node, None))
            self.budget.debit(cost)
            self._reservation[node] = cost
            self._register(task, node)

    def _register(self, task: asyncio.Task, node: Node) -> None:
        self._tasks[task] = node
        self._inflight[node.pool] += 1
        self._t0[node] = time.monotonic()
        self.admitted += 1

    async def _run_node(self, node: Node, payload: Any) -> Any:
        if self._tm is not None:
            self._span_id[node] = self._tm.begin_deferred_span()
        return await node.run(NodeContext(self, node), payload)

    # --------------------------------------------------------------- reaping

    def _reap(self, done) -> None:
        for task in done:
            node = self._tasks.pop(task)
            self._inflight[node.pool] -= 1
            reservation = self._reservation.pop(node, 0)
            t0 = self._t0.pop(node, 0.0)
            span_id = self._span_id.pop(node, None)
            try:
                result = task.result()
            except BaseException:
                # Failed node releases its reservation: already popped, so
                # the abort sweep can't see (or double-credit) it.
                self.budget.credit(reservation)
                raise
            nbytes = self._nbytes.pop(node, reservation)
            self.record_interval(
                node.kind, t0, node.path, nbytes, stream=node.stream,
                span_id=span_id,
            )
            if node.successor is not None:
                # The edge handoff: result + reservation travel together;
                # the successor's completion (or the abort sweep) credits.
                self._ready.append((node.successor, result, reservation))
            else:
                self.budget.credit(reservation)

    def _recost(self, node: Node, nbytes: int) -> None:
        old = self._reservation.get(node)
        if old is None:
            return
        self.budget.credit(old)
        self.budget.debit(nbytes)
        self._reservation[node] = nbytes
        self._nbytes[node] = nbytes

    # ------------------------------------------------------------- telemetry

    def record_interval(
        self,
        kind: str,
        t0: float,
        path: str = "",
        nbytes: int = 0,
        stream: Optional[str] = "auto",
        span_id: Optional[int] = None,
    ) -> None:
        """One finished node/sub-step: record its interval (stats) and,
        when telemetry is on, the corresponding span. ``stream="auto"``
        routes ``io`` to the io stream and everything else to the staging
        stream."""
        t1 = time.monotonic()
        if stream == "auto":
            stream = "io" if kind == "io" else "stage"
        if stream == "io":
            self.io_intervals.append((t0, t1, nbytes))
        elif stream == "stage":
            self.stage_intervals.append((t0, t1))
        tm = self._tm
        if tm is not None:
            tm.add_span(
                f"{self._span_prefix}.{kind}",
                self._span_prefix,
                t0,
                t1 - t0,
                {"path": path, "nbytes": nbytes, "rank": self.rank},
                span_id=span_id,
            )

    # ---------------------------------------------------------------- aborts

    async def abort(self) -> None:
        """Failure path: cancel every in-flight task, await them, and
        credit back every outstanding reservation — task tables and
        handed-off edges alike — so an aborted operation leaves the budget
        balanced and no node body running against a torn-down engine."""
        tasks = list(self._tasks)
        for task in tasks:
            task.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        for task in tasks:
            node = self._tasks.pop(task)
            self._inflight[node.pool] -= 1
            reservation = self._reservation.pop(node, 0)
            self._t0.pop(node, None)
            self._span_id.pop(node, None)
            self._nbytes.pop(node, None)
            self.budget.credit(reservation)
        while self._ready:
            _node, _payload, reservation = self._ready.popleft()
            self.budget.credit(reservation)
        self._pending.clear()
        self._deferred.clear()
        self._note_resumed()

    def assert_balanced(self, context: str) -> None:
        self.budget.assert_balanced(context)

    # -------------------------------------------------------------- watchdog

    def _spawn_watchdog(self) -> Optional[asyncio.Task]:
        """Opt-in liveness: one structured warning per stall (no byte
        progress for TORCHSNAPSHOT_TPU_STALL_WARN_S seconds). Armed around
        every run() call when the engine has a progress tracker."""
        if self._progress is None:
            return None
        warn_s = knobs.get_stall_warn_s()
        if warn_s <= 0:
            return None
        def on_fire() -> None:
            telemetry.counter_add("scheduler.stall_warnings", 1)
            telemetry.fleet.note_anomaly("stall_warning")
            telemetry.recorder.record_event(
                "engine.stall_warning",
                {
                    "engine": self.kind,
                    "rank": self.rank,
                    "occupancy": self.occupancy(),
                    "bytes_done": self._bytes_done(),
                },
            )

        watchdog = telemetry.StallWatchdog(
            self._progress,
            warn_s,
            occupancy=self.occupancy,
            rank=self.rank,
            on_fire=on_fire,
        )
        return asyncio.ensure_future(watchdog.run())

    @staticmethod
    async def _reap_watchdog(task: Optional[asyncio.Task]) -> None:
        if task is not None:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)


async def run_graph(
    nodes: List[Node],
    *,
    budget_bytes: int,
    owner: str,
    kind: str = "engine",
    span_prefix: str = "engine",
    rank: int = 0,
    caps: Optional[Dict[str, Optional[Callable[[], int]]]] = None,
    priority: Priority = Priority.BACKGROUND,
) -> GraphExecutor:
    """Build-and-run convenience for the secondary consumers (scrub,
    verify, gc waves): one flat BACKGROUND-class graph, ledger-audited,
    aborted cleanly on failure. Returns the executor (counters,
    intervals)."""
    eng = GraphExecutor(
        budget_bytes=budget_bytes,
        rank=rank,
        owner=owner,
        kind=kind,
        span_prefix=span_prefix,
        caps=caps,
        priority=priority,
    )
    for node in nodes:
        eng.add(node)
    try:
        await eng.run()
    except BaseException:
        await eng.abort()
        eng.assert_balanced(f"{owner} abort")
        raise
    eng.assert_balanced(f"{owner} close")
    return eng
