"""Snapshot — the user API: take / async_take / restore / read_object.

TPU-native re-design of the reference's ``snapshot.py:76-991``. Semantics
preserved (see ``docs/`` and the reference's getting_started.rst):

- a snapshot is **atomic**: data objects are written by all ranks first, then
  a barrier, then rank 0 commits ``.snapshot_metadata``; a reader observes
  either a complete snapshot or none (reference ``snapshot.py:230-237``);
- values are per-rank / replicated / sharded; replicated + sharded snapshots
  restore under any world size (elasticity);
- ``async_take`` returns as soon as every byte is staged in host RAM; a
  background thread drains storage I/O and commits via a store-based
  :class:`LinearBarrier` (XLA collectives, like c10d's, cannot run off the
  main thread — reference ``snapshot.py:904-988``);
- the RNG invariant: host RNG state restored from a snapshot equals the RNG
  state at the *start* of ``take`` (reference ``snapshot.py:331-376``).

TPU-first differences:

- replication is detected from ``jax.Array`` shardings — a fully-replicated
  GSPMD array is checkpointed once globally with its write load partitioned
  across processes, no DDP-sniffing or user globs needed (globs remain for
  non-array leaves);
- restore targets keep their live sharding: each process reads only the
  bytes overlapping its addressable shards, buffers land via
  ``jax.device_put`` per shard, and cross-sharding restore is an overlap
  computation, not a gather (no inter-process tensor traffic at all);
- control-plane collectives ride the jax coordination service (or a
  built-in TCPStore), never the TPU interconnect.
"""

from __future__ import annotations

import asyncio
import contextlib
import fnmatch
import hashlib
import logging
import os
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Set, Tuple

import numpy as np

from .flatten import flatten, inflate
from .device_programs import is_oom_error
from .io_preparer import prepare_write
from .io_preparers.array import is_jax_array
from .io_preparers.array import ArrayIOPreparer
from .io_preparers.chunked_array import ChunkedArrayIOPreparer
from .io_preparers.object import ObjectIOPreparer
from .io_preparers.sharded_array import (
    ShardedArrayIOPreparer,
    assemble_jax_array,
    target_shard_rects,
)
from .io_types import (
    SMALL_OBJECT_BYTES,
    ReadIO,
    ReadReq,
    StoragePlugin,
    WriteIO,
    acquire_target_of,
    destination_of,
)
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    Manifest,
    ObjectEntry,
    PrimitiveEntry,
    ShardedArrayEntry,
    SnapshotMetadata,
    SNAPSHOT_METADATA_FNAME,
    get_manifest_for_rank,
    is_container_entry,
)
from .engine import qos as engine_qos
from .parallel.coordinator import Coordinator, get_coordinator
from .parallel.store import BarrierError, LinearBarrier
from .partitioner import partition_write_reqs_with_assignment
from .rng_state import RNGState
from .scheduler import (
    CHECKSUM_FILE_PREFIX,
    PendingIOWork,
    PipelinePools,
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from .stateful import AppState, Stateful
from .storage_plugin import url_to_storage_plugin_in_event_loop
from . import hashing, host_arena, restore_times, telemetry
from .utils import knobs
from .version import __version__

logger = logging.getLogger(__name__)

# Stall decomposition of this process's most recent take/async_take: phase
# name -> seconds (gather_keys_and_flatten, prepare_write, partition,
# d2h_hint, manifest_gather, memory_budget, capture). Derived from the
# telemetry phase spans (``telemetry.PhaseTracker``) — the stall IS these
# phases — device bytes drain in the background — so regressions here are
# regressions of the headline metric. Diagnostics only: overwritten per
# take, per process.
LAST_TAKE_PHASES: Dict[str, float] = {}

# Stream-overlap accounting (wall/stage_busy/io_busy/overlap/idle, seconds)
# of the most recent SYNC ``Snapshot.take``'s drain — the same decomposition
# async takes expose via ``PendingSnapshot.drain_stats``, so a sync-take
# throughput regression can be attributed to a stream (D2H+serialize vs
# storage writes) rather than re-derived from wall clock. Diagnostics only:
# overwritten per take, per process.
LAST_SYNC_DRAIN_STATS: Dict[str, float] = {}

# Restore-side accounting of this process's most recent ``restore()``:
# end-to-end wall seconds, aggregated read-pipeline stats (bytes_read /
# read_wall_s / requests), the broadcast-restore record
# (``bcast.LAST_RESTORE_BCAST``), the swarm-restore record
# (``swarm.LAST_RESTORE_SWARM``), and the origin-vs-peer-vs-cache byte
# attribution (``attribution``). The restore analogue of the take
# diagnostics above — ``perfbench`` and ``dev/probe_*.py`` read it without
# needing a telemetry session. Diagnostics only:
# overwritten per restore, per process.
LAST_RESTORE_STATS: Dict[str, Any] = {}


@contextlib.contextmanager
def _qos_scope(qos: Any):
    """Bind an operation's QoS class: the ambient priority scope (every
    pipeline, swarm session, and origin fetch built inside inherits it) plus
    a whole-operation demand registration, so e.g. a FOREGROUND restore
    keeps lower-class engines paused across its planning/device_put gaps —
    not just while its read pipelines run. ``qos`` is
    ``"foreground" | "normal" | "background"`` (or an ``engine.Priority``);
    None inherits the ambient class untouched."""
    priority = engine_qos.parse_priority(qos)
    if priority is None:
        yield
        return
    with engine_qos.priority_scope(priority):
        with engine_qos.demand_scope(priority):
            yield


@contextlib.contextmanager
def _barrier_stall_guard(rank: int):
    """Arm a thread-mode stall watchdog around a synchronous barrier hold.

    The engine's own watchdog dies with its event loop, but the place a
    straggler actually parks peers is the commit/post-load LinearBarrier —
    a plain blocking poll loop with no loop to ride. A fresh tracker never
    moves bytes, so the watchdog fires exactly once after the stall-warn
    threshold, and its warning carries ``blocked_on`` (the barrier's fleet
    wait edges) naming the missing peer(s). No-op when the stall-warn knob
    is off."""
    warn_s = knobs.get_stall_warn_s()
    if warn_s <= 0:
        yield
        return
    watchdog = telemetry.StallWatchdog(
        telemetry.ProgressTracker(), warn_s, rank=rank
    )
    thread, stop = telemetry.watchdog_thread(watchdog)
    try:
        yield
    finally:
        stop.set()
        thread.join(timeout=5.0)


def _restore_attribution(
    bcast_rec: Dict[str, Any],
    swarm_rec: Dict[str, Any],
    read_totals: Dict[str, float],
    storage: Any,
) -> Dict[str, int]:
    """Origin-vs-peer-vs-cache byte attribution for one restore — the
    production-observable form of the serving-path claims ("warm restores
    read 0 origin bytes", "swarm origin bytes ≈ one snapshot at any K").

    - ``origin_bytes``: bytes THIS rank pulled from origin storage — the
      broadcast phase's fetched/direct reads, the swarm phase's assigned/
      re-elected/fallback chunk reads, and the direct read pipeline's
      fetches minus whatever the read-through cache served locally;
    - ``peer_bytes``: bytes received from other ranks through the
      coordinator store (broadcast payloads + swarm chunks);
    - ``cache_bytes``: bytes served from the local read-through cache
      (pipeline hits + swarm cache-held chunks).

    Per-object breakdowns live in ``LAST_RESTORE_STATS["bcast"]
    ["per_object"]`` and ``["swarm"]["per_object"]``."""
    cache_hit_bytes = 0
    try:
        from .storage_plugins.cache import find_read_cache

        cache = find_read_cache(storage)
        if cache is not None:
            cache_hit_bytes = int(cache.stats.get("hit_bytes", 0))
    except Exception:  # noqa: BLE001 - diagnostics never fail a restore
        pass
    # The swarm's cache-probe hits are counted inside cache.stats too;
    # pipeline-side cache bytes are the remainder.
    swarm_cache = int(swarm_rec.get("cache_bytes", 0))
    pipeline_cache = max(0, cache_hit_bytes - swarm_cache)
    pipeline_read = int(read_totals.get("bytes_read", 0))
    return {
        "origin_bytes": (
            int(bcast_rec.get("origin_bytes", 0))
            + int(swarm_rec.get("origin_bytes", 0))
            + max(0, pipeline_read - pipeline_cache)
        ),
        "peer_bytes": (
            int(bcast_rec.get("recv_bytes", 0))
            + int(swarm_rec.get("peer_bytes", 0))
        ),
        "cache_bytes": swarm_cache + pipeline_cache,
    }


def _begin_telemetry(
    explicit: Optional["telemetry.Telemetry"],
) -> Tuple[Optional["telemetry.Telemetry"], Optional["telemetry.Telemetry"]]:
    """Start a telemetry session for one take/restore: an explicit
    ``_telemetry=`` object wins, else ``TORCHSNAPSHOT_TPU_TRACE`` or the
    (default-on) persisted-artifact knob creates one — the artifact needs
    the metrics registry and byte counters, so auditable-by-default
    checkpoints imply a session per op. Only with artifacts explicitly
    disabled (and no trace/_telemetry) does the op run with telemetry fully
    off, where the instrumented paths cost one None-check. Returns
    (session, previously-active session)."""
    tm = explicit
    if tm is None and (
        knobs.get_trace_path() or knobs.is_telemetry_artifacts_enabled()
    ):
        tm = telemetry.Telemetry()
    prev = telemetry.activate(tm) if tm is not None else None
    return tm, prev


def _finish_telemetry(
    tm: Optional["telemetry.Telemetry"],
    prev: Optional["telemetry.Telemetry"],
    rank: int,
) -> None:
    """Close a session: restore the previous activation, publish it as
    ``Snapshot.last_telemetry``, and write the Chrome/Perfetto trace if the
    trace knob is set (rank 0 writes the path verbatim; other ranks append
    ``.rank<N>`` so one shared filesystem path never interleaves). A trace
    write failure degrades to a warning — never a failed checkpoint."""
    if tm is None:
        return
    tm.rank = rank
    telemetry.deactivate(tm, prev)
    if tm.buffer.dropped:
        # Make capacity truncation visible in the metrics dump (and thus
        # the persisted artifact) — never a silently partial trace.
        tm.metrics.counter("telemetry.spans_dropped").add(tm.buffer.dropped)
    Snapshot.last_telemetry = tm
    trace_path = knobs.get_trace_path()
    if trace_path:
        path = trace_path if rank == 0 else f"{trace_path}.rank{rank}"
        try:
            # Flight-recorder engine samples ride along as Perfetto counter
            # tracks (write rate, budget HWM) beside the span tracks — only
            # when the recorder is live; "C" events are ignored by the
            # trace round-trip readers.
            samples = None
            rec = telemetry.recorder.get_recorder()
            if rec is not None:
                samples = rec.snapshot()
            telemetry.write_chrome_trace(tm, path, recorder_samples=samples)
        except Exception:  # noqa: BLE001 - diagnostics must not fail the op
            logger.warning(
                "failed to write telemetry trace to %s", path, exc_info=True
            )


# Artifact BUILD failures also log once per process (the write path has its
# own once-guard in storage_plugin.write_telemetry_artifact).
_artifact_build_warned = False


def _persist_op_artifact(
    storage: StoragePlugin,
    event_loop: asyncio.AbstractEventLoop,
    rank: int,
    world_size: int,
    op: str,
    tm: Optional["telemetry.Telemetry"],
    phase_spans=None,
    io_summary: Optional[Dict[str, Any]] = None,
    restore_stats: Optional[Dict[str, float]] = None,
) -> None:
    """Persist this rank's telemetry artifact into the snapshot, fail-open.

    Called pre-commit (take/async_take: after the drain, before the commit
    barrier; restore: before the post-load barrier) so a committed snapshot
    always carries every rank's artifact. Any failure logs once and never
    fails or delays the operation."""
    global _artifact_build_warned
    if not knobs.is_telemetry_artifacts_enabled():
        return
    from .storage_plugin import write_telemetry_artifact
    from .telemetry import artifact as telemetry_artifact

    try:
        payload = telemetry_artifact.dumps_artifact(
            telemetry_artifact.build_artifact(
                op=op,
                rank=rank,
                world_size=world_size,
                tm=tm,
                phase_spans=phase_spans,
                io_summary=io_summary,
                restore_stats=restore_stats,
            )
        )
    except Exception:  # noqa: BLE001 - diagnostics must not fail the op
        if not _artifact_build_warned:
            _artifact_build_warned = True
            logger.warning(
                "failed to build telemetry artifact for %s (snapshot "
                "unaffected)", op, exc_info=True,
            )
        else:
            logger.debug(
                "failed to build telemetry artifact for %s", op, exc_info=True
            )
        return
    write_telemetry_artifact(
        storage,
        event_loop,
        telemetry_artifact.artifact_path(rank, op),
        payload,
    )


class CheckpointAbortedError(RuntimeError):
    """A take OR restore failed mid-flight and was aborted — cleanly.

    Raised on EVERY rank (the failing one and its peers, via the commit /
    post-load barrier's error fan-out) within the barrier timeout, so no
    rank ever hangs on a dead or failing peer. Structured attribution:

    - ``rank``: the rank whose failure aborted the operation (``None``
      when unattributable — e.g. a peer died without reporting and the
      barrier timed out);
    - ``phase``: what that rank was doing (takes: ``"write"`` — staging +
      storage drain, ``"commit"`` — the metadata barrier; restores:
      ``"restore.plan"`` / ``"restore.read"`` / ``"restore.barrier"``);
    - ``detail``: the underlying error's text.

    Invariants that hold when a TAKE aborts: ``.snapshot_metadata`` was
    never written (the snapshot is invisible to readers; a previously
    committed snapshot at another path is untouched), the scheduler's
    memory budget has been fully credited back, and the pipeline pools are
    shut down. Debris (temp files, data objects of the torn take) may
    remain — ``Snapshot.gc`` reclaims it. When a RESTORE aborts, the
    snapshot itself is untouched (the read path writes nothing) and the
    budget/pool invariants hold identically; live restore targets may be
    partially loaded and must be re-restored before use.

    Subclasses RuntimeError: existing callers that catch RuntimeError from
    ``take()``/``PendingSnapshot.wait()`` keep working.
    """

    def __init__(
        self,
        path: str,
        rank: Optional[int],
        phase: Optional[str],
        detail: str,
    ) -> None:
        self.path = path
        self.rank = rank
        self.phase = phase
        self.detail = detail
        who = f"rank {rank}" if rank is not None else "a peer rank"
        doing = f" during {phase}" if phase else ""
        super().__init__(
            f"checkpoint to {path} aborted: {who} failed{doing}: {detail}"
        )


def _abort_exception(
    path: str,
    barrier: Optional[LinearBarrier],
    rank: int,
    phase: str,
    e: BaseException,
) -> BaseException:
    """Turn a take failure into the exception to raise: report it through
    the commit barrier (unblocking + failing every peer), prefer a peer's
    earlier report for attribution, and wrap in
    :class:`CheckpointAbortedError`. Non-Exception BaseExceptions
    (KeyboardInterrupt, SystemExit) are reported but re-raised raw."""
    telemetry.counter_add("snapshot.abort")
    if isinstance(e, BarrierError):
        # A peer already failed and fanned out through the barrier: name it.
        return CheckpointAbortedError(path, e.rank, e.phase or phase, str(e))
    if barrier is not None:
        try:
            barrier.report_error(
                e if isinstance(e, Exception) else RuntimeError(repr(e)),
                phase=phase,
            )
        except Exception:  # noqa: BLE001 - reporting is best-effort
            pass
    if not isinstance(e, Exception):
        return e
    if isinstance(e, TimeoutError):
        # The barrier (or a store collective) timed out: a peer died or
        # wedged without reporting. The barrier's per-rank arrival markers
        # name WHO is missing, and the fleet bus (when live) adds WHAT it
        # was last doing — "rank 1 (last phase: restore.read)" instead of
        # an unattributed timeout.
        missing = list(getattr(e, "missing_ranks", None) or [])
        culprit: Optional[int] = missing[0] if missing else None
        detail = repr(e)
        if culprit is not None:
            last_phase = None
            try:
                last_phase = telemetry.fleet.peer_phase(culprit)
            except Exception:  # noqa: BLE001 - attribution is best-effort
                pass
            if last_phase:
                detail = f"{detail} (last beaconed phase: {last_phase})"
        return CheckpointAbortedError(path, culprit, phase, detail)
    return CheckpointAbortedError(path, rank, phase, repr(e))


def _chain_len_for(plan: "TakePlan") -> int:
    """Chain length a catalog-managed take records: 0 for a full snapshot,
    base-chain + 1 when the base was catalog-auto-resolved (the preflight
    broadcast carried its recorded chain length to every rank), and a
    conservative 1 for an EXPLICIT user base (its chain, if any, is not
    known SPMD-consistently — the rebase-to-full policy only governs
    auto-selected chains anyway)."""
    if not plan.base:
        return 0
    if plan.base_chain_len >= 0:
        return plan.base_chain_len + 1
    return 1


def _note_chain_commit(plan: "TakePlan", job: str) -> None:
    """Refresh the per-process chain cache on EVERY rank after a
    catalog-managed commit, so the next same-job take auto-selects this
    snapshot without storage I/O. Fail-open diagnostics-grade state."""
    from . import catalog as catalog_mod

    if not knobs.is_catalog_enabled():
        return
    try:
        split = catalog_mod.split_bucket(plan.path)
        if split is not None:
            catalog_mod.note_commit(
                split[0], job, split[1], _chain_len_for(plan)
            )
    except Exception:  # noqa: BLE001 - cache refresh must never fail a take
        logger.debug("chain-cache refresh failed for %s", plan.path,
                     exc_info=True)


class Snapshot:
    """A reference to a persisted snapshot at ``path``.

    Usage::

        app_state = {"model": model_state, "progress": progress}
        snapshot = Snapshot.take("/checkpoints/step_1000", app_state)
        ...
        snapshot = Snapshot("/checkpoints/step_1000")
        snapshot.restore(app_state)
    """

    # Telemetry session of this process's most recent completed
    # take/async_take/restore that had one (explicit ``_telemetry=`` or the
    # TORCHSNAPSHOT_TPU_TRACE knob). Diagnostics only; overwritten per op.
    last_telemetry: Optional["telemetry.Telemetry"] = None

    # SPMD sync-commit sequence (the sync-take analogue of
    # ``PendingSnapshot._seq``): every rank takes snapshots in the same
    # order, so the counter is identical across ranks and keeps commit
    # barrier ids unique when the same path is snapshotted twice.
    _commit_seq = 0

    def __init__(self, path: str, coordinator: Optional[Coordinator] = None) -> None:
        self.path = path
        self._coordinator = coordinator
        self._metadata: Optional[SnapshotMetadata] = None

    # ------------------------------------------------------------------ take
    @classmethod
    def take(
        cls,
        path: str,
        app_state: AppState,
        coordinator: Optional[Coordinator] = None,
        replicated: Optional[List[str]] = None,
        base: Optional[str] = None,
        job: Optional[str] = None,
        step: Optional[int] = None,
        max_chain_len: Optional[int] = None,
        qos: Any = None,
        _telemetry: Optional["telemetry.Telemetry"] = None,
    ) -> "Snapshot":
        """``base``: path of an earlier snapshot for an INCREMENTAL take —
        storage objects byte-identical to the base (matched by size +
        sha256 from its checksum sidecars) are hard-linked (filesystem) or
        server-side copied (GCS/S3) instead of rewritten; any failure falls
        back to a full write. Hard links share inodes, so the base may be
        deleted later without invalidating this snapshot. Near-free
        checkpoints when most state is frozen (LoRA/partial finetunes,
        embedding-heavy models).

        ``job``: opt into the per-bucket snapshot **catalog**
        (``catalog.py``, docs/lifecycle.md): the committed snapshot is
        recorded under ``<parent>/.catalog/`` (job id, ``step``, base
        pointer, chain length, byte attribution), and — when ``base`` is
        not given explicitly — the best base is auto-selected from the
        catalog: the latest committed same-job snapshot, unless its chain
        is already ``max_chain_len`` deltas deep (default:
        ``TORCHSNAPSHOT_TPU_MAX_CHAIN_LEN``), in which case the take
        REBASES to a full snapshot. ``step`` defaults to trailing digits
        of the snapshot name. Selection happens on rank 0 inside the
        preflight round, so every rank uses the same base by construction.

        ``qos``: the take's QoS class (``"foreground"``/``"normal"``/
        ``"background"``, default: the ambient class — NORMAL outside any
        scope). A ``"background"`` take's pipeline yields its next
        admission (budget, io/hash/transfer-pool slots) to
        any higher-class operation in this process — see
        docs/performance.md, "The dataflow engine".

        ``_telemetry``: a :class:`telemetry.Telemetry` session to record
        this take's spans/metrics into (semi-public; the stable switch is
        the ``TORCHSNAPSHOT_TPU_TRACE`` knob). The session is also
        published as ``Snapshot.last_telemetry``."""
        with _qos_scope(qos):
            return cls._take_sync(
                path,
                app_state,
                coordinator,
                replicated,
                base,
                job,
                step,
                max_chain_len,
                _telemetry,
            )

    @classmethod
    def _take_sync(
        cls,
        path: str,
        app_state: AppState,
        coordinator: Optional[Coordinator],
        replicated: Optional[List[str]],
        base: Optional[str],
        job: Optional[str],
        step: Optional[int],
        max_chain_len: Optional[int],
        _telemetry: Optional["telemetry.Telemetry"],
    ) -> "Snapshot":
        cls._validate_app_state(app_state)
        coord = get_coordinator(coordinator)
        rank = coord.get_rank()
        base = cls._maybe_auto_base(base, job, max_chain_len)
        tm, tm_prev = _begin_telemetry(_telemetry)
        telemetry.fleet.note_op("take")
        try:
            plan = cls._plan_take(path, app_state, coord, replicated or [], base)
            event_loop = asyncio.new_event_loop()
            storage = url_to_storage_plugin_in_event_loop(plan.path, event_loop)
            # Store-based commit barrier WITH error fan-out (the async path's
            # LinearBarrier, now on the sync path too): a rank failing
            # mid-write or mid-commit unblocks and fails every peer within
            # the barrier timeout — structured CheckpointAbortedError
            # everywhere, never a peer deadlocked on a dead rank. SPMD seq:
            # every rank constructs sync takes in the same order, so the
            # barrier id is unique per take even when one path repeats.
            barrier = None
            if coord.get_world_size() > 1:
                Snapshot._commit_seq += 1
                barrier = LinearBarrier(
                    store=coord.store,
                    barrier_id=f"commit/{Snapshot._commit_seq}/{plan.path}",
                    rank=rank,
                    world_size=coord.get_world_size(),
                )
            phase = "write"
            try:
                pending_io_work, metadata = cls._take_impl(
                    plan=plan,
                    coord=coord,
                    storage=storage,
                    event_loop=event_loop,
                    is_async_snapshot=False,
                )
                pending_io_work.sync_complete(event_loop)
                LAST_SYNC_DRAIN_STATS.clear()
                LAST_SYNC_DRAIN_STATS.update(pending_io_work.pipeline_stats)
                # Per-rank telemetry artifact, written pre-barrier so the
                # committed snapshot carries every rank's record of how it
                # was written. Fail-open by contract.
                _persist_op_artifact(
                    storage,
                    event_loop,
                    rank=rank,
                    world_size=coord.get_world_size(),
                    op="take",
                    tm=tm,
                    phase_spans=plan.phase_tracker.spans
                    if plan.phase_tracker
                    else None,
                    io_summary=pending_io_work.telemetry_io_summary(),
                )
                # Commit metadata only after ALL ranks finished writing data.
                phase = "commit"
                with telemetry.span("take.commit", cat="take", bridge=True), \
                        _barrier_stall_guard(rank):
                    if barrier is not None:
                        barrier.arrive()
                    if rank == 0:
                        cls._write_snapshot_metadata(
                            metadata, storage, event_loop
                        )
                        # Catalog append rides the commit, pre-barrier:
                        # metadata is already visible (the record implies a
                        # committed snapshot) and peers are still parked in
                        # the barrier, so when take() returns on ANY rank
                        # the bucket's catalog names this snapshot.
                        # Fail-open by contract.
                        if job is not None:
                            cls._append_catalog_record(
                                plan.path,
                                storage,
                                event_loop,
                                world_size=metadata.world_size,
                                job=job,
                                step=step,
                                base=plan.base,
                                chain_len=_chain_len_for(plan),
                            )
                    # ...and return only after the commit is visible:
                    # otherwise a non-zero rank could immediately open the
                    # path for restore and race rank 0's metadata write.
                    if barrier is not None:
                        barrier.depart()
                        # The depart doubles as a full-world rendezvous:
                        # let the coordinator collect collective keys
                        # posted before it.
                        coord.note_external_barrier()
                # Main-thread op end on the fleet bus: GC superseded beacon
                # generations (bounded store occupancy) — fail-open, no-op
                # when the bus is off.
                telemetry.fleet.gc_beacons()
                if job is not None:
                    _note_chain_commit(plan, job)
            except BaseException as e:
                aborted = _abort_exception(plan.path, barrier, rank, phase, e)
                if aborted is e:
                    raise
                raise aborted from e
            finally:
                from . import prepare_cache as prepare_cache_mod

                prepare_cache_mod.release(plan.prepared_entry)
                storage.sync_close(event_loop)
                event_loop.close()
        finally:
            # The op's LAST beacon is an idle one (force-published): peers'
            # dead-beacon detection keys off "last word was mid-op".
            telemetry.fleet.note_op(None)
            _finish_telemetry(tm, tm_prev, coord.get_rank())
        snapshot = cls(path=plan.path, coordinator=coord)
        snapshot._metadata = metadata
        return snapshot

    @classmethod
    def async_take(
        cls,
        path: str,
        app_state: AppState,
        coordinator: Optional[Coordinator] = None,
        replicated: Optional[List[str]] = None,
        base: Optional[str] = None,
        job: Optional[str] = None,
        step: Optional[int] = None,
        max_chain_len: Optional[int] = None,
        qos: Any = None,
        _telemetry: Optional["telemetry.Telemetry"] = None,
    ) -> "PendingSnapshot":
        """Returns after planning + forking device buffers (milliseconds);
        device→host transfer, storage I/O, and the atomic commit all happen on
        a background thread. Training may replace — or donate — the app
        state's arrays immediately after this returns.

        This diverges from the reference (whose ``async_take`` must capture
        all data in host RAM before returning, ``snapshot.py:245-314``)
        because jax arrays are immutable: an on-device fork detaches the
        snapshot from subsequent donation, so the train-step stall is
        planning time only, independent of checkpoint size.

        A telemetry session (``_telemetry=`` or the TORCHSNAPSHOT_TPU_TRACE
        knob) stays active through the background drain and closes — and
        the trace file is written — when the snapshot commits.

        ``job``/``step``/``max_chain_len``: catalog-managed delta chains,
        exactly as in :meth:`take`; the catalog record is appended by the
        background commit thread, after metadata lands and before the
        commit barrier releases.

        ``qos``: the take's QoS class, as in :meth:`take`. The write
        pipeline captures it at planning time, so ``qos="background"``
        classifies the BACKGROUND DRAIN itself: a higher-class operation
        (e.g. a ``qos="foreground"`` restore) arriving mid-drain steals the
        drain's next admission at chunk granularity."""
        cls._validate_app_state(app_state)
        coord = get_coordinator(coordinator)
        with _qos_scope(qos):
            return cls._async_take_impl(
                path,
                app_state,
                coord,
                replicated,
                base,
                job,
                step,
                max_chain_len,
                _telemetry,
            )

    @classmethod
    def _async_take_impl(
        cls,
        path: str,
        app_state: AppState,
        coord: Coordinator,
        replicated: Optional[List[str]],
        base: Optional[str],
        job: Optional[str],
        step: Optional[int],
        max_chain_len: Optional[int],
        _telemetry: Optional["telemetry.Telemetry"],
    ) -> "PendingSnapshot":
        base = cls._maybe_auto_base(base, job, max_chain_len)
        tm, tm_prev = _begin_telemetry(_telemetry)
        telemetry.fleet.note_op("async_take")
        try:
            plan = cls._plan_take(path, app_state, coord, replicated or [], base)
            event_loop = asyncio.new_event_loop()
            storage = url_to_storage_plugin_in_event_loop(plan.path, event_loop)
            try:
                pending_io_work, metadata = cls._take_impl(
                    plan=plan,
                    coord=coord,
                    storage=storage,
                    event_loop=event_loop,
                    is_async_snapshot=True,
                )
            except BaseException:
                # On planning/staging failure no PendingSnapshot exists to
                # own cleanup; close here or the loop + plugin threads leak.
                from . import prepare_cache as prepare_cache_mod

                prepare_cache_mod.release(plan.prepared_entry)
                storage.sync_close(event_loop)
                event_loop.close()
                raise
        except BaseException:
            telemetry.fleet.note_op(None)
            _finish_telemetry(tm, tm_prev, coord.get_rank())
            raise
        return PendingSnapshot(
            path=plan.path,
            pending_io_work=pending_io_work,
            coord=coord,
            metadata=metadata,
            storage=storage,
            event_loop=event_loop,
            tm=tm,
            tm_prev=tm_prev,
            phase_spans=plan.phase_tracker.spans if plan.phase_tracker else None,
            catalog_info=(
                (job, step, plan.base, _chain_len_for(plan))
                if job is not None
                else None
            ),
            prepared_entry=plan.prepared_entry,
        )

    @classmethod
    def _plan_take(
        cls,
        path: str,
        app_state: AppState,
        coord: Coordinator,
        replicated: List[str],
        base: Optional[str],
    ) -> "TakePlan":
        """Flatten local state, fingerprint the plan-shaping structure, and
        run the preflight collective round (one gather to rank 0 + one
        broadcast) that canonicalizes path/base/globs and decides whether
        the cross-take plan cache hits (see ``take_plan.py``).

        Local keys are flattened in sorted order with no interleaved
        barriers: the coordinator's store-based collectives are namespaced
        by generation counters, which stay aligned as long as every rank
        issues the same SPMD sequence — the per-key barrier the reference
        needs to keep c10d collectives from interleaving
        (``snapshot.py:360-370``) buys nothing here and cost O(keys x world)
        store round-trips per take. Constraint (unchanged from the old
        global-union loop, whose barriers could not fix it either): a
        stateful whose ``state_dict()`` itself issues coordinator
        collectives must be present on EVERY rank, or the ranks that skip
        it fall behind on the collective generation counter.
        """
        from .take_plan import (
            TakePlan,
            compute_fingerprint,
            preflight,
            probe_plan,
        )

        # Phase boundaries are telemetry spans; the legacy LAST_TAKE_PHASES
        # dict is derived from the same tracker at the end of _take_impl.
        tracker = telemetry.PhaseTracker(
            cat="take.phase", first="gather_keys_and_flatten"
        )

        # Snapshot the mapping itself: a stateful whose state_dict() mutates
        # the caller's app_state dict must not perturb this iteration.
        app_state = dict(app_state)
        # RNG invariant: capture host RNG state before anything else can
        # advance it, and reinstate it after the take completes, so that a
        # restore reproduces the state as of the start of take().
        rng_states = [
            (key, s, s.state_dict())
            for key, s in app_state.items()
            if isinstance(s, RNGState)
        ]

        manifest: Manifest = {}
        flattened: Dict[str, Any] = {}
        for key in sorted(app_state.keys()):
            stateful = app_state[key]
            if isinstance(stateful, RNGState):
                # Use the pre-captured state, not a fresh (possibly
                # advanced) one.
                sd = next(st for k, s, st in rng_states if k == key)
            else:
                sd = stateful.state_dict()
            mnfst, flat = flatten(sd, prefix=key)
            manifest.update(mnfst)
            flattened.update(flat)
        tracker.mark("gather_keys_and_flatten", then="preflight")

        # The plan-cache probe only matters at world > 1 (preflight
        # bypasses the collectives entirely at world 1 and plans are never
        # stored there), but the fingerprint itself is also the
        # PREPARED-state cache's key (prepare_cache.py), which pays off at
        # every world size — so compute it whenever either cache wants it;
        # with both caches off the single-process stall stays free of the
        # per-leaf descriptor + sha256 cost.
        plan_cache_on = (
            coord.get_world_size() > 1 and knobs.is_plan_cache_enabled()
        )
        if plan_cache_on or knobs.is_prepared_cache_enabled():
            fingerprint = compute_fingerprint(
                flattened, coord.get_world_size(), replicated
            )
            cached = probe_plan(coord, fingerprint) if plan_cache_on else None
        else:
            fingerprint = ""
            cached = None
        # SPMD take counter: every rank increments once per take, so the
        # value doubles as the plan token certifying "stored by take #N".
        coord._take_seq = getattr(coord, "_take_seq", 0) + 1  # type: ignore[attr-defined]
        import hashlib as _hashlib

        keys_sig = _hashlib.sha1(
            "\x00".join(sorted(app_state.keys())).encode()
        ).hexdigest()[:12]
        pf = preflight(
            coord,
            path,
            base,
            replicated,
            plan_token=cached.token if cached is not None else None,
            keys_sig=keys_sig,
        )
        tracker.mark("preflight", then="prepare_write")
        return TakePlan(
            path=pf.path,
            base=pf.base,
            replicated_globs=pf.replicated_globs,
            flattened=flattened,
            manifest=manifest,
            rng_states=rng_states,
            fingerprint=fingerprint,
            cache_hit=pf.hit,
            cached=cached if pf.hit else None,
            phase_tracker=tracker,
            base_chain_len=pf.base_chain_len,
        )

    @classmethod
    def _take_impl(
        cls,
        plan: "TakePlan",
        coord: Coordinator,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        is_async_snapshot: bool,
    ) -> Tuple[PendingIOWork, SnapshotMetadata]:
        from .take_plan import CachedPlan, gather_manifest_delta, store_plan

        rank = coord.get_rank()
        world_size = coord.get_world_size()
        base = plan.base
        # Continue the planning tracker: the gap since its last mark
        # (plugin construction, event-loop creation) lands in the next
        # phase, so the decomposition COVERS the stall instead of leaking
        # un-phased time (test_stall_decomposition's coverage assertion).
        tracker = plan.phase_tracker or telemetry.PhaseTracker(
            cat="take.phase", first="prepare_write"
        )

        def _phase(name: str, then: Optional[str] = None) -> None:
            tracker.mark(name, then=then)

        manifest: Manifest = dict(plan.manifest)
        flattened = plan.flattened
        rng_states = plan.rng_states
        _count_leaves(flattened)

        replicated_paths = cls._match_replicated_paths(
            set(flattened.keys()), plan.replicated_globs
        )
        prepare_timings: Dict[str, float] = {}
        # Prepared-state cache (prepare_cache.py): on a fingerprint hit the
        # prepare + partition + batching stages collapse into re-binding
        # the new step's arrays into the cached stagers. SPMD safety at
        # world > 1: per-rank hit/miss may diverge (an entry is busy while
        # its pipeline drains), so the cache only engages when the miss
        # path is collective-free — world 1, or a certified plan-cache hit
        # (whose replayed assignment makes partition local). Incremental
        # takes (base=) are excluded entirely: dedup-vs-base is a function
        # of the step's BYTES, not its structure, and it relocates manifest
        # entries to the base's files — artifacts a later take must never
        # inherit. Slab paths must also stay fresh per take so the
        # content-keyed incremental index is what dedups them.
        from . import prepare_cache as prepare_cache_mod

        prep_key = None
        prepared = None
        if (
            plan.fingerprint
            and plan.base is None
            and knobs.is_prepared_cache_enabled()
            and (world_size == 1 or plan.cache_hit)
        ):
            prep_key = (
                plan.fingerprint,
                type(storage).__name__,
                is_async_snapshot,
            )
            prepared = prepare_cache_mod.acquire(coord, prep_key)
            # Attached up front so every completion/failure path (sync
            # finally, async error path, background commit finally)
            # releases the busy latch even if this take aborts mid-phase.
            plan.prepared_entry = prepared
        assignment: Dict[str, int] = {}
        if prepared is not None:
            t0 = time.monotonic()
            try:
                local_manifest, write_reqs, assignment = prepared.rebind(
                    flattened, world_size, is_async_snapshot, prepare_timings
                )
            except prepare_cache_mod.RebindMismatch:
                # Should be unreachable (the fingerprint pins the
                # structure); fall back to a full re-prepare.
                logger.warning(
                    "prepared-state rebind mismatch for %s; re-preparing",
                    plan.path,
                    exc_info=True,
                )
                prepare_cache_mod.release(prepared)
                prepare_cache_mod.invalidate(coord, prep_key)
                plan.prepared_entry = None
                prepared = None
            else:
                prepare_timings["cache_hit"] = max(
                    0.0,
                    time.monotonic()
                    - t0
                    - prepare_timings.get("d2h_hint", 0.0),
                )
                manifest.update(local_manifest)
        if prepared is None:
            leaf_index: Optional[Dict[str, List]] = (
                {} if prep_key is not None else None
            )
            local_manifest, write_reqs = prepare_write(
                flattened=flattened,
                rank=rank,
                world_size=world_size,
                replicated_paths=replicated_paths,
                is_async_snapshot=is_async_snapshot,
                timings=prepare_timings,
                leaf_index=leaf_index,
            )
            manifest.update(local_manifest)
        _phase("prepare_write", then="partition")

        if prepared is None:
            write_reqs, assignment = partition_write_reqs_with_assignment(
                manifest,
                write_reqs,
                coord,
                assignment=plan.cached.assignment if plan.cache_hit else None,
            )

            if knobs.is_batching_enabled():
                from .batcher import batch_write_requests

                entries = list(manifest.values())
                _, write_reqs = batch_write_requests(entries, write_reqs)
            if prep_key is not None:
                # Store the post-partition post-batch artifacts for the
                # next take's hit. O(leaves) bookkeeping — the artifacts
                # already exist (this take is using them), so the cache
                # never constructs anything on the critical path; the
                # entry stays busy until this pipeline completes.
                t0 = time.monotonic()
                from .io_preparer import HostCapturedArray, classify

                entry = prepare_cache_mod.PreparedTake(
                    key=prep_key,
                    leaf_kinds={
                        p: (
                            classify(v, world_size),
                            isinstance(v, HostCapturedArray),
                        )
                        for p, v in flattened.items()
                    },
                    leaf_index=leaf_index or {},
                    local_manifest=local_manifest,
                    write_reqs=write_reqs,
                    assignment=assignment,
                )
                prepare_cache_mod.store(coord, prep_key, entry)
                plan.prepared_entry = entry
                prepare_timings["cache_miss"] = time.monotonic() - t0
        _phase("partition", then="d2h_hint")
        # Decompose the dominant stall phases into stage.prepare.* sub-spans
        # (d2h_hint: the defensive device fork, and a blocking host
        # capture's own hints; the drain's transfers are hinted by the lanes
        # under their window, ``d2h.TransferLanes``, never in the stall;
        # stager_construction: per-preparer planning; plan: the remainder;
        # cache_hit / cache_miss: prepared-state rebind / store overhead).
        # Out-of-band notes: they ride the tracker's span list into
        # LAST_TAKE_PHASES and the persisted telemetry artifact without
        # moving the sequential phase boundary.
        for bucket, dur in sorted(prepare_timings.items()):
            tracker.note(f"stage.prepare.{bucket}", dur)

        _phase("d2h_hint", then="manifest_gather")

        if plan.cache_hit:
            global_manifest = gather_manifest_delta(manifest, coord, plan.cached)
        else:
            global_manifest, local_dicts, gathered_dicts = cls._gather_manifest(
                manifest, coord
            )
            if world_size > 1 and knobs.is_plan_cache_enabled():
                store_plan(
                    coord,
                    plan.fingerprint,
                    CachedPlan(
                        token=getattr(coord, "_take_seq", 0),
                        assignment=assignment,
                        local_entry_dicts=local_dicts,
                        gathered_entry_dicts=gathered_dicts,
                    ),
                )
        # None on non-zero ranks: only the committing rank holds the global
        # manifest in memory; everyone else reads it lazily post-commit.
        codec_versions = None
        if knobs.get_compression() != "none":
            from .serialization import codec_library_versions

            codec_versions = codec_library_versions()
        metadata = (
            SnapshotMetadata(
                version=__version__,
                world_size=world_size,
                manifest=global_manifest,
                codec_versions=codec_versions,
            )
            if global_manifest is not None
            else None
        )
        _phase("manifest_gather", then="memory_budget")

        # On a cache hit the hostname all_gather inside the budget
        # computation is skipped: the local world size was derived (and
        # cached in knobs) by the take that populated the plan; the RAM
        # reading itself stays fresh either way.
        memory_budget = get_process_memory_budget_bytes(
            None if plan.cache_hit else coord
        )
        _phase("memory_budget", then="capture")
        if base and not (
            knobs.is_checksums_enabled()
            and knobs.is_dedup_digests_enabled(has_base=True)
        ):
            logger.warning(
                "base=%s ignored: incremental dedup requires checksums and "
                "dedup digests (TORCHSNAPSHOT_TPU_CHECKSUMS / "
                "TORCHSNAPSHOT_TPU_DEDUP_DIGESTS is off) — taking a full "
                "snapshot", base
            )
            base = None

        base_loader = None
        if base:
            # Resolved lazily on the pipeline (for async takes: on the
            # background drain), so reading the base's metadata + sidecars
            # never extends async_take's size-independent stall.
            def base_loader(base=base):
                loop = asyncio.new_event_loop()
                try:
                    return cls._load_base_digests(base, loop)
                except Exception:  # never abort the take over a bad base
                    logger.warning(
                        "base=%s digest load failed; taking a full snapshot",
                        base,
                        exc_info=True,
                    )
                    return None
                finally:
                    loop.close()
        # Runs to the capture point: mutable host state is staged into
        # private buffers; device-array staging is deferred for async
        # snapshots (immutable + defensively forked), so the async stall is
        # planning time plus host-state capture only — the background thread
        # drains device→host→storage under the budget.
        pending_io_work = sync_execute_write_reqs(
            write_reqs=write_reqs,
            storage=storage,
            memory_budget_bytes=memory_budget,
            rank=rank,
            event_loop=event_loop,
            base_loader=base_loader,
            synchronous=not is_async_snapshot,
        )
        _phase("capture")

        # Reinstate the pre-take RNG state (taking a snapshot must not
        # perturb the program's randomness).
        for _, stateful, state in rng_states:
            stateful.load_state_dict(state)
        LAST_TAKE_PHASES.clear()
        LAST_TAKE_PHASES.update(tracker.durations)
        return pending_io_work, metadata

    @classmethod
    def _maybe_auto_base(
        cls,
        base: Optional[str],
        job: Optional[str],
        max_chain_len: Optional[int],
    ) -> Optional[str]:
        """Plant the catalog auto-base sentinel for a ``job=`` take with no
        explicit ``base=``: the preflight round resolves it on rank 0 (one
        catalog reader per take, the result broadcast with the canonical
        path) — see ``catalog.resolve_auto_base``. An explicit base always
        wins; with the catalog knob off the take is a plain full take."""
        if job is None or base is not None or not knobs.is_catalog_enabled():
            return base
        from . import catalog as catalog_mod

        return catalog_mod.auto_base_token(
            job,
            max_chain_len
            if max_chain_len is not None
            else knobs.get_max_chain_len(),
        )

    @classmethod
    def _append_catalog_record(
        cls,
        path: str,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        world_size: int,
        job: str,
        step: Optional[int],
        base: Optional[str],
        chain_len: int,
    ) -> None:
        """Rank 0's commit-time catalog append (fail-open by contract: the
        snapshot is already committed; a failed append only drops it from
        the chain/retention view until ``catalog rebuild``). Byte
        attribution is derived from the snapshot's own checksum sidecars
        vs the base's — no collectives."""
        if not knobs.is_catalog_enabled():
            return
        import re as _re

        from . import catalog as catalog_mod

        try:
            split = catalog_mod.split_bucket(path)
            if split is None:
                logger.warning(
                    "snapshot %s has no parent bucket; catalog record "
                    "skipped", path,
                )
                return
            bucket, name = split
            total, written, deduped = catalog_mod.byte_attribution(
                storage, world_size, base, event_loop
            )
            if step is None:
                m = _re.search(r"(\d+)$", name)
                step = int(m.group(1)) if m else -1
            base_field = None
            if base:
                bsplit = catalog_mod.split_bucket(base)
                base_field = (
                    bsplit[1] if bsplit and bsplit[0] == bucket else base
                )
            record = catalog_mod.CatalogRecord(
                name=name,
                job=job,
                step=int(step),
                wall_time=time.time(),
                base=base_field,
                chain_len=chain_len,
                world_size=world_size,
                bytes_total=total,
                bytes_written=written,
                bytes_deduped=deduped,
            )
            with catalog_mod.Catalog(bucket, event_loop=event_loop) as cat:
                cat.append(record)
                cls._append_step_telemetry_record(
                    cat,
                    storage,
                    event_loop,
                    world_size,
                    job=job,
                    step=int(step),
                    name=name,
                    base=base_field,
                    chain_len=chain_len,
                )
        except Exception:  # noqa: BLE001 - fail-open by contract
            logger.warning(
                "catalog record for %s could not be appended (snapshot "
                "commit unaffected)", path, exc_info=True,
            )

    @classmethod
    def _append_step_telemetry_record(
        cls,
        cat: "Any",
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        world_size: int,
        *,
        job: str,
        step: int,
        name: str,
        base: Optional[str],
        chain_len: int,
    ) -> None:
        """Rank 0's commit-time step-telemetry rollup: merge the per-rank
        artifacts every rank persisted before the commit barrier (so they
        are all readable here) and append the compact step record beside
        the catalog record. Fail-open on its own — a telemetry problem
        must not take down the catalog append it rides with, and the
        record is rebuildable from the artifacts while the snapshot
        lives."""
        if not knobs.is_step_telemetry_enabled():
            return
        if not knobs.is_telemetry_artifacts_enabled():
            return  # no artifacts → nothing to roll up
        try:
            artifacts, problems = telemetry.aggregate.read_artifacts(
                storage, event_loop, world_size, op="take"
            )
            if not artifacts:
                logger.warning(
                    "no telemetry artifacts readable for %s "
                    "(problems: %s); step-telemetry record skipped",
                    name,
                    problems,
                )
                return
            agg = telemetry.aggregate.aggregate(artifacts, world_size)
            record = telemetry.steprecord.build_step_record(
                job,
                step,
                name,
                agg,
                artifacts,
                base=base,
                chain_len=chain_len,
            )
            cat.append_step_telemetry(record)
        except Exception:  # noqa: BLE001 - fail-open by contract
            logger.warning(
                "step-telemetry record for %s could not be appended "
                "(snapshot commit and catalog record unaffected)",
                name,
                exc_info=True,
            )

    def _append_rollout_record(
        self,
        *,
        job: str,
        step: Optional[int],
        rank: int,
        world_size: int,
        event_loop: asyncio.AbstractEventLoop,
    ) -> None:
        """This rank's restore-side (rollout) record: wall time + byte
        attribution from ``LAST_RESTORE_STATS``, appended under the
        bucket's catalog. Per-rank (every rank appends its own file — no
        commit barrier exists to elect a merger behind) and fail-open by
        contract: a telemetry problem never fails the restore."""
        if not knobs.is_catalog_enabled():
            return
        if not knobs.is_step_telemetry_enabled():
            return
        import re as _re

        from . import catalog as catalog_mod

        try:
            split = catalog_mod.split_bucket(self.path)
            if split is None:
                logger.warning(
                    "snapshot %s has no parent bucket; rollout record "
                    "skipped", self.path,
                )
                return
            bucket, name = split
            if step is None:
                m = _re.search(r"(\d+)$", name)
                step = int(m.group(1)) if m else None
            attr = LAST_RESTORE_STATS.get("attribution") or {}
            swarm_rec = LAST_RESTORE_STATS.get("swarm") or {}
            bcast_rec = LAST_RESTORE_STATS.get("bcast") or {}
            if swarm_rec.get("chunks_peer") or swarm_rec.get("chunks_origin"):
                mode = "swarm"
            elif bcast_rec.get("entries") or bcast_rec.get("received"):
                mode = "bcast"
            else:
                mode = "direct"
            record = telemetry.steprecord.build_rollout_record(
                job=job,
                step=step,
                name=name,
                rank=rank,
                world_size=world_size,
                wall_s=LAST_RESTORE_STATS.get("wall_s", 0.0) or 0.0,
                attribution=attr,
                mode=mode,
            )
            with catalog_mod.Catalog(bucket, event_loop=event_loop) as cat:
                cat.append_rollout_record(record)
        except Exception:  # noqa: BLE001 - fail-open by contract
            logger.warning(
                "rollout record for %s could not be appended (restore "
                "unaffected)", self.path, exc_info=True,
            )

    @classmethod
    def _load_base_digests(
        cls, base: str, event_loop: asyncio.AbstractEventLoop
    ) -> Optional[Tuple[str, Dict[str, list]]]:
        """(base root, merged {storage_path: [crc, size, sha256]}) for an
        incremental take, or None when the base can't serve as one
        (uncommitted, or pre-digest sidecars) — the take then proceeds as a
        full snapshot.

        The root is an absolute filesystem path for local/``fs://`` bases
        (dedup = hard links) and the original URL for cloud bases (dedup =
        server-side copies via the target plugin's ``link_in``); a
        base/target storage mismatch simply makes every ``link_in`` refuse
        and the take falls back to full writes."""
        root = base[len("fs://") :] if base.startswith("fs://") else base
        if "://" not in root:
            root = os.path.abspath(root)
        try:
            storage = url_to_storage_plugin_in_event_loop(base, event_loop)
        except Exception:
            # An unusable base (bad URL/scheme, missing SDK, absent
            # credentials) must never abort the checkpoint itself.
            logger.warning(
                "base=%s is unusable; taking a full snapshot",
                base,
                exc_info=True,
            )
            return None
        try:
            try:
                metadata = cls(base)._read_metadata(storage, event_loop)
            except Exception:
                logger.warning(
                    "base=%s has no committed metadata; taking a full snapshot",
                    base,
                )
                return None
            codec = knobs.get_compression()
            # Compressed bitstreams are deterministic only within one codec
            # library version; a version change between base and incremental
            # take silently degrades dedup to full rewrites — make that
            # visible (ADVICE round 2, item 3). Only the ACTIVE codec
            # matters, and only when the base recorded versions at all (an
            # uncompressed or pre-versioning base has nothing to compare).
            if codec != "none" and metadata.codec_versions:
                from .serialization import codec_library_versions

                recorded = metadata.codec_versions.get(codec)
                current = codec_library_versions().get(codec)
                if recorded is not None and recorded != current:
                    logger.warning(
                        "base=%s compressed its objects with %s %s but this "
                        "take uses %s; byte-identical dedup will likely miss "
                        "all compressed objects",
                        base,
                        codec,
                        recorded,
                        current,
                    )
            merged, _, unreadable = _read_checksum_sidecars(
                storage, metadata.world_size, event_loop
            )
            if unreadable:
                # Degraded dedup is acceptable (missing digests just mean
                # full writes for those objects) but must be visible.
                logger.warning(
                    "base=%s: checksum sidecars unreadable (%s); objects "
                    "recorded only there will be fully rewritten",
                    base,
                    unreadable,
                )
            # Skip entries without a collision-resistant content identity
            # (dedup digests were off): an identity-less base then hits the
            # no-digests warning below instead of loading as a silently
            # useless base. ``hashing.record_content_keys`` owns both
            # formats — a v1 whole-object sha AND a v2 tree root qualify.
            digests: Dict[str, Any] = {
                k: v
                for k, v in merged.items()
                if hashing.record_content_keys(v)
            }
            if digests and len(digests) < len(merged):
                # Mixed coverage: some ranks of the base take recorded shas
                # and others didn't (heterogeneous hosts under the auto
                # gate, or knob churn between takes). Dedup still works for
                # the covered objects; make the silent partial rewrite
                # visible instead of letting the log imply full dedup.
                logger.warning(
                    "base=%s: %d of %d objects carry no sha256 dedup "
                    "identity and will be rewritten (ranks of the base "
                    "take disagreed on TORCHSNAPSHOT_TPU_DEDUP_DIGESTS — "
                    "pin it to 1 on every host for full incremental dedup)",
                    base,
                    len(merged) - len(digests),
                    len(merged),
                )
            if not digests:
                logger.warning(
                    "base=%s carries no sha256 dedup identities (no sidecars, "
                    "or its take ran with dedup digests off — the auto "
                    "default on single-core hosts); taking a full snapshot. "
                    "Pin TORCHSNAPSHOT_TPU_DEDUP_DIGESTS=1 for every take to "
                    "checkpoint incrementally on such hosts",
                    base,
                )
                return None
            return root, digests
        finally:
            storage.sync_close(event_loop)

    # --------------------------------------------------------------- restore
    def restore(
        self,
        app_state: AppState,
        _telemetry: Optional["telemetry.Telemetry"] = None,
        include: Optional[List[str]] = None,
        qos: Any = None,
        job: Optional[str] = None,
        step: Optional[int] = None,
    ) -> None:
        """``include``: optional list of logical-path globs (e.g.
        ``["model/encoder/*"]``) restricting the restore to the matching
        manifest subtrees — a lazy partial restore reads ONLY the byte
        ranges those entries need, leaving the rest of the snapshot
        untouched (loading one tower of a model doesn't fetch the others).
        A pattern selects an entry when it fnmatch-es its logical path or
        names one of its ancestors. Statefuls receive a partially-populated
        state dict for the filtered-out leaves; their ``load_state_dict``
        must tolerate that (flax/optax dicts do). SPMD: every rank must
        pass the same ``include``.

        Device-resident targets: a restored ``jax.Array`` leaf is a new
        buffer beside its live target, so the restore peaks at twice the
        state in HBM. When a leaf cannot be allocated beside its target,
        the target's buffers are released first (it is about to be
        replaced; other references to it become deleted arrays) — counted
        as ``restore.targets_consumed`` — so a job whose state fills more
        than half of HBM can still resume into zero targets.

        Failure semantics mirror ``take``: any mid-restore failure —
        transient storms past the retry window, permanent storage faults,
        verification failures, a dead peer — surfaces as a structured
        :class:`CheckpointAbortedError` naming the failing rank and phase
        on EVERY rank within the barrier timeout. The snapshot itself is
        read-only here and stays untouched; live state may be partially
        loaded (restore targets must be re-restored before use), and device
        targets that were consumed to make room (above) are DELETED arrays,
        not stale ones — the closing warning names them, and a retry needs
        fresh targets for those paths.

        ``qos``: the restore's QoS class. ``qos="foreground"`` — the
        serving-replica restart path — registers FOREGROUND demand for the
        WHOLE restore, so any lower-class engine in this process (a
        background drain, scrub, gc, cache populate, a background swarm
        fetch) pauses its next admission at chunk granularity until this
        restore completes.

        ``job``/``step``: opt into the catalog's ROLLOUT record stream —
        each rank appends one compact restore-side record (wall time,
        origin/peer/cache byte attribution) under the bucket's
        ``.catalog/rollouts/``, the read half of the step-telemetry series
        the ``timeline`` CLI trends. Fail-open like every telemetry
        surface; ``step`` defaults to trailing digits of the snapshot
        name."""
        with _qos_scope(qos):
            self._restore_impl(app_state, _telemetry, include, job, step)

    def _restore_impl(
        self,
        app_state: AppState,
        _telemetry: Optional["telemetry.Telemetry"] = None,
        include: Optional[List[str]] = None,
        job: Optional[str] = None,
        step: Optional[int] = None,
    ) -> None:
        self._validate_app_state(app_state)
        event_loop = asyncio.new_event_loop()
        coord = get_coordinator(self._coordinator)
        rank = coord.get_rank()
        tm, tm_prev = _begin_telemetry(_telemetry)
        telemetry.fleet.note_op("restore")
        restore_t0 = time.monotonic()
        from . import bcast as bcast_mod
        from . import swarm as swarm_mod

        bcast_mod.reset_diagnostics()
        swarm_mod.reset_diagnostics()
        LAST_RESTORE_STATS.clear()
        read_totals = {"bytes_read": 0.0, "read_wall_s": 0.0, "requests": 0.0}
        # Where this restore's time goes (restore_times.py): every layer of
        # the read path stamps its own intervals, reduced once at the end.
        times = restore_times.RestoreTimes(tm)
        times_token = restore_times.activate(times)
        # Before any storage IO: the metadata read below would otherwise
        # freeze the FS plugin's O_DIRECT stream cap at the unscaled default
        # in a fresh (restore-only) process.
        memory_budget = get_process_memory_budget_bytes(coord)
        storage = url_to_storage_plugin_in_event_loop(self.path, event_loop)
        # Broadcast restore: resolved once per restore (pure function of
        # world size + knob + the storage plugin's locality flag) so every
        # stateful of this restore — and every rank — agrees on the gate.
        bcast_enabled = knobs.is_broadcast_restore_enabled(
            coord.get_world_size(), storage
        )
        # Swarm restore (chunk-granular peer-to-peer fan-out for replicated
        # objects above the broadcast cap): same once-per-restore gate
        # discipline as broadcast, so every stateful and every rank agree.
        swarm_enabled = knobs.is_swarm_restore_enabled(
            coord.get_world_size(), storage
        )
        # One pool set for every per-stateful read pipeline of this restore
        # (instead of a fresh ThreadPoolExecutor per stateful).
        pools = PipelinePools()
        # The host pages that device-bound leaves are read into, handed from
        # leaf to leaf (host_arena.py); nothing is allocated until the plan
        # meets a leaf bound for a device that copies, and from then until
        # its first read the pages are first touched in the background. This
        # restore's alone.
        arena = host_arena.HostArena(min(host_arena.CAPACITY_BYTES, memory_budget))
        # Post-load rendezvous WITH error fan-out (the take path's
        # LinearBarrier, on the read side too): a rank failing mid-restore
        # unblocks and fails every peer within the barrier timeout —
        # structured CheckpointAbortedError everywhere, never a peer
        # deadlocked waiting on a dead reader.
        barrier = None
        if coord.get_world_size() > 1:
            Snapshot._commit_seq += 1
            barrier = LinearBarrier(
                store=coord.store,
                barrier_id=f"restore/{Snapshot._commit_seq}/{self.path}",
                rank=rank,
                world_size=coord.get_world_size(),
            )
        phase = "restore.plan"
        try:
            # The preamble is planning too, and starts where wall_s does.
            with times.work("plan", since=restore_t0):
                with telemetry.span("restore.read_metadata", cat="restore"):
                    metadata = self._read_metadata(storage, event_loop)
                # The snapshot's parsed checksum sidecars, read once per
                # restore: the read-through cache keys data objects by them,
                # and the read pipeline / broadcast phase verify fetched bytes
                # against them (TORCHSNAPSHOT_TPU_VERIFY_READS).
                digest_index = self._load_digest_index(
                    storage, metadata, event_loop
                )
                self._attach_cache_digests(storage, digest_index)
                phase = "restore.read"
                manifest = get_manifest_for_rank(metadata, rank)
                # One-pass prefix index: bucket entries by their FIRST path
                # segment so per-key planning below is O(bucket), not
                # O(manifest). Without this, restore planning is
                # O(keys x manifest) — at a 10^5-entry manifest with hundreds of
                # keys that is pure quadratic waste (the reference pays the same
                # scan per key, ``snapshot.py:693-701``).
                # Lookup below is by the KEY's first segment (not the key
                # itself): an app key containing '/' spans paths whose first
                # segment is shorter than the key, and _load_stateful's own
                # exact-prefix filter narrows the bucket.
                by_first_seg: Dict[str, Manifest] = {}
                for p, e in manifest.items():
                    by_first_seg.setdefault(p.partition("/")[0], {})[p] = e

                # Restore RNG last so loading other statefuls can't perturb it.
                # One gather+broadcast round resolves the global key order; the
                # per-key barriers of rounds 1-3 are gone: every rank loads the
                # union's keys in the same order, so the coordinator's
                # generation-counted collectives stay aligned without them, and
                # jax ops inside load_state_dict synchronize on their own terms.
                # Restore coordination is then O(1) store round-trips per rank —
                # it runs on the exact path a pod takes while restarting after
                # preemption, where O(keys x world) rounds were added downtime.
                keys = self._gather_keys(dict(app_state), coord)
                rng_keys = [
                    k for k in keys if isinstance(app_state.get(k), RNGState)
                ]
            for key in [k for k in keys if k not in rng_keys] + rng_keys:
                if key in app_state:
                    with telemetry.span(
                        "restore.load_stateful", cat="restore", key=key
                    ):
                        stats = self._load_stateful(
                            key=key,
                            stateful=app_state[key],
                            manifest=by_first_seg.get(key.partition("/")[0], {}),
                            storage=storage,
                            memory_budget=memory_budget,
                            event_loop=event_loop,
                            pools=pools,
                            include=include,
                            bcast_enabled=bcast_enabled,
                            swarm_enabled=swarm_enabled,
                            coord=coord,
                            digests=digest_index,
                            arena=arena,
                        )
                        if stats:
                            read_totals["bytes_read"] += stats.get(
                                "bytes_read", 0.0
                            )
                            read_totals["read_wall_s"] += stats.get(
                                "wall_s", 0.0
                            )
                            read_totals["requests"] += stats.get(
                                "requests", 0.0
                            )
            times.add("pretouched_bytes", arena.pretouched_bytes)
            times.add("pretouch_s", arena.pretouch_s)
            times.add("pretouch_stop_wait_s", arena.pretouch_stop_wait_s)
            with times.work("load"):
                # Restore telemetry artifact
                # (.telemetry/restore_rank_<k>.json): the restore-side
                # record — metrics dump (bytes read per plugin),
                # per-stateful load spans, the split of the restore's time
                # as far as it has come (``restore_stats_s``: all of it but
                # this closing interval) — written through the same plugin,
                # fail-open (a read-only snapshot store just logs once).
                _persist_op_artifact(
                    storage,
                    event_loop,
                    rank=rank,
                    world_size=coord.get_world_size(),
                    op="restore",
                    tm=tm,
                    phase_spans=tm.spans(cat="restore") if tm is not None else None,
                    restore_stats=dict(
                        read_totals,
                        wall_s=time.monotonic() - restore_t0,
                        **times.summary(),
                    ),
                )
                # Single post-load barrier: no rank observes restore() as
                # complete (and e.g. deletes/overwrites the snapshot, or
                # reports readiness) while a peer is still reading storage.
                # LinearBarrier (not coord.barrier): a failing or dead peer
                # fails this rank promptly with attribution instead of a
                # bare timeout.
                phase = "restore.barrier"
                if barrier is not None:
                    with _barrier_stall_guard(rank):
                        barrier.arrive()
                        barrier.depart()
                    # Full-world rendezvous: the coordinator may collect
                    # collective keys (incl. broadcast-restore payloads)
                    # posted before it.
                    coord.note_external_barrier()
                # Main-thread op end on the fleet bus: GC superseded beacon
                # generations (bounded store occupancy).
                telemetry.fleet.gc_beacons()
            LAST_RESTORE_STATS.update(read_totals)
            LAST_RESTORE_STATS.update(times.summary())
            LAST_RESTORE_STATS["wall_s"] = time.monotonic() - restore_t0
            LAST_RESTORE_STATS["bcast"] = dict(bcast_mod.LAST_RESTORE_BCAST)
            LAST_RESTORE_STATS["swarm"] = dict(swarm_mod.LAST_RESTORE_SWARM)
            LAST_RESTORE_STATS["attribution"] = _restore_attribution(
                bcast_mod.LAST_RESTORE_BCAST,
                swarm_mod.LAST_RESTORE_SWARM,
                read_totals,
                storage,
            )
            if job is not None:
                self._append_rollout_record(
                    job=job,
                    step=step,
                    rank=rank,
                    world_size=coord.get_world_size(),
                    event_loop=event_loop,
                )
        except BaseException as e:
            aborted = _abort_exception(self.path, barrier, rank, phase, e)
            if aborted is e:
                raise
            if getattr(e, "_tss_app_hook_error", False):
                # An application load hook raised (marked in
                # _load_stateful): peers were just released with
                # attribution via the barrier report above, but the caller
                # gets the original error type — a missing pytree leaf is
                # a KeyError, not a checkpoint abort.
                raise
            raise aborted from e
        finally:
            restore_times.deactivate(times_token)
            arena.close()
            _warn_consumed_targets()
            telemetry.fleet.note_op(None)
            pools.shutdown()
            storage.sync_close(event_loop)
            event_loop.close()
            _finish_telemetry(tm, tm_prev, rank)

    def _load_stateful(
        self,
        key: str,
        stateful: Stateful,
        manifest: Manifest,
        storage: StoragePlugin,
        memory_budget: int,
        event_loop: asyncio.AbstractEventLoop,
        pools: Optional[PipelinePools] = None,
        include: Optional[List[str]] = None,
        bcast_enabled: bool = False,
        swarm_enabled: bool = False,
        coord: Optional[Coordinator] = None,
        digests: Optional[Dict[str, Any]] = None,
        arena: Optional["host_arena.HostArena"] = None,
    ) -> Dict[str, float]:
        """Restore one stateful, in three stretches that the restore's
        interval sink keeps apart: the plan, the pipeline (broadcast, swarm,
        the read graph with its consumers and inline finalizers, the
        deferred finalizers) and the load."""
        times = restore_times.get_active() or restore_times.RestoreTimes()
        with times.work("plan", path=key):
            plan = self._plan_stateful(
                key, stateful, manifest, storage, memory_budget, event_loop,
                include, bcast_enabled, swarm_enabled, coord, digests, times,
                arena,
            )
        pipeline_t0 = time.monotonic()
        from . import bcast as bcast_mod
        from . import swarm as swarm_mod

        if plan.bcast_items:
            # Broadcast phase first (replicated entries land before the
            # bulk pipeline): one elected rank per object reads storage,
            # the bytes fan out over the coordinator store, every rank
            # consumes + finalizes locally.
            bcast_mod.run_broadcast(
                plan.bcast_items,
                storage,
                coord,
                event_loop,
                executor=pools.consuming_executor() if pools else None,
                digests=digests,
            )

        if plan.swarm_items:
            # Swarm phase: chunk-granular fan-out for replicated objects
            # above the broadcast cap — every rank origin-reads a distinct
            # chunk subset and trades the rest peer-to-peer, each chunk
            # verified against the sidecar grid on receipt. Reshard items
            # ride the same exchange with per-chunk need sets: shared
            # overlap ranges are fetched once fleet-wide, disjoint ones
            # stay plain direct reads.
            swarm_mod.run_swarm(
                plan.swarm_items,
                storage,
                coord,
                event_loop,
                executor=pools.consuming_executor() if pools else None,
                digests=digests,
                need_maps=plan.swarm_need or None,
            )

        read_stats = sync_execute_read_reqs(
            read_reqs=plan.read_reqs,
            storage=storage,
            memory_budget_bytes=memory_budget,
            rank=get_coordinator(self._coordinator).get_rank(),
            event_loop=event_loop,
            pools=pools,
            digests=digests,
        )
        if arena is not None:
            times.add("target_wait_s", arena.take_wait_s())
        # Overlap on: a successful pipeline consumed every read, so every
        # countdown fired and finalized its entry inline; nothing remains.
        assert not plan.finalizers, f"unfinalized entries: {sorted(plan.finalizers)}"
        # Overlap off: the phase split — finalize everything post-pipeline.
        for finalize in plan.deferred_finalizers:
            finalize()
        times.add_pipeline_window(pipeline_t0, time.monotonic())

        with times.work("load", path=key):
            prefix = f"{key}/"
            loaded = plan.loaded
            container_manifest = {
                p: e
                for p, e in manifest.items()
                if (p == key or p.startswith(prefix)) and is_container_entry(e)
            }
            if not container_manifest and len(loaded) == 1 and key in loaded:
                state_dict = loaded[key]
            else:
                full_manifest: Manifest = dict(container_manifest)
                state_dict = inflate(full_manifest, loaded, prefix=key)
            try:
                stateful.load_state_dict(state_dict)
            except Exception as e:
                # The application's own load hook raised: a programming
                # error in app state (shape drift, missing leaf), not a
                # checkpoint fault. Mark it so restore() releases waiting
                # peers but propagates the ORIGINAL exception type to the
                # caller.
                with contextlib.suppress(Exception):
                    e._tss_app_hook_error = True  # type: ignore[attr-defined]
                raise
        return read_stats or {}

    def _plan_stateful(
        self,
        key: str,
        stateful: Stateful,
        manifest: Manifest,
        storage: StoragePlugin,
        memory_budget: int,
        event_loop: asyncio.AbstractEventLoop,
        include: Optional[List[str]],
        bcast_enabled: bool,
        swarm_enabled: bool,
        coord: Optional[Coordinator],
        digests: Optional[Dict[str, Any]],
        times: "restore_times.RestoreTimes",
        arena: Optional["host_arena.HostArena"] = None,
    ) -> "_StatefulPlan":
        """Everything one stateful's restore does before its first byte
        moves: flatten the live values, fetch frame tables, route and plan
        every entry (host targets allocated here), batch the reads."""
        # Per-read cap = the whole process budget: a single object/shard
        # larger than the budget would otherwise be admitted whole through
        # the scheduler's one-over-budget escape hatch — the RSS spike the
        # byte-range sub-read machinery exists to prevent. Reads within the
        # budget stay whole and are paced by the scheduler as usual.
        _memory_budget_bytes_per_read = memory_budget
        # Live values serve as in-place targets (np) or sharding donors (jax).
        _, live_flattened = flatten(stateful.state_dict(), prefix=key)

        prefix = f"{key}/"
        entries = {
            p: e
            for p, e in manifest.items()
            if (p == key or p.startswith(prefix)) and not is_container_entry(e)
        }
        excluded_paths: List[str] = []
        if include:
            # Lazy partial restore: only the requested subtrees are planned,
            # so only their byte ranges are ever fetched. Excluded leaves
            # keep their LIVE values (seeded into ``loaded`` below), so the
            # state dict handed to ``load_state_dict`` stays full-shaped
            # and the un-restored parts of the stateful are untouched.
            selected = {
                p: e
                for p, e in entries.items()
                if _matches_include(p, include)
            }
            excluded_paths = [p for p in entries if p not in selected]
            entries = selected
        loaded: Dict[str, Any] = {}
        for p in excluded_paths:
            if p in live_flattened:
                loaded[p] = live_flattened[p]
        read_reqs: List[ReadReq] = []
        # Overlapped restore (knob-gated, see is_restore_overlap_enabled):
        # each entry's finalizer (its host → device transfer) runs ON THE
        # EVENT-LOOP THREAD the moment the entry's last read has been
        # consumed — inline in the consume coroutine, so H2D overlaps the
        # storage reads still in flight instead of serializing after the
        # whole pipeline, and each entry's host buffers are released as
        # soon as it is finalized (the counting consumer drops its target
        # reference after consuming; the finalizer closure dies right after
        # it runs), bounding restore peak transient RSS by the scheduler
        # budget + in-flight entries rather than state size. The loop thread IS the main thread, so jax dispatch
        # stays where it is fast. Two rejected alternatives, both measured
        # on the reshard workload: finalizing on an executor thread (round
        # 3: 12x slower — jax dispatch off the main thread) and running the
        # pipeline on a background thread with a main-thread finalizer pump
        # (round 4: 2.5x slower — cross-thread loop wakeups). On CPU-backend
        # hosts with no spare core even inline overlap loses (the copy
        # executes on the host's only core and starves behind GIL-holding
        # consumers) — but with a real accelerator backend the device_put
        # is a PJRT hand-off and overlap won 1.5x even on one core
        # (round 5, a harness since deleted; not measured on the current
        # chip), hence the platform-aware
        # auto gate; gated off, finalizers run phase-split after the
        # pipeline.
        # The hint keeps a numpy-only restore from consulting (and thereby
        # initializing) the jax backend inside the knob; live device
        # targets imply jax is already up, making the platform probe free.
        # The gate derives from the TARGET arrays' shard devices (callable:
        # evaluated only on the knob's single-core branch), not the
        # process-default backend — they disagree exactly when a CPU-default
        # process restores onto an explicitly-addressed accelerator.
        def _target_platforms() -> Set[str]:
            platforms: Set[str] = set()
            for v in live_flattened.values():
                if is_jax_array(v):
                    for d in v.sharding.device_set:
                        platforms.add(getattr(d, "platform", "cpu"))
            return platforms

        overlap = knobs.is_restore_overlap_enabled(
            has_jax_targets=any(
                is_jax_array(v) for v in live_flattened.values()
            ),
            target_platforms=_target_platforms,
        )
        finalizers: Dict[int, Callable[[], None]] = {}
        deferred_finalizers: List[Callable[[], None]] = []
        frame_tables = _fetch_frame_tables(
            [(e, live_flattened.get(p)) for p, e in entries.items()],
            storage,
            event_loop,
            _memory_budget_bytes_per_read,
        )
        from . import bcast as bcast_mod
        from . import swarm as swarm_mod

        bcast_items: List["bcast_mod.BroadcastItem"] = []
        swarm_items: List["swarm_mod.SwarmItem"] = []
        swarm_need: Dict[str, List[frozenset]] = {}
        for idx, (logical_path, entry) in enumerate(entries.items()):
            live = live_flattened.get(logical_path)
            # direct / bcast / swarm / reshard, selected SPMD-pure per
            # entry (size, world gate, knobs, sidecar chunk grids, and the
            # GLOBAL target sharding — identical on every rank):
            # replicated entries under BCAST_MAX_BYTES ride the
            # single-reader broadcast, larger chunk-addressable ones the
            # peer-to-peer swarm, sharded-onto-sharded reshards the
            # need-aware swarm, everything else the direct pipeline.
            mode = bcast_mod.select_restore_mode(
                entry,
                live,
                bcast_enabled and coord is not None,
                swarm_enabled and coord is not None,
                digests,
            )
            if mode == "reshard":
                # Need sets from the global device→index map: which ranks'
                # exact-overlap plans touch each hash chunk of each shard
                # object. Pure, so every rank computes the identical map —
                # including the identical None on failure (all fall back
                # to direct together).
                need = swarm_mod.plan_reshard_need(
                    entry,
                    live.sharding,
                    entry.shape,
                    digests,
                    coord.get_world_size(),
                )
                if need is None:
                    mode = "direct"
                else:
                    reqs, finalize = _prepare_restore_one(
                        logical_path,
                        entry,
                        live,
                        loaded,
                        buffer_size_limit_bytes=None,
                        frame_tables=frame_tables,
                        digests=digests,
                    )
                    swarm_need.update(need)
                    swarm_items.append(
                        swarm_mod.SwarmItem(
                            logical_path,
                            reqs,
                            finalize,
                            paths=[s.tensor.location for s in entry.shards],
                        )
                    )
                    continue
            if mode in ("bcast", "swarm"):
                # Collective path. Planned with NO budget sub-read limit so
                # the (path, byte_range) sequence is a pure function of the
                # entry — identical on every rank, which the fenced store
                # keys below require. Bounded by BCAST_MAX_BYTES (bcast) /
                # one-object-at-a-time chunk assembly (swarm).
                reqs, finalize = _prepare_restore_one(
                    logical_path,
                    entry,
                    live,
                    loaded,
                    buffer_size_limit_bytes=None,
                    frame_tables=frame_tables,
                    digests=digests,
                )
                if mode == "bcast":
                    bcast_items.append(
                        bcast_mod.BroadcastItem(logical_path, reqs, finalize)
                    )
                else:
                    swarm_items.append(
                        swarm_mod.SwarmItem(logical_path, reqs, finalize)
                    )
                continue
            reqs, finalize = _prepare_restore_one(
                logical_path,
                entry,
                live,
                loaded,
                buffer_size_limit_bytes=_memory_budget_bytes_per_read,
                frame_tables=frame_tables,
                digests=digests,
                # Views go back as finalizers run: with the finalizers put
                # off to the pipeline's end, none would.
                arena=arena if overlap else None,
            )
            if finalize is not None:
                if not reqs:
                    # Nothing to read (e.g. no saved shard overlaps this
                    # process): finalize immediately.
                    finalize()
                else:
                    # The countdown notes when the entry's last read was
                    # consumed — what its finalizer then waits from — and,
                    # with overlap on, runs the finalizer at that moment.
                    countdown = _ReadCountdown(
                        idx, len(reqs), finalizers if overlap else None
                    )
                    finalize = countdown.waited(finalize, times)
                    if overlap:
                        finalizers[idx] = finalize
                    else:
                        deferred_finalizers.append(finalize)
                    reqs = [
                        ReadReq(
                            path=r.path,
                            buffer_consumer=_CountingConsumer(
                                r.buffer_consumer, countdown
                            ),
                            byte_range=r.byte_range,
                        )
                        for r in reqs
                    ]
            read_reqs.extend(reqs)

        if knobs.is_batching_enabled():
            from .batcher import batch_read_requests

            read_reqs = batch_read_requests(
                read_reqs, max_merged_bytes=_memory_budget_bytes_per_read
            )
        return _StatefulPlan(
            loaded,
            read_reqs,
            finalizers,
            deferred_finalizers,
            bcast_items,
            swarm_items,
            swarm_need,
        )

    # ----------------------------------------------------------- read_object
    def read_object(
        self,
        path: str,
        obj_out: Optional[Any] = None,
        memory_budget_bytes: Optional[int] = None,
    ) -> Any:
        """Random access to one persisted object — or a manifest SUBTREE —
        addressed as ``"<rank>/<logical_path>"`` (reference
        ``snapshot.py:507-612``).

        A leaf path returns that value. A container path (or any prefix of
        logical paths) performs a **lazy partial read**: only the entries
        under the subtree are planned, their byte ranges coalesced through
        the read batcher, and the nested structure is rebuilt and returned
        — loading one tower of a model never touches the rest of the
        snapshot. ``obj_out`` applies to leaf reads only.

        Works against cloud storage via ranged reads without fetching the
        whole snapshot; ``memory_budget_bytes`` caps host RSS for huge
        arrays by fetching budget-sized byte ranges.

        This is a single-rank API: it runs no collectives, so any subset of
        ranks may call it independently.
        """
        event_loop = asyncio.new_event_loop()
        storage = url_to_storage_plugin_in_event_loop(self.path, event_loop)
        try:
            metadata = self._read_metadata(storage, event_loop)
            digest_index = self._load_digest_index(storage, metadata, event_loop)
            self._attach_cache_digests(storage, digest_index)
            rank_str, _, logical_path = path.partition("/")
            manifest = get_manifest_for_rank(metadata, int(rank_str))
            entry = manifest.get(logical_path)
            if entry is None or is_container_entry(entry):
                return self._read_subtree(
                    path,
                    logical_path,
                    manifest,
                    storage,
                    event_loop,
                    memory_budget_bytes,
                    digests=digest_index,
                )
            if isinstance(entry, PrimitiveEntry):
                return entry.get_value()
            loaded: Dict[str, Any] = {}
            frame_tables = _fetch_frame_tables(
                [(entry, obj_out)], storage, event_loop, memory_budget_bytes
            )
            reqs, finalize = _prepare_restore_one(
                logical_path,
                entry,
                obj_out,
                loaded,
                buffer_size_limit_bytes=memory_budget_bytes,
                frame_tables=frame_tables,
                digests=digest_index,
            )
            from .batcher import batch_read_requests

            reqs = batch_read_requests(
                reqs, max_merged_bytes=memory_budget_bytes
            )
            sync_execute_read_reqs(
                read_reqs=reqs,
                storage=storage,
                # coordinator=None: budget from local memory only — no
                # collectives in this single-rank path.
                memory_budget_bytes=memory_budget_bytes
                or get_process_memory_budget_bytes(None),
                rank=0,
                event_loop=event_loop,
                digests=digest_index,
            )
            if finalize is not None:
                finalize()
            return loaded[logical_path]
        finally:
            storage.sync_close(event_loop)
            event_loop.close()

    def _read_subtree(
        self,
        path: str,
        logical_path: str,
        manifest: Manifest,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        memory_budget_bytes: Optional[int],
        digests: Optional[Dict[str, Any]] = None,
    ) -> Any:
        """Lazy partial read of one manifest subtree: plan only the entries
        under ``logical_path``, coalesce their byte ranges through the read
        batcher (near-adjacent slab-member ranges merge per the
        READ_MERGE_GAP_BYTES knob), execute, and inflate the nested
        structure. The rest of the snapshot's bytes are never requested."""
        sub_prefix = f"{logical_path}/"
        leaves = {
            p: e
            for p, e in manifest.items()
            if (p == logical_path or p.startswith(sub_prefix))
            and not is_container_entry(e)
        }
        if not leaves:
            raise KeyError(
                f"{path!r} not found in snapshot (no entries under "
                f"{logical_path!r})"
            )
        loaded: Dict[str, Any] = {}
        read_reqs: List[ReadReq] = []
        finalizers: List[Callable[[], None]] = []
        frame_tables = _fetch_frame_tables(
            [(e, None) for e in leaves.values()],
            storage,
            event_loop,
            memory_budget_bytes,
        )
        for p, entry in leaves.items():
            reqs, finalize = _prepare_restore_one(
                p,
                entry,
                None,
                loaded,
                buffer_size_limit_bytes=memory_budget_bytes,
                frame_tables=frame_tables,
                digests=digests,
            )
            read_reqs.extend(reqs)
            if finalize is not None:
                finalizers.append(finalize)
        from .batcher import batch_read_requests

        read_reqs = batch_read_requests(
            read_reqs, max_merged_bytes=memory_budget_bytes
        )
        sync_execute_read_reqs(
            read_reqs=read_reqs,
            storage=storage,
            memory_budget_bytes=memory_budget_bytes
            or get_process_memory_budget_bytes(None),
            rank=0,
            event_loop=event_loop,
            digests=digests,
        )
        for finalize in finalizers:
            finalize()
        containers = {
            p: e
            for p, e in manifest.items()
            if (p == logical_path or p.startswith(sub_prefix))
            and is_container_entry(e)
        }
        return inflate(containers, loaded, prefix=logical_path)

    def _load_digest_index(
        self,
        storage: StoragePlugin,
        metadata: SnapshotMetadata,
        event_loop: asyncio.AbstractEventLoop,
    ) -> Optional[Dict[str, Any]]:
        """The snapshot's merged checksum-sidecar map (``{path: [crc32,
        size, sha256 | None]}``), read once per restore/read_object when
        anything will consume it — the read-through cache (digest keying +
        hit verification) or the read pipeline / broadcast phase
        (``TORCHSNAPSHOT_TPU_VERIFY_READS``). None when nothing needs it or
        the sidecars are unreadable (fail-open: readers degrade to
        unverified, path-keyed behavior — a missing sidecar must never fail
        a restore that checksums-off takes produced legitimately)."""
        wants_digests = bool(knobs.get_read_cache_dir()) or (
            knobs.get_verify_reads_mode() != "off"
        )
        if not wants_digests:
            return None
        try:
            merged, _, _ = _read_checksum_sidecars(
                storage, metadata.world_size, event_loop
            )
        except Exception:  # noqa: BLE001 - degrade, never fail the restore
            logger.warning(
                "could not read checksum sidecars; restore reads proceed "
                "unverified and the read cache stays path-keyed",
                exc_info=True,
            )
            return None
        return merged or None

    def _attach_cache_digests(
        self,
        storage: StoragePlugin,
        digest_index: Optional[Dict[str, Any]],
    ) -> None:
        """When a read-through cache wraps this plugin stack, hand it the
        snapshot's ``{path: (size, sha256)}`` dedup digests (from the
        checksum sidecars) so data-object reads become content-addressed.
        Fail-open: without an index those reads just stay path-keyed."""
        if not digest_index or not knobs.get_read_cache_dir():
            return
        from .storage_plugins.cache import find_read_cache

        cache = find_read_cache(storage)
        if cache is None:
            return
        # One 4-tuple per object: (size, cache-key, crc, chunk-info). A v1
        # sha (or v2 tree root + grain) makes the cache entry
        # content-addressed; a key-less record (dedup digests off at take
        # time) still enables size+crc validation of path-keyed hits. v2
        # chunk info lets the cache verify only the chunks a ranged hit
        # actually serves.
        index = {}
        for p, v in digest_index.items():
            size = hashing.record_size(v)
            if size is None:
                continue
            index[p] = (
                size,
                hashing.record_cache_key(v),
                hashing.record_crc(v),
                hashing.record_chunk_info(v),
            )
        if index:
            cache.attach_digest_index(index)

    def verify(self) -> Dict[str, str]:
        """Audit the snapshot's storage objects against the CRC32 sidecars
        recorded at write time (``.checksums.<rank>``, one per rank; written
        pre-commit, so every committed snapshot taken with
        ``TORCHSNAPSHOT_TPU_CHECKSUMS=1`` — the default — carries them).

        Returns a ``{storage_path: problem}`` dict. Problem classes:
        ``"missing"`` (the object is absent — ``FileNotFoundError`` per the
        StoragePlugin contract), ``"crc mismatch (...)"`` (corrupted bytes),
        ``"unreadable (...)"`` (the read failed for a non-absence reason,
        e.g. throttling past the plugin's retry window — possibly
        transient), ``"sidecar unreadable (...)"`` (a ``.checksums.<rank>``
        file exists but can't be read/parsed), and ``"unverified (...)"``
        (a manifest object no readable sidecar covers). Empty dict ==
        clean. Raises ``RuntimeError`` if the manifest references storage
        objects but no checksum sidecar exists at all (taken with checksums
        disabled); a snapshot of only inline primitives has no objects to
        audit and returns clean.

        Beyond the reference's capability surface: it has no integrity
        audit; this one enables post-transfer/post-incident validation
        without a full restore.
        """
        import zlib as _zlib

        from .utils import knobs as _knobs

        event_loop = asyncio.new_event_loop()
        storage = url_to_storage_plugin_in_event_loop(self.path, event_loop)
        try:
            metadata = self._read_metadata(storage, event_loop)
            # Can't tell "rank wrote no objects" from "sidecar lost"; the
            # manifest cross-check below reports uncovered objects either way.
            expected, sidecars, unreadable = _read_checksum_sidecars(
                storage, metadata.world_size, event_loop
            )
            manifest_locations = _manifest_storage_locations(metadata.manifest)
            if not sidecars and not unreadable:
                if not manifest_locations:
                    # All-primitive snapshot: no storage objects were ever
                    # written, so there is nothing to audit — trivially clean.
                    return {}
                raise RuntimeError(
                    "snapshot has no checksum sidecars (taken with "
                    "TORCHSNAPSHOT_TPU_CHECKSUMS=0?); nothing to verify"
                )
            problems: Dict[str, str] = {}
            # A sidecar that exists but can't be read/parsed is its own
            # problem class: the integrity metadata may be intact on the
            # backend (transient throttling), so don't misreport its
            # objects as 'unverified (no checksum recorded)'.
            for r, err in sorted(unreadable.items()):
                problems[f"{CHECKSUM_FILE_PREFIX}{r}"] = (
                    f"sidecar unreadable ({err})"
                )
            # Coverage cross-check: every storage object the manifest points
            # at must carry a recorded checksum, else a lost sidecar would
            # yield a false "clean".
            for location in sorted(manifest_locations):
                if location not in expected:
                    problems[location] = _uncovered_problem(location, unreadable)

            async def check_all() -> None:
                # A BACKGROUND-class engine graph: one `verify` node per
                # object, costed at its recorded size, capped by the IO
                # knob AND the process memory budget (16 concurrent
                # full-object reads of 512 MB shards would otherwise buffer
                # ~8 GB — an OOM on the small operator VMs this audit
                # targets) — and ledger-audited like every other pipeline.
                # At BACKGROUND priority the audit yields its next
                # admission to any NORMAL/FOREGROUND take or restore in
                # this process.
                from .engine import Node as _Node
                from .engine import Priority as _Priority
                from .engine import run_graph as _run_graph

                budget_total = get_process_memory_budget_bytes(None)

                def make_check(path: str, want):
                    async def check(_ctx, _payload) -> None:
                        read_io = ReadIO(path=path)
                        try:
                            await storage.read(read_io)
                        except FileNotFoundError:
                            problems[path] = "missing"
                            return
                        except Exception as e:  # noqa: BLE001
                            # Same distinction as for sidecars: a read
                            # failing past the plugin's retry window is
                            # not evidence the object is gone.
                            problems[path] = f"unreadable ({e!r})"
                            return
                        got = _zlib.crc32(read_io.buf.getbuffer())
                        # Sidecar value: bare crc int (pre-digest
                        # snapshots), [crc, size, sha256] (v1), or a v2
                        # tree record — whose combined crc is
                        # bit-identical to the serial fold, so this
                        # quick audit needs no per-chunk work.
                        want_crc = hashing.record_crc(want)
                        if want_crc is not None and got != want_crc:
                            problems[path] = (
                                f"crc mismatch (recorded {want_crc}, "
                                f"found {got})"
                            )

                    return check

                nodes = []
                for path, want in sorted(expected.items()):
                    # Recorded size when the sidecar has one (v1 list or v2
                    # tree record); a conservative slice of the budget for
                    # legacy int-format entries. Oversize objects clamp to
                    # the whole budget and are admitted alone (the engine's
                    # over-budget escape).
                    rec_size = hashing.record_size(want)
                    cost = (
                        rec_size if rec_size is not None else budget_total // 8
                    )
                    nodes.append(
                        _Node(
                            "verify",
                            make_check(path, want),
                            cost_bytes=min(cost, budget_total),
                            pool="io",
                            path=path,
                        )
                    )
                await _run_graph(
                    nodes,
                    budget_bytes=budget_total,
                    owner="verify",
                    kind="verify",
                    caps={
                        "io": lambda: _knobs.get_max_concurrent_io_for(
                            storage
                        )
                    },
                    priority=_Priority.BACKGROUND,
                )

            event_loop.run_until_complete(check_all())
            return problems
        finally:
            storage.sync_close(event_loop)
            event_loop.close()

    # ------------------------------------------------------------------ scrub
    def scrub(self, repair: bool = False) -> Dict[str, Any]:
        """Deep integrity audit — and with ``repair=True``, self-healing —
        of one committed snapshot.

        Streams every storage object the manifest references through the
        same budgeted, concurrency-capped read discipline restores use and
        validates each against the checksum sidecars (size, then sha256
        when recorded, else crc32) and every framed payload's ``.ftab``
        frame table (parseable, frame sizes summing to the payload
        length). Where ``verify()`` is the quick crc audit, scrub is the
        full bit-rot sweep a serving fleet runs on a schedule.

        Returns a structured per-entry report::

            {"entries": {path: {"status": ..., "detail": ...}},
             "objects": N, "bytes": N, "problems": N,
             "corrupt": N, "repaired": N, "quarantined": N, "clean": bool}

        Statuses: ``ok``, ``corrupt`` (bytes exist but don't match the
        recorded digest), ``missing``, ``unreadable`` (non-absence read
        failure — possibly transient), ``unverified`` (no readable sidecar
        covers the object), ``ftab-mismatch``, and under ``repair=True``
        ``repaired`` / ``quarantined``.

        ``repair=True``: a corrupt or missing object whose exact content
        survives elsewhere in the snapshot — an alternate rank's copy of
        the same replicated value, or any object with identical (size,
        sha256) in the sidecar index (incremental chains dedup by exactly
        this identity) — is rewritten from that clean copy and
        re-verified. Unrepairable corrupt objects are **quarantined**:
        their bytes are moved aside to ``<path>.quarantined`` (so a later
        restore fails fast with ``missing`` instead of silently consuming
        rot; ``Snapshot.gc`` reclaims quarantined files as unreferenced
        debris) and any read-cache entries for the path are purged.

        Single-rank API: no collectives; any operator host can run it.
        """
        event_loop = asyncio.new_event_loop()
        storage = url_to_storage_plugin_in_event_loop(self.path, event_loop)
        try:
            with telemetry.span("scrub.scan", cat="scrub", path=self.path):
                return self._scrub_impl(storage, event_loop, repair)
        finally:
            storage.sync_close(event_loop)
            event_loop.close()

    def _scrub_impl(
        self,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        repair: bool,
    ) -> Dict[str, Any]:
        import zlib as _zlib

        metadata = self._read_metadata(storage, event_loop)
        expected, _found, unreadable_sidecars = _read_checksum_sidecars(
            storage, metadata.world_size, event_loop
        )
        locations = sorted(_manifest_storage_locations(metadata.manifest))
        framed = _framed_locations(metadata.manifest)
        entries: Dict[str, Dict[str, str]] = {}
        sizes: Dict[str, int] = {}  # actual bytes read per path
        bytes_scanned = 0
        # Content index for repair: (size, content-key) -> clean source
        # paths, keyed by every identity the record carries (v1 whole-sha
        # AND/OR v2 tree root). Populated as objects VERIFY, so a repair
        # source is always bytes this scrub has itself validated.
        clean_by_content: Dict[Tuple[int, str], List[str]] = {}
        # v2 chunk attribution: path -> corrupt chunk indices, feeding the
        # repair pass's chunk-extent rewrites.
        corrupt_chunks: Dict[str, List[int]] = {}

        def record(path: str, status: str, detail: str = "") -> None:
            entries[path] = {"status": status, "detail": detail}

        def digest_of(path: str):
            """The raw sidecar record (legacy int, v1 list, or v2 dict) —
            interpreted everywhere via ``hashing``'s accessors."""
            rec = expected.get(path)
            if (
                isinstance(rec, int)
                or hashing.record_size(rec) is not None
            ):
                return rec
            return None

        async def scan_all() -> None:
            # Same memory discipline as verify(), same machinery: one
            # BACKGROUND-class engine graph of costed `verify` nodes (IO
            # cap + byte budget, so scrubbing 512 MB shards can't OOM a
            # small operator VM) — the scheduled bit-rot sweep yields its
            # next admission to any serving restore or live take in this
            # process, and its budget is ledger-audited like every other
            # pipeline's.
            from .engine import Node as _Node
            from .engine import Priority as _Priority
            from .engine import run_graph as _run_graph

            budget_total = get_process_memory_budget_bytes(None)

            def make_scan(path: str, want):
                async def scan(_ctx, _payload) -> None:
                    nonlocal bytes_scanned
                    read_io = ReadIO(path=path)
                    try:
                        await storage.read(read_io)
                    except FileNotFoundError:
                        record(path, "missing")
                        return
                    except Exception as e:  # noqa: BLE001 - reported
                        record(path, "unreadable", repr(e))
                        return
                    data = read_io.buf.getbuffer()
                    sizes[path] = data.nbytes
                    bytes_scanned += data.nbytes
                    if want is None:
                        record(
                            path,
                            "unverified",
                            _uncovered_problem(path, unreadable_sidecars),
                        )
                        return
                    size_want = hashing.record_size(want)
                    if size_want is not None and data.nbytes != size_want:
                        record(
                            path,
                            "corrupt",
                            f"size {data.nbytes} != recorded {size_want}",
                        )
                        return
                    info = hashing.record_chunk_info(want)
                    if info is not None:
                        # v2 tree record: per-chunk audit attributes
                        # corruption to the exact chunk(s), and the
                        # repair pass can rewrite just their extents.
                        bad = hashing.find_bad_chunks(data, want)
                        if bad:
                            grain = info[0]
                            kind = (
                                "sha256" if info[1] is not None else "crc32"
                            )
                            corrupt_chunks[path] = bad
                            record(
                                path,
                                "corrupt",
                                f"chunk {kind} mismatch at chunk(s) "
                                f"{bad} (grain {grain})",
                            )
                            return
                    else:
                        sha_want = hashing.record_whole_sha(want)
                        if sha_want:
                            got = hashlib.sha256(data).hexdigest()
                            if got != sha_want:
                                record(
                                    path,
                                    "corrupt",
                                    f"sha256 {got} != recorded {sha_want}",
                                )
                                return
                        crc_want = hashing.record_crc(want)
                        got_crc = _zlib.crc32(data)
                        if isinstance(crc_want, int) and got_crc != crc_want:
                            record(
                                path,
                                "corrupt",
                                f"crc32 {got_crc} != recorded {crc_want}",
                            )
                            return
                    record(path, "ok")
                    if size_want is not None:
                        for key in hashing.record_content_keys(want):
                            clean_by_content.setdefault(
                                (size_want, key), []
                            ).append(path)

                return scan

            nodes = []
            for path in locations:
                want = digest_of(path)
                rec_size = hashing.record_size(want)
                cost = rec_size if rec_size is not None else budget_total // 8
                nodes.append(
                    _Node(
                        "verify",
                        make_scan(path, want),
                        cost_bytes=min(cost, budget_total),
                        pool="io",
                        path=path,
                    )
                )
            await _run_graph(
                nodes,
                budget_bytes=budget_total,
                owner="scrub",
                kind="scrub",
                caps={
                    "io": lambda: knobs.get_max_concurrent_io_for(storage)
                },
                priority=_Priority.BACKGROUND,
            )

        event_loop.run_until_complete(scan_all())

        # Frame-table validation: every framed payload's .ftab must parse
        # and its frame sizes must sum to the payload's actual length —
        # a rotten table silently breaks budgeted sub-reads and slab-member
        # reads even when the payload bytes are pristine.
        event_loop.run_until_complete(
            self._scrub_ftabs(storage, framed, sizes, record)
        )

        # Sidecar files that exist but could not be read/parsed: their own
        # problem class, same attribution verify() gives.
        for r, err in sorted(unreadable_sidecars.items()):
            record(
                f"{CHECKSUM_FILE_PREFIX}{r}", "unreadable",
                f"sidecar unreadable ({err})",
            )

        repaired = quarantined = 0
        if repair:
            repaired, quarantined = event_loop.run_until_complete(
                self._scrub_repair(
                    storage, entries, digest_of, clean_by_content,
                    corrupt_chunks,
                )
            )

        corrupt = sum(
            1 for e in entries.values() if e["status"] == "corrupt"
        )
        problems = sum(
            1 for e in entries.values() if e["status"] not in ("ok", "repaired")
        )
        telemetry.counter_add("scrub.objects", len(locations))
        telemetry.counter_add("scrub.bytes", bytes_scanned)
        if corrupt:
            telemetry.counter_add("scrub.corrupt", corrupt)
        if repaired:
            telemetry.counter_add("scrub.repaired", repaired)
        if quarantined:
            telemetry.counter_add("scrub.quarantined", quarantined)
        return {
            "entries": entries,
            "objects": len(locations),
            "bytes": bytes_scanned,
            "problems": problems,
            "corrupt": corrupt,
            "repaired": repaired,
            "quarantined": quarantined,
            "clean": problems == 0,
        }

    async def _scrub_ftabs(
        self,
        storage: StoragePlugin,
        framed: Set[str],
        sizes: Dict[str, int],
        record: Callable[..., None],
    ) -> None:
        import json as _json

        from .io_preparers.array import FRAME_TABLE_SUFFIX

        sem = asyncio.Semaphore(knobs.get_max_concurrent_io_for(storage))

        async def check_one(loc: str) -> None:
            ftab_path = loc + FRAME_TABLE_SUFFIX
            async with sem:
                read_io = ReadIO(path=ftab_path)
                try:
                    await storage.read(read_io)
                except FileNotFoundError:
                    record(ftab_path, "missing", f"frame table of {loc}")
                    return
                except Exception as e:  # noqa: BLE001 - reported
                    record(ftab_path, "unreadable", repr(e))
                    return
            try:
                parsed = _json.loads(read_io.buf.getvalue().decode())
                frame_sizes = [int(s) for s in parsed["sizes"]]
                if parsed.get("member_framed") and len(frame_sizes) != len(
                    parsed["raw_sizes"]
                ):
                    raise ValueError(
                        f"{len(frame_sizes)} frames vs "
                        f"{len(parsed['raw_sizes'])} raw sizes"
                    )
            except Exception as e:  # noqa: BLE001 - a rotten table
                record(ftab_path, "ftab-mismatch", f"unparseable: {e!r}")
                return
            payload_size = sizes.get(loc)
            if payload_size is not None and sum(frame_sizes) != payload_size:
                record(
                    ftab_path,
                    "ftab-mismatch",
                    f"frames sum to {sum(frame_sizes)} but payload is "
                    f"{payload_size} bytes",
                )
            else:
                record(ftab_path, "ok")

        await asyncio.gather(*(check_one(loc) for loc in sorted(framed)))

    async def _scrub_repair(
        self,
        storage: StoragePlugin,
        entries: Dict[str, Dict[str, str]],
        digest_of: Callable[[str], Optional[list]],
        clean_by_content: Dict[Tuple[int, str], List[str]],
        corrupt_chunks: Optional[Dict[str, List[int]]] = None,
    ) -> Tuple[int, int]:
        """Repair pass: rewrite corrupt/missing objects from a verified
        clean copy with an identical content identity (v1 whole-sha or v2
        tree root at matching size); quarantine corrupt objects with no
        such copy. When the scan attributed corruption to specific chunks
        (v2 records), repair fetches only THOSE chunks' extents from the
        clean source — a single rotten 32 MB chunk of a multi-GB object no
        longer costs a full-object copy — patches the local bytes, and
        re-verifies the whole tree before rewriting. crc-only sidecars
        can't prove a content match, so their objects are never repaired —
        only quarantined. Returns (repaired, quarantined)."""
        from .storage_plugins.cache import find_read_cache

        cache = find_read_cache(storage)
        corrupt_chunks = corrupt_chunks or {}
        repaired = quarantined = 0
        targets = [
            p
            for p, e in entries.items()
            if e["status"] in ("corrupt", "missing")
            and digest_of(p) is not None
        ]
        for path in sorted(targets):
            status = entries[path]["status"]
            rec = digest_of(path)
            size_want = hashing.record_size(rec)
            keys = hashing.record_content_keys(rec)
            sources: List[str] = []
            if keys and size_want is not None:
                seen: Set[str] = set()
                for key in keys:
                    for s in clean_by_content.get((size_want, key), []):
                        if s != path and s not in seen:
                            seen.add(s)
                            sources.append(s)
            bad = corrupt_chunks.get(path)
            info = hashing.record_chunk_info(rec)
            healed = False
            for src in sources:
                try:
                    if bad and info is not None and status == "corrupt":
                        # Chunk-extent repair: read the object once, fetch
                        # only the bad chunks' byte ranges from the clean
                        # source, patch, and re-verify the whole tree.
                        grain = info[0]
                        cur = ReadIO(path=path)
                        await storage.read(cur)
                        data = bytearray(cur.buf.getvalue())
                        if len(data) != size_want:
                            raise ValueError(
                                f"object is {len(data)} bytes now, "
                                f"recorded {size_want}"
                            )
                        for k in bad:
                            b, e = k * grain, min((k + 1) * grain, size_want)
                            rio = ReadIO(path=src, byte_range=(b, e))
                            await storage.read(rio)
                            data[b:e] = rio.buf.getvalue()
                        if hashing.verify_buffer(
                            memoryview(data), rec
                        ) is not None:
                            continue  # source rotted since the scan pass
                        await storage.write(
                            WriteIO(path=path, buf=bytes(data))
                        )
                        how = f"chunk(s) {bad} patched from {src}"
                    else:
                        read_io = ReadIO(path=src)
                        await storage.read(read_io)
                        data = read_io.buf.getvalue()
                        if hashing.verify_buffer(
                            memoryview(data), rec
                        ) is not None:
                            continue  # source rotted since the scan pass
                        await storage.write(WriteIO(path=path, buf=data))
                        how = f"rewritten from {src}"
                except Exception:  # noqa: BLE001 - try the next source
                    logger.warning(
                        "scrub repair of %s from %s failed", path, src,
                        exc_info=True,
                    )
                    continue
                prior = entries[path]["detail"] or entries[path]["status"]
                entries[path] = {
                    "status": "repaired",
                    "detail": f"{how} (was: {prior})",
                }
                repaired += 1
                healed = True
                break
            if healed:
                if cache is not None:
                    cache.quarantine_path(path)  # stale entries, if any
                continue
            if status != "corrupt":
                continue  # missing + no copy: nothing to quarantine
            # Unrepairable corrupt object: move it aside so no restore can
            # silently consume it — fail-fast "missing" beats silent rot.
            try:
                read_io = ReadIO(path=path)
                await storage.read(read_io)
                await storage.write(
                    WriteIO(path=f"{path}.quarantined", buf=read_io.buf.getvalue())
                )
                await storage.delete(path)
            except Exception:  # noqa: BLE001 - report, don't abort the scrub
                logger.warning(
                    "could not quarantine corrupt object %s", path,
                    exc_info=True,
                )
                continue
            if cache is not None:
                cache.quarantine_path(path)
            entries[path] = {
                "status": "quarantined",
                "detail": f"moved to {path}.quarantined "
                f"({entries[path]['detail']})",
            }
            quarantined += 1
        return repaired, quarantined

    # -------------------------------------------------------------------- gc
    @classmethod
    def gc(
        cls,
        path: str,
        dry_run: bool = True,
        keep_roots: Optional[Set[str]] = None,
        roots: Optional[List[str]] = None,
        collect_debris: bool = True,
    ) -> Dict[str, Any]:
        """Garbage-collect under ``path`` — the ONE deletion path both the
        whole-bucket crash-debris sweep and the catalog's retention engine
        (``catalog.retain`` / ``gc --policy``) drive.

        ``path`` is either one snapshot root or a directory whose immediate
        children are snapshot roots (the usual ``/checkpoints/step_N``
        layout). For each committed snapshot (``.snapshot_metadata``
        present) the kept set is: the metadata file, every storage object
        the manifest references, their ``.ftab`` frame tables, the checksum
        sidecars, and the ``.telemetry/`` artifacts; everything else —
        ``*.tmp.*`` files from torn fs writes, data objects of a crashed
        retake — is debris. A child tree with NO committed metadata is
        debris in its entirety (the atomic-commit contract: without
        ``.snapshot_metadata`` the tree is invisible to every reader).

        ``keep_roots`` — the **explicit keep-set** (bucket mode only):
        committed child roots NOT named here (and not pinned in the
        bucket's catalog — pins always survive) are **condemned** and
        deleted whole, in a crash-convergent order: ``.snapshot_metadata``
        first (the snapshot atomically stops being restorable), then the
        data tree, then its catalog record LAST — so a crash mid-delete
        leaves a record-marked *zombie* the next gc run finishes, and a
        re-run always converges (chaos-tested). ``None`` keeps every
        committed root (the classic debris sweep).

        ``roots`` — extra candidate root names to consider beyond what the
        bucket listing shows (``memory://`` children live in disjoint
        namespaces the bucket cannot list; the retention engine passes the
        catalog's record names so those backends collect too).

        ``collect_debris=False`` restricts deletion to condemned roots,
        zombies, and stale catalog records — uncommitted record-less trees
        (possibly an IN-FLIGHT take) and loose files are left untouched,
        which is what makes retention gc safe to run concurrently with
        takes into the same bucket. The full sweep (default) keeps the
        long-standing caveat: do NOT run it concurrently with a take, an
        in-flight take is indistinguishable from a crashed one until it
        commits.

        The bucket's ``.catalog/`` tree is never treated as a snapshot
        root: records of retained snapshots and pins are kept, records of
        condemned/vanished snapshots are removed (after their trees).

        Dry-run by default. Single-rank, no collectives. Returns
        ``{"committed": [prefixes], "uncommitted": [prefixes],
        "condemned": [prefixes], "keep": [paths], "remove": [paths],
        "removed": int, "dry_run": bool}`` (paths relative to ``path``).
        """
        from . import catalog as catalog_mod
        from .io_preparers.array import FRAME_TABLE_SUFFIX

        event_loop = asyncio.new_event_loop()
        storage = url_to_storage_plugin_in_event_loop(path, event_loop)
        sub_plugins: Dict[str, StoragePlugin] = {}

        def sub_plugin(root: str) -> StoragePlugin:
            if root not in sub_plugins:
                sub_plugins[root] = url_to_storage_plugin_in_event_loop(
                    catalog_mod.join_bucket(path, root), event_loop
                )
            return sub_plugins[root]

        try:
            with telemetry.span("gc.scan", cat="gc", path=path):
                all_paths = set(
                    event_loop.run_until_complete(storage.list_prefix(""))
                )
                single = SNAPSHOT_METADATA_FNAME in all_paths
                if single and keep_roots is not None:
                    raise ValueError(
                        "keep_roots applies to bucket-level gc; "
                        f"{path} is itself a committed snapshot root"
                    )
                cat_prefix = f"{catalog_mod.CATALOG_DIR}/"
                # Catalog layer: record object -> snapshot name, pins, and
                # catalog files we cannot classify (kept, fail-safe).
                record_paths: Dict[str, List[str]] = {}
                pinned: Set[str] = set()
                catalog_keep: Set[str] = set()
                import json as _json

                if not single:
                    for p in sorted(
                        q for q in all_paths if q.startswith(cat_prefix)
                    ):
                        name = None
                        try:
                            read_io = ReadIO(path=p)
                            storage.sync_read(read_io, event_loop)
                            body = read_io.buf.getvalue().decode()
                            name = str(_json.loads(body)["name"])
                        except Exception:  # noqa: BLE001 - unclassifiable
                            catalog_keep.add(p)
                            continue
                        if p.startswith(
                            (
                                f"{catalog_mod.RECORD_DIR}/",
                                f"{catalog_mod.STEP_TELEMETRY_DIR}/",
                            )
                        ):
                            # Step-telemetry rollups share their snapshot's
                            # lifecycle: kept with a retained root, deleted
                            # in the record wave with a condemned one.
                            record_paths.setdefault(name, []).append(p)
                        elif p.startswith(f"{catalog_mod.PIN_DIR}/"):
                            pinned.add(name)
                            catalog_keep.add(p)
                        else:
                            catalog_keep.add(p)

                # Candidate snapshot roots: the bucket listing's children,
                # every catalog-recorded name, and the caller's universe.
                if single:
                    root_names = [""]
                else:
                    root_names = sorted(
                        (
                            {
                                p.partition("/")[0]
                                for p in all_paths
                                if "/" in p
                            }
                            - {catalog_mod.CATALOG_DIR}
                        )
                        | set(record_paths)
                        | set(roots or [])
                    )

                # Per-root view: file paths (root-relative) and the plugin
                # that owns them — the bucket plugin for listed children,
                # the root's own sub-plugin for namespaces the bucket
                # cannot list (memory://).
                views: Dict[str, Dict[str, Any]] = {}
                for root in root_names:
                    prefix = f"{root}/" if root else ""
                    if root:
                        listed = sorted(
                            p[len(prefix):]
                            for p in all_paths
                            if p.startswith(prefix)
                        )
                    else:
                        listed = sorted(all_paths)
                    sub: Optional[StoragePlugin] = None
                    if root and not listed:
                        try:
                            sub = sub_plugin(root)
                            listed = sorted(
                                event_loop.run_until_complete(
                                    sub.list_prefix("")
                                )
                            )
                        except Exception:  # noqa: BLE001 - unlistable root
                            listed = []
                        if not listed:
                            sub = None
                    views[root] = {
                        "paths": listed,
                        "sub": sub,
                        "committed": SNAPSHOT_METADATA_FNAME in listed,
                    }

                committed = sorted(
                    r for r, v in views.items() if v["committed"]
                )
                uncommitted = sorted(
                    r
                    for r, v in views.items()
                    if not v["committed"] and v["paths"]
                )
                keep_set = (
                    set(keep_roots) | pinned
                    if keep_roots is not None
                    else None
                )
                # Condemnation universe: when the caller names its known
                # roots (the retention engine passes the catalog's record
                # names), only THOSE may be condemned — a committed
                # snapshot the caller doesn't know about (unrecorded, or
                # the whole catalog unreadable) is implicitly retained.
                # Without this, a corrupted catalog would hand gc an empty
                # keep-set and retention would delete every visible
                # snapshot in the bucket.
                universe = set(roots) if roots is not None else None
                condemned = sorted(
                    r
                    for r in committed
                    if keep_set is not None
                    and r not in keep_set
                    and (universe is None or r in universe)
                )
                # Zombies: a catalog record names the root but its tree is
                # uncommitted — a crash interrupted a previous condemned
                # delete after the metadata went. Finish the job (any
                # mode; convergence demands it).
                zombies = sorted(
                    r
                    for r in uncommitted
                    if r in record_paths
                )

                retained = [r for r in committed if r not in condemned]
                keep: Set[str] = set(catalog_keep)
                observed: Set[str] = set(all_paths)
                for root in views:
                    prefix = f"{root}/" if root else ""
                    if views[root]["sub"] is not None:
                        observed.update(
                            f"{prefix}{p}" for p in views[root]["paths"]
                        )
                for root in retained:
                    v = views[root]
                    prefix = f"{root}/" if root else ""
                    meta_path = f"{prefix}{SNAPSHOT_METADATA_FNAME}"
                    if v["sub"] is not None:
                        read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
                        v["sub"].sync_read(read_io, event_loop)
                    else:
                        read_io = ReadIO(path=meta_path)
                        storage.sync_read(read_io, event_loop)
                    metadata = SnapshotMetadata.from_json(
                        read_io.buf.getvalue().decode("utf-8")
                    )
                    keep.add(meta_path)
                    for loc in _manifest_storage_locations(metadata.manifest):
                        keep.add(f"{prefix}{loc}")
                        keep.add(f"{prefix}{loc}{FRAME_TABLE_SUFFIX}")
                    for r in range(metadata.world_size):
                        keep.add(f"{prefix}{CHECKSUM_FILE_PREFIX}{r}")
                    keep.update(
                        f"{prefix}{p}"
                        for p in v["paths"]
                        if p.startswith(".telemetry/")
                    )
                    keep.update(record_paths.get(root, []))

                # What goes, in three crash-ordered waves (bucket coords).
                meta_wave: List[str] = []
                tree_wave: List[str] = []
                record_wave: List[str] = []
                for root in condemned:
                    prefix = f"{root}/" if root else ""
                    meta_wave.append(f"{prefix}{SNAPSHOT_METADATA_FNAME}")
                    tree_wave.extend(
                        f"{prefix}{p}"
                        for p in views[root]["paths"]
                        if p != SNAPSHOT_METADATA_FNAME
                    )
                    record_wave.extend(record_paths.get(root, []))
                for root in zombies:
                    prefix = f"{root}/" if root else ""
                    tree_wave.extend(
                        f"{prefix}{p}" for p in views[root]["paths"]
                    )
                    record_wave.extend(record_paths.get(root, []))
                # Stale records: the named tree is gone entirely (a prior
                # gc crashed between tree and record deletion).
                for name, paths in record_paths.items():
                    if name in views and not views[name]["paths"]:
                        record_wave.extend(paths)
                if collect_debris:
                    zombie_set = set(zombies)
                    for root in uncommitted:
                        if root in zombie_set:
                            continue
                        prefix = f"{root}/" if root else ""
                        tree_wave.extend(
                            f"{prefix}{p}" for p in views[root]["paths"]
                        )
                        record_wave.extend(record_paths.get(root, []))
                    # Debris inside retained roots + loose bucket files.
                    handled = {
                        r
                        for r in views
                        if r in set(condemned) | zombie_set | set(uncommitted)
                    }
                    tree_wave.extend(
                        sorted(
                            p
                            for p in observed
                            if p not in keep
                            and not p.startswith(cat_prefix)
                            and p.partition("/")[0] not in handled
                            and p
                            not in set(meta_wave)
                        )
                    )
                remove = sorted(set(meta_wave) | set(tree_wave))
                remove_all = sorted(
                    set(meta_wave) | set(tree_wave) | set(record_wave)
                )
            telemetry.counter_add("gc.files_scanned", len(observed))
            telemetry.counter_add("gc.files_debris", len(remove_all))
            removed = 0
            if not dry_run and remove_all:
                with telemetry.span(
                    "gc.delete", cat="gc", path=path, files=len(remove_all)
                ):

                    def owner_of(p: str) -> Tuple[StoragePlugin, str]:
                        root = p.partition("/")[0]
                        v = views.get(root)
                        if v is not None and v["sub"] is not None:
                            return v["sub"], p[len(root) + 1:]
                        return storage, p

                    async def delete_wave(paths: List[str]) -> int:
                        # One BACKGROUND-class engine graph per wave: the
                        # crash-ordered waves stay sequential (wave N+1's
                        # graph only runs after wave N's completes), while
                        # inside a wave deletes run capped at the IO knob —
                        # and a retention sweep running beside a serving
                        # restore yields its next deletions to it.
                        from .engine import Node as _Node
                        from .engine import Priority as _Priority
                        from .engine import run_graph as _run_graph

                        done = {"n": 0}

                        def make_delete(p: str):
                            async def delete(_ctx, _payload) -> None:
                                plugin, rel = owner_of(p)
                                try:
                                    await plugin.delete(rel)
                                    done["n"] += 1
                                except FileNotFoundError:
                                    done["n"] += 1  # already gone — goal
                                    # reached

                            return delete

                        await _run_graph(
                            [
                                _Node("delete", make_delete(p), path=p)
                                for p in sorted(set(paths))
                            ],
                            budget_bytes=0,
                            owner="gc",
                            kind="gc",
                            caps={
                                "io": lambda: (
                                    knobs.get_max_concurrent_io_for(storage)
                                )
                            },
                            priority=_Priority.BACKGROUND,
                        )
                        return done["n"]

                    # Wave 1: condemned metadata — each snapshot atomically
                    # stops being restorable before any data byte goes.
                    removed += event_loop.run_until_complete(
                        delete_wave(meta_wave)
                    )
                    # Wave 2: the trees (and, full sweep, loose debris).
                    removed += event_loop.run_until_complete(
                        delete_wave(tree_wave)
                    )
                    # Wave 3: catalog records LAST — a record only goes
                    # once its tree is gone, so a crash anywhere above
                    # leaves a zombie the next run recognizes and finishes.
                    n_records = event_loop.run_until_complete(
                        delete_wave(record_wave)
                    )
                    removed += n_records
                    if n_records:
                        telemetry.counter_add(
                            "gc.records_removed", n_records
                        )
                    # Even with no files to delete, a crashed take may have
                    # left empty directory skeletons (fs): prune them.
                    event_loop.run_until_complete(storage.prune_empty())
                    for sub in sub_plugins.values():
                        event_loop.run_until_complete(sub.prune_empty())
                telemetry.counter_add("gc.files_removed", removed)
            elif not dry_run:
                with telemetry.span(
                    "gc.delete", cat="gc", path=path, files=0
                ):
                    event_loop.run_until_complete(storage.prune_empty())
            return {
                "committed": committed,
                "uncommitted": uncommitted,
                "condemned": condemned,
                "keep": sorted(keep & observed),
                "remove": remove,
                "remove_records": sorted(set(record_wave)),
                "removed": removed,
                "dry_run": dry_run,
            }
        finally:
            for sub in sub_plugins.values():
                sub.sync_close(event_loop)
            storage.sync_close(event_loop)
            event_loop.close()

    # -------------------------------------------------------------- metadata
    @property
    def metadata(self) -> SnapshotMetadata:
        if self._metadata is None:
            event_loop = asyncio.new_event_loop()
            storage = url_to_storage_plugin_in_event_loop(self.path, event_loop)
            try:
                self._metadata = self._read_metadata(storage, event_loop)
            finally:
                storage.sync_close(event_loop)
                event_loop.close()
        return self._metadata

    def get_manifest(self) -> Manifest:
        """The global ``"<rank>/<logical_path>" -> Entry`` manifest."""
        return dict(self.metadata.manifest)

    def _read_metadata(
        self, storage: StoragePlugin, event_loop: asyncio.AbstractEventLoop
    ) -> SnapshotMetadata:
        if self._metadata is not None:
            return self._metadata
        read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
        storage.sync_read(read_io, event_loop)
        self._metadata = SnapshotMetadata.from_json(
            read_io.buf.getvalue().decode("utf-8")
        )
        return self._metadata

    @classmethod
    def _write_snapshot_metadata(
        cls,
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
    ) -> None:
        storage.sync_write(
            WriteIO(
                path=SNAPSHOT_METADATA_FNAME,
                buf=metadata.to_json().encode("utf-8"),
            ),
            event_loop,
        )

    # --------------------------------------------------------------- helpers
    @staticmethod
    def _validate_app_state(app_state: AppState) -> None:
        for key, value in app_state.items():
            if not (hasattr(value, "state_dict") and hasattr(value, "load_state_dict")):
                raise TypeError(
                    f"app_state[{key!r}] is not Stateful "
                    f"(needs state_dict/load_state_dict): {type(value)}"
                )

    @staticmethod
    def _gather_keys(app_state: Dict[str, Any], coord: Coordinator) -> List[str]:
        """Global union of app-state keys in a deterministic order.

        One gather to rank 0 + one broadcast back — constant store
        round-trips per non-zero rank (the all_gather it replaces cost
        O(world) store reads on EVERY rank)."""
        if coord.get_world_size() == 1:
            return sorted(app_state.keys())
        gathered = coord.gather_object(sorted(app_state.keys()), dst=0)
        union: Optional[List[str]] = None
        if gathered is not None:  # rank 0
            union = sorted({k for keys in gathered for k in keys})
        return coord.broadcast_object(union, src=0)

    @staticmethod
    def _match_replicated_paths(paths: Set[str], globs: List[str]) -> Set[str]:
        matched: Set[str] = set()
        for g in globs:
            matched.update(p for p in paths if fnmatch.fnmatch(p, g))
        return matched

    @classmethod
    def _gather_manifest(
        cls, manifest: Manifest, coord: Coordinator
    ) -> Tuple[Optional[Manifest], Dict[str, dict], Optional[List[Dict[str, dict]]]]:
        """Merge per-rank manifests into the global rank-namespaced manifest.

        Returns ``(global_manifest, local_entry_dicts, gathered_entry_dicts)``
        — the global manifest on rank 0 (None elsewhere), plus the
        serialized per-entry dicts that seed the plan cache's delta baseline
        (``take_plan.gather_manifest_delta``); ``gathered_entry_dicts`` is
        rank 0's copy of every rank's dicts (None elsewhere)."""
        from .manifest import entry_from_dict, entry_to_dict

        local = {p: entry_to_dict(e) for p, e in manifest.items()}
        if coord.get_world_size() == 1:
            return (
                {f"0/{p}": entry_from_dict(d) for p, d in local.items()},
                local,
                [local],
            )

        # Gather to rank 0 only: it alone commits the metadata. Pulling W
        # manifests to all W ranks would be O(W^2 x manifest-size) store
        # traffic on the take() critical path; non-zero ranks lazily read
        # the committed ``.snapshot_metadata`` if they ever need it.
        gathered = coord.gather_object(local, dst=0)
        if gathered is None:
            return None, local, None
        global_manifest: Manifest = {}
        for r, m in enumerate(gathered):
            for p, d in m.items():
                global_manifest[f"{r}/{p}"] = entry_from_dict(d)
        # Batching may have relocated replicated entries on the writer rank
        # only; reconcile every rank's copy.
        from .partitioner import consolidate_replicated_entries

        consolidate_replicated_entries(global_manifest)
        return global_manifest, local, gathered


# ---------------------------------------------------------------------------
# Per-entry restore planning shared by restore() and read_object()
# ---------------------------------------------------------------------------

class _StatefulPlan(NamedTuple):
    """What ``Snapshot._plan_stateful`` hands the pipeline."""

    loaded: Dict[str, Any]  # logical path -> restored value, filled as entries land
    read_reqs: List[ReadReq]
    finalizers: Dict[int, Callable[[], None]]  # overlap on: run by the countdowns
    deferred_finalizers: List[Callable[[], None]]  # overlap off: after the pipeline
    bcast_items: List[Any]
    swarm_items: List[Any]
    swarm_need: Dict[str, List[frozenset]]


class _ReadCountdown:
    """Per-entry outstanding-read counter. When the entry's last read has
    been consumed it keeps the moment (on the consuming thread's clock:
    what the entry's finalizer then waits from) and, given the shared
    ``finalizers`` dict (overlap on), runs the entry's finalizer, popping it
    so its host buffers free eagerly. Called on the event-loop thread —
    which is the caller's (main) thread, where jax dispatch is fast; the
    lock makes the countdown safe under any future consumer-threading
    change."""

    __slots__ = ("idx", "remaining", "finalizers", "consumed_at", "lock")

    def __init__(
        self,
        idx: int,
        n_reads: int,
        finalizers: Optional[Dict[int, Callable[[], None]]],
    ) -> None:
        self.idx = idx
        self.remaining = n_reads
        self.finalizers = finalizers
        self.consumed_at = 0.0
        self.lock = threading.Lock()

    def __call__(self, consumed_at: float) -> None:
        with self.lock:
            self.remaining -= 1
            self.consumed_at = max(self.consumed_at, consumed_at)
            done = self.remaining == 0
        if done and self.finalizers is not None:
            self.finalizers.pop(self.idx)()

    def waited(
        self, finalize: Callable[[], None], times: "restore_times.RestoreTimes"
    ) -> Callable[[], None]:
        """``finalize``, counting first how long the consumed entry waited
        for it (``place_wait_s``)."""

        def run() -> None:
            times.add("place_wait_s", max(0.0, time.monotonic() - self.consumed_at))
            finalize()

        return run


class _CountingConsumer:
    """Proxies one read's consumer, reporting completion to the entry's
    countdown and dropping the inner consumer (and thus its target-buffer
    reference) eagerly so finalized entries' host memory is reclaimable
    while the pipeline still runs."""

    def __init__(self, inner: Any, countdown: _ReadCountdown) -> None:
        self.inner = inner
        self.countdown = countdown
        # batch_read_requests reads this attribute to keep framed sub-reads
        # unmerged; proxy it or wrapped framed reads would coalesce.
        self.merge_exempt = getattr(inner, "merge_exempt", False)

    def destination(self) -> Optional[memoryview]:
        return destination_of(self.inner)

    async def acquire_target(self) -> None:
        await acquire_target_of(self.inner)

    async def consume_buffer(self, buf, executor=None) -> None:
        inner = self.inner
        await inner.consume_buffer(buf, executor)
        self.inner = None
        # Back on the event-loop thread here: the countdown's finalize (jax
        # device_put / make_array_from_callback) runs main-thread.
        self.countdown(restore_times.consumed_at())

    def get_consuming_cost_bytes(self) -> int:
        inner = self.inner
        return inner.get_consuming_cost_bytes() if inner is not None else 0

def _read_checksum_sidecars(
    storage: StoragePlugin,
    world_size: int,
    event_loop: asyncio.AbstractEventLoop,
) -> Tuple[Dict[str, Any], int, Dict[int, str]]:
    """Read + merge every rank's ``.checksums.<rank>`` sidecar concurrently.

    Returns (merged {storage_path: digest}, number of sidecars found,
    {rank: error} for sidecars that exist-or-may-exist but could not be
    read). Absence (``FileNotFoundError``, per the StoragePlugin contract)
    is expected — a rank that staged no storage objects writes no sidecar;
    any *other* failure (cloud throttling past the plugin's retry window, a
    corrupt JSON body) is reported separately so callers never mistake a
    transient read failure for lost integrity metadata.
    The single source of truth for sidecar parsing: ``verify()`` and the
    incremental-base loader must never diverge on the format.
    """
    import json as _json

    merged: Dict[str, Any] = {}
    found = 0
    unreadable: Dict[int, str] = {}

    async def read_all() -> None:
        nonlocal found
        # Capped like every other IO path: a 1024-rank snapshot must not
        # fire 1024 simultaneous cloud requests (throttling would surface
        # as silently-skipped sidecars, i.e. spurious 'unverified'/'no
        # digests' outcomes).
        sem = asyncio.Semaphore(knobs.get_max_concurrent_io_for(storage))

        async def read_one(rank: int):
            async with sem:
                read_io = ReadIO(path=f"{CHECKSUM_FILE_PREFIX}{rank}")
                try:
                    await storage.read(read_io)
                except FileNotFoundError:
                    return None  # absent — the rank wrote no objects
                except Exception as e:  # noqa: BLE001 - reported, not dropped
                    unreadable[rank] = repr(e)
                    return None
                try:
                    parsed = _json.loads(read_io.buf.getvalue().decode())
                except Exception as e:  # noqa: BLE001 - corrupt sidecar body
                    unreadable[rank] = f"unparseable: {e!r}"
                    return None
                if not isinstance(parsed, dict):
                    # Valid JSON but not a digest map (truncation artifacts
                    # like 'null' or '[]'): corruption, not absence.
                    unreadable[rank] = (
                        f"unparseable: expected a JSON object, got "
                        f"{type(parsed).__name__}"
                    )
                    return None
                return parsed

        results = await asyncio.gather(*(read_one(r) for r in range(world_size)))
        for r in results:
            if r is not None:
                found += 1
                merged.update(r)

    event_loop.run_until_complete(read_all())
    return merged, found, unreadable


def _uncovered_problem(location: str, unreadable: Dict[int, str]) -> str:
    """Problem text for a manifest object no readable sidecar covers.

    Attribution matters operationally: 'unreadable' suggests a transient
    backend failure (retry verify), while 'no checksum recorded' means the
    integrity metadata is genuinely gone. Per-rank locations (``<rank>/...``)
    attribute precisely via their path prefix; ``sharded/``/``replicated/``/
    ``batched/`` objects may have been written by any rank, so when some
    sidecar was unreadable the report stays hedged rather than wrongly
    asserting the metadata never existed."""
    owner, _, _ = location.partition("/")
    if owner.isdigit():
        if int(owner) in unreadable:
            return "unverified (this rank's checksum sidecar was unreadable)"
        return "unverified (no checksum recorded)"
    if unreadable:
        ranks = ",".join(str(r) for r in sorted(unreadable))
        return (
            "unverified (uncovered by any readable sidecar; the sidecar of "
            f"rank(s) {ranks} was unreadable and may cover this object)"
        )
    return "unverified (no checksum recorded)"


def _framed_locations(manifest: Manifest) -> Set[str]:
    """Storage locations that carry a ``.ftab`` frame-table side object:
    framed compressed payloads (``frame_bytes``) and member-framed slabs
    (any member with a ``raw_range``). Scrub validates these tables — a
    rotten table breaks budgeted sub-reads and slab-member reads even when
    the payload bytes are pristine."""

    def has_table(sub) -> bool:
        return bool(getattr(sub, "frame_bytes", None)) or (
            getattr(sub, "raw_range", None) is not None
        )

    out: Set[str] = set()
    for entry in manifest.values():
        if getattr(entry, "location", None) and has_table(entry):
            out.add(entry.location)
        for chunk in getattr(entry, "chunks", None) or []:
            if has_table(chunk.tensor):
                out.add(chunk.tensor.location)
        for shard in getattr(entry, "shards", None) or []:
            if has_table(shard.tensor):
                out.add(shard.tensor.location)
    return out


def _manifest_storage_locations(manifest: Manifest) -> Set[str]:
    """Every storage-object path the manifest points at (slab members share
    one location; primitives are inline and contribute none)."""
    locations: Set[str] = set()
    for entry in manifest.values():
        loc = getattr(entry, "location", None)
        if loc:
            locations.add(loc)
        for chunk in getattr(entry, "chunks", None) or []:
            locations.add(chunk.tensor.location)
        for shard in getattr(entry, "shards", None) or []:
            locations.add(shard.tensor.location)
    return locations


def _count_leaves(flattened: Dict[str, Any]) -> None:
    """How many array leaves this rank's take holds, and how many of them
    (with their bytes) lie under ``SMALL_OBJECT_BYTES``: with default knobs
    each is a transfer and a storage object of its own."""
    if telemetry.get_active() is None:
        return
    leaves = small = small_bytes = 0
    for value in flattened.values():
        nbytes = getattr(value, "nbytes", None)
        if nbytes is None or not hasattr(value, "shape"):
            continue
        leaves += 1
        if nbytes < SMALL_OBJECT_BYTES:
            small += 1
            small_bytes += nbytes
    telemetry.counter_add("take.leaves", leaves)
    telemetry.counter_add("take.small_leaves", small)
    telemetry.counter_add("take.small_leaf_bytes", small_bytes)


def _matches_include(path: str, globs: List[str]) -> bool:
    """Whether a logical path is selected by a lazy-restore include list.

    A pattern selects a path when it fnmatch-es the full path, equals it,
    or names one of its ancestors (``"model/encoder"`` selects everything
    under that subtree without needing a trailing ``/*``)."""
    for g in globs:
        g = g.rstrip("/")
        if path == g or path.startswith(f"{g}/") or fnmatch.fnmatch(path, g):
            return True
    return False


def _wanted_framed_locations(
    entry: Entry, live: Any, buffer_size_limit_bytes: Optional[int]
) -> List[str]:
    """Framed payload locations under ``entry`` whose ``.ftab`` this
    process's restore will actually need: member-framed compressed slab
    members (``raw_range`` — always, the table is how a member's bytes are
    even located) and big framed payloads a budget will sub-read.

    Sharded entries are filtered by overlap with the live target's
    addressable shards — each rank reads only ~1/world of a sharded array's
    shards, and fetching every shard's table would be O(world²) wasted
    cloud GETs pod-wide. No live sharded target (host-materialized restore)
    means every shard is read, so every table is wanted."""
    from .io_preparers.sharded_array import index_to_offsets_sizes, overlap
    from .serialization import array_nbytes

    def big_and_framed(sub) -> bool:
        return bool(
            buffer_size_limit_bytes is not None
            and getattr(sub, "frame_bytes", None)
            and array_nbytes(sub.shape, sub.dtype) > buffer_size_limit_bytes
        )

    def member_framed(sub) -> bool:
        return getattr(sub, "raw_range", None) is not None

    out: List[str] = []
    if isinstance(entry, ArrayEntry) and (
        big_and_framed(entry) or member_framed(entry)
    ):
        out.append(entry.location)
    for chunk in getattr(entry, "chunks", None) or []:
        if big_and_framed(chunk.tensor) or member_framed(chunk.tensor):
            out.append(chunk.tensor.location)
    shards = getattr(entry, "shards", None) or []
    if shards:
        targets = None
        if is_jax_array(live) and list(live.shape) == list(entry.shape):
            targets = []
            seen = set()
            index_map = live.sharding.addressable_devices_indices_map(
                tuple(int(s) for s in entry.shape)
            )
            for index in index_map.values():
                offsets, sizes = index_to_offsets_sizes(index, entry.shape)
                key = tuple(offsets)
                if key not in seen:
                    seen.add(key)
                    targets.append((offsets, sizes))
        for shard in shards:
            if not big_and_framed(shard.tensor):
                continue
            if targets is not None and not any(
                overlap(shard.offsets, shard.sizes, t_off, t_sz) is not None
                for t_off, t_sz in targets
            ):
                continue
            out.append(shard.tensor.location)
    return out


def _fetch_frame_tables(
    entry_live_pairs,
    storage: StoragePlugin,
    event_loop: asyncio.AbstractEventLoop,
    buffer_size_limit_bytes: Optional[int],
) -> Dict[str, Any]:
    """Read the ``.ftab`` side objects a restore needs: member-framed
    compressed slabs (always — the table maps each member's ``raw_range``
    to its compressed frames; value = ``{"sizes", "raw_sizes"}`` dict) and
    big framed payloads a budget will sub-read (value = frame-size list;
    whole-object reads need no table since frames decode by concatenation).
    A missing/corrupt table degrades to whole-object reads with a warning —
    never a failed restore."""
    import json as _json

    from .io_preparers.array import FRAME_TABLE_SUFFIX

    locations: Dict[str, None] = {}  # insertion-ordered set
    for entry, live in entry_live_pairs:
        for loc in _wanted_framed_locations(entry, live, buffer_size_limit_bytes):
            locations[loc] = None
    if not locations:
        return {}
    tables: Dict[str, Any] = {}

    async def fetch_all() -> None:
        sem = asyncio.Semaphore(knobs.get_max_concurrent_io_for(storage))

        async def fetch_one(loc: str) -> None:
            async with sem:
                read_io = ReadIO(path=loc + FRAME_TABLE_SUFFIX)
                try:
                    await storage.read(read_io)
                    parsed = _json.loads(read_io.buf.getvalue().decode())
                    if parsed.get("member_framed"):
                        tables[loc] = {
                            "sizes": [int(s) for s in parsed["sizes"]],
                            "raw_sizes": [int(s) for s in parsed["raw_sizes"]],
                        }
                    else:
                        tables[loc] = [int(s) for s in parsed["sizes"]]
                except Exception:  # noqa: BLE001 - degrade, don't fail
                    logger.warning(
                        "frame table %s%s unreadable; falling back to a "
                        "whole-object read",
                        loc,
                        FRAME_TABLE_SUFFIX,
                        exc_info=True,
                    )

        await asyncio.gather(*(fetch_one(loc) for loc in locations))

    event_loop.run_until_complete(fetch_all())
    return tables


# Logical paths of the live targets the restore in progress consumed (see
# ``_place_over_target``); drained into ONE warning when that restore ends.
# Finalizers may run on consumer threads, hence the lock.
_consumed_targets: List[str] = []
_consumed_targets_lock = threading.Lock()


def _placing(
    times: Optional["restore_times.RestoreTimes"],
    logical_path: str,
    sharding: Any,
    shape: Any,
    itemsize: int,
):
    """The ``place`` interval of one finalizer (a ``restore.place`` span),
    counting the bytes it puts on this process's devices: one shard's bytes
    times the addressable devices, replicas included, as the link moves
    them."""
    if times is None:
        return contextlib.nullcontext()
    shard_shape = sharding.shard_shape(tuple(int(d) for d in shape))
    nbytes = (
        int(np.prod(shard_shape, dtype=np.int64))
        * itemsize
        * len(sharding.addressable_devices)
    )
    times.add("place_bytes", nbytes)
    return times.work("place", path=logical_path, nbytes=nbytes)


def _place_over_target(
    logical_path: str,
    live: Any,
    place: Callable[[], Any],
    times: Optional["restore_times.RestoreTimes"] = None,
) -> Any:
    """Put one restored leaf on device, where its live target already is.

    A restore overwrites its targets (the reference restores in place), but
    jax arrays are immutable: the restored leaf is a NEW buffer beside the
    live one, so restoring into device-resident targets peaks at twice the
    state — a job that fills more than half of HBM could never resume. When,
    and only when, the allocation fails (it does so synchronously at
    ``device_put``), the target — about to be replaced anyway — gives up its
    buffers and the leaf is placed again. Counted as
    ``restore.targets_consumed`` and named in the restore's closing warning;
    every other reference the caller holds to a consumed target (tied or
    EMA parameters, a serving copy) is a deleted array afterwards. What the
    failed first attempt cost goes to the restore's ``place_retry_s``."""
    t0 = time.monotonic()
    try:
        return place()
    except Exception as e:  # noqa: BLE001 - only allocation failure degrades
        if not is_oom_error(e) or live.is_deleted():
            raise
    if times is not None:
        times.add("place_retry_s", time.monotonic() - t0)
        times.add("targets_consumed", 1)
    telemetry.counter_add("restore.targets_consumed")
    with _consumed_targets_lock:
        _consumed_targets.append(logical_path)
    live.delete()
    return place()


def _warn_consumed_targets() -> None:
    """One warning per restore — completed or failed — naming the targets it
    consumed: after a failure they are gone, not merely stale."""
    with _consumed_targets_lock:
        paths, _consumed_targets[:] = list(_consumed_targets), []
    if paths:
        logger.warning(
            "restore: HBM could not hold %d restored leaves beside their live "
            "targets, so those targets' buffers were released first (consumed, "
            "as by donation; any other reference to them is now a deleted "
            "array): %s%s",
            len(paths),
            ", ".join(paths[:8]),
            f", ... (+{len(paths) - 8} more)" if len(paths) > 8 else "",
        )


_TargetSpec = Tuple[Tuple[int, ...], np.dtype]  # shape, dtype


def _fresh_targets(
    times: Optional["restore_times.RestoreTimes"], specs: List[_TargetSpec]
) -> List[np.ndarray]:
    """Host targets of fresh pages, one a spec, that the restore allocates
    itself (counted as ``fresh_target_bytes``)."""
    targets = [np.empty(shape, dtype=dtype) for shape, dtype in specs]
    if times is not None:
        times.add("fresh_target_bytes", sum(t.nbytes for t in targets))
    return targets


class _HostTargets:
    """The host targets of one entry that is bound for a device: fresh pages
    allocated at plan time, as a restore's targets have always been (the
    CPU backend, where the placed array may share them; every path but the
    overlapped direct one)."""

    def __init__(
        self,
        specs: List[_TargetSpec],
        plan: Callable[[List[np.ndarray]], List[ReadReq]],
        times: Optional["restore_times.RestoreTimes"],
    ) -> None:
        self._targets: Optional[List[np.ndarray]] = _fresh_targets(times, specs)
        self.reqs = plan(self._targets)

    def targets(self) -> List[np.ndarray]:
        return self._targets

    def placed(self, array: Any) -> None:
        self._targets = None


class _LeasedHostTargets:
    """The same, out of the restore's arena of host pages
    (``host_arena.py``): views taken when the entry's first read is about to
    be fetched and given back when the placed array is ready, so the next
    entries are read into pages touched before.

    The reads are planned twice over the same pure planner: once over
    stand-ins that are never written (``np.empty`` touches nothing), for what
    the pipeline must know beforehand (paths, ranges, costs), and once over
    the views, for the consumers that fill them."""

    def __init__(
        self,
        arena: "host_arena.HostArena",
        specs: List[_TargetSpec],
        plan: Callable[[List[np.ndarray]], List[ReadReq]],
        times: Optional["restore_times.RestoreTimes"],
    ) -> None:
        self.specs = specs
        self.plan = plan
        self.times = times
        sketch = plan([np.empty(shape, dtype=dtype) for shape, dtype in specs])
        self.lease = arena.lease(
            [int(np.prod(shape, dtype=np.int64)) * dtype.itemsize for shape, dtype in specs],
            reads=len(sketch),
        )
        # Nothing is fetched while the restore plans: the arena's pages are
        # first touched meanwhile, as far as the planned leases will reach.
        arena.pretouch(self.lease.nbytes)
        self._targets: Optional[List[np.ndarray]] = None
        self._consumers: Optional[List[Any]] = None
        self.reqs = [
            ReadReq(
                path=r.path,
                buffer_consumer=_LeasedConsumer(self, i, r.buffer_consumer),
                byte_range=r.byte_range,
            )
            for i, r in enumerate(sketch)
        ]

    async def acquire(self) -> None:
        views = await self.lease.acquire()
        if self._targets is None:
            self._bind(views)

    def _bind(self, views: Optional[List[np.ndarray]]) -> None:
        if views is None:  # no room that was safe to wait for: fresh pages
            targets = _fresh_targets(self.times, self.specs)
        else:
            targets = [
                view.view(dtype).reshape(shape)
                for view, (shape, dtype) in zip(views, self.specs)
            ]
            if self.times is not None:
                recycled = self.lease.recycled_bytes
                self.times.add("recycled_bytes", recycled)
                self.times.add(
                    "fresh_target_bytes", sum(t.nbytes for t in targets) - recycled
                )
        self._targets = targets
        self._consumers = [r.buffer_consumer for r in self.plan(targets)]

    def consumer(self, index: int) -> Any:
        self.targets()
        return self._consumers[index]

    def targets(self) -> List[np.ndarray]:
        if self._targets is None:  # no read of the entry was fetched through acquire
            self._bind(self.lease.take_nowait())
        return self._targets

    def placed(self, array: Any) -> None:
        """The entry is on its way to the device: the views go back when it
        has arrived. ``device_put`` returns before the runtime has read the
        host pages."""
        self._targets = self._consumers = None
        self.lease.give_back_when(array.block_until_ready)


class _LeasedConsumer:
    """One read of a :class:`_LeasedHostTargets` entry: the consumer planned
    over the entry's views, once there are views."""

    def __init__(self, owner: _LeasedHostTargets, index: int, planned: Any) -> None:
        self.owner = owner
        self.index = index
        self.cost_bytes = planned.get_consuming_cost_bytes()
        self.merge_exempt = getattr(planned, "merge_exempt", False)

    async def acquire_target(self) -> None:
        await self.owner.acquire()

    def destination(self) -> Optional[memoryview]:
        return destination_of(self.owner.consumer(self.index))

    async def consume_buffer(self, buf, executor=None) -> None:
        await self.owner.consumer(self.index).consume_buffer(buf, executor)

    def get_consuming_cost_bytes(self) -> int:
        return self.cost_bytes


def _host_targets(
    arena: Optional["host_arena.HostArena"],
    live: Any,
    specs: List[_TargetSpec],
    plan: Callable[[List[np.ndarray]], List[ReadReq]],
    times: Optional["restore_times.RestoreTimes"],
):
    """Host targets for an entry restored onto ``live``'s devices: out of
    ``arena`` where there is one and the placed array cannot alias the host
    pages it was put from (judged from the target sharding's devices), else
    fresh ones."""
    if (
        arena is not None
        and host_arena.copies_on_put(live.sharding.device_set)
        and not any(dtype.hasobject for _shape, dtype in specs)
    ):
        return _LeasedHostTargets(arena, specs, plan, times)
    return _HostTargets(specs, plan, times)


def _prepare_restore_one(  # spmd-pure
    logical_path: str,
    entry: Entry,
    live: Any,
    loaded: Dict[str, Any],
    buffer_size_limit_bytes: Optional[int] = None,
    frame_tables: Optional[Dict[str, List[int]]] = None,
    digests: Optional[Dict[str, Any]] = None,
    arena: Optional["host_arena.HostArena"] = None,
) -> Tuple[List[ReadReq], Optional[Callable[[], None]]]:
    """Plan the reads for one entry; returns (read_reqs, finalizer).

    The finalizer (run after all reads complete) converts filled host buffers
    into the final leaf value (e.g. ``jax.device_put`` with the live
    sharding) and records it in ``loaded[logical_path]``.

    ``digests`` (the snapshot's merged checksum sidecars — identical on
    every rank) lets the sharded exact-overlap planner align its byte
    ranges to the v2 hash-chunk grain, so ranged reshard reads verify at
    chunk granularity and compose with the read cache's sub-range tier.

    ``arena`` (the overlapped direct path of a restore only): where a leaf
    bound for a device may take its host targets from
    (:func:`_host_targets`); a target the caller will see never does.
    """
    from .serialization import string_to_dtype

    # The restore's interval sink, where one is active (a restore, not a
    # read_object): finalizers stamp their ``place`` intervals into it.
    times = restore_times.get_active()
    if isinstance(entry, PrimitiveEntry):
        loaded[logical_path] = entry.get_value()
        return [], None

    if isinstance(entry, ObjectEntry):
        reqs, consumer = ObjectIOPreparer.prepare_read(entry)

        def on_obj(obj: Any) -> None:
            loaded[logical_path] = obj

        consumer.set_consume_callback(on_obj)
        return reqs, None

    if isinstance(entry, (ArrayEntry, ChunkedArrayEntry)):
        from .io_preparers.array import entry_np_dtype

        serializer = (
            entry.chunks[0].tensor.serializer
            if isinstance(entry, ChunkedArrayEntry)
            else entry.serializer
        )
        np_dtype = entry_np_dtype(entry.dtype, serializer)
        in_place = (
            isinstance(live, np.ndarray)
            and live.dtype == np_dtype
            and list(live.shape) == list(entry.shape)
            and live.flags["C_CONTIGUOUS"]
            and live.flags["WRITEABLE"]
        )
        def plan(targets: List[np.ndarray]) -> List[ReadReq]:
            # A target of the restore's own may have its read land in it; the
            # caller's live array is overwritten only by bytes fetched whole.
            if isinstance(entry, ChunkedArrayEntry):
                return ChunkedArrayIOPreparer.prepare_read(
                    entry,
                    targets[0],
                    buffer_size_limit_bytes,
                    frame_tables=frame_tables,
                    fresh_target=not in_place,
                )
            return ArrayIOPreparer.prepare_read(
                entry,
                targets[0],
                buffer_size_limit_bytes,
                frame_table=(frame_tables or {}).get(entry.location),
                fresh_target=not in_place,
            )

        if is_jax_array(live):
            # The host target exists only to be put on the device.
            held = _host_targets(
                arena, live, [(tuple(entry.shape), np_dtype)], plan, times
            )

            def finalize_jax() -> None:
                import jax

                (target,) = held.targets()
                sharding = live.sharding
                if sharding.is_fully_addressable:
                    place = lambda: jax.device_put(target, sharding)  # noqa: E731
                else:
                    # device_put onto a multiprocess sharding runs a jitted
                    # consistency collective (refused outright on the
                    # multiprocess CPU backend); building the global array
                    # shard-by-shard needs no collective on any backend —
                    # every rank holds the full host target here.
                    place = lambda: jax.make_array_from_callback(  # noqa: E731
                        tuple(int(s) for s in entry.shape),
                        sharding,
                        lambda idx: target[idx],
                    )
                with _placing(
                    times, logical_path, sharding, entry.shape, target.dtype.itemsize
                ):
                    loaded[logical_path] = _place_over_target(
                        logical_path, live, place, times
                    )
                held.placed(loaded[logical_path])

            return held.reqs, finalize_jax
        if in_place:
            target = live
        else:
            (target,) = _fresh_targets(times, [(tuple(entry.shape), np_dtype)])
        reqs = plan([target])
        loaded[logical_path] = target
        return reqs, None

    if isinstance(entry, ShardedArrayEntry):
        np_dtype = string_to_dtype(entry.dtype)
        if is_jax_array(live) and list(live.shape) == list(entry.shape):
            sharding = live.sharding
            rects = target_shard_rects(sharding, entry.shape)

            def plan(targets: List[np.ndarray]) -> List[ReadReq]:
                return ShardedArrayIOPreparer.prepare_read(
                    entry,
                    [(buf, off, sz) for buf, (off, sz) in zip(targets, rects)],
                    buffer_size_limit_bytes,
                    frame_tables=frame_tables,
                    digests=digests,
                    fresh_targets=True,
                )

            held = _host_targets(
                arena, live, [(tuple(sz), np_dtype) for _off, sz in rects], plan, times
            )

            def finalize_sharded() -> None:
                buffers = {
                    tuple(off): (buf, off, sz)
                    for buf, (off, sz) in zip(held.targets(), rects)
                }
                with _placing(
                    times, logical_path, sharding, entry.shape, np_dtype.itemsize
                ):
                    loaded[logical_path] = _place_over_target(
                        logical_path,
                        live,
                        lambda: assemble_jax_array(sharding, entry.shape, buffers),
                        times,
                    )
                held.placed(loaded[logical_path])

            return held.reqs, finalize_sharded
        # No live sharded target: materialize the full array on host.
        in_place = (
            isinstance(live, np.ndarray)
            and live.dtype == np_dtype
            and list(live.shape) == list(entry.shape)
            and live.flags["C_CONTIGUOUS"]
            and live.flags["WRITEABLE"]
        )
        if in_place:
            target = live
        else:
            (target,) = _fresh_targets(times, [(tuple(entry.shape), np_dtype)])
        reqs = ShardedArrayIOPreparer.prepare_read(
            entry,
            [(target, [0] * len(entry.shape), list(entry.shape))],
            buffer_size_limit_bytes,
            frame_tables=frame_tables,
            digests=digests,
            fresh_targets=not in_place,
        )
        loaded[logical_path] = target
        return reqs, None

    raise TypeError(f"Cannot restore entry type {entry.type} at {logical_path}")


# ---------------------------------------------------------------------------
# PendingSnapshot — async_take's handle
# ---------------------------------------------------------------------------

class PendingSnapshot:
    """Handle for an in-flight async snapshot (reference ``snapshot.py:904-988``).

    The background thread drains storage I/O, then runs the two-phase
    store-based barrier around rank 0's metadata commit. Any rank's failure
    is propagated through the store so no partial snapshot is ever committed;
    ``wait()`` re-raises the failure in the caller's thread.
    """

    # SPMD sequence number: every rank constructs PendingSnapshots in the
    # same order, so this per-process counter is identical across ranks and
    # makes barrier ids unique even when the same path is snapshotted twice
    # (otherwise stale arrive/done keys from a previous commit would let a
    # later commit tear).
    _seq = 0

    def __init__(
        self,
        path: str,
        pending_io_work: PendingIOWork,
        coord: Coordinator,
        metadata: SnapshotMetadata,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
        tm: Optional["telemetry.Telemetry"] = None,
        tm_prev: Optional["telemetry.Telemetry"] = None,
        phase_spans=None,
        catalog_info: Optional[Tuple[str, Optional[int], Optional[str], int]] = None,
        prepared_entry=None,
    ) -> None:
        self.path = path
        self._coord = coord
        # Prepared-state cache entry this take holds busy; released (array
        # refs unbound) when the background pipeline completes.
        self._prepared_entry = prepared_entry
        self._metadata = metadata
        self._pending_io_work = pending_io_work
        # (job, step, resolved base, chain_len) of a catalog-managed take;
        # the background commit thread appends the record post-metadata,
        # pre-barrier (rank 0) and refreshes the chain cache (every rank).
        self._catalog_info = catalog_info
        # Telemetry session opened by async_take; closed (and the trace
        # written) when the background commit finishes, so drain spans land
        # in the same trace as the stall's planning phases.
        self._tm = tm
        self._tm_prev = tm_prev
        # The take's phase spans (final by construction time: _take_impl has
        # returned), persisted into the snapshot's telemetry artifact by the
        # background drain.
        self._phase_spans = phase_spans
        PendingSnapshot._seq += 1
        self._barrier_id = f"async_commit/{PendingSnapshot._seq}/{path}"
        self._exc: Optional[BaseException] = None
        self._phase = "write"  # what the background thread is doing now
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._complete_snapshot,
            args=(pending_io_work, storage, event_loop),
            daemon=True,
            name="tss-async-commit",
        )
        self._thread.start()

    def _complete_snapshot(
        self,
        pending_io_work: PendingIOWork,
        storage: StoragePlugin,
        event_loop: asyncio.AbstractEventLoop,
    ) -> None:
        # NOTE: no XLA collectives are legal on this thread; coordination
        # happens via the KV store only.
        rank = self._coord.get_rank()
        barrier = LinearBarrier(
            store=self._coord.store,
            barrier_id=self._barrier_id,
            rank=rank,
            world_size=self._coord.get_world_size(),
        )
        try:
            self._phase = "write"
            pending_io_work.sync_complete(event_loop)
            # Pre-barrier, like the checksum sidecars: every committed
            # snapshot carries every rank's artifact. Fail-open.
            _persist_op_artifact(
                storage,
                event_loop,
                rank=rank,
                world_size=self._coord.get_world_size(),
                op="async_take",
                tm=self._tm,
                phase_spans=self._phase_spans,
                io_summary=pending_io_work.telemetry_io_summary(),
            )
            self._phase = "commit"
            # The take's own session, not whichever is active by now: the
            # next operation may have begun beside this drain. The artifact
            # above is already written, so the span shows in
            # ``Snapshot.last_telemetry`` and the chrome trace only.
            with (self._tm or telemetry).span(
                "take.commit", cat="take", bridge=True
            ), _barrier_stall_guard(rank):
                barrier.arrive()
                if rank == 0:
                    Snapshot._write_snapshot_metadata(
                        self._metadata, storage, event_loop
                    )
                    if self._catalog_info is not None:
                        # Same pre-barrier discipline as the sync path: the
                        # record lands after metadata, before peers are
                        # released. Fail-open; storage-only (no collectives
                        # are legal on this thread, and none are used).
                        job, step, base, chain_len = self._catalog_info
                        Snapshot._append_catalog_record(
                            self.path,
                            storage,
                            event_loop,
                            world_size=self._metadata.world_size,
                            job=job,
                            step=step,
                            base=base,
                            chain_len=chain_len,
                        )
                barrier.depart()
            if self._catalog_info is not None:
                from . import catalog as catalog_mod

                try:
                    split = catalog_mod.split_bucket(self.path)
                    if split is not None and knobs.is_catalog_enabled():
                        catalog_mod.note_commit(
                            split[0],
                            self._catalog_info[0],
                            split[1],
                            self._catalog_info[3],
                        )
                except Exception:  # noqa: BLE001 - cache refresh only
                    pass
        except BaseException as e:  # noqa: BLE001 - re-raised in wait()
            logger.error(
                "Async snapshot failed on rank %d:\n%s", rank, traceback.format_exc()
            )
            telemetry.counter_add("snapshot.abort")
            try:
                barrier.report_error(
                    e if isinstance(e, Exception) else RuntimeError(repr(e)),
                    phase=self._phase,
                )
            except Exception:
                pass
            self._exc = e
        finally:
            try:
                from . import prepare_cache as prepare_cache_mod

                prepare_cache_mod.release(self._prepared_entry)
            except Exception:
                pass
            try:
                storage.sync_close(event_loop)
                event_loop.close()
            except Exception:
                pass
            # Op end on the fleet bus from the commit thread (the publish
            # is plain store traffic — legal here; beacon GC stays on the
            # main thread with the coordinator's deferred deletes).
            telemetry.fleet.note_op(None)
            _finish_telemetry(self._tm, self._tm_prev, rank)
            self._done.set()

    def wait(self) -> Snapshot:
        self._thread.join()
        if self._exc is not None:
            e = self._exc
            # Same structured abort as the sync path: peers' reports carry
            # their rank + phase through the barrier; a barrier timeout
            # (peer died without reporting) stays unattributed; everything
            # else names THIS rank. RuntimeError subclass + original cause
            # chained, so existing `except RuntimeError` callers and
            # cause-inspecting tests keep working.
            if isinstance(e, BarrierError):
                raise CheckpointAbortedError(
                    self.path, e.rank, e.phase or "commit", str(e)
                ) from e
            if isinstance(e, TimeoutError):
                raise CheckpointAbortedError(
                    self.path, None, self._phase, repr(e)
                ) from e
            raise CheckpointAbortedError(
                self.path, self._coord.get_rank(), self._phase, repr(e)
            ) from e
        snapshot = Snapshot(path=self.path, coordinator=self._coord)
        snapshot._metadata = self._metadata
        return snapshot

    def done(self) -> bool:
        return self._done.is_set()

    def progress(self) -> Dict[str, float]:
        """Live progress of the background drain, safe to call from the
        training thread at any time: strictly nondecreasing
        ``bytes_staged`` / ``bytes_written`` / ``requests_done`` counters
        fed by the scheduler (``bytes_written`` ends equal to the take's
        total payload bytes), plus ``bytes_total`` / ``requests_total``,
        instantaneous and EWMA write rates over the polling window, and an
        ``eta_s`` estimate (None until a rate is established, 0.0 when all
        bytes are written). See ``telemetry.ProgressTracker.snapshot``."""
        return self._pending_io_work.progress_snapshot()

    @property
    def drain_stats(self) -> Dict[str, float]:
        """Overlap accounting of the background drain (empty until the
        snapshot commits): wall_s, stage_busy_s (D2H+serialize in flight),
        io_busy_s (storage writes in flight), overlap_s (both), idle_s.
        Low overlap relative to the shorter stream means the drain
        serialized D2H against storage writes — the thing to tune at
        multi-GB checkpoint scale."""
        return self._pending_io_work.drain_stats
