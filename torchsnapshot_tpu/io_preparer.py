"""Value -> (Entry, WriteReqs/ReadReqs) dispatch.

TPU-native analogue of the reference's ``io_preparer.py:51-178``, with the
routing redesigned around ``jax.Array``'s sharding metadata instead of
torch's type taxonomy:

- primitives -> inline :class:`PrimitiveEntry`;
- ``jax.Array`` **fully replicated across every process** -> the replicated
  array path (saved once globally, write load split by the partitioner).
  This replaces the reference's DDP-module sniffing
  (``snapshot.py:828-844``): on TPU, replication is *read off the sharding*,
  no user globs required;
- ``jax.Array`` on exactly one local device -> per-rank array path;
- any other ``jax.Array`` (sharded / partially replicated) -> the sharded
  path (elastic by construction);
- ``np.ndarray`` -> array path (replicated only via user glob);
- anything else -> pickled object.

Arrays whose serialized size exceeds the chunking knob are split into dim-0
chunks for transfer/I-O pipelining.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from .io_types import WriteReq
from .manifest import (
    Manifest,
    PrimitiveEntry,
    PRIMITIVE_TYPES,
)
from . import d2h, device_programs, telemetry
from .device_programs import PiecedArray, copy_preserves_bits, is_oom_error
from .io_preparers.array import ArrayIOPreparer, is_jax_array
from .io_preparers.chunked_array import ChunkedArrayIOPreparer, should_chunk
from .io_preparers.object import ObjectIOPreparer
from .io_preparers.sharded_array import ShardedArrayIOPreparer
from .utils import knobs

logger = logging.getLogger(__name__)


def get_storage_path(logical_path: str, rank: int, replicated: bool) -> str:
    """Reference ``io_preparer.py:51-57`` (``sharded/`` handled separately)."""
    return f"replicated/{logical_path}" if replicated else f"{rank}/{logical_path}"


def _globally_replicated(arr: Any, world_size: int) -> bool:
    sharding = arr.sharding
    if not sharding.is_fully_replicated:
        return False
    procs = {d.process_index for d in sharding.device_set}
    return len(procs) == world_size and world_size > 1


class _HostShard:
    """Mimics ``jax.Shard`` for host-captured data: the planning-visible
    metadata (index/replica_id/device) with the data already in host RAM."""

    __slots__ = ("index", "replica_id", "device", "data")

    def __init__(self, index: Any, replica_id: int, device: Any, data: np.ndarray) -> None:
        self.index = index
        self.replica_id = replica_id
        self.device = device
        self.data = data


class HostCapturedArray:
    """A donation-safe *host* capture of a ``jax.Array``.

    Produced by the degraded async-fork path when HBM can't hold an
    on-device defensive copy (the reference's host-capture semantics,
    ``io_preparers/tensor.py:254-278`` — which always work, at the cost of a
    blocking D2H inside the take stall). Preserves exactly the metadata the
    write planners read — ``shape``/``dtype``/``sharding``/
    ``addressable_shards`` with per-shard ``index``/``replica_id`` — so the
    resulting plan (entries, shard locations, partition assignment) is
    byte-identical to the device-forked plan; only the stagers' data source
    differs (private host buffers instead of forked device buffers).
    """

    def __init__(self, shape: Tuple[int, ...], dtype: Any, sharding: Any, shards: List[_HostShard]) -> None:
        self.shape = shape
        self.dtype = dtype
        self.sharding = sharding
        self.addressable_shards = shards

    def assembled_local(self) -> np.ndarray:
        """The full local value (what ``np.asarray`` yields for the original
        array): shard 0 when one shard covers the array, else the shards
        scattered into a host buffer (a per-rank array sharded across
        multiple *local* devices classifies as "array" and stages whole)."""
        shards = self.addressable_shards
        if len(shards) == 1 or self.sharding.is_fully_replicated:
            return shards[0].data
        out = np.empty(self.shape, dtype=self.dtype)
        for s in shards:
            out[s.index] = s.data
        return out


def _is_plannable_array(value: Any) -> bool:
    """jax.Array, or a capture carrying the same planning metadata: through
    the host, or forked in pieces."""
    return is_jax_array(value) or isinstance(
        value, (HostCapturedArray, PiecedArray)
    )


def classify(value: Any, world_size: int) -> str:
    """One of: primitive | sharded | replicated_array | array | object."""
    if isinstance(value, PRIMITIVE_TYPES) and not isinstance(value, np.generic):
        return "primitive"
    if _is_plannable_array(value):
        if _globally_replicated(value, world_size):
            return "replicated_array"
        procs = {d.process_index for d in value.sharding.device_set}
        if world_size > 1 and len(procs) == 1:
            # Device set confined to one process: this is per-rank data, not
            # a slice of a global array. The sharded path would write it to
            # rank-less ``sharded/<path>`` locations where different ranks'
            # distinct arrays at the same logical path clobber each other.
            return "array"
        if len(value.sharding.device_set) == 1:
            return "array"
        return "sharded"
    if isinstance(value, np.ndarray):
        return "array"
    return "object"


def _defensive_device_copies(arrs: List[Any]) -> List[Any]:
    """Fork jax arrays' device buffers for async capture — in ONE program.

    TPU-native replacement for the reference's defensive *host* copies
    (``io_preparers/tensor.py:254-278``): torch must capture mutable tensors
    in host RAM before ``async_take`` returns; jax arrays are immutable, so
    the only hazard is the training step *donating* the buffers
    (``donate_argnums``), which marks every reference deleted. An on-device
    copy (dispatched asynchronously — microseconds on the host timeline,
    HBM-bandwidth on the device) detaches the snapshot from donation.

    All leaves are copied in a single jitted call: per-leaf ``jit(jnp.copy)``
    would compile one XLA program per (sharding, shape) — tens of seconds of
    cold-start stall on a real transformer state — whereas one program
    compiles once per state *structure* and dispatches once per take.

    The copy runs under ``jit`` pinned to each array's own sharding: eager
    ``jnp.copy`` would raise on non-fully-addressable (multi-process) global
    arrays, and every rank reaches this point in the same gathered-key
    order, so the SPMD requirement holds. ``out_shardings`` is explicit —
    downstream routing (``classify``, shard enumeration) reads the copy's
    sharding, so propagation must not be allowed to pick a different one.

    One jitted computation requires all operands to share a device
    assignment, so leaves are grouped by assignment first (params on the
    full mesh vs. a step counter committed to one device vs. host-offloaded
    state); each group compiles and dispatches once.

    **HBM-pressure degradation** (the availability guarantee): exactly when
    checkpointing matters most — model + optimizer near HBM capacity — the
    full-state copy may not fit. An allocation failure
    (``RESOURCE_EXHAUSTED``) from a group's fork degrades that group by
    bisection: sub-groups whose fork still fits stay device-forked (their
    D2H drains asynchronously in the background as usual), and leaves whose
    fork fails even alone are captured *through host RAM, blocking, from
    the original buffers* — zero HBM overhead, donation-safe because it
    completes before ``async_take`` returns. This is the reference's
    host-capture design (``io_preparers/tensor.py:254-278``), applied only
    to the residual that doesn't fit, so ``async_take`` is never less
    available than the reference: the HBM overhead is bounded by what
    actually fit (by construction), and only the host-captured bytes extend
    the stall (a warning reports both).

    **Dtypes the copy would rewrite** (float16, float8: the device copy
    replaces NaN payloads, ``device_programs.copy_preserves_bits``) are
    never forked: those leaves are host-captured up front — D2H moves bits
    unchanged — and counted (``capture.dtype_captured_leaves``).
    """
    groups: Dict[Any, List[int]] = {}
    out: List[Any] = [None] * len(arrs)
    inexact = [i for i, a in enumerate(arrs) if not copy_preserves_bits(a.dtype)]
    if inexact:
        telemetry.counter_add("capture.dtype_captured_leaves", len(inexact))
        global _dtype_capture_warned
        if not _dtype_capture_warned:
            _dtype_capture_warned = True
            logger.warning(
                "async_take captures %d leaves of dtype %s through host RAM "
                "inside the stall: a device copy does not return these "
                "dtypes bit for bit (NaN payloads are rewritten)",
                len(inexact),
                sorted({str(arrs[i].dtype) for i in inexact}),
            )
        for i, c in zip(inexact, _host_capture_group([arrs[i] for i in inexact])):
            out[i] = c
    for i, a in enumerate(arrs):
        if out[i] is None:
            groups.setdefault(device_programs.device_assignment_key(a.sharding), []).append(i)
    # Cumulative successfully-forked local bytes across this take, for the
    # simulated-HBM-limit knob (mirrors real accounting: forks accumulate).
    forked_bytes = [0]
    captured: List[Any] = []  # host-captured leaves, for the warning
    for indices in groups.values():
        group = [arrs[i] for i in indices]
        copies = _fork_or_capture(group, forked_bytes, captured)
        for i, c in zip(indices, copies):
            out[i] = c
    if captured:
        logger.warning(
            "async_take defensive fork hit HBM pressure: %d of %d leaves "
            "(%.3f GB) were captured through host RAM instead (blocking "
            "D2H inside the take stall; device-forked leaves still drain "
            "in the background). The snapshot remains donation-safe.",
            len(captured),
            len(arrs),
            sum(_local_fork_nbytes(a) for a in captured) / 1e9,
        )
    return out


def _local_fork_nbytes(arr: Any) -> int:
    """HBM bytes a defensive fork of ``arr`` allocates on this process."""
    return sum(int(s.data.nbytes) for s in arr.addressable_shards)


# Log-once guards: the backend-capability degradation below, and leaves whose
# dtype the fork program would rewrite (a property of the model, not of a take).
_fork_unsupported_warned = False
_dtype_capture_warned = False


def _is_fork_unsupported_error(group: List[Any], e: BaseException) -> bool:
    """jax's CPU backend refuses multiprocess jitted computations outright
    (INVALID_ARGUMENT), regardless of size. Bisection can't help; the whole
    group must capture through host RAM (the reference's design, still
    donation-safe). CPU-only by construction: on an accelerator the same
    text is an error like any other."""
    on_cpu = all(d.platform == "cpu" for d in group[0].sharding.device_set)
    return on_cpu and "implemented on the CPU backend" in str(e)


def _try_fork(group: List[Any], forked_bytes: List[int]) -> List[Any]:
    """One batched jitted copy of ``group``; raises on allocation failure.

    PJRT allocates output buffers synchronously at dispatch, so a real
    ``RESOURCE_EXHAUSTED`` surfaces from this call without blocking on the
    copy itself. The knob simulates the same failure for tests/tiny-HBM."""
    limit = knobs.get_async_fork_hbm_limit_bytes()
    if limit is not None:
        need = sum(_local_fork_nbytes(a) for a in group)
        if forked_bytes[0] + need > limit:
            raise RuntimeError(
                f"RESOURCE_EXHAUSTED: simulated HBM limit "
                f"({forked_bytes[0]} + {need} > {limit} bytes)"
            )
    shardings = tuple(a.sharding for a in group)
    while True:
        # A leaf the planner will chunk is cut there, not here.
        cuts = tuple(
            None if should_chunk(a) else device_programs.leaf_cut(a) for a in group
        )
        try:
            copies = device_programs.batch_copy_fn(shardings, cuts)(group)
            break
        except Exception as e:  # noqa: BLE001 - only the kernel's compiler degrades
            if not any(cuts) or "Mosaic" not in str(e):
                raise
            device_programs.give_up_cut(cuts, e)
    copies = [
        c if cut is None else PiecedArray(a.shape, a.dtype, a.sharding, c, cut.ranges)
        for a, c, cut in zip(group, copies, cuts)
    ]
    relaid = [a for a, cut in zip(group, cuts) if cut is not None and cut.relaid]
    telemetry.counter_add("capture.fork_relaid_leaves", len(relaid))
    telemetry.counter_add("capture.fork_relaid_bytes", sum(int(a.nbytes) for a in relaid))
    telemetry.counter_add("capture.forked_leaves", len(group))
    if limit is not None:
        # Accounting feeds only the simulated limit; skip the per-shard
        # walk on the production hot path.
        forked_bytes[0] += need
    return copies


# Bisection depth bound for the degraded fork: each distinct sub-group is a
# fresh XLA program whose compile runs inside the (already degraded) stall,
# so recursion stops at quarters — at most 6 extra compiles per failing
# group, reused across takes via ``device_programs``' LRU of forks. Anything
# a quarter group can't fit is host-captured without further compile
# attempts. (The simulated-limit knob raises before compiling, so tests pay
# nothing.)
_MAX_FORK_BISECT_DEPTH = 2


def _fork_or_capture(
    group: List[Any], forked_bytes: List[int], captured: List[Any], depth: int = 0
) -> List[Any]:
    """Fork the group; on allocation failure bisect so what fits stays
    device-forked and the rest is host-captured (see
    ``_defensive_device_copies``)."""
    try:
        return _try_fork(group, forked_bytes)
    except Exception as e:  # noqa: BLE001 - only OOM/capability degrades
        if _is_fork_unsupported_error(group, e):
            global _fork_unsupported_warned
            if not _fork_unsupported_warned:
                _fork_unsupported_warned = True
                logger.warning(
                    "async_take defensive device fork is unsupported on "
                    "this backend (%s); capturing through host RAM instead "
                    "— donation-safe, but the blocking D2H joins the take "
                    "stall",
                    e,
                )
            return _host_capture_group(group)
        if not is_oom_error(e):
            raise
    if len(group) == 1 or depth >= _MAX_FORK_BISECT_DEPTH:
        captured.extend(group)
        return _host_capture_group(group)
    mid = len(group) // 2
    return _fork_or_capture(
        group[:mid], forked_bytes, captured, depth + 1
    ) + _fork_or_capture(group[mid:], forked_bytes, captured, depth + 1)


def _host_capture_group(group: List[Any]) -> List[HostCapturedArray]:
    """Blocking host capture of a group of arrays: async D2H hints for EVERY
    shard of EVERY array first, so the per-shard resolves pipeline on the
    transfer engine instead of serializing array by array."""
    # Which path a leaf took is a fact of the take, exported with it (the
    # persisted telemetry artifact), not only a log line.
    telemetry.counter_add("capture.host_captured_leaves", len(group))
    telemetry.counter_add(
        "capture.host_captured_bytes", sum(_local_fork_nbytes(a) for a in group)
    )
    for a in group:
        for s in a.addressable_shards:
            d2h.hint_copy_to_host(s.data)
    return [_host_capture(a) for a in group]


def _aliases_device_buffer(shard_data: Any) -> bool:
    """Whether ``np.asarray(shard_data)`` may alias the XLA buffer (which
    donation would then free under the stager). A TPU device-memory D2H
    result is always a private host copy; CPU-backed and host-offloaded
    arrays can be zero-copy views — and jax returns its cached ``np.asarray``
    read-only with ``base=None`` on every backend, so the numpy flags can't
    distinguish the two."""
    try:
        if next(iter(shard_data.devices())).platform == "cpu":
            return True
        return shard_data.sharding.memory_kind not in (None, "device")
    except Exception:  # pragma: no cover - be safe on exotic platforms
        return True


def _host_capture(arr: Any) -> HostCapturedArray:
    host_shards = []
    for s in arr.addressable_shards:
        data = np.asarray(s.data)
        if _aliases_device_buffer(s.data):
            data = data.copy()
        host_shards.append(_HostShard(s.index, s.replica_id, s.device, data))
    return HostCapturedArray(
        tuple(int(d) for d in arr.shape), np.dtype(arr.dtype), arr.sharding, host_shards
    )


def capture_flattened(
    flattened: Dict[str, Any], timings: Optional[Dict[str, float]] = None
) -> Dict[str, Any]:
    """The async-take capture step, shared by the full prepare path and the
    prepared-cache rebind path (``prepare_cache.py``): detach device arrays
    from the training step before ``async_take`` returns.

    Under the default ``fork`` capture mode this dispatches the defensive
    on-device copies (donation safety — see ``_defensive_device_copies``).
    Under ``donate`` (``TORCHSNAPSHOT_TPU_ASYNC_CAPTURE=donate``) the
    caller has promised not to donate or delete the passed arrays until
    the snapshot commits, so the immutable arrays are captured ZERO-COPY:
    no fork, no HBM overhead, capture cost ~0 — the steady-state mode.

    Returns ``flattened`` with device leaves replaced by their captures
    (the input dict is never mutated); ``timings["d2h_hint"]`` accumulates
    the capture wall time."""
    device_paths = [p for p, v in flattened.items() if is_jax_array(v)]
    if (
        not device_paths
        or not knobs.is_async_device_copy_enabled()
        or knobs.get_async_capture_mode() == "donate"
    ):
        return flattened
    t0 = time.monotonic()
    copies = _defensive_device_copies([flattened[p] for p in device_paths])
    if timings is not None:
        timings["d2h_hint"] = timings.get("d2h_hint", 0.0) + (
            time.monotonic() - t0
        )
    flattened = dict(flattened)
    flattened.update(zip(device_paths, copies))
    return flattened


def prepare_write(
    flattened: Dict[str, Any],
    rank: int,
    world_size: int,
    replicated_paths: Set[str],
    is_async_snapshot: bool = False,
    timings: Optional[Dict[str, float]] = None,
    leaf_index: Optional[Dict[str, List[WriteReq]]] = None,
) -> Tuple[Manifest, List[WriteReq]]:
    """Plan all writes for this rank's flattened state (no data moves yet).

    ``timings``: optional out-param decomposing this call's wall time into
    the ``stage.prepare.*`` buckets — ``d2h_hint`` (the defensive device
    fork + transfer hints), ``stager_construction`` (the per-preparer
    ``prepare_write`` calls building stagers/manifest entries), and
    ``plan`` (classification, path mapping, everything else). The take
    path persists them as sub-spans of the ``prepare_write`` stall phase,
    so the stall decomposition's dominant phase is attributable instead of
    a single opaque number.

    ``leaf_index``: optional out-param mapping each logical path to the
    write requests its leaf produced, in construction order — the
    prepared-state cache's rebind map (``prepare_cache.py``). Primitives
    record an empty list (manifest entry only)."""
    t_begin = time.monotonic()
    d2h_hint_s = 0.0
    stager_s = 0.0
    manifest: Manifest = {}
    write_reqs: List[WriteReq] = []
    if is_async_snapshot:
        # Device arrays are immutable; fork them against donation (or
        # capture them zero-copy under the donate contract) and defer
        # their staging past async_take's return. Mutable host state keeps
        # defer_staging=False and is captured (staged under the budget)
        # before async_take returns — the reference's semantics
        # (``scheduler.py:178-214``).
        capture_timings: Dict[str, float] = {}
        flattened = capture_flattened(flattened, capture_timings)
        d2h_hint_s += capture_timings.get("d2h_hint", 0.0)
    device_paths_set = {p for p, v in flattened.items() if _is_plannable_array(v)}
    for logical_path, value in flattened.items():
        is_device_value = logical_path in device_paths_set
        kind = classify(value, world_size)
        glob_replicated = logical_path in replicated_paths
        # Host-captured leaves already hold private host buffers: their
        # stagers must not re-copy (is_async_snapshot=False below), but
        # their staging still defers past async_take's return like any
        # other immutable capture.
        is_captured = isinstance(value, HostCapturedArray)

        if kind == "primitive":
            manifest[logical_path] = PrimitiveEntry.from_value(
                value, replicated=glob_replicated
            )
            if leaf_index is not None:
                leaf_index[logical_path] = []
            continue

        if kind == "sharded":
            t0 = time.monotonic()
            entry, reqs = ShardedArrayIOPreparer.prepare_write(
                logical_path,
                value,
                is_async_snapshot=is_async_snapshot and not is_captured,
            )
            stager_s += time.monotonic() - t0
            manifest[logical_path] = entry
            if is_async_snapshot:
                for r in reqs:
                    r.defer_staging = True
            if leaf_index is not None:
                leaf_index[logical_path] = list(reqs)
            write_reqs.extend(reqs)
            continue

        if kind in ("replicated_array", "array"):
            replicated = kind == "replicated_array" or glob_replicated
            arr = value
            if is_captured:
                arr = arr.assembled_local()
            elif (
                is_jax_array(arr)
                and len(arr.sharding.device_set) > 1
                and arr.sharding.is_fully_replicated
            ):
                # Fully-replicated multi-device array: stage from the local copy.
                arr = arr.addressable_shards[0].data
            storage_path = get_storage_path(logical_path, rank, replicated)
            t0 = time.monotonic()
            if should_chunk(arr):
                entry, reqs = ChunkedArrayIOPreparer.prepare_write(
                    storage_path, arr, replicated, is_async_snapshot and not is_captured
                )
            else:
                entry, reqs = ArrayIOPreparer.prepare_write(
                    storage_path,
                    arr,
                    replicated,
                    is_async_snapshot and not is_captured,
                    whole_leaf=True,
                )
            stager_s += time.monotonic() - t0
            manifest[logical_path] = entry
            if is_async_snapshot and is_device_value:
                for r in reqs:
                    r.defer_staging = True
            if leaf_index is not None:
                leaf_index[logical_path] = list(reqs)
            write_reqs.extend(reqs)
            continue

        # object fallback
        storage_path = get_storage_path(logical_path, rank, glob_replicated)
        t0 = time.monotonic()
        entry, reqs = ObjectIOPreparer.prepare_write(
            storage_path, value, replicated=glob_replicated
        )
        stager_s += time.monotonic() - t0
        manifest[logical_path] = entry
        if leaf_index is not None:
            leaf_index[logical_path] = list(reqs)
        write_reqs.extend(reqs)
    if timings is not None:
        total = time.monotonic() - t_begin
        timings["d2h_hint"] = d2h_hint_s
        timings["stager_construction"] = stager_s
        # Classification, path mapping, manifest assembly — the remainder.
        timings["plan"] = max(0.0, total - d2h_hint_s - stager_s)
    return manifest, write_reqs
