"""Per-process prepared-state cache: amortized ``prepare_write`` for
steady-state takes.

A training job taking periodic snapshots of the same pytree re-runs the
entire prepare machinery every step — leaf classification, per-leaf stager
and manifest-entry construction, the partition collective, slab batching —
even though every one of those decisions is a pure function of the take's
*structure* (shapes/dtypes/shardings, the replicated globs, world size,
and every prepare-affecting knob). That structure is exactly what the
``take_plan`` fingerprint hashes (it folds in the batch/capture knobs),
so the fingerprint is a sound cache key for the *prepared
artifacts themselves*:

- the post-partition, post-batch write requests (stagers constructed,
  slabs packed into their frame layout, defer flags set);
- the local manifest leaf entries (locations, byte/raw ranges — already
  relocated/slab-mutated);
- the partition assignment (so the hit path skips the partition
  collective as well).

On a fingerprint hit, ``prepare_write`` + partition + batching collapse
into :meth:`PreparedTake.rebind`: capture the new step's arrays (under
``TORCHSNAPSHOT_TPU_ASYNC_CAPTURE=donate`` a zero-copy no-op), point each
cached stager at the new step's leaf values, and reset per-take staging
state. Everything structural — entries, slab offsets, compression levels
— is reused as-is. Primitive entries embed their
values, so those are the one thing recomputed per take.

Strict invalidation is inherited from the key: any shape/dtype/sharding
change, any world-size change, any prepare-affecting knob flip produces a
different fingerprint and therefore a miss (full re-prepare, exactly
today's path). Belt-and-braces, ``rebind`` re-classifies every leaf and
raises :class:`RebindMismatch` on any disagreement with the cached plan
(kind, captured-ness, piece count), which the caller treats as a miss.

Concurrency: a cached state's stagers are single-use-at-a-time (they hold
the step's array refs until the pipeline drains). Each entry carries an
``in_use`` latch — ``acquire`` refuses a busy entry (an overlapping second
take simply misses and stores a replacement) and ``release`` (called when
the pipeline completes, success or failure) *unbinds* the array references
so a cached state never pins device or host buffers between takes.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .device_programs import chunk_row_ranges
from .io_preparer import HostCapturedArray, capture_flattened, classify
from .io_preparers.array import (
    ArrayBufferStager,
    PollingTableStager,
    is_jax_array,
)
from .io_preparers.chunked_array import should_chunk
from .io_preparers.object import ObjectBufferStager
from .io_preparers.sharded_array import local_unique_shards, shard_pieces
from .io_types import WriteReq
from .manifest import Entry, PrimitiveEntry
from .utils import knobs

logger = logging.getLogger(__name__)

Manifest = Dict[str, Entry]

# (fingerprint, storage plugin class, sync/async): stagers are built with
# async-dependent defer flags, so states prepared for one mode (or against
# one backend) must not serve another.
CacheKey = Tuple[str, str, bool]


class RebindMismatch(RuntimeError):
    """The new step's tree disagrees with the cached plan — treat as miss."""


@dataclass
class PreparedTake:
    """One fingerprint's prepared artifacts (see module docstring)."""

    key: CacheKey
    # Leaf structure recorded at prepare time: {path: (kind, captured)}.
    leaf_kinds: Dict[str, Tuple[str, bool]]
    # {path: the write reqs that leaf produced, in construction order}.
    leaf_index: Dict[str, List[WriteReq]]
    # Local manifest leaf entries (live objects, post-partition/batch).
    local_manifest: Manifest
    # Post-partition post-batch requests, pipeline-ready.
    write_reqs: List[WriteReq]
    # The partition assignment the hit path replays (skips the collective).
    assignment: Dict[str, int]
    in_use: bool = field(default=False)
    hits: int = field(default=0)

    def rebind(
        self,
        flattened: Dict[str, Any],
        world_size: int,
        is_async_snapshot: bool,
        timings: Optional[Dict[str, float]] = None,
    ) -> Tuple[Manifest, List[WriteReq], Dict[str, int]]:
        """Bind the new step's values into the cached stagers and return
        ``(local_manifest, write_reqs, assignment)`` — the hit-path
        replacement for prepare_write + partition + batching.

        Raises :class:`RebindMismatch` if the tree's structure disagrees
        with the cached plan in any way the fingerprint should have caught
        (defense in depth — the caller falls back to a full re-prepare)."""
        if set(flattened.keys()) != set(self.leaf_kinds.keys()):
            raise RebindMismatch("leaf path set changed")
        if is_async_snapshot:
            # The capture step still runs per take: under fork mode the
            # defensive device fork is the donation-safety contract; under
            # donate mode this is a zero-copy no-op and the whole rebind
            # is O(leaves) pointer swaps.
            flattened = capture_flattened(flattened, timings)
        for path in self.leaf_kinds:
            value = flattened[path]
            kind, was_captured = self.leaf_kinds[path]
            if classify(value, world_size) != kind:
                raise RebindMismatch(f"{path}: leaf kind changed")
            if isinstance(value, HostCapturedArray) != was_captured:
                raise RebindMismatch(f"{path}: capture mode changed")
            reqs = self.leaf_index.get(path, [])
            if kind == "primitive":
                old = self.local_manifest[path]
                self.local_manifest[path] = PrimitiveEntry.from_value(
                    value, replicated=old.replicated
                )
                continue
            if kind == "object":
                self._rebind_object(path, value, reqs)
                continue
            pieces = self._pieces_for(kind, value)
            self._rebind_arrays(path, pieces, reqs)
        self._reset_slab_state()
        # Fresh list (same req objects): the pipeline may reorder/filter
        # its input, and the cached ordering must survive for the next hit.
        return self.local_manifest, list(self.write_reqs), self.assignment

    @staticmethod
    def _pieces_for(kind: str, value: Any) -> List[Any]:
        """The leaf's staged pieces, in the exact order the preparers
        produced them at prepare time (their iteration is deterministic
        given the structure the fingerprint pins)."""
        if kind == "sharded":
            max_shard = knobs.get_max_shard_size_bytes()
            pieces: List[Any] = []
            for data, offsets, sizes, replica_id in local_unique_shards(value):
                if replica_id != 0:
                    continue
                pieces.extend(
                    piece for _, _, piece in shard_pieces(data, offsets, sizes, max_shard)
                )
            return pieces
        # array / replicated_array: the same unwraps prepare_write applies.
        arr = value
        if isinstance(arr, HostCapturedArray):
            arr = arr.assembled_local()
        elif (
            is_jax_array(arr)
            and len(arr.sharding.device_set) > 1
            and arr.sharding.is_fully_replicated
        ):
            arr = arr.addressable_shards[0].data
        if should_chunk(arr):
            dtype = np.dtype(arr.dtype)
            ranges = chunk_row_ranges(
                list(arr.shape), dtype.itemsize, knobs.get_max_chunk_size_bytes()
            )
            return [arr[r0:r1] for r0, r1 in ranges]
        return [arr]

    @staticmethod
    def _rebind_object(path: str, value: Any, reqs: List[WriteReq]) -> None:
        bound = 0
        for req in reqs:
            stager = req.buffer_stager
            if isinstance(stager, ObjectBufferStager):
                stager.rebind(value)
                bound += 1
            elif not isinstance(stager, PollingTableStager):
                raise RebindMismatch(f"{path}: unexpected stager {type(stager)}")
        if bound != 1:
            raise RebindMismatch(f"{path}: expected 1 object stager, saw {bound}")

    @staticmethod
    def _rebind_arrays(path: str, pieces: List[Any], reqs: List[WriteReq]) -> None:
        it = iter(pieces)
        bound = 0
        for req in reqs:
            stager = req.buffer_stager
            if isinstance(stager, ArrayBufferStager):
                try:
                    stager.rebind(next(it))
                except StopIteration:
                    raise RebindMismatch(f"{path}: fewer pieces than stagers")
                bound += 1
            elif not isinstance(stager, PollingTableStager):
                raise RebindMismatch(f"{path}: unexpected stager {type(stager)}")
        if bound != len(pieces):
            raise RebindMismatch(
                f"{path}: {len(pieces)} pieces for {bound} stagers"
            )

    def _reset_slab_state(self) -> None:
        from .batcher import CompressedSlabStager

        for req in self.write_reqs:
            stager = req.buffer_stager
            if isinstance(stager, CompressedSlabStager):
                stager.reset_take()

    def unbind(self) -> None:
        """Drop every array/object reference held by the cached stagers so
        the cache pins no device or host buffers between takes."""
        for reqs in self.leaf_index.values():
            for req in reqs:
                stager = req.buffer_stager
                unbind = getattr(stager, "unbind", None)
                if unbind is not None:
                    unbind()


# ---------------------------------------------------------------------------
# Per-process store. Like the cross-take plan cache this hangs off the
# coordinator (a process-wide singleton across takes; per-rank objects in
# multi-rank simulations), keyed by the full CacheKey — an LRU of
# TORCHSNAPSHOT_TPU_PREPARED_CACHE_SIZE entries.
# ---------------------------------------------------------------------------

_ATTR = "_prepared_take_cache"
_LOCK = threading.Lock()


def _cache(coord) -> "OrderedDict[CacheKey, PreparedTake]":
    cache = getattr(coord, _ATTR, None)
    if cache is None:
        cache = OrderedDict()
        setattr(coord, _ATTR, cache)
    return cache


def acquire(coord, key: CacheKey) -> Optional[PreparedTake]:
    """Probe the cache; a hit marks the entry busy (``in_use``) until the
    owning pipeline calls :func:`release`. A busy entry (overlapping take
    on the same structure) is a miss by design."""
    with _LOCK:
        cache = _cache(coord)
        entry = cache.get(key)
        if entry is None or entry.in_use:
            return None
        entry.in_use = True
        entry.hits += 1
        cache.move_to_end(key)
        return entry


def store(coord, key: CacheKey, entry: PreparedTake) -> None:
    """Insert a freshly prepared state (busy until its pipeline releases
    it). Replaces any same-key entry; trims LRU-oldest idle entries beyond
    the size knob (busy entries are dropped from the map but keep their
    artifacts alive until their own release)."""
    with _LOCK:
        cache = _cache(coord)
        old = cache.pop(key, None)
        if old is not None and not old.in_use:
            old.unbind()
        entry.in_use = True
        cache[key] = entry
        cache.move_to_end(key)
        limit = knobs.get_prepared_cache_size()
        while len(cache) > limit:
            _, evicted = cache.popitem(last=False)
            if not evicted.in_use:
                evicted.unbind()


def release(entry: Optional[PreparedTake]) -> None:
    """Pipeline-completion hook (success or failure): unbind the step's
    array references and return the entry to the pool."""
    if entry is None:
        return
    with _LOCK:
        entry.unbind()
        entry.in_use = False


def invalidate(coord, key: CacheKey) -> None:
    """Drop one entry (rebind-mismatch fallback)."""
    with _LOCK:
        cache = _cache(coord)
        entry = cache.pop(key, None)
        if entry is not None and not entry.in_use:
            entry.unbind()


def reset(coord) -> None:
    """Drop all of one coordinator's entries (tests)."""
    with _LOCK:
        cache = getattr(coord, _ATTR, None)
        if cache:
            for entry in cache.values():
                if not entry.in_use:
                    entry.unbind()
            cache.clear()


def stats(coord) -> Dict[str, Any]:
    """Introspection for tests/bench: entry count and per-entry hit counts."""
    with _LOCK:
        cache = _cache(coord)
        return {
            "entries": len(cache),
            "hits": {
                f"{k[0][:12]}:{'async' if k[2] else 'sync'}": e.hits
                for k, e in cache.items()
            },
        }
