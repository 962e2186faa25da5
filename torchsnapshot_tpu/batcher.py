"""Small-write batching: coalesce many small arrays into slab objects.

Analogue of the reference's ``batcher.py:49-482``. Storage backends (cloud
object stores especially) pay a fixed per-object cost; a model with thousands
of small params would otherwise issue thousands of writes. Batching packs all
raw-serialized arrays smaller than the slab threshold into ``batched/<uuid>``
slab objects and relocates their entries via ``byte_range``.

Key TPU-first simplification over the reference: every raw-serialized
array's byte size is computable from (shape, dtype) at *planning* time, so
slab layout (member offsets) is decided before any data is staged — no
two-phase relocation pass is needed. The read side merges adjacent byte
ranges of the same object into single ranged reads.

Gated off by default behind ``knobs.is_batching_enabled()`` (reference
``knobs.py:53-57``; enable with ``TORCHSNAPSHOT_TPU_ENABLE_BATCHING=1``).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
import uuid
from concurrent.futures import Executor
from typing import Dict, List, Optional, Tuple

from .io_types import (
    BufferConsumer,
    BufferStager,
    BufferType,
    ReadReq,
    WriteReq,
)
from .manifest import (
    ArrayEntry,
    ChunkedArrayEntry,
    Entry,
    ShardedArrayEntry,
)
from .device_programs import device_assignment_key, is_oom_error
from .io_preparers.array import (
    FRAME_TABLE_SUFFIX as _FRAME_TABLE_SUFFIX,
    PollingTableStager,
)
from .serialization import (
    Serializer,
    array_nbytes,
)
from . import telemetry
from .utils import knobs
from .utils.lru import BoundedLRU

logger = logging.getLogger(__name__)


def _collect_array_entries(entries: List[Entry]) -> Dict[str, ArrayEntry]:
    """location -> ArrayEntry for every array entry, incl. nested ones."""
    out: Dict[str, ArrayEntry] = {}
    for entry in entries:
        if isinstance(entry, ArrayEntry):
            out[entry.location] = entry
        elif isinstance(entry, ChunkedArrayEntry):
            for chunk in entry.chunks:
                out[chunk.tensor.location] = chunk.tensor
        elif isinstance(entry, ShardedArrayEntry):
            for shard in entry.shards:
                out[shard.tensor.location] = shard.tensor
    return out


class CompressedSlabStager(BufferStager):
    """Compresses a packed raw slab with ONE FRAME PER MEMBER at staging
    time (on the drain for all-deferred device slabs — never inside
    async_take's stall), publishing the per-frame compressed sizes for the
    companion :class:`SlabFrameTableStager`.

    This is what lets small compressed entries keep BOTH batching wins:
    compressed member sizes don't exist at planning time (when slab offsets
    and the manifest are fixed), so the manifest speaks raw coordinates
    (``ArrayEntry.raw_range``) and the raw→compressed mapping travels in
    the slab's ``.ftab`` side object. Round 3 instead compressed eagerly at
    plan time (host members only, serially, inside the stall) and left
    deferred device members unbatched entirely."""

    def __init__(
        self,
        inner: "BatchedBufferStager",
        member_sizes: List[int],
        serializer: str,
        level: int,
    ) -> None:
        self.inner = inner
        self.member_sizes = member_sizes
        self.serializer = serializer
        self.level = level
        self.frame_sizes: Optional[List[int]] = None
        self.frame_error: Optional[BaseException] = None
        # frame_sizes is published from an executor thread (work()) and
        # cleared loop-side between takes (reset_take, prepared cache);
        # the pipeline serializes the two in time, the lock makes the
        # cross-thread hand-off well-defined.
        self._frame_lock = threading.Lock()

    def reset_take(self) -> None:
        """Clear per-take frame publication so a cached prepared state can
        re-stage this slab for a new step (the member stagers were rebound
        by the prepared cache; offsets/sizes are structural and keep)."""
        with self._frame_lock:
            self.frame_sizes = None
            self.frame_error = None

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        from . import d2h
        from .serialization import compress_member_framed

        # Captured here, not inside work(): executor threads don't inherit
        # the pipeline's StagingContext contextvar.
        ctx = d2h.get_active()
        times = ctx.times if ctx is not None else None
        try:
            raw = await self.inner.stage_buffer(executor)

            def work() -> bytes:
                with d2h.timed(times, "serialize") as compressing:
                    payload, sizes = compress_member_framed(
                        raw, self.member_sizes, self.serializer, self.level
                    )
                    compressing.sized(len(payload))
                with self._frame_lock:
                    self.frame_sizes = sizes
                return payload

            if executor is not None:
                loop = asyncio.get_running_loop()
                return await loop.run_in_executor(executor, work)
            return work()
        except BaseException as e:  # noqa: BLE001 - published, then re-raised
            self.frame_error = e
            raise

    def get_staging_cost_bytes(self) -> int:
        # Raw slab + compressed output coexist during compression.
        return 2 * self.inner.get_staging_cost_bytes()


class SlabFrameTableStager(PollingTableStager):
    """A compressed slab's ``.ftab``: per-frame raw AND compressed sizes
    (frames are member-aligned, so both are needed to map a member's
    ``raw_range`` to its compressed byte range)."""

    def __init__(self, main: CompressedSlabStager, path: str) -> None:
        super().__init__(main, described=f"slab {path}")

    def _table(self) -> dict:
        return {
            "member_framed": True,
            "raw_sizes": self.main.member_sizes,
            "sizes": self.main.frame_sizes,
        }


class BatchedBufferStager(BufferStager):
    """Stages all members of one slab and concatenates their bytes."""

    def __init__(self, members: List[Tuple[WriteReq, int, int]]) -> None:
        # (orig write req, begin offset, end offset) — offsets precomputed.
        self.members = members
        self.total = members[-1][2] if members else 0

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        telemetry.counter_add("batcher.slabs_host_packed")
        slab = bytearray(self.total)

        async def stage_one(req: WriteReq, begin: int, end: int) -> None:
            buf = await req.buffer_stager.stage_buffer(executor)
            mv = memoryview(buf)
            if mv.nbytes != end - begin:
                raise RuntimeError(
                    f"Staged size {mv.nbytes} != planned slab slot "
                    f"{end - begin} for {req.path}"
                )
            slab[begin:end] = mv

        await asyncio.gather(*(stage_one(*m) for m in self.members))
        return slab

    def get_staging_cost_bytes(self) -> int:
        return self.total


class DeviceBatchedBufferStager(BatchedBufferStager):
    """Packs member device arrays into ONE on-device uint8 slab, fetched with
    a single D2H transfer.

    Analogue of the reference's ``GPUBatchedBufferStager``
    (``batcher.py:102-157``), which packs CUDA source tensors into one device
    buffer for a single copy and falls back on OOM. The TPU-native packing is
    a jitted bitcast-to-bytes + concatenate: per-transfer overhead (latency,
    descriptor setup) is paid once per slab instead of once per member —
    exactly the regime slab batching targets (thousands of small params).
    Only a device allocation failure (the pack needs a slab's worth of HBM)
    falls back to the host-side per-member packing inherited from
    :class:`BatchedBufferStager`; anything else — a refused compile, a
    byte-length mismatch — is a bug on this backend and raises. Which path
    each slab took is counted: ``batcher.slabs_device_packed``, or
    ``batcher.slabs_host_packed`` (every slab packed on the host, by plan
    or by degradation) of which ``batcher.slabs_pack_degraded`` were
    planned for the device.
    """

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        import numpy as np

        from .io_preparers.array import _traced_to_host

        arrs = tuple(req.buffer_stager.arr for req, _, _ in self.members)
        key = _pack_key(arrs)
        with _PACK_LOCK:
            failed_at = _PACK_FAILED.get(key)
            if failed_at is not None and (
                time.monotonic() - failed_at >= _PACK_RETRY_COOLDOWN_S
            ):
                # Cooldown elapsed: HBM pressure is transient and deserves
                # another chance.
                _PACK_FAILED.pop(key, None)
                failed_at = None
        if failed_at is not None:
            # This signature hit HBM pressure recently; don't pay a failed
            # allocation plus a full-traceback warning on every take.
            telemetry.counter_add("batcher.slabs_pack_degraded")
            return await super().stage_buffer(executor)
        try:
            packed = _pack_to_device_bytes(key, arrs)
            # _traced_to_host wraps the async-hint-then-resolve pattern (plus
            # a d2h telemetry span when tracing); an asynchronous HBM OOM
            # from the pack's allocation surfaces at the resolve.
            host = await _traced_to_host(
                packed, executor, self.members[0][0].path, self.total
            )
        except Exception as e:
            if not is_oom_error(e):
                raise
            with _PACK_LOCK:
                if len(_PACK_FAILED) >= _PACK_FAILED_CAP:
                    # Evict oldest (insertion order) rather than refusing
                    # the insert: a refusing cap would defeat the cooldown
                    # and re-warn on every take once full.
                    _PACK_FAILED.pop(next(iter(_PACK_FAILED)), None)
                _PACK_FAILED[key] = time.monotonic()
            logger.warning(
                "On-device slab packing hit HBM pressure; falling back to "
                "host-side packing for %d members (device path for this "
                "slab signature paused for %.0f s)",
                len(self.members),
                _PACK_RETRY_COOLDOWN_S,
                exc_info=True,
            )
            telemetry.counter_add("batcher.slabs_pack_degraded")
            return await super().stage_buffer(executor)
        if host.nbytes != self.total:
            raise RuntimeError(
                f"Device-packed slab is {host.nbytes} bytes, "
                f"planned {self.total}"
            )
        telemetry.counter_add("batcher.slabs_device_packed")
        return np.ascontiguousarray(host)


# Dtypes an on-device packed slab can carry: byte-width dtypes whose jitted
# bitcast-to-uint8 byte stream equals the host array's raw little-endian
# bytes. Sub-byte dtypes (int4/uint4/float4) are excluded — numpy stores
# them unpacked one-per-byte, and an 8→4-bit bitcast would mis-size the
# slab. Sub-32-bit floats (bfloat16, float16, float8) are excluded too: the
# pack program flushes their denormals and rewrites their NaN payloads
# (``device_programs.slice_preserves_bits``), so their slabs are packed
# on the host from per-member transfers. bool packs via astype (same 0/1
# byte representation). Complex bitcasts are unsupported by XLA.
_DEVICE_PACKABLE_DTYPES = frozenset(
    {
        "bool",
        "int8",
        "int16",
        "int32",
        "int64",
        "uint8",
        "uint16",
        "uint32",
        "uint64",
        "float32",
        "float64",
    }
)


def _device_batchable(req: WriteReq) -> bool:
    """True when a member can join an on-device packed slab."""
    from .io_preparers.array import ArrayBufferStager, is_jax_array

    stager = req.buffer_stager
    if not isinstance(stager, ArrayBufferStager) or not is_jax_array(stager.arr):
        return False
    arr = stager.arr
    # Fully-addressable only: packing is an independent local computation, so
    # it stays legal from the async-commit background thread (no SPMD
    # program-order requirement across processes).
    if not getattr(arr, "is_fully_addressable", False):
        return False
    import numpy as np

    return np.dtype(arr.dtype).name in _DEVICE_PACKABLE_DTYPES


def _pack_key(arrs) -> tuple:
    return tuple(
        (str(a.dtype), a.shape, device_assignment_key(a.sharding)) for a in arrs
    )


def _pack_to_device_bytes(key, arrs):
    """Jitted concat of each array's raw little-endian bytes (C order)."""

    def build():
        import jax
        import jax.numpy as jnp
        from jax import lax

        def pack(xs):
            parts = []
            for x in xs:
                if x.dtype == jnp.bool_:
                    b = x.astype(jnp.uint8)
                else:
                    # bitcast to uint8 appends a trailing axis of itemsize
                    # (none for 1-byte dtypes); C-order flatten of
                    # (element, byte-within-element) is the array's raw
                    # little-endian byte stream.
                    b = lax.bitcast_convert_type(x, jnp.uint8)
                parts.append(b.reshape(-1))
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

        return jax.jit(pack)

    # Lock held across build(): it only constructs the jit wrapper (no
    # trace/compile — that happens at the call below, outside the lock), and
    # admitting concurrent builders would double-compile the pack fn.
    with _PACK_LOCK:
        fn = _PACK_FNS.get_or_build(key, build)
    return fn(arrs)


# One key per slab (not per state structure): a checkpoint with N small-param
# slabs touches N keys per take in a fixed order, so the capacity must
# comfortably exceed any realistic slab count — at the 128 MB threshold, 256
# slabs ≈ 32 GB of small params. A sequential scan over more keys than
# capacity is the LRU worst case (0% hits, full recompile every take).
_PACK_FNS = BoundedLRU(capacity=256)

# Guards _PACK_FNS and _PACK_FAILED: a sync take's loop thread and an async
# take's background drain can run these pipelines concurrently, and neither
# BoundedLRU nor the dict's check-then-mutate sequences are atomic.
_PACK_LOCK = threading.Lock()

# key -> monotonic time of the last device-path allocation failure. Such
# signatures skip straight to host packing until the cooldown elapses (HBM
# pressure is transient). Capped so pathological signature churn can't grow
# it forever (beyond the cap, new failures just retry+warn).
_PACK_FAILED: dict = {}
_PACK_FAILED_CAP = 1024
_PACK_RETRY_COOLDOWN_S = 600.0


def batch_write_requests(
    entries: List[Entry], write_reqs: List[WriteReq]
) -> Tuple[List[Entry], List[WriteReq]]:
    """Coalesce small raw-array writes into slabs.

    Mutates the affected :class:`ArrayEntry` objects in place (new
    ``location`` + ``byte_range``), which is safe because it runs before the
    manifest is gathered/serialized.
    """
    from .io_preparers.array import ArrayBufferStager

    threshold = knobs.get_slab_size_threshold_bytes()
    by_location = _collect_array_entries(entries)
    # Sharded sub-entries never join COMPRESSED slabs: the sharded read path
    # (overlap scatter, budgeted pieces) speaks file byte ranges, not the
    # raw slab coordinates member-framing uses. They still join RAW slabs.
    shard_locations = {
        shard.tensor.location
        for entry in entries
        if isinstance(entry, ShardedArrayEntry)
        for shard in entry.shards
    }

    small: List[Tuple[WriteReq, ArrayEntry, int]] = []
    small_compressed: List[Tuple[WriteReq, ArrayEntry, int]] = []
    passthrough: List[WriteReq] = []
    for req in write_reqs:
        entry = by_location.get(req.path)
        if entry is None:
            passthrough.append(req)
            continue
        nbytes = array_nbytes(entry.shape, entry.dtype)
        if (
            entry.serializer in (Serializer.RAW_ZSTD, Serializer.RAW_ZLIB)
            and entry.frame_bytes is None  # framed entries are big; unbatched
            and nbytes < threshold
            and isinstance(req.buffer_stager, ArrayBufferStager)
            and req.path not in shard_locations
        ):
            small_compressed.append((req, entry, nbytes))
            continue
        if entry.serializer != Serializer.RAW:
            passthrough.append(req)
            continue
        if nbytes >= threshold:
            passthrough.append(req)
        else:
            small.append((req, entry, nbytes))

    if len(small) + len(small_compressed) <= 1:
        return entries, write_reqs

    batched_reqs: List[WriteReq] = []

    def pack(
        members: List[Tuple[WriteReq, ArrayEntry, int]], compressed: bool
    ) -> None:
        if len(members) <= 1:
            passthrough.extend(req for req, _, _ in members)
            return
        # Deterministic packing order; deferred (device) members group
        # together so their slabs stay all-deferred — one mutable host
        # member would otherwise drag a whole slab's D2H into the capture
        # point. Likewise members the device pack cannot carry bit for bit
        # (sub-32-bit floats, complex): one of them would send its whole
        # slab through the host pack. Slabs close at a group boundary and at
        # the threshold (raw sizes either way: slab offsets must be known at
        # planning time, and compressed sizes aren't — that is the whole
        # reason member-framing exists).
        device_pack = knobs.is_device_batching_enabled()

        def group(req: WriteReq) -> Tuple[int, int]:
            return (
                0 if req.defer_staging else 1,
                0 if device_pack and _device_batchable(req) else 1,
            )

        members = sorted(members, key=lambda t: (group(t[0]), t[0].path))
        slab: List[Tuple[WriteReq, int, int]] = []
        slab_entries: List[ArrayEntry] = []
        offset = 0

        def close_slab() -> None:
            nonlocal slab, slab_entries, offset
            if not slab:
                return
            if len(slab) == 1:
                # A 1-member slab is strictly worse than the plain object
                # (extra indirection, and a .ftab side object when
                # compressed): pass the member through untouched.
                passthrough.append(slab[0][0])
                slab, slab_entries, offset = [], [], 0
                return
            slab_path = f"batched/{uuid.uuid4().hex}"
            for (req, begin, end), entry in zip(slab, slab_entries):
                entry.location = slab_path
                if compressed:
                    entry.raw_range = [begin, end]
                else:
                    entry.byte_range = [begin, end]
            stager: BufferStager
            if (
                knobs.is_device_batching_enabled()
                and all(_device_batchable(req) for req, _, _ in slab)
                and len(
                    {device_assignment_key(req.buffer_stager.arr.sharding) for req, _, _ in slab}
                )
                == 1
            ):
                stager = DeviceBatchedBufferStager(slab)
            else:
                stager = BatchedBufferStager(slab)
            # Deferring past async_take's return is only safe when every
            # member is (immutable device data); one mutable host member
            # forces the whole slab to stage at the capture point.
            defer = all(req.defer_staging for req, _, _ in slab)
            if compressed:
                first = slab[0][0].buffer_stager
                for req, _, _ in slab:
                    # Members stage RAW into the packed slab; compression
                    # happens once at the slab level below.
                    req.buffer_stager.stage_raw = True
                stager = CompressedSlabStager(
                    stager,
                    member_sizes=[end - begin for _, begin, end in slab],
                    serializer=slab_entries[0].serializer,
                    level=first.compression_level,
                )
                batched_reqs.append(
                    WriteReq(
                        path=slab_path, buffer_stager=stager, defer_staging=defer
                    )
                )
                batched_reqs.append(
                    WriteReq(
                        path=slab_path + _FRAME_TABLE_SUFFIX,
                        buffer_stager=SlabFrameTableStager(stager, slab_path),
                        defer_staging=defer,
                    )
                )
            else:
                batched_reqs.append(
                    WriteReq(
                        path=slab_path, buffer_stager=stager, defer_staging=defer
                    )
                )
            slab, slab_entries, offset = [], [], 0

        for req, entry, nbytes in members:
            if slab and (
                offset + nbytes > threshold or group(slab[0][0]) != group(req)
            ):
                close_slab()
            slab.append((req, offset, offset + nbytes))
            slab_entries.append(entry)
            offset += nbytes
        close_slab()

    pack(small, compressed=False)
    # Per-serializer compressed groups: one codec per slab/frame table.
    for serializer in (Serializer.RAW_ZSTD, Serializer.RAW_ZLIB):
        pack(
            [m for m in small_compressed if m[1].serializer == serializer],
            compressed=True,
        )

    # Plan metrics: how much the batcher coalesced. Every original request
    # not in the final passthrough joined a slab; the slab count excludes
    # .ftab side objects so the ratio is members-per-slab, not per-write.
    slabs = len(
        {
            r.path
            for r in batched_reqs
            if not r.path.endswith(_FRAME_TABLE_SUFFIX)
        }
    )
    coalesced = len(write_reqs) - len(passthrough)
    telemetry.counter_add("batcher.write_members", coalesced)
    telemetry.counter_add("batcher.write_slabs", slabs)
    if slabs:
        telemetry.gauge_set("batcher.write_coalescing_ratio", coalesced / slabs)

    return entries, passthrough + batched_reqs


# ---------------------------------------------------------------------------
# Read-side: merge adjacent ranged reads of the same object
# ---------------------------------------------------------------------------

class BatchedBufferConsumer(BufferConsumer):
    """Fans one merged buffer out to the member consumers by sub-range."""

    def __init__(self, members: List[Tuple[ReadReq, int, int]]) -> None:
        self.members = members  # (orig req, begin-in-buffer, end-in-buffer)

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        mv = memoryview(buf)
        await asyncio.gather(
            *(
                req.buffer_consumer.consume_buffer(mv[begin:end], executor)
                for req, begin, end in self.members
            )
        )

    def get_consuming_cost_bytes(self) -> int:
        return sum(
            req.buffer_consumer.get_consuming_cost_bytes()
            for req, _, _ in self.members
        )


def batch_read_requests(
    read_reqs: List[ReadReq],
    max_merged_bytes: Optional[int] = None,
    merge_gap_bytes: Optional[int] = None,
) -> List[ReadReq]:
    """Merge adjacent byte-range reads per object into single reads.

    ``max_merged_bytes`` caps each merged run so budget-capped sub-reads
    (``buffer_size_limit_bytes``) are never coalesced back into the
    whole-object read they were split to avoid; a single request larger
    than the cap still passes through whole (the usual one-over-budget
    escape hatch).

    ``merge_gap_bytes`` (default: the READ_MERGE_GAP_BYTES knob, 0) also
    coalesces *near*-adjacent ranges whose gap is at most this many bytes:
    lazy partial restores of slab-batched subtrees ask for interleaved
    member ranges, and on high-latency backends fetching (and discarding) a
    small gap beats an extra round trip. Gap bytes are read but never
    delivered — each member consumer still sees exactly its own range.
    """
    if merge_gap_bytes is None:
        merge_gap_bytes = knobs.get_read_merge_gap_bytes()
    ranged: Dict[str, List[ReadReq]] = {}
    passthrough: List[ReadReq] = []
    for req in read_reqs:
        if req.byte_range is None or getattr(
            req.buffer_consumer, "merge_exempt", False
        ):
            # Framed sub-reads are already budget-sized in RAW terms; their
            # COMPRESSED ranges are exactly adjacent, so merging them by the
            # compressed-span cap would coalesce up to compression-ratio
            # many groups and decode far more raw bytes than the budget —
            # the whole-object RSS spike framing exists to prevent.
            # (Attribute, not isinstance: wrappers proxy it.)
            passthrough.append(req)
        else:
            ranged.setdefault(req.path, []).append(req)

    out: List[ReadReq] = list(passthrough)
    for path, reqs in ranged.items():
        reqs.sort(key=lambda r: r.byte_range[0])
        run: List[ReadReq] = []

        def close_run() -> None:
            if not run:
                return
            if len(run) == 1:
                out.append(run[0])
                return
            begin = run[0].byte_range[0]
            end = run[-1].byte_range[1]
            members = [
                (r, r.byte_range[0] - begin, r.byte_range[1] - begin) for r in run
            ]
            out.append(
                ReadReq(
                    path=path,
                    buffer_consumer=BatchedBufferConsumer(members),
                    byte_range=(begin, end),
                )
            )

        for req in reqs:
            if run and (
                req.byte_range[0] - run[-1].byte_range[1] > merge_gap_bytes
                or req.byte_range[0] < run[-1].byte_range[1]
                or (
                    max_merged_bytes is not None
                    and req.byte_range[1] - run[0].byte_range[0] > max_merged_bytes
                )
            ):
                close_run()
                run = []
            run.append(req)
        close_run()
    # Plan metrics: merged-away reads per merge pass (requests in minus
    # requests out = storage round-trips the merge saved).
    telemetry.counter_add("batcher.read_reqs_in", len(read_reqs))
    telemetry.counter_add("batcher.read_reqs_merged", len(read_reqs) - len(out))
    return out
