"""Core contracts tying planning to execution to storage.

TPU-native analogue of the reference's ``io_types.py`` (see
``/root/reference/torchsnapshot/io_types.py:19-103``): the planning layer turns
application state into :class:`WriteReq`/:class:`ReadReq` lists, the scheduler
executes them against a :class:`StoragePlugin`, and buffers flow through the
:class:`BufferStager`/:class:`BufferConsumer` protocols so that device-to-host
transfer, serialization, and storage I/O can be pipelined without ever
materializing more than a memory budget's worth of data.
"""

from __future__ import annotations

import abc
import asyncio
import io
import threading
from concurrent.futures import Executor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

# A staged buffer is either raw bytes or a zero-copy view over host memory.
BufferType = Union[bytes, bytearray, memoryview]

# Below this a leaf, a transfer or a storage write counts as small: the
# ``take.small_*`` counters and the ``*_small_s`` drain stats say what a
# state of many sizes pays in per-object fixed costs.
SMALL_OBJECT_BYTES = 1 << 20


class BufferStager(abc.ABC):
    """Produces the bytes for one write request, as lazily as possible.

    ``stage_buffer`` performs the expensive part (device-to-host transfer +
    serialization). It is invoked by the scheduler only when the memory budget
    admits the request, and runs its blocking portions on ``executor``.
    """

    @abc.abstractmethod
    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        ...

    @abc.abstractmethod
    def get_staging_cost_bytes(self) -> int:
        """Estimated peak host memory consumed by :meth:`stage_buffer`."""
        ...

    def release_staged(self) -> None:
        """The staged buffer's last consumer is done with it (hash and
        storage write finished, or the request failed or was cancelled):
        a stager whose buffer is lent memory gives it back. Called by the
        write pipeline, at most once effective a take."""


@dataclass
class WriteReq:
    path: str
    buffer_stager: BufferStager
    # Async snapshots may defer this request's staging past async_take's
    # return (device arrays: immutable + defensively forked, so nothing can
    # invalidate them). Mutable host state leaves this False and is staged
    # before async_take returns, under the memory budget — the reference's
    # capture semantics (``scheduler.py:178-214``).
    defer_staging: bool = False


class BufferConsumer(abc.ABC):
    """Consumes the bytes of one read request (deserialize + copy into place)."""

    @abc.abstractmethod
    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        ...

    @abc.abstractmethod
    def get_consuming_cost_bytes(self) -> int:
        """Estimated peak host memory consumed by :meth:`consume_buffer`."""
        ...

    def destination(self) -> Optional[memoryview]:
        """Where the request's read may land, or None (the default).

        A consumer that would only copy its buffer, byte for byte, into one
        contiguous host buffer the restore itself allocated answers a
        writable flat byte view of exactly the bytes the read will deliver
        (``ReadIO.into``). A plugin may fill it in place of a buffer of its
        own; ``consume_buffer`` tells from the buffer it is handed whether
        one did. Never a caller's live array: that goes on being overwritten
        only by bytes that were fetched whole and, where verification is on,
        verified."""
        return None

    async def acquire_target(self) -> None:
        """Awaited by the read pipeline once the request is admitted, before
        its fetch (and so before :meth:`destination` is asked): a consumer
        whose destination has no memory yet gets it here, and may wait for
        it. The default has nothing to get."""


def destination_of(consumer: object) -> Optional[memoryview]:
    """``consumer.destination()``; None for a consumer that only quacks like
    a :class:`BufferConsumer` and predates the method."""
    offer = getattr(consumer, "destination", None)
    return offer() if offer is not None else None


async def acquire_target_of(consumer: object) -> None:
    """``await consumer.acquire_target()``, likewise."""
    acquire = getattr(consumer, "acquire_target", None)
    if acquire is not None:
        await acquire()


@dataclass
class ReadReq:
    path: str
    buffer_consumer: BufferConsumer
    byte_range: Optional[Tuple[int, int]] = None  # [begin, end)


class WriteTimes:
    """What a take's native writes did, told by the plugin that made them
    (``WriteIO.times``): a thread-safe sink beside the io stream, as
    ``RestoreTimes`` is for a restore's reads. Every interval is on
    ``time.monotonic()``'s clock, as ``(t0, t1, nbytes)``."""

    KINDS = (
        "write_queue", "write_work", "write_copy", "mount_write", "write_crc",
        "bounce_warm", "bounce_fresh",
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._intervals: Dict[str, List[Tuple[float, float, int]]] = {
            k: [] for k in self.KINDS
        }

    def record_native_write(
        self,
        handed: float,
        held: float,
        done: float,
        nbytes: int,
        chunks: List[Tuple[float, float, float, float, float, float, float]],
    ) -> None:
        """One write of the fs plugin through the native engine, stamped on
        the writing thread: ``handed`` to the plugin's executor, ``held``
        when its thread held a writer slot and had opened its
        ``storage.write_work`` span (``write_queue`` before it,
        ``write_work`` after it), ``done`` when the engine call had
        returned, the GIL taken again; ``chunks`` the engine's own stamps
        (``native.WriteChunk``): each chunk's copy into the bounce buffer the
        engine lent the write (warm from the write before, not allocated for
        the object), its ``pwrite`` (``mount_write``, with the bytes it took)
        and its crc; ``bounce_warm`` / ``bounce_fresh`` are the ``pwrite``'s
        interval again with the bytes of it that were copied into pages of the
        buffer an earlier copy had written, and into pages none had (they sum
        to ``mount_write``'s where the mount takes ``O_DIRECT``).
        No span of their own: ``storage.write_work`` stays one an object."""
        with self._lock:
            w = self._intervals
            w["write_queue"].append((handed, held, nbytes))
            w["write_work"].append((held, done, nbytes))
            for t_copy, t_mount, t_crc, t_end, taken, warm, fresh in chunks:
                w["write_copy"].append((t_copy, t_mount, 0))
                w["mount_write"].append((t_mount, t_crc, int(taken)))
                w["write_crc"].append((t_crc, t_end, 0))
                w["bounce_warm"].append((t_mount, t_crc, int(warm)))
                w["bounce_fresh"].append((t_mount, t_crc, int(fresh)))

    def intervals(self) -> Dict[str, List[Tuple[float, float, int]]]:
        """A snapshot copy per kind (safe to merge and clip while writes run)."""
        with self._lock:
            return {k: list(v) for k, v in self._intervals.items()}


@dataclass
class WriteIO:
    path: str
    buf: BufferType
    # want_digest: set by the caller when it will consume digest_out —
    # plugins that can compute the object digest inside their write path
    # ([crc32, size, sha256-hex | None], the sidecar format) then fill
    # digest_out; the native FS engine hashes chunk-by-chunk while the data
    # is cache-hot, sparing the scheduler's Python hashing pass its full
    # extra memory sweep. Writes whose caller hashes elsewhere (incremental
    # takes pre-hash for dedup; sidecar files) leave want_digest False so
    # no plugin wastes a pass. digest_out None = not computed.
    want_digest: bool = False
    digest_out: Optional[list] = None
    # times: the take's sink for what a native write did with its chunks. A
    # plugin whose engine can stamp them does so only when handed one; any
    # other write (a sidecar, the catalog, bare plugin use) stamps nothing.
    times: Optional[WriteTimes] = None


class ReadBuffer:
    """The bytes of one storage read, held by reference.

    What a plugin's ``read`` hands to ``write`` IS what ``getbuffer`` views:
    the array a native read filled, the ``bytes`` a client library
    returned. No pass over the bytes lies between the backend's delivery and
    the consumer. The object must belong to the read or be immutable: a
    ``bytes`` a plugin also keeps (the memory plugin's store, a cache's
    shared fetch) is fine, a ``bytearray`` it goes on writing to is not.
    A read that landed (``ReadIO.into``) holds a view of its consumer's own
    host target: that memory belongs to the restore's leaf, the read only
    borrows it, and a retried attempt overwrites it from its start.

    It answers the part of the reference's ``io.BytesIO`` contract the read
    path uses: ``write``, ``seek(0)`` + ``truncate(0)`` (the reset before a
    retried attempt, which drops the failed attempt's bytes), ``getbuffer``
    and ``getvalue``. A plugin written against ``BytesIO`` that delivers one
    read in several writes still gets their concatenation; that join is a
    copy, and ``copied_bytes`` counts it (the restore's
    ``fetch_copied_bytes``).
    """

    __slots__ = ("_data", "_joined", "copied_bytes")

    def __init__(self) -> None:
        # bytes, bytearray or a flat byte view: ``len`` is its size.
        self._data: BufferType = b""
        self._joined = False  # _data is a bytearray this holder made
        self.copied_bytes = 0

    def write(self, data: BufferType) -> int:
        view = memoryview(data)
        nbytes = view.nbytes
        if not nbytes:
            return 0
        if not len(self._data):
            # Kept as it came; any other exporter as its flat byte view.
            self._data = (
                data if isinstance(data, (bytes, bytearray)) else view.cast("B")
            )
            return nbytes
        if not self._joined:
            self.copied_bytes += len(self._data)
            self._data = bytearray(self._data)
            self._joined = True
        self._data += view
        self.copied_bytes += nbytes
        return nbytes

    def seek(self, pos: int) -> int:
        if pos != 0:
            raise io.UnsupportedOperation("a ReadBuffer seeks to 0 only")
        return 0

    def truncate(self, size: int) -> int:
        if size != 0:
            raise io.UnsupportedOperation("a ReadBuffer truncates to 0 only")
        self._data = b""
        self._joined = False
        return 0

    def getbuffer(self) -> memoryview:
        """A view of the held object itself (read-only over ``bytes``)."""
        return memoryview(self._data)

    def getvalue(self) -> bytes:
        """The bytes as ``bytes``: the held object where it is one, else a
        copy (its callers read metadata, sidecars and frame tables)."""
        return bytes(self._data)


@dataclass
class ReadIO:
    """One storage read: what to fetch, and the buffer it is delivered in.

    ``into`` is an offer, not an order (``BufferConsumer.destination``):
    writable memory of the read's exact size that the consumer owns and the
    read only borrows. A plugin that fills it hands ``buf`` a view of it;
    one that ignores it delivers as ever, and the consumer copies. Wrappers
    pass the ReadIO on untouched."""

    path: str
    byte_range: Optional[Tuple[int, int]] = None
    buf: ReadBuffer = field(default_factory=ReadBuffer)
    into: Optional[memoryview] = None


class StoragePlugin(abc.ABC):
    """Async storage backend contract (reference ``io_types.py:67-103``).

    Implementations must be safe for many concurrent in-flight operations on
    one event loop. Ranged reads (``ReadIO.byte_range``) enable random access
    into cloud-resident snapshots without fetching whole objects.

    **Absence contract**: ``read`` of an object that does not exist raises
    :class:`FileNotFoundError` — each plugin normalizes its backend's absence
    error (ENOENT, GCS ``NotFound``, S3 ``NoSuchKey``) so callers never sniff
    backend-specific exception names or messages. ``delete`` of an absent
    object either succeeds silently (idempotent backends like S3) or raises
    :class:`FileNotFoundError`; it never raises a backend-specific absence
    error.
    """

    # Local-disk backends set this True so the scheduler's default IO
    # concurrency divides across co-hosted ranks (they share one device);
    # network/object stores keep the full default (latency-hiding
    # concurrency, not seek-bound).
    scales_io_with_local_world = False

    @abc.abstractmethod
    async def write(self, write_io: WriteIO) -> None:
        ...

    @abc.abstractmethod
    async def read(self, read_io: ReadIO) -> None:
        ...

    @abc.abstractmethod
    async def delete(self, path: str) -> None:
        ...

    async def link_in(self, src_abs_path: str, path: str) -> bool:
        """Optionally alias an existing file at absolute ``src_abs_path``
        into this store at ``path`` without copying bytes (incremental
        snapshots). Returns False when unsupported or failed — the caller
        falls back to a normal write. Default: unsupported."""
        return False

    async def list_prefix(self, prefix: str) -> List[str]:
        """All object paths under ``prefix`` (relative to the plugin root,
        ``""`` = everything). The substrate of ``Snapshot.gc``: debris from
        torn takes can only be reclaimed on backends that can enumerate it.
        Built-in plugins all implement this; third-party plugins that don't
        simply can't be garbage-collected."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support listing; Snapshot.gc "
            "requires a plugin with list_prefix"
        )

    async def prune_empty(self) -> None:
        """Remove now-empty directories after deletions, where the backend
        has real directories (fs). Object stores have none: default no-op."""

    async def close(self) -> None:
        pass

    # -- sync conveniences driving a caller-owned event loop -----------------
    def sync_write(
        self, write_io: WriteIO, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.write(write_io), event_loop)

    def sync_read(
        self, read_io: ReadIO, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.read(read_io), event_loop)

    def sync_close(
        self, event_loop: Optional[asyncio.AbstractEventLoop] = None
    ) -> None:
        _run(self.close(), event_loop)


def _run(coro, event_loop: Optional[asyncio.AbstractEventLoop]) -> None:
    if event_loop is not None:
        event_loop.run_until_complete(coro)
    else:
        asyncio.new_event_loop().run_until_complete(coro)
