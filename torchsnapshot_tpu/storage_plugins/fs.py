"""Local/network filesystem storage plugin.

Analogue of the reference's ``storage_plugins/fs.py:19-54`` (async file I/O
with a parent-directory creation cache and ranged reads via seek), with one
TPU-VM-specific addition: large transfers route through the native O_DIRECT
engine (``torchsnapshot_tpu/native``), past the page cache; small objects
(manifests, primitives) keep the simple buffered path. The rates on record
are those of the chip machine's 9p mount (``PERF.md``; ``native/tss_io.cpp``
repeats them).

Concurrency: the event loop may have many plugin ops in flight; blocking work
runs on a private thread pool. Writes: a semaphore caps concurrent native
writes at ``knobs.get_direct_io_concurrency()`` objects, and each copies its
chunks into a bounce buffer the engine lends it and keeps between writes (as
many buffers as writes were ever in flight at once, of
``knobs.get_direct_io_chunk_bytes()`` each, at most 256 MiB kept), so the copy
lands in pages an earlier write touched, not in ones new to every object; one
that is handed a ``WriteIO.times`` tells it how long it waited for its slot
and what the engine did with each chunk (copy, ``pwrite``, crc, and how many
of the bytes were copied into warm pages). Reads: no semaphore
around an object. A native read is chunk reads of ``_READ_CHUNK_BYTES`` on
the engine's reader pool, and the cap, ``knobs.get_direct_read_depth()``,
counts chunks on the mount for the whole process: those of one large leaf,
or of as many small shards side by side; a caller's thread only waits, GIL
released, until its object's last chunk has landed.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Set, Union

import aiofiles
import numpy as np

from .. import native, restore_times, telemetry
from ..io_types import ReadIO, StoragePlugin, WriteIO
from ..utils import knobs
from .cloud_retry import (
    TRANSIENT_OS_ERRNOS,
    CollectiveProgress,
    is_transient_os_error,
    retry_transient,
)

# The grain of a native read: one object is this many bytes a chunk read,
# ``knobs.get_direct_read_depth()`` of them on the mount at once (probe of
# PR 29, PERF.md section 6).
_READ_CHUNK_BYTES = 4 << 20

# The transient-errno classification lives in cloud_retry
# (TRANSIENT_OS_ERRNOS) so the scheduler's read-pipeline retry and this
# plugin can never disagree; these aliases keep the plugin's historical
# names importable.
_TRANSIENT_ERRNOS = TRANSIENT_OS_ERRNOS
_is_transient_oserror = is_transient_os_error


class FSStoragePlugin(StoragePlugin):
    scales_io_with_local_world = True  # co-hosted ranks share this disk

    def __init__(self, root: str) -> None:
        self.root = root
        self._dir_cache: Set[str] = set()
        self._executor: Optional[ThreadPoolExecutor] = None
        # threading (not asyncio) semaphore: held inside executor threads, so
        # it works no matter which event loop drives the plugin. Created
        # lazily: plugins are constructed before the take's coordinator
        # derives the local world size, and the cap must reflect it.
        self._direct_sem: Optional[threading.Semaphore] = None
        self._sem_lock = threading.Lock()
        self._read_depth_set = False
        # Transient local OSErrors (stale NFS handles, timed-out round-trips
        # — see _TRANSIENT_ERRNOS) retry under the same collective-progress
        # policy the cloud plugins use: a network-filesystem hiccup behaves
        # like cloud throttling, not like a permanent failure.
        self._progress = CollectiveProgress()

    @property
    def _native(self):
        # Non-blocking: a cached .so dlopens in milliseconds; a missing one
        # compiles on a daemon thread while writes take the buffered path —
        # the first take() never stalls behind g++.
        return native.load_native_nonblocking()

    def _ensure_parent(self, path: str) -> None:
        dir_path = os.path.dirname(path)
        if dir_path and dir_path not in self._dir_cache:
            os.makedirs(dir_path, exist_ok=True)
            self._dir_cache.add(dir_path)

    def _get_direct_sem(self) -> threading.Semaphore:
        if self._direct_sem is None:
            with self._sem_lock:
                if self._direct_sem is None:
                    self._direct_sem = threading.Semaphore(
                        knobs.get_direct_io_concurrency()
                    )
        return self._direct_sem

    def _set_read_depth(self, lib) -> None:
        """Size the engine's reader pool, once per plugin and lazily, for
        the reason the semaphore is lazy: the depth reflects the local
        world size."""
        if not self._read_depth_set:
            native.set_read_depth(lib, knobs.get_direct_read_depth())
            self._read_depth_set = True

    def _get_executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            # Objects under one chunk reach the read depth only side by
            # side: as many callers may block in the engine as it has
            # reader threads.
            self._executor = ThreadPoolExecutor(
                max_workers=max(
                    4,
                    knobs.get_direct_io_concurrency() + 2,
                    knobs.get_direct_read_depth() + 2,
                ),
                thread_name_prefix="tss-fs",
            )
        return self._executor

    def _use_native(self, nbytes: int) -> bool:
        return (
            self._native is not None
            and nbytes >= knobs.get_direct_io_threshold_bytes()
        )

    def _count_write_path(self, nbytes: int, used_native: bool) -> None:
        """Which write path an object's bytes took, as metrics: a run that
        starts before the engine is built writes its first objects buffered
        and later ones O_DIRECT, and a measurement must be able to tell."""
        if used_native:
            telemetry.counter_add("storage.fs.native_write_bytes", nbytes)
        elif (
            knobs.is_native_io_enabled()
            and nbytes >= knobs.get_direct_io_threshold_bytes()
        ):
            telemetry.counter_add("storage.fs.native_fallback_bytes", nbytes)

    async def write(self, write_io: WriteIO) -> None:
        nbytes = memoryview(write_io.buf).nbytes
        # Resolved once per object so retries, the span and the counters
        # agree on which path it took.
        lib = (
            self._native
            if nbytes >= knobs.get_direct_io_threshold_bytes()
            else None
        )
        with telemetry.span(
            "storage.write",
            cat="storage",
            plugin="fs",
            path=write_io.path,
            nbytes=nbytes,
            engine="native" if lib is not None else "buffered",
        ):
            # Retry-safe: every attempt writes a FRESH temp file and the
            # error path below unlinks it, so a retried write can neither
            # observe nor leave a prior attempt's partial bytes.
            await retry_transient(
                lambda: self._write_inner(write_io, lib),
                _is_transient_oserror,
                self._progress,
                "fs",
            )
        telemetry.counter_add("storage.fs.write_bytes", nbytes)
        self._count_write_path(nbytes, lib is not None)

    async def _write_inner(
        self, write_io: WriteIO, lib, rename: bool = True
    ) -> None:
        path = os.path.join(self.root, write_io.path)
        self._ensure_parent(path)
        # Write-then-rename so a crash mid-write can never leave a truncated
        # object behind — load-bearing for ``.snapshot_metadata``, whose
        # presence IS the commit marker (object stores give this per-PUT).
        tmp_path = f"{path}.tmp.{uuid.uuid4().hex[:8]}"
        try:
            if lib is not None:
                # The crc digest rides the write loop (chunk-hot hashing in
                # C++) when the CALLER asked for it; the scheduler uses
                # digest_out instead of a second full pass over the buffer
                # (and fills the sha256 slot itself if dedup digests are on
                # — hashlib's OpenSSL sha is the fast one).
                want_digest = write_io.want_digest
                nbytes = memoryview(write_io.buf).nbytes
                # A take's write pipeline hands its sink along and the
                # engine then stamps what it does with each chunk; any other
                # write asks for nothing and pays this one check.
                times = write_io.times
                handed = time.monotonic()

                def work() -> None:
                    write = (
                        native.write_file_digest if want_digest else native.write_file
                    )
                    chunks = [] if times is not None else None
                    # On the writing thread: what a profiler trace shows of
                    # this write (the span around it lives across awaits).
                    with self._get_direct_sem(), telemetry.span(
                        "storage.write_work", "storage", True,
                        path=write_io.path, nbytes=nbytes,
                    ):
                        # Stamped next to the span's own ends, with
                        # nothing that can block in between: a reader of
                        # a profiler trace pairs these with the
                        # ``tss.storage.write_work`` events.
                        held = time.monotonic()
                        digest = write(
                            lib,
                            tmp_path,
                            write_io.buf,
                            direct=True,
                            chunk_bytes=knobs.get_direct_io_chunk_bytes(),
                            stamps=chunks,
                        )
                        done = time.monotonic()
                    if want_digest:
                        write_io.digest_out = digest
                    if times is not None:
                        times.record_native_write(handed, held, done, nbytes, chunks)
                        telemetry.counter_add(
                            "storage.fs.bounce_warm_bytes",
                            int(sum(c[5] for c in chunks)),
                        )
                        telemetry.counter_add(
                            "storage.fs.bounce_fresh_bytes",
                            int(sum(c[6] for c in chunks)),
                        )

                await asyncio.get_running_loop().run_in_executor(
                    self._get_executor(), work
                )
            else:
                async with aiofiles.open(tmp_path, "wb") as f:
                    await f.write(write_io.buf)
            if not rename:
                # The injected torn write (``faults.py``): a crash between
                # the temp file's bytes and its rename. The temp file stays
                # as the debris ``Snapshot.gc`` reclaims; no object appears.
                return
            # Rename/cleanup are metadata ops, but on network filesystems
            # (NFS-mounted checkpoint dirs) even those can stall for a
            # round-trip — keep the event loop clean and do them on the
            # plugin's pool alongside the write they finalize.
            await asyncio.get_running_loop().run_in_executor(
                self._get_executor(), os.replace, tmp_path, path
            )
        except BaseException:

            def cleanup() -> None:
                with contextlib.suppress(OSError):
                    os.remove(tmp_path)

            await asyncio.get_running_loop().run_in_executor(
                self._get_executor(), cleanup
            )
            raise

    async def link_in(self, src_abs_path: str, path: str) -> bool:
        """Hard-link ``src_abs_path`` to ``path`` (atomically, via a temp
        name + rename). Fails soft — cross-device links, a deleted base, or
        an exotic filesystem all return False and the caller writes the
        bytes instead. Hard links share the inode, so deleting the base
        snapshot later does NOT invalidate this one."""
        with telemetry.span(
            "storage.link_in", cat="storage", plugin="fs", path=path
        ) as sp:
            ok = self._link_in_inner(src_abs_path, path)
            sp.set_attrs(linked=ok)
        if ok:
            telemetry.counter_add("storage.fs.link_in_count")
        return ok

    def _link_in_inner(self, src_abs_path: str, path: str) -> bool:
        dst = os.path.join(self.root, path)
        tmp = f"{dst}.tmp.{uuid.uuid4().hex[:8]}"
        try:
            # Inside the try: a mkdir failure (permissions, race) must also
            # fail soft — link_in's contract is False-then-fallback, never
            # aborting the take.
            self._ensure_parent(dst)
            os.link(src_abs_path, tmp)
            os.replace(tmp, dst)
            return True
        except OSError:
            with contextlib.suppress(OSError):
                os.remove(tmp)
            return False

    async def read(self, read_io: ReadIO) -> None:
        with telemetry.span(
            "storage.read",
            cat="storage",
            plugin="fs",
            path=read_io.path,
        ) as sp:
            async def attempt() -> None:
                # A retried read must not append to a buffer the failed
                # attempt already partially filled.
                read_io.buf.seek(0)
                read_io.buf.truncate(0)
                await self._read_inner(read_io)

            await retry_transient(
                attempt, _is_transient_oserror, self._progress, "fs"
            )
            nbytes = read_io.buf.getbuffer().nbytes
            sp.set_attrs(nbytes=nbytes)
        telemetry.counter_add("storage.fs.read_bytes", nbytes)

    async def _read_inner(self, read_io: ReadIO) -> None:
        path = os.path.join(self.root, read_io.path)
        if read_io.byte_range is not None:
            offset, end = read_io.byte_range
            nbytes = end - offset
            if self._use_native(nbytes):
                data = await self._native_read(path, offset, nbytes, read_io.into)
            else:
                data = await self._buffered_read(path, offset, nbytes)
        elif self._native is not None:
            # Full-object read: the size probe (needed to route + allocate)
            # runs inside the executor task — never stat() on the event loop.
            data = await self._native_read(path, 0, None, read_io.into)
        else:
            data = await self._buffered_read(path, 0, None)
        # Handed over, not copied: the object the read filled is the one
        # the consumer views (``io_types.ReadBuffer``).
        read_io.buf.write(data)

    async def _buffered_read(
        self, path: str, offset: int, nbytes: Optional[int]
    ) -> bytes:
        async with aiofiles.open(path, "rb") as f:
            if offset:
                await f.seek(offset)
            return await (f.read(nbytes) if nbytes is not None else f.read())

    async def _native_read(
        self,
        path: str,
        offset: int,
        nbytes: Optional[int],
        into: Optional[memoryview] = None,
    ) -> Union[np.ndarray, memoryview]:
        """The engine's chunk reads into ``into``, where the consumer
        offered its own destination and it is the read's size (fetch and
        consume are then one pass and one first touch of those pages), else
        into a fresh array."""
        lib = self._native
        # Taken on the loop side: an executor thread inherits no context.
        times = restore_times.get_active()

        def work() -> Union[np.ndarray, memoryview]:
            self._set_read_depth(lib)
            fail_chunk = -1
            if knobs.get_faults_spec():
                # The chunk reads run BELOW the fault wrapper: this is their
                # only road into chaos schedules (`op=read_chunk`).
                from .. import faults

                fail_chunk = faults.read_chunk_fault(path)
            # On the reading thread, as ``storage.write_work`` on the writing.
            with telemetry.span(
                "storage.read_work", "storage", True, path=path
            ) as sp:
                n = native.file_size(lib, path) - offset if nbytes is None else nbytes
                sp.set_attrs(nbytes=n)
                if into is not None and into.nbytes == n and not into.readonly:
                    # A retried attempt overwrites it from its start.
                    out = into
                else:
                    # Uninitialised: ``bytearray(n)`` would zero-fill it
                    # under the GIL. A failed attempt's array dies with its
                    # exception.
                    out = np.empty(n, dtype=np.uint8)
                chunk_reads = native.read_into(
                    lib,
                    path,
                    out,
                    offset=offset,
                    direct=n >= knobs.get_direct_io_threshold_bytes(),
                    chunk_bytes=_READ_CHUNK_BYTES,
                    stamped=times is not None,
                    fail_chunk=fail_chunk,
                )
            if times is not None:
                times.add_mount_reads(chunk_reads, n)
            return out

        return await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), work
        )

    async def delete(self, path: str) -> None:
        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), os.remove, os.path.join(self.root, path)
        )

    async def list_prefix(self, prefix: str) -> List[str]:
        """All file paths under ``root/prefix``, relative to ``root``
        (including crash debris like ``*.tmp.*`` files — that is the point:
        ``Snapshot.gc`` reclaims what a manifest walk can't see)."""

        def work() -> List[str]:
            base = os.path.join(self.root, prefix) if prefix else self.root
            out: List[str] = []
            if not os.path.isdir(base):
                if os.path.isfile(base):
                    out.append(os.path.relpath(base, self.root))
                return out
            for dirpath, _, filenames in os.walk(base):
                for name in filenames:
                    out.append(
                        os.path.relpath(os.path.join(dirpath, name), self.root)
                    )
            return sorted(out)

        return await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), work
        )

    async def prune_empty(self) -> None:
        """Remove directories left empty by deletions (bottom-up), so a
        gc'd snapshot tree doesn't keep its skeleton of empty dirs. The
        root itself is kept. Invalidates the mkdir cache — a pruned dir
        must be re-creatable by a later write."""

        def work() -> None:
            for dirpath, dirnames, filenames in os.walk(self.root, topdown=False):
                if dirpath == self.root or filenames or dirnames:
                    # os.walk(topdown=False) visits children first, but the
                    # dirnames list was computed before they were pruned —
                    # re-check emptiness on disk.
                    if dirpath != self.root and not os.listdir(dirpath):
                        with contextlib.suppress(OSError):
                            os.rmdir(dirpath)
                    continue
                with contextlib.suppress(OSError):
                    os.rmdir(dirpath)
            self._dir_cache.clear()

        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), work
        )

    async def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None
