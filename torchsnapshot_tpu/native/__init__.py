"""Loader for the native I/O engine (``tss_io.cpp``).

The engine is a single C++ translation unit compiled on first use with the
host toolchain (``g++ -O2 -shared -fPIC -pthread -lz``) and loaded via :mod:`ctypes` —
ctypes releases the GIL for the duration of each call, so bounce-buffer
copies and pwrite/pread syscalls overlap the asyncio event loop without a
C extension module.

The library is keyed by the hash of its source: it lives at
``native/build/libtss_io-<sha256[:16]>.so`` inside the package directory and
nowhere else, so a library built from different source (another checkout, an
older tree copied over this one) can never load, whatever its mtime says.
``native/Makefile`` builds to the same name.

If no compiler is available, compilation fails, or
``TORCHSNAPSHOT_TPU_DISABLE_NATIVE_IO=1`` is set, ``load_native()`` returns
``None`` and the FS storage plugin uses the pure-Python path (counted as
``storage.fs.native_fallback_bytes``). Entry points that measure
(``chip_smoke.py``, ``perfbench/run.py``) load it blocking and fail without it.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "tss_io.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "build")

# _lock guards only the published (_lib, _load_attempted) state and is never
# held across a compile; _build_lock serializes the (multi-second) g++ build
# so nonblocking callers checking state don't queue behind it.
_lock = threading.Lock()
_build_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_bg_build: Optional[threading.Thread] = None


@functools.lru_cache(maxsize=1)
def lib_path() -> str:
    """Where the engine built from the current ``tss_io.cpp`` lives."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libtss_io-{digest}.so")


def _build(out_path: str) -> None:
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    # Build to a temp name then rename so concurrent processes never load a
    # half-written .so.
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(out_path), suffix=".so.tmp")
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp, "-lz"],
            check=True,
            capture_output=True,
            text=True,
        )
        os.replace(tmp, out_path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


TOUCHED_ALL = 2**64 - 1


class TouchState(ctypes.Structure):
    """What the touchers of one stretch of memory share
    (:func:`touch_stripes`; ``tss_io.cpp`` has its twin): the owner raises
    ``wanted`` and sets ``stop``, the engine advances ``claimed`` as stripes
    are taken and ``done`` as they are finished."""

    _fields_ = [
        ("claimed", ctypes.c_uint64),
        ("wanted", ctypes.c_uint64),
        ("done", ctypes.c_uint64),
        ("stop", ctypes.c_int32),
    ]


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.tss_io_version.restype = ctypes.c_int
    stamps_out = [
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.tss_write_file.argtypes = [
        ctypes.c_char_p,
        ctypes.c_void_p,
        ctypes.c_uint64,
        ctypes.c_int,
        ctypes.c_uint64,
        *stamps_out,
    ]
    lib.tss_write_file.restype = ctypes.c_int
    lib.tss_read_file.argtypes = [
        ctypes.c_char_p,
        ctypes.c_void_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_int,
        ctypes.c_uint64,
        *stamps_out,
        ctypes.c_int64,
    ]
    lib.tss_read_file.restype = ctypes.c_int
    lib.tss_free.argtypes = [ctypes.c_void_p]
    lib.tss_free.restype = None
    lib.tss_read_pool_configure.argtypes = [ctypes.c_int]
    lib.tss_read_pool_configure.restype = ctypes.c_int
    lib.tss_read_pool_stats.argtypes = [ctypes.POINTER(ctypes.c_uint64 * 6)]
    lib.tss_read_pool_stats.restype = None
    lib.tss_write_bounce_stats.argtypes = [ctypes.POINTER(ctypes.c_uint64 * 4)]
    lib.tss_write_bounce_stats.restype = None
    lib.tss_file_size.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64)]
    lib.tss_file_size.restype = ctypes.c_int
    lib.tss_touch_stripes.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(TouchState),
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.tss_touch_stripes.restype = None
    lib.tss_write_file_digest.argtypes = [
        ctypes.c_char_p,
        ctypes.c_void_p,
        ctypes.c_uint64,
        ctypes.c_int,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32),
        *stamps_out,
    ]
    lib.tss_write_file_digest.restype = ctypes.c_int
    return lib


def _load_built() -> Optional[ctypes.CDLL]:
    """dlopen the engine built from the current source, if it exists."""
    path = lib_path()
    if not os.path.exists(path):
        return None
    try:
        lib = _configure(ctypes.CDLL(path))
    except OSError as e:
        logger.debug("Native IO engine unavailable at %s: %s", path, e)
        return None
    logger.debug("Loaded native IO engine from %s", path)
    return lib


def _publish(lib: Optional[ctypes.CDLL]) -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    with _lock:
        if not _load_attempted:
            _lib = lib
            _load_attempted = True
        return _lib


def load_native() -> Optional[ctypes.CDLL]:
    """Return the native engine, building it if needed; None if unavailable."""
    from ..utils import knobs

    if not knobs.is_native_io_enabled():
        return None
    with _lock:
        if _load_attempted:
            return _lib
    lib = _load_built()
    if lib is None:
        # Build under its own lock so _lock stays responsive for
        # load_native_nonblocking callers during the multi-second compile.
        with _build_lock:
            with _lock:
                if _load_attempted:
                    return _lib
            lib = _load_built()  # another builder may have just finished
            if lib is None:
                path = lib_path()
                try:
                    _build(path)
                    lib = _configure(ctypes.CDLL(path))
                    logger.debug("Built native IO engine at %s", path)
                except (OSError, subprocess.CalledProcessError) as e:
                    logger.info(
                        "Native IO engine unavailable (%s: %s); using "
                        "pure-Python file I/O",
                        path,
                        getattr(e, "stderr", None) or e,
                    )
    return _publish(lib)


def loaded_path() -> Optional[str]:
    """Path of the engine this process actually loaded (None: not loaded)."""
    return _lib._name if _lib is not None else None


def load_native_nonblocking() -> Optional[ctypes.CDLL]:
    """Like :func:`load_native`, but never blocks on compilation.

    If the engine for the current source is already built this loads it
    synchronously (a dlopen, milliseconds). Otherwise the g++ build runs on a daemon thread
    and this returns ``None`` until it completes — callers fall back to
    buffered I/O in the meantime, keeping first-``take`` latency free of the
    multi-second compile. ``_lock`` is never held across the build, so this
    never stalls behind an in-flight compile either.
    """
    global _bg_build
    from ..utils import knobs

    if not knobs.is_native_io_enabled():
        return None
    if _load_attempted:
        return _lib
    lib = _load_built()
    if lib is not None:
        return _publish(lib)
    with _lock:
        if _load_attempted:
            return _lib
        if _bg_build is None or not _bg_build.is_alive():
            _bg_build = threading.Thread(
                target=load_native, daemon=True, name="tss-native-build"
            )
            _bg_build.start()
    return None


def _as_uint8_view(buf) -> "memoryview":
    mv = memoryview(buf)
    if mv.ndim != 1 or mv.format not in ("B", "b", "c"):
        mv = mv.cast("B")
    return mv


def _buf_address(mv: memoryview) -> int:
    # numpy gives a stable pointer for read-only buffers, which
    # ctypes.from_buffer refuses.
    import numpy as np

    return np.frombuffer(mv, dtype=np.uint8).ctypes.data if mv.nbytes else 0


# What the engine stamps, GIL-free, on ``time.monotonic()``'s clock
# (``tss_io.cpp``): a write chunk as ``(t_copy, t_mount, t_crc, t_end,
# nbytes, warm, fresh)``: the writing thread copied into the bounce buffer over
# ``[t_copy, t_mount)``, was in ``pwrite`` over ``[t_mount, t_crc)`` and hashed
# over ``[t_crc, t_end)``; ``nbytes`` is what the ``pwrite`` took of the
# object, ``warm`` of them copied into pages of the borrowed buffer that an
# earlier copy had written and ``fresh`` into pages none had (both 0 for a
# buffered chunk, which has no copy). A read chunk as ``(t0, t1, p0, p1)``:
# the reader thread had the chunk over ``[t0, t1)`` (the ``pread`` into its
# bounce buffer and the copy out of it into the destination's pages) and was
# in ``pread`` over ``[p0, p1)``.
WriteChunk = Tuple[float, float, float, float, float, float, float]
ReadChunk = Tuple[float, float, float, float]
_WRITE_STAMP_DOUBLES = 7
_READ_STAMP_DOUBLES = 4


class _StampsOut:
    """The engine's optional stamps-out: two null pointers unless
    ``wanted``, so an unstamped call reads no clock and allocates nothing."""

    def __init__(self, lib: ctypes.CDLL, wanted: bool, width: int) -> None:
        self._lib = lib
        self._width = width
        self._rows = ctypes.POINTER(ctypes.c_double)()
        self._count = ctypes.c_uint64(0)
        self.args = (
            (ctypes.byref(self._rows), ctypes.byref(self._count))
            if wanted
            else (None, None)
        )

    def take(self) -> List[Tuple[float, ...]]:
        """The rows as tuples; the engine's array is released."""
        w = self._width
        try:
            return [
                tuple(self._rows[w * k : w * (k + 1)])
                for k in range(self._count.value)
            ]
        finally:
            self._lib.tss_free(self._rows)


def write_file(
    lib: ctypes.CDLL,
    path: str,
    buf,
    *,
    direct: bool,
    chunk_bytes: int,
    stamps: Optional[List[WriteChunk]] = None,
) -> None:
    """Write ``buf`` (any buffer-protocol object) to ``path`` via the engine.
    ``stamps``: a list the engine's :data:`WriteChunk` rows are appended to
    (``None``: the engine stamps nothing)."""
    mv = _as_uint8_view(buf)
    out = _StampsOut(lib, stamps is not None, _WRITE_STAMP_DOUBLES)
    rc = lib.tss_write_file(
        os.fsencode(path),
        _buf_address(mv),
        mv.nbytes,
        1 if direct else 0,
        chunk_bytes,
        *out.args,
    )
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    if stamps is not None:
        stamps.extend(out.take())


def write_file_digest(
    lib: ctypes.CDLL,
    path: str,
    buf,
    *,
    direct: bool,
    chunk_bytes: int,
    stamps: Optional[List[WriteChunk]] = None,
):
    """Write ``buf`` and return its ``[crc32, size, None]`` digest, the crc
    computed inside the write loop (no extra memory pass). The sha256 slot
    is None by design — hashlib's OpenSSL (SHA-NI) implementation beats any
    embedded portable one, so collision-resistant dedup digests stay in
    Python and the scheduler fills the slot when it needs one. ``stamps``:
    as :func:`write_file` takes it.
    """
    mv = _as_uint8_view(buf)
    crc = ctypes.c_uint32(0)
    out = _StampsOut(lib, stamps is not None, _WRITE_STAMP_DOUBLES)
    rc = lib.tss_write_file_digest(
        os.fsencode(path),
        _buf_address(mv),
        mv.nbytes,
        1 if direct else 0,
        chunk_bytes,
        ctypes.byref(crc),
        *out.args,
    )
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    if stamps is not None:
        stamps.extend(out.take())
    return [crc.value, mv.nbytes, None]


def read_into(
    lib: ctypes.CDLL,
    path: str,
    dst,
    *,
    offset: int = 0,
    direct: bool = True,
    chunk_bytes: int = 4 << 20,
    stamped: bool = False,
    fail_chunk: int = -1,
) -> List[ReadChunk]:
    """Fill writable buffer ``dst`` from ``path[offset : offset+len(dst)]``,
    as chunk reads of ``chunk_bytes`` on the engine's reader pool
    (:func:`set_read_depth`): several of them, of this call and of others,
    are on the mount at once. ``stamped``: return each chunk's
    :data:`ReadChunk` (else nothing). ``fail_chunk`` is the fault harness's
    torn read (``faults.read_chunk_fault``)."""
    mv = _as_uint8_view(dst)
    if mv.readonly:
        raise ValueError("read_into requires a writable buffer")
    out = _StampsOut(lib, stamped, _READ_STAMP_DOUBLES)
    rc = lib.tss_read_file(
        os.fsencode(path),
        _buf_address(mv),
        offset,
        mv.nbytes,
        1 if direct else 0,
        chunk_bytes,
        *out.args,
        fail_chunk,
    )
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    return out.take()


def set_read_depth(lib: ctypes.CDLL, depth: int) -> None:
    """How many chunk reads the engine keeps on the mount at once, across
    every ``read_into`` of the process (as many reader threads, each with
    one bounce buffer it keeps)."""
    rc = lib.tss_read_pool_configure(depth)
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))


def read_pool_stats(lib: ctypes.CDLL) -> Dict[str, int]:
    """The reader pool's gauges: ``depth``, chunk reads ``in_flight``, the
    ``high_water`` of that and the ``chunks_read`` since the depth was last
    set, and the ``buffers`` / ``buffer_bytes`` of bounce memory it holds."""
    out = (ctypes.c_uint64 * 6)()
    lib.tss_read_pool_stats(ctypes.byref(out))
    keys = ("depth", "in_flight", "high_water", "buffers", "buffer_bytes", "chunks_read")
    return dict(zip(keys, out))


def write_bounce_stats(lib: ctypes.CDLL) -> Dict[str, int]:
    """The gauges of the bounce buffers the engine lends to direct writes:
    ``allocated`` since the process (or its fork) began, ``lent`` to a write
    now, ``kept`` for the next one and the ``kept_bytes`` of those."""
    out = (ctypes.c_uint64 * 4)()
    lib.tss_write_bounce_stats(ctypes.byref(out))
    return dict(zip(("allocated", "lent", "kept", "kept_bytes"), out))


def touch_stripes(
    lib: ctypes.CDLL, address: int, state: TouchState, stripe_bytes: int, page_bytes: int
) -> int:
    """One toucher's whole life, GIL-free: first-touch the memory at
    ``address`` a byte a page, in stripes claimed from ``state.claimed``
    upward as far as ``state.wanted`` has come, until ``state.stop`` is set
    (looked at before every page). Returns the offset of the first page of
    its last stripe that it left untouched, or :data:`TOUCHED_ALL` where it
    finished every stripe it claimed."""
    unfinished_at = ctypes.c_uint64(0)
    lib.tss_touch_stripes(
        address, ctypes.byref(state), stripe_bytes, page_bytes, ctypes.byref(unfinished_at)
    )
    return unfinished_at.value


def file_size(lib: ctypes.CDLL, path: str) -> int:
    out = ctypes.c_uint64(0)
    rc = lib.tss_file_size(os.fsencode(path), ctypes.byref(out))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc), path)
    return out.value
