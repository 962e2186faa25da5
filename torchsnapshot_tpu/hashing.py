"""Parallel chunked hashing: tree digests that scale with cores and verify
byte ranges.

A staging ablation on the host (PR 6) attributed essentially all remaining
null-sink staging wall to hashing: the sidecar format
(``[crc32, size, sha256-hex]``) forces one *serial* crc32+sha256 fold per
storage object — a whole-object sha256 cannot be computed out of order,
cannot be split across the hash pool, and cannot verify a byte range. This
module replaces that fold with a **two-level tree digest** at a fixed grain
(``TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES``, default 64 MiB):

- each grain-sized chunk of the object's bytes is hashed independently
  (crc32 + sha256) on the hash pool — chunks of one object hash
  **concurrently**;
- the chunk crc32s combine into the whole-object crc32 with a pure-Python
  :func:`crc32_combine` (the zlib GF(2) matrix trick, O(log n) per merge) —
  the sidecar's top-level crc32 is **bit-identical to the serial fold**
  regardless of chunk grain or completion order;
- the content digest is the tree **root**: sha256 over the ordered
  concatenation of the per-chunk sha256 digests. Dedup (``take(base=)``)
  and the read cache key off the root; the recorded chunk-digest list lets
  the read side verify **ranged** reads at chunk granularity, lets scrub
  attribute corruption to the exact chunk, and lets repair rewrite a single
  bad chunk's extent.

Sidecar record formats (the ``.checksums.<rank>`` JSON values):

- legacy: a bare crc32 int (pre-digest snapshots);
- **v1**: ``[crc32, size, sha256-hex | None]`` — still written for objects
  no larger than one hash chunk (and for every object when the grain knob
  is ``0``, the serial-compat escape hatch), so small-object sidecars stay
  bit-identical to prior releases;
- **v2**: ``{"v": 2, "crc": int, "size": int, "grain": int,
  "root": hex | None, "chunks": [hex, ...] | None, "crcs": [int, ...],
  "sha": hex | None}`` — ``chunks``/``root`` only when dedup digests are
  on; ``sha`` (the whole-object sha256) only when an incremental take had
  to match a v1 base (the compat shim — v1 sidecars are never rewritten).

Every consumer of sidecar records (verify/scrub, the read pipeline's
``VERIFY_READS``, broadcast pre-fan-out verification, the read cache's
digest index, incremental dedup) goes through the accessors here, so the
formats can never diverge between readers.
"""

from __future__ import annotations

import asyncio
import functools
import hashlib
import time
import zlib
from typing import Any, List, Optional, Sequence, Tuple

from . import d2h, telemetry
from .utils import knobs

__all__ = [
    "crc32_combine",
    "tree_root",
    "chunk_extents",
    "is_v2_record",
    "record_crc",
    "record_size",
    "record_whole_sha",
    "record_chunk_info",
    "record_content_keys",
    "record_cache_key",
    "range_verifiable",
    "verify_buffer",
    "verify_range",
    "find_bad_chunks",
    "serial_digest",
    "hash_buffer",
]


# ---------------------------------------------------------------------------
# crc32_combine — the zlib GF(2) matrix trick, in pure Python.
#
# crc32 is linear over GF(2): crc(A ++ B) is a function of crc(A), crc(B)
# and len(B) only. Appending one zero byte to A multiplies crc(A)'s state by
# a fixed 32x32 bit-matrix; appending len(B) zero bytes is that matrix
# raised to the 8*len(B)-th power, computed in O(log len(B)) squarings.
# ---------------------------------------------------------------------------

_CRC_POLY = 0xEDB88320


def _gf2_matrix_times(mat: Sequence[int], vec: int) -> int:
    s = 0
    i = 0
    while vec:
        if vec & 1:
            s ^= mat[i]
        vec >>= 1
        i += 1
    return s


def _gf2_matrix_square(square: List[int], mat: Sequence[int]) -> None:
    for n in range(32):
        square[n] = _gf2_matrix_times(mat, mat[n])


@functools.lru_cache(maxsize=128)
def _zeros_operator(len2: int) -> Tuple[int, ...]:
    """The 32x32 GF(2) matrix advancing a crc register across ``len2`` zero
    bytes, via square-and-multiply over MATRICES. Cached per distinct
    length: an object's chunks all share the hash grain (plus one short
    tail), so after the first combine every further one is a single 32-op
    matrix-vector product instead of ~44 matrix squarings — measured to
    matter (a cold combine costs about as much pure-Python time as hashing
    the chunk it merges)."""
    even = [0] * 32  # operator for 2^(2k+1) zero bits
    odd = [0] * 32  # operator for 2^(2k) zero bits
    # One zero BIT.
    odd[0] = _CRC_POLY
    row = 1
    for n in range(1, 32):
        odd[n] = row
        row <<= 1
    # One zero byte (8 zero bits): square twice.
    _gf2_matrix_square(even, odd)
    _gf2_matrix_square(odd, even)
    mat: Optional[List[int]] = None  # cumulative operator (None = identity)
    while True:
        _gf2_matrix_square(even, odd)
        if len2 & 1:
            mat = (
                list(even)
                if mat is None
                else [_gf2_matrix_times(even, c) for c in mat]
            )
        len2 >>= 1
        if len2 == 0:
            break
        _gf2_matrix_square(odd, even)
        if len2 & 1:
            mat = (
                list(odd)
                if mat is None
                else [_gf2_matrix_times(odd, c) for c in mat]
            )
        len2 >>= 1
        if len2 == 0:
            break
    assert mat is not None  # len2 >= 1 always sets at least one bit
    return tuple(mat)


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """``zlib.crc32(a + b)`` from ``crc32(a)``, ``crc32(b)``, ``len(b)``.

    Bit-identical to hashing the concatenation (unit-tested against
    ``zlib.crc32`` on random splits), so per-chunk crcs computed in any
    order on the hash pool still combine into the exact serial-fold value.
    """
    if len2 <= 0:
        return crc1 & 0xFFFFFFFF
    return (
        _gf2_matrix_times(_zeros_operator(len2), crc1 & 0xFFFFFFFF) ^ crc2
    ) & 0xFFFFFFFF


def tree_root(chunk_shas: Sequence[str]) -> str:
    """Root digest: sha256 over the ordered concatenation of the raw
    per-chunk sha256 digests (bytes, not hex)."""
    h = hashlib.sha256()
    for c in chunk_shas:
        h.update(bytes.fromhex(c))
    return h.hexdigest()


def chunk_extents(size: int, grain: int) -> List[Tuple[int, int]]:
    """The fixed chunk grid of an object: [k*grain, min((k+1)*grain, size))."""
    if grain <= 0:
        return [(0, size)] if size else []
    return [(b, min(b + grain, size)) for b in range(0, size, grain)]


# ---------------------------------------------------------------------------
# Sidecar record accessors — the single owner of both formats.
# ---------------------------------------------------------------------------


def is_v2_record(rec: Any) -> bool:
    return isinstance(rec, dict) and rec.get("v") == 2


def record_crc(rec: Any) -> Optional[int]:
    """Whole-object crc32 (v2 records store the combined value, which is
    bit-identical to the serial fold)."""
    if isinstance(rec, int):
        return rec
    if isinstance(rec, list) and len(rec) == 3 and isinstance(rec[0], int):
        return rec[0]
    if is_v2_record(rec) and isinstance(rec.get("crc"), int):
        return rec["crc"]
    return None


def record_size(rec: Any) -> Optional[int]:
    if isinstance(rec, list) and len(rec) == 3 and isinstance(rec[1], int):
        return rec[1]
    if is_v2_record(rec) and isinstance(rec.get("size"), int):
        return rec["size"]
    return None


def record_whole_sha(rec: Any) -> Optional[str]:
    """The whole-object sha256 when one was recorded (always for v1 records
    taken with dedup digests on; only via the compat shim for v2)."""
    if isinstance(rec, list) and len(rec) == 3:
        return rec[2]
    if is_v2_record(rec):
        return rec.get("sha")
    return None


def record_chunk_info(
    rec: Any,
) -> Optional[Tuple[int, Optional[List[str]], Optional[List[int]]]]:
    """``(grain, chunk_shas | None, chunk_crcs | None)`` for v2 records with
    a usable chunk grid; None for v1/legacy records (not chunk-verifiable)."""
    if not is_v2_record(rec):
        return None
    grain = rec.get("grain")
    size = rec.get("size")
    if not isinstance(grain, int) or grain <= 0 or not isinstance(size, int):
        return None
    n = len(chunk_extents(size, grain))
    shas = rec.get("chunks")
    if not (isinstance(shas, list) and len(shas) == n):
        shas = None
    crcs = rec.get("crcs")
    if not (isinstance(crcs, list) and len(crcs) == n):
        crcs = None
    if shas is None and crcs is None:
        return None
    return grain, shas, crcs


def record_content_keys(rec: Any) -> Tuple[str, ...]:
    """The record's collision-resistant content identities, most specific
    first. Dedup (``take(base=)``) matches two objects iff their sizes match
    and their key sets intersect:

    - v1 with sha: ``sha:<hex>`` (the whole-object sha256);
    - v2: ``tree:<grain>:<root>`` plus ``sha:<hex>`` when the compat shim
      recorded a whole sha too — so v2 writes dedup against v1 bases and
      vice versa, and v2-vs-v2 dedups on the root alone.

    crc-only records have no collision-resistant identity and return ().
    """
    keys: List[str] = []
    if is_v2_record(rec):
        root = rec.get("root")
        grain = rec.get("grain")
        if root and isinstance(grain, int):
            keys.append(f"tree:{grain}:{root}")
    sha = record_whole_sha(rec)
    if sha:
        keys.append(f"sha:{sha}")
    return tuple(keys)


def record_cache_key(rec: Any) -> Optional[str]:
    """Content-address for the read cache's ``by-digest`` store. v1 records
    keep the bare whole-object sha hex (existing caches stay warm); v2
    records key off the tree root, suffixed with the grain so two grains of
    the same bytes never share (and never corrupt) one entry."""
    if is_v2_record(rec):
        root = rec.get("root")
        grain = rec.get("grain")
        if root and isinstance(grain, int):
            return f"{root}-t{grain}"
        return None
    sha = record_whole_sha(rec)
    return sha or None


# ---------------------------------------------------------------------------
# Verification (full-object, per-chunk, ranged).
# ---------------------------------------------------------------------------


def _chunk_mismatches(
    mv: memoryview,
    grain: int,
    shas: Optional[List[str]],
    crcs: Optional[List[int]],
    first: int,
    base: int,
) -> List[int]:
    """Chunk indices whose bytes in ``mv`` don't match the recorded chunk
    digests. ``mv`` holds chunks ``first..`` of the object, with chunk
    ``first`` starting at ``base`` within ``mv``; every checked chunk must
    be fully present in ``mv`` (callers guarantee it)."""
    bad: List[int] = []
    n = len(shas) if shas is not None else len(crcs or [])
    off = base
    idx = first
    while idx < n and off < mv.nbytes:
        end = min(off + grain, mv.nbytes)
        part = mv[off:end]
        if shas is not None:
            if hashlib.sha256(part).hexdigest() != shas[idx]:
                bad.append(idx)
        elif crcs is not None:
            if zlib.crc32(part) != crcs[idx]:
                bad.append(idx)
        off = end
        idx += 1
    return bad


def find_bad_chunks(mv: memoryview, rec: Any) -> Optional[List[int]]:
    """Per-chunk audit of a FULL object's bytes against a v2 record: the
    list of corrupt chunk indices (empty == clean), or None when the record
    carries no chunk grid (v1/legacy — not chunk-attributable)."""
    info = record_chunk_info(rec)
    if info is None:
        return None
    grain, shas, crcs = info
    return _chunk_mismatches(memoryview(mv).cast("B"), grain, shas, crcs, 0, 0)


def verify_buffer(mv: memoryview, rec: Any) -> Optional[str]:
    """Full-object check against any record format; returns a mismatch
    description or None. Runs on an executor thread — every hash here
    releases the GIL for large buffers."""
    mv = memoryview(mv).cast("B")
    size = record_size(rec)
    if size is not None and mv.nbytes != size:
        return f"size {mv.nbytes} != recorded {size}"
    info = record_chunk_info(rec)
    if info is not None:
        grain, shas, crcs = info
        bad = _chunk_mismatches(mv, grain, shas, crcs, 0, 0)
        if bad:
            kind = "sha256" if shas is not None else "crc32"
            return f"chunk {kind} mismatch at chunk(s) {bad} (grain {grain})"
        return None
    sha = record_whole_sha(rec)
    if sha:
        got = hashlib.sha256(mv).hexdigest()
        if got != sha:
            return f"sha256 {got} != recorded {sha}"
        return None
    crc = record_crc(rec)
    if isinstance(crc, int):
        got_crc = zlib.crc32(mv)
        if got_crc != crc:
            return f"crc32 {got_crc} != recorded {crc}"
    return None


def _contained_chunks(
    rec: Any, begin: int, end: int
) -> Optional[Tuple[int, int, int]]:
    """``(first_chunk, last_chunk_exclusive, grain)`` for the chunks FULLY
    contained in byte range [begin, end) of the object; None when the
    record has no chunk grid or no chunk fits entirely in the range."""
    info = record_chunk_info(rec)
    if info is None:
        return None
    grain, _shas, _crcs = info
    size = record_size(rec)
    if size is None:
        return None
    first = (begin + grain - 1) // grain
    # A chunk is contained if its full extent [k*grain, min((k+1)*grain,
    # size)) lies inside [begin, end) — the object's LAST chunk may be
    # short, so containment is against its real extent.
    extents = chunk_extents(size, grain)
    last = first
    for k in range(first, len(extents)):
        if extents[k][1] <= end:
            last = k + 1
        else:
            break
    if last <= first:
        return None
    return first, last, grain


def verify_chunks_of(
    mv: memoryview,
    info: Tuple[int, Optional[List[str]], Optional[List[int]]],
    begin: Optional[int] = None,
    end: Optional[int] = None,
) -> Optional[str]:
    """Verify chunks of a FULL object's bytes against a chunk grid
    (``record_chunk_info`` tuple); with ``begin``/``end``, only the chunks
    *intersecting* [begin, end) — the read cache's ranged-hit check, which
    holds the whole entry and therefore verifies even partially-covered
    edge chunks completely. Returns a mismatch description or None."""
    grain, shas, crcs = info
    mv = memoryview(mv).cast("B")
    total = len(shas) if shas is not None else len(crcs or [])
    if begin is None:
        first, last = 0, total
    else:
        first = min(total, max(0, begin) // grain)
        last = (
            min(total, (end + grain - 1) // grain)
            if end is not None
            else total
        )
    if last <= first:
        return None
    bad = _chunk_mismatches(
        mv[first * grain :],
        grain,
        shas[:last] if shas is not None else None,
        crcs[:last] if crcs is not None else None,
        first,
        0,
    )
    if bad:
        kind = "sha256" if shas is not None else "crc32"
        return f"chunk {kind} mismatch at chunk(s) {bad} (grain {grain})"
    return None


def range_verifiable(rec: Any, begin: int, end: int) -> bool:
    """Whether a ranged read of [begin, end) covers at least one full chunk
    of the record's grid — i.e. chunk-granular verification can check it."""
    return _contained_chunks(rec, begin, end) is not None


def verify_range(mv: memoryview, rec: Any, begin: int, end: int) -> Optional[str]:
    """Verify a RANGED read's bytes (``mv`` holds exactly [begin, end) of
    the object) at chunk granularity: every chunk fully contained in the
    range is checked against its recorded digest; partial edge chunks are
    skipped (their digests cover bytes the range didn't fetch). Returns a
    mismatch description or None — including when nothing was verifiable.
    """
    contained = _contained_chunks(rec, begin, end)
    if contained is None:
        return None
    first, last, grain = contained
    info = record_chunk_info(rec)
    assert info is not None
    _grain, shas, crcs = info
    mv = memoryview(mv).cast("B")
    sub_shas = shas[:last] if shas is not None else None
    sub_crcs = crcs[:last] if crcs is not None else None
    bad = _chunk_mismatches(
        mv, grain, sub_shas, sub_crcs, first, first * grain - begin
    )
    if bad:
        kind = "sha256" if shas is not None else "crc32"
        return (
            f"chunk {kind} mismatch at chunk(s) {bad} (grain {grain}, "
            f"range [{begin}, {end}))"
        )
    return None


# ---------------------------------------------------------------------------
# The hashing engines.
# ---------------------------------------------------------------------------


def serial_digest(mv: memoryview, want_sha: bool) -> list:
    """The v1 serial fold: ``[crc32, size, sha256-hex | None]`` of one
    buffer in a single pass. Still the path for small objects (<= one hash
    chunk) and for ``TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES=0``."""
    mv = memoryview(mv).cast("B")
    sha = None
    if want_sha:
        h = hashlib.sha256()
        h.update(mv)
        sha = h.hexdigest()
    return [zlib.crc32(mv), mv.nbytes, sha]


def _hash_chunk(
    chunk: memoryview,
    want_sha: bool,
    times: Optional[Any],
    path: str,
) -> Tuple[int, int, Optional[str]]:
    """One grain-chunk's (crc32, nbytes, sha256-hex) — the executor thunk,
    a pure function of its arguments."""
    t0 = time.monotonic()
    crc = zlib.crc32(chunk)
    sha = hashlib.sha256(chunk).hexdigest() if want_sha else None
    if times is not None:
        times.record(
            "hash", t0, time.monotonic(), path=path, nbytes=chunk.nbytes,
            span="stage.hash_chunk",
        )
    return crc, chunk.nbytes, sha


def _combine_results(
    results: Sequence[Tuple[int, int, Optional[str]]],
    grain: int,
    want_sha: bool,
    whole_sha: Optional[str] = None,
):
    """Fold per-chunk (crc, n, sha) results into a sidecar record: v1 list
    for single-chunk objects, v2 dict otherwise. The combine itself is
    O(chunks * log grain) integer math — metric ``hash.combine_s``."""
    t0 = time.monotonic()
    if not results:
        rec = serial_digest(memoryview(b""), want_sha)
        if whole_sha is not None:
            rec[2] = whole_sha
        return rec
    if len(results) == 1:
        crc, n, sha = results[0]
        return [crc, n, whole_sha if whole_sha is not None else sha]
    crc, total = results[0][0], results[0][1]
    for c, n, _sha in results[1:]:
        crc = crc32_combine(crc, c, n)
        total += n
    shas = [r[2] for r in results]
    have_shas = all(s is not None for s in shas)
    rec = {
        "v": 2,
        "crc": crc,
        "size": total,
        "grain": grain,
        "root": tree_root(shas) if have_shas else None,
        "chunks": list(shas) if have_shas else None,
        "crcs": [r[0] for r in results],
        "sha": whole_sha,
    }
    telemetry.counter_add("hash.chunks", len(results))
    telemetry.counter_add("hash.combine_s", time.monotonic() - t0)
    return rec


async def hash_buffer(
    mv: memoryview,
    grain: int,
    want_sha: bool,
    loop: asyncio.AbstractEventLoop,
    executor,
    times: Optional[Any] = None,
    path: str = "",
    want_whole_sha: bool = False,
):
    """Digest one fully-materialized buffer. Objects larger than one grain
    hash chunk-parallel on ``executor``: each grain-chunk is an independent
    job, at most ``2 x HASH_WORKERS`` of one object dispatched and
    unfinished at once, gathered in order and combined into the sidecar
    record. Smaller ones (or grain 0) take the single-task serial fold.
    ``want_whole_sha`` additionally computes
    the whole-object sha256 as ONE sequential job concurrent with the chunk
    jobs — the compat shim for incremental takes whose base recorded v1
    whole-object identities."""
    mv = memoryview(mv).cast("B")
    if grain <= 0 or mv.nbytes <= grain:

        def serial():
            with d2h.timed(times, "hash", path=path, nbytes=mv.nbytes):
                return serial_digest(mv, want_sha)

        return await loop.run_in_executor(executor, serial)

    whole_fut = None
    if want_whole_sha:

        def whole():
            with d2h.timed(times, "hash", path=path, nbytes=mv.nbytes):
                return hashlib.sha256(mv).hexdigest()

        whole_fut = loop.run_in_executor(executor, whole)
    sem = asyncio.Semaphore(max(1, 2 * knobs.get_hash_workers()))
    futures: List[asyncio.Future] = []
    try:
        for begin, end in chunk_extents(mv.nbytes, grain):
            await sem.acquire()
            fut = loop.run_in_executor(
                executor, _hash_chunk, mv[begin:end], want_sha, times, path
            )
            # run_in_executor futures invoke callbacks on the loop thread,
            # so the semaphore stays loop-side-only.
            fut.add_done_callback(lambda _f: sem.release())
            futures.append(fut)
        results = await asyncio.gather(*futures)
    except BaseException:
        # Silence what was abandoned, so a failed write never logs
        # 'exception was never retrieved' for its hash jobs.
        for fut in futures:
            if not fut.cancel():
                fut.add_done_callback(
                    lambda f: f.exception() if not f.cancelled() else None
                )
        if whole_fut is not None:
            whole_fut.cancel()
        raise
    rec = _combine_results(results, grain, want_sha)
    if whole_fut is not None:
        whole_sha = await whole_fut
        if isinstance(rec, list):
            rec[2] = whole_sha if want_sha else rec[2]
        else:
            rec["sha"] = whole_sha
    return rec


def digest_of_bytes(data, grain: int, want_sha: bool = True):
    """Synchronous convenience (tests, scrub repair re-verification): the
    record :func:`hash_buffer` would produce for ``data`` at ``grain``."""
    mv = memoryview(data).cast("B")
    if grain <= 0 or mv.nbytes <= grain:
        return serial_digest(mv, want_sha)
    results = [
        _hash_chunk(mv[b:e], want_sha, None, "")
        for b, e in chunk_extents(mv.nbytes, grain)
    ]
    return _combine_results(results, grain, want_sha)
