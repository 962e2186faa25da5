"""Content-addressed read-through cache, layered over any storage plugin.

The serving-scale read problem: a fleet of K inference replicas cold-starts
from ONE committed snapshot, and every replica independently hammers the
origin bucket for the same bytes. :class:`CachedStoragePlugin` wraps the
origin plugin (fs/gcs/s3/memory alike) with a byte-bounded local store so
repeat reads — a replica restarting, several co-hosted replicas, successive
snapshots sharing frozen layers — are served from local disk instead of the
origin.

Two entry tiers:

- **Digest-keyed** (``by-digest/<aa>/<sha256>``): objects covered by the
  snapshot's checksum sidecars (the dedup digests PR 1 pinned —
  ``[crc32, size, sha256]`` per storage object). Content-addressed, so the
  same bytes are cached ONCE across snapshots (incremental takes hard-link
  unchanged objects: every snapshot in a delta chain hits the same cache
  entry) and a hit can be *verified* against its recorded sha256 before it
  is served (``TORCHSNAPSHOT_TPU_READ_CACHE_VERIFY``, default on) — a
  corrupt local entry falls back to the origin and is re-populated. The
  digest index is attached by ``Snapshot.restore``/``read_object`` after
  reading the sidecars (:meth:`CachedStoragePlugin.attach_digest_index`).
- **Path-keyed** (``by-path/<sha256(origin || path)>``): everything else —
  ``.snapshot_metadata``, the sidecars themselves, ``.ftab`` frame tables.
  Keyed by (origin URL, path), so distinct origins never collide. Writes or
  deletes issued *through this process's plugin* invalidate the path entry;
  an out-of-band retake into the same committed path from another host is
  the documented staleness caveat (serve immutable, uniquely-named snapshot
  roots — the ``/checkpoints/step_N`` layout — and this never triggers).

Guarantees:

- **Populate is atomic** (write to ``tmp/``, then ``os.replace``): a
  concurrent reader observes a fully-populated entry or none — never torn
  bytes. Two processes populating the same digest both land identical
  content; within one process, concurrent readers of one key share a single
  origin fetch (in-flight dedup).
- **Byte-bounded**: after each populate the store is scanned (the local
  analogue of ``list_prefix``) and least-recently-used entries — hits bump
  an entry's mtime — are evicted until the store fits
  ``TORCHSNAPSHOT_TPU_READ_CACHE_BYTES``.
- **Ranged reads never over-fetch**: a byte-range miss passes through to
  the origin untouched (lazy partial restores must read only the ranges
  they need); ranges are served locally from an already-cached full object
  — or, for digest-known objects with a v2 chunk grid, from a **sparse
  entry** holding only some hash chunks (below).

**Sparse (chunk-granular) entries**: objects whose sidecar record carries a
v2 chunk grid cache *sub-ranges* too — the reshard read path fetches only
the byte ranges each target shard overlaps, and without this tier every
ranged read re-fetched from origin forever. A sparse entry is the data file
(pre-sized to the full object, written at chunk offsets) plus a
``<entry>.chunks`` presence bitmap; the bitmap rename is the commit point,
so a concurrent reader sees a chunk as present only after its bytes landed.
A ranged read is served when every hash chunk it touches is present (the
covering chunks are digest-verified, then sliced); a ranged origin fetch
populates exactly the chunks it fully contains. When the last chunk lands
the bitmap is removed and the entry IS a full entry — the two tiers
converge. Ranged misses on digest-known paths count as
``cache.range_misses`` (servable, not yet resident); ranged reads of paths
the digest index doesn't know remain ``cache.bypass_reads`` (the cache
cannot address them at all).
- **Fail-open**: any cache-store failure (disk full, permissions) degrades
  to a plain origin read — the cache can slow a restore down, never fail it.

Telemetry: ``cache.hits``/``cache.misses`` (+ ``_bytes``),
``cache.bypass_reads`` (ranged pass-throughs on digest-unknown paths),
``cache.range_misses`` (ranged pass-throughs on digest-known paths — the
sub-range tier COULD have served them), ``cache.range_populates`` (chunk
sub-range populates), ``cache.evictions``/``cache.evicted_bytes``,
``cache.corrupt_entries``; populates are traced as
``storage.cache_populate`` spans.
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import logging
import os
import threading
import uuid
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

from .. import hashing, telemetry
from ..io_types import ReadIO, StoragePlugin, WriteIO
from ..engine import qos
from ..utils import knobs

logger = logging.getLogger(__name__)

# Sidecar paths churn per take (and are tiny); caching them path-keyed is
# still correct because a write through this plugin invalidates the entry.
_TMP_DIR = "tmp"
_DIGEST_DIR = "by-digest"
_PATH_DIR = "by-path"


def find_read_cache(storage) -> Optional["CachedStoragePlugin"]:
    """Locate the cache layer inside a (possibly wrapped) plugin stack —
    e.g. ``FaultyStoragePlugin(CachedStoragePlugin(origin))`` under chaos
    testing. Walks ``inner`` links; None when no cache layer is present."""
    seen = 0
    while storage is not None and seen < 8:
        if isinstance(storage, CachedStoragePlugin):
            return storage
        storage = getattr(storage, "inner", None)
        seen += 1
    return None


class CachedStoragePlugin(StoragePlugin):
    """Read-through cache over ``inner``; all writes delegate (write-through
    with path-entry invalidation). See the module docstring for semantics."""

    def __init__(
        self,
        inner: StoragePlugin,
        origin_id: str,
        cache_dir: Optional[str] = None,
        max_bytes: Optional[int] = None,
    ) -> None:
        self.inner = inner
        self.origin_id = origin_id
        self.cache_dir = cache_dir or knobs.get_read_cache_dir() or ""
        if not self.cache_dir:
            raise ValueError(
                "CachedStoragePlugin needs a cache directory (argument or "
                "TORCHSNAPSHOT_TPU_READ_CACHE_DIR)"
            )
        self._max_bytes = (
            max_bytes if max_bytes is not None else knobs.get_read_cache_bytes()
        )
        # path -> (size, cache-key | None, crc32 | None, chunk-info | None):
        # the sidecar digests of the snapshot(s) being read, attached by
        # Snapshot.restore/read_object. A key (v1 whole-object sha, or a v2
        # tree root suffixed with its grain) makes the entry
        # content-addressed; without one (DEDUP_DIGESTS off at take time)
        # the entry stays path-keyed but hits are still size+crc-validated.
        # chunk-info (a ``hashing.record_chunk_info`` tuple) switches hit
        # verification to per-chunk — ranged hits then check only the
        # chunks they serve. Paths absent here fall back to unvalidated
        # path-keyed entries.
        self._digests: Dict[str, Tuple] = {}
        self._executor: Optional[ThreadPoolExecutor] = None
        # Guards the store-size accounting and LRU bookkeeping, which are
        # mutated from executor threads.
        self._lock = threading.Lock()
        self._total_bytes: Optional[int] = None  # lazy first-scan
        # In-flight populate dedup: concurrent readers of one cache key on
        # one event loop share a single origin fetch.
        self._inflight: Dict[str, asyncio.Future] = {}
        # Entries eviction must not touch: mid-populate (between the tmp
        # write and the post-rename accounting) or with an in-flight reader
        # (between open and the verified serve). Refcounted under _lock —
        # a tight byte budget can otherwise evict a just-renamed entry out
        # from under the reader that is validating it.
        self._pinned: Dict[str, int] = {}
        # Per-instance byte accounting (the plugin stack is constructed
        # fresh per take/restore, so these are per-operation): feeds the
        # restore's origin-vs-peer-vs-cache attribution
        # (``snapshot.LAST_RESTORE_STATS``) without a telemetry session.
        self.stats: Dict[str, int] = {"hit_bytes": 0, "miss_bytes": 0}

    # -- capability flags proxy the origin ----------------------------------
    @property
    def scales_io_with_local_world(self) -> bool:  # type: ignore[override]
        return bool(getattr(self.inner, "scales_io_with_local_world", False))

    # -- digest index --------------------------------------------------------
    def attach_digest_index(self, index: Dict[str, Tuple]) -> None:
        """Merge ``{path: (size, key | None, crc32 | None[, chunk-info])}``
        — the parsed checksum sidecars — so reads of those paths become
        content-addressed (key present) or at least size+crc-validated.
        3-tuples (the pre-tree-digest shape) are accepted and normalized.
        Idempotent; callers may attach once per snapshot they read through
        this plugin."""
        with self._lock:
            for p, v in index.items():
                self._digests[p] = tuple(v) + (None,) * (4 - len(v))

    # -- local store helpers (blocking; run on the executor) -----------------
    def _get_executor(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="tss-cache"
            )
        return self._executor

    def _digest_entry_path(self, sha: str) -> str:
        return os.path.join(self.cache_dir, _DIGEST_DIR, sha[:2], sha)

    def _path_entry_path(self, path: str) -> str:
        key = hashlib.sha256(
            f"{self.origin_id}\0{path}".encode()
        ).hexdigest()
        return os.path.join(self.cache_dir, _PATH_DIR, key[:2], key)

    def _entry_for(self, path: str) -> Tuple[str, Optional[Tuple]]:
        digest = self._digests.get(path)
        if digest is not None and digest[1]:
            return self._digest_entry_path(digest[1]), digest
        return self._path_entry_path(path), digest

    def _pin(self, entry: str) -> None:
        with self._lock:
            self._pinned[entry] = self._pinned.get(entry, 0) + 1

    def _unpin(self, entry: str) -> None:
        with self._lock:
            n = self._pinned.get(entry, 0) - 1
            if n <= 0:
                self._pinned.pop(entry, None)
            else:
                self._pinned[entry] = n

    def _read_entry(
        self,
        entry: str,
        expect: Optional[Tuple],
        verify: bool,
        byte_range: Optional[Tuple[int, int]] = None,
    ) -> Optional[bytes]:
        """Read one cache entry, validating it against the sidecar digest
        when one is known (size always; under the verify knob: per-chunk
        tree digests when the record carries a chunk grid — a RANGED hit
        then verifies only the chunks it serves — else the v1 whole-object
        sha256, else crc32). Returns None on miss or corruption (the
        corrupt entry is unlinked). The entry is pinned against eviction
        for the duration — a concurrent populate's LRU pass never unlinks
        the bytes mid-verified-read."""
        self._pin(entry)
        try:
            return self._read_entry_pinned(entry, expect, verify, byte_range)
        finally:
            self._unpin(entry)

    @staticmethod
    def _bitmap_path(entry: str) -> str:
        return entry + ".chunks"

    def _read_entry_pinned(
        self,
        entry: str,
        expect: Optional[Tuple],
        verify: bool,
        byte_range: Optional[Tuple[int, int]] = None,
    ) -> Optional[bytes]:
        if os.path.exists(self._bitmap_path(entry)):
            # A presence bitmap marks a SPARSE entry: the data file is
            # pre-sized to the full object but only some chunks hold real
            # bytes — never servable as a complete entry (the sub-range
            # tier serves what it can through _read_sparse_range).
            return None
        try:
            with open(entry, "rb") as f:
                data = f.read()
        except FileNotFoundError:
            return None
        except OSError:
            logger.warning("cache entry %s unreadable", entry, exc_info=True)
            return None
        if expect is not None:
            size, key, crc = expect[0], expect[1], expect[2]
            chunks = expect[3] if len(expect) > 3 else None
            ok = len(data) == size
            if ok and verify:
                if chunks is not None:
                    begin, end = byte_range if byte_range else (None, None)
                    ok = (
                        hashing.verify_chunks_of(
                            memoryview(data), chunks, begin, end
                        )
                        is None
                    )
                elif key:
                    ok = hashlib.sha256(data).hexdigest() == key
                elif crc is not None:
                    ok = zlib.crc32(data) == crc
            if not ok:
                telemetry.counter_add("cache.corrupt_entries")
                logger.warning(
                    "corrupt cache entry %s (expected %d bytes, digest %s); "
                    "falling back to origin and re-populating",
                    entry,
                    size,
                    (key or crc),
                )
                with contextlib.suppress(OSError):
                    os.remove(entry)
                return None
        # LRU touch: hits keep an entry young. Never fatal.
        with contextlib.suppress(OSError):
            os.utime(entry)
        return data

    def _write_entry(self, entry: str, data: bytes) -> None:
        """Atomic populate-then-rename; a concurrent reader sees the full
        entry or none. Failures propagate to the fail-open caller. The
        entry stays pinned from before the rename until its own eviction
        pass below completes, so a concurrent populate's LRU scan can never
        evict the just-renamed bytes before a reader sees them."""
        tmp_dir = os.path.join(self.cache_dir, _TMP_DIR)
        os.makedirs(tmp_dir, exist_ok=True)
        os.makedirs(os.path.dirname(entry), exist_ok=True)
        tmp = os.path.join(tmp_dir, f"{uuid.uuid4().hex}.tmp")
        self._pin(entry)
        try:
            try:
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, entry)
            except BaseException:
                with contextlib.suppress(OSError):
                    os.remove(tmp)
                raise
            # A full populate supersedes any sparse state: the data file now
            # holds every byte, so the presence bitmap (which would demote
            # the entry back to partial) must go.
            with contextlib.suppress(OSError):
                os.remove(self._bitmap_path(entry))
            with self._lock:
                if self._total_bytes is not None:
                    self._total_bytes += len(data)
            self._maybe_evict()
        finally:
            self._unpin(entry)

    # -- sparse (chunk-granular) entries -------------------------------------
    def _chunk_span(
        self, expect: Tuple, begin: int, end: int, contained: bool
    ) -> Optional[Tuple[int, int, int]]:
        """``(first_chunk, last_chunk_exclusive, grain)`` of the hash
        chunks *touching* [begin, end) (``contained=False``, the serve-side
        coverage check) or *fully contained* in it (``contained=True``, the
        populate side — a partially fetched chunk must never be cached).
        None when the record has no usable chunk grid."""
        chunks = expect[3] if len(expect) > 3 else None
        if chunks is None:
            return None
        grain = chunks[0]
        size = expect[0]
        if not isinstance(grain, int) or grain <= 0 or not size:
            return None
        n = -(size // -grain)
        if contained:
            c0 = -(begin // -grain)
            c1 = c0
            for k in range(c0, n):
                if min((k + 1) * grain, size) <= end:
                    c1 = k + 1
                else:
                    break
        else:
            c0 = min(n, max(0, begin) // grain)
            c1 = min(n, -(end // -grain))
        if c1 <= c0:
            return None
        return c0, c1, grain

    def _verify_span(
        self, span: bytes, expect: Tuple, c0: int, c1: int
    ) -> Optional[str]:
        """Digest-verify chunks ``c0..c1`` of a sparse entry's span bytes
        (``span`` starts exactly at chunk ``c0``'s extent)."""
        _grain_, key_shas, crcs = expect[3][0], expect[3][1], expect[3][2]
        bad = hashing._chunk_mismatches(
            memoryview(span),
            _grain_,
            key_shas[:c1] if key_shas is not None else None,
            crcs[:c1] if crcs is not None else None,
            c0,
            0,
        )
        return f"chunk mismatch at {bad}" if bad else None

    def _read_sparse_range(
        self, entry: str, expect: Tuple, begin: int, end: int, verify: bool
    ) -> Optional[bytes]:
        """Serve [begin, end) from a sparse entry: every touching chunk
        must be present per the bitmap; the covering chunk span is read,
        verified (all covering chunks are fully resident by construction),
        and sliced. Returns None on miss; a corrupt span drops the whole
        sparse entry (data + bitmap)."""
        span_info = self._chunk_span(expect, begin, end, contained=False)
        if span_info is None:
            return None
        c0, c1, grain = span_info
        self._pin(entry)
        try:
            try:
                with open(self._bitmap_path(entry), "rb") as f:
                    bitmap = f.read()
            except OSError:
                return None
            if len(bitmap) < c1 or not all(bitmap[c0:c1]):
                return None
            size = expect[0]
            span_b, span_e = c0 * grain, min(c1 * grain, size)
            try:
                with open(entry, "rb") as f:
                    f.seek(span_b)
                    span = f.read(span_e - span_b)
            except OSError:
                return None
            if len(span) != span_e - span_b:
                return None
            if verify and self._verify_span(span, expect, c0, c1) is not None:
                telemetry.counter_add("cache.corrupt_entries")
                logger.warning(
                    "corrupt sparse cache entry %s (chunks %d..%d); "
                    "dropping and falling back to origin",
                    entry,
                    c0,
                    c1,
                )
                self._drop_entry(entry)
                return None
            with contextlib.suppress(OSError):
                os.utime(entry)
                os.utime(self._bitmap_path(entry))
            return span[begin - span_b : end - span_b]
        finally:
            self._unpin(entry)

    def _write_entry_range(
        self, entry: str, expect: Tuple, begin: int, end: int, data: bytes
    ) -> None:
        """Populate the hash chunks fully contained in [begin, end) into a
        sparse entry. The bitmap rename is the commit point: chunk bytes
        land in the (pre-sized) data file first, presence flips after — a
        concurrent reader never sees a chunk it can't read. When the last
        chunk lands the bitmap is removed and the entry IS a full entry."""
        span_info = self._chunk_span(expect, begin, end, contained=True)
        if span_info is None:
            return
        c0, c1, grain = span_info
        size = expect[0]
        n = -(size // -grain)
        bitmap_path = self._bitmap_path(entry)
        self._pin(entry)
        try:
            created = False
            with self._lock:
                # One writer mutates a given sparse entry's files at a time
                # in this process; cross-process writers land identical
                # content (same digests), so a lost bitmap bit just costs a
                # future re-fetch (fail-open).
                if os.path.exists(entry) and not os.path.exists(bitmap_path):
                    return  # already a complete entry
                if not os.path.exists(bitmap_path):
                    self._replace_bitmap(bitmap_path, bytes(n))
                if not os.path.exists(entry):
                    os.makedirs(os.path.dirname(entry), exist_ok=True)
                    # Sparse writes are deliberately non-atomic on the DATA
                    # file: chunks are published by the bitmap's atomic
                    # rename (_replace_bitmap), so a torn write here is
                    # never marked present and the next read re-fetches.
                    with open(entry, "wb") as f:  # noqa: TSA1001
                        f.truncate(size)
                    created = True
                span_b, span_e = c0 * grain, min(c1 * grain, size)
                with open(entry, "r+b") as f:  # noqa: TSA1001
                    f.seek(span_b)
                    f.write(data[span_b - begin : span_e - begin])
                with open(bitmap_path, "rb") as f:
                    bitmap = bytearray(f.read())
                if len(bitmap) != n:
                    bitmap = bytearray(n)
                for k in range(c0, c1):
                    bitmap[k] = 1
                if all(bitmap):
                    # Complete: the data file now holds every chunk —
                    # removing the bitmap promotes it to a full entry.
                    with contextlib.suppress(OSError):
                        os.remove(bitmap_path)
                else:
                    self._replace_bitmap(bitmap_path, bytes(bitmap))
                if created and self._total_bytes is not None:
                    self._total_bytes += size
            telemetry.counter_add("cache.range_populates")
            self._maybe_evict()
        finally:
            self._unpin(entry)

    def _replace_bitmap(self, bitmap_path: str, content: bytes) -> None:
        tmp_dir = os.path.join(self.cache_dir, _TMP_DIR)
        os.makedirs(tmp_dir, exist_ok=True)
        os.makedirs(os.path.dirname(bitmap_path), exist_ok=True)
        tmp = os.path.join(tmp_dir, f"{uuid.uuid4().hex}.tmp")
        try:
            with open(tmp, "wb") as f:
                f.write(content)
            if knobs.get_faults_spec():
                # The bitmap rename is a commit point BELOW the fault
                # wrapper: this is its only road into chaos schedules
                # (`op=cache_bitmap`). See faults.maybe_inject_local.
                from .. import faults

                faults.maybe_inject_local("cache_bitmap", bitmap_path)
            os.replace(tmp, bitmap_path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.remove(tmp)
            raise

    def _drop_entry(self, entry: str) -> None:
        """Remove an entry's data file AND its sparse bitmap (if any)."""
        for p in (entry, self._bitmap_path(entry)):
            with contextlib.suppress(OSError):
                os.remove(p)

    def _scan(self) -> List[Tuple[str, int, float]]:
        """All cache entries as (abs path, size, mtime) — the local-store
        analogue of ``list_prefix``, and the substrate of eviction."""
        out: List[Tuple[str, int, float]] = []
        for sub in (_DIGEST_DIR, _PATH_DIR):
            base = os.path.join(self.cache_dir, sub)
            for dirpath, _, filenames in os.walk(base):
                for name in filenames:
                    if name.endswith(".chunks"):
                        # Sparse-presence bitmaps ride their data file: never
                        # evicted alone (a partial data file with no bitmap
                        # would masquerade as complete), removed with it.
                        continue
                    p = os.path.join(dirpath, name)
                    try:
                        st = os.stat(p)
                    except OSError:
                        continue  # evicted/replaced underfoot
                    out.append((p, st.st_size, st.st_mtime))
        return out

    def _maybe_evict(self) -> None:
        """Evict least-recently-used entries until the store fits the byte
        budget. Runs after each populate, on the executor thread that
        populated; the scan re-derives ground truth so concurrent
        populators never double-count. Pinned entries (mid-populate, or
        with an in-flight reader) are never evicted — they stay counted
        toward the total, so the store may transiently exceed the budget
        by the pinned bytes rather than tear a concurrent read."""
        with self._lock:
            total = self._total_bytes
        if total is None or total > self._max_bytes:
            entries = self._scan()
            total = sum(sz for _, sz, _ in entries)
            evicted = 0
            evicted_bytes = 0
            if total > self._max_bytes:
                for p, sz, _ in sorted(entries, key=lambda e: e[2]):
                    if total <= self._max_bytes:
                        break
                    # Re-checked per entry (not a snapshot before the loop)
                    # so a reader pinning mid-pass is still protected.
                    with self._lock:
                        if p in self._pinned:
                            continue
                    with contextlib.suppress(OSError):
                        os.remove(p)
                        total -= sz
                        evicted += 1
                        evicted_bytes += sz
                    with contextlib.suppress(OSError):
                        os.remove(self._bitmap_path(p))
            if evicted:
                telemetry.counter_add("cache.evictions", evicted)
                telemetry.counter_add("cache.evicted_bytes", evicted_bytes)
            with self._lock:
                self._total_bytes = total

    def _invalidate_path(self, path: str) -> None:
        self._drop_entry(self._path_entry_path(path))

    def quarantine_path(self, path: str) -> int:
        """Remove every local entry that could serve ``path`` — the
        digest-keyed content entry (when the digest index knows one) AND
        the path-keyed entry. Called by the read pipeline when a fetched
        object fails digest verification: whatever the cache holds for the
        path is suspect and must never be served twice; the next read
        misses and re-populates from origin. Blocking (unlinks); callers on
        an event loop run it on an executor. Returns entries removed."""
        with self._lock:
            digest = self._digests.get(path)
        targets = {self._path_entry_path(path)}
        if digest is not None and digest[1]:
            targets.add(self._digest_entry_path(digest[1]))
        removed = 0
        for entry in targets:
            with contextlib.suppress(OSError):
                os.remove(self._bitmap_path(entry))
            try:
                size = os.path.getsize(entry)
                os.remove(entry)
            except OSError:
                continue
            removed += 1
            with self._lock:
                if self._total_bytes is not None:
                    self._total_bytes -= size
        if removed:
            telemetry.counter_add("cache.quarantined", removed)
            logger.warning(
                "quarantined %d cache entr%s for %s after a failed "
                "read verification",
                removed,
                "y" if removed == 1 else "ies",
                path,
            )
        return removed

    # -- swarm surface -------------------------------------------------------
    async def try_read_object(self, path: str) -> Optional[bytes]:
        """The full object's bytes from the LOCAL store only (verified the
        same way a hit is), or None — never touches the origin. The swarm
        restore probes this before planning origin fetches: a host that
        already holds the content serves its assigned chunks to peers from
        local bytes, reading zero origin bytes. Restricted to digest-known
        paths: an unvalidated path-keyed entry is not strong enough to
        seed a fan-out."""
        entry, expect = self._entry_for(path)
        if expect is None:
            return None
        loop = asyncio.get_running_loop()
        data = await loop.run_in_executor(
            self._get_executor(),
            self._read_entry,
            entry,
            expect,
            knobs.is_read_cache_verify_enabled(),
        )
        if data is not None:
            telemetry.counter_add("cache.hits")
            telemetry.counter_add("cache.hit_bytes", len(data))
            self.stats["hit_bytes"] += len(data)
        return data

    async def try_read_range(
        self, path: str, begin: int, end: int
    ) -> Optional[bytes]:
        """Bytes [begin, end) of ``path`` from the LOCAL store only
        (verified like any hit: a full entry's covering chunks, or a sparse
        entry whose bitmap covers the range), or None — never touches the
        origin. The reshard swarm probes this per needed chunk so a warm
        host serves its assigned chunks from local bytes. Digest-known
        paths only — an unvalidated path-keyed entry is not strong enough
        to seed a fan-out."""
        entry, expect = self._entry_for(path)
        if expect is None:
            return None
        loop = asyncio.get_running_loop()
        verify = knobs.is_read_cache_verify_enabled()
        data = await loop.run_in_executor(
            self._get_executor(),
            self._read_entry,
            entry,
            expect,
            verify,
            (begin, end),
        )
        if data is not None:
            data = data[begin:end]
        else:
            data = await loop.run_in_executor(
                self._get_executor(),
                self._read_sparse_range,
                entry,
                expect,
                begin,
                end,
                verify,
            )
        if data is not None:
            telemetry.counter_add("cache.hits")
            telemetry.counter_add("cache.hit_bytes", len(data))
            self.stats["hit_bytes"] += len(data)
        return data

    async def populate_range(
        self, path: str, begin: int, end: int, data: bytes
    ) -> None:
        """Populate the hash chunks of ``path`` fully contained in
        [begin, end) from bytes the caller already holds and has verified —
        the reshard swarm lands each rank's assembled chunk runs here, so
        the NEXT reshard on this host serves them locally. No-op for paths
        without a v2 chunk grid in the digest index. Fail-open like every
        populate."""
        entry, expect = self._entry_for(path)
        if expect is None:
            return
        # Populates are deferrable follow-on work: yield the disk write to
        # any operation of a strictly higher QoS class before starting it
        # (chunk-granular; the bytes are already safe in the caller's RAM).
        await qos.pause_point()
        try:
            with telemetry.span(
                "storage.cache_populate",
                cat="storage",
                path=path,
                nbytes=len(data),
            ):
                await asyncio.get_running_loop().run_in_executor(
                    self._get_executor(),
                    self._write_entry_range,
                    entry,
                    expect,
                    begin,
                    end,
                    bytes(data),
                )
        except Exception:  # noqa: BLE001 - fail-open by contract
            logger.warning(
                "failed to range-populate read cache for %s (restore "
                "proceeds; caching disabled for this range)",
                path,
                exc_info=True,
            )

    async def populate_object(self, path: str, data: bytes) -> None:
        """Populate ``path``'s cache entry from bytes the caller already
        holds and has verified — the swarm restore lands each assembled,
        chunk-verified object here so the NEXT restore on this host reads
        zero origin AND zero peer bytes. Digest-keyed when the index knows
        the path (content-addressed across snapshots), else path-keyed.
        Fail-open like every populate."""
        entry, _expect = self._entry_for(path)
        await qos.pause_point()
        try:
            with telemetry.span(
                "storage.cache_populate",
                cat="storage",
                path=path,
                nbytes=len(data),
            ):
                await asyncio.get_running_loop().run_in_executor(
                    self._get_executor(), self._write_entry, entry, bytes(data)
                )
        except Exception:  # noqa: BLE001 - fail-open by contract
            logger.warning(
                "failed to populate read cache for %s (swarm restore "
                "proceeds; caching disabled for this object)",
                path,
                exc_info=True,
            )

    # -- read path -----------------------------------------------------------
    async def read(self, read_io: ReadIO) -> None:
        loop = asyncio.get_running_loop()
        executor = self._get_executor()
        path = read_io.path
        entry, expect = self._entry_for(path)
        verify = knobs.is_read_cache_verify_enabled()

        # A ranged read spanning the WHOLE object (the scheduler expresses
        # raw full-object reads as explicit ``(0, nbytes)`` ranges) is a
        # full read in range clothing: eligible for populate, not bypass.
        # Recognizable only when the digest index records the size.
        full_range = (
            read_io.byte_range is not None
            and expect is not None
            and read_io.byte_range[0] == 0
            and read_io.byte_range[1] == expect[0]
        )
        if read_io.byte_range is not None and not full_range:
            # Serve a range from an already-cached full object, or — for
            # digest-known objects with a v2 chunk grid — from a sparse
            # entry whose bitmap covers every chunk the range touches. A
            # miss passes through untouched so lazy partial restores never
            # fetch more than the ranges they asked for, then populates the
            # chunks the fetched range fully contains (the reshard read
            # path's repeat-restore hits ride this tier). Hit verification
            # covers only the chunks the range touches.
            begin, end = read_io.byte_range
            data = await loop.run_in_executor(
                executor,
                self._read_entry,
                entry,
                expect,
                verify,
                read_io.byte_range,
            )
            if data is not None:
                data = data[begin:end]
            elif expect is not None:
                data = await loop.run_in_executor(
                    executor,
                    self._read_sparse_range,
                    entry,
                    expect,
                    begin,
                    end,
                    verify,
                )
            if data is not None:
                telemetry.counter_add("cache.hits")
                telemetry.counter_add("cache.hit_bytes", len(data))
                self.stats["hit_bytes"] += len(data)
                read_io.buf.write(data)
                return
            if expect is None:
                # The digest index doesn't know this path: the cache can't
                # address (or ever serve) the range — a true bypass.
                telemetry.counter_add("cache.bypass_reads")
                await self.inner.read(read_io)
                return
            # Digest-known range the cache COULD have served but doesn't
            # hold yet: its own counter, so the reshard bench can prove the
            # sub-range tier's hits against a denominator of real misses.
            telemetry.counter_add("cache.range_misses")
            await self.inner.read(read_io)
            fetched = read_io.buf.getvalue()
            self.stats["miss_bytes"] += len(fetched)
            telemetry.counter_add("cache.miss_bytes", len(fetched))
            try:
                await loop.run_in_executor(
                    executor,
                    self._write_entry_range,
                    entry,
                    expect,
                    begin,
                    begin + len(fetched),
                    fetched,
                )
            except Exception:  # noqa: BLE001 - fail-open by contract
                logger.warning(
                    "failed to range-populate read cache for %s (read "
                    "served from origin)",
                    path,
                    exc_info=True,
                )
            return

        data = await loop.run_in_executor(
            executor, self._read_entry, entry, expect, verify
        )
        if data is not None:
            telemetry.counter_add("cache.hits")
            telemetry.counter_add("cache.hit_bytes", len(data))
            self.stats["hit_bytes"] += len(data)
            read_io.buf.write(data)
            return

        # Miss: fetch from origin (deduping concurrent fetches of one key),
        # serve, and populate fail-open.
        telemetry.counter_add("cache.misses")
        pending = self._inflight.get(entry)
        if pending is not None:
            data = await asyncio.shield(pending)
            telemetry.counter_add("cache.hit_bytes", len(data))
            self.stats["hit_bytes"] += len(data)
            read_io.buf.write(data)
            return
        fut: asyncio.Future = loop.create_future()
        self._inflight[entry] = fut
        try:
            await self.inner.read(read_io)
            data = read_io.buf.getvalue()
            fut.set_result(data)
        except BaseException as e:
            if not fut.done():
                fut.set_exception(e)
                # Peers awaiting the shared fetch see the failure; nobody
                # retries through a half-set future.
                with contextlib.suppress(BaseException):
                    fut.exception()  # mark retrieved
            raise
        finally:
            self._inflight.pop(entry, None)
        telemetry.counter_add("cache.miss_bytes", len(data))
        self.stats["miss_bytes"] += len(data)
        try:
            with telemetry.span(
                "storage.cache_populate",
                cat="storage",
                path=path,
                nbytes=len(data),
            ):
                await loop.run_in_executor(
                    executor, self._write_entry, entry, data
                )
        except Exception:  # noqa: BLE001 - fail-open by contract
            logger.warning(
                "failed to populate read cache for %s (read served from "
                "origin; caching disabled for this object)",
                path,
                exc_info=True,
            )

    # -- write/delete delegate (with path-entry invalidation) ----------------
    async def write(self, write_io: WriteIO) -> None:
        await self.inner.write(write_io)
        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), self._invalidate_path, write_io.path
        )

    async def delete(self, path: str) -> None:
        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), self._invalidate_path, path
        )
        await self.inner.delete(path)

    async def link_in(self, src_abs_path: str, path: str) -> bool:
        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), self._invalidate_path, path
        )
        return await self.inner.link_in(src_abs_path, path)

    async def list_prefix(self, prefix: str) -> List[str]:
        return await self.inner.list_prefix(prefix)

    async def prune_empty(self) -> None:
        await self.inner.prune_empty()

    async def close(self) -> None:
        await self.inner.close()
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None


def maybe_wrap_with_read_cache(
    plugin: StoragePlugin, origin_id: str
) -> StoragePlugin:
    """Wrap ``plugin`` when the read-cache knob points at a directory.
    Called by ``url_to_storage_plugin`` on every plugin it constructs
    (inside the fault wrapper, so chaos schedules inject through the cache
    surface)."""
    if not knobs.get_read_cache_dir():
        return plugin
    return CachedStoragePlugin(plugin, origin_id=origin_id)
