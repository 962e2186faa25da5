"""Per-bucket snapshot catalog: the lifecycle layer for continuous checkpointing.

TorchSnapshot's design (PAPER.md) stops at single, independent snapshots.
The production workload this module serves is *continuous* multi-tenant
checkpointing: many jobs snapshotting every few steps into one bucket,
indefinitely. Three questions then need durable answers that no single
snapshot can carry — which snapshots exist, how they chain, and which can
be safely collected:

- **Catalog** — an append-only, atomically-updated record set under
  ``<bucket>/.catalog/``: one small JSON record per committed snapshot
  (job/tenant id, step, wall time, base pointer, chain length, byte
  attribution full-vs-dedup'd), written by rank 0 at commit time — after
  ``.snapshot_metadata`` lands, before the commit barrier releases — so a
  record's existence implies a committed snapshot. Each record is one
  atomic object write; concurrent jobs append without any read-modify-write
  race. The catalog is *advisory and reconstructable*: :meth:`Catalog.rebuild`
  re-derives records by scanning the bucket, and every consumer degrades
  gracefully when records are missing (a lost record just drops that
  snapshot out of its chain — snapshots are physically self-contained, see
  below).

- **Managed delta chains** — ``Snapshot.take(..., job=...)`` auto-selects
  the best ``base=``: the latest committed same-job snapshot from the
  catalog, unless its recorded chain is already ``max_chain_len`` deltas
  deep, in which case the take *rebases to a full snapshot*. Selection runs
  on rank 0 inside the existing preflight round (the resolved base rides
  the preflight broadcast, so every rank agrees by construction), and a
  per-process chain cache makes the steady-state lookup free of storage
  I/O.

- **Retention** — policies (keep-last-K, keep-hourly/daily, pins) computed
  per job over the catalog, whose retained set drives
  :meth:`Snapshot.gc`'s explicit keep-set parameter. The chain-aware
  guarantee: collecting ANY condemned prefix never breaks a retained
  snapshot's bit-exact restore. This holds structurally, not by careful
  bookkeeping: incremental dedup materializes shared objects under every
  snapshot root (fs hard links share inodes; cloud backends server-side
  copy), so each committed snapshot is physically self-contained and a
  delta never *reads through* its base at restore time. The catalog's
  chain-safety validator (:func:`validate_chain_closure`) re-checks that
  invariant against the retained manifests before any deletion, so a
  future layout that DID share bytes across roots would fail loudly
  instead of tearing a live chain.

Chain-aware restore needs no new machinery: the content-addressed read
cache (``storage_plugins/cache.py``) keys data objects by their sidecar
digests, which dedup'd chain objects share — a warm replica following a
chain reads only each delta's new bytes from origin
(``tests/test_catalog.py``).

Crash convergence of retention GC (chaos-tested in ``tests/test_chaos.py``):
condemned snapshots are deleted in a fixed order — ``.snapshot_metadata``
first (the snapshot atomically stops being restorable-from), then the data
tree, then the catalog record last. A crash at any point leaves either a
committed snapshot (nothing deleted yet) or an uncommitted tree whose
still-present record marks it as a half-collected *zombie* that the next
GC run finishes off; records are only removed once their tree is gone.
Re-running GC therefore always converges to exactly the retained set.

See ``docs/lifecycle.md`` for the record schema, the retention-policy
grammar, and the operational guarantees.
"""

from __future__ import annotations

import asyncio
import fnmatch
import hashlib
import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from . import telemetry
from .io_types import ReadIO, StoragePlugin, WriteIO
from .manifest import SNAPSHOT_METADATA_FNAME
from .storage_plugin import url_to_storage_plugin_in_event_loop
from .utils import knobs

logger = logging.getLogger(__name__)

# Everything catalog-owned lives under this prefix of the bucket. Records
# are append-only (one atomic object per committed snapshot); pins are
# marker objects an operator adds/removes explicitly.
CATALOG_DIR = ".catalog"
RECORD_DIR = f"{CATALOG_DIR}/records"
PIN_DIR = f"{CATALOG_DIR}/pins"
# Per-step telemetry rollups (telemetry/steprecord.py) ride beside the
# catalog records: same per-job grouping, same name/step object identity,
# same lifecycle (retention GC keeps a step record exactly as long as its
# snapshot's catalog record).
STEP_TELEMETRY_DIR = f"{CATALOG_DIR}/telemetry"
ROLLOUT_TELEMETRY_DIR = f"{CATALOG_DIR}/rollouts"

# Bump when the record layout changes incompatibly. Loaders skip records
# with a NEWER schema (a downgraded reader must not misinterpret them) and
# accept older ones forever.
CATALOG_SCHEMA_VERSION = 1

# Sentinel scheme carried in the ``base=`` slot through take planning:
# "resolve the base from the catalog on rank 0 during preflight". Never a
# real storage URL.
_AUTO_BASE_SCHEME = "catalog-auto://"

# Per-process chain cache: (bucket_url, job) -> (snapshot name, chain_len)
# of the most recently committed same-job snapshot this process took or
# looked up. Makes steady-state auto-base selection free of storage I/O;
# retention GC invalidates the bucket's entries (a cached base may have
# been condemned). A stale entry is safe regardless: the base fallback
# ladder in snapshot.py degrades a vanished/unreadable base to a full
# snapshot.
_CHAIN_CACHE: Dict[Tuple[str, str], Tuple[str, int]] = {}


# ---------------------------------------------------------------------------
# Bucket/path plumbing
# ---------------------------------------------------------------------------

def split_bucket(path: str) -> Optional[Tuple[str, str]]:
    """Split a snapshot path/URL into ``(bucket_url, snapshot_name)``.

    The bucket is the snapshot's parent prefix — where the catalog lives
    and what retention GC scans. Returns None when the path has no parent
    (a snapshot taken at a filesystem/bucket root has no bucket to catalog
    into; such takes simply go unrecorded)."""
    if "://" in path:
        proto, _, rest = path.partition("://")
        rest = rest.rstrip("/")
        if "/" not in rest or not rest:
            return None
        parent, _, name = rest.rpartition("/")
        if not parent or not name:
            return None
        return f"{proto}://{parent}", name
    p = os.path.abspath(path).rstrip("/")
    parent, name = os.path.split(p)
    if not name or parent in ("", "/", p):
        return None
    return parent, name


def join_bucket(bucket_url: str, name: str) -> str:
    """Inverse of :func:`split_bucket`."""
    return f"{bucket_url.rstrip('/')}/{name}"


def _slug(text: str) -> str:
    """Filesystem/object-safe token for ``text``, collision-disambiguated:
    keeps [A-Za-z0-9_-] verbatim and appends a short content hash whenever
    anything was altered (two jobs must never share a record directory)."""
    safe = re.sub(r"[^A-Za-z0-9_\-]", "_", text) or "_"
    if safe != text:
        safe = f"{safe}-{hashlib.sha1(text.encode()).hexdigest()[:8]}"
    return safe


def _name_key(name: str) -> str:
    """Stable per-snapshot-name token used in record/pin object names: the
    same snapshot path always maps to the same object, so re-taking a name
    overwrites its record atomically instead of accumulating duplicates."""
    return hashlib.sha1(name.encode()).hexdigest()[:12]


def record_path(job: str, name: str, step: int) -> str:
    """Catalog object path (bucket-relative) of one snapshot's record.
    Grouped per job so same-job listing is one prefix scan; the step is
    zero-padded so lexical order is chain order for the common
    monotonic-step case (selection itself sorts numerically)."""
    return (
        f"{RECORD_DIR}/{_slug(job)}/"
        f"{max(0, int(step)):020d}-{_name_key(name)}.json"
    )


def pin_path(name: str) -> str:
    return f"{PIN_DIR}/{_name_key(name)}.json"


def step_record_path(job: str, name: str, step: int) -> str:
    """Catalog object path of one snapshot's step-telemetry record —
    :func:`record_path`'s layout under :data:`STEP_TELEMETRY_DIR`, so a
    re-taken name overwrites its record and same-job listing is one prefix
    scan."""
    return (
        f"{STEP_TELEMETRY_DIR}/{_slug(job)}/"
        f"{max(0, int(step)):020d}-{_name_key(name)}.json"
    )


def rollout_record_path(job: str, name: str, step: Optional[int], rank: int) -> str:
    """Catalog object path of one RANK's rollout (restore-side) record.
    Same layout as :func:`step_record_path` under a ``rollouts/`` sibling,
    with the rank in the filename: restores append per-process (there is no
    commit barrier to elect a merger behind), so per-rank files avoid
    last-writer-wins collisions by construction."""
    return (
        f"{ROLLOUT_TELEMETRY_DIR}/{_slug(job)}/"
        f"{max(0, int(step or 0)):020d}-{_name_key(name)}_r{int(rank)}.json"
    )


def _run(coro, loop: Optional[asyncio.AbstractEventLoop]):
    if loop is not None:
        return loop.run_until_complete(coro)
    inner = asyncio.new_event_loop()
    try:
        return inner.run_until_complete(coro)
    finally:
        inner.close()


# ---------------------------------------------------------------------------
# Records
# ---------------------------------------------------------------------------

@dataclass
class CatalogRecord:
    """One committed snapshot, as the catalog knows it.

    ``bytes_total`` is the snapshot's full logical payload (every storage
    object's size, from its own checksum sidecars); ``bytes_deduped`` is
    the share of that payload whose content identity (v1 whole-object
    sha256 or v2 tree-digest root) already existed in the base snapshot's
    sidecars — i.e. bytes the incremental machinery could share instead of
    rewriting; ``bytes_written`` is the remainder (the delta's new bytes).
    Derived from sidecar digests, so the attribution needs no collectives
    and is exact up to link-in failures (a failed hard link falls back to
    a full write but still counts as dedup-shareable here). All three are
    0 when sidecars were unavailable (checksums off)."""

    name: str
    job: str
    step: int
    wall_time: float
    base: Optional[str] = None  # base snapshot NAME (same bucket) or path
    chain_len: int = 0  # 0 = full snapshot; k = k-th delta of its chain
    world_size: int = 1
    bytes_total: int = 0
    bytes_written: int = 0
    bytes_deduped: int = 0
    schema: int = CATALOG_SCHEMA_VERSION
    # Bucket-relative catalog object this record was loaded from (absent on
    # freshly-built records until append assigns it). Not serialized.
    path: Optional[str] = field(default=None, compare=False)

    def to_json(self) -> str:
        return json.dumps(
            {
                "schema": self.schema,
                "name": self.name,
                "job": self.job,
                "step": self.step,
                "wall_time": self.wall_time,
                "base": self.base,
                "chain_len": self.chain_len,
                "world_size": self.world_size,
                "bytes_total": self.bytes_total,
                "bytes_written": self.bytes_written,
                "bytes_deduped": self.bytes_deduped,
            },
            sort_keys=True,
        )

    @classmethod
    def from_json(cls, s: str) -> "CatalogRecord":
        d = json.loads(s)
        if not isinstance(d, dict):
            raise ValueError("catalog record is not a JSON object")
        return cls(
            name=str(d["name"]),
            job=str(d.get("job", "")),
            step=int(d.get("step", -1)),
            wall_time=float(d.get("wall_time", 0.0)),
            base=d.get("base"),
            chain_len=int(d.get("chain_len", 0)),
            world_size=int(d.get("world_size", 1)),
            bytes_total=int(d.get("bytes_total", 0)),
            bytes_written=int(d.get("bytes_written", 0)),
            bytes_deduped=int(d.get("bytes_deduped", 0)),
            schema=int(d.get("schema", 1)),
        )

    @property
    def order_key(self) -> Tuple[int, float, str]:
        """Recency order within one job: step first (the training clock),
        wall time as the tiebreak, name last for determinism."""
        return (self.step, self.wall_time, self.name)


class Catalog:
    """Handle on one bucket's catalog. Opens the bucket through the same
    ``url_to_storage_plugin`` stack snapshots use (read cache and fault
    injection wrap it identically), on a caller-owned or private event
    loop. Cheap to construct; close() releases the plugin."""

    def __init__(
        self,
        bucket_url: str,
        event_loop: Optional[asyncio.AbstractEventLoop] = None,
        storage: Optional[StoragePlugin] = None,
    ) -> None:
        self.bucket_url = bucket_url
        self._own_loop = event_loop is None
        self._loop = event_loop or asyncio.new_event_loop()
        self._own_storage = storage is None
        self._storage = storage or url_to_storage_plugin_in_event_loop(
            bucket_url, self._loop
        )

    def close(self) -> None:
        if self._own_storage:
            self._storage.sync_close(self._loop)
        if self._own_loop:
            self._loop.close()

    def __enter__(self) -> "Catalog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------- append
    def append(self, record: CatalogRecord) -> bool:
        """Atomically write one record (one object; plugin writes are
        atomic). Returns False on any failure — the catalog is fail-open:
        a missed append degrades the chain/retention view, never the
        snapshot commit it rides alongside."""
        path = record_path(record.job, record.name, record.step)
        try:
            with telemetry.span(
                "catalog.append", cat="catalog", path=path
            ):
                self._storage.sync_write(
                    WriteIO(path=path, buf=record.to_json().encode()),
                    self._loop,
                )
            record.path = path
            telemetry.counter_add("catalog.appends")
            return True
        except Exception:  # noqa: BLE001 - fail-open by contract
            telemetry.counter_add("catalog.append_failures")
            logger.warning(
                "catalog append for %s under %s failed (snapshot commit "
                "unaffected; `catalog rebuild` can reconstruct the record)",
                record.name,
                self.bucket_url,
                exc_info=True,
            )
            return False

    def append_step_telemetry(self, record: Dict[str, Any]) -> bool:
        """Atomically write one step-telemetry record (built by
        ``telemetry.steprecord.build_step_record``) beside the snapshot's
        catalog record. Fail-open like :meth:`append` — a missed record
        loses one point of the trend line, never the commit, and the point
        is rebuildable from the snapshot's per-rank artifacts."""
        path = step_record_path(
            str(record.get("job", "")),
            str(record.get("name", "")),
            int(record.get("step", 0)),
        )
        try:
            from .telemetry import steprecord

            with telemetry.span(
                "catalog.step_append", cat="catalog", path=path
            ):
                self._storage.sync_write(
                    WriteIO(
                        path=path, buf=steprecord.dumps_step_record(record)
                    ),
                    self._loop,
                )
            telemetry.counter_add("catalog.step_appends")
            return True
        except Exception:  # noqa: BLE001 - fail-open by contract
            telemetry.counter_add("catalog.step_append_failures")
            logger.warning(
                "step-telemetry append for %s under %s failed (snapshot "
                "commit unaffected; the record is rebuildable from the "
                "snapshot's .telemetry artifacts)",
                record.get("name"),
                self.bucket_url,
                exc_info=True,
            )
            return False

    def load_step_telemetry(
        self, job: Optional[str] = None
    ) -> List[Dict[str, Any]]:
        """All readable step-telemetry records, step order (per job),
        de-duplicated by snapshot name — the step series the health
        detectors and the ``timeline`` CLI run over. Unreadable or
        newer-schema records are skipped with one warning each."""
        from .telemetry import steprecord

        prefix = (
            STEP_TELEMETRY_DIR
            if job is None
            else f"{STEP_TELEMETRY_DIR}/{_slug(job)}"
        )
        with telemetry.span("catalog.step_scan", cat="catalog", path=prefix):
            try:
                paths = _run(self._storage.list_prefix(prefix), self._loop)
            except FileNotFoundError:
                return []
            by_name: Dict[str, Dict[str, Any]] = {}
            for p in sorted(paths):
                if not p.endswith(".json"):
                    continue
                try:
                    read_io = ReadIO(path=p)
                    self._storage.sync_read(read_io, self._loop)
                    rec = steprecord.parse_step_record(
                        read_io.buf.getvalue()
                    )
                except Exception:  # noqa: BLE001 - degrade, never fail
                    logger.warning(
                        "unreadable step-telemetry record %s under %s "
                        "(skipped)",
                        p,
                        self.bucket_url,
                        exc_info=True,
                    )
                    continue
                if job is not None and rec.get("job") != job:
                    continue
                key = str(rec.get("name", p))
                prev = by_name.get(key)
                if prev is None or (
                    rec.get("step", 0),
                    rec.get("created_unix", 0.0),
                ) >= (prev.get("step", 0), prev.get("created_unix", 0.0)):
                    by_name[key] = rec
        return sorted(
            by_name.values(),
            key=lambda r: (r.get("step", 0), r.get("created_unix", 0.0)),
        )

    def append_rollout_record(self, record: Dict[str, Any]) -> bool:
        """Atomically write one rank's rollout (restore-side) record —
        built by ``telemetry.steprecord.build_rollout_record``. Fail-open
        like :meth:`append_step_telemetry`: a missed record loses one point
        of the restore trend line, never the restore itself."""
        path = rollout_record_path(
            str(record.get("job", "")),
            str(record.get("name", "")),
            record.get("step"),
            int(record.get("rank", 0) or 0),
        )
        try:
            from .telemetry import steprecord

            with telemetry.span(
                "catalog.rollout_append", cat="catalog", path=path
            ):
                self._storage.sync_write(
                    WriteIO(
                        path=path, buf=steprecord.dumps_rollout_record(record)
                    ),
                    self._loop,
                )
            telemetry.counter_add("catalog.rollout_appends")
            return True
        except Exception:  # noqa: BLE001 - fail-open by contract
            telemetry.counter_add("catalog.rollout_append_failures")
            logger.warning(
                "rollout record append for %s under %s failed (restore "
                "unaffected)",
                record.get("name"),
                self.bucket_url,
                exc_info=True,
            )
            return False

    def load_rollout_telemetry(
        self, job: Optional[str] = None
    ) -> List[Dict[str, Any]]:
        """All readable rollout records, (step, rank, created) order.
        Per-rank records are NOT merged — restore skew across ranks is the
        signal. Unreadable or newer-schema records are skipped with one
        warning each."""
        from .telemetry import steprecord

        prefix = (
            ROLLOUT_TELEMETRY_DIR
            if job is None
            else f"{ROLLOUT_TELEMETRY_DIR}/{_slug(job)}"
        )
        out: List[Dict[str, Any]] = []
        with telemetry.span(
            "catalog.rollout_scan", cat="catalog", path=prefix
        ):
            try:
                paths = _run(self._storage.list_prefix(prefix), self._loop)
            except FileNotFoundError:
                return []
            for p in sorted(paths):
                if not p.endswith(".json"):
                    continue
                try:
                    read_io = ReadIO(path=p)
                    self._storage.sync_read(read_io, self._loop)
                    rec = steprecord.parse_rollout_record(
                        read_io.buf.getvalue()
                    )
                except Exception:  # noqa: BLE001 - degrade, never fail
                    logger.warning(
                        "unreadable rollout record %s under %s (skipped)",
                        p,
                        self.bucket_url,
                        exc_info=True,
                    )
                    continue
                if job is not None and rec.get("job") != job:
                    continue
                out.append(rec)
        return sorted(
            out,
            key=lambda r: (
                r.get("step") or 0,
                r.get("rank", 0),
                r.get("created_unix", 0.0),
            ),
        )

    # --------------------------------------------------------------- load
    def load(self, job: Optional[str] = None) -> List[CatalogRecord]:
        """All readable records, newest last (per-job ``order_key`` order
        interleaved by job), de-duplicated by snapshot name (the newest
        record wins — a re-taken name supersedes its older record).
        Unreadable or newer-schema records are skipped with a warning;
        ``job=`` filters on the record body (not the directory slug)."""
        prefix = RECORD_DIR if job is None else f"{RECORD_DIR}/{_slug(job)}"
        self.last_scan_skipped = 0
        with telemetry.span("catalog.scan", cat="catalog", path=prefix):
            paths = _run(self._storage.list_prefix(prefix), self._loop)
            by_name: Dict[str, CatalogRecord] = {}
            for p in sorted(paths):
                if not p.endswith(".json"):
                    continue
                rec = self._read_record(p)
                if rec is None:
                    self.last_scan_skipped += 1
                    continue
                if job is not None and rec.job != job:
                    continue
                prev = by_name.get(rec.name)
                if prev is None or rec.order_key >= prev.order_key:
                    by_name[rec.name] = rec
        records = sorted(by_name.values(), key=lambda r: r.order_key)
        telemetry.counter_add("catalog.records_scanned", len(records))
        return records

    def _read_record(self, path: str) -> Optional[CatalogRecord]:
        try:
            read_io = ReadIO(path=path)
            self._storage.sync_read(read_io, self._loop)
            rec = CatalogRecord.from_json(read_io.buf.getvalue().decode())
        except Exception:  # noqa: BLE001 - degrade, never fail a scan
            logger.warning(
                "unreadable catalog record %s under %s (skipped)",
                path,
                self.bucket_url,
                exc_info=True,
            )
            return None
        if rec.schema > CATALOG_SCHEMA_VERSION:
            logger.warning(
                "catalog record %s has schema %d > supported %d (skipped; "
                "upgrade this reader)",
                path,
                rec.schema,
                CATALOG_SCHEMA_VERSION,
            )
            return None
        rec.path = path
        return rec

    def latest(self, job: str) -> Optional[CatalogRecord]:
        records = self.load(job=job)
        return records[-1] if records else None

    # --------------------------------------------------------------- pins
    def pins(self) -> Set[str]:
        """Names of pinned snapshots (never condemned by any policy)."""
        out: Set[str] = set()
        try:
            for p in _run(self._storage.list_prefix(PIN_DIR), self._loop):
                try:
                    read_io = ReadIO(path=p)
                    self._storage.sync_read(read_io, self._loop)
                    out.add(str(json.loads(read_io.buf.getvalue())["name"]))
                except Exception:  # noqa: BLE001 - skip unreadable pin
                    logger.warning("unreadable pin %s (skipped)", p)
        except Exception:  # noqa: BLE001 - no pin dir == no pins
            pass
        return out

    def pin(self, name: str) -> None:
        self._storage.sync_write(
            WriteIO(
                path=pin_path(name), buf=json.dumps({"name": name}).encode()
            ),
            self._loop,
        )

    def unpin(self, name: str) -> bool:
        try:
            _run(self._storage.delete(pin_path(name)), self._loop)
            return True
        except FileNotFoundError:
            return False

    # ------------------------------------------------------------ rebuild
    def rebuild(self) -> List[CatalogRecord]:
        """Reconstruct missing records by scanning the bucket for committed
        snapshots: any child tree carrying ``.snapshot_metadata`` that no
        readable record names gets a synthesized record (job unknown →
        ``""``, step parsed from trailing digits of the name, wall time and
        base unknown). Existing records are never rewritten. Returns the
        records written. Memory-backed buckets cannot be scanned (their
        roots are disjoint namespaces) and rebuild returns []."""
        existing = {r.name for r in self.load()}
        written: List[CatalogRecord] = []
        try:
            all_paths = _run(self._storage.list_prefix(""), self._loop)
        except Exception:  # noqa: BLE001 - unlistable bucket: nothing to do
            logger.warning(
                "catalog rebuild: cannot list %s", self.bucket_url,
                exc_info=True,
            )
            return []
        roots = sorted(
            {
                p.partition("/")[0]
                for p in all_paths
                if "/" in p and not p.startswith(f"{CATALOG_DIR}/")
            }
        )
        for root in roots:
            if root in existing:
                continue
            meta_path = f"{root}/{SNAPSHOT_METADATA_FNAME}"
            if meta_path not in all_paths:
                continue
            try:
                from .manifest import SnapshotMetadata

                read_io = ReadIO(path=meta_path)
                self._storage.sync_read(read_io, self._loop)
                metadata = SnapshotMetadata.from_json(
                    read_io.buf.getvalue().decode()
                )
            except Exception:  # noqa: BLE001 - torn metadata: skip
                logger.warning(
                    "catalog rebuild: unreadable metadata for %s (skipped)",
                    root,
                    exc_info=True,
                )
                continue
            m = re.search(r"(\d+)$", root)
            rec = CatalogRecord(
                name=root,
                job="",
                step=int(m.group(1)) if m else -1,
                wall_time=0.0,
                base=None,
                chain_len=0,
                world_size=metadata.world_size,
            )
            if self.append(rec):
                written.append(rec)
        return written


# ---------------------------------------------------------------------------
# Auto-base selection (managed delta chains)
# ---------------------------------------------------------------------------

def auto_base_token(job: str, max_chain_len: int) -> str:
    """The ``base=`` sentinel ``Snapshot.take(job=...)`` plants for the
    preflight round to resolve on rank 0 (one reader, every rank receives
    the same resolved base via the existing preflight broadcast)."""
    return f"{_AUTO_BASE_SCHEME}{max(1, int(max_chain_len))}/{job}"


def is_auto_base(base: Optional[str]) -> bool:
    return bool(base) and str(base).startswith(_AUTO_BASE_SCHEME)


def parse_auto_base(token: str) -> Tuple[str, int]:
    """(job, max_chain_len) from an auto-base token."""
    rest = token[len(_AUTO_BASE_SCHEME):]
    max_str, _, job = rest.partition("/")
    return job, max(1, int(max_str))


def note_commit(bucket_url: str, job: str, name: str, chain_len: int) -> None:
    """Record a just-committed snapshot in the per-process chain cache so
    the next same-job take selects it without storage I/O. Called on EVERY
    rank (the cache is process-local; all ranks hold the same canonical
    path/job from preflight)."""
    _CHAIN_CACHE[(bucket_url, job)] = (name, chain_len)


def invalidate_chain_cache(bucket_url: str) -> None:
    """Drop the bucket's cached chain heads (retention GC may have
    condemned them). A stale survivor would still be safe — the base
    fallback ladder degrades a vanished base to a full snapshot — but
    invalidating keeps steady-state takes on real chains."""
    for key in [k for k in _CHAIN_CACHE if k[0] == bucket_url]:
        _CHAIN_CACHE.pop(key, None)


def resolve_auto_base(
    token: str, snapshot_path: str
) -> Tuple[Optional[str], int]:
    """Resolve an auto-base token against the catalog of ``snapshot_path``'s
    bucket. Returns ``(base_path_or_None, base_chain_len)``:

    - the latest committed same-job snapshot, from the per-process chain
      cache when warm (zero storage I/O in steady state) else a catalog
      scan, as a full path the incremental loader accepts;
    - ``(None, 0)`` — take a FULL snapshot — when the catalog knob is off,
      the bucket has no catalog / no same-job record, the candidate's
      chain is already ``max_chain_len`` deltas deep (the rebase-to-full
      policy), or anything at all fails (fail-open, like every other
      degrade on the base ladder).
    """
    try:
        job, max_chain = parse_auto_base(token)
    except Exception:  # noqa: BLE001 - malformed token: full snapshot
        logger.warning("malformed auto-base token %r; taking a full snapshot",
                       token)
        return None, 0
    if not knobs.is_catalog_enabled():
        return None, 0
    split = split_bucket(snapshot_path)
    if split is None:
        return None, 0
    bucket, _name = split
    cached = _CHAIN_CACHE.get((bucket, job))
    if cached is not None:
        name, chain_len = cached
        if chain_len + 1 > max_chain:
            logger.info(
                "job %s: chain at %s is %d deltas deep (max %d); rebasing "
                "to a full snapshot",
                job, name, chain_len, max_chain,
            )
            return None, 0
        return join_bucket(bucket, name), chain_len
    try:
        with Catalog(bucket) as cat:
            records = cat.load(job=job)
            # Newest first; probe that the candidate is still a committed,
            # present snapshot (retention GC may have condemned it after
            # the record was read — or a crash left a zombie record). A
            # bounded number of probes: an entirely stale chain degrades
            # to a full snapshot rather than an unbounded scan.
            for rec in list(reversed(records))[:3]:
                if _metadata_exists(join_bucket(bucket, rec.name)):
                    note_commit(bucket, job, rec.name, rec.chain_len)
                    if rec.chain_len + 1 > max_chain:
                        logger.info(
                            "job %s: chain at %s is %d deltas deep (max "
                            "%d); rebasing to a full snapshot",
                            job, rec.name, rec.chain_len, max_chain,
                        )
                        return None, 0
                    return join_bucket(bucket, rec.name), rec.chain_len
    except Exception:  # noqa: BLE001 - fail-open: full snapshot
        logger.warning(
            "auto-base selection for job %s under %s failed; taking a "
            "full snapshot",
            job, snapshot_path, exc_info=True,
        )
    return None, 0


def _metadata_exists(snapshot_url: str) -> bool:
    loop = asyncio.new_event_loop()
    try:
        storage = url_to_storage_plugin_in_event_loop(snapshot_url, loop)
        try:
            read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
            storage.sync_read(read_io, loop)
            return True
        except Exception:  # noqa: BLE001 - absent or unreadable: not usable
            return False
        finally:
            storage.sync_close(loop)
    finally:
        loop.close()


def chain_len_of_base(bucket_url: str, base: str) -> int:
    """Chain length this snapshot acquires by building on ``base`` (an
    EXPLICIT ``base=`` whose record may or may not exist): the base's
    recorded chain + 1, or 1 when the base is unrecorded / out-of-bucket
    (conservative: an unknown base is assumed to be a full snapshot)."""
    split = split_bucket(base)
    if split is None or split[0] != bucket_url:
        return 1
    base_name = split[1]
    try:
        with Catalog(bucket_url) as cat:
            for rec in reversed(cat.load()):
                if rec.name == base_name:
                    return rec.chain_len + 1
    except Exception:  # noqa: BLE001 - unknown base: assume full
        pass
    return 1


# ---------------------------------------------------------------------------
# Byte attribution (full vs dedup'd), from checksum sidecars
# ---------------------------------------------------------------------------

def byte_attribution(
    storage: StoragePlugin,
    world_size: int,
    base_url: Optional[str],
    event_loop: asyncio.AbstractEventLoop,
) -> Tuple[int, int, int]:
    """(bytes_total, bytes_written, bytes_deduped) of a just-committed
    snapshot: totals from its own checksum sidecars; the dedup share is
    every object whose (size, content key) also appears in the BASE's
    sidecars — i.e. bytes the chain shares rather than re-stores. No
    collectives: rank 0 computes it alone at append time. (0, 0, 0) when
    sidecars are unavailable (checksums off)."""
    from . import hashing
    from .snapshot import _read_checksum_sidecars

    try:
        merged, _, _ = _read_checksum_sidecars(storage, world_size, event_loop)
    except Exception:  # noqa: BLE001 - attribution is best-effort
        return 0, 0, 0
    base_keys: Set[Tuple[int, str]] = set()
    if base_url:
        loop = asyncio.new_event_loop()
        try:
            base_storage = url_to_storage_plugin_in_event_loop(base_url, loop)
            try:
                from .manifest import SnapshotMetadata

                read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
                base_storage.sync_read(read_io, loop)
                base_world = SnapshotMetadata.from_json(
                    read_io.buf.getvalue().decode()
                ).world_size
                base_merged, _, _ = _read_checksum_sidecars(
                    base_storage, base_world, loop
                )
                for rec in base_merged.values():
                    size = hashing.record_size(rec)
                    if size is None:
                        continue
                    for key in hashing.record_content_keys(rec):
                        base_keys.add((size, key))
            finally:
                base_storage.sync_close(loop)
        except Exception:  # noqa: BLE001 - no base view: all bytes "new"
            base_keys = set()
        finally:
            loop.close()
    total = written = deduped = 0
    for rec in merged.values():
        size = hashing.record_size(rec)
        if size is None:
            continue
        total += size
        if base_keys and any(
            (size, key) in base_keys
            for key in hashing.record_content_keys(rec)
        ):
            deduped += size
        else:
            written += size
    return total, written, deduped


# ---------------------------------------------------------------------------
# Retention policies
# ---------------------------------------------------------------------------

@dataclass
class RetentionPolicy:
    """Parsed retention policy, applied per job. Grammar (comma-separated
    ``key=value`` clauses; see docs/lifecycle.md)::

        last=<K>      keep the newest K snapshots of each job
        hourly=<H>    additionally keep the newest snapshot of each of the
                      last H distinct hours (by record wall time)
        daily=<D>     ...and of each of the last D distinct days
        job=<glob>    restrict the policy to matching job ids (others are
                      fully retained); repeatable

    Pinned snapshots are always retained, whatever the clauses say. A
    policy with no clauses retains everything (the explicit no-op)."""

    last: Optional[int] = None
    hourly: Optional[int] = None
    daily: Optional[int] = None
    job_globs: List[str] = field(default_factory=list)

    @classmethod
    def parse(cls, spec: str) -> "RetentionPolicy":
        policy = cls()
        spec = (spec or "").strip()
        if not spec:
            return policy
        for clause in spec.split(","):
            clause = clause.strip()
            if not clause:
                continue
            key, sep, value = clause.partition("=")
            if not sep:
                raise ValueError(
                    f"retention clause {clause!r} is not key=value"
                )
            key = key.strip()
            value = value.strip()
            if key in ("last", "hourly", "daily"):
                try:
                    count = int(value)
                except ValueError:
                    raise ValueError(
                        f"retention clause {clause!r}: {value!r} is not an "
                        "integer"
                    ) from None
                if count < 0:
                    raise ValueError(
                        f"retention clause {clause!r}: negative counts are "
                        "meaningless"
                    )
                setattr(policy, key, count)
            elif key == "job":
                policy.job_globs.append(value)
            else:
                raise ValueError(
                    f"unknown retention clause {key!r} (grammar: last=K, "
                    "hourly=H, daily=D, job=<glob>)"
                )
        return policy

    def applies_to(self, job: str) -> bool:
        if not self.job_globs:
            return True
        return any(fnmatch.fnmatch(job, g) for g in self.job_globs)

    def retained(
        self, records: List[CatalogRecord], now: Optional[float] = None
    ) -> Set[str]:
        """Names retained from ONE job's records (any order)."""
        ordered = sorted(records, key=lambda r: r.order_key, reverse=True)
        if self.last is None and self.hourly is None and self.daily is None:
            return {r.name for r in ordered}
        keep: Set[str] = set()
        if self.last:
            keep.update(r.name for r in ordered[: self.last])
        for clause, bucket_s in (("hourly", 3600), ("daily", 86400)):
            count = getattr(self, clause)
            if not count:
                continue
            seen_buckets: Set[int] = set()
            for r in ordered:  # newest first: first hit per bucket wins
                if r.wall_time <= 0:
                    continue  # synthesized/rebuilt record: no wall clock
                b = int(r.wall_time // bucket_s)
                if b not in seen_buckets:
                    seen_buckets.add(b)
                    keep.add(r.name)
                if len(seen_buckets) >= count:
                    break
        return keep


@dataclass
class RetentionPlan:
    """What a policy run would keep and collect."""

    retained: List[str]
    condemned: List[str]
    pinned: List[str]
    by_job: Dict[str, Dict[str, List[str]]]


def plan_retention(
    records: List[CatalogRecord],
    pins: Set[str],
    policy: RetentionPolicy,
    now: Optional[float] = None,
) -> RetentionPlan:
    """Apply ``policy`` per job over the catalog. Pins always retain; jobs
    the policy's ``job=`` globs exclude are fully retained. Condemned =
    recorded, committed-at-record-time snapshots the policy drops — any
    PREFIX of a chain may land here: snapshots are self-contained (see the
    module docstring), so collecting a retained delta's base never breaks
    the delta's restore."""
    by_job: Dict[str, List[CatalogRecord]] = {}
    for r in records:
        by_job.setdefault(r.job, []).append(r)
    retained: Set[str] = set()
    per_job: Dict[str, Dict[str, List[str]]] = {}
    for job, recs in sorted(by_job.items()):
        if not policy.applies_to(job):
            kept = {r.name for r in recs}
        else:
            kept = policy.retained(recs, now=now)
        kept |= pins & {r.name for r in recs}
        retained |= kept
        per_job[job] = {
            "retained": sorted(kept),
            "condemned": sorted({r.name for r in recs} - kept),
        }
    all_names = {r.name for r in records}
    condemned = sorted(all_names - retained)
    return RetentionPlan(
        retained=sorted(retained),
        condemned=condemned,
        pinned=sorted(pins & all_names),
        by_job=per_job,
    )


def validate_chain_closure(
    bucket_url: str,
    retained: List[str],
    condemned: List[str],
) -> None:
    """The chain-aware safety check run before any retention deletion:
    every storage object a RETAINED snapshot's manifest references must
    live under a retained root. Today that holds structurally (manifest
    locations are snapshot-root-relative; dedup materializes shared
    objects under every root as hard links / server-side copies), so this
    walk is a cheap invariant re-check — but a future layout that stored
    chain-shared objects once, outside the deltas, would trip it HERE
    instead of silently tearing a retained snapshot's restore. Raises
    ``RuntimeError`` naming the violating references."""
    from .manifest import SnapshotMetadata
    from .snapshot import _manifest_storage_locations

    condemned_set = set(condemned)
    violations: List[str] = []
    loop = asyncio.new_event_loop()
    try:
        for name in retained:
            url = join_bucket(bucket_url, name)
            try:
                storage = url_to_storage_plugin_in_event_loop(url, loop)
                try:
                    read_io = ReadIO(path=SNAPSHOT_METADATA_FNAME)
                    storage.sync_read(read_io, loop)
                    metadata = SnapshotMetadata.from_json(
                        read_io.buf.getvalue().decode()
                    )
                finally:
                    storage.sync_close(loop)
            except Exception:  # noqa: BLE001 - unreadable retained manifest
                # Retention must not delete anything whose keep-set it
                # cannot compute; the caller surfaces this as a hard error.
                raise RuntimeError(
                    f"retention: cannot read the manifest of retained "
                    f"snapshot {name!r} under {bucket_url} — refusing to "
                    "collect anything"
                ) from None
            for loc in _manifest_storage_locations(metadata.manifest):
                # Locations are root-relative by construction; an absolute
                # or parent-escaping location would reach outside this
                # snapshot's root — exactly what a condemned-prefix delete
                # could tear.
                if loc.startswith(("/", "..")) or any(
                    loc.startswith(f"{c}/") for c in condemned_set
                ):
                    violations.append(f"{name}: {loc}")
    finally:
        loop.close()
    if violations:
        raise RuntimeError(
            "retention: retained snapshots reference objects outside their "
            "own roots (collecting the condemned set would tear them): "
            + "; ".join(sorted(violations)[:8])
        )


def retain(
    bucket_url: str,
    policy: RetentionPolicy,
    dry_run: bool = True,
    now: Optional[float] = None,
) -> Dict[str, Any]:
    """The retention engine: plan per-job retention over the catalog,
    validate chain closure, and drive :meth:`Snapshot.gc`'s shared
    deletion path with the explicit keep-set. Only RECORDED snapshots are
    ever condemned, and uncommitted record-less trees are left alone
    (in-flight takes are indistinguishable from crashes here — the plain
    whole-bucket ``Snapshot.gc`` reclaims those, with its documented
    don't-run-concurrently caveat). Returns the gc report extended with
    the plan."""
    from .snapshot import Snapshot

    with Catalog(bucket_url) as cat:
        records = cat.load()
        pins = cat.pins()
        skipped = getattr(cat, "last_scan_skipped", 0)
    if skipped:
        # Unreadable records are fail-open at the SAFE end: their
        # snapshots cannot be condemned (gc only condemns roots in the
        # record universe) — the bucket over-retains until the records
        # are readable again or rebuilt.
        logger.warning(
            "retention under %s: %d catalog record(s) unreadable — their "
            "snapshots are implicitly retained this run (rebuild the "
            "catalog to reclaim them)",
            bucket_url,
            skipped,
        )
    plan = plan_retention(records, pins, policy, now=now)
    if plan.condemned:
        validate_chain_closure(bucket_url, plan.retained, plan.condemned)
    report = Snapshot.gc(
        bucket_url,
        dry_run=dry_run,
        keep_roots=set(plan.retained) | pins,
        roots=[r.name for r in records],
        collect_debris=False,
    )
    report["policy"] = {
        "retained": plan.retained,
        "condemned": plan.condemned,
        "pinned": plan.pinned,
        "by_job": plan.by_job,
    }
    if not dry_run:
        telemetry.counter_add("gc.roots_condemned", len(plan.condemned))
        # Cached chain heads may be among the condemned; the next
        # auto-base take re-reads the catalog.
        invalidate_chain_cache(bucket_url)
    return report


def chain_of(
    records: List[CatalogRecord], name: str
) -> List[CatalogRecord]:
    """The base chain ending at ``name``, oldest first, as far back as the
    records reach (display/diagnostics — restore never walks this)."""
    by_name = {r.name: r for r in records}
    chain: List[CatalogRecord] = []
    cur = by_name.get(name)
    seen: Set[str] = set()
    while cur is not None and cur.name not in seen:
        seen.add(cur.name)
        chain.append(cur)
        cur = by_name.get(cur.base) if cur.base else None
    return list(reversed(chain))
