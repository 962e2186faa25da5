"""Debug-mode durable-effect journal: the crash-state explorer's input.

Every crash-consistency claim the lifecycle layer makes ("temp+rename is
the commit point", "publish the catalog record only after the payload is
durable", "GC may only delete outside the keep-set") is a claim about the
ORDER in which durable effects reach storage. The static TSA10xx
durability-discipline pass (``dev/analyze/durability_discipline.py``)
checks the order in the source; this module observes it at runtime: when
the ``TORCHSNAPSHOT_TPU_DEBUG_EFFECTS`` knob is set,
``url_to_storage_plugin`` wraps every plugin it constructs in an
:class:`EffectRecordingPlugin` that appends one sequence-numbered
:class:`Effect` per mutating op — op class, path, payload, content
fingerprint, and the originating call site above the storage plumbing.

The journal deliberately sits at the BOTTOM of the wrapper stack (below
the fault injector, directly above the real backend): an op a fault rule
suppresses (a torn write's temp-file debris included: no object ever
appeared) never reached storage and is never journaled — the journal is
the ground truth of what a crash at any instant could have left behind.
The
crash-state explorer (``dev/crash_explorer.py``) replays every journal
prefix into a fresh store and asserts each one is a restorable crash
state, naming the effect seq and call site when one is not.

Off (the default), nothing here is imported and the only cost is the one
knob check ``url_to_storage_plugin`` already performs — the same
zero-allocation contract as the budget ledger and the collective tracer.
Payloads are retained by default (the explorer needs real bytes to
replay); journaled runs are test-sized by design.
"""

from __future__ import annotations

import asyncio
import hashlib
import os
import threading
import traceback
from dataclasses import dataclass
from typing import List, Optional

from .io_types import ReadIO, StoragePlugin, WriteIO

# Mutating op classes, aligned with ``faults._OPS`` so a journal entry and
# a kill-point rule name the same thing.
MUTATING_OPS = ("write", "delete", "link")


def _fingerprint(data) -> str:
    if data is None:
        return "-"
    return hashlib.sha1(bytes(data)).hexdigest()[:12]


def _origin_site() -> str:
    """file:line(function) of the frame that initiated the mutation — the
    first frame below the journal/plugin/fault-injection plumbing."""
    _plumbing = (
        "effect_journal.py", "faults.py", "io_types.py", "cloud_retry.py",
    )
    for frame in reversed(traceback.extract_stack()):
        if os.path.basename(frame.filename) in _plumbing:
            continue
        if frame.name in ("run", "_retrying"):
            continue  # the fault injector's retry shims
        norm = frame.filename.replace(os.sep, "/")
        if "/asyncio/" in norm or "/concurrent/" in norm:
            continue  # event-loop / executor internals between coro steps
        filename = frame.filename
        marker = "torchsnapshot_tpu"
        idx = filename.rfind(marker)
        if idx != -1:
            filename = filename[idx:]
        else:
            filename = filename.rsplit("/", 1)[-1]
        return f"{filename}:{frame.lineno} ({frame.name})"
    return "<unknown>"


@dataclass(frozen=True)
class Effect:
    """One durable mutation, as observed at the storage boundary.

    ``seq`` is process-wide and monotonic across every journaled plugin:
    the total order a single-process crash could truncate. ``payload``
    is a private copy of the written bytes (None for delete), retained so
    the explorer can replay the effect bit-exactly."""

    seq: int
    op: str
    origin: str  # the plugin root/url the effect targeted
    path: str
    nbytes: int
    fingerprint: str
    site: str
    payload: Optional[bytes] = None

    def render(self) -> str:
        return (
            f"#{self.seq} {self.op} {self.path} ({self.nbytes}B "
            f"{self.fingerprint}) at {self.site}"
        )


class EffectJournal:
    """Process-wide, thread-safe, append-only journal of durable effects."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._effects: List[Effect] = []

    def record(
        self,
        op: str,
        origin: str,
        path: str,
        payload=None,
    ) -> Effect:
        data = None if payload is None else bytes(payload)
        site = _origin_site()
        with self._lock:
            effect = Effect(
                seq=len(self._effects),
                op=op,
                origin=origin,
                path=path,
                nbytes=0 if data is None else len(data),
                fingerprint=_fingerprint(data),
                site=site,
                payload=data,
            )
            self._effects.append(effect)
        return effect

    def effects(self) -> List[Effect]:
        """A point-in-time copy, seq order."""
        with self._lock:
            return list(self._effects)

    def __len__(self) -> int:
        with self._lock:
            return len(self._effects)

    def clear(self) -> None:
        with self._lock:
            self._effects.clear()


# ---------------------------------------------------------------------------
# Process-wide instance. Like the flight recorder, `_JOURNAL is None` IS the
# disabled state; the knob is read once, at first use.
# ---------------------------------------------------------------------------

_JOURNAL: Optional[EffectJournal] = None
_INITIALIZED = False
_INIT_LOCK = threading.Lock()


def _init() -> None:
    global _JOURNAL, _INITIALIZED
    from .utils import knobs

    with _INIT_LOCK:
        if _INITIALIZED:
            return
        if knobs.is_debug_effects_enabled():
            _JOURNAL = EffectJournal()
        _INITIALIZED = True


def get_journal() -> Optional[EffectJournal]:
    """The process-wide journal, or None when the knob disables it. Tests
    that override the knob call :func:`reset` to re-evaluate."""
    if not _INITIALIZED:
        _init()
    return _JOURNAL


def reset() -> None:
    """Drop the process-wide journal and re-read the knob at next use."""
    global _JOURNAL, _INITIALIZED
    with _INIT_LOCK:
        _JOURNAL = None
        _INITIALIZED = False


class EffectRecordingPlugin(StoragePlugin):
    """Wraps any :class:`StoragePlugin`; journals every mutating op.

    Non-mutating ops (read / list_prefix / prune_empty / close) proxy
    straight through. Completed atomic ops (write, link_in) journal AFTER
    the inner op succeeds — an op the backend rejected never became
    durable."""

    def __init__(
        self, inner: StoragePlugin, journal: EffectJournal, origin: str,
    ) -> None:
        self.inner = inner
        self._journal = journal
        self._origin = origin

    @property
    def scales_io_with_local_world(self) -> bool:  # type: ignore[override]
        return self.inner.scales_io_with_local_world

    async def write(self, write_io: WriteIO) -> None:
        await self.inner.write(write_io)
        self._journal.record(
            "write", self._origin, write_io.path, payload=write_io.buf,
        )

    async def read(self, read_io: ReadIO) -> None:
        await self.inner.read(read_io)

    async def delete(self, path: str) -> None:
        await self.inner.delete(path)
        self._journal.record("delete", self._origin, path)

    async def link_in(self, src_abs_path: str, path: str) -> bool:
        linked = await self.inner.link_in(src_abs_path, path)
        if linked:
            # The linked object's bytes ARE the src file's bytes; retain
            # them so a replay can materialize the link as a copy. Read on
            # an executor like any blocking file IO.
            def _read_src() -> Optional[bytes]:
                try:
                    with open(src_abs_path, "rb") as f:
                        return f.read()
                except OSError:
                    return None

            loop = asyncio.get_event_loop()
            payload = await loop.run_in_executor(None, _read_src)
            self._journal.record(
                "link", self._origin, path, payload=payload,
            )
        return linked

    async def list_prefix(self, prefix: str) -> List[str]:
        return await self.inner.list_prefix(prefix)

    async def prune_empty(self) -> None:
        await self.inner.prune_empty()

    async def close(self) -> None:
        await self.inner.close()


def maybe_wrap_with_effects(
    plugin: StoragePlugin, origin: str,
) -> StoragePlugin:
    """Wrap ``plugin`` when the debug-effects journal is enabled."""
    journal = get_journal()
    if journal is None:
        return plugin
    return EffectRecordingPlugin(plugin, journal, origin)
