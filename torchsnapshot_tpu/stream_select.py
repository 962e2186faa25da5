"""Measurement-driven streaming auto-select (``STREAM_WRITES=auto``).

A global streaming default can ship **inverted** on a host: streaming ON
drains slower than OFF wherever per-chunk staging overhead (slicing + copy
per chunk, timeshared with the appends) costs more than the intra-request
overlap buys. Streaming is a
per-host, per-plugin trade — so instead of a global boolean default, the
shipped default is ``auto``: this module keeps a per-plugin **scorecard**
of measured throughput on both sides, fed by the write pipeline's own
instrumentation (the same points that record the
``storage.<plugin>.append_s.<bucket>`` histograms):

- ``note_streamed``: bytes and in-flight append seconds of streamed
  requests, plus (``note_stream_stage``) each chunk's staging seconds;
- ``note_whole``: bytes and write seconds of whole-buffer requests, plus
  (``note_whole_stage``) each request's staging seconds.

Staging seconds are IN the rates on purpose: the r07 inversion was not
slow appends — it was per-chunk staging overhead (slice + copy per chunk,
timesharing CPU with the appends) that the whole-buffer path simply does
not pay. A scorecard of storage-op seconds alone would have certified the
inversion as a streaming win. Each side's rate is therefore bytes per
BUSY second (staging + storage op): a deliberately overlap-blind measure
— identical per-byte work (D2H, serialize) cancels between the sides, and
what remains is exactly the per-chunk overhead asymmetry the decision
must weigh.

``resolve(storage)`` — called once per pipeline at graph-build time —
returns the decision: the knob verbatim when forced ``on``/``off``; under
``auto``, streaming iff the streamed side's measured byte rate is at least
the whole-buffer side's, with an optimistic-ON prior until BOTH sides have
credible evidence (enough bytes and operations). Every resolution is
recorded (``last_decision``) so the bench's regression gate can fail when
auto picks the measured losing side, and mirrored into
``knobs.note_stream_auto_resolution`` so code without a plugin in hand
(the stager's D2H pre-hint) tracks the same decision.

``ab_probe`` runs an explicit A/B against a destination (one object
streamed, one whole, then deleted) and feeds the scorecard — how a fresh
process (or the bench's auto leg) buys evidence without waiting for
steady-state drains to accumulate it.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional

from . import telemetry
from .utils import knobs

logger = logging.getLogger(__name__)

# Evidence thresholds: a side is credible once this many bytes and ops were
# measured. Below them auto keeps the optimistic-ON prior — tiny writes'
# fixed overheads would otherwise dominate the rates and flip decisions on
# noise.
MIN_CREDIBLE_BYTES = 64 * 1024 * 1024
MIN_CREDIBLE_OPS = 2


def storage_label(storage) -> str:
    """Short plugin label for the scorecard and per-plugin metric names:
    ``FSStoragePlugin`` → ``fs`` — matching ``storage.<plugin>.write_bytes``."""
    name = type(storage).__name__
    if name.endswith("StoragePlugin"):
        name = name[: -len("StoragePlugin")]
    return name.lower() or "unknown"


@dataclass
class _SideStats:
    bytes: int = 0
    seconds: float = 0.0
    ops: int = 0

    def rate(self) -> Optional[float]:
        return self.bytes / self.seconds if self.seconds > 0 else None

    def credible(self) -> bool:
        return (
            self.bytes >= MIN_CREDIBLE_BYTES
            and self.ops >= MIN_CREDIBLE_OPS
            and self.seconds > 0
        )


_LOCK = threading.Lock()
# {plugin label: {"stream" | "whole": _SideStats}}
_SCORE: Dict[str, Dict[str, _SideStats]] = {}
# {plugin label: last resolve() record}; "" holds the most recent overall.
_DECISIONS: Dict[str, dict] = {}


def _side(label: str, side: str) -> _SideStats:
    return _SCORE.setdefault(label, {}).setdefault(side, _SideStats())


def note_streamed(label: str, nbytes: int, seconds: float) -> None:
    """One streamed append's bytes + in-flight seconds (called per chunk,
    from the pipeline's append instrumentation)."""
    if nbytes <= 0 or seconds <= 0:
        return
    with _LOCK:
        s = _side(label, "stream")
        s.bytes += nbytes
        s.seconds += seconds
        s.ops += 1


def note_whole(label: str, nbytes: int, seconds: float) -> None:
    """One whole-buffer storage write's bytes + seconds."""
    if nbytes <= 0 or seconds <= 0:
        return
    with _LOCK:
        s = _side(label, "whole")
        s.bytes += nbytes
        s.seconds += seconds
        s.ops += 1


def note_stream_stage(label: str, seconds: float) -> None:
    """One streamed chunk's staging seconds (slice + D2H + serialize) —
    seconds only; the chunk's bytes/op are counted by its append."""
    if seconds <= 0:
        return
    with _LOCK:
        _side(label, "stream").seconds += seconds


def note_whole_stage(label: str, seconds: float) -> None:
    """One whole-buffer request's staging seconds — seconds only; the
    request's bytes/op are counted by its write."""
    if seconds <= 0:
        return
    with _LOCK:
        _side(label, "whole").seconds += seconds


def resolve(storage) -> bool:
    """Streaming decision for one write pipeline (graph-build time).

    Forced modes pass through; ``auto`` consults the plugin's scorecard.
    The decision and its evidence are recorded for ``last_decision`` and
    mirrored into the knobs module (process-wide boolean view)."""
    mode = knobs.get_stream_writes_mode()
    label = storage_label(storage)
    supports = bool(getattr(storage, "supports_streaming", False))
    if mode != "auto":
        enabled = mode == "on"
        _record(label, mode, enabled and supports, None, None, "forced")
        return enabled
    if not supports:
        # Nothing to decide — and the non-decision must not overwrite a
        # real plugin's process-wide resolution.
        return False
    with _LOCK:
        sides = _SCORE.get(label, {})
        s = sides.get("stream", _SideStats())
        w = sides.get("whole", _SideStats())
        if s.credible() and w.credible():
            enabled = s.rate() >= w.rate()
            reason = "measured"
        else:
            enabled = True
            reason = "insufficient-evidence"
        srate, wrate = s.rate(), w.rate()
    _record(label, mode, enabled, srate, wrate, reason)
    knobs.note_stream_auto_resolution(enabled)
    return enabled


def _record(
    label: str,
    mode: str,
    enabled: bool,
    stream_bps: Optional[float],
    whole_bps: Optional[float],
    reason: str,
) -> None:
    rec = {
        "plugin": label,
        "mode": mode,
        "enabled": enabled,
        "stream_bps": stream_bps,
        "whole_bps": whole_bps,
        "reason": reason,
    }
    with _LOCK:
        _DECISIONS[label] = rec
        _DECISIONS[""] = rec
    telemetry.gauge_set("scheduler.stream_auto_on", 1.0 if enabled else 0.0)
    if mode == "auto" and reason == "measured" and not enabled:
        # The inversion signal, now acted on instead of shipped: say so
        # once per flip direction would be nicer, but resolutions are one
        # per pipeline — debug level keeps steady state quiet.
        logger.debug(
            "stream auto-select: OFF for %s (streamed %.3f GB/s < whole "
            "%.3f GB/s)",
            label,
            (stream_bps or 0) / 1e9,
            (whole_bps or 0) / 1e9,
        )


def last_decision(label: Optional[str] = None) -> Optional[dict]:
    """The most recent ``resolve`` record (for ``label``, or overall)."""
    with _LOCK:
        rec = _DECISIONS.get(label if label is not None else "")
        return dict(rec) if rec is not None else None


def scorecard(label: str) -> Dict[str, dict]:
    """Copy of the evidence for one plugin: ``{side: {bytes, seconds, ops,
    rate}}`` — the bench reports it beside the auto decision."""
    with _LOCK:
        out = {}
        for side, s in _SCORE.get(label, {}).items():
            out[side] = {
                "bytes": s.bytes,
                "seconds": s.seconds,
                "ops": s.ops,
                "rate_bps": s.rate(),
            }
        return out


def reset() -> None:
    """Drop all evidence and decisions (tests / bench isolation)."""
    with _LOCK:
        _SCORE.clear()
        _DECISIONS.clear()
    knobs.note_stream_auto_resolution(None)


def ab_probe(
    url_path: str,
    nbytes: int = 128 * 1024 * 1024,
    reps: int = 1,
) -> Optional[dict]:
    """Explicit A/B probe against the plugin serving ``url_path``: write a
    probe object of ``nbytes`` via the append stream (at the configured
    chunk grain) and again as one whole buffer, feed both measurements into
    the scorecard, and delete the probe objects. Returns the measured rates
    (or None if the plugin does not support streaming). The caller pays
    ``2 x nbytes x reps`` of writes against the destination — this is the
    opt-in way to buy auto-mode evidence up front instead of accumulating
    it across steady-state drains."""
    from .storage_plugin import url_to_storage_plugin_in_event_loop

    loop = asyncio.new_event_loop()
    try:
        storage = url_to_storage_plugin_in_event_loop(url_path, loop)
        try:
            if not getattr(storage, "supports_streaming", False):
                return None
            label = storage_label(storage)
            chunk = knobs.get_stream_chunk_bytes()
            payload = memoryview(bytearray(nbytes))
            stream_s = whole_s = 0.0
            for rep in range(max(1, reps)):
                stream_s += loop.run_until_complete(
                    _probe_streamed(storage, f".probe/stream_ab.on.{rep}", payload, chunk)
                )
                whole_s += loop.run_until_complete(
                    _probe_whole(storage, f".probe/stream_ab.off.{rep}", payload)
                )
            total = nbytes * max(1, reps)
            note_streamed(label, total, stream_s)
            note_whole(label, total, whole_s)
            return {
                "plugin": label,
                "probe_bytes": total,
                "stream_bps": total / stream_s if stream_s > 0 else None,
                "whole_bps": total / whole_s if whole_s > 0 else None,
            }
        finally:
            storage.sync_close(loop)
    except Exception:  # noqa: BLE001 - evidence is optional, never fatal
        logger.warning("stream A/B probe against %s failed", url_path, exc_info=True)
        return None
    finally:
        loop.close()


async def _probe_streamed(storage, path: str, payload: memoryview, chunk: int) -> float:
    t0 = time.monotonic()
    stream = await storage.write_stream(path)
    try:
        for off in range(0, payload.nbytes, chunk):
            await stream.append(payload[off : off + chunk])
        await stream.commit()
    except BaseException:
        try:
            await stream.abort()
        except Exception:  # noqa: BLE001 - the original failure wins
            pass
        raise
    dt = time.monotonic() - t0
    await storage.delete(path)
    return dt


async def _probe_whole(storage, path: str, payload: memoryview) -> float:
    from .io_types import WriteIO

    t0 = time.monotonic()
    await storage.write(WriteIO(path=path, buf=payload))
    dt = time.monotonic() - t0
    await storage.delete(path)
    return dt
