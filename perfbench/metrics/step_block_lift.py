"""Whether the first chip's idle gaps under ``pb.step.block`` care what the
host is doing: for one activity of the library, the **lift**

    P(activity open | chip idle under pb.step.block)
    ----------------------------------------------
    P(activity open | under pb.step.block)

of the traced cycle. Beside a drain nearly everything is open nearly always,
so a share says nothing (``step_block_d2h_pct``); a lift of 1.0 says the gaps
fall where the activity is open no more often than anywhere else under the
span, a lift well over 1.0 puts them down to it.

Three activities (``reader.activity`` of the metric's file):

    gather        ``tss.stage.gather`` events of the trace: a lane copying a
                  resolved piece into its leaf's fresh host buffer
    write_copy    ``intervals/write_copy`` of the take's own artifact: a
                  writer thread copying a chunk into its bounce buffer
    mount_write   ``intervals/mount_write``: a ``pwrite`` on the mount

The last two are stamped inside the native engine on ``time.monotonic()``'s
clock, with no GIL and so no profiler annotation. They are placed on the
trace's clock by one offset: the median, over the starts and the ends of the
take's ``storage.write_work`` spans, of the time the
``tss.storage.write_work`` event has in the trace less the time the span has
in the artifact (``intervals/write_work``, which the library stamps right
beside the span's own ends). A median over two ends of every span is moved by
no one of them. The reader checks that anchor itself and prints two
residuals on a ``[summary]`` line of its own: through the offset, every span
has both its ends within ``trace_clock_residual_far_ms`` of its event's, and
its start or its end within ``trace_clock_residual_ms`` (the nearer of the
two: a thread that loses its core or the GIL between the library's stamp and
the profiler's costs that one end milliseconds, and says nothing of the
clocks). Over :data:`RESIDUAL_LIMIT_MS` of the latter the two lifts that lean
on the anchor read ``None``. A program without these intervals (the parent of
PR 40) reads ``None`` everywhere and prints nothing.

Two cautions (``PERF.md`` section 5). The span covers the whole cycle and the
drain runs beside about half of it, so an activity that exists only beside a
drain reads 2.0-2.5 where the gaps merely fall inside the drain: that, not
1.0, is what a reading is held against. And a lift divides by a few tenths of
an idle second a traced cycle: two runs of one cell read a factor of two
apart, so one reading ranks nothing.
"""

import json
import statistics

from perfbench import libspans, readers
from perfbench.trace import WINDOW, clip, length, union

RESIDUAL_LIMIT_MS = 1.0
WORK_EVENT = libspans.PREFIX + "storage.write_work"
GATHER_EVENT = libspans.PREFIX + "stage.gather"

# One reduction a run: three metric files read it.
_READ = {}


def clock_offset(planes: dict, artifact: dict):
    """``(offset_s, near_ms, far_ms, spans)``: what to add to an interval of
    the artifact to have it on the trace's clock, how far the worst
    ``tss.storage.write_work`` event lies from its span through that, by the
    nearer and by the farther of its two ends, and how many there were.
    ``None`` where the two do not pair one to one."""
    spans = readers.lookup(artifact, "intervals/write_work")
    if not spans:
        return None
    lo, hi = _window(planes)
    events = sorted((s, e) for n, s, e in planes["host"] if n == WORK_EVENT and lo <= s < hi)
    if len(events) != len(spans):
        return None
    apart = [
        (e0 - s0, e1 - s1)
        for (e0, e1), (s0, s1) in zip(events, sorted(map(tuple, spans)))
    ]
    offset = statistics.median(a for pair in apart for a in pair)
    off = [sorted(abs(a - offset) for a in pair) for pair in apart]
    return offset, 1e3 * max(o[0] for o in off), 1e3 * max(o[1] for o in off), len(spans)


def _window(planes: dict):
    windows = [(s, e) for n, s, e in planes["host"] if n == WINDOW]
    return min(s for s, _ in windows), max(e for _, e in windows)


def lift(block: list, idle: list, activity: list):
    """The ratio above, from merged interval lists on one clock."""
    if not length(idle) or not length(block):
        return None
    everywhere = length(libspans.intersect(block, activity)) / length(block)
    if not everywhere:
        return None
    return length(libspans.intersect(idle, activity)) / length(idle) / everywhere


def lifts(planes: dict, artifact: dict, span: str) -> dict:
    """Every activity's lift under the harness span ``span``, and the
    anchor's check. Empty where the program stamps none of this."""
    clock = clock_offset(planes, artifact)
    block = libspans._in_window(planes, span)
    if clock is None or not block or not planes["busy"]:
        return {}
    offset, residual_ms, residual_far_ms, spans = clock
    lo, hi = _window(planes)
    first = union([list(iv) for iv in planes["busy"][sorted(planes["busy"])[0]]])
    idle = libspans.subtract(block, first)
    out = {
        "trace_clock_residual_ms": residual_ms,
        "trace_clock_residual_far_ms": residual_far_ms,
        "trace_clock_offset_s": offset,
        "write_work_spans": spans,
        "step_block_s": length(block),
        "step_block_idle_s": length(idle),
        "gather": lift(block, idle, libspans._in_window(planes, GATHER_EVENT) or []),
    }
    anchored = residual_ms <= RESIDUAL_LIMIT_MS
    for name in ("write_copy", "mount_write"):
        shifted = [[s + offset, e + offset] for s, e in readers.lookup(artifact, "intervals/" + name, [])]
        out[name] = lift(block, idle, union(clip(shifted, lo, hi))) if anchored else None
    return out


def read(facts, spec):
    artifact = readers.lookup(facts, "traced/save/telemetry")
    if not readers.lookup(artifact, "intervals/write_work"):
        return None
    if "lifts" not in _READ:
        planes = libspans.planes_of_this_run()
        _READ["lifts"] = {} if planes is None else lifts(planes, artifact, spec["span"])
        if _READ["lifts"]:
            print("[summary] " + json.dumps(_READ["lifts"]), flush=True)
    return _READ["lifts"].get(spec["activity"])
