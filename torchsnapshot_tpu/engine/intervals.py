"""Interval algebra for the engine's stream-overlap stats.

The engine records one ``(t0, t1)`` interval per node execution (the same
data telemetry exports as spans), and the drain/pipeline stats are DERIVED
from those intervals by union/intersection — so the trace and the stats
can never disagree about where the time went. Moved verbatim from
``scheduler.py`` (which re-exports these names) when the three execution
paths were lowered onto the engine.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]


def merge_intervals(intervals: Sequence[Tuple[float, ...]]) -> List[Interval]:
    """Sorted union of possibly-overlapping intervals. A record may carry
    more than its two ends (``(t0, t1, nbytes)``): only the ends are read."""
    out: List[Interval] = []
    for t0, t1 in sorted((i[0], i[1]) for i in intervals if i[1] > i[0]):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def smaller_than(
    records: Sequence[Tuple[float, float, int]], limit: int
) -> List[Tuple[float, float, int]]:
    """The ``(t0, t1, nbytes)`` records of objects under ``limit`` bytes. A
    record that carries no size (``nbytes`` 0) is not one of them."""
    return [r for r in records if 0 < r[2] < limit]


def clip_merged(
    merged: List[Interval], w0: float, w1: float
) -> List[Interval]:
    return [
        (max(t0, w0), min(t1, w1)) for t0, t1 in merged if t1 > w0 and t0 < w1
    ]


def measure(merged: List[Interval]) -> float:
    return sum(t1 - t0 for t0, t1 in merged)


def busy_in(merged: List[Interval], windows: Sequence[Interval]) -> float:
    """The measure of a merged interval list inside the accounting windows."""
    return sum(measure(clip_merged(merged, w0, w1)) for w0, w1 in windows)


def sum_in(
    records: Sequence[Tuple[float, ...]], windows: Sequence[Interval]
) -> float:
    """The plain sum of the records' durations inside the accounting
    windows, overlaps counted as often as they occur (``sum_in / busy_in``
    is the depth the records ran at). Only a record's two ends are read."""
    return sum(
        max(0.0, min(r[1], w1) - max(r[0], w0))
        for r in records
        for w0, w1 in windows
    )


def intersect_merged(
    a: List[Interval], b: List[Interval]
) -> List[Interval]:
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        t0 = max(a[i][0], b[j][0])
        t1 = min(a[i][1], b[j][1])
        if t1 > t0:
            out.append((t0, t1))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def stream_stats(
    windows: List[Interval],
    stage_intervals: List[Interval],
    io_intervals: List[Interval],
) -> Dict[str, float]:
    """wall/stage_busy/io_busy/overlap/idle over the given accounting
    windows. Only activity inside a window is attributed (matching the old
    wait-loop accounting: the gap between an async take's capture point and
    its background drain is nobody's time)."""
    stage = merge_intervals(stage_intervals)
    io = merge_intervals(io_intervals)
    both = intersect_merged(stage, io)
    wall = stage_busy = io_busy = overlap = 0.0
    for w0, w1 in windows:
        wall += w1 - w0
        stage_busy += measure(clip_merged(stage, w0, w1))
        io_busy += measure(clip_merged(io, w0, w1))
        overlap += measure(clip_merged(both, w0, w1))
    union = stage_busy + io_busy - overlap
    return {
        "wall_s": wall,
        "stage_busy_s": stage_busy,  # D2H + serialize stream in flight
        "io_busy_s": io_busy,  # storage-write stream in flight
        "overlap_s": overlap,  # both streams concurrently in flight
        "idle_s": max(0.0, wall - union),  # neither stream active
    }
