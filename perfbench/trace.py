"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the result line's ``device`` and ``breakdown`` read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran on the chip and ``XLA Modules`` one per
program. Host threads carry the harness's ``jax.profiler.TraceAnnotation``
spans (``pb.*``), on the same clock. Everything is clipped to the
``pb.traced`` span, which the harness puts around the one round it traces.

    python3 perfbench/trace.py <file.xplane.pb>     # print the reduction
"""

import glob
import json
import os
import re
import sys

WINDOW = "pb.traced"
PREFIX = "pb."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merged, sorted, non-overlapping ``[start, end]`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def _short(name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line: keep the
    operation's own name (``%fusion.92 = (bf16[...`` -> ``fusion.92``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _events(line) -> list:
    return [
        (_short(ev.name), ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
        for ev in line.events
    ]


def read_planes(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}``
    with events as ``(name, start_s, end_s)``; ``host`` holds only the
    harness's own annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices[plane.name] = {
                "ops": _events(lines["XLA Ops"]),
                "modules": _events(lines["XLA Modules"]) if "XLA Modules" in lines else [],
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line) if e[0].startswith(PREFIX))
    return {"devices": devices, "host": host}


def _module_name(name: str) -> str:
    """``jit_pb_train_step(123456789)`` -> ``jit_pb_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes: dict) -> dict:
    host = planes["host"]
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows or not planes["devices"]:
        return {}
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    per_chip_busy, busy_by_chip = [], {}
    op_time, module_time = {}, {}
    for plane, lines in planes["devices"].items():
        busy = union(clip([[s, e] for _, s, e in lines["ops"]], lo, hi))
        busy_by_chip[plane] = busy
        per_chip_busy.append(length(busy))
        for name, s, e in lines["ops"]:
            if e > lo and s < hi:
                op_time[name] = op_time.get(name, 0.0) + (min(e, hi) - max(s, lo))
        for name, s, e in lines["modules"]:
            if e > lo and s < hi:
                key = _module_name(name)
                module_time.setdefault(key, []).append(min(e, hi) - max(s, lo))
    chips = len(per_chip_busy)
    # Host spans by name, and the device time inside each (mean over chips).
    spans = {}
    for name in sorted({n for n, _, _ in host}):
        mine = union(clip([[s, e] for n, s, e in host if n == name], lo, hi))
        inside = sum(
            length([iv for b in mine for iv in clip(busy, b[0], b[1])]) for busy in busy_by_chip.values()
        )
        spans[name] = {
            "count": sum(1 for n, _, _ in host if n == name),
            "total_s": length(mine),
            "device_busy_s": inside / chips,
        }
    # Idle gaps of the first chip, each stretch of a gap named by the
    # innermost harness span open over it: what the host was doing while
    # the device waited.
    first = busy_by_chip[sorted(busy_by_chip)[0]]
    edges = [lo] + [t for iv in first for t in iv] + [hi]
    named = [(s, e, n) for n, s, e in host if n != WINDOW]
    gaps = {}
    for start, end in zip(edges[0::2], edges[1::2]):
        cuts = sorted({start, end} | {t for s, e, _ in named for t in (s, e) if start < t < end})
        for a, b in zip(cuts, cuts[1:]):
            open_spans = [(e - s, n) for s, e, n in named if s <= a and b <= e]
            name = min(open_spans)[1] if open_spans else "(no harness span)"
            gaps[name] = gaps.get(name, 0.0) + (b - a)
    # Per-chip share of a sharded program: the modules line of every chip
    # lists it, so a module's time is the mean over chips of its total.
    return {
        "window_s": hi - lo,
        "busy_s": sum(per_chip_busy) / chips,
        "chips": chips,
        "modules": {
            k: {"count": len(v) // chips or 1, "total_s": sum(v) / chips} for k, v in module_time.items()
        },
        "spans": spans,
        "device_ops": sorted(([k, v / chips] for k, v in op_time.items()), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:10],
    }


def reduce_file(path: str) -> dict:
    return reduce_planes(read_planes(path))


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
