"""The library's own spans in a run's profiler trace.

``torchsnapshot_tpu`` opens a ``jax.profiler.TraceAnnotation("tss.<span>")``
around its synchronous work (the stall's phases, a D2H lane's resolve,
serialize and hash, the fs plugin's reads and writes, a restore's plan,
consume, place and load), so a traced round holds them as host events on the
clock of the device's ``XLA Ops``, beside the harness's ``pb.*``
(``perfbench/trace.py`` reads only those). Everything here is clipped to
``pb.traced``.

A program without the bridge leaves no ``tss.*`` event: every reading below is
then ``None``, and the metric is left out of the line.

    python3 perfbench/libspans.py <file.xplane.pb>     # print both readings
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import target  # noqa: E402
from perfbench.trace import WINDOW, clip, find_xplane, length, union  # noqa: E402

PREFIX = "tss."


def trace_dir_of_this_run():
    """``facts`` carries no path: the traced run is the one this process was
    started as, and it writes under ``OUT_DIR/runs/<workload>-seed<N>-trace1``."""
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=0)
    args, _ = parser.parse_known_args(sys.argv[1:])
    if not args.workload:
        return None
    return os.path.join(target.OUT_DIR, "runs", f"{args.workload}-seed{args.seed}-trace1", "trace")


def read_planes(path: str) -> dict:
    """``{"busy": {device plane: [(start_s, end_s)]}, "host": [(name, start_s, end_s)]}``:
    every chip's operations, and the host events of the harness and of the
    library, all threads."""
    from jax.profiler import ProfileData

    busy, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    busy[plane.name] = [
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events
                    if ev.name.startswith((PREFIX, "pb."))
                )
    return {"busy": busy, "host": host}


def planes_of_this_run():
    """The planes of this run's trace; ``None`` where there is none to read."""
    trace_dir = trace_dir_of_this_run()
    if trace_dir is None:
        return None
    try:
        return read_planes(find_xplane(trace_dir))
    except FileNotFoundError:
        return None


def intersect(a: list, b: list) -> list:
    """Of two merged interval lists, the stretches in both."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """Of a merged interval list, the stretches outside another."""
    out = []
    for lo, hi in a:
        for s, e in b:
            if e <= lo or s >= hi:
                continue
            if s > lo:
                out.append([lo, s])
            lo = max(lo, e)
        if hi > lo:
            out.append([lo, hi])
    return out


def _in_window(planes: dict, name: str, prefix: bool = False) -> list:
    """Union of the host events of that name (or, with ``prefix``, whose
    name starts so), clipped to ``pb.traced``; ``None`` without that span."""
    host = planes["host"]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    mine = [[s, e] for n, s, e in host if (n.startswith(name) if prefix else n == name)]
    return union(clip(mine, lo, hi))


def unspanned_pct(planes: dict, span: str):
    """Share of the harness span ``span`` in which no ``tss.*`` event is open
    on any host thread, in %: what the library's instrumentation cannot see."""
    outer, inner = _in_window(planes, span), _in_window(planes, PREFIX, prefix=True)
    if not outer or not inner:
        return None
    return 100.0 * length(subtract(outer, inner)) / length(outer)


def idle_under_pct(planes: dict, under: str, event: str):
    """Of the first chip's idle time under the harness span ``under``, the
    share during which a ``tss.<event>`` is open on some thread, in %."""
    outer, inner = _in_window(planes, under), _in_window(planes, PREFIX + event)
    if not outer or not inner or not planes["busy"]:
        return None
    first = union([list(iv) for iv in planes["busy"][sorted(planes["busy"])[0]]])
    idle = subtract(outer, first)
    if not length(idle):
        return None
    return 100.0 * length(intersect(idle, inner)) / length(idle)


if __name__ == "__main__":
    read = read_planes(sys.argv[1])
    by_name = {}
    for name in sorted({n for n, _, _ in read["host"]}):
        merged = _in_window(read, name) or []
        by_name[name] = [sum(1 for n, _, _ in read["host"] if n == name), round(length(merged), 6)]
    print(json.dumps({
        "events as [count, seconds of their union inside pb.traced]": by_name,
        "restore_unspanned_pct": unspanned_pct(read, "pb.restore"),
        "step_block_d2h_pct": idle_under_pct(read, "pb.step.block", "stage.d2h"),
    }, indent=1))
