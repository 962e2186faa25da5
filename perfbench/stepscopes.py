"""Which part of the model a device operation of the traced round belongs to.

An architecture puts ``jax.named_scope`` around its parts (``qn.gdn``,
``qn.attn``, ``qn.moe``, ``qn.head``). The scope reaches the device plane of
the profiler's ``.xplane.pb`` only as the ``tf_op`` stat of each operation's
event *metadata* (``jit(pb_train_step)/.../qn.moe/dot_general``).
``jax.profiler.ProfileData`` hands out an event's own stats and not its
metadata's (``perfbench/tests/test_qwen3_next.py`` holds it to that on the
recorded trace), and the only generated bindings of the format in the
installation lie inside ``tensorflow``, a second framework that nothing here
imports into the process that holds the chip. So the four messages that carry
the stat are read from the wire format (``XSpace.planes``, and of a device
plane its name, ``event_metadata`` and ``stat_metadata``; the lines, nearly all
of the file, are skipped whole): field numbers that the format cannot change.
The times come from ``perfbench/trace.py`` as everywhere else, clipped to
``pb.traced``. The two shares describe the step the drain runs beside, not the
library: a reading of the cell's load, with no better direction of its own.

A trace whose operations carry no op name gives ``None``, and the metric is
left out of the line.

    python3 perfbench/stepscopes.py <file.xplane.pb> [scope ...]
"""

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import libspans, trace  # noqa: E402
from perfbench.trace import clip, find_xplane, length, union  # noqa: E402

DEVICE = "/device:TPU:"
OP_NAME_STAT = "tf_op"


def _varint(buf, pos: int):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an int,
    a length-delimited field as a view of its bytes; fixed-width fields are
    passed over."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield number, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield number, buf[pos:pos + size]
            pos += size
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry):
    """The value message of one ``map<int64, Message>`` entry."""
    return next((v for n, v in _fields(entry) if n == 2), b"")


def op_names(path: str) -> dict:
    """``{event name: op name}`` over the device planes of an ``.xplane.pb``:
    for every operation that ran on a chip, the name jax gave it (scopes
    included). XPlane: name 2, event_metadata 4, stat_metadata 5.
    XEventMetadata: name 2, stats 5. XStat: metadata_id 1, str_value 5,
    ref_value 7. XStatMetadata: id 1, name 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, value in _fields(plane):
            if n == 2:
                name = _text(value)
            elif n == 4:
                events.append(_map_entry(value))
            elif n == 5:
                meta = dict(_fields(_map_entry(value)))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith(DEVICE):
            continue
        for event in events:
            event_name, op_name = "", None
            for n, value in _fields(event):
                if n == 2:
                    event_name = _text(value)
                elif n == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        op_name = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
            if op_name:
                out[event_name] = op_name
    return out


@functools.lru_cache(maxsize=1)
def _read(path: str):
    """Op names by the short event names of ``perfbench/trace.py``, and its
    planes; kept for the next scope asked of the same trace."""
    names = {trace._short(event): op for event, op in op_names(path).items()}
    return names, trace.read_planes(path)


def share_pct(path: str, scope: str):
    """Of the time some operation ran on the device inside ``pb.traced``, the
    share in which one whose op name holds ``scope`` ran, in % (mean over the
    chips). Unions, so an operation inside a loop's event counts once."""
    names, planes = _read(path)
    windows = [(s, e) for name, s, e in planes["host"] if name == trace.WINDOW]
    if not names or not windows:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    shares = []
    for lines in planes["devices"].values():
        busy = length(union(clip([[s, e] for _, s, e in lines["ops"]], lo, hi)))
        mine = [[s, e] for name, s, e in lines["ops"] if scope in names.get(name, "")]
        if busy:
            shares.append(100.0 * length(union(clip(mine, lo, hi))) / busy)
    return sum(shares) / len(shares) if shares else None


def share_of_this_run_pct(scope: str):
    """``share_pct`` of this run's own trace; ``None`` where there is none."""
    trace_dir = libspans.trace_dir_of_this_run()
    if trace_dir is None:
        return None
    try:
        return share_pct(find_xplane(trace_dir), scope)
    except FileNotFoundError:
        return None


if __name__ == "__main__":
    scopes = sys.argv[2:] or ["qn.gdn", "qn.attn", "qn.moe", "qn.head"]
    print(json.dumps({scope: share_pct(sys.argv[1], scope) for scope in scopes}, indent=1))
