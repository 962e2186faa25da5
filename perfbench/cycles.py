"""Arithmetic over a run's per-round records (``rounds.jsonl``).

A round is one pass of the traffic's loop. In a save-only mix a round is a
whole **save cycle**: from one ``async_take`` call to the next, so its wall
holds the stall, every step of the period (those beside the drain and beside
the retirement of an older snapshot, and those after it), any wait for a
commit that outlasted the period, and nothing else.

The end-to-end metrics are taken over all the work and all the time of the
window's whole rounds: all steps over all cycle time, all bytes over all
call-to-commit (or call-to-ready) time. The medians over rounds stand beside
them as per-layer readings. Everything is computed here from the file and
from nothing else, so an estimator can be judged offline on records already
paid for (``python3 perfbench/cycles.py rounds.jsonl``).
"""

import json
import statistics
import sys


def load(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def in_window(records: list) -> list:
    """The window's whole rounds: those the harness flagged, in order."""
    return [r for r in records if r.get("in_window")]


def _done(records: list, op: str) -> list:
    """The window's rounds whose ``op`` ran to its end (a failed operation
    has no time; it is counted in ``failed`` and makes the run incorrect)."""
    return [r for r in in_window(records) if r.get(op) and not r[op].get("error")]


def _cycles(records: list) -> list:
    return [r for r in _done(records, "save") if r.get("cycle")]


def goodput_pct(records: list, step_alone_s: float):
    """Steps completed in the window's save cycles, times the undisturbed
    step's time, over the cycles' whole wall time, in %."""
    rounds = _cycles(records)
    if not rounds:
        return None
    steps = sum(len(r["step_s"]) for r in rounds)
    return 100.0 * steps * step_alone_s / sum(r["wall_s"] for r in rounds)


def rate_gbps(records: list, op: str):
    """All logical bytes of the window's ``op``s over all their time:
    ``async_take`` call -> ``wait()`` returned for a save, ``restore`` call
    -> every leaf ready on its target for a restore."""
    rounds = _done(records, op)
    if not rounds:
        return None
    return sum(r[op]["bytes"] for r in rounds) / 1e9 / sum(r[op]["wall_s"] for r in rounds)


def save_cost_s(records: list, step_alone_s: float):
    """What one save takes from training: the cycles' whole wall time less
    their steps at the undisturbed step's time, per cycle."""
    rounds = _cycles(records)
    if not rounds:
        return None
    steps = sum(len(r["step_s"]) for r in rounds)
    return (sum(r["wall_s"] for r in rounds) - steps * step_alone_s) / len(rounds)


def commit_wait_s(records: list):
    """Time per cycle in which no step ran because the commit (or the
    retirement after it) outlasted the cycle's steps."""
    rounds = _cycles(records)
    if not rounds:
        return None
    return sum(r["save"].get("commit_wait_s", 0.0) for r in rounds) / len(rounds)


def goodput_per_cycle(records: list, step_alone_s: float) -> list:
    """``steps x step_alone_s / cycle wall x 100`` of each save cycle."""
    return [100.0 * len(r["step_s"]) * step_alone_s / r["wall_s"] for r in _cycles(records)]


def gbps_per_op(records: list, op: str) -> list:
    return [r[op]["bytes"] / 1e9 / r[op]["wall_s"] for r in _done(records, op)]


def summarise(records: list, step_alone_s: float = None) -> dict:
    """The window's totals under the end-to-end metrics' names, and the
    median and count of each per-round series beside them."""
    out = {"rounds": len(in_window(records))}
    totals = {"save_gbps": rate_gbps(records, "save"), "restore_gbps": rate_gbps(records, "restore")}
    series = {"save_gbps": gbps_per_op(records, "save"), "restore_gbps": gbps_per_op(records, "restore")}
    if step_alone_s:
        totals["goodput_pct"] = goodput_pct(records, step_alone_s)
        series["goodput_pct"] = goodput_per_cycle(records, step_alone_s)
    for name, values in series.items():
        if values:
            out[name] = totals[name]
            out[name + ".median"] = statistics.median(values)
            out[name + ".n"] = len(values)
    if step_alone_s and _cycles(records):
        out["save_cost_s"] = save_cost_s(records, step_alone_s)
        out["commit_wait_s"] = commit_wait_s(records)
    return out


if __name__ == "__main__":
    records = load(sys.argv[1])
    alone = next((r["step_alone_s"] for r in records if r.get("step_alone_s")), None)
    print(json.dumps(summarise(records, alone), indent=1))
