"""The generic readers a metric file (``perfbench/metrics/<name>.json``) may name.

A reader takes the run's ``facts`` and the ``reader`` object of the metric
file and returns a number, or ``None`` when it finds nothing to read (the
harness then leaves the metric out of the line). ``facts`` holds:

    rounds   the window's round records (``rounds.jsonl``, in-window only);
             ``save/telemetry`` is the snapshot's own ``.telemetry/rank_0.json``,
             ``restore/stats`` a copy of ``LAST_RESTORE_STATS``
    traced   the one round the profiler traced (``--trace 1``)
    trace    the reduction of that trace (``perfbench/trace.py``)
    setup    set-up readings (``step_alone_s``, ``setup_s``, ...)
    device   ``memory_stats()`` of the fullest chip at window close
    link     the link probe (``--trace 1``)
    peaks    the row of ``perfbench/peaks.json`` for this ``device_kind``

A path is keys joined by ``/``. A metric no reader here fits brings a
``<name>.py`` beside its ``.json`` with a ``read(facts, spec)`` of its own.
"""

import statistics

def lookup(node, path: str, default=None):
    for key in path.split("/"):
        if isinstance(node, dict) and key in node:
            node = node[key]
        else:
            return default
    return node


def _ratio_in(node, spec: dict):
    num = lookup(node, spec["num"], spec.get("num_default"))
    if num is None:
        return None
    if "den" not in spec:
        return float(num)
    den = lookup(node, spec["den"])
    return float(num) / float(den) if den else None


def read_ratio(facts: dict, spec: dict):
    """``num / den`` (or ``num`` alone) times ``scale``: once against the
    facts, or per record of ``over`` and then the median over the records."""
    if "over" not in spec:
        value = _ratio_in(facts, spec)
        return None if value is None else value * spec.get("scale", 1.0)
    values = [_ratio_in(rec, spec) for rec in lookup(facts, spec["over"], [])]
    values = [v for v in values if v is not None]
    if not values:
        return None
    return statistics.median(values) * spec.get("scale", 1.0)


def read_idle(facts: dict, spec: dict):
    """Share of the harness span ``span`` in which no operation ran on the
    device (mean over the chips used), in %."""
    span = lookup(facts, f"trace/spans/{spec['span']}")
    if not span or not span["total_s"]:
        return None
    return 100.0 * (1.0 - span["device_busy_s"] / span["total_s"])


def read_roofline(facts: dict, spec: dict):
    """A memory-bound program's share of its roofline, in %: the least time
    the chip could take for ``bytes_factor x bytes`` at the peak named, over
    the device time of the trace's module ``module``."""
    module = lookup(facts, f"trace/modules/{spec['module']}")
    nbytes = lookup(facts, spec["bytes"])
    peak = lookup(facts, f"peaks/{spec['peak']}")
    if not module or not module["total_s"] or nbytes is None:
        return None
    if peak is None:
        raise KeyError(f"peaks.json has no {spec['peak']!r} for this device")
    chips = lookup(facts, "trace/chips", 1)
    least = spec["bytes_factor"] * nbytes * module["count"] / chips / peak
    return 100.0 * least / module["total_s"]


READERS = {"ratio": read_ratio, "idle": read_idle, "roofline": read_roofline}
