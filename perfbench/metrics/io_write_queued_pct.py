"""Of a native write's seconds in the fs plugin's executor, the share spent
waiting for a writer slot: ``queued / (queued + work) x 100`` a round, the
median over the window's rounds (the ``ratio`` reader takes one key as its
denominator, this one is a sum of two)."""

import statistics

from perfbench import readers


def read(facts, spec):
    shares = []
    for rec in readers.lookup(facts, spec["over"], []):
        queued, work = readers.lookup(rec, spec["queued"]), readers.lookup(rec, spec["work"])
        if queued is not None and work is not None and queued + work:
            shares.append(100.0 * queued / (queued + work))
    return statistics.median(shares) if shares else None
