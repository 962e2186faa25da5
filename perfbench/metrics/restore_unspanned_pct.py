"""Share of the traced ``pb.restore`` in which no span of the library is open
on any host thread (``perfbench/libspans.py``)."""

from perfbench import libspans


def read(facts, spec):
    planes = libspans.planes_of_this_run()
    return None if planes is None else libspans.unspanned_pct(planes, spec["span"])
