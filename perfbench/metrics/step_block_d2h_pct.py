"""Of the first chip's idle time under ``pb.step.block`` in the traced cycle,
the share during which a D2H lane is resolving a transfer
(``perfbench/libspans.py``)."""

from perfbench import libspans


def read(facts, spec):
    planes = libspans.planes_of_this_run()
    return None if planes is None else libspans.idle_under_pct(planes, spec["span"], spec["event"])
