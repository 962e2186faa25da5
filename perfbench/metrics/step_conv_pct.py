"""Share of the traced cycle's device busy time spent in operations whose op
name carries the scope ``lf.conv`` (``perfbench/stepscopes.py``)."""

from perfbench import stepscopes


def read(facts, spec):
    return stepscopes.share_of_this_run_pct(spec["scope"])
