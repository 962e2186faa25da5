"""One activity's lift of the first chip's idle gaps under ``pb.step.block``
(``perfbench/metrics/step_block_lift.py``; the activity is in this metric's
``.json``)."""

from perfbench.metrics.step_block_lift import read  # noqa: F401
