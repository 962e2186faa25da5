"""Checkpoint-invariant static analyzer (the ``dev/lint.py`` analysis gate).

Ten AST passes over the library, zero third-party dependencies:

1. async-safety (TSA1xx) — no blocking calls on the event loop;
2. task-leak (TSA2xx) — every spawned task AND executor future retained
   and reaped;
3. knob-drift (TSA3xx) — env knobs live in ``utils/knobs.py`` and the docs
   catalog, bidirectionally;
4. telemetry-discipline (TSA4xx) — spans context-managed, names cataloged;
5. manifest-schema (TSA5xx) — Entry fields stay JSON-serializable;
6. resource-balance (TSA6xx) — flow-sensitive: every budget debit
   credited, handed off, or try/finally-protected on every path;
7. thread-safety (TSA7xx) — no unguarded attribute mutation shared between
   executor threads and the event loop;
8. fault-coverage (TSA8xx) — every StoragePlugin op wrapped by
   FaultyStoragePlugin's injection map;
9. collective-discipline (TSA9xx) — collective call sequences stay
   SPMD-pure: no collective behind rank/time/filesystem/exception-derived
   branches, none in except/finally handlers, none per-iteration of
   divergent loops, and plan-affecting functions read only
   manifest/knob/entry state;
10. durability-discipline (TSA10xx) — flow-sensitive crash consistency:
    durable writes go through an atomic-commit idiom, catalog publishes
    are dominated by the data commit, GC deletes are keep-set gated, and
    every commit-point function stays pinned to a ``faults.py``
    kill-point op class.

Run: ``python -m dev.analyze`` (``--jobs N`` fans per-file passes out to
worker processes; ``--timings`` prints a per-pass wall-time report), or
via ``python dev/lint.py``.
See ``docs/static-analysis.md`` for codes, suppression, and the baseline
workflow.
"""

from .core import (
    AnalysisContext,
    Finding,
    apply_baseline,
    default_context,
    get_passes,
    load_baseline,
    run_passes,
    write_baseline,
)

__all__ = [
    "AnalysisContext",
    "Finding",
    "apply_baseline",
    "default_context",
    "get_passes",
    "load_baseline",
    "run_passes",
    "write_baseline",
]
