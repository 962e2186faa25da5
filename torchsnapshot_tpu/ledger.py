"""Debug-mode budget-ledger sanitizer (``TORCHSNAPSHOT_TPU_DEBUG_LEDGER``).

The runtime half of the resource-balance story: the static TSA6xx pass
proves debit/credit discipline over the code's control-flow graph, and this
ledger proves it over *actual executions* — the two cross-check each other
in CI (the chaos matrix and the d2h/scheduler suites run with the knob on).

When the knob is set, every pipeline :class:`~.scheduler._Budget` carries a
:class:`BudgetLedger`: each debit is tagged with its **owner** (the
pipeline's label) and its **site** — the first stack frame outside the
ledger/budget plumbing, i.e. the line of code that made the reservation
(the engine's admission, a stage body's ``recost``, …). Credits consume
entries by exact amount when one matches, else most-recent-first, so estimate-correction idioms
(``credit(cost); debit(nbytes)``) and aggregated sweeps
(``credit(outstanding)``) both reconcile.

At pipeline close AND on every abort path the scheduler calls
:meth:`BudgetLedger.assert_balanced`: any outstanding bytes raise
:class:`LedgerLeakError` naming each leaking site and the leaked amount —
turning "the budget drifted" (a symptom the PR 5/PR 6 leaks showed only as
slow admission starvation) into a one-line attribution at the moment the
invariant broke.

Production jobs leave the knob unset: no ledger object is ever allocated
and the budget hot path stays two integer adds.
"""

from __future__ import annotations

import os
import threading
import traceback
from typing import List, Optional, Tuple

__all__ = ["BudgetLedger", "LedgerLeakError", "maybe_ledger"]


class LedgerLeakError(RuntimeError):
    """The budget ledger found outstanding (or over-credited) bytes at a
    point where the pipeline asserts balance (close/abort)."""


def _origin_site() -> str:
    """file:line(function) of the frame that initiated the debit/credit —
    the first frame below the ledger/budget plumbing."""
    for frame in reversed(traceback.extract_stack()):
        if os.path.basename(frame.filename) == "ledger.py":
            continue
        if frame.name in ("debit", "credit"):
            continue  # the _Budget shim in scheduler.py
        filename = frame.filename
        marker = "torchsnapshot_tpu"
        idx = filename.rfind(marker)
        if idx != -1:
            filename = filename[idx:]
        else:
            filename = filename.rsplit("/", 1)[-1]
        return f"{filename}:{frame.lineno} ({frame.name})"
    return "<unknown>"


class BudgetLedger:
    """Thread-safe debit/credit journal with per-site attribution.

    Debits append ``[site, bytes]`` entries; credits reconcile against them
    (exact-amount match preferred, else LIFO consumption). Credits that
    exceed all outstanding debits are tracked as over-credit with their own
    site — both directions of imbalance are reported.
    """

    def __init__(self, owner: str) -> None:
        self.owner = owner
        self._lock = threading.Lock()
        self._entries: List[List] = []  # [site, bytes], insertion-ordered
        self._over_credits: List[Tuple[str, int]] = []

    def record_debit(self, nbytes: int) -> None:
        site = _origin_site()
        with self._lock:
            self._entries.append([site, int(nbytes)])

    def record_credit(self, nbytes: int) -> None:
        n = int(nbytes)
        with self._lock:
            # Exact-amount match first (the debit/credit pairs of request
            # admission), most recent wins.
            for entry in reversed(self._entries):
                if entry[1] == n:
                    self._entries.remove(entry)
                    return
            # Aggregated credit (a sweep's `credit(outstanding)`): consume
            # most-recent-first.
            while n > 0 and self._entries:
                entry = self._entries[-1]
                if entry[1] <= n:
                    n -= entry[1]
                    self._entries.pop()
                else:
                    entry[1] -= n
                    n = 0
            if n > 0:
                self._over_credits.append((_origin_site(), n))

    @property
    def outstanding_bytes(self) -> int:
        with self._lock:
            return sum(e[1] for e in self._entries) - sum(
                n for _, n in self._over_credits
            )

    def open_entries(self) -> List[Tuple[str, int]]:
        """Outstanding (site, bytes) debits, insertion-ordered."""
        with self._lock:
            return [(site, n) for site, n in self._entries]

    def assert_balanced(self, context: str) -> None:
        """Raise :class:`LedgerLeakError` naming every leaking site unless
        outstanding bytes are exactly zero (both directions)."""
        with self._lock:
            entries = [(site, n) for site, n in self._entries]
            over = list(self._over_credits)
        if not entries and not over:
            return
        lines = [
            f"budget ledger imbalance at {context} (owner={self.owner}):"
        ]
        for site, n in entries:
            lines.append(f"  leaked {n} bytes debited at {site}")
        for site, n in over:
            lines.append(f"  over-credited {n} bytes at {site}")
        raise LedgerLeakError("\n".join(lines))


def maybe_ledger(owner: str) -> Optional[BudgetLedger]:
    """A :class:`BudgetLedger` when ``TORCHSNAPSHOT_TPU_DEBUG_LEDGER`` is
    set, else None (the production fast path allocates nothing)."""
    from .utils import knobs

    if not knobs.is_debug_ledger_enabled():
        return None
    return BudgetLedger(owner)
