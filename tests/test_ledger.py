"""Budget-ledger sanitizer (``TORCHSNAPSHOT_TPU_DEBUG_LEDGER``).

The runtime half of the resource-balance invariant: every debit tagged with
its owner + originating site, zero outstanding bytes asserted at pipeline
close and on abort, and a deliberate leak named by the site that debited
it. The static TSA6xx pass and these assertions cross-check each other —
the same suites run ledger-enabled in CI.
"""

import asyncio

import pytest

from torchsnapshot_tpu import ledger
from torchsnapshot_tpu.io_types import BufferStager, WriteReq
from torchsnapshot_tpu.ledger import BudgetLedger, LedgerLeakError
from torchsnapshot_tpu.scheduler import _Budget, execute_write_reqs
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# --------------------------------------------------------------- unit level


def test_ledger_disabled_by_default() -> None:
    assert ledger.maybe_ledger("x") is None
    budget = _Budget(100)
    assert budget.ledger is None
    budget.debit(10)
    budget.assert_balanced("noop")  # no ledger -> no check, no raise


def test_ledger_enabled_by_knob_and_balanced_close_is_quiet() -> None:
    with knobs.override_debug_ledger(True):
        budget = _Budget(100, owner="unit")
        assert isinstance(budget.ledger, BudgetLedger)
        budget.debit(30)
        budget.debit(20)
        budget.credit(20)
        budget.credit(30)
        budget.assert_balanced("close")


def test_ledger_leak_names_owner_site_and_bytes() -> None:
    with knobs.override_debug_ledger(True):
        budget = _Budget(100, owner="unit-owner")

        def leaky_site() -> None:
            budget.debit(64)

        leaky_site()
        with pytest.raises(LedgerLeakError) as exc:
            budget.assert_balanced("close")
        msg = str(exc.value)
        assert "owner=unit-owner" in msg
        assert "64 bytes" in msg
        assert "leaky_site" in msg
        assert "test_ledger.py" in msg


def test_ledger_estimate_correction_and_aggregate_credit() -> None:
    with knobs.override_debug_ledger(True):
        budget = _Budget(1000, owner="unit")
        # Estimate correction: debit(cost) ... credit(cost); debit(nbytes).
        budget.debit(100)
        budget.credit(100)
        budget.debit(87)
        # Streamed chunks + aggregated cleanup credit.
        budget.debit(10)
        budget.debit(10)
        budget.credit(107)  # 87 + 10 + 10 consumed most-recent-first
        budget.assert_balanced("close")


def test_ledger_over_credit_is_reported() -> None:
    with knobs.override_debug_ledger(True):
        budget = _Budget(100, owner="unit")
        budget.credit(5)
        with pytest.raises(LedgerLeakError) as exc:
            budget.assert_balanced("close")
        assert "over-credited 5 bytes" in str(exc.value)


def test_ledger_outstanding_and_open_entries() -> None:
    led = BudgetLedger("x")
    led.record_debit(7)
    led.record_debit(3)
    assert led.outstanding_bytes == 10
    [(site_a, a), (site_b, b)] = led.open_entries()
    assert (a, b) == (7, 3)
    assert "test_ledger.py" in site_a and "test_ledger.py" in site_b
    led.record_credit(3)
    assert led.outstanding_bytes == 7


# ------------------------------------------------------------ pipeline level


class _Stager(BufferStager):
    def __init__(self, nbytes: int, fail: bool = False) -> None:
        self.nbytes = nbytes
        self.fail = fail

    async def stage_buffer(self, executor=None):
        if self.fail:
            raise RuntimeError("staging blew up")
        return b"x" * self.nbytes

    def get_staging_cost_bytes(self) -> int:
        return self.nbytes


def test_pipeline_close_balanced_under_ledger() -> None:
    with knobs.override_debug_ledger(True):
        storage = MemoryStoragePlugin(root="ledger-ok")
        reqs = [WriteReq(f"p{i}", _Stager(100)) for i in range(8)]

        async def go():
            pending = await execute_write_reqs(reqs, storage, 10**6, rank=0)
            await pending.complete()
            return pending

        pending = _run(go())
        assert pending.budget_balanced  # ledger asserted at close already


def test_pipeline_abort_balanced_under_ledger() -> None:
    with knobs.override_debug_ledger(True):
        storage = MemoryStoragePlugin(root="ledger-abort")
        reqs = [WriteReq(f"p{i}", _Stager(100, fail=(i == 3))) for i in range(6)]

        async def go():
            pending = await execute_write_reqs(reqs, storage, 10**6, rank=0)
            await pending.complete()

        # The staging failure propagates (NOT a LedgerLeakError): the abort
        # path credited every debit, so the ledger assertion stayed quiet.
        with pytest.raises(RuntimeError, match="staging blew up"):
            _run(go())


def test_pipeline_injected_leak_raises_at_abort_with_site() -> None:
    """A deliberately-unbalanced pipeline (a debit the abort sweep cannot
    see) is caught by the abort-path assertion and named by site."""
    with knobs.override_debug_ledger(True):
        storage = MemoryStoragePlugin(root="ledger-leak")
        reqs = [
            WriteReq("ok", _Stager(100)),
            # Deferred so the failure fires in the background drain — after
            # the rogue debit below has been made.
            WriteReq("boom", _Stager(100, fail=True), defer_staging=True),
        ]

        async def go():
            pending = await execute_write_reqs(reqs, storage, 10**6, rank=0)

            def rogue_reservation():
                # Emulates the PR 5 bug class: bytes debited outside the
                # task tables, invisible to _abort_inflight's sweep.
                pending._pipeline.budget.debit(4242)

            rogue_reservation()
            await pending.complete()

        with pytest.raises(LedgerLeakError) as exc:
            _run(go())
        msg = str(exc.value)
        assert "4242 bytes" in msg
        assert "rogue_reservation" in msg
