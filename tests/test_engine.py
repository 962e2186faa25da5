"""The unified dataflow engine: graph execution, budget-handoff edges,
priority classes, chunk-granular preemption, and abort-sweep balance.

The engine is the single executor all three scheduler paths lower onto
(see ``engine/``); these tests pin its semantics directly — the scheduler
suites pin the lowered paths."""

import asyncio
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.engine import (
    GraphExecutor,
    Node,
    Priority,
    current_priority,
    demand_scope,
    get_arbiter,
    parse_priority,
    pause_point,
    priority_scope,
    run_graph,
)
from torchsnapshot_tpu.utils import knobs


@pytest.fixture(autouse=True)
def _debug_ledger():
    """The engine suite runs under the budget-ledger sanitizer: every
    graph asserts zero outstanding bytes at close/abort, naming leaking
    sites."""
    with knobs.override_debug_ledger(True):
        yield


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _node(kind, body, **kw):
    return Node(kind, body, **kw)


# ----------------------------------------------------------------- basics


def test_chain_executes_in_order_with_payload_handoff() -> None:
    events = []

    async def a(_ctx, _payload):
        events.append("a")
        return 41

    async def b(_ctx, payload):
        events.append(("b", payload))
        return payload + 1

    async def go():
        eng = GraphExecutor(budget_bytes=100, owner="t")
        eng.add(_node("stage", a, cost_bytes=10, pool="staging",
                      successor=_node("io", b, pool="io")))
        await eng.run()
        eng.assert_balanced("close")
        assert eng.all_done()

    _run(go())
    assert events == ["a", ("b", 41)]


def test_budget_reservation_rides_the_edge() -> None:
    """The admission debit is held across the whole chain and credited only
    when the edge's final node completes."""
    seen = {}

    async def stage(ctx, _payload):
        seen["during_stage"] = ctx.engine.budget.available
        return b"x" * 30

    async def io(ctx, buf):
        seen["during_io"] = ctx.engine.budget.available
        return None

    async def go():
        eng = GraphExecutor(budget_bytes=100, owner="t")
        eng.add(_node("stage", stage, cost_bytes=30, pool="staging",
                      successor=_node("io", io, pool="io")))
        await eng.run()
        assert eng.budget.available == 100
        eng.assert_balanced("close")

    _run(go())
    assert seen["during_stage"] == 70
    assert seen["during_io"] == 70


def test_recost_corrects_estimate() -> None:
    async def stage(ctx, _payload):
        ctx.recost(55)
        return None

    async def go():
        eng = GraphExecutor(budget_bytes=100, owner="t")
        eng.add(_node("stage", stage, cost_bytes=10, pool="staging"))
        await eng.run()
        assert eng.budget.available == 100
        assert eng.budget.high_water_bytes == 55
        eng.assert_balanced("close")

    _run(go())


def test_over_budget_node_admitted_only_when_engine_empty() -> None:
    order = []

    def body(name, delay=0.01):
        async def run(_ctx, _payload):
            order.append(("start", name))
            await asyncio.sleep(delay)
            order.append(("end", name))

        return run

    async def go():
        eng = GraphExecutor(budget_bytes=100, owner="t")
        # Head-of-line: the huge node is first (cost-desc order is the
        # builder's contract) and blocks until the engine is empty... but
        # with nothing in flight it admits immediately despite the budget.
        eng.add(_node("stage", body("huge"), cost_bytes=10_000, pool="staging"))
        eng.add(_node("stage", body("small"), cost_bytes=10, pool="staging"))
        await eng.run()
        eng.assert_balanced("close")

    _run(go())
    assert order[0] == ("start", "huge")


def test_failure_credits_and_abort_sweeps_balanced() -> None:
    async def ok(_ctx, _payload):
        await asyncio.sleep(0.05)

    async def boom(_ctx, _payload):
        raise RuntimeError("node exploded")

    async def go():
        eng = GraphExecutor(budget_bytes=1000, owner="t")
        for _ in range(4):
            eng.add(_node("stage", ok, cost_bytes=100, pool="staging"))
        eng.add(_node("stage", boom, cost_bytes=100, pool="staging"))
        with pytest.raises(RuntimeError, match="node exploded"):
            await eng.run()
        await eng.abort()
        assert eng.budget.available == 1000
        eng.assert_balanced("abort")

    _run(go())


def test_run_graph_background_helper_balances() -> None:
    hits = []

    def make(i):
        async def body(_ctx, _payload):
            hits.append(i)

        return body

    async def go():
        eng = await run_graph(
            [_node("verify", make(i), cost_bytes=10) for i in range(8)],
            budget_bytes=25,
            owner="t-verify",
            caps={"io": lambda: 2},
        )
        assert eng.budget.available == 25

    _run(go())
    assert sorted(hits) == list(range(8))


def test_pool_caps_bound_concurrency() -> None:
    live = {"n": 0, "peak": 0}

    async def body(_ctx, _payload):
        live["n"] += 1
        live["peak"] = max(live["peak"], live["n"])
        await asyncio.sleep(0.01)
        live["n"] -= 1

    async def go():
        eng = GraphExecutor(
            budget_bytes=10**6, owner="t", caps={"io": lambda: 3}
        )
        for _ in range(12):
            eng.add(_node("io", body, cost_bytes=1, pool="io"))
        await eng.run()
        eng.assert_balanced("close")

    _run(go())
    assert live["peak"] <= 3


# ------------------------------------------------------------ QoS classes


def test_parse_priority_and_scope() -> None:
    assert parse_priority("foreground") is Priority.FOREGROUND
    assert parse_priority("NORMAL") is Priority.NORMAL
    assert parse_priority(Priority.BACKGROUND) is Priority.BACKGROUND
    assert parse_priority(None) is None
    with pytest.raises(ValueError, match="unknown QoS class"):
        parse_priority("turbo")
    assert current_priority() is Priority.NORMAL
    with priority_scope(Priority.BACKGROUND):
        assert current_priority() is Priority.BACKGROUND
    assert current_priority() is Priority.NORMAL


def test_arbiter_preemption_ordering() -> None:
    arb = get_arbiter()
    assert not arb.preempted(Priority.BACKGROUND)
    with demand_scope(Priority.NORMAL):
        assert arb.preempted(Priority.BACKGROUND)
        assert not arb.preempted(Priority.NORMAL)
        assert not arb.preempted(Priority.FOREGROUND)
        with demand_scope(Priority.FOREGROUND):
            assert arb.preempted(Priority.NORMAL)
            assert arb.preempted(Priority.BACKGROUND)
            assert not arb.preempted(Priority.FOREGROUND)
    assert not arb.preempted(Priority.BACKGROUND)


def test_qos_knob_off_disables_preemption() -> None:
    arb = get_arbiter()
    with demand_scope(Priority.FOREGROUND):
        with knobs.override_qos(False):
            assert not arb.preempted(Priority.BACKGROUND)
        assert arb.preempted(Priority.BACKGROUND)


def test_background_engine_pauses_admission_under_foreground_demand() -> None:
    """While FOREGROUND demand is registered, a BACKGROUND engine admits
    nothing new; the moment it clears, the engine drains — and counts the
    preemption episode."""
    done = []

    def make(i):
        async def body(_ctx, _payload):
            done.append(i)

        return body

    async def go():
        eng = GraphExecutor(
            budget_bytes=10**6, owner="bg", priority=Priority.BACKGROUND
        )
        for i in range(4):
            eng.add(_node("io", make(i), cost_bytes=1, pool="io"))
        arb = get_arbiter()
        arb.register(Priority.FOREGROUND)
        runner = asyncio.ensure_future(eng.run())
        await asyncio.sleep(0.15)
        assert done == []  # paused: nothing admitted
        arb.unregister(Priority.FOREGROUND)
        await asyncio.wait_for(runner, timeout=10)
        assert sorted(done) == [0, 1, 2, 3]
        assert eng.preemptions >= 1
        assert eng.preempted_wait_s > 0.05
        eng.assert_balanced("close")

    with knobs.override_qos_poll_s(0.01):
        _run(go())


def test_max_pause_bounds_starvation() -> None:
    """A continuously-preempted BACKGROUND engine still trickles work once
    per max-pause bound — demand that never clears cannot wedge it."""
    done = []

    async def body(_ctx, _payload):
        done.append(1)

    async def go():
        eng = GraphExecutor(
            budget_bytes=10**6, owner="bg", priority=Priority.BACKGROUND
        )
        eng.add(_node("io", body, cost_bytes=1, pool="io"))
        arb = get_arbiter()
        arb.register(Priority.FOREGROUND)
        try:
            await asyncio.wait_for(eng.run(), timeout=10)
        finally:
            arb.unregister(Priority.FOREGROUND)
        assert done == [1]

    with knobs.override_qos_poll_s(0.01), knobs.override_qos_max_pause_s(0.1):
        _run(go())


def test_pause_point_yields_and_resumes() -> None:
    async def go():
        arb = get_arbiter()
        waited = await pause_point(Priority.BACKGROUND)
        assert waited == 0.0  # fast path: no demand, no pause
        arb.register(Priority.FOREGROUND)

        async def release():
            await asyncio.sleep(0.1)
            arb.unregister(Priority.FOREGROUND)

        rel = asyncio.ensure_future(release())
        waited = await pause_point(Priority.BACKGROUND)
        await rel
        assert waited >= 0.05

    with knobs.override_qos_poll_s(0.01):
        _run(go())


# ----------------------------------------------- end-to-end QoS preemption


def test_foreground_restore_preempts_background_drain(tmp_path) -> None:
    """The tentpole scenario, in miniature: a BACKGROUND async-take drain
    and a FOREGROUND restore share one process. The restore's demand
    pauses the drain's admissions (observed via the drain engine's
    preemption counters), both operations complete, verify clean, and
    restore bit-exact."""
    rng = np.random.default_rng(7)
    drain_state = StateDict(
        **{f"w{i}": rng.standard_normal((64, 256)).astype(np.float32)
           for i in range(8)}
    )
    fg_state = StateDict(v=rng.standard_normal(1024).astype(np.float32))
    fg_path = str(tmp_path / "fg")
    Snapshot.take(fg_path, {"m": fg_state})

    with knobs.override_qos_poll_s(0.005):
        pending = Snapshot.async_take(
            str(tmp_path / "bg"), {"m": drain_state}, qos="background"
        )
        # Foreground restore while the drain runs.
        restored = StateDict(v=np.zeros(1024, dtype=np.float32))
        Snapshot(fg_path).restore({"m": restored}, qos="foreground")
        assert np.array_equal(restored["v"], fg_state["v"])
        pending.wait()

    assert Snapshot(str(tmp_path / "bg")).verify() == {}
    back = StateDict(
        **{f"w{i}": np.zeros((64, 256), dtype=np.float32) for i in range(8)}
    )
    Snapshot(str(tmp_path / "bg")).restore({"m": back})
    for i in range(8):
        assert np.array_equal(back[f"w{i}"], drain_state[f"w{i}"])


def test_drains_through_the_fs_plugin_borrow_their_bounce_buffers_and_return_them(
    tmp_path,
) -> None:
    """A synchronous take, then a background drain beside a foreground
    restore, every leaf through the native engine: the engine lends the
    writes its bounce buffers, never more of them than the plugin has
    writer slots, has them all back when the drains end, and the drains'
    stats say into which pages every written byte was copied."""
    import json
    import os

    from torchsnapshot_tpu import native

    lib = native.load_native()
    if lib is None:
        pytest.skip("native IO engine unavailable")
    rng = np.random.default_rng(11)
    state = StateDict(
        **{f"w{i}": rng.standard_normal((64, 256)).astype(np.float32)
           for i in range(8)}
    )
    before = native.write_bounce_stats(lib)
    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_qos_poll_s(0.005):
        Snapshot.take(str(tmp_path / "sync"), {"m": state})
        pending = Snapshot.async_take(
            str(tmp_path / "bg"), {"m": state}, qos="background"
        )
        restored = StateDict(
            **{f"w{i}": np.zeros((64, 256), dtype=np.float32) for i in range(8)}
        )
        Snapshot(str(tmp_path / "sync")).restore({"m": restored}, qos="foreground")
        pending.wait()
    after = native.write_bounce_stats(lib)
    slots = knobs.get_direct_io_concurrency()
    assert after["lent"] == 0 and after["kept"] <= max(slots, before["kept"])
    assert after["allocated"] - before["allocated"] <= slots
    assert after["kept_bytes"] <= 256 << 20
    for name in ("sync", "bg"):
        with open(os.path.join(tmp_path, name, ".telemetry", "rank_0.json")) as f:
            artifact = json.load(f)
        stats = artifact["pipeline_stats_s"]
        # (Not ==: a synchronous take's stats leave out a pwrite that began
        # between its two accounting windows.)
        assert 0 < stats["mount_write_bytes"] <= sum(v.nbytes for v in state.values())
        bounced = stats["write_bounce_warm_bytes"] + stats["write_bounce_fresh_bytes"]
        # 0: a filesystem that refuses O_DIRECT, where nothing is bounced.
        assert bounced in (0.0, stats["mount_write_bytes"])
        assert Snapshot(str(tmp_path / name)).verify() == {}
    for i in range(8):
        assert np.array_equal(restored[f"w{i}"], state[f"w{i}"])


def test_preemption_is_thread_safe_across_event_loops() -> None:
    """The arbiter is consulted from two event loops on two threads (the
    production shape: drain thread + main-thread restore) without locks
    leaking or counters corrupting."""
    arb = get_arbiter()
    results = []

    def bg_thread():
        async def body(_ctx, _payload):
            await asyncio.sleep(0.001)

        async def go():
            eng = GraphExecutor(
                budget_bytes=10**6, owner="bg", priority=Priority.BACKGROUND
            )
            for _ in range(20):
                eng.add(_node("io", body, cost_bytes=1, pool="io"))
            await eng.run()
            eng.assert_balanced("close")
            results.append("bg-done")

        _run(go())

    with knobs.override_qos_poll_s(0.005):
        t = threading.Thread(target=bg_thread)
        t.start()
        # Pulse foreground demand from the main thread while the
        # background engine runs on its own loop.
        for _ in range(3):
            with demand_scope(Priority.FOREGROUND):
                time.sleep(0.01)
            time.sleep(0.005)
        t.join(timeout=30)
    assert results == ["bg-done"]
