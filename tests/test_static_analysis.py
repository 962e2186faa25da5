"""Fixture tests for the checkpoint-invariant static analyzer (dev/analyze).

Each pass is proven both ways: it flags a seeded violation, and it stays
quiet on the compliant idiom the library actually uses (executor-wrapped
I/O, reaped tasks, registered knobs, with-scoped cataloged spans). A final
smoke test runs the full analyzer over the real repo and requires zero
non-baselined findings — the same gate ``python dev/lint.py`` runs in CI.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from dev.analyze import (  # noqa: E402
    AnalysisContext,
    apply_baseline,
    default_context,
    load_baseline,
    run_passes,
    write_baseline,
)


def make_ctx(tmp_path, files, **kwargs):
    """A miniature repo: ``files`` maps relpath -> dedented source."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    lib = sorted(r for r in files if r.endswith(".py"))
    return AnalysisContext(root=str(tmp_path), lib_files=lib, **kwargs)


def codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# Pass 1: async-safety
# ---------------------------------------------------------------------------


def test_async_safety_flags_blocking_calls(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import time
            import os

            async def bad_sleep():
                time.sleep(1)

            async def bad_open():
                with open("/tmp/x") as f:
                    return f.read()

            async def bad_rename(a, b):
                os.replace(a, b)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA101", "TSA101", "TSA101"]
    assert {f.key for f in found} == {
        "bad_sleep:time.sleep",
        "bad_open:open",
        "bad_rename:os.replace",
    }


def test_async_safety_quiet_on_executor_idiom(tmp_path):
    # The library's actual pattern: blocking work lives in a nested sync
    # thunk passed to run_in_executor — no blocking call node in async code.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio
            import os

            async def good(path, executor):
                def work():
                    with open(path, "rb") as f:
                        return f.read()

                loop = asyncio.get_event_loop()
                data = await loop.run_in_executor(executor, work)
                await loop.run_in_executor(executor, os.remove, path)
                await asyncio.sleep(0)
                return data
            """
        },
    )
    assert run_passes(ctx) == []


def test_async_safety_executor_future_result(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            async def bad(executor):
                fut = executor.submit(len, b"x")
                return fut.result()

            async def also_bad(executor):
                return executor.submit(len, b"x").result()

            async def fine(done_task):
                # asyncio.Task.result() on a reaped task does not block.
                return done_task.result()
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA102", "TSA102"]


def test_async_safety_loop_reentry_and_noqa(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import time

            async def bad(loop, coro):
                return loop.run_until_complete(coro)

            async def suppressed():
                time.sleep(0.01)  # noqa: TSA101
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA103"]


# ---------------------------------------------------------------------------
# Pass 2: task-leak
# ---------------------------------------------------------------------------


def test_task_leak_flags_discarded_and_unreaped(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio

            async def discarded(coro):
                asyncio.ensure_future(coro)

            async def unreaped(coro):
                task = asyncio.create_task(coro)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA201", "TSA202"]


def test_task_leak_quiet_on_reaped_idioms(tmp_path):
    # The scheduler's patterns: dict-keyed tasks reaped via .result(),
    # gathered lists, and add_done_callback fire-and-forget.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio

            async def dict_reap(reqs):
                tasks = {}
                for r in reqs:
                    t = asyncio.ensure_future(r.run())
                    tasks[t] = r
                done, _ = await asyncio.wait(set(tasks))
                for t in done:
                    t.result()

            async def gathered(coros):
                tasks = [asyncio.ensure_future(c) for c in coros]
                return await asyncio.gather(*tasks)

            async def fire_and_forget(coro, handler):
                asyncio.ensure_future(coro).add_done_callback(handler)

            async def awaited(coro):
                return await asyncio.ensure_future(coro)
            """
        },
    )
    assert run_passes(ctx) == []


def test_task_leak_flags_discarded_and_unreaped_executor_futures(tmp_path):
    # The TSA2xx extension to concurrent.futures: the PR 5 `_reap` bug shape
    # was exactly a spawned unit of work whose failure nobody collected.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            def discarded(pool, job):
                pool.submit(job)

            def unreaped(pool, job):
                fut = pool.submit(job)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA203", "TSA204"]


def test_task_leak_quiet_on_collected_executor_futures(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio

            def collected(pool, job):
                fut = pool.submit(job)
                return fut.result()

            async def wrapped(pool, job):
                fut = pool.submit(job)
                return await asyncio.wrap_future(fut)

            def cancelled_on_error(pool, jobs):
                futs = [pool.submit(j) for j in jobs]
                try:
                    return [f.result() for f in futs]
                except Exception:
                    for f in futs:
                        f.cancel()
                    raise

            def chained(pool, job, handler):
                pool.submit(job).add_done_callback(handler)

            def submit(x):
                # A bare function named `submit` is not an executor call.
                pass

            def uses_bare_submit(x):
                submit(x)
            """
        },
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Pass 3: knob-registry drift
# ---------------------------------------------------------------------------

_KNOBS = """
import os

_ENV_A = "TORCHSNAPSHOT_TPU_ALPHA"
_ENV_B = "TORCHSNAPSHOT_TPU_BETA"


def get_alpha():
    return os.environ.get(_ENV_A)


def get_beta():
    return os.environ.get(_ENV_B)
"""


def _knob_ctx(tmp_path, lib_src, doc_src):
    return make_ctx(
        tmp_path,
        {"pkg/knobs.py": _KNOBS, "pkg/lib.py": lib_src, "docs/knobs.md": doc_src},
        knobs_path="pkg/knobs.py",
        catalog_path="docs/knobs.md",
        doc_files=["docs/knobs.md"],
    )


def test_knob_drift_flags_literal_outside_registry(tmp_path):
    ctx = _knob_ctx(
        tmp_path,
        """
        import os

        def bad():
            return os.environ.get("TORCHSNAPSHOT_TPU_ALPHA")
        """,
        "`TORCHSNAPSHOT_TPU_ALPHA` and `TORCHSNAPSHOT_TPU_BETA`\n",
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA301"]
    assert found[0].path == "pkg/lib.py"


def test_knob_drift_flags_undocumented_and_dead_knobs(tmp_path):
    ctx = _knob_ctx(
        tmp_path,
        "from . import knobs\n",
        "`TORCHSNAPSHOT_TPU_ALPHA` and `TORCHSNAPSHOT_TPU_GONE`\n",
    )
    found = run_passes(ctx)
    # BETA exists but is undocumented; GONE is documented but gone.
    assert codes(found) == ["TSA302", "TSA303"]
    by_code = {f.code: f for f in found}
    assert by_code["TSA302"].key == "TORCHSNAPSHOT_TPU_BETA"
    assert by_code["TSA303"].key == "TORCHSNAPSHOT_TPU_GONE"


def test_knob_drift_quiet_when_consistent(tmp_path):
    ctx = _knob_ctx(
        tmp_path,
        """
        from . import knobs

        def good():
            return knobs.get_alpha() or knobs.get_beta()
        """,
        "`TORCHSNAPSHOT_TPU_ALPHA` and `TORCHSNAPSHOT_TPU_BETA`\n",
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Pass 4: telemetry discipline
# ---------------------------------------------------------------------------

_TELEMETRY_DOC = """
<!-- analyzer: telemetry-catalog-begin -->
    span  storage.write
    span  scheduler.stage
    metric  storage.<plugin>.write_bytes
    metric  cloud_retry.<plugin>.retries
<!-- analyzer: telemetry-catalog-end -->
"""


def _telemetry_ctx(tmp_path, lib_src):
    return make_ctx(
        tmp_path,
        {"lib.py": lib_src, "docs/obs.md": _TELEMETRY_DOC},
        telemetry_catalog_path="docs/obs.md",
    )


def test_telemetry_flags_span_outside_with(tmp_path):
    ctx = _telemetry_ctx(
        tmp_path,
        """
        from . import telemetry

        def bad():
            sp = telemetry.span("storage.write", cat="storage")
            return sp
        """,
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA401"]


def test_telemetry_flags_uncataloged_names(tmp_path):
    ctx = _telemetry_ctx(
        tmp_path,
        """
        from . import telemetry

        def bad(nbytes, plugin):
            with telemetry.span("storage.mystery", cat="storage"):
                telemetry.counter_add("storage.fs.mystery_bytes", nbytes)
                telemetry.counter_add(f"made_up.{plugin}.retries")
        """,
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA402", "TSA402", "TSA402"]


def test_telemetry_quiet_on_compliant_sites(tmp_path):
    ctx = _telemetry_ctx(
        tmp_path,
        """
        from . import telemetry

        def good(nbytes, label, tm, t0, dur):
            with telemetry.span("storage.write", cat="storage"):
                telemetry.counter_add("storage.fs.write_bytes", nbytes)
                telemetry.counter_add(f"cloud_retry.{label}.retries")
            # add_span records an already-closed interval: exempt from 401,
            # name still checked.
            tm.add_span("scheduler.stage", "scheduler", t0, dur, {})
        """,
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Pass 5: manifest schema
# ---------------------------------------------------------------------------


def test_manifest_schema_flags_unserializable_field(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "manifest.py": """
            from dataclasses import dataclass
            from typing import List, Optional

            import numpy as np


            @dataclass
            class Entry:
                type: str


            @dataclass
            class GoodEntry(Entry):
                location: str
                shape: List[int]
                byte_range: Optional[List[int]] = None


            @dataclass
            class BadEntry(Entry):
                payload: np.ndarray
            """
        },
        manifest_path="manifest.py",
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA501"]
    assert found[0].key == "BadEntry.payload"


def test_manifest_schema_allows_nested_schema_classes(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "manifest.py": """
            from dataclasses import dataclass
            from typing import Dict, List


            @dataclass
            class Shard:
                offsets: List[int]
                sizes: List[int]


            @dataclass
            class Entry:
                type: str


            @dataclass
            class ShardedEntry(Entry):
                shards: List[Shard]
                extra: Dict[str, "Shard"]
            """
        },
        manifest_path="manifest.py",
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Pass 6: resource balance (flow-sensitive)
# ---------------------------------------------------------------------------


def test_resource_balance_flags_await_between_debit_and_protection(tmp_path):
    # The PR 5 regression shape: the reservation is balanced on the happy
    # path, but cancellation (or a failure) at the await strands it.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            async def admit_and_wait(self, req):
                cost = req.cost
                self.budget.debit(cost)
                buf = await req.stage()
                self.budget.credit(cost)
                return buf
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA602"]
    assert "cancellation" in found[0].message


def test_resource_balance_flags_early_return_and_raise_paths(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            def early_return(self, cost, hurry):
                self.budget.debit(cost)
                if hurry:
                    return None
                self.budget.credit(cost)

            def unprotected_raise(self, cost, req):
                self.budget.debit(cost)
                validate(req)
                self.budget.credit(cost)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA601", "TSA601"]


def test_resource_balance_quiet_on_sanctioned_idioms(tmp_path):
    # The scheduler's real shapes: try/finally protection, task-table
    # handoff, ledger-counter accumulation, estimate correction.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            async def protected(self, cost, req):
                self.budget.debit(cost)
                try:
                    buf = await req.stage()
                finally:
                    self.budget.credit(cost)
                return buf

            def handed_to_task_table(self, req, cost, task):
                self.budget.debit(cost)
                self.staging_tasks[task] = (req, cost)

            async def counter_ledger(self, budget, chunk_est, agen):
                outstanding = 0
                try:
                    while True:
                        budget.debit(chunk_est)
                        outstanding += chunk_est
                        buf = await agen.next()
                        if buf is None:
                            break
                finally:
                    if outstanding:
                        budget.credit(outstanding)

            def estimate_correction(self, cost, buf):
                nbytes = memoryview(buf).nbytes
                self.budget.credit(cost)
                self.budget.debit(nbytes)
                self.ready_for_io.append((self.path, buf))
            """
        },
    )
    assert run_passes(ctx) == []


def test_resource_balance_quiet_when_except_credits(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            async def credits_on_error(self, cost, req):
                self.budget.debit(cost)
                try:
                    buf = await req.stage()
                except BaseException:
                    self.budget.credit(cost)
                    raise
                self.handoff[req.path] = (buf, cost)
            """
        },
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Pass 7: cross-thread mutation
# ---------------------------------------------------------------------------


def test_thread_safety_flags_unguarded_cross_thread_attribute(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio

            class Pipeline:
                def __init__(self):
                    self.bytes_done = 0

                async def drain(self, executor, chunk):
                    def work():
                        self.bytes_done += chunk.nbytes
                        return chunk

                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(executor, work)

                def reset(self):
                    self.bytes_done = 0
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA701"]
    assert "bytes_done" in found[0].message


def test_thread_safety_quiet_on_locks_and_safe_types(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio
            import threading
            from queue import Queue

            class Guarded:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0
                    self.results = Queue()

                async def drain(self, executor, chunk):
                    def work():
                        with self._lock:
                            self.count += 1
                        self.results = Queue()
                        return chunk

                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(executor, work)

                def reset(self):
                    with self._lock:
                        self.count = 0
                    self.results = Queue()

                def method_calls_are_fine(self, tracker):
                    # Mutating THROUGH a thread-safe object is method calls,
                    # which the pass never flags.
                    tracker.note_staged(1)
            """
        },
    )
    assert run_passes(ctx) == []


def test_thread_safety_flags_nonlocal_rebinding(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import asyncio

            async def tally(executor, chunks):
                total = 0

                def work(c):
                    nonlocal total
                    total += c.nbytes

                loop = asyncio.get_running_loop()
                for c in chunks:
                    await loop.run_in_executor(executor, work, c)
                total = -1
                return total
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA702"]


# ---------------------------------------------------------------------------
# Pass 8: fault-injection coverage
# ---------------------------------------------------------------------------

_CONTRACT = """
import abc


class StoragePlugin(abc.ABC):
    async def write(self, write_io):
        ...

    async def read(self, read_io):
        ...

    async def list_prefix(self, prefix):
        ...

    async def close(self):
        ...
"""


def _fault_ctx(tmp_path, faults_src):
    return make_ctx(
        tmp_path,
        {"pkg/io_types.py": _CONTRACT, "pkg/faults.py": faults_src},
        io_types_path="pkg/io_types.py",
        faults_path="pkg/faults.py",
    )


def test_fault_coverage_flags_unwrapped_and_unguarded_ops(tmp_path):
    ctx = _fault_ctx(
        tmp_path,
        """
        _OPS = ("write", "read", "list")
        _PASSTHROUGH_OPS = ("close",)


        class FaultyStoragePlugin:
            async def write(self, write_io):
                await self._guard("write", write_io.path)
                await self.inner.write(write_io)

            async def list_prefix(self, prefix):
                # un-guarded proxy, not declared passthrough
                return await self.inner.list_prefix(prefix)

            async def close(self):
                await self.inner.close()
        """,
    )
    found = run_passes(ctx)
    # read has no override at all; list_prefix proxies without _guard.
    assert codes(found) == ["TSA801", "TSA802"]
    by_code = {f.code: f for f in found}
    assert "read" in by_code["TSA801"].message
    assert "list_prefix" in by_code["TSA802"].message


def test_fault_coverage_flags_typoed_guard_op(tmp_path):
    ctx = _fault_ctx(
        tmp_path,
        """
        _OPS = ("write", "read", "list")
        _PASSTHROUGH_OPS = ("close",)


        class FaultyStoragePlugin:
            async def write(self, write_io):
                await self._guard("writ", write_io.path)
                await self.inner.write(write_io)

            async def read(self, read_io):
                await self._guard("read", read_io.path)
                await self.inner.read(read_io)

            async def list_prefix(self, prefix):
                await self._guard("list", prefix)
                return await self.inner.list_prefix(prefix)

            async def close(self):
                await self.inner.close()
        """,
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA803"]
    assert "writ" in found[0].message


def test_fault_coverage_quiet_when_surface_fully_wrapped(tmp_path):
    ctx = _fault_ctx(
        tmp_path,
        """
        _OPS = ("write", "read", "list")
        _PASSTHROUGH_OPS = ("close",)


        class FaultyStoragePlugin:
            async def write(self, write_io):
                await self._guard("write", write_io.path)
                await self.inner.write(write_io)

            async def read(self, read_io):
                await self._guard("read", read_io.path)
                await self.inner.read(read_io)

            async def list_prefix(self, prefix):
                await self._guard("list", prefix)
                return await self.inner.list_prefix(prefix)

            async def close(self):
                await self.inner.close()
        """,
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Pass 9: collective discipline
# ---------------------------------------------------------------------------


def test_collective_discipline_flags_rank_conditional_broadcast(tmp_path):
    # The seeded-hazard shapes from the acceptance criteria: a
    # rank-conditional broadcast_object (one taken straight, one through a
    # derived flag) — the ranks on the other side wait on a key nobody
    # posts.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            def bad_direct(coord, rank, cfg):
                if rank == 0:
                    coord.broadcast_object(cfg, src=0)

            def bad_derived(coord, rank):
                is_leader = rank == 0
                if is_leader:
                    coord.barrier()
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA901", "TSA901"]
    assert "broadcast_object" in found[0].message
    assert "rank identity" in found[0].message
    assert "derived from rank identity" in found[1].message


def test_collective_discipline_flags_time_and_gather_conditionals(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import time

            def bad_time(coord, deadline):
                if time.monotonic() > deadline:
                    coord.barrier()

            def bad_gather(coord, obj):
                gathered = coord.gather_object(obj, dst=0)
                if gathered is not None:
                    coord.barrier()
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA901", "TSA901"]
    assert "wall-clock" in found[0].message
    assert "gather_object result" in found[1].message


def test_collective_discipline_flags_barrier_in_except(tmp_path):
    # The acceptance shape "a barrier added only in an except branch": the
    # happy-path ranks never reach it — one failure becomes a fleet hang.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            def bad_handler(coord, work):
                try:
                    work()
                except Exception:
                    coord.barrier()
                    raise

            def bad_finally(barrier, work):
                try:
                    work()
                finally:
                    barrier.arrive()
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA902", "TSA902"]
    assert "`except` handler" in found[0].message
    assert "`finally` block" in found[1].message


def test_collective_discipline_flags_data_dependent_collective_loop(tmp_path):
    # The acceptance shape "a data-dependent collective loop": trip counts
    # derived from local filesystem state / wall clock differ across ranks.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import os
            import time

            def bad_listing_loop(coord, d):
                for f in os.listdir(d):
                    coord.broadcast_object(f, src=0)

            def bad_deadline_loop(ns, deadline):
                while time.monotonic() < deadline:
                    ns.add("progress", 1)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA903", "TSA903"]
    assert "local filesystem state" in found[0].message
    assert "wall-clock" in found[1].message
    assert "store.add" in found[1].message


def test_collective_discipline_quiet_on_sanctioned_idioms(tmp_path):
    # The library's real shapes: leader-only work BETWEEN symmetric barrier
    # phases, a world-size gate on a barrier object merely parameterized by
    # rank, collectives matched on both sides of a rank branch, loops over
    # broadcast/knob-derived bounds, report_error in handlers, and
    # constant-test polling loops.
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            from . import knobs

            def leader_commit(barrier, rank, write_metadata):
                barrier.arrive()
                if rank == 0:
                    write_metadata()
                barrier.depart()

            def world_size_gate(store, coord, rank, path):
                barrier = None
                if coord.get_world_size() > 1:
                    barrier = LinearBarrier(
                        store=store, barrier_id=path, rank=rank, world_size=2
                    )
                if barrier is not None:
                    barrier.arrive()
                    barrier.depart()

            def matched_roles(coord, rank, cfg):
                if rank == 0:
                    decision = coord.broadcast_object(cfg, src=0)
                else:
                    decision = coord.broadcast_object(None, src=0)
                return decision

            def spmd_loop(coord, app_state):
                keys = coord.broadcast_object(sorted(app_state), src=0)
                for key in keys:
                    coord.broadcast_object(key, src=0)

            def knob_bounded_attempts(ns):
                for attempt in range(1 + knobs.get_reelect_max()):
                    ns.try_get(str(attempt))

            def error_fanout(barrier, work, phase):
                try:
                    work()
                except Exception as e:
                    barrier.report_error(e, phase=phase)
                    raise

            def polling(ns, key):
                while True:
                    payload = ns.try_get(key)
                    if payload is not None:
                        return payload
            """
        },
    )
    assert run_passes(ctx) == []


def test_collective_discipline_spmd_pure_marker(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import os

            from . import knobs

            def bad_fs_probe(entry):  # spmd-pure
                if os.path.exists(entry.location):
                    return False
                return entry.nbytes <= knobs.get_max_bytes()

            def bad_rank_read(entry, rank):  # spmd-pure
                return entry.nbytes + rank

            def good_plan(entry):  # spmd-pure
                limit = knobs.get_max_bytes()
                return [c.location for c in entry.chunks if c.nbytes <= limit]

            def unmarked_impure_is_fine(entry):
                return os.path.exists(entry.location)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA904", "TSA904"]
    assert "os.path.exists" in found[0].message
    assert "rank identity" in found[1].message


def test_collective_discipline_noqa_suppresses(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            def deliberate(coord, rank, cfg):
                if rank == 0:
                    coord.broadcast_object(cfg, src=0)  # noqa: TSA901
            """
        },
    )
    assert run_passes(ctx) == []


# ---------------------------------------------------------------------------
# Baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_written_deterministically(tmp_path):
    """--update-baseline output is byte-stable regardless of finding order,
    so baseline diffs review as pure adds/removes."""
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import time

            async def a():
                time.sleep(1)

            async def b():
                time.sleep(2)
            """
        },
    )
    found = run_passes(ctx)
    assert len(found) == 2
    p1, p2 = str(tmp_path / "b1.json"), str(tmp_path / "b2.json")
    write_baseline(p1, found)
    write_baseline(p2, list(reversed(found)))
    assert open(p1).read() == open(p2).read()


def test_unreadable_file_is_single_one_line_finding(tmp_path):
    """A missing/unreadable analyzed file yields one TSA000 finding (the
    CLI contract: file:line, never a traceback)."""
    ctx = AnalysisContext(root=str(tmp_path), lib_files=["nope.py"])
    found = run_passes(ctx)
    assert codes(found) == ["TSA000"]
    assert found[0].path == "nope.py"
    assert "not readable" in found[0].message


def test_ast_and_parent_map_are_parsed_once_and_shared(tmp_path):
    ctx = make_ctx(tmp_path, {"mod.py": "x = 1\n"})
    assert ctx.tree("mod.py") is ctx.tree("mod.py")
    assert ctx.parents("mod.py") is ctx.parents("mod.py")


def test_baseline_grandfathers_and_detects_stale(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import time

            async def grandfathered():
                time.sleep(1)
            """
        },
    )
    found = run_passes(ctx)
    assert codes(found) == ["TSA101"]

    baseline_path = str(tmp_path / "baseline.json")
    write_baseline(baseline_path, found)
    baseline = load_baseline(baseline_path)

    fresh, stale = apply_baseline(found, baseline)
    assert fresh == [] and stale == []

    # A second identical violation is NOT absorbed (multiset semantics).
    fresh, stale = apply_baseline(found + found, baseline)
    assert codes(fresh) == ["TSA101"]

    # Fixing the violation makes the entry stale — the gate must fail.
    fresh, stale = apply_baseline([], baseline)
    assert fresh == [] and len(stale) == 1


# ---------------------------------------------------------------------------
# Pass 10: durability-discipline (TSA1001-TSA1004)
# ---------------------------------------------------------------------------


def test_durability_flags_bare_final_path_write(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import os

            def dump_table(path, rows):
                with open(path, "w") as f:
                    f.write(rows)
            """,
        },
    )
    found = [f for f in run_passes(ctx) if f.code == "TSA1001"]
    assert len(found) == 1
    assert found[0].key == "bare-open:dump_table"


def test_durability_quiet_on_atomic_idioms_and_noqa(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import os

            def atomic_dump(path, rows):
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    f.write(rows)
                os.replace(tmp, path)

            def rename_commit(work, final):
                # Not tmp-NAMED, but os.replace()d in place: still atomic.
                with open(work, "wb") as f:
                    f.write(b"x")
                os.replace(work, final)

            def routed(storage, write_io):
                storage.sync_write(write_io)

            def documented_sidecar(path):
                with open(path, "w") as f:  # noqa: TSA1001
                    f.write("fail-open by design")
            """,
        },
    )
    assert [f for f in run_passes(ctx) if f.code == "TSA1001"] == []


def test_durability_flags_publish_not_dominated_by_commit(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            class Snap:
                def commit(self, ok):
                    if ok:
                        self._write_snapshot_metadata()
                    self._append_catalog_record()
            """,
        },
    )
    found = [f for f in run_passes(ctx) if f.code == "TSA1002"]
    assert len(found) == 1
    assert found[0].key == (
        "publish-before-commit:Snap.commit:_append_catalog_record"
    )


def test_durability_quiet_when_commit_dominates_publish(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            class Snap:
                def commit(self):
                    self._write_snapshot_metadata()
                    self._append_catalog_record()
                    self._append_step_telemetry_record()

                def unrelated(self):
                    return 1
            """,
        },
    )
    assert [f for f in run_passes(ctx) if f.code == "TSA1002"] == []


def test_durability_flags_ungated_gc_delete(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import os

            def gc_sweep(paths):
                for p in paths:
                    os.remove(p)
            """,
        },
    )
    found = [f for f in run_passes(ctx) if f.code == "TSA1003"]
    assert len(found) == 1
    assert found[0].key == "ungated-delete:gc_sweep"


def test_durability_quiet_on_keep_gated_delete_and_non_gc_scope(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """
            import os

            def gc_sweep(paths, keep):
                for p in paths:
                    if p not in keep:
                        os.remove(p)

            def evict_entries(storage, victims, pinned):
                for v in victims:
                    if v in pinned:
                        continue
                    storage.delete(v)

            def replace_artifact(path):
                # A delete outside GC/retention scope is not this rule's
                # business (resource cleanup, overwrite-then-delete, ...).
                os.remove(path)
            """,
        },
    )
    assert [f for f in run_passes(ctx) if f.code == "TSA1003"] == []


def _durability_ctx(tmp_path, faults_src):
    return make_ctx(
        tmp_path,
        {
            "pkg/writer.py": """
            import os

            def finalize(tmp, dst):
                os.replace(tmp, dst)
            """,
            "faults.py": faults_src,
        },
        faults_path="faults.py",
    )


def test_durability_crash_surface_pins_commit_points(tmp_path):
    ctx = _durability_ctx(
        tmp_path,
        """
        _OPS = ("write", "commit", "any")
        _CRASH_SURFACE = (
            ("writer.py:finalize", "commit"),
        )
        """,
    )
    assert [f for f in run_passes(ctx) if f.code == "TSA1004"] == []


def test_durability_flags_unpinned_stale_and_bad_op(tmp_path):
    ctx = _durability_ctx(
        tmp_path,
        """
        _OPS = ("write", "commit", "any")
        _CRASH_SURFACE = (
            ("writer.py:gone", "commit"),
            ("writer.py:finalize", "explode"),
        )
        """,
    )
    keys = sorted(f.key for f in run_passes(ctx) if f.code == "TSA1004")
    # finalize IS in the table (so not unpinned) but names a made-up op
    # class; gone isn't a discoverable commit point anymore.
    assert keys == [
        "badop:writer.py:finalize:explode",
        "stale:writer.py:gone",
    ]

    unpinned = _durability_ctx(
        tmp_path / "unpinned",
        """
        _OPS = ("write", "commit", "any")
        _CRASH_SURFACE = ()
        """,
    )
    keys = [f.key for f in run_passes(unpinned) if f.code == "TSA1004"]
    assert keys == ["unpinned:writer.py:finalize"]


def test_durability_flags_missing_crash_surface_table(tmp_path):
    ctx = _durability_ctx(tmp_path, "_OPS = ('write', 'any')\n")
    keys = [f.key for f in run_passes(ctx) if f.code == "TSA1004"]
    assert keys == ["no-crash-surface"]


def test_crash_surface_table_matches_discovered_inventory():
    """Satellite of the TSA1004 gate, asserted directly against the live
    modules: the reviewable ``faults._CRASH_SURFACE`` mirror, the pass's
    discovered inventory, and the catalog layout can never drift apart."""
    from dev.analyze.durability_discipline import discover_commit_points
    from torchsnapshot_tpu import catalog, faults

    inventory = discover_commit_points(default_context(REPO_ROOT))
    table = dict(faults._CRASH_SURFACE)
    assert set(table) == set(inventory)
    assert set(table.values()) <= set(faults._OPS) | {"fail-open"}
    # Derived write classes stay glued to the catalog's real layout, and
    # each names a rule-matchable op class.
    assert faults._CATALOG_RECORD_PREFIX == f"{catalog.RECORD_DIR}/"
    assert faults._STEP_TELEMETRY_PREFIX == f"{catalog.STEP_TELEMETRY_DIR}/"
    assert faults._DERIVED_OP_SET <= set(faults._OPS)


# ---------------------------------------------------------------------------
# --jobs / --timings plumbing
# ---------------------------------------------------------------------------


def _two_file_ctx(tmp_path):
    return make_ctx(
        tmp_path,
        {
            "a.py": """
            def dump_a(path):
                with open(path, "w") as f:
                    f.write("a")
            """,
            "b.py": """
            def dump_b(path):
                with open(path, "w") as f:
                    f.write("b")
            """,
        },
    )


def test_run_passes_parallel_matches_serial_and_times_passes(tmp_path):
    from dev.analyze import get_passes

    serial_timings = {}
    serial = run_passes(_two_file_ctx(tmp_path), timings=serial_timings)
    parallel_timings = {}
    parallel = run_passes(
        _two_file_ctx(tmp_path), jobs=2, timings=parallel_timings
    )
    assert serial == parallel
    assert sorted(f.key for f in serial) == ["bare-open:dump_a", "bare-open:dump_b"]
    pass_names = {name for name, _ in get_passes()}
    assert set(serial_timings) == pass_names
    assert set(parallel_timings) == pass_names
    assert all(t >= 0 for t in parallel_timings.values())


@pytest.mark.slow
def test_analyzer_cli_jobs_and_timings_smoke():
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analyze", "--jobs", "2", "--timings"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "analyzer clean" in proc.stdout
    assert "per-pass wall time" in proc.stdout
    assert "durability-discipline" in proc.stdout


# ---------------------------------------------------------------------------
# Repo gates
# ---------------------------------------------------------------------------


def test_repo_is_clean_under_all_passes():
    """The real library carries zero non-baselined findings — the exact
    invariant `python dev/lint.py` enforces in CI."""
    ctx = default_context(REPO_ROOT)
    findings = run_passes(ctx)
    baseline = load_baseline(
        os.path.join(REPO_ROOT, "dev", "analyze", "baseline.json")
    )
    fresh, stale = apply_baseline(findings, baseline)
    assert fresh == [], "\n".join(f.render() for f in fresh)
    assert stale == [], f"stale baseline entries: {stale}"


def test_repo_telemetry_catalog_parses():
    """The machine-readable catalog in docs/observability.md stays parseable
    and non-trivial (a silently-empty catalog would let every name pass)."""
    from dev.analyze.telemetry_discipline import parse_catalog

    with open(
        os.path.join(REPO_ROOT, "docs", "observability.md"), encoding="utf-8"
    ) as f:
        catalog = parse_catalog(f.read())
    kinds = {k for k, _ in catalog}
    assert kinds == {"span", "metric"}
    assert len(catalog) > 20


@pytest.mark.slow
def test_analyzer_cli_smoke():
    proc = subprocess.run(
        [sys.executable, "-m", "dev.analyze"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "analyzer clean" in proc.stdout


def test_lint_fix_mode(tmp_path):
    """`dev/lint.py --fix` remediates trailing whitespace and missing final
    newlines in place."""
    target = tmp_path / "messy.py"
    target.write_text("x = 1   \ny = 2")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "dev", "lint.py"),
            "--fix",
            str(target),
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert target.read_text() == "x = 1\ny = 2\n"


# ---------------------------------------------------------------------------
# Import direction: how a leaf leaves the device is decided in
# ``device_programs.py``, under the planner and the preparers that use it.
# ---------------------------------------------------------------------------

_PACKAGE = os.path.join(REPO_ROOT, "torchsnapshot_tpu")
# module (relative to the package) -> the package's modules it may not
# import, at any depth of nesting: a function-level import is an arrow too.
_MAY_NOT_IMPORT = {
    "device_programs.py": {"io_preparer", "io_preparers", "scheduler", "snapshot"},
    **{
        os.path.join("io_preparers", name): {"io_preparer"}
        for name in sorted(os.listdir(os.path.join(_PACKAGE, "io_preparers")))
        if name.endswith(".py")
    },
}


def _package_imports(relpath):
    """The package's own modules ``relpath`` imports, as dotted names under
    ``torchsnapshot_tpu``, from every ``import`` in the file (function bodies
    included)."""
    import ast

    with open(os.path.join(_PACKAGE, relpath)) as f:
        tree = ast.parse(f.read())
    here = ["torchsnapshot_tpu"] + relpath[: -len(".py")].split(os.sep)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.level:
                base = here[: len(here) - node.level]
                base += node.module.split(".") if node.module else []
                names = [base] if node.module else []
                names += [base + [a.name] for a in node.names]
            else:
                names = [(node.module or "").split(".")]
        elif isinstance(node, ast.Import):
            names = [a.name.split(".") for a in node.names]
        else:
            continue
        found.update(
            ".".join(n[1:]) for n in names if n[0] == "torchsnapshot_tpu" and len(n) > 1
        )
    return found


@pytest.mark.parametrize("relpath", sorted(_MAY_NOT_IMPORT))
def test_import_direction_device_programs_sit_under_the_preparers(relpath):
    banned = _MAY_NOT_IMPORT[relpath]
    upward = {m for m in _package_imports(relpath) if m.split(".")[0] in banned}
    assert not upward, f"{relpath} imports upward: {sorted(upward)}"
