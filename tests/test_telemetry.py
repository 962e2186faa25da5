"""Unified telemetry: spans, metrics, Perfetto export, and the end-to-end
take/restore instrumentation (ISSUE 1 tentpole).

The load-bearing assertions:

- spans nest across asyncio task boundaries (contextvars propagation);
- the trace buffer is bounded and drops LOUDLY (``dropped`` counter);
- the Chrome/Perfetto JSON survives a schema round-trip;
- an end-to-end traced take emits phase + scheduler + storage spans whose
  summed storage-write bytes equal the manifest's logical byte total;
- telemetry OFF allocates no spans (the no-op singleton) and records
  nothing.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, telemetry
from torchsnapshot_tpu.serialization import array_nbytes
from torchsnapshot_tpu.snapshot import _manifest_storage_locations
from torchsnapshot_tpu.telemetry import (
    Telemetry,
    metrics_from_chrome_trace,
    spans_from_chrome_trace,
    to_chrome_trace,
)
from torchsnapshot_tpu.utils import knobs


# --------------------------------------------------------------------- spans

def test_span_nesting_sync() -> None:
    tm = Telemetry()
    prev = telemetry.activate(tm)
    try:
        with telemetry.span("outer", cat="t") as outer:
            with telemetry.span("inner", cat="t") as inner:
                pass
    finally:
        telemetry.deactivate(tm, prev)
    spans = {s.name: s for s in tm.spans(cat="t")}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["inner"].dur is not None and spans["inner"].dur >= 0
    # The context managers expose their records too.
    assert outer.span.span_id == spans["outer"].span_id
    assert inner.span.parent_id == outer.span.span_id


def test_span_nesting_across_asyncio_tasks() -> None:
    """A span opened inside an asyncio task parents to the span that was
    open where the task was SPAWNED — ensure_future snapshots the caller's
    contextvars, so nesting needs no explicit plumbing."""
    tm = Telemetry()
    prev = telemetry.activate(tm)
    try:

        async def child(i: int) -> None:
            with telemetry.span(f"child_{i}", cat="t"):
                await asyncio.sleep(0)

        async def main() -> None:
            with telemetry.span("parent", cat="t"):
                await asyncio.gather(*(child(i) for i in range(3)))
            # Outside the parent: a sibling root.
            with telemetry.span("sibling", cat="t"):
                pass

        asyncio.new_event_loop().run_until_complete(main())
    finally:
        telemetry.deactivate(tm, prev)
    spans = {s.name: s for s in tm.spans(cat="t")}
    parent_id = spans["parent"].span_id
    for i in range(3):
        assert spans[f"child_{i}"].parent_id == parent_id
    assert spans["sibling"].parent_id is None


def test_span_disabled_is_shared_noop() -> None:
    """Telemetry off: span() hands out ONE shared no-op object — no Span
    allocation on the hot path — and records nothing anywhere."""
    assert telemetry.get_active() is None
    a = telemetry.span("x", cat="t", nbytes=1)
    b = telemetry.span("y")
    assert a is b is telemetry.NOOP_SPAN
    with a as entered:
        entered.set_attrs(nbytes=2)  # must be a no-op, not an error
    # Metric helpers are free no-ops too.
    telemetry.counter_add("nope", 1)
    telemetry.gauge_set("nope", 1)
    telemetry.histogram_observe("nope", 1)


def test_span_records_error_attr() -> None:
    tm = Telemetry()
    prev = telemetry.activate(tm)
    try:
        with pytest.raises(ValueError):
            with telemetry.span("boom", cat="t"):
                raise ValueError("x")
    finally:
        telemetry.deactivate(tm, prev)
    (sp,) = tm.spans(name="boom")
    assert sp.attrs["error"] == "ValueError"


def test_activation_is_guarded_against_late_deactivate() -> None:
    """A late-finishing background session must not clobber a newer one —
    and, once closed, must never be resurrected when the newer one closes.
    Concurrent QoS-classed operations (a BACKGROUND drain beside a
    FOREGROUND restore) close their sessions out of LIFO order; restoring
    a closed session would leak it as permanently active (nothing will
    ever deactivate it again) and silently swallow every later op's
    spans."""
    old, new = Telemetry(), Telemetry()
    prev_old = telemetry.activate(old)
    prev_new = telemetry.activate(new)  # newer session takes over
    telemetry.deactivate(old, prev_old)  # late deactivate of the OLD one
    assert telemetry.get_active() is new  # guarded: no clobber
    telemetry.deactivate(new, prev_new)
    # The already-closed old session is walked past, not resurrected.
    assert telemetry.get_active() is None


def test_lifo_deactivate_still_restores_open_previous() -> None:
    """The nested (LIFO) shape keeps its semantics: closing the inner
    session restores the still-open outer one."""
    outer, inner = Telemetry(), Telemetry()
    prev_outer = telemetry.activate(outer)
    prev_inner = telemetry.activate(inner)
    telemetry.deactivate(inner, prev_inner)
    assert telemetry.get_active() is outer
    telemetry.deactivate(outer, prev_outer)
    assert telemetry.get_active() is None


# -------------------------------------------------------------------- buffer

def test_trace_buffer_bounded_overflow() -> None:
    tm = Telemetry(capacity=10)
    prev = telemetry.activate(tm)
    try:
        for i in range(25):
            with telemetry.span(f"s{i}", cat="t"):
                pass
    finally:
        telemetry.deactivate(tm, prev)
    assert len(tm.buffer) == 10
    assert tm.buffer.dropped == 15
    # Overflow keeps the HEAD of the trace (the part whose start is
    # predictable), drops the tail.
    assert [s.name for s in tm.buffer.snapshot()] == [f"s{i}" for i in range(10)]
    # The dropped count rides the export so partial traces are visible.
    assert to_chrome_trace(tm)["otherData"]["dropped_spans"] == 15


# ------------------------------------------------------------------- metrics

def test_metrics_aggregation() -> None:
    tm = Telemetry()
    tm.metrics.counter("c").add(3)
    tm.metrics.counter("c").add(4)
    tm.metrics.gauge("g").set(5)
    tm.metrics.gauge("g").set(2)
    tm.metrics.gauge("hwm").set_max(7)
    tm.metrics.gauge("hwm").set_max(3)
    for v in (1.0, 2.0, 9.0):
        tm.metrics.histogram("h").observe(v)
    d = tm.metrics.as_dict()
    assert d["c"] == 7
    assert d["g"] == 2 and d["g.max"] == 5
    assert d["hwm"] == 7
    assert d["h.count"] == 3
    assert d["h.sum"] == 12.0
    assert d["h.min"] == 1.0 and d["h.max"] == 9.0
    assert d["h.mean"] == 4.0


def test_metrics_helpers_record_into_active_session() -> None:
    tm = Telemetry()
    prev = telemetry.activate(tm)
    try:
        telemetry.counter_add("k.bytes", 10)
        telemetry.counter_add("k.bytes", 5)
        telemetry.gauge_max("k.hwm", 4)
        telemetry.gauge_max("k.hwm", 2)
        telemetry.histogram_observe("k.s", 0.5)
    finally:
        telemetry.deactivate(tm, prev)
    d = tm.metrics.as_dict()
    assert d["k.bytes"] == 15 and d["k.hwm"] == 4 and d["k.s.count"] == 1


# -------------------------------------------------------------------- export

def test_chrome_trace_schema_round_trip() -> None:
    tm = Telemetry()
    prev = telemetry.activate(tm)
    try:
        with telemetry.span("outer", cat="phase", label="x"):
            with telemetry.span("inner", cat="storage", nbytes=123):
                pass
        tm.metrics.counter("bytes").add(123)
    finally:
        telemetry.deactivate(tm, prev)
    # Through JSON text and back: what Perfetto ingests is what we parse.
    trace = json.loads(json.dumps(to_chrome_trace(tm)))
    assert isinstance(trace["traceEvents"], list)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0  # microseconds, rebased
    spans = {s.name: s for s in spans_from_chrome_trace(trace)}
    assert set(spans) == {"outer", "inner"}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner"].attrs["nbytes"] == 123
    assert spans["outer"].cat == "phase"
    orig = {s.name: s for s in tm.spans()}
    for name, sp in spans.items():
        assert sp.dur == pytest.approx(orig[name].dur or 0.0, abs=1e-6)
    assert metrics_from_chrome_trace(trace) == {"bytes": 123}


# ---------------------------------------------------------------- end-to-end

def _logical_bytes(manifest) -> int:
    total = 0
    for entry in manifest.values():
        if hasattr(entry, "shape") and hasattr(entry, "dtype"):
            total += array_nbytes(entry.shape, entry.dtype)
    return total


def test_e2e_traced_take_and_restore(tmp_path) -> None:
    """The acceptance criterion: a CPU-backend take + restore with
    TORCHSNAPSHOT_TPU_TRACE set emits valid Chrome trace JSON containing
    phase, scheduler stage/io, and storage-plugin spans whose summed
    storage-write bytes equal the manifest's logical byte total, while
    the ``LAST_TAKE_PHASES`` / drain-stats keys stay unchanged."""
    from torchsnapshot_tpu import snapshot as snapshot_mod

    app = {
        "m": StateDict(
            w=np.arange(64 * 64, dtype=np.float32).reshape(64, 64),
            b=np.ones(128, dtype=np.float32),
            step=7,
        )
    }
    trace_path = str(tmp_path / "take_trace.json")
    with knobs.override_trace_path(trace_path):
        snap = Snapshot.take(str(tmp_path / "ck"), app)
    assert os.path.exists(trace_path)
    trace = json.load(open(trace_path))
    spans = spans_from_chrome_trace(trace)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)

    # Phase spans (the stall decomposition, now first-class spans).
    for phase in ("prepare_write", "partition", "manifest_gather", "capture"):
        assert phase in by_name, sorted(by_name)
        assert by_name[phase][0].cat == "take.phase"
    # ...and the legacy dict is a derived view with unchanged keys.
    assert {
        "gather_keys_and_flatten",
        "prepare_write",
        "partition",
        "manifest_gather",
        "memory_budget",
        "capture",
    } <= set(snapshot_mod.LAST_TAKE_PHASES)
    for phase, dur in snapshot_mod.LAST_TAKE_PHASES.items():
        assert dur == pytest.approx(
            sum(s.dur for s in by_name[phase]), abs=1e-5
        )
    # Drain stats: the classic keys plus the stage_busy decomposition
    # (stage_d2h_s / stage_serialize_s / stage_hash_s sub-streams).
    assert {
        "wall_s",
        "stage_busy_s",
        "io_busy_s",
        "overlap_s",
        "idle_s",
        "stage_d2h_s",
        "stage_serialize_s",
        "stage_hash_s",
        # ... and of two of them the seconds on objects under 1 MiB.
        "stage_d2h_small_s",
        "io_busy_small_s",
        # ... what the lanes' seconds in stage.d2h are made of,
        "stage_d2h_sum_s",
        "stage_gather_s",
        "stage_gather_sum_s",
        # ... and, inside io, what the native writes' are.
        "write_work_sum_s",
        "write_queue_sum_s",
        "mount_write_s",
        "mount_write_sum_s",
        "mount_write_bytes",
        "write_copy_sum_s",
        "write_crc_sum_s",
        "write_bounce_warm_bytes",
        "write_bounce_fresh_bytes",
        # ... and what a synchronous take's stage did with its big leaves.
        "stage_sync_cut_leaves",
        "stage_sync_cut_bytes",
        "stage_sync_cut_relaid_bytes",
        "stage_sync_cut_refused",
        "stage_recycled_bytes",
        "stage_fresh_bytes",
        "stage_target_wait_s",
    } == set(snapshot_mod.LAST_SYNC_DRAIN_STATS)

    # Scheduler stage/io spans.
    assert "scheduler.stage" in by_name and "scheduler.io" in by_name

    # Storage-plugin write spans: summed bytes over the manifest's storage
    # locations == the manifest's logical byte total (sidecars/metadata are
    # extra objects and are excluded by the location filter).
    manifest = snap.get_manifest()
    locations = _manifest_storage_locations(manifest)
    written = sum(
        s.attrs["nbytes"]
        for s in by_name["storage.write"]
        if s.attrs["path"] in locations
    )
    assert written == _logical_bytes(manifest) > 0

    # The session is published for programmatic use.
    assert Snapshot.last_telemetry is not None
    assert Snapshot.last_telemetry.metrics.as_dict()["storage.fs.write_bytes"] > 0

    # Restore leg: storage reads + scheduler + per-stateful spans, and the
    # restored values are intact.
    tgt = {
        "m": StateDict(
            w=np.zeros((64, 64), np.float32),
            b=np.zeros(128, np.float32),
            step=0,
        )
    }
    rtrace_path = str(tmp_path / "restore_trace.json")
    with knobs.override_trace_path(rtrace_path):
        Snapshot(str(tmp_path / "ck")).restore(tgt)
    assert np.array_equal(tgt["m"]["w"], app["m"]["w"])
    rnames = {s.name for s in spans_from_chrome_trace(json.load(open(rtrace_path)))}
    assert {
        "restore.read_metadata",
        "restore.load_stateful",
        "scheduler.read_io",
        "storage.read",
    } <= rnames


def test_small_object_seconds_skip_records_without_a_size() -> None:
    """``stage_d2h_small_s`` / ``io_busy_small_s`` are reduced from the
    streams' own ``(t0, t1, nbytes)`` records: one without a size is no
    small object, and the union reads only the ends."""
    from torchsnapshot_tpu.engine.intervals import (
        measure,
        merge_intervals,
        smaller_than,
    )
    from torchsnapshot_tpu.io_types import SMALL_OBJECT_BYTES

    records = [(0.0, 1.0, 128), (0.5, 2.0, 0), (3.0, 4.0, SMALL_OBJECT_BYTES), (5.0, 6.0, 4096)]
    assert smaller_than(records, SMALL_OBJECT_BYTES) == [records[0], records[3]]
    assert merge_intervals(records) == [(0.0, 2.0), (3.0, 4.0), (5.0, 6.0)]
    assert measure(merge_intervals(smaller_than(records, SMALL_OBJECT_BYTES))) == 2.0


def test_e2e_async_take_trace_written_on_commit(tmp_path) -> None:
    """async_take keeps the session open through the background drain; the
    trace lands when the snapshot commits and includes the drain's
    scheduler.io spans."""
    import jax
    import jax.numpy as jnp

    arrs = {
        f"a{i}": jax.random.normal(jax.random.PRNGKey(i), (64, 64), jnp.float32)
        for i in range(3)
    }
    trace_path = str(tmp_path / "async_trace.json")
    with knobs.override_trace_path(trace_path):
        pending = Snapshot.async_take(str(tmp_path / "ck"), {"m": StateDict(**arrs)})
        pending.wait()
    assert os.path.exists(trace_path)
    names = {s.name for s in spans_from_chrome_trace(json.load(open(trace_path)))}
    assert {"capture", "scheduler.io", "storage.write", "stage.d2h"} <= names
    # Session deactivated after commit: nothing global left behind.
    assert telemetry.get_active() is None


def test_explicit_telemetry_object_no_trace_file(tmp_path) -> None:
    """_telemetry= records without the env knob (and writes no file)."""
    tm = Telemetry()
    app = {"m": StateDict(w=np.arange(256, dtype=np.float32))}
    Snapshot.take(str(tmp_path / "ck"), app, _telemetry=tm)
    assert telemetry.get_active() is None
    assert Snapshot.last_telemetry is tm
    assert tm.spans(name="storage.write")
    assert tm.metrics.as_dict()["scheduler.bytes_staged"] == 256 * 4
    assert not list(tmp_path.glob("*.json"))


def test_untraced_take_records_nothing(tmp_path) -> None:
    """No knob, no _telemetry, artifacts off: the take runs with telemetry
    fully off (persisted artifacts — on by default — otherwise create a
    session per op so the snapshot is auditable after the fact)."""
    before = Snapshot.last_telemetry
    app = {"m": StateDict(w=np.arange(64, dtype=np.float32))}
    with knobs.override_telemetry_artifacts(False):
        Snapshot.take(str(tmp_path / "ck"), app)
    assert telemetry.get_active() is None
    assert Snapshot.last_telemetry is before  # untouched


def test_default_take_records_session_for_artifact(tmp_path) -> None:
    """Artifacts on (the default): every take gets a session, published as
    last_telemetry, and deactivated on completion."""
    app = {"m": StateDict(w=np.arange(64, dtype=np.float32))}
    Snapshot.take(str(tmp_path / "ck"), app)
    assert telemetry.get_active() is None
    assert Snapshot.last_telemetry is not None
    assert Snapshot.last_telemetry.metrics.as_dict()["scheduler.bytes_staged"] == 64 * 4


def test_histogram_log_bucket_percentiles() -> None:
    """p50/p95/p99 from the fixed log buckets are within one bucket's
    relative width (~19%) of the exact percentiles."""
    tm = Telemetry()
    h = tm.metrics.histogram("lat")
    for v in range(1, 1001):
        h.observe(float(v))
    for q, exact in ((50, 500.0), (95, 950.0), (99, 990.0)):
        est = h.percentile(q)
        assert exact / 1.25 <= est <= exact * 1.25, (q, est)
    d = tm.metrics.as_dict()
    assert d["lat.p50"] == h.percentile(50)
    assert d["lat.p95"] == h.percentile(95)
    assert d["lat.p99"] == h.percentile(99)
    # Percentiles clamp into [min, max]; empty histograms export zeros.
    assert h.percentile(100) == 1000.0
    h2 = tm.metrics.histogram("empty")
    assert h2.percentile(50) == 0.0
    assert tm.metrics.as_dict()["empty.p99"] == 0.0
    # Non-positive observations land below every positive bucket.
    h3 = tm.metrics.histogram("zeros")
    for v in (0.0, 0.0, 0.0, 8.0):
        h3.observe(v)
    assert h3.percentile(50) == 0.0
    assert h3.percentile(99) == pytest.approx(8.0)


def test_session_close_records_spans_dropped_metric(tmp_path) -> None:
    """A session that dropped spans closes with a telemetry.spans_dropped
    counter, so truncation rides the metrics dump and the artifact."""
    tm = Telemetry(capacity=3)
    app = {"m": StateDict(w=np.arange(64, dtype=np.float32))}
    Snapshot.take(str(tmp_path / "ck"), app, _telemetry=tm)
    assert tm.buffer.dropped > 0
    assert (
        tm.metrics.as_dict()["telemetry.spans_dropped"] == tm.buffer.dropped
    )


def test_cli_trace_subcommand(tmp_path, capsys) -> None:
    from torchsnapshot_tpu.__main__ import main

    app = {"m": StateDict(w=np.arange(4096, dtype=np.float32), step=3)}
    ck = str(tmp_path / "ck")
    Snapshot.take(ck, app)
    out_path = str(tmp_path / "cli_trace.json")
    assert main(["trace", ck, "-o", out_path]) == 0
    out = capsys.readouterr().out
    assert "trace written to" in out and "perfetto" in out
    trace = json.load(open(out_path))
    reads = [s for s in spans_from_chrome_trace(trace) if s.name == "storage.read"]
    # Every manifest storage object was read under a span.
    assert {s.attrs["path"] for s in reads} >= {"0/m/w"}
    assert metrics_from_chrome_trace(trace)["storage.fs.read_bytes"] > 0
