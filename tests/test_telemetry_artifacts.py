"""Persisted telemetry artifacts, cross-rank aggregation, live progress,
and the stall watchdog (ISSUE 4 tentpole).

The load-bearing assertions:

- every take persists a schema-versioned ``.telemetry/rank_<k>.json``
  through the snapshot's own storage plugin (fs and memory here; the
  fake-GCS leg lives in ``test_gcs_storage_plugin.py``), readable back via
  the aggregation API;
- aggregation degrades (never crashes) on a missing rank, and attributes
  the straggler + per-rank commit-barrier wait from the artifacts alone;
- artifact persistence is fail-open: an injected storage fault on the
  artifact path logs once and the snapshot still commits clean;
- ``PendingSnapshot.progress()`` is strictly nondecreasing over large
  leaves and ends with ``bytes_written == bytes_total`` == the payload
  size;
- the stall watchdog fires EXACTLY once per stall on injected hung
  storage writes, naming the stuck stage.
"""

import asyncio
import json
import logging
import os
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, telemetry
from torchsnapshot_tpu.io_types import BufferStager, WriteReq
from torchsnapshot_tpu.scheduler import execute_write_reqs
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.telemetry import aggregate as agg_mod
from torchsnapshot_tpu.telemetry import artifact as art_mod
from torchsnapshot_tpu.utils import knobs


def _app():
    return {
        "m": StateDict(
            w=np.arange(64 * 64, dtype=np.float32).reshape(64, 64), step=7
        )
    }


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ----------------------------------------------------------- artifact writes

def test_take_persists_artifact_fs(tmp_path) -> None:
    """Default knobs: a committed snapshot carries its rank artifact, with
    the full schema (phases, pipeline stats, bytes, metrics, env)."""
    path = str(tmp_path / "ck")
    snap = Snapshot.take(path, _app())
    art_file = os.path.join(path, art_mod.ARTIFACT_DIR, "rank_0.json")
    assert os.path.exists(art_file)
    with open(art_file, "rb") as f:
        art = art_mod.parse_artifact(f.read())
    assert art["schema_version"] == art_mod.SCHEMA_VERSION
    assert art["op"] == "take" and art["rank"] == 0 and art["world_size"] == 1
    assert {"capture", "prepare_write", "manifest_gather"} <= set(art["phases_s"])
    # The byte accounting closes: written == total == staged payload.
    assert (
        art["bytes"]["written"]
        == art["bytes"]["total"]
        == art["bytes"]["staged"]
        > 0
    )
    assert art["requests"]["done"] == art["requests"]["total"] > 0
    assert art["metrics"]["storage.fs.write_bytes"] > 0
    # The progress counters ride the artifact once, as its bytes/requests
    # blocks: no mirror of them among the metrics.
    assert not [k for k in art["metrics"] if k.startswith("progress.")]
    # Environment fingerprint: conftest pins the dedup knob for every test.
    assert art["env"]["knobs"].get("TORCHSNAPSHOT_TPU_DEDUP_DIGESTS") == "1"
    # The snapshot itself stays clean: artifacts are invisible to verify().
    assert snap.verify() == {}


def test_async_take_persists_artifact_and_restore_writes_its_own(tmp_path) -> None:
    path = str(tmp_path / "ck")
    Snapshot.async_take(path, _app()).wait()
    take_art = os.path.join(path, art_mod.ARTIFACT_DIR, "rank_0.json")
    assert os.path.exists(take_art)
    assert json.load(open(take_art))["op"] == "async_take"
    tgt = {"m": StateDict(w=np.zeros((64, 64), np.float32), step=0)}
    Snapshot(path).restore(tgt)
    restore_art = os.path.join(path, art_mod.ARTIFACT_DIR, "restore_rank_0.json")
    art = json.load(open(restore_art))
    assert art["op"] == "restore"
    assert art["metrics"]["storage.fs.read_bytes"] > 0
    assert "restore.load_stateful" in art["phases_s"]
    # The take's artifact was not clobbered.
    assert json.load(open(take_art))["op"] == "async_take"


def test_artifact_knob_off_writes_nothing_and_keeps_telemetry_off(tmp_path) -> None:
    path = str(tmp_path / "ck")
    before = Snapshot.last_telemetry
    with knobs.override_telemetry_artifacts(False):
        Snapshot.take(path, _app())
    assert not os.path.exists(os.path.join(path, art_mod.ARTIFACT_DIR))
    # With artifacts off and no trace knob, the take ran with telemetry
    # fully off (the pre-artifact zero-overhead path).
    assert Snapshot.last_telemetry is before


def test_artifact_round_trip_memory_plugin() -> None:
    """Plugin-level round trip through the write/read seams the snapshot
    paths use (memory backend)."""
    from torchsnapshot_tpu.storage_plugin import write_telemetry_artifact

    plugin = MemoryStoragePlugin()
    loop = asyncio.new_event_loop()
    try:
        art = art_mod.build_artifact(op="take", rank=0, world_size=2)
        assert write_telemetry_artifact(
            plugin, loop, art_mod.artifact_path(0), art_mod.dumps_artifact(art)
        )
        artifacts, problems = agg_mod.read_artifacts(plugin, loop, world_size=2)
    finally:
        plugin.sync_close(loop)
        loop.close()
    assert set(artifacts) == {0} and problems == {1: "missing"}
    assert artifacts[0]["op"] == "take"
    assert artifacts[0]["hostname"] == art["hostname"]


def test_parse_artifact_rejects_garbage_and_newer_schema() -> None:
    with pytest.raises(ValueError):
        art_mod.parse_artifact(b"not json")
    with pytest.raises(ValueError):
        art_mod.parse_artifact(b"[1, 2]")
    newer = art_mod.build_artifact(op="take", rank=0, world_size=1)
    newer["schema_version"] = art_mod.SCHEMA_VERSION + 1
    with pytest.raises(ValueError):
        art_mod.parse_artifact(json.dumps(newer).encode())


def test_artifact_write_fail_open(tmp_path, monkeypatch, caplog) -> None:
    """Injected storage fault on the artifact path: logs once, and the
    snapshot still commits clean (satellite: fail-open by contract)."""
    import torchsnapshot_tpu.storage_plugin as sp_mod
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    orig_write = FSStoragePlugin.write

    async def failing_write(self, write_io):
        if write_io.path.startswith(art_mod.ARTIFACT_DIR + "/"):
            raise RuntimeError("injected artifact fault")
        await orig_write(self, write_io)

    monkeypatch.setattr(FSStoragePlugin, "write", failing_write)
    monkeypatch.setattr(sp_mod, "_artifact_write_warned", False)
    path = str(tmp_path / "ck")
    with caplog.at_level(logging.WARNING, logger="torchsnapshot_tpu.storage_plugin"):
        snap = Snapshot.take(path, _app())
        # Second take: the once-guard keeps the warning from repeating.
        Snapshot.take(str(tmp_path / "ck2"), _app())
    warnings = [
        r
        for r in caplog.records
        if "failed to persist telemetry artifact" in r.getMessage()
    ]
    assert len(warnings) == 1
    # Commit was unaffected: metadata readable, data verifies clean, and no
    # artifact landed.
    assert snap.verify() == {}
    assert not os.path.exists(os.path.join(path, art_mod.ARTIFACT_DIR, "rank_0.json"))
    tgt = {"m": StateDict(w=np.zeros((64, 64), np.float32), step=0)}
    Snapshot(path).restore(tgt)
    assert np.array_equal(tgt["m"]["w"], _app()["m"]["w"])


# ------------------------------------------------------------- aggregation

def _fake_artifact(rank, world_size, start, end, written, op="take"):
    wall = end - start
    return {
        "schema_version": art_mod.SCHEMA_VERSION,
        "op": op,
        "rank": rank,
        "world_size": world_size,
        "hostname": f"host{rank}",
        "phases_s": {"capture": 0.1 * (rank + 1), "prepare_write": 0.05},
        "phase_spans": [
            {"name": "capture", "ts_unix": start, "dur_s": 0.1 * (rank + 1)}
        ],
        "pipeline_stats_s": {
            "wall_s": wall,
            "stage_busy_s": wall * 0.5,
            "io_busy_s": wall * 0.6,
            "overlap_s": wall * 0.3,
            "idle_s": wall * 0.2,
        },
        "drain_stats_s": {},
        "bytes": {"staged": written, "written": written, "total": written, "deduped": 0},
        "requests": {"done": 3, "total": 3},
        "intervals": {"windows": [[start, end]], "stage": [[start, end - 1]], "io": [[start + 1, end]]},
        "metrics": {"storage.fs.write_bytes": written},
        "spans_dropped": 0,
    }


def test_aggregate_straggler_and_barrier_wait() -> None:
    t0 = 1000.0
    artifacts = {
        0: _fake_artifact(0, 3, t0, t0 + 10.0, 10**9),
        1: _fake_artifact(1, 3, t0, t0 + 14.0, 10**9),  # the straggler
        2: _fake_artifact(2, 3, t0, t0 + 11.0, 10**9),
    }
    agg = agg_mod.aggregate(artifacts)
    assert agg["missing_ranks"] == []
    assert agg["skew"]["straggler_rank"] == 1
    assert agg["skew"]["end_skew_s"] == pytest.approx(4.0)
    # Everyone waits for the straggler at the commit barrier.
    assert agg["skew"]["barrier_wait_s"][1] == pytest.approx(0.0)
    assert agg["skew"]["barrier_wait_s"][0] == pytest.approx(4.0)
    assert agg["skew"]["barrier_wait_s"][2] == pytest.approx(3.0)
    assert agg["totals"]["bytes_written"] == 3 * 10**9
    assert agg["phases_s"]["capture"]["max_rank"] == 2  # 0.1 * (rank + 1)
    assert agg["storage_bytes"]["storage.fs.write_bytes"] == 3 * 10**9


def test_aggregate_missing_rank_degrades() -> None:
    t0 = 1000.0
    artifacts = {
        0: _fake_artifact(0, 3, t0, t0 + 10.0, 10**9),
        2: _fake_artifact(2, 3, t0, t0 + 12.0, 10**9),
    }
    agg = agg_mod.aggregate(artifacts, world_size=3)
    assert agg["missing_ranks"] == [1]
    assert agg["skew"]["straggler_rank"] == 2
    lines = "\n".join(agg_mod.format_stats(agg))
    assert "rank 1 artifact missing" in lines
    assert "straggler: rank 2" in lines


def test_merged_chrome_trace_pid_is_rank() -> None:
    t0 = 1000.0
    artifacts = {
        0: _fake_artifact(0, 2, t0, t0 + 5.0, 10**6),
        1: _fake_artifact(1, 2, t0 + 0.5, t0 + 6.0, 10**6),
    }
    trace = agg_mod.merged_chrome_trace(artifacts)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    assert all(e["ts"] >= 0 for e in xs)
    names = {e["name"] for e in xs}
    assert {"capture", "stage_busy", "io_busy"} <= names
    # Rank 1 started 0.5 s after rank 0: visible on the shared axis.
    r1_capture = [e for e in xs if e["pid"] == 1 and e["name"] == "capture"]
    assert r1_capture[0]["ts"] == pytest.approx(0.5e6)


def test_diff_stats_lines() -> None:
    t0 = 1000.0
    a = agg_mod.aggregate({0: _fake_artifact(0, 1, t0, t0 + 10.0, 10**9)})
    b = agg_mod.aggregate({0: _fake_artifact(0, 1, t0, t0 + 5.0, 10**9)})
    lines = "\n".join(agg_mod.diff_stats(a, b))
    assert "wall_s" in lines and "gbps" in lines and "capture" in lines


# ---------------------------------------------------------------- progress

def test_progress_monotone_over_large_leaves(tmp_path) -> None:
    """Acceptance: progress() reports strictly nondecreasing bytes_written
    that ends equal to the total payload bytes — polled live against a
    drain of leaves several hash grains long."""
    import jax
    import jax.numpy as jnp

    arrs = {
        f"a{i}": jax.random.normal(
            jax.random.PRNGKey(i), (512, 256), jnp.float32
        )
        for i in range(2)
    }
    total = sum(a.nbytes for a in arrs.values())
    with knobs.override_hash_chunk_bytes(64 * 1024):
        pending = Snapshot.async_take(str(tmp_path / "ck"), {"m": StateDict(**arrs)})
        polls = []
        while not pending.done():
            polls.append(pending.progress())
            time.sleep(0.0005)
        pending.wait()
    final = pending.progress()
    seq = polls + [final]
    for prev, cur in zip(seq, seq[1:]):
        for key in ("bytes_staged", "bytes_written", "requests_done"):
            assert cur[key] >= prev[key], (key, prev, cur)
    assert final["bytes_written"] == final["bytes_total"] == total
    assert final["requests_done"] == final["requests_total"]
    assert final["eta_s"] == 0.0


# ---------------------------------------------------------------- watchdog

class _BytesStager(BufferStager):
    def __init__(self, data: bytes):
        self.data = data

    async def stage_buffer(self, executor=None):
        return self.data

    def get_staging_cost_bytes(self) -> int:
        return len(self.data)


def _piece_reqs(pieces):
    # defer_staging: everything runs on the drain (complete()) — the
    # async-take shape the watchdog targets.
    return [
        WriteReq(f"obj{i}", _BytesStager(p), defer_staging=True)
        for i, p in enumerate(pieces)
    ]


class _HangingWriteStorage(MemoryStoragePlugin):
    """Data writes run one at a time and hang after the first until
    released — the injected hung storage of the watchdog satellite."""

    def __init__(self):
        super().__init__()
        self.release = asyncio.Event()
        self.serial = asyncio.Lock()
        self.writes = 0

    async def write(self, write_io) -> None:
        if not write_io.path.startswith("obj"):
            return await super().write(write_io)
        async with self.serial:
            self.writes += 1
            if self.writes > 1:
                await self.release.wait()
            await super().write(write_io)


def test_watchdog_fires_exactly_once_per_stall(caplog) -> None:
    chunk = 1024
    chunks = [bytes([i]) * chunk for i in range(6)]
    storage = _HangingWriteStorage()

    async def go():
        pending = await execute_write_reqs(
            _piece_reqs(chunks), storage, memory_budget_bytes=1 << 20, rank=0
        )

        async def release_later():
            # Hold the stall for >3x the warn threshold: a re-firing
            # watchdog would log 2+ warnings in this window.
            await asyncio.sleep(0.6)
            storage.release.set()

        releaser = asyncio.ensure_future(release_later())
        await pending.complete()
        await releaser

    with knobs.override_stall_warn_s(0.15):
        with caplog.at_level(
            logging.WARNING, logger="torchsnapshot_tpu.telemetry.progress"
        ):
            _run(go())
    stalls = [
        r for r in caplog.records if "snapshot drain stalled" in r.getMessage()
    ]
    assert len(stalls) == 1, [r.getMessage() for r in stalls]
    payload = json.loads(stalls[0].getMessage().split("stalled: ", 1)[1])
    assert payload["event"] == "snapshot_stall"
    assert payload["stuck_stage"] == "io"
    assert payload["bytes_written"] < payload["bytes_total"]
    # The writes completed after release: the objects are intact.
    assert [storage.objects[f"obj{i}"] for i in range(6)] == chunks


def test_watchdog_rearms_for_a_second_stall(caplog) -> None:
    """Two distinct stalls (progress resumes in between) -> two warnings."""
    chunk = 512
    chunks = [bytes([i]) * chunk for i in range(4)]

    class _TwoStallStorage(MemoryStoragePlugin):
        def __init__(self):
            super().__init__()
            self.serial = asyncio.Lock()
            self.writes = 0

        async def write(self, write_io) -> None:
            if not write_io.path.startswith("obj"):
                return await super().write(write_io)
            async with self.serial:
                self.writes += 1
                if self.writes in (2, 4):
                    await asyncio.sleep(0.35)  # two separate stalls
                await super().write(write_io)

    storage = _TwoStallStorage()

    async def go():
        pending = await execute_write_reqs(
            _piece_reqs(chunks), storage, memory_budget_bytes=1 << 20, rank=0
        )
        await pending.complete()

    with knobs.override_stall_warn_s(0.12):
        with caplog.at_level(
            logging.WARNING, logger="torchsnapshot_tpu.telemetry.progress"
        ):
            _run(go())
    stalls = [
        r for r in caplog.records if "snapshot drain stalled" in r.getMessage()
    ]
    assert len(stalls) == 2, [r.getMessage() for r in stalls]
    assert [storage.objects[f"obj{i}"] for i in range(4)] == chunks


# --------------------------------------------------------- progress tracker

def test_progress_tracker_totals_converge() -> None:
    t = telemetry.ProgressTracker()
    t.set_totals(requests=2, bytes_=100)
    t.note_staged(70, estimate=50)  # actual bigger than the estimate
    t.note_written(70)
    t.note_request_done()
    t.note_staged(30, estimate=50)  # actual smaller
    t.note_written(30)
    t.note_request_done()
    c = t.counters()
    assert c["bytes_written"] == c["bytes_total"] == 100
    assert c["requests_done"] == c["requests_total"] == 2
    snap = t.snapshot()
    assert snap["eta_s"] == 0.0
