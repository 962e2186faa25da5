"""Per-step telemetry rollups: steprecord build/parse semantics, the
catalog append/scan storage layer, the take(job=, step=) commit hook, the
retention-GC lifecycle, and the timeline/monitor CLI surfaces.
"""

import json
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu import catalog as catalog_mod
from torchsnapshot_tpu.__main__ import main as cli_main
from torchsnapshot_tpu.telemetry import steprecord
from torchsnapshot_tpu.telemetry.recorder import FlightRecorder
from torchsnapshot_tpu.utils import knobs


# ---------------------------------------------------------------------------
# build_step_record semantics
# ---------------------------------------------------------------------------

def _agg(op: str) -> dict:
    return {
        "op": op,
        "world_size": 1,
        "ranks": [0],
        "missing_ranks": [],
        "per_rank": {0: {"phases_s": {"capture": 0.1, "stage": 0.2}, "bytes_deduped": 5}},
        "totals": {"bytes_written": 100, "wall_s": 1.0},
        "phases_s": {"capture": {"mean": 0.1, "max": 0.1, "max_rank": 0}},
        "skew": {"end_skew_s": 0.01, "straggler_rank": 0},
        "spans_dropped": 0,
    }


_ARTIFACTS = {
    0: {
        "drain_stats_s": {"wall_s": 2.0},
        "metrics": {"engine.preemptions": 3, "cache.hits": 7},
    }
}


def test_sync_stall_includes_the_drain_async_does_not() -> None:
    # A sync take blocks the training loop through the drain; an
    # async_take returns after staging and drains in the background.
    sync = steprecord.build_step_record("j", 0, "s0", _agg("take"), _ARTIFACTS)
    assert abs(sync["stall_s"] - (0.3 + 2.0)) < 1e-6
    asyn = steprecord.build_step_record(
        "j", 0, "s0", _agg("async_take"), _ARTIFACTS
    )
    assert abs(asyn["stall_s"] - 0.3) < 1e-6
    for r in (sync, asyn):
        assert r["schema_version"] == steprecord.STEP_SCHEMA_VERSION
        assert r["drain_wall_s"] == 2.0
        assert r["drain_gbps"] == round(100 / 1e9 / 2.0, 6)
        assert r["bytes"] == {"written": 100, "deduped": 5}
        assert r["counters"]["preemptions"] == 3
        assert r["counters"]["cache_hits"] == 7
        assert r["skew"] == {"end_skew_s": 0.01, "straggler_rank": 0}


def test_parse_step_record_validates() -> None:
    good = steprecord.build_step_record("j", 1, "s1", _agg("take"), _ARTIFACTS)
    assert steprecord.parse_step_record(steprecord.dumps_step_record(good))[
        "step"
    ] == 1
    for bad in (
        b"not json",
        b"[1, 2]",
        b'{"job": "j", "step": 1}',  # no schema_version
        json.dumps({**good, "schema_version": 99}).encode(),  # newer schema
        json.dumps({"schema_version": 1}).encode(),  # missing job/step
    ):
        with pytest.raises(ValueError):
            steprecord.parse_step_record(bad)


def test_summarize_series() -> None:
    assert steprecord.summarize_series([]) == {"steps": 0}
    series = [
        steprecord.build_step_record("j", s, f"s{s}", _agg("take"), _ARTIFACTS)
        for s in (2, 0, 1)
    ]
    summary = steprecord.summarize_series(series)
    assert summary["steps"] == 3
    assert summary["first_step"] == 0 and summary["last_step"] == 2
    assert summary["bytes_written_total"] == 300
    assert summary["preemptions_total"] == 9
    assert summary["stall_s"]["max"] == summary["stall_s"]["p50"]


# ---------------------------------------------------------------------------
# Commit hook + catalog storage + GC lifecycle
# ---------------------------------------------------------------------------

def _take_steps(bucket: str, n: int, job: str = "tj") -> None:
    sd = {"m": StateDict(x=np.arange(512, dtype=np.float32))}
    for step in range(n):
        Snapshot.take(
            os.path.join(bucket, f"s{step}"), sd, job=job, step=step
        )


def test_job_take_appends_loadable_step_records(tmp_path) -> None:
    bucket = str(tmp_path / "bucket")
    _take_steps(bucket, 3)
    with catalog_mod.Catalog(bucket) as cat:
        series = cat.load_step_telemetry(job="tj")
        assert cat.load_step_telemetry(job="other") == []
    assert [r["step"] for r in series] == [0, 1, 2]
    for r in series:
        assert r["job"] == "tj" and r["op"] == "take"
        assert r["world_size"] == 1 and r["missing_ranks"] == []
        assert r["bytes"]["written"] > 0
        assert r["stall_s"] > 0 and r["drain_wall_s"] > 0
    # The records live beside the catalog records, one prefix per job.
    tel_dir = os.path.join(bucket, catalog_mod.STEP_TELEMETRY_DIR, "tj")
    assert len(os.listdir(tel_dir)) == 3


def test_step_telemetry_knob_off_skips_rollup_only(tmp_path) -> None:
    bucket = str(tmp_path / "bucket")
    with knobs.override_step_telemetry(False):
        _take_steps(bucket, 1)
    with catalog_mod.Catalog(bucket) as cat:
        assert cat.load_step_telemetry(job="tj") == []
        assert len(cat.load(job="tj")) == 1  # the catalog record still lands


def test_unreadable_record_is_skipped_not_fatal(tmp_path) -> None:
    bucket = str(tmp_path / "bucket")
    _take_steps(bucket, 2)
    victim = os.path.join(bucket, catalog_mod.STEP_TELEMETRY_DIR, "tj")
    victim = os.path.join(victim, sorted(os.listdir(victim))[0])
    with open(victim, "w") as f:
        f.write("{corrupt")
    with catalog_mod.Catalog(bucket) as cat:
        series = cat.load_step_telemetry(job="tj")
    assert [r["step"] for r in series] == [1]


def test_retention_gc_prunes_step_records_with_their_snapshots(tmp_path) -> None:
    bucket = str(tmp_path / "bucket")
    _take_steps(bucket, 5)
    catalog_mod.retain(
        bucket, catalog_mod.RetentionPolicy.parse("last=2"), dry_run=False
    )
    with catalog_mod.Catalog(bucket) as cat:
        series = cat.load_step_telemetry(job="tj")
    # Step records follow their snapshots' lifecycle: condemned snapshots
    # take their trend points with them, retained ones keep theirs.
    assert [r["step"] for r in series] == [3, 4]


# ---------------------------------------------------------------------------
# CLI: timeline
# ---------------------------------------------------------------------------

def test_cli_timeline_clean_run_exits_zero(tmp_path, capsys) -> None:
    bucket = str(tmp_path / "bucket")
    _take_steps(bucket, 3)
    assert cli_main(["timeline", bucket, "--job", "tj"]) == 0
    out = capsys.readouterr().out
    assert "job tj: 3 step(s)" in out
    assert "anomalies: none" in out


def test_cli_timeline_empty_job_points_at_the_knobs(tmp_path, capsys) -> None:
    bucket = str(tmp_path / "bucket")
    os.makedirs(bucket)
    assert cli_main(["timeline", bucket, "--job", "nope"]) == 0
    assert "no step-telemetry records" in capsys.readouterr().out


def _seed_synthetic_series(bucket: str, n: int, spike_at: int) -> None:
    """Write a synthetic step series straight through the catalog layer —
    detector-shaped data without paying n real takes."""
    with catalog_mod.Catalog(bucket) as cat:
        for s in range(n):
            rec = steprecord.build_step_record(
                "sj", s, f"s{s}", _agg("take"), _ARTIFACTS
            )
            if s == spike_at:
                rec["stall_s"] = 60.0
            assert cat.append_step_telemetry(rec)


def test_cli_timeline_flags_anomaly_and_exits_one(tmp_path, capsys) -> None:
    bucket = str(tmp_path / "bucket")
    os.makedirs(bucket)
    _seed_synthetic_series(bucket, 8, spike_at=6)
    assert cli_main(["timeline", bucket, "--job", "sj"]) == 1
    out = capsys.readouterr().out
    assert "stall_spike" in out and "[stall_spike] step 6" in out


def test_cli_timeline_last_slices_render_not_detection(tmp_path, capsys) -> None:
    bucket = str(tmp_path / "bucket")
    os.makedirs(bucket)
    _seed_synthetic_series(bucket, 8, spike_at=6)
    # The spike at step 6 is outside the last-1 window: the render is
    # clean, so the exit code is 0 — but detectors still saw full history.
    assert cli_main(["timeline", bucket, "--job", "sj", "--last", "1"]) == 0
    assert "anomalies: none" in capsys.readouterr().out
    # Window covering the spike: flagged, exit 1, and --json is parseable.
    assert (
        cli_main(["timeline", bucket, "--job", "sj", "--last", "3", "--json"])
        == 1
    )
    payload = json.loads(capsys.readouterr().out)
    assert [r["step"] for r in payload["series"]] == [5, 6, 7]
    assert payload["anomalies"][0]["kind"] == "stall_spike"


# ---------------------------------------------------------------------------
# CLI: monitor
# ---------------------------------------------------------------------------

def test_cli_monitor_renders_a_dump(tmp_path, capsys) -> None:
    r = FlightRecorder(capacity=16)
    r.record(
        "engine.sample",
        {
            "engine": "write",
            "priority": "NORMAL",
            "paused": False,
            "admitted": 4,
            "bytes_done": 2 * 10**9,
            "budget_available": 10**9,
            "occupancy": {"io": 2},
        },
    )
    r.record("engine.stall_warning", {"engine": "write", "rank": 0})
    dump = str(tmp_path / "ring.json")
    r.dump(dump)
    assert cli_main(["monitor", dump]) == 0
    out = capsys.readouterr().out
    assert f"flight recorder @ {dump}" in out
    assert "write" in out and "NORMAL" in out and "io=2" in out
    assert "engine.stall_warning" in out
    assert cli_main(["monitor", dump, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["capacity"] == 16


def test_cli_monitor_defaults_to_the_dump_knob(tmp_path, capsys) -> None:
    dump = str(tmp_path / "ring.json")
    FlightRecorder(capacity=16).dump(dump)
    with knobs.override_recorder_dump_path(dump):
        assert cli_main(["monitor"]) == 0
    assert "0 sample(s)" in capsys.readouterr().out
    # No argument and no knob: a one-line scriptable error, exit 2.
    assert cli_main(["monitor"]) == 2
    assert capsys.readouterr().err.startswith("error:")
