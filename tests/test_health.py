"""Health detectors (`telemetry/health.py`): every detector's fire AND
no-fire side — trailing-median arming, absolute floors, streak semantics,
one-event-per-drift — because a detector that false-positives on a healthy
job gets its warnings ignored the week it matters.
"""

import logging

from torchsnapshot_tpu.telemetry import health


def rec(
    step: int,
    stall: float = 0.05,
    drain: float = 0.1,
    gbps: float = 1.0,
    bytes_w: int = 10**9,
    skew: float = 0.0,
    straggler=None,
) -> dict:
    return {
        "schema_version": 1,
        "job": "j",
        "step": step,
        "name": f"s{step}",
        "stall_s": stall,
        "drain_wall_s": drain,
        "drain_gbps": gbps,
        "bytes": {"written": bytes_w, "deduped": 0},
        "counters": {"preemptions": 0},
        "skew": {"end_skew_s": skew, "straggler_rank": straggler},
    }


def steady(n: int, **kw) -> list:
    return [rec(i, **kw) for i in range(n)]


def kinds(events) -> list:
    return sorted({e["kind"] for e in events})


# ---------------------------------------------------------------------------
# Arming + stall spike
# ---------------------------------------------------------------------------

def test_short_series_never_fires() -> None:
    series = steady(health.MIN_HISTORY)  # MIN_HISTORY-1 steps of history max
    series[-1]["stall_s"] = 100.0
    series[-1]["drain_wall_s"] = 100.0
    assert health.detect_anomalies(series) == []


def test_stall_spike_fires_with_step_and_baseline() -> None:
    series = steady(10)
    series[7]["stall_s"] = 2.0  # vs trailing median 0.05
    events = health.detect_anomalies(series)
    assert kinds(events) == ["stall_spike"]
    (ev,) = events
    assert ev["step"] == 7 and ev["value"] == 2.0
    assert abs(ev["baseline"] - 0.05) < 1e-9
    assert "2.000s" in ev["detail"]


def test_stall_ratio_alone_is_below_the_floor() -> None:
    # 4x the median but only +0.15s absolute: sub-floor jitter on fast
    # steps must not trip the ratio test.
    series = steady(10)
    series[7]["stall_s"] = 0.2
    assert health.detect_anomalies(series) == []


def test_consistently_slow_job_is_quiet() -> None:
    # A job that is ALWAYS slow is a provisioning problem, not a drift.
    assert health.detect_anomalies(steady(20, stall=5.0, drain=8.0)) == []


# ---------------------------------------------------------------------------
# Drain cliff
# ---------------------------------------------------------------------------

def test_drain_cliff_fires_above_ratio_and_floor() -> None:
    series = steady(10)
    series[8]["drain_wall_s"] = 2.0  # > max(3 x 0.1, 0.1 + 1.0)
    assert kinds(health.detect_anomalies(series)) == ["drain_cliff"]


# ---------------------------------------------------------------------------
# Straggler drift
# ---------------------------------------------------------------------------

def test_straggler_drift_fires_once_at_streak_with_rank() -> None:
    series = steady(6) + [
        rec(s, skew=0.6, straggler=1) for s in range(6, 11)
    ]
    events = health.detect_anomalies(series)
    assert kinds(events) == ["straggler_drift"]
    (ev,) = events  # one event per drift, not one per step past the streak
    assert ev["rank"] == 1
    assert ev["step"] == 8  # the STRAGGLER_STREAK-th consecutive step


def test_rotating_stragglers_are_healthy_noise() -> None:
    series = steady(6) + [
        rec(s, skew=0.6, straggler=s % 2) for s in range(6, 12)
    ]
    assert health.detect_anomalies(series) == []


def test_immaterial_skew_never_streaks() -> None:
    # Same rank every step, but the skew is under the absolute floor.
    series = [rec(i, skew=0.1, straggler=1) for i in range(12)]
    assert health.detect_anomalies(series) == []


# ---------------------------------------------------------------------------
# Bucket growth
# ---------------------------------------------------------------------------

def test_bucket_growth_needs_both_args_and_fires_once() -> None:
    series = steady(12)
    growing = [10**9 + i * 10**9 for i in range(12)]
    assert health.detect_anomalies(series) == []  # no bytes given
    assert (
        health.detect_anomalies(series, bucket_bytes=growing) == []
    )  # no bound given
    events = health.detect_anomalies(
        series, bucket_bytes=growing, window_bound=2 * 10**9
    )
    assert kinds(events) == ["bucket_growth"]
    assert len(events) == 1  # first step the policy lost the race, only


def test_plateaued_bucket_is_quiet_even_above_nothing() -> None:
    series = steady(12)
    plateau = [5 * 10**9] * 12  # big but not growing
    assert (
        health.detect_anomalies(
            series, bucket_bytes=plateau, window_bound=10**9
        )
        == []
    )


# ---------------------------------------------------------------------------
# Rendering + logging
# ---------------------------------------------------------------------------

def test_render_timeline_flags_anomalous_steps() -> None:
    series = steady(10)
    series[7]["stall_s"] = 2.0
    lines = health.render_timeline(series)
    assert lines[0].split() == [
        "step", "stall_s", "drain_s", "GB/s", "GB",
        "preempt", "skew_s", "straggler", "flags",
    ]
    row7 = next(ln for ln in lines if ln.startswith("     7"))
    assert "stall_spike" in row7
    assert any(ln.startswith("anomalies: 1") for ln in lines)


def test_render_timeline_clean_says_none() -> None:
    lines = health.render_timeline(steady(10))
    assert lines[-1] == "anomalies: none"


def test_log_anomalies_one_warning_per_kind(caplog) -> None:
    series = steady(12)
    series[7]["stall_s"] = 2.0
    series[9]["stall_s"] = 3.0
    series[9]["drain_wall_s"] = 4.0
    events = health.detect_anomalies(series)
    assert len([e for e in events if e["kind"] == "stall_spike"]) == 2
    with caplog.at_level(logging.WARNING):
        health.log_anomalies(events)
    msgs = [r.message for r in caplog.records]
    assert len([m for m in msgs if "[stall_spike]" in m]) == 1
    assert len([m for m in msgs if "[drain_cliff]" in m]) == 1
