"""The cycle arithmetic on a hand-written ``rounds.jsonl``."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import cycles  # noqa: E402

STEP_ALONE = 0.25


def cycle(index, steps, wall, save_wall, in_window=True, nbytes=3_000_000_000):
    return {
        "round": index, "in_window": in_window, "cycle": True, "wall_s": wall,
        "step_s": steps, "save": {"bytes": nbytes, "wall_s": save_wall, "stall_s": 0.02, "error": None},
    }


@pytest.fixture
def records(tmp_path):
    rows = [
        cycle(-1, [0.3] * 10, 3.1, 3.0, in_window=False),  # set-up's warm round
        cycle(0, [0.5] * 10, 5.0, 4.9),    # 10 steps in 5.0 s: 50 %
        cycle(1, [0.5] * 10, 5.0, 4.8),    # 50 %
        cycle(2, [0.3] * 9 + [2.3], 5.0, 5.0),  # one giant block, still 50 %
        cycle(3, [0.5] * 12, 6.0, 5.0),    # 12 in 6.0: 50 %
        cycle(4, [0.5] * 4 + [8.0], 10.0, 10.0),  # a drain that hung: 12.5 %
        cycle(5, [0.3] * 3, 1.0, 0.9, in_window=False),  # the check round
    ]
    path = tmp_path / "rounds.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return cycles.load(str(path))


def test_whole_cycles_of_the_window_only(records):
    assert [r["round"] for r in cycles.in_window(records)] == [0, 1, 2, 3, 4]
    assert cycles.summarise(records, STEP_ALONE)["rounds"] == 5


def test_goodput_is_all_steps_over_all_cycle_time_and_the_median_stands_beside_it(records):
    per_cycle = cycles.goodput_per_cycle(records, STEP_ALONE)
    assert per_cycle == pytest.approx([50.0, 50.0, 50.0, 50.0, 12.5])
    summary = cycles.summarise(records, STEP_ALONE)
    # 47 steps of 0.25 s in 31.0 s of cycles.
    assert summary["goodput_pct"] == pytest.approx(100 * 47 * 0.25 / 31.0)
    assert summary["goodput_pct.median"] == pytest.approx(50.0)
    assert summary["goodput_pct.n"] == 5


def test_a_stall_in_one_cycle_moves_the_end_to_end_reading_and_not_the_median(records):
    calm = [r for r in records if r["round"] != 4]
    assert cycles.summarise(calm, STEP_ALONE)["goodput_pct"] == pytest.approx(50.0)
    assert cycles.summarise(calm, STEP_ALONE)["goodput_pct.median"] == pytest.approx(50.0)
    assert cycles.summarise(records, STEP_ALONE)["goodput_pct.median"] == pytest.approx(50.0)
    assert cycles.summarise(records, STEP_ALONE)["goodput_pct"] < 40.0
    assert cycles.summarise(records, STEP_ALONE)["save_gbps"] < 0.9 * cycles.summarise(calm, STEP_ALONE)["save_gbps"]


def test_save_rate_is_all_bytes_over_all_call_to_commit_time(records):
    rates = cycles.gbps_per_op(records, "save")
    assert rates == pytest.approx([3 / 4.9, 3 / 4.8, 3 / 5.0, 3 / 5.0, 3 / 10.0])
    summary = cycles.summarise(records, STEP_ALONE)
    assert summary["save_gbps"] == pytest.approx(15 / 29.7)
    assert summary["save_gbps.median"] == pytest.approx(0.6)


def test_save_cost_is_the_cycles_wall_less_their_steps_at_the_undisturbed_time(records):
    summary = cycles.summarise(records, STEP_ALONE)
    # 31.0 s of cycles held 47 steps of 0.25 s: 19.25 s lost over 5 saves.
    assert summary["save_cost_s"] == pytest.approx((31.0 - 47 * 0.25) / 5)
    assert summary["commit_wait_s"] == 0.0
    records[3]["save"]["commit_wait_s"] = 1.5  # the commit outlasted the period
    assert cycles.summarise(records, STEP_ALONE)["commit_wait_s"] == pytest.approx(0.3)


def test_a_failed_operation_has_no_rate_and_no_time(records):
    records[1]["save"] = {"bytes": 3_000_000_000, "stall_s": 0.02, "error": "boom"}
    assert len(cycles.gbps_per_op(records, "save")) == 4
    assert len(cycles.goodput_per_cycle(records, STEP_ALONE)) == 4
    assert cycles.rate_gbps(records, "save") == pytest.approx(12 / 24.8)


def test_restore_rate_and_no_goodput_without_a_step(records):
    rows = [
        {"round": 0, "in_window": True, "wall_s": 31.0,
         "restore": {"bytes": 9_730_000_000, "wall_s": 30.0, "error": None}},
        {"round": 1, "in_window": True, "wall_s": 33.0,
         "restore": {"bytes": 9_730_000_000, "wall_s": 32.0, "error": None}},
        {"round": 2, "in_window": False, "wall_s": 20.0,
         "restore": {"bytes": 9_730_000_000, "wall_s": 19.0, "error": None}},
    ]
    summary = cycles.summarise(rows, None)
    assert summary["restore_gbps"] == pytest.approx(2 * 9.73 / 62)
    assert summary["restore_gbps.median"] == pytest.approx((9.73 / 30 + 9.73 / 32) / 2)
    assert "goodput_pct" not in summary and "save_gbps" not in summary
