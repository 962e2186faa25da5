"""The benchmark's own guards in tier-1: every configuration's architecture
(``perfbench/tests/test_architecture.py``) and ``BENCHMARK.json`` against the
contract's rules of form and its data files (``perfbench/tests/test_contract.py``).
The driver runs ``tests/`` and never ``perfbench/tests``, so the tests of
those two files are collected here as they stand.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

for _name in ("test_architecture", "test_contract"):
    _module = run.load_module("pb_" + _name, os.path.join(ROOT, "perfbench", "tests", _name + ".py"))
    globals().update({name: obj for name, obj in vars(_module).items() if name.startswith("test_")})

# Whatever these tests start keeps its compile cache under their own tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


# --------------------------- the metrics PR 40 brought, PR 43's one, PR 51's one
#
# Each reads what the take's artifact and ``LAST_RESTORE_STATS`` say of the
# seconds inside the native engine's calls; against a program that stamps
# none of it (the parent) each reads ``None`` and is left out of the line.

from perfbench import readers  # noqa: E402

_DRAIN = {
    "wall_s": 5.0, "io_busy_s": 4.8, "mount_write_s": 2.0, "mount_write_sum_s": 3.0,
    "mount_write_bytes": 3.0e9, "write_work_sum_s": 8.0, "write_copy_sum_s": 4.0,
    "write_crc_sum_s": 0.5, "write_queue_sum_s": 24.0, "write_bounce_warm_bytes": 2.7e9,
    "stage_d2h_sum_s": 10.0, "stage_gather_sum_s": 6.0,
}
_RESTORE = {
    "mount_bytes": 9.6e9, "mount_busy_s": 4.0, "mount_sum_s": 32.0,
    "pread_busy_s": 3.0, "pread_sum_s": 12.0, "reader_copy_sum_s": 20.0,
    "bytes_read": 9.6e9, "pretouched_bytes": 0.48e9,
}
_RATIO_METRICS = {
    "io_mount_write_busy_pct": 40.0, "io_mount_write_gbps": 1.5, "io_mount_write_depth": 1.5,
    "io_writer_mount_pct": 37.5, "io_writer_copy_pct": 50.0, "io_writer_crc_pct": 6.25,
    "io_write_queued_pct": 75.0, "stage_d2h_gather_pct": 60.0, "io_bounce_warm_pct": 90.0,
    "restore_pread_gbps": 3.2, "restore_pread_depth": 4.0, "restore_reader_copy_pct": 62.5,
    "restore_pretouched_pct": 5.0,
}
_LIFT_METRICS = {
    "step_block_gather_lift": "gather",
    "step_block_writer_copy_lift": "write_copy",
    "step_block_mount_write_lift": "mount_write",
}


def _facts(drain: dict, restore: dict) -> dict:
    rec = {"save": {"telemetry": {"drain_stats_s": drain}}, "restore": {"stats": restore}}
    return {"rounds": [rec, rec], "traced": rec}


def _metric(name: str) -> dict:
    found = run.find_cell(ROOT, "pythia-6.9b-d6.save_weights" if "restore" not in name else "pythia-6.9b-d6.resume")
    (metric,) = [m for m in found["per_layer"] if m["name"] == name]
    return metric


@pytest.mark.parametrize("name", sorted(_RATIO_METRICS))
def test_a_new_ratio_metric_reads_its_number_and_nothing_from_the_parent(name):
    metric = _metric(name)
    # One share's denominator is a sum of two keys: a reader of its own.
    assert (metric["read"] is None) == (name != "io_write_queued_pct") and metric["reader"]["kind"] == "ratio"
    got = run.read_metrics([metric], _facts(_DRAIN, _RESTORE))
    assert got[name]["value"] == pytest.approx(_RATIO_METRICS[name]) and got[name]["unit"] == metric["unit"]
    # The parent's program: the keys it has, none of the new ones.
    old = _facts({"wall_s": 5.0, "io_busy_s": 4.8}, {"mount_bytes": 9.6e9, "mount_busy_s": 4.0, "mount_sum_s": 32.0})
    assert run.read_metrics([metric], old) == {}


@pytest.mark.parametrize("name", sorted(_RATIO_METRICS))
def test_a_new_ratio_metric_is_listed_for_the_cells_that_can_read_it(name):
    bench = run.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    save = {w["name"] for w in bench["workloads"] if w["traffic"].startswith("save_") and w["chips"] == 1}
    restore = {w["name"] for w in bench["workloads"] if w["traffic"] in ("resume", "save_reshard")}
    assert set(entry["workloads"]) == (restore if "restore" in name else save)
    assert entry["moves"] == ("restore_gbps" if "restore" in name else "goodput_pct")


# The trace's clock is the artifact's unix time less 4988 s: its monotonic
# clock is unix - 4000, and the profiler's session began 988 s into it.
_UNIX = 4988.0


def _lift_planes(work_starts, late=0.0):
    """``late``: the profiler stamped the second event's start that much
    after the library stamped its span's (its end is on time)."""
    host = [("pb.traced", 0.0, 100.0), ("pb.step.block", 10.0, 20.0), ("pb.step.block", 30.0, 40.0)]
    host += [("tss.storage.write_work", s + late * (i == 1), s + 3.0) for i, s in enumerate(work_starts)]
    host += [("tss.stage.gather", 10.0, 14.0), ("tss.stage.gather", 13.0, 20.0), ("tss.stage.gather", 50.0, 60.0)]
    # Idle under pb.step.block: [15, 20] and [38, 40], 7 s of 20.
    return {"busy": {"/device:TPU:0": [(10.0, 15.0), (30.0, 38.0)]}, "host": host}


def _lift_artifact():
    def unix(ivs):
        return [[s + _UNIX, e + _UNIX] for s, e in ivs]

    return {
        "intervals": {
            "write_work": unix([(12.0, 15.0), (31.0, 34.0), (45.0, 48.0)]),
            "mount_write": unix([(16.0, 20.0), (34.0, 36.0), (45.0, 46.0)]),
            "write_copy": unix([(12.0, 14.0), (31.0, 33.0)]),
        },
    }


def _lift_module():
    from perfbench.metrics import step_block_lift

    step_block_lift._READ.clear()
    return step_block_lift


def test_lift_is_how_much_likelier_the_activity_is_open_in_the_idle_gaps():
    mod = _lift_module()
    got = mod.lifts(_lift_planes([12.0, 31.0, 45.0]), _lift_artifact(), "pb.step.block")
    assert got["trace_clock_residual_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["trace_clock_residual_far_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["trace_clock_offset_s"] == pytest.approx(-_UNIX) and got["write_work_spans"] == 3
    assert got["step_block_s"] == pytest.approx(20.0) and got["step_block_idle_s"] == pytest.approx(7.0)
    # A pwrite is open over 4 of the 7 idle seconds and 6 of the 20 under the span.
    assert got["mount_write"] == pytest.approx((4 / 7) / (6 / 20))
    # A gather over 5 of 7 and 10 of 20; a copy never in a gap.
    assert got["gather"] == pytest.approx((5 / 7) / (10 / 20))
    assert got["write_copy"] == 0.0


def test_lifts_that_lean_on_the_anchor_read_none_past_the_residual_limit():
    mod = _lift_module()
    got = mod.lifts(_lift_planes([12.0, 31.0 + 0.005, 45.0]), _lift_artifact(), "pb.step.block")
    assert mod.RESIDUAL_LIMIT_MS < got["trace_clock_residual_ms"] == pytest.approx(5.0)
    assert got["trace_clock_residual_far_ms"] == pytest.approx(5.0)
    assert got["mount_write"] is None and got["write_copy"] is None
    assert got["gather"] == pytest.approx((5 / 7) / (10 / 20))  # on the trace's clock already


def test_one_late_stamp_of_a_span_does_not_condemn_the_anchor():
    mod = _lift_module()
    got = mod.lifts(_lift_planes([12.0, 31.0, 45.0], late=0.004), _lift_artifact(), "pb.step.block")
    assert got["trace_clock_residual_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["trace_clock_residual_far_ms"] == pytest.approx(4.0)  # and is reported all the same
    assert got["trace_clock_offset_s"] == pytest.approx(-_UNIX)
    assert got["mount_write"] == pytest.approx((4 / 7) / (6 / 20))


@pytest.mark.parametrize("why", ["events_and_spans_do_not_pair", "no_anchor", "no_step_block"])
def test_lifts_read_nothing_where_the_anchor_cannot_be_checked(why):
    mod = _lift_module()
    planes, artifact = _lift_planes([12.0, 31.0, 45.0]), _lift_artifact()
    if why == "events_and_spans_do_not_pair":
        planes = _lift_planes([12.0, 31.0])
    elif why == "no_anchor":
        del artifact["intervals"]["write_work"]
    else:
        planes["host"] = [ev for ev in planes["host"] if ev[0] != "pb.step.block"]
    assert mod.lifts(planes, artifact, "pb.step.block") == {}


@pytest.mark.parametrize("name", sorted(_LIFT_METRICS))
def test_a_lift_metric_reads_its_activity_once_a_run_and_nothing_from_the_parent(name, monkeypatch, capsys):
    mod = _lift_module()
    metric = _metric(name)
    assert metric["reader"] == {"kind": "idle", "span": "pb.step.block", "activity": _LIFT_METRICS[name]}
    reads = []

    def planes_of_this_run():
        reads.append(1)
        return _lift_planes([12.0, 31.0, 45.0])

    monkeypatch.setattr(mod.libspans, "planes_of_this_run", planes_of_this_run)
    parent = {"traced": {"save": {"telemetry": {"intervals": {"io": []}}}}}
    assert run.read_metrics([metric], parent) == {} and not reads
    facts = {"traced": {"save": {"telemetry": _lift_artifact()}}}
    want = mod.lifts(_lift_planes([12.0, 31.0, 45.0]), _lift_artifact(), "pb.step.block")
    for _ in range(2):
        assert run.read_metrics([metric], facts)[name]["value"] == pytest.approx(want[_LIFT_METRICS[name]])
    assert len(reads) == 1
    summary = [line for line in capsys.readouterr().out.splitlines() if line.startswith("[summary] ")]
    assert len(summary) == 1 and '"trace_clock_residual_ms"' in summary[0]
    mod._READ.clear()
