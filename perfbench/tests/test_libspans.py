"""The two readers of the library's own spans (``tss.*``), on hand-made
planes: interval arithmetic, the window's clip, and silence where the
program has no bridge."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import libspans  # noqa: E402


def planes(host, busy=None):
    return {"busy": {"/device:TPU:0": busy or []}, "host": [("pb.traced", 0.0, 100.0)] + host}


def test_intersect_and_subtract():
    a, b = [[0, 4], [6, 9]], [[1, 2], [3, 7], [8.5, 12]]
    assert libspans.intersect(a, b) == [[1, 2], [3, 4], [6, 7], [8.5, 9]]
    assert libspans.subtract(a, b) == [[0, 1], [2, 3], [7, 8.5]]
    assert libspans.subtract(a, []) == a and libspans.subtract([], b) == []


def test_unspanned_is_the_part_of_the_restore_under_no_library_span_of_any_thread():
    host = [
        ("pb.restore", 10.0, 30.0),
        ("tss.restore.plan", 10.0, 11.0),            # main thread
        ("tss.storage.read_work", 10.5, 16.0),       # a reader thread, overlapping
        ("tss.scheduler.consume_work", 18.0, 20.0),  # a consumer thread
        ("tss.restore.place", 19.0, 24.0),
        ("tss.restore.place", 40.0, 50.0),           # outside pb.restore: not counted
        ("pb.reference", 30.0, 60.0),
    ]
    # Covered inside [10, 30]: [10, 16] and [18, 24] = 12 of 20 s.
    assert libspans.unspanned_pct(planes(host), "pb.restore") == pytest.approx(40.0)


def test_events_are_clipped_to_the_traced_window():
    host = [("pb.traced", 0.0, 20.0), ("pb.restore", 10.0, 30.0), ("tss.restore.place", 15.0, 30.0)]
    got = libspans.unspanned_pct({"busy": {}, "host": host}, "pb.restore")
    assert got == pytest.approx(50.0)  # [10, 20] of the restore is inside; [15, 20] is spanned


def test_step_block_idle_is_attributed_to_open_d2h_transfers():
    host = [
        ("pb.step", 0.0, 10.0), ("pb.step.block", 1.0, 10.0),
        ("pb.step.block", 21.0, 22.0),               # a step the device was busy under throughout
        ("tss.stage.d2h", 4.0, 7.0),                 # a lane thread
        ("tss.stage.d2h", 6.0, 8.5),                 # another lane, overlapping
        ("tss.stage.hash", 8.0, 9.5),                # not a transfer
    ]
    busy = [(1.0, 3.0), (9.0, 10.0), (20.0, 23.0)]
    # Idle under pb.step.block: [3, 9] = 6 s; a d2h is open over [4, 8.5] = 4.5 s of it.
    got = libspans.idle_under_pct(planes(host, busy), "pb.step.block", "stage.d2h")
    assert got == pytest.approx(75.0)


@pytest.mark.parametrize("read", [
    lambda p: libspans.unspanned_pct(p, "pb.restore"),
    lambda p: libspans.idle_under_pct(p, "pb.step.block", "stage.d2h"),
])
def test_a_program_without_the_bridge_reads_nothing(read):
    host = [("pb.restore", 10.0, 30.0), ("pb.step.block", 40.0, 50.0)]
    assert read(planes(host, [(45.0, 46.0)])) is None
    assert read({"busy": {}, "host": []}) is None  # no pb.traced either


def test_the_trace_is_found_from_the_command_line_of_the_run(monkeypatch, tmp_path):
    monkeypatch.setattr(libspans.target, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "c.t", "--seed", "2147507201", "--trace", "1"])
    want = os.path.join(str(tmp_path), "runs", "c.t-seed2147507201-trace1", "trace")
    assert libspans.trace_dir_of_this_run() == want
    assert libspans.planes_of_this_run() is None  # nothing recorded there: no error
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert libspans.trace_dir_of_this_run() is None and libspans.planes_of_this_run() is None


def test_the_metric_files_read_through_libspans(monkeypatch):
    from perfbench import run

    found = run.find_cell(run.REPO_ROOT, "pythia-6.9b-d6.resume")
    metric = next(m for m in found["per_layer"] if m["name"] == "restore_unspanned_pct")
    host = [("pb.restore", 0.0, 10.0), ("tss.restore.place", 0.0, 9.0)]
    monkeypatch.setattr(libspans, "planes_of_this_run", lambda: planes(host))
    assert run.read_metrics([metric], {}) == {
        "restore_unspanned_pct": {"value": pytest.approx(10.0), "unit": "%"}
    }
    monkeypatch.setattr(libspans, "planes_of_this_run", lambda: None)
    assert run.read_metrics([metric], {}) == {}
    found = run.find_cell(run.REPO_ROOT, "pythia-6.9b-d6.save_weights")
    assert "step_block_d2h_pct" in [m["name"] for m in found["per_layer"]]
