"""The trace reduction: interval arithmetic on hand-made planes, and the
whole reduction on a trace recorded on the v5e (``record_trace.py``:
one fork of two 2 MiB leaves, three steps of a 1024x1024 matmul)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perfbench import readers, trace  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "recorded_v5e.xplane.pb")


def test_union_clip_length():
    merged = trace.union([[3, 4], [0, 1], [0.5, 2], [2, 2.5]])
    assert merged == [[0, 2.5], [3, 4]]
    assert trace.length(merged) == pytest.approx(3.5)
    assert trace.clip(merged, 1, 3.5) == [[1, 2.5], [3, 3.5]]


def planes():
    ops = [("fusion.1", 1.0, 2.0), ("copy.2", 1.5, 2.5), ("fusion.1", 4.0, 5.0), ("late", 11.0, 12.0)]
    modules = [("jit_step(11)", 1.0, 2.5), ("jit__lambda_(7)", 4.0, 5.0)]
    host = [("pb.traced", 0.0, 10.0), ("pb.async_take", 0.0, 1.0), ("pb.step", 2.0, 9.0),
            ("pb.step.block", 2.2, 9.0)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": host}


def test_busy_is_the_union_of_device_ops_inside_the_traced_span():
    got = trace.reduce_planes(planes())
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx(2.5)  # [1, 2.5] and [4, 5]; "late" is outside
    assert got["modules"]["jit__lambda_"] == {"count": 1, "total_s": pytest.approx(1.0)}
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert got["spans"]["pb.step"]["device_busy_s"] == pytest.approx(1.5)


def test_idle_gaps_are_named_stretch_by_stretch_by_the_innermost_harness_span():
    gaps = dict(trace.reduce_planes(planes())["idle_gaps"])
    # [0,1] lies under pb.async_take, [2.5,4] and [5,9] under pb.step.block,
    # [9,10] under no span of the harness.
    assert gaps == {"pb.async_take": pytest.approx(1.0), "pb.step.block": pytest.approx(5.5),
                    "(no harness span)": pytest.approx(1.0)}


def test_two_chips_are_averaged():
    two = planes()
    two["devices"]["/device:TPU:1"] = {"ops": [("fusion.1", 1.0, 2.0)], "modules": [("jit_step(11)", 1.0, 2.0)]}
    got = trace.reduce_planes(two)
    assert got["chips"] == 2 and got["busy_s"] == pytest.approx((2.5 + 1.0) / 2)


def test_no_device_plane_or_no_traced_span_reduces_to_nothing():
    assert trace.reduce_planes({"devices": {}, "host": planes()["host"]}) == {}
    assert trace.reduce_planes({"devices": planes()["devices"], "host": []}) == {}


def test_recorded_v5e_trace():
    got = trace.reduce_file(RECORDED)
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(2.65949e-3, rel=1e-4)
    assert got["busy_s"] == pytest.approx(5.9652e-5, rel=1e-4)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["modules"]["jit_pb_train_step"]["count"] == 3
    assert got["modules"]["jit_pb_fork"] == {"count": 1, "total_s": pytest.approx(1.2682e-5, rel=1e-3)}
    assert got["spans"]["pb.step"]["count"] == 3 and got["spans"]["pb.traced"]["count"] == 1
    assert [name for name, _ in got["device_ops"]][:2] == ["fusion", "copy-done"]
    assert all(" = " not in name and len(name) <= 80 for name, _ in got["device_ops"])
    assert got["idle_gaps"][0][0] == "pb.step.block"
    idle = sum(seconds for _, seconds in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)


def test_roofline_and_idle_readers_on_the_recorded_trace():
    facts = {
        "trace": trace.reduce_file(RECORDED),
        "traced": {"save": {"bytes": 2 * 1024 * 1024 * 2}},  # two bf16 1024x1024 leaves
        "peaks": {"hbm_bytes_per_s": 819e9},
    }
    spec = {"kind": "roofline", "module": "jit_pb_fork", "bytes": "traced/save/bytes",
            "bytes_factor": 2, "peak": "hbm_bytes_per_s"}
    share = readers.read_roofline(facts, spec)
    # 8 MiB moved in 12.7 us is 661 GB/s of 819: a share of the peak, under 100.
    assert share == pytest.approx(100 * (8 * 1024 * 1024 / 819e9) / 1.2682e-5, rel=1e-3)
    assert 50 < share < 100
    idle = readers.read_idle(facts, {"kind": "idle", "span": "pb.traced"})
    assert idle == pytest.approx(100 * (1 - 5.9652e-5 / 2.65949e-3), rel=1e-4)
    with pytest.raises(KeyError):
        readers.read_roofline(dict(facts, peaks={}), spec)
