"""A configuration, a traffic mix and a per-layer metric are added as new
files plus a ``BENCHMARK.json`` entry, with no edit to any file there is."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402


def dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def test_new_config_traffic_and_metric_files_are_picked_up(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "perfbench", sub), os.path.join(root, "perfbench", sub))
    before = {
        os.path.join(d, n): open(os.path.join(d, n), "rb").read()
        for d, _, names in os.walk(os.path.join(root, "perfbench")) for n in names
    }
    # What a later PR brings: three data files, one reader of its own...
    base = run.load_json(root, "perfbench", "configs", "pythia-6.9b-d6.json")
    dump(os.path.join(root, "perfbench", "configs", "pythia-6.9b-d8.json"),
         dict(base, name="pythia-6.9b-d8", num_hidden_layers=8))
    mix = run.load_json(root, "perfbench", "traffic", "save_weights.json")
    dump(os.path.join(root, "perfbench", "traffic", "save_moments.json"),
         dict(mix, name="save_moments", saved="opt_state"))
    dump(os.path.join(root, "perfbench", "metrics", "serialize_share_pct.json"), {
        "reader": {"kind": "ratio", "over": "rounds", "scale": 100.0,
                   "num": "save/telemetry/drain_stats_s/stage_serialize_s",
                   "den": "save/telemetry/drain_stats_s/wall_s"},
    })
    dump(os.path.join(root, "perfbench", "metrics", "steps_per_save.json"), {"reader": {"kind": "own"}})
    with open(os.path.join(root, "perfbench", "metrics", "steps_per_save.py"), "w") as f:
        f.write("def read(facts, spec):\n    return max(len(r['step_s']) for r in facts['rounds'])\n")
    # ... and entries in BENCHMARK.json.
    bench = run.load_json(root, "BENCHMARK.json")
    bench["configs"].append({
        "name": "pythia-6.9b-d8", "source": "x", "why": "x", "reduced": ["num_hidden_layers"],
        "file": "perfbench/configs/pythia-6.9b-d8.json",
    })
    bench["workloads"].append({
        "name": "pythia-6.9b-d8.save_moments", "config": "pythia-6.9b-d8",
        "traffic": "save_moments", "chips": 1, "why": "x",
    })
    bench["per_layer"].append({
        "name": "serialize_share_pct", "unit": "%", "better": "lower", "source": "program_span",
        "layer": "stage (scheduler.py, d2h.py, hashing.py)", "moves": "goodput_pct",
        "workloads": ["pythia-6.9b-d8.save_moments"],
    })
    bench["per_layer"].append({
        "name": "steps_per_save", "unit": "steps", "better": "higher", "source": "program_counter",
        "layer": "harness (perfbench rounds)", "moves": "goodput_pct",
        "workloads": ["pythia-6.9b-d8.save_moments"],
    })
    for m in bench["end_to_end"]:
        if m["name"] == "goodput_pct":
            m["workloads"].append("pythia-6.9b-d8.save_moments")
    dump(os.path.join(root, "BENCHMARK.json"), bench)

    found = run.find_cell(root, "pythia-6.9b-d8.save_moments")
    assert found["config"]["num_hidden_layers"] == 8
    assert found["traffic"]["saved"] == "opt_state"
    assert {m["name"] for m in found["end_to_end"]} == {"goodput_pct", "setup_s"}
    assert {m["name"] for m in found["per_layer"]} == {"serialize_share_pct", "steps_per_save"}
    facts = {"rounds": [
        {"step_s": [0.2] * 7, "save": {"telemetry": {"drain_stats_s": {"stage_serialize_s": 0.5, "wall_s": 5.0}}}},
        {"step_s": [0.2] * 9, "save": {"telemetry": {"drain_stats_s": {"stage_serialize_s": 1.0, "wall_s": 5.0}}}},
    ]}
    got = run.read_metrics(found["per_layer"], facts)
    assert got["serialize_share_pct"]["value"] == pytest.approx(15.0) and got["serialize_share_pct"]["unit"] == "%"
    assert got["steps_per_save"] == {"value": 9, "unit": "steps"}
    # The old cells still resolve, and no file that was there has changed.
    assert run.find_cell(root, "pythia-6.9b-d6.resume")["traffic"]["round"] == ["restore"]
    for path, content in before.items():
        assert open(path, "rb").read() == content


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    found = run.find_cell(ROOT, "pythia-6.9b-d6.save_weights")
    facts = {"rounds": [], "setup": {}, "summary": {}, "device": {}, "link": {}, "trace": {}, "traced": None,
             "peaks": {}}
    assert run.read_metrics(found["per_layer"], facts) == {}
