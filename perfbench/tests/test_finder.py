"""A configuration, a traffic mix, a per-layer metric and an architecture
are added as new files plus a ``BENCHMARK.json`` entry, with no edit to any
file there is."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402


def dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def contents(root):
    """Every file under ``root/perfbench``, by path."""
    return {
        os.path.join(d, n): open(os.path.join(d, n), "rb").read()
        for d, _, names in os.walk(os.path.join(root, "perfbench")) for n in names
    }


def test_new_config_traffic_and_metric_files_are_picked_up(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "perfbench", sub), os.path.join(root, "perfbench", sub))
    before = contents(root)
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


OTHER_ARCHITECTURE = '''"""A made-up architecture: an embedding kept in float32 and a bf16 head."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PUBLISHED = {"width": 48, "classes": 96}
TINY = {"width": 16, "classes": 32}


def param_tree(cfg):
    return {
        "table": {"kernel": jax.ShapeDtypeStruct((cfg["classes"], cfg["width"]), jnp.float32)},
        "head": {"kernel": jax.ShapeDtypeStruct((cfg["width"], cfg["classes"]), jnp.bfloat16)},
    }


def init_leaf(path, leaf, key):
    return (0.1 * jax.random.normal(key, leaf.shape, jnp.float32)).astype(leaf.dtype)


def param_spec(path):
    return P()


def token_range(cfg):
    return cfg["classes"]


def loss_fn(cfg, params, tokens):
    x = params["table"]["kernel"][tokens[:, :-1]]
    logp = jax.nn.log_softmax(x @ params["head"]["kernel"].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
'''


def test_a_new_architecture_comes_as_files_and_its_cell_runs(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "perfbench"), os.path.join(root, "perfbench"),
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    before = contents(root)
    # What a later PR brings: the architecture, a configuration of it...
    with open(os.path.join(root, "perfbench", "models", "two_matrices.py"), "w") as f:
        f.write(OTHER_ARCHITECTURE)
    dump(os.path.join(root, "perfbench", "configs", "two-matrices.json"), {
        "name": "two-matrices", "model_type": "two_matrices", "width": 48, "classes": 96, "reduced": {},
        "assumed": {"checkpoint_target_fstype": "9p"},
        "job": {"seq_len": 64, "micro_batch": 2, "learning_rate": 0.001},
        "layout": {"chips": 1, "mesh": None}, "guarantees": ["restore is bit-exact for every leaf, every dtype"],
    })
    # ... and entries in BENCHMARK.json, under a mix that is there.
    cell = "two-matrices.resume"
    bench = run.load_json(root, "BENCHMARK.json")
    bench["configs"].append({
        "name": "two-matrices", "source": "x", "why": "x", "reduced": [],
        "file": "perfbench/configs/two-matrices.json",
    })
    bench["workloads"].append({"name": cell, "config": "two-matrices", "traffic": "resume", "chips": 1, "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "pythia-6.9b-d6.resume" in m.get("workloads", []):
            m["workloads"].append(cell)
    dump(os.path.join(root, "BENCHMARK.json"), bench)

    # The library comes from this checkout; everything of the harness from the copy.
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"), "--workload", cell, "--seed", "2147484001",
         "--seconds", "1", "--trace", "0", "--platform", "cpu", "--tiny"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    # Two parameters, adamw's two moments of each and its count; float32,
    # bfloat16 and int32 side by side, each compared after every restore.
    records = [json.loads(line) for line in open(os.path.join(
        root, ".perfbench_out", "runs", f"{cell}-seed2147484001-trace0", "rounds.jsonl"))]
    restores = [r["restore"] for r in records if r.get("restore")]
    assert restores and all(r["leaves_compared"] == 7 and r["leaves_differing"] == 0 for r in restores)
    assert "loss_gap_after_restore=0.0 (limit 0)" in proc.stdout
    # A model_type with no file is refused by name, with the files there are.
    with pytest.raises(SystemExit, match=r"no architecture 'gpt_neoy'.*'gpt_neox', 'two_matrices'"):
        run.find_architecture(root, "gpt_neoy")
    for path, content in before.items():
        assert open(path, "rb").read() == content


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    found = run.find_cell(ROOT, "pythia-6.9b-d6.save_weights")
    facts = {"rounds": [], "setup": {}, "summary": {}, "device": {}, "link": {}, "trace": {}, "traced": None,
             "peaks": {}}
    assert run.read_metrics(found["per_layer"], facts) == {}
