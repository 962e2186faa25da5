"""``BENCHMARK.json`` against the contract's rules of form, and against
the data files it names."""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    bench = load("BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16 and all(PATH.match(p) for p in bench["paths"])
    assert len(bench["command"]) <= 32 and all(line(w) for w in bench["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["configs"]) <= 24 and 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128


def test_names_units_and_the_keys_of_every_entry():
    bench = load("BENCHMARK.json")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), (group, names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"]) and PATH.match(c["file"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    assert len({c["source"] for c in bench["configs"]}) == len(bench["configs"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4) and line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(bench["workloads"]) // 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_cell_reports_what_the_contract_asks_and_moves_point_at_reported_metrics():
    bench = load("BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    for cell in cells:
        assert "setup_s" in [n for n, ws in e2e.items() if cell in ws]
        assert len([n for n, ws in e2e.items() if cell in ws]) >= 2
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower().replace(" ", ""), set()).add(m["layer"])
    assert all(len(spellings) == 1 for spellings in layers.values())


def test_every_name_has_its_data_file_and_the_files_agree_with_the_entries():
    bench = load("BENCHMARK.json")
    for c in bench["configs"]:
        cfg = load(c["file"])
        assert cfg["name"] == c["name"] and set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"] and cfg["assumed"]["checkpoint_target_fstype"]
        # No width is changed: every key its architecture publishes is the
        # source's, but the keys ``reduced`` lists, and those do differ.
        published = run.find_architecture(ROOT, cfg["model_type"]).PUBLISHED
        assert published and set(c["reduced"]) <= set(published)
        for key, value in published.items():
            assert (cfg[key] == value) == (key not in c["reduced"]), (c["name"], key)
        assert all(cfg["job"][key] > 0 for key in ("seq_len", "micro_batch", "learning_rate"))
    for w in bench["workloads"]:
        assert load("perfbench", "traffic", w["traffic"] + ".json")["name"] == w["traffic"]
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert load(cfg["file"])["layout"]["chips"] == w["chips"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        spec = load("perfbench", "metrics", m["name"] + ".json")
        assert set(spec) == {"reader"} and spec["reader"]["kind"] in ("ratio", "idle", "roofline")


def test_files_under_paths_are_named_from_the_allowed_characters():
    for root, dirs, files in os.walk(os.path.join(ROOT, "perfbench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            rel = os.path.relpath(os.path.join(root, name), ROOT)
            assert PATH.match(rel), rel
