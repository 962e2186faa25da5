"""Each cell's ``--platform cpu --tiny`` dry run on four virtual devices,
the refusal without the platform asked for, and the controls: with the
path under test broken underneath, ``correct`` comes out false."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
DEVICE_METRICS = [m["name"] for group in ("end_to_end", "per_layer") for m in BENCH[group]]
DRY = ("--seconds", "1", "--platform", "cpu", "--tiny")


def traffic_of(cell):
    mix = next(w["traffic"] for w in BENCH["workloads"] if w["name"] == cell)
    return json.load(open(os.path.join(ROOT, "perfbench", "traffic", mix + ".json")))


def drive(script, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", script), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def periodic_cell():
    """A cell whose mix saves every so many steps, if the benchmark has one."""
    for cell in CELLS:
        if traffic_of(cell).get("period_steps"):
            return cell
    pytest.skip("no cell saves on a period")


def test_every_cell_the_benchmark_lists_resolves_to_its_files():
    from perfbench import run

    assert CELLS and len(CELLS) == len(set(CELLS))
    for cell in CELLS:
        found = run.find_cell(ROOT, cell)
        assert found["traffic"]["round"] and found["end_to_end"] and found["per_layer"]
        assert os.path.exists(os.path.join(ROOT, "perfbench", "models", found["config"]["model_type"] + ".py"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run_is_correct_and_reports_no_device_metric(cell, trace):
    proc = drive("run.py", "--workload", cell, "--seed", "2147483999", "--trace", trace, *DRY)
    result = last_line(proc)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    # The last line's keys, as the contract names them.
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["count"] == next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert "compiles_in_window=0 (limit 0)" in proc.stdout and "fallback_path_bytes=0 (limit 0)" in proc.stdout
    for line in proc.stdout.splitlines()[:-1]:
        assert not any(f'"{name}"' in line for name in DEVICE_METRICS), line


def test_a_periodic_mix_steps_its_period_in_every_cycle_and_its_warm_round_does_not():
    from perfbench import cycles, target

    cell, seed = periodic_cell(), "2147483998"
    period = traffic_of(cell)["period_steps"]
    last_line(drive("run.py", "--workload", cell, "--seed", seed, "--trace", "0", *DRY))
    records = cycles.load(os.path.join(target.OUT_DIR, "runs", f"{cell}-seed{seed}-trace0", "rounds.jsonl"))
    window = cycles.in_window(records)
    assert window and all(len(r["step_s"]) == period and "commit_wait_s" in r["save"] for r in window)
    warm = [r for r in records if r["round"] < 0]
    assert warm and all(len(r["step_s"]) < period for r in warm)


def test_a_run_without_the_platform_asked_for_fails_and_prints_no_result():
    proc = drive("run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1")
    assert proc.returncode != 0
    assert "refusing to measure" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


def test_cpu_without_tiny_is_refused():
    proc = drive("run.py", "--workload", CELLS[0], "--platform", "cpu")
    assert proc.returncode != 0 and "needs --tiny" in proc.stderr


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["round_fp8", "flip_bit", "no_commit", "host_capture", "late_compile"])
def test_control_comes_out_not_correct(cell, kind):
    mix = traffic_of(cell)
    if kind == "host_capture" and not ("save" in mix["round"] and mix.get("fork_fits")):
        pytest.skip("the mix's window takes no snapshot whose fork has to fit")
    proc = drive("tests/control.py", "--break", kind, "--workload", cell, "--seed", "11", *DRY)
    result = last_line(proc)
    assert result["correct"] is False
    if kind in ("no_commit", "host_capture"):
        assert result["failed"] >= 1
    elif kind == "late_compile":
        assert "inside the window" in proc.stdout
    else:
        assert "leaves differ" in proc.stdout
