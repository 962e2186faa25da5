"""Staging micro-bench harness: fast unit coverage + the slow-lane smoke.

The slow-marked smoke is registered in pre_commit.yaml's slow lane so the
zero-copy RAW staging path (lanes, null sink, digest ablation) is exercised
on every PR at a size of several hash grains a leaf.
"""

import json
import subprocess
import sys

import pytest


def _run_bench(mb: int, arrays: int, extra_env: dict = None) -> dict:
    env = {
        "PATH": "/usr/bin:/bin:/usr/local/bin",
        "JAX_PLATFORMS": "cpu",
        "STAGING_BENCH_MB": str(mb),
        "STAGING_BENCH_ARRAYS": str(arrays),
    }
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, "benchmarks/staging/main.py"],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_staging_bench_smoke_tiny() -> None:
    """The harness runs, stages every byte into the null sink, and reports
    the stage-time decomposition for every ablation config."""
    rec = _run_bench(mb=16, arrays=2)
    assert rec["metric"] == "staging_overhead_gbps"
    det = rec["detail"]
    assert det["size_gb"] > 0
    for name in ("full", "serial_hash", "no_dedup_sha", "no_digests"):
        cfg = det["configs"][name]
        assert cfg["wall_s"] > 0
        assert cfg["gbps"] > 0
        for k in ("stage_d2h_s", "stage_serialize_s", "stage_hash_s"):
            assert k in cfg
    # Digest ablation is measurable: the no-digest config never hashes.
    assert det["configs"]["no_digests"]["stage_hash_s"] == 0
    assert det["hash_cost_s"] >= 0
    # Chunked-v2 vs serial-v1 hashing stays directly comparable every run.
    assert det["serial_hash_cost_s"] >= 0
    # The fast smoke skips the grain x worker sweep (slow lane material).
    assert det["hash_sweep"] is None


@pytest.mark.slow
def test_staging_bench_slow_smoke() -> None:
    """Slow-lane smoke at a size where every array is hashed in chunks:
    the zero-copy RAW path (views into host buffers) runs end to end, and
    the full config's hash stream is non-zero while the digest-free
    config's is zero."""
    rec = _run_bench(mb=256, arrays=4)
    det = rec["detail"]
    full = det["configs"]["full"]
    assert full["stage_hash_s"] > 0
    assert det["configs"]["no_digests"]["stage_hash_s"] == 0
    # The null sink makes staging the whole wall: busy time is attributed,
    # not lost (hash chunks may overlap the write, so compare
    # against the decomposition's own total).
    assert full["wall_s"] >= full["stage_busy_s"] - 0.5


@pytest.mark.slow
def test_staging_bench_hash_sweep() -> None:
    """The hash-grain x hash-worker sweep (serial-v1 vs chunked-v2 cells,
    STAGING_BENCH_HASH_SWEEP=1) reports wall + hash_cost_s per cell at a
    size where every array is hashed in chunks."""
    rec = _run_bench(
        mb=128, arrays=2, extra_env={"STAGING_BENCH_HASH_SWEEP": "1"}
    )
    sweep = rec["detail"]["hash_sweep"]
    assert sweep, "sweep env set but no cells reported"
    # At least one serial-v1 cell and one chunked-v2 cell per worker width.
    assert any(name.startswith("serial_w") for name in sweep)
    assert any(not name.startswith("serial_w") for name in sweep)
    for name, cell in sweep.items():
        assert cell["wall_s"] > 0, name
        assert cell["hash_cost_s"] >= 0, name
