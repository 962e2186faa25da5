"""Reshard bench harness: fast 2→4 smoke in tier-1 + the slow-lane
MULTICHIP reshard matrix (8→4, 4→8, transposed axes, N→M with
replication) and the K-rank replicated-overlap fleet leg — the counted
form of "elastic reshard is minimal-byte" (bit-exact, origin bytes ≤
1.1× theoretical overlap, replicated overlaps fetched once fleet-wide)."""

import json
import os
import subprocess
import sys

import pytest

# The harness's children keep their compile cache under this test's tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


def _run_bench(cells: str, mb: int, fleet_ks: str, timeout: int = 420) -> dict:
    out = subprocess.run(
        [sys.executable, "benchmarks/reshard/main.py"],
        env={
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": os.environ["JAX_COMPILATION_CACHE_DIR"],
            "RESHARD_BENCH_CELLS": cells,
            "RESHARD_BENCH_MB": str(mb),
            "RESHARD_BENCH_GRAIN": "65536",
            "RESHARD_BENCH_FLEET_KS": fleet_ks,
            "RESHARD_BENCH_FLEET_MB": "2",
        },
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _check_cells(det: dict, expected) -> None:
    assert det["platform"] == "cpu"  # a CPU run says so
    cells = det["cells"]
    assert [c["cell"] for c in cells] == expected
    for c in cells:
        assert c["platform"] == "cpu"
        assert c["bit_exact"] is True
        assert c["origin_ratio"] <= 1.1
        # Bytes and ratios only: no time or rate from the CPU backend.
        assert not {"reshard_gbps", "reshard_wall_s"} & set(c)
        assert c["theoretical_overlap_bytes"] > 0
        # Per-object attribution rode along.
        assert set(c["attribution"]) >= {"origin_bytes", "peer_bytes"}


def test_reshard_bench_smoke_2to4() -> None:
    """Tier-1: one tiny 2→4 cell, no fleet — proves the harness end to end
    (bit-exactness, exact-overlap byte accounting, the ratio assert)."""
    rec = _run_bench(cells="2to4", mb=4, fleet_ks="")
    assert rec["metric"] == "reshard_origin_ratio_worst"
    assert rec["value"] <= 1.1
    _check_cells(rec["detail"], ["2to4"])


@pytest.mark.slow
@pytest.mark.multiprocess
def test_reshard_bench_full_matrix_and_fleet() -> None:
    """Slow lane: the full MULTICHIP reshard matrix plus the K∈{2,4,8}
    replicated-overlap fleet sweep (every chunk origin-fetched exactly
    once fleet-wide, total origin bytes ≤ 1.1× one payload at every K)."""
    rec = _run_bench(
        cells="8to4,4to8,8to4_transposed,4to8_replicated",
        mb=32,
        fleet_ks="2,4,8",
        timeout=1200,
    )
    det = rec["detail"]
    _check_cells(det, ["8to4", "4to8", "8to4_transposed", "4to8_replicated"])
    fleet = det["fleet"]
    assert [f["k"] for f in fleet] == [2, 4, 8]
    for f in fleet:
        assert f["origin_ratio_vs_one_payload"] <= 1.1
        assert all(n > 0 for n in f["per_rank_origin_reads"])
