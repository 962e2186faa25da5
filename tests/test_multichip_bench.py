"""Per-device drain-scaling bench harness, driven as the explicit CPU dry
run (virtual devices; bytes and counts only — the curve itself is a chip
measurement): fast tier-1 smoke + the slow-lane sweep."""

import json
import os
import subprocess
import sys

import pytest

# The harness's children keep their compile cache under this test's tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


def _run_bench(devices: str, mb: int, timeout: int = 420, platform="cpu"):
    return subprocess.run(
        [sys.executable, "benchmarks/multichip/main.py", "--platform", platform],
        env={
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": os.environ["JAX_COMPILATION_CACHE_DIR"],
            "MULTICHIP_BENCH_DEVICES": devices,
            "MULTICHIP_BENCH_MB": str(mb),
        },
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def _dry_run(devices: str, mb: int, timeout: int = 420) -> dict:
    out = _run_bench(devices, mb, timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _check_curve(rec: dict, expected_devices) -> None:
    assert rec["metric"] == "multichip_dry_run_cells"
    det = rec["detail"]
    assert det["platform"] == "cpu"  # a CPU run says so
    curve = det["curve"]
    assert [c["devices"] for c in curve] == expected_devices
    for cell in curve:
        assert cell["platform"] == "cpu"
        assert cell["payload_gb"] > 0
        # Every device of the mesh drained its share; none queued on the first.
        per_device = cell["d2h_bytes_per_device"]
        assert len(per_device) == cell["devices"]
        assert all(v > 0 for v in per_device.values()), per_device
        # No time or rate is reported from the CPU backend.
        assert not {"drain_gbps", "drain_s", "stall_s"} & set(cell)


def test_multichip_bench_smoke_tiny() -> None:
    _check_curve(_dry_run(devices="1,2", mb=8), [1, 2])


def test_multichip_bench_refuses_cpu_unless_asked() -> None:
    """The default is the chip: a cell that finds only the CPU backend
    fails, naming what it found, instead of measuring virtual devices."""
    out = _run_bench(devices="1", mb=8, platform="tpu")
    assert out.returncode != 0
    assert "'platform': 'cpu'" in out.stderr


@pytest.mark.slow
def test_multichip_bench_full_sweep() -> None:
    """The full 1→8 virtual-device sweep at 128 MB a cell."""
    _check_curve(_dry_run(devices="1,2,4,8", mb=128, timeout=900), [1, 2, 4, 8])
