"""Per-device drain scaling: drain GB/s vs device count, as a curve.

For each device count N this spawns a fresh process that shards one large
parameter array across a flat ``(N,)`` mesh of the first N devices and drives
an ``async_take`` whose background drain runs **N per-device D2H lanes and N
per-shard writes concurrently** (transfer lanes sized to the device count).
The result is the drain-GB/s-vs-device-
count curve — the regression surface for "the drain scales with devices",
not just "the drain is fast on one chip" — with the bytes each device's
transfers moved.

A chip belongs to one process: this parent never touches jax, and each cell
is a child that fails unless it finds N devices of the platform asked for.
``--platform tpu`` (the default) measures real chips. ``--platform cpu`` is a
dry run on ``--xla_force_host_platform_device_count`` virtual devices: it
says so in its output and reports bytes and counts only — a time or a rate
from the CPU backend is not a device metric.

One JSON line on stdout; progress on stderr.

  python benchmarks/multichip/main.py                        # 1,2,4 chips x 256 MB
  MULTICHIP_BENCH_DEVICES=1,2 MULTICHIP_BENCH_MB=32 \
  python benchmarks/multichip/main.py --platform cpu         # dry run
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import (  # noqa: E402
    REPO_ROOT,
    configure_compile_cache,
    device_record,
    require_native_engine,
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def child(platform: str, n_devices: int, total_mb: float, root: str) -> None:
    """One sweep cell: N devices, one flat-sharded array, one async_take.
    Runs in a fresh process and fails unless it finds what it was sent for."""
    configure_compile_cache()
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.telemetry import aggregate, fleet
    from torchsnapshot_tpu.utils import knobs

    device = device_record()
    if device["platform"] != platform or device["count"] < n_devices:
        raise SystemExit(
            f"cell N={n_devices} was sent to {n_devices} {platform} device(s); "
            f"jax found {device}"
        )
    require_native_engine()
    devices = jax.devices()[:n_devices]
    mesh = Mesh(np.array(devices), ("all",))
    rows = max(n_devices, int(total_mb * 1e6 / 2 / 16384))
    rows -= rows % n_devices  # evenly shardable
    host = np.arange(rows * 16384, dtype=np.uint16).reshape(rows, 16384)
    arr = jax.device_put(
        host.view(jax.numpy.bfloat16.dtype), NamedSharding(mesh, P("all"))
    )
    jax.block_until_ready(arr)
    payload_gb = arr.nbytes / 1e9

    try:
        # Per-device transfer lanes + per-shard writes: the drain should
        # hold one lane and one storage write busy per device.
        # Fleet telemetry forced on for the measured drain (single-process
        # cell, so "auto" resolves off): the cell record carries the
        # beacon rollup — engine high-water mark, final phase — beside the
        # throughput numbers.
        with knobs.override_d2h_lanes(max(4, n_devices)), (
            knobs.override_fleet_telemetry("1")
        ), knobs.override_fleet_beacon_s(0.05):
            fleet.reset()
            # Warmup absorbs compile/native-engine costs outside the
            # measured drain.
            Snapshot.take(os.path.join(root, "warm"), {"m": StateDict(x=arr)})
            t0 = time.perf_counter()
            pending = Snapshot.async_take(
                os.path.join(root, "ckpt"), {"m": StateDict(x=arr)}
            )
            stall_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            pending.wait()
            drain_s = time.perf_counter() - t0
            bus = fleet.get_bus()
            bus.publish(force=True)
            view = aggregate.fleet_view(bus.read_beacons())
            mine = (view.get("per_rank") or {}).get(0) or {}
            fleet_summary = {
                "ranks": view.get("ranks"),
                "engine": mine.get("engine"),
                "budget_hwm": mine.get("budget_hwm"),
                "phase": mine.get("phase"),
                "anomalies": mine.get("anomalies"),
            }
        fleet.reset()  # back to the ambient knob state
        metrics = Snapshot.last_telemetry.metrics.as_dict()
        rec = {
            "devices": n_devices,
            "platform": device["platform"],
            "device_kind": device["kind"],
            "payload_gb": round(payload_gb, 4),
            "d2h_bytes_per_device": {
                str(d.id): int(metrics.get(f"d2h.device_bytes.{d.id}", 0))
                for d in devices
            },
            "fleet": fleet_summary,
        }
        if platform != "cpu":
            ds = pending.drain_stats
            rec.update(
                stall_s=round(stall_s, 4),
                drain_s=round(drain_s, 4),
                drain_gbps=round(payload_gb / max(drain_s, 1e-9), 4),
                stage_busy_s=round(ds.get("stage_busy_s", 0.0), 3),
                io_busy_s=round(ds.get("io_busy_s", 0.0), 3),
                overlap_s=round(ds.get("overlap_s", 0.0), 3),
            )
        with open(os.path.join(root, "cell.json"), "w") as f:
            json.dump(rec, f)
    finally:
        shutil.rmtree(os.path.join(root, "warm"), ignore_errors=True)
        shutil.rmtree(os.path.join(root, "ckpt"), ignore_errors=True)


def run_cell(platform: str, n_devices: int, total_mb: float) -> dict:
    # Inside the checkout (.benchtmp/ is git-ignored): /tmp may be RAM.
    root = os.path.join(REPO_ROOT, ".benchtmp", f"multichip_{n_devices}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        # Appended last so it wins over any pre-set flag (last duplicate
        # wins in XLA).
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        )
    try:
        proc = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--platform",
                platform,
                "--child",
                str(n_devices),
                str(total_mb),
                root,
            ],
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"cell N={n_devices} failed:\n{proc.stderr[-2000:]}"
            )
        with open(os.path.join(root, "cell.json")) as f:
            return json.load(f)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--child", nargs=3, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        n, mb, root = args.child
        child(args.platform, int(n), float(mb), root)
        return
    total_mb = float(os.environ.get("MULTICHIP_BENCH_MB", "256"))
    default_counts = "1,2,4" if args.platform == "tpu" else "1,2,4,8"
    device_counts = [
        int(n)
        for n in os.environ.get(
            "MULTICHIP_BENCH_DEVICES", default_counts
        ).split(",")
        if n.strip()
    ]
    curve = []
    for n in device_counts:
        rec = run_cell(args.platform, n, total_mb)
        curve.append(rec)
        log(f"N={n}: {rec}")
    detail = {"payload_mb": total_mb, "platform": args.platform, "curve": curve}
    if args.platform == "cpu":
        # A dry run: the cells ran and every device drained; no rate.
        result = {
            "metric": "multichip_dry_run_cells",
            "value": len(curve),
            "unit": "cells",
            "detail": detail,
        }
    else:
        best = max(curve, key=lambda r: r["drain_gbps"])
        detail["scaling_vs_single"] = round(
            curve[-1]["drain_gbps"] / max(curve[0]["drain_gbps"], 1e-9), 3
        )
        detail["best"] = {
            "devices": best["devices"],
            "drain_gbps": best["drain_gbps"],
        }
        result = {
            "metric": "drain_gbps_at_max_devices",
            "value": curve[-1]["drain_gbps"],
            "unit": "GB/s",
            "detail": detail,
        }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
