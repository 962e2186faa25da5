"""Staging-only micro-bench: _WritePipeline overhead without a device.

The r02→r05 drain regression (32s → 55s on the same 1.11 GB workload) hid
inside ``stage_busy`` — a single opaque number polluted by TPU/link variance.
This harness makes staging overhead measurable in isolation, bisect-style:

- **synthetic host buffers** (numpy, no device, no D2H variance): a
  ``np.asarray`` on a host array is free, so the measured wall is purely the
  pipeline's own machinery — serialization, hashing, budget accounting,
  event-loop dispatch;
- **a null storage sink** (writes discard after a length probe): no
  disk, no page cache, no O_DIRECT alignment — ``io_busy`` collapses to the
  call overhead, so ``stage_busy`` is the whole story;
- **an ablation matrix** over the staging features that have historically
  eaten drain time: checksums on/off, dedup digests on/off. A regression bisects by diffing configs between two commits.

Reported per config: wall seconds, GB/s through staging, and the
``stage_d2h_s``/``stage_serialize_s``/``stage_hash_s`` decomposition. One
JSON line on stdout; progress on stderr.

  python benchmarks/staging/main.py                 # default ~0.5 GB
  STAGING_BENCH_MB=64 python benchmarks/staging/main.py   # quick smoke
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import start_host_only_run  # noqa: E402

import numpy as np  # noqa: E402

from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer  # noqa: E402
from torchsnapshot_tpu.io_types import (  # noqa: E402
    ReadIO,
    StoragePlugin,
    WriteIO,
)
from torchsnapshot_tpu.scheduler import execute_write_reqs  # noqa: E402
from torchsnapshot_tpu.utils import knobs  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NullStoragePlugin(StoragePlugin):
    """Discards every byte after a length probe: the staging stream runs
    against a zero-cost drain, so the pipeline's wall time IS staging."""

    def __init__(self) -> None:
        self.bytes_sunk = 0

    async def write(self, write_io: WriteIO) -> None:
        self.bytes_sunk += memoryview(write_io.buf).nbytes

    async def read(self, read_io: ReadIO) -> None:
        raise FileNotFoundError(read_io.path)

    async def delete(self, path: str) -> None:
        pass  # idempotent: nothing is ever stored


def build_host_state(total_mb: int, arrays: int, seed: int = 0):
    """``arrays`` float32 host arrays summing to ~total_mb MB."""
    rng = np.random.default_rng(seed)
    per = max(1, total_mb // arrays)
    rows = max(2, per * 1024 * 1024 // (1024 * 4))
    return [
        rng.standard_normal((rows, 1024)).astype(np.float32)
        for _ in range(arrays)
    ]


def run_config(
    arrs,
    checksums: bool,
    dedup: bool,
    hash_grain: int = None,
    hash_workers: int = None,
) -> dict:
    storage = NullStoragePlugin()
    reqs = []
    for i, a in enumerate(arrs):
        _entry, sub = ArrayIOPreparer.prepare_write(f"obj_{i}", a)
        reqs.extend(sub)
    total = sum(a.nbytes for a in arrs)

    async def go():
        pending = await execute_write_reqs(
            reqs, storage, memory_budget_bytes=2**33, rank=0
        )
        await pending.complete()
        return pending

    import contextlib

    overrides = contextlib.ExitStack()
    if hash_grain is not None:
        overrides.enter_context(knobs.override_hash_chunk_bytes(hash_grain))
    if hash_workers is not None:
        overrides.enter_context(knobs.override_hash_workers(hash_workers))
    loop = asyncio.new_event_loop()
    try:
        with overrides, \
                knobs.override_checksums(checksums), \
                knobs.override_dedup_digests(dedup):
            t0 = time.perf_counter()
            pending = loop.run_until_complete(go())
            wall = time.perf_counter() - t0
    finally:
        loop.close()
    assert storage.bytes_sunk >= total, (storage.bytes_sunk, total)
    stats = pending.pipeline_stats
    return {
        "wall_s": round(wall, 4),
        "gbps": round(total / 1e9 / wall, 3),
        "stage_busy_s": round(stats.get("stage_busy_s", 0.0), 4),
        "stage_d2h_s": round(stats.get("stage_d2h_s", 0.0), 4),
        "stage_serialize_s": round(stats.get("stage_serialize_s", 0.0), 4),
        "stage_hash_s": round(stats.get("stage_hash_s", 0.0), 4),
    }


def main() -> None:
    host_only = start_host_only_run("staging", writes_files=False)  # null sink
    total_mb = int(os.environ.get("STAGING_BENCH_MB", "512"))
    arrays = int(os.environ.get("STAGING_BENCH_ARRAYS", "8"))
    arrs = build_host_state(total_mb, arrays)
    total_gb = sum(a.nbytes for a in arrs) / 1e9
    log(f"staging micro-bench: {total_gb:.2f} GB across {arrays} host arrays")

    # Warmup: absorb one-time costs (thread-pool spawn, hashing-engine
    # operator caches, lazy imports) on a tiny slice so the matrix's FIRST
    # cell isn't charged ~0.2s the others never pay.
    run_config([a[:64] for a in arrs[:1]], checksums=True, dedup=True)

    # The ablation matrix: diffing rows bisects which staging feature a
    # regression lives in. "full" is the production default path (chunked
    # v2 tree hashing); "serial_hash" pins the v1 serial fold (grain 0) so
    # chunked-vs-serial hashing stays directly comparable every run.
    matrix = {
        "full": dict(checksums=True, dedup=True),
        "serial_hash": dict(checksums=True, dedup=True, hash_grain=0),
        "no_dedup_sha": dict(checksums=True, dedup=False),
        "no_digests": dict(checksums=False, dedup=False),
    }
    results = {}
    for name, cfg in matrix.items():
        results[name] = run_config(arrs, **cfg)
        log(f"  {name}: {results[name]}")

    full, bare = results["full"], results["no_digests"]

    def hash_cost(cell: dict) -> float:
        # Wall paid over the digest-free baseline: the cell's hashing bill.
        return round(max(0.0, cell["wall_s"] - bare["wall_s"]), 4)

    # Optional hash-grain x hash-worker sweep (serial v1 vs chunked v2 at
    # several grains, across pool widths): the tuning map for
    # TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES / _HASH_WORKERS. The full sweep is
    # slow-lane material (pre_commit.yaml); the fast smoke skips it.
    hash_sweep = None
    if os.environ.get("STAGING_BENCH_HASH_SWEEP"):
        default_grain = knobs.get_hash_chunk_bytes()
        default_workers = knobs.get_hash_workers()
        grains = {
            "serial": 0,
            f"g{default_grain // (1024 * 1024)}m": default_grain,
            f"g{max(1, default_grain // 4) // (1024 * 1024)}m": max(
                1024 * 1024, default_grain // 4
            ),
        }
        workers = sorted({1, default_workers, 2 * default_workers})
        hash_sweep = {}
        for gname, grain in grains.items():
            for w in workers:
                cell = run_config(
                    arrs,
                    checksums=True,
                    dedup=True,
                    hash_grain=grain,
                    hash_workers=w,
                )
                cell["hash_cost_s"] = hash_cost(cell)
                hash_sweep[f"{gname}_w{w}"] = cell
                log(f"  hash sweep {gname}_w{w}: {cell}")

    print(
        json.dumps(
            {
                "metric": "staging_overhead_gbps",
                "value": results["full"]["gbps"],
                "unit": "GB/s",
                "device": host_only,
                "detail": {
                    "size_gb": round(total_gb, 3),
                    "arrays": arrays,
                    "configs": results,
                    # The hash satellite's measurable delta: staging rate
                    # with vs without the digest pipeline — chunked (the
                    # default) and the serial v1 fold side by side.
                    "hash_cost_s": hash_cost(full),
                    "serial_hash_cost_s": hash_cost(results["serial_hash"]),
                    "hash_sweep": hash_sweep,
                    "env": {"knobs": knobs.env_fingerprint()},
                },
            }
        )
    )


if __name__ == "__main__":
    main()
