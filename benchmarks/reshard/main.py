"""Elastic reshard harness: N→M restore as a minimal-byte operation, counted.

The resharding engine (``io_preparers/sharded_array.py``) restores a
snapshot across changed mesh shapes, axis orders, and device counts; this
harness makes "minimal-byte" a counted claim instead of a correctness-only
one. Its cells need up to 8 devices and change the device count between save
and restore, so every child runs on the CPU platform with
``--xla_force_host_platform_device_count`` virtual devices, says so in its
output (``"platform": "cpu"``), and reports bytes, ratios and counts only —
a reshard time or rate is a device metric and is not taken here. The parent
never touches jax.

- **Matrix cells** (fresh process per side — the device count is fixed at
  backend init, so save and restore each get their own child process):
  ``8to4``, ``4to8``, ``8to4_transposed`` (mesh axes swapped), and
  ``4to8_replicated`` (the restored mesh replicates one axis). Every cell
  asserts bit-exactness, then reports origin
  bytes vs **theoretical overlap bytes** (the union of saved-shard rows
  the targets actually overlap — what a minimal-byte reshard must fetch;
  ratio target ≤ 1.1×, the slack being hash-chunk alignment), and the
  per-object origin/peer/cache attribution from
  ``snapshot.LAST_RESTORE_STATS["attribution"]``.
- **Fleet leg** (``RESHARD_BENCH_FLEET_KS``, default ``2``): K real ranks
  (jax.distributed on CPU, 2 devices each) restore onto a mesh whose
  leading axis REPLICATES across processes — every rank needs every byte,
  the replicated-overlap case. Asserts every hash chunk is origin-fetched
  exactly ONCE fleet-wide (total origin bytes == one payload, not K×) and
  every peer-received chunk verified.

One JSON line on stdout; progress on stderr.

  python benchmarks/reshard/main.py                    # 64 MB matrix + K=2
  RESHARD_BENCH_MB=8 RESHARD_BENCH_FLEET_KS=2,4,8 \
  python benchmarks/reshard/main.py                    # fleet sweep
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import configure_compile_cache  # noqa: E402

COLS = 4096  # fp32 -> 16 KiB rows
GRAIN = int(os.environ.get("RESHARD_BENCH_GRAIN", str(1 << 20)))

# name -> (save_devices, save_mesh, save_axes, save_spec,
#          restore_devices, restore_mesh, restore_axes, restore_spec)
CELLS = {
    "2to4": (2, (2,), ("x",), ("x",), 4, (4,), ("x",), ("x",)),
    "8to4": (8, (8,), ("x",), ("x",), 4, (4,), ("x",), ("x",)),
    "4to8": (4, (4,), ("x",), ("x",), 8, (8,), ("x",), ("x",)),
    "8to4_transposed": (
        8, (4, 2), ("a", "b"), ("a", "b"), 4, (2, 2), ("a", "b"), ("b", "a"),
    ),
    "4to8_replicated": (
        4, (4,), ("x",), ("x",), 8, (4, 2), ("a", "b"), ("a",),
    ),
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _host(rows: int):
    import numpy as np

    # Deterministic content both child processes can regenerate.
    return (
        np.arange(rows * COLS, dtype=np.uint32)
        .reshape(rows, COLS)
        .view(np.float32)
    )


def _place(host, mesh_shape, axes, spec_axes, n_devices):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = np.array(jax.devices()[:n_devices]).reshape(mesh_shape)
    mesh = Mesh(devices, axes)
    spec = P(*spec_axes) if spec_axes else P()
    return jax.device_put(host, NamedSharding(mesh, spec))


def child_take(cell: str, rows: int, root: str) -> None:
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    n, mesh_shape, axes, spec = CELLS[cell][:4]
    arr = _place(_host(rows), mesh_shape, axes, spec, n)
    with knobs.override_hash_chunk_bytes(GRAIN):
        Snapshot.take(os.path.join(root, "ckpt"), {"m": StateDict(x=arr)})


def child_restore(cell: str, rows: int, root: str, out_path: str) -> None:
    import jax
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import snapshot as snapshot_mod
    from torchsnapshot_tpu.io_preparers.sharded_array import (
        index_to_offsets_sizes,
        overlap_row_intervals,
    )
    from torchsnapshot_tpu.serialization import string_to_dtype

    m, mesh_shape, axes, spec = CELLS[cell][4:]
    host = _host(rows)
    tgt_arr = _place(
        np.zeros_like(host), mesh_shape, axes, spec, m
    )
    path = os.path.join(root, "ckpt")
    entry = Snapshot(path).get_manifest()["0/m/x"]

    # Theoretical overlap bytes: for every saved shard, the union of row
    # intervals THIS process's target shards overlap (row-covering — the
    # contiguity unit a byte-range read can fetch), pre-alignment.
    sharding = tgt_arr.sharding
    rects, seen = [], set()
    for d in sharding.addressable_devices:
        idx = sharding.addressable_devices_indices_map(tuple(host.shape))[d]
        off, sz = index_to_offsets_sizes(idx, host.shape)
        if tuple(off) not in seen:
            seen.add(tuple(off))
            rects.append((off, sz))
    theoretical = 0
    for shard in entry.shards:
        itemsize = string_to_dtype(shard.tensor.dtype).itemsize
        row_bytes = itemsize * int(np.prod(shard.sizes[1:]))
        for b, e in overlap_row_intervals(shard.offsets, shard.sizes, rects):
            theoretical += (e - b) * row_bytes

    tgt = StateDict(x=tgt_arr)
    Snapshot(path).restore({"m": tgt})
    for shard in tgt["x"].addressable_shards:
        assert np.array_equal(
            np.asarray(shard.data).view(np.uint8),
            host[shard.index].view(np.uint8),
        ), f"cell {cell}: restore NOT bit-exact at {shard.index}"
    attr = snapshot_mod.LAST_RESTORE_STATS["attribution"]
    origin = int(attr["origin_bytes"])
    rec = {
        "cell": cell,
        "platform": jax.devices()[0].platform,
        "payload_gb": round(host.nbytes / 1e9, 4),
        "origin_bytes": origin,
        "theoretical_overlap_bytes": theoretical,
        "origin_ratio": round(origin / max(theoretical, 1), 4),
        "attribution": {k: int(v) for k, v in attr.items()},
        "bit_exact": True,
    }
    assert rec["origin_ratio"] <= 1.1, rec
    with open(out_path, "w") as f:
        json.dump(rec, f)


def _spawn(args, n_devices: int, timeout: int = 600):
    env = dict(os.environ)
    # Virtual devices exist on the CPU platform only: asked for by name, not
    # inherited, so a host that exports JAX_PLATFORMS=tpu cannot send these
    # children to fight over a chip.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + args,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"child {args} failed:\n{proc.stderr[-3000:]}")


def run_cell(cell: str, total_mb: float) -> dict:
    spec = CELLS[cell]
    rows = max(16, int(total_mb * 1e6 / 4 / COLS))
    rows -= rows % 16  # divisible by every mesh extent used here
    root = tempfile.mkdtemp(prefix=f"tss_reshard_{cell}_")
    out_path = os.path.join(root, "cell.json")
    try:
        _spawn(["--take", cell, str(rows), root], spec[0])
        _spawn(["--restore", cell, str(rows), root, out_path], spec[4])
        with open(out_path) as f:
            return json.load(f)
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Fleet leg: replicated-overlap chunks fetched exactly once across K ranks.
# ---------------------------------------------------------------------------

def _fleet_worker(
    rank: int, world_size: int, shared: str, rows: int, grain: int
) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import swarm as swarm_mod
    from torchsnapshot_tpu.utils import knobs

    host = _host(rows)
    path = os.path.join(shared, "ckpt")
    devices = np.array(jax.devices())  # world_size * 2 global devices
    src = jax.make_array_from_callback(
        host.shape,
        NamedSharding(Mesh(devices, ("x",)), P(None, "x")),
        lambda idx: host[idx],
    )
    with knobs.override_hash_chunk_bytes(grain):
        Snapshot.take(path, {"m": StateDict(x=src)})

    # Leading mesh axis spans processes and is NOT in the spec: every
    # process needs every byte — the replicated-overlap case.
    mesh = Mesh(devices.reshape(world_size, 2), ("a", "b"))
    tgt_arr = jax.make_array_from_callback(
        host.shape,
        NamedSharding(mesh, P(None, "b")),
        lambda idx: np.zeros_like(host)[idx],
    )
    tgt = StateDict(x=tgt_arr)
    with knobs.override_swarm_restore(True):
        Snapshot(path).restore({"m": tgt})
    for shard in tgt["x"].addressable_shards:
        assert np.array_equal(np.asarray(shard.data), host[shard.index])
    d = dict(swarm_mod.LAST_RESTORE_SWARM)
    assert d["peer_chunks_verified"] == d["chunks_peer"], d
    with open(os.path.join(shared, f"fleet_diag_{rank}.json"), "w") as f:
        json.dump(
            {
                "origin_reads": d["origin_reads"],
                "origin_bytes": d["origin_bytes"],
                "peer_bytes": d["peer_bytes"],
                "chunks": d["chunks"],
            },
            f,
        )


def run_fleet(k: int, total_mb: float) -> dict:
    from torchsnapshot_tpu.test_utils import run_with_processes

    rows = max(16, int(total_mb * 1e6 / 4 / COLS))
    rows -= rows % 16
    payload = rows * COLS * 4
    # The save spreads 2K column shards; each must span several hash
    # chunks or there is no v2 grid and the swarm (correctly) declines.
    grain = max(16384, min(GRAIN, payload // (2 * k) // 2))
    shared = tempfile.mkdtemp(prefix=f"tss_reshard_fleet{k}_")
    try:
        run_with_processes(
            _fleet_worker,
            nproc=k,
            init_jax_distributed=True,
            args=(shared, rows, grain),
            timeout_s=600.0,
        )
        diags = [
            json.load(open(os.path.join(shared, f"fleet_diag_{r}.json")))
            for r in range(k)
        ]
    finally:
        shutil.rmtree(shared, ignore_errors=True)
    assert diags[0]["chunks"] > 0, (
        f"K={k}: the need-aware swarm never engaged (no v2 chunk grids?)"
    )
    all_reads = [tuple(x) for d in diags for x in d["origin_reads"]]
    assert len(all_reads) == len(set(all_reads)), (
        f"K={k}: a chunk was origin-fetched twice"
    )
    total_origin = sum(d["origin_bytes"] for d in diags)
    ratio = total_origin / payload
    assert ratio <= 1.1, (k, total_origin, payload)
    return {
        "k": k,
        "payload_gb": round(payload / 1e9, 4),
        "fleet_origin_bytes": total_origin,
        "origin_ratio_vs_one_payload": round(ratio, 4),
        "chunks": diags[0]["chunks"],
        "peer_bytes_total": sum(d["peer_bytes"] for d in diags),
        "per_rank_origin_reads": [len(d["origin_reads"]) for d in diags],
    }


def main() -> None:
    configure_compile_cache()  # before the backend initialises
    if len(sys.argv) > 1 and sys.argv[1] == "--take":
        child_take(sys.argv[2], int(sys.argv[3]), sys.argv[4])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--restore":
        child_restore(sys.argv[2], int(sys.argv[3]), sys.argv[4], sys.argv[5])
        return
    total_mb = float(os.environ.get("RESHARD_BENCH_MB", "64"))
    fleet_mb = float(os.environ.get("RESHARD_BENCH_FLEET_MB", "8"))
    cells = [
        c
        for c in os.environ.get(
            "RESHARD_BENCH_CELLS", ",".join(CELLS)
        ).split(",")
        if c.strip()
    ]
    fleet_ks = [
        int(x)
        for x in os.environ.get("RESHARD_BENCH_FLEET_KS", "2").split(",")
        if x.strip()
    ]
    matrix = []
    for cell in cells:
        rec = run_cell(cell, total_mb)
        matrix.append(rec)
        log(f"{cell}: {rec}")
    fleet = []
    for k in fleet_ks:
        rec = run_fleet(k, fleet_mb)
        fleet.append(rec)
        log(f"fleet K={k}: {rec}")
    worst_ratio = max(r["origin_ratio"] for r in matrix)
    print(
        json.dumps(
            {
                "metric": "reshard_origin_ratio_worst",
                "value": worst_ratio,
                "unit": "x_theoretical_overlap",
                "detail": {
                    "platform": "cpu",
                    "matrix_mb": total_mb,
                    "grain": GRAIN,
                    "cells": matrix,
                    "fleet": fleet,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
