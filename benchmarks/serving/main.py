"""Serving-scale read-path benchmark: K replicas cold-start from ONE snapshot.

Production inference restores the same snapshot on a fleet; the read path's
job is to make that cost 1x the snapshot, not Kx. This harness simulates a
fleet of K replicas and measures the three serving-path mechanisms:

- **read-through cache** (``TORCHSNAPSHOT_TPU_READ_CACHE_DIR``): replicas
  sharing a local cache volume restore with p50/p99 wall times reported for
  cache off vs on; with the cache on, every replica after the first reads
  **0 bytes from origin storage** (asserted from per-restore telemetry);
- **broadcast restore** (``TORCHSNAPSHOT_TPU_BCAST_RESTORE``): K real
  processes restore replicated entries with broadcast off vs on; with it
  on, each replicated object is read from origin by **exactly one rank**
  (asserted from ``bcast.LAST_RESTORE_BCAST`` gathered across ranks);
- **lazy partial reads**: ``read_object`` of one tower's manifest subtree
  fetches only that subtree's bytes (asserted against the tower/total
  payload ratio from storage read counters);
- **swarm restore** (``TORCHSNAPSHOT_TPU_SWARM_RESTORE``): K real ranks
  cold-restore ONE replicated object too big for broadcast via the
  chunk-granular swarm, at K ∈ ``SERVING_BENCH_SWARM_KS`` (default 2,4,8);
  asserted per K: every chunk origin-read by **exactly one rank**
  fleet-wide, **total origin bytes ≤ 1.1× one snapshot independent of K**,
  and every peer-received chunk verified against the sidecar v2 grid.

One JSON line on stdout; progress on stderr.

  python benchmarks/serving/main.py                       # ~64 MB, K=8
  SERVING_BENCH_MB=8 SERVING_BENCH_REPLICAS=3 \
  SERVING_BENCH_BCAST=0 python benchmarks/serving/main.py  # fast smoke
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import start_host_only_run  # noqa: E402

import numpy as np  # noqa: E402

from torchsnapshot_tpu import Snapshot, StateDict, telemetry  # noqa: E402
from torchsnapshot_tpu import snapshot as snapshot_mod  # noqa: E402
from torchsnapshot_tpu.utils import knobs  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pct(values, q: float) -> float:
    s = sorted(values)
    if not s:
        return 0.0
    idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[idx]


def build_state(total_mb: float, towers: int = 4, seed: int = 0) -> StateDict:
    """``towers`` equal towers of float32 layers — the lazy-read unit."""
    rng = np.random.default_rng(seed)
    per_tower = max(1, int(total_mb * 1e6 / towers / 4))
    model = {}
    for t in range(towers):
        model[f"tower_{t}"] = {
            "w": rng.standard_normal(per_tower, dtype=np.float32)
        }
    return StateDict(model=model, step=0)


def fresh_targets(total_mb: float, towers: int = 4) -> StateDict:
    per_tower = max(1, int(total_mb * 1e6 / towers / 4))
    model = {
        f"tower_{t}": {"w": np.zeros(per_tower, dtype=np.float32)}
        for t in range(towers)
    }
    return StateDict(model=model, step=0)


def restore_once(path: str, total_mb: float) -> dict:
    """One replica's cold restore; returns wall + origin-byte accounting."""
    tm = telemetry.Telemetry()
    targets = fresh_targets(total_mb)
    t0 = time.perf_counter()
    Snapshot(path).restore({"app": targets}, _telemetry=tm)
    wall = time.perf_counter() - t0
    m = tm.metrics.as_dict()
    origin = sum(
        v for k, v in m.items() if k.endswith(".read_bytes") and k.startswith("storage.")
    )
    return {
        "wall_s": wall,
        "origin_bytes": int(origin),
        "cache_hits": int(m.get("cache.hits", 0)),
        "cache_misses": int(m.get("cache.misses", 0)),
    }


def run_cache_leg(origin_root: str, total_mb: float, replicas: int) -> dict:
    """K sequential replica cold-starts, cache off vs on (shared local
    cache volume — the co-hosted-replicas serving shape)."""
    path = os.path.join(origin_root, "snap")
    out = {}
    for mode in ("off", "on"):
        walls = []
        records = []
        if mode == "on":
            cache_dir = tempfile.mkdtemp(prefix="tss_serving_cache_")
            ctx = knobs.override_read_cache_dir(cache_dir)
        else:
            cache_dir = None
            ctx = None
        try:
            if ctx is not None:
                ctx.__enter__()
            for _ in range(replicas):
                rec = restore_once(path, total_mb)
                walls.append(rec["wall_s"])
                records.append(rec)
        finally:
            if ctx is not None:
                ctx.__exit__(None, None, None)
            if cache_dir:
                shutil.rmtree(cache_dir, ignore_errors=True)
        warm_origin = sum(r["origin_bytes"] for r in records[1:])
        out[mode] = {
            "replicas": replicas,
            "restore_p50_s": round(_pct(walls, 0.50), 4),
            "restore_p99_s": round(_pct(walls, 0.99), 4),
            "cold_origin_bytes": records[0]["origin_bytes"],
            "warm_origin_bytes_total": warm_origin,
            "total_origin_bytes": sum(r["origin_bytes"] for r in records),
        }
        log(f"cache {mode}: {out[mode]}")
    assert out["on"]["warm_origin_bytes_total"] == 0, (
        "cache-on repeat restores must read 0 bytes from origin: "
        f"{out['on']}"
    )
    return out


def _bcast_worker(rank: int, world: int, path: str, total_mb: float, result_path: str) -> None:
    """One fleet rank: take a replicated snapshot together, then restore it
    with broadcast off and on, gathering walls + broadcast records."""
    from torchsnapshot_tpu import bcast
    from torchsnapshot_tpu.parallel.coordinator import get_coordinator

    state = build_state(total_mb, seed=7)
    Snapshot.take(path, {"app": state}, replicated=["app/*"])
    results = {}
    for mode in ("off", "on"):
        targets = fresh_targets(total_mb)
        with knobs.override_broadcast_restore(mode == "on"):
            t0 = time.perf_counter()
            Snapshot(path).restore({"app": targets})
            wall = time.perf_counter() - t0
        d = dict(bcast.LAST_RESTORE_BCAST)
        coord = get_coordinator()
        gathered = coord.all_gather_object(
            {
                "wall_s": wall,
                "origin_reads": d.get("origin_reads", []),
                "recv_bytes": d.get("recv_bytes", 0),
                "origin_bytes": d.get("origin_bytes", 0),
            }
        )
        if rank == 0:
            walls = [g["wall_s"] for g in gathered]
            all_origin = [p for g in gathered for p in g["origin_reads"]]
            results[mode] = {
                "ranks": world,
                "restore_p50_s": round(_pct(walls, 0.50), 4),
                "restore_p99_s": round(_pct(walls, 0.99), 4),
                "origin_reads_total": len(all_origin),
                "origin_reads_unique": len(set(all_origin)),
                "recv_bytes_total": sum(g["recv_bytes"] for g in gathered),
            }
    if rank == 0:
        on = results["on"]
        assert on["origin_reads_total"] == on["origin_reads_unique"], (
            f"broadcast restore read a replicated object from more than one "
            f"rank: {results}"
        )
        assert on["origin_reads_total"] > 0 and on["recv_bytes_total"] > 0, (
            f"broadcast restore never engaged: {results}"
        )
        with open(result_path, "w") as f:
            json.dump(results, f)


def run_bcast_leg(total_mb: float, ranks: int) -> dict:
    from torchsnapshot_tpu.test_utils import run_with_processes

    root = tempfile.mkdtemp(prefix="tss_serving_bcast_")
    result_path = os.path.join(root, "results.json")
    try:
        run_with_processes(
            _bcast_worker,
            nproc=ranks,
            args=(os.path.join(root, "snap"), total_mb, result_path),
            timeout_s=600.0,
        )
        with open(result_path) as f:
            results = json.load(f)
        for mode, rec in results.items():
            log(f"broadcast {mode}: {rec}")
        return results
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _swarm_worker(
    rank: int, world: int, path: str, total_mb: float, result_path: str
) -> None:
    """One fleet rank of the swarm leg: take ONE replicated object too big
    for broadcast, cold-restore it via the chunk swarm, and gather the
    per-rank swarm records so rank 0 can assert the headline invariants."""
    from torchsnapshot_tpu import swarm as swarm_mod
    from torchsnapshot_tpu.parallel.coordinator import get_coordinator

    nbytes = int(total_mb * 1e6)
    arr = np.frombuffer(
        np.random.default_rng(11).bytes(nbytes), dtype=np.uint8
    ).copy()
    # One big replicated array; a small grain keeps the chunk grid wide
    # enough that every rank gets assigned chunks even at K=8.
    grain = max(64 * 1024, nbytes // 64)
    with knobs.override_hash_chunk_bytes(grain):
        Snapshot.take(path, {"app": StateDict(w=arr)}, replicated=["app/*"])
    tgt = StateDict(w=np.zeros(nbytes, np.uint8))
    # Cap broadcast far below the object so mode selection picks swarm.
    with knobs.override_swarm_restore(True), knobs.override_broadcast_max_bytes(
        64 * 1024
    ):
        t0 = time.perf_counter()
        Snapshot(path).restore({"app": tgt})
        wall = time.perf_counter() - t0
    assert np.array_equal(tgt["w"], arr), "swarm restore not bit-exact"
    d = dict(swarm_mod.LAST_RESTORE_SWARM)
    coord = get_coordinator()
    gathered = coord.all_gather_object(
        {
            "wall_s": wall,
            "origin_reads": [list(x) for x in d["origin_reads"]],
            "origin_bytes": d["origin_bytes"],
            "peer_bytes": d["peer_bytes"],
            "chunks": d["chunks"],
            "chunks_peer": d["chunks_peer"],
            "peer_chunks_verified": d["peer_chunks_verified"],
        }
    )
    if rank == 0:
        walls = [g["wall_s"] for g in gathered]
        all_reads = [tuple(x) for g in gathered for x in g["origin_reads"]]
        total_origin = sum(g["origin_bytes"] for g in gathered)
        rec = {
            "ranks": world,
            "restore_p50_s": round(_pct(walls, 0.50), 4),
            "restore_p99_s": round(_pct(walls, 0.99), 4),
            "chunks": gathered[0]["chunks"],
            "origin_chunk_reads_total": len(all_reads),
            "origin_chunk_reads_unique": len(set(all_reads)),
            "origin_bytes_total": total_origin,
            "origin_bytes_vs_snapshot": round(total_origin / nbytes, 3),
            "peer_bytes_total": sum(g["peer_bytes"] for g in gathered),
            "peer_chunks_total": sum(g["chunks_peer"] for g in gathered),
            "peer_chunks_verified": sum(
                g["peer_chunks_verified"] for g in gathered
            ),
        }
        # The headline asserts: every chunk origin-read EXACTLY once
        # fleet-wide, total origin bytes ≈ one snapshot independent of K,
        # every peer-received chunk verified against the sidecar grid.
        assert (
            rec["origin_chunk_reads_total"]
            == rec["origin_chunk_reads_unique"]
            == rec["chunks"]
        ), rec
        assert rec["origin_bytes_total"] <= 1.1 * nbytes, rec
        assert rec["peer_chunks_verified"] == rec["peer_chunks_total"] > 0, rec
        with open(result_path, "w") as f:
            json.dump(rec, f)


def run_swarm_leg(total_mb: float, ranks_list) -> dict:
    """Chunk-swarm cold start at K∈ranks_list: origin bytes must stay ≈ one
    snapshot (and cold-start p99 ≈ flat) as the fleet grows — the curve
    broadcast restore cannot produce above its payload cap."""
    from torchsnapshot_tpu.test_utils import run_with_processes

    out = {}
    for ranks in ranks_list:
        root = tempfile.mkdtemp(prefix="tss_serving_swarm_")
        result_path = os.path.join(root, "results.json")
        try:
            run_with_processes(
                _swarm_worker,
                nproc=ranks,
                args=(os.path.join(root, "snap"), total_mb, result_path),
                timeout_s=600.0,
            )
            with open(result_path) as f:
                rec = json.load(f)
            out[str(ranks)] = rec
            log(f"swarm K={ranks}: {rec}")
        finally:
            shutil.rmtree(root, ignore_errors=True)
    # Flat-in-K: origin bytes at the largest K stay within 10% of one
    # snapshot, same as the smallest K (asserted per K above already).
    return out


def run_lazy_leg(origin_root: str, total_mb: float) -> dict:
    """Read ONE tower's subtree; origin bytes must track the tower's size,
    not the snapshot's."""
    path = os.path.join(origin_root, "snap")
    tm = telemetry.Telemetry()
    prev = telemetry.activate(tm)
    t0 = time.perf_counter()
    try:
        sub = Snapshot(path).read_object("0/app/model/tower_0")
    finally:
        telemetry.deactivate(tm, prev)
    wall = time.perf_counter() - t0
    tower_bytes = int(sub["w"].nbytes)
    m = tm.metrics.as_dict()
    origin = sum(
        v for k, v in m.items() if k.endswith(".read_bytes") and k.startswith("storage.")
    )
    total_bytes = int(total_mb * 1e6)
    rec = {
        "wall_s": round(wall, 4),
        "subtree_bytes": tower_bytes,
        "origin_bytes": int(origin),
        "snapshot_payload_bytes": total_bytes,
        "overhead_ratio": round(origin / max(tower_bytes, 1), 3),
    }
    # Subtree bytes + metadata/sidecar overhead — but never the other towers
    # (which would roughly quadruple the bytes here).
    assert origin < tower_bytes + total_bytes / 2, (
        f"lazy read fetched beyond its subtree: {rec}"
    )
    log(f"lazy subtree read: {rec}")
    return rec


def main() -> None:
    host_only = start_host_only_run("serving")
    total_mb = float(os.environ.get("SERVING_BENCH_MB", "64"))
    replicas = int(os.environ.get("SERVING_BENCH_REPLICAS", "8"))
    bcast_on = os.environ.get("SERVING_BENCH_BCAST", "1") not in ("0", "false")
    bcast_ranks = int(os.environ.get("SERVING_BENCH_BCAST_RANKS", "8"))
    swarm_on = os.environ.get("SERVING_BENCH_SWARM", "1") not in ("0", "false")
    swarm_ks = [
        int(k)
        for k in os.environ.get("SERVING_BENCH_SWARM_KS", "2,4,8").split(",")
        if k.strip()
    ]

    origin_root = tempfile.mkdtemp(prefix="tss_serving_")
    try:
        state = build_state(total_mb)
        t0 = time.perf_counter()
        Snapshot.take(os.path.join(origin_root, "snap"), {"app": state})
        log(f"took {total_mb:.0f} MB snapshot in {time.perf_counter() - t0:.2f}s")

        lazy = run_lazy_leg(origin_root, total_mb)
        cache = run_cache_leg(origin_root, total_mb, replicas)
        bcast_res = run_bcast_leg(total_mb, bcast_ranks) if bcast_on else {}
        swarm_res = run_swarm_leg(total_mb, swarm_ks) if swarm_on else {}

        print(
            json.dumps(
                {
                    "metric": "serving_cold_start_restore_p50",
                    "value": cache["on"]["restore_p50_s"],
                    "unit": "s",
                    "device": host_only,
                    "detail": {
                        "payload_mb": total_mb,
                        "replicas": replicas,
                        "cache": cache,
                        "broadcast": bcast_res,
                        "swarm": swarm_res,
                        "lazy_subtree": lazy,
                        "restore_stats": {
                            k: v
                            for k, v in snapshot_mod.LAST_RESTORE_STATS.items()
                            if k != "bcast"
                        },
                        "env": {"knobs": knobs.env_fingerprint()},
                    },
                }
            )
        )
    finally:
        shutil.rmtree(origin_root, ignore_errors=True)


if __name__ == "__main__":
    main()
