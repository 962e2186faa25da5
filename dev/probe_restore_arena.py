"""Chip probe of PR 41 (``chiprun -- python dev/probe_restore_arena.py``):
one train state shaped as ``pythia-6.9b-d6``'s (9.73 GB of 2-byte leaves)
saved once, then restored into zero device targets under each capacity of
the restore's arena of host pages (``torchsnapshot_tpu/host_arena.py``; 0 is
no arena: every target fresh, as before PR 41). Every leaf is checked after
every restore by an integer checksum taken on the device. ``--tiny`` is a
dry run for the CPU. ``PERF.md`` section 6, ``CHANGES.md`` PR 41."""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MIB = 1 << 20


def leaf_shapes(tiny: bool):
    hidden, inter, vocab, layers = (64, 256, 788, 2) if tiny else (4096, 16384, 50432, 6)
    one = [(vocab, hidden), (vocab, hidden), (hidden,), (hidden,)]
    for _ in range(layers):
        one += [
            (hidden, 3 * hidden), (3 * hidden,), (hidden, hidden), (hidden,),
            (hidden, inter), (inter,), (inter, hidden), (hidden,),
            (hidden,), (hidden,), (hidden,), (hidden,),
        ]
    return one * 3  # params and both moments


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--capacities-mib", default="0,512,1024,1536,2048,3072,1536,0")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict, host_arena
    from torchsnapshot_tpu import snapshot as snapshot_mod

    shapes = leaf_shapes(args.tiny)
    make = jax.jit(
        lambda key, shape: jax.random.bits(key, shape, dtype=jnp.uint16).view(jnp.int16),
        static_argnums=1,
    )
    checksum = jax.jit(lambda x: jnp.sum(x.astype(jnp.int32) * (jnp.arange(x.size, dtype=jnp.int32).reshape(x.shape) | 1)))
    keys = jax.random.split(jax.random.PRNGKey(41), len(shapes))
    state = {f"l{i:03d}": make(k, s) for i, (k, s) in enumerate(zip(keys, shapes))}
    want = {k: int(checksum(v)) for k, v in state.items()}
    nbytes = sum(v.nbytes for v in state.values())
    root = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    path = os.path.join(root, "snap")
    t0 = time.perf_counter()
    Snapshot.take(path, {"train": StateDict(**state)})
    print(f"[take] {nbytes / 1e9:.3f} GB in {time.perf_counter() - t0:.2f} s", flush=True)
    for v in state.values():
        v.delete()
    del state

    out = []
    keep = (
        "recycled_bytes", "fresh_target_bytes", "target_wait_s", "landed_bytes", "bytes_read",
        "wall_s", "fetch_busy_s", "fetch_sum_s", "mount_sum_s", "mount_busy_s", "pread_sum_s",
        "pread_busy_s", "reader_copy_sum_s", "place_busy_s", "place_retry_s", "idle_s", "plan_s",
    )
    for mib in [int(c) for c in args.capacities_mib.split(",")]:
        host_arena.CAPACITY_BYTES = mib * MIB
        targets = jax.block_until_ready(
            {f"l{i:03d}": jnp.zeros(s, jnp.int16) for i, s in enumerate(shapes)}
        )
        sd = StateDict(**targets)
        del targets
        t0 = time.perf_counter()
        Snapshot(path).restore({"train": sd})
        jax.block_until_ready(dict(sd))
        wall = time.perf_counter() - t0
        stats = {k: snapshot_mod.LAST_RESTORE_STATS.get(k) for k in keep}
        bad = [k for k in want if int(checksum(sd[k])) != want[k]]
        rec = {
            "capacity_mib": mib,
            "restore_gbps": round(nbytes / wall / 1e9, 4),
            "call_to_ready_s": round(wall, 4),
            "leaves_differing": len(bad),
            "recycled_pct": round(100 * stats["recycled_bytes"] / stats["bytes_read"], 2),
            "target_wait_pct": round(100 * stats["target_wait_s"] / stats["wall_s"], 2),
            "reader_copy_pct": round(100 * stats["reader_copy_sum_s"] / stats["mount_sum_s"], 2)
            if stats["mount_sum_s"] else None,
            "pread_depth": round(stats["pread_sum_s"] / stats["pread_busy_s"], 3)
            if stats["pread_busy_s"] else None,
            "place_busy_pct": round(100 * stats["place_busy_s"] / stats["wall_s"], 2),
            "stats": stats,
        }
        out.append(rec)
        print(json.dumps({k: v for k, v in rec.items() if k != "stats"}), flush=True)
        for v in sd.values():
            v.delete()
        del sd
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_restore_arena.json", "w") as f:
        json.dump(out, f, indent=1)
    shutil.rmtree(root, ignore_errors=True)
    return 1 if any(r["leaves_differing"] for r in out) else 0


if __name__ == "__main__":
    sys.exit(main())
