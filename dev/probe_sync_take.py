"""Chip probe of PR 43 (``chiprun -- python dev/probe_sync_take.py``): what a
whole-state synchronous ``Snapshot.take`` is made of, from its own stats.

The state has the shape of ``pythia-6.9b-d6``'s big leaves (80 bf16 matrices,
9.727 GB: six layers of four matrices and two embeddings, three times over)
as plain arrays on the chip. Two takes: the first stages every leaf from the
device, the second finds the arrays' host copies cached and only writes.
Prints ``snapshot.LAST_SYNC_DRAIN_STATS`` of each. ``PERF.md`` section 5."""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from torchsnapshot_tpu import Snapshot, StateDict, native
from torchsnapshot_tpu import snapshot as snapshot_mod

LAYER = (("qkv", (4096, 12288)), ("dense", (4096, 4096)), ("h_to_4h", (4096, 16384)), ("4h_to_h", (16384, 4096)))


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"no accelerator: {jax.devices()}")
    print("engine", native.load_native() is not None, native.loaded_path(), flush=True)
    key = jax.random.PRNGKey(0)
    tree = {}
    for copy in ("params", "mu", "nu"):
        shapes = [(f"{copy}_{i}_{name}", shape) for i in range(6) for name, shape in LAYER]
        shapes += [(f"{copy}_{name}", (50432, 4096)) for name in ("embed_in", "embed_out")]
        for name, shape in shapes:
            key, sub = jax.random.split(key)
            tree[name] = jax.random.normal(sub, shape, jnp.bfloat16)
    jax.block_until_ready(tree)
    nbytes = sum(v.nbytes for v in tree.values())
    root = tempfile.mkdtemp(prefix="probe-sync-take-")
    try:
        for i in range(2):
            path = os.path.join(root, f"snap{i}")
            t0 = time.perf_counter()
            Snapshot.take(path, {"m": StateDict(**tree)})
            wall = time.perf_counter() - t0
            stats = {k: round(v, 4) for k, v in snapshot_mod.LAST_SYNC_DRAIN_STATS.items()}
            stats.update(take=i, take_wall_s=round(wall, 3), gb=round(nbytes / 1e9, 3), gbps=round(nbytes / 1e9 / wall, 3))
            print(json.dumps(stats), flush=True)
            shutil.rmtree(path)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
