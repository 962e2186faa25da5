"""Sharded (FSDP-style) transformer save/load benchmark
(reference ``benchmarks/fsdp/main.py:35-72``: 1.9 B-param transformer,
flat params as ShardedTensor).

TPU equivalent: the flagship transformer's params FSDP+TP-sharded over a
(dp, tp) mesh; measures sync take, async stall, and restore.

  python benchmarks/fsdp/main.py --layers 8 --d-model 2048
"""

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import start_measured_run  # noqa: E402


def main() -> None:
    start_measured_run()  # refuses the CPU backend
    parser = argparse.ArgumentParser()
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--d-model", type=int, default=1024)
    parser.add_argument("--tp", type=int, default=0, help="0 = auto")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        shard_params,
    )
    from torchsnapshot_tpu.tricks.train_state import Box, PyTreeStateful

    n = len(jax.devices())
    tp = args.tp or (2 if n % 2 == 0 else 1)
    mesh = Mesh(np.array(jax.devices()).reshape(n // tp, tp), ("dp", "tp"))
    cfg = TransformerConfig(
        vocab_size=32000,
        d_model=args.d_model,
        n_heads=max(4, args.d_model // 128),
        n_layers=args.layers,
        d_ff=4 * args.d_model,
    )
    _, params = init_params(cfg)
    params = shard_params(params, mesh, fsdp=True)
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    gb = nbytes / 1e9
    print(f"{gb:.2f} GB params on mesh {dict(mesh.shape)}")

    holder = Box(params)
    app_state = {"params": PyTreeStateful(holder)}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ckpt")
        t0 = time.perf_counter()
        Snapshot.take(path, app_state)
        sync_s = time.perf_counter() - t0
        print(f"sync take: {sync_s:.2f}s ({gb / sync_s:.2f} GB/s)")

        t0 = time.perf_counter()
        pending = Snapshot.async_take(os.path.join(tmp, "ckpt2"), app_state)
        stall_s = time.perf_counter() - t0
        pending.wait()
        print(f"async stall: {stall_s:.2f}s")

        restored = Box(jax.tree.map(jnp.zeros_like, params))
        t0 = time.perf_counter()
        Snapshot(path).restore({"params": PyTreeStateful(restored)})
        load_s = time.perf_counter() - t0
        print(f"restore: {load_s:.2f}s ({gb / load_s:.2f} GB/s)")
        ok = all(
            np.array_equal(
                np.ascontiguousarray(np.asarray(a)).view(np.uint8),
                np.ascontiguousarray(np.asarray(b)).view(np.uint8),
            )
            for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(restored.value),
            )
        )
        print(f"bit-exact: {ok}")


if __name__ == "__main__":
    main()
