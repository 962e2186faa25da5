"""Full train-state (params + fp32 optimizer moments) checkpoint benchmark
(reference ``benchmarks/deepspeed_opt/main.py:27-31``: OPT-30B-shaped model,
ZeRO-3 partitioned optimizer state via the DeepSpeed adapter).

TPU equivalent: an adamw train state — bf16 params plus fp32 first/second
moments (3x the param bytes, the same ratio ZeRO-3 shards) — FSDP-sharded
over the mesh and checkpointed through :class:`PyTreeStateful`, the analogue
of the reference's engine adapter (``tricks/deepspeed.py:30-103``).

  python benchmarks/optimizer/main.py --layers 4 --d-model 1024
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
    import optax
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
    if n % tp != 0:
        raise SystemExit(f"--tp {tp} must divide the device count ({n})")
    mesh = Mesh(np.array(jax.devices()).reshape(n // tp, tp), ("dp", "tp"))
    cfg = TransformerConfig(
        vocab_size=32000,
        d_model=args.d_model,
        n_heads=max(4, args.d_model // 128),
        n_layers=args.layers,
        d_ff=4 * args.d_model,
    )
    _, params = init_params(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    params = shard_params(params, mesh, fsdp=True)

    # fp32 adamw moments inherit each param's sharding (computation follows
    # data), i.e. the optimizer state is FSDP-partitioned like ZeRO-3's.
    tx = optax.adamw(1e-3)
    opt_state = jax.jit(tx.init)(
        jax.tree.map(lambda x: x.astype(jnp.float32), params)
    )
    state = {"params": params, "opt_state": opt_state, "step": 0}
    jax.block_until_ready((params, opt_state))

    nbytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(state) if hasattr(x, "nbytes")
    )
    gb = nbytes / 1e9
    print(f"{gb:.2f} GB train state (params + fp32 moments) on mesh {dict(mesh.shape)}")

    holder = Box(state)
    app_state = {"train_state": PyTreeStateful(holder)}
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

        zeroed = Box(
            jax.tree.map(
                lambda x: jnp.zeros_like(x) if hasattr(x, "dtype") else x, state
            )
        )
        t0 = time.perf_counter()
        Snapshot(path).restore({"train_state": PyTreeStateful(zeroed)})
        load_s = time.perf_counter() - t0
        print(f"restore: {load_s:.2f}s ({gb / load_s:.2f} GB/s)")

        ok = all(
            np.array_equal(
                np.ascontiguousarray(np.asarray(a)).reshape(-1).view(np.uint8),
                np.ascontiguousarray(np.asarray(b)).reshape(-1).view(np.uint8),
            )
            for a, b in zip(
                (x for x in jax.tree_util.tree_leaves(state) if hasattr(x, "dtype")),
                (
                    x
                    for x in jax.tree_util.tree_leaves(zeroed.value)
                    if hasattr(x, "dtype")
                ),
            )
        )
        print(f"bit-exact: {ok}")


if __name__ == "__main__":
    main()
