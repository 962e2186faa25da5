"""Row-sharded embedding save + reshard benchmark
(reference ``benchmarks/torchrec/main.py:54-113``: DLRM row-wise sharded
embedding bags, sync vs async save, 4->2/2->4 rank reshard).

TPU equivalent: a large embedding table row-sharded over the device mesh,
saved, then restored under a different mesh factorization.

  python benchmarks/embedding/main.py --rows 1000000 --dim 128
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
    parser.add_argument("--rows", type=int, default=1_000_000)
    parser.add_argument("--dim", type=int, default=128)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict

    n = len(jax.devices())
    rows = args.rows - args.rows % n
    mesh_a = Mesh(np.array(jax.devices()), ("shard",))

    table = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (rows, args.dim), jnp.float32),
        NamedSharding(mesh_a, P("shard")),
    )
    jax.block_until_ready(table)
    gb = table.nbytes / 1e9

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ckpt")
        t0 = time.perf_counter()
        Snapshot.take(path, {"emb": StateDict(table=table)})
        sync_s = time.perf_counter() - t0
        print(f"row-sharded save {gb:.2f} GB over {n} devices: {sync_s:.2f}s "
              f"({gb / sync_s:.2f} GB/s)")

        t0 = time.perf_counter()
        pending = Snapshot.async_take(os.path.join(tmp, "ckpt2"), {"emb": StateDict(table=table)})
        stall = time.perf_counter() - t0
        pending.wait()
        print(f"async stall: {stall:.2f}s")

        # Reshard: restore under a different mesh factorization (the 4->2 /
        # 2->4 reshard of the reference, expressed as mesh reshape).
        if n % 2 == 0:
            mesh_b = Mesh(np.array(jax.devices()).reshape(2, n // 2), ("a", "b"))
            tgt = StateDict(
                table=jax.device_put(
                    jnp.zeros((rows, args.dim), jnp.float32),
                    NamedSharding(mesh_b, P(("a", "b"))),
                )
            )
            t0 = time.perf_counter()
            Snapshot(path).restore({"emb": tgt})
            print(f"reshard restore: {time.perf_counter() - t0:.2f}s")
            ok = np.array_equal(np.asarray(tgt["table"]), np.asarray(table))
            print(f"bit-exact: {ok}")


if __name__ == "__main__":
    main()
