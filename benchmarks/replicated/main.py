"""Replicated-model save benchmark (reference ``benchmarks/ddp/main.py``).

The reference's headline: a 20 GB DDP (fully replicated) model saved by N
ranks in parallel vs one ``torch.save``. TPU equivalent: a replicated param
set saved by N processes, write load partitioned across them; baseline is a
single-process pickle of the same bytes.

  python benchmarks/replicated/main.py --gb 2 --nproc 4
"""

import argparse
import os
import pickle
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import (  # noqa: E402
    maybe_init_distributed,
    start_host_only_run,
)


def _make_state(total_gb: float):
    n = max(1, int(total_gb * 1e9 / (64 * 1024 * 1024)))
    rng = np.random.default_rng(0)
    return {
        f"p{i}": rng.standard_normal(16 * 1024 * 1024).astype(np.float32)
        for i in range(n)
    }


def _worker(rank: int, world_size: int, shared: str, total_gb: float) -> None:
    from torchsnapshot_tpu import Snapshot, StateDict

    state = StateDict(**_make_state(total_gb))
    t0 = time.perf_counter()
    Snapshot.take(os.path.join(shared, "ckpt"), {"m": state}, replicated=["m/*"])
    if rank == 0:
        elapsed = time.perf_counter() - t0
        print(
            f"[torchsnapshot_tpu] {total_gb:.1f} GB replicated, "
            f"{world_size} procs: {elapsed:.2f}s ({total_gb / elapsed:.2f} GB/s)"
        )


def main() -> None:
    start_host_only_run("replicated")
    maybe_init_distributed()
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=1.0)
    parser.add_argument("--nproc", type=int, default=4)
    args = parser.parse_args()

    state = _make_state(args.gb)
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        with open(os.path.join(tmp, "baseline.pkl"), "wb") as f:
            pickle.dump(state, f, protocol=pickle.HIGHEST_PROTOCOL)
        base = time.perf_counter() - t0
        print(f"[pickle baseline] {args.gb:.1f} GB: {base:.2f}s "
              f"({args.gb / base:.2f} GB/s)")

    from torchsnapshot_tpu.test_utils import run_with_processes

    with tempfile.TemporaryDirectory() as shared:
        run_with_processes(
            _worker, nproc=args.nproc, args=(shared, args.gb), timeout_s=600
        )


if __name__ == "__main__":
    main()
