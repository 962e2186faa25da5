"""Budgeted single-array load (reference ``benchmarks/load_tensor/main.py``:
a 10 GB tensor read under a 100 MB RSS budget).

Proves ``read_object(memory_budget_bytes=...)`` caps host memory: the array
is fetched as budget-sized byte ranges written straight into the target.

  python benchmarks/load_tensor/main.py --gb 2 --budget-mb 100
"""

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import (  # noqa: E402
    maybe_init_distributed,
    start_host_only_run,
)


def main() -> None:
    start_host_only_run("load_tensor")
    maybe_init_distributed()
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=1.0)
    parser.add_argument("--budget-mb", type=int, default=100)
    args = parser.parse_args()

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils.rss_profiler import measure_rss_deltas

    n = int(args.gb * 1e9 / 4)
    arr = np.arange(n, dtype=np.float32)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ckpt")
        Snapshot.take(path, {"s": StateDict(big=arr)})

        target = np.zeros_like(arr)
        budget = args.budget_mb * 1024 * 1024
        deltas = []
        t0 = time.perf_counter()
        with measure_rss_deltas(rss_deltas=deltas):
            Snapshot(path).read_object(
                "0/s/big", obj_out=target, memory_budget_bytes=budget
            )
        elapsed = time.perf_counter() - t0
        peak_mb = max(deltas) / 1e6
        print(
            f"read {args.gb:.1f} GB with {args.budget_mb} MB budget: "
            f"{elapsed:.2f}s, peak RSS delta {peak_mb:.0f} MB"
        )
        assert np.array_equal(target, arr)
        print("bit-exact: True")


if __name__ == "__main__":
    main()
