"""Incremental-snapshot benchmark: the LoRA/partial-finetune checkpoint.

No reference analogue (the reference rewrites every byte each checkpoint).
State shape: a large frozen backbone + small trainable adapters. Each
checkpoint interval, only the adapters changed; ``take(base=prev)``
hard-links the frozen objects and writes just the changed bytes.

  python benchmarks/incremental/main.py --frozen-gb 1 --adapter-mb 16
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
    # Pin dedup digests ON: the auto default disables them on single-vCPU
    # hosts, and a base taken without sha256 identities silently degrades
    # every incremental take to a full rewrite — this benchmark would then
    # "pass" while measuring nothing (ADVICE round 5).
    os.environ["TORCHSNAPSHOT_TPU_DEDUP_DIGESTS"] = "1"
    start_host_only_run("incremental")
    maybe_init_distributed()
    parser = argparse.ArgumentParser()
    parser.add_argument("--frozen-gb", type=float, default=1.0)
    parser.add_argument("--adapter-mb", type=float, default=16.0)
    args = parser.parse_args()

    from torchsnapshot_tpu import Snapshot, StateDict

    rng = np.random.default_rng(0)
    n_frozen = max(1, int(args.frozen_gb * 1e9 / (64 * 1024 * 1024)))
    frozen = {
        f"backbone{i}": rng.standard_normal(16 * 1024 * 1024).astype(np.float32)
        for i in range(n_frozen)
    }
    n_adapt = max(1, int(args.adapter_mb * 1e6 / (4 * 1024 * 1024)))
    adapters = {
        f"lora{i}": rng.standard_normal(1024 * 1024).astype(np.float32)
        for i in range(n_adapt)
    }
    total_gb = sum(a.nbytes for a in {**frozen, **adapters}.values()) / 1e9
    root = tempfile.mkdtemp(prefix="tss_inc_")

    def app():
        return {"m": StateDict(**frozen, **adapters)}

    t0 = time.perf_counter()
    Snapshot.take(os.path.join(root, "step0"), app())
    full_s = time.perf_counter() - t0
    print(f"full take: {total_gb:.2f} GB in {full_s:.2f}s")

    # "Train": only the adapters change.
    for k in adapters:
        adapters[k] = adapters[k] + 1.0

    t0 = time.perf_counter()
    Snapshot.take(
        os.path.join(root, "step1"), app(), base=os.path.join(root, "step0")
    )
    inc_s = time.perf_counter() - t0
    changed_gb = sum(a.nbytes for a in adapters.values()) / 1e9
    print(
        f"incremental take: {total_gb:.2f} GB state, {changed_gb:.3f} GB "
        f"changed, {inc_s:.2f}s ({full_s / inc_s:.1f}x faster than full)"
    )

    # Hard-linking must actually have happened: a silent fallback to full
    # rewrites (digests missing, cross-device link failure) would otherwise
    # report a bogus "speedup". Same inode == same bytes on disk.
    loc = Snapshot(os.path.join(root, "step1")).get_manifest()[
        "0/m/backbone0"
    ].location
    assert os.path.samefile(
        os.path.join(root, "step0", loc), os.path.join(root, "step1", loc)
    ), "backbone object was rewritten, not hard-linked — dedup silently degraded"

    out = StateDict()
    Snapshot(os.path.join(root, "step1")).restore({"m": out})
    ok = np.array_equal(out["lora0"], adapters["lora0"]) and np.array_equal(
        out["backbone0"], frozen["backbone0"]
    )
    print(f"restore bit-exact: {ok}; verify: {Snapshot(os.path.join(root, 'step1')).verify() == {}}")

    import shutil

    shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
