"""Restore-overlap A/B on real hardware.

The overlapped restore (``TORCHSNAPSHOT_TPU_RESTORE_OVERLAP``) finalizes
each entry's host→device transfer inline as its last storage read consumes,
instead of phase-splitting all H2D after the read pipeline. The suite
demonstrates the overlap only on a synthetic latency-bound storage fake
(``tests/test_restore_overlap.py``); this harness measures both modes on
real hardware, wall + peak RSS, interleaved with alternating order. The
auto gate is platform-aware: accelerator-backend H2D dispatch is a PJRT
hand-off, so overlap needs no spare core — only the CPU backend on one
core keeps the phase split. Not measured on the current chip.

  python benchmarks/restore_overlap/main.py --gb 0.5 --reps 3

Reports one row per mode: median wall, spread, median peak RSS delta.
"""

import argparse
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import start_measured_run  # noqa: E402


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=0.5)
    parser.add_argument("--reps", type=int, default=3)
    args = parser.parse_args()

    start_measured_run()  # refuses the CPU backend
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs
    from torchsnapshot_tpu.utils.rss_profiler import measure_rss_deltas

    n_arrays = max(2, round(args.gb * 1e9 / (32 * 1024 * 1024)))
    ks = jax.random.split(jax.random.PRNGKey(0), n_arrays)
    state = {
        f"a{i}": jax.random.normal(ks[i], (2048, 8192), jnp.bfloat16)
        for i in range(n_arrays)
    }
    jax.block_until_ready(state)
    gb = sum(x.nbytes for x in state.values()) / 1e9
    print(f"state: {gb:.2f} GB in {n_arrays} arrays", file=sys.stderr)

    root = tempfile.mkdtemp(prefix="tss_overlap_")
    path = os.path.join(root, "ckpt")
    Snapshot.take(path, {"m": StateDict(**state)})

    def run_restore(overlap: bool):
        tgt = StateDict(
            **{k: jnp.zeros_like(v) for k, v in state.items()}
        )
        jax.block_until_ready(dict(tgt))
        deltas = [0]
        with knobs.override_restore_overlap(overlap):
            t0 = time.perf_counter()
            with measure_rss_deltas(rss_deltas=deltas):
                Snapshot(path).restore({"m": tgt})
            wall = time.perf_counter() - t0
        a0 = tgt["a0"]
        assert np.array_equal(
            np.asarray(a0).view(np.uint8), np.asarray(state["a0"]).view(np.uint8)
        )
        return wall, max(deltas)

    # Warm both paths once (jit/plan caches, page cache for the reads).
    run_restore(False)
    run_restore(True)

    results = {False: [], True: []}
    for rep in range(args.reps):
        order = [False, True] if rep % 2 == 0 else [True, False]
        for overlap in order:
            wall, rss = run_restore(overlap)
            results[overlap].append((wall, rss))
            print(
                f"rep {rep} overlap={'on' if overlap else 'off'}: "
                f"{wall:.2f}s, peak RSS delta {rss/1e9:.2f} GB",
                file=sys.stderr,
            )

    print(f"--- restore of {gb:.2f} GB, {args.reps} interleaved reps/mode")
    print(f"{'mode':>14} {'median_s':>9} {'spread_s':>15} {'peak_rss_gb':>12}")
    for overlap in (False, True):
        walls = [w for w, _ in results[overlap]]
        rsss = [r for _, r in results[overlap]]
        print(
            f"{('overlap' if overlap else 'phase-split'):>14} "
            f"{statistics.median(walls):>9.2f} "
            f"{min(walls):>7.2f}-{max(walls):<7.2f} "
            f"{statistics.median(rsss)/1e9:>12.2f}"
        )


if __name__ == "__main__":
    main()
