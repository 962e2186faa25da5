"""Compile-only rehearsal: each configuration's train step and the batched
fork of its saved leaves, for a described ``v5e:2x2`` topology. No chip.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [--config NAME ...] [--micro-batch N ...]

Prints ``memory_analysis()`` of each program and the sum the micro-batch is
chosen by: step temporaries + resident state + one fork of the saved bytes,
against 90 % of the chip's ``bytes_limit``. Nothing runs, so nothing here is
a time or a rate.
"""

import argparse
import glob
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench import run, trainstate  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# memory_stats()["bytes_limit"] of one v5e chip (my chip run, PR 21).
BYTES_LIMIT = 16_909_000_000


def rehearse(cfg: dict, topo, micro_batch: int, saved: str) -> dict:
    cfg = dict(cfg, job=dict(cfg["job"], micro_batch=micro_batch))
    arch = run.find_architecture(os.path.dirname(HERE), cfg["model_type"])
    job = trainstate.Job(arch, cfg, list(topo.devices))
    state, tokens = job.abstract_args()
    step = job.train_step.lower(state, tokens).compile()
    mem = step.memory_analysis()
    chips = cfg["layout"]["chips"]
    saved_tree = state if saved == "state" else state["params"]
    saved_shardings = job.shardings if saved == "state" else job.shardings["params"]
    # The program the library forks a take's leaves with: one jitted copy
    # of every leaf, pinned to each leaf's sharding (io_preparer.py).
    leaves = jax.tree_util.tree_leaves(saved_tree)
    fork = (
        jax.jit(
            lambda xs: [jnp.copy(x) for x in xs],
            out_shardings=jax.tree_util.tree_leaves(saved_shardings),
        )
        .lower(leaves)
        .compile()
    )
    fork_mem = fork.memory_analysis()
    # Per device: memory_analysis() counts one device's share of a sharded program.
    resident = mem.argument_size_in_bytes
    total = resident + mem.temp_size_in_bytes + fork_mem.output_size_in_bytes
    return {
        "config": cfg["name"],
        "micro_batch": micro_batch,
        "saved": saved,
        "chips": chips,
        "state_bytes_all_chips": trainstate.tree_nbytes(job.abstract),
        "saved_bytes_all_chips": trainstate.tree_nbytes(saved_tree),
        "step_argument_bytes": mem.argument_size_in_bytes,
        "step_temp_bytes": mem.temp_size_in_bytes,
        "step_output_bytes": mem.output_size_in_bytes,
        "step_alias_bytes": mem.alias_size_in_bytes,
        "fork_output_bytes": fork_mem.output_size_in_bytes,
        "fork_temp_bytes": fork_mem.temp_size_in_bytes,
        "state_plus_step_plus_fork_bytes": total,
        "share_of_bytes_limit_pct": 100.0 * total / BYTES_LIMIT,
        "fits_under_90_pct": total < 0.9 * BYTES_LIMIT,
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", nargs="*", help="configuration names (default: all)")
    parser.add_argument("--micro-batch", nargs="*", type=int, default=[1, 2, 4])
    parser.add_argument(
        "--saved", choices=("params", "state"), nargs="*", default=["params", "state"],
        help="what the take forks: the params (save_weights) or the whole state (save_reshard)",
    )
    args = parser.parse_args()
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    paths = sorted(glob.glob(os.path.join(HERE, "configs", "*.json")))
    for path in paths:
        with open(path) as f:
            cfg = json.load(f)
        if args.config and cfg["name"] not in args.config:
            continue
        for micro_batch in args.micro_batch:
            for saved in args.saved:
                try:
                    print(json.dumps(rehearse(cfg, topo, micro_batch, saved)), flush=True)
                except Exception as e:  # noqa: BLE001 - the compiler's refusal is the result
                    print(json.dumps({
                        "config": cfg["name"], "micro_batch": micro_batch, "saved": saved,
                        "refused": str(e).splitlines()[0][:400],
                    }), flush=True)


if __name__ == "__main__":
    main()
