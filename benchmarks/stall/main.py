"""Async-take stall decomposition + coordination-cost model at world > 1.

The headline metric of the framework is the training stall of
``Snapshot.async_take`` — planning plus mutable-host-state capture, NOT
checkpoint size (device bytes drain in the background). This harness measures
that stall *with the sharded path fully engaged*: N spawned processes form a
real multi-process jax CPU runtime (2 virtual devices each, the analogue of
the reference's multi-rank benches on gloo), a train-state-shaped pytree is
sharded over the global (dp, tp) mesh, and each rank reports its stall, its
per-phase decomposition, and — new in round 3 — its **store round-trip
counts** per take from ``parallel.store.get_op_counts``.

Why round-trips: on a host with fewer cores than ranks, wall time at world 8 confounds
coordination cost with CPU time-slicing; the round-trip count is the
confound-free quantity. Steady-state takes hit the cross-take plan cache
(``take_plan.py``) and issue a CONSTANT number of round-trips per rank
regardless of world size; first takes pay O(world) on rank 0's gathers. The
``--sweep`` mode runs worlds {1,2,4,8}, verifies the constant-steady-state
property, and projects the v5e-256 stall as
``roundtrips x per-op latency`` — a calculation, not an extrapolated wall
time.

  python benchmarks/stall/main.py --nproc 4 --mb-per-rank 64 --steps 3
  python benchmarks/stall/main.py --sweep
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import start_host_only_run  # noqa: E402


def _worker(
    rank: int,
    world_size: int,
    shared: str,
    mb_per_rank: int,
    steps: int,
    plan_cache: bool,
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import snapshot as snapshot_mod
    from torchsnapshot_tpu.parallel import store as store_mod
    from torchsnapshot_tpu.utils import knobs

    devices = np.array(jax.devices()).reshape(world_size, -1)
    mesh = Mesh(devices, ("dp", "tp"))
    n_dev = devices.size

    # Train-state shape: params sharded over tp, adam-style moments likewise,
    # plus a replicated scalar step and per-rank host progress.
    total_elems = mb_per_rank * world_size * 1024 * 1024 // 4 // 3
    dim = int(np.sqrt(total_elems / 4))
    dim = max(n_dev, dim - dim % n_dev)
    # Same key on every process: device_put of a multi-process global array
    # requires identical host values everywhere.
    key = jax.random.PRNGKey(0)

    def mk(spec):
        return jax.device_put(
            jax.random.normal(key, (dim, 4 * dim), dtype=jnp.float32),
            NamedSharding(mesh, spec),
        )

    params = mk(P("dp", "tp"))
    mu = mk(P("dp", "tp"))
    nu = mk(P("dp", "tp"))
    app = {
        "train": StateDict(params=params, mu=mu, nu=nu, step=0),
        "progress": StateDict(rank=rank),
    }

    stalls = []
    phase_sums: dict = {}
    roundtrips = []  # per-take store ops issued by THIS rank during the stall
    ctx = knobs.override_plan_cache(plan_cache)
    with ctx:
        for step in range(steps):
            app["train"]["step"] = step
            path = os.path.join(shared, f"ckpt_{step}")
            store_mod.reset_op_counts()
            t0 = time.perf_counter()
            pending = Snapshot.async_take(path, app, replicated=["train/step"])
            stall = time.perf_counter() - t0
            # Main thread only: the background commit thread's barrier ops
            # would otherwise race into the counted window run-to-run.
            ops = store_mod.get_op_counts(current_thread_only=True)
            pending.wait()
            stalls.append(stall)
            roundtrips.append(sum(ops.values()))
            for k, v in getattr(snapshot_mod, "LAST_TAKE_PHASES", {}).items():
                phase_sums.setdefault(k, []).append(v)

    # Pod-restart path: restore coordination cost. Restore runs one key
    # gather+broadcast plus a single post-load barrier — constant store
    # round-trips per rank (the round-3 design paid a key all_gather plus a
    # barrier PER KEY: O(keys x world) reads per rank, all added downtime
    # while a preempted pod restarts).
    store_mod.reset_op_counts()
    t0 = time.perf_counter()
    Snapshot(os.path.join(shared, f"ckpt_{steps - 1}")).restore(app)
    restore_wall = time.perf_counter() - t0
    # Exclude "delete": the coordinator's lazy GC of keys posted by the
    # preceding take loop fires inside this window and would report
    # take-dependent backlog as restore coordination cost.
    restore_ops = sum(
        v
        for k, v in store_mod.get_op_counts(current_thread_only=True).items()
        if k != "delete"
    )

    # First take pays one-time costs (jit warmup, pool spinup): report both.
    result = {
        "rank": rank,
        "world_size": world_size,
        "devices": n_dev,
        "plan_cache": plan_cache,
        "bytes_per_rank": int(3 * dim * 4 * dim * 4 / world_size),
        "stall_first_s": round(stalls[0], 4),
        "stall_steady_s": round(min(stalls[1:]) if len(stalls) > 1 else stalls[0], 4),
        "store_roundtrips_first": roundtrips[0],
        "store_roundtrips_steady": min(roundtrips[1:]) if len(roundtrips) > 1 else roundtrips[0],
        "restore_roundtrips": restore_ops,
        "restore_wall_s": round(restore_wall, 4),
        "phases_last_s": {k: round(v[-1], 4) for k, v in phase_sums.items()},
    }
    with open(os.path.join(shared, f"result_{rank}.json"), "w") as f:
        json.dump(result, f)


def _run_world(nproc: int, mb_per_rank: int, steps: int, plan_cache: bool):
    from torchsnapshot_tpu.test_utils import run_with_processes

    with tempfile.TemporaryDirectory() as shared:
        run_with_processes(
            _worker,
            nproc=nproc,
            init_jax_distributed=True,
            args=(shared, mb_per_rank, steps, plan_cache),
            timeout_s=900,
        )
        results = []
        for rank in range(nproc):
            with open(os.path.join(shared, f"result_{rank}.json")) as f:
                results.append(json.load(f))
        return results


def _sweep(mb_per_rank: int, steps: int) -> None:
    """Worlds {1,2,4,8} x {cache on, cache off}: the coordination model.

    Prints one summary JSON with per-world (stall, round-trips) and a
    projected v5e-256 (64-process) steady-state stall computed from the
    round-trip count times the measured per-op store latency.
    """
    from torchsnapshot_tpu.parallel.store import LocalStore

    # Per-op latency probe: LocalStore is in-process (lower bound); the
    # interesting number for the projection is a typical coordination-service
    # RTT on a pod, which the user can override.
    probe = LocalStore()
    t0 = time.perf_counter()
    n_probe = 1000
    for i in range(n_probe):
        probe.set(f"k{i}", b"x")
        probe.get(f"k{i}")
    local_op_latency_s = (time.perf_counter() - t0) / (2 * n_probe)
    # Representative single-digit-ms gRPC RTT for the jax coordination
    # service across a pod's DCN (what a real v5e-256 pays per store op).
    pod_op_latency_s = float(os.environ.get("STALL_POD_OP_LATENCY_S", "0.002"))

    rows = []
    _last_results = {}
    for nproc in (1, 2, 4, 8):
        for plan_cache in (True, False):
            results = _run_world(nproc, mb_per_rank, steps, plan_cache)
            if plan_cache:
                _last_results[nproc] = results
            worst = max(r["stall_steady_s"] for r in results)
            rts = max(r["store_roundtrips_steady"] for r in results)
            rts_first = max(r["store_roundtrips_first"] for r in results)
            rows.append(
                {
                    "world": nproc,
                    "plan_cache": plan_cache,
                    "stall_steady_max_s": worst,
                    "store_roundtrips_steady_max": rts,
                    "store_roundtrips_first_max": rts_first,
                    "restore_roundtrips_max": max(
                        r["restore_roundtrips"] for r in results
                    ),
                }
            )
            print(json.dumps(rows[-1]), flush=True)

    cached = {r["world"]: r for r in rows if r["plan_cache"]}
    uncached = {r["world"]: r for r in rows if not r["plan_cache"]}
    worlds = sorted(cached)
    rt_cached = [cached[w]["store_roundtrips_steady_max"] for w in worlds]
    rt_uncached = [uncached[w]["store_roundtrips_steady_max"] for w in worlds]

    def fit(ys):
        # Least-squares rt = a*world + b. Non-zero ranks are constant under
        # the cache; the max (rank 0, which reads every gather key) is
        # linear in both modes — with a far smaller slope when cached
        # (2 gathers/take vs gathers+all_gathers+per-key barriers).
        n = len(worlds)
        sx = sum(worlds)
        sy = sum(ys)
        sxx = sum(w * w for w in worlds)
        sxy = sum(w * y for w, y in zip(worlds, ys))
        a = (n * sxy - sx * sy) / max(1, (n * sxx - sx * sx))
        return a, (sy - a * sx) / n

    a_c, b_c = fit(rt_cached)
    a_u, b_u = fit(rt_uncached)
    rt_restore = [cached[w]["restore_roundtrips_max"] for w in worlds]
    a_r, b_r = fit(rt_restore)
    nonzero_rank_cached = min(
        min(r["store_roundtrips_steady"] for r in _last_results[w])
        for w in worlds
        if w > 1
    ) if any(w > 1 for w in worlds) else 0
    proj = {
        "local_store_op_latency_s": round(local_op_latency_s, 8),
        "pod_op_latency_s": pod_op_latency_s,
        "worlds": worlds,
        "roundtrips_steady_cached": rt_cached,
        "roundtrips_steady_uncached": rt_uncached,
        "nonzero_rank_roundtrips_steady_cached": nonzero_rank_cached,
        "fit_rt_per_world": {"cached": round(a_c, 2), "uncached": round(a_u, 2)},
        "projected_world64_stall_cached_s": round(
            (a_c * 64 + b_c) * pod_op_latency_s, 4
        ),
        "projected_world64_stall_uncached_s": round(
            (a_u * 64 + b_u) * pod_op_latency_s, 4
        ),
        "projected_world256_stall_cached_s": round(
            (a_c * 256 + b_c) * pod_op_latency_s, 4
        ),
        "projected_world256_stall_uncached_s": round(
            (a_u * 256 + b_u) * pod_op_latency_s, 4
        ),
        # Pod-restart coordination: restore's store round-trips x RTT —
        # what restore ADDS to restart downtime beyond the storage reads.
        "roundtrips_restore": rt_restore,
        "projected_world256_restore_coordination_s": round(
            (a_r * 256 + b_r) * pod_op_latency_s, 4
        ),
        # The 2 ms/op RTT is an assumption, not a measurement; carry the
        # projection across plausible control-plane latencies so the <5 s
        # claim's sensitivity is explicit.
        "rtt_sensitivity": {
            f"{rtt * 1000:g}ms": {
                "world256_stall_cached_s": round((a_c * 256 + b_c) * rtt, 4),
                "world256_stall_uncached_s": round((a_u * 256 + b_u) * rtt, 4),
                "world256_restore_coordination_s": round(
                    (a_r * 256 + b_r) * rtt, 4
                ),
            }
            for rtt in (0.002, 0.005, 0.010)
        },
    }
    print(json.dumps({"coordination_model": proj}, indent=2))


def main() -> None:
    start_host_only_run("stall")
    parser = argparse.ArgumentParser()
    parser.add_argument("--nproc", type=int, default=4)
    parser.add_argument("--mb-per-rank", type=int, default=64)
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument(
        "--no-plan-cache", action="store_true", help="A/B: disable the plan cache"
    )
    parser.add_argument(
        "--sweep",
        action="store_true",
        help="worlds {1,2,4,8} x cache {on,off} + v5e-256 projection",
    )
    args = parser.parse_args()

    if args.sweep:
        _sweep(args.mb_per_rank, args.steps)
        return

    for r in _run_world(
        args.nproc, args.mb_per_rank, args.steps, not args.no_plan_cache
    ):
        print(json.dumps(r))


if __name__ == "__main__":
    main()
