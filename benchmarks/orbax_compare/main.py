"""Head-to-head vs orbax.checkpoint — the incumbent JAX/TPU checkpointer.

The reference's flagship table compares against ``torch.save``
(``benchmarks/ddp/README.md``); the equivalent incumbent on TPU is orbax.
This harness saves/restores the SAME state with both libraries on the same
devices and reports, per leg:

- async save **stall** (time until the save call returns and training may
  resume) — the headline metric;
- total save wall time (stall + background drain / wait_until_finished);
- blocking restore time, with bit-exactness asserted for both.

Legs (``--leg`` — the differentiating axes):

- ``single``  — one-chip bf16 param pytree (the round-2 leg);
- ``sharded`` — params + adam moments sharded over a (dp, tp) device mesh;
- ``reshard`` — saved under one PartitionSpec layout, restored into a
  transposed layout (both libraries reshard on restore);
- ``incremental`` — LoRA-shaped state (frozen backbone + small adapter):
  this library's ``take(base=prev)`` hard-link dedup vs orbax's full save
  of the same changed state.

  python benchmarks/orbax_compare/main.py --gb 0.5
  python benchmarks/orbax_compare/main.py --leg sharded   # needs >= 2 chips

Runs on the accelerator and refuses the CPU backend: a time from XLA's host
code is not a device metric.
"""

import argparse
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.common import start_measured_run  # noqa: E402


def _bit_eq(a, b) -> bool:
    import numpy as np

    return (
        np.ascontiguousarray(np.asarray(a)).view(np.uint8).tobytes()
        == np.ascontiguousarray(np.asarray(b)).view(np.uint8).tobytes()
    )


def _report(leg: str, tss, orbax) -> None:
    print(f"--- leg: {leg}")
    print(f"{'':24s}{'stall_s':>10s}{'total_s':>10s}{'restore_s':>10s}")
    print(f"{'torchsnapshot_tpu':24s}{tss[0]:>10.3f}{tss[1]:>10.2f}{tss[2]:>10.2f}")
    print(f"{'orbax':24s}{orbax[0]:>10.3f}{orbax[1]:>10.2f}{orbax[2]:>10.2f}")
    print(
        f"stall speedup vs orbax: {orbax[0] / max(tss[0], 1e-9):.1f}x; "
        f"total {orbax[1] / max(tss[1], 1e-9):.2f}x; "
        f"restore {orbax[2] / max(tss[2], 1e-9):.2f}x"
    )


def _run_sharded_leg(root: str, gb: float, reshard: bool, reps: int = 2) -> None:
    """Params + adam moments on a (dp, tp) mesh; optionally restore into a
    TRANSPOSED layout (elasticity/resharding — the axis this library claims
    as its differentiation; orbax reshards via abstract targets)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import orbax.checkpoint as ocp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict

    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(ndev // 2, 2), ("dp", "tp"))
    d = 2048
    n_layers = max(1, round(gb * 1e9 / (4 * d * d * (2 + 4 + 4))))

    def build(seed: int):
        key = jax.random.PRNGKey(seed)
        spec = NamedSharding(mesh, P("dp", "tp"))
        state = {}
        for i in range(n_layers):
            key, k1 = jax.random.split(key)
            w = jax.device_put(
                jax.random.normal(k1, (d, 4 * d), jnp.bfloat16), spec
            )
            state[f"layer_{i}"] = {
                "w": w,
                "mu": jax.device_put(jnp.zeros((d, 4 * d), jnp.float32), spec),
                "nu": jax.device_put(jnp.ones((d, 4 * d), jnp.float32), spec),
            }
        jax.block_until_ready(state)
        return state

    def target_sharding():
        # Transposed axis order + different spec for the reshard leg.
        tmesh = Mesh(np.array(jax.devices()).reshape(2, ndev // 2), ("tp", "dp"))
        return NamedSharding(tmesh, P(None, "tp")) if reshard else NamedSharding(
            mesh, P("dp", "tp")
        )

    warm = build(100)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(warm))
    print(f"sharded state: {nbytes/1e9:.2f} GB over {ndev} devices", file=sys.stderr)

    def run_tss(state, tag):
        app = {"m": StateDict(**state)}
        t0 = time.perf_counter()
        pending = Snapshot.async_take(os.path.join(root, f"tss{tag}"), app)
        stall = time.perf_counter() - t0
        pending.wait()
        total = time.perf_counter() - t0
        tspec = target_sharding()
        tgt = StateDict(
            **{
                k: {
                    kk: jax.device_put(jnp.zeros_like(vv), tspec)
                    for kk, vv in v.items()
                }
                for k, v in state.items()
            }
        )
        t0 = time.perf_counter()
        Snapshot(os.path.join(root, f"tss{tag}")).restore({"m": tgt})
        restore_s = time.perf_counter() - t0
        for k, v in state.items():
            for kk in v:
                assert _bit_eq(tgt[k][kk], v[kk]), (k, kk)
        return stall, total, restore_s

    def run_orbax(state, tag):
        ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        path = os.path.join(root, f"orbax{tag}")
        t0 = time.perf_counter()
        ckptr.save(path, args=ocp.args.StandardSave(state))
        stall = time.perf_counter() - t0
        ckptr.wait_until_finished()
        total = time.perf_counter() - t0
        tspec = target_sharding()
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=tspec),
            state,
        )
        restorer = ocp.Checkpointer(ocp.StandardCheckpointHandler())
        t0 = time.perf_counter()
        restored = restorer.restore(path, args=ocp.args.StandardRestore(abstract))
        restore_s = time.perf_counter() - t0
        for k, v in state.items():
            for kk in v:
                assert _bit_eq(restored[k][kk], v[kk]), (k, kk)
        ckptr.close()
        restorer.close()
        return stall, total, restore_s

    # Warmups (jit of defensive copies / tensorstore spinup), then
    # INTERLEAVED reps on fresh states with MEDIAN reporting (+ per-rep
    # lines and restore spreads): this host's page-cache writeback makes
    # any single IO-heavy measurement noisy at the 2x level, and serial
    # one-shot runs hand one library the bad window (same posture as
    # bench.py's A/B medians).
    Snapshot.async_take(os.path.join(root, "tss_warm"), {"m": StateDict(**warm)}).wait()
    _w = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    _w.save(os.path.join(root, "orbax_warm"), args=ocp.args.StandardSave(warm))
    _w.wait_until_finished()
    _w.close()
    tss_runs = []
    orbax_runs = []
    for rep in range(reps):
        # Alternate which library goes first so page-cache/writeback drift
        # biases neither side.
        if rep % 2 == 0:
            tss_runs.append(run_tss(build(10 + rep), tag=rep))
            orbax_runs.append(run_orbax(build(20 + rep), tag=rep))
        else:
            orbax_runs.append(run_orbax(build(20 + rep), tag=rep))
            tss_runs.append(run_tss(build(10 + rep), tag=rep))
        print(
            f"rep {rep}: tss (stall/total/restore) "
            f"{tss_runs[-1][0]:.3f}/{tss_runs[-1][1]:.2f}/{tss_runs[-1][2]:.2f} s, "
            f"orbax {orbax_runs[-1][0]:.3f}/{orbax_runs[-1][1]:.2f}/{orbax_runs[-1][2]:.2f} s",
            file=sys.stderr,
        )
    import statistics

    med = lambda runs: tuple(  # noqa: E731
        statistics.median(r[i] for r in runs) for i in range(3)
    )
    _report("reshard" if reshard else "sharded", med(tss_runs), med(orbax_runs))
    for name, runs in (("tss", tss_runs), ("orbax", orbax_runs)):
        print(
            f"{name} restore spread: "
            f"{min(r[2] for r in runs):.2f}-{max(r[2] for r in runs):.2f} s "
            f"over {reps} interleaved reps",
        )


def _run_incremental_leg(root: str, gb: float) -> None:
    """LoRA-shaped state: frozen backbone + small adapter that changes per
    step. This library's second take dedups the backbone against the first
    via ``base=`` (hard links); orbax re-saves everything."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import orbax.checkpoint as ocp

    from torchsnapshot_tpu import Snapshot, StateDict

    n_frozen = max(1, round(gb * 1e9 / (16 * 1024 * 1024)))

    def build(seed: int, step: int):
        key = jax.random.PRNGKey(seed)
        state = {}
        for i in range(n_frozen):
            key, k1 = jax.random.split(key)
            state[f"frozen_{i}"] = jax.random.normal(k1, (2048, 2048), jnp.bfloat16)
        key, k2 = jax.random.split(jax.random.PRNGKey(1000 + step))
        state["adapter"] = jax.random.normal(k2, (2048, 128), jnp.float32)
        jax.block_until_ready(state)
        return state

    def run_tss():
        # Pin dedup digests ON for both takes: the auto default turns them
        # off on single-vCPU hosts, and a base without sha256 identities
        # silently degrades the second take to a full rewrite — this leg
        # would then compare orbax against nothing (ADVICE round 5).
        os.environ["TORCHSNAPSHOT_TPU_DEDUP_DIGESTS"] = "1"
        s0 = build(0, step=0)
        p0 = os.path.join(root, "tss_step0")
        t0 = time.perf_counter()
        Snapshot.take(p0, {"m": StateDict(**s0)})
        first_s = time.perf_counter() - t0
        s1 = dict(s0, adapter=build(0, step=1)["adapter"])
        p1 = os.path.join(root, "tss_step1")
        t0 = time.perf_counter()
        Snapshot.take(p1, {"m": StateDict(**s1)}, base=p0)
        incr_s = time.perf_counter() - t0
        # The claimed speedup is only real if the frozen objects were
        # hard-linked, not rewritten; same inode proves it.
        loc = Snapshot(p1).get_manifest()["0/m/frozen_0"].location
        assert os.path.samefile(
            os.path.join(p0, loc), os.path.join(p1, loc)
        ), "frozen object was rewritten, not hard-linked — dedup silently degraded"
        tgt = StateDict(**{k: jnp.zeros_like(v) for k, v in s1.items()})
        t0 = time.perf_counter()
        Snapshot(p1).restore({"m": tgt})
        restore_s = time.perf_counter() - t0
        for k, v in s1.items():
            assert _bit_eq(tgt[k], v), k
        return first_s, incr_s, restore_s

    def run_orbax():
        ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())
        s0 = build(2, step=0)
        t0 = time.perf_counter()
        ckptr.save(os.path.join(root, "orbax_step0"), args=ocp.args.StandardSave(s0))
        first_s = time.perf_counter() - t0
        s1 = dict(s0, adapter=build(2, step=1)["adapter"])
        t0 = time.perf_counter()
        ckptr.save(os.path.join(root, "orbax_step1"), args=ocp.args.StandardSave(s1))
        second_s = time.perf_counter() - t0
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), s1
        )
        t0 = time.perf_counter()
        restored = ckptr.restore(
            os.path.join(root, "orbax_step1"), args=ocp.args.StandardRestore(abstract)
        )
        restore_s = time.perf_counter() - t0
        for k, v in s1.items():
            assert _bit_eq(restored[k], v), k
        ckptr.close()
        return first_s, second_s, restore_s

    tss = run_tss()
    orbax = run_orbax()
    print("--- leg: incremental (LoRA-shaped; 2nd save after adapter-only change)")
    print(f"{'':24s}{'first_save_s':>14s}{'second_save_s':>14s}{'restore_s':>10s}")
    print(f"{'torchsnapshot_tpu':24s}{tss[0]:>14.2f}{tss[1]:>14.2f}{tss[2]:>10.2f}")
    print(f"{'orbax (full saves)':24s}{orbax[0]:>14.2f}{orbax[1]:>14.2f}{orbax[2]:>10.2f}")
    print(
        f"second-save speedup vs orbax: {orbax[1] / max(tss[1], 1e-9):.1f}x "
        f"(take(base=prev) rewrites only the changed adapter)"
    )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--gb", type=float, default=0.5)
    parser.add_argument(
        "--reps", type=int, default=2, help="interleaved reps per library (sharded legs)"
    )
    parser.add_argument(
        "--leg",
        choices=["single", "sharded", "reshard", "incremental", "all"],
        default="single",
    )
    args = parser.parse_args()

    start_measured_run()  # refuses the CPU backend
    import jax

    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    if args.leg in ("sharded", "reshard", "incremental", "all"):
        root = tempfile.mkdtemp()
        try:
            if args.leg in ("sharded", "all"):
                _run_sharded_leg(
                    os.path.join(root, "sh"), args.gb, reshard=False, reps=args.reps
                )
            if args.leg in ("reshard", "all"):
                _run_sharded_leg(
                    os.path.join(root, "rs"), args.gb, reshard=True, reps=args.reps
                )
            if args.leg in ("incremental", "all"):
                _run_incremental_leg(os.path.join(root, "inc"), args.gb)
        finally:
            shutil.rmtree(root, ignore_errors=True)
        if args.leg != "all":
            return
        # fall through to the single leg for --leg all

    d_model = 4096
    n_layers = max(1, round(args.gb * 1e9 / (4 * d_model * d_model * 2)))

    @jax.jit
    def mk(key):
        return jax.random.normal(key, (d_model, 4 * d_model), jnp.bfloat16)

    def build(seed: int):
        key = jax.random.PRNGKey(seed)
        params = {}
        for i in range(n_layers):
            key, sub = jax.random.split(key)
            params[f"layer_{i}"] = mk(sub)
        jax.block_until_ready(params)
        return params

    # FAIRNESS: each library gets its own freshly generated params for the
    # timed run, never host-transferred beforehand. jax Arrays cache their
    # host copy after the first device->host transfer, so re-saving the
    # same (or warmed-up) arrays lets a capture-to-host design report a
    # near-zero "stall" that no training run would ever see — every real
    # checkpoint saves arrays whose values changed since the last transfer.
    warm_params = build(100)
    params_tss = build(0)
    params_orbax = build(1)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params_tss))
    print(f"state: {nbytes/1e9:.2f} GB bf16", file=sys.stderr)

    root = tempfile.mkdtemp()

    def run_tss():
        # Warmup take (jit of defensive copies, pools) on separate data.
        Snapshot.async_take(
            os.path.join(root, "tss_warm"), {"m": StateDict(**warm_params)}
        ).wait()
        params = params_tss
        app = {"m": StateDict(**params)}
        t0 = time.perf_counter()
        pending = Snapshot.async_take(os.path.join(root, "tss"), app)
        stall = time.perf_counter() - t0
        pending.wait()
        total = time.perf_counter() - t0
        tgt = StateDict(**{k: jnp.zeros_like(v) for k, v in params.items()})
        t0 = time.perf_counter()
        Snapshot(os.path.join(root, "tss")).restore({"m": tgt})
        restore_s = time.perf_counter() - t0
        for k in params:
            assert (
                np.ascontiguousarray(np.asarray(tgt[k])).view(np.uint8).tobytes()
                == np.ascontiguousarray(np.asarray(params[k])).view(np.uint8).tobytes()
            ), f"torchsnapshot_tpu restore mismatch at {k}"
        return stall, total, restore_s

    def run_orbax():
        import orbax.checkpoint as ocp

        path = os.path.join(root, "orbax")
        ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        # Warmup on a throwaway path with separate data (see FAIRNESS note).
        warm = os.path.join(root, "orbax_warm")
        ckptr.save(warm, args=ocp.args.StandardSave(warm_params))
        ckptr.wait_until_finished()
        params = params_orbax
        t0 = time.perf_counter()
        ckptr.save(path, args=ocp.args.StandardSave(params))
        stall = time.perf_counter() - t0
        ckptr.wait_until_finished()
        total = time.perf_counter() - t0
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            params,
        )
        restorer = ocp.Checkpointer(ocp.StandardCheckpointHandler())
        t0 = time.perf_counter()
        restored = restorer.restore(path, args=ocp.args.StandardRestore(abstract))
        restore_s = time.perf_counter() - t0
        for k in params:
            assert (
                np.ascontiguousarray(np.asarray(restored[k])).view(np.uint8).tobytes()
                == np.ascontiguousarray(np.asarray(params[k])).view(np.uint8).tobytes()
            ), f"orbax restore mismatch at {k}"
        ckptr.close()
        restorer.close()
        return stall, total, restore_s

    tss = run_tss()
    orbax = run_orbax()
    shutil.rmtree(root, ignore_errors=True)
    _report("single", tss, orbax)


if __name__ == "__main__":
    main()
