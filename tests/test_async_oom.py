"""HBM-pressure degradation of async_take's defensive device fork.

The reference's async snapshot always works because it captures through host
RAM (``io_preparers/tensor.py:254-278``); the TPU design's on-device fork is
faster but allocates a full state copy in HBM. These tests force allocation
failure (via the simulated-HBM-limit knob and via injected
RESOURCE_EXHAUSTED errors) and assert the take degrades — device-forking
what fits, host-capturing the rest — instead of raising, while staying
donation-safe and producing a byte-identical snapshot layout.
"""

import logging
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.test_utils import run_with_processes
from torchsnapshot_tpu.utils import knobs


def _mesh_sharded(n=64):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    return jax.device_put(
        np.arange(n, dtype=np.float32).reshape(8, n // 8),
        NamedSharding(mesh, P("x")),
    )


def _single_device(val=7):
    import jax
    import jax.numpy as jnp

    return jax.device_put(jnp.int32(val), jax.devices()[0])


def _restore_and_check(snap, w_expected, step_expected):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    tgt = StateDict(
        w=jax.device_put(
            jnp.zeros(w_expected.shape, jnp.float32), NamedSharding(mesh, P("x"))
        ),
        step=jax.device_put(jnp.int32(0), jax.devices()[0]),
    )
    snap.restore({"s": tgt})
    assert np.array_equal(np.asarray(tgt["w"]), w_expected)
    assert int(tgt["step"]) == step_expected


def test_zero_hbm_limit_degrades_everything_and_survives_donation(
    tmp_path, caplog
) -> None:
    """limit=0: no fork fits; every device leaf is host-captured. The take
    must still succeed, stay donation-safe, and restore bit-exact."""
    w = _mesh_sharded()
    step = _single_device(7)
    expected = np.asarray(w).copy()
    with knobs.override_async_fork_hbm_limit_bytes(0):
        with caplog.at_level(logging.WARNING, logger="torchsnapshot_tpu.io_preparer"):
            pending = Snapshot.async_take(
                str(tmp_path / "ckpt"), {"s": StateDict(w=w, step=step)}
            )
    # Donation: training invalidates every reference right after return.
    w.delete()
    step.delete()
    snap = pending.wait()
    _restore_and_check(snap, expected, 7)
    assert any(
        "captured through host RAM" in r.getMessage() for r in caplog.records
    )


def test_partial_fit_forks_what_fits_captures_the_rest(tmp_path, caplog) -> None:
    """4 equal leaves in one device-assignment group under a limit that fits
    half: bisection keeps 2 device-forked, host-captures 2."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    arrs = {
        f"a{i}": jax.device_put(
            jnp.full(256, i, dtype=jnp.float32), dev
        )  # 1 KiB each
        for i in range(4)
    }
    # Full group = 4 KiB > 2.5 KiB; one half (2 KiB) fits, then nothing else.
    with knobs.override_async_fork_hbm_limit_bytes(2560):
        with caplog.at_level(logging.WARNING, logger="torchsnapshot_tpu.io_preparer"):
            pending = Snapshot.async_take(
                str(tmp_path / "ckpt"), {"s": StateDict(**arrs)}
            )
    for a in arrs.values():
        a.delete()
    snap = pending.wait()
    msg = next(
        r.getMessage()
        for r in caplog.records
        if "captured through host RAM" in r.getMessage()
    )
    assert "2 of 4 leaves" in msg, msg
    # Which path each leaf took is exported with the take, not only logged.
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["capture.forked_leaves"] == 2
    assert metrics["capture.host_captured_leaves"] == 2
    assert metrics["capture.host_captured_bytes"] == 2 * 1024
    tgt = StateDict(**{f"a{i}": jnp.zeros(256, jnp.float32) for i in range(4)})
    snap.restore({"s": tgt})
    for i in range(4):
        assert np.array_equal(np.asarray(tgt[f"a{i}"]), np.full(256, i, np.float32))


def test_degraded_take_layout_matches_normal_take(tmp_path) -> None:
    """The degraded capture changes the data path, never the plan: manifests
    of a degraded and a normal take of the same state are identical."""
    w = _mesh_sharded()
    step = _single_device(3)
    state = {"s": StateDict(w=w, step=step)}
    normal = Snapshot.take(str(tmp_path / "normal"), state)
    with knobs.override_async_fork_hbm_limit_bytes(0):
        degraded = Snapshot.async_take(str(tmp_path / "degraded"), state).wait()

    def layout(snap):
        from torchsnapshot_tpu.manifest import entry_to_dict

        return {p: entry_to_dict(e) for p, e in snap.get_manifest().items()}

    assert layout(normal) == layout(degraded)


def test_injected_resource_exhausted_from_fork_degrades(tmp_path, monkeypatch) -> None:
    """A real XLA RESOURCE_EXHAUSTED raised by the batched copy (not the
    simulation knob) takes the same degradation path."""
    from torchsnapshot_tpu import device_programs

    def exploding_copy_fn(shardings, cuts):
        def fn(xs):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
                "1234 bytes"
            )

        return fn

    monkeypatch.setattr(device_programs, "batch_copy_fn", exploding_copy_fn)
    x = _mesh_sharded()
    expected = np.asarray(x).copy()
    pending = Snapshot.async_take(str(tmp_path / "ckpt"), {"s": StateDict(w=x)})
    x.delete()
    snap = pending.wait()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    tgt = StateDict(
        w=jax.device_put(jnp.zeros((8, 8), jnp.float32), NamedSharding(mesh, P("x")))
    )
    snap.restore({"s": tgt})
    assert np.array_equal(np.asarray(tgt["w"]), expected)


def test_non_oom_fork_error_still_raises(tmp_path, monkeypatch) -> None:
    """Degradation is for allocation failure only; other fork errors are
    real bugs and must propagate."""
    from torchsnapshot_tpu import device_programs

    def broken_copy_fn(shardings, cuts):
        def fn(xs):
            raise ValueError("not an allocation failure")

        return fn

    monkeypatch.setattr(device_programs, "batch_copy_fn", broken_copy_fn)
    x = _mesh_sharded()
    with pytest.raises(ValueError, match="not an allocation failure"):
        Snapshot.async_take(str(tmp_path / "ckpt"), {"s": StateDict(w=x)})


def test_degraded_capture_composes_with_compressed_slabs(tmp_path, caplog) -> None:
    """HBM-degraded host captures still join member-framed compressed slabs
    (their stagers hold private host buffers and pack like any host member)
    and the take stays donation-safe and bit-exact."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    arrs = {
        f"a{i}": jax.device_put(jnp.arange(256, dtype=jnp.float32) + i, dev)
        for i in range(4)
    }
    path = str(tmp_path / "ckpt")
    with knobs.override_async_fork_hbm_limit_bytes(0):
        with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
            with caplog.at_level(
                logging.WARNING, logger="torchsnapshot_tpu.io_preparer"
            ):
                pending = Snapshot.async_take(path, {"m": StateDict(**arrs)})
            for a in arrs.values():
                a.delete()
            pending.wait()
    # Guard the premise: the degraded path really ran.
    assert any(
        "captured through host RAM" in r.getMessage() for r in caplog.records
    )
    manifest = Snapshot(path).get_manifest()
    batched = [
        e
        for e in manifest.values()
        if getattr(e, "location", "").startswith("batched/")
    ]
    assert len(batched) == 4 and all(e.raw_range is not None for e in batched)
    tgt = StateDict(**{f"a{i}": jnp.zeros(256, jnp.float32) for i in range(4)})
    Snapshot(path).restore({"m": tgt})
    for i in range(4):
        assert np.array_equal(
            np.asarray(tgt[f"a{i}"]), np.arange(256, dtype=np.float32) + i
        )


def _worker_degraded_multirank(rank: int, world_size: int, shared: str) -> None:
    """Degradation is rank-local but plan-identical, so mixed-pressure ranks
    (rank 1 degraded, rank 0 not) must still compose one valid snapshot."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot as Snap, StateDict as SD

    if rank == 1:
        os.environ["TORCHSNAPSHOT_TPU_ASYNC_FORK_HBM_LIMIT_BYTES"] = "0"

    mesh = Mesh(np.array(jax.devices()), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    full = np.arange(64, dtype=np.float32).reshape(8, 8)
    w = jax.make_array_from_callback((8, 8), sharding, lambda idx: full[idx])

    path = os.path.join(shared, "ckpt")
    pending = Snap.async_take(path, {"s": SD(w=w)})
    w.delete()
    snap = pending.wait()

    tgt = SD(
        w=jax.make_array_from_callback(
            (8, 8), sharding, lambda idx: np.zeros((8, 8), np.float32)[idx]
        )
    )
    snap.restore({"s": tgt})
    for shard in tgt["w"].addressable_shards:
        assert np.array_equal(np.asarray(shard.data), full[shard.index])


@pytest.mark.multiprocess
def test_degraded_fork_mixed_across_ranks(tmp_path) -> None:
    run_with_processes(
        _worker_degraded_multirank,
        nproc=2,
        args=(str(tmp_path),),
        init_jax_distributed=True,
    )


def _worker_degraded_local_device_sharded(rank: int, world_size: int, shared: str) -> None:
    """A per-rank array sharded across one process's LOCAL devices
    classifies as "array" and stages whole; its degraded host capture must
    assemble ALL local shards, not truncate to shard 0."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot as Snap, StateDict as SD

    os.environ["TORCHSNAPSHOT_TPU_ASYNC_FORK_HBM_LIMIT_BYTES"] = "0"
    # No jax.distributed: each process sees only its own 2 CPU devices.
    mesh = Mesh(np.array(jax.devices()), ("x",))
    full = np.arange(32, dtype=np.float32).reshape(8, 4) + 100 * rank
    w = jax.device_put(full, NamedSharding(mesh, P("x")))
    assert len(w.addressable_shards) > 1  # the regression's precondition

    path = os.path.join(shared, "ckpt")
    pending = Snap.async_take(path, {"s": SD(w=w)})
    w.delete()
    snap = pending.wait()
    tgt = SD(w=np.zeros((8, 4), np.float32))
    snap.restore({"s": tgt})
    assert np.array_equal(tgt["w"], full)


@pytest.mark.multiprocess
def test_degraded_capture_of_locally_sharded_per_rank_array(tmp_path) -> None:
    run_with_processes(
        _worker_degraded_local_device_sharded, nproc=2, args=(str(tmp_path),)
    )


def test_restore_consumes_its_target_when_hbm_cannot_hold_both(
    tmp_path, monkeypatch, caplog
) -> None:
    """Restoring into device-resident targets peaks at twice the state. When
    the restored leaf cannot be allocated beside its target, the target —
    about to be replaced — gives up its buffers and the leaf is placed
    again; counted, named in ONE warning per restore (every restore, not
    the first of the process), and any other failure still raises."""
    import jax
    import jax.numpy as jnp

    src = jnp.arange(256, dtype=jnp.float32)
    snap = Snapshot.take(str(tmp_path / "ckpt"), {"s": StateDict(a=src)})
    target = jnp.zeros(256, jnp.float32)
    real_device_put = jax.device_put
    calls = []

    def full_hbm_once(x, *args, **kwargs):
        calls.append(target.is_deleted())
        if len(calls) == 1:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Error allocating device buffer (simulated)"
            )
        return real_device_put(x, *args, **kwargs)

    for _ in range(2):
        target = jnp.zeros(256, jnp.float32)
        calls.clear()
        caplog.clear()
        monkeypatch.setattr(jax, "device_put", full_hbm_once)
        sd = StateDict(a=target)
        with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
            snap.restore({"s": sd})
        monkeypatch.undo()
        assert calls == [False, True]  # placed again only after the release
        assert target.is_deleted()
        assert np.array_equal(np.asarray(sd["a"]), np.asarray(src))
        metrics = Snapshot.last_telemetry.metrics.as_dict()
        assert metrics["restore.targets_consumed"] == 1
        (warning,) = [r.getMessage() for r in caplog.records if "consumed" in r.getMessage()]
        assert "s/a" in warning and "deleted array" in warning

    def refused(x, *args, **kwargs):
        raise RuntimeError("INVALID_ARGUMENT: simulated")

    monkeypatch.setattr(jax, "device_put", refused)
    keep = jnp.zeros(256, jnp.float32)
    with pytest.raises(RuntimeError, match="simulated"):
        snap.restore({"s": StateDict(a=keep)})
    monkeypatch.undo()
    assert not keep.is_deleted()
