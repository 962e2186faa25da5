"""async_take semantics + fault injection
(reference model: ``tests/test_async_take.py:25-64``)."""

import asyncio
import os
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.io_types import WriteIO
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.test_utils import run_with_processes


class SlowFSStoragePlugin(FSStoragePlugin):
    """Delays every write so staging finishes long before I/O does."""

    async def write(self, write_io: WriteIO) -> None:
        await asyncio.sleep(0.5)
        await super().write(write_io)


class FaultyFSStoragePlugin(FSStoragePlugin):
    async def write(self, write_io: WriteIO) -> None:
        raise RuntimeError("injected storage failure")


def test_async_take_returns_before_io(tmp_path, monkeypatch) -> None:
    import torchsnapshot_tpu.storage_plugin as sp

    monkeypatch.setattr(
        sp, "url_to_storage_plugin", lambda url: SlowFSStoragePlugin(url)
    )
    # Untimed warmup: first-use costs (lazy imports, event-loop/plugin
    # bootstrap) must not count against the staging-latency assertion.
    Snapshot.async_take(
        str(tmp_path / "warmup"), {"s": StateDict(w=np.ones(4))}
    ).wait()

    path = str(tmp_path / "ckpt")
    sd = StateDict(v=np.arange(32, dtype=np.float32))
    t0 = time.monotonic()
    pending = Snapshot.async_take(path, {"s": sd})
    returned_after = time.monotonic() - t0
    assert returned_after < 0.5  # returned at staging-complete, not io-complete
    assert not pending.done()
    # Consistency: mutations after return must not affect the snapshot.
    sd["v"][:] = -1
    snap = pending.wait()
    assert pending.done()
    tgt = StateDict(v=np.zeros(32, dtype=np.float32))
    snap.restore({"s": tgt})
    assert np.array_equal(tgt["v"], np.arange(32, dtype=np.float32))


def test_async_take_survives_donation(tmp_path) -> None:
    """Training may donate (invalidate) the checkpointed jax arrays right
    after ``async_take`` returns; the on-device defensive fork
    (``io_preparer._defensive_device_copies``) keeps the capture intact."""
    import jax.numpy as jnp

    x = jnp.arange(1024, dtype=jnp.float32)
    path = str(tmp_path / "ckpt")
    pending = Snapshot.async_take(path, {"s": StateDict(x=x)})
    x.delete()  # what donate_argnums does to every reference
    snap = pending.wait()
    tgt = StateDict(x=jnp.zeros(1024, dtype=jnp.float32))
    snap.restore({"s": tgt})
    assert np.array_equal(np.asarray(tgt["x"]), np.arange(1024, dtype=np.float32))


def test_async_take_device_copy_disabled_still_works_without_donation(
    tmp_path,
) -> None:
    from torchsnapshot_tpu.utils import knobs

    import jax.numpy as jnp

    with knobs.override_async_device_copy(False):
        x = jnp.arange(16, dtype=jnp.float32)
        pending = Snapshot.async_take(str(tmp_path / "ckpt"), {"s": StateDict(x=x)})
        snap = pending.wait()
        tgt = StateDict(x=jnp.zeros(16, dtype=jnp.float32))
        snap.restore({"s": tgt})
        assert np.array_equal(np.asarray(tgt["x"]), np.arange(16, dtype=np.float32))


def test_async_take_failure_never_commits(tmp_path, monkeypatch) -> None:
    import torchsnapshot_tpu.storage_plugin as sp

    monkeypatch.setattr(
        sp, "url_to_storage_plugin", lambda url: FaultyFSStoragePlugin(url)
    )
    path = str(tmp_path / "ckpt")
    pending = Snapshot.async_take(path, {"s": StateDict(v=np.ones(4))})
    with pytest.raises(RuntimeError, match="failed"):
        pending.wait()
    # The cardinal rule: no partial snapshot is ever committed.
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))


def _worker_async_rank_failure(rank: int, world_size: int, shared: str) -> None:
    import torchsnapshot_tpu.storage_plugin as sp
    from torchsnapshot_tpu import Snapshot as Snap, StateDict as SD

    if rank == 1:
        sp.url_to_storage_plugin_orig = sp.url_to_storage_plugin
        sp.url_to_storage_plugin = lambda url: FaultyFSStoragePlugin(url)

    path = os.path.join(shared, "ckpt")
    pending = Snap.async_take(path, {"s": SD(v=np.full(4, rank))})
    try:
        pending.wait()
        committed = True
    except RuntimeError:
        committed = False
    if rank == 1:
        assert not committed  # the faulty rank must fail
    # Leader must never commit when any rank failed.
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))


@pytest.mark.multiprocess
def test_async_rank_failure_propagates(tmp_path) -> None:
    """A failing rank aborts the commit on every rank via the store barrier."""
    run_with_processes(_worker_async_rank_failure, nproc=2, args=(str(tmp_path),))


def test_sync_take_failure_never_commits(tmp_path, monkeypatch) -> None:
    import torchsnapshot_tpu.storage_plugin as sp

    monkeypatch.setattr(
        sp, "url_to_storage_plugin", lambda url: FaultyFSStoragePlugin(url)
    )
    path = str(tmp_path / "ckpt")
    with pytest.raises(RuntimeError, match="injected"):
        Snapshot.take(path, {"s": StateDict(v=np.ones(4))})
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))


def test_async_take_mixed_device_assignments(tmp_path) -> None:
    """Leaves with different device assignments (mesh-sharded params next to
    a counter committed to one device) must each be forked in their own
    batched-copy program — one jit call over all of them would raise
    'incompatible devices for jitted computation'."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("x",))
    sharded = jax.device_put(
        np.arange(64, dtype=np.float32).reshape(8, 8),
        NamedSharding(mesh, P("x")),
    )
    single = jax.device_put(jnp.int32(7), devices[0])
    replicated_host = np.float64(2.5)

    path = str(tmp_path / "ckpt")
    pending = Snapshot.async_take(
        path, {"s": StateDict(w=sharded, step=single, lr=replicated_host)}
    )
    snap = pending.wait()

    tgt = StateDict(
        w=jax.device_put(jnp.zeros((8, 8), jnp.float32), NamedSharding(mesh, P("x"))),
        step=jax.device_put(jnp.int32(0), devices[0]),
        lr=np.float64(0.0),
    )
    snap.restore({"s": tgt})
    assert np.array_equal(np.asarray(tgt["w"]), np.arange(64, dtype=np.float32).reshape(8, 8))
    assert int(tgt["step"]) == 7
    assert float(tgt["lr"]) == 2.5


# ---------------------------------------------------------------------------
# Preemption torture (async_take under TPU-VM preemption): a worker is
# SIGKILLed mid-background-drain. The new snapshot
# must never commit, survivors must fail within the barrier timeout with a
# clear error, and a previously committed snapshot must stay verifiably
# intact. (Reference pattern: ``tests/test_async_take.py:25-64``.)
# ---------------------------------------------------------------------------

class PreemptSlowFSStoragePlugin(FSStoragePlugin):
    """Per-process write delay: the doomed rank gets a long drain so SIGKILL
    lands mid-flight; survivors drain fast and reach the commit barrier."""

    delay_s = 0.05

    async def write(self, write_io: WriteIO) -> None:
        await asyncio.sleep(type(self).delay_s)
        await super().write(write_io)


def _worker_preempted_async_take(rank: int, world_size: int, shared: str) -> None:
    import signal

    import torchsnapshot_tpu.storage_plugin as sp
    from torchsnapshot_tpu import Snapshot as Snap, StateDict as SD

    # Phase 0: a committed snapshot that must survive the preemption.
    prev = os.path.join(shared, "prev")
    Snap.take(prev, {"s": SD(v=np.full(8, rank, np.float32))})
    assert os.path.exists(os.path.join(prev, ".snapshot_metadata"))

    # Keep the commit-barrier timeout short so the survivor's failure is
    # prompt (production default is 30 min — sized for the slowest rank's
    # full data write, not for a test).
    os.environ["TORCHSNAPSHOT_TPU_BARRIER_TIMEOUT_S"] = "8"
    # Rank 1 never checks out of the launcher's exit drain (it's SIGKILLed);
    # don't make the survivor idle the full default linger.
    os.environ["TORCHSNAPSHOT_TPU_LAUNCHER_DRAIN_S"] = "1"
    PreemptSlowFSStoragePlugin.delay_s = 5.0 if rank == 1 else 0.05
    sp.url_to_storage_plugin = lambda url: PreemptSlowFSStoragePlugin(url)

    path = os.path.join(shared, "ckpt")
    state = {
        "s": SD(**{f"v{i}": np.full(512, rank + i, np.float32) for i in range(4)})
    }
    pending = Snap.async_take(path, state)
    if rank == 1:
        time.sleep(0.5)  # mid-drain: ~5 s of storage writes still in flight
        os.kill(os.getpid(), signal.SIGKILL)

    # Survivor: the drain finishes, the commit barrier waits for the dead
    # rank, times out, and wait() surfaces a clear error.
    t0 = time.monotonic()
    try:
        pending.wait()
        raise AssertionError("commit must not succeed after a rank died")
    except RuntimeError as e:
        elapsed = time.monotonic() - t0
        assert elapsed < 30, f"failure took {elapsed:.1f}s (barrier timeout 8s)"
        assert "timed out" in repr(e.__cause__), repr(e.__cause__)
    # The cardinal rule, under preemption: no partial snapshot commits.
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))
    # And the previous snapshot is still fully intact.
    assert Snap(prev).verify() == {}


@pytest.mark.multiprocess
def test_async_take_sigkill_mid_drain_never_commits(tmp_path) -> None:
    with pytest.raises(RuntimeError) as exc_info:
        run_with_processes(
            _worker_preempted_async_take, nproc=2, args=(str(tmp_path),)
        )
    msg = str(exc_info.value)
    # Exactly the SIGKILLed rank fails (reported as died-without-reporting);
    # the survivor's in-worker assertions all passed.
    assert "rank 1" in msg and "died without reporting" in msg, msg
    assert "rank 0" not in msg, msg
    assert not os.path.exists(str(tmp_path / "ckpt" / ".snapshot_metadata"))


def test_async_take_failure_never_commits_on_gcs(tmp_path, monkeypatch) -> None:
    """The no-partial-commit guarantee on the GCS path: uploads start dying
    mid-drain (fatal backend error), wait() raises, no metadata blob ever
    appears, and an earlier committed snapshot still verifies clean."""
    import sys as _sys

    from test_gcs_storage_plugin import _install_fake_gcs

    blobs: dict = {}
    _install_fake_gcs(monkeypatch, blobs, {})

    prev = "gs://bucket/prev"
    Snapshot.take(prev, {"s": StateDict(v=np.arange(64, dtype=np.float32))})
    assert any(k.endswith(".snapshot_metadata") for k in blobs)
    assert Snapshot(prev).verify() == {}

    blob_cls = type(
        _sys.modules["google.cloud.storage"].Client().bucket("b").blob("x")
    )
    monkeypatch.setattr(
        blob_cls,
        "upload_from_file",
        lambda self, *a, **k: (_ for _ in ()).throw(
            ValueError("backend gone mid-drain")
        ),
    )
    pending = Snapshot.async_take(
        "gs://bucket/ckpt", {"s": StateDict(v=np.ones(64, np.float32))}
    )
    with pytest.raises(RuntimeError, match="failed"):
        pending.wait()
    assert not any(
        k.startswith("ckpt/") and k.endswith(".snapshot_metadata") for k in blobs
    )
    assert Snapshot(prev).verify() == {}
