"""Multi-rank cloud composition: full ``async_take`` → commit → ``restore``
against the GCS emulator, with slabs + compression + resumable uploads all
active at once.

Every component below has single-process emulator coverage in
``test_gcs_storage_plugin.py``; what had never been proven is the *pod
story* — partitioned replicated writes, member-framed compressed slabs,
resumable uploads, and the store-based commit barrier composed across real
coordinated processes on one wire path. The reference only drives its cloud
plugins end-to-end single-process against live buckets
(``/root/reference/tests/test_gcs_storage_plugin.py:1-60``); this runs
multi-rank and credential-free.

The workers talk to a ``FakeGCSServer`` in the parent process via
``STORAGE_EMULATOR_HOST`` (real google-cloud-storage SDK wire path); the
parent then asserts on the server's object store and request log directly.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from torchsnapshot_tpu.test_utils import run_with_processes

pytest.importorskip("google.cloud.storage")

BUCKET = "bkt"
# Arrays above this go resumable/chunked on the wire; below it, multipart.
CHUNK_BYTES = 64 * 1024


def _worker_env(endpoint: str) -> None:
    os.environ["STORAGE_EMULATOR_HOST"] = endpoint
    os.environ["GOOGLE_CLOUD_PROJECT"] = "test-project"
    os.environ["TORCHSNAPSHOT_TPU_ENABLE_BATCHING"] = "1"
    os.environ["TORCHSNAPSHOT_TPU_SLAB_SIZE_THRESHOLD_BYTES"] = "8192"
    # zlib, not zstd: the pod-story composition (slabs + compression +
    # resumable uploads + commit barrier) is codec-agnostic, and zlib is
    # stdlib — an optional-dependency skip can't surface from inside a
    # worker process, it would just fail the whole matrix.
    os.environ["TORCHSNAPSHOT_TPU_COMPRESSION"] = "zlib"
    os.environ["TORCHSNAPSHOT_TPU_GCS_CHUNK_BYTES"] = str(CHUNK_BYTES)



def _zeros_global(shape, sharding):
    """Zeroed multiprocess array without jax.device_put: device_put onto a
    global sharding runs a jitted consistency psum, which this jax version
    refuses on the multiprocess CPU backend — make_array_from_callback
    builds shards host-side with no collective at all."""
    import jax
    import numpy as np_

    return jax.make_array_from_callback(
        shape, sharding, lambda idx: np_.zeros(shape, "float32")[idx]
    )

def _worker_cloud_composition(
    rank: int, world_size: int, endpoint: str, prefix: str
) -> None:
    _worker_env(endpoint)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("x",))
    n_dev = len(devices)

    # Sharded: 4 MB of incompressible data -> per-shard writes above the
    # resumable threshold even after zstd.
    big_np = np.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (4096, 256), jnp.float32)
    )
    sharded = jax.make_array_from_callback(
        big_np.shape, NamedSharding(mesh, P("x")), lambda idx: big_np[idx]
    )
    # Replicated on the global mesh: the partitioner splits these writes
    # across ranks (each is written exactly once, by one rank).
    repl_np = [
        np.asarray(
            jax.random.normal(jax.random.PRNGKey(20 + i), (96 * 1024 // 4,), jnp.float32)
        )
        for i in range(2)
    ]
    replicated = [
        jax.make_array_from_callback(
            a.shape, NamedSharding(mesh, P(None)), lambda idx, a=a: a[idx]
        )
        for a in repl_np
    ]
    # Small per-rank host arrays -> member-framed compressed slabs + .ftab.
    smalls = {
        f"s{i}": np.full((256,), rank * 100 + i, dtype=np.float32)
        for i in range(12)
    }

    path = f"gs://{BUCKET}/{prefix}"
    sd = StateDict(
        big=sharded, r0=replicated[0], r1=replicated[1], **smalls
    )
    pending = Snapshot.async_take(path, {"s": sd})
    snap = pending.wait()

    # Restore into fresh zeroed targets with the same shardings.
    tgt = StateDict(
        big=_zeros_global(big_np.shape, NamedSharding(mesh, P("x"))),
        r0=_zeros_global(repl_np[0].shape, NamedSharding(mesh, P(None))),
        r1=_zeros_global(repl_np[1].shape, NamedSharding(mesh, P(None))),
        **{k: np.zeros_like(v) for k, v in smalls.items()},
    )
    snap.restore({"s": tgt})

    for shard in tgt["big"].addressable_shards:
        assert np.array_equal(np.asarray(shard.data), big_np[shard.index])
    assert np.array_equal(np.asarray(tgt["r0"]), repl_np[0])
    assert np.array_equal(np.asarray(tgt["r1"]), repl_np[1])
    for k, v in smalls.items():
        assert np.array_equal(tgt[k], v)

    # Cloud + reshard composition: restore the sharded array into a
    # DIFFERENT layout (sharded along the other axis) straight off the
    # emulator — overlap-scatter planning drives ranged HTTP reads of the
    # saved shard objects.
    tgt2 = StateDict(
        big=_zeros_global(big_np.shape, NamedSharding(mesh, P(None, "x")))
    )
    snap.restore({"s": tgt2})
    # The restored array must keep the transposed donor layout — a silent
    # fallback to the saved P("x") layout would satisfy a data-only check.
    assert tgt2["big"].sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "x")), tgt2["big"].ndim
    ), tgt2["big"].sharding
    for shard in tgt2["big"].addressable_shards:
        assert np.array_equal(np.asarray(shard.data), big_np[shard.index])
    del n_dev


def _worker_cloud_fault(
    rank: int, world_size: int, endpoint: str, prefix: str
) -> None:
    _worker_env(endpoint)
    from torchsnapshot_tpu import Snapshot, StateDict

    path = f"gs://{BUCKET}/{prefix}"
    # One above-chunk-threshold array per rank — INCOMPRESSIBLE (the worker
    # env turns zstd on; a constant array would compress to a few KB and
    # slip under the resumable threshold): its upload initiates a RESUMABLE
    # session, which is what the parent armed fatal (403) faults against.
    # Everything else (small arrays, sidecars, and crucially the metadata
    # commit) goes multipart and is never faulted — so a broken commit
    # barrier would land `.snapshot_metadata` and be caught.
    sd = StateDict(
        big=np.random.default_rng(rank).standard_normal(
            CHUNK_BYTES // 4 * 2
        ).astype(np.float32),
        **{f"v{i}": np.full((512,), rank * 10 + i, dtype=np.int32) for i in range(4)},
    )
    pending = Snapshot.async_take(path, {"s": sd})
    with pytest.raises(Exception):
        # The faulted rank's upload dies on the 403; the peer is aborted by
        # the store-propagated failure at the commit barrier. Either way no
        # rank may observe a committed snapshot.
        pending.wait()


@pytest.mark.multiprocess
def test_multirank_cloud_composition_async_take_commit_restore() -> None:
    from gcs_emulator import FakeGCSServer

    prefix = "ck_ok"
    with FakeGCSServer() as srv:
        run_with_processes(
            _worker_cloud_composition,
            nproc=2,
            init_jax_distributed=True,
            args=(srv.endpoint, prefix),
        )
        names = [n for (b, n) in srv.state.objects if b == BUCKET]
        log = srv.state.request_log
        # Committed: the metadata object is the last thing written.
        assert f"{prefix}/.snapshot_metadata" in names
        # Both ranks' checksum sidecars landed.
        assert f"{prefix}/.checksums.0" in names
        assert f"{prefix}/.checksums.1" in names
        # Member-framed compressed slabs (+ their .ftab side objects).
        assert any("/batched/" in n for n in names)
        assert any(n.endswith(".ftab") for n in names)
        # The big shard writes actually used the resumable session protocol.
        assert any("uploadType=resumable" in line for line in log)
        assert any("uploadType=multipart" in line for line in log)
        # Partitioned replicated writes: each replicated array was written
        # exactly once, under the shared `replicated/` namespace.
        repl = [n for n in names if n.startswith(f"{prefix}/replicated/")]
        assert len([n for n in repl if "/r0" in n]) == 1
        assert len([n for n in repl if "/r1" in n]) == 1


@pytest.mark.multiprocess
def test_multirank_cloud_fault_never_commits() -> None:
    """A fatal (non-transient) upload failure on any rank mid-take must
    abort the commit on every rank: no ``.snapshot_metadata`` object may
    ever land on the bucket."""
    from gcs_emulator import FakeGCSServer

    prefix = "ck_fault"
    with FakeGCSServer() as srv:
        # Fatal faults scoped to RESUMABLE initiations only (each rank's one
        # big array). The metadata commit is a multipart POST, which no
        # armed fault can ever match — so if the commit-abort logic were
        # broken, `.snapshot_metadata` WOULD land and the assertion below
        # would catch it; the check cannot pass vacuously.
        srv.fail_next("uploadType=resumable", n=2, status=403)
        run_with_processes(
            _worker_cloud_fault,
            nproc=2,
            args=(srv.endpoint, prefix),
        )
        names = [n for (b, n) in srv.state.objects if b == BUCKET]
        assert not any(n.endswith(".snapshot_metadata") for n in names), names
        # Both armed faults actually fired (one per rank's big upload).
        assert not srv.state.faults, srv.state.faults
