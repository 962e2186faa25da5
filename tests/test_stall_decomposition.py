"""Async-take stall decomposition: phase timings exist, add up, and the
steady-state stall of a sharded take stays within budget.

The stall (planning + mutable-host capture, NOT device bytes) is the
framework's headline metric; these tests keep it observable and bounded so a
planning-path regression (e.g. an accidental collective or full D2H inside
``async_take``) fails the suite rather than silently eating the budget.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu import snapshot as snapshot_mod

# Generous vs CI noise, brutal vs real regressions: an accidental synchronous
# D2H+write of the ~48 MB state below costs well under a second, but an
# accidental barrier timeout or full-manifest pickle explosion costs tens.
STEADY_STALL_BUDGET_S = 5.0


def _sharded_app():
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
    k = jax.random.PRNGKey(0)
    params = jax.device_put(
        jax.random.normal(k, (1024, 4096), jnp.float32),
        NamedSharding(mesh, P("dp", "tp")),
    )
    mu = jax.device_put(
        jnp.zeros((1024, 4096), jnp.float32), NamedSharding(mesh, P("dp", "tp"))
    )
    return {
        "train": StateDict(params=params, mu=mu, step=3),
        "progress": StateDict(epoch=1),
    }


def test_phase_timings_recorded_and_consistent(tmp_path) -> None:
    app = _sharded_app()
    pending = Snapshot.async_take(str(tmp_path / "s"), app)
    pending.wait()
    phases = dict(snapshot_mod.LAST_TAKE_PHASES)
    assert {
        "gather_keys_and_flatten",
        "prepare_write",
        "partition",
        "manifest_gather",
        "memory_budget",
        "capture",
    } <= set(phases)
    assert all(v >= 0 for v in phases.values())
    # The recorded phases must COVER the stall: a new expensive step added
    # to _take_impl without a _phase() call would show up as stall time the
    # decomposition can't account for. 250 ms of slack absorbs the
    # un-phased overhead (path/replication coalescing, plugin construction,
    # thread start) plus CI noise.
    t0 = time.perf_counter()
    pending = Snapshot.async_take(str(tmp_path / "s2"), app)
    stall = time.perf_counter() - t0
    pending.wait()
    phases2 = dict(snapshot_mod.LAST_TAKE_PHASES)
    assert sum(phases2.values()) >= stall - 0.25


def test_steady_state_stall_within_budget(tmp_path) -> None:
    app = _sharded_app()
    # Warmup: jit compiles, thread pools, coordinator bootstrap.
    Snapshot.async_take(str(tmp_path / "warm"), app).wait()
    stalls = []
    for i in range(2):
        t0 = time.perf_counter()
        pending = Snapshot.async_take(str(tmp_path / f"s{i}"), app)
        stalls.append(time.perf_counter() - t0)
        pending.wait()
    assert min(stalls) < STEADY_STALL_BUDGET_S, stalls


def test_sync_take_also_records_phases(tmp_path) -> None:
    app = {"s": StateDict(x=np.arange(64, dtype=np.float32))}
    Snapshot.take(str(tmp_path / "s"), app)
    phases = dict(snapshot_mod.LAST_TAKE_PHASES)
    assert "prepare_write" in phases and "capture" in phases


def test_drain_stats_recorded(tmp_path) -> None:
    """The background drain reports stream-overlap accounting (D2H+serialize
    vs storage-write busy time) so drain-throughput regressions are
    observable."""
    app = _sharded_app()
    pending = Snapshot.async_take(str(tmp_path / "s"), app)
    snap = pending.wait()
    stats = pending.drain_stats
    assert {"wall_s", "stage_busy_s", "io_busy_s", "overlap_s", "idle_s"} <= set(
        stats
    )
    # stage_busy decomposes into the d2h/serialize/hash sub-streams.
    assert {"stage_d2h_s", "stage_serialize_s", "stage_hash_s"} <= set(stats)
    assert all(stats[k] >= 0 for k in ("stage_d2h_s", "stage_serialize_s"))
    assert stats["wall_s"] >= 0
    # Overlap can never exceed either stream's busy time, and the union of
    # busy + idle can never exceed wall (within float slop).
    assert stats["overlap_s"] <= stats["stage_busy_s"] + 1e-6
    assert stats["overlap_s"] <= stats["io_busy_s"] + 1e-6
    union = stats["stage_busy_s"] + stats["io_busy_s"] - stats["overlap_s"]
    assert union <= stats["wall_s"] + 1e-6
    assert stats["idle_s"] >= 0
    # The snapshot itself is intact.
    assert snap.verify() == {}


def test_sync_take_drain_stats_cover_staging(tmp_path) -> None:
    """A SYNC take stages everything before its drain loop; the recorded
    stream stats must still attribute that staging time (round-5: the
    accounting moved into the shared wait loop so sync-take regressions
    decompose the same way async drains do)."""
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import Snapshot, StateDict, snapshot as snapshot_mod

    arrs = {
        f"a{i}": jax.random.normal(jax.random.PRNGKey(i), (256, 256), jnp.float32)
        for i in range(4)
    }
    Snapshot.take(str(tmp_path / "ckpt"), {"m": StateDict(**arrs)})
    stats = snapshot_mod.LAST_SYNC_DRAIN_STATS
    assert {"wall_s", "stage_busy_s", "io_busy_s", "overlap_s", "idle_s"} <= set(
        stats
    )
    # The staging stream (device_get + serialize of 4 arrays) must be
    # attributed, not reported as an empty stream.
    assert stats["stage_busy_s"] > 0
    assert stats["wall_s"] >= stats["stage_busy_s"] - 1e-6
