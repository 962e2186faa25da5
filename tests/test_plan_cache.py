"""Cross-take plan cache: a second take of an identical app-state structure
must issue NO O(world) collectives — no key/partition/hostname all_gathers,
no per-key barriers — only the constant-cost preflight round, the manifest
delta gather, and the commit barriers.

Correctness under the cache is covered from several angles: changed primitive
values must flow through the delta gather into the committed manifest,
replicated entries must still be written exactly once under the cached
partition assignment, and any structure change must force a miss (and a
correct full-path take).
"""

import os

import numpy as np
import pytest

from torchsnapshot_tpu.test_utils import run_with_processes

pytestmark = pytest.mark.multiprocess


def _counting_coordinator():
    """Wrap the process coordinator's collectives with call counters."""
    from torchsnapshot_tpu.parallel.coordinator import get_coordinator

    coord = get_coordinator()
    counts = {"all_gather": 0, "barrier": 0, "gather": 0, "broadcast": 0}
    orig = {
        "all_gather": coord.all_gather_object,
        "barrier": coord.barrier,
        "gather": coord.gather_object,
        "broadcast": coord.broadcast_object,
    }

    def wrap(name):
        def inner(*args, **kwargs):
            counts[name] += 1
            return orig[name](*args, **kwargs)

        return inner

    coord.all_gather_object = wrap("all_gather")
    coord.barrier = wrap("barrier")
    coord.gather_object = wrap("gather")
    coord.broadcast_object = wrap("broadcast")
    return coord, counts


def _worker_steady_state_no_allgathers(rank, world_size, shared):
    from torchsnapshot_tpu import Snapshot, StateDict

    coord, counts = _counting_coordinator()

    app = {
        "train": StateDict(
            w=np.arange(16, dtype=np.float32) + rank, step=0
        ),
        "repl": StateDict(table=np.arange(6, dtype=np.int64)),
    }
    Snapshot.take(os.path.join(shared, "c0"), app, replicated=["repl/*"])
    first = dict(counts)
    # First take pays the full coordination bill (preflight + partition
    # all_gather + hostname all_gather + manifest gather + barriers).
    assert first["all_gather"] >= 1, first

    for k in counts:
        counts[k] = 0
    app["train"]["step"] = 7
    Snapshot.take(os.path.join(shared, "c1"), app, replicated=["repl/*"])
    second = dict(counts)

    # The done-criterion: no key-gather/partition/hostname
    # all_gathers and no per-key barriers on a steady-state take. The
    # data-done/commit-visible rendezvous no longer rides coordinator
    # barriers at all: sync takes commit through the store-based
    # LinearBarrier (arrive/depart with cross-rank error fan-out), so
    # coordinator barrier count is zero.
    assert second["all_gather"] == 0, second
    assert second["barrier"] == 0, second  # commit rides the LinearBarrier
    assert second["gather"] == 2, second  # preflight + manifest delta
    assert second["broadcast"] == 1, second  # preflight decision

    # The changed primitive must have flowed through the delta gather into
    # the committed manifest...
    snap = Snapshot(os.path.join(shared, "c1"))
    manifest = snap.get_manifest()
    assert manifest[f"{rank}/train/step"].get_value() == 7
    # ...and the cached partition assignment must still write replicated
    # data exactly once, to the rank-less replicated/ namespace.
    locations = {
        e.location
        for k, e in manifest.items()
        if getattr(e, "replicated", False) and hasattr(e, "location")
    }
    assert locations == {"replicated/repl/table"}, locations

    tgt = {
        "train": StateDict(w=np.zeros(16, dtype=np.float32), step=-1),
        "repl": StateDict(table=np.zeros(6, dtype=np.int64)),
    }
    snap.restore(tgt)
    assert tgt["train"]["step"] == 7
    assert np.array_equal(
        tgt["train"]["w"], np.arange(16, dtype=np.float32) + rank
    )
    assert np.array_equal(tgt["repl"]["table"], np.arange(6, dtype=np.int64))


def test_steady_state_take_issues_no_allgathers(tmp_path) -> None:
    run_with_processes(
        _worker_steady_state_no_allgathers, nproc=2, args=(str(tmp_path),)
    )


def _worker_structure_change_forces_miss(rank, world_size, shared):
    from torchsnapshot_tpu import Snapshot, StateDict

    coord, counts = _counting_coordinator()

    app = {"s": StateDict(w=np.arange(8, dtype=np.float32))}
    Snapshot.take(os.path.join(shared, "c0"), app)
    for k in counts:
        counts[k] = 0
    # Same logical paths, different shape: the fingerprint must miss and the
    # full (all_gather-bearing) path must run.
    app2 = {"s": StateDict(w=np.arange(12, dtype=np.float32))}
    Snapshot.take(os.path.join(shared, "c1"), app2)
    assert counts["all_gather"] >= 1, counts

    tgt = {"s": StateDict(w=np.zeros(12, dtype=np.float32))}
    Snapshot(os.path.join(shared, "c1")).restore(tgt)
    assert np.array_equal(tgt["s"]["w"], np.arange(12, dtype=np.float32))


def test_structure_change_forces_miss(tmp_path) -> None:
    run_with_processes(
        _worker_structure_change_forces_miss, nproc=2, args=(str(tmp_path),)
    )


def _worker_knob_disables_cache(rank, world_size, shared):
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    coord, counts = _counting_coordinator()
    with knobs.override_plan_cache(False):
        app = {"s": StateDict(w=np.full((4,), rank, dtype=np.float32))}
        Snapshot.take(os.path.join(shared, "c0"), app)
        for k in counts:
            counts[k] = 0
        Snapshot.take(os.path.join(shared, "c1"), app)
        # Cache off: the partition/hostname all_gathers run every take.
        assert counts["all_gather"] >= 1, counts
    tgt = {"s": StateDict(w=np.zeros(4, dtype=np.float32))}
    Snapshot(os.path.join(shared, "c1")).restore(tgt)
    assert np.array_equal(tgt["s"]["w"], np.full((4,), rank, dtype=np.float32))


def test_knob_disables_cache(tmp_path) -> None:
    run_with_processes(
        _worker_knob_disables_cache, nproc=2, args=(str(tmp_path),)
    )


def _worker_sharded_cache_hit_bit_exact(rank, world_size, shared):
    """Sharded GSPMD arrays under the cache: the second take must hit and
    still commit shard layouts + fresh values bit-exactly."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot, StateDict

    coord, counts = _counting_coordinator()
    devices = np.array(jax.devices()).reshape(world_size * 2)
    mesh = Mesh(devices, ("x",))
    base = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)

    def make(data):
        return jax.make_array_from_callback(
            (16, 4), NamedSharding(mesh, P("x")), lambda idx: data[idx]
        )

    Snapshot.take(os.path.join(shared, "c0"), {"s": StateDict(x=make(base))})
    for k in counts:
        counts[k] = 0
    bumped = base + 100.0
    Snapshot.take(os.path.join(shared, "c1"), {"s": StateDict(x=make(bumped))})
    assert counts["all_gather"] == 0, counts

    tgt = StateDict(x=make(np.zeros_like(base)))
    Snapshot(os.path.join(shared, "c1")).restore({"s": tgt})
    for shard in tgt["x"].addressable_shards:
        got = np.asarray(shard.data)
        assert np.array_equal(
            got.view(np.uint8), bumped[shard.index].view(np.uint8)
        )


def test_sharded_cache_hit_bit_exact(tmp_path) -> None:
    run_with_processes(
        _worker_sharded_cache_hit_bit_exact,
        nproc=2,
        init_jax_distributed=True,
        args=(str(tmp_path),),
    )


def _worker_async_take_cache_hit(rank, world_size, shared):
    """async_take shares the plan path: the second async take of an
    identical structure must hit (no all_gathers in the stall window) and
    the background commit must still produce a complete, correct snapshot.
    Also pins the published coordination claim: a steady-state stall costs
    a non-zero rank exactly 3 store round-trips (preflight set + decision
    get + manifest-delta set)."""
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.parallel import store as store_mod

    coord, counts = _counting_coordinator()
    app = {"s": StateDict(w=np.full((8,), rank, dtype=np.float32), step=0)}
    Snapshot.async_take(os.path.join(shared, "a0"), app).wait()
    for k in counts:
        counts[k] = 0
    store_mod.reset_op_counts()
    app["s"]["step"] = 5
    pending = Snapshot.async_take(os.path.join(shared, "a1"), app)
    stall_counts = dict(counts)
    # Coordination plane only: the fleet bus's rate-limited beacon set
    # (auto-on at world>1) counts as telemetry.*, not a coordination
    # round-trip.
    stall_ops = sum(
        store_mod.get_op_counts(
            current_thread_only=True, include_telemetry=False
        ).values()
    )
    snap = pending.wait()
    assert stall_counts["all_gather"] == 0, stall_counts
    if rank != 0:
        assert stall_ops == 3, stall_ops
    else:
        # Rank 0 additionally reads every rank's gather keys: 2W + 3.
        assert stall_ops == 2 * world_size + 3, stall_ops
    assert snap.verify() == {}
    tgt = {"s": StateDict(w=np.zeros(8, dtype=np.float32), step=-1)}
    snap.restore(tgt)
    assert tgt["s"]["step"] == 5
    assert np.array_equal(tgt["s"]["w"], np.full((8,), rank, dtype=np.float32))


def test_async_take_cache_hit(tmp_path) -> None:
    run_with_processes(
        _worker_async_take_cache_hit, nproc=2, args=(str(tmp_path),)
    )


def _worker_knob_change_forces_miss(rank, world_size, shared):
    """Plan-shaping knobs are in the fingerprint: flipping the compression
    codec between takes must miss (a cached partition assignment computed
    under different serializers must never be replayed)."""
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    coord, counts = _counting_coordinator()
    app = {"s": StateDict(w=np.arange(64, dtype=np.float32))}
    Snapshot.take(os.path.join(shared, "c0"), app)
    for k in counts:
        counts[k] = 0
    # zlib, not zstd: the point is only that a knob change flips the
    # fingerprint, and zlib is stdlib — no optional dependency in a worker
    # process where a skip can't surface.
    with knobs.override_compression("zlib"):
        Snapshot.take(os.path.join(shared, "c1"), app)
    assert counts["all_gather"] >= 1, counts  # full path ran
    tgt = {"s": StateDict(w=np.zeros(64, dtype=np.float32))}
    Snapshot(os.path.join(shared, "c1")).restore(tgt)
    assert np.array_equal(tgt["s"]["w"], np.arange(64, dtype=np.float32))


def test_knob_change_forces_miss(tmp_path) -> None:
    run_with_processes(
        _worker_knob_change_forces_miss, nproc=2, args=(str(tmp_path),)
    )


def _worker_cache_hit_composes_with_incremental(rank, world_size, shared):
    """The two flagship cost-cutters together: a steady-state (cache-HIT)
    take with base=prev must still dedup unchanged objects via hard links
    and restore the changed ones correctly — base rides the preflight
    broadcast, dedup rides the write pipeline."""
    from torchsnapshot_tpu import Snapshot, StateDict

    coord, counts = _counting_coordinator()
    frozen = np.arange(4096, dtype=np.float32) + rank
    p0 = os.path.join(shared, "c0")
    p1 = os.path.join(shared, "c1")
    Snapshot.take(p0, {"m": StateDict(frozen=frozen, step=0)})
    for k in counts:
        counts[k] = 0
    Snapshot.take(p1, {"m": StateDict(frozen=frozen, step=1)}, base=p0)
    assert counts["all_gather"] == 0, counts  # the take HIT the plan cache
    # The frozen array deduped: same inode as the base's object.
    a = os.path.join(p0, str(rank), "m", "frozen")
    b = os.path.join(p1, str(rank), "m", "frozen")
    assert os.path.samefile(a, b), (a, b)
    tgt = {"m": StateDict(frozen=np.zeros(4096, dtype=np.float32), step=-1)}
    Snapshot(p1).restore(tgt)
    assert tgt["m"]["step"] == 1
    assert np.array_equal(tgt["m"]["frozen"], frozen)


def test_cache_hit_composes_with_incremental(tmp_path) -> None:
    run_with_processes(
        _worker_cache_hit_composes_with_incremental,
        nproc=2,
        args=(str(tmp_path),),
    )


def _worker_lru_keeps_steadily_hit_plan(rank, world_size, shared):
    """Hits refresh recency: a steadily-hit structure must survive more cold
    structures passing through than the cache bound (default 4) can hold —
    the round-3 behavior only reordered on store, so 4 cold takes evicted
    the hot plan."""
    from torchsnapshot_tpu import Snapshot, StateDict

    coord, counts = _counting_coordinator()

    def hot_app():
        return {"hot": StateDict(w=np.arange(8, dtype=np.float32) + rank)}

    def cold_app(n):
        return {"cold": StateDict(w=np.arange(n, dtype=np.float32))}

    Snapshot.take(os.path.join(shared, "h0"), hot_app())  # miss: stored
    for i, n in enumerate((4, 5, 6, 7)):  # 4 distinct cold structures
        for k in counts:
            counts[k] = 0
        Snapshot.take(os.path.join(shared, f"h{i + 1}"), hot_app())
        assert counts["all_gather"] == 0, (i, counts)  # hot still hits
        Snapshot.take(os.path.join(shared, f"x{i}"), cold_app(n))
    for k in counts:
        counts[k] = 0
    Snapshot.take(os.path.join(shared, "hfinal"), hot_app())
    # The decisive assertion: after 4 cold structures (== the bound) the
    # steadily-hit plan must still be cached.
    assert counts["all_gather"] == 0, counts
    tgt = {"hot": StateDict(w=np.zeros(8, dtype=np.float32))}
    Snapshot(os.path.join(shared, "hfinal")).restore(tgt)
    assert np.array_equal(tgt["hot"]["w"], np.arange(8, dtype=np.float32) + rank)


def test_lru_keeps_steadily_hit_plan(tmp_path) -> None:
    run_with_processes(
        _worker_lru_keeps_steadily_hit_plan, nproc=2, args=(str(tmp_path),)
    )


def _worker_plan_cache_size_knob(rank, world_size, shared):
    """The retention bound is knob-tunable: at size 1, alternating two
    structures evicts on every take (always a miss); the default keeps both."""
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    coord, counts = _counting_coordinator()

    def app_a():
        return {"a": StateDict(w=np.arange(8, dtype=np.float32))}

    def app_b():
        return {"b": StateDict(w=np.arange(6, dtype=np.float32))}

    with knobs.override_plan_cache_size(1):
        Snapshot.take(os.path.join(shared, "a0"), app_a())
        Snapshot.take(os.path.join(shared, "b0"), app_b())  # evicts a
        for k in counts:
            counts[k] = 0
        Snapshot.take(os.path.join(shared, "a1"), app_a())
        assert counts["all_gather"] >= 1, counts  # miss: was evicted

    # Default bound (4): both structures stay cached.
    Snapshot.take(os.path.join(shared, "a2"), app_a())
    Snapshot.take(os.path.join(shared, "b1"), app_b())
    for k in counts:
        counts[k] = 0
    Snapshot.take(os.path.join(shared, "a3"), app_a())
    Snapshot.take(os.path.join(shared, "b2"), app_b())
    assert counts["all_gather"] == 0, counts


def test_plan_cache_size_knob(tmp_path) -> None:
    run_with_processes(
        _worker_plan_cache_size_knob, nproc=2, args=(str(tmp_path),)
    )


def _worker_restore_constant_round_trips(rank, world_size, shared):
    """Restore coordination is O(1) rounds per rank — one key
    gather+broadcast plus a single post-load barrier, independent of the
    number of app-state keys (the round-3 design paid a key all_gather plus
    a barrier per key on the exact path a preempted pod takes while
    restarting)."""
    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.parallel import store as store_mod

    coord, counts = _counting_coordinator()

    def make_app(nkeys):
        return {
            f"s{i}": StateDict(w=np.arange(8, dtype=np.float32) + rank + i)
            for i in range(nkeys)
        }

    small, big = os.path.join(shared, "small"), os.path.join(shared, "big")
    Snapshot.take(small, make_app(2))
    Snapshot.take(big, make_app(6))

    def measured_restore(path, nkeys):
        tgt = {
            f"s{i}": StateDict(w=np.zeros(8, dtype=np.float32))
            for i in range(nkeys)
        }
        for k in counts:
            counts[k] = 0
        store_mod.reset_op_counts()
        Snapshot(path).restore(tgt)
        # Exclude "delete": the coordinator lazily garbage-collects keys
        # posted by EARLIER collectives at the next post, so delete counts
        # reflect prior-window backlog, not this restore's cost.
        ops = sum(
            v
            for k, v in store_mod.get_op_counts(current_thread_only=True).items()
            if k != "delete"
        )
        for i in range(nkeys):
            assert np.array_equal(
                tgt[f"s{i}"]["w"], np.arange(8, dtype=np.float32) + rank + i
            )
        return dict(counts), ops

    small_counts, small_ops = measured_restore(small, 2)
    big_counts, big_ops = measured_restore(big, 6)
    # Key union + hostname (memory budget) each one gather+broadcast, no
    # all_gathers — the same collective shape and store-op count
    # regardless of key count. The single post-load rendezvous is a
    # LinearBarrier (store ops, counted in small_ops/big_ops below — still
    # one per restore), not a coordinator barrier: a failing or dead peer
    # then fails this rank promptly with rank/phase attribution instead of
    # a bare timeout.
    expected = {"all_gather": 0, "gather": 2, "broadcast": 2, "barrier": 0}
    assert small_counts == expected, small_counts
    assert big_counts == expected, big_counts
    # Timing jitter in the op totals is inherent and load-dependent (NOT a
    # per-key cost): the barrier-release `set` lands on whichever rank
    # arrives last (1 op), and every extra second of cross-rank skew in the
    # LinearBarrier wait loop re-polls `try_get(error)` + `get(done)` (2
    # ops per cycle — observed under full-suite load, where this margin at
    # <= 1 was an order-dependent flake). The decisive signal is an order
    # of magnitude larger: a per-key design pays >= 2 ops per extra key,
    # i.e. >= 8 ops across the 4-key spread measured here — so assert
    # strictly below that, robust to scheduler noise from prior tests.
    assert abs(small_ops - big_ops) < 8, (small_ops, big_ops)


def test_restore_constant_round_trips(tmp_path) -> None:
    run_with_processes(
        _worker_restore_constant_round_trips, nproc=2, args=(str(tmp_path),)
    )


def _worker_keyset_divergence_warns(rank, world_size, shared):
    """Asymmetric app_state keysets are legal (per-rank statefuls) but a
    footgun when a skipped stateful's state_dict() issues collectives; the
    preflight gather carries a keyset checksum so rank 0 SURFACES the
    asymmetry instead of leaving a later hang undiagnosed (ADVICE round 3,
    item 4)."""
    import logging

    from torchsnapshot_tpu import Snapshot, StateDict

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    logging.getLogger("torchsnapshot_tpu.take_plan").addHandler(handler)
    try:
        app = {"common": StateDict(w=np.arange(4, dtype=np.float32))}
        if rank == 1:
            app["only_on_rank1"] = StateDict(x=1)
        Snapshot.take(os.path.join(shared, "c0"), app)
    finally:
        logging.getLogger("torchsnapshot_tpu.take_plan").removeHandler(handler)
    if rank == 0:
        assert any("Rank-divergent app_state keysets" in m for m in records), records
    # The take itself still commits and restores fine.
    tgt = {"common": StateDict(w=np.zeros(4, dtype=np.float32))}
    Snapshot(os.path.join(shared, "c0")).restore(tgt)
    assert np.array_equal(tgt["common"]["w"], np.arange(4, dtype=np.float32))


def test_keyset_divergence_warns(tmp_path) -> None:
    run_with_processes(
        _worker_keyset_divergence_warns, nproc=2, args=(str(tmp_path),)
    )
