"""Restore measured from inside, and the library's spans on the profiler's
clock.

``Snapshot.restore`` splits its own time into plan / fetch / verify /
consume / place / load / idle (``restore_times.py``): the numbers land in
``LAST_RESTORE_STATS`` and in the restore artifact, the same intervals are
the session's spans, and the spans that are synchronous on a thread are
``tss.*`` events of a running ``jax.profiler`` trace. CPU runs: counts,
orderings and identities only, never a rate.
"""

import glob
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, restore_times, telemetry
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.utils import knobs

NEW_KEYS = (
    "plan_s", "fetch_busy_s", "fetch_sum_s", "fetch_wait_s", "verify_busy_s",
    "consume_busy_s", "consume_sum_s", "consume_wait_s", "place_busy_s",
    "place_bytes", "place_wait_s", "place_retry_s", "targets_consumed",
    "load_s", "idle_s", "pipeline_s", "mount_busy_s", "mount_sum_s", "mount_bytes",
    "pread_busy_s", "pread_sum_s", "reader_copy_sum_s",
)


def _mixed_tree():
    """Plain, chunked-on-restore and sharded jax leaves, a host leaf, a
    primitive and an object: every consumer and both finalizers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    plain = jnp.arange(64 * 1024, dtype=jnp.float32).reshape(64, 1024)
    big = jax.random.normal(jax.random.PRNGKey(1), (512, 1024), dtype=jnp.bfloat16)
    sharded = jax.device_put(
        jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64),
        NamedSharding(mesh, P("a", "b")),
    )
    return mesh, dict(plain=plain, big=big, sharded=sharded, host=np.arange(10), step=7, obj={"k": (1, 2)})


def _targets(mesh, tree):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    return dict(
        plain=jnp.zeros_like(tree["plain"]),
        big=jnp.zeros_like(tree["big"]),
        # Restored onto the transposed layout: every target shard reads
        # from two saved shards.
        sharded=jax.device_put(jnp.zeros((64, 64), jnp.float32), NamedSharding(mesh, P("b", "a"))),
        host=np.zeros(10, np.int64),
        step=0,
        obj=None,
    )


def _device_bytes(arr) -> int:
    return sum(s.data.nbytes for s in arr.addressable_shards)


def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


@pytest.fixture
def saved(tmp_path):
    mesh, tree = _mixed_tree()
    path = str(tmp_path / "ckpt")
    Snapshot.take(path, {"s": StateDict(**tree), "progress": StateDict(epoch=3)})
    return path, mesh, tree


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "phase_split"])
def test_restore_splits_its_own_time(saved, overlap) -> None:
    path, mesh, tree = saved
    targets = StateDict(**_targets(mesh, tree))
    want_bytes = sum(_device_bytes(targets[k]) for k in ("plain", "big", "sharded"))
    with knobs.override_restore_overlap(overlap):
        Snapshot(path).restore({"s": targets, "progress": StateDict(epoch=0)})
    for k in ("plain", "big", "sharded", "host"):
        assert np.array_equal(_bits(targets[k]), _bits(tree[k])), k
    assert targets["step"] == 7 and targets["obj"] == {"k": (1, 2)}

    stats = snapshot_mod.LAST_RESTORE_STATS
    for key in NEW_KEYS:
        assert isinstance(stats[key], float), key
        assert stats[key] >= 0.0, key
    # The old keys stay what they were.
    assert stats["requests"] >= 5 and stats["bytes_read"] > 0 and stats["read_wall_s"] > 0
    # A busy time is the measure of a union, a sum the sum of durations.
    assert stats["fetch_busy_s"] <= stats["fetch_sum_s"] + 1e-9
    assert stats["consume_busy_s"] <= stats["consume_sum_s"] + 1e-9
    assert 0 < stats["fetch_busy_s"] and 0 < stats["consume_busy_s"] and 0 < stats["place_busy_s"]
    assert stats["verify_busy_s"] == 0.0  # origin reads are not verified by default
    # The three stretches cover the restore: nothing of it is nobody's.
    covered = stats["plan_s"] + stats["pipeline_s"] + stats["load_s"]
    assert covered == pytest.approx(stats["wall_s"], rel=0.05)
    assert stats["idle_s"] <= stats["pipeline_s"]
    assert stats["place_bytes"] == want_bytes
    assert stats["place_retry_s"] == 0.0 and stats["targets_consumed"] == 0.0

    with open(os.path.join(path, ".telemetry", "restore_rank_0.json")) as f:
        artifact = json.load(f)
    block = artifact["restore_stats_s"]
    assert set(NEW_KEYS) | {"bytes_read", "read_wall_s", "requests", "wall_s"} <= set(block)
    assert block["place_bytes"] == want_bytes and block["requests"] == stats["requests"]
    # Written before the closing interval: all of the restore but that.
    assert block["wall_s"] <= stats["wall_s"] and block["load_s"] <= stats["load_s"]
    # A handful of phase lines, not one per leaf.
    assert "restore.place" not in artifact["phases_s"] and "restore.plan" in artifact["phases_s"]


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "phase_split"])
def test_mount_keys_count_the_engines_chunk_reads(saved, monkeypatch, overlap) -> None:
    """``mount_*``: the union and the sum of the intervals in which a chunk
    read of the native engine was on the mount, and the bytes they
    delivered: what says whether the read depth engages."""
    from torchsnapshot_tpu import native
    from torchsnapshot_tpu.storage_plugins import fs as fs_mod

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    path, mesh, tree = saved
    targets = StateDict(**_targets(mesh, tree))
    monkeypatch.setattr(fs_mod, "_READ_CHUNK_BYTES", 64 * 1024)
    native_bytes, chunk_reads = [], []
    real_read_into = native.read_into

    def counting_read_into(lib, path, dst, **kwargs):
        out = real_read_into(lib, path, dst, **kwargs)
        native_bytes.append(memoryview(dst).nbytes)
        chunk_reads.extend(out)
        return out

    monkeypatch.setattr(native, "read_into", counting_read_into)
    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_restore_overlap(overlap):
        Snapshot(path).restore({"s": targets})
    for k in ("plain", "big", "sharded", "host"):
        assert np.array_equal(_bits(targets[k]), _bits(tree[k])), k
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["mount_bytes"] == sum(native_bytes) > tree["big"].nbytes
    assert 0.0 < stats["mount_busy_s"] <= stats["mount_sum_s"] + 1e-9
    assert stats["mount_sum_s"] == pytest.approx(sum(t1 - t0 for t0, t1, _, _ in chunk_reads))
    assert stats["pread_sum_s"] == pytest.approx(sum(p1 - p0 for _, _, p0, p1 in chunk_reads))
    # A chunk read is on the mount only while its fetch is open.
    assert stats["mount_busy_s"] <= stats["fetch_busy_s"] + 1e-9
    assert len(chunk_reads) > len(native_bytes)  # "big" is sixteen chunks


def test_verification_has_its_own_interval(saved) -> None:
    path, mesh, tree = saved
    targets = StateDict(**_targets(mesh, tree))
    with knobs.override_verify_reads("all"):
        Snapshot(path).restore({"s": targets})
    assert snapshot_mod.LAST_RESTORE_STATS["verify_busy_s"] > 0.0
    spans = Snapshot.last_telemetry.spans(name="scheduler.verify")
    assert spans and all(sp.parent_id is not None for sp in spans)


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "phase_split"])
def test_place_spans_are_one_per_jax_leaf_and_never_overlap(saved, overlap) -> None:
    path, mesh, tree = saved
    targets = StateDict(**_targets(mesh, tree))
    with knobs.override_restore_overlap(overlap):
        Snapshot(path).restore({"s": targets})
    tm = Snapshot.last_telemetry
    places = sorted(tm.spans(name="restore.place"), key=lambda sp: sp.ts)
    assert sorted(sp.attrs["path"] for sp in places) == ["s/big", "s/plain", "s/sharded"]
    assert {sp.tid for sp in places} == {threading.get_ident()}  # the loop thread is the caller's
    for a, b in zip(places, places[1:]):
        assert a.ts + a.dur <= b.ts
    by_path = {sp.attrs["path"]: sp.attrs["nbytes"] for sp in places}
    assert by_path == {f"s/{k}": _device_bytes(targets[k]) for k in ("plain", "big", "sharded")}
    # The parts of a request's documented spans are their children.
    ids = {sp.span_id: sp.name for sp in tm.spans()}
    assert {ids[sp.parent_id] for sp in tm.spans(name="scheduler.fetch")} == {"scheduler.read_io"}
    assert {ids[sp.parent_id] for sp in tm.spans(name="scheduler.consume_work")} == {"scheduler.consume"}
    assert {ids[sp.parent_id] for sp in tm.spans(name="restore.plan") if sp.parent_id} == {"restore.load_stateful"}
    consumes = tm.spans(name="scheduler.consume_work")
    assert len(consumes) >= 5 and threading.get_ident() in {sp.tid for sp in consumes}  # the object, inline
    assert len({sp.tid for sp in consumes}) >= 2  # arrays, on consumer threads
    # With overlap the finalizer runs in the consume coroutine: scheduler.consume
    # holds it, restore.place says how much of it.
    inside = [
        any(c.ts <= p.ts and p.ts + p.dur <= c.ts + c.dur for c in tm.spans(name="scheduler.consume"))
        for p in places
    ]
    assert all(inside) if overlap else not any(inside)


def test_a_failed_allocation_is_counted_where_it_costs(tmp_path, monkeypatch) -> None:
    import jax
    import jax.numpy as jnp

    src = {f"w{i}": jnp.arange(256, dtype=jnp.float32) + i for i in range(3)}
    snap = Snapshot.take(str(tmp_path / "ckpt"), {"s": StateDict(**src)})
    real_device_put = jax.device_put
    failed = []

    def full_hbm_twice(x, *args, **kwargs):
        import time

        fresh = not any(x is seen for seen in failed)
        if fresh and len(failed) < 2 and isinstance(x, np.ndarray) and x.shape == (256,):
            failed.append(x)  # the first attempt of two leaves; the second try goes through
            time.sleep(0.02)  # what the refused attempt took
            raise RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer (simulated)")
        return real_device_put(x, *args, **kwargs)

    targets = StateDict(**{k: jnp.zeros(256, jnp.float32) for k in src})
    monkeypatch.setattr(jax, "device_put", full_hbm_twice)
    snap.restore({"s": targets})
    monkeypatch.undo()
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["targets_consumed"] == 2.0
    assert Snapshot.last_telemetry.metrics.as_dict()["restore.targets_consumed"] == 2
    assert 0.04 <= stats["place_retry_s"] <= stats["place_busy_s"]
    for k, v in src.items():
        assert np.array_equal(np.asarray(targets[k]), np.asarray(v))


def test_outside_a_restore_nothing_is_recorded(saved) -> None:
    path, _, tree = saved
    assert restore_times.get_active() is None
    with knobs.override_telemetry_artifacts(False):
        tm = telemetry.Telemetry()
        prev = telemetry.activate(tm)
        try:
            got = Snapshot(path).read_object("0/s/plain")
        finally:
            telemetry.deactivate(tm, prev)
    assert np.array_equal(_bits(got), _bits(tree["plain"]))
    names = {sp.name for sp in tm.spans()}
    assert "scheduler.read_io" in names
    assert not names & {"scheduler.fetch", "scheduler.consume_work", "restore.place"}
    assert restore_times.get_active() is None


def test_restore_with_telemetry_off_still_fills_the_stats(saved) -> None:
    path, mesh, tree = saved
    targets = StateDict(**_targets(mesh, tree))
    with knobs.override_telemetry_artifacts(False):
        assert telemetry.get_active() is None
        Snapshot(path).restore({"s": targets})
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["place_busy_s"] > 0 and stats["fetch_busy_s"] > 0 and stats["consume_busy_s"] > 0
    assert np.array_equal(_bits(targets["big"]), _bits(tree["big"]))


def test_summary_is_interval_algebra() -> None:
    times = restore_times.RestoreTimes()
    times.add_interval("fetch", 0.0, 4.0)
    times.add_interval("fetch", 2.0, 6.0)
    times.add_interval("consume", 5.0, 7.0)
    times.add_interval("place", 9.0, 10.0)
    times.add_interval("place", 20.0, 21.0)  # outside any pipeline: busy, but not the pipeline's
    times.add_interval("plan", -1.0, 0.0)
    times.add_pipeline_window(0.0, 12.0)
    times.add("place_bytes", 10)
    got = times.summary()
    assert got["fetch_busy_s"] == 6.0 and got["fetch_sum_s"] == 8.0
    assert got["consume_busy_s"] == got["consume_sum_s"] == 2.0
    assert got["place_busy_s"] == 2.0 and got["plan_s"] == 1.0 and got["pipeline_s"] == 12.0
    assert got["idle_s"] == 12.0 - (7.0 + 1.0)
    assert got["place_bytes"] == 10.0 and all(isinstance(v, float) for v in got.values())


# ----------------------------------------------------- the profiler's clock


def _host_events(trace_dir):
    """{event name: set of (plane, line) it was seen on} for tss.* events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("tss.") or ev.name == "test.main":
                    seen.setdefault(ev.name, set()).add((plane.name, i))
    return seen


def test_library_spans_show_in_a_profiler_trace_on_the_thread_that_ran_them(saved, tmp_path) -> None:
    import jax
    import jax.numpy as jnp

    path, mesh, tree = saved
    trace_dir = str(tmp_path / "trace")
    dev = {"w": jnp.arange(1 << 16, dtype=jnp.float32), "v": jnp.ones((256, 256), jnp.bfloat16)}
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("test.main"):
            Snapshot.async_take(str(tmp_path / "ckpt2"), {"s": StateDict(**dev)}).wait()
            Snapshot(path).restore({"s": StateDict(**_targets(mesh, tree))})
    finally:
        jax.profiler.stop_trace()
    seen = _host_events(trace_dir)
    (main,) = seen["test.main"]
    on_main = {name for name, where in seen.items() if main in where}
    phases = {
        "gather_keys_and_flatten", "preflight", "prepare_write", "partition",
        "d2h_hint", "manifest_gather", "memory_budget", "capture",
    }
    # The stall's phases, the plan, the finalizers and the load ran on the
    # caller's thread; the consumers, the lanes and the commit did not.
    assert {f"tss.{p}" for p in phases} <= on_main
    assert {"tss.restore.plan", "tss.restore.place", "tss.restore.load_state_dict"} <= on_main
    for name in ("tss.stage.d2h", "tss.take.commit", "tss.storage.read_work"):
        assert seen[name] and main not in seen[name], name
    assert seen["tss.scheduler.consume_work"] - {main}
    # Spans that live across awaits on the loop thread are not bridged.
    assert not {"tss.scheduler.read_io", "tss.scheduler.consume", "tss.scheduler.fetch", "tss.storage.read"} & set(seen)


def test_async_commit_has_its_span(tmp_path) -> None:
    import jax.numpy as jnp

    tm = telemetry.Telemetry()
    Snapshot.async_take(
        str(tmp_path / "ckpt"), {"s": StateDict(w=jnp.arange(1024, dtype=jnp.float32))}, _telemetry=tm
    ).wait()
    (commit,) = tm.spans(name="take.commit")
    assert commit.cat == "take" and commit.dur > 0 and commit.tid != threading.get_ident()
    assert Snapshot.last_telemetry is tm


def test_bridge_and_phase_annotations_without_a_session_or_jax_cost_nothing() -> None:
    assert telemetry.get_active() is None
    assert telemetry.span("restore.place", "restore.leaf", True) is telemetry.NOOP_SPAN
    tracker = telemetry.PhaseTracker(first="gather_keys_and_flatten")
    assert tracker._ann is None
    tracker.mark("gather_keys_and_flatten", then="preflight")
    assert tracker._ann is None and tracker.durations["gather_keys_and_flatten"] >= 0


def test_telemetry_imports_without_jax() -> None:
    code = (
        "import sys\n"
        "import torchsnapshot_tpu.telemetry as t\n"
        "from torchsnapshot_tpu.telemetry import core\n"
        "assert 'jax' not in sys.modules, 'telemetry imported jax'\n"
        "assert core.open_annotation('x') is None\n"
        "tm = t.Telemetry(); prev = t.activate(tm)\n"
        "with t.span('a', 'c', True):\n"
        "    pass\n"
        "tr = t.PhaseTracker(first='p'); tr.mark('p', then='q'); tr.mark('q')\n"
        "t.deactivate(tm, prev)\n"
        "assert [s.name for s in tm.spans()] == ['a', 'p', 'q']\n"
        "assert 'jax' not in sys.modules\n"
    )
    # The package's __init__ imports jax-using modules; telemetry itself is
    # loaded alone, as a layer below them may.
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    loader = (
        "import importlib.util, sys, types, os\n"
        f"root = {root!r}\n"
        "pkg = types.ModuleType('torchsnapshot_tpu'); pkg.__path__ = [os.path.join(root, 'torchsnapshot_tpu')]\n"
        "sys.modules['torchsnapshot_tpu'] = pkg\n"
    )
    # The fleet bus's ``auto`` mode asks jax whether a coordination service is
    # up, at a process's first phase mark; that is the bus's business.
    env = dict(os.environ, TORCHSNAPSHOT_TPU_FLEET_TELEMETRY="0")
    done = subprocess.run(
        [sys.executable, "-c", loader + code], capture_output=True, text=True, timeout=120, env=env
    )
    assert done.returncode == 0, done.stderr
