"""Prepared-state cache (``prepare_cache.py``): steady-state takes re-bind
cached stagers instead of re-running prepare/partition/batching.

Covered here:

- warm takes HIT (and stay bit-exact vs a cache-disabled take of the same
  state);
- the invalidation matrix: every prepare-affecting input — shapes, dtypes,
  shardings, world size (via the fingerprint), each knob folded into the
  fingerprint, its version, and the storage plugin — forces a full re-prepare;
- the ``in_use`` latch: an overlapping take on the same structure misses
  (store-replace) instead of sharing busy stagers, and completed takes
  unbind their array references so the cache pins nothing between takes;
- rebind-mismatch defense-in-depth falls back to a correct full take;
- a real process kill mid-take on a cache HIT leaves no metadata, gc
  reclaims the debris, and a retake succeeds (the chaos guarantees hold on
  the rebind path exactly as on the cold path);
- 2-rank SPMD: cache engagement is identical across ranks (no rank ever
  waits on a collective its peer skipped).
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, prepare_cache
from torchsnapshot_tpu.parallel.coordinator import get_coordinator
from torchsnapshot_tpu.utils import knobs

from torchsnapshot_tpu.faults import KILL_EXIT_CODE


@pytest.fixture(autouse=True)
def _fresh_cache():
    prepare_cache.reset(get_coordinator())
    yield
    prepare_cache.reset(get_coordinator())


def _state(seed: int = 0, rows: int = 64):
    rng = np.random.default_rng(seed)
    return {
        "model": StateDict(
            w=jnp.asarray(rng.standard_normal((rows, 32)).astype(np.float32)),
            b=jnp.asarray(rng.standard_normal(rows).astype(np.float32)),
            meta={"k": [seed, "x"]},
            step=seed,
        )
    }


def _hits(coord=None) -> int:
    return sum(prepare_cache.stats(coord or get_coordinator())["hits"].values())


def _entries(coord=None) -> int:
    return prepare_cache.stats(coord or get_coordinator())["entries"]


def _restored(path: str):
    out = StateDict()
    Snapshot(path).restore({"model": out})
    return out


def test_second_take_hits_and_restores_bit_exact(tmp_path) -> None:
    s = _state(seed=1)
    Snapshot.take(str(tmp_path / "s0"), s)
    assert _entries() == 1 and _hits() == 0

    s2 = _state(seed=2)
    Snapshot.take(str(tmp_path / "s1"), s2)
    assert _hits() == 1

    # Bit-exact vs a cache-disabled take of the identical state.
    with knobs.override_prepared_cache(False):
        Snapshot.take(str(tmp_path / "ref"), _state(seed=2))
    got, ref = _restored(str(tmp_path / "s1")), _restored(str(tmp_path / "ref"))
    for k in ("w", "b"):
        assert np.array_equal(
            np.asarray(got[k]).view(np.uint8), np.asarray(ref[k]).view(np.uint8)
        ), k
    assert got["meta"] == ref["meta"] and got["step"] == ref["step"]
    assert Snapshot(str(tmp_path / "s1")).verify() == {}


def test_async_take_hits_and_restores_bit_exact(tmp_path) -> None:
    s = _state(seed=3)
    Snapshot.async_take(str(tmp_path / "a0"), s).wait()
    Snapshot.async_take(str(tmp_path / "a1"), _state(seed=4)).wait()
    assert _hits() == 1
    got = _restored(str(tmp_path / "a1"))
    ref = _state(seed=4)["model"]
    assert np.array_equal(np.asarray(got["w"]), np.asarray(ref["w"]))
    assert got["step"] == 4


def test_primitive_values_refresh_on_hit(tmp_path) -> None:
    """PrimitiveEntry embeds its value in the manifest — the one part of a
    cached local manifest that must be recomputed per take."""
    s = _state(seed=1)
    Snapshot.take(str(tmp_path / "s0"), s)
    s["model"]["step"] = 999
    Snapshot.take(str(tmp_path / "s1"), s)
    assert _hits() == 1
    assert _restored(str(tmp_path / "s1"))["step"] == 999


@pytest.mark.parametrize(
    "mutate",
    [
        "shape",
        "dtype",
        "leaf_set",
        "compression",
        "hash_chunk",
        "device_batching",
        "capture_mode",
        "batching",
    ],
)
def test_invalidation_matrix(tmp_path, mutate) -> None:
    """Every prepare-affecting input flip forces a miss (full re-prepare)
    AND the resulting snapshot stays bit-exact vs an uncached take."""
    Snapshot.take(str(tmp_path / "warm0"), _state(seed=5))
    Snapshot.take(str(tmp_path / "warm1"), _state(seed=5))
    assert _hits() == 1, "precondition: the unmutated structure hits"

    import contextlib

    override = contextlib.nullcontext()
    s = _state(seed=6)
    if mutate == "shape":
        s["model"]["w"] = jnp.zeros((8, 32), dtype=jnp.float32)
    elif mutate == "dtype":
        s["model"]["w"] = jnp.zeros((64, 32), dtype=jnp.bfloat16)
    elif mutate == "leaf_set":
        s["model"]["extra"] = jnp.ones((4,), dtype=jnp.float32)
    elif mutate == "compression":
        override = knobs.override_compression("zlib")
    elif mutate == "hash_chunk":
        override = knobs.override_hash_chunk_bytes(1 << 20)
    elif mutate == "device_batching":
        override = knobs.override_device_batching(
            not knobs.is_device_batching_enabled()
        )
    elif mutate == "capture_mode":
        override = knobs.override_async_capture("donate")
    elif mutate == "batching":
        override = knobs._override_env("TORCHSNAPSHOT_TPU_ENABLE_BATCHING", "1")

    hits_before = _hits()
    with override:
        Snapshot.take(str(tmp_path / "mut"), s)
        assert _hits() == hits_before, f"{mutate}: expected a miss"
        with knobs.override_prepared_cache(False):
            Snapshot.take(str(tmp_path / "ref"), s)
    got, ref = _restored(str(tmp_path / "mut")), _restored(str(tmp_path / "ref"))
    assert np.array_equal(
        np.asarray(got["w"]).view(np.uint8), np.asarray(ref["w"]).view(np.uint8)
    )
    assert Snapshot(str(tmp_path / "mut")).verify() == {}


@pytest.mark.parametrize("cache", ["plan", "prepared"])
def test_entries_of_an_older_fingerprint_version_miss(
    tmp_path, monkeypatch, cache
) -> None:
    """What a process cached under fingerprint version 4 (the knob
    signature that still carried the stream knobs) never satisfies a
    lookup of the current version, in the plan cache or the prepared one."""
    from torchsnapshot_tpu import take_plan

    current = take_plan._FINGERPRINT_VERSION
    assert current > 4
    coord = get_coordinator()
    if cache == "plan":
        flat = {"m/w": np.zeros((4, 4), np.float32)}
        monkeypatch.setattr(take_plan, "_FINGERPRINT_VERSION", 4)
        old = take_plan.compute_fingerprint(flat, 1, [])
        take_plan.store_plan(coord, old, take_plan.CachedPlan(0, {}, {}, None))
        assert take_plan.probe_plan(coord, old) is not None
        monkeypatch.setattr(take_plan, "_FINGERPRINT_VERSION", current)
        new = take_plan.compute_fingerprint(flat, 1, [])
        assert new != old
        assert take_plan.probe_plan(coord, new) is None
        return
    monkeypatch.setattr(take_plan, "_FINGERPRINT_VERSION", 4)
    Snapshot.take(str(tmp_path / "old0"), _state(seed=5))
    Snapshot.take(str(tmp_path / "old1"), _state(seed=5))
    assert _hits() == 1, "precondition: the old version hits itself"
    monkeypatch.setattr(take_plan, "_FINGERPRINT_VERSION", current)
    Snapshot.take(str(tmp_path / "new"), _state(seed=6))
    assert _hits() == 1, "an entry of the old version served the new one"
    assert Snapshot(str(tmp_path / "new")).verify() == {}


def test_plugin_swap_is_a_different_entry(tmp_path) -> None:
    """The cache key includes the storage plugin class: a state prepared
    for one plugin must not serve another."""
    s = _state(seed=7)
    Snapshot.take(str(tmp_path / "fs0"), s)
    with knobs.override_faults("op=read,kind=fail,path=__none__"):
        # The fault wrapper changes the plugin class seen by the scheduler.
        Snapshot.take(str(tmp_path / "fault0"), _state(seed=7))
    assert _entries() == 2
    assert _hits() == 0


def test_donate_capture_roundtrip_and_hit(tmp_path) -> None:
    """Under ASYNC_CAPTURE=donate the stall path never forks device
    buffers; repeated takes hit and stay correct as long as the caller
    honors the no-donate-until-commit contract (this test keeps the arrays
    alive across wait())."""
    with knobs.override_async_capture("donate"):
        s = _state(seed=8)
        Snapshot.async_take(str(tmp_path / "d0"), s).wait()
        s["model"]["w"] = s["model"]["w"] + 1.0
        pending = Snapshot.async_take(str(tmp_path / "d1"), s)
        pending.wait()
        assert _hits() == 1
        got = _restored(str(tmp_path / "d1"))
        assert np.array_equal(np.asarray(got["w"]), np.asarray(s["model"]["w"]))


def test_overlapping_takes_miss_on_busy_entry(tmp_path) -> None:
    """A second take launched while the first still holds the entry busy
    must MISS (store-replace), not share in-flight stagers."""
    s = _state(seed=9)
    Snapshot.async_take(str(tmp_path / "o0"), s).wait()
    p1 = Snapshot.async_take(str(tmp_path / "o1"), _state(seed=10))
    # While p1 is pending its entry is busy; this take must not hit it.
    p2 = Snapshot.async_take(str(tmp_path / "o2"), _state(seed=11))
    p1.wait()
    p2.wait()
    st = prepare_cache.stats(get_coordinator())
    assert sum(st["hits"].values()) <= 1  # p2 hit only if p1 released first
    for name, seed in (("o1", 10), ("o2", 11)):
        got = _restored(str(tmp_path / name))
        assert np.array_equal(
            np.asarray(got["w"]), np.asarray(_state(seed=seed)["model"]["w"])
        ), name


def test_release_unbinds_array_references(tmp_path) -> None:
    """Completed takes leave no array refs in the cached stagers — the
    cache must not pin device/host buffers between takes."""
    from torchsnapshot_tpu.io_preparers.array import ArrayBufferStager
    from torchsnapshot_tpu.io_preparers.object import ObjectBufferStager

    Snapshot.take(str(tmp_path / "u0"), _state(seed=12))
    coord = get_coordinator()
    cache = getattr(coord, "_prepared_take_cache")
    assert len(cache) == 1
    entry = next(iter(cache.values()))
    assert not entry.in_use
    for reqs in entry.leaf_index.values():
        for req in reqs:
            stager = req.buffer_stager
            if isinstance(stager, ArrayBufferStager):
                assert stager.arr is None
            elif isinstance(stager, ObjectBufferStager):
                assert stager.obj is None


def test_rebind_mismatch_falls_back_to_full_prepare(tmp_path) -> None:
    """Defense in depth: a corrupted cached plan (kind disagreement) must
    degrade to a correct full re-prepare, never a wrong snapshot."""
    Snapshot.take(str(tmp_path / "m0"), _state(seed=13))
    coord = get_coordinator()
    cache = getattr(coord, "_prepared_take_cache")
    entry = next(iter(cache.values()))
    path = next(p for p, (kind, _) in entry.leaf_kinds.items() if kind == "array")
    entry.leaf_kinds[path] = ("object", False)
    s = _state(seed=14)
    Snapshot.take(str(tmp_path / "m1"), s)
    got = _restored(str(tmp_path / "m1"))
    assert np.array_equal(np.asarray(got["w"]), np.asarray(s["model"]["w"]))
    assert Snapshot(str(tmp_path / "m1")).verify() == {}


def test_lru_eviction_respects_size_knob(tmp_path) -> None:
    with knobs.override_prepared_cache_size(1):
        Snapshot.take(str(tmp_path / "e0"), _state(seed=1))
        big = {"model": StateDict(w=jnp.zeros((128, 32), jnp.float32))}
        Snapshot.take(str(tmp_path / "e1"), big)
        assert _entries() == 1
        # The first structure was evicted: taking it again misses.
        Snapshot.take(str(tmp_path / "e2"), _state(seed=2))
        assert _hits() == 0


def test_disabled_cache_stores_nothing(tmp_path) -> None:
    with knobs.override_prepared_cache(False):
        Snapshot.take(str(tmp_path / "n0"), _state(seed=1))
        Snapshot.take(str(tmp_path / "n1"), _state(seed=1))
    assert _entries() == 0


def test_chaos_kill_mid_take_on_cache_hit(tmp_path) -> None:
    """Process death mid-write on a cache-HIT take: no metadata for the
    torn take, the prior committed snapshot stays restorable, gc reclaims
    the debris, and a fresh process retakes successfully."""
    parent = str(tmp_path)
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np\n"
        "from torchsnapshot_tpu import Snapshot, StateDict\n"
        "from torchsnapshot_tpu import prepare_cache\n"
        "from torchsnapshot_tpu.parallel.coordinator import get_coordinator\n"
        "from torchsnapshot_tpu.utils import knobs\n"
        "def state(seed):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    return {'s': StateDict(w=rng.standard_normal(512).astype(np.float32), step=seed)}\n"
        "base = os.environ['CHAOS_DIR']\n"
        "Snapshot.take(os.path.join(base, 'prev'), state(1))\n"
        "assert prepare_cache.stats(get_coordinator())['entries'] == 1\n"
        "with knobs.override_faults('op=write,at=1,kind=kill'):\n"
        "    Snapshot.take(os.path.join(base, 'cur'), state(2))\n"
    )
    env = dict(os.environ, CHAOS_DIR=parent)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TORCHSNAPSHOT_TPU_TRACE", None)
    result = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, timeout=120
    )
    assert result.returncode == KILL_EXIT_CODE, result.stderr.decode()[-2000:]
    assert not os.path.exists(os.path.join(parent, "cur", ".snapshot_metadata"))
    assert Snapshot(os.path.join(parent, "prev")).verify() == {}
    got = StateDict()
    Snapshot(os.path.join(parent, "prev")).restore({"s": got})
    assert got["step"] == 1
    Snapshot.gc(parent, dry_run=False)
    assert not os.path.exists(os.path.join(parent, "cur"))
    snap = Snapshot.take(os.path.join(parent, "cur"), _state(seed=2))
    assert snap.verify() == {}


def _worker_spmd_hits(rank: int, world_size: int, shared: str) -> None:
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu import prepare_cache as pc
    from torchsnapshot_tpu.parallel.coordinator import get_coordinator

    coord = get_coordinator()

    def state(step):
        return {
            "train": StateDict(
                w=np.arange(64, dtype=np.float32) + rank + step, step=step
            ),
            "repl": StateDict(table=np.arange(8, dtype=np.int64) + step),
        }

    # Take 1: plan-cache miss -> prepared cache disengaged at world>1.
    # Take 2: plan-cache hit -> prepared cache stores. Take 3: prepared hit.
    for step in range(3):
        Snapshot.take(
            os.path.join(shared, f"s{step}"),
            state(step),
            replicated=["repl/**"],
        )
    st = pc.stats(coord)
    assert st["entries"] == 1, (rank, st)
    assert sum(st["hits"].values()) == 1, (rank, st)
    out_t, out_r = StateDict(), StateDict()
    Snapshot(os.path.join(shared, "s2")).restore({"train": out_t, "repl": out_r})
    assert np.array_equal(out_t["w"], np.arange(64, dtype=np.float32) + rank + 2)
    assert np.array_equal(out_r["table"], np.arange(8, dtype=np.int64) + 2)


@pytest.mark.multiprocess
def test_spmd_cache_hits_identical_across_ranks(tmp_path) -> None:
    from torchsnapshot_tpu.test_utils import run_with_processes

    run_with_processes(_worker_spmd_hits, nproc=2, args=(str(tmp_path),))


@pytest.mark.slow
def test_steady_state_warm_stall_within_target(tmp_path) -> None:
    """The tentpole's acceptance number, in CI-runnable form: repeated
    async takes of the same tree under donate capture must hold the WARM
    (cache-hit) stall at or under the 0.1s target, with the cold
    (store-on-miss) take excluded. Sized small so the bound holds on shared
    CI runners."""
    import time

    from torchsnapshot_tpu import snapshot as snapshot_mod

    s = _state(seed=11, rows=256)
    stalls = []
    with knobs.override_async_capture("donate"):
        for step in range(4):
            t0 = time.perf_counter()
            pend = Snapshot.async_take(str(tmp_path / f"step_{step}"), s)
            stalls.append(time.perf_counter() - t0)
            phases = dict(snapshot_mod.LAST_TAKE_PHASES)
            pend.wait()
    assert _hits() == 3
    # Steps 1+ ran the rebind path; every warm stall holds the target.
    warm = stalls[1:]
    assert max(warm) <= 0.1, stalls
    # The decomposition attributes the warm prepare to the cache-hit span.
    assert "stage.prepare.cache_hit" in phases, sorted(phases)
    assert phases["stage.prepare.cache_hit"] <= 0.1
