"""Incremental snapshots: take(base=...) hard-links unchanged objects.

Beyond the reference's capability surface. The dedup identity is
(size, sha256) recorded in the base's checksum sidecars; matching
objects are hard-linked (same inode) instead of rewritten, so checkpoints
of mostly-frozen state (LoRA, partial finetunes) cost only the changed
bytes. Deleting the base later must NOT invalidate the incremental.
"""

import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.utils import knobs


def _state(step: int):
    frozen = {
        f"frozen{i}": np.arange(1000, dtype=np.float32) + i for i in range(4)
    }
    return StateDict(**frozen, lora=np.full((100,), step, np.float32), step=step)


def test_incremental_links_unchanged_objects(tmp_path) -> None:
    base = str(tmp_path / "step0")
    inc = str(tmp_path / "step1")
    Snapshot.take(base, {"m": _state(0)})
    Snapshot.take(inc, {"m": _state(1)}, base=base)

    for i in range(4):
        b = os.stat(os.path.join(base, "0", "m", f"frozen{i}"))
        n = os.stat(os.path.join(inc, "0", "m", f"frozen{i}"))
        assert b.st_ino == n.st_ino, f"frozen{i} not hard-linked"
    # The changed array is a fresh object.
    b = os.stat(os.path.join(base, "0", "m", "lora"))
    n = os.stat(os.path.join(inc, "0", "m", "lora"))
    assert b.st_ino != n.st_ino

    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert np.array_equal(out["lora"], np.full((100,), 1, np.float32))
    assert np.array_equal(out["frozen2"], np.arange(1000, dtype=np.float32) + 2)
    assert out["step"] == 1
    assert Snapshot(inc).verify() == {}


def test_incremental_survives_base_deletion(tmp_path) -> None:
    import shutil

    base = str(tmp_path / "step0")
    inc = str(tmp_path / "step1")
    Snapshot.take(base, {"m": _state(0)})
    Snapshot.take(inc, {"m": _state(1)}, base=base)
    shutil.rmtree(base)
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert np.array_equal(out["frozen0"], np.arange(1000, dtype=np.float32))
    assert Snapshot(inc).verify() == {}


def test_incremental_async_take(tmp_path) -> None:
    import jax
    import jax.numpy as jnp

    base = str(tmp_path / "step0")
    inc = str(tmp_path / "step1")
    frozen = jax.device_put(jnp.arange(512, dtype=jnp.bfloat16))
    app0 = {"m": StateDict(frozen=frozen, head=jnp.zeros(16))}
    app1 = {"m": StateDict(frozen=frozen, head=jnp.ones(16))}
    Snapshot.async_take(base, app0).wait()
    Snapshot.async_take(inc, app1, base=base).wait()
    b = os.stat(os.path.join(base, "0", "m", "frozen"))
    n = os.stat(os.path.join(inc, "0", "m", "frozen"))
    assert b.st_ino == n.st_ino
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert np.array_equal(np.asarray(out["head"]), np.ones(16, np.float32))
    assert Snapshot(inc).verify() == {}


def test_incremental_base_without_digests_falls_back(tmp_path, caplog) -> None:
    base = str(tmp_path / "step0")
    inc = str(tmp_path / "step1")
    with knobs.override_checksums(False):
        Snapshot.take(base, {"m": _state(0)})
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
        Snapshot.take(inc, {"m": _state(0)}, base=base)
    assert any("full snapshot" in r.message for r in caplog.records)
    # Full (non-linked) but correct.
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert out["step"] == 0


def test_incremental_identical_state_links_everything(tmp_path) -> None:
    base = str(tmp_path / "a")
    inc = str(tmp_path / "b")
    Snapshot.take(base, {"m": _state(5)})
    Snapshot.take(inc, {"m": _state(5)}, base=base)
    for name in ["frozen0", "frozen1", "frozen2", "frozen3", "lora"]:
        b = os.stat(os.path.join(base, "0", "m", name))
        n = os.stat(os.path.join(inc, "0", "m", name))
        assert b.st_ino == n.st_ino, name
    assert Snapshot(inc).verify() == {}


def test_invalid_base_never_aborts_take(tmp_path, caplog) -> None:
    """A typo'd/unsupported base URL must warn and fall back to a full
    snapshot — never fail the checkpoint itself."""
    path = str(tmp_path / "ckpt")
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
        Snapshot.take(path, {"m": _state(0)}, base="foo://not/a/thing")
    assert any("full snapshot" in r.message for r in caplog.records)
    out = StateDict()
    Snapshot(path).restore({"m": out})
    assert out["step"] == 0


def test_dedup_digests_knob_off_skips_sha_and_dedup(tmp_path) -> None:
    """With dedup digests off, sidecars record [crc, size, None]; such a
    base warns and the take stays full (no links), but verify still works."""
    import json

    base = str(tmp_path / "a")
    inc = str(tmp_path / "b")
    with knobs.override_dedup_digests(False):
        Snapshot.take(base, {"m": _state(0)})
        recorded = json.loads(
            open(os.path.join(base, ".checksums.0")).read()
        )
        assert all(v[2] is None for v in recorded.values())
        Snapshot.take(inc, {"m": _state(0)}, base=base)
    b = os.stat(os.path.join(base, "0", "m", "frozen0"))
    n = os.stat(os.path.join(inc, "0", "m", "frozen0"))
    assert b.st_ino != n.st_ino  # no links without digests
    assert Snapshot(base).verify() == {}
    assert Snapshot(inc).verify() == {}


def test_incremental_dedups_batched_slabs_by_content(tmp_path) -> None:
    """Slab objects get fresh batched/<uuid> paths every take; identical
    slab bytes must still dedup via the content-keyed index."""
    base = str(tmp_path / "a")
    inc = str(tmp_path / "b")
    arrs = {f"p{i}": np.arange(50, dtype=np.float32) + i for i in range(10)}
    with knobs.override_batching_enabled(True):
        Snapshot.take(base, {"m": StateDict(**arrs)})
        Snapshot.take(inc, {"m": StateDict(**arrs)}, base=base)
    import glob as _glob

    (base_slab,) = _glob.glob(os.path.join(base, "batched", "*"))
    (inc_slab,) = _glob.glob(os.path.join(inc, "batched", "*"))
    assert os.path.basename(base_slab) != os.path.basename(inc_slab)
    assert os.stat(base_slab).st_ino == os.stat(inc_slab).st_ino  # linked
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert np.array_equal(out["p7"], arrs["p7"])
    assert Snapshot(inc).verify() == {}


def test_incremental_dedups_compressed_slabs(tmp_path) -> None:
    """Member-framed COMPRESSED slabs dedup too: member packing order and
    zstd at a fixed level are deterministic, so an unchanged state's slab
    bytes (and its .ftab) are byte-identical across takes and hard-link via
    the content-keyed index despite fresh batched/<uuid> paths."""
    base = str(tmp_path / "a")
    inc = str(tmp_path / "b")
    arrs = {f"p{i}": np.arange(512, dtype=np.float32) + i for i in range(10)}
    with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
        Snapshot.take(base, {"m": StateDict(**arrs)})
        Snapshot.take(inc, {"m": StateDict(**arrs)}, base=base)
    import glob as _glob

    def slab_and_tab(root):
        paths = _glob.glob(os.path.join(root, "batched", "*"))
        (slab,) = [p for p in paths if not p.endswith(".ftab")]
        (tab,) = [p for p in paths if p.endswith(".ftab")]
        return slab, tab

    base_slab, base_tab = slab_and_tab(base)
    inc_slab, inc_tab = slab_and_tab(inc)
    assert os.stat(base_slab).st_ino == os.stat(inc_slab).st_ino  # linked
    # The .ftab side object dedups as well.
    assert os.stat(base_tab).st_ino == os.stat(inc_tab).st_ino
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    for i in range(10):
        assert np.array_equal(out[f"p{i}"], arrs[f"p{i}"])
    assert Snapshot(inc).verify() == {}


def test_chained_incrementals(tmp_path) -> None:
    """s0 -> s1 -> s2: each step links unchanged objects against its direct
    predecessor; all restore bit-exactly and verify clean."""
    paths = [str(tmp_path / f"s{i}") for i in range(3)]
    Snapshot.take(paths[0], {"m": _state(0)})
    Snapshot.take(paths[1], {"m": _state(1)}, base=paths[0])
    Snapshot.take(paths[2], {"m": _state(2)}, base=paths[1])
    inos = [os.stat(os.path.join(p, "0", "m", "frozen0")).st_ino for p in paths]
    assert inos[0] == inos[1] == inos[2]
    for step, p in enumerate(paths):
        out = StateDict()
        Snapshot(p).restore({"m": out})
        assert out["step"] == step
        assert np.array_equal(out["lora"], np.full((100,), step, np.float32))
        assert Snapshot(p).verify() == {}


def _worker_multirank_incremental(rank: int, world_size: int, shared: str) -> None:
    """2 coordinated ranks: replicated backbone (write-partitioned across
    ranks) + per-rank adapters; the second take dedups the backbone via the
    MERGED per-rank sidecars (an object may have been written by the peer)
    and rewrites only the changed adapter."""
    import os

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    base = os.path.join(shared, "inc_base")
    nxt = os.path.join(shared, "inc_next")
    backbone = {
        f"w{i}": np.arange(4096, dtype=np.float32) + i for i in range(4)
    }

    def app(step: int):
        return {
            "m": StateDict(**backbone),
            "a": StateDict(v=np.full((64,), rank * 100 + step, np.float32)),
        }

    Snapshot.take(base, app(0), replicated=["m/**"])
    Snapshot.take(nxt, app(1), base=base, replicated=["m/**"])

    if rank == 0:
        for i in range(4):
            b = os.path.join(base, "replicated", "m", f"w{i}")
            n = os.path.join(nxt, "replicated", "m", f"w{i}")
            assert os.path.exists(n), n
            assert os.path.samefile(b, n), f"backbone w{i} must hard-link"
        for r in range(world_size):
            vb = os.path.join(base, str(r), "a", "v")
            vn = os.path.join(nxt, str(r), "a", "v")
            assert not os.path.samefile(vb, vn), "changed adapter must rewrite"

    # Both ranks restore the incremental and see step-1 state.
    tgt = {
        "m": StateDict(**{k: np.zeros_like(v) for k, v in backbone.items()}),
        "a": StateDict(v=np.zeros((64,), np.float32)),
    }
    Snapshot(nxt).restore(tgt)
    for k, v in backbone.items():
        assert np.array_equal(tgt["m"][k], v)
    assert np.array_equal(
        tgt["a"]["v"], np.full((64,), rank * 100 + 1, np.float32)
    )
    assert Snapshot(nxt).verify() == {}


@pytest.mark.multiprocess
def test_multirank_incremental_dedup(tmp_path) -> None:
    from torchsnapshot_tpu.test_utils import run_with_processes

    run_with_processes(
        _worker_multirank_incremental, nproc=2, args=(str(tmp_path),)
    )


# ---------------------------------------------------------------------------
# The base= fallback ladder (snapshot.py): every degrade branch must fall
# back (to a full snapshot, or to degraded dedup) WITH its warning — a
# silent degrade would report bogus incremental "speedups" while rewriting
# every byte. One parametrized case per branch.
# ---------------------------------------------------------------------------

def _ladder_no_dedup_knob(tmp_path):
    """Branch: dedup digests off at take time -> base ignored outright."""
    base = str(tmp_path / "base")
    Snapshot.take(base, {"m": _state(0)})
    ctx = knobs.override_dedup_digests(False)
    return base, ctx, "ignored: incremental dedup requires"


def _ladder_unusable_url(tmp_path):
    """Branch: base URL unparseable/unsupported -> unusable."""
    return "foo://not/a/thing", None, "is unusable"


def _ladder_no_metadata(tmp_path):
    """Branch: base tree exists but was never committed."""
    base = str(tmp_path / "base")
    os.makedirs(base)
    with open(os.path.join(base, "junk"), "w") as f:
        f.write("x")
    return base, None, "has no committed metadata"


def _ladder_unreadable_sidecars(tmp_path):
    """Branch: committed base whose checksum sidecar is corrupt JSON."""
    base = str(tmp_path / "base")
    Snapshot.take(base, {"m": _state(0)})
    with open(os.path.join(base, ".checksums.0"), "w") as f:
        f.write("{torn")
    return base, None, "checksum sidecars unreadable"


def _ladder_no_sha_identities(tmp_path):
    """Branch: sidecars present but recorded without sha256 identities."""
    import json

    base = str(tmp_path / "base")
    Snapshot.take(base, {"m": _state(0)})
    sidecar_path = os.path.join(base, ".checksums.0")
    with open(sidecar_path) as f:
        sidecar = json.load(f)
    stripped = {}
    for k, v in sidecar.items():
        if isinstance(v, list):
            stripped[k] = [v[0], v[1], None]
        elif isinstance(v, dict):
            stripped[k] = [v["crc"], v["size"], None]
        else:
            stripped[k] = v
    with open(sidecar_path, "w") as f:
        json.dump(stripped, f)
    return base, None, "carries no sha256 dedup identities"


@pytest.mark.parametrize(
    "make_base",
    [
        _ladder_no_dedup_knob,
        _ladder_unusable_url,
        _ladder_no_metadata,
        _ladder_unreadable_sidecars,
        _ladder_no_sha_identities,
    ],
    ids=[
        "no-dedup-knob",
        "unusable-url",
        "no-committed-metadata",
        "unreadable-sidecars",
        "no-sha-identities",
    ],
)
def test_base_fallback_ladder_full_snapshot(tmp_path, caplog, make_base) -> None:
    """Each degrade branch: the take SUCCEEDS as a full snapshot (no hard
    links, zero deduped bytes) and logs its specific warning."""
    import contextlib

    base, ctx, expected_warning = make_base(tmp_path)
    inc = str(tmp_path / "inc")
    with ctx if ctx is not None else contextlib.nullcontext():
        with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
            Snapshot.take(inc, {"m": _state(0)}, base=base)
    assert any(expected_warning in r.message for r in caplog.records), (
        expected_warning,
        [r.message for r in caplog.records],
    )
    # Full, not incremental: fresh inodes for every object.
    base_obj = os.path.join(base, "0", "m", "frozen0")
    inc_obj = os.path.join(inc, "0", "m", "frozen0")
    if os.path.exists(base_obj):
        assert os.stat(base_obj).st_ino != os.stat(inc_obj).st_ino
    # ...and correct.
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert out["step"] == 0
    assert np.array_equal(out["frozen1"], np.arange(1000, dtype=np.float32) + 1)
    assert Snapshot(inc).verify() == {}


def test_base_fallback_codec_version_mismatch_warns(tmp_path, caplog) -> None:
    """Branch: the base compressed with a different codec library version —
    dedup is still ATTEMPTED (identical bitstreams may exist) but the
    likely-miss is surfaced, never silent."""
    import json

    base = str(tmp_path / "base")
    inc = str(tmp_path / "inc")
    with knobs.override_compression("zlib"):
        Snapshot.take(base, {"m": _state(0)})
        meta_path = os.path.join(base, ".snapshot_metadata")
        with open(meta_path) as f:
            meta = json.load(f)
        meta["codec_versions"] = {"zlib": "0.0.not-this-one"}
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
            Snapshot.take(inc, {"m": _state(0)}, base=base)
    assert any(
        "byte-identical dedup will likely miss" in r.message
        for r in caplog.records
    )
    out = StateDict()
    Snapshot(inc).restore({"m": out})
    assert out["step"] == 0
    assert Snapshot(inc).verify() == {}


def test_base_fallback_mixed_coverage_warns_and_partially_dedups(
    tmp_path, caplog
) -> None:
    """Branch: some base objects carry sha identities and some don't
    (heterogeneous hosts / knob churn): covered objects still hard-link,
    uncovered ones rewrite, and the partial rewrite is surfaced."""
    import json

    base = str(tmp_path / "base")
    inc = str(tmp_path / "inc")
    Snapshot.take(base, {"m": _state(0)})
    sidecar_path = os.path.join(base, ".checksums.0")
    with open(sidecar_path) as f:
        sidecar = json.load(f)
    # Strip the sha identity from exactly one object.
    victim = "0/m/frozen0"
    assert victim in sidecar
    v = sidecar[victim]
    sidecar[victim] = (
        [v[0], v[1], None]
        if isinstance(v, list)
        else [v["crc"], v["size"], None]
    )
    with open(sidecar_path, "w") as f:
        json.dump(sidecar, f)
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
        Snapshot.take(inc, {"m": _state(0)}, base=base)
    assert any(
        "carry no sha256 dedup identity" in r.message for r in caplog.records
    )
    # The stripped object was rewritten; a covered one still hard-links.
    assert (
        os.stat(os.path.join(base, victim)).st_ino
        != os.stat(os.path.join(inc, victim)).st_ino
    )
    assert (
        os.stat(os.path.join(base, "0", "m", "frozen1")).st_ino
        == os.stat(os.path.join(inc, "0", "m", "frozen1")).st_ino
    )
    assert Snapshot(inc).verify() == {}


def test_auto_gate_single_core_writes_crc_only_sidecars(tmp_path, monkeypatch) -> None:
    """The round-5 default on a single-core host: takes still write checksum
    sidecars (verify() stays green) but with no sha256 — the dedup identity
    whose hashing was measured to steal the core feeding the device
    transfer."""
    import json

    monkeypatch.setenv("TORCHSNAPSHOT_TPU_DEDUP_DIGESTS", "auto")
    monkeypatch.setattr(knobs, "_usable_cpu_count", lambda: 1)
    path = str(tmp_path / "ckpt")
    Snapshot.take(path, {"m": _state(0)})
    with open(os.path.join(path, ".checksums.0")) as f:
        sidecar = json.load(f)
    assert sidecar
    assert all(v[2] is None for v in sidecar.values()), sidecar
    assert Snapshot(path).verify() == {}
