"""Snapshot catalog, managed delta chains, retention-driven GC.

Unit coverage of ``catalog.py`` (records, policy grammar/math, auto-base
selection) plus end-to-end lifecycle tests: ``take(job=...)`` chains
committed snapshots via catalog-auto bases and rebases to full at
``max_chain_len``; retention policies condemn any chain prefix while every
retained snapshot stays bit-exact restorable (snapshots are physically
self-contained — fs hard links / full rewrites — which is exactly the
guarantee ``validate_chain_closure`` re-checks); ``Snapshot.gc``'s explicit
keep-set parameter is the ONE deletion path both the debris sweep and the
retention engine drive, with the crash-convergent metadata→tree→record
deletion order."""

import json
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu import catalog
from torchsnapshot_tpu.utils import knobs


def _state(step: int):
    return {
        "m": StateDict(
            frozen=np.arange(4000, dtype=np.float32),
            lora=np.full((64,), step, np.float32),
            step=step,
        )
    }


def _assert_restores(path: str, step: int) -> None:
    out = StateDict()
    Snapshot(path).restore({"m": out})
    assert out["step"] == step
    assert np.array_equal(out["frozen"], np.arange(4000, dtype=np.float32))
    assert np.array_equal(out["lora"], np.full((64,), step, np.float32))
    assert Snapshot(path).verify() == {}


@pytest.fixture(autouse=True)
def _fresh_chain_cache():
    """Each test starts with a cold per-process chain cache (auto-base
    then exercises the real catalog scan path, not a prior test's heads)."""
    catalog._CHAIN_CACHE.clear()
    yield
    catalog._CHAIN_CACHE.clear()


# ---------------------------------------------------------------------------
# Plumbing units
# ---------------------------------------------------------------------------

def test_split_bucket() -> None:
    assert catalog.split_bucket("/ckpts/step_1") == ("/ckpts", "step_1")
    assert catalog.split_bucket("/a/b/c/") == ("/a/b", "c")
    assert catalog.split_bucket("gs://bkt/run/step_1") == (
        "gs://bkt/run", "step_1",
    )
    assert catalog.split_bucket("memory://bkt/s1") == ("memory://bkt", "s1")
    assert catalog.split_bucket("memory://lonely") is None
    assert catalog.split_bucket("/") is None
    assert catalog.join_bucket("gs://bkt/run", "s") == "gs://bkt/run/s"


def test_record_roundtrip_and_path_stability() -> None:
    rec = catalog.CatalogRecord(
        name="step_7", job="träiner/a", step=7, wall_time=123.5,
        base="step_6", chain_len=2, world_size=4,
        bytes_total=100, bytes_written=10, bytes_deduped=90,
    )
    back = catalog.CatalogRecord.from_json(rec.to_json())
    assert back == rec
    # Same (job, name, step) always maps to the same record object — a
    # re-taken name overwrites, never accumulates.
    p1 = catalog.record_path("träiner/a", "step_7", 7)
    assert p1 == catalog.record_path("träiner/a", "step_7", 7)
    # Unsafe job ids slug apart (hash-disambiguated), never collide.
    assert catalog.record_path("a/b", "s", 1) != catalog.record_path(
        "a_b", "s", 1
    )


def test_loader_skips_newer_schema_and_junk(tmp_path) -> None:
    bucket = str(tmp_path)
    good = catalog.CatalogRecord(name="s1", job="j", step=1, wall_time=1.0)
    with catalog.Catalog(bucket) as cat:
        cat.append(good)
        assert cat.load() == [good]
    rec_dir = os.path.join(bucket, catalog.RECORD_DIR, "j")
    with open(os.path.join(rec_dir, "junk.json"), "w") as f:
        f.write("{not json")
    newer = catalog.CatalogRecord(
        name="s2", job="j", step=2, wall_time=2.0, schema=99
    )
    with open(os.path.join(rec_dir, "zzz.json"), "w") as f:
        f.write(newer.to_json())
    with catalog.Catalog(bucket) as cat:
        assert [r.name for r in cat.load()] == ["s1"]


def test_retention_policy_grammar() -> None:
    p = catalog.RetentionPolicy.parse("last=3, hourly=24 ,daily=7,job=tr-*")
    assert (p.last, p.hourly, p.daily, p.job_globs) == (3, 24, 7, ["tr-*"])
    assert catalog.RetentionPolicy.parse("").last is None
    for bad in ("last", "last=x", "last=-1", "weekly=2"):
        with pytest.raises(ValueError):
            catalog.RetentionPolicy.parse(bad)


def test_retention_policy_math() -> None:
    hour = 3600.0
    recs = [
        catalog.CatalogRecord(
            name=f"s{i}", job="j", step=i, wall_time=1000000.0 + i * 20 * 60
        )
        for i in range(12)  # 20-minute cadence: 3 per hour, 4 hours
    ]
    keep = catalog.RetentionPolicy.parse("last=2").retained(recs)
    assert keep == {"s10", "s11"}
    keep = catalog.RetentionPolicy.parse("hourly=2").retained(recs)
    # The newest snapshot of each of the 2 most recent distinct hours.
    by_hour = {}
    for r in recs:
        by_hour.setdefault(int(r.wall_time // hour), r)
        by_hour[int(r.wall_time // hour)] = max(
            by_hour[int(r.wall_time // hour)], r, key=lambda x: x.order_key
        )
    newest_hours = sorted(by_hour)[-2:]
    assert keep == {by_hour[h].name for h in newest_hours}
    # No clauses = retain everything.
    keep = catalog.RetentionPolicy.parse("").retained(recs)
    assert len(keep) == 12
    # Zero-wall-time (rebuilt) records never satisfy time clauses but do
    # count for last-K.
    synth = [
        catalog.CatalogRecord(name="r0", job="j", step=50, wall_time=0.0)
    ]
    assert catalog.RetentionPolicy.parse("hourly=5").retained(synth) == set()
    assert catalog.RetentionPolicy.parse("last=1").retained(synth) == {"r0"}


def test_plan_retention_per_job_and_pins() -> None:
    recs = [
        catalog.CatalogRecord(name=f"a{i}", job="a", step=i, wall_time=i)
        for i in range(4)
    ] + [
        catalog.CatalogRecord(name=f"b{i}", job="b", step=i, wall_time=i)
        for i in range(3)
    ]
    plan = catalog.plan_retention(
        recs, pins={"a0"}, policy=catalog.RetentionPolicy.parse("last=1")
    )
    assert plan.retained == ["a0", "a3", "b2"]  # pin + last-1 per job
    assert plan.condemned == ["a1", "a2", "b0", "b1"]
    # job= glob restricts the policy; other jobs fully retained.
    plan = catalog.plan_retention(
        recs, pins=set(),
        policy=catalog.RetentionPolicy.parse("last=1,job=a"),
    )
    assert plan.condemned == ["a0", "a1", "a2"]


def test_chain_of() -> None:
    recs = [
        catalog.CatalogRecord(name="s0", job="j", step=0, wall_time=0),
        catalog.CatalogRecord(
            name="s1", job="j", step=1, wall_time=1, base="s0", chain_len=1
        ),
        catalog.CatalogRecord(
            name="s2", job="j", step=2, wall_time=2, base="s1", chain_len=2
        ),
    ]
    assert [r.name for r in catalog.chain_of(recs, "s2")] == ["s0", "s1", "s2"]
    assert [r.name for r in catalog.chain_of(recs, "s0")] == ["s0"]


# ---------------------------------------------------------------------------
# Managed chains end to end
# ---------------------------------------------------------------------------

def test_job_take_chains_and_rebases(tmp_path) -> None:
    bucket = str(tmp_path)
    for i in range(5):
        Snapshot.take(
            os.path.join(bucket, f"step_{i}"), _state(i),
            job="j", step=i, max_chain_len=3,
        )
    with catalog.Catalog(bucket) as cat:
        recs = cat.load(job="j")
    assert [(r.name, r.base, r.chain_len) for r in recs] == [
        ("step_0", None, 0),
        ("step_1", "step_0", 1),
        ("step_2", "step_1", 2),
        ("step_3", "step_2", 3),
        ("step_4", None, 0),  # rebase-to-full at max_chain_len
    ]
    # The chain dedups for real: frozen shares one inode along each chain.
    ino = lambda n: os.stat(  # noqa: E731
        os.path.join(bucket, n, "0", "m", "frozen")
    ).st_ino
    assert ino("step_0") == ino("step_1") == ino("step_3")
    assert ino("step_3") != ino("step_4")
    # Byte attribution: deltas share the frozen bytes, rewrite the rest.
    assert recs[1].bytes_deduped > 0
    assert recs[1].bytes_written < recs[0].bytes_written
    assert recs[0].bytes_deduped == 0
    assert (
        recs[1].bytes_total
        == recs[1].bytes_written + recs[1].bytes_deduped
        == recs[0].bytes_total
    )


def test_warm_replica_following_a_chain_reads_only_the_delta_from_origin(tmp_path) -> None:
    """A replica that restored step T-1 through the read cache restores
    step T reading the delta's new bytes from origin and the chain-shared
    backbone from the cache: the cache keys objects by their digests, which
    dedup'd chain objects share."""
    from torchsnapshot_tpu import snapshot as snapshot_mod

    bucket = str(tmp_path / "bucket")
    for i in range(2):
        Snapshot.take(os.path.join(bucket, f"step_{i}"), _state(i), job="j", step=i)
    frozen_bytes, lora_bytes = 4000 * 4, 64 * 4

    def restore(step: int) -> dict:
        _assert_restores(os.path.join(bucket, f"step_{step}"), step)
        return dict(snapshot_mod.LAST_RESTORE_STATS["attribution"])

    with knobs.override_read_cache_dir(str(tmp_path / "cache")):
        cold = restore(0)
        warm = restore(1)
    assert cold["cache_bytes"] == 0 and cold["origin_bytes"] >= frozen_bytes + lora_bytes
    assert warm["cache_bytes"] >= frozen_bytes
    assert lora_bytes <= warm["origin_bytes"] < frozen_bytes


def test_job_take_cold_process_scans_catalog(tmp_path) -> None:
    """A fresh process (cold chain cache) finds the chain head by catalog
    scan, not only via the in-process fast path."""
    bucket = str(tmp_path)
    Snapshot.take(os.path.join(bucket, "step_0"), _state(0), job="j", step=0)
    catalog._CHAIN_CACHE.clear()  # simulate process restart
    Snapshot.take(os.path.join(bucket, "step_1"), _state(1), job="j", step=1)
    with catalog.Catalog(bucket) as cat:
        assert cat.load()[-1].base == "step_0"


def test_job_take_ignores_other_jobs_and_explicit_base_wins(tmp_path) -> None:
    bucket = str(tmp_path)
    Snapshot.take(os.path.join(bucket, "a_0"), _state(0), job="a", step=0)
    Snapshot.take(os.path.join(bucket, "b_0"), _state(0), job="b", step=0)
    Snapshot.take(os.path.join(bucket, "b_1"), _state(1), job="b", step=1)
    with catalog.Catalog(bucket) as cat:
        by_name = {r.name: r for r in cat.load()}
    assert by_name["b_1"].base == "b_0"  # never chains across jobs
    # Explicit base beats auto-selection (and records a conservative
    # chain of 1 — the rebase policy only governs auto chains).
    Snapshot.take(
        os.path.join(bucket, "b_2"), _state(2),
        job="b", step=2, base=os.path.join(bucket, "a_0"),
    )
    with catalog.Catalog(bucket) as cat:
        rec = {r.name: r for r in cat.load()}["b_2"]
    assert rec.base == "a_0" and rec.chain_len == 1


def test_job_take_with_catalog_disabled(tmp_path) -> None:
    bucket = str(tmp_path)
    with knobs.override_catalog(False):
        Snapshot.take(
            os.path.join(bucket, "step_0"), _state(0), job="j", step=0
        )
    assert not os.path.exists(os.path.join(bucket, catalog.CATALOG_DIR))
    _assert_restores(os.path.join(bucket, "step_0"), 0)


def test_snapshot_at_root_goes_unrecorded(tmp_path, caplog) -> None:
    """memory:// with no parent: no bucket to catalog into — the take
    commits, warns, and writes no record."""
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
        Snapshot.take("memory://rootsnap", _state(0), job="j", step=0)
    assert any("no parent bucket" in r.message for r in caplog.records)
    out = StateDict()
    Snapshot("memory://rootsnap").restore({"m": out})
    assert out["step"] == 0


def test_stale_chain_head_degrades_to_full_take(tmp_path, caplog) -> None:
    """The take-vs-gc race, deterministically: the cached chain head is
    condemned and deleted between takes; the next auto-base take selects
    it (cache is stale by design), the base fallback ladder degrades to a
    full snapshot, and the commit still lands bit-exact."""
    import shutil

    bucket = str(tmp_path)
    Snapshot.take(os.path.join(bucket, "step_0"), _state(0), job="j", step=0)
    assert catalog._CHAIN_CACHE  # head cached by the commit
    shutil.rmtree(os.path.join(bucket, "step_0"))
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.snapshot"):
        Snapshot.take(
            os.path.join(bucket, "step_1"), _state(1), job="j", step=1
        )
    assert any("full snapshot" in r.message for r in caplog.records)
    _assert_restores(os.path.join(bucket, "step_1"), 1)


def test_auto_base_skips_zombie_records(tmp_path) -> None:
    """A record whose snapshot lost its metadata (crashed GC) is probed
    and skipped; the take chains from the newest USABLE snapshot."""
    bucket = str(tmp_path)
    Snapshot.take(os.path.join(bucket, "step_0"), _state(0), job="j", step=0)
    Snapshot.take(os.path.join(bucket, "step_1"), _state(1), job="j", step=1)
    os.remove(os.path.join(bucket, "step_1", ".snapshot_metadata"))
    catalog._CHAIN_CACHE.clear()
    Snapshot.take(os.path.join(bucket, "step_2"), _state(2), job="j", step=2)
    with catalog.Catalog(bucket) as cat:
        assert {r.name: r.base for r in cat.load()}["step_2"] == "step_0"


# ---------------------------------------------------------------------------
# Retention + the shared gc deletion path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["fs", "memory"])
def test_retention_collects_prefix_keeps_restorable(
    tmp_path, backend, request
) -> None:
    bucket = (
        str(tmp_path / "bkt")
        if backend == "fs"
        else f"memory://ret-{request.node.name}"
    )
    for i in range(5):
        Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
    report = catalog.retain(
        bucket, catalog.RetentionPolicy.parse("last=2"), dry_run=True
    )
    assert report["dry_run"] and report["policy"]["condemned"] == [
        "step_0", "step_1", "step_2",
    ]
    report = catalog.retain(
        bucket, catalog.RetentionPolicy.parse("last=2"), dry_run=False
    )
    assert report["condemned"] == ["step_0", "step_1", "step_2"]
    # Any condemned prefix: the retained tail restores bit-exact.
    _assert_restores(f"{bucket}/step_3", 3)
    _assert_restores(f"{bucket}/step_4", 4)
    with catalog.Catalog(bucket) as cat:
        assert [r.name for r in cat.load()] == ["step_3", "step_4"]
    # Idempotent re-run: nothing left to condemn or delete.
    report = catalog.retain(
        bucket, catalog.RetentionPolicy.parse("last=2"), dry_run=False
    )
    assert report["condemned"] == [] and report["removed"] == 0


def test_pins_survive_every_policy(tmp_path) -> None:
    bucket = str(tmp_path)
    for i in range(3):
        Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
    with catalog.Catalog(bucket) as cat:
        cat.pin("step_0")
    report = catalog.retain(
        bucket, catalog.RetentionPolicy.parse("last=1"), dry_run=False
    )
    assert report["condemned"] == ["step_1"]
    _assert_restores(f"{bucket}/step_0", 0)
    with catalog.Catalog(bucket) as cat:
        cat.unpin("step_0")
    report = catalog.retain(
        bucket, catalog.RetentionPolicy.parse("last=1"), dry_run=False
    )
    assert report["condemned"] == ["step_0"]


def test_gc_keep_roots_is_the_shared_deletion_path(tmp_path) -> None:
    """Snapshot.gc(keep_roots=...) condemns unnamed committed roots
    directly — the same path retain() drives."""
    bucket = str(tmp_path)
    for i in range(3):
        Snapshot.take(f"{bucket}/step_{i}", _state(i))
    report = Snapshot.gc(bucket, dry_run=False, keep_roots={"step_2"})
    assert report["condemned"] == ["step_0", "step_1"]
    assert sorted(os.listdir(bucket)) == ["step_2"]
    _assert_restores(f"{bucket}/step_2", 2)


def test_gc_keep_roots_rejected_on_single_root(tmp_path) -> None:
    path = str(tmp_path / "snap")
    Snapshot.take(path, _state(0))
    with pytest.raises(ValueError, match="keep_roots"):
        Snapshot.gc(path, keep_roots={"x"})


def test_gc_legacy_debris_sweep_unchanged_with_catalog_present(
    tmp_path,
) -> None:
    """The classic whole-bucket sweep must keep catalog records of live
    snapshots (never eat the catalog as 'an uncommitted tree')."""
    bucket = str(tmp_path)
    Snapshot.take(f"{bucket}/step_0", _state(0), job="j", step=0)
    # Crash debris: an uncommitted tree + a loose temp file.
    os.makedirs(f"{bucket}/torn/0")
    with open(f"{bucket}/torn/0/obj.tmp.1", "w") as f:
        f.write("x")
    with open(f"{bucket}/loose.tmp", "w") as f:
        f.write("x")
    report = Snapshot.gc(bucket, dry_run=False)
    assert report["committed"] == ["step_0"]
    assert "torn" in report["uncommitted"]
    assert not os.path.exists(f"{bucket}/torn")
    assert not os.path.exists(f"{bucket}/loose.tmp")
    with catalog.Catalog(bucket) as cat:
        assert [r.name for r in cat.load()] == ["step_0"]
    _assert_restores(f"{bucket}/step_0", 0)


def test_gc_crash_convergence_zombie_and_stale_record(tmp_path) -> None:
    """The deletion order's two crash windows, reconstructed exactly:
    metadata deleted but tree+record present (zombie) → the next retention
    run finishes tree AND record; tree gone but record present (stale) →
    the record alone is removed."""
    import shutil

    bucket = str(tmp_path)
    for i in range(3):
        Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
    # Crash window 1: metadata went, tree + record remain.
    os.remove(f"{bucket}/step_0/.snapshot_metadata")
    # Crash window 2: tree fully gone, record remains.
    shutil.rmtree(f"{bucket}/step_1")
    report = catalog.retain(
        bucket, catalog.RetentionPolicy.parse("last=3"), dry_run=False
    )
    # Policy retains everything retainable; the zombie and stale record
    # are converged away regardless.
    assert not os.path.exists(f"{bucket}/step_0")
    with catalog.Catalog(bucket) as cat:
        assert [r.name for r in cat.load()] == ["step_2"]
    _assert_restores(f"{bucket}/step_2", 2)
    assert report["removed"] > 0


def test_validate_chain_closure_refuses_unreadable_retained(tmp_path) -> None:
    bucket = str(tmp_path)
    for i in range(2):
        Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
    os.remove(f"{bucket}/step_1/.snapshot_metadata")
    with pytest.raises(RuntimeError, match="refusing"):
        catalog.validate_chain_closure(bucket, ["step_1"], ["step_0"])


def test_rebuild_reconstructs_from_scan(tmp_path) -> None:
    import shutil

    bucket = str(tmp_path)
    for i in range(2):
        Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
    shutil.rmtree(os.path.join(bucket, catalog.CATALOG_DIR))
    with catalog.Catalog(bucket) as cat:
        written = cat.rebuild()
        assert sorted(r.name for r in written) == ["step_0", "step_1"]
        recs = cat.load()
    assert [r.step for r in recs] == [0, 1]  # parsed from the names
    assert all(r.job == "" and r.chain_len == 0 for r in recs)
    # Idempotent: existing records are never rewritten.
    with catalog.Catalog(bucket) as cat:
        assert cat.rebuild() == []


def test_append_failure_is_fail_open(tmp_path, caplog) -> None:
    """A catalog write failure must never fail the commit (here: a FILE
    squats where the record tree should go, so the record write cannot
    create its directory — robust even when running as root, where
    permission bits don't block)."""
    bucket = str(tmp_path)
    os.makedirs(os.path.join(bucket, catalog.CATALOG_DIR))
    with open(os.path.join(bucket, catalog.RECORD_DIR), "w") as f:
        f.write("squatter")
    with caplog.at_level("WARNING"):
        snap = Snapshot.take(
            os.path.join(bucket, "step_0"), _state(0), job="j", step=0
        )
    assert snap.verify() == {}
    assert any(
        "catalog append" in r.message or "could not be appended" in r.message
        for r in caplog.records
    )
    _assert_restores(os.path.join(bucket, "step_0"), 0)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_catalog_roundtrip(tmp_path, capsys) -> None:
    from torchsnapshot_tpu.__main__ import main

    bucket = str(tmp_path)
    for i in range(3):
        Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
    assert main(["catalog", "ls", bucket]) == 0
    out = capsys.readouterr().out
    assert "step_2" in out and "base=step_1" in out and "job=j" in out
    assert main(["catalog", "ls", bucket, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert [r["name"] for r in parsed] == ["step_0", "step_1", "step_2"]
    assert main(["catalog", "pin", bucket, "step_0"]) == 0
    capsys.readouterr()
    assert main(["gc", bucket, "--policy", "last=1"]) == 0
    out = capsys.readouterr().out
    assert "condemned (dry run): step_1" in out
    assert "step_0 [pinned]" in out
    assert os.path.isdir(f"{bucket}/step_1")  # dry run deleted nothing
    assert main(["gc", bucket, "--policy", "last=1", "--apply"]) == 0
    capsys.readouterr()
    assert not os.path.isdir(f"{bucket}/step_1")
    _assert_restores(f"{bucket}/step_0", 0)
    _assert_restores(f"{bucket}/step_2", 2)
    assert main(["catalog", "unpin", bucket, "step_0"]) == 0
    assert main(["catalog", "retain", bucket, "--policy", "last=1",
                 "--apply"]) == 0
    capsys.readouterr()
    assert not os.path.isdir(f"{bucket}/step_0")
    # Bad policy surfaces as the CLI's one-line scriptable error (exit 2).
    assert main(["gc", bucket, "--policy", "weekly=1"]) == 2


# ---------------------------------------------------------------------------
# Crash-state exploration of the continuous-checkpointing lifecycle: the
# runtime counterpart of the static TSA10xx durability pass, over THIS
# suite's core scenario. CI's crash-explorer slow lane runs the full sweep.
# ---------------------------------------------------------------------------

def test_continuous_checkpointing_every_effect_prefix_restorable(
    tmp_path,
) -> None:
    """Chained takes + retention GC, journaled effect-by-effect under
    TORCHSNAPSHOT_TPU_DEBUG_EFFECTS: replaying every prefix of the durable
    effect order (every crash a single process could suffer) leaves every
    catalog-visible snapshot bit-exact restorable, no record pointing at a
    never-committed snapshot, and a GC that converges in one run."""
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from dev import crash_explorer
    from torchsnapshot_tpu import effect_journal

    bucket = str(tmp_path / "bkt")
    with knobs.override_debug_effects(True):
        effect_journal.reset()
        for i in range(3):
            Snapshot.take(f"{bucket}/step_{i}", _state(i), job="j", step=i)
        catalog.retain(
            bucket, catalog.RetentionPolicy.parse("last=2"), dry_run=False
        )
        effects = effect_journal.get_journal().effects()
    effect_journal.reset()
    assert any(".catalog/records/" in e.path for e in effects)
    assert any(e.op == "delete" for e in effects)
    report = crash_explorer.explore(
        effects, str(tmp_path / "explore"), seed=3, interior_samples=3
    )
    assert report.ok, report.render()
    assert report.prefixes == len(effects)
    assert report.interior_samples == 3
