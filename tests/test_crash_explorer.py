"""Tests for the durable-effect journal (``effect_journal.py``) and the
crash-state explorer (``dev/crash_explorer.py``).

The journal is the runtime ground truth of the order durable mutations
reached storage; the explorer replays every prefix of that order and
asserts each one is a restorable crash state. Proven both ways, like the
static passes: a real take/GC schedule passes every prefix, and a
deliberately non-atomic catalog publish (the journal reordered so the
record lands before ``.snapshot_metadata``) is caught with the exact
effect seq and call site.
"""

import os
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from dev import crash_explorer  # noqa: E402
from torchsnapshot_tpu import Snapshot, StateDict, effect_journal  # noqa: E402
from torchsnapshot_tpu.io_types import WriteIO  # noqa: E402
from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin  # noqa: E402
from torchsnapshot_tpu.utils import knobs  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_journal():
    """Each test re-reads the knob and starts from an empty journal."""
    effect_journal.reset()
    yield
    effect_journal.reset()


def _state(seed: int = 0):
    rng = np.random.default_rng(seed)
    return {
        "s": StateDict(
            w=rng.standard_normal(512).astype(np.float32),
            b=np.arange(64, dtype=np.int64) + seed,
            step=seed,
        )
    }


def _restore_check(root: str) -> None:
    """Real bit-exact restore of a replayed snapshot root (seed recovered
    from the ``step_N`` naming the fixtures use)."""
    seed = int(os.path.basename(root).rsplit("_", 1)[1])
    src = _state(seed)["s"]
    tgt = {
        "s": StateDict(
            w=np.zeros(512, np.float32), b=np.zeros(64, np.int64), step=-1
        )
    }
    Snapshot(root).restore(tgt)
    assert np.array_equal(
        tgt["s"]["w"].view(np.uint8), np.asarray(src["w"]).view(np.uint8)
    )
    assert np.array_equal(tgt["s"]["b"], src["b"])
    assert tgt["s"]["step"] == src["step"]


def _journaled_takes(bucket: str, seeds=(1, 2)):
    with knobs.override_debug_effects(True):
        effect_journal.reset()
        for seed in seeds:
            Snapshot.take(f"{bucket}/step_{seed}", _state(seed), job="j")
        journal = effect_journal.get_journal()
        assert journal is not None
        effects = journal.effects()
    effect_journal.reset()
    return effects


# ---------------------------------------------------------------------------
# Effect journal
# ---------------------------------------------------------------------------


def test_journal_disabled_by_default(tmp_path) -> None:
    assert effect_journal.get_journal() is None
    plugin = url_to_storage_plugin(str(tmp_path))
    # Zero-allocation off: no wrapper in the stack, the plugin is untouched.
    assert not isinstance(plugin, effect_journal.EffectRecordingPlugin)
    p = plugin
    while p is not None:
        assert not isinstance(p, effect_journal.EffectRecordingPlugin)
        p = getattr(p, "inner", None)


def test_wrapper_journals_mutations_in_seq_order(tmp_path) -> None:
    import asyncio

    with knobs.override_debug_effects(True):
        effect_journal.reset()
        plugin = url_to_storage_plugin(str(tmp_path))
        loop = asyncio.new_event_loop()

        async def scenario():
            await plugin.write(WriteIO(path="a/obj", buf=memoryview(b"payload")))
            await plugin.write(WriteIO(path="a/other", buf=b"chunk0chunk1"))
            await plugin.delete("a/obj")
            await plugin.close()

        try:
            loop.run_until_complete(scenario())
        finally:
            loop.close()
        effects = effect_journal.get_journal().effects()

    ops = [e.op for e in effects]
    assert ops == ["write", "write", "delete"]
    assert set(ops) <= set(effect_journal.MUTATING_OPS)
    assert [e.seq for e in effects] == list(range(len(effects)))
    # Payload fingerprints are real content hashes; non-payload ops carry
    # the sentinel.
    assert effects[0].nbytes == len(b"payload")
    assert effects[0].fingerprint != "-"
    assert effects[1].payload == b"chunk0chunk1"
    assert effects[2].fingerprint == "-"
    # Call sites point above the storage plumbing (this test file).
    assert "test_crash_explorer" in effects[0].site


def test_journal_knob_reset_reevaluates(tmp_path) -> None:
    assert effect_journal.get_journal() is None
    with knobs.override_debug_effects(True):
        # Still None: the disabled decision was cached at first use...
        assert effect_journal.get_journal() is None
        effect_journal.reset()  # ...until reset re-reads the knob.
        assert effect_journal.get_journal() is not None


# ---------------------------------------------------------------------------
# Crash-state explorer: the real tree passes
# ---------------------------------------------------------------------------


def test_real_take_every_prefix_restorable(tmp_path) -> None:
    effects = _journaled_takes(str(tmp_path / "bucket"))
    assert any(".catalog/records/" in e.path for e in effects)
    report = crash_explorer.explore(
        effects,
        str(tmp_path / "explore"),
        seed=7,
        interior_samples=3,
        restore_check=_restore_check,
    )
    assert report.ok
    assert report.prefixes == len(effects)
    assert report.interior_samples == 3


def test_gc_schedule_every_prefix_restorable(tmp_path) -> None:
    """A retention delete lands in the journal; zombie crash states (record
    outliving a deleted ``.snapshot_metadata``) are legal, and GC converges
    from every one of them."""
    bucket = str(tmp_path / "bucket")
    with knobs.override_debug_effects(True):
        effect_journal.reset()
        Snapshot.take(f"{bucket}/step_1", _state(1), job="j")
        Snapshot.take(f"{bucket}/step_2", _state(2), job="j")
        Snapshot.gc(bucket, dry_run=False, keep_roots={"step_2"})
        effects = effect_journal.get_journal().effects()
    effect_journal.reset()
    assert any(e.op == "delete" for e in effects)
    report = crash_explorer.explore(
        effects, str(tmp_path / "explore"), seed=0, interior_samples=2
    )
    assert report.ok
    assert report.prefixes == len(effects)


def test_prefix_enumeration_is_deterministic(tmp_path) -> None:
    effects = _journaled_takes(str(tmp_path / "bucket"), seeds=(1,))
    plan_a = crash_explorer._interior_plan(effects, seed=13, interior_samples=3)
    plan_b = crash_explorer._interior_plan(effects, seed=13, interior_samples=3)
    assert plan_a == plan_b
    assert len(plan_a) == 3
    for idx, cut in plan_a:
        assert effects[idx].op in ("write", "append", "link")
        assert 1 <= cut < effects[idx].nbytes
    rep_a = crash_explorer.explore(
        effects, str(tmp_path / "xa"), seed=13, interior_samples=3
    )
    rep_b = crash_explorer.explore(
        effects, str(tmp_path / "xb"), seed=13, interior_samples=3
    )
    assert (rep_a.prefixes, rep_a.interior_samples) == (
        rep_b.prefixes,
        rep_b.interior_samples,
    )


# ---------------------------------------------------------------------------
# Crash-state explorer: seeded broken fixtures are caught, with attribution
# ---------------------------------------------------------------------------


def test_nonatomic_catalog_publish_is_caught_with_attribution(tmp_path) -> None:
    """The regression fixture the tentpole demands: reorder the journal so
    the catalog record is published BEFORE ``.snapshot_metadata`` — the
    crash state right after the record write has a catalog pointer to an
    uncommitted snapshot, and the explorer names that exact effect."""
    effects = _journaled_takes(str(tmp_path / "bucket"), seeds=(1,))
    meta_i = next(
        i for i, e in enumerate(effects) if e.path == ".snapshot_metadata"
    )
    rec_i = next(
        i for i, e in enumerate(effects) if ".catalog/records/" in e.path
    )
    assert meta_i < rec_i  # the real code publishes after the commit
    broken = list(effects)
    broken[meta_i], broken[rec_i] = broken[rec_i], broken[meta_i]

    with pytest.raises(crash_explorer.CrashStateViolation) as exc:
        crash_explorer.explore(
            broken, str(tmp_path / "explore"), seed=0, interior_samples=0
        )
    violations = exc.value.report.violations
    assert violations
    v = violations[0]
    # Attribution: the record-write effect, by seq AND call site.
    record_effect = effects[rec_i]
    assert v.seq == record_effect.seq
    assert v.site == record_effect.site
    assert "catalog.py" in v.site
    assert "publish-before-payload" in v.problem


def test_lost_payload_write_fails_bit_exact_restore(tmp_path) -> None:
    """Drop a data-object write from the journal: the committed metadata
    then references bytes that never became durable, and invariant A flags
    the commit-point effect."""
    effects = _journaled_takes(str(tmp_path / "bucket"), seeds=(1,))
    payload_i = next(
        i for i, e in enumerate(effects) if e.path.startswith("0/")
    )
    broken = [e for i, e in enumerate(effects) if i != payload_i]

    with pytest.raises(crash_explorer.CrashStateViolation) as exc:
        crash_explorer.explore(
            broken, str(tmp_path / "explore"), seed=0, interior_samples=0
        )
    assert any(
        "not bit-exact" in v.problem or "failed verify" in v.problem
        for v in exc.value.report.violations
    )


def test_explore_journal_requires_enabled_nonempty_journal(tmp_path) -> None:
    with pytest.raises(RuntimeError, match="disabled"):
        crash_explorer.explore_journal(str(tmp_path / "x"))
    with knobs.override_debug_effects(True):
        effect_journal.reset()
        with pytest.raises(RuntimeError, match="empty"):
            crash_explorer.explore_journal(str(tmp_path / "x"))
