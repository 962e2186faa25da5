"""Restore planning must be O(manifest) total, not O(keys x manifest):
``restore()`` builds a one-pass prefix index instead of rescanning the full
per-rank manifest for every app-state key.
"""

import numpy as np

import torchsnapshot_tpu.snapshot as snapshot_mod
from torchsnapshot_tpu import Snapshot, StateDict


def _many_key_app(n_keys: int, filled: bool):
    return {
        f"k{i:04d}": StateDict(
            a=(np.arange(4, dtype=np.float32) + i)
            if filled
            else np.zeros(4, dtype=np.float32),
            b=i if filled else -1,
        )
        for i in range(n_keys)
    }


class _CountingManifest(dict):
    """Counts full iterations; the index pass should be the only one."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.items_calls = 0

    def items(self):
        self.items_calls += 1
        return super().items()


def test_restore_scans_manifest_once(tmp_path, monkeypatch) -> None:
    n_keys = 50
    app = _many_key_app(n_keys, filled=True)
    snap = Snapshot.take(str(tmp_path / "s"), app)

    counting = {}
    orig = snapshot_mod.get_manifest_for_rank

    def wrapped(metadata, rank):
        m = _CountingManifest(orig(metadata, rank))
        counting["m"] = m
        return m

    monkeypatch.setattr(snapshot_mod, "get_manifest_for_rank", wrapped)

    tgt = _many_key_app(n_keys, filled=False)
    snap.restore(tgt)
    # The per-rank manifest is iterated exactly once (the prefix-index
    # build), independent of the number of app-state keys. The old planner
    # iterated it twice per key (entries + containers): 100 times here.
    assert counting["m"].items_calls == 1, counting["m"].items_calls

    for i in range(n_keys):
        sd = tgt[f"k{i:04d}"]
        assert sd["b"] == i
        assert np.array_equal(sd["a"], np.arange(4, dtype=np.float32) + i)


def test_restore_app_key_containing_slash(tmp_path) -> None:
    """An app-state key with '/' spans manifest paths whose first segment is
    shorter than the key; the prefix index must still route its entries
    (regression: bucketing by first segment + lookup by full key silently
    restored nothing)."""
    app = {
        "opt/adam": StateDict(m=np.arange(3, dtype=np.float32), step=9),
        "opt/sgd": StateDict(v=np.arange(5, dtype=np.float32)),
    }
    snap = Snapshot.take(str(tmp_path / "s"), app)
    tgt = {
        "opt/adam": StateDict(m=np.zeros(3, dtype=np.float32), step=-1),
        "opt/sgd": StateDict(v=np.zeros(5, dtype=np.float32)),
    }
    snap.restore(tgt)
    assert tgt["opt/adam"]["step"] == 9
    assert np.array_equal(tgt["opt/adam"]["m"], np.arange(3, dtype=np.float32))
    assert np.array_equal(tgt["opt/sgd"]["v"], np.arange(5, dtype=np.float32))
