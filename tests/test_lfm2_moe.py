"""LFM2-24B-A2B in tier-1: the architecture against its float32 reference (the
tests of ``perfbench/tests/test_lfm2_moe.py``, which the driver's run of
``tests/`` alone would never reach: loss and gradients, the gated short
convolution tap by tap, both gates, blocked attention, the per-head norms before
the rotation, the share of the experts, the biased sigmoid routing, the tied
table's summed gradient, ``layer_types``, the leaf names), and its train state
through the library with default knobs: ``take``, ``async_take``, ``restore``,
and the restore through the arena of host pages.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run, trainstate  # noqa: E402
from test_host_arena import arenas, devices_that_copy  # noqa: E402,F401 - fixtures: a place that copies, the arenas a restore makes

_model_tests = run.load_module("pb_test_lfm2_moe", os.path.join(ROOT, "perfbench", "tests", "test_lfm2_moe.py"))
globals().update({name: obj for name, obj in vars(_model_tests).items() if name.startswith("test_")})
arch, TINY, TINY_LEAVES = _model_tests.arch, _model_tests.TINY, _model_tests.TINY_LEAVES
# Whatever these tests start keeps its compile cache under their own tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


def bits(x):
    host = np.asarray(x)
    return host.reshape(-1).view(f"uint{8 * host.dtype.itemsize}")


def trained_state():
    """The tiny train state after one step (moments off zero), and its leaves' bits."""
    job = trainstate.Job(arch, dict(TINY, job=dict(TINY["job"], seq_len=32)), jax.devices()[:1])
    state = job.init_state(2147483999)
    state, _ = job.train_step(state, job.make_batches(2147483999, 1)[0])
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    return job, state, leaves, [bits(x).copy() for _, x in leaves]


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_the_tiny_train_state_goes_through_take_and_restore_bit_for_bit(how, tmp_path):
    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.tricks.train_state import Box, PyTreeStateful

    job, state, leaves, want = trained_state()
    kinds = {(str(x.dtype), x.ndim) for _, x in leaves}
    assert {("float32", 1), ("float32", 2), ("bfloat16", 3), ("bfloat16", 2), ("bfloat16", 1)} <= kinds
    # float32 beside bf16, each with its two moments: the router and its bias in the eight sparse layers.
    float32 = [trainstate.path_str(p) for p, x in leaves if str(x.dtype) == "float32"]
    assert len(float32) == 3 * 8 * 2 and all(p.endswith(_model_tests.FLOAT32) for p in float32)
    # Three stacks (held, in, out) a sparse layer; the convolution's taps as published, (channels, 1, taps);
    # and one table, with its two moments, for the embedding and the head: no lm_head anywhere in the take.
    stacks = [x.shape for p, x in leaves if "/experts/" in trainstate.path_str(p)]
    assert len(stacks) == 3 * 8 * 3 and set(stacks) == {(2, 64, 32), (2, 32, 64)}
    taps = [x.shape for p, x in leaves if trainstate.path_str(p).endswith("conv/conv/weight")]
    assert len(taps) == 3 * 7 and set(taps) == {(64, 1, 3)}
    paths = [trainstate.path_str(p) for p, _ in leaves]
    assert sum("embed_tokens" in p for p in paths) == 3 and not any("lm_head" in p for p in paths)
    path = str(tmp_path / "snap")
    app_state = {"train": PyTreeStateful(Box(state))}
    if how == "take":
        Snapshot.take(path, app_state)
    else:
        pending = Snapshot.async_take(path, app_state)
        trainstate.free_tree(state)  # as a donated step would
        pending.wait()
    box = Box(job.zero_targets("state"))
    Snapshot(path).restore({"train": PyTreeStateful(box)})
    got = jax.tree_util.tree_leaves(box.value)
    assert len(got) == len(want) == 3 * TINY_LEAVES + 1 == 289
    assert all((bits(g) == w).all() for g, w in zip(got, want))
    assert [(g.shape, g.dtype) for g in got] == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(job.abstract)]
    artifact = json.load(open(os.path.join(path, ".telemetry", "rank_0.json")))
    assert artifact["metrics"]["take.leaves"] == len(want)
    assert artifact["metrics"].get("capture.host_captured_bytes", 0) == 0


def test_the_tiny_train_state_is_restored_through_the_arena(tmp_path, monkeypatch, devices_that_copy):
    """On an accelerator a leaf bound for the device is read into a view of
    the restore's arena (``host_arena.py``); the CPU backend takes fresh
    pages, so the devices are said to copy and the place does
    (``tests/test_host_arena.py``), every page scribbled over as it goes
    back. Every stack, every float32 leaf and the three-dimensional taps are
    such views, and every leaf comes back bit for bit."""
    from torchsnapshot_tpu import Snapshot, native
    from torchsnapshot_tpu import snapshot as snapshot_mod
    from torchsnapshot_tpu.tricks.train_state import Box, PyTreeStateful
    from torchsnapshot_tpu.utils import knobs

    if native.load_native() is None:
        pytest.skip("native IO engine unavailable")
    job, state, leaves, want = trained_state()
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"train": PyTreeStateful(Box(state))})
    leased = []
    bind = snapshot_mod._LeasedHostTargets._bind

    def spy(self, views):
        leased.extend((tuple(shape), str(dtype), views is not None) for shape, dtype in self.specs)
        bind(self, views)

    monkeypatch.setattr(snapshot_mod._LeasedHostTargets, "_bind", spy)
    box = Box(job.zero_targets("state"))
    # Toy-sized leaves take the native route, as the configuration's do at their size.
    with knobs.override_direct_io_threshold_bytes(1024), knobs.override_restore_overlap(True):
        Snapshot(path).restore({"train": PyTreeStateful(box)})
    got = jax.tree_util.tree_leaves(box.value)
    assert len(got) == len(want) and all((bits(g) == w).all() for g, w in zip(got, want))
    (arena,) = devices_that_copy
    assert arena._closed and arena.in_use_hwm_bytes > 0
    views = {(shape, dtype) for shape, dtype, view in leased if view}
    for _, x in leaves:
        if x.ndim == 3 or str(x.dtype) == "float32":
            assert (tuple(x.shape), str(x.dtype)) in views
    assert all(view for _, _, view in leased) and len(leased) == len(want)  # adamw's count too
    stats = snapshot_mod.LAST_RESTORE_STATS
    assert stats["recycled_bytes"] + stats["fresh_target_bytes"] == sum(w.nbytes for w in want)
