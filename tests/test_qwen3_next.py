"""Qwen3-Next in tier-1: the architecture against its float32 reference
(the tests of ``perfbench/tests/test_qwen3_next.py``, which the driver's run
of ``perfbench/`` alone would never reach: loss and gradients, the shares of
the expert layer, the chunked recurrence, the leaf names), and its train
state through the library with default knobs.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run, trainstate  # noqa: E402

_model_tests = run.load_module("pb_test_qwen3_next", os.path.join(ROOT, "perfbench", "tests", "test_qwen3_next.py"))
globals().update({name: obj for name, obj in vars(_model_tests).items() if name.startswith("test_")})
arch, TINY = _model_tests.arch, _model_tests.TINY
# Whatever these tests start keeps its compile cache under their own tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


# (e) the train state through the library, default knobs -------------------------

def bits(x):
    host = np.asarray(x)
    return host.reshape(-1).view(f"uint{8 * host.dtype.itemsize}") if host.shape else host.reshape(1).view(np.uint8)


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_the_tiny_train_state_goes_through_take_and_restore_bit_for_bit(how, tmp_path):
    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.tricks.train_state import Box, PyTreeStateful

    job = trainstate.Job(arch, dict(TINY, job=dict(TINY["job"], seq_len=32)), jax.devices()[:1])
    state = job.init_state(2147483999)
    state, _ = job.train_step(state, job.make_batches(2147483999, 1)[0])  # moments off zero
    leaves = jax.tree_util.tree_leaves(state)
    want = [bits(x).copy() for x in leaves]
    kinds = {(str(x.dtype), x.ndim) for x in leaves}
    assert {("float32", 1), ("bfloat16", 3), ("float32", 2), ("bfloat16", 1)} <= kinds
    assert any(x.size == 8 for x in leaves)  # the gated norm's 8-element weight
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
    assert len(got) == len(want) == 3 * 70 + 1
    assert all((bits(g) == w).all() for g, w in zip(got, want))
    assert [(g.shape, g.dtype) for g in got] == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(job.abstract)]
    # The take's own record: the new counters are the leaf counts, and the
    # small-object seconds are parts of the streams they belong to.
    artifact = json.load(open(os.path.join(path, ".telemetry", "rank_0.json")))
    sizes = [a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(job.abstract)]
    small = [n for n in sizes if n < 1 << 20]
    assert len(small) == len(sizes)  # toy widths: every leaf is small
    assert artifact["metrics"]["take.leaves"] == len(sizes)
    assert artifact["metrics"]["take.small_leaves"] == len(small)
    assert artifact["metrics"]["take.small_leaf_bytes"] == sum(small)
    for stats in (artifact["drain_stats_s"], artifact["pipeline_stats_s"]):
        assert 0.0 <= stats["stage_d2h_small_s"] <= stats["stage_d2h_s"] + 1e-6
        assert 0.0 <= stats["io_busy_small_s"] <= stats["io_busy_s"] + 1e-6
    assert artifact["pipeline_stats_s"]["io_busy_small_s"] > 0.0


def test_small_seconds_leave_large_objects_out(tmp_path):
    """A leaf of 4 MiB beside one of 32 bytes: the small sub-streams are
    strictly inside their streams, and the write's span carries its size."""
    from torchsnapshot_tpu import Snapshot, StateDict

    app_state = {"m": StateDict(big=jnp.ones((1024, 1024), jnp.float32), tiny=jnp.ones((8,), jnp.float32))}
    Snapshot.take(str(tmp_path / "snap"), app_state)
    artifact = json.load(open(tmp_path / "snap" / ".telemetry" / "rank_0.json"))
    assert artifact["metrics"]["take.leaves"] == 2
    assert artifact["metrics"]["take.small_leaves"] == 1 and artifact["metrics"]["take.small_leaf_bytes"] == 32
    stats = artifact["pipeline_stats_s"]
    assert 0.0 < stats["io_busy_small_s"] < stats["io_busy_s"]
    assert stats["stage_d2h_small_s"] <= stats["stage_d2h_s"]
