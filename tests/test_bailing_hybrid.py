"""Ling-3.0-flash in tier-1: the architecture against its float32 reference
(the tests of ``perfbench/tests/test_bailing_hybrid.py``, which the driver's
run of ``tests/`` alone would never reach: loss and gradients, the chunked
delta attention at saturated gates, the share of the experts, the biased
sigmoid routing, the layer pattern, the leaf names), and its train state
through the library with default knobs.
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

_model_tests = run.load_module("pb_test_bailing_hybrid", os.path.join(ROOT, "perfbench", "tests", "test_bailing_hybrid.py"))
globals().update({name: obj for name, obj in vars(_model_tests).items() if name.startswith("test_")})
arch, TINY, TINY_LEAVES = _model_tests.arch, _model_tests.TINY, _model_tests.TINY_LEAVES
# Whatever these tests start keeps its compile cache under their own tmp_path.
pytestmark = pytest.mark.usefixtures("compile_cache_dir")


def bits(x):
    host = np.asarray(x)
    return host.reshape(-1).view(f"uint{8 * host.dtype.itemsize}")


@pytest.mark.parametrize("how", ["take", "async_take"])
def test_the_tiny_train_state_goes_through_take_and_restore_bit_for_bit(how, tmp_path):
    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.tricks.train_state import Box, PyTreeStateful

    job = trainstate.Job(arch, dict(TINY, job=dict(TINY["job"], seq_len=32)), jax.devices()[:1])
    state = job.init_state(2147483999)
    state, _ = job.train_step(state, job.make_batches(2147483999, 1)[0])  # moments off zero
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    want = [bits(x).copy() for _, x in leaves]
    kinds = {(str(x.dtype), x.ndim) for _, x in leaves}
    assert {("float32", 2), ("float32", 1), ("bfloat16", 3), ("bfloat16", 2), ("bfloat16", 1)} <= kinds
    # float32 beside bf16, each with its two moments: A_log and dt_bias in the three KDA layers, the router
    # and its bias in the three sparse ones; and the (channels, taps) convolutions.
    float32 = [trainstate.path_str(p) for p, x in leaves if str(x.dtype) == "float32"]
    assert len(float32) == 3 * (3 * 2 + 3 * 2)
    assert all(p.endswith(("A_log", "dt_bias", "mlp/gate/weight", "mlp/gate/expert_bias")) for p in float32)
    convolutions = [x.shape for p, x in leaves if "conv1d" in trainstate.path_str(p)]
    assert len(convolutions) == 3 * 3 * 3 and set(convolutions) == {(16, 4)}
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
    assert len(got) == len(want) == 3 * TINY_LEAVES + 1
    assert all((bits(g) == w).all() for g, w in zip(got, want))
    assert [(g.shape, g.dtype) for g in got] == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(job.abstract)]
    artifact = json.load(open(os.path.join(path, ".telemetry", "rank_0.json")))
    assert artifact["metrics"]["take.leaves"] == len(want)
    assert artifact["metrics"].get("capture.host_captured_bytes", 0) == 0
