"""The architecture of a configuration is found by its ``model_type``, and
the generic train state built on it is, leaf for leaf, the one the harness
had when it knew one architecture only (``models/<model_type>.parent_state.json``,
taken from the parent's ``Job`` before the split: path, shape, dtype and,
on a mesh, ``PartitionSpec`` of every leaf)."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402

MODELS = os.path.join(ROOT, "perfbench", "models")
CONFIGS = {
    os.path.basename(p)[:-5]: json.load(open(p))
    for p in sorted(glob.glob(os.path.join(ROOT, "perfbench", "configs", "*.json")))
}
PINNED = {
    (os.path.basename(p).split(".")[0], name): pinned
    for p in sorted(glob.glob(os.path.join(MODELS, "*.parent_state.json")))
    for name, pinned in json.load(open(p)).items()
}
GIVES = ("param_tree", "init_leaf", "param_spec", "loss_fn", "token_range", "TINY", "PUBLISHED")


def job_of(cfg, **kwargs):
    import jax

    from perfbench import trainstate

    arch = run.find_architecture(ROOT, cfg["model_type"])
    return trainstate.Job(arch, cfg, jax.devices()[: cfg["layout"]["chips"]], **kwargs)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_configuration_finds_its_architecture_and_all_it_gives(name):
    arch = run.find_architecture(ROOT, CONFIGS[name]["model_type"])
    assert all(hasattr(arch, attr) for attr in GIVES)
    # The toy widths replace keys the configuration has, and nothing else.
    assert arch.TINY and set(arch.TINY) <= set(CONFIGS[name])


def test_an_unknown_model_type_is_refused_by_name_with_the_files_there_are():
    there = sorted(os.path.basename(p)[:-3] for p in glob.glob(os.path.join(MODELS, "*.py")))
    with pytest.raises(SystemExit) as refusal:
        run.find_architecture(ROOT, "no_such_type")
    assert isinstance(refusal.value, run.Refused)
    assert "'no_such_type'" in str(refusal.value) and str(there) in str(refusal.value)


@pytest.mark.parametrize("model_type,name", sorted(PINNED))
def test_the_state_is_leaf_for_leaf_the_parents(model_type, name):
    import jax

    from perfbench import trainstate

    cfg, pinned = CONFIGS[name], PINNED[model_type, name]
    assert cfg["model_type"] == model_type
    job = job_of(cfg)
    assert list(job.batch_shape) == pinned["batch_shape"]
    leaves = jax.tree_util.tree_flatten_with_path(job.abstract)[0]
    shardings = jax.tree_util.tree_leaves(job.shardings)
    got = [
        [trainstate.path_str(p), list(a.shape), str(a.dtype), list(s.spec) if job.mesh is not None else None]
        for (p, a), s in zip(leaves, shardings)
    ]
    assert got == pinned["leaves"]
    params = [row for row in pinned["leaves"] if row[0].startswith("params/")]
    assert len(jax.tree_util.tree_leaves(job.abstract["params"])) == len(params)
    # adamw: two moments a parameter and one count.
    assert len(got) == 3 * len(params) + 1


@pytest.mark.parametrize("name", sorted(n for n in CONFIGS if CONFIGS[n]["layout"].get("mesh")))
def test_the_batch_is_split_over_the_meshs_first_axis_and_the_restore_mesh_is_the_transposed_grid(name):
    cfg = CONFIGS[name]
    job, other = job_of(cfg), job_of(cfg, transposed=True)
    first = next(iter(cfg["layout"]["mesh"]))
    assert tuple(job.batch_sharding.spec) == (first,)
    assert job.batch_shape[0] == cfg["job"]["micro_batch"] * cfg["layout"]["mesh"][first]
    assert other.mesh.axis_names == job.mesh.axis_names
    assert (other.mesh.devices == job.mesh.devices.T).all()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_from_a_seed_are_the_abstract_state_and_a_step_keeps_every_dtype(name):
    import jax

    arch = run.find_architecture(ROOT, CONFIGS[name]["model_type"])
    cfg = dict(CONFIGS[name], **arch.TINY)
    job = job_of(dict(cfg, job=dict(cfg["job"], seq_len=32)))
    state = job.init_state(2147483999)
    tokens = job.make_batches(2147483999, 2)
    assert int(max(t.max() for t in tokens)) < arch.token_range(cfg)
    same = job.init_state(2147483999)
    assert all((a == b).all() for a, b in zip(*map(jax.tree_util.tree_leaves, (state, same))))
    for _ in range(2):
        shapes = [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(state)]
        assert shapes == [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(job.abstract)]
        state, loss = job.train_step(state, tokens[0])
        assert float(loss) == float(loss) and float(loss) > 0.0
