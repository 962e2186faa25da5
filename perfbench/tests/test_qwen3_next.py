"""Qwen3-Next as the benchmark runs it (``perfbench/models/qwen3_next.py``)
against its plain float32 reference (``perfbench/models/reference/qwen3_next.py``)
at ``TINY`` widths on the CPU, and its leaves against the published tensor
names. ``tests/test_qwen3_next.py`` runs these under the repo's tier-1 too.

Tolerances. With float32 parameters the system and the reference compute the
same equations in the same precision and differ only in the order of sums
(chunked against position by position, sorted rows against a loop over
experts): 1e-4 relative on the loss, 2e-3 of a gradient's largest element.
With the bf16 parameters the configuration states, the system keeps bf16
activations where the reference has float32: 2e-2 relative on the loss, the
order of bf16's 8 bits of mantissa over a few dozen roundings.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import run, trainstate  # noqa: E402

arch = run.find_architecture(ROOT, "qwen3_next")
ref = run.load_module("pb_reference_qwen3_next", os.path.join(ROOT, "perfbench", "models", "reference", "qwen3_next.py"))
CONFIG = json.load(open(os.path.join(ROOT, "perfbench", "configs", "qwen3-next-80b-a3b-ep16.json")))
TINY = dict(CONFIG, **arch.TINY)
SHARES = TINY["num_routed_experts"] // TINY["num_experts"]


def seeded_params(cfg, seed, dtype=None, spread=4.0):
    """Every leaf from the architecture's own rule, the matrices scaled up and
    the norms and biases moved off their initial 0 and 1, so that no term of
    the equations is multiplied away."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(arch.param_tree(cfg))
    out = []
    for (path, leaf), key in zip(leaves, jax.random.split(jax.random.PRNGKey(seed), len(leaves))):
        path = trainstate.path_str(path)
        value = arch.init_leaf(path, leaf, key).astype(jnp.float32)
        if leaf.ndim == 1:
            value = value + 0.3 * jax.random.normal(key, leaf.shape)
        elif not path.endswith("A_log"):
            value = value * spread
        out.append(value.astype(dtype or leaf.dtype))
    return treedef.unflatten(out)


def tokens_of(cfg, seed, batch, length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, arch.token_range(cfg))


# (a) the loss and its gradients against the reference ------------------------

@pytest.mark.parametrize("length", [32, 100])
def test_loss_and_gradients_equal_the_references_in_float32(length):
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, length)
    held = arch.held_experts(TINY)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: arch.loss_fn(TINY, p, tokens)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(TINY, p, tokens, experts=held)))(params)
    assert abs(float(loss) - float(want)) <= 1e-4 * abs(float(want))
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == 70
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0.0, trainstate.path_str(path)  # every leaf is used
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-3 * float(jnp.max(jnp.abs(w))), trainstate.path_str(path)


def test_loss_in_the_stated_dtypes_is_near_the_float32_reference():
    params, tokens = seeded_params(TINY, 3), tokens_of(TINY, 4, 2, 100)
    dtypes = {str(x.dtype) for x in jax.tree_util.tree_leaves(params)}
    assert dtypes == {"bfloat16", "float32"}
    loss = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    assert abs(loss - want) <= 2e-2 * abs(want)


@pytest.mark.parametrize("broken", ["shared_expert", "output_gate", "decay"])
def test_the_comparison_is_tight_enough_to_see_a_part_left_out(broken, monkeypatch):
    """The float32 tolerance of the loss fails a system without its shared
    expert, its attention output gate or its decay."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 100)
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    if broken == "shared_expert":
        layer = arch.expert_layer
        monkeypatch.setattr(arch, "expert_layer", lambda cfg, p, x: layer(cfg, p, x, shared=False))
    elif broken == "output_gate":
        attention = arch._attention
        monkeypatch.setattr(arch, "_attention", lambda cfg, p, x: attention(cfg, p, x, output_gate=False))
    else:
        rule = arch.chunked_delta_rule
        monkeypatch.setattr(arch, "chunked_delta_rule", lambda q, k, v, g, beta: rule(q, k, v, 0.0 * g, beta))
    loss = float(arch.loss_fn(TINY, params, tokens))
    assert abs(loss - want) > 1e-3 * abs(want)


# (b) the shares add up to the uncut layer --------------------------------------

def test_expert_layer_summed_over_all_shares_is_the_uncut_references():
    """model-configs section 4: what every share's experts give, with what
    every chip computes alike (the shared expert) counted once, adds up to
    the uncut reference's expert layer."""
    routed, held = TINY["num_routed_experts"], TINY["num_experts"]
    uncut = seeded_params(dict(TINY, num_experts=routed), 5, jnp.float32)["model"]["layers"]["0"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, TINY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(TINY, uncut, x, (0, routed))
    total = jnp.zeros_like(x)
    for rank in range(SHARES):
        cfg = dict(TINY, expert_parallel_rank=rank)
        lo, hi = arch.held_experts(cfg)
        assert (lo, hi) == (rank * held, (rank + 1) * held)
        mine = dict(uncut, experts={k: v[lo:hi] for k, v in uncut["experts"].items()})
        total = total + arch.expert_layer(cfg, mine, x, shared=rank == 0)
    assert float(jnp.max(jnp.abs(total - want))) <= 1e-4 * float(jnp.max(jnp.abs(want)))
    # And a share alone is the reference's for that range: nothing stands in for the absent.
    alone = arch.expert_layer(TINY, dict(uncut, experts={k: v[:held] for k, v in uncut["experts"].items()}), x)
    with jax.default_matmul_precision("highest"):
        want_alone = ref.expert_layer(TINY, uncut, x, (0, held))
    assert float(jnp.max(jnp.abs(alone - want_alone))) <= 1e-4 * float(jnp.max(jnp.abs(want_alone)))
    assert float(jnp.max(jnp.abs(alone - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))


# (c) chunked recurrence against position by position ---------------------------

@pytest.mark.parametrize("length,chunk", [(150, 64), (37, 16), (64, 64), (5, 64)])
def test_chunked_delta_rule_equals_the_recurrence_position_by_position(length, chunk):
    keys = jax.random.split(jax.random.PRNGKey(length), 5)
    b, h, dk, dv = 2, 3, 8, 16
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, length, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, h, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    g = -jax.random.uniform(keys[3], (b, length, h), minval=0.0, maxval=3.0)
    beta = jax.random.uniform(keys[4], (b, length, h))
    got = arch.chunked_delta_rule(q, k, v, g, beta, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(q, k, v, g, beta)
    assert got.shape == want.shape == (b, length, h, dv)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * float(jnp.max(jnp.abs(want)))
    # Gradients through the triangular inverse's own rule, against the recurrence's.
    weight = jax.random.normal(keys[0], want.shape)
    grad = jax.grad(lambda k_: jnp.sum(arch.chunked_delta_rule(q, k_, v, g, beta, chunk=chunk) * weight))(k)
    with jax.default_matmul_precision("highest"):
        want_grad = jax.grad(lambda k_: jnp.sum(ref.delta_rule(q, k_, v, g, beta) * weight))(k)
    assert float(jnp.max(jnp.abs(grad - want_grad))) <= 1e-3 * float(jnp.max(jnp.abs(want_grad)))


# (d) leaf names against the published tensor names -----------------------------

LAYER = ["input_layernorm.weight", "post_attention_layernorm.weight", "mlp.gate.weight",
         "mlp.shared_expert.gate_proj.weight", "mlp.shared_expert.up_proj.weight",
         "mlp.shared_expert.down_proj.weight", "mlp.shared_expert_gate.weight"]
# The one departure: the held experts of a layer are three stacked leaves, where the
# checkpoint has mlp.experts.<e>.{gate,up,down}_proj.weight for each expert e.
STACKS = ["mlp.experts.gate_proj", "mlp.experts.up_proj", "mlp.experts.down_proj"]
DELTA_NET = ["linear_attn.in_proj_qkvz.weight", "linear_attn.in_proj_ba.weight", "linear_attn.conv1d.weight",
             "linear_attn.out_proj.weight", "linear_attn.dt_bias", "linear_attn.A_log", "linear_attn.norm.weight"]
ATTENTION = ["self_attn.q_proj.weight", "self_attn.k_proj.weight", "self_attn.v_proj.weight",
             "self_attn.o_proj.weight", "self_attn.q_norm.weight", "self_attn.k_norm.weight"]


def published_names(cfg):
    names = ["model.embed_tokens.weight", "model.norm.weight", "lm_head.weight"]
    for i in range(cfg["num_hidden_layers"]):
        mixer = ATTENTION if (i + 1) % cfg["full_attention_interval"] == 0 else DELTA_NET
        names += [f"model.layers.{i}.{n}" for n in LAYER + STACKS + mixer]
    return sorted(names)


def test_leaves_are_the_published_tensor_names_and_the_stated_shapes_and_dtypes():
    leaves = {
        trainstate.path_str(p).replace("/", "."): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(arch.param_tree(CONFIG))[0]
    }
    assert sorted(leaves) == published_names(CONFIG)
    depth = CONFIG["num_hidden_layers"]
    assert len(leaves) == depth // 4 * (3 * 17 + 16) + 3 == {8: 137, 12: 204}[depth]
    float32 = {n for n, leaf in leaves.items() if leaf.dtype == jnp.float32}
    assert float32 == {n for n in leaves if n.endswith(("A_log", "dt_bias", "mlp.gate.weight"))}
    assert all(leaf.dtype == jnp.bfloat16 for n, leaf in leaves.items() if n not in float32)
    assert leaves["model.layers.0.mlp.experts.gate_proj"].shape == (32, 2048, 512)
    assert leaves["model.layers.0.mlp.experts.down_proj"].shape == (32, 512, 2048)
    assert leaves["model.layers.0.mlp.gate.weight"].shape == (2048, 512)
    assert leaves["model.layers.0.linear_attn.in_proj_qkvz.weight"].shape == (2048, 12288)
    assert leaves["model.layers.0.linear_attn.conv1d.weight"].shape == (8192, 1, 4)
    assert leaves["model.layers.3.self_attn.q_proj.weight"].shape == (2048, 8192)
    assert leaves["model.embed_tokens.weight"].shape == leaves["lm_head.weight"].shape == (18992, 2048)
    # The sizes ISSUE 28 reckons: parameters and bytes of the params and of the state.
    job = trainstate.Job(arch, CONFIG, jax.devices()[:1])
    count, nbytes = trainstate.tree_size(job.abstract["params"]), trainstate.tree_nbytes(job.abstract["params"])
    assert (round(count / 1e9, 3), round(nbytes / 1e9, 2)) == {8: (1.174, 2.36), 12: (1.721, 3.47)}[depth]
    assert round(trainstate.tree_nbytes(job.abstract) / 1e9, 2) == {8: 7.09, 12: 10.40}[depth]
    small = [leaf for leaf in leaves.values() if np.prod(leaf.shape) * leaf.dtype.itemsize < 1 << 20]
    assert len(small) == {8: 59, 12: 88}[depth]


def test_param_spec_puts_the_expert_axis_on_the_stacks_and_the_vocabulary():
    assert tuple(arch.param_spec("model/layers/0/mlp/experts/up_proj")) == ("ep",)
    assert tuple(arch.param_spec("model/embed_tokens/weight")) == ("ep",)
    assert tuple(arch.param_spec("lm_head/weight")) == ("ep",)
    assert tuple(arch.param_spec("model/layers/0/mlp/gate/weight")) == ()
    cfg = dict(TINY, layout={"chips": 2, "mesh": {"ep": 2}}, job=dict(TINY["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:2])
    shardings = {trainstate.path_str(p): s.spec for p, s in jax.tree_util.tree_flatten_with_path(job.shardings)[0]}
    assert tuple(shardings["opt_state/0/mu/model/layers/1/mlp/experts/down_proj"]) == ("ep",)
    assert tuple(shardings["params/model/layers/1/linear_attn/A_log"]) == ()
    state, loss = job.train_step(job.init_state(7), job.make_batches(7, 1)[0])
    assert float(loss) > 0.0 and state["params"]["lm_head"]["weight"].sharding.spec == shardings["params/lm_head/weight"]


# (f) the scope of a device operation, read from the trace's wire format -----------

RECORDED = os.path.join(ROOT, "perfbench", "tests", "recorded_v5e.xplane.pb")


def test_the_op_name_of_a_device_operation_is_read_from_the_recorded_trace():
    """``stepscopes.op_names`` finds the name jax gave the recorded step's
    one fusion, and the share of the traced window under a scope follows."""
    from perfbench import stepscopes

    names = stepscopes.op_names(RECORDED)
    assert [(event.split(" = ")[0], op) for event, op in names.items()] == [("%fusion", "jit(pb_train_step)/dot_general:")]
    assert 0.0 < stepscopes.share_pct(RECORDED, "pb_train_step") < 100.0
    assert stepscopes.share_pct(RECORDED, "qn.moe") == 0.0


def test_profile_data_still_hides_the_op_name():
    """Why ``stepscopes`` reads the wire format: no event of the device plane
    carries ``tf_op`` among the stats ``ProfileData`` hands out. When this
    fails, jax has begun to hand it out and the private reader can go."""
    from jax.profiler import ProfileData

    stats = {
        name
        for plane in ProfileData.from_file(RECORDED).planes if plane.name.startswith("/device:TPU:")
        for line in plane.lines for event in line.events for name, _ in event.stats
    }
    assert stats and "tf_op" not in stats
