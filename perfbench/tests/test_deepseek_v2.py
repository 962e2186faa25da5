"""DeepSeek-V2 as the benchmark runs it (``perfbench/models/deepseek_v2.py``)
against its plain float32 reference (``perfbench/models/reference/deepseek_v2.py``)
at ``TINY`` widths on the CPU, its two shares (experts, heads) against the
uncut layer, and its leaves against the published tensor names.
``tests/test_deepseek_v2.py`` runs these under the repo's tier-1 too.

Tolerances. With float32 parameters the system and the reference compute the
same equations in the same precision and differ only in the order of sums
(blocks of queries against whole rows, two score products against one over
joined keys, sorted rows against a loop over experts): 1e-4 relative on the
loss, 2e-3 of a gradient's largest element. With the bf16 parameters the
configuration states, the system keeps bf16 activations where the reference
has float32: 2e-2 relative on the loss, the order of bf16's 8 bits of
mantissa over a few dozen roundings.
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

arch = run.find_architecture(ROOT, "deepseek_v2")
ref = run.load_module("pb_reference_deepseek_v2", os.path.join(ROOT, "perfbench", "models", "reference", "deepseek_v2.py"))
CONFIG = json.load(open(os.path.join(ROOT, "perfbench", "configs", "deepseek-v2-ep16.json")))
TINY = dict(CONFIG, **arch.TINY)
SHARES = TINY["num_routed_experts"] // TINY["n_routed_experts"]  # chips that share a layer
# The uncut model at toy widths: every expert, every head.
UNCUT = dict(TINY, n_routed_experts=TINY["num_routed_experts"], num_attention_heads=SHARES * TINY["num_attention_heads"])


def seeded_params(cfg, seed, dtype=None, spread=4.0):
    """Every leaf from the architecture's own rule, the matrices scaled up and
    the norms moved off their initial 1, so that no term of the equations is
    multiplied away."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(arch.param_tree(cfg))
    out = []
    for (path, leaf), key in zip(leaves, jax.random.split(jax.random.PRNGKey(seed), len(leaves))):
        value = arch.init_leaf(trainstate.path_str(path), leaf, key).astype(jnp.float32)
        value = value + 0.3 * jax.random.normal(key, leaf.shape) if leaf.ndim == 1 else value * spread
        out.append(value.astype(dtype or leaf.dtype))
    return treedef.unflatten(out)


def tokens_of(cfg, seed, batch, length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, arch.token_range(cfg))


def close(got, want, relative):
    return float(jnp.max(jnp.abs(got - want))) <= relative * float(jnp.max(jnp.abs(want)))


# (a) the loss and its gradients against the reference ------------------------

@pytest.mark.parametrize("length,block", [(32, 1024), (100, 1024), (100, 32)])
def test_loss_and_gradients_equal_the_references_in_float32(length, block, monkeypatch):
    """``block`` 32 cuts the 100 positions into four blocks of queries and of
    the head, the last one short, as 1024 cuts the configuration's 4096."""
    monkeypatch.setattr(arch, "QUERY_BLOCK", block)
    monkeypatch.setattr(arch, "HEAD_BLOCK", block)
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, length)
    held = arch.held_experts(TINY)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: arch.loss_fn(TINY, p, tokens)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(TINY, p, tokens, experts=held)))(params)
    assert abs(float(loss) - float(want)) <= 1e-4 * abs(float(want))
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == 12 + 2 * 16 + 3
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0.0, trainstate.path_str(path)  # every leaf is used
        assert close(g, w, 2e-3), trainstate.path_str(path)


def test_loss_in_the_stated_dtypes_is_near_the_float32_reference():
    params, tokens = seeded_params(TINY, 3), tokens_of(TINY, 4, 2, 100)
    by_dtype = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        by_dtype.setdefault(str(x.dtype), []).append(trainstate.path_str(path))
    assert set(by_dtype) == {"bfloat16", "float32"}
    assert all(p.endswith("mlp/gate/weight") for p in by_dtype["float32"]) and len(by_dtype["float32"]) == 2
    loss = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    assert abs(loss - want) <= 2e-2 * abs(want)


@pytest.mark.parametrize("broken", [None, "shared_experts", "group_limit", "k_pe_rotation", "kv_a_layernorm"])
def test_the_comparison_is_tight_enough_to_see_a_part_left_out(broken, monkeypatch):
    """On the reference's own most likely next tokens (the training loss on
    random targets is ``log(rows) + var / 2`` of the logits whatever the layers
    compute, so it hardly sees them) the float32 tolerance of the loss holds
    the sound system and fails one without its shared experts, with a plain
    top-k in place of the group-limited one, with the shared rotary key left
    unrotated, or without the norm of the kv latent."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 100)
    inputs, held = tokens[:, :-1], arch.held_experts(TINY)
    greedy = jnp.argmax(jax.jit(lambda p: ref.logits(TINY, p, inputs, held))(params), axis=-1)
    want = float(jnp.mean(jax.jit(lambda p: ref.token_nll(TINY, p, inputs, greedy, held))(params)))
    layer, attention = arch.expert_layer, arch.attention
    if broken == "shared_experts":
        monkeypatch.setattr(arch, "expert_layer", lambda cfg, p, x: layer(cfg, p, x, shared=False))
    elif broken == "group_limit":
        monkeypatch.setattr(arch, "expert_layer", lambda cfg, p, x: layer(cfg, p, x, group_limit=False))
    elif broken == "k_pe_rotation":
        monkeypatch.setattr(arch, "attention", lambda cfg, p, x: attention(cfg, p, x, rotate_key=False))
    elif broken == "kv_a_layernorm":
        monkeypatch.setattr(arch, "attention", lambda cfg, p, x: attention(cfg, p, x, norm_kv=False))
    loss = float(jnp.mean(arch.token_nll(TINY, params, inputs, greedy)))
    assert (abs(loss - want) <= 1e-4 * abs(want)) == (broken is None)
    assert broken is None or abs(loss - want) > 1e-3 * abs(want)


# (b) the shares add up to the uncut layer --------------------------------------

def test_expert_layer_summed_over_all_shares_is_the_uncut_references():
    """model-configs section 4: what every share's experts give, with what
    every chip computes alike (the shared experts) counted once, adds up to
    the uncut reference's expert layer."""
    routed, held = TINY["num_routed_experts"], TINY["n_routed_experts"]
    uncut = seeded_params(UNCUT, 5, jnp.float32)["model"]["layers"]["1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, TINY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(TINY, uncut, x, (0, routed))
    total = jnp.zeros_like(x)
    for rank in range(SHARES):
        cfg = dict(TINY, layer_share_rank=rank)
        lo, hi = arch.held_experts(cfg)
        assert (lo, hi) == (rank * held, (rank + 1) * held)
        mine = dict(uncut, experts={k: v[lo:hi] for k, v in uncut["experts"].items()})
        total = total + arch.expert_layer(cfg, mine, x, shared=rank == 0)
    assert close(total, want, 1e-4)
    # And a share alone is the reference's for that range: nothing stands in for the absent.
    alone = arch.expert_layer(TINY, dict(uncut, experts={k: v[:held] for k, v in uncut["experts"].items()}), x)
    with jax.default_matmul_precision("highest"):
        want_alone = ref.expert_layer(TINY, uncut, x, (0, held))
    assert close(alone, want_alone, 1e-4)
    assert not close(alone, want, 1e-2)


def heads_of(cfg, p, lo, hi):
    """The rows of ``q_b_proj``, ``kv_b_proj`` and ``o_proj`` that belong to
    heads ``[lo, hi)`` of an attention's parameters."""
    q, kv, v = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q, kv = q + kv, cfg["qk_nope_head_dim"] + v
    return dict(
        p,
        q_b_proj={"weight": p["q_b_proj"]["weight"][lo * q:hi * q]},
        kv_b_proj={"weight": p["kv_b_proj"]["weight"][lo * kv:hi * kv]},
        o_proj={"weight": p["o_proj"]["weight"][lo * v:hi * v]},
    )


def test_attention_summed_over_all_shares_of_the_heads_is_the_uncut_references():
    """The other division: what every share's heads give through their rows
    of ``o_proj`` adds up to the uncut reference's attention; the latents are
    whole in every share."""
    held = TINY["num_attention_heads"]
    uncut = seeded_params(UNCUT, 7, jnp.float32)["model"]["layers"]["0"]["self_attn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, TINY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(UNCUT, uncut, x)
    total = jnp.zeros_like(x)
    for rank in range(SHARES):
        cfg = dict(TINY, layer_share_rank=rank)
        lo, hi = arch.held_heads(cfg)
        assert (lo, hi) == (rank * held, (rank + 1) * held)
        mine = heads_of(TINY, uncut, lo, hi)
        assert mine["q_a_proj"] is uncut["q_a_proj"] and mine["kv_a_proj_with_mqa"] is uncut["kv_a_proj_with_mqa"]
        total = total + arch.attention(cfg, mine, x)
    assert close(total, want, 1e-4)
    alone = arch.attention(TINY, heads_of(TINY, uncut, 0, held), x)
    with jax.default_matmul_precision("highest"):
        want_alone = ref.attention(TINY, heads_of(TINY, uncut, 0, held), x)
    assert close(alone, want_alone, 1e-4)
    assert not close(alone, want, 1e-2)


# (c) group-limited routing against a hand-made case -----------------------------

def test_a_top_expert_outside_the_top_groups_is_not_chosen():
    """16 experts in 4 groups of 4, the 2 best groups kept, top 3. Expert 12
    has the third largest score of all, but its group's maximum (0.10) is
    below those of groups 0 (0.30) and 1 (0.25): the group-limited choice
    passes it over for expert 1, the plain top-3 takes it."""
    cfg = dict(TINY, num_routed_experts=16, n_group=4, topk_group=2, num_experts_per_tok=3)
    scores = np.full((1, 16), 0.025, np.float32)  # 12 x 0.025 + 0.70 = 1
    scores[0, [0, 1, 4, 12]] = [0.30, 0.05, 0.25, 0.10]
    weights, chosen = arch.route(cfg, jnp.asarray(scores))
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 1, 4]
    # Not renormalised: the scores themselves, times routed_scaling_factor.
    want = {0: 0.30, 1: 0.05, 4: 0.25}
    for w, e in zip(np.asarray(weights)[0], np.asarray(chosen)[0]):
        assert w == pytest.approx(want[int(e)] * cfg["routed_scaling_factor"], rel=1e-6)
    _, plain = arch.route(cfg, jnp.asarray(scores), group_limit=False)
    assert sorted(np.asarray(plain)[0].tolist()) == [0, 4, 12]
    # The reference's gate makes the same choice from logits that give these scores.
    p = {"gate": {"weight": jnp.asarray(np.log(scores))}}
    ref_weights, ref_chosen = ref.gate(cfg, p, jnp.ones((1, 1), jnp.float32))
    assert sorted(np.asarray(ref_chosen)[0].tolist()) == [0, 1, 4]
    assert float(jnp.sum(ref_weights)) == pytest.approx(float(jnp.sum(weights)), rel=1e-5)


def test_the_configurations_chip_holds_half_of_a_routing_group():
    lo, hi = arch.held_experts(CONFIG)
    assert (lo, hi) == (0, 10) and CONFIG["num_routed_experts"] // CONFIG["n_group"] == 20
    assert arch.held_heads(CONFIG) == (0, 8)
    assert arch.held_experts(dict(CONFIG, layer_share_rank=15)) == (150, 160)
    assert arch.held_heads(dict(CONFIG, layer_share_rank=15)) == (120, 128)


# (d) YaRN's frequencies and scale ------------------------------------------------

def test_yarn_blends_the_frequencies_and_scales_the_scores_as_published():
    """The fast dims keep their frequency, the slow ones turn ``factor`` times
    slower, the ramp lies between the dims that turn 32 times and once in the
    original 4096 positions; the scores' scale carries ``m^2``."""
    inv = arch.yarn_inv_freq(CONFIG)
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,) and np.allclose(inv[:10], plain[:10]) and np.allclose(inv[24:], plain[24:] / 40)
    assert all(plain[i] / 40 < inv[i] < plain[i] for i in range(11, 23))
    m = 0.1 * 0.707 * np.log(40) + 1
    assert arch.softmax_scale(CONFIG) == pytest.approx(m * m / np.sqrt(192), rel=1e-12)
    (cos, sin), (ref_cos, ref_sin) = arch.yarn_cos_sin(CONFIG, 8), ref.yarn_cos_sin(CONFIG, 8)
    assert np.allclose(cos, np.cos(np.arange(8)[:, None] * inv[None]), atol=1e-6)
    assert np.allclose(ref_cos[:, :32], cos, atol=1e-6) and np.allclose(ref_sin[:, 32:], sin, atol=1e-6)
    assert arch.softmax_scale(dict(CONFIG, rope_scaling=None)) == pytest.approx(192 ** -0.5)


# (e) leaf names against the published tensor names -----------------------------

ATTENTION = ["self_attn.q_a_proj.weight", "self_attn.q_a_layernorm.weight", "self_attn.q_b_proj.weight",
             "self_attn.kv_a_proj_with_mqa.weight", "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
             "self_attn.o_proj.weight", "input_layernorm.weight", "post_attention_layernorm.weight"]
DENSE = ["mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight"]
SPARSE = ["mlp.gate.weight", "mlp.shared_experts.gate_proj.weight", "mlp.shared_experts.up_proj.weight",
          "mlp.shared_experts.down_proj.weight"]
# The one departure: the held experts of a layer are three stacked leaves, where the
# checkpoint has mlp.experts.<e>.{gate,up,down}_proj.weight for each expert e.
STACKS = ["mlp.experts.gate_proj", "mlp.experts.up_proj", "mlp.experts.down_proj"]


def published_names(cfg):
    names = ["model.embed_tokens.weight", "model.norm.weight", "lm_head.weight"]
    for i in range(cfg["num_hidden_layers"]):
        sparse = i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0
        names += [f"model.layers.{i}.{n}" for n in ATTENTION + (SPARSE + STACKS if sparse else DENSE)]
    return sorted(names)


def test_leaves_are_the_published_tensor_names_and_the_stated_shapes_and_dtypes():
    leaves = {
        trainstate.path_str(p).replace("/", "."): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(arch.param_tree(CONFIG))[0]
    }
    assert sorted(leaves) == published_names(CONFIG)
    assert len(leaves) == 12 + 4 * 16 + 3 == 79
    float32 = {n for n, leaf in leaves.items() if leaf.dtype == jnp.float32}
    assert float32 == {n for n in leaves if n.endswith("mlp.gate.weight")} and len(float32) == 4
    assert all(leaf.dtype == jnp.bfloat16 for n, leaf in leaves.items() if n not in float32)
    shapes = {
        "model.layers.1.mlp.experts.gate_proj": (10, 5120, 1536), "model.layers.1.mlp.experts.down_proj": (10, 1536, 5120),
        "model.layers.1.mlp.gate.weight": (5120, 160), "model.layers.1.mlp.shared_experts.up_proj.weight": (5120, 3072),
        "model.layers.0.mlp.down_proj.weight": (12288, 5120), "model.layers.0.self_attn.q_a_proj.weight": (5120, 1536),
        "model.layers.0.self_attn.q_b_proj.weight": (8 * 192, 1536), "model.layers.0.self_attn.kv_a_proj_with_mqa.weight": (5120, 576),
        "model.layers.0.self_attn.kv_b_proj.weight": (8 * 256, 512), "model.layers.4.self_attn.o_proj.weight": (8 * 128, 5120),
        "model.layers.0.self_attn.q_a_layernorm.weight": (1536,), "model.layers.0.self_attn.kv_a_layernorm.weight": (512,),
        "model.embed_tokens.weight": (12800, 5120), "lm_head.weight": (12800, 5120),
    }
    assert {n: leaves[n].shape for n in shapes} == shapes
    # The sizes ISSUE 32 reckons: parameters and bytes of the params and of the state.
    job = trainstate.Job(arch, CONFIG, jax.devices()[:1])
    count, nbytes = trainstate.tree_size(job.abstract["params"]), trainstate.tree_nbytes(job.abstract["params"])
    assert (round(count / 1e6, 1), round(nbytes / 1e9, 3)) == (1552.9, 3.112)
    assert round(trainstate.tree_nbytes(job.abstract) / 1e9, 3) == 9.337
    small = [leaf for leaf in leaves.values() if np.prod(leaf.shape) * leaf.dtype.itemsize < 1 << 20]
    assert len(small) == 21
    # The uncut model's own count from the same rule: 236 B parameters, as published.
    whole = dict(CONFIG, **CONFIG["published"])
    assert round(trainstate.tree_size(arch.param_tree(whole)) / 1e9, 1) == 235.7


def test_param_spec_names_the_axes_of_both_divisions_and_of_the_vocabulary():
    assert tuple(arch.param_spec("model/layers/1/mlp/experts/up_proj")) == ("ep",)
    assert tuple(arch.param_spec("model/embed_tokens/weight")) == ("ep",)
    assert tuple(arch.param_spec("lm_head/weight")) == ("ep",)
    for by_head in ("q_b_proj", "kv_b_proj", "o_proj"):  # the heads lead in all three
        assert tuple(arch.param_spec(f"model/layers/0/self_attn/{by_head}/weight")) == ("tp",)
    for whole in ("self_attn/q_a_proj", "self_attn/kv_a_proj_with_mqa", "mlp/gate", "mlp/shared_experts/up_proj", "mlp/down_proj"):
        assert tuple(arch.param_spec(f"model/layers/1/{whole}/weight")) == ()
    cfg = dict(TINY, layout={"chips": 4, "mesh": {"ep": 2, "tp": 2}}, job=dict(TINY["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:4])
    shardings = {trainstate.path_str(p): s.spec for p, s in jax.tree_util.tree_flatten_with_path(job.shardings)[0]}
    assert tuple(shardings["opt_state/0/mu/model/layers/1/mlp/experts/down_proj"]) == ("ep",)
    assert tuple(shardings["opt_state/0/nu/model/layers/2/self_attn/kv_b_proj/weight"]) == ("tp",)
    assert tuple(shardings["params/model/layers/1/mlp/gate/weight"]) == ()
    state, loss = job.train_step(job.init_state(7), job.make_batches(7, 1)[0])
    assert float(loss) > 0.0 and state["params"]["lm_head"]["weight"].sharding.spec == shardings["params/lm_head/weight"]
