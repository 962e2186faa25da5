"""Ling-3.0-flash as the benchmark runs it (``perfbench/models/bailing_hybrid.py``)
against its plain float32 reference (``perfbench/models/reference/bailing_hybrid.py``)
at ``TINY`` widths on the CPU, its share of the experts against the uncut
layer, and its leaves against the tensor names. ``tests/test_bailing_hybrid.py``
runs these under the repo's tier-1 too.

Tolerances. With float32 parameters the system and the reference compute the
same equations in the same precision and differ only in the order of sums
(chunks against position by position, blocks of queries against whole rows,
sorted rows against a loop over experts): 1e-4 relative on the loss, 2e-3 of a
gradient's largest element. With the bf16 parameters the configuration states,
the system keeps bf16 activations where the reference has float32: 2e-2
relative on the loss, the order of bf16's 8 bits of mantissa over a few dozen
roundings.
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

arch = run.find_architecture(ROOT, "bailing_hybrid")
ref = run.load_module("pb_reference_bailing_hybrid", os.path.join(ROOT, "perfbench", "models", "reference", "bailing_hybrid.py"))
CONFIG = json.load(open(os.path.join(ROOT, "perfbench", "configs", "ling-3.0-flash-ep32.json")))
TINY = dict(CONFIG, **arch.TINY)
SHARES = TINY["num_routed_experts"] // TINY["num_experts"]  # chips that share a layer
TINY_LEAVES = 3 + 18 + 2 * 23 + 18  # layer 0 KDA + dense; 1 and 3 KDA + sparse; 2 MLA + sparse
DRAWN = ("A_log", "dt_bias", "expert_bias")  # leaves whose own draw already covers their range


def seeded_params(cfg, seed, dtype=None, spread=4.0):
    """Every leaf from the architecture's own rule, the matrices scaled up and
    the norms moved off their initial 1, so that no term of the equations is
    multiplied away."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(arch.param_tree(cfg))
    out = []
    for (path, leaf), key in zip(leaves, jax.random.split(jax.random.PRNGKey(seed), len(leaves))):
        value = arch.init_leaf(trainstate.path_str(path), leaf, key).astype(jnp.float32)
        if not trainstate.path_str(path).endswith(DRAWN):
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
    the head, the last one short, as 1024 cuts the configuration's 4096; 100
    positions are six chunks of the recurrence and a seventh of four."""
    monkeypatch.setattr(arch, "QUERY_BLOCK", block)
    monkeypatch.setattr(arch, "HEAD_BLOCK", block)
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, length)
    held = arch.held_experts(TINY)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: arch.loss_fn(TINY, p, tokens)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(TINY, p, tokens, experts=held)))(params)
    assert abs(float(loss) - float(want)) <= 1e-4 * abs(float(want))
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(got) == TINY_LEAVES
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want_grads)):
        path = trainstate.path_str(path)
        if path.endswith("expert_bias"):  # a buffer: it steers a choice, and no gradient reaches it
            assert not g.any() and not w.any(), path
            continue
        assert float(jnp.max(jnp.abs(w))) > 0.0, path  # every other leaf is used
        assert close(g, w, 2e-3), path


def test_loss_in_the_stated_dtypes_is_near_the_float32_reference():
    params, tokens = seeded_params(TINY, 3), tokens_of(TINY, 4, 2, 100)
    by_dtype = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        by_dtype.setdefault(str(x.dtype), []).append(trainstate.path_str(path))
    assert set(by_dtype) == {"bfloat16", "float32"}
    assert all(p.endswith(("A_log", "dt_bias", "mlp/gate/weight", "mlp/gate/expert_bias")) for p in by_dtype["float32"])
    assert len(by_dtype["float32"]) == 3 * 2 + 3 * 2
    loss = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    assert abs(loss - want) <= 2e-2 * abs(want)


# The parts the chip's comparison breaks (the function of the architecture to replace, and the control it is
# called with), so that both break the same things.
BROKEN = run.load_module("pb_reference_on_chip_bailing_hybrid", os.path.join(ROOT, "perfbench", "tests", "reference_on_chip_bailing_hybrid.py")).BROKEN


@pytest.mark.parametrize("broken", [None] + sorted(BROKEN))
def test_the_comparison_is_tight_enough_to_see_a_part_left_out(broken, monkeypatch):
    """On the reference's own most likely next tokens (the training loss on
    random targets is ``log(rows) + var / 2`` of the logits whatever the layers
    compute, so it hardly sees them) the float32 tolerance of the loss, taken
    position by position, holds the sound system and fails one whose decay is
    one scalar a head, whose gate is the softplus one, whose bias is left out
    of the choice or let into the weights, whose choice is a plain top-k, or
    that lacks its shared expert or the latent attention's gate a head."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 100)
    inputs, held = tokens[:, :-1], arch.held_experts(TINY)
    greedy = jnp.argmax(jax.jit(lambda p: ref.logits(TINY, p, inputs, held))(params), axis=-1)
    want = jax.jit(lambda p: ref.token_nll(TINY, p, inputs, greedy, held))(params)
    if broken:
        name, control = BROKEN[broken]
        sound = getattr(arch, name)
        monkeypatch.setattr(arch, name, lambda cfg, p, x: sound(cfg, p, x, **control))
    # Position by position, so that gaps of either sign do not cancel in the mean.
    gap = float(jnp.mean(jnp.abs(arch.token_nll(TINY, params, inputs, greedy) - want))) / float(jnp.mean(want))
    assert (gap <= 1e-4) == (broken is None)
    assert broken is None or gap > 1e-3


# (b) the shares add up to the uncut layer --------------------------------------

def test_expert_layer_summed_over_all_shares_is_the_uncut_references():
    """model-configs section 4: what every share's experts give, with what
    every chip computes alike (the shared expert) counted once, adds up to
    the uncut reference's expert layer."""
    routed, held = TINY["num_routed_experts"], TINY["num_experts"]
    uncut = seeded_params(dict(TINY, num_experts=routed), 5, jnp.float32)["model"]["layers"]["1"]["mlp"]
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
    assert close(total, want, 1e-4)
    # And a share alone is the reference's for that range: nothing stands in for the absent.
    alone = arch.expert_layer(TINY, dict(uncut, experts={k: v[:held] for k, v in uncut["experts"].items()}), x)
    with jax.default_matmul_precision("highest"):
        want_alone = ref.expert_layer(TINY, uncut, x, (0, held))
    assert close(alone, want_alone, 1e-4)
    assert not close(alone, want, 1e-2)


def test_the_configurations_chip_holds_a_quarter_of_a_routing_group():
    assert arch.held_experts(CONFIG) == (0, 16) and CONFIG["num_routed_experts"] // CONFIG["n_group"] == 64
    assert arch.held_experts(dict(CONFIG, expert_parallel_rank=31)) == (496, 512)
    assert (CONFIG["topk_group"], CONFIG["num_experts_per_tok"], CONFIG["n_group"]) == (4, 8, 8)


# (c) the chunked recurrence against position by position --------------------------

def kda_inputs(length, g_of):
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    b, h, dk, dv = 2, 3, 8, 16
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, length, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, h, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    beta = jax.random.uniform(keys[4], (b, length, h))
    return (q, k, v, g_of(keys[3], (b, length, h, dk)), beta), jax.random.normal(keys[5], (b, length, h, dv))


@pytest.mark.parametrize("length,chunk", [(150, 16), (37, 16), (16, 16), (5, 16), (50, 8)])
def test_chunked_kda_equals_the_recurrence_position_by_position(length, chunk):
    """A decay a channel, drawn over the whole of ``(kda_lower_bound, 0)``."""
    (q, k, v, g, beta), weight = kda_inputs(length, lambda key, shape: -jax.random.uniform(key, shape, maxval=5.0))
    got = arch.chunked_kda(q, k, v, g, beta, chunk=chunk)
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(q, k, v, g, beta)
    assert got.shape == want.shape == weight.shape
    assert close(got, want, 1e-4)
    # Gradients through the triangular inverse's own rule, against the recurrence's.
    grad = jax.grad(lambda k_, g_: jnp.sum(arch.chunked_kda(q, k_, v, g_, beta, chunk=chunk) * weight), (0, 1))(k, g)
    with jax.default_matmul_precision("highest"):
        want_grad = jax.grad(lambda k_, g_: jnp.sum(ref.delta_rule(q, k_, v, g_, beta) * weight), (0, 1))(k, g)
    assert all(close(a, b, 1e-3) for a, b in zip(grad, want_grad))


@pytest.mark.parametrize("cotangent", [1.0, 1e-9])
def test_chunked_kda_holds_every_gate_at_its_lower_bound(cotangent):
    """The case the chunk size answers: every channel decays by ``exp(-5)`` at
    every position, so the factors of a chunk span ``exp(-40)`` to ``exp(40)``
    about its middle (``exp(80)`` from its start). Nothing overflows, and on
    the way back a cotangent as small as a mean over a batch's tokens leaves
    (1e-9) is not flushed to zero between the small factor and the large one."""
    bound = float(CONFIG["kda_lower_bound"])
    assert -bound * arch.CHUNK / 2 < 44 and arch.CHUNK == 16
    (q, k, v, g, beta), weight = kda_inputs(70, lambda key, shape: jnp.full(shape, bound))
    weight = weight * cotangent
    got, vjp = jax.vjp(arch.chunked_kda, q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want, want_vjp = jax.vjp(ref.delta_rule, q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all()) and close(got, want, 1e-4)
    for a, b in zip(vjp(weight), want_vjp(weight)):
        assert bool(jnp.isfinite(a).all()) and float(jnp.max(jnp.abs(b))) > 0.0 and close(a, b, 1e-3)


def test_the_seeded_decays_cover_the_bounded_range_and_differ_within_a_head():
    """``init_leaf`` draws ``A_log`` and ``dt_bias`` so that the log-decays of
    a layer reach both ends of ``(kda_lower_bound, 0)`` and are a channel's
    own: or the comparisons with one scalar a head would guard nothing."""
    p = seeded_params(TINY, 9, jnp.float32)["model"]["layers"]["0"]["attention"]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 64, TINY["hidden_size"]))
    g = arch.log_decay(TINY, p, x)
    assert g.shape == (2, 64, TINY["num_attention_heads"], TINY["head_dim"])
    assert float(g.min()) < -4.5 and float(g.max()) > -0.5 and bool((g < 0).all()) and bool((g > -5).all())
    assert float(jnp.mean(g.max(-1) - g.min(-1))) > 1.0
    assert float(jnp.max(jnp.abs(arch.log_decay(TINY, p, x, scalar_decay=True) - g.mean(-1, keepdims=True)))) < 1e-6


# (d) sigmoid routing with a bias, against hand-made cases --------------------------

ROUTING = dict(TINY, num_routed_experts=16, n_group=4, topk_group=2, num_experts_per_tok=3)


def routed(scores, bias=None, **controls):
    bias = np.zeros(16, np.float32) if bias is None else bias
    weights, chosen = arch.route(ROUTING, jnp.asarray(scores)[None], jnp.asarray(bias), **controls)
    return dict(zip(np.asarray(chosen)[0].tolist(), np.asarray(weights)[0].tolist()))


def test_a_bias_that_changes_the_choice_leaves_the_weights_to_the_scores():
    """16 experts in 4 groups of 4, the 2 best groups kept, top 3. Without a
    bias experts 0, 1 and 4 are chosen; a bias of 0.3 on expert 5 puts it in
    expert 1's place, and its weight is its score's share, not its biased one."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 1, 4, 5]] = [0.9, 0.5, 0.8, 0.4]
    assert sorted(routed(scores)) == [0, 1, 4]
    bias = np.zeros(16, np.float32)
    bias[5] = 0.3
    got = routed(scores, bias)
    assert sorted(got) == [0, 4, 5]
    for e, w in got.items():  # s of the chosen, normalised to 1, times routed_scaling_factor
        assert w == pytest.approx(2.5 * scores[e] / (0.9 + 0.8 + 0.4), rel=1e-6)
    assert sum(got.values()) == pytest.approx(ROUTING["routed_scaling_factor"], rel=1e-6)
    let_in = routed(scores, bias, bias_in_weights=True)
    assert let_in[5] == pytest.approx(2.5 * 0.7 / (0.9 + 0.8 + 0.7), rel=1e-6)
    assert sorted(routed(scores, bias, bias_in_choice=False)) == [0, 1, 4]
    # The reference's gate makes the same choice with the same weights from logits that give these scores.
    p = {"gate": {"weight": jnp.asarray(np.log(scores / (1 - scores)))[None], "expert_bias": jnp.asarray(bias)}}
    ref_weights, ref_chosen = ref.gate(ROUTING, p, jnp.ones((1, 1), jnp.float32))
    want = dict(zip(np.asarray(ref_chosen)[0].tolist(), np.asarray(ref_weights)[0].tolist()))
    assert sorted(want) == [0, 4, 5] and all(want[e] == pytest.approx(got[e], rel=1e-5) for e in got)


def test_a_top_expert_outside_the_top_groups_is_not_chosen():
    """Expert 12 has the third largest score of all, but its group's two best
    (0.6 + 0.05) are below those of groups 0 (0.9 + 0.5) and 1 (0.8 + 0.05):
    the group-limited choice passes it over for expert 1, the plain top-3
    takes it."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 1, 4, 12]] = [0.9, 0.5, 0.8, 0.6]
    assert sorted(routed(scores)) == [0, 1, 4]
    assert sorted(routed(scores, group_limit=False)) == [0, 4, 12]


def test_a_groups_score_is_the_sum_of_its_two_best():
    """Group 0 holds the largest score of all (0.9) beside 0.05s: 0.95. Groups
    1 (0.6 + 0.6) and 2 (0.55 + 0.55) outscore it by their two best, so the
    choice is made inside them and expert 0 is passed over; by the groups'
    maxima (the rule of ``group_limited_greedy``) it would have been first."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 4, 5, 8, 9]] = [0.9, 0.6, 0.6, 0.55, 0.55]
    got = routed(scores)
    assert 0 not in got and sorted(got)[:2] == [4, 5] and sorted(got)[2] in (8, 9)


def test_the_bias_gets_no_gradient_and_the_router_does():
    p = seeded_params(TINY, 11, jnp.float32)["model"]["layers"]["1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, TINY["hidden_size"]), jnp.float32)
    grads = jax.grad(lambda p_: jnp.sum(jnp.square(arch.expert_layer(TINY, p_, x))))(p)
    assert not grads["gate"]["expert_bias"].any() and bool(jnp.any(grads["gate"]["weight"] != 0))
    # It steers all the same: without it some token's choice is another.
    scores = jax.nn.sigmoid(x.reshape(-1, x.shape[-1]) @ p["gate"]["weight"])
    _, with_bias = arch.route(TINY, scores, p["gate"]["expert_bias"])
    _, without = arch.route(TINY, scores, p["gate"]["expert_bias"], bias_in_choice=False)
    assert bool(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1)))


# (e) the layer pattern, leaf names, shapes and sizes -------------------------------

def test_the_layer_pattern_at_the_published_depth():
    whole = dict(CONFIG, **CONFIG["published"])
    depth = whole["num_hidden_layers"]
    assert depth == 42 and [i for i in range(depth) if arch.is_mla(whole, i)] == [5, 11, 17, 23, 29, 35, 41]
    assert [i for i in range(depth) if not arch.is_sparse(whole, i)] == [0, 1]
    # The cut: one dense layer, then a whole period, five KDA to one MLA among the sparse layers.
    held = range(CONFIG["num_hidden_layers"])
    assert [i for i in held if arch.is_mla(CONFIG, i)] == [5] and [i for i in held if not arch.is_sparse(CONFIG, i)] == [0]


KDA = ["attention.q_proj.weight", "attention.k_proj.weight", "attention.v_proj.weight", "attention.q_conv1d.weight",
       "attention.k_conv1d.weight", "attention.v_conv1d.weight", "attention.f_proj.weight", "attention.b_proj.weight",
       "attention.A_log", "attention.dt_bias", "attention.g_proj.weight", "attention.o_norm.weight", "attention.o_proj.weight"]
MLA = ["attention.q_proj.weight", "attention.kv_a_proj_with_mqa.weight", "attention.kv_a_layernorm.weight",
       "attention.kv_b_proj.weight", "attention.query_layernorm.weight", "attention.key_layernorm.weight",
       "attention.g_proj.weight", "attention.dense.weight"]
NORMS = ["input_layernorm.weight", "post_attention_layernorm.weight"]
DENSE = ["mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight"]
SPARSE = ["mlp.gate.weight", "mlp.gate.expert_bias", "mlp.shared_experts.gate_proj.weight",
          "mlp.shared_experts.up_proj.weight", "mlp.shared_experts.down_proj.weight"]
# The one departure: the held experts of a layer are three stacked leaves, where the
# checkpoint has mlp.experts.<e>.{gate,up,down}_proj.weight for each expert e.
STACKS = ["mlp.experts.gate_proj", "mlp.experts.up_proj", "mlp.experts.down_proj"]


def tensor_names(cfg):
    names = ["model.word_embeddings.weight", "model.norm.weight", "lm_head.weight"]
    for i in range(cfg["num_hidden_layers"]):
        mixer = MLA if (i + 1) % cfg["layer_group_size"] == 0 else KDA
        mlp = SPARSE + STACKS if i >= cfg["first_k_dense_replace"] else DENSE
        names += [f"model.layers.{i}.{n}" for n in mixer + NORMS + mlp]
    return sorted(names)


def test_leaves_are_the_tensor_names_and_the_stated_shapes_and_dtypes():
    leaves = {
        trainstate.path_str(p).replace("/", "."): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(arch.param_tree(CONFIG))[0]
    }
    assert sorted(leaves) == tensor_names(CONFIG)
    assert len(leaves) == 3 + 18 + 5 * 23 + 18 == 154
    float32 = {n for n, leaf in leaves.items() if leaf.dtype == jnp.float32}
    assert float32 == {n for n in leaves if n.endswith(("A_log", "dt_bias", "mlp.gate.weight", "mlp.gate.expert_bias"))}
    assert len(float32) == 6 * 2 + 6 * 2
    assert all(leaf.dtype == jnp.bfloat16 for n, leaf in leaves.items() if n not in float32)
    shapes = {
        "model.layers.1.mlp.experts.gate_proj": (16, 2560, 768), "model.layers.1.mlp.experts.down_proj": (16, 768, 2560),
        "model.layers.1.mlp.gate.weight": (2560, 512), "model.layers.1.mlp.gate.expert_bias": (512,),
        "model.layers.1.mlp.shared_experts.up_proj.weight": (2560, 768), "model.layers.0.mlp.down_proj.weight": (6144, 2560),
        "model.layers.0.attention.f_proj.weight": (2560, 4096), "model.layers.0.attention.g_proj.weight": (2560, 4096),
        "model.layers.0.attention.q_conv1d.weight": (4096, 4), "model.layers.0.attention.b_proj.weight": (2560, 32),
        "model.layers.0.attention.A_log": (32,), "model.layers.0.attention.dt_bias": (4096,),
        "model.layers.0.attention.o_norm.weight": (128,), "model.layers.6.attention.o_proj.weight": (4096, 2560),
        "model.layers.5.attention.q_proj.weight": (2560, 32 * 192), "model.layers.5.attention.kv_a_proj_with_mqa.weight": (2560, 576),
        "model.layers.5.attention.kv_b_proj.weight": (512, 32 * 256), "model.layers.5.attention.g_proj.weight": (2560, 32),
        "model.layers.5.attention.query_layernorm.weight": (192,), "model.layers.5.attention.dense.weight": (4096, 2560),
        "model.word_embeddings.weight": (19648, 2560), "lm_head.weight": (19648, 2560),
    }
    assert {n: leaves[n].shape for n in shapes} == shapes
    # The sizes ISSUE 34 reckons: parameters and bytes of the params and of the state, and the leaves by size.
    job = trainstate.Job(arch, CONFIG, jax.devices()[:1])
    count, nbytes = trainstate.tree_size(job.abstract["params"]), trainstate.tree_nbytes(job.abstract["params"])
    assert (round(count / 1e6, 1), round(nbytes / 1e9, 3)) == (1167.6, 2.351)
    assert round(trainstate.tree_nbytes(job.abstract) / 1e9, 3) == 7.053
    sizes = [int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves.values()]
    assert sum(s < 1 << 20 for s in sizes) == 67
    assert (sizes.count(16 * 2560 * 768 * 2), sizes.count(2560 * 4096 * 2), sizes.count(2560 * 6144 * 2)) == (18, 37, 4)
    # The uncut model's own count from the same rule: the trunk without the prediction module.
    whole = dict(CONFIG, **CONFIG["published"])
    assert round(trainstate.tree_size(arch.param_tree(whole)) / 1e9, 1) == 124.4


def test_param_spec_puts_the_expert_axis_on_the_stacks_and_the_vocabulary():
    assert tuple(arch.param_spec("model/layers/1/mlp/experts/up_proj")) == ("ep",)
    assert tuple(arch.param_spec("model/word_embeddings/weight")) == ("ep",)
    assert tuple(arch.param_spec("lm_head/weight")) == ("ep",)
    for whole in ("attention/q_proj/weight", "attention/A_log", "mlp/gate/weight", "mlp/gate/expert_bias", "mlp/shared_experts/up_proj/weight"):
        assert tuple(arch.param_spec(f"model/layers/1/{whole}")) == ()
    cfg = dict(TINY, layout={"chips": 2, "mesh": {"ep": 2}}, job=dict(TINY["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:2])
    shardings = {trainstate.path_str(p): s.spec for p, s in jax.tree_util.tree_flatten_with_path(job.shardings)[0]}
    assert tuple(shardings["opt_state/0/mu/model/layers/1/mlp/experts/down_proj"]) == ("ep",)
    assert tuple(shardings["params/model/layers/1/attention/q_conv1d/weight"]) == ()
    state, loss = job.train_step(job.init_state(7), job.make_batches(7, 1)[0])
    assert float(loss) > 0.0 and state["params"]["lm_head"]["weight"].sharding.spec == shardings["params/lm_head/weight"]
