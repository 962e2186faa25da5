"""Trinity-Large-Preview as the benchmark runs it (``perfbench/models/afmoe.py``)
against its plain float32 reference (``perfbench/models/reference/afmoe.py``)
at ``TINY`` widths on the CPU (a window of 12 under sequences of 32 to 100,
blocks of 8 queries that the window is no multiple of), its blocked window
attention against the dense mask, its share of the experts against the uncut
layer, and its leaves against the tensor names. ``tests/test_afmoe.py`` runs
these under the repo's tier-1 too.

Tolerances. With float32 parameters the system and the reference compute the
same equations in the same precision and differ only in the order of sums
(blocks of queries over a span of keys against whole rows under a mask, a
key-value head at a time against all heads at once, sorted rows against a
loop over experts): 1e-4 relative on the loss, 2e-3 of a gradient's largest
element. With the bf16 parameters the configuration states, the system keeps
bf16 activations where the reference has float32: 2e-2 relative on the loss,
the order of bf16's 8 bits of mantissa over a few dozen roundings.
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

arch = run.find_architecture(ROOT, "afmoe")
ref = run.load_module("pb_reference_afmoe", os.path.join(ROOT, "perfbench", "models", "reference", "afmoe.py"))
on_chip = run.load_module("pb_reference_on_chip_afmoe", os.path.join(ROOT, "perfbench", "tests", "reference_on_chip_afmoe.py"))
CONFIG = json.load(open(os.path.join(ROOT, "perfbench", "configs", "trinity-large-preview-ep32.json")))
TINY = dict(CONFIG, **arch.TINY)
SHARES = TINY["num_routed_experts"] // TINY["num_experts"]  # chips that share a layer
TINY_LEAVES = 3 + 14 + 4 * 19  # layer 0 attention + norms + dense; 1-4 attention + norms + sparse
WINDOW = TINY["sliding_window"]


def seeded_params(cfg, seed, dtype=None, spread=4.0):
    """Every leaf from the architecture's own rule, the matrices scaled up and
    the norms' gains spread further, so that no term of the equations is
    multiplied away."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(arch.param_tree(cfg))
    out = []
    for (path, leaf), key in zip(leaves, jax.random.split(jax.random.PRNGKey(seed), len(leaves))):
        value = arch.init_leaf(trainstate.path_str(path), leaf, key).astype(jnp.float32)
        if not trainstate.path_str(path).endswith("expert_bias"):
            value = value + 0.3 * jax.random.normal(key, leaf.shape) if leaf.ndim == 1 else value * spread
        out.append(value.astype(dtype or leaf.dtype))
    return treedef.unflatten(out)


def tokens_of(cfg, seed, batch, length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, arch.token_range(cfg))


def close(got, want, relative):
    return float(jnp.max(jnp.abs(got - want))) <= relative * float(jnp.max(jnp.abs(want)))


# (a) the loss and its gradients against the reference ------------------------

@pytest.mark.parametrize("length,block", [(32, 1024), (100, 1024), (100, 8)])
def test_loss_and_gradients_equal_the_references_in_float32(length, block, monkeypatch):
    """``block`` 8 cuts the 100 positions into thirteen blocks of queries, the
    last one short, each over a span of at most 19 keys that the window of 12
    is no multiple of, as 1024 cuts the configuration's 8192 under its window
    of 4096; and the head into blocks likewise."""
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
    assert all(p.endswith(("mlp/router/gate/weight", "mlp/expert_bias")) for p in by_dtype["float32"])
    assert len(by_dtype["float32"]) == 4 * 2
    loss = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    assert abs(loss - want) <= 2e-2 * abs(want)


@pytest.mark.parametrize("kind", [None] + sorted(on_chip.BROKEN))
def test_the_comparison_is_tight_enough_to_see_a_part_left_out(kind, monkeypatch):
    """On the reference's own most likely next tokens (the training loss on
    random targets is ``log(rows) + var / 2`` of the logits whatever the layers
    compute, so it hardly sees them) the float32 tolerance of the loss, taken
    position by position, holds the sound system and fails each part the
    chip's comparison breaks (``reference_on_chip_afmoe.BROKEN``: the same
    functions, the same controls): the window left out or halved, the rotation
    in the wrong kind of layer, the gate, the per-head norms, the two
    post-norms, the bias out of the choice or in the weights, the weights not
    normalised, the shared expert, the embedding's multiplier."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 100)
    inputs, held = tokens[:, :-1], arch.held_experts(TINY)
    greedy = jnp.argmax(jax.jit(lambda p: ref.logits(TINY, p, inputs, held))(params), axis=-1)
    want = jax.jit(lambda p: ref.token_nll(TINY, p, inputs, greedy, held))(params)
    if kind:
        monkeypatch.setattr(arch, *on_chip.broken(arch, TINY, kind))
    # Position by position, so that gaps of either sign do not cancel in the mean.
    gap = float(jnp.mean(jnp.abs(arch.token_nll(TINY, params, inputs, greedy) - want))) / float(jnp.mean(want))
    assert (gap <= 1e-4) == (kind is None)
    assert kind is None or gap > 3e-4


# (b) the blocked window attention against the dense mask --------------------------

def attention_inputs(length, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 4)
    b, g, r, d = 2, 2, 3, 8
    q = jax.random.normal(keys[0], (b, length, g, r, d))
    k, v = jax.random.normal(keys[1], (b, length, g, d)), jax.random.normal(keys[2], (b, length, g, d))
    return (q, k, v), jax.random.normal(keys[3], (b, length, g, r, d))


def dense_attention(q, k, v, window, scale):
    """Every query against every key, the mask written out from ``(i, j)``."""
    length = q.shape[1]
    i, j = np.arange(length)[:, None], np.arange(length)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (i - j < window)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * scale
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


def out_and_grads(attend, q, k, v, window, scale, weight):
    """``attend``'s output and what the cotangent ``weight`` sends back to q, k and v, in one program."""

    @jax.jit
    def both(q, k, v):
        out, vjp = jax.vjp(lambda *qkv: attend(*qkv, window, scale), q, k, v)
        return out, vjp(weight)

    return both(q, k, v)


@pytest.mark.parametrize("window", [WINDOW, None])
@pytest.mark.parametrize("length,block", [(8, 8), (5, 8), (WINDOW, 8), (WINDOW, 1024), (WINDOW + 1, 8), (50, 8), (50, 16), (37, 5)])
def test_blocked_attention_is_the_dense_mask_forward_and_backward(length, block, window, monkeypatch):
    """A sequence of one block and of less, of the window exactly and of one
    key more, and of several windows (four and more), in blocks the window is
    and is not a multiple of; and the full layer's causal mask in the same
    blocks."""
    monkeypatch.setattr(arch, "QUERY_BLOCK", block)
    (q, k, v), weight = attention_inputs(length)
    scale = q.shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(arch.softmax_attention, q, k, v, window, scale, weight)
        want, want_grads = out_and_grads(dense_attention, q, k, v, window, scale, weight)
        assert got.shape == want.shape and close(got, want, 1e-5)
        for a, b in zip(grads, want_grads):
            assert float(jnp.max(jnp.abs(b))) > 0.0 and close(a, b, 1e-4)
    # The reference's own mask is that mask.
    full = window is None
    mine = np.asarray(ref.visible(dict(TINY, sliding_window=window or 0), full, np.arange(length), length))
    i, j = np.arange(length)[:, None], np.arange(length)[None, :]
    assert (mine == ((j <= i) if full else (j <= i) & (i - j < window))).all()


@pytest.mark.parametrize("block", [8, 16, 1024])
def test_a_key_outside_the_window_has_no_effect_and_the_last_one_inside_has(block, monkeypatch):
    """Key ``j`` (and its value) changed: no query at ``j + window`` or after
    moves by a bit, in the output or in the gradient it sends back; the query
    at ``j + window - 1``, the last that sees it, does; and in a full layer
    every query from ``j`` on does."""
    monkeypatch.setattr(arch, "QUERY_BLOCK", block)
    (q, k, v), _ = attention_inputs(50)
    j, scale = 9, q.shape[-1] ** -0.5
    k2, v2 = k.at[:, j].add(1.0), v.at[:, j].add(1.0)
    windowed = jax.jit(lambda *a: arch.softmax_attention(*a, WINDOW, scale))
    causal = jax.jit(lambda *a: arch.softmax_attention(*a, None, scale))
    one, two = windowed(q, k, v), windowed(q, k2, v2)
    moved = np.asarray(jnp.any(one != two, axis=(0, 2, 3, 4)))
    assert not moved[:j].any() and moved[j:j + WINDOW].all() and not moved[j + WINDOW:].any()
    full = np.asarray(jnp.any(causal(q, k, v) != causal(q, k2, v2), axis=(0, 2, 3, 4)))
    assert not full[:j].any() and full[j:].all()
    # And nothing flows back to it from outside the window.
    late = jnp.zeros(one.shape).at[:, j + WINDOW:].set(1.0)
    dk, dv = jax.jit(jax.grad(lambda k_, v_: jnp.sum(windowed(q, k_, v_) * late), (0, 1)))(k, v)
    assert not dk[:, j].any() and not dv[:, j].any() and bool(dv[:, j + 1].any())


def test_the_full_layer_is_not_rotated_and_the_window_layers_are(monkeypatch):
    p = seeded_params(TINY, 7, jnp.float32)["model"]["layers"]["3"]["self_attn"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, TINY["hidden_size"]), jnp.float32)
    assert bool((arch.attention(TINY, p, x, True) == arch.attention(TINY, p, x, True, rotate=False)).all())
    assert not close(arch.attention(TINY, p, x, True), arch.attention(TINY, p, x, True, rotate=True), 1e-3)
    assert bool((arch.attention(TINY, p, x, False) == arch.attention(TINY, p, x, False, rotate=True)).all())
    assert not close(arch.attention(TINY, p, x, False), arch.attention(TINY, p, x, False, rotate=False), 1e-3)
    # An unrotated layer knows a key's position only by what it may see: keys 0 and 1 swapped (and their
    # values), every query from 2 on gives the same; rotated, it does not.
    swap = jnp.concatenate([x[:, 1:2], x[:, 0:1], x[:, 2:]], axis=1)
    assert close(arch.attention(TINY, p, swap, True)[:, 2:], arch.attention(TINY, p, x, True)[:, 2:], 1e-5)
    assert not close(arch.attention(TINY, p, swap, False)[:, 2:WINDOW], arch.attention(TINY, p, x, False)[:, 2:WINDOW], 1e-3)
    # Swap the kinds' rotation and the loss moves.
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 64)
    sound = float(arch.loss_fn(TINY, params, tokens))
    real = arch.attention
    monkeypatch.setattr(arch, "attention", lambda cfg, p_, x_, full: real(cfg, p_, x_, full, rotate=full))
    assert abs(float(arch.loss_fn(TINY, params, tokens)) - sound) > 1e-3 * sound


# (c) the shares add up to the uncut layer --------------------------------------

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


def test_the_configurations_chip_holds_eight_of_the_routers_256_and_a_sequence_longer_than_its_window():
    assert arch.held_experts(CONFIG) == (0, 8) and arch.held_experts(dict(CONFIG, layer_share_rank=31)) == (248, 256)
    assert (CONFIG["num_routed_experts"], CONFIG["num_experts_per_tok"], CONFIG["n_group"]) == (256, 4, 1)
    assert CONFIG["sliding_window"] == 4096 < CONFIG["job"]["seq_len"] == 8192
    assert (CONFIG["route_scale"], CONFIG["route_norm"], CONFIG["score_func"], CONFIG["mup_enabled"]) == (2.448, True, "sigmoid", True)
    # The dry run's sequence (32) and the tests' are longer than the toy window too.
    assert arch.TINY["sliding_window"] < 32


# (d) sigmoid routing with a bias, against hand-made cases --------------------------

ROUTING = dict(TINY, num_routed_experts=16, num_experts_per_tok=3)


def routed(scores, bias=None, **controls):
    bias = np.zeros(16, np.float32) if bias is None else bias
    weights, chosen = arch.route(ROUTING, jnp.asarray(scores)[None], jnp.asarray(bias), **controls)
    return dict(zip(np.asarray(chosen)[0].tolist(), np.asarray(weights)[0].tolist()))


def test_a_bias_that_changes_the_choice_leaves_the_weights_to_the_scores():
    """16 experts, top 3. Without a bias experts 0, 4 and 1 are chosen; a bias
    of 0.3 on expert 5 puts it in expert 1's place, and its weight is its
    score's share, not its biased one."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 1, 4, 5]] = [0.9, 0.5, 0.8, 0.4]
    assert sorted(routed(scores)) == [0, 1, 4]
    bias = np.zeros(16, np.float32)
    bias[5] = 0.3
    got = routed(scores, bias)
    assert sorted(got) == [0, 4, 5]
    for e, w in got.items():  # s of the chosen, normalised to 1, times route_scale
        assert w == pytest.approx(2.448 * scores[e] / (0.9 + 0.8 + 0.4), rel=1e-6)
    assert sum(got.values()) == pytest.approx(ROUTING["route_scale"], rel=1e-6)
    let_in = routed(scores, bias, bias_in_weights=True)
    assert let_in[5] == pytest.approx(2.448 * 0.7 / (0.9 + 0.8 + 0.7), rel=1e-6)
    assert sorted(routed(scores, bias, bias_in_choice=False)) == [0, 1, 4]
    plain = routed(scores, bias, route_norm=False)
    assert plain[5] == pytest.approx(2.448 * 0.4, rel=1e-6) and sum(plain.values()) == pytest.approx(2.448 * 2.1, rel=1e-6)
    # The reference's gate makes the same choice with the same weights from logits that give these scores.
    p = {"router": {"gate": {"weight": jnp.asarray(np.log(scores / (1 - scores)))[None]}}, "expert_bias": jnp.asarray(bias)}
    ref_weights, ref_chosen = ref.gate(ROUTING, p, jnp.ones((1, 1), jnp.float32))
    want = dict(zip(np.asarray(ref_chosen)[0].tolist(), np.asarray(ref_weights)[0].tolist()))
    assert sorted(want) == [0, 4, 5] and all(want[e] == pytest.approx(got[e], rel=1e-5) for e in got)


def test_no_group_limits_the_choice():
    """``n_group`` 1: the three largest of all are taken wherever they lie."""
    scores = np.full(16, 0.05, np.float32)
    scores[[3, 9, 15]] = [0.6, 0.7, 0.8]
    assert sorted(routed(scores)) == [3, 9, 15]
    assert (CONFIG["n_group"], CONFIG["topk_group"], CONFIG["num_expert_groups"], CONFIG["num_limited_groups"]) == (1, 1, 1, 1)


def test_the_bias_gets_no_gradient_and_the_router_does():
    p = seeded_params(TINY, 11, jnp.float32)["model"]["layers"]["1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, TINY["hidden_size"]), jnp.float32)
    grads = jax.grad(lambda p_: jnp.sum(jnp.square(arch.expert_layer(TINY, p_, x))))(p)
    assert not grads["expert_bias"].any() and bool(jnp.any(grads["router"]["gate"]["weight"] != 0))
    # It steers all the same: without it some token's choice is another.
    scores = jax.nn.sigmoid(x.reshape(-1, x.shape[-1]) @ p["router"]["gate"]["weight"])
    _, with_bias = arch.route(TINY, scores, p["expert_bias"])
    _, without = arch.route(TINY, scores, p["expert_bias"], bias_in_choice=False)
    assert bool(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1)))


def test_the_seeded_gains_are_not_one_and_the_seeded_bias_changes_some_choices():
    """``init_leaf`` as the cells run it (no test's spread on top): or the
    comparisons with a norm left out and with the bias out of the choice
    would guard nothing."""
    job = trainstate.Job(arch, dict(TINY, job=dict(TINY["job"], seq_len=32)), jax.devices()[:1])
    params = job.init_state(5)["params"]
    layer = params["model"]["layers"]["1"]
    gains = [layer[n]["weight"] for n in ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm", "post_mlp_layernorm")]
    gains += [layer["self_attn"]["q_norm"]["weight"], layer["self_attn"]["k_norm"]["weight"], params["model"]["norm"]["weight"]]
    for gain in gains:
        gain = np.asarray(gain, np.float32)
        assert 0.05 < gain.std() < 0.2 and abs(gain.mean() - 1.0) < 0.1
    # Logits as wide as the configuration's: 0.02 * normal weights over 3072 inputs, not over the toy 64.
    x = jax.random.normal(jax.random.PRNGKey(6), (64, TINY["hidden_size"]), jnp.float32) * (CONFIG["hidden_size"] / TINY["hidden_size"]) ** 0.5
    scores = jax.nn.sigmoid(x @ layer["mlp"]["router"]["gate"]["weight"])
    _, with_bias = arch.route(TINY, scores, layer["mlp"]["expert_bias"])
    _, without = arch.route(TINY, scores, layer["mlp"]["expert_bias"], bias_in_choice=False)
    changed = float(jnp.mean(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1), axis=-1)))
    assert 0.05 < changed < 0.95


# (e) the layer pattern, leaf names, shapes and sizes -------------------------------

def test_the_layer_pattern_at_the_published_depth_and_at_the_cut():
    whole = dict(CONFIG, **CONFIG["published"])
    depth = whole["num_hidden_layers"]
    assert depth == 60 == len(whole["layer_types"]) and whole == dict(whole, **{k: arch.PUBLISHED[k] for k in CONFIG["published"]})
    full = [i for i in range(depth) if arch.is_full(whole, i)]
    assert full == list(range(3, 60, 4)) == [i for i in range(depth) if (i + 1) % whole["global_attn_every_n_layers"] == 0]
    assert [i for i in range(depth) if not arch.is_sparse(whole, i)] == [0, 1, 2, 3, 4, 5]
    # The cut: one dense layer, then a whole period, three window layers to one full among the sparse layers.
    held = range(CONFIG["num_hidden_layers"])
    assert CONFIG["layer_types"] == whole["layer_types"][:5]
    assert [i for i in held if arch.is_full(CONFIG, i)] == [3] and [i for i in held if not arch.is_sparse(CONFIG, i)] == [0]


ATTENTION = ["self_attn.q_proj.weight", "self_attn.k_proj.weight", "self_attn.v_proj.weight", "self_attn.gate_proj.weight",
             "self_attn.o_proj.weight", "self_attn.q_norm.weight", "self_attn.k_norm.weight"]
NORMS = ["input_layernorm.weight", "post_attention_layernorm.weight", "pre_mlp_layernorm.weight", "post_mlp_layernorm.weight"]
DENSE = ["mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight"]
SPARSE = ["mlp.router.gate.weight", "mlp.expert_bias", "mlp.shared_experts.gate_proj.weight",
          "mlp.shared_experts.up_proj.weight", "mlp.shared_experts.down_proj.weight"]
# The one departure: the held experts of a layer are three stacked leaves, where the
# checkpoint has mlp.experts.<e>.{gate,up,down}_proj.weight for each expert e.
STACKS = ["mlp.experts.gate_proj", "mlp.experts.up_proj", "mlp.experts.down_proj"]


def tensor_names(cfg):
    names = ["model.embed_tokens.weight", "model.norm.weight", "lm_head.weight"]
    for i in range(cfg["num_hidden_layers"]):
        mlp = SPARSE + STACKS if i >= cfg["num_dense_layers"] else DENSE
        names += [f"model.layers.{i}.{n}" for n in ATTENTION + NORMS + mlp]
    return sorted(names)


def test_leaves_are_the_tensor_names_and_the_stated_shapes_and_dtypes():
    leaves = {
        trainstate.path_str(p).replace("/", "."): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(arch.param_tree(CONFIG))[0]
    }
    assert sorted(leaves) == tensor_names(CONFIG)
    assert len(leaves) == 3 + 14 + 4 * 19 == 93
    float32 = {n for n, leaf in leaves.items() if leaf.dtype == jnp.float32}
    assert float32 == {n for n in leaves if n.endswith(("mlp.router.gate.weight", "mlp.expert_bias"))} and len(float32) == 8
    assert all(leaf.dtype == jnp.bfloat16 for n, leaf in leaves.items() if n not in float32)
    shapes = {
        "model.layers.1.mlp.experts.gate_proj": (8, 3072, 3072), "model.layers.4.mlp.experts.down_proj": (8, 3072, 3072),
        "model.layers.1.mlp.router.gate.weight": (3072, 256), "model.layers.1.mlp.expert_bias": (256,),
        "model.layers.1.mlp.shared_experts.up_proj.weight": (3072, 3072), "model.layers.0.mlp.down_proj.weight": (12288, 3072),
        "model.layers.0.self_attn.q_proj.weight": (3072, 6144), "model.layers.3.self_attn.k_proj.weight": (3072, 1024),
        "model.layers.3.self_attn.gate_proj.weight": (3072, 6144), "model.layers.3.self_attn.o_proj.weight": (6144, 3072),
        "model.layers.3.self_attn.q_norm.weight": (128,), "model.layers.0.pre_mlp_layernorm.weight": (3072,),
        "model.embed_tokens.weight": (25024, 3072), "lm_head.weight": (25024, 3072),
    }
    assert {n: leaves[n].shape for n in shapes} == shapes
    # The sizes ISSUE 42 reckons: parameters and bytes of the params and of the state, and the leaves by size.
    job = trainstate.Job(arch, CONFIG, jax.devices()[:1])
    count, nbytes = trainstate.tree_size(job.abstract["params"]), trainstate.tree_nbytes(job.abstract["params"])
    assert (round(count / 1e6, 1), round(nbytes / 1e9, 3)) == (1604.0, 3.214)
    assert round(trainstate.tree_nbytes(job.abstract) / 1e9, 3) == 9.643 and list(job.batch_shape) == [1, 8193]
    sizes = [int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves.values()]
    assert sum(s < 1 << 20 for s in sizes) == 35
    by_size = {n: sizes.count(n) for n in (8 * 3072 * 3072 * 2, 25024 * 3072 * 2, 3072 * 6144 * 2, 3072 * 12288 * 2, 3072 * 3072 * 2, 3072 * 1024 * 2, 3072 * 256 * 4)}
    assert list(by_size.values()) == [12, 2, 15, 3, 12, 10, 4]
    assert round(100 * 12 * 8 * 3072 * 3072 * 2 / nbytes) == 56
    # The uncut model's own count from the same rule.
    whole = dict(CONFIG, **CONFIG["published"])
    assert round(trainstate.tree_size(arch.param_tree(dict(whole, num_routed_experts=256))) / 1e9, 1) == 398.6


def test_param_spec_puts_the_expert_axis_on_the_stacks_and_the_vocabulary():
    assert tuple(arch.param_spec("model/layers/1/mlp/experts/up_proj")) == ("ep",)
    assert tuple(arch.param_spec("model/embed_tokens/weight")) == ("ep",)
    assert tuple(arch.param_spec("lm_head/weight")) == ("ep",)
    for whole in ("self_attn/q_proj/weight", "self_attn/q_norm/weight", "mlp/router/gate/weight", "mlp/expert_bias", "mlp/shared_experts/up_proj/weight"):
        assert tuple(arch.param_spec(f"model/layers/1/{whole}")) == ()
    cfg = dict(TINY, layout={"chips": 2, "mesh": {"ep": 2}}, job=dict(TINY["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:2])
    shardings = {trainstate.path_str(p): s.spec for p, s in jax.tree_util.tree_flatten_with_path(job.shardings)[0]}
    assert tuple(shardings["opt_state/0/mu/model/layers/1/mlp/experts/down_proj"]) == ("ep",)
    assert tuple(shardings["params/model/layers/1/self_attn/gate_proj/weight"]) == ()
    state, loss = job.train_step(job.init_state(7), job.make_batches(7, 1)[0])
    assert float(loss) > 0.0 and state["params"]["lm_head"]["weight"].sharding.spec == shardings["params/lm_head/weight"]
