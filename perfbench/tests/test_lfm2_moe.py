"""LFM2-24B-A2B as the benchmark runs it (``perfbench/models/lfm2_moe.py``)
against its plain float32 reference (``perfbench/models/reference/lfm2_moe.py``)
at ``TINY`` widths on the CPU (sequences of 32 to 100 under blocks of 8 queries
that 100 is no multiple of), its gated short convolution tap by tap, its
attention blocked against the dense mask and normed before it is rotated, its
share of the experts against the uncut layer, its tied table, and its leaves
against the tensor names. ``tests/test_lfm2_moe.py`` runs these under the
repo's tier-1 too.

Tolerances. With float32 parameters the system and the reference compute the
same equations in the same precision and differ only in the order of sums
(shifted copies against the framework's grouped convolution, blocks of queries
against whole rows under a mask, a key-value head at a time against all heads
at once, sorted rows against a loop over experts, the head in blocks of
positions): 1e-4 relative on the loss, 2e-3 of a gradient's largest element.
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

arch = run.find_architecture(ROOT, "lfm2_moe")
ref = run.load_module("pb_reference_lfm2_moe", os.path.join(ROOT, "perfbench", "models", "reference", "lfm2_moe.py"))
on_chip = run.load_module("pb_reference_on_chip_lfm2_moe", os.path.join(ROOT, "perfbench", "tests", "reference_on_chip_lfm2_moe.py"))
CONFIG = json.load(open(os.path.join(ROOT, "perfbench", "configs", "lfm2-24b-a2b-ep8.json")))
TINY = dict(CONFIG, **arch.TINY)
SHARES = TINY["num_routed_experts"] // TINY["num_experts"]  # chips that share a layer
TINY_LEAVES = 2 + 8 + 6 * 10 + 2 * 13  # the table and embedding_norm; layer 0 conv + dense; six conv and two attention layers over experts
FLOAT32 = ("feed_forward/gate/weight", "feed_forward/expert_bias")


def seeded_params(cfg, seed, dtype=None, spread=4.0):
    """Every leaf from the architecture's own rule, the matrices scaled up and
    the norms' gains spread further, so that no term of the equations is
    multiplied away; the convolution's taps and the bias that steers the
    choice stay as they are drawn."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(arch.param_tree(cfg))
    out = []
    for (path, leaf), key in zip(leaves, jax.random.split(jax.random.PRNGKey(seed), len(leaves))):
        value = arch.init_leaf(trainstate.path_str(path), leaf, key).astype(jnp.float32)
        if leaf.ndim == 2:
            value = value * spread
        elif leaf.ndim == 1 and not trainstate.path_str(path).endswith("expert_bias"):
            value = value + 0.3 * jax.random.normal(key, leaf.shape)
        out.append(value.astype(dtype or leaf.dtype))
    return treedef.unflatten(out)


def tokens_of(cfg, seed, batch, length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, arch.token_range(cfg))


def close(got, want, relative):
    return float(jnp.max(jnp.abs(got - want))) <= relative * float(jnp.max(jnp.abs(want)))


def out_and_grads(f, args, weight):
    """``f``'s output and what the cotangent ``weight`` sends back to each argument, in one program."""

    @jax.jit
    def both(*args):
        out, vjp = jax.vjp(f, *args)
        return out, vjp(weight)

    return both(*args)


# (a) the loss and its gradients against the reference ------------------------

@pytest.mark.parametrize("length,block", [(32, 1024), (100, 1024), (100, 8)])
def test_loss_and_gradients_equal_the_references_in_float32(length, block, monkeypatch):
    """``block`` 8 cuts 100 positions into thirteen blocks of queries and of
    the head, the last one short, as 256 cuts the configuration's 8192 into 32."""
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
    assert all(p.endswith(FLOAT32) for p in by_dtype["float32"]) and len(by_dtype["float32"]) == 8 * 2
    loss = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    assert abs(loss - want) <= 2e-2 * abs(want)


@pytest.mark.parametrize("kind", [None] + sorted(on_chip.BROKEN) + ["f32_as_bf16"])
def test_the_comparison_is_tight_enough_to_see_a_part_left_out(kind, monkeypatch):
    """On the reference's own most likely next tokens (the training loss on
    random targets is ``log(rows) + var / 2`` of the logits whatever the layers
    compute, so it hardly sees them) the float32 tolerance of the loss, taken
    position by position, holds the sound system and fails each part the
    chip's comparison breaks (``reference_on_chip_lfm2_moe.BROKEN``: the same
    functions, the same controls): the convolution left out, either gate
    dropped, the taps reversed, a ``conv`` layer given a position, the per-head
    norms left out or put after the rotation, the rotation left out, a layer's
    first norm left out, the bias out of the choice or in the weights, the
    weights not normalised, the head read from another matrix than the table;
    and the float32 leaves rounded through bf16."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 100)
    inputs, held = tokens[:, :-1], arch.held_experts(TINY)
    greedy = jnp.argmax(jax.jit(lambda p: ref.logits(TINY, p, inputs, held))(params), axis=-1)
    want = jax.jit(lambda p: ref.token_nll(TINY, p, inputs, greedy, held))(params)
    if kind == "f32_as_bf16":
        stated = jax.tree.map(lambda a, leaf: a.astype(leaf.dtype), params, arch.param_tree(TINY))
        params = jax.tree.map(lambda a: a.astype(jnp.float32), on_chip.rounded(stated, kind))
    elif kind:
        monkeypatch.setattr(arch, *on_chip.broken(arch, kind))
    # Position by position, so that gaps of either sign do not cancel in the mean.
    got = jax.jit(lambda p: arch.token_nll(TINY, p, inputs, greedy))(params)
    gap = float(jnp.mean(jnp.abs(got - want))) / float(jnp.mean(want))
    assert (gap <= 1e-5) == (kind is None)
    assert kind is None or gap > 3e-5


def test_the_chips_comparison_breaks_every_part_the_issue_names():
    assert {"no_conv", "no_b_gate", "no_c_gate", "taps_reversed", "conv_rotated", "no_qk_norm", "not_rotated",
            "bias_in_the_weights", "bias_out_of_the_choice", "norm_topk_prob", "untied_head"} <= set(on_chip.BROKEN)
    assert on_chip.KINDS[-2:] == ("fp8", "f32_as_bf16")
    for kind, (name, control) in on_chip.BROKEN.items():
        assert callable(getattr(arch, name)), kind
        replaced, function = on_chip.broken(arch, kind)
        assert replaced == name and callable(function) and control


# (b) the gated short convolution ---------------------------------------------------

def test_the_convolution_is_causal_without_bias_and_its_last_tap_is_the_positions_own():
    taps = jnp.asarray(np.arange(1, 10, dtype=np.float32).reshape(3, 1, 3))
    u = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3))
    got = np.asarray(arch.causal_conv(u, taps))
    us, w = np.asarray(u)[0], np.asarray(taps)[:, 0]
    for t in range(6):
        want = sum(w[:, k] * (us[t - 2 + k] if t - 2 + k >= 0 else 0.0) for k in range(3))
        assert np.allclose(got[0, t], want, rtol=1e-5, atol=1e-6)
    # Position 0 reads its own value through the last tap and nothing else; no bias, no activation.
    assert np.allclose(got[0, 0], w[:, 2] * us[0], rtol=1e-6) and not arch.causal_conv(jnp.zeros_like(u), taps).any()
    assert np.allclose(np.asarray(arch.causal_conv(-u, taps)), -got, rtol=1e-6)  # linear: nothing bends it
    # The reference's grouped convolution over the published layout says the same.
    with jax.default_matmul_precision("highest"):
        assert close(jnp.asarray(got), ref.depthwise_conv(u, taps), 1e-6)
    assert CONFIG["conv_L_cache"] == 3 and CONFIG["conv_bias"] is False


def conv_operator(seed=7):
    p = seeded_params(TINY, seed, jnp.float32)["model"]["layers"]["0"]["conv"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 40, TINY["hidden_size"]), jnp.float32)
    return p, x


@pytest.mark.parametrize("length", [1, 2, 3, 40])
def test_the_conv_operator_is_the_references_forward_and_backward(length):
    p, x = conv_operator()
    x = x[:, :length]
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda p_, x_: arch.short_conv(TINY, p_, x_), (p, x), weight)
        want, want_grads = out_and_grads(lambda p_, x_: ref.short_conv(TINY, p_, x_), (p, x), weight)
    assert got.shape == x.shape and close(got, want, 1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(w))) > 0.0 and close(g, w, 1e-4)


def test_a_later_position_has_no_effect_and_a_position_reaches_two_after_it():
    p, x = conv_operator()
    op = jax.jit(lambda x_: arch.short_conv(TINY, p, x_))
    moved = np.asarray(jnp.any(op(x) != op(x.at[:, 20].add(1.0)), axis=(0, 2)))
    assert not moved[:20].any() and moved[20:23].all() and not moved[23:].any()  # three taps: itself and two on
    # And no position is told where it is: the sequence shifted by five gives the output shifted by five.
    late = jnp.concatenate([jnp.zeros_like(x[:, :5]), x], axis=1)
    assert close(op(late)[:, 5:], op(x), 1e-5)
    turned = jax.jit(lambda x_: arch.short_conv(TINY, p, x_, rotate=True))
    assert not close(turned(late)[:, 5:], turned(x), 1e-3)


def test_both_gates_the_taps_and_their_order_are_each_needed():
    """``out = (C * conv(B * x~)) W_out`` by hand from the columns of
    ``in_proj`` in their order, and each control away from it."""
    p, x = conv_operator()
    d = TINY["hidden_size"]
    with jax.default_matmul_precision("highest"):
        sound = arch.short_conv(TINY, p, x)
        bcx = np.asarray(x @ p["in_proj"]["weight"], np.float64)
        b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        w = np.asarray(p["conv"]["weight"], np.float64)[:, 0]
        bu = np.pad(b * u, [(0, 0), (2, 0), (0, 0)])
        v = sum(bu[:, k:k + x.shape[1]] * w[:, k] for k in range(3))
        by_hand = (c * v) @ np.asarray(p["out_proj"]["weight"], np.float64)
        assert np.allclose(np.asarray(sound), by_hand, rtol=1e-4, atol=1e-5 * np.abs(by_hand).max())
        for control in ({"conv": False}, {"b_gate": False}, {"c_gate": False}, {"reverse_taps": True}, {"rotate": True}):
            assert not close(arch.short_conv(TINY, p, x, **control), sound, 1e-2), control
    assert set(p) == {"in_proj", "conv", "out_proj"} and p["in_proj"]["weight"].shape == (d, 3 * d)
    assert p["conv"]["weight"].shape == (d, 1, 3) and set(p["conv"]) == {"weight"}  # no bias (conv_bias false)


# (c) attention: blocked against the dense mask; normed, then rotated ----------------

def attention_inputs(length, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 4)
    b, g, r, d = 2, 2, 3, 8
    q = jax.random.normal(keys[0], (b, length, g, r, d))
    k, v = jax.random.normal(keys[1], (b, length, g, d)), jax.random.normal(keys[2], (b, length, g, d))
    return (q, k, v), jax.random.normal(keys[3], (b, length, g, r, d))


def dense_attention(q, k, v, scale):
    """Every query against every key, the mask written out from ``(i, j)``."""
    length = q.shape[1]
    mask = np.arange(length)[None, :] <= np.arange(length)[:, None]
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * scale
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


@pytest.mark.parametrize("length,block", [(8, 8), (5, 8), (50, 8), (50, 16), (37, 5), (40, 1024)])
def test_blocked_attention_is_the_dense_mask_forward_and_backward(length, block, monkeypatch):
    monkeypatch.setattr(arch, "QUERY_BLOCK", block)
    (q, k, v), weight = attention_inputs(length)
    scale = q.shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *qkv: arch.softmax_attention(*qkv, scale), (q, k, v), weight)
        want, want_grads = out_and_grads(lambda *qkv: dense_attention(*qkv, scale), (q, k, v), weight)
        assert got.shape == want.shape and close(got, want, 1e-5)
        for a, b in zip(grads, want_grads):
            assert float(jnp.max(jnp.abs(b))) > 0.0 and close(a, b, 1e-4)


def test_queries_and_keys_are_normed_a_head_at_a_time_before_they_are_rotated():
    """One gain of the head's width for the queries and one for the keys;
    rotate-half over the whole head at ``rope_theta`` 1e6; the norm first: put
    after the rotation, the gain of a dim meets its pair's value, and the
    result is another."""
    p = seeded_params(TINY, 7, jnp.float32)["model"]["layers"]["2"]["self_attn"]
    assert arch.is_attention(TINY, 2) and set(p) == {"q_proj", "k_proj", "v_proj", "out_proj", "q_layernorm", "k_layernorm"}
    hd = arch.head_width(TINY)
    assert p["q_layernorm"]["weight"].shape == p["k_layernorm"]["weight"].shape == (hd,) == (16,)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, TINY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        sound = arch.attention(TINY, p, x)
        assert close(sound, ref.attention(TINY, p, x), 1e-4)
        for control in ({"qk_norm": False}, {"norm_first": False}, {"rotate": False}):
            assert not close(arch.attention(TINY, p, x, **control), sound, 1e-3), control
        # With gains that are equal within each pair of dims the norm and the rotation commute: the order is
        # seen only because a gain is a channel's own.
        even = jnp.tile(p["q_layernorm"]["weight"][: hd // 2], 2)
        paired = dict(p, q_layernorm={"weight": even}, k_layernorm={"weight": even})
        assert close(arch.attention(TINY, paired, x, norm_first=False), arch.attention(TINY, paired, x), 1e-5)
    # Rotated: keys 0 and 1 swapped (and their values) show to every later query; unrotated they would not.
    swap = jnp.concatenate([x[:, 1:2], x[:, 0:1], x[:, 2:]], axis=1)
    assert not close(arch.attention(TINY, p, swap)[:, 2:], sound[:, 2:], 1e-3)
    plain = arch.attention(TINY, p, x, rotate=False)
    assert close(arch.attention(TINY, p, swap, rotate=False)[:, 2:], plain[:, 2:], 1e-4)
    moved = np.asarray(jnp.any(arch.attention(TINY, p, x.at[:, 20].add(1.0)) != sound, axis=(0, 2)))
    assert not moved[:20].any() and moved[20:].all()
    assert CONFIG["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert arch.head_width(CONFIG) == 64 and CONFIG["num_attention_heads"] // CONFIG["num_key_value_heads"] == 4


def test_the_rotation_turns_the_two_halves_of_a_head_as_pairs_at_the_stated_base():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    got = np.asarray(arch._rotary(x, 1e6))
    xs = np.asarray(x, np.float64)
    for t in range(5):
        for c in range(4):
            angle = t / 1e6 ** (c / 4)
            a, b = xs[0, t, :, c], xs[0, t, :, c + 4]
            assert np.allclose(got[0, t, :, c], a * np.cos(angle) - b * np.sin(angle), atol=1e-5)
            assert np.allclose(got[0, t, :, c + 4], b * np.cos(angle) + a * np.sin(angle), atol=1e-5)
    assert close(jnp.asarray(got).swapaxes(1, 2), ref.rotate_half(x.swapaxes(1, 2), 1e6), 1e-5)


# (d) the shares add up to the uncut layer --------------------------------------

def expert_block(cfg, seed):
    return seeded_params(cfg, seed, jnp.float32)["model"]["layers"]["1"]["feed_forward"]


def test_expert_layer_summed_over_all_shares_is_the_uncut_references():
    """model-configs section 4: what every share's experts give adds up to the
    uncut reference's expert layer; nothing is computed on every chip alike
    (no shared expert), so nothing is counted once. 8 shares, as 8 chips
    share a layer."""
    routed, held = TINY["num_routed_experts"], TINY["num_experts"]
    assert SHARES == 8 == CONFIG["num_routed_experts"] // CONFIG["num_experts"]
    uncut = expert_block(dict(TINY, num_experts=routed), 5)
    assert set(uncut) == {"gate", "expert_bias", "experts"} and set(uncut["experts"]) == {"w1", "w2", "w3"}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, TINY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(TINY, uncut, x, (0, routed))
    total = jnp.zeros_like(x)
    for rank in range(SHARES):
        cfg = dict(TINY, layer_share_rank=rank)
        lo, hi = arch.held_experts(cfg)
        assert (lo, hi) == (rank * held, (rank + 1) * held)
        mine = dict(uncut, experts={k: v[lo:hi] for k, v in uncut["experts"].items()})
        total = total + jax.jit(lambda p_, cfg=cfg: arch.expert_layer(cfg, p_, x))(mine)
    assert close(total, want, 1e-4)
    # And a share alone is the reference's for that range: nothing stands in for the absent.
    alone = arch.expert_layer(TINY, dict(uncut, experts={k: v[:held] for k, v in uncut["experts"].items()}), x)
    with jax.default_matmul_precision("highest"):
        want_alone = ref.expert_layer(TINY, uncut, x, (0, held))
    assert close(alone, want_alone, 1e-4)
    assert not close(alone, want, 1e-2)


def test_an_expert_is_a_gated_mlp_of_three_matrices_and_a_token_without_a_held_expert_gets_nothing():
    """Every expert held, one token: ``sum_k w_k w2_k(silu(w1_k x) * w3_k x)``
    over its chosen experts, by hand. Then a share that holds none of them."""
    routed = TINY["num_routed_experts"]
    cfg = dict(TINY, num_experts=routed)
    p = expert_block(cfg, 9)
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 1, TINY["hidden_size"]), jnp.float32)
    scores = jax.nn.sigmoid(x[0] @ p["gate"]["weight"])
    weights, chosen = arch.route(cfg, scores, p["expert_bias"])
    want = np.zeros(TINY["hidden_size"], np.float64)
    row = np.asarray(x[0, 0], np.float64)
    for w, e in zip(np.asarray(weights[0], np.float64), np.asarray(chosen[0])):
        w1, w2, w3 = (np.asarray(p["experts"][n][e], np.float64) for n in ("w1", "w2", "w3"))
        a = row @ w1
        want += w * ((a / (1 + np.exp(-a)) * (row @ w3)) @ w2)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.expert_layer(cfg, p, x))[0, 0]
    assert np.allclose(got, want, rtol=1e-4, atol=1e-6)
    absent = next(r for r in range(SHARES) if not set(range(2 * r, 2 * r + 2)) & set(np.asarray(chosen[0]).tolist()))
    share = dict(p, experts={k: v[2 * absent:2 * absent + 2] for k, v in p["experts"].items()})
    assert not np.asarray(arch.expert_layer(dict(TINY, layer_share_rank=absent), share, x)).any()


def test_the_configurations_chip_holds_eight_of_the_routers_64():
    assert arch.held_experts(CONFIG) == (0, 8) and arch.held_experts(dict(CONFIG, layer_share_rank=7)) == (56, 64)
    assert (CONFIG["num_routed_experts"], CONFIG["num_experts"], CONFIG["num_experts_per_tok"]) == (64, 8, 4)
    assert (CONFIG["routed_scaling_factor"], CONFIG["norm_topk_prob"], CONFIG["use_expert_bias"]) == (1, True, True)
    assert CONFIG["published"]["num_experts"] == 64 == arch.PUBLISHED["num_experts"]
    gate = arch.param_tree(CONFIG)["model"]["layers"]["1"]["feed_forward"]["gate"]["weight"]
    assert gate.shape == (2048, 64) and gate.dtype == jnp.float32  # the router keeps its published width


# (e) sigmoid routing with a bias, against hand-made cases --------------------------

ROUTING = dict(TINY, num_routed_experts=16, num_experts_per_tok=3)


def routed(scores, bias=None, **controls):
    bias = np.zeros(16, np.float32) if bias is None else bias
    weights, chosen = arch.route(ROUTING, jnp.asarray(scores)[None], jnp.asarray(bias), **controls)
    return dict(zip(np.asarray(chosen)[0].tolist(), np.asarray(weights)[0].tolist()))


def test_a_bias_that_changes_the_choice_leaves_the_weights_to_the_scores():
    """16 experts, top 3. Without a bias experts 0, 4 and 1 are chosen; a bias
    of 0.3 on expert 5 puts it in expert 1's place, and its weight is its
    score's share, not its biased one (``routed_scaling_factor`` 1)."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 1, 4, 5]] = [0.9, 0.5, 0.8, 0.4]
    assert sorted(routed(scores)) == [0, 1, 4]
    bias = np.zeros(16, np.float32)
    bias[5] = 0.3
    got = routed(scores, bias)
    assert sorted(got) == [0, 4, 5]
    for e, w in got.items():  # s of the chosen, divided by their sum + 1e-6
        assert w == pytest.approx(scores[e] / (0.9 + 0.8 + 0.4 + 1e-6), rel=1e-6)
    assert sum(got.values()) == pytest.approx(1.0, rel=1e-5) and ROUTING["routed_scaling_factor"] == 1
    let_in = routed(scores, bias, bias_in_weights=True)
    assert let_in[5] == pytest.approx(0.7 / (0.9 + 0.8 + 0.7), rel=1e-5)
    assert sorted(routed(scores, bias, bias_in_choice=False)) == [0, 1, 4]
    plain = routed(scores, bias, norm_topk_prob=False)
    assert plain[5] == pytest.approx(0.4, rel=1e-6) and sum(plain.values()) == pytest.approx(2.1, rel=1e-6)
    # No group limits the choice: the three largest of all are taken wherever they lie.
    spread = np.full(16, 0.05, np.float32)
    spread[[3, 9, 15]] = [0.6, 0.7, 0.8]
    assert sorted(routed(spread)) == [3, 9, 15]
    # The reference's gate makes the same choice with the same weights from logits that give these scores.
    p = {"gate": {"weight": jnp.asarray(np.log(scores / (1 - scores)))[None]}, "expert_bias": jnp.asarray(bias)}
    ref_weights, ref_chosen = ref.gate(ROUTING, p, jnp.ones((1, 1), jnp.float32))
    want = dict(zip(np.asarray(ref_chosen)[0].tolist(), np.asarray(ref_weights)[0].tolist()))
    assert sorted(want) == [0, 4, 5] and all(want[e] == pytest.approx(got[e], rel=1e-5) for e in got)


def test_the_bias_gets_no_gradient_and_the_router_does():
    p = expert_block(TINY, 11)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, TINY["hidden_size"]), jnp.float32)
    grads = jax.jit(jax.grad(lambda p_: jnp.sum(jnp.square(arch.expert_layer(TINY, p_, x)))))(p)
    assert not grads["expert_bias"].any() and bool(jnp.any(grads["gate"]["weight"] != 0))
    assert p["expert_bias"].dtype == jnp.float32 == arch.param_tree(TINY)["model"]["layers"]["1"]["feed_forward"]["expert_bias"].dtype
    # It steers all the same: without it some token's choice is another.
    scores = jax.nn.sigmoid(x.reshape(-1, x.shape[-1]) @ p["gate"]["weight"])
    _, with_bias = arch.route(TINY, scores, p["expert_bias"])
    _, without = arch.route(TINY, scores, p["expert_bias"], bias_in_choice=False)
    assert bool(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1)))


def test_a_pinned_choice_is_taken_as_it_is_and_weighed_by_the_scores():
    """``route``'s ``chosen``: another computation's choice (the reference's,
    on the chip) keeps the system's own scores as weights."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 1, 4, 5]] = [0.9, 0.5, 0.8, 0.4]
    pin = jnp.asarray([[5, 1, 9]])
    weights, chosen = arch.route(ROUTING, jnp.asarray(scores)[None], jnp.zeros(16), chosen=pin)
    assert np.asarray(chosen).tolist() == [[5, 1, 9]]
    assert np.asarray(weights)[0].tolist() == pytest.approx([s / (0.4 + 0.5 + 0.05 + 1e-6) for s in (0.4, 0.5, 0.05)], rel=1e-6)


def test_the_chips_gradient_comparison_pins_the_systems_experts_to_the_references():
    """In float32 the system chooses as the reference does, so the reference's
    choices pinned change nothing; every token sent where its neighbour goes
    moves the loss; every sparse layer takes its own, once."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 40)
    sound = arch.expert_layer
    choices = on_chip.reference_choices(ref, TINY, params, tokens[:, :-1], arch.held_experts(TINY), None)
    assert len(choices) == 8 and all(c.shape == (2 * 40, TINY["num_experts_per_tok"]) for c in choices)
    assert len({np.asarray(c).tobytes() for c in choices}) == 8
    want = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    with on_chip.pinned(arch, choices):
        assert float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params)) == pytest.approx(want, rel=1e-6)
    with on_chip.pinned(arch, [jnp.roll(c, 1, axis=0) for c in choices]):
        moved = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    assert abs(moved - want) > 1e-4 * want
    with pytest.raises(AssertionError), on_chip.pinned(arch, choices + choices[:1]):
        jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params)
    assert arch.expert_layer is sound


# (f) the tied table ---------------------------------------------------------------

def test_the_tied_leafs_gradient_is_the_sum_of_the_tables_and_the_heads():
    """One leaf read twice. Given to the model as two arguments, as an untied
    model would hold them, the two gradients are each non-zero, differ, and
    add up to the one leaf's; and the tree has no ``lm_head``."""
    params, tokens = seeded_params(TINY, 1, jnp.float32), tokens_of(TINY, 2, 2, 48)
    assert set(params) == {"model"} and set(params["model"]) == {"embed_tokens", "layers", "embedding_norm"}
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    real = arch.head_nll

    def untied(table, head):
        """The same model with the head handed in beside the table."""
        with_table = {"model": dict(params["model"], embed_tokens={"weight": table})}
        with_head = {"model": dict(params["model"], embed_tokens={"weight": head})}
        arch.head_nll = lambda cfg, _, x, t: real(cfg, with_head, x, t)
        try:
            return jnp.mean(arch.token_nll(TINY, with_table, inputs, targets))
        finally:
            arch.head_nll = real

    table = params["model"]["embed_tokens"]["weight"]
    tied = jax.grad(lambda p: arch.loss_fn(TINY, p, tokens))(params)["model"]["embed_tokens"]["weight"]
    of_table, of_head = jax.grad(untied, (0, 1))(table, table)
    assert float(untied(table, table)) == pytest.approx(float(arch.loss_fn(TINY, params, tokens)), rel=1e-6)
    assert bool(of_table.any()) and bool(of_head.any()) and not close(of_table, of_head, 1e-1)
    assert close(tied, of_table + of_head, 1e-5)
    # The reference ties it too, and reading the head from another matrix is another model.
    want = jax.grad(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params)
    assert close(tied, want["model"]["embed_tokens"]["weight"], 2e-3)
    nll = jnp.mean(arch.head_nll(TINY, params, table[inputs], targets))
    assert abs(float(jnp.mean(arch.head_nll(TINY, params, table[inputs], targets, tied=False))) - float(nll)) > 1e-3 * float(nll)


# (g) the seeded leaves -------------------------------------------------------------

def test_the_seeded_leaves_show_a_part_left_out():
    """``init_leaf`` as the cell runs it (no test's spread on top): or the
    comparisons with a norm left out, with the bias out of the choice and with
    the taps reversed would guard nothing."""
    job = trainstate.Job(arch, dict(TINY, job=dict(TINY["job"], seq_len=32)), jax.devices()[:1])
    params = job.init_state(5)["params"]["model"]
    layers = params["layers"]
    gains = [layers[str(i)][n]["weight"] for i in range(9) for n in ("operator_norm", "ffn_norm")] + [params["embedding_norm"]["weight"]]
    gains += [layers[i]["self_attn"][n]["weight"] for i in "26" for n in ("q_layernorm", "k_layernorm")]
    for gain in gains:
        gain = np.asarray(gain, np.float32)
        assert 0.03 < gain.std() < 0.2 and abs(gain.mean() - 1.0) < 0.1
    taps = np.asarray(layers["0"]["conv"]["conv"]["weight"], np.float32)
    assert taps.shape == (64, 1, 3) and 0.2 < taps.std() < 0.4
    matrix = np.asarray(layers["0"]["conv"]["in_proj"]["weight"], np.float32)
    assert 0.015 < matrix.std() < 0.025
    # Logits as wide as the configuration's: 0.02 * normal weights over 2048 inputs, not over the toy 64.
    wider = (CONFIG["hidden_size"] / TINY["hidden_size"]) ** 0.5
    ffn = layers["1"]["feed_forward"]
    x = jax.random.normal(jax.random.PRNGKey(6), (64, TINY["hidden_size"]), jnp.float32) * wider
    scores = jax.nn.sigmoid(x @ ffn["gate"]["weight"])
    _, with_bias = arch.route(TINY, scores, ffn["expert_bias"])
    _, without = arch.route(TINY, scores, ffn["expert_bias"], bias_in_choice=False)
    changed = float(jnp.mean(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1), axis=-1)))
    assert 0.05 < changed < 0.95


# (h) layer_types, leaf names, shapes and sizes -------------------------------------

CONV = ["conv.in_proj.weight", "conv.conv.weight", "conv.out_proj.weight"]
ATTENTION = ["self_attn.q_proj.weight", "self_attn.k_proj.weight", "self_attn.v_proj.weight", "self_attn.out_proj.weight",
             "self_attn.q_layernorm.weight", "self_attn.k_layernorm.weight"]
DENSE = ["feed_forward.w1.weight", "feed_forward.w2.weight", "feed_forward.w3.weight"]
SPARSE = ["feed_forward.gate.weight", "feed_forward.expert_bias"]
# The one departure: the held experts of a layer are three stacked leaves, where the
# checkpoint has feed_forward.experts.<e>.{w1,w2,w3}.weight for each expert e.
STACKS = ["feed_forward.experts.w1", "feed_forward.experts.w2", "feed_forward.experts.w3"]


def tensor_names(cfg):
    names = ["model.embed_tokens.weight", "model.embedding_norm.weight"]
    for i, kind in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        operator = ATTENTION if kind == "full_attention" else CONV
        ffn = SPARSE + STACKS if i >= cfg["num_dense_layers"] else DENSE
        names += [f"model.layers.{i}.{n}" for n in ["operator_norm.weight", "ffn_norm.weight"] + operator + ffn]
    return sorted(names)


def test_layer_types_drives_the_kinds_at_the_published_depth_and_at_the_cut():
    whole = dict(CONFIG, **CONFIG["published"])
    kinds = whole["layer_types"]
    assert whole["num_hidden_layers"] == 40 == len(kinds) and kinds == arch.PUBLISHED["layer_types"]
    assert (kinds.count("conv"), kinds.count("full_attention")) == (30, 10) and set(kinds) == {"conv", "full_attention"}
    assert [i for i in range(40) if arch.is_attention(whole, i)] == list(range(2, 40, 4))
    assert [arch.is_sparse(whole, i) for i in range(4)] == [False, False, True, True] and whole["num_dense_layers"] == 2
    # The cut: the first nine, one dense layer and two whole periods of the pattern over experts.
    assert CONFIG["layer_types"] == kinds[:9] == ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv"]
    assert CONFIG["num_hidden_layers"] == 9 and CONFIG["num_dense_layers"] == 1
    assert [arch.is_sparse(CONFIG, i) for i in range(9)] == [False] + [True] * 8
    layers = arch.param_tree(CONFIG)["model"]["layers"]
    for i, kind in enumerate(CONFIG["layer_types"]):
        operator = "self_attn" if kind == "full_attention" else "conv"
        assert set(layers[str(i)]) == {"operator_norm", "ffn_norm", "feed_forward", operator}
        assert set(layers[str(i)]["feed_forward"]) == ({"gate", "expert_bias", "experts"} if i else {"w1", "w2", "w3"})
    # Other kinds, another model: the same widths under attention first have the leaves and the loss of that list.
    other = dict(TINY, num_hidden_layers=3, layer_types=["full_attention", "conv", "full_attention"], num_dense_layers=2)
    params, tokens = seeded_params(other, 1, jnp.float32), tokens_of(other, 2, 1, 24)
    assert ["self_attn" in params["model"]["layers"][str(i)] for i in range(3)] == [True, False, True]
    assert ["experts" in params["model"]["layers"][str(i)]["feed_forward"] for i in range(3)] == [False, False, True]
    want = float(jax.jit(lambda p: ref.loss(other, p, tokens, experts=arch.held_experts(other)))(params))
    assert abs(float(jax.jit(lambda p: arch.loss_fn(other, p, tokens))(params)) - want) <= 1e-4 * want


def test_leaves_are_the_tensor_names_and_the_stated_shapes_and_dtypes():
    leaves = {
        trainstate.path_str(p).replace("/", "."): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(arch.param_tree(CONFIG))[0]
    }
    assert sorted(leaves) == tensor_names(CONFIG)
    assert len(leaves) == 2 + 8 + 6 * 10 + 2 * 13 == 96 and not any("lm_head" in n for n in leaves)
    # Every name the configuration lists under assumed.tensor_names is some leaf's.
    for stem in ("model.embed_tokens", "model.embedding_norm", "operator_norm", "ffn_norm", "conv.in_proj", "conv.conv", "conv.out_proj",
                 "self_attn.q_layernorm", "self_attn.out_proj", "feed_forward.gate", "feed_forward.expert_bias", "feed_forward.experts", "feed_forward.w1"):
        assert any(stem in n for n in leaves), stem
    for word in ("model.embed_tokens", "operator_norm", "ffn_norm", "in_proj", "q_layernorm", "k_layernorm", "expert_bias", "experts", "embedding_norm"):
        assert word in CONFIG["assumed"]["tensor_names"], word
    float32 = {n for n, leaf in leaves.items() if leaf.dtype == jnp.float32}
    assert float32 == {n for n in leaves if n.endswith(tuple(p.replace("/", ".") for p in FLOAT32))} and len(float32) == 16
    assert all(leaf.dtype == jnp.bfloat16 for n, leaf in leaves.items() if n not in float32)
    shapes = {
        "model.layers.1.feed_forward.experts.w1": (8, 2048, 1536), "model.layers.8.feed_forward.experts.w2": (8, 1536, 2048),
        "model.layers.4.feed_forward.experts.w3": (8, 2048, 1536),
        "model.layers.1.feed_forward.gate.weight": (2048, 64), "model.layers.1.feed_forward.expert_bias": (64,),
        "model.layers.0.feed_forward.w1.weight": (2048, 11776), "model.layers.0.feed_forward.w2.weight": (11776, 2048),
        "model.layers.0.feed_forward.w3.weight": (2048, 11776),
        "model.layers.0.conv.in_proj.weight": (2048, 6144), "model.layers.0.conv.conv.weight": (2048, 1, 3),
        "model.layers.0.conv.out_proj.weight": (2048, 2048),
        "model.layers.2.self_attn.q_proj.weight": (2048, 2048), "model.layers.2.self_attn.k_proj.weight": (2048, 512),
        "model.layers.2.self_attn.v_proj.weight": (2048, 512), "model.layers.6.self_attn.out_proj.weight": (2048, 2048),
        "model.layers.6.self_attn.q_layernorm.weight": (64,), "model.layers.6.self_attn.k_layernorm.weight": (64,),
        "model.layers.7.operator_norm.weight": (2048,), "model.layers.7.ffn_norm.weight": (2048,),
        "model.embed_tokens.weight": (8192, 2048), "model.embedding_norm.weight": (2048,),
    }
    assert {n: leaves[n].shape for n in shapes} == shapes
    assert 1536 % 128 == 0 and 2048 % 128 == 0  # every stack's rows fill whole lanes: the plain row cut takes them
    # The sizes ISSUE 50 reckons, from param_tree: parameters and bytes of the params and of the state, the leaves by size.
    job = trainstate.Job(arch, CONFIG, jax.devices()[:1])
    count, nbytes = trainstate.tree_size(job.abstract["params"]), trainstate.tree_nbytes(job.abstract["params"])
    assert (count, round(count / 1e6, 2), round(nbytes / 1e9, 3)) == (832652032, 832.65, 1.667)
    assert round(trainstate.tree_nbytes(job.abstract) / 1e9, 3) == 5.002
    assert list(job.batch_shape) == [CONFIG["job"]["micro_batch"], 8193]
    assert len(jax.tree_util.tree_leaves(job.abstract)) == 3 * 96 + 1 == 289
    per_layer = {i: trainstate.tree_size(arch.param_tree(CONFIG)["model"]["layers"][i]) for i in "012"}
    assert per_layer == {"0": 89139200, "1": 92416064, "2": 86118592}  # conv + dense, conv + experts, attention + experts
    sizes = [int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves.values()]
    assert sum(s < 1 << 20 for s in sizes) == 46
    by_size = {n: sizes.count(n) for n in (8 * 2048 * 1536 * 2, 2048 * 11776 * 2, 8192 * 2048 * 2, 2048 * 6144 * 2, 2048 * 2048 * 2, 2048 * 512 * 2,
                                          2048 * 64 * 4, 2048 * 3 * 2, 2048 * 2, 64 * 4, 64 * 2)}
    assert list(by_size.values()) == [24, 3, 1, 7, 7 + 4, 4, 8, 7, 19, 8, 4] and sum(by_size.values()) == 96
    assert round(100 * 24 * 8 * 2048 * 1536 * 2 / nbytes, 1) == 72.4
    # The uncut model's own count from the same rule: the published 24 B, its table counted once.
    whole = dict(CONFIG, **CONFIG["published"])
    assert round(trainstate.tree_size(arch.param_tree(whole)) / 1e9, 2) == 23.84


def test_param_spec_puts_the_expert_axis_on_the_stacks_and_the_vocabulary():
    assert tuple(arch.param_spec("model/layers/1/feed_forward/experts/w1")) == ("ep",)
    assert tuple(arch.param_spec("model/embed_tokens/weight")) == ("ep",)
    for whole in ("0/conv/in_proj/weight", "0/conv/conv/weight", "2/self_attn/q_proj/weight", "2/self_attn/q_layernorm/weight",
                  "1/feed_forward/gate/weight", "1/feed_forward/expert_bias", "0/feed_forward/w1/weight", "1/operator_norm/weight"):
        assert tuple(arch.param_spec(f"model/layers/{whole}")) == ()
    assert tuple(arch.param_spec("model/embedding_norm/weight")) == ()
    cfg = dict(TINY, layout={"chips": 2, "mesh": {"ep": 2}}, job=dict(TINY["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:2])
    shardings = {trainstate.path_str(p): s.spec for p, s in jax.tree_util.tree_flatten_with_path(job.shardings)[0]}
    assert tuple(shardings["opt_state/0/mu/model/layers/1/feed_forward/experts/w2"]) == ("ep",)
    assert tuple(shardings["params/model/layers/0/conv/conv/weight"]) == ()
    state, loss = job.train_step(job.init_state(7), job.make_batches(7, 1)[0])
    assert float(loss) > 0.0 and state["params"]["model"]["embed_tokens"]["weight"].sharding.spec == shardings["params/model/embed_tokens/weight"]
