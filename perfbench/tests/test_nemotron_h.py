"""Nemotron-3-Nano-30B-A3B as the benchmark runs it (``perfbench/models/nemotron_h.py``)
against its plain float32 reference (``perfbench/models/reference/nemotron_h.py``)
at ``TINY`` widths on the CPU (chunks of 8 positions under sequences of 32 to 100
that 8 does not divide, blocks of 8 queries), its chunked recurrence against the
token-by-token one, its grouped gated norm, its unrotated attention, its share of
the experts against the uncut layer, and its leaves against the tensor names.
``tests/test_nemotron_h.py`` runs these under the repo's tier-1 too.

Tolerances. With float32 parameters the system and the reference compute the
same equations in the same precision and differ only in the order of sums
(chunks of the recurrence against token by token, blocks of queries against
whole rows under a mask, a key-value head at a time against all heads at once,
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

arch = run.find_architecture(ROOT, "nemotron_h")
ref = run.load_module("pb_reference_nemotron_h", os.path.join(ROOT, "perfbench", "models", "reference", "nemotron_h.py"))
on_chip = run.load_module("pb_reference_on_chip_nemotron_h", os.path.join(ROOT, "perfbench", "tests", "reference_on_chip_nemotron_h.py"))
CONFIG = json.load(open(os.path.join(ROOT, "perfbench", "configs", "nemotron-3-nano-30b-a3b-ep8.json")))
TINY = dict(CONFIG, **arch.TINY)
SHARES = TINY["num_routed_experts"] // TINY["n_routed_experts"]  # chips that share a layer
TINY_LEAVES = 3 + 4 * 9 + 4 * 7 + 5  # embeddings, norm_f, lm_head; a norm and a mixer's leaves a block
CHUNK = TINY["chunk_size"]


def seeded_params(cfg, seed, dtype=None, spread=4.0):
    """Every leaf from the architecture's own rule, the matrices scaled up and
    the gains and biases spread further, so that no term of the equations is
    multiplied away; the decay's and the step's leaves, the convolution's taps
    and the bias that steers the choice stay as they are drawn."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(arch.param_tree(cfg))
    out = []
    for (path, leaf), key in zip(leaves, jax.random.split(jax.random.PRNGKey(seed), len(leaves))):
        path = trainstate.path_str(path)
        value = arch.init_leaf(path, leaf, key).astype(jnp.float32)
        if leaf.ndim == 2:
            value = value * spread
        elif leaf.ndim == 1 and not path.endswith(("e_score_correction_bias", "A_log", "dt_bias")):
            value = value + 0.3 * jax.random.normal(key, leaf.shape)
        out.append(value.astype(dtype or leaf.dtype))
    return treedef.unflatten(out)


def tokens_of(cfg, seed, batch, length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, arch.token_range(cfg))


def close(got, want, relative):
    return float(jnp.max(jnp.abs(got - want))) <= relative * float(jnp.max(jnp.abs(want)))


# (a) the loss and its gradients against the reference ------------------------

@pytest.mark.parametrize("length,block", [(32, 1024), (100, 1024), (100, 8)])
def test_loss_and_gradients_equal_the_references_in_float32(length, block, monkeypatch):
    """100 positions are twelve chunks of 8 and a half one; ``block`` 8 cuts
    them into thirteen blocks of queries and of the head, the last one short,
    as 512 and 1024 cut the configuration's 8192."""
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
        if path.endswith("e_score_correction_bias"):  # a buffer: it steers a choice, and no gradient reaches it
            assert not g.any() and not w.any(), path
            continue
        assert float(jnp.max(jnp.abs(w))) > 0.0, path  # every other leaf is used
        assert close(g, w, 2e-3), path


FLOAT32 = ("mixer/A_log", "mixer/D", "mixer/dt_bias", "mixer/gate/weight", "mixer/gate/e_score_correction_bias")


def test_loss_in_the_stated_dtypes_is_near_the_float32_reference():
    params, tokens = seeded_params(TINY, 3), tokens_of(TINY, 4, 2, 100)
    by_dtype = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        by_dtype.setdefault(str(x.dtype), []).append(trainstate.path_str(path))
    assert set(by_dtype) == {"bfloat16", "float32"}
    assert all(p.endswith(FLOAT32) for p in by_dtype["float32"]) and len(by_dtype["float32"]) == 4 * 3 + 4 * 2
    loss = float(jax.jit(lambda p: arch.loss_fn(TINY, p, tokens))(params))
    want = float(jax.jit(lambda p: ref.loss(TINY, p, tokens, experts=arch.held_experts(TINY)))(params))
    assert abs(loss - want) <= 2e-2 * abs(want)


@pytest.mark.parametrize("kind", [None] + sorted(on_chip.BROKEN) + ["f32_as_bf16"])
def test_the_comparison_is_tight_enough_to_see_a_part_left_out(kind, monkeypatch):
    """On the reference's own most likely next tokens (the training loss on
    random targets is ``log(rows) + var / 2`` of the logits whatever the blocks
    compute, so it hardly sees them) the float32 tolerance of the loss, taken
    position by position, holds the sound system and fails each part the
    chip's comparison breaks (``reference_on_chip_nemotron_h.BROKEN``: the same
    functions, the same controls): the gate after the norm, the recurrence or
    ``D x`` left out, ``dt_bias`` or the convolution's bias left out, one norm
    group, attention rotated, a block's norm left out, the bias out of the
    choice or in the weights, the weights not normalised, the shared expert, a
    plain ReLU; and the float32 leaves rounded through bf16."""
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


# (b) the chunked recurrence against the token-by-token one -------------------------

def scan_inputs(length, seed=0, lo=1e-3, hi=4.0):
    """Steps drawn log-uniformly in ``[lo, hi]`` under rates of 1 to 16: a
    position's decay ``exp(delta A)`` runs from nearly 1 (0.999) to nearly 0
    (e^-64), and a chunk's from 0.99 to e^-500."""
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 6)
    bsz, h, p, g, s = 2, 4, 8, 2, 16
    x = jax.random.normal(keys[0], (bsz, length, h, p))
    step = jnp.exp(jax.random.uniform(keys[1], (bsz, length, h), minval=np.log(lo), maxval=np.log(hi)))
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=0.0, maxval=np.log(16.0)))
    b, c = jax.random.normal(keys[3], (bsz, length, g, s)), jax.random.normal(keys[4], (bsz, length, g, s))
    return (x, step, a, b, c), jax.random.normal(keys[5], (bsz, length, h, p))


def out_and_grads(f, args, weight):
    """``f``'s output and what the cotangent ``weight`` sends back to each argument, in one program."""

    @jax.jit
    def both(*args):
        out, vjp = jax.vjp(f, *args)
        return out, vjp(weight)

    return both(*args)


def token_by_token(x, step, a, b, c):
    heads = x.shape[2] // b.shape[2]
    return ref.recurrence(x, step, a, jnp.repeat(b, heads, axis=2), jnp.repeat(c, heads, axis=2))


@pytest.mark.parametrize("length,chunk", [(8, 8), (5, 8), (9, 8), (100, 8), (37, 5), (64, 16)])
def test_the_chunked_recurrence_is_the_token_by_token_one_forward_and_backward(length, chunk):
    """A sequence of one chunk, of less, of one position more, and of many
    chunks that the chunk does not divide, decays near 0 and near 1 alike."""
    args, weight = scan_inputs(length)
    with jax.default_matmul_precision("highest"):
        got, grads = out_and_grads(lambda *v: arch.chunked_scan(*v, chunk), args, weight)
        want, want_grads = out_and_grads(token_by_token, args, weight)
        assert got.shape == want.shape and got.dtype == jnp.float32 and close(got, want, 1e-5)
        for g, w in zip(grads, want_grads):
            assert float(jnp.max(jnp.abs(w))) > 0.0 and close(g, w, 1e-4)


def test_gradients_are_finite_with_the_mask_before_the_exponential_and_not_with_it_after():
    """Steps of up to 40 under rates of up to 16: above the diagonal ``cum_i -
    cum_j`` reaches several hundred, ``exp`` of it is ``inf``, and ``inf * 0``
    is in the cotangents unless the mask came first."""
    args, weight = scan_inputs(64, seed=1, lo=1.0, hi=40.0)
    out, grads = out_and_grads(lambda *v: arch.chunked_scan(*v, 16), args, weight)
    assert bool(jnp.isfinite(out).all()) and all(bool(jnp.isfinite(g).all()) for g in grads)
    _, late = out_and_grads(lambda *v: arch.chunked_scan(*v, 16, mask_first=False), args, weight)
    assert not all(bool(jnp.isfinite(g).all()) for g in late)


def test_a_later_position_has_no_effect_and_the_state_carries_over_chunks():
    (x, step, a, b, c), _ = scan_inputs(40, lo=1e-3, hi=0.05)
    scan = jax.jit(lambda x_: arch.chunked_scan(x_, step, a, b, c, 8))
    one, two = scan(x), scan(x.at[:, 20].add(1.0))
    moved = np.asarray(jnp.any(one != two, axis=(0, 2, 3)))
    assert not moved[:20].any() and moved[20:].all()  # through the chunk's own matrix and through three chunks' states


# (c) the grouped gated norm ----------------------------------------------------

def test_the_gated_norm_gates_first_and_norms_each_group_by_itself():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    y, z = jax.random.normal(keys[0], (2, 9, 32)) * jnp.arange(1, 33), jax.random.normal(keys[1], (2, 9, 32))
    w = 1.0 + 0.1 * jax.random.normal(keys[2], (32,))
    got = arch.gated_norm(y, z, w, 4, 1e-5)
    assert close(got, ref.grouped_gated_norm(y, z, w, 4, 1e-5), 1e-6)
    gated = np.asarray(y * jax.nn.silu(z))
    by_hand = np.concatenate([
        part / np.sqrt((part ** 2).mean(-1, keepdims=True) + 1e-5) for part in np.split(gated, 4, axis=-1)
    ], axis=-1) * np.asarray(w)
    assert np.allclose(np.asarray(got), by_hand, rtol=1e-5, atol=1e-6)
    assert not close(arch.gated_norm(y, z, w, 4, 1e-5, gate_first=False), got, 1e-2)
    assert not close(arch.gated_norm(y, z, w, 1, 1e-5), got, 1e-2)
    # The mixer norms over n_groups groups, and the gain is one of d_inner.
    assert TINY["n_groups"] == 2 and arch.param_tree(TINY)["backbone"]["layers"]["0"]["mixer"]["norm"]["weight"].shape == (32,)


def test_the_convolution_is_causal_with_bias_and_its_last_tap_is_the_positions_own():
    p = {"weight": jnp.asarray(np.arange(1, 13, dtype=np.float32).reshape(3, 1, 4)), "bias": jnp.asarray([0.5, -0.5, 0.0])}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 3))
    got = np.asarray(arch._causal_conv(x, p))
    xs, w = np.asarray(x)[0], np.asarray(p["weight"])[:, 0]
    for t in range(6):
        pre = sum(w[:, j] * (xs[t - 3 + j] if t - 3 + j >= 0 else 0.0) for j in range(4)) + np.asarray(p["bias"])
        assert np.allclose(got[0, t], pre / (1 + np.exp(-pre)), rtol=1e-5, atol=1e-6)


# (d) attention: blocked against the dense mask, and not rotated --------------------

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


def test_attention_knows_a_position_only_by_the_mask(monkeypatch):
    """Nothing is rotated: with keys 0 and 1 swapped (and their values), every
    query from 2 on gives the same, and a later key never reaches an earlier
    query; rotated, the swap shows. 2 query heads a key-value head at the toy
    widths, 16 at the configuration's."""
    p = seeded_params(TINY, 7, jnp.float32)["backbone"]["layers"]["5"]["mixer"]
    assert arch.kind(TINY, 5) == "*" and set(p) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, TINY["hidden_size"]), jnp.float32)
    swap = jnp.concatenate([x[:, 1:2], x[:, 0:1], x[:, 2:]], axis=1)
    plain, turned = jax.jit(lambda x_: arch.attention(TINY, p, x_)), jax.jit(lambda x_: arch.attention(TINY, p, x_, rotate=True))
    sound = plain(x)
    assert close(plain(swap)[:, 2:], sound[:, 2:], 1e-5)
    assert not close(turned(swap)[:, 2:], turned(x)[:, 2:], 1e-3) and not close(turned(x), sound, 1e-3)
    moved = np.asarray(jnp.any(plain(x.at[:, 20].add(1.0)) != sound, axis=(0, 2)))
    assert not moved[:20].any() and moved[20:].all()
    assert close(sound, jax.jit(lambda x_: ref.attention(TINY, p, x_))(x), 1e-4)
    assert CONFIG["num_attention_heads"] // CONFIG["num_key_value_heads"] == 16 and CONFIG["head_dim"] == 128


# (e) the shares add up to the uncut layer --------------------------------------

def expert_block(cfg, seed):
    return seeded_params(cfg, seed, jnp.float32)["backbone"]["layers"]["1"]["mixer"]


def test_expert_layer_summed_over_all_shares_is_the_uncut_references():
    """model-configs section 4: what every share's experts give, with what
    every chip computes alike (the shared expert) counted once, adds up to
    the uncut reference's expert layer. 8 shares, as 8 chips share a layer."""
    routed, held = TINY["num_routed_experts"], TINY["n_routed_experts"]
    assert SHARES == 8 == CONFIG["num_routed_experts"] // CONFIG["n_routed_experts"]
    uncut = expert_block(dict(TINY, n_routed_experts=routed), 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, TINY["hidden_size"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(TINY, uncut, x, (0, routed))
    total = jnp.zeros_like(x)
    for rank in range(SHARES):
        cfg = dict(TINY, layer_share_rank=rank)
        lo, hi = arch.held_experts(cfg)
        assert (lo, hi) == (rank * held, (rank + 1) * held)
        mine = dict(uncut, experts={k: v[lo:hi] for k, v in uncut["experts"].items()})
        total = total + jax.jit(lambda p_, cfg=cfg, rank=rank: arch.expert_layer(cfg, p_, x, shared=rank == 0))(mine)
    assert close(total, want, 1e-4)
    # And a share alone is the reference's for that range: nothing stands in for the absent.
    alone = arch.expert_layer(TINY, dict(uncut, experts={k: v[:held] for k, v in uncut["experts"].items()}), x)
    with jax.default_matmul_precision("highest"):
        want_alone = ref.expert_layer(TINY, uncut, x, (0, held))
    assert close(alone, want_alone, 1e-4)
    assert not close(alone, want, 1e-2)


def test_an_expert_is_two_matrices_and_a_squared_relu_between_them():
    """Every expert held, one token: ``sum_k w_k down_k(relu(up_k x)^2)`` over
    its chosen experts plus the shared expert's ``down(relu(up x)^2)``, by
    hand; there is no gate projection among the leaves."""
    routed = TINY["num_routed_experts"]
    cfg = dict(TINY, n_routed_experts=routed)
    p = expert_block(cfg, 9)
    assert set(p["experts"]) == {"up_proj", "down_proj"} and set(p["shared_experts"]) == {"up_proj", "down_proj"}
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 1, TINY["hidden_size"]), jnp.float32)
    scores = jax.nn.sigmoid(x[0] @ p["gate"]["weight"])
    weights, chosen = arch.route(cfg, scores, p["gate"]["e_score_correction_bias"])
    want = np.zeros(TINY["hidden_size"], np.float64)
    row = np.asarray(x[0, 0], np.float64)
    for w, e in zip(np.asarray(weights[0], np.float64), np.asarray(chosen[0])):
        up, down = (np.asarray(p["experts"][n][e], np.float64) for n in ("up_proj", "down_proj"))
        want += w * (np.maximum(row @ up, 0.0) ** 2 @ down)
    up, down = (np.asarray(p["shared_experts"][n]["weight"], np.float64) for n in ("up_proj", "down_proj"))
    want += np.maximum(row @ up, 0.0) ** 2 @ down
    with jax.default_matmul_precision("highest"):
        got = np.asarray(arch.expert_layer(cfg, p, x))[0, 0]
        plain = np.asarray(arch.expert_layer(cfg, p, x, squared=False))[0, 0]
    assert np.allclose(got, want, rtol=1e-4, atol=1e-6) and not np.allclose(plain, want, rtol=1e-2, atol=1e-6)


def test_the_configurations_chip_holds_sixteen_of_the_routers_128():
    assert arch.held_experts(CONFIG) == (0, 16) and arch.held_experts(dict(CONFIG, layer_share_rank=7)) == (112, 128)
    assert (CONFIG["num_routed_experts"], CONFIG["num_experts_per_tok"], CONFIG["n_group"], CONFIG["topk_group"]) == (128, 6, 1, 1)
    assert (CONFIG["routed_scaling_factor"], CONFIG["norm_topk_prob"], CONFIG["mlp_hidden_act"]) == (2.5, True, "relu2")
    assert CONFIG["published"]["n_routed_experts"] == 128 == arch.PUBLISHED["n_routed_experts"]


# (f) sigmoid routing with a bias, against hand-made cases --------------------------

ROUTING = dict(TINY, num_routed_experts=16, num_experts_per_tok=3)


def routed(scores, bias=None, **controls):
    bias = np.zeros(16, np.float32) if bias is None else bias
    weights, chosen = arch.route(ROUTING, jnp.asarray(scores)[None], jnp.asarray(bias), **controls)
    return dict(zip(np.asarray(chosen)[0].tolist(), np.asarray(weights)[0].tolist()))


def test_a_bias_that_changes_the_choice_leaves_the_weights_to_the_scores():
    """16 experts, top 3. Without a bias experts 0, 4 and 1 are chosen; a bias
    of 0.3 on expert 5 puts it in expert 1's place, and its weight is its
    score's share, not its biased one, times 2.5."""
    scores = np.full(16, 0.05, np.float32)
    scores[[0, 1, 4, 5]] = [0.9, 0.5, 0.8, 0.4]
    assert sorted(routed(scores)) == [0, 1, 4]
    bias = np.zeros(16, np.float32)
    bias[5] = 0.3
    got = routed(scores, bias)
    assert sorted(got) == [0, 4, 5]
    for e, w in got.items():  # s of the chosen, divided by their sum, times routed_scaling_factor
        assert w == pytest.approx(2.5 * scores[e] / (0.9 + 0.8 + 0.4), rel=1e-6)
    assert sum(got.values()) == pytest.approx(ROUTING["routed_scaling_factor"], rel=1e-6)
    let_in = routed(scores, bias, bias_in_weights=True)
    assert let_in[5] == pytest.approx(2.5 * 0.7 / (0.9 + 0.8 + 0.7), rel=1e-6)
    assert sorted(routed(scores, bias, bias_in_choice=False)) == [0, 1, 4]
    plain = routed(scores, bias, norm_topk_prob=False)
    assert plain[5] == pytest.approx(2.5 * 0.4, rel=1e-6) and sum(plain.values()) == pytest.approx(2.5 * 2.1, rel=1e-6)
    # No group limits the choice (n_group 1): the three largest of all are taken wherever they lie.
    spread = np.full(16, 0.05, np.float32)
    spread[[3, 9, 15]] = [0.6, 0.7, 0.8]
    assert sorted(routed(spread)) == [3, 9, 15]
    # The reference's gate makes the same choice with the same weights from logits that give these scores.
    p = {"gate": {"weight": jnp.asarray(np.log(scores / (1 - scores)))[None], "e_score_correction_bias": jnp.asarray(bias)}}
    ref_weights, ref_chosen = ref.gate(ROUTING, p, jnp.ones((1, 1), jnp.float32))
    want = dict(zip(np.asarray(ref_chosen)[0].tolist(), np.asarray(ref_weights)[0].tolist()))
    assert sorted(want) == [0, 4, 5] and all(want[e] == pytest.approx(got[e], rel=1e-5) for e in got)


def test_the_bias_gets_no_gradient_and_the_router_does():
    p = expert_block(TINY, 11)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, TINY["hidden_size"]), jnp.float32)
    grads = jax.jit(jax.grad(lambda p_: jnp.sum(jnp.square(arch.expert_layer(TINY, p_, x)))))(p)
    assert not grads["gate"]["e_score_correction_bias"].any() and bool(jnp.any(grads["gate"]["weight"] != 0))
    # It steers all the same: without it some token's choice is another.
    scores = jax.nn.sigmoid(x.reshape(-1, x.shape[-1]) @ p["gate"]["weight"])
    _, with_bias = arch.route(TINY, scores, p["gate"]["e_score_correction_bias"])
    _, without = arch.route(TINY, scores, p["gate"]["e_score_correction_bias"], bias_in_choice=False)
    assert bool(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1)))


def test_the_seeded_leaves_keep_the_scan_in_its_working_range_and_show_a_leaf_left_out():
    """``init_leaf`` as the cell runs it (no test's spread on top): or the
    comparisons with a norm left out, with the bias out of the choice and with
    the recurrence left out would guard nothing."""
    job = trainstate.Job(arch, dict(TINY, job=dict(TINY["job"], seq_len=32)), jax.devices()[:1])
    params = job.init_state(5)["params"]
    layers = params["backbone"]["layers"]
    gains = [layers[str(i)]["norm"]["weight"] for i in range(9)] + [params["backbone"]["norm_f"]["weight"]]
    gains += [layers[i]["mixer"]["norm"]["weight"] for i in "0247"]
    for gain in gains:
        gain = np.asarray(gain, np.float32)
        assert 0.05 < gain.std() < 0.2 and abs(gain.mean() - 1.0) < 0.1
    # At the configuration's 64 heads: rates of 1 to 16, steps of 0.001 to 0.1 before the input moves them.
    wide = arch.param_tree(CONFIG)["backbone"]["layers"]["0"]["mixer"]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    rate = np.exp(np.asarray(arch.init_leaf("m/A_log", wide["A_log"], keys[0])))
    step = np.log1p(np.exp(np.asarray(arch.init_leaf("m/dt_bias", wide["dt_bias"], keys[1]), np.float64)))
    assert rate.dtype == np.float32 and 1.0 <= rate.min() < 4.0 and 12.0 < rate.max() <= 16.0
    assert 0.001 <= step.min() < 0.004 and 0.03 < step.max() <= 0.1 + 1e-6
    skip = np.asarray(arch.init_leaf("m/D", wide["D"], keys[2]))
    assert skip.dtype == np.float32 and 0.05 < skip.std() < 0.2 and abs(skip.mean() - 1.0) < 0.1
    # The recurrence adds a share of what ``D x`` adds that a comparison can see (ISSUE 44's 0.02 * normal taps: a 500th).
    mixer = jax.tree.map(lambda a: a.astype(jnp.float32), layers["0"]["mixer"])
    wider = (CONFIG["hidden_size"] / TINY["hidden_size"]) ** 0.5  # 0.02 * normal weights over 2688 inputs, not over the toy 64
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, TINY["hidden_size"]), jnp.float32) * wider
    taps = np.asarray(mixer["conv1d"]["weight"])
    assert taps.shape == (2 * 16 * 2 + 32, 1, 4) and 0.2 < taps.std() < 0.4 and 0.05 < np.asarray(mixer["conv1d"]["bias"]).std() < 0.2
    whole, no_scan = (jax.jit(lambda x_, scan=scan: arch.mamba(TINY, mixer, x_, scan=scan))(x) for scan in (True, False))
    # 0.015 with the toy 16 states a head; 0.17 at the configuration's widths (128 states, 1024 positions).
    assert 0.005 < float(jnp.abs(whole - no_scan).mean() / jnp.abs(whole).mean()) < 2.0
    # Logits as wide as the configuration's, too.
    gate = layers["1"]["mixer"]["gate"]
    x = jax.random.normal(jax.random.PRNGKey(6), (64, TINY["hidden_size"]), jnp.float32) * wider
    scores = jax.nn.sigmoid(x @ gate["weight"])
    _, with_bias = arch.route(TINY, scores, gate["e_score_correction_bias"])
    _, without = arch.route(TINY, scores, gate["e_score_correction_bias"], bias_in_choice=False)
    changed = float(jnp.mean(jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1), axis=-1)))
    assert 0.05 < changed < 0.95


# (g) the pattern, leaf names, shapes and sizes -------------------------------------

def test_the_pattern_string_drives_the_block_kinds_at_the_published_depth_and_at_the_cut():
    whole = dict(CONFIG, **CONFIG["published"])
    pattern = whole["hybrid_override_pattern"]
    assert whole["num_hidden_layers"] == 52 == len(pattern) and pattern == arch.PUBLISHED["hybrid_override_pattern"]
    kinds = [arch.kind(whole, i) for i in range(52)]
    assert (kinds.count("M"), kinds.count("E"), kinds.count("*")) == (23, 23, 6) and set(kinds) == {"M", "E", "*"}
    assert [i for i, k in enumerate(kinds) if k == "*"] == [5, 12, 19, 26, 33, 42]
    # The cut: the first nine characters, 4 : 4 : 1.
    assert CONFIG["hybrid_override_pattern"] == pattern[:9] == "MEMEM*EME" and CONFIG["num_hidden_layers"] == 9
    layers = arch.param_tree(CONFIG)["backbone"]["layers"]
    leaves_of = {"M": {"in_proj", "conv1d", "dt_bias", "A_log", "D", "norm", "out_proj"},
                 "E": {"gate", "experts", "shared_experts"}, "*": {"q_proj", "k_proj", "v_proj", "o_proj"}}
    for i, k in enumerate(CONFIG["hybrid_override_pattern"]):
        assert set(layers[str(i)]) == {"norm", "mixer"} and set(layers[str(i)]["mixer"]) == leaves_of[k]  # one mixer, no MLP half
    # Another pattern, another model: the same widths under "M*E" have the leaves and the loss of that string.
    other = dict(TINY, num_hidden_layers=3, hybrid_override_pattern="M*E")
    params, tokens = seeded_params(other, 1, jnp.float32), tokens_of(other, 2, 1, 24)
    assert [set(params["backbone"]["layers"][str(i)]["mixer"]) for i in range(3)] == [leaves_of[k] for k in "M*E"]
    want = float(jax.jit(lambda p: ref.loss(other, p, tokens, experts=arch.held_experts(other)))(params))
    assert abs(float(jax.jit(lambda p: arch.loss_fn(other, p, tokens))(params)) - want) <= 1e-4 * want


MAMBA = ["mixer.in_proj.weight", "mixer.conv1d.weight", "mixer.conv1d.bias", "mixer.dt_bias", "mixer.A_log", "mixer.D",
         "mixer.norm.weight", "mixer.out_proj.weight"]
ATTENTION = ["mixer.q_proj.weight", "mixer.k_proj.weight", "mixer.v_proj.weight", "mixer.o_proj.weight"]
SPARSE = ["mixer.gate.weight", "mixer.gate.e_score_correction_bias", "mixer.shared_experts.up_proj.weight",
          "mixer.shared_experts.down_proj.weight"]
# The one departure: the held experts of a block are two stacked leaves, where the
# checkpoint has mixer.experts.<e>.{up,down}_proj.weight for each expert e.
STACKS = ["mixer.experts.up_proj", "mixer.experts.down_proj"]


def tensor_names(cfg):
    names = ["backbone.embeddings.weight", "backbone.norm_f.weight", "lm_head.weight"]
    for i, k in enumerate(cfg["hybrid_override_pattern"]):
        mixer = {"M": MAMBA, "*": ATTENTION, "E": SPARSE + STACKS}[k]
        names += [f"backbone.layers.{i}.{n}" for n in ["norm.weight"] + mixer]
    return sorted(names)


def test_leaves_are_the_tensor_names_and_the_stated_shapes_and_dtypes():
    leaves = {
        trainstate.path_str(p).replace("/", "."): leaf
        for p, leaf in jax.tree_util.tree_flatten_with_path(arch.param_tree(CONFIG))[0]
    }
    assert sorted(leaves) == tensor_names(CONFIG)
    assert len(leaves) == 3 + 4 * 9 + 4 * 7 + 5 == 72
    # Every name the configuration lists under assumed.tensor_names is some leaf's.
    for stem in ("backbone.embeddings", "backbone.norm_f", "lm_head", "mixer.experts", "mixer.shared_experts", "mixer.gate", "mixer.conv1d.bias"):
        assert stem.split(".", 1)[-1] in CONFIG["assumed"]["tensor_names"] and any(stem in n for n in leaves)
    float32 = {n for n, leaf in leaves.items() if leaf.dtype == jnp.float32}
    assert float32 == {n for n in leaves if n.endswith(tuple(p.replace("/", ".") for p in FLOAT32))} and len(float32) == 20
    assert all(leaf.dtype == jnp.bfloat16 for n, leaf in leaves.items() if n not in float32)
    shapes = {
        "backbone.layers.1.mixer.experts.up_proj": (16, 2688, 1856), "backbone.layers.8.mixer.experts.down_proj": (16, 1856, 2688),
        "backbone.layers.1.mixer.gate.weight": (2688, 128), "backbone.layers.1.mixer.gate.e_score_correction_bias": (128,),
        "backbone.layers.1.mixer.shared_experts.up_proj.weight": (2688, 3712), "backbone.layers.3.mixer.shared_experts.down_proj.weight": (3712, 2688),
        "backbone.layers.0.mixer.in_proj.weight": (2688, 10304), "backbone.layers.0.mixer.out_proj.weight": (4096, 2688),
        "backbone.layers.2.mixer.conv1d.weight": (6144, 1, 4), "backbone.layers.2.mixer.conv1d.bias": (6144,),
        "backbone.layers.4.mixer.A_log": (64,), "backbone.layers.4.mixer.D": (64,), "backbone.layers.4.mixer.dt_bias": (64,),
        "backbone.layers.7.mixer.norm.weight": (4096,), "backbone.layers.7.norm.weight": (2688,),
        "backbone.layers.5.mixer.q_proj.weight": (2688, 4096), "backbone.layers.5.mixer.k_proj.weight": (2688, 256),
        "backbone.layers.5.mixer.v_proj.weight": (2688, 256), "backbone.layers.5.mixer.o_proj.weight": (4096, 2688),
        "backbone.embeddings.weight": (16384, 2688), "lm_head.weight": (16384, 2688), "backbone.norm_f.weight": (2688,),
    }
    assert {n: leaves[n].shape for n in shapes} == shapes
    assert 1856 % 128 == 64 and 10304 == 4096 + 6144 + 64 and 6144 == 4096 + 2 * 8 * 128  # the up stack's minor dimension is no multiple of the lanes
    # The sizes ISSUE 44 reckons: parameters and bytes of the params and of the state, and the leaves by size.
    job = trainstate.Job(arch, CONFIG, jax.devices()[:1])
    count, nbytes = trainstate.tree_size(job.abstract["params"]), trainstate.tree_nbytes(job.abstract["params"])
    assert (count, round(count / 1e6, 2), round(nbytes / 1e9, 3)) == (986254848, 986.25, 1.975)
    assert round(trainstate.tree_nbytes(job.abstract) / 1e9, 3) == 5.926 and list(job.batch_shape) == [1, 8193]
    assert len(jax.tree_util.tree_leaves(job.abstract)) == 217
    per_block = {k: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(arch.param_tree(CONFIG)["backbone"]["layers"][i])) for k, i in (("M", "0"), ("E", "1"), ("*", "5"))}
    assert per_block == {"M": 38744896, "E": 179948288, "*": 23399040}  # a block's norm counted with its mixer
    sizes = [int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in leaves.values()]
    assert sum(s < 1 << 20 for s in sizes) == 38
    # k_proj and v_proj (2688 x 256 bf16) and the four float32 routers (2688 x 128) are 1,376,256 bytes alike.
    by_size = {n: sizes.count(n) for n in (16 * 2688 * 1856 * 2, 2688 * 10304 * 2, 16384 * 2688 * 2, 2688 * 3712 * 2, 4096 * 2688 * 2, 2688 * 256 * 2,
                                          6144 * 4 * 2, 6144 * 2, 4096 * 2, 2688 * 2, 128 * 4, 64 * 4)}
    assert list(by_size.values()) == [8, 4, 2, 8, 6, 2 + 4, 4, 4, 4, 10, 4, 12] and sum(by_size.values()) == 72
    assert round(100 * 8 * 16 * 2688 * 1856 * 2 / nbytes, 1) == 64.7
    # The uncut model's own count from the same rule: the published 31.6 B.
    whole = dict(CONFIG, **CONFIG["published"])
    assert round(trainstate.tree_size(arch.param_tree(whole)) / 1e9, 1) == 31.6


def test_param_spec_puts_the_expert_axis_on_the_stacks_and_the_vocabulary():
    assert tuple(arch.param_spec("backbone/layers/1/mixer/experts/up_proj")) == ("ep",)
    assert tuple(arch.param_spec("backbone/embeddings/weight")) == ("ep",)
    assert tuple(arch.param_spec("lm_head/weight")) == ("ep",)
    for whole in ("0/mixer/in_proj/weight", "0/mixer/A_log", "0/mixer/conv1d/weight", "5/mixer/q_proj/weight", "1/mixer/gate/weight",
                  "1/mixer/gate/e_score_correction_bias", "1/mixer/shared_experts/up_proj/weight", "1/norm/weight"):
        assert tuple(arch.param_spec(f"backbone/layers/{whole}")) == ()
    cfg = dict(TINY, layout={"chips": 2, "mesh": {"ep": 2}}, job=dict(TINY["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:2])
    shardings = {trainstate.path_str(p): s.spec for p, s in jax.tree_util.tree_flatten_with_path(job.shardings)[0]}
    assert tuple(shardings["opt_state/0/mu/backbone/layers/1/mixer/experts/down_proj"]) == ("ep",)
    assert tuple(shardings["params/backbone/layers/0/mixer/conv1d/weight"]) == ()
    state, loss = job.train_step(job.init_state(7), job.make_batches(7, 1)[0])
    assert float(loss) > 0.0 and state["params"]["lm_head"]["weight"].sharding.spec == shardings["params/lm_head/weight"]
