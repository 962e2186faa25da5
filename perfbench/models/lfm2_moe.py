"""LFM2-24B-A2B as LiquidAI/LFM2-24B-A2B publishes it (``config.json``,
``model_type`` ``lfm2_moe``), told which experts and which rows of the
vocabulary it holds: one chip's share of an expert-parallel job.

Layer ``i`` is ``h = x + operator_i(operator_norm(x))``, ``y = h +
feed_forward_i(ffn_norm(h))``, both norms RMSNorm of ``hidden_size``
(``norm_eps``). The operator is what ``layer_types[i]`` says: ``conv`` (three
layers in four) or ``full_attention`` (``i % 4 == 2``). The first
``num_dense_layers`` layers have a dense SwiGLU MLP of ``intermediate_size``,
the others the sparse mixture.

``conv``, the gated short convolution: ``B | C | x~ = x W_in`` (``hidden_size``
each, in that order, no bias: ``conv_bias`` false); ``u = B * x~``; ``v_t =
sum_k w[:, k] u_(t - L + 1 + k)`` over the ``L = conv_L_cache`` taps of a
depth-wise causal convolution (left padding ``L - 1``, the last tap the
position's own, no bias); ``out = (C * v) W_out``. **No activation, no
recurrence, no softmax, and no position: two elementwise gates round three
taps.**

``full_attention``: ``q_proj`` (``num_attention_heads`` x head), ``k_proj``,
``v_proj`` (``num_key_value_heads`` x head), ``out_proj``, no bias, the head
``hidden_size / num_attention_heads`` wide; RMSNorm over the channels of every
query head and of every key head (gains ``q_layernorm``, ``k_layernorm``, one of
the head's width each) **before** the rotation; all of the head's dims turned by
the position's angle (rotate-half, ``rope_parameters.rope_theta``, no scaling);
causal grouped-query softmax at ``head^-1/2``; no gate.

The mixture: ``s = sigmoid(x W_gate)`` in float32 over all
``num_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b`` are
chosen, ``b`` the float32 buffer ``feed_forward.expert_bias`` (``use_expert_bias``;
no gradient reaches it); the weights are ``s`` (not ``s + b``) of the chosen,
divided by their sum + 1e-6 (``norm_topk_prob``), times
``routed_scaling_factor``; an expert is ``w2(silu(w1 x) * w3 x)``; **no shared
expert**.

The model: ``embed_tokens(ids)``, the layers, ``embedding_norm``, and **the
embedding matrix itself as the head**: one leaf read twice, whose gradient is
the sum of both uses; the tree has no ``lm_head``.

Plain ``jax.numpy`` over a nested dict of tensor names (inferred, no network:
``configs/lfm2-24b-a2b-ep8.json`` ``assumed.tensor_names``). Linear weights are
held ``(in, out)``; ``embed_tokens`` a row a token: the vocabulary is what is
sliced over chips. ``conv.conv.weight`` is held as published, ``(channels, 1,
taps)``.

Departures from the published checkpoint, all of them:

- the experts held here are three stacked leaves a layer,
  ``feed_forward.experts.{w1,w2,w3}`` of shape ``(held, in, out)``, where the
  checkpoint has three matrices an expert. ``num_experts`` counts the experts
  held: experts ``[rank * num_experts, (rank + 1) * num_experts)`` of the router's
  ``num_routed_experts``, ``rank`` being ``layer_share_rank``. The router keeps its
  published width, its bias and its experts per token, the renormalisation stays
  over all of a token's experts, and what the absent experts would add is left
  out; no code stands in for the absent chips;
- ``vocab_size`` counts the rows of the vocabulary held (ids ``[0, vocab_size)``);
- the router ``feed_forward.gate.weight`` and ``feed_forward.expert_bias`` are
  float32 beside bf16 leaves;
- seeded weights replace the published initialisation: ``0.02 * normal``, every
  gain ``1 + 0.1 * normal`` (a norm left out, or a gain read as 1, then shows),
  ``expert_bias`` ``0.1 * normal`` (wide enough to change some choices),
  ``conv.conv.weight`` ``0.3 * normal`` (near ``U(-1/sqrt 3, 1/sqrt 3)`` of three
  taps: at ``0.02`` the operator would add a fifteenth of what it adds and a tap
  left out would not show);
- left out: the rule that moves ``expert_bias`` (no key sizes it) and any
  balance loss.

What an architecture gives the harness (``perfbench/README.md``), and all it
gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``, ``token_range``,
``TINY``, ``PUBLISHED``. ``causal_conv``, ``short_conv``, ``softmax_attention``,
``attention``, ``route``, ``expert_layer``, ``layer``, ``head_nll`` and
``token_nll`` are what ``loss_fn`` is made of, named so that the tests can hold
each to the reference (``models/reference/lfm2_moe.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# What is the same in every architecture here that has softmax attention over sorted experts, from the one that has
# it last: RMSNorm with float32 statistics, one key-value head's attention, the two gathers whose way back is a
# gather, and a block of the head's loss.
from perfbench.models.nemotron_h import _attend, _block_nll, _permute, _rms_norm, _rows_of

PARAM_DTYPE = jnp.bfloat16

# The catalog row's ``config``, every key: what no configuration may change
# unless its ``reduced`` lists the key (perfbench/tests/test_contract.py).
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": ["full_attention" if i % 4 == 2 else "conv" for i in range(40)],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "vocab_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_routed_experts": 16, "num_experts": 2,
    "num_experts_per_tok": 3,
}

# Queries a block of the attention; a block's key-value heads go one at a time, each with its 4 query heads'
# float32 scores over up to 8192 keys: 34 MB a sequence. On the v5e a score of this attention, forward and backward,
# costs 0.021 ns where a head's scores of one block are 67 MB (256 queries at micro-batch 2), 0.124 ns at 134 MB and
# 0.252 ns at 268 MB (512 and 1024 queries: the compiler's score fusions fall off a cliff there; my chip run, PR 50).
QUERY_BLOCK = 256
HEAD_BLOCK = 1024  # positions a block of the head and its loss


def is_attention(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "full_attention"


def is_sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def head_width(cfg: dict) -> int:
    """The family states no ``head_dim``: a head is ``hidden_size /
    num_attention_heads`` wide."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def held_experts(cfg: dict):
    """The range of the router's experts whose weights live here."""
    lo = cfg.get("layer_share_rank", 0) * cfg["num_experts"]
    return lo, lo + cfg["num_experts"]


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the tensor names."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], head_width(cfg)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held, routed = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["num_routed_experts"]

    def leaf(*shape, dtype=PARAM_DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weight(*shape, dtype=PARAM_DTYPE):
        return {"weight": leaf(*shape, dtype=dtype)}

    operators = {
        "conv": {"conv": {"in_proj": weight(d, 3 * d), "conv": weight(d, 1, cfg["conv_L_cache"]), "out_proj": weight(d, d)}},
        "full_attention": {"self_attn": {
            "q_proj": weight(d, heads * hd), "k_proj": weight(d, kv_heads * hd), "v_proj": weight(d, kv_heads * hd),
            "out_proj": weight(heads * hd, d), "q_layernorm": weight(hd), "k_layernorm": weight(hd),
        }},
    }
    width = cfg["intermediate_size"]
    dense = {"w1": weight(d, width), "w2": weight(width, d), "w3": weight(d, width)}
    sparse = {
        "gate": weight(d, routed, dtype=jnp.float32), "expert_bias": leaf(routed, dtype=jnp.float32),
        "experts": {"w1": leaf(held, d, f), "w2": leaf(held, f, d), "w3": leaf(held, d, f)},
    }

    def one_layer(i):
        return dict(
            operators[cfg["layer_types"][i]], operator_norm=weight(d), ffn_norm=weight(d),
            feed_forward=sparse if is_sparse(cfg, i) else dense,
        )

    return {
        "model": {
            "embed_tokens": weight(v, d),
            "layers": {str(i): one_layer(i) for i in range(cfg["num_hidden_layers"])},
            "embedding_norm": weight(d),
        },
    }


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key (the module's docstring has the
    rule and why)."""
    draw = jax.random.normal(key, leaf.shape, jnp.float32)
    if path.endswith("norm/weight"):  # operator_norm, ffn_norm, embedding_norm, q_layernorm, k_layernorm
        return (1.0 + 0.1 * draw).astype(leaf.dtype)
    if path.endswith("expert_bias"):
        return (0.1 * draw).astype(leaf.dtype)
    return ((0.3 if path.endswith("conv/conv/weight") else 0.02) * draw).astype(leaf.dtype)


def param_spec(path: str) -> P:
    """Expert parallelism over a layout whose mesh names ``ep``: the expert
    stacks over their expert dimension, the embedding (which is the head too)
    over the vocabulary; everything else of a layer whole on each chip."""
    if "/experts/" in path or "embed_tokens" in path:
        return P("ep")
    return P()


def token_range(cfg: dict) -> int:
    """Token ids of a batch are drawn from ``[0, token_range)``: the slice of
    the vocabulary held here."""
    return cfg["vocab_size"]


def _rotary(x, theta):
    """x: (B, S, H, D): dim ``c`` of the first half and dim ``c + D / 2`` of
    the second are a pair, turned by the position's angle (rotate-half)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# The gated short convolution
# ---------------------------------------------------------------------------

def causal_conv(u, taps):
    """Depth-wise causal convolution, no bias, no activation. u: (B, S,
    channels); taps: (channels, 1, L) as published, the last tap the
    position's own: ``v_t = sum_k taps[:, 0, k] u_(t - L + 1 + k)``."""
    width, s = taps.shape[-1], u.shape[1]
    padded = jnp.pad(u, [(0, 0), (width - 1, 0), (0, 0)])
    return sum(padded[:, k:k + s] * taps[:, 0, k] for k in range(width))


def short_conv(cfg, p, x, conv: bool = True, b_gate: bool = True, c_gate: bool = True, reverse_taps: bool = False,
               rotate: bool = False):
    """The ``conv`` operator of one layer. The tests' controls: ``conv`` False
    leaves the convolution out (``v = u``), ``b_gate`` / ``c_gate`` False drop a
    gate, ``reverse_taps`` reads the taps last first (the first tap the
    position's own), ``rotate`` turns ``B`` and ``C`` as an attention layer's
    heads by their positions' angles, which a ``conv`` layer never does."""
    gate_b, gate_c, u = jnp.split(x @ p["in_proj"]["weight"], 3, axis=-1)
    if rotate:
        heads = x.shape[:2] + (cfg["num_attention_heads"], head_width(cfg))
        theta = cfg["rope_parameters"]["rope_theta"]
        gate_b, gate_c = (_rotary(g.reshape(heads), theta).reshape(g.shape) for g in (gate_b, gate_c))
    if b_gate:
        u = gate_b * u
    if conv:
        taps = p["conv"]["weight"]
        u = causal_conv(u, taps[..., ::-1] if reverse_taps else taps)
    if c_gate:
        u = gate_c * u
    return u @ p["out_proj"]["weight"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def softmax_attention(q, k, v, scale):
    """Grouped-query causal softmax attention in blocks. q: (B, S, G, R, D),
    ``R`` query heads a key-value head; k, v: (B, S, G, D). A block of
    ``QUERY_BLOCK`` queries reads the keys up to its last position and none
    after. The key-value heads of a block go one at a time (``jax.lax.map``)
    under ``jax.checkpoint``, so one head's float32 scores are all that is
    live."""
    s = q.shape[1]
    one_head = jax.checkpoint(_attend, static_argnums=(4,))
    out = []
    for start in range(0, s, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, s)
        gap = (start + jnp.arange(end - start))[:, None] - jnp.arange(end)[None, :]
        heads = (jnp.moveaxis(x, 2, 0) for x in (q[:, start:end], k[:, :end], v[:, :end]))
        block = jax.lax.map(lambda qkv, gap=gap: one_head(*qkv, gap, scale), tuple(heads))
        out.append(jnp.moveaxis(block, 0, 2))
    return jnp.concatenate(out, axis=1)


def attention(cfg, p, x, rotate: bool = True, qk_norm: bool = True, norm_first: bool = True):
    """The ``full_attention`` operator of one layer. The tests' controls:
    ``rotate`` False turns nothing, ``qk_norm`` False leaves the two per-head
    norms out, ``norm_first`` False norms after the rotation."""
    b, s, _ = x.shape
    heads, kv_heads, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_width(cfg), cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, hd)
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv_heads, hd)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv_heads, hd)

    def normed(q, k):
        return _rms_norm(q, p["q_layernorm"]["weight"], eps), _rms_norm(k, p["k_layernorm"]["weight"], eps)

    if qk_norm and norm_first:
        q, k = normed(q, k)
    if rotate:
        q, k = _rotary(q, theta), _rotary(k, theta)
    if qk_norm and not norm_first:
        q, k = normed(q, k)
    q = q.reshape(b, s, kv_heads, heads // kv_heads, hd)
    return softmax_attention(q, k, v, hd ** -0.5).reshape(b, s, heads * hd) @ p["out_proj"]["weight"]


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def route(cfg, scores, bias, bias_in_choice: bool = True, bias_in_weights: bool = False, norm_topk_prob=None, chosen=None):
    """``(weights, chosen)`` of every token, each ``(tokens, num_experts_per_tok)``.
    The choice is the top of ``scores + bias`` over all experts (no group
    limit). The weights are the scores themselves of the chosen, divided by
    their sum + 1e-6 where ``norm_topk_prob`` says so, times
    ``routed_scaling_factor``. The tests' controls: ``bias_in_choice=False``
    chooses on the scores alone, ``bias_in_weights`` weighs with ``scores +
    bias``, ``norm_topk_prob`` overrides the configuration's, ``chosen`` is
    the choice itself (another computation's, so that two that round a score
    apart can be compared on the same experts)."""
    if chosen is None:
        _, chosen = jax.lax.top_k(scores + bias if bias_in_choice else scores, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores + bias if bias_in_weights else scores, chosen, axis=-1)
    if cfg["norm_topk_prob"] if norm_topk_prob is None else norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return weights * cfg["routed_scaling_factor"], chosen


def expert_layer(cfg, p, x, **controls):
    """Sigmoid router over all ``num_routed_experts`` in float32, the choice
    steered by the bias, and the part of the result that the experts held
    here give, with no token dropped: every (token, expert) pair is sorted by
    expert, the pairs of absent experts last, and the three held stacks are
    applied by ``jax.lax.ragged_dot`` over the sorted rows. There is no
    shared expert: a token none of whose experts live here gets nothing."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    tokens, top = x.shape[0], cfg["num_experts_per_tok"]
    lo, hi = held_experts(cfg)
    logits = jnp.dot(x.astype(jnp.float32), p["gate"]["weight"], precision=jax.lax.Precision.HIGHEST)
    bias = jax.lax.stop_gradient(p["expert_bias"])  # a buffer: a rule of its own moves it, no gradient
    weights, chosen = route(cfg, jax.nn.sigmoid(logits), bias, **controls)
    chosen = chosen.reshape(-1)
    held = (chosen >= lo) & (chosen < hi)
    slot = jnp.where(held, chosen - lo, hi - lo)
    order = jnp.argsort(slot, stable=True)
    inverse = jnp.argsort(order)
    group_sizes = jnp.bincount(slot, length=hi - lo + 1)[: hi - lo].astype(jnp.int32)
    rows = _rows_of(x, order, inverse, top)
    # The rows past the held pairs belong to no group: what a ragged product
    # leaves there is not defined on every backend (NaN on the v5e), so they
    # are zeroed going in and coming out (and so are their cotangents on the
    # way back).
    mine = (jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None]

    def grouped(lhs, stack):
        return jnp.where(mine, jax.lax.ragged_dot(jnp.where(mine, lhs, 0), stack, group_sizes), 0)

    experts = p["experts"]
    hidden = jax.nn.silu(grouped(rows, experts["w1"])) * grouped(rows, experts["w3"])
    rows = _permute(grouped(hidden, experts["w2"]), inverse, order).reshape(tokens, top, -1)
    scale = jnp.where(held.reshape(tokens, top), weights, 0.0).astype(rows.dtype)
    return (rows * scale[..., None]).sum(1).reshape(shape)


def _gated_mlp(x, p):
    return (jax.nn.silu(x @ p["w1"]["weight"]) * (x @ p["w3"]["weight"])) @ p["w2"]["weight"]


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def layer(cfg, i, p, x, operator_norm: bool = True):
    """Layer ``i``: ``h = x + operator(operator_norm(x))``, ``y = h +
    feed_forward(ffn_norm(h))`` (``operator_norm`` False leaves the first norm
    out: the tests' control)."""
    eps = cfg["norm_eps"]
    h = _rms_norm(x, p["operator_norm"]["weight"], eps) if operator_norm else x
    if is_attention(cfg, i):
        with jax.named_scope("lf.attn"):
            x = x + attention(cfg, p["self_attn"], h)
    else:
        with jax.named_scope("lf.conv"):
            x = x + short_conv(cfg, p["conv"], h)
    h = _rms_norm(x, p["ffn_norm"]["weight"], eps)
    if is_sparse(cfg, i):
        with jax.named_scope("lf.moe"):
            return x + expert_layer(cfg, p["feed_forward"], h)
    with jax.named_scope("lf.dense"):
        return x + _gated_mlp(h, p["feed_forward"])


def head_nll(cfg, params, x, targets, tied: bool = True):
    """``embedding_norm``, then the head, **which is the embedding matrix**,
    and the loss of every position, in blocks of ``HEAD_BLOCK`` positions
    (``tied`` False reads the table's rows in reverse, another matrix of the
    same draw: the tests' control)."""
    table = params["model"]["embed_tokens"]["weight"]
    x = _rms_norm(x, params["model"]["embedding_norm"]["weight"], cfg["norm_eps"])
    head, nll_of = table if tied else table[::-1], jax.checkpoint(_block_nll)
    nll = [nll_of(x[:, s:s + HEAD_BLOCK], head, targets[:, s:s + HEAD_BLOCK]) for s in range(0, x.shape[1], HEAD_BLOCK)]
    return jnp.concatenate(nll, axis=1)


def token_nll(cfg, params, inputs, targets):
    """The loss of every position (batch, sequence): ``targets`` under the
    model's next-token distribution after ``inputs``, over the slice of the
    vocabulary held. Every layer under its own ``jax.checkpoint``."""
    x = params["model"]["embed_tokens"]["weight"][inputs]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg, i))(params["model"]["layers"][str(i)], x)
    with jax.named_scope("lf.head"):
        return head_nll(cfg, params, x, targets)


def loss_fn(cfg, params, tokens):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:]))
