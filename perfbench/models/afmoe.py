"""Trinity-Large-Preview as arcee-ai/Trinity-Large-Preview publishes it
(``config.json``, ``model_type`` ``afmoe``), told which experts and which rows
of the vocabulary it holds: one chip's share of an expert-parallel job.

Layer ``i`` is what ``layer_types[i]`` says: ``sliding_attention`` (three of
four) or ``full_attention`` (``(i + 1) % global_attn_every_n_layers == 0``).
The first ``num_dense_layers`` layers have a dense SwiGLU MLP, the others the
sparse mixture.

Attention, every layer: ``q = x W_q`` (``num_attention_heads`` x ``head_dim``),
``k = x W_k``, ``v = x W_v`` (``num_key_value_heads`` x ``head_dim``), ``g = x
W_g`` (a channel of the heads' output each); RMSNorm of each head's query and
of each head's key over ``head_dim`` (gains ``q_norm``, ``k_norm``). **A
window layer** turns all ``head_dim`` dims of ``q`` and ``k`` by their
position's angle (rotate-half, ``rope_theta``, no scaling) and lets query ``i``
see key ``j`` iff ``0 <= i - j < sliding_window``; **a full layer** turns
nothing and sees every key before it. Grouped-query softmax at
``head_dim^-1/2``; ``out = (attn * sigmoid(g)) W_o``.

A layer is a sandwich of four RMSNorms: ``h = x + post_attention_layernorm(
attn(input_layernorm(x)))``, ``y = h + post_mlp_layernorm(mlp(
pre_mlp_layernorm(h)))``.

The mixture (``score_func`` sigmoid): ``s = sigmoid(x W_r)`` in float32 over
all ``num_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b``
are chosen, ``b`` the float32 buffer ``mlp.expert_bias`` (no gradient reaches
it; ``n_group`` 1: no group limit); the weights are ``s`` (not ``s + b``) of
the chosen, normalised to 1 (``route_norm``), times ``route_scale``; plus one
shared SwiGLU added to every token.

The model: ``embed_tokens(ids) * sqrt(hidden_size)`` (``mup_enabled``), the
layers, a final RMSNorm, an untied ``lm_head``.

Plain ``jax.numpy`` over a nested dict of tensor names (inferred, no network:
``configs/trinity-large-preview-ep32.json`` ``assumed.tensor_names``). Linear
weights are held ``(in, out)``, but for ``lm_head``, held a row a token like
``embed_tokens``: the vocabulary is what is sliced over chips.

Departures from the published checkpoint, all of them:

- the experts held here are three stacked leaves a layer,
  ``mlp.experts.{gate_proj,up_proj,down_proj}`` of shape ``(held, in, out)``,
  where the checkpoint has three matrices an expert. ``num_experts`` counts the
  experts held: experts ``[rank * num_experts, (rank + 1) * num_experts)`` of
  the router's ``num_routed_experts``, ``rank`` being ``layer_share_rank``. The
  router keeps its published width, its bias and its experts per token, the
  renormalisation stays over all of a token's experts, and what the absent
  experts would add is left out; no code stands in for the absent chips;
- ``vocab_size`` counts the rows of the vocabulary held (ids ``[0, vocab_size)``);
- the router ``mlp.router.gate.weight`` and ``mlp.expert_bias`` are float32
  beside bf16 leaves;
- seeded weights replace the depth-scaled initialisation: ``0.02 * normal``,
  the norms' gains ``1 + 0.1 * normal`` (so that a norm left out shows),
  ``expert_bias`` ``0.1 * normal``, wide enough to change some choices (the
  checkpoint's are trained);
- left out: the rule that moves ``expert_bias`` (``load_balance_coeff`` sizes
  it, nothing gives its form) and any balance loss.

What an architecture gives the harness (``perfbench/README.md``), and all it
gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``,
``token_range``, ``TINY``, ``PUBLISHED``. ``softmax_attention``, ``attention``,
``route``, ``expert_layer``, ``layer``, ``embed`` and ``token_nll`` are what
``loss_fn`` is made of, named so that the tests can hold each to the reference
(``models/reference/afmoe.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PARAM_DTYPE = jnp.bfloat16

# The catalog row's ``config``, every key: what no configuration may change
# unless its ``reduced`` lists the key (perfbench/tests/test_contract.py).
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 3072,
    "intermediate_size": 12288, "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096, "tie_word_embeddings": False, "topk_group": 1,
    "use_grouped_mm": True, "vocab_size": 200192,
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "vocab_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "sliding_window": 12,
    "num_routed_experts": 16, "num_experts": 2, "num_experts_per_tok": 3,
}

QUERY_BLOCK = 1024  # queries a block of the attention; a block's key-value heads go one at a time
# Positions a block of the head and its loss. The step's temporaries are the compiler's schedule more than
# the arithmetic (perfbench/rehearse.py for the v5e, PR 42): 2.87 GB at 1024, 3.07 at 2048, 5.73 at 512, and
# 3.59 with the blocks one after another under jax.lax.map: measure before changing it.
HEAD_BLOCK = 1024


def is_full(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "full_attention"


def is_sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def held_experts(cfg: dict):
    """The range of the router's experts whose weights live here."""
    lo = cfg.get("layer_share_rank", 0) * cfg["num_experts"]
    return lo, lo + cfg["num_experts"]


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the tensor names."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]

    def leaf(*shape, dtype=PARAM_DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weight(*shape, dtype=PARAM_DTYPE):
        return {"weight": leaf(*shape, dtype=dtype)}

    def gated_mlp(width):
        return {"gate_proj": weight(d, width), "up_proj": weight(d, width), "down_proj": weight(width, d)}

    self_attn = {
        "q_proj": weight(d, heads * hd), "k_proj": weight(d, kv_heads * hd), "v_proj": weight(d, kv_heads * hd),
        "gate_proj": weight(d, heads * hd), "o_proj": weight(heads * hd, d),
        "q_norm": weight(hd), "k_norm": weight(hd),
    }
    sparse = {
        "router": {"gate": weight(d, cfg["num_routed_experts"], dtype=jnp.float32)},
        "expert_bias": leaf(cfg["num_routed_experts"], dtype=jnp.float32),
        "experts": {"gate_proj": leaf(held, d, f), "up_proj": leaf(held, d, f), "down_proj": leaf(held, f, d)},
        "shared_experts": gated_mlp(f * cfg["num_shared_experts"]),
    }

    def one_layer(i):
        return {
            "self_attn": self_attn,
            "input_layernorm": weight(d), "post_attention_layernorm": weight(d),
            "pre_mlp_layernorm": weight(d), "post_mlp_layernorm": weight(d),
            "mlp": sparse if is_sparse(cfg, i) else gated_mlp(cfg["intermediate_size"]),
        }

    return {
        "model": {
            "embed_tokens": weight(v, d),
            "layers": {str(i): one_layer(i) for i in range(cfg["num_hidden_layers"])},
            "norm": weight(d),
        },
        "lm_head": weight(v, d),
    }


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key: ``1 + 0.1 * normal`` for the
    norms' gains (a norm left out, or a gain read as 1, then shows),
    ``expert_bias = 0.1 * normal``, ``0.02 * normal`` otherwise."""
    draw = jax.random.normal(key, leaf.shape, jnp.float32)
    if path.endswith("norm/weight"):
        return (1.0 + 0.1 * draw).astype(leaf.dtype)
    return ((0.1 if path.endswith("expert_bias") else 0.02) * draw).astype(leaf.dtype)


def param_spec(path: str) -> P:
    """Expert parallelism over a layout whose mesh names ``ep``: the expert
    stacks over their expert dimension, embedding and head over the
    vocabulary; everything else of a layer whole on each chip."""
    if "/experts/" in path or "embed_tokens" in path or "lm_head" in path:
        return P("ep")
    return P()


def token_range(cfg: dict) -> int:
    """Token ids of a batch are drawn from ``[0, token_range)``: the slice of
    the vocabulary held here."""
    return cfg["vocab_size"]


# ---------------------------------------------------------------------------
# Norms, rotary
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    """``w * x / sqrt(mean(x^2) + eps)``, the statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True) + eps)
    return w * y.astype(x.dtype)


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
# Attention
# ---------------------------------------------------------------------------

def _attend(q, k, v, gap, window, scale):
    """One key-value head: its queries (B, Q, R, D) over its keys and values
    (B, K, D); ``gap[i, j]`` is query ``i``'s position less key ``j``'s."""
    scores = jnp.einsum("bqrd,bkd->brqk", q, k, preferred_element_type=jnp.float32)
    visible = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    probs = jax.nn.softmax(jnp.where(visible, scores * scale, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("brqk,bkd->bqrd", probs, v)


def softmax_attention(q, k, v, window, scale):
    """Grouped-query causal softmax attention in blocks. q: (B, S, G, R, D),
    ``R`` query heads a key-value head; k, v: (B, S, G, D). Query ``i`` sees
    key ``j`` iff ``0 <= i - j`` and, where ``window`` is a number, ``i - j <
    window``. A block of ``QUERY_BLOCK`` queries reads only the keys some
    query of it can see, from the block's first position less ``window - 1``
    (or from 0) to its last: a span of at most ``window - 1 + QUERY_BLOCK``
    keys, masked at both edges; what lies before it is never read, in the
    forward or, under ``jax.checkpoint``, on the way back. The key-value heads
    of a block go one at a time (``jax.lax.map``), so one head's float32
    scores are all that is live."""
    s = q.shape[1]
    one_head = jax.checkpoint(_attend, static_argnums=(4, 5))
    out = []
    for start in range(0, s, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, s)
        first = 0 if window is None else max(0, start - window + 1)
        gap = (start + jnp.arange(end - start))[:, None] - (first + jnp.arange(end - first))[None, :]
        heads = (jnp.moveaxis(x, 2, 0) for x in (q[:, start:end], k[:, first:end], v[:, first:end]))
        block = jax.lax.map(lambda qkv, gap=gap: one_head(*qkv, gap, window, scale), tuple(heads))
        out.append(jnp.moveaxis(block, 0, 2))
    return jnp.concatenate(out, axis=1)


def attention(cfg, p, x, full, window="sliding_window", rotate=None, output_gate=True, qk_norm=True):
    """The gated attention of one layer; ``full`` says which kind. The tests'
    controls: ``window`` (a window layer's: None lets it see every key before
    the query, a number is that window), ``rotate`` (True / False whatever the
    kind), ``output_gate`` False leaves ``sigmoid(x W_g)`` out, ``qk_norm``
    False the two per-head norms."""
    b, s, _ = x.shape
    heads, kv_heads, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, hd)
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv_heads, hd)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv_heads, hd)
    if qk_norm:
        q, k = _rms_norm(q, p["q_norm"]["weight"], eps), _rms_norm(k, p["k_norm"]["weight"], eps)
    if (not full) if rotate is None else rotate:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    if full:
        window = None
    elif window == "sliding_window":
        window = cfg["sliding_window"]
    q = q.reshape(b, s, kv_heads, heads // kv_heads, hd)
    out = softmax_attention(q, k, v, window, hd ** -0.5).reshape(b, s, heads * hd)
    if output_gate:
        out = out * jax.nn.sigmoid((x @ p["gate_proj"]["weight"]).astype(jnp.float32)).astype(out.dtype)
    return out @ p["o_proj"]["weight"]


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def route(cfg, scores, bias, bias_in_choice: bool = True, bias_in_weights: bool = False, route_norm=None):
    """``(weights, chosen)`` of every token, each ``(tokens, num_experts_per_tok)``.
    The choice is the top of ``scores + bias`` over all experts (``n_group``
    1: no group limit). The weights are the scores themselves of the chosen,
    normalised to 1 where ``route_norm`` says so, times ``route_scale``. The
    tests' controls: ``bias_in_choice=False`` chooses on the scores alone,
    ``bias_in_weights`` weighs with ``scores + bias``, ``route_norm`` overrides
    the configuration's."""
    _, chosen = jax.lax.top_k(scores + bias if bias_in_choice else scores, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores + bias if bias_in_weights else scores, chosen, axis=-1)
    if cfg["route_norm"] if route_norm is None else route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg["route_scale"], chosen


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of(x, order, inverse, top):
    """Row ``order[i] // top`` of ``x`` for each ``i``: every token's row once
    for each of its ``top`` experts, in the order ``order`` of the (token,
    expert) pairs. The way back is a gather by the inverse permutation and a
    sum over each token's ``top`` rows, not a scatter."""
    return x[order // top]


def _rows_of_fwd(x, order, inverse, top):
    return x[order // top], inverse


def _rows_of_bwd(top, inverse, g):
    return g[inverse].reshape(-1, top, g.shape[-1]).sum(1), None, None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@jax.custom_vjp
def _permute(x, order, inverse):
    """``x[order]`` for a permutation whose inverse is known, so that the way
    back is a gather too."""
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _gated_mlp(x, p):
    return (jax.nn.silu(x @ p["gate_proj"]["weight"]) * (x @ p["up_proj"]["weight"])) @ p["down_proj"]["weight"]


def expert_layer(cfg, p, x, shared: bool = True, **controls):
    """Sigmoid router over all ``num_routed_experts`` in float32, the choice
    steered by the bias, and the part of the result that the experts held
    here give, with no token dropped: every (token, expert) pair is sorted by
    expert, the pairs of absent experts last, and the held stacks are applied
    by ``jax.lax.ragged_dot`` over the sorted rows. Plus the shared expert,
    which every chip computes alike (``shared`` False leaves it out: the
    share test counts it once)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    tokens, top = x.shape[0], cfg["num_experts_per_tok"]
    lo, hi = held_experts(cfg)
    logits = jnp.dot(x.astype(jnp.float32), p["router"]["gate"]["weight"], precision=jax.lax.Precision.HIGHEST)
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
    hidden = jax.nn.silu(grouped(rows, experts["gate_proj"])) * grouped(rows, experts["up_proj"])
    rows = _permute(grouped(hidden, experts["down_proj"]), inverse, order).reshape(tokens, top, -1)
    scale = jnp.where(held.reshape(tokens, top), weights, 0.0).astype(rows.dtype)
    y = (rows * scale[..., None]).sum(1)
    if shared:
        y = y + _gated_mlp(x, p["shared_experts"])
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def attention_half(cfg, i, p, x, post_norms: bool = True):
    """``x + post_attention_layernorm(attn(input_layernorm(x)))`` of layer
    ``i`` (``post_norms`` False leaves the norm after it out: the tests'
    control)."""
    eps, full = cfg["rms_norm_eps"], is_full(cfg, i)
    with jax.named_scope("af.full" if full else "af.swa"):
        h = attention(cfg, p["self_attn"], _rms_norm(x, p["input_layernorm"]["weight"], eps), full)
    return x + (_rms_norm(h, p["post_attention_layernorm"]["weight"], eps) if post_norms else h)


def mlp_half(cfg, i, p, x, post_norms: bool = True):
    """``x + post_mlp_layernorm(mlp(pre_mlp_layernorm(x)))`` of layer ``i``."""
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, p["pre_mlp_layernorm"]["weight"], eps)
    if is_sparse(cfg, i):
        with jax.named_scope("af.moe"):
            h = expert_layer(cfg, p["mlp"], h)
    else:
        with jax.named_scope("af.dense"):
            h = _gated_mlp(h, p["mlp"])
    return x + (_rms_norm(h, p["post_mlp_layernorm"]["weight"], eps) if post_norms else h)


def layer(cfg, i, p, x, **controls):
    """Layer ``i``: the sandwich of four norms round its attention and its
    MLP, each half under its own ``jax.checkpoint``."""
    x = jax.checkpoint(functools.partial(attention_half, cfg, i, **controls))(p, x)
    return jax.checkpoint(functools.partial(mlp_half, cfg, i, **controls))(p, x)


def embed(cfg, params, inputs, scale: bool = True):
    """``embed_tokens(ids)``, times ``sqrt(hidden_size)`` under ``mup_enabled``
    (``scale`` False leaves it out: the tests' control)."""
    x = params["model"]["embed_tokens"]["weight"][inputs]
    if scale and cfg["mup_enabled"]:
        x = (x.astype(jnp.float32) * cfg["hidden_size"] ** 0.5).astype(x.dtype)
    return x


def _block_nll(x, head, targets):
    logits = jnp.einsum("bsd,vd->bsv", x, head, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def token_nll(cfg, params, inputs, targets):
    """The loss of every position (batch, sequence): ``targets`` under the
    model's next-token distribution after ``inputs``, over the slice of the
    vocabulary held."""
    x = embed(cfg, params, inputs)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(cfg, i, params["model"]["layers"][str(i)], x)
    with jax.named_scope("af.head"):
        x = _rms_norm(x, params["model"]["norm"]["weight"], cfg["rms_norm_eps"])
        block = jax.checkpoint(_block_nll)
        nll = [
            block(x[:, s:s + HEAD_BLOCK], params["lm_head"]["weight"], targets[:, s:s + HEAD_BLOCK])
            for s in range(0, x.shape[1], HEAD_BLOCK)
        ]
        return jnp.concatenate(nll, axis=1)


def loss_fn(cfg, params, tokens):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:]))
