"""DeepSeek-V2 as deepseek-ai/DeepSeek-V2 publishes it (``config.json``,
``model_type`` ``deepseek_v2``, ``DeepseekV2ForCausalLM``), told which
experts, which heads and which rows of the vocabulary it holds: one chip's
share of the chips that divide each layer among them.

Every layer is multi-head latent attention: ``c_q = RMSNorm(x W_qa)``,
``q = c_q W_qb`` (per head ``qk_nope_head_dim`` dims without position and
``qk_rope_head_dim`` rotary ones); ``[c_kv, k_pe] = x W_kva``,
``[k_nope, v] = RMSNorm(c_kv) W_kvb`` per head, ``k_pe`` one rotary key that
all heads share; scores ``(q_nope k_nope + q_pe k_pe) / sqrt(192) * m^2`` with
YaRN's blended frequencies and ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
The first ``first_k_dense_replace`` layers have a dense SwiGLU MLP, the others
the sparse mixture: softmax scores over all ``num_routed_experts``, the
``topk_group`` groups of ``n_group`` with the largest maxima, the top
``num_experts_per_tok`` inside them, weights not renormalised and times
``routed_scaling_factor``; plus ``n_shared_experts`` shared experts as one
SwiGLU of their summed width, added to every token. Plain ``jax.numpy`` over
a nested dict of the published tensor names. Linear weights are held
``(in, out)``, but for those whose outputs are what is divided over chips:
``lm_head`` (the vocabulary) and ``q_b_proj`` and ``kv_b_proj`` (the heads)
are held as published, a row an output, so that the divided dimension leads,
as it does in the embedding, in ``o_proj`` and in the expert stacks.

Departures from the published checkpoint, all of them:

- the experts held here are three stacked leaves a layer,
  ``mlp.experts.{gate_proj,up_proj,down_proj}`` of shape ``(held, in, out)``,
  as JAX trainers hold them, where the checkpoint has three matrices an
  expert. ``n_routed_experts`` counts the experts held: experts
  ``[rank * n_routed_experts, (rank + 1) * n_routed_experts)`` of the router's
  ``num_routed_experts``, ``rank`` being ``layer_share_rank``. The router keeps
  its published width, its groups and its experts per token, and what the
  absent experts would add is left out;
- ``num_attention_heads`` (and ``num_key_value_heads``, the same number in
  this family) counts the heads held: ``q_b_proj``, ``kv_b_proj`` and ``o_proj``
  have the held heads' rows, and the attention returns the
  part of its result that these heads give through ``o_proj``. The latents
  (``q_a_proj``, ``kv_a_proj_with_mqa``, their norms) are whole on every chip.
  No code stands in for the absent chips;
- ``vocab_size`` counts the rows of the vocabulary held (ids ``[0, vocab_size)``):
  embedding, head, logits and loss are over that slice;
- the router ``mlp.gate.weight`` is float32 beside bf16 leaves (the checkpoint
  is bf16 throughout; the published code computes the router in float32);
- left out: the balance losses (``seq_aux`` says which kind; no key of the
  config sizes their coefficients).

What an architecture gives the harness (``perfbench/README.md``), and all it
gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``,
``token_range``, ``TINY``, ``PUBLISHED``. ``attention``, ``route``,
``expert_layer`` and ``token_nll`` are what ``loss_fn`` is made of, named so
that the tests can hold each to the reference
(``models/reference/deepseek_v2.py``).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PARAM_DTYPE = jnp.bfloat16

# The catalog row's ``config``, every key: what no configuration may change
# unless its ``reduced`` lists the key (perfbench/tests/test_contract.py).
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 12288, "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 128,
    "num_experts_per_tok": 6, "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn",
    },
    "rope_theta": 10000, "routed_scaling_factor": 16, "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400,
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3, "vocab_size": 64,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "num_attention_heads": 2, "num_key_value_heads": 2, "moe_intermediate_size": 32,
    "num_routed_experts": 16, "n_routed_experts": 2, "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
}

QUERY_BLOCK = 1024  # queries a block of the attention
HEAD_BLOCK = 1024  # positions a block of the head and its loss


def is_sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0


def held_experts(cfg: dict):
    """The range of the router's experts whose weights live here."""
    lo = cfg.get("layer_share_rank", 0) * cfg["n_routed_experts"]
    return lo, lo + cfg["n_routed_experts"]


def held_heads(cfg: dict):
    """The range of the model's heads whose columns and rows live here."""
    lo = cfg.get("layer_share_rank", 0) * cfg["num_attention_heads"]
    return lo, lo + cfg["num_attention_heads"]


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the published names."""
    d, v, heads = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]

    def leaf(*shape, dtype=PARAM_DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weight(*shape, dtype=PARAM_DTYPE):
        return {"weight": leaf(*shape, dtype=dtype)}

    def gated_mlp(width):
        return {"gate_proj": weight(d, width), "up_proj": weight(d, width), "down_proj": weight(width, d)}

    self_attn = {
        "q_a_proj": weight(d, rq), "q_a_layernorm": weight(rq), "q_b_proj": weight(heads * (nope + rope), rq),
        "kv_a_proj_with_mqa": weight(d, rkv + rope), "kv_a_layernorm": weight(rkv),
        "kv_b_proj": weight(heads * (nope + vd), rkv), "o_proj": weight(heads * vd, d),
    }
    sparse = {
        "gate": weight(d, cfg["num_routed_experts"], dtype=jnp.float32),
        "experts": {"gate_proj": leaf(held, d, f), "up_proj": leaf(held, d, f), "down_proj": leaf(held, f, d)},
        "shared_experts": gated_mlp(f * cfg["n_shared_experts"]),
    }

    def layer(i):
        mlp = sparse if is_sparse(cfg, i) else gated_mlp(cfg["intermediate_size"])
        return {"self_attn": self_attn, "input_layernorm": weight(d), "post_attention_layernorm": weight(d), "mlp": mlp}

    return {
        "model": {
            "embed_tokens": weight(v, d),
            "layers": {str(i): layer(i) for i in range(cfg["num_hidden_layers"])},
            "norm": weight(d),
        },
        "lm_head": weight(v, d),
    }


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key: 1 for the norms, ``0.02 *
    normal`` otherwise."""
    if path.endswith(("layernorm/weight", "model/norm/weight")):
        return jnp.ones(leaf.shape, leaf.dtype)
    return (0.02 * jax.random.normal(key, leaf.shape, jnp.float32)).astype(leaf.dtype)


def param_spec(path: str) -> P:
    """A layer divided over a layout whose mesh names ``ep`` and ``tp``: the
    expert stacks over their expert dimension and the embedding and head over
    the vocabulary (``ep``), the heads' rows of ``q_b_proj``, ``kv_b_proj``
    and ``o_proj`` over ``tp``; everything else of a layer whole on each
    chip. Always the leading dimension."""
    if "/experts/" in path or "embed_tokens" in path or "lm_head" in path:
        return P("ep")
    if "q_b_proj" in path or "kv_b_proj" in path or "o_proj" in path:
        return P("tp")
    return P()


def token_range(cfg: dict) -> int:
    """Token ids of a batch are drawn from ``[0, token_range)``: the slice of
    the vocabulary held here."""
    return cfg["vocab_size"]


# ---------------------------------------------------------------------------
# Norm, rotary with YaRN's frequencies
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    """``w * x / sqrt(mean(x^2) + eps)``, the statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True) + eps)
    return w * y.astype(x.dtype)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict):
    """The rotary frequencies (``qk_rope_head_dim / 2`` of them, float64):
    each a blend of the plain frequency and the one ``factor`` times slower,
    by a linear ramp between the dims that turn ``beta_fast`` and ``beta_slow``
    times within the original context. Without ``rope_scaling``: plain."""
    dim, base, scaling = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return plain

    def correction_dim(rotations):
        original = scaling["original_max_position_embeddings"]
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(cfg: dict) -> float:
    """``1 / sqrt(q head dim)``, times YaRN's ``m^2`` where the config scales."""
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg["rope_scaling"]
    if scaling and scaling.get("mscale_all_dim", 0):
        scale *= _yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def yarn_cos_sin(cfg: dict, seq: int):
    """cos and sin of every position's angles, each (seq, rope dims / 2). They
    carry ``mscale``'s ratio to ``mscale_all_dim``'s (1 where the two are
    equal, as published)."""
    scaling = cfg["rope_scaling"]
    ang = np.arange(seq, dtype=np.float64)[:, None] * yarn_inv_freq(cfg)[None, :]
    m = 1.0
    if scaling:
        m = _yarn_mscale(scaling["factor"], scaling["mscale"]) / _yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    return jnp.asarray(np.cos(ang) * m, jnp.float32), jnp.asarray(np.sin(ang) * m, jnp.float32)


def _rotary(x, cos, sin):
    """x: (B, S, ..., rope dims), as published: the pairs ``(x0, x1), (x2, x3)``
    are first sorted into halves ``[x0, x2, ...; x1, x3, ...]``, then the halves
    are rotated by the position's angles."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2)).astype(jnp.float32)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Multi-head latent attention over the heads held
# ---------------------------------------------------------------------------

def _attention_block(q_nope, q_pe, k_nope, k_pe, v, start, scale):
    """Causal softmax attention of one block of queries (positions from
    ``start``) over the keys up to the block's end. q_nope: (B, Q, H, nope);
    q_pe: (B, Q, H, rope); k_nope: (B, S, H, nope); k_pe: (B, S, rope), the
    one rotary key of all heads; v: (B, S, H, vd)."""
    scores = jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope, preferred_element_type=jnp.float32)
    scores = scores + jnp.einsum("bqhr,bsr->bhqs", q_pe, k_pe, preferred_element_type=jnp.float32)
    visible = (start + jnp.arange(q_nope.shape[1]))[:, None] >= jnp.arange(k_nope.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(visible, scores * scale, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


def attention(cfg, p, x, rotate_key: bool = True, norm_kv: bool = True):
    """The part of the attention's result that the heads held give through
    their rows of ``o_proj``. ``rotate_key=False`` leaves ``k_pe`` unrotated,
    ``norm_kv=False`` leaves ``kv_a_layernorm`` out (the tests' controls)."""
    b, s, _ = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd, rkv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    cos, sin = yarn_cos_sin(cfg, s)
    c_q = _rms_norm(x @ p["q_a_proj"]["weight"], p["q_a_layernorm"]["weight"], eps)
    q = (c_q @ p["q_b_proj"]["weight"].T).reshape(b, s, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], _rotary(q[..., nope:], cos, sin)
    kv_a = x @ p["kv_a_proj_with_mqa"]["weight"]
    if not rotate_key:  # angle 0 at every position
        cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
    c_kv, k_pe = kv_a[..., :rkv], _rotary(kv_a[..., rkv:], cos, sin)
    if norm_kv:
        c_kv = _rms_norm(c_kv, p["kv_a_layernorm"]["weight"], eps)
    kv = (c_kv @ p["kv_b_proj"]["weight"].T).reshape(b, s, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    block = jax.checkpoint(_attention_block, static_argnums=(5, 6))
    scale = softmax_scale(cfg)
    out = [
        block(q_nope[:, start:start + QUERY_BLOCK], q_pe[:, start:start + QUERY_BLOCK],
              k_nope[:, :start + QUERY_BLOCK], k_pe[:, :start + QUERY_BLOCK], v[:, :start + QUERY_BLOCK], start, scale)
        for start in range(0, s, QUERY_BLOCK)
    ]
    return jnp.concatenate(out, axis=1).reshape(b, s, heads * vd) @ p["o_proj"]["weight"]


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def route(cfg, scores, group_limit: bool = True):
    """``(weights, chosen)`` of every token, each ``(tokens, num_experts_per_tok)``:
    of the ``n_group`` groups of experts the ``topk_group`` with the largest
    maxima are kept, and the top experts are taken inside them; the weights
    are the scores themselves, renormalised only where ``norm_topk_prob``
    says so, else times ``routed_scaling_factor``. ``group_limit=False`` is a
    plain top-k over all experts (the tests' control)."""
    tokens, experts = scores.shape
    if group_limit and cfg["topk_method"] == "group_limited_greedy":
        groups = cfg["n_group"]
        best = scores.reshape(tokens, groups, experts // groups).max(-1)
        _, kept = jax.lax.top_k(best, cfg["topk_group"])
        allowed = (kept[..., None] == jnp.arange(groups)).any(1)
        scores = jnp.where(jnp.repeat(allowed, experts // groups, axis=1), scores, 0.0)
    weights, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["num_experts_per_tok"] > 1 and cfg["norm_topk_prob"]:
        return weights / (weights.sum(-1, keepdims=True) + 1e-20), chosen
    return weights * cfg["routed_scaling_factor"], chosen


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


def expert_layer(cfg, p, x, shared: bool = True, group_limit: bool = True):
    """Router over all ``num_routed_experts`` in float32, group-limited top
    ``num_experts_per_tok``, and the part of the result that the experts held
    here give, with no token dropped: every (token, expert) pair is sorted by
    expert, the pairs of absent experts last, and the held stacks are applied
    by ``jax.lax.ragged_dot`` over the sorted rows. Plus the shared experts,
    which every chip computes alike (``shared`` False leaves them out: the
    share test counts them once)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    tokens, top = x.shape[0], cfg["num_experts_per_tok"]
    lo, hi = held_experts(cfg)
    logits = jnp.dot(x.astype(jnp.float32), p["gate"]["weight"], precision=jax.lax.Precision.HIGHEST)
    weights, chosen = route(cfg, jax.nn.softmax(logits, axis=-1), group_limit)
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

def _layer(cfg, sparse, p, x):
    eps = cfg["rms_norm_eps"]
    with jax.named_scope("dsv2.mla"):
        x = x + attention(cfg, p["self_attn"], _rms_norm(x, p["input_layernorm"]["weight"], eps))
    h = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    if sparse:
        with jax.named_scope("dsv2.moe"):
            return x + expert_layer(cfg, p["mlp"], h)
    with jax.named_scope("dsv2.dense"):
        return x + _gated_mlp(h, p["mlp"])


def _block_nll(x, head, targets):
    logits = jnp.einsum("bsd,vd->bsv", x, head, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def token_nll(cfg, params, inputs, targets):
    """The loss of every position (batch, sequence): ``targets`` under the
    model's next-token distribution after ``inputs``, over the slice of the
    vocabulary held. Every layer under ``jax.checkpoint``."""
    model = params["model"]
    x = model["embed_tokens"]["weight"][inputs]
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(functools.partial(_layer, cfg, is_sparse(cfg, i)))
        x = layer(model["layers"][str(i)], x)
    with jax.named_scope("dsv2.head"):
        x = _rms_norm(x, model["norm"]["weight"], cfg["rms_norm_eps"])
        block = jax.checkpoint(_block_nll)
        nll = [
            block(x[:, s:s + HEAD_BLOCK], params["lm_head"]["weight"], targets[:, s:s + HEAD_BLOCK])
            for s in range(0, x.shape[1], HEAD_BLOCK)
        ]
        return jnp.concatenate(nll, axis=1)


def loss_fn(cfg, params, tokens):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:]))
