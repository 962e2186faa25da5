"""Plain reference of DeepSeek-V2's forward pass and loss: ``jax.numpy``,
float32, matrix products at ``highest`` precision, no blocks, no sorting, no
rematerialisation. It imports nothing of ``perfbench``; the equations are
those of the published modelling code (``modeling_deepseek.py`` of
deepseek-ai/DeepSeek-V2), written out again.

    loss(cfg, params, tokens, experts=(lo, hi), attn_block=None)

``params`` is the tree of ``perfbench/models/deepseek_v2.py``. ``experts`` is
the range of the router's experts whose weights the stacks hold: what the
absent ones would add is left out. The heads are those whose rows of
``q_b_proj``, ``kv_b_proj`` and ``o_proj`` the tree holds,
``cfg["num_attention_heads"]`` of them: what the absent heads would add
through ``o_proj`` is left out. With ``(0, num_routed_experts)``, every head
and the whole vocabulary it is the uncut model. The vocabulary slice is the
tables' own row count: ids ``[0, rows)``. The experts run one at a time in a
Python loop under a dense mask, the rotary key is copied to every head and
joined to the keys as the published code does, and the attention is unblocked
unless ``attn_block`` is given (on the chip, where a whole score matrix of
4096 positions does not fit beside the weights).

Departures from the published modelling code, all of them: linear weights are
``(in, out)`` (``x @ W``, not ``x @ W.T``) but for ``lm_head``, ``q_b_proj``
and ``kv_b_proj``, which are as published; the experts of
a layer are three stacks ``(held, in, out)``, not three matrices an expert;
the router's weight is float32 in the tree (the published code casts it); the
balance losses (``seq_aux``) are left out.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, w, eps):
    return w * (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_cos_sin(cfg, seq):
    """``DeepseekV2YarnRotaryEmbedding``: cos and sin, each (seq, rope dims)."""
    dim, base, s = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = 1.0 / (s["factor"] * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def find_correction_dim(num_rotations):
        return dim * math.log(s["original_max_position_embeddings"] / (num_rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(find_correction_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = np.outer(np.arange(seq, dtype=np.float64), inv_freq)
    mscale = yarn_get_mscale(s["factor"], s["mscale"]) / yarn_get_mscale(s["factor"], s["mscale_all_dim"])
    emb = np.concatenate([freqs, freqs], -1)
    return jnp.asarray(np.cos(emb) * mscale, F32), jnp.asarray(np.sin(emb) * mscale, F32)


def apply_rotary(x, cos, sin):
    """x: (B, H, S, d). The published ``apply_rotary_pos_emb``: the dims are
    first sorted from pairs into halves, then the halves are rotated."""
    b, h, s, d = x.shape
    x = x.reshape(b, h, s, d // 2, 2).swapaxes(4, 3).reshape(b, h, s, d)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[None, None] + rotated * sin[None, None]


def attention(cfg, p, x, attn_block=None):
    b, s, _ = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd, rkv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = rms_norm(x @ p["q_a_proj"]["weight"], p["q_a_layernorm"]["weight"], eps) @ p["q_b_proj"]["weight"].T
    q = q.reshape(b, s, heads, nope + rope).swapaxes(1, 2)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    compressed = x @ p["kv_a_proj_with_mqa"]["weight"]
    compressed, k_pe = compressed[..., :rkv], compressed[..., rkv:]
    k_pe = k_pe.reshape(b, s, 1, rope).swapaxes(1, 2)
    kv = rms_norm(compressed, p["kv_a_layernorm"]["weight"], eps) @ p["kv_b_proj"]["weight"].T
    kv = kv.reshape(b, s, heads, nope + vd).swapaxes(1, 2)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    cos, sin = yarn_cos_sin(cfg, s)
    q_pe, k_pe = apply_rotary(q_pe, cos, sin), apply_rotary(k_pe, cos, sin)
    query = jnp.concatenate([q_nope, q_pe], -1)
    key = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, heads, s, rope))], -1)
    softmax_scale = (nope + rope) ** -0.5
    if cfg["rope_scaling"].get("mscale_all_dim", 0):
        mscale = yarn_get_mscale(cfg["rope_scaling"]["factor"], cfg["rope_scaling"]["mscale_all_dim"])
        softmax_scale = softmax_scale * mscale * mscale
    step = attn_block or s
    out = []
    for start in range(0, s, step):
        scores = jnp.einsum("bhqd,bhsd->bhqs", query[:, :, start:start + step], key) * softmax_scale
        visible = (start + jnp.arange(scores.shape[2]))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqs,bhsd->bhqd", probs, v))
    out = jnp.concatenate(out, axis=2).swapaxes(1, 2).reshape(b, s, heads * vd)
    return out @ p["o_proj"]["weight"]


def gate(cfg, p, x):
    """``MoEGate``: (weights, chosen) of every token, each (tokens, top)."""
    scores = jax.nn.softmax(x @ p["gate"]["weight"], axis=-1)
    tokens, groups, top = scores.shape[0], cfg["n_group"], cfg["num_experts_per_tok"]
    if cfg["topk_method"] == "group_limited_greedy":
        group_scores = scores.reshape(tokens, groups, -1).max(-1)
        _, group_idx = jax.lax.top_k(group_scores, cfg["topk_group"])
        group_mask = jnp.zeros_like(group_scores).at[jnp.arange(tokens)[:, None], group_idx].set(1.0)
        score_mask = jnp.repeat(group_mask, scores.shape[1] // groups, axis=1)
        scores = jnp.where(score_mask > 0, scores, 0.0)
    weights, chosen = jax.lax.top_k(scores, top)
    if top > 1 and cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    else:
        weights = weights * cfg["routed_scaling_factor"]
    return weights, chosen


def mlp(p, x):
    return (jax.nn.silu(x @ p["gate_proj"]["weight"]) * (x @ p["up_proj"]["weight"])) @ p["down_proj"]["weight"]


def routed_experts(cfg, p, x, experts):
    """The part of the mixture that experts ``[lo, hi)`` give, one at a time."""
    lo, hi = experts
    weights, chosen = gate(cfg, p, x)
    y = jnp.zeros_like(x)
    stacks = p["experts"]
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1, keepdims=True)
        hidden = jax.nn.silu(x @ stacks["gate_proj"][e - lo]) * (x @ stacks["up_proj"][e - lo])
        y = y + weight * (hidden @ stacks["down_proj"][e - lo])
    return y


def expert_layer(cfg, p, x, experts):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    return (routed_experts(cfg, p, x, experts) + mlp(p["shared_experts"], x)).reshape(shape)


def logits(cfg, params, inputs, experts=None, attn_block=None):
    """The next-token logits of every position, (batch, sequence, rows held)."""
    experts = experts or (0, cfg["num_routed_experts"])
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        model, eps = params["model"], cfg["rms_norm_eps"]
        x = model["embed_tokens"]["weight"][inputs]
        for i in range(cfg["num_hidden_layers"]):
            p = model["layers"][str(i)]
            x = x + attention(cfg, p["self_attn"], rms_norm(x, p["input_layernorm"]["weight"], eps), attn_block)
            h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
            if i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0:
                x = x + expert_layer(cfg, p["mlp"], h, experts)
            else:
                x = x + mlp(p["mlp"], h)
        return rms_norm(x, model["norm"]["weight"], eps) @ params["lm_head"]["weight"].T


def token_nll(cfg, params, inputs, targets, experts=None, attn_block=None):
    """The loss of every position, (batch, sequence)."""
    logp = jax.nn.log_softmax(logits(cfg, params, inputs, experts, attn_block), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(cfg, params, tokens, experts=None, attn_block=None):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:], experts, attn_block))
