"""Plain reference of Ling-3.0-flash's forward pass and loss: ``jax.numpy``,
float32, matrix products at ``highest`` precision, no chunks, no blocks, no
sorting, no rematerialisation. It imports nothing of ``perfbench``; the
equations are those of the published description (``config.json`` of
inclusionAI/Ling-3.0-flash; Kimi Linear, arXiv:2510.26692, for the delta
attention; DeepSeek-V3's ``noaux_tc`` gate for the router), written out again.

    loss(cfg, params, tokens, experts=(lo, hi), attn_block=None)

``params`` is the tree of ``perfbench/models/bailing_hybrid.py``. ``experts``
is the range of the router's experts whose weights the stacks hold: what the
absent ones would add is left out. With ``(0, num_routed_experts)`` and the
whole vocabulary it is the uncut model. The vocabulary slice is the tables'
own row count: ids ``[0, rows)``. Kimi delta attention is the recurrence
itself, one position at a time (``lax.scan`` over the state); the experts run
one at a time in a Python loop under a dense mask; the softmax attention is
unblocked unless ``attn_block`` is given (on the chip, where a whole score
matrix of 4096 positions does not fit beside the weights).

Departures from the published description, all of them: linear weights are
``(in, out)`` (``x @ W``) but for ``lm_head``; the experts of a layer are three
stacks ``(held, in, out)``, not three matrices an expert; the short
convolutions are ``(channels, taps)``; ``A_log``, ``dt_bias``, the router's
weight and its bias are float32 in the tree; the multi-token-prediction
module (loss factor 0), the bias-update rule, ``seq_aux``'s loss and the
SwiGLU clamps (limit 0 in every layer the tree holds) are left out.
"""

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, w, eps):
    return w * (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def mlp(p, x):
    return (jax.nn.silu(x @ p["gate_proj"]["weight"]) * (x @ p["up_proj"]["weight"])) @ p["down_proj"]["weight"]


# Kimi delta attention ---------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """Position by position. q, k: (B, T, H, dk); v: (B, T, H, dv); g: (B, T,
    H, dk), a log-decay a channel; beta: (B, T, H). ``S <- Diag(exp g_t) S; r =
    v_t - S^T k_t; S <- S + k_t (beta_t r)^T; o_t = S^T q_t``, which is ``S_t =
    (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``."""
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * r)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(out, 0, 1)


def short_conv(x, taps):
    """y[t] = silu(sum_j w[j] x[t - (width - 1) + j]), causal, a channel at a time."""
    width, s = taps.shape[-1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    conv = jnp.zeros_like(x)
    for j in range(width):
        conv = conv + padded[:, j:j + s] * taps[:, j]
    return jax.nn.silu(conv)


def kda(cfg, p, x):
    b, s, _ = x.shape
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    q = short_conv(x @ p["q_proj"]["weight"], p["q_conv1d"]["weight"]).reshape(b, s, heads, hd)
    k = short_conv(x @ p["k_proj"]["weight"], p["k_conv1d"]["weight"]).reshape(b, s, heads, hd)
    v = short_conv(x @ p["v_proj"]["weight"], p["v_conv1d"]["weight"]).reshape(b, s, heads, hd)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(hd)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    # The safe gate: a log-decay a channel, bounded in (kda_lower_bound, 0).
    f = (x @ p["f_proj"]["weight"] + p["dt_bias"]).reshape(b, s, heads, hd)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    beta = jax.nn.sigmoid(x @ p["b_proj"]["weight"])
    out = rms_norm(delta_rule(q, k, v, g, beta), p["o_norm"]["weight"], cfg["rms_norm_eps"])
    out = out * jax.nn.sigmoid(x @ p["g_proj"]["weight"]).reshape(b, s, heads, hd)
    return out.reshape(b, s, heads * hd) @ p["o_proj"]["weight"]


# Multi-head latent attention --------------------------------------------------

def rotate_pairs(x, theta):
    """x: (B, H, S, d): the pairs (x0, x1), (x2, x3), ... each turned by the
    position's angle, where they stand (interleaved)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = np.outer(np.arange(x.shape[2], dtype=np.float64), inv_freq)
    cos, sin = jnp.asarray(np.repeat(np.cos(freqs), 2, -1), F32), jnp.asarray(np.repeat(np.sin(freqs), 2, -1), F32)
    swapped = jnp.stack([-x[..., 1::2], x[..., 0::2]], -1).reshape(x.shape)
    return x * cos + swapped * sin


def mla(cfg, p, x, attn_block=None):
    b, s, _ = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd, rkv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, nope + rope).swapaxes(1, 2)
    compressed = x @ p["kv_a_proj_with_mqa"]["weight"]
    compressed, k_pe = compressed[..., :rkv], compressed[..., rkv:]
    kv = rms_norm(compressed, p["kv_a_layernorm"]["weight"], eps) @ p["kv_b_proj"]["weight"]
    kv = kv.reshape(b, s, heads, nope + vd).swapaxes(1, 2)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    # The one rotary key is copied to every head, then each head's query and key are normed over all their dims.
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (b, heads, s, rope))], -1)
    q, k = rms_norm(q, p["query_layernorm"]["weight"], eps), rms_norm(k, p["key_layernorm"]["weight"], eps)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], cfg["rope_theta"])], -1)
    k = jnp.concatenate([k[..., :nope], rotate_pairs(k[..., nope:], cfg["rope_theta"])], -1)
    step = attn_block or s
    out = []
    for start in range(0, s, step):
        scores = jnp.einsum("bhqd,bhsd->bhqs", q[:, :, start:start + step], k) * (nope + rope) ** -0.5
        visible = (start + jnp.arange(scores.shape[2]))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqs,bhsd->bhqd", probs, v))
    out = jnp.concatenate(out, axis=2).swapaxes(1, 2)  # (B, S, H, vd)
    out = out * jax.nn.sigmoid(x @ p["g_proj"]["weight"])[..., None]  # a gate a head
    return out.reshape(b, s, heads * vd) @ p["dense"]["weight"]


# The mixture --------------------------------------------------------------------

def gate(cfg, p, x):
    """The ``noaux_tc`` gate: (weights, chosen) of every token, each (tokens, top)."""
    scores = jax.nn.sigmoid(x @ p["gate"]["weight"])
    for_choice = scores + p["gate"]["expert_bias"]
    tokens, groups, top = scores.shape[0], cfg["n_group"], cfg["num_experts_per_tok"]
    group_scores = jax.lax.top_k(for_choice.reshape(tokens, groups, -1), 2)[0].sum(-1)
    _, group_idx = jax.lax.top_k(group_scores, cfg["topk_group"])
    group_mask = jnp.zeros_like(group_scores).at[jnp.arange(tokens)[:, None], group_idx].set(1.0)
    score_mask = jnp.repeat(group_mask, scores.shape[1] // groups, axis=1)
    _, chosen = jax.lax.top_k(jnp.where(score_mask > 0, for_choice, -jnp.inf), top)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"], chosen


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


# The model ------------------------------------------------------------------------

def logits(cfg, params, inputs, experts=None, attn_block=None):
    """The next-token logits of every position, (batch, sequence, rows held)."""
    experts = experts or (0, cfg["num_routed_experts"])
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        model, eps = params["model"], cfg["rms_norm_eps"]
        x = model["word_embeddings"]["weight"][inputs]
        for i in range(cfg["num_hidden_layers"]):
            p = model["layers"][str(i)]
            h = rms_norm(x, p["input_layernorm"]["weight"], eps)
            if (i + 1) % cfg["layer_group_size"] == 0:
                x = x + mla(cfg, p["attention"], h, attn_block)
            else:
                x = x + kda(cfg, p["attention"], h)
            h = rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
            if i >= cfg["first_k_dense_replace"]:
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
