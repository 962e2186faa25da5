"""Plain reference of Qwen3-Next's forward pass and loss: ``jax.numpy``,
float32, matrix products at ``highest`` precision, no chunking, no sorting,
no rematerialisation. It imports nothing of ``perfbench``; the equations are
those of the published modelling code, written out again.

    loss(cfg, params, tokens, experts=(lo, hi), attn_block=None)

``params`` is the tree of ``perfbench/models/qwen3_next.py`` (linear weights
``(in, out)`` but for ``lm_head``, a row a token; the experts of a layer stacked). ``experts`` is the range of the
router's experts whose weights the stacks hold: what the absent ones would add
is left out, and the renormalisation stays over all of a token's experts. With
``(0, num_routed_experts)`` and the whole vocabulary it is the uncut model.
The vocabulary slice is the tables' own row count: ids ``[0, rows)``.
The recurrence runs position by position, the experts in a Python loop, the
attention unblocked unless ``attn_block`` is given (on the chip, where a whole
score matrix of 4096 positions does not fit beside the weights).
"""

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def gated_norm(x, z, w, eps):
    return w * (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * jax.nn.silu(z)


def rotary(x, theta, rot):
    """x: (B, S, H, hd): the first ``rot`` dims of each head rotated, halves."""
    half = rot // 2
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.asarray(np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :], F32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def attention(cfg, p, x, attn_block=None):
    b, s, _ = x.shape
    heads, kv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    qg = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv, hd)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv, hd)
    rot = int(hd * cfg["partial_rotary_factor"])
    q = rotary(rms_norm(q, p["q_norm"]["weight"], eps), cfg["rope_theta"], rot)
    k = rotary(rms_norm(k, p["k_norm"]["weight"], eps), cfg["rope_theta"], rot)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    step = attn_block or s
    out = []
    for start in range(0, s, step):
        scores = jnp.einsum("bqhd,bshd->bhqs", q[:, start:start + step], k) / np.sqrt(hd)
        visible = (start + jnp.arange(scores.shape[2]))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqs,bshd->bqhd", probs, v))
    attn = jnp.concatenate(out, axis=1) * jax.nn.sigmoid(gate)
    return attn.reshape(b, s, heads * hd) @ p["o_proj"]["weight"]


def delta_rule(q, k, v, g, beta):
    """Position by position. q, k: (B, T, H, dk); v: (B, T, H, dv); g, beta:
    (B, T, H). ``S <- exp(g_t) S; r = v_t - S^T k_t; S <- S + k_t (beta_t r)^T;
    o_t = S^T q_t``."""
    b, _, h, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * r)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(out, 0, 1)


def delta_net(cfg, p, x):
    b, s, _ = x.shape
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    r = hv // hk
    qkvz = (x @ p["in_proj_qkvz"]["weight"]).reshape(b, s, hk, 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, s, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(b, s, hv, dv)
    ba = (x @ p["in_proj_ba"]["weight"]).reshape(b, s, hk, 2 * r)
    b_in, a_in = ba[..., :r].reshape(b, s, hv), ba[..., r:].reshape(b, s, hv)
    mixed = jnp.concatenate([q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1)], -1)
    taps = p["conv1d"]["weight"][:, 0, :]  # (channels, width)
    width = taps.shape[-1]
    padded = jnp.pad(mixed, [(0, 0), (width - 1, 0), (0, 0)])
    conv = jnp.zeros_like(mixed)
    for j in range(width):  # y[t] = sum_j w[j] x[t - (width - 1) + j]
        conv = conv + padded[:, j:j + s] * taps[:, j]
    mixed = jax.nn.silu(conv)
    q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, r, axis=2) / np.sqrt(dk), jnp.repeat(k, r, axis=2)
    beta = jax.nn.sigmoid(b_in)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a_in + p["dt_bias"])
    out = gated_norm(delta_rule(q, k, v, g, beta), z, p["norm"]["weight"], cfg["rms_norm_eps"])
    return out.reshape(b, s, hv * dv) @ p["out_proj"]["weight"]


def routed_experts(cfg, p, x, experts):
    """The part of the mixture that experts ``[lo, hi)`` give, one at a time."""
    lo, hi = experts
    probs = jax.nn.softmax(x @ p["gate"]["weight"], axis=-1)
    weights, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    y = jnp.zeros_like(x)
    stacks = p["experts"]
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1, keepdims=True)
        hidden = jax.nn.silu(x @ stacks["gate_proj"][e - lo]) * (x @ stacks["up_proj"][e - lo])
        y = y + weight * (hidden @ stacks["down_proj"][e - lo])
    return y


def shared_expert(p, x):
    s = p["shared_expert"]
    hidden = jax.nn.silu(x @ s["gate_proj"]["weight"]) * (x @ s["up_proj"]["weight"])
    return jax.nn.sigmoid(x @ p["shared_expert_gate"]["weight"]) * (hidden @ s["down_proj"]["weight"])


def expert_layer(cfg, p, x, experts):
    return routed_experts(cfg, p, x, experts) + shared_expert(p, x)


def logits(cfg, params, inputs, experts=None, attn_block=None):
    """The next-token logits of every position, (batch, sequence, rows held)."""
    experts = experts or (0, cfg["num_routed_experts"])
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        model, eps = params["model"], cfg["rms_norm_eps"]
        x = model["embed_tokens"]["weight"][inputs]
        for i in range(cfg["num_hidden_layers"]):
            p = model["layers"][str(i)]
            h = rms_norm(x, p["input_layernorm"]["weight"], eps)
            if (i + 1) % cfg["full_attention_interval"] == 0:
                x = x + attention(cfg, p["self_attn"], h, attn_block)
            else:
                x = x + delta_net(cfg, p["linear_attn"], h)
            x = x + expert_layer(cfg, p["mlp"], rms_norm(x, p["post_attention_layernorm"]["weight"], eps), experts)
        return rms_norm(x, model["norm"]["weight"], eps) @ params["lm_head"]["weight"].T


def token_nll(cfg, params, inputs, targets, experts=None, attn_block=None):
    """The loss of every position, (batch, sequence)."""
    logp = jax.nn.log_softmax(logits(cfg, params, inputs, experts, attn_block), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(cfg, params, tokens, experts=None, attn_block=None):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:], experts, attn_block))
