"""Plain reference of Trinity-Large-Preview's forward pass and loss:
``jax.numpy``, float32, matrix products at ``highest`` precision, no blocks,
no sorting, no rematerialisation. It imports nothing of ``perfbench``; the
equations are those of the published description (``config.json`` of
arcee-ai/Trinity-Large-Preview, ``model_type`` ``afmoe``: gated attention,
three windowed layers to one full one, sandwich norm, sigmoid routing with a
bias that steers the choice), written out again.

    loss(cfg, params, tokens, experts=(lo, hi), attn_block=None)

``params`` is the tree of ``perfbench/models/afmoe.py``. ``experts`` is the
range of the router's experts whose weights the stacks hold: what the absent
ones would add is left out. With ``(0, num_routed_experts)`` and the whole
vocabulary it is the uncut model. The vocabulary slice is the tables' own row
count: ids ``[0, rows)``. Attention is one dense score matrix a layer, every
query against every key, under an explicit ``(i, j)`` mask (``visible``); the
experts run one at a time in a Python loop under a dense mask over the
tokens. ``attn_block`` cuts the score matrix into blocks of query rows, each
still against every key under the same mask (on the chip, where 48 heads'
matrix of 8192 x 8192 does not fit beside the weights).

Departures from the published description, all of them: linear weights are
``(in, out)`` (``x @ W``) but for ``lm_head``; the experts of a layer are three
stacks ``(held, in, out)``, not three matrices an expert; the router's weight
and its bias are float32 in the tree; the rule that moves ``expert_bias``, any
balance loss and the depth-scaled initialisation are left out.
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


# Attention ----------------------------------------------------------------------

def rotate_half(x, theta):
    """x: (B, H, S, d): ``x cos + rotate_half(x) sin`` with ``rotate_half(x) =
    [-x2, x1]`` of the two halves and the angles of ``d / 2`` frequencies
    repeated over both."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = np.outer(np.arange(x.shape[2], dtype=np.float64), inv_freq)
    cos = jnp.asarray(np.concatenate([np.cos(freqs)] * 2, -1), F32)
    sin = jnp.asarray(np.concatenate([np.sin(freqs)] * 2, -1), F32)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + turned * sin


def visible(cfg, full, rows, length):
    """The mask itself: ``[i, j]`` is whether the query at position ``rows[i]``
    sees the key at position ``j``: never a later one, and in a window layer
    none ``sliding_window`` or more positions back."""
    i, j = np.asarray(rows)[:, None], np.arange(length)[None, :]
    return (j <= i) if full else (j <= i) & (i - j < cfg["sliding_window"])


def attention(cfg, p, x, full, attn_block=None):
    b, s, _ = x.shape
    heads, kv_heads, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, hd).swapaxes(1, 2)
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv_heads, hd).swapaxes(1, 2)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv_heads, hd).swapaxes(1, 2)
    q, k = rms_norm(q, p["q_norm"]["weight"], eps), rms_norm(k, p["k_norm"]["weight"], eps)
    if not full:  # only the window layers are rotated
        q, k = rotate_half(q, cfg["rope_theta"]), rotate_half(k, cfg["rope_theta"])
    # Grouped queries: query head h reads key-value head h // (heads / kv_heads).
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    step = attn_block or s
    out = []
    for start in range(0, s, step):
        rows = np.arange(start, min(start + step, s))
        scores = jnp.einsum("bhqd,bhsd->bhqs", q[:, :, rows[0]:rows[-1] + 1], k) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(visible(cfg, full, rows, s), scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqs,bhsd->bhqd", probs, v))
    out = jnp.concatenate(out, axis=2).swapaxes(1, 2).reshape(b, s, heads * hd)
    return (out * jax.nn.sigmoid(x @ p["gate_proj"]["weight"])) @ p["o_proj"]["weight"]


# The mixture --------------------------------------------------------------------

def gate(cfg, p, x):
    """(weights, chosen) of every token, each (tokens, top): the choice on the
    biased sigmoid scores, the weights from the scores alone."""
    scores = jax.nn.sigmoid(x @ p["router"]["gate"]["weight"])
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["route_norm"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * cfg["route_scale"], chosen


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
        x = model["embed_tokens"]["weight"][inputs]
        if cfg["mup_enabled"]:
            x = x * np.sqrt(cfg["hidden_size"])
        for i in range(cfg["num_hidden_layers"]):
            p = model["layers"][str(i)]
            full = cfg["layer_types"][i] == "full_attention"
            h = attention(cfg, p["self_attn"], rms_norm(x, p["input_layernorm"]["weight"], eps), full, attn_block)
            x = x + rms_norm(h, p["post_attention_layernorm"]["weight"], eps)
            h = rms_norm(x, p["pre_mlp_layernorm"]["weight"], eps)
            h = expert_layer(cfg, p["mlp"], h, experts) if i >= cfg["num_dense_layers"] else mlp(p["mlp"], h)
            x = x + rms_norm(h, p["post_mlp_layernorm"]["weight"], eps)
        return rms_norm(x, model["norm"]["weight"], eps) @ params["lm_head"]["weight"].T


def token_nll(cfg, params, inputs, targets, experts=None, attn_block=None):
    """The loss of every position, (batch, sequence)."""
    logp = jax.nn.log_softmax(logits(cfg, params, inputs, experts, attn_block), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(cfg, params, tokens, experts=None, attn_block=None):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:], experts, attn_block))
