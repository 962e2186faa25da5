"""Plain reference of LFM2-24B-A2B's forward pass and loss: ``jax.numpy``,
float32, matrix products at ``highest`` precision, no blocks, no sorting, no
rematerialisation. It imports nothing of ``perfbench``; the equations are those
of the published description (``config.json`` of LiquidAI/LFM2-24B-A2B,
``model_type`` ``lfm2_moe``: gated short convolutions three layers in four,
grouped-query attention with per-head norms before the rotation in the fourth,
sigmoid routing with a bias that steers the choice, a tied embedding), written
out again.

    loss(cfg, params, tokens, experts=(lo, hi), attn_block=None)

``params`` is the tree of ``perfbench/models/lfm2_moe.py``. ``experts`` is the
range of the router's experts whose weights the stacks hold: what the absent
ones would add is left out. With ``(0, num_routed_experts)`` and the whole
vocabulary it is the uncut model. The vocabulary slice is the table's own row
count: ids ``[0, rows)``. The convolution is the framework's own grouped
convolution over the published ``(channels, 1, taps)`` weight, padded by ``taps -
1`` on both sides and cut to the sequence, as the published code calls it;
attention is one dense score matrix a layer, every query against every key,
under an explicit ``(i, j)`` mask; the experts run one at a time in a Python
loop under a dense mask over the tokens. ``attn_block`` cuts the score matrix
into blocks of query rows, each still against every key under the same mask
(on the chip, where 32 heads' matrix of 8192 x 8192 does not fit beside the
weights).

Departures from the published description, all of them: linear weights are
``(in, out)`` (``x @ W``); the experts of a layer are three stacks ``(held, in,
out)``, not three matrices an expert; the router's weight and its bias are
float32 in the tree; the rule that moves ``expert_bias``, any balance loss and
the published initialisation are left out. Inferred, the config having no key
for it (the family's published code, from memory): the columns of ``in_proj``
are ``B | C | x``, the gates have no activation, the per-head norms come before
the rotation, the head is ``hidden_size / num_attention_heads`` wide, the
renormalisation adds 1e-6 to the sum, and the head of the model is the embedding
matrix.
"""

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, w, eps):
    return w * (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def mlp(w1, w2, w3, x):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


# The gated short convolution ----------------------------------------------------------

def depthwise_conv(u, weight):
    """u: (B, S, channels); weight: (channels, 1, taps). A grouped
    convolution, a group a channel, padded by ``taps - 1`` on both sides; the
    first ``S`` outputs are the causal ones."""
    taps = weight.shape[-1]
    out = jax.lax.conv_general_dilated(
        u.swapaxes(1, 2), weight, window_strides=(1,), padding=[(taps - 1, taps - 1)],
        dimension_numbers=("NCH", "OIH", "NCH"), feature_group_count=weight.shape[0],
        precision=jax.lax.Precision.HIGHEST,
    )
    return out[..., : u.shape[1]].swapaxes(1, 2)


def short_conv(cfg, p, x):
    d = cfg["hidden_size"]
    bcx = x @ p["in_proj"]["weight"]
    b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    return (c * depthwise_conv(b * u, p["conv"]["weight"])) @ p["out_proj"]["weight"]


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


def attention(cfg, p, x, attn_block=None):
    b, s, _ = x.shape
    heads, kv_heads, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["norm_eps"]
    hd, theta = cfg["hidden_size"] // heads, cfg["rope_parameters"]["rope_theta"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, hd).swapaxes(1, 2)
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv_heads, hd).swapaxes(1, 2)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv_heads, hd).swapaxes(1, 2)
    q, k = rms_norm(q, p["q_layernorm"]["weight"], eps), rms_norm(k, p["k_layernorm"]["weight"], eps)
    q, k = rotate_half(q, theta), rotate_half(k, theta)
    # Grouped queries: query head h reads key-value head h // (heads / kv_heads).
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    step = attn_block or s
    out = []
    for start in range(0, s, step):
        rows = np.arange(start, min(start + step, s))
        scores = jnp.einsum("bhqd,bhsd->bhqs", q[:, :, rows[0]:rows[-1] + 1], k) * hd ** -0.5
        visible = np.arange(s)[None, :] <= rows[:, None]  # query i sees key j iff j <= i
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqs,bhsd->bhqd", probs, v))
    return jnp.concatenate(out, axis=2).swapaxes(1, 2).reshape(b, s, heads * hd) @ p["out_proj"]["weight"]


# The mixture --------------------------------------------------------------------

def gate(cfg, p, x):
    """(weights, chosen) of every token, each (tokens, top): the choice on the
    biased sigmoid scores, the weights from the scores alone."""
    scores = jax.nn.sigmoid(x @ p["gate"]["weight"])
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return weights * cfg["routed_scaling_factor"], chosen


def expert_layer(cfg, p, x, experts):
    """The part of the mixture that experts ``[lo, hi)`` give, one at a time;
    there is no shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    lo, hi = experts
    weights, chosen = gate(cfg, p, x)
    y = jnp.zeros_like(x)
    stacks = p["experts"]
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1, keepdims=True)
        y = y + weight * mlp(stacks["w1"][e - lo], stacks["w2"][e - lo], stacks["w3"][e - lo], x)
    return y.reshape(shape)


# The model ------------------------------------------------------------------------

def logits(cfg, params, inputs, experts=None, attn_block=None):
    """The next-token logits of every position, (batch, sequence, rows held)."""
    experts = experts or (0, cfg["num_routed_experts"])
    with jax.default_matmul_precision("highest"):
        model, eps = _f32(params)["model"], cfg["norm_eps"]
        table = model["embed_tokens"]["weight"]
        x = table[inputs]
        for i in range(cfg["num_hidden_layers"]):
            p = model["layers"][str(i)]
            h = rms_norm(x, p["operator_norm"]["weight"], eps)
            if cfg["layer_types"][i] == "full_attention":
                x = x + attention(cfg, p["self_attn"], h, attn_block)
            else:
                x = x + short_conv(cfg, p["conv"], h)
            h, ffn = rms_norm(x, p["ffn_norm"]["weight"], eps), p["feed_forward"]
            if i >= cfg["num_dense_layers"]:
                x = x + expert_layer(cfg, ffn, h, experts)
            else:
                x = x + mlp(ffn["w1"]["weight"], ffn["w2"]["weight"], ffn["w3"]["weight"], h)
        return rms_norm(x, model["embedding_norm"]["weight"], eps) @ table.T  # the head is the table


def token_nll(cfg, params, inputs, targets, experts=None, attn_block=None):
    """The loss of every position, (batch, sequence)."""
    logp = jax.nn.log_softmax(logits(cfg, params, inputs, experts, attn_block), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(cfg, params, tokens, experts=None, attn_block=None):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:], experts, attn_block))
