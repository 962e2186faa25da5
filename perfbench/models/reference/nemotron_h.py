"""Plain reference of Nemotron-3-Nano-30B-A3B's forward pass and loss:
``jax.numpy``, float32, matrix products at ``highest`` precision, no chunks, no
blocks, no sorting, no rematerialisation. It imports nothing of ``perfbench``;
the equations are those of the published description (``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type`` ``nemotron_h``: a
pattern string of Mamba-2 mixers, squared-ReLU experts under a sigmoid router
with a bias that steers the choice, and attention without rotation, one mixer
a block), written out again.

    loss(cfg, params, tokens, experts=(lo, hi), attn_block=None)

``params`` is the tree of ``perfbench/models/nemotron_h.py``. ``experts`` is the
range of the router's experts whose weights the stacks hold: what the absent
ones would add is left out. With ``(0, num_routed_experts)`` and the whole
vocabulary it is the uncut model. The vocabulary slice is the tables' own row
count: ids ``[0, rows)``. The state-space layer goes **token by token**
(``jax.lax.scan`` over the positions, the state ``h_t = exp(delta_t A) h_(t-1) +
delta_t B_t (x) x_t`` written out, ``y_t = C_t . h_t + D x_t``); the convolution
is a sum over its taps of shifted copies; attention is one dense score matrix,
every query against every key, under an explicit ``(i, j)`` mask; the experts
run one at a time in a Python loop under a dense mask over the tokens.
``attn_block`` cuts the score matrix into blocks of query rows, each still
against every key under the same mask (on the chip, where 32 heads' matrix of
8192 x 8192 does not fit beside the weights).

Departures from the published description, all of them: linear weights are
``(in, out)`` (``x @ W``) but for ``lm_head``; the experts of a block are two
stacks ``(held, in, out)``, not two matrices an expert; ``A_log``, ``D``,
``dt_bias``, the router's weight and its bias are float32 in the tree; the rule
that moves ``e_score_correction_bias``, any balance loss and the published
initialisation are left out. Inferred, the config having no key for it (the
family's published code, from memory): ``d_inner`` is heads x head width, the
columns of ``in_proj`` are ``z | xBC | dt`` and the convolution's ``x | B | C``,
``delta`` is not clamped, the gate goes on before the grouped norm, attention
turns nothing by its position.
"""

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def rms_norm(x, w, eps):
    return w * (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def relu2_mlp(up, down, x):
    return jnp.square(jnp.maximum(x @ up, 0.0)) @ down


# The Mamba-2 mixer ----------------------------------------------------------------

def recurrence(x, delta, a, b, c):
    """Token by token. x: (B, T, H, P); delta: (B, T, H); a: (H,), negative; b,
    c: (B, T, H, S), each head's own copy of its group's. Returns ``C_t . h_t``
    of every position, (B, T, H, P)."""

    def token(h, xs):
        x_t, delta_t, b_t, c_t = xs  # (B, H, P), (B, H), (B, H, S), (B, H, S)
        h = jnp.exp(delta_t * a)[..., None, None] * h + (delta_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return h, jnp.sum(h * c_t[..., None, :], axis=-1)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], F32)
    _, y = jax.lax.scan(token, start, tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1)


def grouped_gated_norm(y, z, w, groups, eps):
    """``y * silu(z)`` first, then each group of channels by its own root mean
    square, a group at a time."""
    y = y * jax.nn.silu(z)
    size = y.shape[-1] // groups
    normed = [
        part / jnp.sqrt(jnp.mean(part * part, -1, keepdims=True) + eps)
        for part in (y[..., g * size:(g + 1) * size] for g in range(groups))
    ]
    return w * jnp.concatenate(normed, axis=-1)


def mamba(cfg, p, x):
    bsz, t, _ = x.shape
    heads, width, groups, states = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = heads * width
    conv_dim = d_inner + 2 * groups * states
    mixed = x @ p["in_proj"]["weight"]
    z, xbc, dt = mixed[..., :d_inner], mixed[..., d_inner:d_inner + conv_dim], mixed[..., d_inner + conv_dim:]
    # Depth-wise and causal: channel c at position t is bias_c + sum_j w[c, 0, j] xbc[t - (taps - 1) + j, c].
    taps = cfg["conv_kernel"]
    padded = jnp.pad(xbc, [(0, 0), (taps - 1, 0), (0, 0)])
    conv = p["conv1d"]["bias"] + sum(padded[:, j:j + t] * p["conv1d"]["weight"][:, 0, j] for j in range(taps))
    conv = jax.nn.silu(conv)
    xs = conv[..., :d_inner].reshape(bsz, t, heads, width)
    b = conv[..., d_inner:d_inner + groups * states].reshape(bsz, t, groups, states)
    c = conv[..., d_inner + groups * states:].reshape(bsz, t, groups, states)
    # Head h reads group h // (heads / groups).
    b, c = jnp.repeat(b, heads // groups, axis=2), jnp.repeat(c, heads // groups, axis=2)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(xs, delta, -jnp.exp(p["A_log"]), b, c) + p["D"][:, None] * xs
    y = grouped_gated_norm(y.reshape(bsz, t, d_inner), z, p["norm"]["weight"], groups, cfg["layer_norm_epsilon"])
    return y @ p["out_proj"]["weight"]


# Attention ------------------------------------------------------------------------

def attention(cfg, p, x, attn_block=None):
    b, s, _ = x.shape
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, hd).swapaxes(1, 2)
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv_heads, hd).swapaxes(1, 2)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv_heads, hd).swapaxes(1, 2)
    # Grouped queries: query head h reads key-value head h // (heads / kv_heads). Nothing is rotated.
    k, v = jnp.repeat(k, heads // kv_heads, axis=1), jnp.repeat(v, heads // kv_heads, axis=1)
    step = attn_block or s
    out = []
    for start in range(0, s, step):
        rows = np.arange(start, min(start + step, s))
        scores = jnp.einsum("bhqd,bhsd->bhqs", q[:, :, rows[0]:rows[-1] + 1], k) * hd ** -0.5
        visible = np.arange(s)[None, :] <= rows[:, None]  # never a later key
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqs,bhsd->bhqd", probs, v))
    out = jnp.concatenate(out, axis=2).swapaxes(1, 2).reshape(b, s, heads * hd)
    return out @ p["o_proj"]["weight"]


# The mixture --------------------------------------------------------------------

def gate(cfg, p, x):
    """(weights, chosen) of every token, each (tokens, top): the choice on the
    biased sigmoid scores, the weights from the scores alone."""
    scores = jax.nn.sigmoid(x @ p["gate"]["weight"])
    _, chosen = jax.lax.top_k(scores + p["gate"]["e_score_correction_bias"], cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"], chosen


def routed_experts(cfg, p, x, experts):
    """The part of the mixture that experts ``[lo, hi)`` give, one at a time."""
    lo, hi = experts
    weights, chosen = gate(cfg, p, x)
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1, keepdims=True)
        y = y + weight * relu2_mlp(p["experts"]["up_proj"][e - lo], p["experts"]["down_proj"][e - lo], x)
    return y


def expert_layer(cfg, p, x, experts):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    shared = p["shared_experts"]
    y = routed_experts(cfg, p, x, experts) + relu2_mlp(shared["up_proj"]["weight"], shared["down_proj"]["weight"], x)
    return y.reshape(shape)


# The model ------------------------------------------------------------------------

def logits(cfg, params, inputs, experts=None, attn_block=None):
    """The next-token logits of every position, (batch, sequence, rows held)."""
    experts = experts or (0, cfg["num_routed_experts"])
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        backbone, eps = params["backbone"], cfg["layer_norm_epsilon"]
        x = backbone["embeddings"]["weight"][inputs]
        for i in range(cfg["num_hidden_layers"]):
            p = backbone["layers"][str(i)]
            h, mixer = rms_norm(x, p["norm"]["weight"], eps), cfg["hybrid_override_pattern"][i]
            if mixer == "M":
                h = mamba(cfg, p["mixer"], h)
            elif mixer == "*":
                h = attention(cfg, p["mixer"], h, attn_block)
            else:
                h = expert_layer(cfg, p["mixer"], h, experts)
            x = x + h  # one mixer a block, and nothing after it
        return rms_norm(x, backbone["norm_f"]["weight"], eps) @ params["lm_head"]["weight"].T


def token_nll(cfg, params, inputs, targets, experts=None, attn_block=None):
    """The loss of every position, (batch, sequence)."""
    logp = jax.nn.log_softmax(logits(cfg, params, inputs, experts, attn_block), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss(cfg, params, tokens, experts=None, attn_block=None):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:], experts, attn_block))
