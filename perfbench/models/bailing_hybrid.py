"""Ling-3.0-flash as inclusionAI/Ling-3.0-flash publishes it (``config.json``,
``model_type`` ``bailing_hybrid``), told which experts and which rows of the
vocabulary it holds: one chip's share of an expert-parallel job.

Layer ``i`` is multi-head latent attention (MLA) when ``(i + 1) %
layer_group_size == 0`` and Kimi delta attention (KDA, arXiv:2510.26692)
otherwise; the first ``first_k_dense_replace`` layers have a dense SwiGLU MLP,
the others the sparse mixture.

KDA: ``q, k, v = SiLU(conv(x W_{q,k,v}))``, a causal depthwise convolution of
``short_conv_kernel_size`` taps a channel; ``q, k`` L2-normalised per head,
``q`` times ``head_dim^-1/2``; a log-decay **a channel**, ``g_t =
kda_lower_bound * sigmoid(exp(A_log_h) (x_t W_f + dt_bias))`` (the safe gate:
bounded in ``(kda_lower_bound, 0)``); ``beta_t = sigmoid(x_t W_b)`` a head; per
head a state ``S`` (head_dim x head_dim, float32), ``S_t = (I - beta_t k_t
k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; out
``(RMSNorm_head(o) * sigmoid(x W_g)) W_o``. ``W_f`` and ``W_g`` are full rank
(``no_kda_lora``).

MLA without a query latent (``q_lora_rank`` null): ``q = x W_q`` per head
``qk_nope_head_dim + qk_rope_head_dim``; ``[c_kv, k_pe] = x W_kva``, ``[k_nope,
v] = RMSNorm(c_kv) W_kvb`` per head, ``k_pe`` one rotary key for all heads;
RMSNorm of each head's query and key over their 192 dims (``use_qk_norm``),
then the rotation of the rope dims in interleaved pairs at ``rope_theta``;
causal softmax at ``192^-1/2``; a gate a head, ``sigmoid(x W_g)``
(``gated_attention_proj_granularity_type`` ``head_wise``), before the output
projection.

The mixture (``topk_method`` ``noaux_tc``): ``s = sigmoid(x W_r)`` in float32
over all ``num_routed_experts``; the choice is made on ``s + b``, ``b`` the
float32 buffer ``mlp.gate.expert_bias`` (no gradient reaches it): a group's
score is the sum of its two largest, the ``topk_group`` best of ``n_group``
groups are kept and the top ``num_experts_per_tok`` taken inside them; the
weights are ``s`` (not ``s + b``) of the chosen, normalised to 1, times
``routed_scaling_factor``; plus one shared SwiGLU added to every token.

Plain ``jax.numpy`` over a nested dict of tensor names (inferred, no network:
``configs/ling-3.0-flash-ep32.json`` ``assumed.tensor_names``). Linear weights
are held ``(in, out)``, but for ``lm_head``, held a row a token like
``word_embeddings``: the vocabulary is what is sliced over chips.

Departures from the published checkpoint, all of them:

- the experts held here are three stacked leaves a layer,
  ``mlp.experts.{gate_proj,up_proj,down_proj}`` of shape ``(held, in, out)``,
  where the checkpoint has three matrices an expert. ``num_experts`` counts the
  experts held: experts ``[rank * num_experts, (rank + 1) * num_experts)`` of
  the router's ``num_routed_experts``, ``rank`` being ``expert_parallel_rank``.
  The router keeps its published width, its groups, its bias and its experts
  per token, the renormalisation stays over all of a token's experts, and what
  the absent experts would add is left out; no code stands in for the absent
  chips;
- ``vocab_size`` counts the rows of the vocabulary held (ids ``[0, vocab_size)``);
- the three short convolutions are ``(channels, taps)`` leaves (a
  ``torch.nn.Conv1d`` holds ``(channels, 1, taps)``);
- ``A_log``, ``dt_bias``, the router ``mlp.gate.weight`` and its
  ``expert_bias`` are float32 beside bf16 leaves;
- ``A_log`` is ``log(U(1, 16))`` and ``dt_bias`` ``U(-1, 1)``, so that the
  seeded decays cover ``(kda_lower_bound, 0)``; ``expert_bias`` is ``0.1 *
  normal``, wide enough to change some choices (the checkpoint's are trained);
- left out: the multi-token-prediction module (``num_nextn_predict_layers`` 1
  at ``mtp_loss_scaling_factor`` 0: it adds nothing to the published loss), the
  rule that updates ``expert_bias`` and ``seq_aux``'s loss (no key sizes
  either), and the clamps of ``expert_swiglu_limit_list`` /
  ``share_expert_swiglu_limit_list``, which are 0 (none) in every layer held.

What an architecture gives the harness (``perfbench/README.md``), and all it
gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``,
``token_range``, ``TINY``, ``PUBLISHED``. ``kda``, ``chunked_kda``, ``mla``,
``route``, ``expert_layer`` and ``token_nll`` are what ``loss_fn`` is made of,
named so that the tests can hold each to the reference
(``models/reference/bailing_hybrid.py``).
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
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7, "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6, "linear_silu": True,
    "max_position_embeddings": 262144, "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True, "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512, "num_experts_per_tok": 8,
    "num_hidden_layers": 42, "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1, "partial_rotary_factor": 0.5,
    "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5, "scale_router_input": False,
    "score_function": "sigmoid", "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "use_qk_norm": True, "use_qkv_bias": False, "v_head_dim": 128, "value_norm": False,
    "vocab_size": 157184, "model_type": "bailing_hybrid",
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 4, "layer_group_size": 3, "vocab_size": 64,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 8, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "qk_head_dim": 16, "rotary_dim": 8, "v_head_dim": 8,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "num_routed_experts": 16, "num_experts": 2, "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
}

# Positions a chunk of the KDA recurrence. The chunked form factors the decay
# between two positions of a chunk, exp(G_s - G_j), G the running sum of the
# chunk's log-decays, into exp(G_s - G_m) and exp(G_m - G_j): with a decay a
# channel it cannot be taken out of the product over channels as a head's one
# scalar can. With m the chunk's middle the exponents reach -kda_lower_bound *
# CHUNK / 2 = 40 either way. float32 would hold exp(x) up to x = 88, but on the
# way back a cotangent (1e-8 and less at 4096 tokens a mean) is multiplied by
# the small factor before the large one takes it back: exp(-40) * 1e-8 stays a
# normal float32, exp(-80) * 1e-8 does not (and the chip flushes it to zero).
CHUNK = 16
QUERY_BLOCK = 1024  # queries a block of the softmax attention
HEAD_BLOCK = 1024  # positions a block of the head and its loss


def is_mla(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def is_sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def held_experts(cfg: dict):
    """The range of the router's experts whose weights live here."""
    lo = cfg.get("expert_parallel_rank", 0) * cfg["num_experts"]
    return lo, lo + cfg["num_experts"]


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the tensor names."""
    d, v, heads, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"], cfg["head_dim"]
    nope, rope, vd, rkv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    f, held, taps = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["short_conv_kernel_size"]

    def leaf(*shape, dtype=PARAM_DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weight(*shape, dtype=PARAM_DTYPE):
        return {"weight": leaf(*shape, dtype=dtype)}

    def gated_mlp(width):
        return {"gate_proj": weight(d, width), "up_proj": weight(d, width), "down_proj": weight(width, d)}

    kda_mixer = {
        "q_proj": weight(d, heads * hd), "k_proj": weight(d, heads * hd), "v_proj": weight(d, heads * hd),
        "q_conv1d": weight(heads * hd, taps), "k_conv1d": weight(heads * hd, taps), "v_conv1d": weight(heads * hd, taps),
        "f_proj": weight(d, heads * hd), "b_proj": weight(d, heads), "g_proj": weight(d, heads * hd),
        "A_log": leaf(heads, dtype=jnp.float32), "dt_bias": leaf(heads * hd, dtype=jnp.float32),
        "o_norm": weight(hd), "o_proj": weight(heads * hd, d),
    }
    mla_mixer = {
        "q_proj": weight(d, heads * (nope + rope)), "kv_a_proj_with_mqa": weight(d, rkv + rope),
        "kv_a_layernorm": weight(rkv), "kv_b_proj": weight(rkv, heads * (nope + vd)),
        "query_layernorm": weight(nope + rope), "key_layernorm": weight(nope + rope),
        "g_proj": weight(d, heads), "dense": weight(heads * vd, d),
    }
    sparse = {
        "gate": {"weight": leaf(d, cfg["num_routed_experts"], dtype=jnp.float32),
                 "expert_bias": leaf(cfg["num_routed_experts"], dtype=jnp.float32)},
        "experts": {"gate_proj": leaf(held, d, f), "up_proj": leaf(held, d, f), "down_proj": leaf(held, f, d)},
        "shared_experts": gated_mlp(cfg["moe_shared_expert_intermediate_size"] * cfg["num_shared_experts"]),
    }

    def layer(i):
        return {
            "attention": mla_mixer if is_mla(cfg, i) else kda_mixer,
            "input_layernorm": weight(d), "post_attention_layernorm": weight(d),
            "mlp": sparse if is_sparse(cfg, i) else gated_mlp(cfg["intermediate_size"]),
        }

    return {
        "model": {
            "word_embeddings": weight(v, d),
            "layers": {str(i): layer(i) for i in range(cfg["num_hidden_layers"])},
            "norm": weight(d),
        },
        "lm_head": weight(v, d),
    }


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key: 1 for the norms, ``A_log =
    log(U(1, 16))``, ``dt_bias = U(-1, 1)``, ``expert_bias = 0.1 * normal``,
    ``0.02 * normal`` otherwise."""
    if path.endswith(("layernorm/weight", "o_norm/weight", "model/norm/weight")):
        return jnp.ones(leaf.shape, leaf.dtype)
    if path.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, leaf.shape, jnp.float32, 1.0, 16.0)).astype(leaf.dtype)
    if path.endswith("dt_bias"):
        return jax.random.uniform(key, leaf.shape, jnp.float32, -1.0, 1.0).astype(leaf.dtype)
    scale = 0.1 if path.endswith("expert_bias") else 0.02
    return (scale * jax.random.normal(key, leaf.shape, jnp.float32)).astype(leaf.dtype)


def param_spec(path: str) -> P:
    """Expert parallelism over a layout whose mesh names ``ep``: the expert
    stacks over their expert dimension, embedding and head over the
    vocabulary; everything else of a layer whole on each chip."""
    if "/experts/" in path or "word_embeddings" in path or "lm_head" in path:
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


def _l2_norm(x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.square(x32).sum(-1, keepdims=True) + eps)


def _rotary(x, theta):
    """x: (B, S, H, rope dims): each pair ``(x0, x1), (x2, x3), ...`` turned
    by its position's angle, in place (``rope_interleave``)."""
    rot = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (rot // 2, 2)).astype(jnp.float32)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Kimi delta attention
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., C, C), by
    forward substitution row by row."""
    size = a.shape[-1]

    def row(i, x):
        mine = jax.lax.dynamic_index_in_dim(x, i, axis=-2, keepdims=False)
        new = mine + jnp.sum(mine[..., :, None] * x, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(x, new, i, axis=-2)

    return jax.lax.fori_loop(1, size, row, -a) + jnp.eye(size, dtype=a.dtype)


def _unit_lower_inverse_fwd(a):
    x = _unit_lower_inverse(a)
    return x, x


def _unit_lower_inverse_bwd(x, g):
    # d(X) = -X d(a) X, so d(a) = -X^T g X^T on the strictly lower triangle.
    xt = jnp.swapaxes(x, -1, -2)
    return (jnp.tril(-(xt @ g @ xt), -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunked_kda(q, k, v, g, beta, chunk: int = CHUNK):
    """The delta rule with a decay a channel, in chunks (the WY form): per
    head a state ``S`` (dk x dv, float32, zero at the start), ``S <-
    Diag(exp g_t) S; S <- S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t``.
    q, k: (B, T, H, dk), normalised and scaled; v: (B, T, H, dv); g: (B, T, H,
    dk), each in ``[-80 / chunk, 0]``; beta: (B, T, H). Returns (B, T, H, dv)
    in float32."""
    b, t, h, dk = q.shape
    n = -(-t // chunk)
    pad = n * chunk - t  # padded positions have q = k = v = beta = g = 0: no effect

    def chunks(x):
        x = jnp.pad(x.astype(jnp.float32), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)  # (B, H, N, C, ...)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)  # (B, H, N, C, dk): G_s, position s included
    shrink = jnp.exp(gc)
    # Inside the chunk: exp(G_s - G_j) as exp(G_s - G_m) exp(G_m - G_j), m the chunk's middle.
    mid = jnp.exp(gc - gc[..., chunk // 2:chunk // 2 + 1, :])
    k_from, k_mid, q_mid = k / mid, k * mid, q * mid
    beta = beta[..., None]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # a[s, j] = beta_s sum_c k_s k_j exp(G_s - G_j) for j < s. The entries on and above the diagonal,
    # where the exponent is not negative, are finite and dropped.
    a = jnp.where(lower.T, 0.0, jnp.einsum("bhnck,bhnsk->bhncs", k_mid * beta, k_from))
    solve = _unit_lower_inverse(a)
    u = solve @ (v * beta)  # (B, H, N, C, dv)
    w = solve @ (k * shrink * beta)  # (B, H, N, C, dk)
    within = jnp.where(lower, jnp.einsum("bhnck,bhnsk->bhncs", q_mid, k_from), 0.0)
    q_to = q * shrink
    total = gc[..., -1, :]  # (B, H, N, dk)
    k_out = k * jnp.exp(total[..., None, :] - gc)

    def step(state, xs):
        u_n, w_n, within_n, q_n, k_n, total_n = xs
        v_new = u_n - w_n @ state
        out = q_n @ state + within_n @ v_new
        state = state * jnp.exp(total_n)[..., None] + jnp.swapaxes(k_n, -1, -2) @ v_new
        return state, out

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, within, q_to, k_out, total))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, -1)[:, :, :t]
    return jnp.moveaxis(out, 1, 2)


def _short_conv(x, taps):
    """Causal depthwise convolution (left pad taps - 1, no bias), then SiLU
    (``linear_silu``). x: (B, S, channels); taps: (channels, width)."""
    width, s = taps.shape[-1], x.shape[1]
    padded = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    return jax.nn.silu(sum(padded[:, j:j + s] * taps[:, j] for j in range(width)))


def log_decay(cfg, p, x, scalar_decay: bool = False, softplus_gate: bool = False):
    """(B, S, H, head_dim) in float32, each in ``(kda_lower_bound, 0)``. The
    tests' controls: ``scalar_decay`` gives every channel its head's mean (one
    decay a head, as a gated DeltaNet has); ``softplus_gate`` is ``-exp(A_log)
    softplus(.)``, the unbounded gate the safe one replaces, clipped at the
    bound so that the chunks still hold it."""
    b, s, _ = x.shape
    heads, hd, bound = cfg["num_attention_heads"], cfg["head_dim"], float(cfg["kda_lower_bound"])
    f = jnp.dot(x, p["f_proj"]["weight"], preferred_element_type=jnp.float32) + p["dt_bias"]
    rate = jnp.exp(p["A_log"])[:, None]
    if softplus_gate:
        g = jnp.maximum(-rate * jax.nn.softplus(f.reshape(b, s, heads, hd)), bound)
    else:
        g = bound * jax.nn.sigmoid(rate * f.reshape(b, s, heads, hd))
    return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape) if scalar_decay else g


def kda(cfg, p, x, **controls):
    b, s, _ = x.shape
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]

    def mixed(name):
        return _short_conv(x @ p[name + "_proj"]["weight"], p[name + "_conv1d"]["weight"]).reshape(b, s, heads, hd)

    q, k, v = _l2_norm(mixed("q")) * hd ** -0.5, _l2_norm(mixed("k")), mixed("v")
    beta = jax.nn.sigmoid(jnp.dot(x, p["b_proj"]["weight"], preferred_element_type=jnp.float32))
    out = chunked_kda(q, k, v, log_decay(cfg, p, x, **controls), beta)
    gate = jax.nn.sigmoid((x @ p["g_proj"]["weight"]).astype(jnp.float32)).reshape(b, s, heads, hd)
    out = _rms_norm(out, p["o_norm"]["weight"].astype(jnp.float32), cfg["rms_norm_eps"]) * gate
    return out.reshape(b, s, heads * hd).astype(x.dtype) @ p["o_proj"]["weight"]


# ---------------------------------------------------------------------------
# Multi-head latent attention
# ---------------------------------------------------------------------------

def _attention_block(q, k, v, start, scale):
    """Causal softmax attention of one block of queries (positions from
    ``start``) over the keys up to the block's end. q: (B, Q, H, dq); k: (B,
    S, H, dq); v: (B, S, H, dv)."""
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k, preferred_element_type=jnp.float32)
    visible = (start + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(visible, scores * scale, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


def mla(cfg, p, x, head_gate: bool = True):
    """``head_gate=False`` leaves ``sigmoid(x W_g)`` out (the tests' control)."""
    b, s, _ = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd, rkv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, nope + rope)
    kv_a = x @ p["kv_a_proj_with_mqa"]["weight"]
    c_kv, k_pe = _rms_norm(kv_a[..., :rkv], p["kv_a_layernorm"]["weight"], eps), kv_a[..., rkv:]
    kv = (c_kv @ p["kv_b_proj"]["weight"]).reshape(b, s, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :], (b, s, heads, rope))], -1)
    v = kv[..., nope:]
    q, k = _rms_norm(q, p["query_layernorm"]["weight"], eps), _rms_norm(k, p["key_layernorm"]["weight"], eps)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], cfg["rope_theta"])], -1)
    k = jnp.concatenate([k[..., :nope], _rotary(k[..., nope:], cfg["rope_theta"])], -1)
    block = jax.checkpoint(_attention_block, static_argnums=(3, 4))
    scale = (nope + rope) ** -0.5
    out = [
        block(q[:, start:start + QUERY_BLOCK], k[:, :start + QUERY_BLOCK], v[:, :start + QUERY_BLOCK], start, scale)
        for start in range(0, s, QUERY_BLOCK)
    ]
    out = jnp.concatenate(out, axis=1)
    if head_gate:
        out = out * jax.nn.sigmoid((x @ p["g_proj"]["weight"]).astype(jnp.float32)).astype(out.dtype)[..., None]
    return out.reshape(b, s, heads * vd) @ p["dense"]["weight"]


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def route(cfg, scores, bias, group_limit: bool = True, bias_in_choice: bool = True, bias_in_weights: bool = False):
    """``(weights, chosen)`` of every token, each ``(tokens, num_experts_per_tok)``.
    The choice is made on ``scores + bias``: of the ``n_group`` groups the
    ``topk_group`` with the largest sums of their two best are kept, and the
    top experts taken inside them. The weights are the scores themselves of
    the chosen, normalised to 1 where ``norm_topk_prob`` says so, times
    ``routed_scaling_factor``. The tests' controls: ``group_limit=False`` is a
    plain top-k over all experts, ``bias_in_choice=False`` chooses on the
    scores alone, ``bias_in_weights`` weighs with ``scores + bias``."""
    tokens, experts = scores.shape
    choice = scores + bias if bias_in_choice else scores
    if group_limit:
        groups = cfg["n_group"]
        best = jax.lax.top_k(choice.reshape(tokens, groups, experts // groups), 2)[0].sum(-1)
        _, kept = jax.lax.top_k(best, cfg["topk_group"])
        allowed = (kept[..., None] == jnp.arange(groups)).any(1)
        choice = jnp.where(jnp.repeat(allowed, experts // groups, axis=1), choice, -jnp.inf)
    _, chosen = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores + bias if bias_in_weights else scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
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


def expert_layer(cfg, p, x, shared: bool = True, **controls):
    """Sigmoid router over all ``num_routed_experts`` in float32, the choice
    steered by the bias and limited to the best groups, and the part of the
    result that the experts held here give, with no token dropped: every
    (token, expert) pair is sorted by expert, the pairs of absent experts
    last, and the held stacks are applied by ``jax.lax.ragged_dot`` over the
    sorted rows. Plus the shared expert, which every chip computes alike
    (``shared`` False leaves it out: the share test counts it once)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    tokens, top = x.shape[0], cfg["num_experts_per_tok"]
    lo, hi = held_experts(cfg)
    logits = jnp.dot(x.astype(jnp.float32), p["gate"]["weight"], precision=jax.lax.Precision.HIGHEST)
    bias = jax.lax.stop_gradient(p["gate"]["expert_bias"])  # a buffer: the bias-update rule moves it, no gradient
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

def _layer(cfg, latent, sparse, p, x):
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, p["input_layernorm"]["weight"], eps)
    if latent:
        with jax.named_scope("bl.mla"):
            x = x + mla(cfg, p["attention"], h)
    else:
        with jax.named_scope("bl.kda"):
            x = x + kda(cfg, p["attention"], h)
    h = _rms_norm(x, p["post_attention_layernorm"]["weight"], eps)
    if sparse:
        with jax.named_scope("bl.moe"):
            return x + expert_layer(cfg, p["mlp"], h)
    with jax.named_scope("bl.dense"):
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
    x = model["word_embeddings"]["weight"][inputs]
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(functools.partial(_layer, cfg, is_mla(cfg, i), is_sparse(cfg, i)))
        x = layer(model["layers"][str(i)], x)
    with jax.named_scope("bl.head"):
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
