"""Qwen3-Next as Qwen/Qwen3-Next-80B-A3B-Instruct publishes it (``config.json``,
``model_type`` ``qwen3_next``), told which experts and which rows of the
vocabulary it holds: one chip's share of an expert-parallel job.

Layer ``i`` is gated softmax attention when ``(i + 1) % full_attention_interval
== 0`` and a gated DeltaNet otherwise; every layer's MLP is the sparse
mixture (a ``num_routed_experts``-way router, top ``num_experts_per_tok``,
renormalised) plus a gated shared expert. Plain ``jax.numpy`` over a nested
dict of the published tensor names. Linear weights are held ``(in, out)``,
but for ``lm_head``, which is held as published, a row a token, like the
embedding: the vocabulary is what is sliced over chips.

Departures from the published checkpoint, all of them:

- the experts held here are three stacked leaves a layer,
  ``mlp.experts.{gate_proj,up_proj,down_proj}`` of shape ``(held, in, out)``,
  as JAX trainers hold them, where the checkpoint has three matrices an
  expert. ``num_experts`` counts the experts held: experts
  ``[rank * num_experts, (rank + 1) * num_experts)`` of the router's
  ``num_routed_experts``, ``rank`` being ``expert_parallel_rank``. The router
  keeps its published width and its experts per token, the renormalisation
  stays over all of a token's experts, and what the absent experts would add
  is left out; no code stands in for the absent chips;
- ``vocab_size`` counts the rows of the vocabulary held (ids ``[0, vocab_size)``):
  embedding, head, logits and loss are over that slice;
- ``A_log``, ``dt_bias`` and the router ``mlp.gate.weight`` are float32 beside
  bf16 leaves (the checkpoint is bf16 throughout; trainers keep these three in
  float32);
- ``A_log`` is ``log(U(0.001, 16))``: the published code draws ``U(0, 16)``,
  whose lower end is lifted here so that no seed gives ``log(0)``;
- left out: the multi-token-prediction module (``described_as`` "MTP 1"; no
  key of the config sizes it) and the router's auxiliary loss (no key either).

What an architecture gives the harness (``perfbench/README.md``), and all it
gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``,
``token_range``, ``TINY``, ``PUBLISHED``. ``expert_layer``, ``token_nll`` and
``chunked_delta_rule`` are what ``loss_fn`` is made of, named so that the tests
can hold each to the reference (``models/reference/qwen3_next.py``).
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
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "num_hidden_layers": 4, "vocab_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "linear_key_head_dim": 8,
    "linear_value_head_dim": 8, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_routed_experts": 8, "num_experts": 2, "num_experts_per_tok": 2,
}

CHUNK = 64  # positions a chunk of the DeltaNet recurrence (the published code's)
QUERY_BLOCK = 1024  # queries a block of the softmax attention
HEAD_BLOCK = 1024  # positions a block of the head and its loss


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def held_experts(cfg: dict):
    """The range of the router's experts whose weights live here."""
    lo = cfg.get("expert_parallel_rank", 0) * cfg["num_experts"]
    return lo, lo + cfg["num_experts"]


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the published names."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd, heads, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    f, fs, held = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["num_experts"]

    def leaf(*shape, dtype=PARAM_DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weight(*shape, dtype=PARAM_DTYPE):
        return {"weight": leaf(*shape, dtype=dtype)}

    mlp = {
        "gate": weight(d, cfg["num_routed_experts"], dtype=jnp.float32),
        "experts": {"gate_proj": leaf(held, d, f), "up_proj": leaf(held, d, f), "down_proj": leaf(held, f, d)},
        "shared_expert": {"gate_proj": weight(d, fs), "up_proj": weight(d, fs), "down_proj": weight(fs, d)},
        "shared_expert_gate": weight(d, 1),
    }
    linear_attn = {
        "in_proj_qkvz": weight(d, 2 * hk * dk + 2 * hv * dv),
        "in_proj_ba": weight(d, 2 * hv),
        "conv1d": weight(2 * hk * dk + hv * dv, 1, cfg["linear_conv_kernel_dim"]),
        "dt_bias": leaf(hv, dtype=jnp.float32),
        "A_log": leaf(hv, dtype=jnp.float32),
        "norm": weight(dv),
        "out_proj": weight(hv * dv, d),
    }
    self_attn = {
        "q_proj": weight(d, 2 * heads * hd), "k_proj": weight(d, kv * hd), "v_proj": weight(d, kv * hd),
        "o_proj": weight(heads * hd, d), "q_norm": weight(hd), "k_norm": weight(hd),
    }

    def layer(i):
        mixer = {"self_attn": self_attn} if is_full_attention(cfg, i) else {"linear_attn": linear_attn}
        return dict(mixer, input_layernorm=weight(d), post_attention_layernorm=weight(d), mlp=mlp)

    return {
        "model": {
            "embed_tokens": weight(v, d),
            "layers": {str(i): layer(i) for i in range(cfg["num_hidden_layers"])},
            "norm": weight(d),
        },
        "lm_head": weight(v, d),
    }


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key: 0 for the ``(1 + w)`` norms, 1
    for the gated norm and ``dt_bias``, ``A_log = log(U(0.001, 16))``,
    ``0.02 * normal`` otherwise."""
    if path.endswith(("layernorm/weight", "q_norm/weight", "k_norm/weight", "model/norm/weight")):
        return jnp.zeros(leaf.shape, leaf.dtype)
    if path.endswith(("linear_attn/norm/weight", "dt_bias")):
        return jnp.ones(leaf.shape, leaf.dtype)
    if path.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, leaf.shape, jnp.float32, 1e-3, 16.0)).astype(leaf.dtype)
    return (0.02 * jax.random.normal(key, leaf.shape, jnp.float32)).astype(leaf.dtype)


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
    """Zero-centred RMSNorm, in float32: ``x / sqrt(mean(x^2) + eps) * (1 + w)``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _gated_norm(x, z, w, eps):
    """``w * x / sqrt(mean(x^2) + eps) * silu(z)`` over each head's values."""
    x32, z32 = x.astype(jnp.float32), z.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True) + eps)
    return w.astype(jnp.float32) * y * jax.nn.silu(z32)


def _rotary(x, theta, rot):
    """x: (B, S, H, hd); rotate the first ``rot`` dims of each head (halves)."""
    seq = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    xr, xp = x[..., :rot].astype(jnp.float32), x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([(xr * cos + rotated * sin).astype(x.dtype), xp], -1)


# ---------------------------------------------------------------------------
# Gated softmax attention
# ---------------------------------------------------------------------------

def _attention_block(q, k, v, start):
    """Causal softmax attention of one block of queries (positions from
    ``start``) over the keys up to the block's end. q: (B, Q, G, R, hd);
    k, v: (B, S, G, hd), each kv head serving R query heads."""
    scores = jnp.einsum("bqgrd,bsgd->bgrqs", q, k, preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(q.shape[-1])
    visible = (start + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("bgrqs,bsgd->bqgrd", probs, v)


def _attention(cfg, p, x, output_gate: bool = True):
    """``output_gate=False`` leaves ``sigmoid(gate)`` out (the tests' control)."""
    b, s, _ = x.shape
    heads, kv, hd, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"]
    qg = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv, hd)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv, hd)
    rot = int(hd * cfg["partial_rotary_factor"])
    q = _rotary(_rms_norm(q, p["q_norm"]["weight"], eps), cfg["rope_theta"], rot)
    k = _rotary(_rms_norm(k, p["k_norm"]["weight"], eps), cfg["rope_theta"], rot)
    q = q.reshape(b, s, kv, heads // kv, hd)
    block = jax.checkpoint(_attention_block, static_argnums=3)
    out = [
        block(q[:, start:start + QUERY_BLOCK], k[:, :start + QUERY_BLOCK], v[:, :start + QUERY_BLOCK], start)
        for start in range(0, s, QUERY_BLOCK)
    ]
    attn = jnp.concatenate(out, axis=1).reshape(b, s, heads, hd)
    if output_gate:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)
    return attn.reshape(b, s, heads * hd) @ p["o_proj"]["weight"]


# ---------------------------------------------------------------------------
# Gated DeltaNet
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., C, C), by
    forward substitution row by row, as the published chunked code does."""
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


def chunked_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """The gated delta rule in chunks (the WY form of the published chunked
    code): per head a state ``S`` (dk x dv, float32, zero at the start),
    ``S <- exp(g_t) S; S <- S + k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t``.
    q, k: (B, T, H, dk), normalised and scaled; v: (B, T, H, dv); g, beta:
    (B, T, H). Returns (B, T, H, dv) in float32."""
    b, t, h, dk = q.shape
    n = -(-t // chunk)
    pad = n * chunk - t  # padded positions have k = v = beta = g = 0: no effect

    def chunks(x):
        x = jnp.pad(x.astype(jnp.float32), [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)  # (B, H, N, C, ...)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # (B, H, N, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    a = jnp.tril(jnp.einsum("bhnck,bhnsk->bhncs", kb, k) * decay, -1)
    solve = _unit_lower_inverse(a)
    u = solve @ vb  # (B, H, N, C, dv)
    w = solve @ (kb * jnp.exp(gc)[..., None])  # (B, H, N, C, dk)
    within = jnp.einsum("bhnck,bhnsk->bhncs", q, k) * decay
    q_in = q * jnp.exp(gc)[..., None]
    total = gc[..., -1]  # (B, H, N)
    k_out = k * jnp.exp(total[..., None] - gc)[..., None]

    def step(state, xs):
        u_n, w_n, within_n, q_n, k_n, total_n = xs
        v_new = u_n - w_n @ state
        out = q_n @ state + within_n @ v_new
        state = state * jnp.exp(total_n)[..., None, None] + jnp.swapaxes(k_n, -1, -2) @ v_new
        return state, out

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, within, q_in, k_out, total))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, -1)[:, :, :t]
    return jnp.moveaxis(out, 1, 2)


def _l2_norm(x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.square(x32).sum(-1, keepdims=True) + eps)


def _delta_net(cfg, p, x):
    b, s, _ = x.shape
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    r = hv // hk  # value heads a key head
    # Per key head: q (dk), k (dk), its r value heads' v and z; per key head b, a of its r value heads.
    qkvz = (x @ p["in_proj_qkvz"]["weight"]).reshape(b, s, hk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (x @ p["in_proj_ba"]["weight"]).reshape(b, s, hk, 2 * r)
    beta_in, a_in = ba[..., :r].reshape(b, s, hv), ba[..., r:].reshape(b, s, hv)
    mixed = jnp.concatenate([q.reshape(b, s, -1), k.reshape(b, s, -1), v.reshape(b, s, -1)], -1)
    # Causal depthwise convolution (left pad width - 1, no bias), then silu.
    taps = p["conv1d"]["weight"][:, 0, :]
    width = taps.shape[-1]
    padded = jnp.pad(mixed, [(0, 0), (width - 1, 0), (0, 0)])
    mixed = jax.nn.silu(sum(padded[:, j:j + s] * taps[:, j] for j in range(width)))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    q = jnp.repeat(_l2_norm(q.reshape(b, s, hk, dk)), r, axis=2) * dk ** -0.5
    k = jnp.repeat(_l2_norm(k.reshape(b, s, hk, dk)), r, axis=2)
    beta = jax.nn.sigmoid(beta_in.astype(jnp.float32))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a_in.astype(jnp.float32) + p["dt_bias"])
    out = chunked_delta_rule(q, k, v.reshape(b, s, hv, dv), g, beta)
    out = _gated_norm(out, z.reshape(b, s, hv, dv), p["norm"]["weight"], cfg["rms_norm_eps"])
    return out.reshape(b, s, hv * dv).astype(x.dtype) @ p["out_proj"]["weight"]


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

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


def _gated_mlp(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def expert_layer(cfg, p, x, shared: bool = True):
    """Router over all ``num_routed_experts`` in float32, the top
    ``num_experts_per_tok`` renormalised, and the part of the result that the
    experts held here give, with no token dropped: every (token, expert)
    pair is sorted by expert, the pairs of absent experts last, and the held
    stacks are applied by ``jax.lax.ragged_dot`` over the sorted rows. Plus
    the gated shared expert, which every chip computes alike (``shared``
    False leaves it out: the share test counts it once)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    tokens, top = x.shape[0], cfg["num_experts_per_tok"]
    lo, hi = held_experts(cfg)
    logits = jnp.dot(x.astype(jnp.float32), p["gate"]["weight"], precision=jax.lax.Precision.HIGHEST)
    weights, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top)
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    chosen = chosen.reshape(-1)
    held = (chosen >= lo) & (chosen < hi)
    slot = jnp.where(held, chosen - lo, hi - lo)
    order = jnp.argsort(slot, stable=True)
    inverse = jnp.argsort(order)
    group_sizes = jnp.bincount(slot, length=hi - lo + 1)[: hi - lo].astype(jnp.int32)
    rows = _permute(jnp.repeat(x, top, axis=0), order, inverse)
    # The rows past the held pairs belong to no group: what a ragged product
    # leaves there is not defined on every backend, so they are zeroed going
    # in and coming out (and so are their cotangents on the way back).
    mine = (jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None]

    def grouped(lhs, stack):
        return jnp.where(mine, jax.lax.ragged_dot(jnp.where(mine, lhs, 0), stack, group_sizes), 0)

    experts = p["experts"]
    hidden = jax.nn.silu(grouped(rows, experts["gate_proj"])) * grouped(rows, experts["up_proj"])
    rows = _permute(grouped(hidden, experts["down_proj"]), inverse, order).reshape(tokens, top, -1)
    scale = jnp.where(held.reshape(tokens, top), weights, 0.0).astype(rows.dtype)
    y = (rows * scale[..., None]).sum(1)
    if shared:
        s = p["shared_expert"]
        mlp = _gated_mlp(x, s["gate_proj"]["weight"], s["up_proj"]["weight"], s["down_proj"]["weight"])
        y = y + jax.nn.sigmoid((x @ p["shared_expert_gate"]["weight"]).astype(jnp.float32)).astype(x.dtype) * mlp
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def _layer(cfg, full_attention, p, x):
    eps = cfg["rms_norm_eps"]
    if full_attention:
        with jax.named_scope("qn.attn"):
            x = x + _attention(cfg, p["self_attn"], _rms_norm(x, p["input_layernorm"]["weight"], eps))
    else:
        with jax.named_scope("qn.gdn"):
            x = x + _delta_net(cfg, p["linear_attn"], _rms_norm(x, p["input_layernorm"]["weight"], eps))
    with jax.named_scope("qn.moe"):
        return x + expert_layer(cfg, p["mlp"], _rms_norm(x, p["post_attention_layernorm"]["weight"], eps))


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
        layer = jax.checkpoint(functools.partial(_layer, cfg, is_full_attention(cfg, i)))
        x = layer(model["layers"][str(i)], x)
    with jax.named_scope("qn.head"):
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
