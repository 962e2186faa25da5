"""Nemotron-3-Nano-30B-A3B as nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 publishes
it (``config.json``, ``model_type`` ``nemotron_h``), told which experts and which
rows of the vocabulary it holds: one chip's share of an expert-parallel job.

Block ``i`` is what character ``i`` of ``hybrid_override_pattern`` says: ``M`` a
Mamba-2 mixer, ``E`` the sparse mixture, ``*`` softmax attention. **Every block is
``x + mixer(RMSNorm(x))``: one norm of ``hidden_size`` (``layer_norm_epsilon``),
one mixer, and no MLP half after it.**

``M``, Mamba-2. ``d_inner = mamba_num_heads x mamba_head_dim`` (not ``expand x
hidden_size``). ``in_proj`` (no bias) gives ``z`` (``d_inner``) | ``xBC``
(``d_inner + 2 n_groups ssm_state_size``) | ``dt`` (``mamba_num_heads``). A
depth-wise causal convolution of ``conv_kernel`` taps with bias over ``xBC``, then
SiLU; split into ``x`` (heads x head width), ``B`` and ``C`` (``n_groups`` x
``ssm_state_size``; a group serves ``mamba_num_heads / n_groups`` heads).
``delta_t = softplus(dt_t + dt_bias)`` and ``A = -exp(A_log)`` a head, float32,
no clamp. ``h_t = exp(delta_t A) h_(t-1) + delta_t B_t (x) x_t`` (a state of head
width x ``ssm_state_size`` a head), ``y_t = C_t . h_t + D x_t``. Gated norm: ``y *
SiLU(z)`` first, then RMSNorm over each of the ``n_groups`` groups of channels
under one gain of ``d_inner``; ``out_proj`` (no bias).

``*``, attention: ``q_proj`` (``num_attention_heads`` x ``head_dim``), ``k_proj``,
``v_proj`` (``num_key_value_heads`` x ``head_dim``), ``o_proj``, no bias; causal
grouped-query softmax at ``head_dim^-1/2``; **no positional rotation at all**, no
per-head norm, no gate.

``E``, the mixture: ``s = sigmoid(x W_r)`` in float32 over all
``num_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b`` are
chosen, ``b`` the float32 buffer ``gate.e_score_correction_bias`` (no gradient
reaches it; ``n_group`` 1: no group limit); the weights are ``s`` (not ``s + b``)
of the chosen, divided by their sum (``norm_topk_prob``), times
``routed_scaling_factor``. An expert is two matrices and no gate:
``down(relu(up(x))^2)`` (``mlp_hidden_act`` relu2). One shared expert of the same
form, ``moe_shared_expert_intermediate_size`` wide, is added to every token.

The model: ``embeddings(ids)``, the blocks, ``norm_f``, an untied ``lm_head``.

Plain ``jax.numpy`` over a nested dict of tensor names (inferred, no network:
``configs/nemotron-3-nano-30b-a3b-ep8.json`` ``assumed.tensor_names``). Linear
weights are held ``(in, out)``, but for ``lm_head``, held a row a token like
``embeddings``: the vocabulary is what is sliced over chips. ``conv1d.weight`` is
held as published, ``(channels, 1, taps)``.

Departures from the published checkpoint, all of them:

- the experts held here are two stacked leaves a block, ``mixer.experts.{up_proj,
  down_proj}`` of shape ``(held, in, out)``, where the checkpoint has two matrices
  an expert. ``n_routed_experts`` counts the experts held: experts ``[rank *
  n_routed_experts, (rank + 1) * n_routed_experts)`` of the router's
  ``num_routed_experts``, ``rank`` being ``layer_share_rank``. The router keeps its
  published width, its bias and its experts per token, the renormalisation stays
  over all of a token's experts, and what the absent experts would add is left
  out; no code stands in for the absent chips;
- ``vocab_size`` counts the rows of the vocabulary held (ids ``[0, vocab_size)``);
- ``A_log``, ``D``, ``dt_bias``, the router ``mixer.gate.weight`` and its buffer
  ``mixer.gate.e_score_correction_bias`` are float32 beside bf16 leaves;
- seeded weights replace the published initialisation (``rescale_prenorm_residual``
  with it): ``A_log = log(U[1, 16])``; ``dt_bias`` the inverse softplus of a step
  drawn log-uniformly in ``[time_step_min, time_step_max]`` and floored at
  ``time_step_floor``; ``D`` and every gain ``1 + 0.1 * normal`` (a gain read as 1,
  or a norm left out, then shows); the two biases (``e_score_correction_bias``,
  ``conv1d.bias``) ``0.1 * normal``; ``conv1d.weight`` ``0.3 * normal`` (near the
  published ``U(-1/2, 1/2)`` of four taps: at ``0.02`` the recurrence would add a
  five-hundredth of what ``D x`` adds, and a scan left out would not show);
  ``0.02 * normal`` otherwise;
- left out: the rule that moves ``e_score_correction_bias`` (no key sizes it) and
  any balance loss.

What an architecture gives the harness (``perfbench/README.md``), and all it
gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``, ``token_range``,
``TINY``, ``PUBLISHED``. ``chunked_scan``, ``gated_norm``, ``mamba``,
``softmax_attention``, ``attention``, ``route``, ``expert_layer``, ``block`` and
``token_nll`` are what ``loss_fn`` is made of, named so that the tests can hold
each to the reference (``models/reference/nemotron_h.py``).
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
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "vocab_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "chunk_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "intermediate_size": 24, "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "num_routed_experts": 16, "n_routed_experts": 2, "num_experts_per_tok": 3,
}

# Queries a block of the attention; a block's key-value heads go one at a time, each with its 16 query heads'
# float32 scores over up to 8192 keys: 268 MB at 512.
QUERY_BLOCK = 512
HEAD_BLOCK = 1024  # positions a block of the head and its loss


def kind(cfg: dict, i: int) -> str:
    """``"M"``, ``"E"`` or ``"*"``: the mixer of block ``i``."""
    return cfg["hybrid_override_pattern"][i]


def held_experts(cfg: dict):
    """The range of the router's experts whose weights live here."""
    lo = cfg.get("layer_share_rank", 0) * cfg["n_routed_experts"]
    return lo, lo + cfg["n_routed_experts"]


def mamba_widths(cfg: dict):
    """``(d_inner, channels of xBC)``: heads x head width, and that plus ``B``
    and ``C`` of every group."""
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return d_inner, d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the tensor names."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    heads, kv_heads, ssm_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["mamba_num_heads"]
    f, held, routed = cfg["moe_intermediate_size"], cfg["n_routed_experts"], cfg["num_routed_experts"]
    shared = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    d_inner, conv_dim = mamba_widths(cfg)

    def leaf(*shape, dtype=PARAM_DTYPE):
        return jax.ShapeDtypeStruct(shape, dtype)

    def weight(*shape, dtype=PARAM_DTYPE):
        return {"weight": leaf(*shape, dtype=dtype)}

    mixers = {
        "M": {
            "in_proj": weight(d, d_inner + conv_dim + ssm_heads),
            "conv1d": {"weight": leaf(conv_dim, 1, cfg["conv_kernel"]), "bias": leaf(conv_dim)},
            "dt_bias": leaf(ssm_heads, dtype=jnp.float32), "A_log": leaf(ssm_heads, dtype=jnp.float32),
            "D": leaf(ssm_heads, dtype=jnp.float32),
            "norm": weight(d_inner), "out_proj": weight(d_inner, d),
        },
        "*": {
            "q_proj": weight(d, heads * hd), "k_proj": weight(d, kv_heads * hd), "v_proj": weight(d, kv_heads * hd),
            "o_proj": weight(heads * hd, d),
        },
        "E": {
            "gate": {"weight": leaf(d, routed, dtype=jnp.float32), "e_score_correction_bias": leaf(routed, dtype=jnp.float32)},
            "experts": {"up_proj": leaf(held, d, f), "down_proj": leaf(held, f, d)},
            "shared_experts": {"up_proj": weight(d, shared), "down_proj": weight(shared, d)},
        },
    }
    return {
        "backbone": {
            "embeddings": weight(v, d),
            "layers": {
                str(i): {"norm": weight(d), "mixer": mixers[kind(cfg, i)]} for i in range(cfg["num_hidden_layers"])
            },
            "norm_f": weight(d),
        },
        "lm_head": weight(v, d),
    }


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key (the module's docstring has the
    rule and why)."""
    if path.endswith(("A_log", "dt_bias")):
        u = jax.random.uniform(key, leaf.shape, jnp.float32)
        if path.endswith("A_log"):
            return jnp.log(1.0 + 15.0 * u)
        lo, hi = np.log(PUBLISHED["time_step_min"]), np.log(PUBLISHED["time_step_max"])
        step = jnp.maximum(jnp.exp(lo + (hi - lo) * u), PUBLISHED["time_step_floor"])
        return step + jnp.log(-jnp.expm1(-step))  # softplus^-1
    draw = jax.random.normal(key, leaf.shape, jnp.float32)
    if path.endswith(("norm/weight", "norm_f/weight", "/D")):
        return (1.0 + 0.1 * draw).astype(leaf.dtype)
    if path.endswith(("e_score_correction_bias", "conv1d/bias")):
        return (0.1 * draw).astype(leaf.dtype)
    return ((0.3 if path.endswith("conv1d/weight") else 0.02) * draw).astype(leaf.dtype)


def param_spec(path: str) -> P:
    """Expert parallelism over a layout whose mesh names ``ep``: the expert
    stacks over their expert dimension, embedding and head over the
    vocabulary; everything else of a block whole on each chip."""
    if "/experts/" in path or "embeddings" in path or "lm_head" in path:
        return P("ep")
    return P()


def token_range(cfg: dict) -> int:
    """Token ids of a batch are drawn from ``[0, token_range)``: the slice of
    the vocabulary held here."""
    return cfg["vocab_size"]


def _rms_norm(x, w, eps):
    """``w * x / sqrt(mean(x^2) + eps)``, the statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.square(x32).mean(-1, keepdims=True) + eps)
    return w * y.astype(x.dtype)


# ---------------------------------------------------------------------------
# The Mamba-2 mixer
# ---------------------------------------------------------------------------

def _scan_group(x, step, a, b, c, mask_first: bool):
    """One group's heads, in chunks, float32. x: (B, N, R, Q, P), ``N`` chunks
    of ``Q`` positions, ``R`` heads of width ``P``; step: (B, N, R, Q), the
    ``delta``; a: (R,), negative; b, c: (B, N, Q, S), the group's. With
    ``l_t = delta_t a`` and ``cum_i`` its sum over the chunk up to ``i``
    included, position ``i`` reads ``sum_(j <= i) exp(cum_i - cum_j) (C_i . B_j)
    delta_j x_j`` of its own chunk and ``exp(cum_i) C_i . h`` of the state ``h``
    the chunk was entered with; the chunk leaves ``exp(cum_last) h + sum_j
    exp(cum_last - cum_j) delta_j B_j (x) x_j``. Every exponent is ``<= 0``: the
    mask goes on before the exponential."""
    cum = jnp.cumsum(step * a[:, None], axis=-1)  # (B, N, R, Q)
    gap = cum[..., :, None] - cum[..., None, :]  # [i, j] = cum_i - cum_j
    lower = jnp.tril(jnp.ones((x.shape[-2],) * 2, bool))
    if mask_first:
        decay = jnp.exp(jnp.where(lower, gap, -jnp.inf))
    else:  # the tests' control: exp of a positive gap overflows, and inf * 0 reaches the cotangents
        decay = jnp.where(lower, jnp.exp(gap), 0.0)
    moved = x * step[..., None]  # delta_j x_j
    within = jnp.einsum("bnqs,bnks->bnqk", c, b)[:, :, None] * decay  # (B, N, R, Q, Q)
    y = jnp.einsum("bnrqk,bnrkp->bnrqp", within, moved)
    to_end = jnp.exp(cum[..., -1:] - cum)  # (B, N, R, Q)
    added = jnp.einsum("bnrkp,bnks->bnrps", moved * to_end[..., None], b)  # (B, N, R, P, S)
    shrink = jnp.exp(cum[..., -1])  # (B, N, R)

    def chunk(state, xs):
        added_n, shrink_n = xs
        return state * shrink_n[..., None, None] + added_n, state

    xs = (jnp.moveaxis(added, 1, 0), jnp.moveaxis(shrink, 1, 0))
    _, entered = jax.lax.scan(chunk, jnp.zeros(added.shape[:1] + added.shape[2:], jnp.float32), xs)
    entered = jnp.moveaxis(entered, 0, 1)  # (B, N, R, P, S): the state each chunk was entered with
    return y + jnp.einsum("bnqs,bnrps->bnrqp", c, entered) * jnp.exp(cum)[..., None]


def chunked_scan(x, step, a, b, c, chunk: int, mask_first: bool = True):
    """The recurrence ``h_t = exp(delta_t A) h_(t-1) + delta_t B_t (x) x_t``,
    ``y_t = C_t . h_t`` from a zero state, in chunks of ``chunk`` positions. x:
    (B, T, H, P); step: (B, T, H), ``delta``, float32; a: (H,), ``A``, negative;
    b, c: (B, T, G, S), ``G`` groups that serve ``H / G`` heads each. Returns
    (B, T, H, P) in float32. The groups go one at a time (``jax.lax.map``), each
    under its own ``jax.checkpoint``, so that one group's decay matrices
    (chunks x heads x ``chunk`` x ``chunk``) are all that is live."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    n = -(-t // chunk)
    pad = n * chunk - t  # padded positions have delta = x = B = C = 0: no decay, nothing added

    def chunks(v):  # (B, T, ...) -> (B, N, Q, ...)
        v = jnp.pad(v.astype(jnp.float32), [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape((bsz, n, chunk) + v.shape[2:])

    x = jnp.moveaxis(chunks(x).reshape(bsz, n, chunk, g, h // g, p), (3, 4), (0, 3))  # (G, B, N, R, Q, P)
    step = jnp.moveaxis(chunks(step).reshape(bsz, n, chunk, g, h // g), (3, 4), (0, 3))  # (G, B, N, R, Q)
    b, c = (jnp.moveaxis(chunks(v), 3, 0) for v in (b, c))  # (G, B, N, Q, S)
    one = jax.checkpoint(functools.partial(_scan_group, mask_first=mask_first))
    y = jax.lax.map(lambda args: one(*args), (x, step, a.reshape(g, h // g), b, c))  # (G, B, N, R, Q, P)
    return jnp.moveaxis(y, (0, 3), (3, 4)).reshape(bsz, n * chunk, h, p)[:, :t]


def gated_norm(y, z, w, groups: int, eps, gate_first: bool = True):
    """``y * SiLU(z)``, then RMSNorm over each of ``groups`` groups of channels
    (statistics in float32), under one gain of all the channels. The tests'
    control: ``gate_first`` False norms ``y`` and gates after."""
    z = jax.nn.silu(z.astype(jnp.float32))
    y = y.astype(jnp.float32)
    if gate_first:
        y = y * z
    grouped = y.reshape(y.shape[:-1] + (groups, -1))
    y = (grouped * jax.lax.rsqrt(jnp.square(grouped).mean(-1, keepdims=True) + eps)).reshape(y.shape)
    if not gate_first:
        y = y * z
    return w * y.astype(w.dtype)


def _causal_conv(x, p, bias: bool = True):
    """Depth-wise causal convolution (left pad taps - 1) with bias, then SiLU.
    x: (B, S, channels); ``conv1d.weight``: (channels, 1, taps), the last tap
    the position's own."""
    taps, s = p["weight"][:, 0, :], x.shape[1]
    width = taps.shape[-1]
    padded = jnp.pad(x, [(0, 0), (width - 1, 0), (0, 0)])
    y = sum(padded[:, j:j + s] * taps[:, j] for j in range(width))
    return jax.nn.silu(y + p["bias"] if bias else y)


def mamba(cfg, p, x, gate_first: bool = True, scan: bool = True, skip: bool = True, step_bias: bool = True,
          conv_bias: bool = True, norm_groups=None):
    """The Mamba-2 mixer of one block. The tests' controls: ``gate_first``
    (``gated_norm``'s), ``scan`` False leaves the recurrence out (``y = D x``),
    ``skip`` False leaves ``D x`` out, ``step_bias`` False ``dt_bias``,
    ``conv_bias`` False the convolution's bias, ``norm_groups`` norms over
    that many groups and not ``n_groups``."""
    b, s, _ = x.shape
    h, hp, g, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    d_inner, conv_dim = mamba_widths(cfg)
    z, xbc, dt = jnp.split(x @ p["in_proj"]["weight"], [d_inner, d_inner + conv_dim], axis=-1)
    xbc = _causal_conv(xbc, p["conv1d"], conv_bias)
    xs, bs, cs = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    xs = xs.reshape(b, s, h, hp)
    step = jax.nn.softplus(dt.astype(jnp.float32) + (p["dt_bias"] if step_bias else 0.0))
    y = jnp.zeros(xs.shape, jnp.float32)
    if scan:
        y = chunked_scan(xs, step, -jnp.exp(p["A_log"]), bs.reshape(b, s, g, n), cs.reshape(b, s, g, n), cfg["chunk_size"])
    if skip:
        y = y + p["D"][:, None] * xs.astype(jnp.float32)
    y = gated_norm(y.reshape(b, s, d_inner), z, p["norm"]["weight"], norm_groups or g, cfg["layer_norm_epsilon"], gate_first)
    return y @ p["out_proj"]["weight"]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _attend(q, k, v, gap, scale):
    """One key-value head: its queries (B, Q, R, D) over its keys and values
    (B, K, D); ``gap[i, j]`` is query ``i``'s position less key ``j``'s."""
    scores = jnp.einsum("bqrd,bkd->brqk", q, k, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(jnp.where(gap >= 0, scores * scale, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("brqk,bkd->bqrd", probs, v)


def softmax_attention(q, k, v, scale):
    """Grouped-query causal softmax attention in blocks. q: (B, S, G, R, D),
    ``R`` query heads a key-value head; k, v: (B, S, G, D). A block of
    ``QUERY_BLOCK`` queries reads the keys up to its last position and none
    after. The key-value heads of a block go one at a time (``jax.lax.map``)
    under ``jax.checkpoint``, so one head's float32 scores are all that is
    live."""
    s = q.shape[1]
    one_head = jax.checkpoint(_attend, static_argnums=(4,))
    out = []
    for start in range(0, s, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, s)
        gap = (start + jnp.arange(end - start))[:, None] - jnp.arange(end)[None, :]
        heads = (jnp.moveaxis(x, 2, 0) for x in (q[:, start:end], k[:, :end], v[:, :end]))
        block = jax.lax.map(lambda qkv, gap=gap: one_head(*qkv, gap, scale), tuple(heads))
        out.append(jnp.moveaxis(block, 0, 2))
    return jnp.concatenate(out, axis=1)


def _rotary(x, theta):
    """Rotate-half over all of ``head_dim``: what this family's attention
    does **not** do, here for the tests' control alone. x: (B, S, H, D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def attention(cfg, p, x, rotate: bool = False):
    """The attention mixer of one block: no rotation, no per-head norm, no
    gate. The tests' control: ``rotate`` True turns ``q`` and ``k`` by their
    positions' angles (``rope_theta``), which the family does not."""
    b, s, _ = x.shape
    heads, kv_heads, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ p["q_proj"]["weight"]).reshape(b, s, heads, hd)
    k = (x @ p["k_proj"]["weight"]).reshape(b, s, kv_heads, hd)
    v = (x @ p["v_proj"]["weight"]).reshape(b, s, kv_heads, hd)
    if rotate:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    q = q.reshape(b, s, kv_heads, heads // kv_heads, hd)
    return softmax_attention(q, k, v, hd ** -0.5).reshape(b, s, heads * hd) @ p["o_proj"]["weight"]


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def route(cfg, scores, bias, bias_in_choice: bool = True, bias_in_weights: bool = False, norm_topk_prob=None):
    """``(weights, chosen)`` of every token, each ``(tokens, num_experts_per_tok)``.
    The choice is the top of ``scores + bias`` over all experts (``n_group``
    1: no group limit). The weights are the scores themselves of the chosen,
    divided by their sum where ``norm_topk_prob`` says so, times
    ``routed_scaling_factor``. The tests' controls: ``bias_in_choice=False``
    chooses on the scores alone, ``bias_in_weights`` weighs with ``scores +
    bias``, ``norm_topk_prob`` overrides the configuration's."""
    _, chosen = jax.lax.top_k(scores + bias if bias_in_choice else scores, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores + bias if bias_in_weights else scores, chosen, axis=-1)
    if cfg["norm_topk_prob"] if norm_topk_prob is None else norm_topk_prob:
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


def _relu2(x, squared: bool = True):
    x = jax.nn.relu(x)
    return jnp.square(x) if squared else x


def expert_layer(cfg, p, x, shared: bool = True, squared: bool = True, **controls):
    """Sigmoid router over all ``num_routed_experts`` in float32, the choice
    steered by the bias, and the part of the result that the experts held
    here give, with no token dropped: every (token, expert) pair is sorted by
    expert, the pairs of absent experts last, and the two held stacks are
    applied by ``jax.lax.ragged_dot`` over the sorted rows, a squared ReLU
    between them. Plus the shared expert, which every chip computes alike
    (``shared`` False leaves it out: the share test counts it once;
    ``squared`` False is the tests' control: a plain ReLU)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    tokens, top = x.shape[0], cfg["num_experts_per_tok"]
    lo, hi = held_experts(cfg)
    logits = jnp.dot(x.astype(jnp.float32), p["gate"]["weight"], precision=jax.lax.Precision.HIGHEST)
    bias = jax.lax.stop_gradient(p["gate"]["e_score_correction_bias"])  # a buffer: a rule of its own moves it
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

    hidden = _relu2(grouped(rows, p["experts"]["up_proj"]), squared)
    rows = _permute(grouped(hidden, p["experts"]["down_proj"]), inverse, order).reshape(tokens, top, -1)
    scale = jnp.where(held.reshape(tokens, top), weights, 0.0).astype(rows.dtype)
    y = (rows * scale[..., None]).sum(1)
    if shared:
        wide = p["shared_experts"]
        y = y + _relu2(x @ wide["up_proj"]["weight"], squared) @ wide["down_proj"]["weight"]
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

SCOPES = {"M": "nh.mamba", "*": "nh.attn", "E": "nh.moe"}


def block(cfg, i, p, x, pre_norm: bool = True):
    """Block ``i``: ``x + mixer(norm(x))``, the mixer its character of the
    pattern names (``pre_norm`` False leaves the norm out: the tests'
    control)."""
    h = _rms_norm(x, p["norm"]["weight"], cfg["layer_norm_epsilon"]) if pre_norm else x
    mixer = {"M": mamba, "*": attention, "E": expert_layer}[kind(cfg, i)]
    with jax.named_scope(SCOPES[kind(cfg, i)]):
        return x + mixer(cfg, p["mixer"], h)


def _block_nll(x, head, targets):
    logits = jnp.einsum("bsd,vd->bsv", x, head, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def token_nll(cfg, params, inputs, targets):
    """The loss of every position (batch, sequence): ``targets`` under the
    model's next-token distribution after ``inputs``, over the slice of the
    vocabulary held. Every block under its own ``jax.checkpoint``."""
    backbone = params["backbone"]
    x = backbone["embeddings"]["weight"][inputs]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(block, cfg, i))(backbone["layers"][str(i)], x)
    with jax.named_scope("nh.head"):
        x = _rms_norm(x, backbone["norm_f"]["weight"], cfg["layer_norm_epsilon"])
        nll_of = jax.checkpoint(_block_nll)
        nll = [
            nll_of(x[:, s:s + HEAD_BLOCK], params["lm_head"]["weight"], targets[:, s:s + HEAD_BLOCK])
            for s in range(0, x.shape[1], HEAD_BLOCK)
        ]
        return jnp.concatenate(nll, axis=1)


def loss_fn(cfg, params, tokens):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1)."""
    return jnp.mean(token_nll(cfg, params, tokens[:, :-1], tokens[:, 1:]))
