"""GPT-NeoX as EleutherAI/pythia publishes it (``config.json``): fused
``query_key_value`` with bias, rotary embedding on the first ``rotary_pct``
of each head, parallel residual (``x + attn(ln1(x)) + mlp(ln2(x))``), two
LayerNorms with bias per layer, untied ``embed_out``. Plain ``jax.numpy``
over a nested dict of the published tensor names, every leaf bf16.

What an architecture gives the harness (``perfbench/README.md``), and all
it gives: ``param_tree``, ``init_leaf``, ``param_spec``, ``loss_fn``,
``token_range``, ``TINY``, ``PUBLISHED``. The library under test never sees
this file: it is given the state tree and nothing else.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PARAM_DTYPE = jnp.bfloat16

# EleutherAI/pythia-6.9b config.json: the widths no configuration may change
# unless its ``reduced`` lists the key (perfbench/tests/test_contract.py).
PUBLISHED = {
    "hidden_size": 4096, "intermediate_size": 16384, "num_attention_heads": 32,
    "num_hidden_layers": 32, "vocab_size": 50432, "max_position_embeddings": 2048,
    "rotary_pct": 0.25, "rotary_emb_base": 10000, "layer_norm_eps": 1e-05,
    "hidden_act": "gelu", "use_parallel_residual": True, "tie_word_embeddings": False,
}

TINY = {  # --platform cpu --tiny: toy widths, a dry run that reports no time
    "hidden_size": 64, "intermediate_size": 256, "num_attention_heads": 4, "vocab_size": 512,
}


def param_tree(cfg: dict) -> dict:
    """Shape and dtype of every parameter, under the published names."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]

    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, PARAM_DTYPE)

    def linear(n_in, n_out):
        return {"weight": leaf(n_in, n_out), "bias": leaf(n_out)}

    def norm():
        return {"weight": leaf(d), "bias": leaf(d)}

    layer = {
        "input_layernorm": norm(),
        "post_attention_layernorm": norm(),
        "attention": {"query_key_value": linear(d, 3 * d), "dense": linear(d, d)},
        "mlp": {"dense_h_to_4h": linear(d, f), "dense_4h_to_h": linear(f, d)},
    }
    return {
        "embed_in": {"weight": leaf(v, d)},
        "layers": {str(i): layer for i in range(cfg["num_hidden_layers"])},
        "final_layer_norm": norm(),
        "embed_out": {"weight": leaf(d, v)},
    }


def _is_norm(path: str) -> bool:
    return "layernorm" in path or "layer_norm" in path


def init_leaf(path: str, leaf, key):
    """The parameter at ``path`` from its key: ones for a norm's weight,
    ``0.02 * normal`` otherwise."""
    if _is_norm(path) and path.endswith("weight"):
        return jnp.ones(leaf.shape, leaf.dtype)
    return (0.02 * jax.random.normal(key, leaf.shape, jnp.float32)).astype(leaf.dtype)


def param_spec(path: str) -> P:
    """FSDP+TP rules of ``torchsnapshot_tpu/models/transformer.py:param_spec``
    for the NeoX names, over a layout whose mesh names ``dp`` and ``tp``:
    ``tp`` on the contraction-adjacent dimension (heads, MLP hidden,
    vocabulary), ``dp`` (FSDP) on the other; norms and biases replicated."""
    if path.endswith("bias") or _is_norm(path):
        return P()
    if "query_key_value" in path or "dense_h_to_4h" in path:
        return P("dp", "tp")
    if "attention/dense" in path or "dense_4h_to_h" in path:
        return P("tp", "dp")
    if "embed_in" in path or "embed_out" in path:
        return P("dp", "tp")
    return P()


def token_range(cfg: dict) -> int:
    """Token ids of a batch are drawn from ``[0, token_range)``."""
    return cfg["vocab_size"]


def _layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = jnp.square(x32 - mean).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["weight"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _linear(x, p):
    return x @ p["weight"] + p["bias"]


def _rotary(x, base, rot):
    """x: (B, S, H, hd); rotate the first ``rot`` dims of each head, NeoX
    style (halves, not interleaved pairs)."""
    seq = x.shape[1]
    inv = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    xr, xp = x[..., :rot].astype(jnp.float32), x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([(xr * cos + rotated * sin).astype(x.dtype), xp], -1)


def _block(cfg, p, x):
    b, s, d = x.shape
    heads = cfg["num_attention_heads"]
    hd = d // heads
    eps = cfg["layer_norm_eps"]
    qkv = _linear(_layer_norm(x, p["input_layernorm"], eps), p["attention"]["query_key_value"])
    q, k, v = jnp.split(qkv.reshape(b, s, heads, 3 * hd), 3, axis=-1)
    rot = int(hd * cfg["rotary_pct"])
    q, k = _rotary(q, cfg["rotary_emb_base"], rot), _rotary(k, cfg["rotary_emb_base"], rot)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1).astype(x.dtype)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    attn = _linear(attn, p["attention"]["dense"])
    h = _linear(_layer_norm(x, p["post_attention_layernorm"], eps), p["mlp"]["dense_h_to_4h"])
    mlp = _linear(jax.nn.gelu(h, approximate=False), p["mlp"]["dense_4h_to_h"])
    return x + attn + mlp  # use_parallel_residual


def loss_fn(cfg, params, tokens):
    """Mean next-token loss of ``tokens`` (batch, sequence + 1), every
    block under ``jax.checkpoint``."""
    x = params["embed_in"]["weight"][tokens[:, :-1]]
    block = jax.checkpoint(lambda p, h: _block(cfg, p, h))
    for i in range(cfg["num_hidden_layers"]):
        x = block(params["layers"][str(i)], x)
    x = _layer_norm(x, params["final_layer_norm"], cfg["layer_norm_eps"])
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["embed_out"]["weight"], preferred_element_type=jnp.float32
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)
