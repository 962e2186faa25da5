"""Flagship workload: a decoder-only transformer with TP/FSDP shardings.

The reference ships no model code of its own — its benchmarks synthesize
large DDP/FSDP/torchrec workloads to checkpoint (its FSDP benchmark,
``main.py:35-72``, builds a 1.9B-param transformer). This module is the TPU-native
equivalent: a flax decoder-only LM sized like the reference's FSDP benchmark,
plus Megatron-style sharding rules over a ``(dp, tp)`` mesh so benchmarks,
the multi-chip dry run, and the torchrec-style embedding tests exercise the
same sharded-checkpoint paths a real pjit training job would.

TPU-first choices: bf16 params/activations by default (MXU-native), einsum
attention with static shapes (single XLA fusion domain), pre-LN blocks, and
parameters laid out so the TP axis maps to contraction dims XLA tiles onto
the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16  # activation/computation dtype
    param_dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        qkv = nn.DenseGeneral(
            features=(3, cfg.n_heads, cfg.head_dim),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="qkv",
        )(h)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        scale = 1.0 / np.sqrt(cfg.head_dim)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        seq = x.shape[1]
        mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        logits = jnp.where(mask, logits, jnp.finfo(cfg.dtype).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(cfg.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        attn_out = nn.DenseGeneral(
            features=cfg.d_model,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="proj",
        )(attn)
        x = x + attn_out
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        up = nn.Dense(
            cfg.d_ff, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="up"
        )(h)
        down = nn.Dense(
            cfg.d_model, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="down"
        )(jax.nn.gelu(up))
        return x + down


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="embed",
        )(tokens)
        pos = nn.Embed(
            cfg.max_seq_len,
            cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="pos_embed",
        )(jnp.arange(tokens.shape[1])[None, :])
        x = x + pos
        for i in range(cfg.n_layers):
            x = Block(cfg, name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        # Tied-free output head.
        return nn.Dense(
            cfg.vocab_size,
            dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            name="lm_head",
        )(x)


def init_params(cfg: TransformerConfig, seed: int = 0):
    model = Transformer(cfg)
    tokens = jnp.zeros((1, min(8, cfg.max_seq_len)), dtype=jnp.int32)
    return model, model.init(jax.random.PRNGKey(seed), tokens)["params"]


# ---------------------------------------------------------------------------
# Sharding rules: Megatron-style TP + FSDP over a (dp, tp) mesh
# ---------------------------------------------------------------------------

def param_spec(path: str, fsdp: bool = True) -> P:
    """PartitionSpec for a param path (joined with '/').

    TP axis shards the contraction-adjacent dims (qkv heads, MLP hidden,
    vocab); the dp axis FSDP-shards the other large dim, so the arrangement
    matches what a real pjit job would checkpoint.
    """
    dp = "dp" if fsdp else None
    if "qkv/kernel" in path:  # (d_model, 3, heads, head_dim)
        return P(dp, None, "tp", None)
    if "proj/kernel" in path:  # (heads, head_dim, d_model)
        return P("tp", None, dp)
    if "up/kernel" in path:  # (d_model, d_ff)
        return P(dp, "tp")
    if "down/kernel" in path:  # (d_ff, d_model)
        return P("tp", dp)
    if "pos_embed/embedding" in path:  # must precede the embed match below
        return P(dp, None)
    if "embed/embedding" in path or "lm_head/kernel" in path:
        return P(dp, "tp")
    return P()  # layer norms, biases: replicated


def shard_params(params, mesh: Mesh, fsdp: bool = True):
    """Place a param pytree on ``mesh`` under the TP/FSDP rules, falling back
    to replication when a dim isn't divisible by its mesh axis."""

    from ..tricks.train_state import _path_str

    def place(path, leaf):
        spec = param_spec(_path_str(path), fsdp=fsdp)
        spec = fit_spec(spec, leaf.shape, mesh)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


def fit_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """``spec`` with every axis that does not divide its dimension (or has
    no dimension) dropped, so any leaf can be placed on any mesh."""
    fitted = []
    for d, axis in enumerate(spec):
        if axis is None or d >= len(shape):
            fitted.append(None)
            continue
        size = int(np.prod([mesh.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))]))
        fitted.append(axis if shape[d] % size == 0 else None)
    return P(*fitted)


def loss_fn(model: Transformer, params, tokens: jax.Array) -> jax.Array:
    logits = model.apply({"params": params}, tokens[:, :-1])
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)
