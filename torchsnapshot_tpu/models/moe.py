"""Mixture-of-Experts workload: expert-parallel (EP) sharded state.

The reference's closest analogue is torchrec's row-wise sharded embedding
tables (its torchrec benchmark, ``main.py:54-113``) — per-device parameter
shards that a checkpoint must save locally and reshard elastically. The
TPU-native version of that regime is MoE expert parallelism: expert weights
stacked on a leading ``experts`` axis and sharded over the mesh's ``ep``
axis, so each device holds a subset of experts.

Checkpoint-wise an EP state is simply a sharded array whose dim 0 is the
expert axis — covered by the generic sharded path — but this module pins
the workload down concretely: a runnable flax MoE layer, EP sharding rules,
and (in ``tests/test_moe.py``) save → reshard-restore across different EP
degrees, the elasticity story for scaling expert count or serving on fewer
chips.

TPU-first choices: dense token dispatch via einsum over a static top-1
gate (no dynamic shapes — XLA-friendly; capacity-style gather/scatter
dispatch is a serving concern, not a checkpoint one), bf16 experts,
expert matmuls batched on the leading axis so XLA tiles each expert's
GEMM onto the MXU.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 128
    d_ff: int = 512
    n_experts: int = 8


class MoELayer(nn.Module):
    """Top-1-gated expert FFN with experts stacked on dim 0."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        gate = nn.Dense(cfg.n_experts, use_bias=False, name="gate")(x)
        # Static one-hot dispatch: every token is evaluated against its
        # top-1 expert via einsum over the expert axis (dense compute,
        # static shapes — the jit/SPMD-friendly formulation). Hard top-1
        # routing: the gate receives no gradient through this layer (a
        # checkpoint workload, not a trainable router — softmax-weighted
        # dispatch would be the trainable variant).
        top1 = jnp.argmax(gate, axis=-1)
        onehot = jax.nn.one_hot(top1, cfg.n_experts, dtype=x.dtype)
        w_up = self.param(
            "w_up",
            nn.initializers.lecun_normal(),
            (cfg.n_experts, cfg.d_model, cfg.d_ff),
            x.dtype,
        )
        w_down = self.param(
            "w_down",
            nn.initializers.lecun_normal(),
            (cfg.n_experts, cfg.d_ff, cfg.d_model),
            x.dtype,
        )
        # [batch, seq, experts, d_ff] -> relu -> back; masked by the gate.
        h = jnp.einsum("bsd,edf->bsef", x, w_up)
        h = jax.nn.relu(h)
        y = jnp.einsum("bsef,efd->bsed", h, w_down)
        return jnp.einsum("bsed,bse->bsd", y, onehot)


def init_params(cfg: MoEConfig, seed: int = 0):
    model = MoELayer(cfg)
    x = jnp.zeros((2, 8, cfg.d_model), jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(seed), x)["params"]
    return model, params


def ep_spec(path: str) -> P:
    """EP sharding rule: expert-stacked weights shard dim 0 over ``ep``;
    the gate is replicated."""
    if "w_up" in path or "w_down" in path:
        return P("ep", None, None)
    return P()


def shard_params_ep(params, mesh: Mesh):
    """Place params on ``mesh`` (which must have an ``ep`` axis)."""

    from ..tricks.train_state import _path_str

    def place(path, leaf):
        spec = ep_spec(_path_str(path))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)
