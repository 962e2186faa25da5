"""The train state the benchmark checkpoints, and the step that runs beside a save.

GPT-NeoX as EleutherAI/pythia publishes it (``config.json``): fused
``query_key_value`` with bias, rotary embedding on the first ``rotary_pct``
of each head, parallel residual (``x + attn(ln1(x)) + mlp(ln2(x))``), two
LayerNorms with bias per layer, untied ``embed_out``. Plain ``jax.numpy``
over a nested dict of the published tensor names; params bf16,
``optax.adamw`` whose moments take the params' dtype. The library under
test never sees this file: it is given the state tree and nothing else.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

PARAM_DTYPE = jnp.bfloat16


def param_shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]

    def linear(n_in, n_out):
        return {"weight": (n_in, n_out), "bias": (n_out,)}

    def norm():
        return {"weight": (d,), "bias": (d,)}

    layer = {
        "input_layernorm": norm(),
        "post_attention_layernorm": norm(),
        "attention": {"query_key_value": linear(d, 3 * d), "dense": linear(d, d)},
        "mlp": {"dense_h_to_4h": linear(d, f), "dense_4h_to_h": linear(f, d)},
    }
    return {
        "embed_in": {"weight": (v, d)},
        "layers": {str(i): layer for i in range(cfg["num_hidden_layers"])},
        "final_layer_norm": norm(),
        "embed_out": {"weight": (d, v)},
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def param_spec(path: str) -> P:
    """FSDP+TP rules of ``torchsnapshot_tpu/models/transformer.py:param_spec``
    for the NeoX names: ``tp`` on the contraction-adjacent dimension (heads,
    MLP hidden, vocabulary), ``dp`` (FSDP) on the other; norms and biases
    replicated."""
    if path.endswith("bias") or "layernorm" in path or "layer_norm" in path:
        return P()
    if "query_key_value" in path or "dense_h_to_4h" in path:
        return P("dp", "tp")
    if "attention/dense" in path or "dense_4h_to_h" in path:
        return P("tp", "dp")
    if "embed_in" in path or "embed_out" in path:
        return P("dp", "tp")
    return P()


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))) for k in path)


def make_mesh(devices, layout: dict, transposed: bool = False):
    """``layout["mesh"]`` is ``{"dp": 2, "tp": 2}`` or null (one device).
    ``transposed`` gives the mesh a resharded restore targets: the same
    axis names over the transposed device grid, so the chip that held block
    (i, j) of a leaf is handed block (j, i)."""
    if not layout.get("mesh"):
        return None
    names = tuple(layout["mesh"])
    grid = np.array(devices[: int(np.prod(list(layout["mesh"].values())))]).reshape(
        [layout["mesh"][n] for n in names]
    )
    return Mesh(grid.T if transposed else grid, names)


def state_shardings(abstract_state, mesh, device):
    """A sharding per leaf of the train state: the rules above on a mesh
    (an axis that does not divide its dimension is dropped), else the one
    device. Moments follow their parameter by path."""

    def one(path, leaf):
        if mesh is None:
            return SingleDeviceSharding(device)
        spec = param_spec(_path_str(path))
        fitted = [
            axis if d < len(leaf.shape) and leaf.shape[d] % mesh.shape[axis] == 0 else None
            for d, axis in enumerate(spec)
        ]
        return NamedSharding(mesh, P(*fitted[: len(leaf.shape)]))

    return jax.tree_util.tree_map_with_path(one, abstract_state)


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


class Job:
    """One configuration's train state, step and data on the devices given.

    ``devices`` may be described devices of a topology (compile-only
    rehearsal): nothing here touches a device until ``init_state`` /
    ``make_batches`` are called."""

    def __init__(self, cfg: dict, devices, transposed: bool = False) -> None:
        self.cfg = cfg
        self.job = cfg["job"]
        self.mesh = make_mesh(devices, cfg["layout"], transposed)
        self.device = devices[0]
        self.tx = optax.adamw(self.job["learning_rate"])
        self._zeros = {}
        dp = self.mesh.shape["dp"] if self.mesh is not None else 1
        # One more position than the sequence: inputs and shifted targets.
        self.batch_shape = (self.job["micro_batch"] * dp, self.job["seq_len"] + 1)
        self.abstract = jax.eval_shape(self._build, jax.random.PRNGKey(0))
        self.shardings = state_shardings(self.abstract, self.mesh, self.device)
        self.batch_sharding = (
            NamedSharding(self.mesh, P("dp")) if self.mesh is not None
            else SingleDeviceSharding(self.device)
        )

        def pb_train_step(state, tokens):
            loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, tokens))(state["params"])
            updates, opt_state = self.tx.update(grads, state["opt_state"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            return {"params": params, "opt_state": opt_state}, loss

        # Donation is the point: the trainer reuses the buffers the snapshot
        # was given, so the snapshot must have detached itself from them.
        self.train_step = jax.jit(
            pb_train_step,
            donate_argnums=0,
            in_shardings=(self.shardings, self.batch_sharding),
            out_shardings=(self.shardings, None),
        )

    def _build(self, key):
        shapes = param_shapes(self.cfg)
        leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
        keys = jax.random.split(key, len(leaves))
        paths = [
            _path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]
        ]

        def leaf(path, shape, k):
            if ("layernorm" in path or "layer_norm" in path) and path.endswith("weight"):
                return jnp.ones(shape, PARAM_DTYPE)
            return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(PARAM_DTYPE)

        params = treedef.unflatten([leaf(p, s, k) for p, s, k in zip(paths, leaves, keys)])
        return {"params": params, "opt_state": self.tx.init(params)}

    def init_state(self, seed: int):
        """Weights from the seed, made on the device in one jitted call in
        the dtype they are trained in."""

        def pb_init(key):
            return self._build(key)

        return jax.jit(pb_init, out_shardings=self.shardings)(jax.random.PRNGKey(seed % (1 << 31)))

    def part(self, tree, part: str):
        """The part of a state-shaped tree that a take saves: all of it
        (``"state"``) or one of its top-level entries (``"params"``)."""
        return tree if part == "state" else tree[part]

    def zero_targets(self, part: str):
        """Zero arrays on the device under the live shardings, as a
        restarted job has before it restores. One program per part."""
        if part not in self._zeros:
            tree = self.part(self.abstract, part)

            def pb_zeros():
                return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), tree)

            self._zeros[part] = jax.jit(pb_zeros, out_shardings=self.part(self.shardings, part))
        return self._zeros[part]()

    def make_batches(self, seed: int, count: int):
        """``count`` token batches from the seed, on the device: the step
        cycles through them, so no program but the step runs in the window."""
        vocab = self.cfg["vocab_size"]

        def pb_batches(key):
            return [
                jax.random.randint(k, self.batch_shape, 0, vocab, jnp.int32)
                for k in jax.random.split(key, count)
            ]

        key = jax.random.PRNGKey((seed + 1) % (1 << 31))
        return jax.jit(pb_batches, out_shardings=[self.batch_sharding] * count)(key)

    def abstract_args(self):
        """Shapes with shardings for a compile without devices."""
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            self.abstract, self.shardings,
        )
        tokens = jax.ShapeDtypeStruct(self.batch_shape, jnp.int32, sharding=self.batch_sharding)
        return state, tokens


def tree_nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def free_tree(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
