"""The train state the benchmark checkpoints, and the step that runs beside a
save: what is true of any architecture, written once.

An architecture is ``perfbench/models/<model_type>.py`` (found by
``run.find_architecture``) and is handed in as ``arch``: its parameter tree
as shape and dtype per leaf, the rule that makes a leaf from a key, a
``PartitionSpec`` per parameter path, its loss and the range of its tokens.
Here: the mesh from the configuration's ``layout``, a sharding per leaf of
the state, ``optax.adamw`` whose moments take their parameter's dtype and
sharding, the donated step, weights and batches from the seed. No line of
this file names a tensor, a width or a parameter's dtype.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))) for k in path)


def make_mesh(devices, layout: dict, transposed: bool = False):
    """``layout["mesh"]`` is axis name to size, in order, or null (one device).
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


def state_shardings(arch, abstract_state, mesh, device):
    """A sharding per leaf of the train state: the architecture's rule for
    the parameter on a mesh (an axis that does not divide its dimension is
    dropped), else the one device. A moment follows its parameter: a state
    leaf takes the rule of the parameter whose path its own ends with, and
    one that ends with none (the optimizer's count) is replicated."""
    if mesh is None:
        return jax.tree.map(lambda _: SingleDeviceSharding(device), abstract_state)
    params = [path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(abstract_state["params"])[0]]

    def one(path, leaf):
        path = "/" + path_str(path)
        owners = [p for p in params if path.endswith("/" + p)]
        spec = arch.param_spec(max(owners, key=len)) if owners else P()
        fitted = [
            axis if d < len(leaf.shape) and leaf.shape[d] % mesh.shape[axis] == 0 else None
            for d, axis in enumerate(spec)
        ]
        return NamedSharding(mesh, P(*fitted[: len(leaf.shape)]))

    return jax.tree_util.tree_map_with_path(one, abstract_state)


class Job:
    """One configuration's train state, step and data on the devices given.

    ``devices`` may be described devices of a topology (compile-only
    rehearsal): nothing here touches a device until ``init_state`` /
    ``make_batches`` are called."""

    def __init__(self, arch, cfg: dict, devices, transposed: bool = False) -> None:
        self.arch = arch
        self.cfg = cfg
        self.job = cfg["job"]
        self.mesh = make_mesh(devices, cfg["layout"], transposed)
        self.device = devices[0]
        self.tx = optax.adamw(self.job["learning_rate"])
        self._zeros = {}
        if self.mesh is None:
            replicas, self.batch_sharding = 1, SingleDeviceSharding(self.device)
        else:  # the batch is split over the first axis the layout's mesh names
            batch_axis = self.mesh.axis_names[0]
            replicas, self.batch_sharding = self.mesh.shape[batch_axis], NamedSharding(self.mesh, P(batch_axis))
        # One more position than the sequence: inputs and shifted targets.
        self.batch_shape = (self.job["micro_batch"] * replicas, self.job["seq_len"] + 1)
        self.abstract = jax.eval_shape(self._build, jax.random.PRNGKey(0))
        self.shardings = state_shardings(arch, self.abstract, self.mesh, self.device)

        def pb_train_step(state, tokens):
            loss, grads = jax.value_and_grad(lambda p: arch.loss_fn(cfg, p, tokens))(state["params"])
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
        """Every parameter from its own key, in the tree's leaf order."""
        leaves, treedef = jax.tree_util.tree_flatten_with_path(self.arch.param_tree(self.cfg))
        keys = jax.random.split(key, len(leaves))
        params = treedef.unflatten(
            [self.arch.init_leaf(path_str(p), leaf, k) for (p, leaf), k in zip(leaves, keys)]
        )
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
        tokens = self.arch.token_range(self.cfg)

        def pb_batches(key):
            return [
                jax.random.randint(k, self.batch_shape, 0, tokens, jnp.int32)
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


def tree_size(tree) -> int:
    """Elements of a tree, whatever their dtypes."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def free_tree(tree) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
