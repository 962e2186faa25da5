"""The plain reference: what a checkpoint has to give back.

``fetch`` copies leaves to the host with ``jax.device_get`` and nothing
else; ``differing`` compares two such copies bit for bit through integer
views on the host (never through a float, never on the device: a TPU
bitcast flushes bf16 denormals). It imports nothing of the library under
test and takes nothing the library made.
"""

import jax
import numpy as np


def leaf_paths(tree) -> list:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def fetch(tree, only=None) -> dict:
    """``{leaf path: host array}`` of every leaf, or of the paths in ``only``."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    picked = [
        (jax.tree_util.keystr(p), x) for p, x in leaves if only is None or jax.tree_util.keystr(p) in only
    ]
    values = jax.device_get([x for _, x in picked])
    return {path: np.ascontiguousarray(np.asarray(v)) for (path, _), v in zip(picked, values)}


def bits(a: np.ndarray) -> np.ndarray:
    if a.dtype == np.bool_:
        return a.astype(np.uint8).ravel()
    return a.reshape(-1).view(f"uint{8 * a.dtype.itemsize}")


def differing_on_device(tree, want: dict, group_bytes: int = 1 << 30) -> dict:
    """``differing`` of the leaves of ``tree`` named in ``want``, fetched
    and compared a group at a time so the host never holds a second copy
    of the whole state."""
    bad, group, size = {}, set(), 0
    paths = [p for p in leaf_paths(tree) if p in want]
    for i, path in enumerate(paths):
        group.add(path)
        size += want[path].nbytes
        if size >= group_bytes or i == len(paths) - 1:
            bad.update(differing(fetch(tree, only=group), {p: want[p] for p in group}))
            group, size = set(), 0
    for path in set(want) - set(paths):
        bad[path] = "missing"
    return bad


def differing(got: dict, want: dict) -> dict:
    """Leaves of ``want`` that ``got`` does not hold bit for bit: missing,
    another dtype or shape, or any element's bits differ."""
    bad = {}
    for path, w in want.items():
        g = got.get(path)
        if g is None:
            bad[path] = "missing"
        elif g.dtype != w.dtype or g.shape != w.shape:
            bad[path] = f"{g.dtype}{g.shape} for {w.dtype}{w.shape}"
        else:
            n = int(np.count_nonzero(bits(g) != bits(w)))
            if n:
                bad[path] = f"{n} of {w.size} elements differ"
    return bad
