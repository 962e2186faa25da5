"""The controls: a run of the harness with the path under test broken
underneath, which has to come out with ``correct`` false.

    python3 perfbench/tests/control.py --break round_fp8 --workload <cell> --seed N --seconds S
    (further arguments go to perfbench/run.py; --platform cpu --tiny for the test suite)

``round_fp8``  every restored floating leaf is rounded through float8_e4m3fn
               on its way back: the nearest precision below the bf16 the
               configurations state. Breaks "restore is bit-exact".
``flip_bit``   one bit of one element of one restored leaf is flipped.
``no_commit``  ``.snapshot_metadata`` is never written: ``wait()`` returns on
               a snapshot that did not commit. Breaks "a returned wait()
               means a complete snapshot".
``host_capture``  the async fork is given no HBM, so every leaf is captured
               through host RAM inside the stall: a slower path taken in
               silence, in a mix whose fork fits (``fork_fits``).
``late_compile``  every take and restore first builds a program the warm-up
               never saw: something compiles inside the window.

The benchmark's own runs never come through this file.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


KINDS = ("round_fp8", "flip_bit", "no_commit", "host_capture", "late_compile")


def install(kind: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.tricks.train_state import PyTreeStateful

    if kind == "no_commit":
        Snapshot._write_snapshot_metadata = staticmethod(lambda *args, **kwargs: None)
        return
    if kind == "late_compile":
        fresh = iter(range(1, 1 << 30))

        def compiling(entry):
            def call(*args, **kwargs):
                jax.block_until_ready(jax.jit(lambda x, c=next(fresh): x + c)(jnp.zeros(())))
                return entry(*args, **kwargs)
            return call

        Snapshot.async_take = staticmethod(compiling(Snapshot.async_take))
        Snapshot.restore = compiling(Snapshot.restore)
        return
    load = PyTreeStateful.load_state_dict

    def round_fp8(tree):
        # Leaf by leaf through the host, the restored leaf freed before its
        # rounded copy is put back: a state that fills the chip (the restore's
        # targets are still alive here) has no room for a second copy.
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        for i, x in enumerate(leaves):
            if jnp.issubdtype(x.dtype, jnp.floating):
                host, sharding = np.asarray(x), x.sharding
                x.delete()
                rounded = host.astype(jnp.float8_e4m3fn).astype(host.dtype)
                leaves[i] = jax.block_until_ready(jax.device_put(rounded, sharding))
        return treedef.unflatten(leaves)

    def flip_first(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        host = np.array(leaves[0])
        flat = host.reshape(-1).view(f"uint{8 * host.dtype.itemsize}")
        flat[0] ^= 1
        leaves[0] = jax.device_put(host, leaves[0].sharding)
        return treedef.unflatten(leaves)

    def broken(self, state_dict):
        load(self, state_dict)
        value = self._holder.value
        self._holder.value = round_fp8(value) if kind == "round_fp8" else flip_first(value)

    PyTreeStateful.load_state_dict = broken


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--break", dest="kind", required=True, choices=KINDS)
    args, rest = parser.parse_known_args()
    from perfbench import run

    if args.kind == "host_capture":  # a knob of the library, read from the environment
        os.environ["TORCHSNAPSHOT_TPU_ASYNC_FORK_HBM_LIMIT_BYTES"] = "1"
        return run.main(rest)
    # run.main's preflight sets the platform before jax is imported; the
    # patch needs jax, so it goes in once preflight has run.
    preflight = run.preflight

    def preflight_then_break(*a, **kw):
        ctx = preflight(*a, **kw)
        install(args.kind)
        return ctx

    run.preflight = preflight_then_break
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
