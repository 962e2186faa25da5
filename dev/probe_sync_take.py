"""Chip probe (``chiprun -- python dev/probe_sync_take.py [--state ...]``):
what a whole-state synchronous ``Snapshot.take`` is made of, from its own
stats, and whether every bit survives it.

Two states of plain arrays on the chip, in the shapes of the benchmark's big
leaves, three times over (weights and both moments):

- ``pythia`` (PR 43): ``pythia-6.9b-d6``'s 80 bf16 matrices, 9.727 GB, every
  width a multiple of 128 (cell 2's set-up take);
- ``nemotron`` (PR 48): ``nemotron-3-nano-30b-a3b-ep8``'s 42 leaves over the
  piece size, 5.89 GB: the ``(16, 2688, 1856)`` and ``(2688, 10304)`` leaves
  the chip holds column first (2.58 GB, which a take re-laid on its event
  loop until PR 48 cut them on the device) beside the aligned
  ``(16, 1856, 2688)``, ``(4096, 2688)`` and ``(16384, 2688)`` ones.

Each is taken twice (the second take of a commit that moves whole leaves finds
their host copies cached and only writes) and ``LAST_SYNC_DRAIN_STATS`` is
printed with the stage / write split first and, where the commit has them,
the stage's counters of cut leaves and recycled pages. ``patterns``: every
bfloat16 bit pattern (NaN payloads, denormals, -0) through both movers, and a
float32 / int8 / uint16 sample, taken synchronously and read back from the
snapshot; prints how many elements differ. ``PERF.md`` sections 5 and 6."""

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from torchsnapshot_tpu import Snapshot, StateDict, native
from torchsnapshot_tpu import snapshot as snapshot_mod

PYTHIA_LAYER = (("qkv", (4096, 12288)), ("dense", (4096, 4096)), ("h_to_4h", (4096, 16384)), ("4h_to_h", (16384, 4096)))
NEMOTRON = (
    [(f"up_proj_{i}", (16, 2688, 1856)) for i in range(4)]
    + [(f"down_proj_{i}", (16, 1856, 2688)) for i in range(4)]
    + [(f"in_proj_{i}", (2688, 10304)) for i in range(4)]
    + [(f"out_proj_{i}", (4096, 2688)) for i in range(4)]
    + [("embed", (16384, 2688)), ("head", (16384, 2688))]
)
# dtype, shape: through the re-laying mover and through the DMA cut.
PATTERNS = (
    ("bfloat16", (16, 2688, 1856)),
    ("bfloat16", (2688, 10304)),
    ("bfloat16", (16, 1856, 2688)),
    ("bfloat16", (16384, 2688)),
    ("float32", (2688, 10304)),
    ("int8", (2688, 10304)),
    ("uint16", (16, 2688, 1856)),
    ("float32", (4096, 2688)),
)
FIRST = (
    "wall_s", "stage_busy_s", "io_busy_s", "overlap_s", "stage_d2h_s", "stage_d2h_sum_s", "stage_gather_sum_s",
    "stage_serialize_s", "write_work_sum_s", "write_copy_sum_s", "mount_write_s", "mount_write_sum_s", "write_queue_sum_s",
)


def shapes_of(state: str):
    for copy in ("params", "mu", "nu"):
        if state == "pythia":
            for i in range(6):
                for name, shape in PYTHIA_LAYER:
                    yield f"{copy}_{i}_{name}", shape
            for name in ("embed_in", "embed_out"):
                yield f"{copy}_{name}", (50432, 4096)
        else:
            for name, shape in NEMOTRON:
                yield f"{copy}_{name}", shape


def take_state(state: str, root: str) -> None:
    key = jax.random.PRNGKey(0)
    tree = {}
    for name, shape in shapes_of(state):
        key, sub = jax.random.split(key)
        tree[name] = jax.random.normal(sub, shape, jnp.bfloat16)
    jax.block_until_ready(tree)
    nbytes = sum(v.nbytes for v in tree.values())
    for i in range(2):
        path = os.path.join(root, f"{state}{i}")
        t0 = time.perf_counter()
        Snapshot.take(path, {"m": StateDict(**tree)})
        wall = time.perf_counter() - t0
        stats = dict(snapshot_mod.LAST_SYNC_DRAIN_STATS)
        out = {"state": state, "take": i, "take_wall_s": round(wall, 3), "gb": round(nbytes / 1e9, 3), "gbps": round(nbytes / 1e9 / wall, 3)}
        out.update((k, round(stats.pop(k), 4)) for k in FIRST if k in stats)
        out.update((k, round(v, 4)) for k, v in sorted(stats.items()))
        counters = Snapshot.last_telemetry.metrics.as_dict() if Snapshot.last_telemetry is not None else {}
        out.update((k, v) for k, v in sorted(counters.items()) if k.startswith(("stage.", "d2h.pie", "d2h.hinted", "d2h.window")))
        print(json.dumps(out), flush=True)
        shutil.rmtree(path)
    for v in tree.values():
        v.delete()


def take_patterns(root: str) -> None:
    """Every pattern of the 8- and 16-bit types in turn (random words for
    float32), put from the host, taken synchronously, read back."""
    rng = np.random.default_rng(48)
    host = {}
    for dtype, shape in PATTERNS:
        n = int(np.prod(shape))
        if dtype == "float32":
            bits = rng.integers(0, 2**32, size=n, dtype=np.uint32)
            # every exponent with NaN payloads and denormals among them
            bits[: 1 << 16] = (np.arange(1 << 16, dtype=np.uint32) << 16) | 0x1234
            arr = bits.view(np.float32)
        else:
            width = np.dtype(jnp.dtype(dtype)).itemsize * 8
            bits = (np.arange(n, dtype=np.uint64) % (1 << width)).astype(f"uint{width}")
            arr = bits.view(jnp.dtype(dtype))
        host[f"{dtype}_{'x'.join(map(str, shape))}"] = arr.reshape(shape)
    tree = {k: jax.device_put(v) for k, v in host.items()}
    jax.block_until_ready(tree)
    path = os.path.join(root, "patterns")
    Snapshot.take(path, {"m": StateDict(**tree)})
    counters = Snapshot.last_telemetry.metrics.as_dict()
    differing = {}
    for name, want in host.items():
        got = np.asarray(Snapshot(path).read_object(f"0/m/{name}"))
        differing[name] = int(np.count_nonzero(got.reshape(-1).view(np.uint8) != want.reshape(-1).view(np.uint8)))
    print(
        json.dumps(
            {
                "patterns": differing,
                "elements_differing": sum(differing.values()),
                "verify": Snapshot(path).verify(),
                **{k: v for k, v in sorted(counters.items()) if k.startswith(("stage.", "d2h.pie"))},
            }
        ),
        flush=True,
    )
    shutil.rmtree(path)
    for v in tree.values():
        v.delete()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--state", nargs="*", default=["patterns", "nemotron"], choices=("patterns", "nemotron", "pythia"))
    parser.add_argument(
        "--set", nargs="*", default=[], metavar="MODULE.NAME=MIB",
        help="a constant of the library in MiB for this run, e.g. d2h.SYNC_PIECE_WINDOW_BYTES=64: how its value was chosen",
    )
    args = parser.parse_args()
    for setting in args.set:
        target, mib = setting.split("=")
        module, name = target.rsplit(".", 1)
        setattr(importlib.import_module(f"torchsnapshot_tpu.{module}"), name, int(float(mib) * 1024 * 1024))
        print("set", target, mib, "MiB", flush=True)
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"no accelerator: {jax.devices()}")
    print("engine", native.load_native() is not None, native.loaded_path(), flush=True)
    root = tempfile.mkdtemp(prefix="probe-sync-take-")
    try:
        for state in args.state:
            if state == "patterns":
                take_patterns(root)
            else:
                take_state(state, root)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
