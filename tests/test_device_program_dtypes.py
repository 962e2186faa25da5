"""Sub-32-bit float leaves never enter a device program that would rewrite
their bits.

On the TPU a slice or bitcast of bfloat16 flushes denormals, and a copy,
slice or bitcast of float16 / float8 rewrites NaN payloads (``chip_smoke.py``
checks every bit pattern there). The CPU backend is exact throughout, so
what this suite can pin is the ROUTING: which leaves chunk, subdivide,
device-pack and fork — by dtype alone, on every backend.
"""

import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.device_programs import copy_preserves_bits, slice_preserves_bits
from torchsnapshot_tpu.io_preparers.chunked_array import should_chunk
from torchsnapshot_tpu.io_preparers.sharded_array import shard_pieces
from torchsnapshot_tpu.utils import knobs


def all_patterns(dtype, rows: int) -> np.ndarray:
    """Every bit pattern of ``dtype``, tiled to ``(rows, 256)``: denormals,
    both zeros, infinities and every NaN payload included."""
    dt = np.dtype(dtype)
    bits = np.arange(1 << (8 * dt.itemsize), dtype=f"uint{8 * dt.itemsize}")
    return np.resize(bits, (rows, 256)).view(dt)


SMALL_FLOATS = [
    ml_dtypes.bfloat16,
    np.float16,
    ml_dtypes.float8_e4m3fn,
    ml_dtypes.float8_e5m2,
    ml_dtypes.float8_e4m3fnuz,
]


def test_which_dtypes_each_device_program_preserves() -> None:
    for dt in SMALL_FLOATS:
        assert not slice_preserves_bits(dt), dt
    for dt in (np.float32, np.float64, np.int8, np.uint16, np.int32, np.bool_, np.complex64):
        assert slice_preserves_bits(dt) and copy_preserves_bits(dt), dt
    assert slice_preserves_bits(ml_dtypes.int4)  # not a float: never device-sliced as bytes anyway
    # The fork (``jnp.copy``) kept every bfloat16 pattern on the chip; the
    # other small floats lost NaN payloads.
    assert copy_preserves_bits(ml_dtypes.bfloat16)
    for dt in SMALL_FLOATS[1:]:
        assert not copy_preserves_bits(dt), dt


def test_small_float_arrays_are_not_chunked_or_subdivided() -> None:
    with knobs.override_max_chunk_size_bytes(1024):
        for dt in SMALL_FLOATS:
            host = all_patterns(dt, 64)
            assert not should_chunk(host), dt  # by dtype alone: one layout on every rank
            assert not should_chunk(jax.device_put(host)), dt
        assert should_chunk(jax.device_put(np.zeros((64, 256), np.float32)))
    offsets, sizes = [0, 0], [64, 256]
    bf16 = jax.device_put(all_patterns(ml_dtypes.bfloat16, 64))
    assert shard_pieces(bf16, offsets, sizes, 1024) == [(offsets, sizes, bf16)]
    f32 = jax.device_put(np.zeros((64, 256), np.float32))
    pieces = shard_pieces(f32, offsets, sizes, 16 * 1024)
    assert [(o, s) for o, s, _ in pieces] == [
        ([0, c], [64, 64]) for c in range(0, 256, 64)  # along the largest dim
    ]
    assert all(p.shape == (64, 64) for _, _, p in pieces)


@pytest.mark.parametrize("mode", ["take", "async_take"])
def test_every_bit_pattern_round_trips_and_says_which_path(tmp_path, mode) -> None:
    """Slab batching on, every pattern of every small float put from the
    host: the restore is bit-exact, every big leaf is one object of its own
    bytes, float16/float8 were host-captured instead of forked, and
    small-float slabs were packed on the host."""
    rows = 512  # 128 KiB per 1-byte leaf: four hash grains set below
    host = {f"big_{np.dtype(dt).name}": all_patterns(dt, rows) for dt in SMALL_FLOATS}
    rng = np.random.default_rng(0)
    # Random BITS as float32: denormals and NaN payloads in a dtype every
    # device program preserves.
    host["big_float32"] = rng.integers(0, 1 << 32, (rows, 256), dtype=np.uint32).view(np.float32)
    for dt in SMALL_FLOATS + [np.float32, np.int8]:
        for i in range(3):
            bits = rng.integers(0, 256, (40, np.dtype(dt).itemsize * 8), dtype=np.uint8)
            host[f"small_{np.dtype(dt).name}_{i}"] = bits.view(dt)
    state = StateDict(**{k: jax.device_put(v) for k, v in host.items()})
    path = str(tmp_path / mode)
    with knobs.override_hash_chunk_bytes(
        32 * 1024
    ), knobs.override_batching_enabled(True), knobs.override_slab_size_threshold_bytes(
        4096
    ):
        if mode == "take":
            Snapshot.take(path, {"s": state})
        else:
            Snapshot.async_take(path, {"s": state}).wait()
    metrics = Snapshot.last_telemetry.metrics.as_dict()

    # Each big leaf reached the host whole and is one object of its bytes.
    for k, v in host.items():
        if k.startswith("big_"):
            with open(os.path.join(path, "0", "s", k), "rb") as f:
                assert f.read() == v.tobytes(), k
    assert metrics["batcher.slabs_device_packed"] >= 1  # float32 + int8 members
    assert metrics["batcher.slabs_host_packed"] >= 1
    if mode == "async_take":
        never_forked = [k for k, v in host.items() if not copy_preserves_bits(v.dtype)]
        assert metrics["capture.dtype_captured_leaves"] == len(never_forked)
        assert metrics["capture.host_captured_leaves"] == len(never_forked)
        assert metrics["capture.forked_leaves"] == len(host) - len(never_forked)

    targets = StateDict(**{k: jnp.zeros(v.shape, v.dtype) for k, v in host.items()})
    Snapshot(path).restore({"s": targets})
    for k, v in host.items():
        got = np.ascontiguousarray(np.asarray(targets[k]))
        assert got.dtype == v.dtype, k
        assert np.array_equal(got.view(np.uint8), v.view(np.uint8)), k
