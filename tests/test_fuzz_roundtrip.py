"""Seeded randomized round-trips: arbitrary nested app state must survive
take -> restore bit-exactly (flatten/inflate + every preparer, reference
model: the per-component unit tests, but composed randomly).
"""

import copy

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.test_utils import assert_state_dict_eq
from torchsnapshot_tpu.utils import knobs

_DTYPES = [
    np.float32,
    np.float64,
    np.float16,
    np.int8,
    np.int32,
    np.int64,
    np.uint8,
    np.bool_,
]


def _random_value(rng: np.random.Generator, depth: int):
    roll = rng.integers(0, 10 if depth < 3 else 6)
    if roll < 2:  # primitive
        return rng.choice(
            [int(rng.integers(-1000, 1000)), float(rng.standard_normal()), "s", None, True]
        )
    if roll < 5:  # array
        shape = tuple(int(s) for s in rng.integers(1, 6, size=rng.integers(0, 4)))
        dtype = _DTYPES[rng.integers(0, len(_DTYPES))]
        if dtype is np.bool_:
            return rng.integers(0, 2, size=shape).astype(dtype)
        return (rng.standard_normal(shape) * 100).astype(dtype)
    if roll < 6:  # arbitrary pickled object
        return {"tuple": (1, 2), "set_like": [3, 4]}
    if roll < 8:  # nested dict with adversarial keys
        keys = ["plain", "with/slash", "with%percent", "", "0", "nested"]
        return {
            keys[int(i)]: _random_value(rng, depth + 1)
            for i in rng.integers(0, len(keys), size=rng.integers(1, 4))
        }
    # nested list
    return [_random_value(rng, depth + 1) for _ in range(rng.integers(1, 4))]


# 18 = three passes over the 2x3 batching-x-codec grid; seeds >= 12 keep the
# DEFAULT frame size, so compressed arrays stay unframed and small ones join
# member-framed compressed slabs (the tiny-frame legs instead exercise
# framing, whose entries are excluded from slabs).
@pytest.mark.parametrize("seed", range(18))
def test_random_state_roundtrip(tmp_path, seed) -> None:
    rng = np.random.default_rng(seed)
    sd = StateDict(
        **{f"k{i}": _random_value(rng, 0) for i in range(int(rng.integers(1, 8)))}
    )
    # Deep copy: a take() that mutated source arrays in place would
    # otherwise corrupt both sides of the comparison identically.
    expected = copy.deepcopy(dict(sd))
    path = str(tmp_path / "ckpt")
    # Exercise chunking/batching on alternate seeds and rotate the
    # compression codec, so every pairwise feature composition gets fuzzed.
    import contextlib

    with contextlib.ExitStack() as stack:
        if seed % 2:
            stack.enter_context(knobs.override_batching_enabled(True))
            stack.enter_context(knobs.override_max_chunk_size_bytes(64))
        codec = ("none", "zstd", "zlib")[seed % 3]
        if codec != "none":
            stack.enter_context(knobs.override_compression(codec))
            if seed < 12:
                # Tiny frame size: most compressed arrays become FRAMED
                # (with .ftab side objects), fuzzing framing x batching x
                # chunking. Seeds >= 12 keep the default so small
                # compressed arrays join member-framed slabs instead.
                stack.enter_context(knobs.override_compression_frame_bytes(48))
        Snapshot.take(path, {"s": sd})
    out = StateDict()
    Snapshot(path).restore({"s": out})
    assert_state_dict_eq(dict(out), expected, exact=True)
    assert Snapshot(path).verify() == {}
    # Budgeted random access of one array leaf (framed sub-read path when
    # the codec framed it).
    array_keys = [k for k, v in expected.items() if isinstance(v, np.ndarray)]
    if array_keys:
        k = array_keys[int(rng.integers(0, len(array_keys)))]
        got = Snapshot(path).read_object(f"0/s/{k}", memory_budget_bytes=64)
        assert np.array_equal(
            np.asarray(got).reshape(-1).view(np.uint8),
            expected[k].reshape(-1).view(np.uint8),
        ), k
