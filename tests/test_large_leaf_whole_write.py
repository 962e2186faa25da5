"""A single leaf just over 128 MiB, the size a default-knob take used to
route down the chunk-streamed write path: one stage, one hash, one write.

For every dtype a device slice preserves, RAW and both framed codecs, sync
and async: the stored object is the source's bytes (or decodes to them),
its sidecar record is what ``hashing.digest_of_bytes`` gives for the stored
bytes at the default 64 MiB grain (a v2 tree of three chunks for RAW), and
the restore is bit-exact. The on-disk format is a function of the bytes
alone.
"""

import functools
import json
import os

import jax
import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, hashing
from torchsnapshot_tpu.serialization import Serializer, decode_framed_payload
from torchsnapshot_tpu.utils import knobs

ROWS, COLS = 2049, 16384  # x 4 bytes: 128 MiB + 64 KiB


@functools.lru_cache(maxsize=None)
def _source(dtype: str) -> np.ndarray:
    dt = np.dtype(dtype)
    # Periodic bytes, so the codecs are quick; every byte value occurs, and
    # as float32 bits they include denormals and NaN payloads.
    period = np.arange(251 * 4, dtype=np.uint32).astype(np.uint8)
    if dt == np.bool_:
        period = period % 3 == 0
    flat = np.resize(period.view(np.uint8), ROWS * COLS * 4)
    return flat.view(dt).reshape(ROWS, -1)


@pytest.mark.parametrize("mode", ["take", "async_take"])
@pytest.mark.parametrize("codec", ["none", "zstd", "zlib"])
@pytest.mark.parametrize("dtype", ["float32", "int32", "uint8", "bool"])
def test_one_large_leaf_is_one_exact_object(tmp_path, dtype, codec, mode) -> None:
    host = _source(dtype)
    assert host.nbytes > 2 * knobs.get_hash_chunk_bytes() == 128 << 20
    leaf = jax.device_put(host)
    path = str(tmp_path / "ckpt")
    with knobs.override_compression(codec):
        if mode == "take":
            snap = Snapshot.take(path, {"s": StateDict(w=leaf)})
        else:
            snap = Snapshot.async_take(path, {"s": StateDict(w=leaf)}).wait()
    entry = snap.get_manifest()["0/s/w"]
    assert entry.type == "array"  # one object: not chunked, not sharded
    with open(os.path.join(path, entry.location), "rb") as f:
        stored = f.read()
    source = host.tobytes()
    if codec == "none":
        assert entry.serializer == Serializer.RAW
        assert stored == source
    else:
        assert entry.frame_bytes and len(stored) < len(source)
        assert decode_framed_payload(stored, entry.serializer) == source
    with open(os.path.join(path, ".checksums.0")) as f:
        sidecar = json.load(f)
    rec = sidecar[entry.location]
    want_sha = bool(hashing.record_content_keys(rec))
    assert rec == hashing.digest_of_bytes(
        stored, knobs.get_hash_chunk_bytes(), want_sha=want_sha
    )
    if codec == "none":
        assert hashing.is_v2_record(rec) and len(rec["crcs"]) == 3
    target = StateDict(w=np.zeros_like(host))
    Snapshot(path).restore({"s": target})
    assert np.array_equal(target["w"].view(np.uint8), host.view(np.uint8))
