"""Slab batching round-trips (reference model: ``tests/test_batcher.py``)."""

import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.batcher import batch_read_requests
from torchsnapshot_tpu.io_types import ReadReq
from torchsnapshot_tpu.test_utils import assert_state_dict_eq
from torchsnapshot_tpu.utils import knobs


def test_batched_take_restore(tmp_path) -> None:
    rng = np.random.default_rng(0)
    sd = StateDict(
        **{f"p{i}": rng.standard_normal((7, 5)).astype(np.float32) for i in range(20)}
    )
    expected = dict(sd)
    path = str(tmp_path / "ckpt")
    with knobs.override_batching_enabled(True), knobs.override_slab_size_threshold_bytes(
        400
    ):
        snap = Snapshot.take(path, {"s": sd})
        out = StateDict()
        Snapshot(path).restore({"s": out})
    assert_state_dict_eq(dict(out), expected, exact=True)
    # Entries must have been relocated into slab objects with byte ranges.
    manifest = snap.get_manifest()
    slabbed = [
        e
        for k, e in manifest.items()
        if getattr(e, "location", "").startswith("batched/")
    ]
    assert len(slabbed) == 20
    assert all(e.byte_range is not None for e in slabbed)
    # Multiple params share a slab object.
    assert len({e.location for e in slabbed}) < 20


def test_batched_read_object(tmp_path) -> None:
    sd = StateDict(a=np.arange(10, dtype=np.int32), b=np.ones(4, dtype=np.float64))
    path = str(tmp_path / "ckpt")
    with knobs.override_batching_enabled(True), knobs.override_slab_size_threshold_bytes(
        10**6
    ):
        Snapshot.take(path, {"s": sd})
    got = Snapshot(path).read_object("0/s/a")
    assert np.array_equal(got, sd["a"])


def test_read_merge_adjacent() -> None:
    class DummyConsumer:
        def __init__(self):
            self.got = None

        async def consume_buffer(self, buf, executor=None):
            self.got = bytes(buf)

        def get_consuming_cost_bytes(self):
            return 4

    c1, c2, c3 = DummyConsumer(), DummyConsumer(), DummyConsumer()
    reqs = [
        ReadReq("x", c1, (0, 4)),
        ReadReq("x", c2, (4, 8)),
        ReadReq("x", c3, (12, 16)),  # gap: not merged
    ]
    merged = batch_read_requests(reqs)
    assert len(merged) == 2
    spans = sorted(r.byte_range for r in merged)
    assert spans == [(0, 8), (12, 16)]


def _device_arrays(n=12, dtype="bfloat16"):
    import jax
    import jax.numpy as jnp

    # Generate in int32 and convert: narrow integer dtypes (int8) overflow
    # past ~5 arrays, and numpy 2.x makes out-of-range arange a hard
    # OverflowError instead of wrapping. The byte-identity tests only need
    # distinct deterministic bit patterns, which the wrap preserves.
    return {
        f"p{i}": jax.device_put(
            jnp.arange(i * 24, (i + 1) * 24, dtype=jnp.int32)
            .astype(jnp.dtype(dtype))
            .reshape(6, 4)
        )
        for i in range(n)
    }


@pytest.mark.parametrize(
    "dtype", ["bfloat16", "float32", "int8", "bool", "float8_e4m3fn", "float16"]
)
def test_device_batched_take_restore(tmp_path, dtype) -> None:
    """Slabs of dtypes the pack program returns bit for bit are packed on
    the device (single D2H); sub-32-bit float slabs are packed on the host
    (the device pack rewrites their denormals / NaN payloads on the TPU).
    Either way the take says which, and the restore is byte-identical."""
    import jax.numpy as jnp

    if dtype == "bool":
        arrs = {
            k: (v % 2 == 0) for k, v in _device_arrays(dtype="int32").items()
        }
    elif dtype == "float8_e4m3fn":
        arrs = {
            k: v.astype(jnp.float8_e4m3fn)
            for k, v in _device_arrays(dtype="float32").items()
        }
    else:
        arrs = _device_arrays(dtype=dtype)
    on_device = dtype in ("float32", "int8", "bool")
    expected = {k: np.ascontiguousarray(np.asarray(v)) for k, v in arrs.items()}
    path = str(tmp_path / "dev")
    from torchsnapshot_tpu import batcher as batcher_mod

    batcher_mod._PACK_FNS.clear()
    with knobs.override_batching_enabled(
        True
    ), knobs.override_slab_size_threshold_bytes(10**6):
        snap = Snapshot.take(path, {"s": StateDict(**arrs)})
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert len(batcher_mod._PACK_FNS) == (1 if on_device else 0)
    assert metrics.get("batcher.slabs_device_packed", 0) == (1 if on_device else 0)
    assert metrics.get("batcher.slabs_host_packed", 0) == (0 if on_device else 1)
    assert "batcher.slabs_pack_degraded" not in metrics
    out = StateDict(**{k: jnp.zeros_like(v) for k, v in arrs.items()})
    Snapshot(path).restore({"s": out})
    for k, want in expected.items():
        got = np.ascontiguousarray(np.asarray(out[k]))
        assert got.dtype == want.dtype, k
        assert np.array_equal(
            got.view(np.uint8), want.view(np.uint8)
        ), f"{k} not bit-exact"
    manifest = snap.get_manifest()
    slabbed = {
        e.location
        for e in manifest.values()
        if getattr(e, "location", "").startswith("batched/")
    }
    assert len(slabbed) == 1  # all members fit one slab


def test_mixed_dtype_members_split_into_device_and_host_slabs(tmp_path) -> None:
    """A sub-32-bit float member must not drag the members the device can
    pack onto the host path, nor ride a device slab itself: slabs close at
    the boundary."""
    import jax.numpy as jnp

    arrs = {f"f{k}": v for k, v in _device_arrays(n=4, dtype="float32").items()}
    arrs.update({f"b{k}": v for k, v in _device_arrays(n=4, dtype="bfloat16").items()})
    path = str(tmp_path / "mixed")
    with knobs.override_batching_enabled(
        True
    ), knobs.override_slab_size_threshold_bytes(10**6):
        snap = Snapshot.take(path, {"s": StateDict(**arrs)})
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["batcher.slabs_device_packed"] == 1
    assert metrics["batcher.slabs_host_packed"] == 1
    by_slab = {}
    for logical, e in snap.get_manifest().items():
        if getattr(e, "location", "").startswith("batched/"):
            by_slab.setdefault(e.location, set()).add(e.dtype)
    assert sorted(map(sorted, by_slab.values())) == [["bfloat16"], ["float32"]]
    out = StateDict(**{k: jnp.zeros_like(v) for k, v in arrs.items()})
    Snapshot(path).restore({"s": out})
    for k, v in arrs.items():
        assert np.array_equal(
            np.ascontiguousarray(np.asarray(out[k])).view(np.uint8),
            np.ascontiguousarray(np.asarray(v)).view(np.uint8),
        ), k


def test_device_batched_matches_host_packed_bytes(tmp_path) -> None:
    """The slab object written by the device packer must equal the one the
    host packer writes for the same members."""
    arrs = _device_arrays(dtype="float32")

    def slab_bytes(root: str, device: bool) -> bytes:
        with knobs.override_batching_enabled(
            True
        ), knobs.override_slab_size_threshold_bytes(10**6), knobs.override_device_batching(
            device
        ):
            Snapshot.take(root, {"s": StateDict(**arrs)})
        import glob as _glob

        (slab,) = _glob.glob(os.path.join(root, "batched", "*"))
        with open(slab, "rb") as f:
            return f.read()

    dev = slab_bytes(str(tmp_path / "dev"), True)
    host = slab_bytes(str(tmp_path / "host"), False)
    assert dev == host


def test_device_batched_async_take(tmp_path, caplog) -> None:
    """Deferred (async) slabs of device arrays pack on the background thread."""
    from torchsnapshot_tpu import batcher as batcher_mod

    arrs = _device_arrays(dtype="int8")
    expected = {k: np.ascontiguousarray(np.asarray(v)) for k, v in arrs.items()}
    path = str(tmp_path / "async")
    batcher_mod._PACK_FNS.clear()
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.batcher"):
        with knobs.override_batching_enabled(
            True
        ), knobs.override_slab_size_threshold_bytes(10**6):
            Snapshot.async_take(path, {"s": StateDict(**arrs)}).wait()
    assert len(batcher_mod._PACK_FNS) == 1, "device packing did not engage"
    assert not any("falling back" in r.message for r in caplog.records)
    got = Snapshot(path).read_object("0/s/p3")
    assert np.array_equal(
        np.ascontiguousarray(np.asarray(got)).view(np.uint8),
        expected["p3"].view(np.uint8),
    )


def test_device_batching_unsupported_dtype_packs_on_host(tmp_path) -> None:
    """Non-packable members (complex) get a host-packed slab of their own
    and still round-trip; the packable members keep the device path."""
    import jax.numpy as jnp

    from torchsnapshot_tpu import batcher as batcher_mod

    arrs = _device_arrays(n=4, dtype="float32")
    arrs["c0"] = jnp.arange(8, dtype=jnp.complex64)
    arrs["c1"] = jnp.arange(8, 16, dtype=jnp.complex64)
    batcher_mod._PACK_FNS.clear()
    path = str(tmp_path / "mix")
    with knobs.override_batching_enabled(True), knobs.override_slab_size_threshold_bytes(
        10**6
    ):
        Snapshot.take(path, {"s": StateDict(**arrs)})
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert len(batcher_mod._PACK_FNS) == 1  # the float32 slab only
    assert metrics["batcher.slabs_device_packed"] == 1
    assert metrics["batcher.slabs_host_packed"] == 1
    out = StateDict(**{k: jnp.zeros_like(v) for k, v in arrs.items()})
    Snapshot(path).restore({"s": out})
    for k, v in arrs.items():
        assert np.array_equal(np.asarray(out[k]), np.asarray(v)), k


def test_device_pack_refusal_is_an_error_not_a_fallback(tmp_path, monkeypatch) -> None:
    """Only an allocation failure may degrade to host packing: a compile
    refusal (or any other failure) would otherwise be indistinguishable from
    HBM pressure and hide for the cooldown. It aborts the take."""
    from torchsnapshot_tpu import batcher as batcher_mod
    from torchsnapshot_tpu.snapshot import CheckpointAbortedError

    def boom(key, arrs):
        raise RuntimeError("INVALID_ARGUMENT: simulated compile refusal")

    monkeypatch.setattr(batcher_mod, "_pack_to_device_bytes", boom)
    monkeypatch.setattr(batcher_mod, "_PACK_FAILED", {})  # auto-restored
    arrs = _device_arrays(n=4, dtype="float32")
    with knobs.override_batching_enabled(
        True
    ), knobs.override_slab_size_threshold_bytes(10**6):
        with pytest.raises(CheckpointAbortedError, match="simulated compile refusal"):
            Snapshot.take(str(tmp_path / "a"), {"s": StateDict(**arrs)})
    assert not batcher_mod._PACK_FAILED


def test_device_pack_oom_degrades_counted_and_memoized(
    tmp_path, caplog, monkeypatch
) -> None:
    """A pack that cannot get its HBM warns once and packs on the host, is
    counted (``batcher.slabs_pack_degraded``), then skips the device path on
    subsequent takes instead of re-failing (and re-warning) every time."""
    from torchsnapshot_tpu import batcher as batcher_mod

    def boom(key, arrs):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Error allocating device buffer (simulated)"
        )

    monkeypatch.setattr(batcher_mod, "_pack_to_device_bytes", boom)
    monkeypatch.setattr(batcher_mod, "_PACK_FAILED", {})  # auto-restored
    arrs = _device_arrays(n=4, dtype="float32")
    expected = {k: np.asarray(v) for k, v in arrs.items()}
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu.batcher"):
        with knobs.override_batching_enabled(
            True
        ), knobs.override_slab_size_threshold_bytes(10**6):
            Snapshot.take(str(tmp_path / "a"), {"s": StateDict(**arrs)})
            first_warnings = sum(
                "falling back" in r.message for r in caplog.records
            )
            Snapshot.take(str(tmp_path / "b"), {"s": StateDict(**arrs)})
    total_warnings = sum("falling back" in r.message for r in caplog.records)
    assert first_warnings == 1
    assert total_warnings == 1  # second take skipped silently
    metrics = Snapshot.last_telemetry.metrics.as_dict()
    assert metrics["batcher.slabs_pack_degraded"] == 1
    assert "batcher.slabs_device_packed" not in metrics
    out = StateDict()
    Snapshot(str(tmp_path / "b")).restore({"s": out})
    for k, want in expected.items():
        assert np.array_equal(np.asarray(out[k]), want), k


def test_read_merge_respects_budget_cap() -> None:
    """batch_read_requests must not coalesce budget-capped sub-reads back
    into the whole-object read they were split to avoid."""
    from torchsnapshot_tpu.batcher import batch_read_requests
    from torchsnapshot_tpu.io_types import BufferConsumer, ReadReq

    class _Noop(BufferConsumer):
        async def consume_buffer(self, buf, executor=None):
            pass

        def get_consuming_cost_bytes(self):
            return 0

    reqs = [
        ReadReq(path="obj", buffer_consumer=_Noop(), byte_range=(i * 100, (i + 1) * 100))
        for i in range(8)
    ]
    merged = batch_read_requests(list(reqs), max_merged_bytes=250)
    assert all(r.byte_range[1] - r.byte_range[0] <= 250 for r in merged)
    # Full coverage preserved, in order.
    spans = sorted(r.byte_range for r in merged)
    assert spans[0][0] == 0 and spans[-1][1] == 800
    for (a, b), (c, d) in zip(spans, spans[1:]):
        assert b == c
    # Uncapped: one merged read.
    assert len(batch_read_requests(list(reqs))) == 1
    # A single over-cap request still passes through whole.
    big = [ReadReq(path="obj", buffer_consumer=_Noop(), byte_range=(0, 1000))]
    assert batch_read_requests(list(big), max_merged_bytes=250)[0].byte_range == (0, 1000)


def test_batched_take_restore_with_slabs_above_the_hash_grain(tmp_path) -> None:
    """Slabs several hash grains long land as single objects whose sidecar
    tree records equal an independent recompute, and restore bit-exact."""
    import json
    import os

    from torchsnapshot_tpu import hashing

    rng = np.random.default_rng(2)
    sd = StateDict(
        **{f"p{i}": rng.standard_normal((7, 5)).astype(np.float32) for i in range(20)}
    )
    expected = dict(sd)
    path = str(tmp_path / "ckpt")
    with knobs.override_batching_enabled(True), \
            knobs.override_slab_size_threshold_bytes(400), \
            knobs.override_hash_chunk_bytes(128):
        snap = Snapshot.take(path, {"s": sd})
        out = StateDict()
        Snapshot(path).restore({"s": out})
    assert_state_dict_eq(dict(out), expected, exact=True)
    manifest = snap.get_manifest()
    slabbed = [
        e
        for k, e in manifest.items()
        if getattr(e, "location", "").startswith("batched/")
    ]
    assert len(slabbed) == 20
    assert Snapshot(path).verify() == {}
    sidecar = json.load(open(os.path.join(path, ".checksums.0")))
    slabs = {e.location for e in slabbed}
    assert len(slabs) > 1
    for location in slabs:
        stored = open(os.path.join(path, location), "rb").read()
        rec = sidecar[location]
        assert hashing.is_v2_record(rec) and len(rec["crcs"]) > 2
        assert rec == hashing.digest_of_bytes(
            stored, 128, want_sha=bool(hashing.record_content_keys(rec))
        )
