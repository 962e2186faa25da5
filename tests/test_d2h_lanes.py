"""Parallel D2H lanes + zero-copy RAW staging + stage-time attribution.

The PR-6 staging saturation work: the zero-copy RAW path's bit-exactness
(payload, ``.ftab``, sidecar digests) across dtypes and layouts,
abort-path budget balance with lanes in flight, and the ``stage.d2h``/``stage.serialize``/``stage.hash``
decomposition in drain stats and persisted telemetry artifacts.
"""

import asyncio
import json
import zlib

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, d2h
from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
from torchsnapshot_tpu.scheduler import _WritePipeline, execute_write_reqs
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs

try:
    import ml_dtypes
except ImportError:  # pragma: no cover - ships with jax
    ml_dtypes = None


@pytest.fixture(autouse=True)
def _debug_ledger():
    """The suite runs under the budget-ledger sanitizer: close/abort
    assert zero outstanding bytes with site attribution."""
    with knobs.override_debug_ledger(True):
        yield


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ------------------------------------------------------------ TransferLanes


def test_d2h_knobs() -> None:
    assert knobs.get_d2h_lanes() >= 1
    with knobs.override_d2h_lanes(7):
        assert knobs.get_d2h_lanes() == 7
        assert d2h.TransferLanes().lane_count == 7


# --------------------------------------------------- zero-copy RAW staging


def test_raw_stage_buffer_is_zero_copy_view() -> None:
    """A RAW staged buffer is a memoryview over the host array's own bytes
    — no serialization pass, no intermediate bytes()."""
    arr = np.arange(1024, dtype=np.float32)
    _entry, reqs = ArrayIOPreparer.prepare_write("obj", arr)
    buf = _run(reqs[0].buffer_stager.stage_buffer())
    assert isinstance(buf, memoryview)
    assert np.shares_memory(np.frombuffer(buf, dtype=np.uint8), arr)


def _dtype_cases():
    cases = [np.dtype(np.float32)]
    if ml_dtypes is not None:
        cases.append(np.dtype(ml_dtypes.bfloat16))
        cases.append(np.dtype(ml_dtypes.int4))
    return cases


@pytest.mark.parametrize("dtype", _dtype_cases(), ids=lambda d: d.name)
@pytest.mark.parametrize("contiguous", [True, False])
def test_zero_copy_raw_object_and_sidecar_bit_exact(dtype, contiguous) -> None:
    """The zero-copy RAW path writes the array's C-order bytes and a
    sidecar digest equal to an independent recompute, for every RAW dtype,
    from contiguous AND non-contiguous sources."""
    from torchsnapshot_tpu import hashing

    rng = np.random.default_rng(7)
    base = rng.integers(0, 7, size=(64, 48)).astype(dtype)
    arr = base if contiguous else base.T.copy().T  # F-order, same values
    if not contiguous:
        assert not arr.flags["C_CONTIGUOUS"]
    storage = MemoryStoragePlugin()
    _entry, reqs = ArrayIOPreparer.prepare_write("obj", arr)

    async def go():
        with knobs.override_hash_chunk_bytes(1024), \
                knobs.override_dedup_digests(True):
            pending = await execute_write_reqs(
                reqs, storage, memory_budget_bytes=10**9, rank=0
            )
            await pending.complete()

    _run(go())
    expected = np.ascontiguousarray(arr).view(np.uint8).tobytes()
    assert storage.objects["obj"] == expected
    rec = json.loads(storage.objects[".checksums.0"])["obj"]
    assert hashing.is_v2_record(rec) and rec["grain"] == 1024
    assert hashing.record_crc(rec) == zlib.crc32(expected)
    assert rec == hashing.digest_of_bytes(expected, 1024)


def test_framed_compressed_payload_and_ftab_match_codec() -> None:
    """A framed-zlib entry's payload is the codec's own framed output of
    the array's bytes, and the ``.ftab`` side object lists its frames."""
    from torchsnapshot_tpu.serialization import Serializer, compress_framed

    arr = (np.arange(96 * 64, dtype=np.float32) % 17).reshape(96, 64)
    storage = MemoryStoragePlugin()
    with knobs.override_compression("zlib"), \
            knobs.override_compression_frame_bytes(4096):
        _entry, reqs = ArrayIOPreparer.prepare_write("obj", arr)
        level = reqs[0].buffer_stager.compression_level

        async def go():
            pending = await execute_write_reqs(
                reqs, storage, memory_budget_bytes=10**9, rank=0
            )
            await pending.complete()

        _run(go())
    payload, sizes = compress_framed(
        arr.tobytes(), Serializer.RAW_ZLIB, level, 4096
    )
    assert storage.objects["obj"] == bytes(payload)
    assert json.loads(storage.objects["obj.ftab"])["sizes"] == list(sizes)


# ------------------------------------------ lanes through the write pipeline


def _jax_app(rows=512, cols=256, seed=0):
    import jax
    import jax.numpy as jnp

    arr = jax.random.normal(jax.random.PRNGKey(seed), (rows, cols), jnp.float32)
    jax.block_until_ready(arr)
    return arr


def test_mid_drain_abort_with_lanes_in_flight_credits_every_debit() -> None:
    """A storage write that explodes mid-drain, with other leaves' D2H
    resolving on the lanes: the failure propagates, the failed object is
    absent, and every budget debit is credited back."""

    class FailingWriteStorage(MemoryStoragePlugin):
        written = 0

        async def write(self, write_io):
            FailingWriteStorage.written += 1
            if FailingWriteStorage.written > 2:
                raise OSError("write exploded")
            await super().write(write_io)

    storage = FailingWriteStorage()
    reqs = []
    for i in range(8):
        _entry, leaf_reqs = ArrayIOPreparer.prepare_write(
            f"obj{i}", _jax_app(rows=256, cols=256, seed=i)
        )
        reqs.extend(leaf_reqs)

    async def go():
        await pipeline.run_until_staged()
        await asyncio.wait_for(pipeline.run_to_completion(), timeout=30)

    with knobs.override_d2h_lanes(4):
        # Half the budget: later leaves are admitted (and hinted by the
        # lanes at their turn) while earlier ones write.
        pipeline = _WritePipeline(
            reqs, storage, memory_budget_bytes=4 * 256 * 256 * 4, rank=0
        )
        with pytest.raises(OSError, match="write exploded"):
            _run(go())
    assert len([k for k in storage.objects if k.startswith("obj")]) == 2
    assert pipeline.budget_balanced, (
        pipeline.budget.available, pipeline.budget.total
    )


# --------------------------------------------------- stage-time attribution


def test_stage_substreams_in_drain_stats_and_artifact(tmp_path) -> None:
    """stage_d2h_s / stage_serialize_s / stage_hash_s appear in the drain
    stats and in the persisted telemetry artifact (scalars + merged
    sub-stream intervals)."""
    import jax
    import jax.numpy as jnp

    arrs = {
        f"a{i}": jax.random.normal(jax.random.PRNGKey(i), (128, 64), jnp.float32)
        for i in range(3)
    }
    pending = Snapshot.async_take(str(tmp_path / "ck"), {"m": StateDict(**arrs)})
    pending.wait()
    stats = pending.drain_stats
    for k in ("stage_d2h_s", "stage_serialize_s", "stage_hash_s"):
        assert k in stats and stats[k] >= 0
    # The D2H and hash sub-streams must have actually recorded something
    # for device-backed state with checksums on.
    assert stats["stage_d2h_s"] > 0
    assert stats["stage_hash_s"] > 0

    art = json.loads((tmp_path / "ck" / ".telemetry" / "rank_0.json").read_text())
    for k in ("stage_d2h_s", "stage_serialize_s", "stage_hash_s"):
        assert k in art["drain_stats_s"]
        assert k in art["pipeline_stats_s"]
    for k in ("stage_d2h", "stage_serialize", "stage_hash"):
        assert k in art["intervals"]


def test_stage_spans_emitted_under_session(tmp_path) -> None:
    """With a telemetry session active, the sub-streams also land as
    stage.d2h / stage.hash spans (serialize is ~instant for RAW but still
    recorded)."""
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu import telemetry

    tm = telemetry.Telemetry()
    arr = jax.random.normal(jax.random.PRNGKey(0), (256, 64), jnp.float32)
    Snapshot.take(str(tmp_path / "ck"), {"m": StateDict(w=arr)}, _telemetry=tm)
    assert tm.spans(name="stage.d2h")
    assert tm.spans(name="stage.serialize")
    assert tm.spans(name="stage.hash")


def test_dedup_digests_off_skips_sha_and_shrinks_hash_stream(tmp_path) -> None:
    """DEDUP_DIGESTS=0: the sidecar records no sha256 (crc only) — the
    stage.hash stream measures the lighter fold."""
    arr = np.arange(64 * 1024, dtype=np.float32)

    def sidecar(dedup: bool):
        storage = MemoryStoragePlugin()
        _entry, reqs = ArrayIOPreparer.prepare_write("obj", arr)

        async def go():
            with knobs.override_dedup_digests(dedup):
                pending = await execute_write_reqs(
                    reqs, storage, memory_budget_bytes=10**9, rank=0
                )
                await pending.complete()
                return pending

        pending = _run(go())
        return json.loads(storage.objects[".checksums.0"])["obj"], pending

    (crc_on, _size_on, sha_on), p_on = sidecar(True)
    (crc_off, _size_off, sha_off), p_off = sidecar(False)
    assert crc_on == crc_off
    assert sha_on is not None
    assert sha_off is None
    # Both pipelines measured a hash stream (crc still folds with sha off).
    assert p_on.pipeline_stats["stage_hash_s"] >= 0
    assert p_off.pipeline_stats["stage_hash_s"] >= 0


def test_stager_outside_pipeline_still_works_without_context() -> None:
    """Driven without an active StagingContext (no pipeline), the stager
    resolves its transfer on the caller's executor — no lanes, no
    recording, same bytes."""
    import jax
    import jax.numpy as jnp

    arr = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    _entry, reqs = ArrayIOPreparer.prepare_write("obj", arr)
    stager = reqs[0].buffer_stager

    async def stage():
        assert d2h.get_active() is None
        return bytes(await stager.stage_buffer())

    assert _run(stage()) == np.asarray(arr).tobytes()
