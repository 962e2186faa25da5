"""GCS plugin tests (reference ``tests/test_gcs_storage_plugin.py``).

Unit tests run against a fake ``google.cloud.storage`` SDK injected into
``sys.modules`` (the reference's fake-backend pattern); the live integration
test is env-var gated and skips when no bucket is configured.
"""

import asyncio
import importlib.util
import os
import sys
import types

import pytest

from torchsnapshot_tpu.io_types import ReadIO, WriteIO


def _install_fake_gcs(monkeypatch, blobs: dict, fail_reads: dict) -> None:
    # The fake mirrors the real SDK's error taxonomy: absent blobs raise
    # google.api_core.exceptions.NotFound (installed below), so the
    # plugin's absence normalization (NotFound -> FileNotFoundError) is
    # exercised by every fake-backed test, not just a bespoke one.
    class FakeNotFound(Exception):
        pass

    def _lookup(name: str) -> bytes:
        try:
            return blobs[name]
        except KeyError:
            raise FakeNotFound(f"404 GET {name}") from None

    class FakeBlob:
        def __init__(self, name: str) -> None:
            self._name = name
            self.name = name  # the real SDK exposes .name (list_blobs/gc)

        def upload_from_file(self, fileobj, size=None, rewind=False) -> None:
            if rewind:
                fileobj.seek(0)
            data = fileobj.read(size) if size is not None else fileobj.read()
            blobs[self._name] = bytes(data)

        def download_as_bytes(self, start=None, end=None) -> bytes:
            n_fail = fail_reads.get(self._name, 0)
            if n_fail:
                fail_reads[self._name] = n_fail - 1
                raise ConnectionError("simulated transient failure")
            data = _lookup(self._name)
            if start is None:
                return data
            return data[start : end + 1]  # GCS ranges are inclusive

        def delete(self) -> None:
            _lookup(self._name)
            del blobs[self._name]

        def rewrite(self, src_blob, token=None):
            # One-token resumable rewrite: first call returns a token (as
            # real GCS does for large objects), the second completes.
            if token is None:
                return ("resume-token", 0, len(_lookup(src_blob._name)))
            blobs[self._name] = _lookup(src_blob._name)
            FakeBucket.copies.append((src_blob._name, self._name))
            n = len(blobs[self._name])
            return (None, n, n)

    class FakeBucket:
        copies: list = []  # (src_name, dst_name) server-side copies

        def __init__(self, name: str) -> None:
            self.name = name

        def blob(self, path: str) -> FakeBlob:
            return FakeBlob(path)

    class FakeClient:
        def bucket(self, name: str) -> FakeBucket:
            return FakeBucket(name)

        def list_blobs(self, bucket_name: str, prefix=None):
            return [
                FakeBlob(n)
                for n in sorted(blobs)
                if prefix is None or n.startswith(prefix)
            ]

    storage_mod = types.ModuleType("google.cloud.storage")
    storage_mod.Client = FakeClient
    cloud_mod = types.ModuleType("google.cloud")
    cloud_mod.storage = storage_mod
    gexc_mod = types.ModuleType("google.api_core.exceptions")
    gexc_mod.NotFound = FakeNotFound
    for name in (
        "TooManyRequests",
        "InternalServerError",
        "BadGateway",
        "ServiceUnavailable",
        "GatewayTimeout",
    ):
        setattr(gexc_mod, name, type(name, (Exception,), {}))
    api_core_mod = types.ModuleType("google.api_core")
    api_core_mod.exceptions = gexc_mod
    google_mod = types.ModuleType("google")
    google_mod.cloud = cloud_mod
    google_mod.api_core = api_core_mod
    monkeypatch.setitem(sys.modules, "google", google_mod)
    monkeypatch.setitem(sys.modules, "google.cloud", cloud_mod)
    monkeypatch.setitem(sys.modules, "google.cloud.storage", storage_mod)
    monkeypatch.setitem(sys.modules, "google.api_core", api_core_mod)
    monkeypatch.setitem(sys.modules, "google.api_core.exceptions", gexc_mod)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture
def fake_gcs(monkeypatch):
    blobs: dict = {}
    fail_reads: dict = {}
    _install_fake_gcs(monkeypatch, blobs, fail_reads)
    # Keep retry backoff out of the test's wall clock.
    from torchsnapshot_tpu.storage_plugins import cloud_retry

    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    return blobs, fail_reads


def _bucket_copies():
    """The installed fake's (src, dst) server-side-copy ledger, cleared."""
    import sys as _sys

    cls = type(_sys.modules["google.cloud.storage"].Client().bucket("bucket"))
    cls.copies.clear()
    return cls.copies


def test_write_read_roundtrip(fake_gcs) -> None:
    blobs, _ = fake_gcs
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket/pre/fix")
    payload = bytes(range(256)) * 8

    async def go():
        await plugin.write(WriteIO(path="a/blob", buf=memoryview(payload)))
        rio = ReadIO(path="a/blob")
        await plugin.read(rio)
        await plugin.close()
        return rio.buf.getvalue()

    assert _run(go()) == payload
    assert set(blobs) == {"pre/fix/a/blob"}  # bucket prefix applied


def test_ranged_read_inclusive_end_translation(fake_gcs) -> None:
    _, _ = fake_gcs
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")
    payload = bytes(range(256))

    async def go():
        await plugin.write(WriteIO(path="blob", buf=payload))
        out = []
        for lo, hi in [(0, 16), (100, 200), (255, 256)]:
            rio = ReadIO(path="blob", byte_range=(lo, hi))
            await plugin.read(rio)
            out.append((lo, hi, rio.buf.getvalue()))
        await plugin.close()
        return out

    # Half-open [lo, hi) byte ranges must map to GCS's inclusive ends.
    for lo, hi, got in _run(go()):
        assert got == payload[lo:hi], (lo, hi)


def test_transient_errors_retried(fake_gcs) -> None:
    blobs, fail_reads = fake_gcs
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")
    blobs["blob"] = b"payload"
    fail_reads["blob"] = 2  # fail twice, then succeed

    async def go():
        rio = ReadIO(path="blob")
        await plugin.read(rio)
        await plugin.close()
        return rio.buf.getvalue()

    assert _run(go()) == b"payload"
    assert fail_reads["blob"] == 0


def test_collective_progress_outlasts_fixed_attempt_caps(fake_gcs) -> None:
    """Transient errors retry as long as the plugin's collective-progress
    window is open — here 9 consecutive failures (more than any fixed
    attempt cap) still recover."""
    blobs, fail_reads = fake_gcs
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")
    blobs["blob"] = b"payload"
    fail_reads["blob"] = 9

    async def go():
        rio = ReadIO(path="blob")
        await plugin.read(rio)
        await plugin.close()
        return rio.buf.getvalue()

    assert _run(go()) == b"payload"


def test_collective_progress_deadline_expires(fake_gcs) -> None:
    """Once no op on the plugin has made progress for window_s, a transient
    error propagates instead of retrying forever."""
    blobs, fail_reads = fake_gcs
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")
    plugin._progress.window_s = 0.0  # expire immediately
    plugin._progress._last -= 1.0
    blobs["blob"] = b"payload"
    fail_reads["blob"] = 1

    async def go():
        rio = ReadIO(path="blob")
        await plugin.read(rio)

    with pytest.raises(ConnectionError):
        _run(go())
    _run(plugin.close())


def test_nontransient_error_propagates(fake_gcs, monkeypatch) -> None:
    """A non-transient, non-absence error is neither retried nor remapped."""
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")
    blob = plugin._bucket.blob("x")
    monkeypatch.setattr(
        type(blob),
        "download_as_bytes",
        lambda self, start=None, end=None: (_ for _ in ()).throw(
            PermissionError("403 forbidden")
        ),
    )

    async def go():
        await plugin.read(ReadIO(path="denied"))

    with pytest.raises(PermissionError):
        _run(go())
    _run(plugin.close())


def test_delete(fake_gcs) -> None:
    blobs, _ = fake_gcs
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")

    async def go():
        await plugin.write(WriteIO(path="doomed", buf=b"x"))
        await plugin.delete("doomed")
        await plugin.close()

    _run(go())
    assert blobs == {}


def test_telemetry_artifact_round_trip(fake_gcs) -> None:
    """Persisted-telemetry leg: the artifact write/read seams the snapshot
    paths use work through the GCS plugin (fake SDK), and the missing-rank
    case degrades instead of failing the merge."""
    import asyncio as _asyncio

    from torchsnapshot_tpu.storage_plugin import write_telemetry_artifact
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin
    from torchsnapshot_tpu.telemetry import aggregate as agg_mod
    from torchsnapshot_tpu.telemetry import artifact as art_mod

    blobs, _ = fake_gcs
    plugin = GCSStoragePlugin(root="bucket/snap")
    loop = _asyncio.new_event_loop()
    try:
        art = art_mod.build_artifact(op="take", rank=0, world_size=2)
        assert write_telemetry_artifact(
            plugin, loop, art_mod.artifact_path(0), art_mod.dumps_artifact(art)
        )
        assert "snap/.telemetry/rank_0.json" in blobs
        artifacts, problems = agg_mod.read_artifacts(plugin, loop, world_size=2)
    finally:
        plugin.sync_close(loop)
        loop.close()
    assert set(artifacts) == {0} and problems == {1: "missing"}
    assert artifacts[0]["op"] == "take"
    assert artifacts[0]["hostname"] == art["hostname"]
    agg = agg_mod.aggregate(artifacts, world_size=2)
    assert agg["missing_ranks"] == [1]


def test_missing_sdk_raises_clear_error(monkeypatch) -> None:
    import builtins

    real_import = builtins.__import__

    def no_gcs(name, *args, **kwargs):
        if name.startswith("google"):
            raise ImportError(name)
        return real_import(name, *args, **kwargs)

    for mod in [m for m in sys.modules if m.startswith("google")]:
        monkeypatch.delitem(sys.modules, mod, raising=False)
    monkeypatch.setattr(builtins, "__import__", no_gcs)
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    with pytest.raises(RuntimeError, match="google-cloud-storage"):
        GCSStoragePlugin(root="bucket")


@pytest.mark.skipif(
    "TORCHSNAPSHOT_TPU_GCS_TEST_BUCKET" not in os.environ,
    reason="live GCS integration is env-var gated",
)
def test_live_snapshot_roundtrip(tmp_path) -> None:
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    bucket = os.environ["TORCHSNAPSHOT_TPU_GCS_TEST_BUCKET"]
    path = f"gs://{bucket}/torchsnapshot_tpu_ci/{os.getpid()}"
    arr = np.arange(1024, dtype=np.float32)
    Snapshot.take(path, {"s": StateDict(arr=arr)})
    out = {"s": StateDict(arr=np.zeros(1024, dtype=np.float32))}
    Snapshot(path).restore(out)
    assert np.array_equal(out["s"]["arr"], arr)


def test_incremental_take_uses_server_side_copies(fake_gcs, monkeypatch) -> None:
    """take(base=gs://...) dedups via GCS server-side copies: unchanged
    objects are copied bucket-side, never re-uploaded from this host."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    blobs, _ = fake_gcs
    copies = _bucket_copies()
    frozen = {f"b{i}": np.arange(500, dtype=np.float32) + i for i in range(3)}

    def app(step):
        return {"m": StateDict(**frozen, head=np.full((10,), step, np.float32))}

    Snapshot.take("gs://bucket/s0", app(0))
    Snapshot.take("gs://bucket/s1", app(1), base="gs://bucket/s0")
    copied_dsts = {dst for _, dst in copies}
    assert {f"s1/0/m/b{i}" for i in range(3)} <= copied_dsts
    assert "s1/0/m/head" not in copied_dsts  # changed: re-uploaded
    out = StateDict()
    Snapshot("gs://bucket/s1").restore({"m": out})
    assert np.array_equal(out["head"], np.full((10,), 1, np.float32))
    assert np.array_equal(out["b2"], frozen["b2"])


@pytest.mark.skipif(
    importlib.util.find_spec("zstandard") is None,
    reason="zstandard not installed (optional dependency)",
)
def test_incremental_server_side_copies_compressed_slabs(fake_gcs) -> None:
    """Member-framed compressed slabs dedup on GCS too: slab paths are
    fresh batched/<uuid> every take, so the content-keyed index must drive
    a server-side copy to the NEW path (and the .ftab with it) instead of
    re-uploading."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.utils import knobs

    blobs, _ = fake_gcs
    copies = _bucket_copies()
    frozen = {f"b{i}": np.arange(512, dtype=np.float32) + i for i in range(6)}

    with knobs.override_batching_enabled(True), knobs.override_compression("zstd"):
        Snapshot.take("gs://bucket/s0", {"m": StateDict(**frozen)})
        Snapshot.take(
            "gs://bucket/s1", {"m": StateDict(**frozen)}, base="gs://bucket/s0"
        )
    copied_dsts = {dst for _, dst in copies}
    slab_copies = {d for d in copied_dsts if d.startswith("s1/batched/")}
    # The slab payload and its .ftab both arrive by server-side copy.
    assert any(not d.endswith(".ftab") for d in slab_copies), copied_dsts
    assert any(d.endswith(".ftab") for d in slab_copies), copied_dsts
    out = StateDict()
    Snapshot("gs://bucket/s1").restore({"m": out})
    for i in range(6):
        assert np.array_equal(out[f"b{i}"], frozen[f"b{i}"])
    assert Snapshot("gs://bucket/s1").verify() == {}


def test_absent_object_normalized_to_file_not_found(fake_gcs) -> None:
    """GCS NotFound surfaces as FileNotFoundError per the StoragePlugin
    contract — exercised through the shared fake, whose absent blobs raise
    the (fake) canonical google.api_core NotFound like the real SDK."""
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    plugin = GCSStoragePlugin(root="bucket")

    async def go():
        with pytest.raises(FileNotFoundError):
            await plugin.read(ReadIO(path="missing"))
        with pytest.raises(FileNotFoundError):
            await plugin.delete("missing")
        await plugin.close()

    _run(go())


class _FakeResumableSession:
    """Simulates a GCS resumable-upload session with the real library's
    cursor semantics: a faulted transmit NEVER advances ``bytes_uploaded``
    (google-resumable-media only updates it on success or in ``recover()``);
    the server's partial persistence of the interrupted chunk (here: half,
    256-byte aligned) becomes visible only after ``recover()``. ``faults``
    maps transmit ordinals (0-based) to the exception to raise."""

    def __init__(self, blobs, blob_name, mv, chunk_bytes, faults, stats):
        self._blobs = blobs
        self._name = blob_name
        self._mv = memoryview(mv)
        self._chunk = chunk_bytes
        self._faults = faults
        self._stats = stats
        self._cursor = 0  # client-visible bytes_uploaded
        self._server_persisted = 0  # revealed by recover()
        self._invalid = False
        self._transmits = 0

    @property
    def finished(self):
        return self._cursor >= self._mv.nbytes

    @property
    def bytes_uploaded(self):
        return self._cursor

    def transmit_next_chunk(self):
        if self._invalid:
            raise AssertionError("transmit before recover() on invalid session")
        ordinal = self._transmits
        self._transmits += 1
        end = min(self._cursor + self._chunk, self._mv.nbytes)
        sent = end - self._cursor
        self._stats["sent"] += sent
        if ordinal in self._faults:
            # Server kept an aligned prefix of the interrupted chunk, but
            # the client cursor stays stale until recover().
            kept = (sent // 2) // 256 * 256
            self._server_persisted = self._cursor + kept
            self._invalid = True
            raise self._faults.pop(ordinal)
        self._cursor = end
        self._server_persisted = end
        if self.finished:
            self._blobs[self._name] = bytes(self._mv)

    def recover(self):
        self._stats["recovers"] += 1
        self._cursor = self._server_persisted
        self._invalid = False


def test_resumable_upload_recovers_cursor_mid_chunk(fake_gcs, monkeypatch) -> None:
    """A multi-chunk upload hit by transient mid-chunk faults completes with
    at most one chunk re-sent per fault (reference ``gcs.py:110-122``)."""
    from torchsnapshot_tpu.storage_plugins import gcs as gcs_mod
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin
    from torchsnapshot_tpu.utils import knobs

    blobs, _ = fake_gcs
    payload = bytes(range(256)) * 40  # 10 KiB
    chunk = 1024
    faults = {
        1: ConnectionError("reset mid-chunk"),
        4: TimeoutError("stalled"),
        7: ConnectionError("reset again"),
    }
    n_faults = len(faults)
    stats = {"sent": 0, "recovers": 0}

    def fake_factory(client, bucket_name, blob_name, mv, chunk_bytes, transport_factory=None):
        assert chunk_bytes == chunk
        return _FakeResumableSession(blobs, blob_name, mv, chunk_bytes, faults, stats)

    monkeypatch.setattr(gcs_mod, "_make_resumable_session", fake_factory)
    plugin = GCSStoragePlugin(root="bucket")

    with knobs.override_gcs_chunk_bytes(chunk):
        _run(plugin.write(WriteIO(path="big", buf=payload)))
    _run(plugin.close())

    assert blobs["big"] == payload
    assert stats["recovers"] == n_faults
    # <= one chunk re-sent per fault; with half-chunk server persistence the
    # overshoot is strictly below n_faults full chunks.
    assert stats["sent"] - len(payload) <= n_faults * chunk
    assert stats["sent"] - len(payload) > 0  # faults really did cost re-sends


def test_resumable_backoff_clamped_to_progress_window(fake_gcs, monkeypatch) -> None:
    """The mid-upload retry loop clamps each backoff to the collective-
    progress window's remaining time and re-checks expiry after sleeping —
    uniform with retry_transient (PR 5): a final exponential sleep can
    never overshoot the give-up deadline by a full MAX_BACKOFF period."""
    import time as _time

    from torchsnapshot_tpu.storage_plugins import cloud_retry
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    # Unclamped, the first backoff would sleep ~30-90s.
    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 30.0)
    monkeypatch.setattr(cloud_retry, "MAX_BACKOFF_S", 90.0)
    plugin = GCSStoragePlugin(root="bucket")
    plugin._progress.window_s = 0.2

    class StuckSession:
        finished = False
        bytes_uploaded = 0

        def transmit_next_chunk(self):
            raise ConnectionError("transient mid-upload fault")

        def recover(self):  # pragma: no cover - post-sleep expiry wins
            raise AssertionError("recover must not run past the deadline")

    async def go():
        loop = asyncio.get_running_loop()
        await plugin._drive_resumable(loop, StuckSession(), "big")

    t0 = _time.monotonic()
    with pytest.raises(ConnectionError):
        _run(go())
    elapsed = _time.monotonic() - t0
    assert elapsed < 5.0, (
        f"backoff was not clamped to the progress window: slept {elapsed:.1f}s"
    )
    _run(plugin.close())


def test_small_objects_keep_one_shot_upload(fake_gcs, monkeypatch) -> None:
    from torchsnapshot_tpu.storage_plugins import gcs as gcs_mod
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    blobs, _ = fake_gcs

    def exploding_factory(*a, **k):
        raise AssertionError("resumable session created for a small object")

    monkeypatch.setattr(gcs_mod, "_make_resumable_session", exploding_factory)
    plugin = GCSStoragePlugin(root="bucket")
    _run(plugin.write(WriteIO(path="small", buf=b"tiny")))
    _run(plugin.close())
    assert blobs["small"] == b"tiny"


def test_resumable_upload_nontransient_fault_propagates(fake_gcs, monkeypatch) -> None:
    from torchsnapshot_tpu.storage_plugins import gcs as gcs_mod
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin
    from torchsnapshot_tpu.utils import knobs

    blobs, _ = fake_gcs
    payload = bytes(512)
    stats = {"sent": 0, "recovers": 0}
    faults = {0: PermissionError("403")}

    def fake_factory(client, bucket_name, blob_name, mv, chunk_bytes, transport_factory=None):
        return _FakeResumableSession(blobs, blob_name, mv, chunk_bytes, faults, stats)

    monkeypatch.setattr(gcs_mod, "_make_resumable_session", fake_factory)
    plugin = GCSStoragePlugin(root="bucket")
    with knobs.override_gcs_chunk_bytes(256):
        with pytest.raises(PermissionError):
            _run(plugin.write(WriteIO(path="denied", buf=payload)))
    _run(plugin.close())
    assert "denied" not in blobs
    assert stats["recovers"] == 0


def test_resumable_upload_stalled_chunk_aborts(fake_gcs, monkeypatch) -> None:
    """A chunk that transiently fails forever (while recover() keeps
    succeeding) must abort after the stalled-chunk cap, not retry
    indefinitely — successful recovers refresh the collective-progress
    window, so the window alone can never expire this loop."""
    from torchsnapshot_tpu.storage_plugins import gcs as gcs_mod
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin
    from torchsnapshot_tpu.utils import knobs

    blobs, _ = fake_gcs
    payload = bytes(4096)
    stats = {"sent": 0, "recovers": 0}

    class _AlwaysFailingSession(_FakeResumableSession):
        def transmit_next_chunk(self):
            self._stats["sent"] += 0
            self._invalid = True
            raise ConnectionError("black-holed chunk")

    def fake_factory(client, bucket_name, blob_name, mv, chunk_bytes, transport_factory=None):
        return _AlwaysFailingSession(blobs, blob_name, mv, chunk_bytes, {}, stats)

    monkeypatch.setattr(gcs_mod, "_make_resumable_session", fake_factory)
    monkeypatch.setattr(gcs_mod, "_MAX_STALLED_CHUNK_RETRIES", 3)
    plugin = GCSStoragePlugin(root="bucket")
    with knobs.override_gcs_chunk_bytes(1024):
        with pytest.raises(ConnectionError):
            _run(plugin.write(WriteIO(path="stuck", buf=payload)))
    _run(plugin.close())
    assert "stuck" not in blobs
    # One recovery per stalled attempt: the counter is judged on the
    # recovered cursor, so the cap fires after the third recover shows
    # no progress.
    assert stats["recovers"] == 3


def test_resumable_upload_lost_final_ack_treated_as_committed(
    fake_gcs, monkeypatch
) -> None:
    """If the connection drops after GCS persists the final chunk but before
    the 200 ack arrives, the status probe of the completed session returns
    200 (not 308) and resumable_media surfaces it as an error; the plugin
    must recognize the upload as committed instead of failing the take."""
    from torchsnapshot_tpu.storage_plugins import gcs as gcs_mod
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin
    from torchsnapshot_tpu.utils import knobs

    blobs, _ = fake_gcs
    payload = bytes(range(256)) * 8  # 2 KiB: 2 chunks of 1024
    stats = {"sent": 0, "recovers": 0}

    class _Completed200(Exception):
        def __init__(self):
            self.response = types.SimpleNamespace(status_code=200)

    class _LostAckSession(_FakeResumableSession):
        def transmit_next_chunk(self):
            end = min(self._cursor + self._chunk, self._mv.nbytes)
            self._stats["sent"] += end - self._cursor
            if end >= self._mv.nbytes:
                # Server commits the object; only the ack is lost.
                self._server_persisted = self._mv.nbytes
                self._blobs[self._name] = bytes(self._mv)
                self._invalid = True
                raise ConnectionError("final ack lost")
            self._cursor = end
            self._server_persisted = end

        def recover(self):
            self._stats["recovers"] += 1
            raise _Completed200()

    def fake_factory(client, bucket_name, blob_name, mv, chunk_bytes, transport_factory=None):
        return _LostAckSession(blobs, blob_name, mv, chunk_bytes, {}, stats)

    monkeypatch.setattr(gcs_mod, "_make_resumable_session", fake_factory)
    plugin = GCSStoragePlugin(root="bucket")
    with knobs.override_gcs_chunk_bytes(1024):
        _run(plugin.write(WriteIO(path="acked", buf=payload)))
    _run(plugin.close())
    assert blobs["acked"] == payload
    assert stats["recovers"] == 1


# ---------------------------------------------------------------------------
# Emulator-backed wire-path tests: the REAL google-cloud-storage +
# google-resumable-media SDKs against a local fake GCS server
# (tests/gcs_emulator.py) via STORAGE_EMULATOR_HOST. These cover what the
# monkeypatch-faked tests above cannot: the multipart upload body, the
# resumable session protocol (308/Range cursors, `bytes */N` recovery
# probes), ranged media downloads, and the rewrite-token loop — without any
# cloud credentials.
# ---------------------------------------------------------------------------


@pytest.fixture
def gcs_emulator(monkeypatch):
    from gcs_emulator import FakeGCSServer

    with FakeGCSServer() as srv:
        monkeypatch.setenv("STORAGE_EMULATOR_HOST", srv.endpoint)
        monkeypatch.setenv("GOOGLE_CLOUD_PROJECT", "test-project")
        yield srv


def _emulator_plugin(root="bkt/pre"):
    from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

    return GCSStoragePlugin(root)


def test_emulator_small_object_multipart_roundtrip(gcs_emulator) -> None:
    plugin = _emulator_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = b"payload-" * 1000
        loop.run_until_complete(plugin.write(WriteIO(path="a/b", buf=data)))
        rio = ReadIO(path="a/b")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
        # Ranged read travels as an inclusive HTTP Range on the media URL.
        rio2 = ReadIO(path="a/b", byte_range=(8, 24))
        loop.run_until_complete(plugin.read(rio2))
        assert rio2.buf.getvalue() == data[8:24]
        loop.run_until_complete(plugin.delete("a/b"))
        with pytest.raises(FileNotFoundError):
            loop.run_until_complete(plugin.read(ReadIO(path="a/b")))
        # The multipart upload wire path was actually used.
        assert any(
            "uploadType=multipart" in line
            for line in gcs_emulator.state.request_log
        )
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


@pytest.mark.parametrize("chunk_kib", [256, 768], ids=["8-chunks", "short-tail"])
def test_emulator_resumable_upload_survives_chunk_fault(
    gcs_emulator, chunk_kib
) -> None:
    """A 503 on one chunk PUT is absorbed by the stack (google-resumable-
    media's internal retry re-sends the chunk over the real wire; the
    plugin's cursor recovery is the second line of defense for faults that
    escape it) and the upload completes byte-exact: one object, whichever
    chunk size the plugin cuts a large write into."""
    from torchsnapshot_tpu.utils import knobs as _knobs

    plugin = _emulator_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = bytes(range(256)) * 8192  # 2 MiB
        with _knobs.override_gcs_chunk_bytes(chunk_kib * 1024):
            gcs_emulator.fail_next("PUT /upload", n=1, status=503)
            loop.run_until_complete(plugin.write(WriteIO(path="big", buf=data)))
        rio = ReadIO(path="big")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
        log = gcs_emulator.state.request_log
        assert any("uploadType=resumable" in line for line in log)
        # Every chunk + at least one retransmit of the faulted chunk.
        chunks = -(-len(data) // (chunk_kib * 1024))
        assert sum(1 for line in log if "PUT /upload" in line) >= chunks + 1
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


def test_emulator_session_recover_speaks_real_wire_protocol(gcs_emulator) -> None:
    """The plugin's `_GoogleResumableSession.recover` against the real
    protocol: a `bytes */N` status probe whose `308 + Range` reply resets
    the client cursor to the server's persisted offset."""
    from torchsnapshot_tpu.storage_plugins.gcs import _GoogleResumableSession
    from torchsnapshot_tpu.storage_plugins.gcs import _make_authorized_session

    plugin = _emulator_plugin()
    try:
        data = bytes(range(256)) * 4096  # 1 MiB
        session = _GoogleResumableSession(
            plugin._client,
            "bkt",
            "recov",
            memoryview(data),
            256 * 1024,
            transport_factory=lambda: _make_authorized_session(plugin._client),
        )
        session.transmit_next_chunk()
        assert session.bytes_uploaded == 256 * 1024
        # Simulate an escaped mid-chunk fault: the upload is marked invalid,
        # exactly the state the plugin's recovery path handles.
        session._upload._invalid = True
        session.recover()
        assert session.bytes_uploaded == 256 * 1024
        assert any(
            line.startswith("PROBE") for line in gcs_emulator.state.request_log
        )
        while not session.finished:
            session.transmit_next_chunk()
        loop = asyncio.new_event_loop()
        rio = ReadIO(path="recov")
        # Raw bucket object (no plugin prefix was used for this session).
        from torchsnapshot_tpu.storage_plugins.gcs import GCSStoragePlugin

        raw = GCSStoragePlugin("bkt")
        try:
            loop.run_until_complete(raw.read(rio))
        finally:
            loop.run_until_complete(raw.close())
            loop.close()
        assert rio.buf.getvalue() == data
    finally:
        loop2 = asyncio.new_event_loop()
        loop2.run_until_complete(plugin.close())
        loop2.close()


def test_emulator_transient_download_faults_retried(gcs_emulator) -> None:
    plugin = _emulator_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = b"x" * 4096
        loop.run_until_complete(plugin.write(WriteIO(path="obj", buf=data)))
        gcs_emulator.fail_next("GET /download", n=2, status=503)
        rio = ReadIO(path="obj")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


def test_emulator_link_in_rewrite_token_loop(gcs_emulator) -> None:
    """Server-side copy via the real rewrite API, including a forced
    multi-round token loop (big/cross-class copies return tokens)."""
    plugin = _emulator_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = b"frozen-weights" * 100
        loop.run_until_complete(plugin.write(WriteIO(path="base_obj", buf=data)))
        gcs_emulator.force_rewrite_token_rounds(1)
        ok = loop.run_until_complete(
            plugin.link_in("gs://bkt/pre/base_obj", "copied_obj")
        )
        assert ok
        rio = ReadIO(path="copied_obj")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
        rewrites = [
            line
            for line in gcs_emulator.state.request_log
            if "rewriteTo" in line
        ]
        assert len(rewrites) >= 2  # token round + completion round
        assert any("rewriteToken=" in line for line in rewrites)
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


def test_emulator_snapshot_end_to_end(gcs_emulator) -> None:
    """Full Snapshot.take/restore/read_object/verify against gs:// through
    the real SDK wire path."""
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    arr = np.arange(4096, dtype=np.float32)
    path = "gs://bkt/snapshots/s1"
    Snapshot.take(path, {"s": StateDict(arr=arr, step=3)})
    out = {"s": StateDict(arr=np.zeros(4096, dtype=np.float32), step=0)}
    snap = Snapshot(path)
    snap.restore(out)
    assert np.array_equal(out["s"]["arr"], arr)
    assert out["s"]["step"] == 3
    got = snap.read_object("0/s/arr", memory_budget_bytes=4096)
    assert np.array_equal(got, arr)
    assert snap.verify() == {}
