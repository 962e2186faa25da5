"""S3 plugin tests (reference ``tests/test_s3_storage_plugin.py``): fake
aioboto3 SDK for unit coverage; REAL-SDK wire-path coverage against a local
moto server (gated on aioboto3+moto being importable — CI installs both);
live-bucket integration env-var gated."""

import asyncio
import os
import re
import sys
import types

import pytest

from torchsnapshot_tpu.io_types import ReadIO, WriteIO


def _install_fake_aioboto3(monkeypatch, objects: dict) -> None:
    class FakeStream:
        def __init__(self, data: bytes) -> None:
            self._data = data

        async def __aenter__(self):
            return self

        async def __aexit__(self, *exc):
            return False

        async def read(self) -> bytes:
            return self._data

    class FakeClient:
        async def put_object(self, Bucket, Key, Body) -> None:
            objects[(Bucket, Key)] = bytes(
                Body.read() if hasattr(Body, "read") else Body
            )

        @staticmethod
        def _lookup(Bucket, Key) -> bytes:
            try:
                return objects[(Bucket, Key)]
            except KeyError:
                # Structured botocore-style error response (what the
                # plugin's absence normalization reads).
                e = Exception(f"NoSuchKey: {Key}")
                e.response = {"Error": {"Code": "NoSuchKey"}}
                raise e from None

        async def get_object(self, Bucket, Key, **kwargs):
            data = self._lookup(Bucket, Key)
            if "Range" in kwargs:
                m = re.fullmatch(r"bytes=(\d+)-(\d+)", kwargs["Range"])
                assert m, f"malformed Range header: {kwargs['Range']}"
                lo, hi_inclusive = int(m.group(1)), int(m.group(2))
                data = data[lo : hi_inclusive + 1]
            return {"Body": FakeStream(data)}

        async def delete_object(self, Bucket, Key) -> None:
            objects.pop((Bucket, Key), None)  # S3 deletes are idempotent

    class FakeClientCtx:
        async def __aenter__(self):
            return FakeClient()

        async def __aexit__(self, *exc):
            return False

    class FakeSession:
        def client(self, service):
            assert service == "s3"
            return FakeClientCtx()

    mod = types.ModuleType("aioboto3")
    mod.Session = FakeSession
    monkeypatch.setitem(sys.modules, "aioboto3", mod)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture
def fake_s3(monkeypatch):
    objects: dict = {}
    _install_fake_aioboto3(monkeypatch, objects)
    return objects


def test_write_read_roundtrip(fake_s3) -> None:
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    plugin = S3StoragePlugin(root="bucket/check/points")
    payload = bytes(range(256)) * 4

    async def go():
        await plugin.write(WriteIO(path="a/b", buf=memoryview(payload)))
        rio = ReadIO(path="a/b")
        await plugin.read(rio)
        await plugin.close()
        return rio.buf.getvalue()

    assert _run(go()) == payload
    assert set(fake_s3) == {("bucket", "check/points/a/b")}


def test_ranged_read_http_range_translation(fake_s3) -> None:
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    plugin = S3StoragePlugin(root="bucket")
    payload = bytes(range(256))

    async def go():
        await plugin.write(WriteIO(path="blob", buf=payload))
        out = []
        for lo, hi in [(0, 1), (10, 20), (128, 256)]:
            rio = ReadIO(path="blob", byte_range=(lo, hi))
            await plugin.read(rio)
            out.append((lo, hi, rio.buf.getvalue()))
        await plugin.close()
        return out

    # Half-open [lo, hi) must become an inclusive-end HTTP Range header
    # (reference fixes the same off-by-one at ``s3.py:53-60``).
    for lo, hi, got in _run(go()):
        assert got == payload[lo:hi], (lo, hi)


def test_delete(fake_s3) -> None:
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    plugin = S3StoragePlugin(root="bucket")

    async def go():
        await plugin.write(WriteIO(path="doomed", buf=b"x"))
        await plugin.delete("doomed")
        await plugin.close()

    _run(go())
    assert fake_s3 == {}


def test_missing_sdk_raises_clear_error(monkeypatch) -> None:
    import builtins

    real_import = builtins.__import__

    def no_boto(name, *args, **kwargs):
        if name == "aioboto3":
            raise ImportError(name)
        return real_import(name, *args, **kwargs)

    monkeypatch.delitem(sys.modules, "aioboto3", raising=False)
    monkeypatch.setattr(builtins, "__import__", no_boto)
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    with pytest.raises(RuntimeError, match="aioboto3"):
        S3StoragePlugin(root="bucket")


@pytest.mark.skipif(
    "TORCHSNAPSHOT_TPU_S3_TEST_BUCKET" not in os.environ,
    reason="live S3 integration is env-var gated",
)
def test_live_snapshot_roundtrip() -> None:
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    bucket = os.environ["TORCHSNAPSHOT_TPU_S3_TEST_BUCKET"]
    path = f"s3://{bucket}/torchsnapshot_tpu_ci/{os.getpid()}"
    arr = np.arange(1024, dtype=np.float32)
    Snapshot.take(path, {"s": StateDict(arr=arr)})
    out = {"s": StateDict(arr=np.zeros(1024, dtype=np.float32))}
    Snapshot(path).restore(out)
    assert np.array_equal(out["s"]["arr"], arr)


def test_absent_object_normalized_to_file_not_found(fake_s3) -> None:
    """Per the StoragePlugin contract: read of an absent object raises
    FileNotFoundError (normalized from S3's structured NoSuchKey); delete is
    idempotent (S3 returns 204 for absent keys) and succeeds silently."""
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    plugin = S3StoragePlugin(root="bucket")

    async def go():
        with pytest.raises(FileNotFoundError):
            await plugin.read(ReadIO(path="missing"))
        await plugin.delete("missing")  # idempotent: no error
        await plugin.close()

    _run(go())


# ---------------------------------------------------------------------------
# Multipart uploads with per-part retry (the S3 analogue of GCS resumable
# cursor recovery: a transient fault re-sends at most one part).
# ---------------------------------------------------------------------------

def _install_fake_multipart_s3(monkeypatch, objects: dict, stats: dict, faults: dict):
    """Fake client with multipart APIs; ``faults`` maps part numbers to a
    list of exceptions raised on successive upload attempts of that part."""

    class FakeClient:
        def __init__(self):
            self._mpu: dict = {}  # upload_id -> {part_number: bytes}

        async def put_object(self, Bucket, Key, Body) -> None:
            stats["puts"] = stats.get("puts", 0) + 1
            objects[(Bucket, Key)] = bytes(Body)

        async def create_multipart_upload(self, Bucket, Key):
            upload_id = f"mpu-{len(self._mpu)}"
            self._mpu[upload_id] = {}
            stats["created"] = stats.get("created", 0) + 1
            return {"UploadId": upload_id}

        async def upload_part(self, Bucket, Key, PartNumber, UploadId, Body):
            data = bytes(Body)
            stats["part_bytes_sent"] = stats.get("part_bytes_sent", 0) + len(data)
            pending = faults.get(PartNumber)
            if pending:
                raise pending.pop(0)
            self._mpu[UploadId][PartNumber] = data
            return {"ETag": f"etag-{PartNumber}"}

        async def complete_multipart_upload(self, Bucket, Key, UploadId, MultipartUpload):
            if faults.pop("complete_vanishes", None):
                # The upload id is gone WITHOUT a commit (e.g. aborted by a
                # bucket lifecycle rule mid-upload): NoSuchUpload and no
                # object to probe.
                self._mpu.pop(UploadId, None)
                e = Exception("NoSuchUpload")
                e.response = {"Error": {"Code": "NoSuchUpload"}}
                raise e
            if UploadId not in self._mpu:
                # S3 semantics: a consumed upload id (already completed or
                # aborted) yields NoSuchUpload.
                e = Exception("NoSuchUpload")
                e.response = {"Error": {"Code": "NoSuchUpload"}}
                raise e
            parts = self._mpu.pop(UploadId)
            ordered = [parts[p["PartNumber"]] for p in MultipartUpload["Parts"]]
            objects[(Bucket, Key)] = b"".join(ordered)
            stats["completed"] = stats.get("completed", 0) + 1
            if faults.pop("complete_commits_then_fails", None):
                # S3's documented 200-with-InternalError-body case: the
                # commit HAPPENED server-side but the call surfaces an error.
                e = Exception("InternalError")
                e.response = {"Error": {"Code": "InternalError"}}
                raise e

        async def abort_multipart_upload(self, Bucket, Key, UploadId):
            if UploadId not in self._mpu:
                e = Exception("NoSuchUpload")
                e.response = {"Error": {"Code": "NoSuchUpload"}}
                raise e
            self._mpu.pop(UploadId, None)
            stats["aborted"] = stats.get("aborted", 0) + 1

        async def head_object(self, Bucket, Key):
            stats["heads"] = stats.get("heads", 0) + 1
            if (Bucket, Key) not in objects:
                e = Exception("NotFound")
                e.response = {"Error": {"Code": "404"}}
                raise e
            return {"ContentLength": len(objects[(Bucket, Key)])}

        async def get_object(self, Bucket, Key, **kwargs):
            try:
                data = objects[(Bucket, Key)]
            except KeyError:
                e = Exception(f"NoSuchKey: {Key}")
                e.response = {"Error": {"Code": "NoSuchKey"}}
                raise e from None
            if "Range" in kwargs:
                m = re.fullmatch(r"bytes=(\d+)-(\d+)", kwargs["Range"])
                lo, hi_inclusive = int(m.group(1)), int(m.group(2))
                data = data[lo : hi_inclusive + 1]

            class _Stream:
                async def __aenter__(self):
                    return self

                async def __aexit__(self, *exc):
                    return False

                async def read(self):
                    return data

            return {"Body": _Stream()}

        async def delete_object(self, Bucket, Key) -> None:
            objects.pop((Bucket, Key), None)

    class FakeClientCtx:
        async def __aenter__(self):
            return FakeClient()

        async def __aexit__(self, *exc):
            return False

    class FakeSession:
        def client(self, service):
            return FakeClientCtx()

    mod = types.ModuleType("aioboto3")
    mod.Session = FakeSession
    monkeypatch.setitem(sys.modules, "aioboto3", mod)


@pytest.fixture
def fake_multipart_s3(monkeypatch):
    from torchsnapshot_tpu.storage_plugins import cloud_retry

    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    objects: dict = {}
    stats: dict = {}
    faults: dict = {}
    _install_fake_multipart_s3(monkeypatch, objects, stats, faults)
    return objects, stats, faults


@pytest.mark.parametrize("part", [1024, 1500], ids=["10-parts", "short-tail"])
def test_multipart_upload_with_per_part_faults(fake_multipart_s3, part) -> None:
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin
    from torchsnapshot_tpu.utils import knobs

    objects, stats, faults = fake_multipart_s3
    payload = bytes(range(256)) * 40  # 10 KiB: 10 parts of 1 KiB, or 6 + a tail
    faults[2] = [ConnectionError("reset")]
    faults[7] = [TimeoutError("stall"), ConnectionError("reset again")]

    plugin = S3StoragePlugin(root="bucket/pre")
    with knobs.override_s3_chunk_bytes(part):
        _run(plugin.write(WriteIO(path="big", buf=memoryview(payload))))
    _run(plugin.close())
    # One object, whichever part size the plugin cut the write into.
    assert objects[("bucket", "pre/big")] == payload
    assert stats["completed"] == 1 and stats.get("aborted", 0) == 0
    # Exactly the faulted part re-sent per fault attempt (part 7 is the
    # short tail at 1500).
    size_of = lambda n: min(part, len(payload) - (n - 1) * part)  # noqa: E731
    assert stats["part_bytes_sent"] == len(payload) + size_of(2) + 2 * size_of(7)


def test_multipart_upload_aborts_on_permanent_failure(fake_multipart_s3) -> None:
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin
    from torchsnapshot_tpu.utils import knobs

    objects, stats, faults = fake_multipart_s3
    denied = Exception("AccessDenied")
    denied.response = {"Error": {"Code": "AccessDenied"}}
    faults[3] = [denied]

    plugin = S3StoragePlugin(root="bucket")
    with knobs.override_s3_chunk_bytes(1024):
        with pytest.raises(Exception, match="AccessDenied"):
            _run(plugin.write(WriteIO(path="nope", buf=bytes(4096))))
    _run(plugin.close())
    assert ("bucket", "nope") not in objects
    assert stats.get("aborted", 0) == 1  # no orphaned parts left behind


def test_multipart_complete_committed_server_side_is_success(fake_multipart_s3) -> None:
    """S3's 200-with-InternalError-body case: complete_multipart_upload
    commits server-side but surfaces a transient error; the retry gets
    NoSuchUpload. The plugin must HEAD the object and treat present +
    correct size as success — not a spurious take failure (ADVICE round 2,
    item 1)."""
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin
    from torchsnapshot_tpu.utils import knobs

    objects, stats, faults = fake_multipart_s3
    faults["complete_commits_then_fails"] = True
    payload = bytes(range(256)) * 16  # 4 KiB -> 4 parts

    plugin = S3StoragePlugin(root="bucket")
    with knobs.override_s3_chunk_bytes(1024):
        _run(plugin.write(WriteIO(path="committed", buf=memoryview(payload))))
    _run(plugin.close())
    assert objects[("bucket", "committed")] == payload
    assert stats.get("heads", 0) >= 1  # the probe ran
    assert stats.get("aborted", 0) == 0  # nothing to abort — it committed


def test_probe_failure_surfaces_original_complete_error(fake_multipart_s3) -> None:
    """When the NoSuchUpload probe itself fails (no committed object — the
    upload truly vanished), the surfaced error must be the ORIGINAL
    complete_multipart_upload failure, with the probe error chained beneath
    it — not the probe's 404 masking the root cause (ADVICE round 3,
    item 1)."""
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin
    from torchsnapshot_tpu.utils import knobs

    objects, stats, faults = fake_multipart_s3
    faults["complete_vanishes"] = True
    plugin = S3StoragePlugin(root="bucket")
    with knobs.override_s3_chunk_bytes(1024):
        with pytest.raises(Exception, match="NoSuchUpload") as excinfo:
            _run(plugin.write(WriteIO(path="gone", buf=bytes(4096))))
    _run(plugin.close())
    # The probe's not-found is the cause, not the headline.
    assert "NotFound" in repr(excinfo.value.__cause__)
    assert ("bucket", "gone") not in objects


def test_small_objects_keep_single_put(fake_multipart_s3) -> None:
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin
    from torchsnapshot_tpu.utils import knobs

    objects, stats, _ = fake_multipart_s3
    plugin = S3StoragePlugin(root="bucket")
    with knobs.override_s3_chunk_bytes(1024):
        _run(plugin.write(WriteIO(path="small", buf=b"tiny")))
    _run(plugin.close())
    assert objects[("bucket", "small")] == b"tiny"
    assert stats.get("puts") == 1 and "created" not in stats


def test_transient_s3_codes_retried(fake_s3, monkeypatch) -> None:
    """Structured throttling codes retry; the op eventually succeeds."""
    from torchsnapshot_tpu.storage_plugins import cloud_retry
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    plugin = S3StoragePlugin(root="bucket")

    async def go():
        client = await plugin._get_client()
        real_put = client.put_object
        remaining = {"n": 2}

        async def flaky_put(**kw):
            if remaining["n"]:
                remaining["n"] -= 1
                e = Exception("SlowDown")
                e.response = {"Error": {"Code": "SlowDown"}}
                raise e
            return await real_put(**kw)

        client.put_object = flaky_put
        await plugin.write(WriteIO(path="k", buf=b"v"))
        await plugin.close()

    _run(go())
    assert fake_s3[("bucket", "k")] == b"v"


def test_botocore_network_errors_classified_transient(monkeypatch) -> None:
    """Real aiobotocore network faults are botocore exception types, not the
    Python builtins — they must classify as transient."""
    gexc = types.ModuleType("botocore.exceptions")

    class FakeBotoConnErr(Exception):
        pass

    class FakeHTTPClientError(Exception):
        pass

    gexc.ConnectionError = FakeBotoConnErr
    gexc.HTTPClientError = FakeHTTPClientError
    boto_mod = types.ModuleType("botocore")
    boto_mod.exceptions = gexc
    monkeypatch.setitem(sys.modules, "botocore", boto_mod)
    monkeypatch.setitem(sys.modules, "botocore.exceptions", gexc)
    from torchsnapshot_tpu.storage_plugins.s3 import _is_transient

    assert _is_transient(FakeBotoConnErr("endpoint reset"))
    assert _is_transient(FakeHTTPClientError("read timeout"))
    assert not _is_transient(ValueError("permanent"))
    denied = Exception("AccessDenied")
    denied.response = {"Error": {"Code": "AccessDenied"}}
    assert not _is_transient(denied)


def test_mid_stream_read_fault_retried(fake_s3, monkeypatch) -> None:
    """A connection reset DURING the body download retries the whole read,
    not just the initial request."""
    from torchsnapshot_tpu.storage_plugins import cloud_retry
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    monkeypatch.setattr(cloud_retry, "BASE_BACKOFF_S", 0.001)
    plugin = S3StoragePlugin(root="bucket")

    async def go():
        await plugin.write(WriteIO(path="k", buf=b"payload"))
        client = await plugin._get_client()
        real_get = client.get_object
        remaining = {"n": 2}

        async def get_with_flaky_stream(**kw):
            resp = await real_get(**kw)
            if remaining["n"]:
                remaining["n"] -= 1

                class _Dying:
                    async def __aenter__(self):
                        return self

                    async def __aexit__(self, *exc):
                        return False

                    async def read(self):
                        raise ConnectionError("reset mid-stream")

                return {"Body": _Dying()}
            return resp

        client.get_object = get_with_flaky_stream
        rio = ReadIO(path="k")
        await plugin.read(rio)
        await plugin.close()
        return rio.buf.getvalue()

    assert _run(go()) == b"payload"


# ---------------------------------------------------------------------------
# Emulator-backed wire-path tests: the REAL aioboto3/botocore stack against a
# local moto server. Gated on the SDK +
# moto being importable — this image ships neither, so they self-skip
# locally; CI's unit_test.yaml installs both and runs them on every push.
# The plugin needs no code changes: botocore honors AWS_ENDPOINT_URL_S3.
# ---------------------------------------------------------------------------


@pytest.fixture
def s3_emulator(monkeypatch):
    pytest.importorskip("aioboto3")
    moto_server = pytest.importorskip("moto.server")
    server = moto_server.ThreadedMotoServer(port=0)
    server.start()
    host, port = server.get_host_and_port()
    endpoint = f"http://{host}:{port}"
    monkeypatch.setenv("AWS_ENDPOINT_URL_S3", endpoint)
    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "testing")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "testing")
    monkeypatch.setenv("AWS_DEFAULT_REGION", "us-east-1")
    # Create the bucket through the real sync SDK moto ships with.
    import boto3

    boto3.client("s3", endpoint_url=endpoint).create_bucket(Bucket="bkt")
    try:
        yield endpoint
    finally:
        server.stop()


def _moto_plugin():
    from torchsnapshot_tpu.storage_plugins.s3 import S3StoragePlugin

    return S3StoragePlugin("bkt/pre")


def test_moto_small_object_roundtrip(s3_emulator) -> None:
    plugin = _moto_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = b"abcdefgh" * 1000
        loop.run_until_complete(plugin.write(WriteIO(path="a/b", buf=data)))
        rio = ReadIO(path="a/b")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
        # Inclusive-end HTTP Range translation over the real wire.
        rio2 = ReadIO(path="a/b", byte_range=(8, 24))
        loop.run_until_complete(plugin.read(rio2))
        assert rio2.buf.getvalue() == data[8:24]
        loop.run_until_complete(plugin.delete("a/b"))
        with pytest.raises(FileNotFoundError):
            loop.run_until_complete(plugin.read(ReadIO(path="a/b")))
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


@pytest.mark.parametrize("part_mib", [5, 6], ids=["2-parts", "short-tail"])
def test_moto_multipart_upload_lifecycle(s3_emulator, part_mib) -> None:
    """Objects above the chunk knob upload via REAL S3 multipart
    (create/upload_part/complete) and read back byte-exact: one object,
    whichever part size the plugin cuts a large write into."""
    from torchsnapshot_tpu.utils import knobs as _knobs

    plugin = _moto_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = bytes(range(256)) * 40960  # 10 MiB
        with _knobs.override_s3_chunk_bytes(part_mib * 1024 * 1024):
            loop.run_until_complete(plugin.write(WriteIO(path="big", buf=data)))
        rio = ReadIO(path="big")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


def test_moto_link_in_server_side_copy(s3_emulator) -> None:
    plugin = _moto_plugin()
    loop = asyncio.new_event_loop()
    try:
        data = b"frozen" * 500
        loop.run_until_complete(plugin.write(WriteIO(path="base", buf=data)))
        ok = loop.run_until_complete(
            plugin.link_in("s3://bkt/pre/base", "copied")
        )
        assert ok
        rio = ReadIO(path="copied")
        loop.run_until_complete(plugin.read(rio))
        assert rio.buf.getvalue() == data
    finally:
        loop.run_until_complete(plugin.close())
        loop.close()


def test_moto_snapshot_end_to_end(s3_emulator) -> None:
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    arr = np.arange(4096, dtype=np.float32)
    path = "s3://bkt/snapshots/s1"
    Snapshot.take(path, {"s": StateDict(arr=arr, step=3)})
    out = {"s": StateDict(arr=np.zeros(4096, dtype=np.float32), step=0)}
    snap = Snapshot(path)
    snap.restore(out)
    assert np.array_equal(out["s"]["arr"], arr)
    assert out["s"]["step"] == 3
    assert snap.verify() == {}
