"""Google Cloud Storage plugin — the TPU-native store.

Analogue of the reference's ``storage_plugins/gcs.py:47-270``: chunked
resumable uploads/downloads on a thread pool behind the async interface,
with retry on transient errors and ranged reads for random access.

The ``google-cloud-storage`` SDK is synchronous, so all blob operations run
in a dedicated thread pool (the reference used the same pattern with 8
workers); many uploads/downloads therefore proceed concurrently under the
scheduler's 16-op in-flight cap.

Import of the SDK is lazy and gated: constructing the plugin without
``google-cloud-storage`` installed raises a clear error instead of failing
at import time.
"""

from __future__ import annotations

import asyncio
import logging
from concurrent.futures import ThreadPoolExecutor

from .. import telemetry
from ..io_types import ReadIO, StoragePlugin, WriteIO
from ..memoryview_stream import MemoryviewStream
from ..utils import knobs
from .cloud_retry import CollectiveProgress, backoff_s, retry_transient

logger = logging.getLogger(__name__)

_IO_THREADS = 8
# Consecutive transmits of ONE resumable chunk with no cursor advance before
# the upload aborts (~2.5 min at max backoff). Needed because successful
# cursor-recovery calls keep the collective-progress window open forever.
_MAX_STALLED_CHUNK_RETRIES = 12


class GCSStoragePlugin(StoragePlugin):
    def __init__(self, root: str) -> None:
        try:
            from google.cloud import storage as gcs  # type: ignore[import-not-found]
        except ImportError as e:
            raise RuntimeError(
                "gs:// storage requires the google-cloud-storage package "
                "(pip install 'torchsnapshot_tpu[gcs]')"
            ) from e
        bucket_name, _, self.prefix = root.partition("/")
        self._client = gcs.Client()
        self._bucket = self._client.bucket(bucket_name)
        self._executor = ThreadPoolExecutor(max_workers=_IO_THREADS)
        self._progress = CollectiveProgress()

    def _blob_path(self, path: str) -> str:
        return f"{self.prefix}/{path}" if self.prefix else path

    def _make_upload_transport(self):
        """A fresh AuthorizedSession PER resumable upload. ``requests.
        Session`` is not documented thread-safe, and concurrent large-object
        uploads run on different executor threads — a shared session risks
        cookie-jar/credential-refresh races (ADVICE round 2, item 2). Each
        upload still reuses its own connection across all of its chunks,
        which is where connection reuse actually pays."""
        return _make_authorized_session(self._client)

    async def _retrying(self, fn) -> object:
        loop = asyncio.get_running_loop()
        return await retry_transient(
            lambda: loop.run_in_executor(self._executor, fn),
            _is_transient,
            self._progress,
            "GCS",
        )

    async def write(self, write_io: WriteIO) -> None:
        mv = memoryview(write_io.buf)
        with telemetry.span(
            "storage.write",
            cat="storage",
            plugin="gcs",
            path=write_io.path,
            nbytes=mv.nbytes,
        ):
            if mv.nbytes > knobs.get_gcs_chunk_bytes():
                await self._upload_resumable(write_io.path, mv)
            else:
                blob = self._bucket.blob(self._blob_path(write_io.path))

                def upload() -> None:
                    blob.upload_from_file(
                        MemoryviewStream(mv), size=mv.nbytes, rewind=True
                    )

                await self._retrying(upload)
        telemetry.counter_add("storage.gcs.write_bytes", mv.nbytes)

    async def _upload_resumable(self, path: str, mv: memoryview) -> None:
        """Chunked resumable upload with write-cursor recovery (reference
        ``gcs.py:110-122``).

        On a transient mid-transfer failure the session's persisted byte
        offset is recovered from the server and the stream repositioned
        there, so at most the interrupted chunk is re-sent — re-sending a
        whole 100 MB+ slab per fault on a flaky link is what this avoids.
        Whole-object one-shot uploads (below the chunk threshold) keep the
        simpler retry-the-object path in :meth:`write`.
        """
        loop = asyncio.get_running_loop()
        chunk_bytes = knobs.get_gcs_chunk_bytes()

        def initiate():
            return _make_resumable_session(
                self._client,
                self._bucket.name,
                self._blob_path(path),
                mv,
                chunk_bytes,
                transport_factory=self._make_upload_transport,
            )

        session = await self._retrying(initiate)
        try:
            await self._drive_resumable(loop, session, path)
        finally:
            # The per-upload transport's connection pool dies with the upload.
            close = getattr(session, "close", None)
            if close is not None:
                close()

    async def _drive_resumable(self, loop, session, path: str) -> None:
        """Transmit chunks with transient retry + cursor recovery until the
        session finishes."""
        attempt = 0
        stalled = 0
        while not session.finished:
            cursor = session.bytes_uploaded
            # Op start counts as activity (same convention as _retrying):
            # a single chunk can legitimately take longer than the progress
            # window on a slow link, and its first fault must still get a
            # recover+retry rather than finding the window already expired.
            self._progress.note_progress()
            try:
                await loop.run_in_executor(self._executor, session.transmit_next_chunk)
            except Exception as e:  # noqa: BLE001 - classified below
                if not _is_transient(e) or self._progress.out_of_time():
                    raise
                attempt += 1
                # Same window clamping retry_transient applies (PR 5): a
                # backoff sleep never overshoots the collective-progress
                # deadline by more than the epsilon, and the post-sleep
                # re-check below surfaces the error promptly when nothing
                # else made progress meanwhile.
                backoff = min(
                    backoff_s(attempt), self._progress.remaining_s() + 0.05
                )
                logger.warning(
                    "Transient GCS error mid-upload of %s at byte %d "
                    "(attempt %d, recovering cursor and retrying in %.1fs): %s",
                    path,
                    cursor,
                    attempt,
                    backoff,
                    e,
                )
                await asyncio.sleep(backoff)
                if self._progress.out_of_time():
                    # The window expired during the sleep (and nothing else
                    # made progress): surface the transient error now.
                    raise
                # Recover the server's persisted write cursor; the session
                # repositions the source stream to it. recover() is
                # idempotent, so it gets the same transient-retry treatment
                # as any other op.
                try:
                    await self._retrying(session.recover)
                except Exception as recover_exc:  # noqa: BLE001
                    if _response_status(recover_exc) in (200, 201):
                        # The interrupted transmit was actually the final
                        # chunk and only its ack was lost: a status probe of
                        # a *completed* resumable session returns 200 (not
                        # 308), which resumable_media surfaces as
                        # InvalidResponse. The object is committed
                        # server-side — the upload is done.
                        return
                    raise
                # Stalled-chunk cap, judged on the *recovered* cursor (a
                # failed transmit never advances bytes_uploaded; only
                # recover() reveals server-side partial progress). It exists
                # because the collective-progress window alone cannot expire
                # this loop — a successful recover() refreshes the window
                # every iteration even when no byte ever lands. N consecutive
                # faults with a frozen cursor mean the chunk is
                # undeliverable — give up. Faults with forward progress
                # (flaky link, server keeps partial bytes each round) reset
                # the counter and retry indefinitely within the window.
                stalled = stalled + 1 if session.bytes_uploaded <= cursor else 0
                if stalled >= _MAX_STALLED_CHUNK_RETRIES:
                    raise
                continue
            if session.bytes_uploaded > cursor:
                attempt = 0
                stalled = 0
                self._progress.note_progress()

    async def read(self, read_io: ReadIO) -> None:
        blob = self._bucket.blob(self._blob_path(read_io.path))
        with telemetry.span(
            "storage.read", cat="storage", plugin="gcs", path=read_io.path
        ) as sp:
            try:
                if read_io.byte_range is None:
                    data = await self._retrying(blob.download_as_bytes)
                else:
                    begin, end = read_io.byte_range
                    data = await self._retrying(
                        # GCS ranges are inclusive on both ends.
                        lambda: blob.download_as_bytes(start=begin, end=end - 1)
                    )
            except Exception as e:
                if _is_not_found(e):
                    raise FileNotFoundError(read_io.path) from e
                raise
            sp.set_attrs(nbytes=len(data))
            read_io.buf.write(data)
        telemetry.counter_add("storage.gcs.read_bytes", len(data))

    async def delete(self, path: str) -> None:
        blob = self._bucket.blob(self._blob_path(path))
        try:
            await self._retrying(blob.delete)
        except Exception as e:
            if _is_not_found(e):
                raise FileNotFoundError(path) from e
            raise

    async def list_prefix(self, prefix: str) -> list:
        full = self._blob_path(prefix) if prefix else self.prefix
        strip = f"{self.prefix}/" if self.prefix else ""

        def work() -> list:
            blobs = self._client.list_blobs(self._bucket.name, prefix=full)
            return sorted(
                b.name[len(strip):] for b in blobs if b.name.startswith(strip)
            )

        return await self._retrying(work)

    async def link_in(self, src_abs_path: str, path: str) -> bool:
        """Server-side copy from a base snapshot (incremental takes): a GCS
        rewrite moves no bytes through this host. ``src_abs_path`` is the
        base object's full ``gs://bucket/...`` URL; only same-provider
        sources are supported (cross-bucket works — rewrites are
        server-side either way)."""
        if not src_abs_path.startswith("gs://"):
            return False
        src_bucket_name, _, src_key = src_abs_path[len("gs://") :].partition("/")
        with telemetry.span(
            "storage.link_in", cat="storage", plugin="gcs", path=path
        ) as sp:
            ok = await self._link_in_inner(src_bucket_name, src_key, path)
            sp.set_attrs(linked=ok)
        if ok:
            telemetry.counter_add("storage.gcs.link_in_count")
        return ok

    async def _link_in_inner(
        self, src_bucket_name: str, src_key: str, path: str
    ) -> bool:
        src_abs_path = f"gs://{src_bucket_name}/{src_key}"
        try:
            src_bucket = self._client.bucket(src_bucket_name)
            src_blob = src_bucket.blob(src_key)
            dst_blob = self._bucket.blob(self._blob_path(path))

            def copy() -> None:
                # Rewrite (not objects.copy): resumable via token loop, so
                # multi-GB and cross-location/storage-class copies don't
                # blow a single-request deadline.
                token, _, _ = dst_blob.rewrite(src_blob)
                while token is not None:
                    token, _, _ = dst_blob.rewrite(src_blob, token=token)

            await self._retrying(copy)
            return True
        except Exception:
            logger.warning(
                "Server-side copy of %s failed; rewriting the object",
                src_abs_path,
                exc_info=True,
            )
            return False

    async def close(self) -> None:
        self._executor.shutdown(wait=True)


class _GoogleResumableSession:
    """Thin sync wrapper over ``google.resumable_media``'s resumable upload.

    Everything above this seam (chunk loop, per-chunk retry, cursor
    recovery, collective-progress accounting) is plugin logic drilled by the
    fake-server tests; this class is the only part that touches the real
    wire protocol, covered by the gated integration test.
    """

    def __init__(
        self,
        client,
        bucket_name: str,
        blob_name: str,
        mv: memoryview,
        chunk_bytes: int,
        transport_factory,
    ) -> None:
        from google.resumable_media.requests import ResumableUpload  # type: ignore[import-not-found]

        # Per-upload session (see GCSStoragePlugin._make_upload_transport);
        # closed by the upload loop — or right here if initiate() fails, so
        # retried initiates can't leak one connection pool per attempt.
        self._transport = transport_factory()
        # Honor custom endpoints (emulators, private Google access) the same
        # way Blob.upload does: the base URL comes from the client's
        # connection, not a hardcoded production host.
        api_base = getattr(
            getattr(client, "_connection", None),
            "API_BASE_URL",
            "https://storage.googleapis.com",
        )
        upload_url = (
            f"{api_base}/upload/storage/v1/b/{bucket_name}/o?uploadType=resumable"
        )
        # The wire protocol requires 256 KiB-multiple chunks; round up here
        # (the real-session layer) so any knob value works — passing a raw
        # sub-multiple would raise a non-transient ValueError on the first
        # large write.
        quantum = 256 * 1024
        chunk_bytes = max(quantum, (chunk_bytes + quantum - 1) // quantum * quantum)
        self._upload = ResumableUpload(upload_url, chunk_bytes)
        try:
            self._upload.initiate(
                self._transport,
                MemoryviewStream(mv),
                metadata={"name": blob_name},
                content_type="application/octet-stream",
                total_bytes=mv.nbytes,
            )
        except BaseException:
            self.close()
            raise

    @property
    def finished(self) -> bool:
        return self._upload.finished

    @property
    def bytes_uploaded(self) -> int:
        return int(self._upload.bytes_uploaded or 0)

    def transmit_next_chunk(self) -> None:
        self._upload.transmit_next_chunk(self._transport)

    def recover(self) -> None:
        self._upload.recover(self._transport)

    def close(self) -> None:
        try:
            self._transport.close()
        except Exception:  # pragma: no cover - session already dead
            pass


def _response_status(e: Exception):
    """HTTP status attached to an SDK error (e.g. InvalidResponse), or None."""
    return getattr(getattr(e, "response", None), "status_code", None)


def _make_authorized_session(client):
    from google.auth.transport.requests import AuthorizedSession  # type: ignore[import-not-found]

    return AuthorizedSession(client._credentials)


def _make_resumable_session(
    client,
    bucket_name: str,
    blob_name: str,
    mv: memoryview,
    chunk_bytes: int,
    transport_factory,
):
    """Indirection point: fake-server tests replace this to simulate a GCS
    resumable session with injected mid-chunk faults. ``transport_factory``
    is a zero-arg callable yielding the plugin's shared authorized session;
    fakes never call it."""
    return _GoogleResumableSession(
        client, bucket_name, blob_name, mv, chunk_bytes, transport_factory
    )


def _is_not_found(e: Exception) -> bool:
    """Backend absence, normalized per the StoragePlugin contract."""
    try:
        from google.api_core import exceptions as gexc  # type: ignore[import-not-found]

        return isinstance(e, gexc.NotFound)
    except ImportError:
        return False


def _is_transient(e: Exception) -> bool:
    try:
        from google.api_core import exceptions as gexc  # type: ignore[import-not-found]

        if isinstance(
            e,
            (
                gexc.TooManyRequests,
                gexc.InternalServerError,
                gexc.BadGateway,
                gexc.ServiceUnavailable,
                gexc.GatewayTimeout,
            ),
        ):
            return True
    except ImportError:
        pass
    try:
        from google.resumable_media import InvalidResponse  # type: ignore[import-not-found]

        if isinstance(e, InvalidResponse):
            # Resumable-upload chunk failures surface as InvalidResponse
            # with the HTTP status attached; retry the retryable statuses.
            code = getattr(e.response, "status_code", None)
            return code in (408, 429, 500, 502, 503, 504)
    except ImportError:
        pass
    return isinstance(e, (ConnectionError, TimeoutError))
