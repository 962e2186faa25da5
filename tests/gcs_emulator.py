"""A minimal local GCS emulator speaking the JSON/upload API subset the real
``google-cloud-storage`` + ``google-resumable-media`` SDKs use.

Purpose: the round-2 GCS tests
drilled the plugin's retry/recovery logic through monkeypatched fakes, which
leaves the actual SDK wire path — multipart uploads, the resumable-upload
session protocol (308/Range cursor semantics), ranged media downloads, the
rewrite-token loop — uncovered without cloud credentials. Pointing the real
SDK at this server via ``STORAGE_EMULATOR_HOST`` exercises all of it
offline. (The reference runs its cloud tests against live buckets in a
credential-gated CI job, ``s3_integration_test.yaml``; those gated live
tests remain — this emulator makes the wire path a default-on unit test.)

Implemented endpoints:

- ``POST /upload/storage/v1/b/{bucket}/o?uploadType=multipart`` — small
  object upload (metadata + payload in one multipart/related body);
- ``POST .../o?uploadType=resumable`` — session initiate (Location header);
- ``PUT  /upload/...&upload_id=...`` — chunk upload with ``Content-Range``,
  ``308 + Range`` cursor replies, ``bytes */N`` recovery probes;
- ``GET  /download/storage/v1/b/{bucket}/o/{name}?alt=media`` — media
  download with inclusive HTTP ``Range`` support (206);
- ``GET/DELETE /storage/v1/b/{bucket}/o/{name}`` — metadata / delete;
- ``POST /storage/v1/b/{sb}/o/{sn}/rewriteTo/b/{db}/o/{dn}`` — server-side
  rewrite with an optional forced token round (exercises the token loop).

Fault injection: ``server.fail_next(match, n, status)`` makes the next ``n``
requests whose ``METHOD path`` contains ``match`` fail with ``status`` —
used to drive the *real* SDK's transient-retry and cursor-recovery paths.
"""

from __future__ import annotations

import json
import re
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple


class _State:
    def __init__(self) -> None:
        self.objects: Dict[Tuple[str, str], bytes] = {}
        self.sessions: Dict[str, dict] = {}
        self.next_session = 0
        self.faults: List[Tuple[str, int]] = []  # (substring match, status)
        self.rewrite_tokens: Dict[str, dict] = {}
        self.force_rewrite_rounds = 0  # >0: first N rewrite calls return a token
        self.lock = threading.Lock()
        self.request_log: List[str] = []


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    # ---- helpers -----------------------------------------------------------
    @property
    def state(self) -> _State:
        return self.server.state  # type: ignore[attr-defined]

    def _body(self) -> bytes:
        length = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(length) if length else b""

    def _send(self, status: int, body: bytes = b"", headers: Optional[dict] = None) -> None:
        self.send_response(status)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if body:
            self.wfile.write(body)

    def _send_json(self, status: int, obj: dict, headers: Optional[dict] = None) -> None:
        body = json.dumps(obj).encode()
        h = {"Content-Type": "application/json"}
        h.update(headers or {})
        self._send(status, body, h)

    def _maybe_fault(self) -> bool:
        key = f"{self.command} {self.path}"
        with self.state.lock:
            self.state.request_log.append(key)
            for i, (match, status) in enumerate(self.state.faults):
                if match in key:
                    self.state.faults.pop(i)
                    # Consume the request body first or the client's next
                    # request on this keep-alive socket desyncs.
                    self._body()
                    self._send_json(
                        status, {"error": {"code": status, "message": "injected"}}
                    )
                    return True
        return False

    def _object_json(self, bucket: str, name: str) -> dict:
        data = self.state.objects[(bucket, name)]
        return {
            "kind": "storage#object",
            "bucket": bucket,
            "name": name,
            "size": str(len(data)),
            "generation": "1",
            "metageneration": "1",
        }

    # ---- handlers ----------------------------------------------------------
    def do_GET(self) -> None:  # noqa: N802 - http.server API
        if self._maybe_fault():
            return
        parsed = urllib.parse.urlparse(self.path)
        m = re.fullmatch(r"/download/storage/v1/b/([^/]+)/o/(.+)", parsed.path)
        if m:  # media download
            bucket = m.group(1)
            name = urllib.parse.unquote(m.group(2))
            data = self.state.objects.get((bucket, name))
            if data is None:
                self._send_json(404, {"error": {"code": 404, "message": "Not Found"}})
                return
            rng = self.headers.get("Range")
            if rng:
                mm = re.fullmatch(r"bytes=(\d+)-(\d+)", rng)
                lo, hi = int(mm.group(1)), int(mm.group(2))
                chunk = data[lo : hi + 1]
                self._send(
                    206,
                    chunk,
                    {
                        "Content-Range": f"bytes {lo}-{lo + len(chunk) - 1}/{len(data)}",
                        "Content-Type": "application/octet-stream",
                    },
                )
                return
            self._send(200, data, {"Content-Type": "application/octet-stream"})
            return
        m = re.fullmatch(r"/storage/v1/b/([^/]+)/o/(.+)", parsed.path)
        if m:  # object metadata
            bucket = m.group(1)
            name = urllib.parse.unquote(m.group(2))
            if (bucket, name) not in self.state.objects:
                self._send_json(404, {"error": {"code": 404, "message": "Not Found"}})
                return
            self._send_json(200, self._object_json(bucket, name))
            return
        m = re.fullmatch(r"/storage/v1/b/([^/]+)", parsed.path)
        if m:  # bucket metadata
            self._send_json(200, {"kind": "storage#bucket", "name": m.group(1)})
            return
        self._send_json(404, {"error": {"code": 404, "message": "no route"}})

    def do_DELETE(self) -> None:  # noqa: N802
        if self._maybe_fault():
            return
        m = re.fullmatch(
            r"/storage/v1/b/([^/]+)/o/(.+)", urllib.parse.urlparse(self.path).path
        )
        if m:
            bucket = m.group(1)
            name = urllib.parse.unquote(m.group(2))
            if (bucket, name) not in self.state.objects:
                self._send_json(404, {"error": {"code": 404, "message": "Not Found"}})
                return
            del self.state.objects[(bucket, name)]
            self._send(204)
            return
        self._send_json(404, {"error": {"code": 404, "message": "no route"}})

    def do_POST(self) -> None:  # noqa: N802
        if self._maybe_fault():
            return
        parsed = urllib.parse.urlparse(self.path)
        query = urllib.parse.parse_qs(parsed.query)
        m = re.fullmatch(r"/upload/storage/v1/b/([^/]+)/o", parsed.path)
        if m:
            bucket = m.group(1)
            upload_type = (query.get("uploadType") or [""])[0]
            body = self._body()
            if upload_type == "multipart":
                meta, content = _parse_multipart_related(
                    body, self.headers.get("Content-Type", "")
                )
                name = meta["name"]
                self.state.objects[(bucket, name)] = content
                self._send_json(200, self._object_json(bucket, name))
                return
            if upload_type == "resumable":
                meta = json.loads(body.decode() or "{}")
                with self.state.lock:
                    sid = f"sess{self.state.next_session}"
                    self.state.next_session += 1
                    self.state.sessions[sid] = {
                        "bucket": bucket,
                        "name": meta["name"],
                        "data": bytearray(),
                        "total": None,
                        "done": False,
                    }
                host = self.headers.get("Host")
                self._send(
                    200,
                    b"",
                    {
                        "Location": (
                            f"http://{host}/upload/storage/v1/b/{bucket}/o"
                            f"?uploadType=resumable&upload_id={sid}"
                        )
                    },
                )
                return
            self._send_json(400, {"error": {"code": 400, "message": "bad uploadType"}})
            return
        m = re.fullmatch(
            r"/storage/v1/b/([^/]+)/o/(.+)/rewriteTo/b/([^/]+)/o/(.+)", parsed.path
        )
        if m:
            sb, sn = m.group(1), urllib.parse.unquote(m.group(2))
            db, dn = m.group(3), urllib.parse.unquote(m.group(4))
            self._body()
            if (sb, sn) not in self.state.objects:
                self._send_json(404, {"error": {"code": 404, "message": "Not Found"}})
                return
            token = (query.get("rewriteToken") or [None])[0]
            with self.state.lock:
                if token is None and self.state.force_rewrite_rounds > 0:
                    self.state.force_rewrite_rounds -= 1
                    self._send_json(
                        200,
                        {
                            "kind": "storage#rewriteResponse",
                            "done": False,
                            "rewriteToken": f"tok-{sb}-{sn}",
                            "totalBytesRewritten": "0",
                            "objectSize": str(len(self.state.objects[(sb, sn)])),
                        },
                    )
                    return
            self.state.objects[(db, dn)] = bytes(self.state.objects[(sb, sn)])
            self._send_json(
                200,
                {
                    "kind": "storage#rewriteResponse",
                    "done": True,
                    "totalBytesRewritten": str(len(self.state.objects[(db, dn)])),
                    "objectSize": str(len(self.state.objects[(db, dn)])),
                    "resource": self._object_json(db, dn),
                },
            )
            return
        self._send_json(404, {"error": {"code": 404, "message": "no route"}})

    def do_PUT(self) -> None:  # noqa: N802
        if self._maybe_fault():
            return
        parsed = urllib.parse.urlparse(self.path)
        query = urllib.parse.parse_qs(parsed.query)
        sid = (query.get("upload_id") or [None])[0]
        sess = self.state.sessions.get(sid)
        if sess is None:
            self._send_json(404, {"error": {"code": 404, "message": "no session"}})
            return
        body = self._body()
        content_range = self.headers.get("Content-Range", "")
        probe = re.fullmatch(r"bytes \*/(\d+|\*)", content_range)
        if probe:
            # Cursor recovery: report how many bytes the server holds.
            with self.state.lock:
                self.state.request_log.append(f"PROBE {sid}")
            self._resumable_status(sess)
            return
        mm = re.fullmatch(r"bytes (\d+)-(\d+)/(\d+|\*)", content_range)
        if not mm:
            self._send_json(400, {"error": {"code": 400, "message": content_range}})
            return
        start, end = int(mm.group(1)), int(mm.group(2))
        if mm.group(3) != "*":
            sess["total"] = int(mm.group(3))
        cur = len(sess["data"])
        if start > cur:
            # A gap: reject like GCS (client must recover the cursor).
            self._send_json(400, {"error": {"code": 400, "message": "gap"}})
            return
        sess["data"][start : start + len(body)] = body
        if sess["total"] is not None and len(sess["data"]) >= sess["total"]:
            sess["done"] = True
            self.state.objects[(sess["bucket"], sess["name"])] = bytes(sess["data"])
            self._send_json(200, self._object_json(sess["bucket"], sess["name"]))
            return
        self._resumable_status(sess)

    def _resumable_status(self, sess: dict) -> None:
        if sess["done"]:
            self._send_json(200, self._object_json(sess["bucket"], sess["name"]))
            return
        headers = {}
        if len(sess["data"]):
            headers["Range"] = f"bytes=0-{len(sess['data']) - 1}"
        self._send(308, b"", headers)

    def log_message(self, *args) -> None:  # noqa: D102 - silence
        pass


def _parse_multipart_related(body: bytes, content_type: str) -> Tuple[dict, bytes]:
    mm = re.search(r"boundary=['\"]?([^'\";]+)", content_type)
    boundary = mm.group(1).encode()
    parts = body.split(b"--" + boundary)
    # parts[0] = prologue, parts[1] = metadata, parts[2] = content,
    # parts[3] = epilogue ('--\r\n')
    meta_part = parts[1]
    content_part = parts[2]
    meta_json = meta_part.split(b"\r\n\r\n", 1)[1].rstrip(b"\r\n")
    content = content_part.split(b"\r\n\r\n", 1)[1]
    if content.endswith(b"\r\n"):
        content = content[:-2]
    return json.loads(meta_json.decode()), content


class _QuietServer(ThreadingHTTPServer):
    daemon_threads = True

    def handle_error(self, request, client_address) -> None:
        # Keep-alive sockets reset at shutdown; not worth a traceback.
        pass


class FakeGCSServer:
    """Context manager: a threaded local GCS emulator."""

    def __init__(self) -> None:
        self.state = _State()
        self._httpd = _QuietServer(("127.0.0.1", 0), _Handler)
        self._httpd.state = self.state  # type: ignore[attr-defined]
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )

    @property
    def endpoint(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def fail_next(self, match: str, n: int = 1, status: int = 503) -> None:
        """Fail the next ``n`` requests whose ``METHOD path`` contains
        ``match`` with ``status`` (each fault fires once)."""
        with self.state.lock:
            self.state.faults.extend([(match, status)] * n)

    def force_rewrite_token_rounds(self, n: int) -> None:
        with self.state.lock:
            self.state.force_rewrite_rounds = n

    def __enter__(self) -> "FakeGCSServer":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
