"""Key-value stores + a thread-safe two-phase barrier.

TPU-native analogue of the reference's ``dist_store.py:22-196``. The reference
needs a TCPStore because c10d collectives can't run off the main thread; JAX
has the same constraint (collectives are XLA computations on the main thread),
so the async-snapshot commit barrier runs over a KV store instead:

- :class:`JaxCoordinationStore` rides the jax.distributed coordination
  service (gRPC, callable from any thread) — zero extra infrastructure on a
  TPU pod, where `jax.distributed.initialize` is already required.
- :class:`TCPStore` is a small self-contained socket store for runs without
  jax.distributed (e.g. torch-free multi-process CPU tests, custom pods). The
  server lives in the rank-0 process; every op is a framed pickle message.

:class:`LinearBarrier` is the reference's two-phase (arrive/depart) barrier
with leader-held critical section and cross-rank error propagation
(``dist_store.py:91-196``): if any rank reports an error, every other rank
raises instead of deadlocking, and the leader never commits.
"""

from __future__ import annotations

import abc
import contextlib
import pickle
import socket
import socketserver
import struct
import threading
import time
from typing import Any, Dict, List, Optional

_DEFAULT_TIMEOUT_S = 300.0


# ---------------------------------------------------------------------------
# Store round-trip accounting. Every concrete store op is one logical
# round-trip against the (rank-0-hosted) control-plane server, so these
# counters are the raw material for a coordination-cost scaling model:
# they turn "the stall grows with world size" into
# "this take issued N round-trips" and make the pod-scale stall a
# calculation instead of a hope. Diagnostics only: per-process, reset by the
# caller around the section being measured.
# ---------------------------------------------------------------------------

_OP_LOCK = threading.Lock()
# (thread id, op) -> count: keyed per thread so a measurement window on the
# main thread (e.g. an async_take stall) can exclude ops raced in by the
# background commit thread's LinearBarrier polling.
_OP_COUNTS: Dict[tuple, int] = {}

_TELEMETRY_OP = threading.local()


@contextlib.contextmanager
def telemetry_op_scope():
    """Mark store ops issued inside as telemetry-plane traffic.

    Fleet beacon publishes/reads and wait-graph probes are real store
    round-trips, but they are rate-limited diagnostics, not per-take
    coordination: counting them as ``telemetry.<op>`` keeps them visible
    in the op counters while letting coordination-cost measurements (the
    published 3-round-trips-per-stall claim and its pinning test) exclude
    them with ``include_telemetry=False``."""
    prev = getattr(_TELEMETRY_OP, "on", False)
    _TELEMETRY_OP.on = True
    try:
        yield
    finally:
        _TELEMETRY_OP.on = prev


def _count_op(op: str) -> None:
    if getattr(_TELEMETRY_OP, "on", False):
        op = f"telemetry.{op}"
    key = (threading.get_ident(), op)
    with _OP_LOCK:
        _OP_COUNTS[key] = _OP_COUNTS.get(key, 0) + 1


def get_op_counts(
    current_thread_only: bool = False, include_telemetry: bool = True
) -> Dict[str, int]:
    """{op: count} since the last reset (set/get/try_get/add/delete).

    Ops issued under :func:`telemetry_op_scope` count as
    ``telemetry.<op>``; pass ``include_telemetry=False`` to measure the
    coordination plane alone."""
    me = threading.get_ident()
    out: Dict[str, int] = {}
    with _OP_LOCK:
        for (tid, op), n in _OP_COUNTS.items():
            if current_thread_only and tid != me:
                continue
            if not include_telemetry and op.startswith("telemetry."):
                continue
            out[op] = out.get(op, 0) + n
    return out


def reset_op_counts() -> None:
    with _OP_LOCK:
        _OP_COUNTS.clear()


class Store(abc.ABC):
    """Minimal KV contract needed by the coordinator and LinearBarrier."""

    @abc.abstractmethod
    def set(self, key: str, value: bytes) -> None: ...

    @abc.abstractmethod
    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        """Blocking get: waits until ``key`` exists."""
        ...

    @abc.abstractmethod
    def try_get(self, key: str) -> Optional[bytes]: ...

    @abc.abstractmethod
    def add(self, key: str, delta: int) -> int:
        """Atomic increment; returns the new value (missing key counts as 0)."""
        ...

    def delete(self, key: str) -> None:
        """Best-effort removal of a key (and its counter). Default: no-op."""

    # Bulk ops: the swarm restore path polls MANY chunk keys per round and
    # GC-deletes whole attempt families at once; stores that can batch
    # (LocalStore under one lock, TCPStore in one framed round trip)
    # override these, everything else gets the loop.
    def try_get_many(self, keys: List[str]) -> List[Optional[bytes]]:
        """``try_get`` for each key, in order. One logical round trip on
        stores that batch; the default falls back to per-key calls."""
        return [self.try_get(k) for k in keys]

    def delete_many(self, keys: List[str]) -> None:
        """Best-effort bulk removal (keys and their counters)."""
        for k in keys:
            self.delete(k)

    def prefix(self, p: str) -> "PrefixStore":
        return PrefixStore(p, self)


class PrefixStore(Store):
    def __init__(self, prefix: str, store: Store) -> None:
        self._prefix = prefix
        self._store = store

    def set(self, key: str, value: bytes) -> None:
        self._store.set(f"{self._prefix}/{key}", value)

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        return self._store.get(f"{self._prefix}/{key}", timeout_s)

    def try_get(self, key: str) -> Optional[bytes]:
        return self._store.try_get(f"{self._prefix}/{key}")

    def add(self, key: str, delta: int) -> int:
        return self._store.add(f"{self._prefix}/{key}", delta)

    def delete(self, key: str) -> None:
        self._store.delete(f"{self._prefix}/{key}")

    def try_get_many(self, keys: List[str]) -> List[Optional[bytes]]:
        return self._store.try_get_many([f"{self._prefix}/{k}" for k in keys])

    def delete_many(self, keys: List[str]) -> None:
        self._store.delete_many([f"{self._prefix}/{k}" for k in keys])


# ---------------------------------------------------------------------------
# In-process store (single-process runs and unit tests)
# ---------------------------------------------------------------------------

class LocalStore(Store):
    def __init__(self) -> None:
        self._data: Dict[str, bytes] = {}
        self._counters: Dict[str, int] = {}
        self._cond = threading.Condition()

    def set(self, key: str, value: bytes) -> None:
        _count_op("set")
        with self._cond:
            self._data[key] = value
            self._cond.notify_all()

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        _count_op("get")
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while key not in self._data:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cond.wait(remaining):
                    raise TimeoutError(f"Store.get timed out waiting for {key!r}")
            return self._data[key]

    def try_get(self, key: str) -> Optional[bytes]:
        _count_op("try_get")
        with self._cond:
            return self._data.get(key)

    def add(self, key: str, delta: int) -> int:
        _count_op("add")
        with self._cond:
            self._counters[key] = self._counters.get(key, 0) + delta
            self._cond.notify_all()
            return self._counters[key]

    def delete(self, key: str) -> None:
        _count_op("delete")
        with self._cond:
            self._data.pop(key, None)
            self._counters.pop(key, None)

    def try_get_many(self, keys: List[str]) -> List[Optional[bytes]]:
        _count_op("try_get_many")
        with self._cond:
            return [self._data.get(k) for k in keys]

    def delete_many(self, keys: List[str]) -> None:
        _count_op("delete_many")
        with self._cond:
            for k in keys:
                self._data.pop(k, None)
                self._counters.pop(k, None)


# ---------------------------------------------------------------------------
# jax coordination-service-backed store
# ---------------------------------------------------------------------------

class JaxCoordinationStore(Store):
    """Rides ``jax.distributed``'s coordination service (usable off-thread)."""

    def __init__(self, namespace: str = "tss") -> None:
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "jax.distributed is not initialized; "
                "call jax.distributed.initialize() or provide a TCPStore"
            )
        self._client = client
        self._ns = namespace

    @classmethod
    def available(cls) -> bool:
        from jax._src import distributed

        return distributed.global_state.client is not None

    def _k(self, key: str) -> str:
        return f"{self._ns}/{key}"

    def set(self, key: str, value: bytes) -> None:
        _count_op("set")
        # The Store contract is last-writer-wins (as the TCPStore is); the
        # coordination service refuses a second set unless told otherwise.
        self._client.key_value_set_bytes(
            self._k(key), bytes(value), allow_overwrite=True
        )

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        _count_op("get")
        try:
            return bytes(
                self._client.blocking_key_value_get_bytes(
                    self._k(key), int(timeout_s * 1000)
                )
            )
        except Exception as e:
            # jax surfaces coordination-service timeouts as XlaRuntimeError
            # (DEADLINE_EXCEEDED); normalize so callers that poll with short
            # timeouts (e.g. LinearBarrier) can catch TimeoutError uniformly.
            msg = str(e)
            if "DEADLINE" in msg or "deadline" in msg or "imed out" in msg:
                raise TimeoutError(
                    f"Store.get timed out waiting for {key!r}"
                ) from e
            raise

    def try_get(self, key: str) -> Optional[bytes]:
        _count_op("try_get")
        try:
            return bytes(self._client.key_value_try_get_bytes(self._k(key)))
        except Exception as e:
            # The service reports an absent key as NOT_FOUND; anything else
            # (a dead coordinator, a closed client) is not "absent".
            if "NOT_FOUND" in str(e):
                return None
            raise

    def add(self, key: str, delta: int) -> int:
        _count_op("add")
        return int(self._client.key_value_increment(self._k(key), delta))

    def delete(self, key: str) -> None:
        _count_op("delete")
        try:
            self._client.key_value_delete(self._k(key))
        except Exception:
            pass  # cleanup is best-effort


# ---------------------------------------------------------------------------
# Self-contained TCP store
# ---------------------------------------------------------------------------

def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(n)
        if not chunk:
            raise ConnectionError("store connection closed")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def _send_msg(sock: socket.socket, obj: Any) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(struct.pack("!I", len(payload)) + payload)


def _recv_msg(sock: socket.socket) -> Any:
    (length,) = struct.unpack("!I", _recv_exact(sock, 4))
    return pickle.loads(_recv_exact(sock, length))


class _StoreServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True
    # A fleet's worth of clients connects in one burst at restore start —
    # every rank's executor threads open their lazy per-thread sockets
    # together (the swarm restore alone fans chunk traffic across several
    # threads per rank). The socketserver default backlog of 5 overflows
    # under that burst and the kernel eventually RSTs the half-accepted
    # connections, which surfaced as spurious mid-restore resets at
    # world >= 8.
    request_queue_size = 128

    def __init__(self, addr):
        super().__init__(addr, _StoreHandler)
        self.data: Dict[str, bytes] = {}
        self.counters: Dict[str, int] = {}
        self.cond = threading.Condition()


class _StoreHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        server: _StoreServer = self.server  # type: ignore[assignment]
        try:
            while True:
                op, key, arg = _recv_msg(self.request)
                if op == "set":
                    with server.cond:
                        server.data[key] = arg
                        server.cond.notify_all()
                    _send_msg(self.request, ("ok", None))
                elif op == "get":
                    deadline = time.monotonic() + arg
                    with server.cond:
                        while key not in server.data:
                            remaining = deadline - time.monotonic()
                            if remaining <= 0 or not server.cond.wait(
                                min(remaining, 1.0)
                            ):
                                if time.monotonic() >= deadline:
                                    break
                        val = server.data.get(key)
                    if val is None:
                        _send_msg(self.request, ("timeout", None))
                    else:
                        _send_msg(self.request, ("ok", val))
                elif op == "try_get":
                    with server.cond:
                        val = server.data.get(key)
                    _send_msg(self.request, ("ok", val))
                elif op == "mtry_get":
                    # Bulk try_get: `arg` is the key list, `key` unused —
                    # one framed round trip for a whole swarm poll.
                    with server.cond:
                        vals = [server.data.get(k) for k in arg]
                    _send_msg(self.request, ("ok", vals))
                elif op == "delete":
                    with server.cond:
                        server.data.pop(key, None)
                        server.counters.pop(key, None)
                    _send_msg(self.request, ("ok", None))
                elif op == "mdelete":
                    with server.cond:
                        for k in arg:
                            server.data.pop(k, None)
                            server.counters.pop(k, None)
                    _send_msg(self.request, ("ok", None))
                elif op == "add":
                    with server.cond:
                        server.counters[key] = server.counters.get(key, 0) + arg
                        val = server.counters[key]
                        server.cond.notify_all()
                    _send_msg(self.request, ("ok", val))
                else:
                    _send_msg(self.request, ("err", f"unknown op {op}"))
        except (ConnectionError, EOFError):
            pass


class TCPStore(Store):
    """Socket KV store; the server thread lives in the host process of rank 0."""

    def __init__(self, host: str, port: int, is_server: bool) -> None:
        self.host = host
        self.port = port
        self._server: Optional[_StoreServer] = None
        if is_server:
            self._server = _StoreServer((host, port))
            if port == 0:
                self.port = self._server.server_address[1]
            threading.Thread(
                target=self._server.serve_forever, daemon=True
            ).start()
        self._local = threading.local()

    def _sock(self) -> socket.socket:
        sock = getattr(self._local, "sock", None)
        if sock is None:
            deadline = time.monotonic() + 60
            last_err: Optional[Exception] = None
            while time.monotonic() < deadline:
                try:
                    sock = socket.create_connection((self.host, self.port), timeout=600)
                    break
                except OSError as e:
                    last_err = e
                    time.sleep(0.1)
            else:
                raise ConnectionError(f"cannot reach store: {last_err}")
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._local.sock = sock
        return sock

    def _call(self, op: str, key: str, arg: Any) -> Any:
        _count_op(op)
        sock = self._sock()
        _send_msg(sock, (op, key, arg))
        status, val = _recv_msg(sock)
        if status == "timeout":
            raise TimeoutError(f"Store.get timed out waiting for {key!r}")
        if status != "ok":
            raise RuntimeError(val)
        return val

    def set(self, key: str, value: bytes) -> None:
        self._call("set", key, bytes(value))

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        return self._call("get", key, timeout_s)

    def try_get(self, key: str) -> Optional[bytes]:
        return self._call("try_get", key, None)

    def add(self, key: str, delta: int) -> int:
        return self._call("add", key, delta)

    def delete(self, key: str) -> None:
        self._call("delete", key, None)

    def try_get_many(self, keys: List[str]) -> List[Optional[bytes]]:
        return self._call("mtry_get", "", list(keys))

    def delete_many(self, keys: List[str]) -> None:
        self._call("mdelete", "", list(keys))

    def shutdown(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# LinearBarrier
# ---------------------------------------------------------------------------

class BarrierError(RuntimeError):
    """A peer reported failure through the barrier. Carries the failing
    rank and the phase of the take it was in (``None`` for reports from
    pre-phase-tagging writers) so callers can surface a structured
    :class:`~torchsnapshot_tpu.CheckpointAbortedError` instead of a bare
    string."""

    def __init__(self, message: str, rank: Optional[int] = None,
                 phase: Optional[str] = None) -> None:
        super().__init__(message)
        self.rank = rank
        self.phase = phase


class BarrierTimeout(TimeoutError):
    """A barrier phase timed out. Carries the ranks whose arrival markers
    were still missing at the deadline, so the abort path can NAME the
    straggler (and, through the fleet bus, its last-beaconed phase) instead
    of reporting an unattributed timeout."""

    def __init__(self, message: str, phase: str,
                 missing_ranks: Optional[List[int]] = None) -> None:
        super().__init__(message)
        self.phase = phase
        self.missing_ranks = list(missing_ranks or [])


class LinearBarrier:
    """Two-phase store barrier with leader critical section + error fan-out.

    Usage (reference ``snapshot.py:948-969``)::

        barrier = LinearBarrier(store, barrier_id, rank, world_size)
        try:
            barrier.arrive(timeout)     # all ranks' data is durable
            if rank == 0:
                commit_metadata()       # leader-only critical section
            barrier.depart(timeout)
        except Exception as e:
            barrier.report_error(e)     # unblocks + fails all peers
            raise
    """

    def __init__(self, store: Store, barrier_id: str, rank: int, world_size: int):
        self._store = store.prefix(f"barrier/{barrier_id}")
        self._barrier_id = barrier_id
        self._rank = rank
        self._world_size = world_size

    def arrive(self, timeout_s: Optional[float] = None) -> None:
        self._phase("arrive", self._resolve_timeout(timeout_s))

    def depart(self, timeout_s: Optional[float] = None) -> None:
        self._phase("depart", self._resolve_timeout(timeout_s))

    @staticmethod
    def _resolve_timeout(timeout_s: Optional[float]) -> float:
        if timeout_s is not None:
            return timeout_s
        from ..utils import knobs

        return knobs.get_barrier_timeout_s()

    @staticmethod
    def _unpickle_error(err: bytes) -> "BarrierError":
        payload = pickle.loads(err)
        # Current writers post (rank, phase, msg); tolerate the legacy
        # 2-tuple so mixed-version pods still fail cleanly, not cryptically.
        if len(payload) == 3:
            rank, phase, msg = payload
        else:
            rank, msg = payload
            phase = None
        detail = f" during {phase}" if phase else ""
        return BarrierError(
            f"rank {rank} failed{detail}: {msg}", rank=rank, phase=phase
        )

    def _missing_ranks(self, phase: str) -> List[int]:
        """Ranks whose per-rank arrival markers for ``phase`` are absent —
        the peers everyone still waits on. Best-effort diagnostics: one
        bulk round trip (counted as telemetry, not coordination), [] on
        any store failure."""
        try:
            with telemetry_op_scope():
                vals = self._store.try_get_many(
                    [f"{phase}/r{r}" for r in range(self._world_size)]
                )
        except Exception:  # noqa: BLE001 - attribution is best-effort
            return []
        return [
            r
            for r, v in enumerate(vals)
            if v is None and r != self._rank
        ]

    def _phase(self, phase: str, timeout_s: float) -> None:
        from ..collective_tracer import active_tracer
        from ..telemetry import fleet

        tracer = active_tracer()
        if tracer is not None:
            tracer.record(f"barrier.{phase}", self._barrier_id)
        # Per-rank arrival marker beside the shared counter: the counter
        # says HOW MANY arrived, the markers say WHO — what timeout
        # attribution and the fleet wait graph are built from.
        self._store.set(f"{phase}/r{self._rank}", b"1")
        count = self._store.add(phase, 1)
        if count == self._world_size:
            self._store.set(f"{phase}/done", b"1")
        deadline = time.monotonic() + timeout_s
        wait_site = f"barrier.{phase}:{self._barrier_id}"
        # The first poll round is short so a genuine wait feeds its fleet
        # edge within 0.25 s — the commit-barrier stall watchdog fires
        # EXACTLY ONCE per stall, usually well inside a ~1 s round, and
        # its one warning must already carry the peer attribution. A
        # healthy barrier (arrival skew under the short round) completes
        # inside the first get and pays zero extra store ops, preserving
        # the constant steady-state coordination cost.
        poll_s = 0.25
        try:
            while True:
                err = self._store.try_get("error")
                if err is not None:
                    raise self._unpickle_error(err)
                try:
                    self._store.get(f"{phase}/done", timeout_s=poll_s)
                    # report_error() force-sets the done keys to unblock
                    # waiters, so re-check for a peer failure before
                    # declaring success.
                    err = self._store.try_get("error")
                    if err is not None:
                        raise self._unpickle_error(err)
                    if tracer is not None and (
                        threading.current_thread() is threading.main_thread()
                    ):
                        # Every rank just passed this phase; cross-check the
                        # lockstep fingerprint under the barrier's own (rank-
                        # independent) namespace. Background-thread barriers
                        # (the async commit) skip the check: their
                        # interleaving against main-thread planning
                        # collectives is timing, not SPMD divergence.
                        tracer.crosscheck(
                            self._store,
                            self._rank,
                            self._world_size,
                            phase,
                            timeout_s,
                        )
                    return
                except TimeoutError:
                    # One poll round (0.25 s first, ~1 s after) elapsed
                    # without the phase completing: feed the fleet wait
                    # graph with who is still missing, and keep this
                    # rank's beacon fresh while it waits. Cheap (one bulk
                    # probe per round) and only when the bus is live.
                    poll_s = 1.0
                    if fleet.enabled():
                        fleet.note_blocked(
                            wait_site, self._missing_ranks(phase)
                        )
                        fleet.heartbeat()
                    if time.monotonic() > deadline:
                        missing = self._missing_ranks(phase)
                        detail = ""
                        if missing:
                            detail = "; waiting on rank(s) " + ", ".join(
                                str(r) for r in missing
                            )
                        raise BarrierTimeout(
                            f"LinearBarrier {phase} timed out "
                            f"({count}/{self._world_size} arrived{detail})",
                            phase=phase,
                            missing_ranks=missing,
                        )
        finally:
            fleet.clear_blocked(wait_site)

    def report_error(self, e: Exception, phase: Optional[str] = None) -> None:
        from ..collective_tracer import active_tracer

        tracer = active_tracer()
        if tracer is not None:
            # Only the failing rank posts: asymmetric by design, journaled
            # for attribution but excluded from the lockstep fingerprint.
            tracer.record(
                "barrier.report_error", self._barrier_id, checked=False
            )
        self._store.set(
            "error", pickle.dumps((self._rank, phase, repr(e)))
        )
        # Unblock peers waiting on phase-done keys; they'll see the error.
        self._store.set("arrive/done", b"1")
        self._store.set("depart/done", b"1")
