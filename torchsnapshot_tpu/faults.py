"""Deterministic fault injection for storage plugins.

The robustness analogue of the telemetry layer: every crash-consistency
claim this library makes (atomic commit, failed-write-leaves-nothing,
collective-progress retry, barrier error propagation) is only as good as
the failure scenarios that exercise it, and real storage faults are neither
deterministic nor portable across backends. :class:`FaultyStoragePlugin`
wraps ANY :class:`~.io_types.StoragePlugin` and injects faults from a
seeded, fully deterministic spec, so the chaos harness
(``tests/test_chaos.py``) can replay the exact same torn write / transient
storm / stall / process kill on fs, memory, and (fake) cloud backends alike.

Installation: the ``TORCHSNAPSHOT_TPU_FAULTS`` knob. When set,
``url_to_storage_plugin`` wraps every plugin it constructs — including the
ones child ranks of multiprocess tests construct, since the env var is
inherited — so a single string drives fault injection across a whole fake
pod. Production jobs leave it unset; the wrapper is never even imported.

Spec grammar (rules separated by ``;``, fields by ``,``)::

    TORCHSNAPSHOT_TPU_FAULTS = "rule[;rule...]"
    rule  = seed=<int>                      # global RNG seed (default 0)
          | backoff=<float>                 # transient-retry base backoff (s)
          | window=<float>                  # collective-progress window (s)
          | op=<op>[,<field>=<value>...]    # one injection rule

    op    = write | read | delete | link | list | peer_serve | any
          | catalog_append | steprecord_append | cache_bitmap | read_chunk

    ``catalog_append`` / ``steprecord_append`` are *derived* write classes:
    they fire at plugin writes landing under the catalog's record /
    step-telemetry directories, so a kill-point can target exactly the
    lifecycle layer's publish ops without counting data writes. Rules must
    name them explicitly (``op=any`` does not match a derived class twice).
    ``cache_bitmap`` fires at the sparse read-cache's bitmap-rename commit
    point (``storage_plugins/cache.py``), which lives BELOW this wrapper —
    it is driven through :func:`maybe_inject_local` instead of ``_guard``.

    ``read_chunk`` is a torn read at chunk grain: the fs plugin's native
    engine reads one object as chunk reads (``native/tss_io.cpp``), below
    this wrapper and outside the interpreter. The class counts the plugin's
    native reads (one per object and attempt); where a rule fires, chunk
    ``chunk=<k>`` (default 0) of that read fails with ``ESTALE`` while the
    object's other chunks land in the attempt's destination. ``transient``
    is its only kind: the plugin's own retry runs, and must fill a fresh
    destination. Driven through :func:`read_chunk_fault`.

    ``peer_serve`` is not a storage op: it fires at the swarm restore's
    peer-serving point, just before a rank posts a fetched chunk for its
    peers (``swarm.py``). ``stall`` delays the post past the chunk deadline
    (driving per-chunk re-election), ``kill`` is peer death mid-serve,
    ``corrupt`` flips bytes in the POSTED payload only (the serving rank's
    own copy stays clean — the receiving peer's per-chunk verification must
    catch it and attribute it to the serving rank), ``fail``/``transient``
    surface as a failed serve (peers fall back to origin).
    kind  = transient  raise a retryable error (drives cloud_retry)
          | fail       raise a permanent InjectedFault
          | torn       transfer `bytes` bytes, then fail WITHOUT abort
          |            (simulated crash: atomic backends must expose nothing,
          |            fs leaves a temp file for gc to reclaim)
          | stall      sleep `secs` seconds before the op (drives the
          |            stall watchdog)
          | kill       os._exit the process at the op (preemption)
          | corrupt    read ops only: the read SUCCEEDS but `bytes` bytes
          |            (default 1) of the returned buffer are flipped at
          |            seeded offsets — silent bit rot, the failure mode
          |            digest verification (TORCHSNAPSHOT_TPU_VERIFY_READS,
          |            cache-hit verification, Snapshot.scrub) exists to
          |            catch. No error is raised: an unverified reader
          |            consumes the corrupt bytes without noticing.

    fields:
      at=<k>        inject at the k-th op of this class (0-based; once)
      after=<k>     inject on every op of this class with index >= k
      every=<n>     inject on every n-th op of this class
      p=<float>     inject with this probability (seeded RNG — deterministic
                    for a given seed + op sequence)
      times=<n>     cap total injections for this rule (default: 1 for
                    `at`, unlimited otherwise)
      rank=<r>      only inject on this rank (env rank / jax process index)
      path=<substr> only inject on ops whose path contains this substring
      bytes=<k>     torn mode: bytes transferred before the failure;
                    corrupt mode: bytes flipped (default 1)
      chunk=<k>     corrupt mode only: flip bytes inside hash chunk k's
                    extent ([k*grain, (k+1)*grain) of the OBJECT, grain =
                    TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES) instead of anywhere
                    in the buffer — the seeded rot chunk-granular
                    verification (ranged VERIFY_READS, scrub attribution,
                    per-chunk repair) must detect and localize. Ranged
                    reads translate the extent into buffer coordinates; a
                    read not covering the chunk is left intact.
      secs=<f>      stall mode: sleep duration

Examples::

    op=write,at=2,kind=kill                    # die at the 3rd object write
    op=write,kind=transient,times=3            # 3 retryable write failures
    op=write,path=.snapshot_metadata,kind=fail # commit can never land
    seed=7;op=write,p=0.2,kind=torn,bytes=100  # seeded 20% torn writes

Every op class keeps its own monotonic counter on the wrapper instance;
plugins are constructed fresh per take/restore, so counters (and thus
`at=`/`every=` schedules) are reproducible run to run. Retries count as new
ops — a transient rule with ``times=2`` fails twice and then passes.

Transient faults are retried by the wrapper itself through the shared
:func:`~.storage_plugins.cloud_retry.retry_transient` machinery (the same
policy the GCS/S3 plugins use), so injecting them exercises the real
backoff/collective-progress code paths, not a test double.
"""

from __future__ import annotations

import asyncio
import logging
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from . import telemetry
from .io_types import ReadIO, StoragePlugin, WriteIO
from .storage_plugins.cloud_retry import CollectiveProgress, retry_transient

logger = logging.getLogger(__name__)

_OPS = (
    "write",
    "read",
    "delete",
    "link",
    "list",
    "peer_serve",
    "catalog_append",
    "steprecord_append",
    "cache_bitmap",
    "read_chunk",
    "beacon",
    "any",
)

# Derived write classes: a plugin write whose path starts with one of these
# prefixes ALSO runs that class's injection point (when a rule names it),
# so kill-points can target the lifecycle layer's publish ops — the commit
# points the TSA1004 durability pass pins — without counting data writes.
# Kept as literals (the static-analysis coverage test asserts they match
# ``catalog.RECORD_DIR`` / ``catalog.STEP_TELEMETRY_DIR``) so importing
# this module never pulls the catalog machinery.
_CATALOG_RECORD_PREFIX = ".catalog/records/"
_STEP_TELEMETRY_PREFIX = ".catalog/telemetry/"

_DERIVED_WRITE_OPS = (
    ("catalog_append", _CATALOG_RECORD_PREFIX),
    ("steprecord_append", _STEP_TELEMETRY_PREFIX),
)
_DERIVED_OP_SET = frozenset(
    op for op, _ in _DERIVED_WRITE_OPS
) | {"cache_bitmap", "read_chunk"}
_KINDS = ("transient", "fail", "torn", "stall", "kill", "corrupt")

# Plugin surface the wrapper deliberately proxies WITHOUT an injection
# point: non-data-plane housekeeping where a fault proves nothing about
# crash consistency. The TSA8xx fault-coverage analyzer pass reads this
# tuple — any other un-guarded override (and any contract method with no
# override at all) fails the gate, so new plugin surface can never silently
# bypass chaos testing.
_PASSTHROUGH_OPS = ("prune_empty", "close")

# The commit-point inventory: every function the TSA1004 durability pass
# discovers performing a direct durable mutation (os.replace/rename/link/
# remove/unlink, or a mutating call on a storage plugin), pinned to the
# kill-point op class whose rules reach it — so a chaos schedule can crash
# the process at exactly that commit point. "fail-open" declares a site
# whose loss is harmless by contract (telemetry sidecars, local cache
# entries the next read re-populates, build artifacts): not crash-surface,
# reviewed here so the declaration is explicit. The pass fails on any
# drift in either direction (an unpinned discovery, a stale entry), and
# tests/test_static_analysis.py asserts this table equals the pass's
# inventory exactly.
_CRASH_SURFACE = (
    ("__init__.py:_build", "fail-open"),  # native .so build artifact
    ("aggregate.py:write_merged_chrome_trace", "fail-open"),
    ("cache.py:CachedStoragePlugin._drop_entry", "fail-open"),
    ("cache.py:CachedStoragePlugin._maybe_evict", "fail-open"),
    ("cache.py:CachedStoragePlugin._read_entry_pinned", "fail-open"),
    ("cache.py:CachedStoragePlugin._replace_bitmap", "cache_bitmap"),
    ("cache.py:CachedStoragePlugin._write_entry", "fail-open"),
    ("cache.py:CachedStoragePlugin._write_entry_range", "fail-open"),
    ("cache.py:CachedStoragePlugin.quarantine_path", "fail-open"),
    ("catalog.py:Catalog.append", "catalog_append"),
    # Restore-side rollout records are fail-open telemetry sidecars: a
    # crash mid-append loses at most one record and the snapshot itself
    # is untouched (appends happen strictly after the restore completes).
    ("catalog.py:Catalog.append_rollout_record", "fail-open"),
    ("catalog.py:Catalog.append_step_telemetry", "steprecord_append"),
    ("catalog.py:Catalog.pin", "write"),
    ("catalog.py:Catalog.unpin", "delete"),
    ("export.py:write_trace_obj", "fail-open"),
    ("fs.py:FSStoragePlugin._link_in_inner", "link"),
    ("fs.py:FSStoragePlugin._write_inner", "write"),
    ("recorder.py:FlightRecorder.dump", "fail-open"),
    ("scheduler.py:_WritePipeline._write_one", "write"),
    ("scheduler.py:_WritePipeline.run_to_completion", "write"),
    ("snapshot.py:Snapshot._scrub_repair", "write"),
    ("snapshot.py:Snapshot._write_snapshot_metadata", "write"),
    ("snapshot.py:Snapshot.gc", "delete"),
    ("storage_plugin.py:write_telemetry_artifact", "write"),
)

# Exit code of a `kill` fault — distinctive so the chaos harness (and a
# human reading a CI log) can tell an injected death from a real crash.
KILL_EXIT_CODE = 87


class InjectedFault(RuntimeError):
    """A permanently-failing injected fault (``kind=fail`` / ``kind=torn``)."""


class InjectedTransientFault(InjectedFault):
    """A retryable injected fault (``kind=transient``): the wrapper's own
    retry loop — the shared cloud_retry machinery — classifies exactly this
    type as transient."""


class FaultSpecError(ValueError):
    """The ``TORCHSNAPSHOT_TPU_FAULTS`` spec string does not parse."""


@dataclass
class FaultRule:
    op: str
    kind: str
    at: Optional[int] = None
    after: Optional[int] = None
    every: Optional[int] = None
    p: Optional[float] = None
    times: Optional[int] = None
    rank: Optional[int] = None
    path: Optional[str] = None
    bytes: int = 0
    chunk: Optional[int] = None
    secs: float = 0.0
    injected: int = 0  # how often this rule has fired (mutable state)

    def matches(self, op: str, index: int, path: str, rng: random.Random,
                rank: int) -> bool:
        if self.op != "any" and self.op != op:
            return False
        if self.rank is not None and self.rank != rank:
            return False
        if self.path is not None and self.path not in path:
            return False
        limit = self.times if self.times is not None else (
            1 if self.at is not None else None
        )
        if limit is not None and self.injected >= limit:
            return False
        if self.at is not None:
            return index == self.at
        if self.after is not None:
            return index >= self.after
        if self.every is not None:
            return index % self.every == self.every - 1
        if self.p is not None:
            # One seeded draw per (matching) op: deterministic for a given
            # seed + op sequence, independent of wall clock.
            return rng.random() < self.p
        # No selector: fire on every matching op (bounded by `times`).
        return True


@dataclass
class FaultPlan:
    rules: List[FaultRule] = field(default_factory=list)
    seed: int = 0
    backoff_s: Optional[float] = None
    window_s: Optional[float] = None


_INT_FIELDS = ("at", "after", "every", "times", "rank", "bytes", "chunk")
_FLOAT_FIELDS = ("p", "secs")


def parse_fault_spec(spec: str) -> FaultPlan:
    """Parse a ``TORCHSNAPSHOT_TPU_FAULTS`` string into a :class:`FaultPlan`.

    Raises :class:`FaultSpecError` on any malformed input — a typo'd chaos
    schedule must fail the test loudly, not silently inject nothing.
    """
    plan = FaultPlan()
    for raw_rule in spec.split(";"):
        raw_rule = raw_rule.strip()
        if not raw_rule:
            continue
        fields: Dict[str, str] = {}
        for raw_field in raw_rule.split(","):
            key, sep, value = raw_field.partition("=")
            key = key.strip()
            if not sep or not key or not value.strip():
                raise FaultSpecError(
                    f"malformed field {raw_field!r} in rule {raw_rule!r} "
                    "(expected key=value)"
                )
            if key in fields:
                raise FaultSpecError(
                    f"duplicate field {key!r} in rule {raw_rule!r}"
                )
            fields[key] = value.strip()
        try:
            if "op" not in fields:
                # Global settings rule: seed / backoff / window only.
                for key, value in fields.items():
                    if key == "seed":
                        plan.seed = int(value)
                    elif key == "backoff":
                        plan.backoff_s = float(value)
                    elif key == "window":
                        plan.window_s = float(value)
                    else:
                        raise FaultSpecError(
                            f"unknown global field {key!r} in {raw_rule!r} "
                            "(rules need op=...)"
                        )
                continue
            op = fields.pop("op")
            if op not in _OPS:
                raise FaultSpecError(
                    f"unknown op {op!r} (expected one of {', '.join(_OPS)})"
                )
            kind = fields.pop("kind", None)
            if kind not in _KINDS:
                raise FaultSpecError(
                    f"rule {raw_rule!r} needs kind= one of {', '.join(_KINDS)}"
                )
            rule = FaultRule(op=op, kind=kind)
            for key, value in fields.items():
                if key in _INT_FIELDS:
                    setattr(rule, key, int(value))
                elif key in _FLOAT_FIELDS:
                    setattr(rule, key, float(value))
                elif key == "path":
                    rule.path = value
                else:
                    raise FaultSpecError(
                        f"unknown field {key!r} in rule {raw_rule!r}"
                    )
        except FaultSpecError:
            raise
        except ValueError as e:
            raise FaultSpecError(f"bad value in rule {raw_rule!r}: {e}") from e
        if rule.kind == "torn" and rule.op not in ("write", "any"):
            raise FaultSpecError(
                f"kind=torn applies to write ops, not {rule.op!r}"
            )
        if rule.kind == "corrupt" and rule.op not in ("read", "peer_serve", "any"):
            raise FaultSpecError(
                f"kind=corrupt applies to read/peer_serve ops, not {rule.op!r}"
            )
        if rule.op == "read_chunk" and rule.kind != "transient":
            raise FaultSpecError(
                f"op=read_chunk fails a chunk transiently, not kind={rule.kind!r}"
            )
        if rule.chunk is not None and rule.kind != "corrupt" and rule.op != "read_chunk":
            raise FaultSpecError(
                "chunk= targets corrupt and read_chunk rules only, not "
                f"kind={rule.kind!r}"
            )
        plan.rules.append(rule)
    return plan


def _current_rank() -> int:
    """This process's rank, for ``rank=`` rule filters: the TCPStore
    coordination knob when set (multiprocess tests), else the jax process
    index when jax.distributed is up, else 0."""
    from .utils import knobs

    env_rank = knobs.get_env_rank()
    if env_rank is not None:
        return env_rank
    try:
        from .parallel.store import JaxCoordinationStore

        if JaxCoordinationStore.available():
            import jax

            return jax.process_index()
    except Exception:  # pragma: no cover - jax runtime hiccup
        pass
    return 0


@dataclass
class _Action:
    kind: str
    rule: FaultRule


class FaultyStoragePlugin(StoragePlugin):
    """Wraps any plugin, injecting faults per a :class:`FaultPlan`.

    Transparent when no rule matches: every call (including the
    capability flag) proxies to the inner plugin. Transient
    faults are retried here through the shared ``cloud_retry`` machinery, so
    a transient storm exercises the real backoff + collective-progress
    window; everything else surfaces exactly where a real backend fault
    would."""

    def __init__(self, inner: StoragePlugin, plan: FaultPlan) -> None:
        self.inner = inner
        self.plan = plan
        self._rng = random.Random(plan.seed)
        self._counters: Dict[str, int] = {}
        self._rank = _current_rank()
        self._progress = CollectiveProgress(
            window_s=plan.window_s
        ) if plan.window_s is not None else CollectiveProgress()

    # The capability flag proxies the inner plugin: IO-concurrency scaling
    # must behave as if the wrapper were not there.
    @property
    def scales_io_with_local_world(self) -> bool:  # type: ignore[override]
        return bool(getattr(self.inner, "scales_io_with_local_world", False))

    # ------------------------------------------------------------- injection
    def _next_action(self, op: str, path: str) -> Optional[_Action]:
        index = self._counters.get(op, 0)
        self._counters[op] = index + 1
        for rule in self.plan.rules:
            if op in _DERIVED_OP_SET and rule.op != op:
                continue  # derived classes match only rules naming them
            if rule.matches(op, index, path, self._rng, self._rank):
                rule.injected += 1
                return _Action(kind=rule.kind, rule=rule)
        return None

    async def _guard(self, op: str, path: str) -> Optional[_Action]:
        """Run the injection point for one op. Raises / stalls / kills per
        the matched rule; returns the action for kinds the caller must
        implement itself (torn)."""
        act = self._next_action(op, path)
        if act is None:
            return None
        telemetry.counter_add(f"faults.{act.kind}")
        if act.kind == "stall":
            logger.warning(
                "FAULT stall %.2fs on %s %s", act.rule.secs, op, path
            )
            await asyncio.sleep(act.rule.secs)
            return None
        if act.kind == "kill":
            logger.warning("FAULT kill at %s %s", op, path)
            # os._exit: no atexit, no finally blocks — the closest portable
            # stand-in for SIGKILL-style preemption.
            os._exit(KILL_EXIT_CODE)
        if act.kind == "transient":
            raise InjectedTransientFault(f"injected transient {op} fault: {path}")
        if act.kind == "fail":
            raise InjectedFault(f"injected {op} failure: {path}")
        # torn: the caller transfers partial bytes then fails.
        # corrupt: the caller flips bytes in the completed read's buffer.
        return act

    async def _retrying(self, run, label: str):
        return await retry_transient(
            run,
            lambda e: isinstance(e, InjectedTransientFault),
            self._progress,
            label,
            base_backoff_s=self.plan.backoff_s,
        )

    def _has_rule_for(self, op: str) -> bool:
        return any(rule.op == op for rule in self.plan.rules)

    # ------------------------------------------------------------------- ops
    async def write(self, write_io: WriteIO) -> None:
        async def run() -> None:
            for derived, prefix in _DERIVED_WRITE_OPS:
                if write_io.path.startswith(prefix) and self._has_rule_for(
                    derived
                ):
                    await self._guard(derived, write_io.path)
            act = await self._guard("write", write_io.path)
            if act is not None and act.kind == "torn":
                # Simulated crash mid-write: atomic backends expose no
                # object; fs writes the first `bytes` bytes to its temp
                # file and dies before the rename, leaving crash debris
                # for gc.
                from .storage_plugins.fs import FSStoragePlugin

                fs = self.inner
                while fs is not None and not isinstance(fs, FSStoragePlugin):
                    fs = getattr(fs, "inner", None)
                if fs is not None:
                    mv = memoryview(write_io.buf).cast("B")
                    await fs._write_inner(
                        WriteIO(path=write_io.path, buf=mv[: act.rule.bytes]),
                        None,
                        rename=False,
                    )
                raise InjectedFault(
                    f"injected torn write after {act.rule.bytes} bytes: "
                    f"{write_io.path}"
                )
            await self.inner.write(write_io)

        await self._retrying(run, "faults")

    async def read(self, read_io: ReadIO) -> None:
        async def run() -> None:
            act = await self._guard("read", read_io.path)
            # A retried read must not append to a buffer a failed attempt
            # already partially filled.
            read_io.buf.seek(0)
            read_io.buf.truncate(0)
            await self.inner.read(read_io)
            if act is not None and act.kind == "corrupt":
                self._corrupt_buffer(read_io, act.rule)

        await self._retrying(run, "faults")

    def _corrupt_buffer(self, read_io: ReadIO, rule: FaultRule) -> None:
        """``kind=corrupt``: flip ``rule.bytes`` bytes (default 1) of the
        completed read at seeded offsets — anywhere in the buffer, or
        confined to hash chunk ``rule.chunk``'s extent when the rule is
        chunk-targeted. The read still SUCCEEDS — silent bit rot, which
        only digest verification can catch (and, for chunk-targeted rot,
        must attribute to exactly that chunk)."""
        nbytes = read_io.buf.getbuffer().nbytes
        if nbytes == 0:
            return
        lo, hi = 0, nbytes
        if rule.chunk is not None:
            from .utils import knobs

            grain = knobs.get_hash_chunk_bytes()
            if grain <= 0:
                logger.warning(
                    "FAULT corrupt chunk=%d ignored: hash chunking is "
                    "disabled (grain 0)",
                    rule.chunk,
                )
                return
            # Chunk extents are object coordinates; a ranged read's
            # buffer starts at byte_range[0] of the object.
            base = read_io.byte_range[0] if read_io.byte_range else 0
            lo = max(0, rule.chunk * grain - base)
            hi = min(nbytes, (rule.chunk + 1) * grain - base)
            if hi <= lo:
                logger.warning(
                    "FAULT corrupt chunk=%d skipped: read %s%s does not "
                    "cover the chunk's extent",
                    rule.chunk,
                    read_io.path,
                    f" range {read_io.byte_range}"
                    if read_io.byte_range
                    else "",
                )
                return
        # The read holds its backend's object by reference, which may be
        # the store's own or a cache's: rot a private copy, hand that on.
        rotten = bytearray(read_io.buf.getbuffer())
        for _ in range(max(1, rule.bytes)):
            rotten[lo + self._rng.randrange(hi - lo)] ^= 0xFF
        read_io.buf.seek(0)
        read_io.buf.truncate(0)
        read_io.buf.write(rotten)
        logger.warning(
            "FAULT corrupt %d byte(s) on read %s%s",
            max(1, rule.bytes),
            read_io.path,
            f" (chunk {rule.chunk})" if rule.chunk is not None else "",
        )

    async def delete(self, path: str) -> None:
        async def run() -> None:
            await self._guard("delete", path)
            await self.inner.delete(path)

        await self._retrying(run, "faults")

    async def link_in(self, src_abs_path: str, path: str) -> bool:
        await self._guard("link", path)
        return await self.inner.link_in(src_abs_path, path)

    async def list_prefix(self, prefix: str) -> List[str]:
        async def run() -> List[str]:
            await self._guard("list", prefix)
            return await self.inner.list_prefix(prefix)

        return await self._retrying(run, "faults")

    async def prune_empty(self) -> None:
        await self.inner.prune_empty()

    async def close(self) -> None:
        await self.inner.close()

    # ------------------------------------------------- swarm peer-serve hook
    async def inject_peer_serve(self, path: str, payload: bytearray) -> None:
        """The swarm restore's peer-serving injection point, called with
        the chunk's POSTED payload copy right before this rank fans the
        chunk out to its peers. stall/kill/transient/fail behave as at any
        storage op (a raised fault surfaces as a failed serve); ``corrupt``
        flips seeded bytes of ``payload`` in place — the serving rank's own
        buffer stays clean, modeling a serve that rots in flight
        (NIC/serialization rot), the failure mode per-chunk receipt
        verification exists to catch and attribute to the serving rank."""
        act = await self._guard("peer_serve", path)
        if act is None or act.kind != "corrupt" or not payload:
            return
        flips = max(1, act.rule.bytes)
        for _ in range(flips):
            payload[self._rng.randrange(len(payload))] ^= 0xFF
        logger.warning(
            "FAULT corrupt %d byte(s) in peer-served chunk %s", flips, path
        )


def find_fault_injector(storage) -> Optional[FaultyStoragePlugin]:
    """Locate the fault wrapper inside a (possibly layered) plugin stack —
    the swarm restore drives its peer-serving fault points through it.
    Walks ``inner`` links; None when chaos injection is not installed."""
    seen = 0
    while storage is not None and seen < 8:
        if isinstance(storage, FaultyStoragePlugin):
            return storage
        storage = getattr(storage, "inner", None)
        seen += 1
    return None


def maybe_wrap_with_faults(plugin: StoragePlugin) -> StoragePlugin:
    """Wrap ``plugin`` when the ``TORCHSNAPSHOT_TPU_FAULTS`` knob is set.

    Called by ``url_to_storage_plugin`` on every plugin it constructs; a
    malformed spec raises immediately (tests must fail loudly, and the knob
    never reaches production jobs)."""
    from .utils import knobs

    spec = knobs.get_faults_spec()
    if not spec:
        return plugin
    return FaultyStoragePlugin(plugin, parse_fault_spec(spec))


# ---------------------------------------------------------------------------
# Local (below-the-wrapper) injection points.
#
# Some commit points live INSIDE a plugin the wrapper stacks above — the
# sparse read-cache's bitmap rename is the canonical one — so no storage op
# ever traverses their class through `_guard`. `maybe_inject_local` gives
# those sites a kill-point of their own: a synchronous injection point
# driven by the SAME `TORCHSNAPSHOT_TPU_FAULTS` spec (its own per-op
# counters, its own seeded RNG), matching only rules that name the op class
# explicitly. Unset knob: one env read, no allocation, nothing imported.
# ---------------------------------------------------------------------------


class _LocalInjector:
    """Per-process sync injector for plugin-internal commit points."""

    def __init__(self, spec: str) -> None:
        self.spec = spec
        self.plan = parse_fault_spec(spec)
        self._rng = random.Random(self.plan.seed)
        self._rank = _current_rank()
        self._counters: Dict[str, int] = {}
        self._lock = threading.Lock()

    def match(self, op: str, path: str) -> Optional[FaultRule]:
        """Count one op of this class; the rule that fires at it, if any."""
        with self._lock:
            index = self._counters.get(op, 0)
            self._counters[op] = index + 1
            for rule in self.plan.rules:
                if rule.op != op:
                    continue  # local classes match only rules naming them
                if rule.matches(op, index, path, self._rng, self._rank):
                    rule.injected += 1
                    return rule
        return None

    def inject(self, op: str, path: str) -> None:
        act = self.match(op, path)
        if act is None:
            return
        telemetry.counter_add(f"faults.{act.kind}")
        if act.kind == "stall":
            logger.warning(
                "FAULT stall %.2fs on %s %s", act.secs, op, path
            )
            time.sleep(act.secs)
            return
        if act.kind == "kill":
            logger.warning("FAULT kill at %s %s", op, path)
            os._exit(KILL_EXIT_CODE)
        if act.kind == "transient":
            raise InjectedTransientFault(
                f"injected transient {op} fault: {path}"
            )
        # fail / torn / corrupt all surface as a permanent failure here:
        # these sites are synchronous one-shot commits with no partial
        # transfer or read buffer to manipulate.
        raise InjectedFault(f"injected {op} failure: {path}")


_LOCAL_INJECTOR: Optional[_LocalInjector] = None
_LOCAL_LOCK = threading.Lock()


def _local_injector() -> Optional[_LocalInjector]:
    from .utils import knobs

    spec = knobs.get_faults_spec()
    if not spec:
        return None
    global _LOCAL_INJECTOR
    with _LOCAL_LOCK:
        if _LOCAL_INJECTOR is None or _LOCAL_INJECTOR.spec != spec:
            _LOCAL_INJECTOR = _LocalInjector(spec)
        return _LOCAL_INJECTOR


def maybe_inject_local(op: str, path: str) -> None:
    """Run a plugin-internal injection point (no-op unless the faults knob
    is set AND the spec names ``op``). Callers sit below the wrapper stack,
    so this is their only road into chaos schedules."""
    injector = _local_injector()
    if injector is not None:
        injector.inject(op, path)


def read_chunk_fault(path: str) -> int:
    """The ``read_chunk`` injection point, run by the fs plugin before each
    native read: the chunk of this read that is to fail inside the engine,
    or -1."""
    injector = _local_injector()
    rule = injector.match("read_chunk", path) if injector is not None else None
    if rule is None:
        return -1
    chunk = rule.chunk or 0
    telemetry.counter_add(f"faults.{rule.kind}")
    logger.warning("FAULT transient chunk %d of native read %s", chunk, path)
    return chunk
