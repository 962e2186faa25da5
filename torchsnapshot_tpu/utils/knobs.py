"""Env-var configuration knobs (reference ``knobs.py:21-98``).

Thresholds govern chunking (pipelining within one array), shard subdivision,
and small-write batching. Context-manager overrides exist so tests can force
chunking/batching on tiny arrays.
"""

from __future__ import annotations

import contextlib
import os
from typing import Generator, Optional

_ENV_MAX_CHUNK = "TORCHSNAPSHOT_TPU_MAX_CHUNK_SIZE_BYTES"
_ENV_MAX_SHARD = "TORCHSNAPSHOT_TPU_MAX_SHARD_SIZE_BYTES"
_ENV_SLAB_SIZE_THRESHOLD = "TORCHSNAPSHOT_TPU_SLAB_SIZE_THRESHOLD_BYTES"
_ENV_ENABLE_BATCHER = "TORCHSNAPSHOT_TPU_ENABLE_BATCHING"
_ENV_MEMORY_BUDGET = "TORCHSNAPSHOT_TPU_PER_RANK_MEMORY_BUDGET_BYTES"
_ENV_BARRIER_TIMEOUT = "TORCHSNAPSHOT_TPU_BARRIER_TIMEOUT_S"
_ENV_DISABLE_NATIVE_IO = "TORCHSNAPSHOT_TPU_DISABLE_NATIVE_IO"
_ENV_DIRECT_IO_THRESHOLD = "TORCHSNAPSHOT_TPU_DIRECT_IO_THRESHOLD_BYTES"
_ENV_DIRECT_IO_CONCURRENCY = "TORCHSNAPSHOT_TPU_DIRECT_IO_CONCURRENCY"
_ENV_DIRECT_IO_CHUNK = "TORCHSNAPSHOT_TPU_DIRECT_IO_CHUNK_BYTES"

# Commit barriers wait for the *slowest* rank's full data write; on large
# unbalanced snapshots that can far exceed control-plane latencies.
_DEFAULT_BARRIER_TIMEOUT_S = 1800.0

_DEFAULT_MAX_CHUNK_SIZE_BYTES = 512 * 1024 * 1024
_DEFAULT_MAX_SHARD_SIZE_BYTES = 512 * 1024 * 1024
_DEFAULT_SLAB_SIZE_THRESHOLD_BYTES = 128 * 1024 * 1024


def _get_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    return int(val) if val is not None else default


def get_max_chunk_size_bytes() -> int:
    return _get_int(_ENV_MAX_CHUNK, _DEFAULT_MAX_CHUNK_SIZE_BYTES)


def get_max_shard_size_bytes() -> int:
    return _get_int(_ENV_MAX_SHARD, _DEFAULT_MAX_SHARD_SIZE_BYTES)


def get_slab_size_threshold_bytes() -> int:
    return _get_int(_ENV_SLAB_SIZE_THRESHOLD, _DEFAULT_SLAB_SIZE_THRESHOLD_BYTES)


def is_batching_enabled() -> bool:
    return os.environ.get(_ENV_ENABLE_BATCHER, "0") not in ("0", "", "false", "False")


_ENV_ASYNC_DEVICE_COPY = "TORCHSNAPSHOT_TPU_ASYNC_DEVICE_COPY"
_ENV_DEVICE_BATCHING = "TORCHSNAPSHOT_TPU_DEVICE_BATCHING"


def is_device_batching_enabled() -> bool:
    """Pack slab members on-device and fetch with one D2H transfer.

    Only applies when slab batching itself is on and every member of a slab
    is a fully-addressable device array of a byte-width dtype.
    """
    return os.environ.get(_ENV_DEVICE_BATCHING, "1") not in ("0", "false", "False")


def override_device_batching(enabled: bool):
    return _override_env(_ENV_DEVICE_BATCHING, "1" if enabled else "0")


def is_async_device_copy_enabled() -> bool:
    """Fork device buffers on ``async_take`` (donation safety).

    Costs transient HBM equal to the captured state; disable only if the
    training step never donates the checkpointed arrays.
    """
    return os.environ.get(_ENV_ASYNC_DEVICE_COPY, "1") not in ("0", "false", "False")


_ENV_ASYNC_FORK_HBM_LIMIT = "TORCHSNAPSHOT_TPU_ASYNC_FORK_HBM_LIMIT_BYTES"


def get_async_fork_hbm_limit_bytes() -> Optional[int]:
    """Simulated free-HBM cap for the async defensive fork.

    When set, ``io_preparer._defensive_device_copies`` treats any fork that
    would bring the take's cumulative forked bytes above this limit as an
    allocation failure, exercising the degraded capture path (device-fork
    what fits, blocking host capture for the rest) without real HBM
    pressure. Unset (the default) on real hardware: actual XLA
    RESOURCE_EXHAUSTED errors trigger the same degradation."""
    val = os.environ.get(_ENV_ASYNC_FORK_HBM_LIMIT)
    return int(val) if val is not None else None


def override_async_fork_hbm_limit_bytes(value: int):
    return _override_env(_ENV_ASYNC_FORK_HBM_LIMIT, str(value))


def override_async_device_copy(enabled: bool):
    return _override_env(_ENV_ASYNC_DEVICE_COPY, "1" if enabled else "0")


_ENV_ASYNC_CAPTURE = "TORCHSNAPSHOT_TPU_ASYNC_CAPTURE"


def get_async_capture_mode() -> str:
    """How ``async_take`` detaches device arrays from the training step:
    ``fork`` (default) dispatches the defensive on-device copy, paying
    transient HBM (and, on backends where the fork is unsupported, a
    blocking host capture inside the stall); ``donate`` captures the
    caller's immutable arrays ZERO-COPY — the SNIPPETS donation contract
    inverted: instead of the snapshot ceding buffers to the step, the
    caller promises not to donate (``donate_argnums``) or delete the
    passed arrays until the pending snapshot commits. Under ``donate``
    the capture cost of a steady-state take approaches zero. A violated
    promise reads freed buffers — jax raises on use-after-donate, so the
    failure is loud, but the take is lost; keep ``fork`` when the
    training step donates checkpointed state."""
    val = os.environ.get(_ENV_ASYNC_CAPTURE, "fork").lower()
    return "donate" if val == "donate" else "fork"


def override_async_capture(mode: str):
    return _override_env(_ENV_ASYNC_CAPTURE, mode)


def is_native_io_enabled() -> bool:
    return os.environ.get(_ENV_DISABLE_NATIVE_IO, "0") in ("0", "", "false", "False")


def get_direct_io_threshold_bytes() -> int:
    """Writes/reads at least this large go through the native O_DIRECT engine.

    Below it, page-cache I/O wins (no bounce-buffer copy, no alignment pad)
    and the data is typically metadata-sized anyway.
    """
    return _get_int(_ENV_DIRECT_IO_THRESHOLD, 4 * 1024 * 1024)


def get_direct_io_concurrency() -> int:
    """Max concurrent native *writes* per storage plugin (whole objects,
    under the plugin's semaphore), and, where the
    environment sets it, the cap of the read side too
    (:func:`get_direct_read_depth`).

    The default of two, divided by the local world size (see
    :func:`set_local_world_size`: N co-hosted ranks share one mount), is
    what the write path has been measured with on the chip machine's 9p
    mount (`PERF.md`); no record of this repo says what more writers give.
    An explicit env value is used verbatim.
    """
    return _direct_io_cap(2)


def _direct_io_cap(default: int) -> int:
    val = os.environ.get(_ENV_DIRECT_IO_CONCURRENCY)
    if val is not None:
        return max(1, int(val))
    return max(1, default // get_local_world_size())


# Chunk reads on the mount at once, from the probe of PR 29 on the chip
# machine's 9p mount (PERF.md section 6): the smallest depth within 5 % of
# the best.
_DIRECT_READ_DEPTH = 8


def get_direct_read_depth() -> int:
    """Chunk reads the native engine keeps on the mount at once, over every
    object the process is reading (``native.set_read_depth``). Not a knob of
    its own: a constant of the read path divided by the local world size,
    as the write cap is; where ``TORCHSNAPSHOT_TPU_DIRECT_IO_CONCURRENCY``
    is set, that value verbatim, as for writes (a mount that wants two
    streams gets two)."""
    return _direct_io_cap(_DIRECT_READ_DEPTH)


def get_direct_io_chunk_bytes() -> int:
    return _get_int(_ENV_DIRECT_IO_CHUNK, 64 * 1024 * 1024)


def override_native_io_enabled(enabled: bool):
    return _override_env(_ENV_DISABLE_NATIVE_IO, "0" if enabled else "1")


def override_direct_io_threshold_bytes(value: int):
    return _override_env(_ENV_DIRECT_IO_THRESHOLD, str(value))


_ENV_COMPRESSION = "TORCHSNAPSHOT_TPU_COMPRESSION"
_ENV_COMPRESSION_LEVEL = "TORCHSNAPSHOT_TPU_COMPRESSION_LEVEL"


def get_compression() -> str:
    """Array-payload compression codec: 'none' (default), 'zstd', 'zlib'.

    Recorded per entry at write time (restore auto-detects), so the knob
    only affects new takes. Worth turning on when the store/link is slower
    than the compressor (~0.3 GB/s/thread for zstd-3): trained bf16/f32
    weights typically compress 1.3-1.5x, multiplying effective write
    throughput and shrinking checkpoints by the same factor. Composes with
    byte ranges: large payloads are framed (see
    ``get_compression_frame_bytes``) so budgeted sub-reads stay ranged, and
    small payloads join member-framed compressed slabs (batching AND
    compression, compressed at staging time — async device entries on the
    background drain).

    Stall note: device arrays compress in the background drain, but
    *mutable host* arrays stage (and therefore compress) before
    ``async_take`` returns — with large host-resident state, compression
    time joins the stall. The TPU norm (params/optimizer on device, small
    host leaves) keeps the stall unchanged.
    """
    val = os.environ.get(_ENV_COMPRESSION, "none").lower()
    if val in ("", "0", "false", "off"):
        return "none"
    if val not in ("none", "zstd", "zlib"):
        raise ValueError(
            f"{_ENV_COMPRESSION}={val!r}: expected 'none', 'zstd', or 'zlib'"
        )
    if val == "zstd":
        # Fail fast at knob-read (i.e. at prepare_write during take), not
        # ModuleNotFoundError inside the background drain after async_take
        # already returned.
        try:
            import zstandard  # noqa: F401
        except ImportError as e:
            raise RuntimeError(
                f"{_ENV_COMPRESSION}=zstd requires the 'zstandard' package; "
                "install it or use 'zlib'"
            ) from e
    get_compression_level(_codec=val)  # range-validate alongside the codec
    return val


def get_compression_level(_codec: Optional[str] = None) -> int:
    """Codec level (zstd: 1-22, default 3; zlib: 0-9, default 1)."""
    codec = _codec if _codec is not None else get_compression()
    val = os.environ.get(_ENV_COMPRESSION_LEVEL)
    if codec == "none":
        # Unused, and a stale/garbage level env must never fail a take
        # whose compression is off — don't even parse it.
        return 1
    if val is None:
        return 3 if codec == "zstd" else 1
    level = int(val)
    lo, hi = (1, 22) if codec == "zstd" else (0, 9)
    if not lo <= level <= hi:
        raise ValueError(
            f"{_ENV_COMPRESSION_LEVEL}={level} out of range for "
            f"{codec} ({lo}-{hi})"
        )
    return level


_ENV_COMPRESSION_FRAME = "TORCHSNAPSHOT_TPU_COMPRESSION_FRAME_BYTES"
_DEFAULT_COMPRESSION_FRAME_BYTES = 8 * 1024 * 1024


def get_compression_frame_bytes() -> int:
    """Raw bytes per independent compression frame for arrays whose raw size
    exceeds this value. Framing makes big compressed payloads byte-range
    addressable (budgeted sub-reads fetch + decompress only the covering
    frames instead of the whole object) at a sub-1% ratio cost on typical
    weights. 0 disables framing (single-blob payloads, round-2 behavior)."""
    return _get_int(_ENV_COMPRESSION_FRAME, _DEFAULT_COMPRESSION_FRAME_BYTES)


def override_compression_frame_bytes(value: int):
    return _override_env(_ENV_COMPRESSION_FRAME, str(value))


def override_compression(codec: str):
    return _override_env(_ENV_COMPRESSION, codec)


def override_compression_level(level: int):
    return _override_env(_ENV_COMPRESSION_LEVEL, str(level))


_ENV_S3_CHUNK = "TORCHSNAPSHOT_TPU_S3_CHUNK_BYTES"


def get_s3_chunk_bytes() -> int:
    """Part size for S3 multipart uploads (default 100 MB).

    Objects larger than one part upload multipart with per-part retry (a
    fault re-sends at most one part); smaller ones use one PUT. Real S3
    requires parts of at least 5 MiB (except the last) — values below that
    are only meaningful with fake backends in tests.
    """
    return max(1, _get_int(_ENV_S3_CHUNK, 100 * 1024 * 1024))


def override_s3_chunk_bytes(value: int):
    return _override_env(_ENV_S3_CHUNK, str(value))


_ENV_GCS_CHUNK = "TORCHSNAPSHOT_TPU_GCS_CHUNK_BYTES"


def get_gcs_chunk_bytes() -> int:
    """Chunk size for GCS resumable uploads (reference used 100 MB).

    Objects larger than one chunk upload via a resumable session with
    write-cursor recovery; smaller ones use a one-shot PUT. The GCS wire
    protocol requires 256 KiB-multiple chunks; the real upload session
    rounds up to that quantum itself (``_GoogleResumableSession``), so any
    positive value here works — this getter only sets the
    resumable-vs-one-shot threshold and the requested chunk granularity.
    """
    return max(1, _get_int(_ENV_GCS_CHUNK, 100 * 1024 * 1024))


def override_gcs_chunk_bytes(value: int):
    return _override_env(_ENV_GCS_CHUNK, str(value))


def get_barrier_timeout_s() -> float:
    val = os.environ.get(_ENV_BARRIER_TIMEOUT)
    return float(val) if val is not None else _DEFAULT_BARRIER_TIMEOUT_S


def override_barrier_timeout_s(value: float):
    return _override_env(_ENV_BARRIER_TIMEOUT, str(value))


def get_memory_budget_override_bytes() -> Optional[int]:
    val = os.environ.get(_ENV_MEMORY_BUDGET)
    return int(val) if val is not None else None


_ENV_CHECKSUMS = "TORCHSNAPSHOT_TPU_CHECKSUMS"


def is_checksums_enabled() -> bool:
    """Record a CRC32 per storage object at write time (verified on demand
    by ``Snapshot.verify()``). CRC32 runs at GB/s with the GIL released and
    overlaps storage I/O in the staging pool, so the cost is usually hidden
    behind the write path's bottleneck."""
    return os.environ.get(_ENV_CHECKSUMS, "1") not in ("0", "false", "False")


def override_checksums(enabled: bool):
    return _override_env(_ENV_CHECKSUMS, "1" if enabled else "0")


_ENV_TRACE = "TORCHSNAPSHOT_TPU_TRACE"
_ENV_TELEMETRY_ARTIFACTS = "TORCHSNAPSHOT_TPU_TELEMETRY_ARTIFACTS"
_ENV_STALL_WARN_S = "TORCHSNAPSHOT_TPU_STALL_WARN_S"


def is_telemetry_artifacts_enabled() -> bool:
    """Persist a compact per-rank telemetry artifact
    (``.telemetry/rank_<k>.json``: phase durations, drain/pipeline interval
    stats, byte counters, metrics dump, environment fingerprint) inside
    every snapshot, through the snapshot's own storage plugin, before the
    commit barrier — so committed snapshots are auditable after the fact
    (``python -m torchsnapshot_tpu stats <snapshot>``). On by default;
    artifact persistence is fail-open (a write failure logs once and never
    fails the checkpoint). Disabling also restores the fully-off telemetry
    hot path for untraced takes (no session, no span allocation)."""
    return os.environ.get(_ENV_TELEMETRY_ARTIFACTS, "1") not in (
        "0",
        "false",
        "False",
    )


def override_telemetry_artifacts(enabled: bool):
    return _override_env(_ENV_TELEMETRY_ARTIFACTS, "1" if enabled else "0")


def get_stall_warn_s() -> float:
    """Opt-in drain stall watchdog: when set to a positive number of
    seconds, the write pipeline runs a watchdog task that logs ONE
    structured warning (with the stuck stage and pipeline occupancy) each
    time the drain makes no byte progress for this long, re-arming when
    progress resumes. 0/unset disables the watchdog entirely."""
    val = os.environ.get(_ENV_STALL_WARN_S)
    return float(val) if val else 0.0


def override_stall_warn_s(value: float):
    return _override_env(_ENV_STALL_WARN_S, str(value))


_ENV_RECORDER = "TORCHSNAPSHOT_TPU_RECORDER"
_ENV_RECORDER_CAPACITY = "TORCHSNAPSHOT_TPU_RECORDER_CAPACITY"
_ENV_RECORDER_INTERVAL_S = "TORCHSNAPSHOT_TPU_RECORDER_INTERVAL_S"
_ENV_RECORDER_DUMP = "TORCHSNAPSHOT_TPU_RECORDER_DUMP"
_ENV_STEP_TELEMETRY = "TORCHSNAPSHOT_TPU_STEP_TELEMETRY"

_DEFAULT_RECORDER_CAPACITY = 4096
_DEFAULT_RECORDER_INTERVAL_S = 0.25


def is_recorder_enabled() -> bool:
    """The job-lifetime flight recorder (``telemetry/recorder.py``): a
    process-wide, bounded ring-buffer time-series sampler fed by the
    dataflow engine's introspection surface (pool occupancy, budget
    high-water, per-class QoS demand, preemption/pause waves, stall-watchdog
    firings). On by default — the ring is a few MB at the default capacity
    and sampling is one time-check per engine wait round; ``0`` disables it
    entirely, restoring a zero-allocation no-op at every feed site."""
    return os.environ.get(_ENV_RECORDER, "1") not in ("0", "false", "False")


def get_recorder_capacity() -> int:
    """Ring capacity of the flight recorder, in samples (default 4096).
    When full, the oldest samples are overwritten; ``dropped`` counts the
    overwrites so truncation is never silent."""
    return max(16, _get_int(_ENV_RECORDER_CAPACITY, _DEFAULT_RECORDER_CAPACITY))


def get_recorder_interval_s() -> float:
    """Minimum spacing between two engine samples in the flight recorder
    (default 0.25 s). Discrete events (pause/resume waves, stall-watchdog
    firings) are always recorded regardless of this rate limit."""
    try:
        return max(
            0.0,
            float(
                os.environ.get(
                    _ENV_RECORDER_INTERVAL_S, _DEFAULT_RECORDER_INTERVAL_S
                )
            ),
        )
    except ValueError:
        return _DEFAULT_RECORDER_INTERVAL_S


def get_recorder_dump_path() -> Optional[str]:
    """Local file the flight recorder periodically mirrors its ring to
    (atomic replace, at most ~1/s), so ``python -m torchsnapshot_tpu
    monitor`` can render an in-flight operation from another process.
    Unset (the default) disables the mirror — the ring then lives only in
    process memory."""
    return os.environ.get(_ENV_RECORDER_DUMP) or None


def is_step_telemetry_enabled() -> bool:
    """Per-step telemetry rollups for catalog-managed takes: each
    ``take(job=, step=)`` commit appends a compact schema-versioned record
    under ``<bucket>/.catalog/telemetry/`` (rank 0, fail-open) summarizing
    the step — stall, drain wall, phase durations, bytes written/deduped,
    preemption counters, cross-rank skew — merged from the per-rank
    ``.telemetry/`` artifacts. The job-lifetime series behind
    ``python -m torchsnapshot_tpu timeline`` and the health detectors.
    Requires ``TORCHSNAPSHOT_TPU_TELEMETRY_ARTIFACTS`` (the per-rank
    source data); ``0`` disables the rollup append only."""
    return os.environ.get(_ENV_STEP_TELEMETRY, "1") not in (
        "0",
        "false",
        "False",
    )


def override_recorder(enabled: bool):
    return _override_env(_ENV_RECORDER, "1" if enabled else "0")


def override_recorder_capacity(value: int):
    return _override_env(_ENV_RECORDER_CAPACITY, str(value))


def override_recorder_interval_s(value: float):
    return _override_env(_ENV_RECORDER_INTERVAL_S, str(value))


def override_recorder_dump_path(path: str):
    return _override_env(_ENV_RECORDER_DUMP, path)


def override_step_telemetry(enabled: bool):
    return _override_env(_ENV_STEP_TELEMETRY, "1" if enabled else "0")


_ENV_FLEET_TELEMETRY = "TORCHSNAPSHOT_TPU_FLEET_TELEMETRY"
_ENV_FLEET_BEACON_S = "TORCHSNAPSHOT_TPU_FLEET_BEACON_S"

_DEFAULT_FLEET_BEACON_S = 0.5


def get_fleet_telemetry_mode() -> str:
    """The live fleet telemetry bus (``telemetry/fleet.py``): each process
    publishes a rate-limited, schema-versioned status beacon (op/phase,
    engine rollup, progress rates, QoS demand, blocked-on peers) to its own
    coordinator-store key, read back by ``monitor --fleet`` and the
    ``fleet-health`` detectors. ``auto`` (the default) enables the bus only
    when a cross-process coordinator store is configured (TCPStore env or
    jax's coordination service) — solo/LocalStore processes publish nothing;
    ``1`` forces it on with whatever coordinator resolves (useful for unit
    tests over a LocalStore); ``0`` disables it entirely, restoring a
    zero-allocation no-op at every feed site."""
    val = os.environ.get(_ENV_FLEET_TELEMETRY, "auto").strip().lower()
    if val in ("0", "false", "off"):
        return "0"
    if val in ("1", "true", "on"):
        return "1"
    return "auto"


def get_fleet_beacon_s() -> float:
    """Minimum spacing between two fleet beacon publishes from one process
    (default 0.5 s). Bounds beacon store traffic to ~1/interval small writes
    per process; discrete transitions (op start/end, blocked-on edges) ride
    the next due publish rather than bypassing the limit."""
    try:
        return max(
            0.05,
            float(os.environ.get(_ENV_FLEET_BEACON_S, _DEFAULT_FLEET_BEACON_S)),
        )
    except ValueError:
        return _DEFAULT_FLEET_BEACON_S


def override_fleet_telemetry(value: str):
    return _override_env(_ENV_FLEET_TELEMETRY, value)


def override_fleet_beacon_s(value: float):
    return _override_env(_ENV_FLEET_BEACON_S, str(value))


def env_fingerprint() -> dict:
    """Every ``TORCHSNAPSHOT_TPU_*`` env var currently set, verbatim — the
    knob half of the persisted artifact's environment fingerprint. Reading
    the raw env (rather than each getter) records exactly what the operator
    pinned, including values the resolvers would normalize."""
    prefix = _ENV_TRACE[: _ENV_TRACE.index("TRACE")]  # "TORCHSNAPSHOT_TPU_"
    return {k: v for k, v in sorted(os.environ.items()) if k.startswith(prefix)}


def get_trace_path() -> Optional[str]:
    """Destination for Chrome/Perfetto trace-event JSON. When set, every
    ``Snapshot.take``/``async_take``/``restore`` records a telemetry session
    (phase, scheduler stage/io, D2H, and storage-plugin spans plus the
    metrics registry) and writes it here when the operation commits. The
    path is per-process: rank 0 writes the path verbatim, other ranks
    append ``.rank<N>``. Empty/unset disables tracing entirely — the
    instrumented hot paths then cost one None-check per site."""
    val = os.environ.get(_ENV_TRACE)
    return val if val else None


def override_trace_path(path: str):
    return _override_env(_ENV_TRACE, path)


_ENV_DEDUP_DIGESTS = "TORCHSNAPSHOT_TPU_DEDUP_DIGESTS"


def get_dedup_digests_env() -> str:
    """The RAW (normalized) knob string, including ``auto``. The plan-cache
    fingerprint folds this in instead of the resolved boolean: ``auto``
    resolves per-host (CPU count), and a host-dependent fingerprint would
    make identical-env ranks disagree on plan-cache identity."""
    return os.environ.get(_ENV_DEDUP_DIGESTS, "auto").lower()


def is_dedup_digests_enabled(has_base: bool = False) -> bool:
    """Record a sha256 per storage object alongside the CRC so the snapshot
    can later serve as an incremental ``base``.

    Default ``auto``: enabled on multi-core hosts (a spare core hides the
    hash behind the D2H/storage streams) and whenever the take itself
    passes ``base=`` (the dedup identity is the point of that take);
    disabled otherwise — with one usable core the hash competes with the
    CPU-fed device transfer (interference, not hash time). Whether the gate
    is right for the current chip's host is not measured. ``1``/``0`` force
    it either way.

    Caveat the auto mode implies: on a single-core host, a snapshot taken
    WITHOUT ``base=`` carries no sha256s in its sidecars, so a later
    ``take(base=that_snapshot)`` finds no dedup identities to match and
    rewrites everything. Jobs that checkpoint incrementally on such hosts
    should pin ``TORCHSNAPSHOT_TPU_DEDUP_DIGESTS=1`` for every take."""
    val = os.environ.get(_ENV_DEDUP_DIGESTS, "auto").lower()
    if val in ("auto", ""):
        return has_base or _usable_cpu_count() > 1
    return val not in ("0", "false", "off")


def override_dedup_digests(enabled: bool):
    return _override_env(_ENV_DEDUP_DIGESTS, "1" if enabled else "0")


_ENV_PLAN_CACHE = "TORCHSNAPSHOT_TPU_PLAN_CACHE"


def is_plan_cache_enabled() -> bool:
    """Reuse the take plan (partition assignment, coalesced globs, manifest
    baseline) across takes of an identical app-state structure, shrinking a
    steady-state take's coordination to constant per-rank store traffic
    (see ``take_plan.py``). The fingerprint check makes a hit safe; this
    knob exists for A/B measurement and as an escape hatch. A rank with the
    cache disabled forces a global miss — never a hang."""
    return os.environ.get(_ENV_PLAN_CACHE, "1") not in ("0", "false", "False")


def override_plan_cache(enabled: bool):
    return _override_env(_ENV_PLAN_CACHE, "1" if enabled else "0")


_ENV_RESTORE_OVERLAP = "TORCHSNAPSHOT_TPU_RESTORE_OVERLAP"


def is_restore_overlap_enabled(
    has_jax_targets: bool = False,
    target_platforms=None,
) -> bool:
    """Finalize each restored entry (its host→device transfer) as its last
    read consumes — H2D overlaps the storage reads still in flight, and
    host buffers free eagerly so restore peak RSS tracks the memory budget
    rather than the state size.

    Default ``auto``: enabled on multi-core hosts, and — when the restore
    actually has live jax device targets (``has_jax_targets``) — on any
    host whose TARGET arrays live on a real accelerator: there the
    ``device_put`` dispatch hands off to the PJRT client (transfer-engine/
    network bound), so overlap needs no spare core (not measured on the current chip).
    Disabled when the targets are CPU-backed on a single-core host:
    CPU-backend dispatch executes the copy on the host's only core and
    starves behind the busy read pipeline.

    ``target_platforms``: the platforms of the restore targets' shard
    devices — a set of strings (``{"tpu"}``), or a zero-arg callable
    returning one (evaluated only on the single-core + jax-targets branch,
    so multi-core hosts never pay the device walk). Deriving the gate from
    the TARGETS rather than ``jax.default_backend()`` matters on hosts
    where they disagree (e.g. a CPU-default process restoring onto an
    explicitly-addressed accelerator). Mixed-backend caveat: targets
    spanning CPU *and* accelerator devices disable overlap — the CPU-bound
    finalizers would still starve the single core, and per-entry gating is
    not worth the complexity (restores are per-stateful, so splitting
    device/host state across statefuls regains overlap for the device
    part). ``None`` falls back to ``jax.default_backend()``.

    The platforms/backend are only consulted when ``has_jax_targets`` is
    True — live device targets imply jax is already initialized, so a
    numpy-only restore never triggers PJRT backend initialization from a
    knob read. ``1``/``0`` force it either way."""
    val = os.environ.get(_ENV_RESTORE_OVERLAP, "auto").lower()
    if val in ("auto", ""):
        if _usable_cpu_count() > 1:
            return True
        if not has_jax_targets:
            return False
        try:
            if callable(target_platforms):
                target_platforms = target_platforms()
            if target_platforms:
                return all(p != "cpu" for p in target_platforms)
            import jax

            return jax.default_backend() != "cpu"
        except Exception:  # pragma: no cover - jax not importable/initable
            return False
    return val not in ("0", "false", "off")


def _usable_cpu_count() -> int:
    """CPUs this process may actually run on — cgroup/affinity aware, so a
    quota'd container with many visible-but-unusable CPUs doesn't
    auto-enable concurrency that can't win."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:  # pragma: no cover - non-Linux
        return os.cpu_count() or 1


def override_restore_overlap(enabled: bool):
    return _override_env(_ENV_RESTORE_OVERLAP, "1" if enabled else "0")


_ENV_PLAN_CACHE_SIZE = "TORCHSNAPSHOT_TPU_PLAN_CACHE_SIZE"


def get_plan_cache_size() -> int:
    """Max distinct app-state structures whose take plans are retained per
    process (LRU; probes refresh recency). Each cached plan holds the
    previous take's entry dicts (the manifest-delta baseline), so the bound
    trades memory against hit rate for jobs alternating many checkpoint
    structures."""
    return max(1, _get_int(_ENV_PLAN_CACHE_SIZE, 4))


def override_plan_cache_size(value: int):
    return _override_env(_ENV_PLAN_CACHE_SIZE, str(value))


_ENV_PREPARED_CACHE = "TORCHSNAPSHOT_TPU_PREPARED_CACHE"
_ENV_PREPARED_CACHE_SIZE = "TORCHSNAPSHOT_TPU_PREPARED_CACHE_SIZE"


def is_prepared_cache_enabled() -> bool:
    """Cache the *prepared* take across steps, not just the plan: manifest
    skeleton, constructed stagers/write requests (post-partition,
    post-batch) and the replicated-write assignment, keyed by the take
    fingerprint + storage scheme. On a hit, ``prepare_write`` reduces to
    re-binding the new step's arrays into the cached stagers (the
    ``stage.prepare.cache_hit`` span) — the steady-state stall stops paying
    per-leaf classification/stager construction entirely. Strict
    invalidation: any shape/sharding/knob/world/plugin change misses (the
    fingerprint folds every prepare-affecting input), and a rebind that
    detects drift falls back to the full miss path. See
    docs/performance.md, "The steady-state take model"."""
    return os.environ.get(_ENV_PREPARED_CACHE, "1") not in ("0", "false", "False")


def get_prepared_cache_size() -> int:
    """Max distinct (structure, scheme, sync/async) prepared states retained
    per process (LRU). Cached stagers are UNBOUND between takes (no array
    refs pinned), so an entry costs Python objects proportional to the leaf
    count, not checkpoint bytes."""
    return max(1, _get_int(_ENV_PREPARED_CACHE_SIZE, 4))


def override_prepared_cache(enabled: bool):
    return _override_env(_ENV_PREPARED_CACHE, "1" if enabled else "0")


def override_prepared_cache_size(value: int):
    return _override_env(_ENV_PREPARED_CACHE_SIZE, str(value))


_ENV_D2H_LANES = "TORCHSNAPSHOT_TPU_D2H_LANES"


def get_d2h_lanes() -> int:
    """Concurrent device→host transfer lanes per write pipeline (default 4).

    Each lane is one thread on a dedicated transfer executor that resolves
    a transfer via ``np.asarray`` once the lanes have hinted it
    (``copy_to_host_async``, issued under a window of
    ``d2h.HINT_WINDOW_BYTES`` hinted and unresolved a device, not at
    admission), so a few leaves' transfers run back-to-back while earlier
    leaves serialize/hash/write. Distinct from ``TORCHSNAPSHOT_TPU_STAGING_THREADS``
    (the serialize/compress pool): a multi-second compression job on the
    staging pool can no longer head-of-line block the transfer engine.
    """
    return max(1, _get_int(_ENV_D2H_LANES, 4))


def override_d2h_lanes(value: int):
    return _override_env(_ENV_D2H_LANES, str(value))


_ENV_HASH_CHUNK = "TORCHSNAPSHOT_TPU_HASH_CHUNK_BYTES"
_ENV_HASH_WORKERS = "TORCHSNAPSHOT_TPU_HASH_WORKERS"

_DEFAULT_HASH_CHUNK_BYTES = 64 * 1024 * 1024


def get_hash_chunk_bytes() -> int:
    """Grain of the parallel chunked hashing engine (``hashing.py``): each
    ``HASH_CHUNK_BYTES`` slice of a storage object's bytes is hashed
    as an independent job on the hash pool, the per-chunk crc32s combine
    into the bit-identical whole-object crc32 (``crc32_combine``), and the
    content digest becomes the sha256 tree root over the ordered chunk
    digests — recorded in a v2 sidecar whose chunk list makes RANGED reads
    verifiable and scrub corruption chunk-attributable. Objects no larger
    than one chunk keep the exact v1 record. Default 64 MiB. ``0`` disables
    chunking entirely — the serial v1 fold and v1-only sidecars (the compat escape hatch). The grain is part
    of a v2 object's dedup identity: keep it stable across the takes of an
    incremental chain, or changed-grain objects re-upload."""
    val = os.environ.get(_ENV_HASH_CHUNK)
    if val is None:
        return _DEFAULT_HASH_CHUNK_BYTES
    return max(0, int(val))


def get_hash_workers() -> int:
    """Width of the hash pool (per-operation, ``PipelinePools``): how many
    chunk-hash jobs run concurrently. Default: the staging-thread width —
    hashing (~1 GB/s/thread for crc+sha256) must keep pace with the
    combined D2H lanes, and on incremental takes it replaces the skipped
    storage write. Raise on many-core hosts where ``stage_hash_s`` still
    brackets the drain wall."""
    val = os.environ.get(_ENV_HASH_WORKERS)
    if val is not None:
        return max(1, int(val))
    return get_staging_threads()


def override_hash_chunk_bytes(value: int):
    return _override_env(_ENV_HASH_CHUNK, str(value))


_ENV_QOS = "TORCHSNAPSHOT_TPU_QOS"
_ENV_QOS_POLL_S = "TORCHSNAPSHOT_TPU_QOS_POLL_S"
_ENV_QOS_MAX_PAUSE_S = "TORCHSNAPSHOT_TPU_QOS_MAX_PAUSE_S"


def is_qos_enabled() -> bool:
    """Priority-aware admission (``engine/qos.py``): while a higher-class
    operation (FOREGROUND > NORMAL > BACKGROUND) has registered demand in
    this process, lower-class engines stop admitting new work — budget,
    io/hash/transfer-pool slots all yield at the next admission point
    (in-flight steps finish). Off =
    every operation competes FIFO, the pre-engine behavior."""
    return os.environ.get(_ENV_QOS, "1") not in ("0", "false", "False")


def get_qos_poll_s() -> float:
    """How often a preempted (paused) engine re-checks the arbiter for
    higher-class demand to clear (default 20 ms). The preemption-release
    latency floor; raising it trades foreground responsiveness for fewer
    wakeups on long pauses."""
    val = os.environ.get(_ENV_QOS_POLL_S)
    return float(val) if val else 0.02


def get_qos_max_pause_s() -> float:
    """Starvation bound: a preempted engine paused continuously for this
    long (default 60 s) admits one round of work anyway and re-arms, so a
    long-lived foreground class can slow background work to a trickle but
    never wedge it (a drain must still finish, a scrub must still
    complete). 0 disables the bound (pause as long as demand persists)."""
    val = os.environ.get(_ENV_QOS_MAX_PAUSE_S)
    return float(val) if val else 60.0


def override_qos(enabled: bool):
    return _override_env(_ENV_QOS, "1" if enabled else "0")


def override_qos_poll_s(value: float):
    return _override_env(_ENV_QOS_POLL_S, str(value))


def override_qos_max_pause_s(value: float):
    return _override_env(_ENV_QOS_MAX_PAUSE_S, str(value))


_ENV_STAGING_THREADS = "TORCHSNAPSHOT_TPU_STAGING_THREADS"
_ENV_MAX_CONCURRENT_IO = "TORCHSNAPSHOT_TPU_MAX_CONCURRENT_IO"
_ENV_CONSUMING_THREADS = "TORCHSNAPSHOT_TPU_CONSUMING_THREADS"

# Ranks co-hosted with this process (sharing one local disk / NIC). Set by
# ``scheduler.derive_local_world_size`` from the same hostname gather that
# sizes the memory budget; IO-concurrency *defaults* divide by it so co-hosted
# pipelines don't multiply contention on shared hardware.
_local_world_size = 1


def set_local_world_size(n: int) -> None:
    global _local_world_size
    _local_world_size = max(1, int(n))


def get_local_world_size() -> int:
    return _local_world_size


def get_staging_threads() -> int:
    """Thread-pool width for D2H + serialize staging (reference fixed 4)."""
    return max(1, _get_int(_ENV_STAGING_THREADS, 4))


def get_max_concurrent_io(shared_local_device: bool = False) -> int:
    """Storage ops in flight per pipeline (reference fixed 16).

    With ``shared_local_device`` (local-disk backends opt in via
    ``StoragePlugin.scales_io_with_local_world``) the default divides by the
    local world size so N co-hosted ranks collectively keep ~16 ops against
    the one disk instead of 16 x N (measured to lose at local world 4 in
    round 1). Network/object stores keep the full default — their
    throughput is latency-hiding-concurrency-bound, not seek-bound. An
    explicit env value is used verbatim either way.
    """
    val = os.environ.get(_ENV_MAX_CONCURRENT_IO)
    if val is not None:
        return max(1, int(val))
    if shared_local_device:
        return max(1, 16 // get_local_world_size())
    return 16


def get_max_concurrent_io_for(storage) -> int:
    """IO-concurrency cap for a specific storage plugin — the one place the
    ``scales_io_with_local_world`` flag is consulted (duck-typed so test
    fakes without the StoragePlugin base still work)."""
    return get_max_concurrent_io(
        bool(getattr(storage, "scales_io_with_local_world", False))
    )


def get_consuming_threads() -> int:
    """Thread-pool width for deserialize + scatter on restore."""
    return max(1, _get_int(_ENV_CONSUMING_THREADS, 4))


def override_staging_threads(value: int):
    return _override_env(_ENV_STAGING_THREADS, str(value))


def override_max_concurrent_io(value: int):
    return _override_env(_ENV_MAX_CONCURRENT_IO, str(value))


def override_consuming_threads(value: int):
    return _override_env(_ENV_CONSUMING_THREADS, str(value))


# -- control-plane / operator knobs ------------------------------------------
# Not performance thresholds, but env-var configuration all the same: the
# TCPStore coordination mode, the multi-process launcher's shutdown linger,
# and the CLI's debug switch. Registered here (and in the docs catalog) like
# every other TORCHSNAPSHOT_TPU_* name — the knob-drift analyzer pass
# enforces that no literal appears anywhere else in the library.

_ENV_STORE_ADDR = "TORCHSNAPSHOT_TPU_STORE_ADDR"  # host:port of a TCPStore
_ENV_RANK = "TORCHSNAPSHOT_TPU_RANK"
_ENV_WORLD_SIZE = "TORCHSNAPSHOT_TPU_WORLD_SIZE"
_ENV_LAUNCHER_DRAIN_S = "TORCHSNAPSHOT_TPU_LAUNCHER_DRAIN_S"
_ENV_CLI_TRACEBACK = "TORCHSNAPSHOT_TPU_CLI_TRACEBACK"


def get_store_addr() -> Optional[str]:
    """TCPStore coordination endpoint (``host:port``). Set alongside rank /
    world size to coordinate without ``jax.distributed``; unset, the
    coordinator falls back to jax's coordination service (or runs solo)."""
    return os.environ.get(_ENV_STORE_ADDR) or None


def get_env_rank() -> Optional[int]:
    val = os.environ.get(_ENV_RANK)
    return int(val) if val is not None else None


def get_env_world_size() -> Optional[int]:
    val = os.environ.get(_ENV_WORLD_SIZE)
    return int(val) if val is not None else None


def set_coordinator_env(store_addr: str, rank: int, world_size: int) -> None:
    """Point THIS process (and its children) at a TCPStore: the launcher-side
    writer for the three coordination knobs above."""
    os.environ[_ENV_STORE_ADDR] = store_addr
    os.environ[_ENV_RANK] = str(rank)
    os.environ[_ENV_WORLD_SIZE] = str(world_size)


_ENV_DEBUG_LEDGER = "TORCHSNAPSHOT_TPU_DEBUG_LEDGER"


def is_debug_ledger_enabled() -> bool:
    """Debug-mode budget-ledger sanitizer: when set, every pipeline memory
    budget journals each debit with its owner/call-site and asserts ZERO
    outstanding bytes at pipeline close and on every abort path, raising a
    ``LedgerLeakError`` that names the leaking sites (see ``ledger.py`` and
    ``docs/robustness.md``). The runtime cross-check of the static TSA6xx
    resource-balance pass; enabled across the chaos matrix and the
    d2h/scheduler suites in CI. Off (the default) allocates nothing."""
    return os.environ.get(_ENV_DEBUG_LEDGER, "") not in ("", "0", "false", "False")


def override_debug_ledger(enabled: bool):
    return _override_env(_ENV_DEBUG_LEDGER, "1" if enabled else "0")


_ENV_DEBUG_COLLECTIVES = "TORCHSNAPSHOT_TPU_DEBUG_COLLECTIVES"


def is_debug_collectives_enabled() -> bool:
    """Debug-mode collective lockstep sanitizer: when set, every coordinator
    collective and commit/restore barrier phase is journaled with a monotonic
    sequence number, op-kind/key fingerprint, and originating call site, and
    the rolling fingerprint is cross-checked against every peer through the
    coordinator store at each barrier — a divergent rank raises a
    ``CollectiveDivergenceError`` naming both ranks' call sites and the first
    divergent sequence number (see ``collective_tracer.py`` and
    ``docs/robustness.md``). The runtime cross-check of the static TSA9xx
    collective-discipline pass; enabled across the chaos matrix and the
    multiprocess suites in CI. Off (the default) allocates nothing."""
    return os.environ.get(_ENV_DEBUG_COLLECTIVES, "") not in (
        "", "0", "false", "False",
    )


def override_debug_collectives(enabled: bool):
    return _override_env(_ENV_DEBUG_COLLECTIVES, "1" if enabled else "0")


_ENV_DEBUG_EFFECTS = "TORCHSNAPSHOT_TPU_DEBUG_EFFECTS"


def is_debug_effects_enabled() -> bool:
    """Debug-mode durable-effect journal: when set, every storage plugin
    ``url_to_storage_plugin`` constructs is wrapped in an
    :class:`~torchsnapshot_tpu.effect_journal.EffectRecordingPlugin` that
    records each mutating op (write / delete / link) as one
    sequence-numbered journal entry carrying the op class, path, content fingerprint, payload, and originating call
    site. The journal is the input to the crash-state explorer
    (``dev/crash_explorer.py``), which replays every effect prefix and
    asserts each one is a restorable crash state — the runtime cross-check
    of the static TSA10xx durability-discipline pass (see
    ``effect_journal.py`` and ``docs/robustness.md``). Off (the default)
    allocates nothing; the wrapper is never even imported."""
    return os.environ.get(_ENV_DEBUG_EFFECTS, "") not in (
        "", "0", "false", "False",
    )


def override_debug_effects(enabled: bool):
    return _override_env(_ENV_DEBUG_EFFECTS, "1" if enabled else "0")


_ENV_READ_CACHE_DIR = "TORCHSNAPSHOT_TPU_READ_CACHE_DIR"
_ENV_READ_CACHE_BYTES = "TORCHSNAPSHOT_TPU_READ_CACHE_BYTES"
_ENV_READ_CACHE_VERIFY = "TORCHSNAPSHOT_TPU_READ_CACHE_VERIFY"

_DEFAULT_READ_CACHE_BYTES = 10 * 1024 * 1024 * 1024


def get_read_cache_dir() -> Optional[str]:
    """Root directory of the content-addressed read-through cache. When set,
    every storage plugin ``url_to_storage_plugin`` constructs is wrapped in a
    :class:`~torchsnapshot_tpu.storage_plugins.cache.CachedStoragePlugin`
    that serves repeat reads from this local store instead of the origin
    backend — the serving-fleet knob (K replicas cold-starting from one
    snapshot hit the origin once, not K times). Unset (the default) disables
    the wrapper entirely; it is never even imported."""
    return os.environ.get(_ENV_READ_CACHE_DIR) or None


def get_read_cache_bytes() -> int:
    """Byte budget of the local read-through cache store (default 10 GiB).
    Exceeding it evicts least-recently-used entries after each populate."""
    return max(0, _get_int(_ENV_READ_CACHE_BYTES, _DEFAULT_READ_CACHE_BYTES))


def is_read_cache_verify_enabled() -> bool:
    """Verify digest-keyed cache hits against their recorded sha256 before
    serving (default on). A corrupt local entry then falls back to the
    origin and is re-populated instead of silently serving bad bytes; the
    cost is one hash pass per hit (~GB/s, GIL released).
    ``TORCHSNAPSHOT_TPU_VERIFY_READS=0`` is the master off switch: it
    disables cache-hit verification too."""
    if get_verify_reads_mode() == "off":
        return False
    return os.environ.get(_ENV_READ_CACHE_VERIFY, "1") not in (
        "0",
        "false",
        "False",
    )


def override_read_cache_dir(path: str):
    return _override_env(_ENV_READ_CACHE_DIR, path)


_ENV_VERIFY_READS = "TORCHSNAPSHOT_TPU_VERIFY_READS"


def get_verify_reads_mode() -> str:
    """Read-side digest-verification mode: ``auto`` | ``all`` | ``off``.

    - ``auto`` (default): cache hits are verified against their sidecar
      digest before being served (subject to
      ``TORCHSNAPSHOT_TPU_READ_CACHE_VERIFY``); origin reads are trusted —
      backends carry their own transport checksums.
    - ``all`` (``1``): the read pipeline additionally verifies EVERY
      full-object fetch (origin or cache) against the snapshot's checksum
      sidecars, with one verified re-fetch on mismatch before a structured
      abort — the bit-rot shield for serving fleets.
    - ``off`` (``0``): no read-side verification anywhere, including cache
      hits."""
    val = os.environ.get(_ENV_VERIFY_READS, "auto").lower()
    if val in ("", "auto"):
        return "auto"
    if val in ("0", "false", "off"):
        return "off"
    return "all"


def is_origin_read_verify_enabled() -> bool:
    """Whether the scheduler's read pipeline verifies fetched objects
    against the sidecar digests (the ``all`` mode of
    ``TORCHSNAPSHOT_TPU_VERIFY_READS``)."""
    return get_verify_reads_mode() == "all"


def override_verify_reads(mode: str):
    return _override_env(_ENV_VERIFY_READS, mode)


_ENV_BCAST_RESTORE = "TORCHSNAPSHOT_TPU_BCAST_RESTORE"
_ENV_BCAST_MAX_BYTES = "TORCHSNAPSHOT_TPU_BCAST_MAX_BYTES"

_DEFAULT_BCAST_MAX_BYTES = 256 * 1024 * 1024


def is_broadcast_restore_enabled(world_size: int, storage=None) -> bool:
    """Single-reader + collective-broadcast restore for replicated entries:
    one elected rank per object issues the storage read and the bytes fan
    out over the coordinator store, collapsing N identical bucket reads to
    one.

    Default ``auto``: enabled at world > 1 against network/object stores
    (gcs/s3 — where N identical GETs are the cold-start bottleneck),
    disabled for local-disk-backed plugins (``scales_io_with_local_world``:
    co-hosted ranks re-reading a local file is cheaper than a store
    round-trip) and always at world 1. The broadcast rides the KV store —
    no device collectives — so it works on any mesh/backend mix. ``1``/``0``
    force it either way (still a no-op at world 1)."""
    if world_size <= 1:
        return False
    val = os.environ.get(_ENV_BCAST_RESTORE, "auto").lower()
    if val in ("auto", ""):
        return not bool(getattr(storage, "scales_io_with_local_world", False))
    return val not in ("0", "false", "off")


def get_broadcast_max_bytes() -> int:
    """Largest replicated object restored via broadcast (default 256 MB);
    bigger ones fall back to per-rank reads. Bounds both the store payload
    and the host RAM the broadcast phase holds at once."""
    return max(1, _get_int(_ENV_BCAST_MAX_BYTES, _DEFAULT_BCAST_MAX_BYTES))


def override_broadcast_restore(enabled: bool):
    return _override_env(_ENV_BCAST_RESTORE, "1" if enabled else "0")


def override_broadcast_max_bytes(value: int):
    return _override_env(_ENV_BCAST_MAX_BYTES, str(value))


_ENV_BCAST_READER_DEADLINE = "TORCHSNAPSHOT_TPU_BCAST_READER_DEADLINE_S"
_ENV_BCAST_REELECT_MAX = "TORCHSNAPSHOT_TPU_BCAST_REELECT_MAX"

_DEFAULT_BCAST_READER_DEADLINE_S = 60.0
_DEFAULT_BCAST_REELECT_MAX = 1


def get_bcast_reader_deadline_s() -> float:
    """How long a broadcast-restore peer waits for the elected reader's
    payload (or error marker) before declaring the reader dead and electing
    the next rank in the sha1 order (default 60 s). Each re-election attempt
    gets a fresh deadline; a reader that posts late is still consumed (its
    payload key is generation- and attempt-fenced, so a slow reader can
    never corrupt a later attempt)."""
    try:
        return max(
            0.05,
            float(
                os.environ.get(
                    _ENV_BCAST_READER_DEADLINE,
                    _DEFAULT_BCAST_READER_DEADLINE_S,
                )
            ),
        )
    except ValueError:
        return _DEFAULT_BCAST_READER_DEADLINE_S


def get_bcast_reelect_max() -> int:
    """Max reader re-elections per broadcast object before a peer stops
    waiting and falls back to a DIRECT origin read (default 1). The
    fallback means broadcast mode can never be less available than direct
    mode: a peer that can reach the origin always makes progress."""
    return max(0, _get_int(_ENV_BCAST_REELECT_MAX, _DEFAULT_BCAST_REELECT_MAX))


def override_bcast_reader_deadline_s(value: float):
    return _override_env(_ENV_BCAST_READER_DEADLINE, str(value))


_ENV_SWARM_RESTORE = "TORCHSNAPSHOT_TPU_SWARM_RESTORE"
_ENV_SWARM_CHUNK_DEADLINE = "TORCHSNAPSHOT_TPU_SWARM_CHUNK_DEADLINE_S"
_ENV_SWARM_FANOUT = "TORCHSNAPSHOT_TPU_SWARM_FANOUT"

_DEFAULT_SWARM_CHUNK_DEADLINE_S = 30.0
_DEFAULT_SWARM_FANOUT = 8


def is_swarm_restore_enabled(world_size: int, storage=None) -> bool:
    """Content-addressed swarm restore for LARGE replicated objects (above
    ``TORCHSNAPSHOT_TPU_BCAST_MAX_BYTES``, where single-reader broadcast
    would hold the whole payload in the coordinator store): every rank
    fetches a distinct subset of the object's v2 hash-chunk grid from
    origin (assignment spread by the sha1 election order, SPMD-pure) and
    fills the rest peer-to-peer through the coordinator store, verifying
    each received chunk against the sidecar tree digests — total origin
    bytes ≈ one snapshot regardless of fleet size. Requires the snapshot's
    v2 tree-digest sidecars (chunk-grain records); objects without them
    fall back to direct per-rank reads.

    Default ``auto``: same gate as broadcast restore — enabled at
    world > 1 against network/object stores, disabled for local-disk
    plugins and always at world 1. ``1``/``0`` force (still a no-op at
    world 1)."""
    if world_size <= 1:
        return False
    val = os.environ.get(_ENV_SWARM_RESTORE, "auto").lower()
    if val in ("auto", ""):
        return not bool(getattr(storage, "scales_io_with_local_world", False))
    return val not in ("0", "false", "off")


def get_swarm_chunk_deadline_s() -> float:
    """How long a swarm peer waits for one chunk from its elected serving
    rank before declaring that rank dead for the chunk and re-electing the
    next rank in the sha1 order (default 30 s). Per chunk and per attempt —
    a slow server posting late still lands under its own attempt fence."""
    try:
        return max(
            0.05,
            float(
                os.environ.get(
                    _ENV_SWARM_CHUNK_DEADLINE,
                    _DEFAULT_SWARM_CHUNK_DEADLINE_S,
                )
            ),
        )
    except ValueError:
        return _DEFAULT_SWARM_CHUNK_DEADLINE_S


def get_swarm_fanout() -> int:
    """Peer-fanout cap: concurrent chunk transfers (origin fetches by this
    rank plus chunks being served to peers) per swarm object (default 8).
    Bounds both origin-connection pressure and the host RAM held by
    in-flight chunk payloads beyond the object buffer itself."""
    return max(1, _get_int(_ENV_SWARM_FANOUT, _DEFAULT_SWARM_FANOUT))


def override_swarm_restore(enabled: bool):
    return _override_env(_ENV_SWARM_RESTORE, "1" if enabled else "0")


def override_swarm_chunk_deadline_s(value: float):
    return _override_env(_ENV_SWARM_CHUNK_DEADLINE, str(value))


_ENV_READ_MERGE_GAP = "TORCHSNAPSHOT_TPU_READ_MERGE_GAP_BYTES"


def get_read_merge_gap_bytes() -> int:
    """Max gap between two byte-range reads of one object that the read
    batcher still coalesces into a single ranged request (default 0 =
    exactly-adjacent only, the historical behavior). Lazy partial restores
    of slab-batched subtrees produce near-adjacent member ranges; a small
    gap tolerance trades a few discarded bytes for far fewer storage round
    trips on high-latency backends."""
    return max(0, _get_int(_ENV_READ_MERGE_GAP, 0))


def override_read_merge_gap_bytes(value: int):
    return _override_env(_ENV_READ_MERGE_GAP, str(value))


_ENV_CATALOG = "TORCHSNAPSHOT_TPU_CATALOG"
_ENV_MAX_CHAIN_LEN = "TORCHSNAPSHOT_TPU_MAX_CHAIN_LEN"

_DEFAULT_MAX_CHAIN_LEN = 16


def is_catalog_enabled() -> bool:
    """The per-bucket snapshot catalog (``catalog.py``): takes that pass
    ``job=`` append an atomically-written record (job, step, base pointer,
    chain length, byte attribution) under ``<bucket>/.catalog/`` at commit
    time, auto-select their ``base=`` from the latest committed same-job
    record, and retention policies (``catalog retain`` / ``gc --policy``)
    drive chain-aware garbage collection off those records. ``0`` disables
    both the commit-time append and auto-base selection (takes with
    ``job=`` then behave like plain full takes); existing records are
    never consulted. Default on — the catalog is fail-open by contract
    (an append failure can never fail or delay a commit)."""
    return os.environ.get(_ENV_CATALOG, "1").lower() not in (
        "0", "false", "off",
    )


def get_max_chain_len() -> int:
    """Default rebase-to-full policy for catalog-managed delta chains
    (``Snapshot.take(job=...)`` without an explicit ``max_chain_len=``): an
    auto-selected base whose recorded chain is already this many deltas
    deep is refused and the take rebases to a FULL snapshot (chain length
    0). Bounds both the blast radius of a single rotten delta and the
    sidecar/metadata walk a retention scan pays per chain (default 16,
    floor 1)."""
    return max(1, _get_int(_ENV_MAX_CHAIN_LEN, _DEFAULT_MAX_CHAIN_LEN))


def override_catalog(enabled: bool):
    return _override_env(_ENV_CATALOG, "1" if enabled else "0")


_ENV_FAULTS = "TORCHSNAPSHOT_TPU_FAULTS"


def get_faults_spec() -> Optional[str]:
    """Deterministic storage-fault injection spec (see ``faults.py`` and
    ``docs/robustness.md`` for the grammar). When set, every storage plugin
    ``url_to_storage_plugin`` constructs — in this process and in child
    ranks, since the env var is inherited — is wrapped in a
    :class:`~torchsnapshot_tpu.faults.FaultyStoragePlugin` that injects
    transient/permanent failures, torn writes, latency stalls, and
    process-kill crash points per the seeded spec. Test-only: leave unset
    in production jobs."""
    return os.environ.get(_ENV_FAULTS) or None


def override_faults(spec: str):
    return _override_env(_ENV_FAULTS, spec)


def get_launcher_drain_s() -> float:
    """How long ``test_utils.run_with_processes``'s rank 0 lingers after its
    own work so peers still inside a final store op aren't connection-reset
    (rank 0 hosts the TCPStore server). Tests that kill peers outright
    shrink it so the survivor doesn't idle out the full default."""
    return float(os.environ.get(_ENV_LAUNCHER_DRAIN_S, "20"))


def is_cli_traceback_enabled() -> bool:
    """``python -m torchsnapshot_tpu`` debug switch: surface the full
    traceback instead of the one-line scriptable error."""
    return os.environ.get(_ENV_CLI_TRACEBACK, "") not in ("", "0", "false", "False")


@contextlib.contextmanager
def _override_env(name: str, value: str) -> Generator[None, None, None]:
    prev = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if prev is None:
            del os.environ[name]
        else:
            os.environ[name] = prev


def override_max_chunk_size_bytes(value: int):
    return _override_env(_ENV_MAX_CHUNK, str(value))


def override_max_shard_size_bytes(value: int):
    return _override_env(_ENV_MAX_SHARD, str(value))


def override_slab_size_threshold_bytes(value: int):
    return _override_env(_ENV_SLAB_SIZE_THRESHOLD, str(value))


def override_batching_enabled(enabled: bool):
    return _override_env(_ENV_ENABLE_BATCHER, "1" if enabled else "0")


def override_memory_budget_bytes(value: int):
    return _override_env(_ENV_MEMORY_BUDGET, str(value))
