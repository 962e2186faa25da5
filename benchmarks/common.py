"""Shared helpers for the entry points that measure (``chip_smoke.py``,
``bench.py``, ``benchmarks/*/main.py``).

Importing this module imports neither jax nor the library, so a parent
process that only spawns device children can use it and stay off the chip.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# The library's own programs (batched fork, chunk slices, slab pack) compile
# in well under jax's default 1 s persistence floor: without lowering it
# they are recompiled by every process.
_CACHE_THRESHOLDS = {
    "jax_persistent_cache_min_compile_time_secs": 0.0,
    "jax_persistent_cache_min_entry_size_bytes": 0,
}


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; call before the backend
    initialises. Returns the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set from outside wins and nothing else is
    set in code. Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
    fixed path, never a temp name, pid or timestamp: the path is part of
    the cache key, so a directory that moves never hits. Everything goes
    through the environment so child processes inherit it."""
    # jax reads its environment at import; once imported, the live config
    # has to be told as well.
    config = sys.modules["jax"].config if "jax" in sys.modules else None
    if not os.environ.get(_ENV_CACHE_DIR):
        os.environ[_ENV_CACHE_DIR] = os.path.join(REPO_ROOT, ".jax_cache")
        if config is not None:
            config.update("jax_compilation_cache_dir", os.environ[_ENV_CACHE_DIR])
    for name, value in _CACHE_THRESHOLDS.items():
        if name.upper() not in os.environ:
            os.environ[name.upper()] = str(value)
            if config is not None:
                config.update(name, value)
    return os.environ[_ENV_CACHE_DIR]


def compile_cache_entries(cache_dir: str) -> int:
    """Number of compiled programs persisted under ``cache_dir``."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for name in os.listdir(cache_dir) if name.endswith("-cache"))


def device_record() -> dict:
    """The device as jax reports it; printed with every result."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_accelerator() -> dict:
    """Fail unless jax found an accelerator: a time or a rate measured on
    XLA's CPU backend is not a device metric and is never reported as one."""
    record = device_record()
    if record["platform"] == "cpu":
        raise SystemExit(
            "no accelerator: jax.devices()[0].platform is 'cpu' "
            f"({record['kind']} x{record['count']}). This entry point "
            "measures the device path and refuses to measure the CPU backend."
        )
    return record


def require_native_engine() -> str:
    """Build/load the native I/O engine BLOCKING and fail if it is absent,
    so every take of a measured run uses one write path (the storage plugin
    loads it non-blocking and writes buffered until g++ finishes). Returns
    the path of the library loaded."""
    from torchsnapshot_tpu import native

    if native.load_native() is None:
        raise SystemExit(
            f"native I/O engine unavailable (expected {native.lib_path()}; "
            "needs g++ and zlib, and TORCHSNAPSHOT_TPU_DISABLE_NATIVE_IO "
            "unset): refusing to measure the pure-Python write path"
        )
    return native.loaded_path()


def start_measured_run() -> dict:
    """First call of a harness whose measured path holds the device: compile
    cache placed, the pod joined, an accelerator found (else exit), the
    native engine loaded blocking (else exit). Returns the device record,
    already printed to stderr."""
    cache_dir = configure_compile_cache()
    maybe_init_distributed()
    device = require_accelerator()
    engine = require_native_engine()
    print(
        f"device: {device}; native engine {engine}; compile cache {cache_dir}",
        file=sys.stderr,
        flush=True,
    )
    return device


def start_host_only_run(name: str, writes_files: bool = True) -> dict:
    """First call of a harness with NO device on its measured path (host
    arrays, CPU-platform worker processes, a modelled disk): its times are
    host-clock times of host code, and it says so — on stderr now, and in
    the record returned here, which goes out with its result. One write
    path all the same: the native engine is loaded blocking."""
    configure_compile_cache()
    record = {"platform": "host-only (no accelerator on the measured path)"}
    if writes_files:
        record["native_engine"] = require_native_engine()
    print(
        f"[{name}] host-only harness: no accelerator on the measured path; "
        "every time and rate below is a host-clock number of host code, "
        "not a device metric",
        file=sys.stderr,
        flush=True,
    )
    return record


def maybe_init_distributed() -> None:
    """Join a multi-host run when ``BENCH_DISTRIBUTED=1`` (exported by
    ``benchmarks/run_tpu_vm.sh`` on every pod worker).

    On Cloud TPU, ``jax.distributed.initialize()`` auto-configures the
    coordinator address, process id, and process count from the TPU metadata
    service — no flags needed. Once initialized, the library's coordinator
    rides the jax coordination service, every host writes its partition of
    each checkpoint, and the benchmark's printed per-host numbers aggregate
    across ``jax.process_count()`` hosts. Must run before any other jax
    call. A no-op in local runs.
    """
    if os.environ.get("BENCH_DISTRIBUTED") in ("1", "true"):
        import jax

        jax.distributed.initialize()
