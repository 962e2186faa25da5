"""Shipped test utilities (analogue of reference ``test_utils.py:41-290``).

- array-aware state-dict equality (`assert_state_dict_eq` understands
  jax/numpy arrays, including exact bitwise comparison for checkpoint tests);
- `rand_array` across every supported dtype;
- a multi-process launcher that forks a worker function into N real
  processes coordinated by the built-in TCPStore (and optionally
  `jax.distributed` on CPU) — the analogue of the reference's
  torchelastic-based ``run_with_pet`` (``test_utils.py:227-265``).
"""

from __future__ import annotations

import contextlib
import multiprocessing as mp
import queue as queue_mod
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .serialization import SUPPORTED_DTYPES


def _leaf_eq(a: Any, b: Any, exact: bool) -> bool:
    import jax

    a_arr = isinstance(a, (np.ndarray, jax.Array, np.generic))
    b_arr = isinstance(b, (np.ndarray, jax.Array, np.generic))
    if a_arr != b_arr:
        return False
    if a_arr:
        a_np, b_np = np.asarray(a), np.asarray(b)
        if a_np.dtype != b_np.dtype or a_np.shape != b_np.shape:
            return False
        if exact:
            # Bitwise comparison: NaN payloads must round-trip too.
            return bool(
                np.array_equal(
                    np.ascontiguousarray(a_np).reshape(-1).view(np.uint8),
                    np.ascontiguousarray(b_np).reshape(-1).view(np.uint8),
                )
            )
        return bool(np.allclose(a_np.astype(np.float64), b_np.astype(np.float64)))
    return bool(a == b)


def check_state_dict_eq(a: Any, b: Any, exact: bool = True) -> bool:
    if isinstance(a, dict) and isinstance(b, dict):
        if set(a.keys()) != set(b.keys()):
            return False
        return all(check_state_dict_eq(a[k], b[k], exact) for k in a)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        if len(a) != len(b):
            return False
        return all(check_state_dict_eq(x, y, exact) for x, y in zip(a, b))
    return _leaf_eq(a, b, exact)


def assert_state_dict_eq(tc_or_a: Any, a: Any = None, b: Any = None, exact: bool = True) -> None:
    """assert_state_dict_eq(a, b) or assert_state_dict_eq(test_case, a, b)."""
    if b is None:
        a, b = tc_or_a, a
    if not check_state_dict_eq(a, b, exact):
        raise AssertionError(f"State dicts differ:\n  a={a!r}\n  b={b!r}")


def rand_array(shape, dtype: str, seed: Optional[int] = None) -> np.ndarray:
    """Random array of any supported dtype (reference ``rand_tensor:104``)."""
    rng = np.random.default_rng(seed)
    np_dtype = SUPPORTED_DTYPES[dtype]
    if dtype == "bool":
        return rng.integers(0, 2, size=shape).astype(np.bool_)
    if dtype.startswith(("int", "uint")):
        if dtype in ("int4", "uint4"):
            return rng.integers(0, 8, size=shape).astype(np_dtype)
        # Exercise the full byte width (incl. sign bit for signed types).
        info = np.iinfo(np_dtype)
        return rng.integers(
            int(info.min), int(info.max), size=shape, dtype=np.int64
            if dtype.startswith("int")
            else np.uint64,
        ).astype(np_dtype)
    if dtype.startswith("complex"):
        return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
            np_dtype
        )
    return rng.standard_normal(shape).astype(np_dtype)


# ---------------------------------------------------------------------------
# Multi-process launcher
# ---------------------------------------------------------------------------

_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


def _worker_entry(
    fn: Callable[..., Any],
    rank: int,
    world_size: int,
    store_addr: str,
    error_queue: "mp.Queue",
    init_jax_distributed: bool,
    coordinator_addr: str,
    args: tuple,
) -> None:
    try:
        from .utils import knobs

        knobs.set_coordinator_env(store_addr, rank, world_size)
        if init_jax_distributed:
            import jax

            jax.distributed.initialize(
                coordinator_address=coordinator_addr,
                num_processes=world_size,
                process_id=rank,
            )
        fn(rank, world_size, *args)
        error_queue.put((rank, None))
    except BaseException:  # noqa: BLE001
        error_queue.put((rank, traceback.format_exc()))
        raise
    finally:
        # Rank 0 hosts the TCPStore server: if it exits the moment its own
        # work finishes, peers still inside a final store op get their
        # connections reset. Drain: every rank bumps an exit counter; rank 0
        # lingers (bounded) until all peers have checked out or failed.
        try:
            import time as _time

            from .parallel import coordinator as _coord_mod

            # Only drain through a coordinator the worker actually created:
            # fabricating one here could build a wrong (world=1) coordinator
            # on early-failure paths, or retry-connect to a dead server.
            if _coord_mod._CACHED is not None:
                store = _coord_mod._CACHED.store
                # Deliberately asymmetric, best-effort shutdown accounting
                # (the whole drain is wrapped fail-open and no peer WAITS on
                # these counters — a rank that dies here just shortens rank
                # 0's linger): not a lockstep collective.
                store.add("__launcher_exit__", 1)  # noqa: TSA902
                if rank == 0:
                    # Bounded linger; tests that kill peers outright can
                    # shrink it so the survivor doesn't idle out the full
                    # default waiting for a checkout that will never come.
                    from .utils import knobs

                    drain_s = knobs.get_launcher_drain_s()
                    deadline = _time.monotonic() + drain_s
                    while _time.monotonic() < deadline:
                        # Rank 0 alone polls the exit counter (time-bounded,
                        # fail-open): the linger protocol, not lockstep.
                        if store.add("__launcher_exit__", 0) >= world_size:  # noqa: TSA901,TSA902,TSA903
                            break
                        _time.sleep(0.05)
        except Exception:
            pass


def run_with_processes(
    fn: Callable[..., Any],
    nproc: int,
    init_jax_distributed: bool = False,
    args: tuple = (),
    timeout_s: float = 240.0,
) -> None:
    """Run ``fn(rank, world_size, *args)`` in ``nproc`` spawned processes.

    Coordination: rank 0 hosts the built-in TCPStore; with
    ``init_jax_distributed=True`` the workers additionally form a real
    multi-process CPU jax runtime (global meshes spanning processes).
    """
    from .parallel.store import free_port

    ctx = mp.get_context("spawn")
    store_port = free_port()
    coordinator_port = free_port()
    store_addr = f"127.0.0.1:{store_port}"
    coordinator_addr = f"127.0.0.1:{coordinator_port}"
    error_queue: mp.Queue = ctx.Queue()
    procs: List[mp.Process] = []
    # Workers are CPU-platform processes with two virtual devices each, and
    # are born that way: the environment a spawned child starts with is read
    # by jax at import, before any worker code could set it.
    from .utils import knobs

    with contextlib.ExitStack() as born_with:
        for name, value in _WORKER_ENV.items():
            born_with.enter_context(knobs._override_env(name, value))
        for rank in range(nproc):
            p = ctx.Process(
                target=_worker_entry,
                args=(
                    fn,
                    rank,
                    nproc,
                    store_addr,
                    error_queue,
                    init_jax_distributed,
                    coordinator_addr,
                    args,
                ),
                daemon=False,
            )
            p.start()
            procs.append(p)
    failures: Dict[int, str] = {}
    reported: set = set()
    # A worker killed outright (SIGKILL — the preemption failure mode) never
    # reports; treat "process dead + nothing queued" as its report. The
    # two-consecutive-observations grace covers the race where a worker's
    # queue item is still in flight when the process exits.
    dead_strikes: Dict[int, int] = {}
    deadline = time.monotonic() + timeout_s
    try:
        while len(reported) < nproc:
            try:
                rank, err = error_queue.get(timeout=0.2)
            except queue_mod.Empty:
                for r, p in enumerate(procs):
                    if r in reported or p.is_alive():
                        continue
                    dead_strikes[r] = dead_strikes.get(r, 0) + 1
                    if dead_strikes[r] >= 2:
                        reported.add(r)
                        failures[r] = (
                            f"died without reporting (exitcode {p.exitcode})"
                        )
                if time.monotonic() > deadline:
                    pending = sorted(set(range(nproc)) - reported)
                    raise TimeoutError(
                        f"ranks {pending} neither reported nor exited within "
                        f"{timeout_s}s"
                    )
                continue
            reported.add(rank)
            # A queue item proves feeder threads are still flushing: restart
            # every not-yet-reported rank's death grace, and clear a false
            # death verdict if this rank's real report just arrived late.
            dead_strikes.clear()
            if err is not None:
                failures[rank] = err
            else:
                failures.pop(rank, None)
    finally:
        # Reap promptly on every exit path. On success every rank has
        # already reported (only rank 0's bounded store-drain linger may
        # remain); on failure/timeout a hung child must not stall teardown
        # for 30 s per process — escalate join -> terminate -> kill.
        for p in procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        for p in procs:
            if p.is_alive():
                p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join(timeout=10)
    if failures:
        msgs = "\n".join(f"--- rank {r} ---\n{e}" for r, e in failures.items())
        raise RuntimeError(f"{len(failures)}/{nproc} workers failed:\n{msgs}")
