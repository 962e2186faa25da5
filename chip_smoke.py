"""Chip smoke: take / async_take / restore on the accelerator, end to end.

Drives the library's normal entry points (``Snapshot.take``,
``Snapshot.async_take``, ``PendingSnapshot.wait``, ``Snapshot.restore``,
``verify``, ``read_object``, ``PyTreeStateful``/``Box``) with default knobs
under a real trainer: the flagship transformer (``models/transformer.py``)
at d_model 4096, 32 heads, d_ff 16384, vocab 32000, seq 512, bf16 params,
``optax.adamw``, with depth as the only cut, and a jitted train step that
DONATES its state.

  leg A      fork fits (depth 2, ~4 GB of params+moments): async_take, keep
             stepping with donation while the drain runs, wait, sync take,
             verify, read_object. No leaf may be host-captured.
  leg B      HBM filled as a job fills it (depth from the chip's
             ``bytes_limit``: >= 55% in use, state larger than free HBM):
             the fork cannot fit, so capture degrades leaf by leaf. A step
             the fork leaves no room for is reported, not retried.
  programs   the device programs the library jits — batched fork (whole
             copies and the row cut of leaves over the piece size, by DMA
             on the HBM tiling and re-laid on the device off it),
             on-device slab pack
             (``TORCHSNAPSHOT_TPU_ENABLE_BATCHING=1``) — fed every bit
             pattern of every sub-32-bit float, put from the host: denormals
             and NaN payloads a TPU computation never produces itself.
  four chips (>= 4 devices) FSDP+TP train state on a (2, 2) mesh from ONE
             process, async save, restore into the transposed and the flat
             mesh, bytes drained per device.
  resume     a FRESH process (what a killed job is): zero targets on device,
             restore leg B's snapshot, bit-exact against the saved step by
             on-device digests, then train steps whose losses equal the
             uninterrupted run's.

The parent process never touches jax (a chip belongs to one process): it
spawns one child per phase, in turn. It fails — and prints no result —
unless jax finds the platform asked for (``tpu`` by default). ``--platform
cpu --tiny`` is an explicit dry run at toy widths that reports no time and
no rate. The last stdout line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import contextlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
# Inside the checkout (.benchtmp/ is git-ignored): /tmp may be RAM.
WORKDIR = os.path.join(REPO_ROOT, ".benchtmp", "chip_smoke")
TIME_LIMIT_S = 1150  # the driver allows 1200 s, compilation included

REAL_WIDTH = dict(vocab_size=32000, d_model=4096, n_heads=32, d_ff=16384, max_seq_len=512)
TINY_WIDTH = dict(vocab_size=512, d_model=128, n_heads=4, d_ff=512, max_seq_len=32)


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
    """The device as jax reports it; printed with the result."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_native_engine() -> str:
    """Build/load the native I/O engine BLOCKING and fail if it is absent,
    so every take of the run uses one write path (the storage plugin loads
    it non-blocking and writes buffered until g++ finishes). Returns the
    path of the library loaded."""
    from torchsnapshot_tpu import native

    if native.load_native() is None:
        raise SystemExit(
            f"native I/O engine unavailable (expected {native.lib_path()}; "
            "needs g++ and zlib, and TORCHSNAPSHOT_TPU_DISABLE_NATIVE_IO "
            "unset): refusing to measure the pure-Python write path"
        )
    return native.loaded_path()


def log(msg: str = "") -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    """A leg produced a wrong result, or a fallback hid the device."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Parent: spawns the phases, owns no device
# ---------------------------------------------------------------------------

def run_child(argv, env, timeout_s: float) -> int:
    """Run one phase in its own session so a timeout stops everything it
    started, not just the interpreter."""
    proc = subprocess.Popen(argv, env=env, start_new_session=True)
    try:
        return proc.wait(timeout=timeout_s)
    except BaseException:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        raise


def parent_main(args) -> None:
    if args.platform == "cpu" and not args.tiny:
        sys.exit("--platform cpu is a dry run and needs --tiny")
    t_start = time.monotonic()
    cache_dir = configure_compile_cache()
    env = dict(os.environ)
    if args.platform == "cpu":
        # Asked for by name, never reached by default. Four virtual devices
        # so the four-chip leg's code runs in the dry run too.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        )
    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(WORKDIR)
    results = {}
    try:
        for phase in ("train", "resume"):
            before = compile_cache_entries(cache_dir)
            result_path = os.path.join(WORKDIR, f"{phase}.json")
            argv = [
                sys.executable, os.path.abspath(__file__),
                "--phase", phase, "--result", result_path,
                "--platform", args.platform, "--seed", str(args.seed),
            ] + (["--tiny"] if args.tiny else [])
            remaining = TIME_LIMIT_S - (time.monotonic() - t_start)
            try:
                rc = run_child(argv, env, remaining)
            except subprocess.TimeoutExpired:
                sys.exit(f"chip_smoke: phase {phase} exceeded the time limit")
            if rc != 0:
                sys.exit(f"chip_smoke: phase {phase} FAILED (exit code {rc})")
            with open(result_path) as f:
                results[phase] = json.load(f)
            log(
                f"[{phase}] compile cache {cache_dir}: {before} -> "
                f"{compile_cache_entries(cache_dir)} entries"
            )
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    device = results["train"]["device"]
    check(results["resume"]["device"] == device, "phases saw different devices")
    # A finding the exit code does not carry: whether training could go on
    # while leg B's checkpoint drained.
    leg_b = results["train"]["leg_b"]
    log(
        f"chip_smoke: leg B steps_during_drain={leg_b['steps_during_drain']} "
        f"drain_step_error={leg_b['drain_step_error']}"
    )
    log(f"chip_smoke: all phases passed on {device}")
    # The last line is the contract: exactly these keys, nothing beside them.
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": str(device["platform"]),
                    "kind": str(device["kind"]),
                    "count": int(device["count"]),
                },
            }
        ),
        flush=True,
    )


# ---------------------------------------------------------------------------
# Child: preflight
# ---------------------------------------------------------------------------

def filesystem_type(path: str) -> str:
    best, fstype = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mount, kind = line.split()[:3]
                if path.startswith(mount.rstrip("/") + "/") and len(mount) > len(best):
                    best, fstype = mount, kind
    except OSError:
        pass
    return f"{fstype} (mount {best or '?'})"


def preflight(args) -> dict:
    """Fail unless jax found the platform asked for; print what a reader of
    any later number needs to know about this installation."""
    cache_dir = configure_compile_cache()
    import jax
    import jaxlib

    device = device_record()
    log(
        f"[preflight] platform={device['platform']} device_kind={device['kind']!r} "
        f"device_count={device['count']}"
    )
    if device["platform"] != args.platform:
        sys.exit(
            f"chip_smoke: jax.devices()[0].platform is {device['platform']!r}, "
            f"not {args.platform!r}: refusing to run"
            + (" (pass --platform cpu --tiny for a dry run)"
               if device["platform"] == "cpu" else "")
        )
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    log(f"[preflight] jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")

    from torchsnapshot_tpu.telemetry import fleet
    from torchsnapshot_tpu.utils import knobs

    log(f"[preflight] knobs set in env: {knobs.env_fingerprint() or '{} (all defaults)'}")
    gates = {
        "usable_cpus": len(os.sched_getaffinity(0)),
        "dedup_digests": knobs.is_dedup_digests_enabled(),
        "restore_overlap": knobs.is_restore_overlap_enabled(True, {device["platform"]}),
        "fleet_telemetry": f"{knobs.get_fleet_telemetry_mode()} -> {fleet.enabled()}",
        "d2h_lanes": knobs.get_d2h_lanes(),
        "hash_workers": knobs.get_hash_workers(),
        "staging_threads": knobs.get_staging_threads(),
        "async_capture": knobs.get_async_capture_mode(),
        "batching": knobs.is_batching_enabled(),
    }
    log(f"[preflight] resolved gates: {gates}")
    log(f"[preflight] native engine: {require_native_engine()}")
    log(
        f"[preflight] compile cache: {cache_dir} "
        f"({compile_cache_entries(cache_dir)} entries)"
    )
    os.makedirs(WORKDIR, exist_ok=True)
    log(f"[preflight] checkpoint dir: {WORKDIR} on {filesystem_type(WORKDIR)}")
    hbm = jax.devices()[0].memory_stats()
    log(f"[preflight] memory_stats: {hbm}")
    return {
        "device": device,
        "measured": device["platform"] != "cpu",
        "tiny": args.tiny,
        "width": TINY_WIDTH if args.tiny else REAL_WIDTH,
        "batch": 2,
        "seed": args.seed,
        "cache_dir": cache_dir,
    }


def seconds(ctx: dict, value: float) -> str:
    """A time is a device metric: a CPU dry run reports none."""
    return f"{value:.3f}s" if ctx["measured"] else "not measured (platform=cpu)"


def rate(ctx: dict, nbytes: int, value: float) -> str:
    if not ctx["measured"]:
        return "not measured (platform=cpu)"
    return f"{nbytes / 1e9 / max(value, 1e-9):.3f} GB/s"


# ---------------------------------------------------------------------------
# Trainer: the flagship transformer, adamw, a step that donates its state
# ---------------------------------------------------------------------------

class Trainer:
    def __init__(self, ctx: dict, depth: int, mesh=None) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchsnapshot_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
            loss_fn,
        )

        self.cfg = TransformerConfig(n_layers=depth, **ctx["width"])
        self.model = Transformer(self.cfg)
        self.tx = optax.adamw(1e-4)
        self.mesh = mesh
        self.seed = ctx["seed"]
        self.batch = ctx["batch"] * (mesh.shape["dp"] if mesh is not None else 1)
        self._data_sharding = (
            NamedSharding(mesh, P("dp")) if mesh is not None else None
        )
        model, tx = self.model, self.tx

        def train_step(state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(model, p, tokens)
            )(state["params"])
            updates, opt_state = tx.update(grads, state["opt_state"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            return {"params": params, "opt_state": opt_state}, loss

        # Donation is the point: on a backend where it frees buffers, "the
        # trainer may donate right after async_take returns" is only true if
        # the snapshot really detached itself from the state.
        self.train_step = jax.jit(train_step, donate_argnums=0)
        self._init_params = jax.jit(
            lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        )
        cfg = self.cfg
        self._make_batch = jax.jit(
            lambda key: jax.random.randint(
                key, (self.batch, cfg.max_seq_len), 0, cfg.vocab_size, jnp.int32
            )
        )

    def init_state(self):
        import jax

        from torchsnapshot_tpu.models.transformer import shard_params

        params = self._init_params(jax.random.PRNGKey(self.seed))
        if self.mesh is not None:
            params = shard_params(params, self.mesh, fsdp=True)
        # Eager, as __graft_entry__ does: zeros_like keeps each leaf's sharding.
        state = {"params": params, "opt_state": self.tx.init(params)}
        if self.mesh is None:
            # Committed to its device, as restored state is: the resumed
            # process then lowers the same step program and finds it in the
            # compile cache.
            state = jax.device_put(state, jax.devices()[0])
        return jax.block_until_ready(state)

    def abstract_state(self):
        """Shapes/dtypes of the state without allocating it."""
        import jax

        def build(key):
            params = self._init_params(key)
            return {"params": params, "opt_state": self.tx.init(params)}

        return jax.eval_shape(build, jax.random.PRNGKey(0))

    def batch_for(self, step: int):
        """Deterministic from (--seed, step): the resumed run replays it."""
        import jax

        tokens = self._make_batch(
            jax.random.fold_in(jax.random.PRNGKey(self.seed + 1), step)
        )
        if self._data_sharding is not None:
            tokens = jax.device_put(tokens, self._data_sharding)
        return tokens

    def step(self, state, step: int):
        """One donated step, timed to completion. Returns (state, loss, s)."""
        import jax

        tokens = self.batch_for(step)
        t0 = time.perf_counter()
        state, loss = self.train_step(state, tokens)
        loss = float(jax.block_until_ready(loss))
        return state, loss, time.perf_counter() - t0


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def tree_nbytes(tree) -> int:
    return sum(x.nbytes for x in _leaves(tree))


def param_count(state) -> int:
    return sum(x.size for x in _leaves(state["params"]))


def free_tree(tree) -> None:
    for leaf in _leaves(tree):
        if not leaf.is_deleted():
            leaf.delete()


_DIGEST_FN = None


def digest_tree(tree) -> dict:
    """{leaf path: [sum, position-weighted sum] mod 2**32 of the leaf's raw
    bits}, computed ON DEVICE. The reference must not come from
    ``np.asarray(leaf)``: jax caches that host copy on the array and the
    take's D2H would become a memcpy."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    global _DIGEST_FN
    if _DIGEST_FN is None:

        def digest(x):
            if x.dtype == jnp.bool_:
                words = x.astype(jnp.uint32)
            else:
                bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
                words = lax.bitcast_convert_type(x, bits).astype(jnp.uint32)
            # Linear index from per-axis iotas: elementwise, so a sharded
            # leaf digests where it lives and any layout gives one answer.
            index = jnp.zeros(x.shape, jnp.uint32)
            stride = 1
            for axis in reversed(range(x.ndim)):
                index = index + lax.broadcasted_iota(
                    jnp.uint32, x.shape, axis
                ) * jnp.uint32(stride)
                stride = (stride * x.shape[axis]) % (1 << 32)
            weight = index * jnp.uint32(2654435761) + jnp.uint32(1)
            return jnp.stack(
                [
                    jnp.sum(words, dtype=jnp.uint32),
                    jnp.sum(words * weight, dtype=jnp.uint32),
                ]
            )

        _DIGEST_FN = jax.jit(digest)
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    values = jax.device_get([_DIGEST_FN(leaf) for _, leaf in leaves])
    return {
        jax.tree_util.keystr(path): [int(v[0]), int(v[1])]
        for (path, _), v in zip(leaves, values)
    }


def check_digests(got: dict, want: dict, what: str) -> None:
    check(got.keys() == want.keys(), f"{what}: leaf sets differ")
    bad = [k for k in want if got[k] != want[k]]
    check(not bad, f"{what}: {len(bad)} of {len(want)} leaves differ, first {bad[:3]}")


def hbm_stats():
    import jax

    return jax.devices()[0].memory_stats()


def hbm_line(tag: str) -> str:
    s = hbm_stats()
    if not s:
        return f"[{tag}] memory_stats: not reported by this backend"
    return (
        f"[{tag}] HBM in use {s['bytes_in_use'] / 1e9:.3f} GB of "
        f"{s['bytes_limit'] / 1e9:.3f} GB "
        f"({100 * s['bytes_in_use'] / s['bytes_limit']:.1f}%), peak "
        f"{s['peak_bytes_in_use'] / 1e9:.3f} GB"
    )


def is_oom(e: BaseException) -> bool:
    return "RESOURCE_EXHAUSTED" in str(e) or "out of memory" in str(e).lower()


def take_record(path: str) -> dict:
    """What the snapshot itself recorded about how it was written: the
    persisted per-rank telemetry artifact."""
    from torchsnapshot_tpu.telemetry import aggregate

    _, artifacts, problems = aggregate.read_snapshot_artifacts(path)
    check(not problems and 0 in artifacts, f"{path}: telemetry artifact unreadable {problems}")
    return artifacts[0]


def check_no_hidden_fallback(tag: str, metrics: dict, big_objects: bool) -> None:
    """The fallbacks that would leave a green run proving nothing about the
    device are counted by the library; any of them firing fails the run.
    ``big_objects``: the take wrote objects above the direct-I/O threshold,
    which must then have gone through the native engine."""
    for name in ("storage.fs.native_fallback_bytes", "batcher.slabs_pack_degraded"):
        check(not metrics.get(name), f"[{tag}] fallback fired: {name}={metrics.get(name)}")
    if big_objects:
        check(
            metrics.get("storage.fs.native_write_bytes", 0) > 0,
            f"[{tag}] no object was written through the native engine",
        )


def zero_targets(abstract, shardings=None):
    """Zero arrays ON DEVICE with the live shardings, as a resuming job has
    before it restores."""
    import jax
    import jax.numpy as jnp

    make = jax.jit(
        lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract),
        out_shardings=shardings,
    )
    return jax.block_until_ready(make())


def app_state_for(box):
    from torchsnapshot_tpu.tricks.train_state import PyTreeStateful

    return {"train": PyTreeStateful(box)}


# ---------------------------------------------------------------------------
# Legs A and B: one trainer, one async save under a running, donating step
# ---------------------------------------------------------------------------

def run_save_leg(ctx: dict, tag: str, depth: int, filled: bool) -> dict:
    """``filled=False`` is leg A (the fork fits), ``filled=True`` leg B (HBM
    filled as a job fills it; the snapshot stays on disk for the resume)."""
    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.tricks.train_state import Box

    trainer = Trainer(ctx, depth)
    state = trainer.init_state()
    state_bytes = tree_nbytes(state)
    log(
        f"[{tag}] d_model={trainer.cfg.d_model} n_heads={trainer.cfg.n_heads} "
        f"d_ff={trainer.cfg.d_ff} vocab={trainer.cfg.vocab_size} "
        f"seq={trainer.cfg.max_seq_len} depth={depth} batch={trainer.batch}: "
        f"{param_count(state) / 1e9:.3f} B params, params+moments "
        f"{state_bytes / 1e9:.3f} GB"
    )
    losses = {}
    step = 0
    for _ in range(3):
        step += 1
        state, losses[step], dt = trainer.step(state, step)
        log(f"[{tag}] step {step}: loss {losses[step]:.6f} in {seconds(ctx, dt)}"
            + (" (compiles)" if step == 1 else ""))
    saved_step = step
    want = digest_tree(state)
    log(hbm_line(f"{tag} at async_take"))
    stats = hbm_stats()
    if filled and stats:
        share = stats["bytes_in_use"] / stats["bytes_limit"]
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        check(share >= 0.55, f"[{tag}] HBM only {100 * share:.1f}% in use")
        check(state_bytes > free, f"[{tag}] the fork would fit: {free} bytes free")

    box = Box(state)
    path = os.path.join(WORKDIR, f"{tag}_async")
    t0 = time.perf_counter()
    pending = Snapshot.async_take(path, app_state_for(box))
    stall_s = time.perf_counter() - t0
    t_drain = time.perf_counter()
    log(f"[{tag}] async_take returned (stall {seconds(ctx, stall_s)})")
    log(hbm_line(f"{tag} after async_take"))

    # Keep training, donating the state the take was given, while it drains.
    during, drain_step_error = 0, None
    while not (pending.done() and during >= 2 or during >= 8):
        try:
            state, loss, dt = trainer.step(state, step + 1)
        except Exception as e:  # noqa: BLE001 - only leg B's OOM is a finding
            if not (filled and is_oom(e)):
                raise
            # The fork took the HBM the step needs. A trainer has no retry
            # loop: it would have died of the checkpoint here. Report it and
            # step again only once the drain has given the HBM back.
            drain_step_error = str(e).splitlines()[0][:300]
            log(f"[{tag}] step {step + 1} during the drain FAILED: {drain_step_error}")
            break
        step += 1
        losses[step] = loss
        during += 1
        log(f"[{tag}] step {step} during drain: loss {loss:.6f} in {seconds(ctx, dt)}")
    snap = pending.wait()
    drain_s = time.perf_counter() - t_drain
    check(
        not any(x.is_deleted() for x in _leaves(state)),
        f"[{tag}] the failed step consumed its donated state",
    )
    record = take_record(path)
    metrics = record.get("metrics", {})
    captured = int(metrics.get("capture.host_captured_leaves", 0))
    captured_bytes = int(metrics.get("capture.host_captured_bytes", 0))
    forked = int(metrics.get("capture.forked_leaves", 0))
    log(
        f"[{tag}] drain wall {seconds(ctx, drain_s)} for {state_bytes / 1e9:.3f} GB "
        f"({rate(ctx, state_bytes, drain_s)}); steps_during_drain={during} "
        f"(error: {drain_step_error or 'none'}); "
        f"forked {forked} leaves / {(state_bytes - captured_bytes) / 1e9:.3f} GB, "
        f"host-captured {captured} leaves / {captured_bytes / 1e9:.3f} GB"
    )
    if ctx["measured"]:
        log(f"[{tag}] stall phases: {record.get('phases_s')}")
        log(f"[{tag}] drain stats: {record.get('drain_stats_s')}")
    log(
        f"[{tag}] write path: native {metrics.get('storage.fs.native_write_bytes', 0)} B, "
        f"fallback {metrics.get('storage.fs.native_fallback_bytes', 0)} B; "
        f"d2h {metrics.get('d2h.bytes', 0)} B"
    )
    check(forked + captured == len(want), f"[{tag}] {forked}+{captured} != {len(want)} leaves")
    check_no_hidden_fallback(tag, metrics, big_objects=not ctx["tiny"])
    if not filled:
        check(captured == 0, f"[{tag}] {captured} leaves host-captured though the fork fits")
    t0 = time.perf_counter()
    check(snap.verify() == {}, f"[{tag}] verify() reported problems")
    log(f"[{tag}] verify() == {{}} in {seconds(ctx, time.perf_counter() - t0)}")

    # The uninterrupted run: losses of the steps after the saved one.
    while step < saved_step + 2:
        step += 1
        state, losses[step], dt = trainer.step(state, step)
        log(f"[{tag}] step {step} after wait(): loss {losses[step]:.6f} in {seconds(ctx, dt)}")

    out = {
        "depth": depth,
        "state_bytes": state_bytes,
        "steps_during_drain": during,
        "drain_step_error": drain_step_error,
        "forked_leaves": forked,
        "host_captured_leaves": captured,
        "host_captured_bytes": captured_bytes,
    }
    if ctx["measured"]:
        out.update(stall_s=stall_s, drain_wall_s=drain_s)
    if filled:
        out.update(
            path=path,
            saved_step=saved_step,
            digests=want,
            losses_after_save={str(s): losses[s] for s in (saved_step + 1, saved_step + 2)},
        )
    else:
        # Sync take of the live state, then random access to one object,
        # compared on device against the leaf it came from.
        import jax
        import jax.numpy as jnp

        box = Box(state)
        sync_path = os.path.join(WORKDIR, f"{tag}_sync")
        t0 = time.perf_counter()
        sync_snap = Snapshot.take(sync_path, app_state_for(box))
        sync_s = time.perf_counter() - t0
        log(f"[{tag}] sync take {seconds(ctx, sync_s)} ({rate(ctx, state_bytes, sync_s)})")
        check_no_hidden_fallback(
            f"{tag} sync", take_record(sync_path).get("metrics", {}), not ctx["tiny"]
        )
        check(sync_snap.verify() == {}, f"[{tag}] sync verify() reported problems")
        obj = sync_snap.read_object("0/train/params/block_0/proj/kernel")
        live = state["params"]["block_0"]["proj"]["kernel"]
        same = jnp.array_equal(
            jax.lax.bitcast_convert_type(jax.device_put(obj), jnp.uint16),
            jax.lax.bitcast_convert_type(live, jnp.uint16),
        )
        check(bool(same), f"[{tag}] read_object differs from the live leaf")
        log(f"[{tag}] read_object(params/block_0/proj/kernel) bit-exact")
        if ctx["measured"]:
            out.update(sync_take_s=sync_s)
        shutil.rmtree(sync_path, ignore_errors=True)
        shutil.rmtree(path, ignore_errors=True)
    free_tree(state)
    log(hbm_line(f"{tag} freed"))
    return out


def depth_to_fill(ctx: dict, share: float) -> int:
    """Smallest depth whose params+moments fill ``share`` of HBM: the cut of
    leg B is read off the chip, not assumed."""
    stats = hbm_stats()
    if not stats:
        return 3  # CPU dry run: no HBM to fill
    w = ctx["width"]
    bytes_per_param = 6  # bf16 param + bf16 adam mu + nu
    layer = 4 * w["d_model"] ** 2 + 2 * w["d_model"] * w["d_ff"]
    base = (2 * w["vocab_size"] + w["max_seq_len"]) * w["d_model"]
    need = share * stats["bytes_limit"] / bytes_per_param
    return max(1, -(-int(need - base) // layer))


# ---------------------------------------------------------------------------
# Device programs the library jits beyond the batched fork
# ---------------------------------------------------------------------------

def all_patterns(dtype, shape):
    """Every bit pattern of ``dtype`` in order, tiled to ``shape``: both
    zeros, every denormal, the infinities and every NaN payload."""
    import numpy as np

    dt = np.dtype(dtype)
    bits = np.arange(1 << (8 * dt.itemsize), dtype=f"uint{8 * dt.itemsize}")
    return np.resize(bits, shape).view(dt)


def check_bits(tag: str, targets, host: dict) -> None:
    """Restored device leaves against the host arrays they were put from,
    byte for byte ON THE HOST (D2H moves bits unchanged; a device bitcast,
    like ``digest_tree``'s, would hide exactly what this leg looks for)."""
    import numpy as np

    for k, v in host.items():
        word = f"uint{8 * v.dtype.itemsize}"
        # np.asarray of a device array need not be C-contiguous.
        got = np.ascontiguousarray(np.asarray(targets[k]))
        check(got.dtype == v.dtype and got.shape == v.shape, f"[{tag}] {k}: {got.dtype}{got.shape}")
        got, want = got.view(word).ravel(), v.view(word).ravel()
        bad = np.flatnonzero(got != want)
        check(
            not bad.size,
            f"[{tag}] {k} ({v.dtype}) not bit-exact after restore: {bad.size} of {v.size} "
            f"elements, e.g. {[f'{int(want[i]):#x}->{int(got[i]):#x}' for i in bad[:4]]}",
        )


def run_programs_leg(ctx: dict) -> dict:
    """The programs the library jits beyond a trained state's fork, fed
    what such programs get wrong. A TPU computation flushes denormals and
    emits one NaN, so legs A/B cannot show a program that does the same to
    a leaf it was only meant to move; these leaves are put FROM THE HOST
    and hold every bit pattern of every sub-32-bit float."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.device_programs import copy_preserves_bits
    from torchsnapshot_tpu.utils import knobs

    small_floats = [
        ml_dtypes.bfloat16, np.float16, ml_dtypes.float8_e4m3fn, ml_dtypes.float8_e5m2,
    ]
    rng = np.random.default_rng(ctx["seed"])
    out = {}

    def put(host: dict) -> StateDict:
        state = StateDict(**{k: jax.device_put(v) for k, v in host.items()})
        jax.block_until_ready(dict(state))
        return state

    def round_trip(name: str, mode: str, state, host: dict, *overrides) -> dict:
        """One take under ``overrides``, restored into zero targets and
        compared with ``host`` byte for byte; returns the take's metrics."""
        path = os.path.join(WORKDIR, name)
        with contextlib.ExitStack() as stack:
            for override in overrides:
                stack.enter_context(override)
            if mode == "take":
                Snapshot.take(path, {"s": state})
            else:
                Snapshot.async_take(path, {"s": state}).wait()
        metrics = take_record(path).get("metrics", {})
        targets = StateDict(**{k: jnp.zeros(v.shape, v.dtype) for k, v in host.items()})
        Snapshot(path).restore({"s": targets})
        check_bits(name, targets, host)
        shutil.rmtree(path, ignore_errors=True)
        return metrics

    # (1) Slab pack: opt-in, so no default-knob leg compiles it. 300 small
    # leaves of random BITS, plus every pattern of each small float.
    dtypes = small_floats + [np.float32, np.int8, np.bool_]
    shapes = [(64, 33), (1000,), (7, 5, 3), (128, 128), (1,)]
    host = {}
    for i in range(300):
        dt, shape = np.dtype(dtypes[i % len(dtypes)]), shapes[(i // len(dtypes)) % len(shapes)]
        if dt == np.bool_:
            leaf = rng.integers(0, 2, size=shape) > 0
        else:
            leaf = rng.integers(0, 256, size=shape + (dt.itemsize,), dtype=np.uint8)
            leaf = leaf.view(dt).reshape(shape)
        host[f"leaf_{i:03d}"] = leaf
    for dt in small_floats:
        host[f"every_{np.dtype(dt).name}"] = all_patterns(dt, (1 << (8 * np.dtype(dt).itemsize),))
    state = put(host)
    never_forked = sum(not copy_preserves_bits(v.dtype) for v in host.values())
    for mode in ("take", "async_take"):
        metrics = round_trip(
            f"programs_{mode}", mode, state, host,
            knobs.override_batching_enabled(True),  # TORCHSNAPSHOT_TPU_ENABLE_BATCHING=1
        )
        packed = int(metrics.get("batcher.slabs_device_packed", 0))
        on_host = int(metrics.get("batcher.slabs_host_packed", 0))
        degraded = int(metrics.get("batcher.slabs_pack_degraded", 0))
        dtype_captured = int(metrics.get("capture.dtype_captured_leaves", 0))
        log(
            f"[programs] {mode} of {len(host)} small leaves: {packed} slabs packed on "
            f"device (float32/int8/bool), {on_host} on the host by plan (sub-32-bit "
            f"floats), {degraded} degraded; {dtype_captured} leaves host-captured "
            f"because a device copy would rewrite their dtype"
        )
        check(packed > 0, f"[programs] {mode}: no slab was packed on device")
        check(on_host > 0, f"[programs] {mode}: no small-float slab was packed on the host")
        check(not degraded, f"[programs] {mode}: a slab planned for the device took the host path")
        check(
            dtype_captured == (never_forked if mode == "async_take" else 0),
            f"[programs] {mode}: {dtype_captured} leaves captured by dtype, expected "
            f"{never_forked} in an async take",
        )
        out[f"slabs_device_packed_{mode}"] = packed
    free_tree(dict(state))
    log(
        f"[programs] slab pack: {len(host)} leaves restore bit-exact, every "
        "bfloat16/float16/float8 pattern (denormals, NaN payloads) included"
    )

    # (2) Big leaves, default knobs: the fork of an async take, which cuts
    # the leaves over the piece size into row-range pieces (``d2h.PIECE_BYTES``:
    # on the chip every bfloat16 pattern and the random float32 bits cross in
    # pieces), the same cut made a leaf at a time in a synchronous take's
    # stage, and the whole transfers of the dtypes that never fork (two hash
    # grains and more).
    from torchsnapshot_tpu import d2h
    from torchsnapshot_tpu.device_programs import piece_row_ranges

    nbytes = max(2 * knobs.get_hash_chunk_bytes(), 3 * d2h.PIECE_BYTES) if ctx["measured"] else 256 * 1024
    host = {
        f"big_{np.dtype(dt).name}": all_patterns(dt, (nbytes // np.dtype(dt).itemsize // 4096, 4096))
        for dt in small_floats
    }
    host["big_float32"] = rng.integers(
        0, 1 << 32, size=(2 * nbytes // 4 // 4096, 4096), dtype=np.uint32
    ).view(np.float32)  # random bits: denormals and NaN payloads, twice the size
    # Leaves off the HBM tiling, which the chip holds column first: the
    # fork re-lays them in pieces on the device, bfloat16 through the DMA
    # that takes its bits, the others by XLA alone.
    odd = {
        "odd_bfloat16_stack": (ml_dtypes.bfloat16, (16, 2688, 232)),
        "odd_bfloat16_wide": (ml_dtypes.bfloat16, (2688, 3608)),
        "odd_uint16_stack": (np.uint16, (16, 2688, 232)),
        "odd_int8_wide": (np.int8, (2688, 10304)),
    } if ctx["measured"] else {
        "odd_bfloat16_stack": (ml_dtypes.bfloat16, (16, 24, 29)),
        "odd_int8_wide": (np.int8, (24, 92)),
    }
    for k, (dt, shape) in odd.items():
        host[k] = all_patterns(dt, shape)
    host["odd_float32_wide"] = rng.integers(
        0, 1 << 32, size=(5376, 1100) if ctx["measured"] else (24, 11), dtype=np.uint32
    ).view(np.float32)
    state = put(host)
    total = sum(v.nbytes for v in host.values())
    cut = {
        k: piece_row_ranges(v.shape, v.dtype)
        for k, v in host.items() if copy_preserves_bits(v.dtype)
    }
    want_pieces = sum(len(r.ranges) for r in cut.values() if r)
    want_pieced = sum(host[k].nbytes for k, r in cut.items() if r)
    want_relaid = [k for k, r in cut.items() if r and r.relaid]
    if ctx["measured"]:
        check(
            bool(cut["big_bfloat16"]) and bool(cut["big_float32"]),
            "[programs] the big bfloat16 and float32 leaves are not over the piece size",
        )
        check(
            sorted(want_relaid) == sorted(k for k in host if k.startswith("odd_")),
            f"[programs] the leaves off the tiling are not all cut to be re-laid: {want_relaid}",
        )
    for mode in ("take", "async_take"):
        metrics = round_trip(f"programs_big_{mode}", mode, state, host)
        forked = int(metrics.get("capture.forked_leaves", 0))
        pieces = int(metrics.get("d2h.pieces", 0))
        pieced = int(metrics.get("d2h.pieced_bytes", 0))
        log(
            f"[programs] {mode} of {len(host)} big leaves / {total / 1e6:.0f} MB: {forked} forked, "
            f"{pieces} pieces / {pieced / 1e6:.0f} MB crossed in pieces"
        )
        check(
            forked == (len(cut) if mode == "async_take" else 0),
            f"[programs] {mode}: {forked} big leaves forked, expected {len(cut)} "
            "(float32, bfloat16) in an async take",
        )
        check(
            (pieces, pieced) == (want_pieces, want_pieced),
            f"[programs] {mode}: {pieces} pieces / {pieced} bytes, expected "
            f"{want_pieces} / {want_pieced} (the fork's cut, or the synchronous stage's)",
        )
        relaid = (
            int(metrics.get("capture.fork_relaid_leaves", 0)),
            int(metrics.get("capture.fork_relaid_bytes", 0)),
        )
        staged = (
            int(metrics.get("stage.sync_cut_leaves", 0)),
            int(metrics.get("stage.sync_cut_relaid_bytes", 0)),
            int(metrics.get("stage.sync_cut_refused", 0)),
        )
        on_host = int(metrics.get("stage.host_relaid_bytes", 0))
        log(
            f"[programs] {mode}: {relaid[0]} leaves / {relaid[1] / 1e6:.0f} MB re-laid by the fork, "
            f"{staged[0]} leaves cut in the stage ({staged[1] / 1e6:.0f} MB re-laid there, {staged[2]} refused), "
            f"{on_host / 1e6:.0f} MB re-laid on the host"
        )
        want = (len(want_relaid), sum(host[k].nbytes for k in want_relaid))
        check(
            relaid == (want if mode == "async_take" else (0, 0)),
            f"[programs] {mode}: the fork re-laid {relaid}, expected {want} in an async take "
            "and nothing in a synchronous one",
        )
        want_staged = (sum(1 for r in cut.values() if r), want[1], 0) if mode == "take" else (0, 0, 0)
        check(
            staged == want_staged,
            f"[programs] {mode}: the stage cut (leaves, re-laid bytes, refused) {staged}, expected {want_staged}",
        )
        check(on_host == 0, f"[programs] {mode}: {on_host} bytes were re-laid on the host")
        out[f"stage_cut_leaves_{mode}"] = staged[0]
        out[f"big_leaves_forked_{mode}"] = forked
        out[f"pieces_{mode}"] = pieces
        out[f"fork_relaid_leaves_{mode}"] = relaid[0]
        out[f"host_relaid_bytes_{mode}"] = on_host
    free_tree(dict(state))
    log(
        f"[programs] fork (whole and in pieces) + transfers: {len(host)} leaves / {total / 1e6:.0f} MB "
        "restore bit-exact, every small-float pattern included"
    )
    return out


# ---------------------------------------------------------------------------
# Four chips, one process
# ---------------------------------------------------------------------------

def run_four_chips_leg(ctx: dict) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.models.transformer import fit_spec
    from torchsnapshot_tpu.tricks.train_state import Box

    devices = np.array(jax.devices()[:4])
    mesh = Mesh(devices.reshape(2, 2), ("dp", "tp"))
    trainer = Trainer(ctx, depth=2, mesh=mesh)
    state = trainer.init_state()
    state_bytes = tree_nbytes(state)
    log(
        f"[four] (2,2) mesh, FSDP+TP, depth 2: {param_count(state) / 1e9:.3f} B params, "
        f"params+moments {state_bytes / 1e9:.3f} GB over {[d.id for d in devices]}"
    )
    step, losses = 0, {}
    for _ in range(2):
        step += 1
        state, losses[step], dt = trainer.step(state, step)
        log(f"[four] step {step}: loss {losses[step]:.6f} in {seconds(ctx, dt)}")
    want = digest_tree(state)
    box = Box(state)
    path = os.path.join(WORKDIR, "four_async")
    t0 = time.perf_counter()
    pending = Snapshot.async_take(path, app_state_for(box))
    stall_s = time.perf_counter() - t0
    t_drain = time.perf_counter()
    step += 1
    state, losses[step], dt = trainer.step(state, step)  # donates under the drain
    log(f"[four] step {step} during drain: loss {losses[step]:.6f} in {seconds(ctx, dt)}")
    snap = pending.wait()
    drain_s = time.perf_counter() - t_drain
    free_tree(state)
    metrics = take_record(path).get("metrics", {})
    check_no_hidden_fallback("four", metrics, big_objects=not ctx["tiny"])
    per_device = {
        d.id: int(metrics.get(f"d2h.device_bytes.{d.id}", 0)) for d in devices
    }
    log(
        f"[four] async save: stall {seconds(ctx, stall_s)}, drain {seconds(ctx, drain_s)} "
        f"({rate(ctx, state_bytes, drain_s)}); bytes drained per device: {per_device}; "
        f"host-captured leaves {metrics.get('capture.host_captured_leaves', 0)}"
    )
    check(all(v > 0 for v in per_device.values()), f"[four] a device drained nothing: {per_device}")
    check(snap.verify() == {}, "[four] verify() reported problems")

    abstract = trainer.abstract_state()

    def restore_into(name: str, new_mesh, spec_for_ndim) -> None:
        shardings = jax.tree.map(
            lambda a: NamedSharding(
                new_mesh, fit_spec(spec_for_ndim(len(a.shape)), a.shape, new_mesh)
            ),
            abstract,
        )
        tgt = Box(zero_targets(abstract, shardings))
        t0 = time.perf_counter()
        Snapshot(path).restore(app_state_for(tgt))
        jax.block_until_ready(tgt.value)
        dt = time.perf_counter() - t0
        check_digests(digest_tree(tgt.value), want, f"[four] restore into {name}")
        log(f"[four] restore into {name}: bit-exact in {seconds(ctx, dt)}")
        free_tree(tgt.value)

    restore_into(
        "transposed (tp, dp) mesh",
        Mesh(devices.reshape(2, 2).T, ("tp", "dp")),
        lambda ndim: P("tp", "dp") if ndim >= 2 else (P("dp") if ndim else P()),
    )
    restore_into(
        "flat (4,) mesh",
        Mesh(devices.reshape(4), ("all",)),
        lambda ndim: P("all") if ndim else P(),
    )
    shutil.rmtree(path, ignore_errors=True)
    return {"state_bytes": state_bytes, "bytes_drained_per_device": per_device}


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_train(args) -> dict:
    import jax

    ctx = preflight(args)
    out = {"device": ctx["device"]}
    out["leg_a"] = run_save_leg(ctx, "legA", depth=2, filled=False)
    out["leg_b"] = run_save_leg(ctx, "legB", depth=depth_to_fill(ctx, 0.55), filled=True)
    out["programs"] = run_programs_leg(ctx)
    if len(jax.devices()) >= 4:
        out["four_chips"] = run_four_chips_leg(ctx)
    else:
        log(f"[four] skipped: {len(jax.devices())} device(s) visible, the leg needs 4")
    return out


def phase_resume(args) -> dict:
    """What a killed job does: a fresh process, the state gone."""
    import jax

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu import snapshot as snapshot_mod
    from torchsnapshot_tpu.tricks.train_state import Box

    ctx = preflight(args)
    with open(os.path.join(WORKDIR, "train.json")) as f:
        saved = json.load(f)["leg_b"]
    misses = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: misses.append(name)
        if name == "/jax/compilation_cache/cache_misses" else None
    )
    trainer = Trainer(ctx, saved["depth"])
    box = Box(zero_targets(trainer.abstract_state()))
    log(hbm_line("resume, zero targets on device"))
    t0 = time.perf_counter()
    Snapshot(saved["path"]).restore(app_state_for(box))
    jax.block_until_ready(box.value)
    restore_s = time.perf_counter() - t0
    log(
        f"[resume] restored {saved['state_bytes'] / 1e9:.3f} GB to device in "
        f"{seconds(ctx, restore_s)} ({rate(ctx, saved['state_bytes'], restore_s)}); "
        f"stats {({k: snapshot_mod.LAST_RESTORE_STATS.get(k) for k in ('bytes_read', 'requests')})}"
    )
    consumed = Snapshot.last_telemetry.metrics.as_dict().get("restore.targets_consumed", 0)
    log(
        f"[resume] targets whose buffers were released to make room for "
        f"their restored leaf: {consumed} of {len(saved['digests'])}"
    )
    log(hbm_line("resume, restored"))
    check_digests(digest_tree(box.value), saved["digests"], "[resume] restore")
    log(f"[resume] bit-exact against saved step {saved['saved_step']}: {len(saved['digests'])} leaves")
    state = box.value
    misses_before_step = len(misses)
    for step_str, want_loss in sorted(saved["losses_after_save"].items()):
        state, loss, dt = trainer.step(state, int(step_str))
        log(f"[resume] step {step_str}: loss {loss:.6f} (uninterrupted run: {want_loss:.6f}) in {seconds(ctx, dt)}")
        check(loss == want_loss, f"[resume] step {step_str} loss {loss!r} != {want_loss!r}")
    step_misses = len(misses) - misses_before_step
    log(
        f"[resume] compile-cache misses in this process: {len(misses)} "
        f"({step_misses} while compiling the train step the first process compiled)"
    )
    check(step_misses == 0, "[resume] the train step was recompiled: the compile cache did not hit")
    free_tree(state)
    out = {
        "device": ctx["device"],
        "cache_misses": len(misses),
        "targets_consumed": consumed,
    }
    if ctx["measured"]:
        out["restore_s"] = restore_s
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--tiny", action="store_true", help="toy widths (dry run)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--phase", choices=("train", "resume"), help=argparse.SUPPRESS)
    parser.add_argument("--result", help=argparse.SUPPRESS)
    args = parser.parse_args()
    sys.path.insert(0, REPO_ROOT)
    if args.phase is None:
        parent_main(args)
        return
    try:
        result = {"train": phase_train, "resume": phase_resume}[args.phase](args)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    with open(args.result, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
