"""One run of one cell: ``python3 perfbench/run.py --workload <config>.<traffic>
--seed N --seconds S --trace 0|1``. See ``perfbench/README.md``.

Everything a cell is made of is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json``, and the architecture
``models/<model_type>.py`` by the configuration's own ``model_type``. The
last line of standard output is the result; every earlier line is commentary.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
sys.path.insert(0, REPO_ROOT)

from perfbench import cycles, readers, target as target_mod  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


class Refused(SystemExit):
    """The run cannot measure what it is asked to: no result line."""


class Lap:
    """Prints what each part of set-up took (measured runs only say so)."""

    def __init__(self, quiet: bool) -> None:
        self.t = time.perf_counter()
        self.quiet = quiet

    def __call__(self, what: str) -> None:
        now = time.perf_counter()
        if not self.quiet:
            log(f"[setup] {now - self.t:7.3f} s  {what}")
        self.t = now


# ---------------------------------------------------------------------------
# Finder: everything by name
# ---------------------------------------------------------------------------

def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(name: str, code: str):
    """The Python file ``code`` as a module of its own, by path."""
    module_spec = importlib.util.spec_from_file_location(name, code)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def find_cell(root: str, workload: str) -> dict:
    """The cell and all it names, from ``BENCHMARK.json`` under ``root``
    and the data files under ``root/perfbench``."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"perfbench: no workload {workload!r} in BENCHMARK.json ({sorted(cells)})")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    base = os.path.join(root, "perfbench")

    def wanted(entry: dict) -> bool:
        return workload in entry.get("workloads", [workload])

    def metric(entry: dict) -> dict:
        spec = load_json(base, "metrics", entry["name"] + ".json")
        code = os.path.join(base, "metrics", entry["name"] + ".py")
        if os.path.exists(code):
            spec["read"] = load_module("pb_metric_" + entry["name"], code).read
        return dict(entry, reader=spec["reader"], read=spec.get("read"))

    traffic = load_json(base, "traffic", cell["traffic"] + ".json")
    return {
        "cell": cell,
        "config": load_json(root, config_entry["file"]),
        "traffic": traffic,
        "end_to_end": [metric(m) for m in bench["end_to_end"] if wanted(m)],
        "per_layer": [metric(m) for m in bench["per_layer"] if wanted(m)],
        # Readings a mix wants on its [summary] line beside the totals: metric
        # files by name, whether or not BENCHMARK.json lists them for this cell.
        "summary": [metric({"name": n, "unit": ""}) for n in traffic.get("summary_metrics", [])],
    }


def find_architecture(root: str, model_type: str):
    """The module ``root/perfbench/models/<model_type>.py``: what the harness
    knows of a configuration's architecture (``perfbench/README.md``). It
    imports jax, so a run loads it once preflight has set the platform."""
    models = os.path.join(root, "perfbench", "models")
    code = os.path.join(models, model_type + ".py")
    if not os.path.exists(code):
        names = os.listdir(models) if os.path.isdir(models) else []
        there = sorted(n[:-3] for n in names if n.endswith(".py"))
        raise Refused(f"perfbench: no architecture {model_type!r} in perfbench/models ({there})")
    return load_module("pb_model_" + model_type, code)


def read_metrics(metrics: list, facts: dict) -> dict:
    out = {}
    for m in metrics:
        read = m["read"] or readers.READERS[m["reader"]["kind"]]
        value = read(facts, m["reader"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# Preflight
# ---------------------------------------------------------------------------

def configure_compile_cache() -> str:
    """As ``benchmarks/common.py`` does: ``JAX_COMPILATION_CACHE_DIR`` from
    outside wins; otherwise a fixed path inside the checkout (the path is
    part of the cache's key). Before jax is imported."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO_ROOT, ".jax_cache")
    # The library's own programs compile in well under jax's default floor.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def preflight(args, found: dict) -> dict:
    cache_dir = configure_compile_cache()
    if args.platform == "cpu":
        if not args.tiny:
            raise Refused("perfbench: --platform cpu is a dry run and needs --tiny")
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        )
    import jax
    import jaxlib

    chips = found["cell"]["chips"]
    devices = jax.devices()
    platform = devices[0].platform
    measured = platform != "cpu"
    if measured:
        log(f"[setup] {time.perf_counter() - T_PROCESS_START:7.3f} s  process start to jax.devices()")
    log(f"[preflight] platform={platform} device_kind={devices[0].device_kind!r} count={len(devices)}")
    if platform != args.platform:
        raise Refused(
            f"perfbench: jax.devices()[0].platform is {platform!r}, not {args.platform!r}: "
            "refusing to measure (--platform cpu --tiny is the dry run)"
        )
    if len(devices) < chips:
        raise Refused(f"perfbench: the cell needs {chips} chips, jax found {len(devices)}")
    from torchsnapshot_tpu import native
    from torchsnapshot_tpu.utils import knobs

    if native.load_native() is None:
        raise Refused(f"perfbench: native I/O engine unavailable (expected {native.lib_path()})")
    if measured:
        log(f"[setup] {time.perf_counter() - T_PROCESS_START:7.3f} s  ... to the native engine loaded")
    entries = sum(n.endswith("-cache") for n in os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"[preflight] jax={jax.__version__} jaxlib={jaxlib.__version__}; knobs set in env: "
        f"{knobs.env_fingerprint() or 'none (all defaults)'}; native engine {native.loaded_path()}")
    log(f"[preflight] compile cache {cache_dir}: {entries} entries")
    peaks = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if measured and kind not in peaks:
        raise Refused(f"perfbench: device kind {kind!r} is not in perfbench/peaks.json")
    return {
        "measured": measured,
        "devices": devices[:chips],
        "device": {"platform": platform, "kind": kind, "count": chips},
        "peaks": peaks.get(kind, {}),
        "compiles": count_compiles(jax),
    }


def count_compiles(jax) -> list:
    """Every backend compile of this process, as (time, seconds): none may
    fall inside the window."""
    seen = []

    def on_duration(name, duration, **kwargs):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append((time.perf_counter(), duration))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


# ---------------------------------------------------------------------------
# The general generator: rounds of save and restore beside a running step
# ---------------------------------------------------------------------------

class Run:
    def __init__(self, args, found: dict, ctx: dict) -> None:
        import jax

        from perfbench import reference, trainstate

        self.jax, self.trainstate, self.reference = jax, trainstate, reference
        self.args, self.ctx = args, ctx
        self.traffic = found["traffic"]
        cfg = dict(found["config"])
        arch = find_architecture(REPO_ROOT, cfg["model_type"])
        if args.tiny:  # toy widths, a dry run that reports no time
            cfg.update(arch.TINY, job=dict(cfg["job"], seq_len=32))
        self.cfg = cfg
        self.job = trainstate.Job(arch, cfg, ctx["devices"])
        wants_other = self.traffic.get("restore_layout") == "transposed"
        self.restore_job = (
            trainstate.Job(arch, cfg, ctx["devices"], transposed=True) if wants_other else self.job
        )
        self.saved_key = self.traffic["saved"]  # "params" or "state"
        self.annotate = (
            jax.profiler.TraceAnnotation if args.trace else (lambda name: contextlib.nullcontext())
        )
        self.records = []
        self.problems = []
        self.attempted = self.failed = 0
        self.step_index = 0
        self.target = None
        self.out_dir = os.path.join(
            target_mod.OUT_DIR, "runs", f"{args.workload}-seed{args.seed}-trace{args.trace}"
        )
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)

    # -- pieces of state ---------------------------------------------------

    def saved_of(self, state):
        return self.job.part(state, self.saved_key)

    def app_state(self, tree):
        from torchsnapshot_tpu.tricks.train_state import Box, PyTreeStateful

        box = Box(tree)
        return {"train": PyTreeStateful(box)}, box

    def warm_targets(self) -> None:
        """Load the program that makes a restore's zero targets, so the
        window's first restore does not."""
        self.trainstate.free_tree(self.jax.block_until_ready(self.restore_job.zero_targets(self.saved_key)))

    # -- operations --------------------------------------------------------

    def step(self, state):
        """One donated step, to completion. Returns (state, loss, seconds)."""
        tokens = self.batches[self.step_index % len(self.batches)]
        self.step_index += 1
        t0 = time.perf_counter()
        with self.annotate("pb.step"):
            state, loss = self.job.train_step(state, tokens)
            with self.annotate("pb.step.block"):
                loss = float(loss)
        return state, loss, time.perf_counter() - t0

    def step_alone(self, state, count: int):
        times = []
        for _ in range(count):
            state, _, dt = self.step(state)
            times.append(dt)
        return state, statistics.median(times)

    def save(self, state, path: str, period=None):
        """``async_take`` of the saved part of ``state``, the step running
        beside the drain (where the mix says so): ``period`` steps where the
        mix saves every so many steps, else until the snapshot is committed
        and the one before it retired. Either way the call returns only
        then; what it waited after the last step is ``commit_wait_s``.
        Returns the state as the steps left it, the record, and each step's
        time."""
        from torchsnapshot_tpu import Snapshot

        self.attempted += 1
        app_state, _ = self.app_state(self.saved_of(state))
        committed = threading.Event()
        rec = {"path": path, "bytes": self.trainstate.tree_nbytes(self.saved_of(state)), "error": None}

        def waiter(pending, t_call):
            try:
                with self.annotate("pb.wait"):
                    pending.wait()
                rec["wall_s"] = time.perf_counter() - t_call
                # Retention of one: once this snapshot is committed the one
                # before it goes, while the step runs on; the next take
                # starts when it is gone, so no delete runs beside a drain.
                # The snapshot the check will read back is spared.
                stale, self.last_saved = self.last_saved, path
                if stale and stale != self.keep:
                    with self.annotate("pb.retire"):
                        shutil.rmtree(stale, ignore_errors=True)
                    rec["retire_s"] = time.perf_counter() - t_call - rec["wall_s"]
            except Exception as e:  # noqa: BLE001 - reported as a failed take
                rec["error"] = repr(e)
            finally:
                committed.set()

        t_call = time.perf_counter()
        with self.annotate("pb.async_take"):
            pending = Snapshot.async_take(path, app_state)
        rec["stall_s"] = time.perf_counter() - t_call
        thread = threading.Thread(target=waiter, args=(pending, t_call), name="pb-waiter")
        thread.start()
        step_s = []
        while self.traffic["step_beside_save"] and (
            len(step_s) < period if period else not committed.is_set()
        ):
            state, _, dt = self.step(state)
            step_s.append(dt)
        t_last = time.perf_counter()
        thread.join()
        rec["commit_wait_s"] = time.perf_counter() - t_last
        meta = os.path.join(path, ".snapshot_metadata")
        if rec["error"] is None and not os.path.exists(meta):
            rec["error"] = "wait() returned but .snapshot_metadata is absent"
        if rec["error"] is not None:
            self.failed += 1
            self.problems.append(f"take {path}: {rec['error']}")
        else:
            artifact = os.path.join(path, ".telemetry", "rank_0.json")
            if os.path.exists(artifact):
                rec["telemetry"] = load_json(artifact)
                self.unseen_paths(rec, path)
        return state, rec, step_s

    def unseen_paths(self, rec: dict, path: str) -> None:
        """A leaf that took a slower path without saying so is a failed
        take, not a slower number: bytes the native engine handed back to
        the Python writer, and bytes captured through host RAM in a mix
        whose fork fits the device."""
        counters = rec["telemetry"].get("metrics", {})
        unseen = {"storage.fs.native_fallback_bytes": counters.get("storage.fs.native_fallback_bytes", 0)}
        if self.traffic.get("fork_fits"):
            unseen["capture.host_captured_bytes"] = counters.get("capture.host_captured_bytes", 0)
        rec["unseen_path_bytes"] = sum(unseen.values())
        if rec["unseen_path_bytes"]:
            self.failed += 1
            self.problems.append(f"take {path}: bytes on a fallback path: {unseen}")

    def restore(self, path: str, want: dict):
        """Zero targets on the restore layout, the files fsynced and advised
        out of the page cache, then ``Snapshot.restore`` timed until every
        leaf is ready on its target; then, outside that time, every leaf
        against ``want``. Returns (restored tree, record)."""
        from torchsnapshot_tpu import Snapshot
        from torchsnapshot_tpu import snapshot as snapshot_mod

        self.attempted += 1
        with self.annotate("pb.restore.targets"):
            targets = self.jax.block_until_ready(self.restore_job.zero_targets(self.saved_key))
            app_state, box = self.app_state(targets)
            target_mod.drop_page_cache(target_mod.snapshot_files(path))
        rec = {"bytes": self.trainstate.tree_nbytes(targets), "error": None}
        t0 = time.perf_counter()
        try:
            with self.annotate("pb.restore"):
                Snapshot(path).restore(app_state)
                self.jax.block_until_ready(box.value)
            rec["wall_s"] = time.perf_counter() - t0
            rec["stats"] = {
                k: v for k, v in snapshot_mod.LAST_RESTORE_STATS.items()
                if isinstance(v, (int, float))
            }
        except Exception as e:  # noqa: BLE001 - reported as a failed restore
            rec["error"] = repr(e)
            self.failed += 1
            self.problems.append(f"restore {path}: {rec['error']}")
        if rec["error"] is None:
            self.compare(box.value, want, rec, f"restore of {path}")
        return box.value, rec

    def compare(self, tree, want: dict, rec: dict, what: str) -> None:
        """Restored leaves against the reference's host copies, bit for bit."""
        with self.annotate("pb.reference"):
            bad = self.reference.differing_on_device(tree, want)
        rec["leaves_compared"] = len(want)
        rec["leaves_differing"] = len(bad)
        if bad:
            first = sorted(bad.items())[:3]
            self.problems.append(f"{what}: {len(bad)} of {len(want)} leaves differ, first {first}")

    # -- a round -----------------------------------------------------------

    def round(self, state, index: int, ops=None, warm=False):
        """One pass of the traffic's loop (or of ``ops``: a warm round may do
        less, and steps only until its save is committed). Where a round
        saves and restores, the reference fetches every leaf before the
        save and compares every leaf after the restore. A
        restore-only mix restores the set-up's snapshot. A save-only mix
        fetches nothing inside the window: its first snapshot is kept and
        read back once the window has closed (``check``)."""
        ops = ops or self.traffic["round"]
        rec = {"round": index, "in_window": False}
        t0 = time.perf_counter()
        if "save" in ops:
            path = os.path.join(self.target["dir"], f"snap_{index}")
            want = None
            if "restore" in ops:
                with self.annotate("pb.reference"):
                    want = self.reference.fetch(self.saved_of(state))
            elif index == 0:
                self.keep = path
            period = None if warm else self.traffic.get("period_steps")
            state, rec["save"], rec["step_s"] = self.save(state, path, period)
            rec["cycle"] = self.traffic["round"] == ["save"]
        else:
            path, want = self.snapshot_path, self.fixed_reference
        if "restore" in ops:
            if self.last_restored is not None:
                self.trainstate.free_tree(self.last_restored)
            self.last_restored, rec["restore"] = self.restore(path, want)
        rec["wall_s"] = time.perf_counter() - t0
        self.records.append(rec)
        return state, rec

    # -- the run -----------------------------------------------------------

    def setup(self):
        jax, traffic = self.jax, self.traffic
        saved_bytes = self.trainstate.tree_nbytes(self.saved_of(self.job.abstract))
        try:
            self.target = target_mod.resolve_target(
                2 * saved_bytes + (1 << 30), allow_ram=not self.ctx["measured"]
            )
        except OSError as e:
            raise Refused(f"perfbench: {e}") from e
        log(f"[target] checkpoints under {self.target['dir']} on {self.target['fstype']} "
            f"(mount {self.target['mount']}, class {self.target['class']}, "
            f"O_DIRECT {'accepted' if self.target['o_direct'] else 'refused'})")
        recorded = self.cfg["assumed"]["checkpoint_target_fstype"]
        if self.ctx["measured"] and self.target["fstype"] != recorded:
            raise Refused(
                f"perfbench: the checkpoint target is on {self.target['fstype']}, the configuration "
                f"records {recorded}: numbers on another medium are not comparable"
            )
        lap = Lap(quiet=not self.ctx["measured"])
        state = self.job.init_state(self.args.seed)
        self.batches = self.job.make_batches(self.args.seed, traffic["batches"])
        jax.block_until_ready((state, self.batches))
        lap("weights and batches from the seed")
        log(f"[setup] {self.cfg['name']}: {self.trainstate.tree_size(state['params']) / 1e9:.3f} B "
            f"parameters, state {self.trainstate.tree_nbytes(state) / 1e9:.3f} GB, saved part "
            f"{saved_bytes / 1e9:.3f} GB, batch {self.job.batch_shape}")
        for _ in range(traffic["warmup_steps"]):
            state, loss, _ = self.step(state)
        log(f"[setup] {traffic['warmup_steps']} warm-up steps, loss {loss:.6f}")
        lap("warm-up steps (the step program loads or compiles)")
        self.step_alone_s = None
        if traffic["step_alone_steps"]:
            state, self.step_alone_s = self.step_alone(state, traffic["step_alone_steps"])
            lap(f"step_alone_s over {traffic['step_alone_steps']} steps")
        self.last_saved = self.last_restored = self.fixed_reference = self.keep = None
        self.reference_s = 0.0
        if "save" in traffic["round"]:
            # Warm the cell's own programs: the batched fork, the take's
            # plan, the restore's placement.
            for i in range(traffic["warm_rounds"]):
                state, _ = self.round(state, -1 - i, ops=traffic.get("warm_ops"), warm=True)
            if "restore" in traffic["round"]:
                self.warm_targets()
            lap(f"{traffic['warm_rounds']} warm round(s)")
            if "restore" not in traffic["round"]:
                # What the window's first take has to give back: it saves
                # this very state, no step runs in between.
                t0 = time.perf_counter()
                self.fixed_reference = self.reference.fetch(self.saved_of(state))
                self.reference_s += time.perf_counter() - t0
            return state
        # A restarted job: the snapshot it finds was written by the code
        # under test in this run's set-up, never reused across runs.
        from torchsnapshot_tpu import Snapshot

        self.snapshot_path = os.path.join(self.target["dir"], "snap_setup")
        app_state, _ = self.app_state(self.saved_of(state))
        self.attempted += 1
        Snapshot.take(self.snapshot_path, app_state)
        lap("the set-up's synchronous take")
        if not os.path.exists(os.path.join(self.snapshot_path, ".snapshot_metadata")):
            self.failed += 1
            self.problems.append("set-up take: .snapshot_metadata is absent")
        t0 = time.perf_counter()
        self.fixed_reference = self.reference.fetch(self.saved_of(state))
        self.reference_s += time.perf_counter() - t0
        # The uninterrupted run: the losses of the steps after the saved one.
        self.step_index_at_save = self.step_index
        self.losses_uninterrupted = []
        for _ in range(traffic["loss_steps"]):
            state, loss, _ = self.step(state)
            self.losses_uninterrupted.append(loss)
        self.trainstate.free_tree(state)
        self.warm_targets()
        return None

    def window(self, state):
        """Whole rounds from the first operation's call to the first round
        boundary at or after ``--seconds``; with ``--trace 1`` the profiler
        runs around one of them."""
        args, jax = self.args, self.jax
        trace_dir = os.path.join(self.out_dir, "trace")
        index = 0
        t_start = time.perf_counter()
        while True:
            tracing = bool(args.trace) and index == self.traffic["trace_round"]
            if tracing:
                jax.profiler.start_trace(trace_dir)
            with self.annotate("pb.traced") if tracing else contextlib.nullcontext():
                state, rec = self.round(state, index)
            if tracing:
                jax.profiler.stop_trace()
            rec.update(in_window=True, traced=tracing)
            index += 1
            if time.perf_counter() - t_start >= args.seconds:
                return state, t_start, time.perf_counter()

    def check(self, state):
        """Once the window has closed, outside it. A save-only mix reads
        back the window's first snapshot, taken under the running step, and
        compares every leaf with what the reference fetched before it. A
        restore-only mix goes on training from the last restore. Returns
        the gap of the losses after it (restore-only mixes)."""
        if self.traffic["round"] == ["save"]:
            # The state has done its work, and the targets need its room.
            self.trainstate.free_tree(state)
            self.snapshot_path = self.keep
            self.round(None, len(self.records), ops=["restore"])
            return None
        if "save" in self.traffic["round"] or self.records[-1]["restore"]["error"] is not None:
            return None
        # Training goes on from the restored state as if never interrupted.
        state, self.step_index, losses = self.last_restored, self.step_index_at_save, []
        for _ in range(self.traffic["loss_steps"]):
            state, loss, _ = self.step(state)
            losses.append(loss)
        gap = max(abs(a - b) for a, b in zip(losses, self.losses_uninterrupted))
        if gap != 0.0:
            self.problems.append(f"losses after restore {losses} != uninterrupted {self.losses_uninterrupted}")
        return gap


def link_probe(jax, device, mib: int = 512) -> dict:
    """D2H and H2D rate of this machine: one array, one transfer each way
    (as ``bench.py``'s probe). Traced runs only, after the window."""
    import jax.numpy as jnp
    import numpy as np

    def pb_probe_array(key):
        return jax.random.normal(key, (mib, 512, 1024), jnp.bfloat16)

    a = jax.block_until_ready(jax.jit(pb_probe_array, device=device)(jax.random.PRNGKey(7)))
    t0 = time.perf_counter()
    h = np.asarray(a)
    d2h = h.nbytes / 1e9 / (time.perf_counter() - t0)
    a.delete()
    t0 = time.perf_counter()
    b = jax.block_until_ready(jax.device_put(h, device))
    h2d = h.nbytes / 1e9 / (time.perf_counter() - t0)
    b.delete()
    return {"d2h_gbps": d2h, "h2d_gbps": h2d}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    parser.add_argument("--tiny", action="store_true", help="toy widths: the CPU dry run")
    args = parser.parse_args(argv)
    found = find_cell(REPO_ROOT, args.workload)
    ctx = preflight(args, found)
    jax = sys.modules["jax"]
    run = Run(args, found, ctx)
    # A run that is told to stop still removes its checkpoints.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit("perfbench: terminated"))
    try:
        state = run.setup()
        setup_s = time.perf_counter() - T_PROCESS_START - run.reference_s
        compiles_before = len(ctx["compiles"])
        state, t_start, t_end = run.window(state)
        in_window = [c for c in ctx["compiles"][compiles_before:] if t_start <= c[0] <= t_end]
        stats = [d.memory_stats() or {} for d in ctx["devices"]]
        fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
        step_alone_after = None
        if run.step_alone_s:
            state, step_alone_after = run.step_alone(state, run.traffic["step_alone_after_steps"])
        link = link_probe(jax, ctx["devices"][0]) if args.trace and ctx["measured"] else {}
        loss_gap = run.check(state)
    finally:
        if run.target:
            target_mod.release(run.target)
    return report(args, found, ctx, run, dict(
        setup_s=setup_s, window_s=t_end - t_start, compiles_in_window=in_window, fullest=fullest,
        step_alone_after=step_alone_after, link=link, loss_gap=loss_gap,
    ))


def report(args, found, ctx, run, r) -> int:
    measured = ctx["measured"]
    rounds_path = os.path.join(run.out_dir, "rounds.jsonl")
    with open(rounds_path, "w") as f:
        for rec in run.records:
            f.write(json.dumps(dict(rec, step_alone_s=run.step_alone_s)) + "\n")
    records = cycles.load(rounds_path)
    window = cycles.in_window(records)
    summary = cycles.summarise(records, run.step_alone_s)
    log(f"[window] {len(window)} whole rounds in {r['window_s']:.3f} s (asked {args.seconds}); "
        f"records in {rounds_path}" if measured else f"[window] {len(window)} whole rounds (dry run: no times)")
    if r["compiles_in_window"]:
        run.problems.append(f"{len(r['compiles_in_window'])} programs compiled or loaded inside the window")
    if measured and run.step_alone_s:
        drift = r["step_alone_after"] / run.step_alone_s - 1.0
        log(f"[step] alone before the window {run.step_alone_s:.6f} s, after {r['step_alone_after']:.6f} s "
            f"(drift {100 * drift:+.2f} %)")
        if abs(drift) > 0.02:
            log("WARNING: step_alone_s drifted by more than 2 % across the window")
    if measured:
        log(f"[setup] setup_s {r['setup_s']:.3f} (process start to the window's first call, the "
            f"reference's {run.reference_s:.3f} s left out)")
    uncommitted = sum(1 for rec in records if rec.get("save") and rec["save"]["error"])
    unseen = sum(rec["save"].get("unseen_path_bytes", 0) for rec in records if rec.get("save"))
    differing = sum(
        rec["restore"].get("leaves_differing", 0) for rec in records if rec.get("restore")
    )
    compared = sum(rec["restore"].get("leaves_compared", 0) for rec in records if rec.get("restore"))
    log(f"compared: leaves_differing={differing} of {compared} (limit 0); "
        f"takes_uncommitted={uncommitted} (limit 0); fallback_path_bytes={unseen} (limit 0); "
        f"compiles_in_window={len(r['compiles_in_window'])} (limit 0); "
        f"operations_failed={run.failed} of {run.attempted} (limit 0)"
        + (f"; loss_gap_after_restore={r['loss_gap']} (limit 0)" if r["loss_gap"] is not None else ""))
    for problem in run.problems:
        log(f"PROBLEM: {problem}")
    correct = not run.problems and run.failed == 0 and compared > 0
    facts = {
        "rounds": window,
        "traced": next((rec for rec in window if rec.get("traced")), None),
        "setup": {"step_alone_s": run.step_alone_s, "setup_s": r["setup_s"]},
        "summary": summary,
        "device": r["fullest"],
        "link": r["link"],
        "peaks": ctx["peaks"],
        "trace": {},
    }
    if measured:
        beside = {name: m["value"] for name, m in read_metrics(found["summary"], facts).items()}
        log(f"[summary] {json.dumps(dict(summary, **beside))}")
    device = dict(ctx["device"], memory_peak_bytes=r["fullest"].get("peak_bytes_in_use", 0))
    result = {"correct": correct, "attempted": run.attempted, "failed": run.failed}
    if args.trace and measured:
        from perfbench import trace as trace_mod

        facts["trace"] = trace_mod.reduce_file(trace_mod.find_xplane(os.path.join(run.out_dir, "trace")))
        if not facts["trace"]:
            raise Refused("perfbench: the trace holds no device operation inside pb.traced")
        device.update(busy_s=facts["trace"]["busy_s"], window_s=facts["trace"]["window_s"])
        modules = {k: [v["count"], round(v["total_s"], 6)] for k, v in facts["trace"]["modules"].items()}
        log(f"[trace] one round of {facts['trace']['window_s']:.3f} s; modules as [count, seconds]: {json.dumps(modules)}")
        result["breakdown"] = {k: facts["trace"][k] for k in ("device_ops", "idle_gaps")}
    if not measured:
        # A dry run: counts and correctness, never a time or a rate.
        log("[dry run] platform=cpu: no metric is reported")
        result["metrics"] = {}
    else:
        result["metrics"] = read_metrics(found["per_layer"] if args.trace else found["end_to_end"], facts)
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
