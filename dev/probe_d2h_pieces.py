"""The probe of PR 39, run on the chip before any library code
(``chiprun -- python dev/probe_d2h_pieces.py``; ``PERF.md`` section 6 has what
it read). Three questions:

(i)   does a Pallas HBM->HBM DMA of a dim-0 row range return every bit of
      bfloat16 (all 65,536 patterns), float32 (sampled patterns, every
      exponent with NaN payloads and denormals), int8 and int16, inside one
      jitted program beside ``jnp.copy`` of small leaves;
(ii)  what the fork of ``pythia-6.9b-d6``'s 3.24 GB of params costs on the
      device as whole copies and as pieces;
(iii) how fast the forked bytes reach one host buffer a leaf, and what the
      job's next 12 donated steps lose beside it, for pieces of 8 / 16 / 32 /
      64 MiB under windows of 64 / 128 / 256 MiB with four resolving lanes,
      against whole leaves under 512 MiB (the parent), each beside a
      storage write of every gathered leaf.

Writes ``chiprun_out/probe_d2h_pieces.json`` and prints it.

``--relay`` is the probe of PR 46 (``probe_relay``): (iv) which program
re-lays a leaf the DMA cut refuses, on the device, and keeps every bit.
Writes ``chiprun_out/probe_d2h_relay.json``.
"""

import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
sys.path.insert(0, REPO_ROOT)

from perfbench import run as pbrun  # noqa: E402

pbrun.configure_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

MIB = 1 << 20
INTERPRET = jax.devices()[0].platform != "tpu"


def cut_rows(x, ranges):
    """The library's own row cut (written from this probe's)."""
    from torchsnapshot_tpu.device_programs import _cut_rows

    return _cut_rows(x, tuple(ranges), INTERPRET)


def row_ranges(shape, itemsize, piece_bytes):
    """Even dim-0 ranges of at most ``piece_bytes``; a 2-D leaf is cut at
    multiples of 8 rows (the HBM tile Mosaic's DMA wants)."""
    unit = 8 if len(shape) == 2 else 1
    if len(shape) < 2 or shape[0] % unit or shape[-1] % 128 or (len(shape) > 2 and shape[-2] % 8):
        return None
    units = shape[0] // unit
    unit_bytes = unit * itemsize * int(np.prod(shape[1:]))
    per = max(1, piece_bytes // unit_bytes)
    n = -(-units // per)
    if n < 2:
        return None
    base, extra = divmod(units, n)
    out, r0 = [], 0
    for i in range(n):
        rows = (base + (1 if i < extra else 0)) * unit
        out.append((r0, r0 + rows))
        r0 += rows
    return out


def fork_program(leaves, piece_bytes):
    """One jitted lambda: pieces for the leaves over the piece size, copies
    for the rest. Returns (fn, ranges per leaf or None)."""
    plan = [
        row_ranges(a.shape, a.dtype.itemsize, piece_bytes) if piece_bytes and a.nbytes > piece_bytes else None
        for a in leaves
    ]
    fn = jax.jit(
        lambda xs: [jnp.copy(x) if r is None else list(cut_rows(x, r)) for x, r in zip(xs, plan)]
    )
    return fn, plan


# ---------------------------------------------------------------- (i) exact

def probe_exact():
    out = {}
    rng = np.random.default_rng(39)
    bf16 = np.arange(65536, dtype=np.uint16)
    f32 = rng.integers(0, 1 << 32, size=1 << 20, dtype=np.uint64).astype(np.uint32)
    # Every exponent with a zero, a small and a full mantissa, both signs:
    # the denormals and the NaN payloads among them.
    special = np.array(
        [(s << 31) | (e << 23) | m for s in (0, 1) for e in range(256) for m in (0, 1, 0x7FFFFF, 0x400001)],
        dtype=np.uint32,
    )
    f32[: special.size] = special
    cases = {
        # rows cut at multiples of 8 that are not multiples of 16 or 32
        "bfloat16_2d": (np.tile(bf16, 9).reshape(-1, 512).view(jnp.bfloat16), [(0, 8), (8, 408), (408, 1152)]),
        "bfloat16_3d": (np.tile(bf16, 4).reshape(8, 128, 256).view(jnp.bfloat16), [(0, 1), (1, 4), (4, 8)]),
        "float32_2d": (f32.reshape(-1, 1024).view(np.float32), [(0, 8), (8, 520), (520, 1024)]),
        "int8_2d": (np.tile(np.arange(256, dtype=np.uint8), 4096).reshape(-1, 1024).view(np.int8), [(0, 8), (8, 520), (520, 1024)]),
        "int16_2d": (bf16.reshape(-1, 256).view(np.int16), [(0, 8), (8, 200), (200, 256)]),
    }
    small = jnp.asarray(np.arange(65536, dtype=np.uint16).view(jnp.bfloat16))
    for name, (host, ranges) in cases.items():
        x = jax.device_put(host)
        fn = jax.jit(lambda xs: [list(cut_rows(xs[0], ranges)), jnp.copy(xs[1])])
        pieces, small_copy = fn([x, small])
        got = np.concatenate([np.asarray(p) for p in pieces])
        view = np.uint8
        differing = int((got.view(view) != host.view(view)).sum())
        small_diff = int((np.asarray(small_copy).view(np.uint16) != bf16).sum())
        out[name] = {"shape": list(host.shape), "ranges": ranges, "differing_bytes": differing,
                     "small_copy_differing": small_diff}
        print(f"[exact] {name}: {out[name]}", flush=True)
    return out


# ------------------------------------------------------------- (ii), (iii)

def huge_empty(shape, dtype):
    import mmap

    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    huge = 2 * MIB
    m = mmap.mmap(-1, nbytes + huge)
    m.madvise(mmap.MADV_HUGEPAGE, 0, nbytes + huge)
    base = np.frombuffer(m, dtype=np.uint8)
    off = (-base.ctypes.data) % huge
    return base[off : off + nbytes].view(dtype).reshape(shape)


class Window:
    def __init__(self, limit):
        self.limit, self.ahead, self.hwm = limit, 0, 0
        self.cond = threading.Condition()

    def take(self, n):
        with self.cond:
            while self.ahead and self.ahead + n > self.limit:
                self.cond.wait()
            self.ahead += n
            self.hwm = max(self.hwm, self.ahead)

    def give(self, n):
        with self.cond:
            self.ahead -= n
            self.cond.notify_all()


def drain(forked, plan, window_bytes, out_dir, write, gather="fresh", warm=None, lane_s=None):
    """Move every forked leaf to one host buffer a leaf through four lanes
    under the window; each gathered leaf is written to a file. Returns the
    seconds from the first hint to the last leaf gathered, and to the last
    write done."""
    window = Window(window_bytes)
    lanes = ThreadPoolExecutor(4, thread_name_prefix="probe-d2h")
    writers = ThreadPoolExecutor(2, thread_name_prefix="probe-io")
    futures, writes = [], []
    lock = threading.Lock()

    from torchsnapshot_tpu import native
    from torchsnapshot_tpu.utils import knobs

    lib = native.load_native()

    def write_leaf(i, buf):
        # As the fs plugin writes a big object: the native engine, O_DIRECT,
        # the crc riding the write loop.
        native.write_file_digest(
            lib, os.path.join(out_dir, f"leaf_{i}"), memoryview(buf.reshape(-1).view(np.uint8)),
            direct=True, chunk_bytes=knobs.get_direct_io_chunk_bytes(),
        )

    def resolve(i, arr, buf, r0, r1, left):
        ta = time.perf_counter()
        host = np.asarray(arr)
        tb = time.perf_counter()
        if buf is None:
            done = host
        else:
            # As bytes: a plain memcpy with the GIL released, whatever the dtype.
            buf[r0:r1].reshape(-1).view(np.uint8)[:] = host.reshape(-1).view(np.uint8)
            done = buf
        if lane_s is not None:
            tc = time.perf_counter()
            with lock:
                lane_s["asarray"] += tb - ta
                lane_s["copy"] += tc - tb
        n = arr.nbytes
        arr.delete()
        window.give(n)
        with lock:
            left[0] -= 1
            last = left[0] == 0
        if last and write:
            writes.append(writers.submit(write_leaf, i, done))

    t0 = time.perf_counter()
    # Big first, as the scheduler lowers them.
    order = sorted(range(len(forked)), key=lambda i: -sum(p.nbytes for p in (forked[i] if plan[i] else [forked[i]])))
    for i in order:
        if plan[i] is None:
            arr = forked[i]
            window.take(arr.nbytes)
            arr.copy_to_host_async()
            futures.append(lanes.submit(resolve, i, arr, None, 0, 0, [1]))
            continue
        pieces = forked[i]
        shape = (plan[i][-1][1],) + pieces[0].shape[1:]
        if gather == "none":  # resolve and drop: P5 as PR 33 ran it
            buf = None
        elif gather == "warm":  # pages touched before: a recycled buffer
            buf = warm[i]
        elif gather == "huge":  # fresh pages, 2 MiB each where the kernel gives them
            buf = huge_empty(shape, pieces[0].dtype)
        else:
            buf = np.empty(shape, dtype=pieces[0].dtype)
        left = [len(pieces)]
        for arr, (r0, r1) in zip(pieces, plan[i]):
            window.take(arr.nbytes)
            arr.copy_to_host_async()
            futures.append(lanes.submit(resolve, i, arr, buf, r0, r1, left))
    for f in futures:
        f.result()
    t_gathered = time.perf_counter() - t0
    for f in writes:
        f.result()
    t_written = time.perf_counter() - t0
    lanes.shutdown()
    writers.shutdown()
    return t_gathered, t_written, window.hwm


def job_and_step(seed):
    """``pythia-6.9b-d6``'s train state from the seed, its donated step with
    the loss read (one call a step), the bytes of its params, a directory for
    the writes."""
    from perfbench import target as target_mod, trainstate

    found = pbrun.find_cell(REPO_ROOT, "pythia-6.9b-d6.save_weights")
    cfg = dict(found["config"])
    arch = pbrun.find_architecture(REPO_ROOT, cfg["model_type"])
    if INTERPRET:
        cfg.update(arch.TINY, job=dict(cfg["job"], seq_len=32))
    job = trainstate.Job(arch, cfg, jax.devices()[:1])
    state = job.init_state(seed)
    batches = job.make_batches(seed, 8)
    jax.block_until_ready((state, batches))
    out_dir = os.path.join(target_mod.OUT_DIR, "probe_d2h_pieces")
    os.makedirs(out_dir, exist_ok=True)
    index = [0]

    def step(state):
        tokens = batches[index[0] % len(batches)]
        index[0] += 1
        t0 = time.perf_counter()
        state, loss = job.train_step(state, tokens)
        float(loss)
        return state, time.perf_counter() - t0

    return state, step, trainstate.tree_nbytes(state["params"]), out_dir


def probe_pipeline(seed, reps, write):
    state, step, nbytes, out_dir = job_and_step(seed)
    for _ in range(3):
        state, _ = step(state)
    times = []
    for _ in range(25):
        state, dt = step(state)
        times.append(dt)
    step_alone_s = float(np.median(times))
    print(f"[pipeline] params {nbytes / 1e9:.3f} GB, step_alone_s {step_alone_s:.4f}", flush=True)

    scale = 1 if not INTERPRET else 1 / 4096
    configs = [("whole", 0, 512)] + [(f"p{p}", p, w) for p in (8, 16, 32, 64) for w in (64, 128, 256)]
    results = {"step_alone_s": step_alone_s, "params_bytes": nbytes, "fork": {}, "runs": []}
    programs = {}
    for name, piece_mib, window_mib in configs:
        if piece_mib not in programs:
            leaves = jax.tree_util.tree_leaves(state["params"])
            t0 = time.perf_counter()
            fn, plan = fork_program(leaves, int(piece_mib * MIB * scale))
            warm = fn(leaves)
            jax.block_until_ready(warm)
            compile_s = time.perf_counter() - t0
            del warm
            # (ii): the fork alone, five times to completion.
            fork_s = []
            for _ in range(5):
                t0 = time.perf_counter()
                forked = fn(leaves)
                jax.block_until_ready(forked)
                fork_s.append(time.perf_counter() - t0)
                del forked
            programs[piece_mib] = (fn, plan)
            results["fork"][str(piece_mib)] = {
                "first_call_s": compile_s,
                "fork_to_ready_s": sorted(fork_s),
                "outputs": sum(len(r) if r else 1 for r in plan),
                "pieced_leaves": sum(1 for r in plan if r),
            }
            print(f"[fork] piece {piece_mib} MiB: {results['fork'][str(piece_mib)]}", flush=True)
    for rep in range(reps):
        for name, piece_mib, window_mib in configs:
            fn, plan = programs[piece_mib]
            leaves = jax.tree_util.tree_leaves(state["params"])
            t_call = time.perf_counter()
            forked = fn(leaves)
            stall = time.perf_counter() - t_call
            box = {}

            def run_drain():
                box["r"] = drain(forked, plan, int(window_mib * MIB * scale) or 1, out_dir, write)

            th = threading.Thread(target=run_drain)
            th.start()
            del forked
            steps = []
            for _ in range(12):
                state, dt = step(state)
                steps.append(dt)
            th.join()
            t_gathered, t_written, hwm = box["r"]
            rec = {
                "config": name, "piece_mib": piece_mib, "window_mib": window_mib, "rep": rep,
                "fork_call_s": stall,
                "gather_gbps": nbytes / t_gathered / 1e9, "gathered_s": t_gathered, "written_s": t_written,
                "added_12_steps_s": sum(steps) - 12 * step_alone_s,
                "first_steps_s": [round(s, 4) for s in steps[:4]],
                "hwm_mib": hwm / MIB,
            }
            results["runs"].append(rec)
            print(f"[run] {rec}", flush=True)
            # Settle: a few steps alone so one run's tail is not the next one's start.
            for _ in range(4):
                state, _ = step(state)
    return results


def probe_decompose(seed):
    """Where P5's rate goes: the same pieces resolved and dropped, gathered
    into fresh pages, gathered into pages touched before, each with and
    without the write beside, the donated step always beside."""
    state, step, nbytes, out_dir = job_and_step(seed)
    times = []
    for _ in range(15):
        state, dt = step(state)
        times.append(dt)
    step_alone_s = float(np.median(times[3:]))
    scale = 1 if not INTERPRET else 1 / 4096
    leaves = jax.tree_util.tree_leaves(state["params"])
    t0 = time.perf_counter()
    warm = [np.empty(a.shape, a.dtype) for a in leaves]
    for w in warm:
        w.reshape(-1).view(np.uint8).fill(0)
    touch_s = time.perf_counter() - t0
    print(f"[decompose] step_alone_s {step_alone_s:.4f}; first touch of {nbytes / 1e9:.3f} GB on one thread "
          f"{touch_s:.2f} s = {nbytes / touch_s / 1e9:.2f} GB/s", flush=True)
    t0 = time.perf_counter()
    for w in warm:
        w.reshape(-1).view(np.uint8).fill(1)
    again_s = time.perf_counter() - t0
    print(f"[decompose] second pass {again_s:.2f} s = {nbytes / again_s / 1e9:.2f} GB/s", flush=True)
    results = {"step_alone_s": step_alone_s, "first_touch_gbps": nbytes / touch_s / 1e9,
               "second_pass_gbps": nbytes / again_s / 1e9, "runs": []}
    variants = [(0, 512, "none", False), (0, 512, "none", True)] + [
        (piece, window, gather, write)
        for write in (False, True)
        for piece, window in ((16, 128), (32, 128), (32, 256))
        for gather in ("fresh", "warm", "huge")
    ]
    programs = {}
    for piece, window, gather, write in variants:
        if piece not in programs:
            programs[piece] = fork_program(leaves, int(piece * MIB * scale))
            jax.block_until_ready(programs[piece][0](jax.tree_util.tree_leaves(state["params"])))
        fn, plan = programs[piece]
        forked = fn(jax.tree_util.tree_leaves(state["params"]))
        box, lane_s = {}, {"asarray": 0.0, "copy": 0.0}

        def run_drain():
            box["r"] = drain(forked, plan, int(window * MIB * scale) or 1, out_dir, write,
                             gather=gather, warm=warm, lane_s=lane_s)

        th = threading.Thread(target=run_drain)
        th.start()
        del forked
        steps = []
        for _ in range(12):
            state, dt = step(state)
            steps.append(dt)
        th.join()
        t_gathered, t_written, hwm = box["r"]
        rec = {"piece_mib": piece, "window_mib": window, "gather": gather, "write": write,
               "gather_gbps": nbytes / t_gathered / 1e9, "gathered_s": t_gathered, "written_s": t_written,
               "added_12_steps_s": sum(steps) - 12 * step_alone_s,
               "lane_asarray_s": lane_s["asarray"], "lane_copy_s": lane_s["copy"]}
        results["runs"].append(rec)
        print(f"[decompose] {rec}", flush=True)
        for name in os.listdir(out_dir):
            os.unlink(os.path.join(out_dir, name))
        for _ in range(3):
            state, _ = step(state)
    return results


# ------------------------------------------- (iv) the re-laying cut (PR 46)

RELAY_SHAPES = [(16, 2688, 1856), (2688, 10304), (16, 1856, 2688), (2688, 4096)]  # PR 44's four
RELAY_TINY = [(16, 256, 116), (256, 704), (16, 116, 256), (256, 512)]


def relay_candidates(x, ranges):
    """The two programs that were tried first and do not keep every bit of
    bfloat16 on the v5e: XLA's own bitcast before integer slices, and slices
    of the float under an explicit row-major layout."""
    from jax.experimental.layout import Format, Layout

    row = int(np.prod(x.shape[1:]))
    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")

    def xla_bitcast(x):
        u = jax.lax.bitcast_convert_type(x, bits)
        return [u[a:b].reshape((b - a) * row // 128, 128) for a, b in ranges]

    def layout_float(x):
        return [x[a:b] for a, b in ranges]

    row_major = Format(Layout(major_to_minor=tuple(range(x.ndim))), x.sharding)
    return {
        "xla_bitcast": jax.jit(xla_bitcast),
        "layout_float": jax.jit(layout_float, out_shardings=[row_major] * len(ranges)),
    }


def _timed_ms(fn, x, reps=5):
    jax.block_until_ready(fn(x))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append((time.perf_counter() - t0) * 1e3)
    return sorted(times)[len(times) // 2]


def probe_relay():
    """(iv), PR 46: a leaf the DMA cut refuses for its shape, written by the
    fork as row-range pieces re-laid on the device. PR 44's four shapes in
    bfloat16 (every pattern, put from the host) and the odd ones in float32,
    int8 and uint16, through the library's own fork: the leaf's device
    layout and how its whole copy reaches the host; the cut's mover, the
    pieces' layout, whether their host copies are C-contiguous and are the
    C-order bytes of the rows; the fork's time to ready as pieces and whole;
    how fast the pieces cross on one thread. Beside it, for bfloat16, the
    two programs that do not keep every bit."""
    from torchsnapshot_tpu import d2h, device_programs

    if INTERPRET:
        d2h.PIECE_BYTES = 64 * 1024
    shapes = RELAY_SHAPES if not INTERPRET else RELAY_TINY
    samples = [(s, jnp.bfloat16) for s in shapes]
    samples += [(shapes[1], np.float32), (shapes[1], np.int8), (shapes[0], np.uint16)]
    rng = np.random.default_rng(46)
    out = {}
    for shape, dtype in samples:
        dt = np.dtype(dtype)
        word = f"uint{8 * dt.itemsize}"
        n = int(np.prod(shape))
        if dt.itemsize < 4:
            words = np.resize(np.arange(1 << (8 * dt.itemsize), dtype=word), n)
        else:
            words = rng.integers(0, 1 << 32, size=n, dtype=np.uint64).astype(np.uint32)
            special = np.array(
                [(s << 31) | (e << 23) | m for s in (0, 1) for e in range(256) for m in (0, 1, 0x7FFFFF, 0x400001)],
                dtype=np.uint32,
            )
            words[: special.size] = special
        host = words.view(dt).reshape(shape)
        x = jax.device_put(host)
        back = np.asarray(jnp.copy(x))
        cut = device_programs.leaf_cut(x)
        rec = {
            "device_major_to_minor": list(x.format.layout.major_to_minor),
            "whole_host_c_contiguous": bool(back.flags.c_contiguous),
            "whole_host_strides": list(back.strides),
            "mover": None if cut is None else ("relaid" if cut.relaid else "dma"),
            "dma_takes_bits_in_order": None if cut is None or cut.order is None else list(cut.order),
            "whole_ms": _timed_ms(device_programs.batch_copy_fn((x.sharding,), (None,)), [x]),
        }
        programs = {"library": lambda xs, cut=cut: device_programs.batch_copy_fn((xs.sharding,), (cut,))([xs])[0]}
        if dt.name == "bfloat16":
            programs.update(relay_candidates(x, cut.ranges))
        rec["pieces"] = len(cut.ranges)
        for name, fn in programs.items():
            pieces = fn(x)
            jax.block_until_ready(pieces)
            t0 = time.perf_counter()
            for p in pieces:
                p.copy_to_host_async()
            hosts = [np.asarray(p) for p in pieces]
            d2h_s = time.perf_counter() - t0
            got = np.concatenate([h.reshape(-1).view(np.uint8) for h in hosts]).view(word)
            bad = np.flatnonzero(got != words)
            rec[name] = {
                "piece_shape": list(pieces[0].shape),
                "piece_dtype": str(pieces[0].dtype),
                "piece_major_to_minor": sorted({tuple(p.format.layout.major_to_minor) for p in pieces}),
                "host_c_contiguous": all(h.flags.c_contiguous for h in hosts),
                "differing_elements": int(bad.size),
                "differing_patterns": len({int(words[i]) for i in bad}),
                "d2h_gbps_one_thread": host.nbytes / d2h_s / 1e9,
                "fork_ms": _timed_ms(fn, x),
            }
            del pieces, hosts
            print(f"[relay] {shape} {dt.name} {name}: {rec[name]}", flush=True)
        out[f"{dt.name}{list(shape)}"] = rec
        print(f"[relay] {shape} {dt.name}: {({k: v for k, v in rec.items() if not isinstance(v, dict)})}", flush=True)
        x.delete()
    bad = {k: v["library"]["differing_elements"] for k, v in out.items() if v["library"]["differing_elements"]}
    print("RELAY EXACT:", "every bit" if not bad else f"DIFFERS {bad}")
    out["exact"] = not bad
    return out


def main():
    if "--relay" in sys.argv:
        out = {"relay": probe_relay()}
        os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO_ROOT, "chiprun_out", "probe_d2h_relay.json"), "w") as f:
            json.dump(out, f, indent=1)
        return 0 if out["relay"]["exact"] else 1
    if "--decompose" in sys.argv:
        out = {"decompose": probe_decompose(3900000040)}
        os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO_ROOT, "chiprun_out", "probe_d2h_decompose.json"), "w") as f:
            json.dump(out, f, indent=1)
        return 0
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}}
    out["exact"] = probe_exact()
    reps = int(os.environ.get("PROBE_REPS", "2"))
    out["pipeline"] = probe_pipeline(3900000039, reps, write=True)
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out", "probe_d2h_pieces.json"), "w") as f:
        json.dump(out, f, indent=1)
    bad = {k: v for k, v in out["exact"].items() if v["differing_bytes"] or v["small_copy_differing"]}
    print("EXACT:", "every bit" if not bad else f"DIFFERS {bad}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
