"""Host-only probe of PR 39 (``chiprun -- python dev/probe_first_touch.py``):
what the first write into fresh host pages costs on the chip's machine, and
whether huge pages or a bulk populate cure it. ``PERF.md`` section 6.

PR 41 added the last arm (ROADMAP S1 (a), for the record): eight readers
copy 4 MiB chunks out of warm memory into a fresh 1.5 GiB destination while
8, 13 or 16 other threads touch its pages ahead of them, a chunk at a time;
does touching ahead pass what the readers do alone into fresh pages?

PR 51 added ``--arena``: what the restore's arena touches of its own pages
(``HostArena.pretouch``, eight GIL-free touchers) in the 0.15 s a restore's
plan gives it, while the main thread sleeps, runs Python (the plan holds
the GIL), or allocates and frees big stand-in arrays as the plan does."""

import json
import mmap
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MIB = 1 << 20
HUGE = 2 * MIB
MADV_POPULATE_WRITE = 23


def aligned(nbytes, advice=None):
    m = mmap.mmap(-1, nbytes + HUGE)
    base = np.frombuffer(m, dtype=np.uint8)
    off = (-base.ctypes.data) % HUGE
    if advice is not None:
        m.madvise(advice, 0, nbytes + HUGE)
    return base[off : off + nbytes], m


def fill(arr, threads=1):
    t0 = time.perf_counter()
    if threads == 1:
        arr.fill(1)
    else:
        n = arr.nbytes // threads
        with ThreadPoolExecutor(threads) as ex:
            list(ex.map(lambda i: arr[i * n : (i + 1) * n].fill(1), range(threads)))
    return time.perf_counter() - t0


def touch_ahead(touchers, nbytes=1536 * MIB, chunk=4 * MIB, readers=8, warm=False):
    """GB/s at which ``readers`` threads fill a fresh destination from warm
    memory, each chunk first touched (a byte a page) by one of ``touchers``
    threads running ahead; 0 touchers: the readers take the faults."""
    src = np.ones(chunk, np.uint8)
    dest = np.empty(nbytes, np.uint8)
    if warm:
        dest.fill(1)
    chunks = nbytes // chunk
    touched = [threading.Event() for _ in range(chunks)]

    def toucher(t):
        for i in range(t, chunks, touchers):
            dest[i * chunk : (i + 1) * chunk : mmap.PAGESIZE] = 1
            touched[i].set()

    def reader(r):
        for i in range(r, chunks, readers):
            if touchers:
                touched[i].wait()
            np.copyto(dest[i * chunk : (i + 1) * chunk], src)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(touchers + readers) as ex:
        jobs = [ex.submit(toucher, t) for t in range(touchers)]
        jobs += [ex.submit(reader, r) for r in range(readers)]
        for job in jobs:
            job.result()
    return round(nbytes / (time.perf_counter() - t0) / 1e9, 3)


def arena_touch(beside, lap_s=0.15):
    """GB the arena's touchers finish in ``lap_s`` and their rate, with the
    main thread doing ``beside`` meanwhile."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from torchsnapshot_tpu import host_arena

    arena = host_arena.HostArena()
    t0 = time.perf_counter()
    arena.pretouch(arena.capacity)
    spins = 0
    while time.perf_counter() - t0 < lap_s:
        if beside == "sleep":
            time.sleep(0.001)
        elif beside == "python":
            spins += sum(range(1000))
        else:  # the plan's stand-ins: an mmap and a munmap each
            del [np.empty(400 * MIB, np.uint8) for _ in range(2)][:]
    arena.close()
    return {
        "gb": round(arena.pretouched_bytes / 1e9, 4),
        "gbps": round(arena.pretouched_bytes / 1e9 / arena.pretouch_s, 3),
        "stop_wait_ms": round(arena.pretouch_stop_wait_s * 1e3, 3),
    }


def meminfo(key):
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith(key):
                return line.split()[1] + " kB"


def main():
    n = 512 * MIB
    out = {}
    if "--arena" in sys.argv:
        for round_ in (1, 2, 3):
            for beside in ("sleep", "python", "standins"):
                out[f"arena_touch_beside_{beside}_r{round_}"] = arena_touch(beside)
        print(json.dumps(out, indent=1))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/probe_first_touch_arena.json", "w") as f:
            json.dump(out, f, indent=1)
        return 0
    for name in ("enabled", "defrag", "shmem_enabled"):
        try:
            with open(f"/sys/kernel/mm/transparent_hugepage/{name}") as f:
                out[f"thp_{name}"] = f.read().strip()
        except OSError as e:
            out[f"thp_{name}"] = repr(e)
    out["uname"] = " ".join(os.uname())
    src = np.ones(n, np.uint8)

    def gbps(s):
        return round(n / s / 1e9, 3)

    for threads in (1, 4):
        a = np.empty(n, np.uint8)
        out[f"np_empty_first_fill_t{threads}"] = gbps(fill(a, threads))
        out[f"np_empty_second_fill_t{threads}"] = gbps(fill(a, threads))
        t0 = time.perf_counter()
        np.copyto(a, src)
        out[f"warm_copy_t{threads}"] = gbps(time.perf_counter() - t0)
        del a
        a, m = aligned(n)
        out[f"mmap_first_fill_t{threads}"] = gbps(fill(a, threads))
        del a, m
        a, m = aligned(n, mmap.MADV_HUGEPAGE)
        before = meminfo("AnonHugePages")
        out[f"mmap_hugepage_first_fill_t{threads}"] = gbps(fill(a, threads))
        out[f"anon_huge_pages_t{threads}"] = [before, meminfo("AnonHugePages")]
        out[f"mmap_hugepage_second_fill_t{threads}"] = gbps(fill(a, threads))
        del a, m
    a, m = aligned(n)
    try:
        t0 = time.perf_counter()
        m.madvise(MADV_POPULATE_WRITE, 0, n)
        out["populate_write"] = gbps(time.perf_counter() - t0)
        out["populate_then_fill"] = gbps(fill(a))
    except OSError as e:
        out["populate_write"] = repr(e)
    del a, m
    out["cpu_count"] = os.cpu_count()
    out["readers8_into_touched_1536mib"] = touch_ahead(0, warm=True)
    for round_ in (1, 2):
        out[f"readers8_alone_fresh_1536mib_r{round_}"] = touch_ahead(0)
        for touchers in (8, 13, 16):
            out[f"touchers{touchers}_ahead_of_readers8_1536mib_r{round_}"] = touch_ahead(touchers)
    print(json.dumps(out, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_first_touch.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
