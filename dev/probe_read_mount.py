"""Host-only probe of PR 40 (``chiprun -- python dev/probe_read_mount.py``):
what an ``O_DIRECT`` ``pread`` of 4 MiB costs on the chip machine's mount,
apart from where the chunk lands, read from the engine's own stamps. One file
written through the engine as the fs plugin writes it, then read whole at
depth 1 and 8 into a destination touched before (only the ``pread`` and a
warm copy) and into a fresh one (the restore's case), just after the write,
again, and after asking the kernel to drop the file's pages. ``PERF.md``
section 7, S1."""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchsnapshot_tpu import native  # noqa: E402
from torchsnapshot_tpu.engine.intervals import measure, merge_intervals  # noqa: E402

MIB = 1 << 20
CHUNK = 4 * MIB


def read_once(lib, path, nbytes, depth, warm):
    native.set_read_depth(lib, depth)
    dst = np.empty(nbytes, np.uint8)
    if warm:
        dst.fill(0)
    t0 = time.monotonic()
    rows = native.read_into(lib, path, dst, direct=True, chunk_bytes=CHUNK, stamped=True)
    wall = time.monotonic() - t0
    preads = [(p0, p1) for _, _, p0, p1 in rows]
    chunks = [(c0, c1) for c0, c1, _, _ in rows]
    pread_busy = measure(merge_intervals(preads))
    return {
        "depth": depth,
        "destination": "warm" if warm else "fresh",
        "wall_gbps": nbytes / wall / 1e9,
        "pread_gbps": nbytes / pread_busy / 1e9,
        "pread_depth": measure(preads) / pread_busy,
        "pread_ms_a_chunk": 1e3 * measure(preads) / len(rows),
        "reader_copy_pct": 100.0 * (1.0 - measure(preads) / measure(chunks)),
    }


def main():
    lib = native.load_native()
    nbytes = int(os.environ.get("PROBE_BYTES", 1536 * MIB))
    root = os.path.join(os.getcwd(), ".benchtmp")
    os.makedirs(root, exist_ok=True)
    out = []
    with tempfile.TemporaryDirectory(dir=root) as tmp:
        path = os.path.join(tmp, "obj")
        src = np.random.default_rng(0).integers(0, 256, nbytes, dtype=np.uint8)
        t0 = time.monotonic()
        native.write_file(lib, path, src, direct=True, chunk_bytes=64 * MIB)
        print(json.dumps({"written_gbps": nbytes / (time.monotonic() - t0) / 1e9, "bytes": nbytes}), flush=True)
        for when in ("just_written", "read_again", "after_fadvise_dontneed"):
            if when == "after_fadvise_dontneed":
                fd = os.open(path, os.O_RDONLY)
                try:
                    os.fsync(fd)
                    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                    dropped = "ok"
                except OSError as e:
                    dropped = repr(e)
                finally:
                    os.close(fd)
                print(json.dumps({"fadvise_dontneed": dropped}), flush=True)
            for depth, warm in ((1, True), (8, True), (8, False)):
                row = dict(when=when, **read_once(lib, path, nbytes, depth, warm))
                out.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_read_mount.jsonl", "w") as f:
        f.writelines(json.dumps(row) + "\n" for row in out)


if __name__ == "__main__":
    main()
