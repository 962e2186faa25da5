"""Where a run writes its checkpoints, resolved anew by every run.

The rule: among the directories a run may write, ``TMPDIR``, ``HOME`` and
its checkout, in that order, the first on a local block-backed filesystem
that accepts ``O_DIRECT`` and has room; if there is none, the first with
room that is not RAM-backed. A measured run with nothing but RAM-backed
room fails: a tmpfs is no storage medium, a write into it is a copy into
the page cache (the dry run may use one). No environment variable selects
the target: those above are the driver's per-side directories, used only
as places to look. Nothing is written anywhere else, and a run removes only
the directory it made (``mkdtemp``).
"""

import os
import shutil
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(REPO_ROOT, ".perfbench_out")

RAM_TYPES = {"tmpfs", "ramfs"}
# Not a local medium: the bytes cross to another machine or a user-space server.
REMOTE_TYPES = {"9p", "nfs", "nfs4", "cifs", "virtiofs", "ceph", "lustre"}


def filesystem_of(path: str) -> dict:
    """Type, mount point and source of the filesystem holding ``path``
    (the longest mount point of ``/proc/mounts`` that is a prefix of it)."""
    path = os.path.realpath(path)
    best = {"fstype": "unknown", "mount": "", "source": ""}
    with open("/proc/mounts") as f:
        for line in f:
            source, mount, fstype = line.split()[:3]
            inside = path == mount or path.startswith(mount.rstrip("/") + "/")
            if inside and len(mount) >= len(best["mount"]):
                best = {"fstype": fstype, "mount": mount, "source": source}
    return best


def storage_class(fstype: str) -> str:
    if fstype in RAM_TYPES:
        return "ram"
    if fstype in REMOTE_TYPES or fstype.startswith("fuse"):
        return "remote"
    return "block"


def takes_o_direct(directory: str) -> bool:
    path = os.path.join(directory, f".o_direct_probe.{os.getpid()}")
    try:
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_DIRECT, 0o600)
    except OSError:
        return False
    os.close(fd)
    os.unlink(path)
    return True


PREFIX = "perfbench-ckpt-"


def candidate_dirs() -> list:
    out = [tempfile.gettempdir()]
    if os.environ.get("HOME"):
        out.append(os.environ["HOME"])
    return out + [OUT_DIR]


def resolve_target(need_bytes: int, allow_ram: bool = False) -> dict:
    """Apply the rule; returns ``{"dir", "fstype", "class", "mount"}`` with
    ``dir`` a fresh directory of this run's own, removed by ``release``."""
    rows = []
    for base in candidate_dirs():
        try:
            os.makedirs(base, exist_ok=True)
            fs = filesystem_of(base)
            stat = os.statvfs(base)
        except OSError:
            continue
        rows.append(
            {
                "base": base,
                "class": storage_class(fs["fstype"]),
                "o_direct": takes_o_direct(base),
                "room": stat.f_bavail * stat.f_frsize >= need_bytes,
                **fs,
            }
        )
    pick = next((r for r in rows if r["class"] == "block" and r["o_direct"] and r["room"]), None)
    if pick is None:
        pick = next((r for r in rows if r["class"] != "ram" and r["room"]), None)
    if pick is None and allow_ram:
        pick = next((r for r in rows if r["room"]), None)
    if pick is None:
        raise OSError(
            f"no directory of this run has room for {need_bytes} bytes off RAM-backed filesystems: {rows}"
        )
    pick["dir"] = tempfile.mkdtemp(prefix=PREFIX, dir=pick["base"])
    return pick


def release(target: dict) -> None:
    shutil.rmtree(target["dir"], ignore_errors=True)


def snapshot_files(path: str) -> list:
    return [
        os.path.join(root, name) for root, _, names in os.walk(path) for name in names
    ]


def drop_page_cache(paths) -> None:
    """``fsync`` then ``POSIX_FADV_DONTNEED`` per file: where the kernel
    honours the advice the next read comes from the medium, as it does for
    a restarted job. A RAM-backed filesystem keeps its pages (they are the
    medium) and a sandboxed kernel may take the advice and do nothing, so
    no cell claims a cold read."""
    for path in paths:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        finally:
            os.close(fd)

