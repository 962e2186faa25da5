"""In-memory storage plugin, used by tests and as a fault-injection base.

No reference equivalent (the reference injects faults by subclassing its FS
plugin, ``tests/test_async_take.py:25-40``); a dict-backed plugin makes unit
tests of the scheduler/batcher/preparers hermetic and fast.
"""

from __future__ import annotations

from typing import Dict, List

from .. import telemetry
from ..io_types import ReadIO, StoragePlugin, WriteIO


# ``memory://<name>`` URLs resolve to a per-process shared root so a snapshot
# taken and restored within one process sees the same objects.
_SHARED_ROOTS: Dict[str, "MemoryStoragePlugin"] = {}


class MemoryStoragePlugin(StoragePlugin):
    def __init__(self, root: str = "") -> None:
        self.root = root
        self.objects: Dict[str, bytes] = {}

    async def write(self, write_io: WriteIO) -> None:
        data = bytes(write_io.buf)
        with telemetry.span(
            "storage.write",
            cat="storage",
            plugin="memory",
            path=write_io.path,
            nbytes=len(data),
        ):
            self.objects[write_io.path] = data
        telemetry.counter_add("storage.memory.write_bytes", len(data))

    async def read(self, read_io: ReadIO) -> None:
        with telemetry.span(
            "storage.read", cat="storage", plugin="memory", path=read_io.path
        ) as sp:
            try:
                data = self.objects[read_io.path]
            except KeyError:
                raise FileNotFoundError(read_io.path) from None
            if read_io.byte_range is not None:
                begin, end = read_io.byte_range
                data = data[begin:end]
            sp.set_attrs(nbytes=len(data))
            read_io.buf.write(data)
        telemetry.counter_add("storage.memory.read_bytes", len(data))

    async def delete(self, path: str) -> None:
        try:
            del self.objects[path]
        except KeyError:
            raise FileNotFoundError(path) from None

    async def list_prefix(self, prefix: str) -> List[str]:
        return sorted(p for p in self.objects if p.startswith(prefix))
