"""Fallback pickle preparer for arbitrary objects
(reference ``io_preparers/object.py:34-92``).

Load cannot be in-place for arbitrary objects: the consumer materializes a
fresh object and delivers it through a callback box, which the restore path
splices back into the loaded state dict (reference ``snapshot.py:736-747``).
"""

from __future__ import annotations

import asyncio
import pickle
from concurrent.futures import Executor
from typing import Any, Callable, List, Optional, Tuple

from ..io_types import BufferConsumer, BufferStager, BufferType, ReadReq, WriteReq
from ..manifest import ObjectEntry
from ..restore_times import run_consume_work
from ..serialization import Serializer


class ObjectBufferStager(BufferStager):
    """Objects always stage (pickle into a private buffer) before
    ``async_take`` returns — never ``defer_staging`` — so post-return
    mutations cannot corrupt the snapshot."""

    def __init__(self, obj: Any) -> None:
        self.obj = obj

    def rebind(self, obj: Any) -> None:
        """Swap in the new step's object (prepared-cache hit path); the
        pickle happens at stage time so nothing else is stale."""
        self.obj = obj

    def unbind(self) -> None:
        self.obj = None

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        loop = asyncio.get_running_loop()
        dump = lambda: pickle.dumps(self.obj, protocol=pickle.HIGHEST_PROTOCOL)
        if executor is not None:
            return await loop.run_in_executor(executor, dump)
        return dump()

    def get_staging_cost_bytes(self) -> int:
        # Unknown until pickled; a conservative nominal cost.
        return 1024 * 1024


class ObjectBufferConsumer(BufferConsumer):
    def __init__(self, entry: ObjectEntry) -> None:
        self.entry = entry
        self._callback: Optional[Callable[[Any], None]] = None

    def set_consume_callback(self, callback: Callable[[Any], None]) -> None:
        self._callback = callback

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        def work() -> None:
            obj = pickle.loads(bytes(buf))
            if self._callback is not None:
                self._callback(obj)

        # Inline on the loop thread, as ever: objects are small.
        await run_consume_work(work, None)

    def get_consuming_cost_bytes(self) -> int:
        return 1024 * 1024


class ObjectIOPreparer:
    @staticmethod
    def prepare_write(
        storage_path: str,
        obj: Any,
        replicated: bool = False,
    ) -> Tuple[ObjectEntry, List[WriteReq]]:
        entry = ObjectEntry(
            location=storage_path,
            serializer=Serializer.PICKLE,
            obj_type=type(obj).__qualname__,
            replicated=replicated,
        )
        return entry, [
            WriteReq(path=storage_path, buffer_stager=ObjectBufferStager(obj))
        ]

    @staticmethod
    def prepare_read(  # spmd-pure
        entry: ObjectEntry,
    ) -> Tuple[List[ReadReq], ObjectBufferConsumer]:
        consumer = ObjectBufferConsumer(entry)
        return [ReadReq(path=entry.location, buffer_consumer=consumer)], consumer
