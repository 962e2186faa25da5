"""Dim-0 chunking of large arrays (reference ``io_preparers/chunked_tensor.py:34-126``).

Splitting a big array into independent write requests lets the scheduler
pipeline its D2H transfer with storage I/O *within* one array, and lets the
partitioner split a replicated array's write load across processes at chunk
granularity. On TPU the per-chunk slice ``arr[r0:r1]`` is an XLA device op, so
chunk transfers stream out of HBM back-to-back without a full host-side copy
first — for the dtypes a device slice returns bit for bit; a sub-32-bit float
array is not chunked (``device_programs.slice_preserves_bits``).

The row-range math (``chunk_row_ranges``) lives in ``device_programs.py``,
shared with the fork's piece cut and the prepared-state cache.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from ..io_types import ReadReq, WriteReq
from ..manifest import ChunkedArrayEntry, Shard
from ..device_programs import chunk_row_ranges, slice_preserves_bits
from ..utils import knobs
from .array import ArrayIOPreparer

__all__ = ["should_chunk", "ChunkedArrayIOPreparer"]


def should_chunk(arr: Any) -> bool:
    if not slice_preserves_bits(arr.dtype):
        # Chunks of a device array are cut on the device, and a device slice
        # rewrites this dtype's bits: the array stays one object. By dtype
        # alone, host arrays too: every rank must lay a replicated leaf out
        # the same way whether it forked it or captured it through the host.
        return False
    nbytes = int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize if arr.shape else 0
    return (
        len(arr.shape) >= 1
        and arr.shape[0] > 1
        and nbytes > knobs.get_max_chunk_size_bytes()
    )


class ChunkedArrayIOPreparer:
    @staticmethod
    def prepare_write(
        storage_path: str,
        arr: Any,
        replicated: bool = False,
        is_async_snapshot: bool = False,
    ) -> Tuple[ChunkedArrayEntry, List[WriteReq]]:
        dtype = np.dtype(arr.dtype)
        shape = list(arr.shape)
        ranges = chunk_row_ranges(shape, dtype.itemsize, knobs.get_max_chunk_size_bytes())
        chunks: List[Shard] = []
        write_reqs: List[WriteReq] = []
        for r0, r1 in ranges:
            chunk_path = f"{storage_path}.chunk_{r0}"
            sub_entry, sub_reqs = ArrayIOPreparer.prepare_write(
                storage_path=chunk_path,
                arr=arr[r0:r1],
                replicated=replicated,
                is_async_snapshot=is_async_snapshot,
            )
            offsets = [r0] + [0] * (len(shape) - 1)
            sizes = [r1 - r0] + shape[1:]
            chunks.append(Shard(offsets=offsets, sizes=sizes, tensor=sub_entry))
            write_reqs.extend(sub_reqs)
        entry = ChunkedArrayEntry(
            dtype=chunks[0].tensor.dtype, shape=shape, chunks=chunks, replicated=replicated
        )
        return entry, write_reqs

    @staticmethod
    def prepare_read(  # spmd-pure
        entry: ChunkedArrayEntry,
        target: np.ndarray,
        buffer_size_limit_bytes: Optional[int] = None,
        frame_tables: Optional[dict] = None,
        fresh_target: bool = False,
    ) -> List[ReadReq]:
        read_reqs: List[ReadReq] = []
        for chunk in entry.chunks:
            r0 = chunk.offsets[0]
            r1 = r0 + chunk.sizes[0]
            view = target[r0:r1]
            read_reqs.extend(
                ArrayIOPreparer.prepare_read(
                    chunk.tensor,
                    view,
                    buffer_size_limit_bytes,
                    frame_table=(frame_tables or {}).get(chunk.tensor.location),
                    fresh_target=fresh_target,
                )
            )
        return read_reqs
