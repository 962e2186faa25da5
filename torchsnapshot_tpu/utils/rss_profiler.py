"""Background RSS-delta sampler (reference ``rss_profiler.py:32-56``).

Used by tests to verify the scheduler's memory budget holds::

    deltas = []
    with measure_rss_deltas(rss_deltas=deltas):
        snapshot = Snapshot.take(...)
    assert max(deltas) < budget + slack
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Generator, List

import psutil


@contextlib.contextmanager
def measure_rss_deltas(
    rss_deltas: List[int], interval_ms: float = 100.0
) -> Generator[None, None, None]:
    proc = psutil.Process()
    baseline = proc.memory_info().rss
    stop = threading.Event()

    def sample() -> None:
        while not stop.is_set():
            rss_deltas.append(proc.memory_info().rss - baseline)
            time.sleep(interval_ms / 1000)

    thread = threading.Thread(target=sample, daemon=True)
    thread.start()
    try:
        yield
    finally:
        stop.set()
        thread.join()
        rss_deltas.append(proc.memory_info().rss - baseline)
