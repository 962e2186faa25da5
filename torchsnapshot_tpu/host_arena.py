"""Host pages with one owner: a bounded arena handed from leaf to leaf.

On the chip's machine the first write into a fresh host page costs several
times a write into one touched before (a thread first-touches ``np.empty``
at 1.0 GB/s and copies into touched pages at 7.9; ``PERF.md`` section 6),
and a restore used to read every device-bound leaf into an ``np.empty`` of
its own: the readers paid a first touch for every byte of the state. The
native read pool keeps its bounce buffers warm for the same reason
(``native/tss_io.cpp``); this is "one owner of host pages" (ROADMAP D15)
for the two operations that have nothing beside them to disturb: a restore
and a synchronous take.

A :class:`HostArena` is ``capacity`` bytes, allocated on first use and
touched by the first leaves that use them, or, in a restore, by the arena
itself beforehand (below). An entry's targets are one
:class:`Lease`: page-aligned views carved first-fit from the lowest free
address (so the arena touches no more fresh pages than were ever wanted at
once), taken in one step when the entry's first read is about to be fetched
and given back when the entry's host-to-device transfer has finished with
them (:meth:`Lease.give_back_when`, on a thread that only blocks). Reads are
issued largest first, so a block given back always fits a later entry.

A lease that finds no room waits only for leases that need nothing more
from the read pipeline to come back: those all of whose reads have begun.
An entry whose other reads may still queue behind the waiter is never
waited for, so a waiter never holds the io slot or the budget that the
read it waits for needs. Where there is no such lease, or the entry is
larger than the arena, the entry takes fresh pages of its own (``None``)
as before.

**What is touched when.** The first arena's worth of a restore's reads
would land in pages nobody has touched, at the fault's rate and not the
copy's. The one stretch of a restore in which nobody faults is its plan:
so from the moment the plan has made its first lease
(:meth:`HostArena.pretouch`, called for every lease planned) until the
first lease takes room, ``PRETOUCH_THREADS`` threads of the arena's own
first touch its pages, a byte a page through the native engine (GIL-free;
without the engine nothing is touched beforehand), in stripes claimed from
address 0 upward, as far as the leases planned so far will reach and never
past ``capacity``: a restore that leases 50 MB touches 50 MB. What they
finished without a gap counts as used (``touched_bytes``,
``pretouched_bytes``), and first fit hands those pages to the first,
largest reads. **The invariant: no byte of a view that a lease holds is
ever written by a toucher.** ``_take`` stops and joins the touchers before
it makes the first view (the engine looks at the stop flag before every
page, so that is a page fault's wait, ``pretouch_stop_wait_s``), they never
start again, and ``close`` stops and joins them before the memory goes, on
a failed restore too. Touching *beside* the readers buys nothing: the
faults share one rate (``PERF.md`` section 5, PR 41).

Two users, one policy: **pages are recycled where no train step runs beside
the operation, and stay fresh where one does.** ``Snapshot.restore`` leases
the targets that exist only to be put on a device whose ``device_put``
copies (:func:`copies_on_put`). ``Snapshot.take`` leases the one host buffer
into which a big leaf's pieces are gathered (a lease of one read, taken at
the leaf's turn in the stage and given back when its hash and its storage
write are done, or when the request fails or is cancelled: every lease that
is out is then worth waiting for, and the writers pace the take through
it); the arena is the write pipeline's, made at the first such leaf and
closed with the pipeline; it does not touch beforehand: a take has no plan
in which nothing moves (its first leaf is cut as its stage begins), and the
only end-to-end number that holds a synchronous take is a set-up time that
could not show it. An ``async_take`` drain lands its gathers in fresh
pages of each leaf's own: recycled ones moved faster and cost the steps
beside the drain 4-9 points of their rate (``PERF.md``, PR 39).
"""

from __future__ import annotations

import asyncio
import logging
import mmap
import queue
import threading
import time
from typing import Any, Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from . import native

logger = logging.getLogger(__name__)

PAGE_BYTES = mmap.PAGESIZE
# The most a restore keeps in views at once (and so the most fresh pages it
# touches for device-bound leaves); never more than the memory budget.
# Sized on the v5e's machine, ``CHANGES.md`` PR 41.
CAPACITY_BYTES = 1024 * 1024 * 1024
# Who first-touches a restore's arena while the restore plans
# (:meth:`HostArena.pretouch`): as many threads as the engine has readers at
# its default depth, each a stripe at a time. More touchers fault no faster
# on the v5e's machine (8 / 13 / 16: ``PERF.md`` section 5).
PRETOUCH_THREADS = 8
PRETOUCH_STRIPE_BYTES = 4 * 1024 * 1024

# Platforms whose ``device_put`` of a host array copies it to memory of the
# device's own. The CPU backend may hand back an array that shares the numpy
# buffer: recycling the pages would rewrite a restored leaf.
_COPYING_PLATFORMS = frozenset({"tpu", "gpu", "cuda", "rocm"})


def copies_on_put(devices: Iterable[Any]) -> bool:
    """Whether an array put on ``devices`` can never alias the host pages
    it was put from: every one of them is an accelerator."""
    platforms = {getattr(d, "platform", "cpu") for d in devices}
    return bool(platforms) and platforms <= _COPYING_PLATFORMS


def _round_up(nbytes: int) -> int:
    return -(-nbytes // PAGE_BYTES) * PAGE_BYTES


class Lease:
    """One entry's host targets: ``sizes`` bytes each, filled by ``reads``
    reads. ``views`` (flat uint8, page-aligned, one a size) from
    :meth:`acquire` until the lease is given back."""

    def __init__(self, arena: "HostArena", sizes: Sequence[int], reads: int) -> None:
        self.arena = arena
        self.sizes = [int(s) for s in sizes]
        self.nbytes = sum(_round_up(s) for s in self.sizes)
        self.reads = reads
        self.started = 0  # reads whose bodies have begun
        self.views: Optional[List[np.ndarray]] = None
        self.recycled_bytes = 0  # of the views' bytes, those in pages used before
        self._block: Optional[Tuple[int, int]] = None
        self._decided = False  # views, or fresh pages: settled either way
        self._waiting: Optional[asyncio.Future] = None

    @property
    def full(self) -> bool:
        """Every read of the entry has begun: it needs no io slot and no
        budget that a waiter could be holding."""
        return self.started >= self.reads

    async def acquire(self) -> Optional[List[np.ndarray]]:
        """Called by each read of the entry before its fetch. The first
        takes all of the entry's views, waiting for room where waiting is
        safe; the others get what the first got. None: fresh pages."""
        arena = self.arena
        with arena._lock:
            self.started += 1
            if self._decided:
                return self.views
            if self._waiting is None:
                if arena._take(self) or not arena._worth_waiting(self):
                    self._decided = True
                    return self.views
                self._waiting = asyncio.get_running_loop().create_future()
                if not arena._waiters:
                    arena._waiting_since = time.monotonic()
                arena._waiters.append(self)
            waiting = self._waiting
        await waiting
        return self.views

    def take_nowait(self) -> Optional[List[np.ndarray]]:
        """The views where there is room now, for a read nobody called
        :meth:`acquire` for (a merged read); None: fresh pages."""
        with self.arena._lock:
            if not self._decided:
                self.arena._take(self)
                self._decided = True
            return self.views

    def give_back_when(self, wait: Callable[[], Any]) -> None:
        """Give the views back once ``wait`` returns (or raises): the
        placed array's ``block_until_ready``. Until then the transfer may
        still be reading the pages."""
        if self._block is not None:
            self.arena._settle(self, wait)

    def give_back(self) -> None:
        self.arena._give_back(self)

    def _wake(self) -> None:
        if not self._waiting.done():
            self._waiting.set_result(None)


class HostArena:
    """``capacity_bytes`` of host pages for one restore or one synchronous
    take. Nothing is allocated before the first lease takes room or, in a
    restore, is planned (:meth:`pretouch`)."""

    def __init__(self, capacity_bytes: int = CAPACITY_BYTES) -> None:
        self.capacity = capacity_bytes // PAGE_BYTES * PAGE_BYTES
        self.touched_bytes = 0  # [0, touched_bytes) has been handed out before
        self.in_use_hwm_bytes = 0
        self.wait_s = 0.0  # in which some lease waited for room (a union)
        self._waiting_since = 0.0
        self._lock = threading.Lock()
        self._mem: Optional[np.ndarray] = None
        self._free: List[Tuple[int, int]] = [(0, self.capacity)]  # sorted [start, end)
        self._out: List[Lease] = []  # leases holding a block
        self._waiters: List[Lease] = []  # oldest first
        self._closed = False
        self._settling: Optional["queue.SimpleQueue"] = None
        self._settler: Optional[threading.Thread] = None
        # Pre-touching (``pretouch``). A toucher is one call into the native
        # engine and takes neither ``_lock`` nor the GIL: ``_take`` joins
        # them while it holds both.
        self.pretouched_bytes = 0  # the prefix the touchers finished
        self.pretouch_s = 0.0  # from the first request to their end
        self.pretouch_stop_wait_s = 0.0  # of it, waiting for them to stop
        self._touch = native.TouchState()  # the engine's side of it
        self._touch_unfinished: List[int] = []  # a toucher's, as it ends
        self._touch_since = 0.0
        self._touchers: List[threading.Thread] = []

    def lease(self, sizes: Sequence[int], reads: int) -> Lease:
        return Lease(self, sizes, reads)

    def pretouch(self, nbytes: int) -> None:
        """A lease of ``nbytes`` has been planned and nothing has been
        fetched yet: have that many more bytes of the arena, from address 0
        up and never past ``capacity``, first touched in the background
        until the first lease takes room. The pages then count as used
        (``touched_bytes``), and first fit hands them to the first reads.
        Nothing happens without the native engine, which touches GIL-free."""
        if not 0 < nbytes <= self.capacity:
            return  # the lease will take fresh pages of its own
        with self._lock:
            if self._closed or self._touch.stop:
                return
            if not self._touchers:
                lib = native.load_native_nonblocking()
                if lib is None:
                    return
                self._allocate()
                self._touch_since = time.monotonic()
                for i in range(PRETOUCH_THREADS):
                    toucher = threading.Thread(
                        target=self._touch_all,
                        args=(lib, self._mem.ctypes.data),
                        name=f"tss-host-arena-touch-{i}",
                        daemon=True,
                    )
                    toucher.start()
                    self._touchers.append(toucher)
            self._touch.wanted = min(self.capacity, self._touch.wanted + nbytes)

    @property
    def allocated(self) -> bool:
        return self._mem is not None

    @property
    def in_use_bytes(self) -> int:
        with self._lock:
            return sum(lease.nbytes for lease in self._out)

    def take_wait_s(self) -> float:
        """``wait_s`` since the last call: nobody waits between a restore's
        pipelines."""
        with self._lock:
            waited, self.wait_s = self.wait_s, 0.0
            return waited

    # ------------------------------------------------------------- internals
    # All under ``_lock``: leases are taken on the event-loop thread and given
    # back on the settling thread.

    def _take(self, lease: Lease) -> bool:
        """First fit from the lowest address; all of the entry's views out
        of one block, or nothing."""
        if self._closed or not 0 < lease.nbytes <= self.capacity:
            return False
        for i, (start, end) in enumerate(self._free):
            if end - start >= lease.nbytes:
                break
        else:
            return False
        stop = start + lease.nbytes
        if stop == end:
            del self._free[i]
        else:
            self._free[i] = (stop, end)
        # No byte of a view is ever written by a toucher: they are gone
        # before the first view exists.
        self._end_pretouch()
        self._allocate()
        views, at, recycled = [], start, 0
        for size in lease.sizes:
            views.append(self._mem[at : at + size])
            recycled += max(0, min(at + size, self.touched_bytes) - at)
            at += _round_up(size)
        self.touched_bytes = max(self.touched_bytes, stop)
        lease.views, lease.recycled_bytes, lease._block = views, recycled, (start, stop)
        self._out.append(lease)
        self.in_use_hwm_bytes = max(
            self.in_use_hwm_bytes, sum(held.nbytes for held in self._out)
        )
        return True

    def _allocate(self) -> None:
        if self._mem is None:
            # np.empty, not mmap: its first touch is the cheaper (PR 39).
            raw = np.empty(self.capacity + PAGE_BYTES, dtype=np.uint8)
            skew = -raw.ctypes.data % PAGE_BYTES
            self._mem = raw[skew : skew + self.capacity]

    def _touch_all(self, lib: Any, base: int) -> None:
        self._touch_unfinished.append(
            native.touch_stripes(lib, base, self._touch, PRETOUCH_STRIPE_BYTES, PAGE_BYTES)
        )

    def _end_pretouch(self) -> None:
        """Stop the touchers and wait until the last has left the memory
        (a page fault away: the engine looks at the flag before every page);
        what they finished from address 0 up without a gap counts as used."""
        self._touch.stop = 1  # for good: a lease holds a view from now on
        if not self._touchers:
            return
        t0 = time.monotonic()
        for toucher in self._touchers:
            toucher.join()
        self._touchers = []
        self.pretouched_bytes = min([self._touch.claimed] + self._touch_unfinished)
        self.touched_bytes = max(self.touched_bytes, self.pretouched_bytes)
        now = time.monotonic()
        self.pretouch_s, self.pretouch_stop_wait_s = now - self._touch_since, now - t0

    def _worth_waiting(self, lease: Lease) -> bool:
        return (
            not self._closed
            and 0 < lease.nbytes <= self.capacity
            and any(held.full for held in self._out)
        )

    def _give_back(self, lease: Lease) -> None:
        with self._lock:
            block, lease._block, lease.views = lease._block, None, None
            if self._closed:
                return
            if lease in self._waiters:
                # Given back while it waited (its entry was cancelled): it
                # must not be handed a block nobody would return.
                self._waiters.remove(lease)
                lease._decided = True
                if not self._waiters:
                    self.wait_s += time.monotonic() - self._waiting_since
            elif block is None:
                return
            if block is not None:
                self._out.remove(lease)
                free = sorted(self._free + [block])
                merged = [free[0]]
                for start, end in free[1:]:
                    if start == merged[-1][1]:
                        merged[-1] = (merged[-1][0], end)
                    else:
                        merged.append((start, end))
                self._free = merged
            # Oldest first, and nobody past the first that must go on
            # waiting: reads come largest first, so what fits the second
            # fits the first.
            while self._waiters:
                waiter = self._waiters[0]
                if not self._take(waiter) and self._worth_waiting(waiter):
                    break
                self._decide(self._waiters.pop(0))

    def _decide(self, waiter: Lease) -> None:
        waiter._decided = True
        if not self._waiters:
            self.wait_s += time.monotonic() - self._waiting_since
        try:
            waiter._waiting.get_loop().call_soon_threadsafe(waiter._wake)
        except RuntimeError:  # its restore's loop is closed: nobody waits
            pass

    def _settle(self, lease: Lease, wait: Callable[[], Any]) -> None:
        with self._lock:
            if self._settler is None:
                self._settling = queue.SimpleQueue()
                self._settler = threading.Thread(
                    target=self._settle_loop,
                    args=(self._settling,),
                    name="tss-host-arena",
                    daemon=True,
                )
                self._settler.start()
        self._settling.put((lease, wait))

    def _settle_loop(self, settling: "queue.SimpleQueue") -> None:
        while True:
            item = settling.get()
            if item is None:
                return
            lease, wait = item
            try:
                wait()
            except Exception:  # noqa: BLE001 - the leaf's user will see it
                logger.debug("a placed leaf failed to become ready", exc_info=True)
            del wait, item
            self._give_back(lease)

    # ----------------------------------------------------------------- close

    def close(self) -> None:
        """The restore's end, or its failure: the arena lets go of its pages
        and serves nobody from now on. Views still under a transfer stay
        whole until it is done (the settling thread holds them until then,
        then ends); the memory goes with the last of them."""
        with self._lock:
            self._closed = True
            self._end_pretouch()  # before the memory goes
            self._mem = None
            self._free = []
            self._out = []
            while self._waiters:
                self._decide(self._waiters.pop())
            if self._settling is not None:
                self._settling.put(None)
