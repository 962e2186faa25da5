"""How a leaf leaves the device, and the programs that move it.

One decision, one home: the rules for which device programs return a
dtype's bits unchanged, the rule that cuts a big leaf into row-range pieces
(:class:`PieceCut`, :func:`piece_row_ranges`, :func:`device_piece_cut`), the
predicate that applies it to a live array (:func:`leaf_cut`), the Pallas
movers that write the pieces, and the jitted program that forks a group of
leaves (:func:`batch_copy_fn`: whole copies and pieces in one program a
take) or cuts one leaf at its turn in a synchronous take's stage
(:func:`cut_in_stage`).

Above it: ``io_preparer.py`` (capture policy: which leaves fork, what
degrades to a host capture under HBM pressure) and ``io_preparers/array.py``
(the stage: how the pieces cross and are gathered). Below it: ``d2h`` (the
piece size), ``utils``, numpy and jax. Nothing here imports a preparer, the
scheduler or the snapshot.
"""

from __future__ import annotations

import logging
import math
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from . import d2h
from .utils.lru import BoundedLRU

logger = logging.getLogger(__name__)


# XLA does not treat sub-32-bit floats as opaque bits. On the TPU toolchain
# this repository was brought up on (v5e, jax/jaxlib 0.9.0, libtpu 0.0.34;
# every bit pattern of each dtype put from the host) a slice or a bitcast of
# bfloat16 flushes all 254 denormals to zero, and a copy, slice or bitcast of
# float16 / float8_e4m3fn / float8_e5m2 rewrites NaN payloads; ``jnp.copy`` of
# bfloat16 kept every pattern, and 32-bit floats, integers and bool are exact
# in every program. Transfers (D2H, H2D) move bits unchanged. So a leaf of
# such a dtype never enters a device program that would rewrite it: it
# reaches the host whole and is cut, packed or captured there. The rule is
# by dtype alone, on every backend, so the CPU suite runs the routing the
# chip runs.


def _is_small_float(dtype: Any) -> bool:
    dt = np.dtype(dtype)
    return dt.itemsize < 4 and dt.name.startswith(("float", "bfloat"))


def slice_preserves_bits(dtype: Any) -> bool:
    """Whether a device slice / bitcast / concatenate of ``dtype`` returns
    the operand's bits unchanged (chunk slices, shard subdivision, the slab
    pack)."""
    return not _is_small_float(dtype)


def copy_preserves_bits(dtype: Any) -> bool:
    """Whether ``jnp.copy`` of ``dtype`` returns the operand's bits unchanged
    (the async-take fork)."""
    return not _is_small_float(dtype) or np.dtype(dtype).name == "bfloat16"


def chunk_row_ranges(
    shape, itemsize: int, max_chunk_bytes: int
) -> List[Tuple[int, int]]:
    """Row ranges [r0, r1) per dim-0 chunk, each chunk <= max_chunk_bytes
    (when a single row fits). Shared by the chunked-array preparer (one
    storage object per chunk) and the prepared-state cache's replay of
    that split."""
    dim0 = int(shape[0])
    row_bytes = itemsize * int(np.prod(shape[1:])) if len(shape) > 1 else itemsize
    rows_per_chunk = max(1, max_chunk_bytes // max(row_bytes, 1))
    n_chunks = math.ceil(dim0 / rows_per_chunk)
    # Even spread so the last chunk isn't tiny.
    base = dim0 // n_chunks
    extra = dim0 % n_chunks
    ranges = []
    r0 = 0
    for i in range(n_chunks):
        rows = base + (1 if i < extra else 0)
        ranges.append((r0, r0 + rows))
        r0 += rows
    return ranges


def _dma_moves(dtype: Any) -> bool:
    """Whether the fork's row cut (Pallas HBM-to-HBM DMAs, which move bits
    and compute nothing, and integer copies behind them) takes ``dtype``:
    bfloat16, the 32-bit types and the 8- and 16-bit integers. Mosaic
    refuses bool, float16 and 64-bit types; float16 and float8 never fork
    at all."""
    dt = np.dtype(dtype)
    if dt.name == "bfloat16":
        return True
    return (dt.kind in "iuf" and dt.itemsize == 4) or (
        dt.kind in "iu" and dt.itemsize in (1, 2)
    )


class PieceCut(NamedTuple):
    """How the fork writes a leaf as pieces: the row ranges [r0, r1), and
    which mover writes them. ``relaid`` False: DMAs of whole HBM tiles, a
    piece an array of the leaf's rows. ``relaid`` True: the leaf's bits as
    integers, each range re-laid row-major into lanes of 128
    (``_relay_rows``); ``order`` is then, for a leaf whose bits
    a DMA has to take first (``device_piece_cut``), the device's own order
    of its dimensions, major to minor. Either way a piece's host copy is
    the C-order bytes of its rows."""

    ranges: Tuple[Tuple[int, int], ...]
    relaid: bool
    order: Optional[Tuple[int, ...]] = None


def piece_row_ranges(shape, dtype: Any) -> Optional[PieceCut]:
    """The pieces a forked leaf crosses to the host in, each at most
    ``d2h.PIECE_BYTES`` (when a single unit of rows fits), or None where the
    leaf goes whole: not over the piece size, one row, one piece, a dtype
    the fork's movers do not take, or rows that no whole number of lanes
    holds. One cut, two movers. The DMA moves whole HBM tiles: where the
    last dimension is a multiple of 128 and the one before it of 8, a 2-D
    leaf is cut at multiples of 8 rows and a deeper one between any two of
    its slabs, and no byte is computed on. Any other shape (a width of 1856
    or 10304, 1001 rows) the device may not even hold row-major, and its
    host copy would be re-laid there by a strided copy: the fork re-lays
    it, in integers, cut at multiples of the fewest rows that fill whole
    lanes of 128 elements. By shape and dtype alone, on every backend."""
    shape = tuple(int(d) for d in shape)
    if len(shape) < 2 or not _dma_moves(dtype):
        return None
    itemsize = np.dtype(dtype).itemsize
    if itemsize * int(np.prod(shape)) <= d2h.PIECE_BYTES:
        return None
    unit = 8 if len(shape) == 2 else 1
    relaid = bool(
        shape[0] % unit or shape[-1] % 128 or (len(shape) > 2 and shape[-2] % 8)
    )
    if relaid:
        unit = 128 // math.gcd(int(np.prod(shape[1:])), 128)
        if shape[0] % unit:
            return None
    ranges = chunk_row_ranges(
        (shape[0] // unit, unit) + shape[1:], itemsize, d2h.PIECE_BYTES
    )
    if len(ranges) < 2:
        return None
    return PieceCut(tuple((r0 * unit, r1 * unit) for r0, r1 in ranges), relaid)


def device_piece_cut(
    shape, dtype: Any, device_order: Callable[[], Sequence[int]], on_tpu: bool
) -> Optional[PieceCut]:
    """``piece_row_ranges`` for a leaf as one device holds it: the cut the
    fork program is built from, or None where the leaf goes whole. XLA
    moves integers and 32-bit floats bit for bit, so those are re-laid as
    they are. A bfloat16 leaf to re-lay has its bits taken first, by one
    DMA of the whole leaf in the device's own order of its dimensions
    (``device_order()``, major to minor; asked only for such a leaf), and
    the TPU's kernel compiler takes whole HBM tiles only: handed a leaf
    whose minor dimension in that order is no multiple of 128, or the one
    before it of 8, it does not raise, it aborts the process. Such a leaf
    stays whole."""
    cut = piece_row_ranges(shape, dtype)
    if cut is None or not cut.relaid or slice_preserves_bits(dtype):
        return cut
    order = tuple(int(i) for i in device_order())
    if on_tpu and (int(shape[order[-1]]) % 128 or int(shape[order[-2]]) % 8):
        return None
    return cut._replace(order=order)


class PiecedArray:
    """A forked leaf that left the fork as row-range pieces: the metadata
    the write planners read of a ``jax.Array`` that lives whole on one
    device (``shape`` / ``dtype`` / ``sharding``), so it plans as that leaf
    does (one ``ArrayEntry``, one storage object at the same location),
    and the device arrays that hold its rows. Its stager moves the pieces
    through the transfer lanes into one host buffer of the leaf's size."""

    __slots__ = ("shape", "dtype", "sharding", "pieces", "ranges")

    def __init__(
        self,
        shape: Tuple[int, ...],
        dtype: Any,
        sharding: Any,
        pieces: Sequence[Any],
        ranges: Sequence[Tuple[int, int]],
    ) -> None:
        self.shape = shape
        self.dtype = dtype
        self.sharding = sharding
        self.pieces = pieces
        self.ranges = ranges

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize


def is_oom_error(e: BaseException) -> bool:
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()


def device_assignment_key(sharding) -> Any:
    """One jitted computation requires all operands to share a device
    assignment (order included, which ``device_set`` loses)."""
    return tuple(d.id for d in sharding._device_assignment)


# Whether the kernel compiler has refused a mover of the row cut in this
# process (``give_up_cut``): the leaves it would have cut go whole.
_dma_cut_refused = False
_relay_cut_refused = False


def give_up_cut(cuts: Sequence[Optional[PieceCut]], e: BaseException) -> None:
    """Both movers hand the leaf to a Pallas kernel, and its compiler may
    refuse a shape the rule lets through. A take must not fail for it: this
    process gives up the re-laying cut first (the DMA cut of the aligned
    leaves stays), then the DMA cut, and moves those leaves whole from here
    on."""
    global _dma_cut_refused, _relay_cut_refused
    if any(c is not None and c.relaid for c in cuts):
        _relay_cut_refused, which = True, "re-laying cut"
    else:
        _dma_cut_refused, which = True, "row cut"
    logger.warning(
        "the %s was refused by the kernel compiler (%s); the big leaves "
        "it would take are copied and cross whole from now on",
        which,
        e,
    )


def leaf_cut(arr: Any) -> Optional[PieceCut]:
    """How ``arr`` leaves the device as row-range pieces, or None where it
    goes whole: a leaf that lives whole in one device's own memory and is
    over the piece size in a shape and dtype a mover takes
    (:func:`device_piece_cut`). One predicate for the two places that cut:
    ``async_take``'s fork, which writes the copy as the pieces, and a
    synchronous take's stage (:func:`cut_in_stage`). Both ask it only of a
    leaf that stays one storage object: whether a leaf is chunked is the
    planner's to know (``io_preparers.chunked_array.should_chunk``)."""
    sharding = arr.sharding
    if len(sharding.device_set) != 1 or sharding.memory_kind not in (None, "device"):
        return None
    cut = device_piece_cut(
        arr.shape, arr.dtype, lambda: arr.format.layout.major_to_minor, _on_tpu(sharding)
    )
    if cut is None or (_relay_cut_refused if cut.relaid else _dma_cut_refused):
        return None
    return cut


def _cut_rows(x: Any, ranges: Sequence[Tuple[int, int]], interpret: bool) -> List[Any]:
    """``x``'s rows as one array a range, written by HBM-to-HBM DMAs: every
    byte is read once and written once, as ``jnp.copy`` would, and none is
    computed on, so every bit pattern of every dtype comes through (an XLA
    slice of bfloat16 flushes its denormals). Off the TPU the same kernel
    runs in Pallas's interpreter."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = len(ranges)

    def kernel(x_ref, *refs):
        outs, sems = refs[:k], refs[k]
        copies = [
            pltpu.make_async_copy(x_ref.at[pl.ds(r0, r1 - r0)], out, sems.at[i])
            for i, ((r0, r1), out) in enumerate(zip(ranges, outs))
        ]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    return list(
        pl.pallas_call(
            kernel,
            out_shape=[
                jax.ShapeDtypeStruct((r1 - r0,) + tuple(x.shape[1:]), x.dtype)
                for r0, r1 in ranges
            ],
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * k,
            scratch_shapes=[pltpu.SemaphoreType.DMA((k,))],
            interpret=interpret,
        )(x)
    )


def _bits_by_dma(x: Any, interpret: bool) -> Any:
    """``x``'s bits as unsigned integers of its width, by one DMA between
    two views of HBM: nothing is computed on. XLA's own ``bitcast-convert``
    of bfloat16 is a kernel, and on the v5e it flushes the 254 denormals and
    rewrites 253 NaN payloads (``PERF.md`` section 6, PR 46's probe)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")

    def kernel(x_ref, out, sem):
        copy = pltpu.make_async_copy(x_ref.bitcast(bits), out, sem)
        copy.start()
        copy.wait()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, bits),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(x)


def _relay_rows(
    x: Any,
    ranges: Sequence[Tuple[int, int]],
    order: Optional[Tuple[int, ...]],
    interpret: bool,
) -> List[Any]:
    """``x``'s rows as one array a range, **re-laid on the device**: a leaf
    whose width is no multiple of the 128 lanes the device may hold column
    first (a ``(2688, 10304)`` bfloat16 array lives as ``major_to_minor
    (1, 0)``), its host copy comes in that order, and a strided copy on the
    host makes it contiguous at a third of a GB/s. Here each range is
    sliced and reshaped to ``(n / 128, 128)``, a shape the device holds
    row-major and hands the host C-contiguous: the C-order bytes of the
    rows, whatever the piece's dtype. XLA moves integers and 32-bit floats
    bit for bit and sub-32-bit floats not (``slice_preserves_bits``), so
    bfloat16 comes with ``order``, the device's own order of its
    dimensions, and its bits become integers first, by a DMA that takes the
    leaf in that order: XLA then puts no copy of its own before the DMA,
    the two transposes compile to views."""
    if order is not None:
        inverse = tuple(int(i) for i in np.argsort(order))
        x = _bits_by_dma(x.transpose(order), interpret).transpose(inverse)
    row = int(np.prod(x.shape[1:]))
    return [x[r0:r1].reshape((r1 - r0) * row // 128, 128) for r0, r1 in ranges]


def _on_tpu(sharding: Any) -> bool:
    return all(d.platform == "tpu" for d in sharding.device_set)


def batch_copy_fn(
    shardings: Tuple[Any, ...],
    cuts: Tuple[Optional[PieceCut], ...],
    cache: Optional[BoundedLRU] = None,
):
    """The fork of one group: a whole ``jnp.copy`` a leaf, or its copy as
    row-range pieces where ``cuts`` gives a cut (``leaf_cut``), written by
    the cut's mover, all in one jitted lambda: one program a take."""

    def pieces(x, sharding, cut):
        interpret = not _on_tpu(sharding)
        if cut.relaid:
            return _relay_rows(x, cut.ranges, cut.order, interpret)
        return _cut_rows(x, cut.ranges, interpret)

    def build():
        import jax
        import jax.numpy as jnp

        return jax.jit(
            lambda xs: [
                jnp.copy(x) if cut is None else pieces(x, s, cut)
                for x, s, cut in zip(xs, shardings, cuts)
            ],
            out_shardings=[
                s if cut is None else [s] * len(cut.ranges)
                for s, cut in zip(shardings, cuts)
            ],
        )

    cache = _BATCH_COPIES if cache is None else cache
    return cache.get_or_build((shardings, cuts), build)


_BATCH_COPIES = BoundedLRU()
# A synchronous take's programs of one leaf each (``cut_in_stage``): one a
# distinct cut and sharding, whatever the leaf's other dimensions (``jit``
# keeps an executable a shape). Apart from the forks', which they would push
# out: a state has more kinds of big leaf than a job has state structures.
_STAGE_CUTS = BoundedLRU(64)


def cut_in_stage(arr: Any, cut: PieceCut) -> Optional[PiecedArray]:
    """A synchronous take's cut of one leaf at its turn in the stage: the
    pieces the fork would have written (``cut`` from :func:`leaf_cut`), by
    the fork's own movers in a program of the one leaf, so that the leaf
    crosses under the pieces' window, lands row-major, and is gathered into
    host pages the take has used before (``io_preparers.array``, the
    caller). The
    caller bounds the HBM the pieces hold (``d2h.CUT_WINDOW_BYTES``) and
    leaves the leaf whole where the device has no room for them (an
    allocation failure raised here or, for the program's own temporaries,
    at a piece's resolve: ``is_oom_error``). None where the kernel
    compiler refuses the mover: the leaf then crosses whole too, as it did
    before this existed, and the take goes on."""
    try:
        (pieces,) = batch_copy_fn((arr.sharding,), (cut,), _STAGE_CUTS)([arr])
    except Exception as e:  # noqa: BLE001 - only the kernel's compiler degrades here
        if "Mosaic" not in str(e):
            raise
        give_up_cut((cut,), e)
        return None
    return PiecedArray(arr.shape, arr.dtype, arr.sharding, pieces, cut.ranges)
