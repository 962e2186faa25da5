"""The transfer grain (``d2h.PIECE_BYTES``): a forked leaf over the piece size
leaves the fork as row-range pieces, cut bit for bit by a DMA inside the one
fork program, or, where its shape is off the HBM tiling, re-laid there in
integers; the lanes move the pieces under their own window into one host
buffer a leaf, and what is hashed, written and committed is what the
whole-leaf path writes. Leaves no mover takes go whole, as before.
"""

import asyncio
import math
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, d2h, device_programs, io_preparer, prepare_cache
from torchsnapshot_tpu.device_programs import PiecedArray, device_piece_cut, piece_row_ranges
from torchsnapshot_tpu.io_preparers.array import ArrayBufferStager, ArrayIOPreparer
from torchsnapshot_tpu.manifest import entry_to_dict
from torchsnapshot_tpu.parallel.coordinator import get_coordinator
from torchsnapshot_tpu.scheduler import _WritePipeline
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin
from torchsnapshot_tpu.utils import knobs
from torchsnapshot_tpu.utils.lru import BoundedLRU

PIECE = 64 * 1024
WINDOW = 160 * 1024  # two pieces and a half
WHOLE_WINDOW = 4 * 1024 * 1024


@pytest.fixture(autouse=True)
def _debug_ledger():
    with knobs.override_debug_ledger(True):
        yield


@pytest.fixture(autouse=True)
def _fresh_cache():
    prepare_cache.reset(get_coordinator())
    yield
    prepare_cache.reset(get_coordinator())


@pytest.fixture
def grain(monkeypatch):
    """A piece of 64 KiB under a window of 160 KiB, so leaves of a few
    hundred KiB run the path the chip runs at tens of MiB."""
    monkeypatch.setattr(d2h, "PIECE_BYTES", PIECE)
    monkeypatch.setattr(d2h, "PIECE_WINDOW_BYTES", WINDOW)
    monkeypatch.setattr(d2h, "HINT_WINDOW_BYTES", WHOLE_WINDOW)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _every_bf16(tiles: int = 4):
    import jax.numpy as jnp

    bits = np.tile(np.arange(1 << 16, dtype=np.uint16), tiles)
    return bits.reshape(-1, 256).view(jnp.bfloat16)


def _patterned_state():
    """Leaves over the piece size of every kind the cut takes, with the bit
    patterns a device program could rewrite, and leaves that go whole."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(39)
    f32 = rng.integers(0, 1 << 32, size=128 * 512, dtype=np.uint64).astype(np.uint32)
    # Denormals, infinities and NaN payloads of both signs among the noise.
    f32[:8] = [1, 0x007FFFFF, 0x7F800001, 0x7FC00001, 0xFFFFFFFF, 0x80000001, 0x7F800000, 0xFF800000]
    host = {
        "every_bf16": _every_bf16(),  # 512 KiB: eight pieces
        "stack_bf16": _every_bf16(2).reshape(8, 64, 256),  # cut between slabs
        "f32": f32.view(np.float32).reshape(128, 512),
        "i8": np.tile(np.arange(256, dtype=np.uint8), 1024).view(np.int8).reshape(256, 1024),
        "flags": (rng.integers(0, 2, size=(256, 1024)) > 0),  # bool: whole
        "odd_cols": rng.standard_normal((500, 100)).astype(np.float32),  # no lanes' worth of rows divides 500: whole
        "vector": rng.standard_normal(1 << 16).astype(np.float32),  # 1-D: whole
        "small": np.arange(7, dtype=np.int32),
    }
    return host, {k: jax.device_put(v) for k, v in host.items()}


def _metrics():
    return Snapshot.last_telemetry.metrics.as_dict()


def _objects(path: str) -> dict:
    """Every file of a snapshot by relative path, the take's own telemetry
    and journal left out."""
    out = {}
    for root, _dirs, files in os.walk(path):
        for name in files:
            full = os.path.join(root, name)
            rel = os.path.relpath(full, path)
            if rel.startswith(".telemetry") or rel.startswith(".journal"):
                continue
            with open(full, "rb") as f:
                out[rel] = f.read()
    return out


# ------------------------------------------------------------- the row ranges


def _covers_once(ranges, rows: int) -> None:
    assert ranges[0][0] == 0 and ranges[-1][1] == rows
    for (a0, a1), (b0, _b1) in zip(ranges, ranges[1:]):
        assert a0 < a1 == b0


@pytest.mark.parametrize(
    "shape,dtype",
    [
        ((1000, 128), "float32"),  # 125 units of 8 rows: an odd count
        ((1048, 256), "bfloat16"),  # 131 units
        ((7, 64, 128), "float32"),  # an odd count of slabs
        ((3, 8, 65536), "int8"),  # a single slab is over the piece size
        ((24, 16384), "float32"),  # a single unit of 8 rows is over it
    ],
)
def test_row_ranges_cover_a_leaf_once(grain, shape, dtype) -> None:
    ranges, relaid, _ = piece_row_ranges(shape, np.dtype(dtype))
    assert not relaid and len(ranges) >= 2
    _covers_once(ranges, shape[0])
    unit = 8 if len(shape) == 2 else 1
    assert all(r0 % unit == 0 and r1 % unit == 0 for r0, r1 in ranges)
    row_bytes = int(np.prod(shape[1:])) * np.dtype(dtype).itemsize
    for r0, r1 in ranges:
        assert (r1 - r0) * row_bytes <= PIECE or r1 - r0 == unit


@pytest.mark.parametrize(
    "shape,dtype,why",
    [
        ((64, 256), "float32", "not over the piece size"),
        ((1, 1 << 20), "float32", "one row"),
        ((8, 1 << 16), "float32", "one unit of 8 rows"),
        ((1 << 18,), "float32", "one dimension"),
        ((1023, 100), "float32", "off the tiling, and 32 rows fill whole lanes: 1023 is no multiple"),
        ((15, 12, 1000), "float32", "off the tiling, and 4 slabs fill whole lanes: 15 is no multiple"),
        ((2, 70000), "int8", "off the tiling, and 8 rows fill whole lanes: it has two"),
        ((1024, 128), "bool", "the DMA takes no bool"),
        ((1024, 128), "float16", "float16 never forks"),
        ((1024, 128), "float64", "the DMA takes no 64-bit type"),
        ((1024, 256), "float8_e4m3fn", "float8 never forks"),
    ],
)
def test_leaves_the_cut_does_not_take_go_whole(grain, shape, dtype, why) -> None:
    import jax.numpy as jnp

    assert piece_row_ranges(shape, jnp.dtype(dtype)) is None, why


# ------------------------------------------------------------------ the fork


def test_fork_pieces_big_leaves_in_one_program_and_keeps_every_bit(grain, monkeypatch) -> None:
    import jax

    host, state = _patterned_state()
    built = []
    real = device_programs.batch_copy_fn

    def counting(shardings, cuts):
        built.append(cuts)
        return real(shardings, cuts)

    monkeypatch.setattr(device_programs, "batch_copy_fn", counting)
    names = list(state)
    copies = dict(zip(names, io_preparer._defensive_device_copies([state[n] for n in names])))
    assert len(built) == 1  # one program for the group, pieces and whole copies alike
    pieced = {n for n, c in copies.items() if isinstance(c, PiecedArray)}
    assert pieced == {"every_bf16", "stack_bf16", "f32", "i8"}
    for n in names:
        c = copies[n]
        if n not in pieced:
            assert isinstance(c, jax.Array)
            assert np.asarray(c).tobytes() == host[n].tobytes(), n
            continue
        assert c.shape == host[n].shape and c.dtype == host[n].dtype
        assert c.sharding == state[n].sharding and c.nbytes == host[n].nbytes
        assert [p.shape[0] for p in c.pieces] == [r1 - r0 for r0, r1 in c.ranges]
        assert c.ranges == piece_row_ranges(c.shape, c.dtype).ranges
        assert all(p.nbytes <= PIECE for p in c.pieces)
        got = np.concatenate([np.asarray(p) for p in c.pieces])
        assert got.tobytes() == host[n].tobytes(), n
        assert io_preparer.classify(c, 1) == "array"


def test_sharded_replicated_and_offsize_leaves_fork_whole(grain) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    big = jax.random.normal(jax.random.PRNGKey(0), (1024, 256), jnp.float32)
    leaves = [
        jax.device_put(big, NamedSharding(mesh, P("x"))),
        jax.device_put(big, NamedSharding(mesh, P())),
        big[:32],  # under the piece size
    ]
    copies = io_preparer._defensive_device_copies(leaves)
    assert all(isinstance(c, jax.Array) for c in copies)
    assert isinstance(io_preparer._defensive_device_copies([big])[0], PiecedArray)


def test_a_leaf_chunked_into_storage_objects_forks_whole(grain) -> None:
    """A leaf over the chunking knob is cut into storage objects by device
    slices of its copy: the fork leaves it whole for them."""
    import jax
    import jax.numpy as jnp

    big = jax.random.normal(jax.random.PRNGKey(0), (1024, 256), jnp.float32)
    with knobs.override_max_chunk_size_bytes(256 * 1024):
        assert isinstance(io_preparer._defensive_device_copies([big])[0], jax.Array)


def _fork_program_cases():
    """A leaf each way it leaves the device, and whether the stage's
    program of one leaf (``cut_in_stage``'s cache) is the one built."""
    f32 = np.arange(128 * 512, dtype=np.float32).reshape(128, 512)
    return {
        "whole": (np.arange(7, dtype=np.int32), False),
        "dma_cut": (f32, False),
        "relaid": (_every_bf16()[:1000].reshape(-1, 100), False),  # (2560, 100): off the tiling
        "stage_one_leaf": (f32, True),
    }


@pytest.mark.parametrize("case", sorted(_fork_program_cases()))
def test_the_fork_program_is_the_module_the_benchmark_reads(grain, case) -> None:
    """``perfbench/metrics/fork_roofline.json`` finds the fork's device time
    in a trace by the XLA module's name, ``jit__lambda``: the fork is one
    ``jax.jit`` of a ``lambda``, whatever movers it holds. A refactor that
    names the function zeroes ``fork_roofline`` in six cells in silence, so
    the name is held here until both sides agree on another (ROADMAP D17)."""
    import jax

    host, in_stage = _fork_program_cases()[case]
    x = jax.device_put(host)
    cut = device_programs.leaf_cut(x)
    assert (cut is None) == (case == "whole")
    assert cut is None or cut.relaid == (case == "relaid")
    cache = device_programs._STAGE_CUTS if in_stage else None
    fn = device_programs.batch_copy_fn((x.sharding,), (cut,), cache)
    assert fn.lower([x]).compile().as_text().startswith("HloModule jit__lambda,")


def test_a_stage_cut_is_kept_apart_from_the_forks(grain, monkeypatch) -> None:
    """Two caches, two objects: a synchronous take's programs of one leaf
    land in ``_STAGE_CUTS`` and push no fork out of ``_BATCH_COPIES``."""
    import jax

    monkeypatch.setattr(device_programs, "_STAGE_CUTS", BoundedLRU(64))
    x = jax.device_put(np.arange(128 * 512, dtype=np.float32).reshape(128, 512))
    cut = device_programs.leaf_cut(x)
    forks_before = len(device_programs._BATCH_COPIES)
    pieced = device_programs.cut_in_stage(x, cut)
    assert isinstance(pieced, PiecedArray) and pieced.ranges == cut.ranges
    assert device_programs._STAGE_CUTS is not device_programs._BATCH_COPIES
    assert len(device_programs._STAGE_CUTS) == 1
    assert len(device_programs._BATCH_COPIES) == forks_before
    got = np.concatenate([np.asarray(p) for p in pieced.pieces])
    assert got.tobytes() == np.asarray(x).tobytes()


def test_a_refusal_by_the_kernel_compiler_forks_whole(grain, tmp_path, monkeypatch, caplog) -> None:
    import jax

    def refuse(x, ranges, interpret):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: no such tiling")

    monkeypatch.setattr(device_programs, "_cut_rows", refuse)
    monkeypatch.setattr(device_programs, "_BATCH_COPIES", BoundedLRU())  # no program built before
    monkeypatch.setattr(device_programs, "_dma_cut_refused", False)
    monkeypatch.setattr(device_programs, "_relay_cut_refused", False)
    host, state = _patterned_state()
    path = str(tmp_path / "ck")
    with caplog.at_level("WARNING"):
        Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    assert "row cut was refused" in caplog.text
    assert device_programs._dma_cut_refused and not device_programs._relay_cut_refused
    assert _metrics()["d2h.pieces"] == 0
    copies = io_preparer._defensive_device_copies(list(state.values()))
    assert all(isinstance(c, jax.Array) for c in copies)
    _assert_restores(path, host, state)


# ------------------------------------------------- through take and restore


def _assert_restores(path, host, state) -> None:
    import jax.numpy as jnp

    target = StateDict(**{k: jnp.zeros_like(v) for k, v in state.items()})
    Snapshot(path).restore({"m": target})
    for k, want in host.items():
        got = np.asarray(target[k])
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), k
    assert Snapshot(path).verify() == {}


def test_pieced_take_writes_what_the_whole_leaf_path_writes(grain, tmp_path, monkeypatch) -> None:
    """Every object, checksum sidecar and manifest entry of a pieced take is
    byte for byte what the take writes with no leaf pieced (the parent's
    path), the code that wrote the second restores the first bit for bit,
    and the counters say which ran."""
    host, state = _patterned_state()
    pieced_path, whole_path = str(tmp_path / "pieced"), str(tmp_path / "whole")
    Snapshot.async_take(pieced_path, {"m": StateDict(**state)}).wait()
    pieced = _metrics()
    pieced_names = ("every_bf16", "stack_bf16", "f32", "i8")
    assert pieced["d2h.pieced_bytes"] == sum(host[n].nbytes for n in pieced_names)
    assert pieced["d2h.pieces"] == sum(
        len(piece_row_ranges(host[n].shape, host[n].dtype).ranges) for n in pieced_names
    )
    assert pieced["capture.fork_relaid_leaves"] == pieced["capture.fork_relaid_bytes"] == 0
    assert pieced["stage.host_relaid_bytes"] == 0
    assert pieced["d2h.bytes"] == sum(v.nbytes for v in host.values())  # once a byte
    assert pieced["capture.forked_leaves"] == len(host)
    assert "capture.host_captured_bytes" not in pieced

    monkeypatch.setattr(d2h, "PIECE_BYTES", 1 << 40)
    prepare_cache.reset(get_coordinator())
    Snapshot.async_take(whole_path, {"m": StateDict(**state)}).wait()
    whole = _metrics()
    assert whole["d2h.pieces"] == 0 and whole["d2h.pieced_bytes"] == 0
    assert whole["d2h.bytes"] == pieced["d2h.bytes"]

    a, b = _objects(pieced_path), _objects(whole_path)
    assert sorted(a) == sorted(b)
    assert ".snapshot_metadata" in a and any(r.startswith(".checksums") for r in a)
    for rel in a:
        assert a[rel] == b[rel], rel
    manifest_a = Snapshot(pieced_path).get_manifest()
    manifest_b = Snapshot(whole_path).get_manifest()
    assert {k: entry_to_dict(v) for k, v in manifest_a.items()} == {
        k: entry_to_dict(v) for k, v in manifest_b.items()
    }
    entry = manifest_a["0/m/every_bf16"]
    assert entry.location == "0/m/every_bf16" and list(entry.shape) == [1024, 256]
    # Still with no leaf pieced: the parent's reader on the change's snapshot.
    _assert_restores(pieced_path, host, state)
    _assert_restores(whole_path, host, state)


def test_a_synchronous_take_pieces_what_the_fork_would(grain, tmp_path) -> None:
    """No fork, the same pieces (PR 48; it pieced nothing before): the stage
    cuts each big leaf at its turn (``tests/test_sync_take_stage.py``)."""
    host, state = _patterned_state()
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(**state)})
    metrics = _metrics()
    pieced_names = ("every_bf16", "stack_bf16", "f32", "i8")
    assert metrics["d2h.pieced_bytes"] == sum(host[n].nbytes for n in pieced_names)
    assert metrics["d2h.pieces"] == sum(
        len(piece_row_ranges(host[n].shape, host[n].dtype).ranges) for n in pieced_names
    )
    assert metrics["stage.sync_cut_leaves"] == len(pieced_names)
    assert "capture.forked_leaves" not in metrics
    assert metrics["d2h.bytes"] == sum(v.nbytes for v in host.values())
    _assert_restores(path, host, state)


def test_small_float_leaves_are_captured_through_the_host_not_pieced(grain, tmp_path) -> None:
    import jax
    import jax.numpy as jnp

    bits = np.tile(np.arange(1 << 16, dtype=np.uint16), 4).reshape(-1, 256)
    host = {
        "f16": bits.view(np.float16),
        "f8": np.tile(np.arange(256, dtype=np.uint8), 1024).reshape(-1, 256).view(jnp.float8_e4m3fn),
        "bf16": bits.view(jnp.bfloat16),
    }
    state = {k: jax.device_put(v) for k, v in host.items()}
    path = str(tmp_path / "ck")
    Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    metrics = _metrics()
    assert metrics["capture.dtype_captured_leaves"] == 2
    assert metrics["d2h.pieced_bytes"] == host["bf16"].nbytes
    _assert_restores(path, host, state)


def test_the_window_counts_pieces_and_whole_leaves_as_before(grain, tmp_path) -> None:
    """Pieces are admitted under the pieces' window (more than one in flight
    never exceeds it); a take of whole leaves only is admitted under the
    whole-leaf window as it was."""
    import jax
    import jax.numpy as jnp

    pieced = {
        f"w{i}": jax.random.normal(jax.random.PRNGKey(i), (1024, 128), jnp.float32)
        for i in range(3)
    }  # 512 KiB each: eight pieces of 64 KiB
    Snapshot.async_take(str(tmp_path / "p"), {"m": StateDict(**pieced)}).wait()
    metrics = _metrics()
    assert metrics["d2h.pieces"] == 24
    assert metrics["d2h.hinted_ahead_hwm_bytes"] == 2 * PIECE  # 160 KiB holds two
    assert metrics["d2h.window_waits"] == 22

    whole = {
        f"v{i}": jax.random.normal(jax.random.PRNGKey(i), (500, 100), jnp.float32)
        for i in range(30)
    }  # 200 KB each, not cut (100 columns, 500 rows): twenty fit under 4 MiB
    Snapshot.async_take(str(tmp_path / "w"), {"m": StateDict(**whole)}).wait()
    metrics = _metrics()
    assert metrics["d2h.pieces"] == 0
    assert metrics["d2h.hinted_ahead_hwm_bytes"] == 20 * 500 * 100 * 4
    assert metrics["d2h.window_waits"] == 10


# ---------------------------------------------------------- the re-laying cut

# Scale models of the shapes the DMA cut refuses: an expert stack whose
# minor dimension is no multiple of 128 and a fused projection whose width
# is none ((16, 2688, 1856) and (2688, 10304) in the benchmark's ninth
# cell), rows no multiple of 8, a slab's rows no multiple of 8.
RELAID_SHAPES = [(16, 168, 116), (168, 704), (1001, 128), (1024, 100), (16, 12, 1024)]
RELAID_DTYPES = ["bfloat16", "float32", "int8", "uint16"]


def _bit_patterns(shape, dtype):
    """Every pattern of an 8- or 16-bit dtype, tiled; for float32 random
    words with denormals, infinities and NaN payloads of both signs."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = int(np.prod(shape))
    if dt.itemsize < 4:
        words = np.resize(np.arange(1 << (8 * dt.itemsize), dtype=f"uint{8 * dt.itemsize}"), n)
    else:
        words = np.random.default_rng(46).integers(0, 1 << 32, size=n, dtype=np.uint64).astype(np.uint32)
        words[:8] = [1, 0x007FFFFF, 0x7F800001, 0x7FC00001, 0xFFFFFFFF, 0x80000001, 0x7F800000, 0xFF800000]
    return words.view(dt).reshape(shape)


def _relaid_state():
    import jax

    host = {
        f"{dtype}_{'x'.join(map(str, shape))}": _bit_patterns(shape, dtype)
        for shape in RELAID_SHAPES
        for dtype in RELAID_DTYPES
    }  # the smallest, int8 (1024, 100), is over the piece size
    host["aligned"] = _bit_patterns((1024, 128), "bfloat16")  # the DMA's
    host["small"] = np.arange(7, dtype=np.int32)
    return host, {k: jax.device_put(v) for k, v in host.items()}


@pytest.mark.parametrize("dtype", RELAID_DTYPES)
@pytest.mark.parametrize("shape", RELAID_SHAPES)
def test_relaid_ranges_cover_a_leaf_once_in_whole_lanes(grain, shape, dtype) -> None:
    import jax.numpy as jnp

    itemsize = jnp.dtype(dtype).itemsize
    cut = piece_row_ranges(shape, jnp.dtype(dtype))
    assert cut.relaid and len(cut.ranges) >= 2
    _covers_once(cut.ranges, shape[0])
    row = int(np.prod(shape[1:]))
    unit = 128 // math.gcd(row, 128)  # the fewest rows that fill whole lanes
    for r0, r1 in cut.ranges:
        assert (r1 - r0) % unit == 0 and (r1 - r0) * row % 128 == 0
        assert (r1 - r0) * row * itemsize <= PIECE or r1 - r0 == unit


@pytest.mark.parametrize("dtype", RELAID_DTYPES)
@pytest.mark.parametrize("shape", [(16, 168, 116), (168, 704), (1001, 128)])
def test_the_relaying_cut_keeps_every_bit(grain, shape, dtype) -> None:
    """All 65,536 bfloat16 patterns, every int8 and uint16, float32 with
    NaN payloads and denormals: each piece's host copy is C-contiguous and
    the pieces together are the C-order bytes of the leaf."""
    import jax
    import jax.numpy as jnp

    host = _bit_patterns(shape, dtype)
    (copy,) = io_preparer._defensive_device_copies([jax.device_put(host)])
    assert isinstance(copy, PiecedArray) and copy.shape == host.shape and copy.dtype == host.dtype
    cut = piece_row_ranges(shape, jnp.dtype(dtype))
    assert cut.relaid and copy.ranges == cut.ranges
    row_bytes = host.nbytes // shape[0]
    pieces = [np.asarray(p) for p in copy.pieces]
    assert all(p.flags["C_CONTIGUOUS"] and p.shape[-1] == 128 for p in pieces)
    assert [p.nbytes for p in pieces] == [(r1 - r0) * row_bytes for r0, r1 in cut.ranges]
    # bfloat16 leaves the fork as its bits; XLA moves the others as they are.
    assert all(p.dtype == (np.uint16 if dtype == "bfloat16" else host.dtype) for p in pieces)
    assert b"".join(p.tobytes() for p in pieces) == host.tobytes()


def test_a_relaid_take_writes_what_the_whole_leaf_path_writes(grain, tmp_path, monkeypatch) -> None:
    """The same storage objects, checksums and manifest as the take with no
    leaf pieced, and the counters say which mover wrote what."""
    host, state = _relaid_state()
    relaid_path, whole_path = str(tmp_path / "relaid"), str(tmp_path / "whole")
    Snapshot.async_take(relaid_path, {"m": StateDict(**state)}).wait()
    relaid = _metrics()
    names = [n for n in host if n not in ("aligned", "small")]
    assert len(names) == len(RELAID_SHAPES) * len(RELAID_DTYPES)
    cuts = {n: piece_row_ranges(host[n].shape, host[n].dtype) for n in host}
    assert all(cuts[n].relaid for n in names) and not cuts["aligned"].relaid
    assert relaid["capture.fork_relaid_leaves"] == len(names)
    assert relaid["capture.fork_relaid_bytes"] == sum(host[n].nbytes for n in names)
    assert relaid["capture.forked_leaves"] == len(host)
    assert relaid["d2h.pieces"] == sum(len(c.ranges) for c in cuts.values() if c)
    assert relaid["d2h.pieced_bytes"] == sum(host[n].nbytes for n in names + ["aligned"])
    assert relaid["d2h.bytes"] == sum(v.nbytes for v in host.values())
    assert relaid["stage.host_relaid_bytes"] == 0

    monkeypatch.setattr(d2h, "PIECE_BYTES", 1 << 40)
    prepare_cache.reset(get_coordinator())
    Snapshot.async_take(whole_path, {"m": StateDict(**state)}).wait()
    whole = _metrics()
    assert whole["d2h.pieces"] == 0 and whole["capture.fork_relaid_leaves"] == 0
    a, b = _objects(relaid_path), _objects(whole_path)
    assert sorted(a) == sorted(b) and any(r.startswith(".checksums") for r in a)
    for rel in a:
        assert a[rel] == b[rel], rel
    assert {k: entry_to_dict(v) for k, v in Snapshot(relaid_path).get_manifest().items()} == {
        k: entry_to_dict(v) for k, v in Snapshot(whole_path).get_manifest().items()
    }
    _assert_restores(relaid_path, host, state)


def test_a_strided_host_copy_is_counted_where_the_stage_relays_it(grain, tmp_path) -> None:
    """What is left for the host: a leaf that reaches the stager in another
    order than row-major (here a Fortran-ordered host array; on the chip a
    synchronous take's or a small leaf's device order) is made contiguous
    there, and ``stage.host_relaid_bytes`` counts it."""
    strided = np.asfortranarray(np.arange(96 * 50, dtype=np.float32).reshape(96, 50))
    straight = np.arange(64, dtype=np.float32)
    path = str(tmp_path / "ck")
    Snapshot.take(path, {"m": StateDict(strided=strided, straight=straight)})
    assert _metrics()["stage.host_relaid_bytes"] == strided.nbytes
    assert Snapshot(path).read_object("0/m/strided").tobytes() == np.ascontiguousarray(strided).tobytes()

    class StridedPiece:
        """A piece whose host copy comes in the device's order."""

        def __init__(self, host):
            self.host, self.deleted = host, False

        def __array__(self, dtype=None, copy=None):
            return self.host

        def delete(self):
            self.deleted = True

    times = d2h.StageTimes()
    piece = StridedPiece(strided)
    into = np.empty(strided.nbytes, np.uint8)
    d2h.resolve_on_host(piece, into, times)
    assert piece.deleted and times.host_relaid_bytes == strided.nbytes
    assert into.tobytes() == np.ascontiguousarray(strided).tobytes()
    d2h.resolve_on_host(StridedPiece(straight), np.empty(straight.nbytes, np.uint8), times)
    assert times.host_relaid_bytes == strided.nbytes


def test_a_refusal_of_the_relaying_cut_keeps_the_dma_cut(grain, tmp_path, monkeypatch, caplog) -> None:
    """The kernel compiler refuses the re-laying cut's DMA: those leaves
    fork whole from then on, the aligned ones are still cut."""

    def refuse(x, interpret):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: no such tiling")

    monkeypatch.setattr(device_programs, "_bits_by_dma", refuse)
    monkeypatch.setattr(device_programs, "_BATCH_COPIES", BoundedLRU())
    monkeypatch.setattr(device_programs, "_dma_cut_refused", False)
    monkeypatch.setattr(device_programs, "_relay_cut_refused", False)
    host, state = _relaid_state()
    path = str(tmp_path / "ck")
    with caplog.at_level("WARNING"):
        Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    assert "re-laying cut was refused" in caplog.text
    assert device_programs._relay_cut_refused and not device_programs._dma_cut_refused
    metrics = _metrics()
    assert metrics["capture.fork_relaid_leaves"] == 0
    assert metrics["d2h.pieced_bytes"] == host["aligned"].nbytes
    _assert_restores(path, host, state)


def _never_asked():
    raise AssertionError("the device's order is asked only of a bfloat16 leaf to re-lay")


@pytest.mark.parametrize(
    "shape, dtype, order, on_tpu, want",
    [
        # The TPU's kernel compiler aborts the process on a DMA off the HBM
        # tiling: in the device's own order the minor dimension has to be a
        # multiple of 128 and the one before it of 8, or the leaf stays whole.
        ((1001, 128), "bfloat16", (0, 1), True, None),  # 1001 rows before the lanes
        ((1001, 128), "bfloat16", (0, 1), False, (0, 1)),  # the interpreter takes any shape
        ((16, 256, 116), "bfloat16", (0, 2, 1), True, None),  # 116 % 8
        ((16, 250, 120), "bfloat16", (0, 2, 1), True, None),  # 250 % 128
        ((16, 256, 120), "bfloat16", (0, 2, 1), True, (0, 2, 1)),  # 120: whole tiles of 8, not of 16
        ((256, 1000), "bfloat16", (1, 0), True, (1, 0)),
        ((256, 1004), "bfloat16", (1, 0), True, None),  # 1004 % 8
        ((256, 1000), "bfloat16", (0, 1), True, None),  # held row-major: 1000 % 128
        ((16, 256, 120), "bfloat16", (2, 0, 1), True, (2, 0, 1)),  # 16 slabs before 256 lanes
        # XLA moves these bit for bit: re-laid with no kernel, whatever the order.
        ((1001, 128), "float32", None, True, "xla"),
        ((16, 256, 116), "int8", None, True, "xla"),
        ((256, 1004), "uint16", None, True, "xla"),
        # On the tiling: the DMA cut, and no order is asked for.
        ((1024, 128), "bfloat16", None, True, "dma"),
        ((16, 16, 256), "float32", None, True, "dma"),
        # What the rule sends whole stays whole.
        ((1023, 100), "bfloat16", None, True, "whole"),
        ((64, 128), "bfloat16", None, True, "whole"),
    ],
)
def test_the_cut_of_a_leaf_as_the_device_holds_it(grain, shape, dtype, order, on_tpu, want) -> None:
    """``device_piece_cut`` is what stands between a take and the kernel
    compiler's abort: a pure function of the leaf's shape, dtype, the
    device's order of its dimensions and the platform."""
    import jax.numpy as jnp

    rule = piece_row_ranges(shape, jnp.dtype(dtype))
    cut = device_piece_cut(shape, jnp.dtype(dtype), (lambda: order) if order else _never_asked, on_tpu)
    if want == "whole":
        assert rule is None and cut is None
    elif want == "dma":
        assert cut == rule and not cut.relaid and cut.order is None
    elif want == "xla":
        assert cut == rule and cut.relaid and cut.order is None
    elif want is None:
        assert rule is not None and rule.relaid and cut is None  # the guard alone refuses it
    else:
        assert cut == rule._replace(order=want)



# ------------------------------------------------------------------- failures


def _forked_pipeline(storage, leaves):
    copies = io_preparer._defensive_device_copies(leaves)
    assert all(isinstance(c, PiecedArray) for c in copies)
    reqs = []
    for i, leaf in enumerate(copies):
        _entry, leaf_reqs = ArrayIOPreparer.prepare_write(f"obj{i}", leaf, is_async_snapshot=True)
        reqs.extend(leaf_reqs)
    return _WritePipeline(reqs, storage, memory_budget_bytes=10**9, rank=0), copies


def _drive(pipeline):
    async def go():
        await pipeline.run_until_staged()
        await asyncio.wait_for(pipeline.run_to_completion(), timeout=30)

    _run(go())


def _big_leaves(count: int):
    import jax
    import jax.numpy as jnp

    arrs = [
        jax.random.normal(jax.random.PRNGKey(i), (1024, 128), jnp.float32)
        for i in range(count)
    ]
    jax.block_until_ready(arrs)
    return arrs


def _balanced(pipeline) -> None:
    assert pipeline.budget_balanced, (pipeline.budget.available, pipeline.budget.total)
    lanes = pipeline._staging_ctx.lanes
    assert all(w.ahead == 0 and not w.waiting for w in lanes._windows.values())


def test_a_failing_piece_mid_leaf_commits_nothing_and_balances(grain, tmp_path, monkeypatch) -> None:
    """The fifth piece of the first leaf fails at its hint with pieces of
    three leaves in line behind it: the error propagates, the other pieces
    are cancelled, window and budget balance, nothing is committed."""
    calls = []
    real = d2h.hint_copy_to_host

    def failing(arr):
        calls.append(arr)
        if len(calls) == 5:
            raise RuntimeError("piece exploded")
        real(arr)

    monkeypatch.setattr(d2h, "hint_copy_to_host", failing)
    storage = MemoryStoragePlugin()
    pipeline, _ = _forked_pipeline(storage, _big_leaves(3))
    with pytest.raises(RuntimeError, match="piece exploded"):
        _drive(pipeline)
    assert 5 <= len(calls) < 24
    assert ".checksums.0" not in storage.objects
    _balanced(pipeline)

    calls.clear()
    state = {f"w{i}": a for i, a in enumerate(_big_leaves(3))}
    path = str(tmp_path / "ck")
    pending = Snapshot.async_take(path, {"m": StateDict(**state)})
    with pytest.raises(RuntimeError, match="piece exploded"):
        pending.wait()
    assert not os.path.exists(os.path.join(path, ".snapshot_metadata"))


def test_an_abort_with_pieces_in_line_balances(grain) -> None:
    class FailingWriteStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            raise OSError("write exploded")

    storage = FailingWriteStorage()
    pipeline, _ = _forked_pipeline(storage, _big_leaves(4))
    with pytest.raises(OSError, match="write exploded"):
        _drive(pipeline)
    assert not storage.objects
    _balanced(pipeline)


def test_a_landed_leaf_holds_no_device_buffer(grain) -> None:
    """Each piece is dropped by the lane that landed it: once the leaf is
    staged, none of its device buffers is left."""
    storage = MemoryStoragePlugin()
    pipeline, copies = _forked_pipeline(storage, _big_leaves(2))
    _drive(pipeline)
    assert all(p.is_deleted() for c in copies for p in c.pieces)
    assert len([k for k in storage.objects if k.startswith("obj")]) == 2
    _balanced(pipeline)


def test_a_stager_outside_a_pipeline_gathers_its_pieces(grain) -> None:
    leaf = _big_leaves(1)[0]
    want = np.asarray(leaf).tobytes()
    (copy,) = io_preparer._defensive_device_copies([leaf])
    entry, _ = ArrayIOPreparer.prepare_write("obj", copy)
    buf = _run(ArrayBufferStager(copy, entry).stage_buffer())
    assert bytes(buf) == want


# ------------------------------------------------- caches and HBM pressure


def test_a_second_take_through_the_prepared_cache_stages_the_new_pieces(grain, tmp_path) -> None:
    import jax
    import jax.numpy as jnp

    def state(seed):
        return {
            "m": StateDict(
                w=jax.random.normal(jax.random.PRNGKey(seed), (1024, 128), jnp.float32),
                b=jnp.full((16,), seed, jnp.float32),
            )
        }

    coord = get_coordinator()
    first, second = state(1), state(2)
    Snapshot.async_take(str(tmp_path / "a"), first).wait()
    assert _metrics()["d2h.pieces"] == 8
    Snapshot.async_take(str(tmp_path / "b"), second).wait()
    assert sum(prepare_cache.stats(coord)["hits"].values()) == 1
    assert _metrics()["d2h.pieces"] == 8
    target = StateDict(w=jnp.zeros((1024, 128), jnp.float32), b=jnp.zeros((16,), jnp.float32))
    Snapshot(str(tmp_path / "b")).restore({"m": target})
    assert np.asarray(target["w"]).tobytes() == np.asarray(second["m"]["w"]).tobytes()
    # Between takes the cached stagers pin nothing.
    for entry in getattr(coord, "_prepared_take_cache").values():
        assert not entry.in_use
        for reqs in entry.leaf_index.values():
            for req in reqs:
                if isinstance(req.buffer_stager, ArrayBufferStager):
                    assert req.buffer_stager.arr is None


def test_the_simulated_hbm_limit_still_bisects_and_host_captures(grain, tmp_path) -> None:
    """Room for two of four 512 KiB leaves: two fork (as pieces), two are
    captured through the host, and the snapshot is the same."""
    host = {f"w{i}": np.asarray(a) for i, a in enumerate(_big_leaves(4))}
    import jax

    state = {k: jax.device_put(v) for k, v in host.items()}
    path = str(tmp_path / "ck")
    with knobs.override_async_fork_hbm_limit_bytes(2 * 512 * 1024):
        Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
    metrics = _metrics()
    assert metrics["capture.forked_leaves"] == 2
    assert metrics["capture.host_captured_leaves"] == 2
    assert metrics["d2h.pieces"] == 16 and metrics["d2h.pieced_bytes"] == 2 * 512 * 1024
    _assert_restores(path, host, state)


# ------------------------------------------- the real shapes, for the v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _device_order(leaf):
    """The described chip's own order of ``leaf``'s dimensions, major to
    minor: what ``jax.Array.format`` says of a live array."""
    import jax
    import jax.numpy as jnp

    (formats,), _ = jax.jit(jnp.copy).lower(leaf).compile().input_formats
    return tuple(formats.layout.major_to_minor)


def _described_cut(leaf):
    """``device_programs.leaf_cut`` of a leaf that is only described."""
    return device_piece_cut(leaf.shape, leaf.dtype, lambda: _device_order(leaf), True)


@pytest.mark.parametrize(
    "shapes",
    [
        # pythia-6.9b: embedding, MLP, fused qkv, a square that is exactly the piece size
        [((50432, 4096), "bfloat16"), ((4096, 16384), "bfloat16"), ((4096, 12288), "bfloat16"),
         ((4096, 4096), "bfloat16"), ((4096,), "bfloat16")],
        # expert stacks, a vocabulary share of 18,992 rows, a float32 router
        [((10, 1536, 5120), "bfloat16"), ((32, 2048, 512), "bfloat16"), ((18992, 2048), "bfloat16"),
         ((2560, 6144), "bfloat16"), ((16384, 1024), "float32"), ((2560, 512), "float32")],
        # nemotron-3-nano: stacks of minor dimension 1856 and a fused in_proj of
        # 10304 columns, which the chip holds column first, beside their aligned
        # twins, a vocabulary slice, float32 of an odd width and a vector
        [((16, 2688, 1856), "bfloat16"), ((2688, 10304), "bfloat16"), ((16, 1856, 2688), "bfloat16"),
         ((2688, 4096), "bfloat16"), ((16384, 2688), "bfloat16"), ((4096, 1100), "float32"),
         ((2688,), "float32")],
        # lfm2-24b-a2b: three stacks a layer (minors 1536 and 2048), a fused in_proj, the dense
        # MLP, the tied table: every leaf over the piece size is on the tiling and takes the DMA
        # cut; a square of 8.4 MB, the three-tap convolution and the float32 router (which the
        # chip holds column first) and a gain go whole
        [((8, 2048, 1536), "bfloat16"), ((8, 1536, 2048), "bfloat16"), ((2048, 6144), "bfloat16"),
         ((2048, 11776), "bfloat16"), ((11776, 2048), "bfloat16"), ((8192, 2048), "bfloat16"),
         ((2048, 2048), "bfloat16"), ((2048, 1, 3), "bfloat16"), ((2048, 64), "float32"), ((64,), "float32")],
        # at the guard: bfloat16 the chip holds in partial tiles (rows no multiple of 8
        # before 128 lanes; 2052 % 8 before 4096 lanes) goes whole, handed to the kernel
        # compiler it aborts the process; whole tiles of 8 that are no multiple of 16
        # (1864, 10312) compile; float32 off the tiling needs no kernel
        [((100001, 128), "bfloat16"), ((4096, 2052), "bfloat16"), ((16, 2688, 1864), "bfloat16"),
         ((2688, 10312), "bfloat16"), ((100001, 128), "float32")],
    ],
)
def test_the_fork_of_real_shapes_compiles_for_the_v5e_with_no_temporary(one_chip, shapes) -> None:
    """The TPU's own compiler takes both movers at the sizes the benchmark's
    states have (it refuses a DMA off the HBM tiling, which interpret mode
    does not). Every piece leaves row-major, whatever order the chip holds
    its leaf in; the DMA cut holds no byte beyond its outputs, the
    re-laying cut at most one leaf's."""
    import jax
    import jax.numpy as jnp

    leaves = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip) for s, d in shapes]
    cuts = tuple(_described_cut(a) for a in leaves)
    assert any(cuts) and not all(cuts)
    relaid = [a for a, c in zip(leaves, cuts) if c and c.relaid]
    if shapes[0][0] == (16, 2688, 1856):
        assert [a.shape for a in relaid] == [(16, 2688, 1856), (2688, 10304), (4096, 1100)]
        assert [c.order for c in cuts[:2]] == [(0, 2, 1), (1, 0)]  # not row-major on the chip
    elif shapes[0][0] == (100001, 128):
        assert [c and c.order for c in cuts] == [None, None, (0, 2, 1), (1, 0), None]
        assert cuts[4].relaid and all(piece_row_ranges(a.shape, a.dtype).relaid for a in leaves)
        # Off the TPU nothing aborts and the interpreter takes them all.
        assert all(device_piece_cut(a.shape, a.dtype, lambda: (0, 1), False) for a in leaves[:2])
    else:
        assert not relaid
    compiled = (
        device_programs.batch_copy_fn(tuple(one_chip for _ in leaves), cuts).lower(leaves).compile()
    )
    for cut, formats in zip(cuts, compiled.output_formats):
        if cut is not None:
            assert len(formats) == len(cut.ranges)
            for f in formats:
                order = tuple(f.layout.major_to_minor)
                assert order == tuple(range(len(order))), (cut, order)
    stats = compiled.memory_analysis()
    nbytes = [int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves]
    biggest = max([n for n, a in zip(nbytes, leaves) if a in relaid], default=0)
    assert stats.temp_size_in_bytes <= biggest * 1.06  # the chip pads 1856 lanes to 1920
    # A whole copy comes in the chip's tiles: partial ones are padded to (8, 128).
    padding = 0
    for a, n, cut in zip(leaves, nbytes, cuts):
        if cut is None and len(a.shape) > 1:
            dims = [a.shape[i] for i in _device_order(a)]
            dims[-1], dims[-2] = -(-dims[-1] // 128) * 128, -(-dims[-2] // 8) * 8
            padding += int(np.prod(dims)) * a.dtype.itemsize - n
    assert sum(nbytes) <= stats.output_size_in_bytes <= sum(nbytes) + padding + 4096 * len(leaves)
    kernels = sum(1 for c in cuts if c and (not c.relaid or c.order is not None))
    assert compiled.as_text().count("tpu_custom_call") == kernels


@pytest.mark.parametrize(
    "shape, dtype, relaid, order",
    [
        # a synchronous take cuts a leaf at a time: nemotron's stacks and in_proj
        # the chip holds column first, their aligned twins, a vocabulary slice ...
        ((16, 2688, 1856), "bfloat16", True, (0, 2, 1)),
        ((2688, 10304), "bfloat16", True, (1, 0)),
        ((16, 1856, 2688), "bfloat16", False, None),
        ((16384, 2688), "bfloat16", False, None),
        # ... pythia's embedding and fused qkv, trinity's 151 MB stack, float32 off the tiling
        ((50432, 4096), "bfloat16", False, None),
        ((4096, 12288), "bfloat16", False, None),
        ((8, 3072, 3072), "bfloat16", False, None),
        ((5376, 1100), "float32", True, None),
    ],
)
def test_the_stage_cut_of_one_real_leaf_compiles_for_the_v5e(one_chip, shape, dtype, relaid, order) -> None:
    """``device_programs.cut_in_stage``'s program of one leaf, as the TPU's own
    compiler takes it: pieces row-major, the outputs the leaf's bytes, at
    most one leaf of temporaries where it is re-laid and none where a DMA
    moves it, so a leaf's cut holds at most twice its bytes of HBM while the
    program runs and once while its pieces cross."""
    import jax
    import jax.numpy as jnp

    leaf = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    cut = _described_cut(leaf)
    assert cut is not None and cut.relaid == relaid and cut.order == order
    compiled = device_programs.batch_copy_fn((one_chip,), (cut,), BoundedLRU()).lower([leaf]).compile()
    (formats,) = compiled.output_formats
    assert len(formats) == len(cut.ranges)
    for f in formats:
        assert tuple(f.layout.major_to_minor) == tuple(range(len(f.layout.major_to_minor)))
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    stats = compiled.memory_analysis()
    assert nbytes <= stats.output_size_in_bytes <= nbytes + 4096 * len(cut.ranges)
    assert stats.temp_size_in_bytes <= (nbytes * 1.06 if relaid else 0)
    assert compiled.as_text().count("tpu_custom_call") == (0 if relaid and order is None else 1)

