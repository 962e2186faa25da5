"""Zero-copy array (de)serialization with explicit dtype tables.

TPU-native analogue of the reference's ``serialization.py``
(``/root/reference/torchsnapshot/serialization.py:32-256``). The reference
round-trips ``torch.Tensor`` through the buffer protocol with a special path
for bfloat16 (which numpy can't express); here every accelerator dtype —
including bfloat16, the float8 variants, and int4 — is a first-class numpy
dtype via ``ml_dtypes``, and the uniform zero-copy path is a ``uint8`` view of
the contiguous array (plain ``memoryview(arr)`` raises for ml_dtypes custom
dtypes, so we never use it).

Serializer families:

- ``raw``: little-endian C-contiguous raw bytes. Used for every dtype in
  :data:`SUPPORTED_DTYPES`. Enables ranged reads (a byte range of the
  serialized buffer corresponds to a contiguous region of the flat array).
- ``raw_zstd`` / ``raw_zlib``: the raw byte stream compressed. Opt-in via
  ``TORCHSNAPSHOT_TPU_COMPRESSION`` — on links/stores slower than the
  compressor (cloud buckets, shared NVMe) the ~1.3-1.5x
  typical ratio on trained bf16/f32 weights directly multiplies effective
  write throughput and shrinks checkpoints. Payloads above
  ``TORCHSNAPSHOT_TPU_COMPRESSION_FRAME_BYTES`` are FRAMED — independent
  frames per fixed raw window, compressed frame sizes in a ``.ftab`` side
  object — so budgeted sub-reads stay byte-range addressable (they fetch and
  decompress only covering frames); smaller payloads are single blobs unless
  slab batching coalesces them into member-framed compressed slabs (one
  frame per member, compressed at staging time). The
  serializer is recorded per entry, so restore auto-detects regardless of
  current knobs, and a compressed and an uncompressed snapshot can coexist.
- ``pickle``: ``pickle`` of arbitrary Python objects. Fallback for
  non-array leaves (reference used ``torch.save``; we have no torch
  dependency on the TPU path).
"""

from __future__ import annotations

import zlib

import numpy as np

try:
    import ml_dtypes
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    ml_dtypes = None


class Serializer:
    RAW = "raw"
    RAW_ZSTD = "raw_zstd"
    RAW_ZLIB = "raw_zlib"
    PICKLE = "pickle"


# Serializers whose decoded payload is the raw little-endian byte stream
# (dtype strings come from the canonical table, shapes are exact).
RAW_FAMILY = (Serializer.RAW, Serializer.RAW_ZSTD, Serializer.RAW_ZLIB)


def is_raw_family(serializer: str) -> bool:
    return serializer in RAW_FAMILY


def raw_serializer_for_codec(codec: str) -> str:
    """Map a compression codec name ('none'|'zstd'|'zlib') to a serializer."""
    if codec == "zstd":
        return Serializer.RAW_ZSTD
    if codec == "zlib":
        return Serializer.RAW_ZLIB
    return Serializer.RAW


def codec_for_raw_serializer(serializer: str) -> str:
    """Inverse of :func:`raw_serializer_for_codec` (single owner of the
    mapping in both directions)."""
    if serializer == Serializer.RAW_ZSTD:
        return "zstd"
    if serializer == Serializer.RAW_ZLIB:
        return "zlib"
    return "none"


def ensure_codec_available(serializer: str) -> None:
    """Fail fast with an actionable error when an entry needs a codec this
    host lacks — called at read *planning* time, so a restore on a box
    without ``zstandard`` raises up front, not mid-pipeline in an executor
    thread (symmetric with the take-side check in ``knobs.get_compression``)."""
    if serializer == Serializer.RAW_ZSTD:
        try:
            import zstandard  # noqa: F401
        except ImportError as e:
            raise RuntimeError(
                "this snapshot's entries are zstd-compressed; restoring "
                "requires the 'zstandard' package"
            ) from e


def compress_payload(view, serializer: str, level: int) -> bytes:
    """Compress a raw byte view per ``serializer`` (RAW passes through)."""
    if serializer == Serializer.RAW_ZSTD:
        import zstandard

        return zstandard.ZstdCompressor(level=level).compress(view)
    if serializer == Serializer.RAW_ZLIB:
        return zlib.compress(view, level)
    return view


def decode_raw_payload(buf, serializer: str):
    """Undo :func:`compress_payload`: return the raw little-endian bytes.

    Decompressors take buffer-protocol objects directly — no defensive
    ``bytes()`` copy of a possibly-100 MB compressed payload.
    """
    if serializer == Serializer.RAW_ZSTD:
        import zstandard

        return zstandard.ZstdDecompressor().decompress(memoryview(buf))
    if serializer == Serializer.RAW_ZLIB:
        return zlib.decompress(memoryview(buf))
    return buf


def compress_framed(view, serializer: str, level: int, frame_bytes: int):
    """Compress ``view`` as a sequence of independent frames, each covering
    ``frame_bytes`` raw bytes (the last one short). Returns
    ``(payload_bytes, frame_sizes)`` — frames are simply concatenated, so a
    whole-payload read decodes with :func:`decode_framed_payload` and a
    ranged read of frames [i, j) is byte range
    ``[prefix[i], prefix[j])`` of the payload. Deterministic at a fixed
    codec version + level (same property incremental dedup relies on for
    single-blob payloads)."""
    n = memoryview(view).nbytes
    full, tail = divmod(n, frame_bytes)
    member_sizes = [frame_bytes] * full + ([tail] if tail else [])
    if not member_sizes:
        return b"", []
    return compress_member_framed(view, member_sizes, serializer, level)


def compress_member_framed(view, member_sizes, serializer: str, level: int):
    """Compress ``view`` with one independent frame per MEMBER (member i
    covers ``member_sizes[i]`` raw bytes). The slab-batching analogue of
    :func:`compress_framed`: frame boundaries coincide with member
    boundaries, so reading one member fetches + decodes exactly its own
    frames — no shared-frame decode amplification across a slab's members.
    Returns ``(payload_bytes, frame_sizes)``; a whole-payload read decodes
    with :func:`decode_framed_payload` like any framed stream."""
    mv = memoryview(view)
    parts = []
    sizes = []
    begin = 0
    for n in member_sizes:
        frame = compress_payload(mv[begin : begin + n], serializer, level)
        parts.append(frame)
        sizes.append(len(frame))
        begin += n
    assert begin == mv.nbytes, (begin, mv.nbytes)
    return b"".join(parts), sizes


def decode_framed_payload(buf, serializer: str):
    """Decode a concatenation of compression frames back to raw bytes.

    No frame table needed: zstd and zlib streams are self-terminating, so
    concatenated frames decode by reading across frame boundaries.
    """
    if serializer == Serializer.RAW_ZSTD:
        import zstandard

        # stream_reader takes buffer-protocol sources directly — wrapping in
        # BytesIO would copy the whole compressed payload first.
        reader = zstandard.ZstdDecompressor().stream_reader(
            memoryview(buf), read_across_frames=True
        )
        return reader.read()
    if serializer == Serializer.RAW_ZLIB:
        out = []
        rest = memoryview(buf)
        while rest.nbytes:
            d = zlib.decompressobj()
            out.append(d.decompress(rest))
            rest = memoryview(d.unused_data)
        return b"".join(out)
    return buf


def codec_library_versions() -> dict:
    """Versions of the codec libraries in use, recorded in snapshot metadata
    so incremental takes can warn when the base was compressed by a
    different library version (bitstream determinism — hence dedup hit
    rate — only holds within one version)."""
    versions = {"zlib": zlib.ZLIB_RUNTIME_VERSION}
    try:
        import zstandard

        versions["zstd"] = zstandard.__version__
    except ImportError:  # pragma: no cover - zstd optional
        pass
    return versions


def _build_dtype_table():
    table = {
        "bool": np.dtype(np.bool_),
        "uint8": np.dtype(np.uint8),
        "uint16": np.dtype(np.uint16),
        "uint32": np.dtype(np.uint32),
        "uint64": np.dtype(np.uint64),
        "int8": np.dtype(np.int8),
        "int16": np.dtype(np.int16),
        "int32": np.dtype(np.int32),
        "int64": np.dtype(np.int64),
        "float16": np.dtype(np.float16),
        "float32": np.dtype(np.float32),
        "float64": np.dtype(np.float64),
        "complex64": np.dtype(np.complex64),
        "complex128": np.dtype(np.complex128),
    }
    if ml_dtypes is not None:
        for name in (
            "bfloat16",
            "float8_e4m3fn",
            "float8_e5m2",
            "float8_e4m3b11fnuz",
            "float8_e4m3fnuz",
            "float8_e5m2fnuz",
            "int4",
            "uint4",
            "float4_e2m1fn",
            "float8_e3m4",
            "float8_e4m3",
            "float8_e8m0fnu",
        ):
            dt = getattr(ml_dtypes, name, None)
            if dt is not None:
                table[name] = np.dtype(dt)
    return table


# Canonical string <-> numpy dtype tables (reference ``serialization.py:58-96``).
SUPPORTED_DTYPES = _build_dtype_table()
_DTYPE_TO_STRING = {v: k for k, v in SUPPORTED_DTYPES.items()}


def dtype_to_string(dtype) -> str:
    dtype = np.dtype(dtype)
    try:
        return _DTYPE_TO_STRING[dtype]
    except KeyError:
        raise ValueError(f"Unsupported dtype for raw serialization: {dtype}")


def string_to_dtype(s: str) -> np.dtype:
    try:
        return SUPPORTED_DTYPES[s]
    except KeyError:
        raise ValueError(f"Unknown dtype string: {s}")


def is_raw_serializable(dtype) -> bool:
    return np.dtype(dtype) in _DTYPE_TO_STRING


def dtype_itemsize(s: str) -> int:
    return string_to_dtype(s).itemsize


def array_nbytes(shape, dtype_str: str) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype_itemsize(dtype_str)


def array_as_bytes_view(arr: np.ndarray) -> memoryview:
    """Zero-copy little-endian raw-byte view of ``arr``.

    Copies only when the array is non-contiguous or big-endian (single
    owner of the contiguity fix — callers hand the host array straight in).
    Device fetches CAN be non-C-contiguous: ``np.asarray(jax.Array)``
    reflects the device layout, which for e.g. bf16 matrices on TPU may be
    F-order. The view is the RAW staging fast path's terminal product: it
    flows into plugin writes / the digest fold
    with no intermediate ``bytes()`` materialization, and it keeps the host
    buffer alive for as long as any consumer holds it.
    """
    arr = np.ascontiguousarray(arr)
    if arr.dtype.byteorder == ">":
        arr = arr.astype(arr.dtype.newbyteorder("<"))
    # ml_dtypes custom dtypes reject PEP-3118 export; a uint8 view never
    # does — and ``.data`` already IS the memoryview (no re-wrap copy).
    return arr.view(np.uint8).reshape(-1).data


def array_from_bytes(buf, dtype_str: str, shape) -> np.ndarray:
    """Zero-copy array over ``buf`` (read-only if ``buf`` is)."""
    dtype = string_to_dtype(dtype_str)
    expected = array_nbytes(shape, dtype_str)
    mv = memoryview(buf)
    if mv.nbytes != expected:
        raise ValueError(
            f"Serialized buffer has {mv.nbytes} bytes; "
            f"expected {expected} for shape {tuple(shape)} dtype {dtype_str}"
        )
    return np.frombuffer(mv, dtype=dtype).reshape(shape)
