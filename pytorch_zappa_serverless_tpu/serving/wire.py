"""Zero-copy binary tensor lane: the ``application/x-tpuserve-tensor`` codec.

The JSON+b64 lane pays three host costs per request that have nothing to do
with inference: a JSON parse over a body that is ~99% base64 text, the b64
decode itself (a 33% size tax paid twice), and — for PIL lanes — an image
decode.  The perf plane's ingest stages (``GET /admin/perf``) price exactly
those; this module removes them.  A tensor frame carries a compact
dtype+shape header plus raw row-major bytes, and :func:`unpack` hands the
server ``np.frombuffer`` views over the request body — no base64, no JSON
parse, no per-instance copy (docs/SERVERPATH.md is the wire spec; ISSUE 16).

Frame layout (all integers little-endian)::

    frame  := header block*
    header := magic "TPUT" | version u8 (=1) | flags u8 | count u16
    block  := dtype u8 | ndim u8 | reserved u16 (=0)
              | dim u32 * ndim | data (row-major bytes)

Flags: ``FLAG_LIST`` marks instances-list semantics (the body twin of
``{"instances": [...]}`` — a single-block frame without it is one bare
tensor payload); ``FLAG_META`` marks block 0 as a JSON meta object
(responses carry ``{"model", "timing", ...}`` there).  A block whose dtype
code is :data:`DTYPE_JSON` holds compact UTF-8 JSON instead of tensor bytes
— how structured predictions (classifier top-k dicts) ride the binary
response, byte-decoding to values identical to the JSON lane's.

Malformed frames raise :class:`FrameError` (the server answers 400 with the
request/trace ids); a frame whose *declared* payload exceeds the configured
cap raises :class:`FrameTooLarge` (413) before any allocation, so a hostile
header cannot make the server allocate the lie.
"""

from __future__ import annotations

import json
import struct
from typing import Any

import numpy as np

TENSOR_CONTENT_TYPE = "application/x-tpuserve-tensor"

MAGIC = b"TPUT"
VERSION = 1

FLAG_LIST = 0x01   # instances-list semantics (even when count == 1)
FLAG_META = 0x02   # block 0 is a JSON meta object (response frames)

# Wire dtype codes.  bfloat16 rides ml_dtypes (a jax dependency, so always
# present in this image) but is gated so the codec itself stays stdlib+numpy.
_DTYPE_NAMES = {
    0: "uint8", 1: "int8", 2: "uint16", 3: "int16", 4: "uint32",
    5: "int32", 6: "uint64", 7: "int64", 8: "float16", 9: "float32",
    10: "float64", 11: "bool",
}
try:  # pragma: no cover - import gate
    import ml_dtypes as _ml_dtypes

    _BF16 = np.dtype(_ml_dtypes.bfloat16)
except Exception:  # pragma: no cover - ml_dtypes absent
    _BF16 = None

DTYPE_JSON = 0xF0  # block data is compact UTF-8 JSON, not tensor bytes

_CODE_TO_DTYPE: dict[int, np.dtype] = {
    c: np.dtype(n) for c, n in _DTYPE_NAMES.items()}
if _BF16 is not None:
    _CODE_TO_DTYPE[12] = _BF16
_DTYPE_TO_CODE: dict[np.dtype, int] = {d: c for c, d in _CODE_TO_DTYPE.items()}

_MAX_NDIM = 8
_MAX_COUNT = 4096

_HDR = struct.Struct("<4sBBH")   # magic, version, flags, count
_BLK = struct.Struct("<BBH")     # dtype, ndim, reserved
_DIM = struct.Struct("<I")


class FrameError(ValueError):
    """Malformed tensor frame (bad magic/version/dtype/shape/truncation)."""


class FrameTooLarge(FrameError):
    """Declared payload exceeds the configured frame cap (HTTP 413)."""


def _json_bytes(obj: Any) -> bytes:
    """Compact single-pass JSON encode (the batch-level serializer: one
    encoder walk per frame, never one per instance)."""
    return json.dumps(obj, separators=(",", ":")).encode()


# -- pack ---------------------------------------------------------------------

def _block_parts(item: Any) -> tuple[int, tuple[int, ...], bytes | np.ndarray]:
    """(dtype code, dims, data source) for one block."""
    if isinstance(item, np.ndarray):
        code = _DTYPE_TO_CODE.get(item.dtype)
        if code is None:
            raise FrameError(f"dtype {item.dtype} has no wire code")
        if item.ndim > _MAX_NDIM:
            raise FrameError(f"ndim {item.ndim} exceeds the wire cap "
                             f"({_MAX_NDIM})")
        return code, item.shape, np.ascontiguousarray(item)
    data = _json_bytes(item)
    return DTYPE_JSON, (len(data),), data


def pack(items: list[Any], flags: int = 0,
         pool: "BufferPool | None" = None) -> bytearray:
    """Serialize blocks into ONE exact-size frame buffer.

    ndarray items become tensor blocks; anything else becomes a compact
    JSON block.  The frame is sized up-front and filled through a single
    memoryview — one allocation (or a pooled scratch when ``pool`` is
    given and the caller owns the buffer's lifetime), zero intermediate
    copies, no per-item ``bytes`` concatenation.
    """
    if not items:
        raise FrameError("a frame must carry at least one block")
    if len(items) > _MAX_COUNT:
        raise FrameError(f"count {len(items)} exceeds the wire cap "
                         f"({_MAX_COUNT})")
    parts = [_block_parts(it) for it in items]
    total = _HDR.size + sum(
        _BLK.size + _DIM.size * len(dims)
        + (src.nbytes if isinstance(src, np.ndarray) else len(src))
        for _, dims, src in parts)
    buf = pool.acquire(total) if pool is not None else bytearray(total)
    mv = memoryview(buf)
    _HDR.pack_into(buf, 0, MAGIC, VERSION, flags, len(items))
    off = _HDR.size
    for code, dims, src in parts:
        _BLK.pack_into(buf, off, code, len(dims), 0)
        off += _BLK.size
        for d in dims:
            _DIM.pack_into(buf, off, d)
            off += _DIM.size
        if isinstance(src, np.ndarray):
            n = src.nbytes
            mv[off:off + n] = src.reshape(-1).view(np.uint8).data
        else:
            n = len(src)
            mv[off:off + n] = src
        off += n
    return buf


def pack_response(meta: dict, predictions: list[Any],
                  list_frame: bool) -> bytearray:
    """A response frame: JSON meta block first, then one block per
    prediction — the whole batch serialized in one pass."""
    flags = FLAG_META | (FLAG_LIST if list_frame else 0)
    return pack([meta] + list(predictions), flags=flags)


# -- unpack -------------------------------------------------------------------

def unpack(body: bytes | bytearray | memoryview,
           max_bytes: int = 0) -> tuple[list[Any], int]:
    """Decode a frame into ``([block, ...], flags)`` with zero data copies.

    Tensor blocks come back as read-only ``np.frombuffer`` views over
    ``body``; JSON blocks come back decoded.  Every bound is checked against
    the *declared* sizes before any allocation: truncated or oversized data,
    trailing bytes, unknown dtype codes, and dimension overflow all raise
    :class:`FrameError` / :class:`FrameTooLarge`.
    """
    mv = memoryview(body)
    if max_bytes and len(mv) > max_bytes:
        raise FrameTooLarge(f"frame is {len(mv)} bytes; cap is {max_bytes}")
    if len(mv) < _HDR.size:
        raise FrameError(f"frame shorter than the {_HDR.size}-byte header")
    magic, version, flags, count = _HDR.unpack_from(mv, 0)
    if magic != MAGIC:
        raise FrameError(f"bad magic {magic!r} (want {MAGIC!r})")
    if version != VERSION:
        raise FrameError(f"unsupported frame version {version} "
                         f"(this server speaks {VERSION})")
    if not 1 <= count <= _MAX_COUNT:
        raise FrameError(f"block count {count} outside [1, {_MAX_COUNT}]")
    items: list[Any] = []
    off = _HDR.size
    for i in range(count):
        if len(mv) - off < _BLK.size:
            raise FrameError(f"truncated frame: block {i} header missing")
        code, ndim, reserved = _BLK.unpack_from(mv, off)
        off += _BLK.size
        if reserved != 0:
            raise FrameError(f"block {i}: reserved field must be 0")
        if ndim > _MAX_NDIM:
            raise FrameError(f"block {i}: ndim {ndim} exceeds the wire cap "
                             f"({_MAX_NDIM})")
        if len(mv) - off < _DIM.size * ndim:
            raise FrameError(f"truncated frame: block {i} shape missing")
        dims = tuple(_DIM.unpack_from(mv, off + _DIM.size * j)[0]
                     for j in range(ndim))
        off += _DIM.size * ndim
        if code == DTYPE_JSON:
            if ndim != 1:
                raise FrameError(f"block {i}: JSON blocks are 1-D")
            nbytes = dims[0]
        else:
            dt = _CODE_TO_DTYPE.get(code)
            if dt is None:
                raise FrameError(f"block {i}: unknown dtype code {code}")
            nbytes = dt.itemsize
            for d in dims:
                nbytes *= d
        if max_bytes and nbytes > max_bytes:
            raise FrameTooLarge(f"block {i} declares {nbytes} bytes; "
                                f"cap is {max_bytes}")
        if len(mv) - off < nbytes:
            raise FrameError(f"truncated frame: block {i} declares {nbytes} "
                             f"data bytes, {len(mv) - off} remain")
        data = mv[off:off + nbytes]
        off += nbytes
        if code == DTYPE_JSON:
            try:
                items.append(json.loads(bytes(data)))
            except ValueError as e:
                raise FrameError(f"block {i}: bad JSON block: {e}") from None
        else:
            items.append(np.frombuffer(data, dtype=dt).reshape(dims))
    if off != len(mv):
        raise FrameError(f"{len(mv) - off} trailing bytes after the last "
                         "declared block")
    return items, flags


def unpack_response(body: bytes) -> tuple[dict, list[Any]]:
    """Client-side twin of :func:`pack_response`: ``(meta, predictions)``."""
    items, flags = unpack(body)
    if not flags & FLAG_META:
        raise FrameError("response frame is missing the meta block")
    return items[0], items[1:]


# -- pooled buffers -----------------------------------------------------------

class BufferPool:
    """Free list of serialization scratch buffers.

    Owned by a single task (the server's event loop, or one acceptor
    worker's ring sender), so acquisition/release need no lock — the pool
    amortizes the per-message ``bytearray`` churn on paths that serialize,
    hand the bytes off synchronously (a ring push, a response body the
    caller copies), and release in the same tick.  ``hits``/``misses`` feed
    the serverpath snapshot so pool sizing is observable, not guessed.
    """

    def __init__(self, max_buffers: int = 32, max_bytes: int = 1 << 22):
        self.max_buffers = max_buffers
        self.max_bytes = max_bytes
        self._free: list[bytearray] = []   # guarded-by: event-loop
        self.hits = 0                      # guarded-by: event-loop
        self.misses = 0                    # guarded-by: event-loop

    def acquire(self, n: int) -> bytearray:
        """An exact-size buffer, reusing a pooled allocation when one is
        large enough (shrunk in place: ``bytearray`` keeps its capacity)."""
        for i, buf in enumerate(self._free):
            if len(buf) >= n:
                del self._free[i]
                del buf[n:]
                self.hits += 1
                return buf
        self.misses += 1
        return bytearray(n)

    def release(self, buf: bytearray) -> None:
        if len(self._free) < self.max_buffers and len(buf) <= self.max_bytes:
            self._free.append(buf)

    def snapshot(self) -> dict:
        return {"free": len(self._free), "hits": self.hits,
                "misses": self.misses}
