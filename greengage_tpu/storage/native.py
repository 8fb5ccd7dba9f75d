"""ctypes bindings for the native ggcodec library, with numpy fallbacks.

The native library (native/ggcodec.cpp) is the host-side performance path for
distribution hashing and block encode/decode — the role the reference fills
with C (src/backend/cdb/cdbhash.c, cdbappendonlystorageformat.c). The first
use runs `make -C native` (a no-op when the .so is newer than its source,
so a stale library is rebuilt); if the build fails (no toolchain) the numpy
fallbacks are bit-identical but slower, and build_error() says why.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import zlib

import numpy as np

from greengage_tpu.storage.corruption import CorruptionError

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_SO = os.path.join(_NATIVE_DIR, "libggcodec.so")

HASH_INIT = np.uint32(0x9E3779B9)
COMBINE_MUL = np.uint32(0x01000193)
# "GGB2": bumped with the CRC-covers-header format change so files written
# by the old frame layout fail with a CLEAR bad_magic, not a confusing
# checksum mismatch (must match GG_BLOCK_MAGIC in native/ggcodec.cpp)
BLOCK_MAGIC = 0x47474232
HDR_LEN = 32

_lib = None
_build_error: str | None = None
_load_mu = threading.Lock()


def _load():
    # serialized: two staging threads racing the first load would run
    # `make` twice and publish half-configured handles (gg check races);
    # the steady-state cost is one uncontended acquire per call, noise
    # next to the ctypes dispatch it guards
    with _load_mu:
        return _load_locked()


def _load_locked():
    global _lib, _build_error
    if _lib is not None:
        return _lib
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        # the build ran and failed: whatever .so is there is stale
        _build_error = (e.stderr or e.stdout or str(e)).strip()[-2000:]
    except OSError:
        pass   # no make on this host: a shipped .so is used as it is
    if _build_error is None and not os.path.exists(_SO):
        _build_error = f"{_SO} is missing and make is not available"
    if _build_error is None:
        try:
            lib = ctypes.CDLL(_SO)
            lib.gg_hash_i64_batch.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32, ctypes.c_void_p]
            lib.gg_hash_combine_batch.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
            lib.gg_hash_bytes.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32]
            lib.gg_hash_bytes.restype = ctypes.c_uint32
            lib.gg_block_encode.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_void_p, ctypes.c_int64]
            lib.gg_block_encode.restype = ctypes.c_int64
            lib.gg_block_decode.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
            lib.gg_block_decode.restype = ctypes.c_int64
            _lib = lib
            return lib
        except OSError as e:
            _build_error = str(e)
    _lib = False
    return False


def have_native() -> bool:
    return bool(_load())


def build_error() -> str | None:
    """Why have_native() is false: the failed build's or load's message."""
    _load()
    return _build_error


# ---------------------------------------------------------------------------
# Hashing — numpy reference implementation (spec source of truth shared with
# greengage_tpu/ops/hashing.py, which mirrors it in JAX for on-device motion)
# ---------------------------------------------------------------------------

def _fmix32(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32, copy=True)
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def hash_i64(vals: np.ndarray, seed: int = 0) -> np.ndarray:
    """uint32 hash of an int64 array (spec: fmix32 over lo then hi halves)."""
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    lib = _load()
    if lib:
        out = np.empty(len(vals), dtype=np.uint32)
        lib.gg_hash_i64_batch(vals.ctypes.data, len(vals), ctypes.c_uint32(seed & 0xFFFFFFFF),
                              out.ctypes.data)
        return out
    u = vals.view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    h = np.uint32(seed & 0xFFFFFFFF) ^ HASH_INIT
    h = _fmix32(np.uint32(h) ^ lo)
    h = _fmix32(h ^ hi)
    return h


def hash_combine(acc: np.ndarray, h: np.ndarray) -> np.ndarray:
    acc = np.ascontiguousarray(acc, dtype=np.uint32)
    h = np.ascontiguousarray(h, dtype=np.uint32)
    lib = _load()
    if lib:
        out = acc.copy()
        lib.gg_hash_combine_batch(out.ctypes.data, h.ctypes.data, len(acc))
        return out
    with np.errstate(over="ignore"):
        return _fmix32(acc * COMBINE_MUL ^ h)


def hash_bytes(data: bytes, seed: int = 0) -> int:
    """uint32 hash of a byte string (8-byte LE chunk folding + length)."""
    lib = _load()
    if lib:
        return int(lib.gg_hash_bytes(data, len(data), ctypes.c_uint32(seed & 0xFFFFFFFF)))
    acc = np.uint32(seed & 0xFFFFFFFF) ^ HASH_INIT
    acc_arr = np.array([acc], dtype=np.uint32)
    for i in range(0, len(data), 8):
        chunk = int.from_bytes(data[i : i + 8].ljust(8, b"\0"), "little")
        hv = hash_i64(np.array([np.uint64(chunk).astype(np.int64)], dtype=np.int64).view(np.int64))
        acc_arr = hash_combine(acc_arr, hv)
    acc_arr = hash_combine(acc_arr, hash_i64(np.array([len(data)], dtype=np.int64)))
    return int(acc_arr[0])


# ---------------------------------------------------------------------------
# Block frame codec
# ---------------------------------------------------------------------------

COMP_NONE, COMP_ZLIB, COMP_ZSTD = 0, 1, 2


def block_encode(raw: bytes | np.ndarray, nrows: int, compression: int = COMP_ZLIB,
                 level: int = 1) -> bytes:
    raw = np.frombuffer(raw, dtype=np.uint8) if isinstance(raw, (bytes, bytearray)) else np.ascontiguousarray(raw).view(np.uint8).ravel()
    lib = _load()
    if lib and compression in (COMP_NONE, COMP_ZLIB):
        # capacity covers zlib's worst case (compressBound ~ raw + raw/1000 + 64)
        # plus header; the C side stores raw on any compress failure.
        cap = HDR_LEN + len(raw) + len(raw) // 1000 + 4096
        dst = np.empty(cap, dtype=np.uint8)
        n = lib.gg_block_encode(raw.ctypes.data, len(raw), ctypes.c_uint32(nrows),
                                compression, level, dst.ctypes.data, cap)
        if n < 0:
            raise IOError("block encode failed")
        return dst[:n].tobytes()
    payload = raw.tobytes()
    comp = compression
    if compression == COMP_ZSTD:
        try:
            import zstandard
        except ModuleNotFoundError:
            # optional codec: degrade the WRITE to zlib instead of failing
            # the statement — the frame header records the codec actually
            # used, so readers never need the missing module. zstd levels
            # go to 22; zlib rejects anything past 9.
            compression = comp = COMP_ZLIB
            level = min(level, 9)
    if compression == COMP_ZLIB:
        c = zlib.compress(payload, level)
        if len(c) < len(payload):
            payload = c
        else:
            comp = COMP_NONE
    elif compression == COMP_ZSTD:
        c = zstandard.ZstdCompressor(level=level).compress(payload)
        if len(c) < len(payload):
            payload = c
        else:
            comp = COMP_NONE
    # the CRC covers the header fields as well as the payload, so flipped
    # metadata (nrows/raw_len/comp_len/codec byte) is caught at decode —
    # bit-identical to gg_block_encode in native/ggcodec.cpp
    hdr = (BLOCK_MAGIC.to_bytes(4, "little") + int(nrows).to_bytes(4, "little")
           + bytes([comp, 0]) + b"\0\0" + len(raw).to_bytes(8, "little")
           + len(payload).to_bytes(8, "little"))
    crc = zlib.crc32(payload, zlib.crc32(hdr)) & 0xFFFFFFFF
    return hdr + crc.to_bytes(4, "little") + payload


def _check_frame_header(frame: bytes) -> tuple[int, int, int, int, int, int]:
    """Validate a frame's header WITHOUT touching the payload.
    -> (nrows, comp, raw_len, comp_len, want_crc, total_len)."""
    if len(frame) < HDR_LEN:
        raise CorruptionError(
            "truncated", f"frame is {len(frame)} bytes, header needs {HDR_LEN}")
    magic = int.from_bytes(frame[:4], "little")
    if magic == 0x47474231:   # "GGB1": the pre-header-CRC layout
        # NOT corruption: old-format data must refuse loudly, never feed
        # the repair/quarantine machinery (which would eat valid files)
        raise IOError(
            "unsupported block format GGB1 (written by an older, "
            "incompatible version) — re-ingest from original sources")
    if magic != BLOCK_MAGIC:
        raise CorruptionError("bad_magic", "bad block magic")
    nrows = int.from_bytes(frame[4:8], "little")
    comp = frame[8]
    raw_len = int.from_bytes(frame[12:20], "little")
    comp_len = int.from_bytes(frame[20:28], "little")
    want_crc = int.from_bytes(frame[28:32], "little")
    total = HDR_LEN + comp_len
    if len(frame) < total:
        raise CorruptionError(
            "truncated",
            f"frame payload truncated ({len(frame)} bytes, header claims {total})")
    # bound raw_len BEFORE any allocation: the native fast path allocates
    # its output buffer ahead of the CRC check, so a flipped length must
    # not drive a huge malloc first. zlib expands at most ~1032:1 and
    # stored-raw is 1:1; zstd frames never reach a pre-CRC allocation
    # (python path checks the CRC before decompressing), so a legitimate
    # high-ratio zstd frame is NOT rejected here.
    if raw_len < 0 or (comp == COMP_NONE and raw_len != comp_len) \
            or (comp == COMP_ZLIB and raw_len > comp_len * 1032 + 4096):
        raise CorruptionError(
            "decode_failed",
            f"implausible frame lengths (raw {raw_len}, stored {comp_len})")
    return nrows, comp, raw_len, comp_len, want_crc, total


def block_decode(frame: bytes) -> tuple[bytes, int, int]:
    """-> (raw bytes, nrows, frame length consumed). Verifies the frame
    checksum (header + payload); all failures raise the typed
    CorruptionError so readers can classify repair vs quarantine."""
    nrows, comp, raw_len, comp_len, want_crc, total = \
        _check_frame_header(frame)
    lib = _load()
    if lib and comp in (COMP_NONE, COMP_ZLIB):
        src = np.frombuffer(frame[:total], dtype=np.uint8)
        dst = np.empty(max(raw_len, 1), dtype=np.uint8)
        nrows_out = ctypes.c_uint32()
        n = lib.gg_block_decode(src.ctypes.data, len(src), dst.ctypes.data, len(dst),
                                ctypes.byref(nrows_out))
        if n == -2:
            raise CorruptionError("crc_mismatch", "block checksum mismatch")
        if n == -1:
            raise CorruptionError("bad_magic", "bad block magic")
        if n < 0:
            raise CorruptionError("decode_failed", f"block decode failed ({n})")
        return dst[:n].tobytes(), nrows_out.value, total
    payload = frame[HDR_LEN:total]
    crc = zlib.crc32(payload, zlib.crc32(frame[: HDR_LEN - 4])) & 0xFFFFFFFF
    if crc != want_crc:
        raise CorruptionError("crc_mismatch", "block checksum mismatch")
    if comp == COMP_ZLIB:
        try:
            raw = zlib.decompress(payload)
        except zlib.error as e:
            raise CorruptionError("decode_failed", f"zlib decompress failed: {e}")
    elif comp == COMP_ZSTD:
        try:
            import zstandard
        except ModuleNotFoundError:
            raise IOError(
                "block is zstd-compressed but the optional 'zstandard' "
                "module is not installed on this host")

        try:
            raw = zstandard.ZstdDecompressor().decompress(payload, max_output_size=raw_len)
        except zstandard.ZstdError as e:
            raise CorruptionError("decode_failed", f"zstd decompress failed: {e}")
    elif comp == COMP_NONE:
        if raw_len != comp_len:
            raise CorruptionError(
                "decode_failed",
                f"stored-raw frame length mismatch ({comp_len} != {raw_len})")
        raw = bytes(payload)
    else:
        raise CorruptionError("decode_failed", f"unknown compression {comp}")
    if len(raw) != raw_len:
        raise CorruptionError(
            "decode_failed",
            f"decoded {len(raw)} bytes, header claims {raw_len}")
    return raw, nrows, total


def block_decode_into(frame: bytes, dst: np.ndarray) -> tuple[int, int]:
    """Decode one frame's rows DIRECTLY into ``dst`` (a contiguous uint8
    view of the destination slot) — the in-place staging path: no
    intermediate bytes object, no post-decode copy. Same verification and
    CorruptionError classification as block_decode. -> (bytes written,
    nrows)."""
    nrows, comp, raw_len, comp_len, want_crc, total = \
        _check_frame_header(frame)
    if raw_len > len(dst):
        raise CorruptionError(
            "rowcount_mismatch",
            f"frame holds {raw_len} bytes, destination slot is {len(dst)}")
    lib = _load()
    if lib and comp in (COMP_NONE, COMP_ZLIB):
        src = np.frombuffer(frame[:total], dtype=np.uint8)
        nrows_out = ctypes.c_uint32()
        n = lib.gg_block_decode(src.ctypes.data, len(src),
                                dst.ctypes.data, len(dst),
                                ctypes.byref(nrows_out))
        if n == -2:
            raise CorruptionError("crc_mismatch", "block checksum mismatch")
        if n == -1:
            raise CorruptionError("bad_magic", "bad block magic")
        if n < 0:
            raise CorruptionError("decode_failed", f"block decode failed ({n})")
        return int(n), int(nrows_out.value)
    raw, nrows, _total = block_decode(frame)
    if len(raw) > len(dst):
        raise CorruptionError(
            "rowcount_mismatch",
            f"block holds {len(raw)} bytes, destination slot is {len(dst)}")
    dst[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return len(raw), nrows
