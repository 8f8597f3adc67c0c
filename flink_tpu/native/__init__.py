"""Native runtime layer — C++ ring-buffer ingestion, columnar record codec,
and host spill store, bound via ctypes (SURVEY §2.10: the reference's
Unsafe/Netty/RocksDB native surface, rebuilt for this runtime).

The shared library compiles on first use (g++ -O2, ~1s) and is cached next
to the sources under a name keyed on a hash of the sources and the build
command, so a binary that was not built from exactly these sources is never
loaded (a copied tree may carry one with a newer mtime).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import zlib
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_SRCS = tuple(os.path.join(_SRC, f)
              for f in ("ringbuf.cpp", "spillstore.cpp", "textparse.cpp"))
_CXX = ("g++", "-O2", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None

RECORD_BYTES = 20  # u64 key | i64 ts_ms | f32 value


def _so_path() -> str:
    h = hashlib.sha256(" ".join(_CXX).encode())
    for s in _SRCS:
        with open(s, "rb") as f:
            h.update(f.read())
    return os.path.join(_DIR, f"_flink_tpu_native.{h.hexdigest()[:16]}.so")


def _build() -> str:
    so = _so_path()
    if os.path.exists(so):
        return so
    # per-process temp name: concurrent test workers may build at once
    tmp = f"{so}.{os.getpid()}.tmp"
    subprocess.run([*_CXX, "-o", tmp, *_SRCS, "-lrt"], check=True,
                   capture_output=True)
    os.replace(tmp, so)
    return so


def get_lib() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            try:
                lib = ctypes.CDLL(_build())
            except OSError:
                # a foreign-platform binary under the same source hash:
                # rebuild it here
                os.remove(_so_path())
                lib = ctypes.CDLL(_build())
            u8p = ctypes.POINTER(ctypes.c_uint8)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            i64p = ctypes.POINTER(ctypes.c_int64)
            f32p = ctypes.POINTER(ctypes.c_float)

            lib.rb_create.restype = ctypes.c_void_p
            lib.rb_create.argtypes = [
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,
            ]
            lib.rb_destroy.argtypes = [ctypes.c_void_p]
            lib.rb_capacity.restype = ctypes.c_uint64
            lib.rb_capacity.argtypes = [ctypes.c_void_p]
            lib.rb_readable.restype = ctypes.c_uint64
            lib.rb_readable.argtypes = [ctypes.c_void_p]
            lib.rb_write.restype = ctypes.c_int
            lib.rb_write.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint32]
            lib.rb_read.restype = ctypes.c_int64
            lib.rb_read.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64]

            lib.records_encode.restype = ctypes.c_int64
            lib.records_encode.argtypes = [
                u64p, i64p, f32p, ctypes.c_uint64, u8p, ctypes.c_uint64,
            ]
            lib.records_decode.restype = ctypes.c_int64
            lib.records_decode.argtypes = [
                u8p, ctypes.c_uint64, u64p, i64p, f32p, ctypes.c_uint64,
            ]

            lib.tp_parse.restype = ctypes.c_int64
            lib.tp_parse.argtypes = [
                u8p, ctypes.c_int64, i64p, u64p, i64p,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, i64p,
            ]

            lib.spill_create.restype = ctypes.c_void_p
            lib.spill_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
            lib.spill_destroy.argtypes = [ctypes.c_void_p]
            lib.spill_count.restype = ctypes.c_uint64
            lib.spill_count.argtypes = [ctypes.c_void_p]
            lib.spill_capacity.restype = ctypes.c_uint64
            lib.spill_capacity.argtypes = [ctypes.c_void_p]
            lib.spill_width.restype = ctypes.c_uint64
            lib.spill_width.argtypes = [ctypes.c_void_p]
            lib.spill_put_batch.argtypes = [
                ctypes.c_void_p, u64p, f32p, ctypes.c_uint64,
            ]
            lib.spill_get_batch.argtypes = [
                ctypes.c_void_p, u64p, f32p, u8p, ctypes.c_uint64,
            ]
            lib.spill_delete_batch.restype = ctypes.c_uint64
            lib.spill_delete_batch.argtypes = [
                ctypes.c_void_p, u64p, ctypes.c_uint64,
            ]
            lib.spill_dump.restype = ctypes.c_uint64
            lib.spill_dump.argtypes = [
                ctypes.c_void_p, u64p, f32p, ctypes.c_uint64,
            ]
            lib.spill_save.restype = ctypes.c_int
            lib.spill_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.spill_load.restype = ctypes.c_void_p
            lib.spill_load.argtypes = [ctypes.c_char_p]
            _lib = lib
    return _lib


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


class RingBuffer:
    """SPSC ingestion ring (process-private, or named POSIX shm when `name`
    is given — the cross-process DCN ingestion seam)."""

    def __init__(self, capacity: int = 1 << 22, name: Optional[str] = None,
                 create=True):
        """create: True = owner create (resets even a stale segment),
        False = attach to an existing initialized segment,
        "exclusive" = create only if absent (fails if the name exists —
        the race-safe attach-or-create probe)."""
        self._lib = get_lib()
        mode = 2 if create == "exclusive" else int(bool(create))
        self._h = self._lib.rb_create(
            name.encode() if name else None, capacity, mode
        )
        if not self._h:
            raise OSError(f"ring buffer create failed (name={name!r})")
        # when attaching, the creator's capacity governs (read from the
        # shared header) — size the scratch buffer from the real value
        self._scratch = np.empty(int(self._lib.rb_capacity(self._h)),
                                 np.uint8)

    def close(self):
        if self._h:
            self._lib.rb_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def readable_bytes(self) -> int:
        return int(self._lib.rb_readable(self._h))

    def write_bytes(self, payload: bytes) -> bool:
        if not payload:
            return True  # nothing to enqueue
        buf = np.frombuffer(payload, np.uint8)
        return bool(self._lib.rb_write(self._h, _u8(buf), len(buf)))

    def write_records(self, keys, ts_ms, values) -> bool:
        """Columnar producer: encode + frame one batch; False = ring full
        (backpressure)."""
        keys = np.ascontiguousarray(keys, np.uint64)
        ts_ms = np.ascontiguousarray(ts_ms, np.int64)
        values = np.ascontiguousarray(values, np.float32)
        n = len(keys)
        if n == 0:
            return True
        out = np.empty(n * RECORD_BYTES, np.uint8)
        wrote = self._lib.records_encode(
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            ts_ms.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, _u8(out), len(out),
        )
        if wrote < 0:
            raise ValueError("encode overflow")
        return bool(self._lib.rb_write(self._h, _u8(out), int(wrote)))

    def read_batch(self) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Drain one framed batch into columnar arrays; None when empty."""
        got = self._lib.rb_read(self._h, _u8(self._scratch),
                                len(self._scratch))
        if got == 0:
            return None
        if got < 0:
            raise BufferError("batch larger than scratch buffer")
        n = int(got) // RECORD_BYTES
        keys = np.empty(n, np.uint64)
        ts = np.empty(n, np.int64)
        vals = np.empty(n, np.float32)
        dec = self._lib.records_decode(
            _u8(self._scratch), int(got),
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n,
        )
        if dec < 0:
            raise ValueError("frame corrupt (length not record-aligned)")
        return keys, ts, vals


class SpillStore:
    """Host overflow tier for keyed state (the RocksDB seam): batch
    put/get/delete of (u64 key -> float[width] block), save/load files."""

    def __init__(self, width: int = 1, initial_capacity: int = 1024,
                 _handle=None):
        self._lib = get_lib()
        self._h = (
            _handle if _handle is not None
            else self._lib.spill_create(initial_capacity, width)
        )
        self.width = int(self._lib.spill_width(self._h))

    def close(self):
        if self._h:
            self._lib.spill_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __len__(self) -> int:
        return int(self._lib.spill_count(self._h))

    def put(self, keys, values):
        keys = np.ascontiguousarray(keys, np.uint64)
        values = np.ascontiguousarray(values, np.float32).reshape(
            len(keys), self.width
        )
        self._lib.spill_put_batch(
            self._h,
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            len(keys),
        )

    def get(self, keys) -> Tuple[np.ndarray, np.ndarray]:
        keys = np.ascontiguousarray(keys, np.uint64)
        n = len(keys)
        vals = np.empty((n, self.width), np.float32)
        found = np.empty(n, np.uint8)
        self._lib.spill_get_batch(
            self._h,
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            _u8(found), n,
        )
        return vals, found.astype(bool)

    def delete(self, keys) -> int:
        keys = np.ascontiguousarray(keys, np.uint64)
        return int(self._lib.spill_delete_batch(
            self._h,
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            len(keys),
        ))

    def dump(self) -> Tuple[np.ndarray, np.ndarray]:
        n = len(self)
        keys = np.empty(n, np.uint64)
        vals = np.empty((n, self.width), np.float32)
        got = self._lib.spill_dump(
            self._h,
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n,
        )
        return keys[:got], vals[:got]

    # checksummed dump file: magic + width/count header, CRC32 over the
    # key and value payload bytes. A torn or bit-flipped spill dump must
    # surface as a clean OSError at load — the caller falls back to
    # re-seeding from the logical snapshot — never as silently wrong
    # window state (the pre-CRC native format restored whatever bytes
    # the file held).
    _SAVE_MAGIC = b"SPL2"

    def save(self, path: str):
        keys, vals = self.dump()
        kb = np.ascontiguousarray(keys, np.uint64).tobytes()
        vb = np.ascontiguousarray(vals, np.float32).tobytes()
        crc = zlib.crc32(kb)
        crc = zlib.crc32(vb, crc)
        header = self._SAVE_MAGIC + np.asarray(
            [self.width, len(keys), crc], np.uint64
        ).tobytes()
        try:
            with open(path, "wb") as f:
                f.write(header)
                f.write(kb)
                f.write(vb)
        except OSError as e:
            raise OSError(f"spill save failed: {path}: {e}") from e

    @classmethod
    def load(cls, path: str) -> "SpillStore":
        from flink_tpu.testing import faults

        faults.inject("ckpt.spill.read", path=path)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise OSError(f"spill load failed: {path}: {e}") from e
        m = len(cls._SAVE_MAGIC)
        if len(blob) < m + 24 or blob[:m] != cls._SAVE_MAGIC:
            raise OSError(f"spill load failed: {path}: bad header")
        width, count, crc = (
            int(v) for v in np.frombuffer(blob[m:m + 24], np.uint64)
        )
        kb_end = m + 24 + count * 8
        vb_end = kb_end + count * width * 4
        if len(blob) != vb_end:
            raise OSError(
                f"spill load failed: {path}: truncated "
                f"({len(blob)} bytes, expected {vb_end})"
            )
        got = zlib.crc32(blob[m + 24:kb_end])
        got = zlib.crc32(blob[kb_end:vb_end], got)
        if got != crc:
            raise OSError(
                f"spill load failed: {path}: checksum mismatch "
                f"(stored {crc:#x}, computed {got:#x})"
            )
        store = cls(width=width, initial_capacity=max(16, count * 2))
        if count:
            store.put(
                np.frombuffer(blob[m + 24:kb_end], np.uint64),
                np.frombuffer(blob[kb_end:vb_end], np.float32).reshape(
                    count, width
                ),
            )
        return store


def parse_ts_words(data, cap: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray, int]:
    """One-pass native parse of newline-delimited "<ts> tok tok ..."
    text (native/src/textparse.cpp — the SocketWindowWordCount split/
    parse/hash done once per batch instead of per line in Python).

    Returns (ts int64[n], ids uint64[n], offsets int64[n],
    lengths int32[n], consumed_bytes). Only complete lines are
    consumed; feed the unconsumed tail back with the next chunk.
    ``cap`` bounds the tokens returned per call (line-atomic: parsing
    stops BEFORE a line that would overflow, so a caller re-offers the
    remainder — the poll-contract seam; a single line wider than cap is
    still returned whole rather than wedging). Token ids are FNV-1a 64
    over the token bytes (stable, checkpoint-safe); offsets/lengths
    index into ``data`` so callers can materialize the strings of
    first-seen ids only.
    """
    lib = get_lib()
    buf = (np.frombuffer(data, np.uint8)
           if isinstance(data, (bytes, bytearray, memoryview))
           else np.ascontiguousarray(data, np.uint8))
    nbytes = len(buf)
    if nbytes == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.uint64),
                np.zeros(0, np.int64), np.zeros(0, np.int32), 0)
    # a token occupies >= 2 bytes (1 char + separator/newline)
    hard_cap = nbytes // 2 + 1
    use_cap = min(hard_cap, cap) if cap else hard_cap

    def run(c):
        ts = np.empty(c, np.int64)
        ids = np.empty(c, np.uint64)
        offs = np.empty(c, np.int64)
        lens = np.empty(c, np.int32)
        consumed = ctypes.c_int64(0)
        n = lib.tp_parse(
            _u8(buf), nbytes,
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            c, ctypes.byref(consumed),
        )
        return ts[:n], ids[:n], offs[:n], lens[:n], consumed.value

    out = run(use_cap)
    if out[4] == 0 and len(out[0]) == 0 and use_cap < hard_cap \
            and 0x0A in buf:
        # one line wider than the requested cap: grow until it fits
        # (never wedge on a pathological line)
        while len(out[0]) == 0 and use_cap < hard_cap:
            use_cap = min(hard_cap, use_cap * 2)
            out = run(use_cap)
    return out
